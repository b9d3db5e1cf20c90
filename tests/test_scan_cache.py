"""Device scan cache: repeated actions reuse the uploaded batch; identity,
eviction and the disable conf behave as documented."""
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.api import TpuSession, functions as F
from spark_rapids_tpu.memory.scan_cache import DeviceScanCache, get_cache


def _table(n=100, seed=0):
    rng = np.random.default_rng(seed)
    return pa.table({"a": rng.integers(0, 10, n), "b": rng.random(n)})


def test_repeated_collect_hits_cache():
    t = _table()
    sess = TpuSession({})
    df = sess.create_dataframe(t).groupBy("a").agg(F.count().alias("s"))
    r1 = df.collect()
    cache = get_cache(2 << 30)
    assert cache.get(t, sess.conf.string_max_bytes) is not None
    before = cache.get(t, sess.conf.string_max_bytes)
    r2 = df.collect()
    after = cache.get(t, sess.conf.string_max_bytes)
    assert before is after, "second action should reuse the cached upload"
    assert r1.equals(r2)


class FakeBatch:
    def __init__(self, nbytes=0):
        self.device_size_bytes = nbytes


def test_identity_not_equality():
    """A different table object never hits, even with equal contents."""
    cache = DeviceScanCache(1 << 20)
    t1, t2 = _table(seed=1), _table(seed=1)
    cache.put(t1, 64, FakeBatch())
    assert cache.get(t1, 64) is not None
    assert cache.get(t2, 64) is None


def test_eviction_by_budget():
    cache = DeviceScanCache(100)
    tables = [_table(n=2, seed=i) for i in range(4)]
    for t in tables:
        cache.put(t, 64, FakeBatch(40))
    # 4 * 40 > 100: the two least-recently-used entries were evicted
    assert cache.get(tables[0], 64) is None
    assert cache.get(tables[1], 64) is None
    assert cache.get(tables[2], 64) is not None
    assert cache.get(tables[3], 64) is not None


def test_oversized_entry_not_cached():
    cache = DeviceScanCache(10)
    t = _table(n=2)
    cache.put(t, 64, FakeBatch(100))
    assert cache.get(t, 64) is None


def test_budget_shrink_evicts_on_get_cache():
    from spark_rapids_tpu.memory import scan_cache as sc
    cache = sc.get_cache(1000)
    cache.clear()
    t = _table(n=2, seed=42)
    cache.put(t, 64, FakeBatch(500))
    assert cache.get(t, 64) is not None
    sc.get_cache(100)  # shrink budget -> sweep
    assert cache.get(t, 64) is None
    cache.clear()


def test_disable_conf():
    t = _table(seed=7)
    sess = TpuSession({"spark.rapids.tpu.sql.scanCache.enabled": "false"})
    df = sess.create_dataframe(t).agg(F.count().alias("s"))
    df.collect()
    cache = get_cache(2 << 30)
    assert cache.get(t, sess.conf.string_max_bytes) is None


def test_dead_table_entry_dropped():
    cache = DeviceScanCache(1 << 20)
    t = _table(n=3, seed=9)
    cache.put(t, 64, FakeBatch())
    del t
    cache._evict()
    assert not cache._entries


# ------------------------------------------- the budget comes from the device
V5E_BYTES_LIMIT = 16_909_336_064     # memory_stats()["bytes_limit"], one v5e
V5E_BUDGET = 6_087_360_982           # 0.9 alloc x 0.8 headroom of it, halved
# SF1 device sizes: lineitem 2,181 MB, orders + customer 408 MB together
# (ledger, PR 25; the split of the 408 is by their capacities)
LINEITEM_BYTES, ORDERS_BYTES, CUSTOMER_BYTES = (
    2_181_038_080, 310_378_496, 97_517_568)
POOL = "spark.rapids.tpu.memory.tpu.poolSizeBytes"
MAX_BYTES = "spark.rapids.tpu.sql.scanCache.maxBytes"


@pytest.fixture
def no_device_manager():
    """derived_budget reads a live manager's budget first: none here."""
    from spark_rapids_tpu.memory.device_manager import DeviceManager
    DeviceManager.shutdown()
    yield
    DeviceManager.shutdown()


def _fake_hbm(monkeypatch, nbytes):
    from spark_rapids_tpu.memory.device_manager import DeviceManager
    monkeypatch.setattr(DeviceManager, "_detect_hbm_bytes",
                        staticmethod(lambda: nbytes))


@pytest.mark.parametrize("hbm, conf, expected", [
    (V5E_BYTES_LIMIT, {}, V5E_BUDGET),
    (16 << 30, {}, int(int((16 << 30) * 0.9) * 0.8) // 2),
    # a pool set by hand is the device budget, whatever the chip has
    (V5E_BYTES_LIMIT, {POOL: str(256 << 10)}, int((256 << 10) * 0.8) // 2),
    (V5E_BYTES_LIMIT,
     {"spark.rapids.tpu.memory.outOfCore.headroomFraction": "0.5"},
     int(int(V5E_BYTES_LIMIT * 0.9) * 0.5) // 2),
    # an explicit byte count is the budget: no derivation
    (V5E_BYTES_LIMIT, {MAX_BYTES: "1"}, 1),
    (V5E_BYTES_LIMIT, {MAX_BYTES: str(12 << 30)}, 12 << 30),
])
def test_derived_budget(monkeypatch, no_device_manager, hbm, conf, expected):
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.memory.scan_cache import derived_budget
    _fake_hbm(monkeypatch, hbm)
    assert derived_budget(TpuConf(conf)) == expected


def test_no_hand_set_default():
    from spark_rapids_tpu import config as cfg
    assert cfg.SCAN_CACHE_BYTES.default == 0


def test_derived_budget_yields_to_the_store(no_device_manager):
    """What the device store holds is not the cache's to take: the budget
    is re-derived at every scan from the free device budget."""
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.columnar import DeviceBatch
    from spark_rapids_tpu.memory import BufferId
    from spark_rapids_tpu.memory.device_manager import DeviceManager
    from spark_rapids_tpu.memory.scan_cache import derived_budget
    conf = TpuConf({POOL: str(1 << 20)})
    dm = DeviceManager.initialize(conf)
    assert derived_budget(conf) == int((1 << 20) * 0.8) // 2
    batch = DeviceBatch.from_arrow(_table(n=256), string_max_bytes=16)
    dm.device_store.add_batch(BufferId(1), batch)
    used = dm.device_store.used_bytes
    assert used > 0
    assert derived_budget(conf) == int(((1 << 20) - used) * 0.8) // 2
    dm.device_store.remove(BufferId(1))


def test_sf1_tables_are_kept_under_the_v5e_budget():
    cache = DeviceScanCache(V5E_BUDGET)
    tables = [_table(n=2, seed=i) for i in range(3)]
    for t, n in zip(tables, (LINEITEM_BYTES, ORDERS_BYTES, CUSTOMER_BYTES)):
        assert cache.put(t, 64, FakeBatch(n))
    assert all(cache.get(t, 64) is not None for t in tables)
    assert cache.total_bytes() == (LINEITEM_BYTES + ORDERS_BYTES
                                   + CUSTOMER_BYTES)
    # and under the 2 GiB constant this budget replaced, lineitem was not
    assert not DeviceScanCache(2 << 30).put(tables[0], 64,
                                            FakeBatch(LINEITEM_BYTES))


def test_entry_over_the_derived_budget_is_not_kept():
    cache = DeviceScanCache(V5E_BUDGET)
    small, big = _table(n=2, seed=1), _table(n=2, seed=2)
    assert cache.put(small, 64, FakeBatch(CUSTOMER_BYTES))
    assert not cache.put(big, 64, FakeBatch(V5E_BUDGET + 1))
    assert cache.get(big, 64) is None
    assert cache.get(small, 64) is not None   # and it evicted nothing


def _uploads(df):
    from spark_rapids_tpu.utils import metrics as um
    before = um.TRANSFER_METRICS[um.TRANSFER_UPLOAD_BYTES].value
    df.collect()
    return um.TRANSFER_METRICS[um.TRANSFER_UPLOAD_BYTES].value - before


@pytest.mark.parametrize("conf, uploads_again", [
    ({}, False),                              # derived: kept
    ({MAX_BYTES: "1"}, True),                 # explicit cap: never kept
    ({POOL: "64"}, True),                     # a pool no table fits half of
])
def test_budget_decides_the_second_upload(conf, uploads_again):
    from spark_rapids_tpu.memory.device_manager import DeviceManager
    DeviceManager.shutdown()
    t = _table(n=500, seed=11)
    df = (TpuSession(conf).create_dataframe(t)
          .groupBy("a").agg(F.count().alias("s")))
    try:
        assert _uploads(df) > 0
        assert (_uploads(df) > 0) == uploads_again
    finally:
        DeviceManager.shutdown()


@pytest.mark.parametrize("incoming_batches, cached_left, spilled", [
    (1, 7, 0),      # over by one batch: the LRU scan goes, nothing spills
    (3, 5, 0),
    (9, 0, 1),      # more than the whole cache frees: then, and only then,
])                  # a real buffer is spilled
def test_store_admission_evicts_cached_scans_before_it_spills(
        tmp_path, incoming_batches, cached_left, spilled):
    """A cache that holds most of the device budget yields to the store's
    admission entry by entry, LRU first; the store spills a real buffer
    only once the cache is empty."""
    from test_memory import make_batch
    from spark_rapids_tpu.memory import BufferCatalog, BufferId, \
        build_store_chain
    from spark_rapids_tpu.memory import scan_cache as sc
    _, b = make_batch(64, 0)
    size = b.device_size_bytes
    catalog = BufferCatalog()
    device, host, disk = build_store_chain(catalog, size * 10, size * 100,
                                           str(tmp_path))
    cache = sc.get_cache(size * 8)
    cache.clear()
    tables = [_table(n=2, seed=i) for i in range(8)]
    try:
        device.add_batch(BufferId(100), b)
        for t in tables:                       # 8 of the budget's 10
            cache.put(t, 64, FakeBatch(size))
        # store 1 + cache 8 + incoming: over the budget of 10 by incoming - 1
        device.ensure_capacity(size * (1 + incoming_batches))
        assert cache.total_bytes() == size * cached_left
        kept = [cache.get(t, 64) is not None for t in tables]
        assert kept == [False] * (8 - cached_left) + [True] * cached_left
        assert len(host) == spilled and len(device) == 1 - spilled
    finally:
        cache.clear()
        device.close(), host.close(), disk.close()


def test_instants_say_held_and_budget(monkeypatch):
    from spark_rapids_tpu.utils import tracing
    t = tracing.Tracer(capacity=64)
    monkeypatch.setattr(tracing, "TRACER", t)
    cache = DeviceScanCache(100)
    kept, over = _table(n=2, seed=1), _table(n=2, seed=2)
    with t.activate():
        cache.get_or_put(kept, 64, lambda: FakeBatch(60))       # miss
        cache.get_or_put(kept, 64, lambda: FakeBatch(60))       # hit
        cache.get_or_put(over, 64, lambda: FakeBatch(101))      # not kept
    got = [(r.name, r.args) for r in t.since(0)]
    assert got == [
        ("scan_cache.miss", {"bytes": 60, "held": 60, "budget": 100}),
        ("scan_cache.hit", {"bytes": 60, "held": 60, "budget": 100}),
        ("scan_cache.not_kept", {"bytes": 101, "held": 60, "budget": 100}),
    ]
