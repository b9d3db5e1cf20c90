"""Deterministic chaos tests for the fault-tolerant shuffle.

One test per fault class (connection drop, frame corruption, handler
failure, slow peer), each driving the REAL end-to-end shuffle protocol
(caching writer → metadata/transfer RPCs → chunked tag-addressed receives →
reader) through the FaultInjectingTransport with a fixed seed, asserting
both correct results AND that the recovery machinery (retry counters,
client eviction, checksum detection) actually engaged — a green run must
prove the fault fired and was absorbed, not that it never happened.

Plus unit tests for the backoff schedule, checksum round-trip, plan
parsing, scoped failure domains, and the reader's overall deadline.
"""
import queue
import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.shuffle.codec import (ChecksumError, checksum_of,
                                            verify_checksum)
from spark_rapids_tpu.shuffle.faults import (FaultInjectingTransport,
                                             FaultPlan, FaultSpec)
from spark_rapids_tpu.shuffle.inprocess import _Fabric
from spark_rapids_tpu.shuffle.manager import (MapOutputTracker, ShuffleEnv,
                                              ShuffleFetchFailedError,
                                              ShuffleManager)
from spark_rapids_tpu.shuffle.retry import backoff_ms, backoff_schedule
from spark_rapids_tpu.utils import metrics as mt
from tests.test_shuffle import (collect_partition, sample_table,
                                write_partitioned)

FAULT_TRANSPORT = "spark_rapids_tpu.shuffle.faults.FaultInjectingTransport"


@pytest.fixture(autouse=True)
def fresh_fabric():
    _Fabric.reset()
    yield
    _Fabric.reset()


#: the default is 2 x 32 x 4 MiB a transport; these tests move a few bytes
SMALL_BOUNCE = {"spark.rapids.tpu.shuffle.bounceBuffers.size": 1024,
                "spark.rapids.tpu.shuffle.bounceBuffers.count": 16}


def wait_for(cond, timeout=30):
    """Poll for what another thread makes true: a loaded host may take
    seconds to schedule it."""
    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert cond()


def fault_cluster(tmp_path, plan="", seed=7, n=2, extra=None):
    """n ShuffleEnvs riding the fault wrapper around the in-process fabric.
    Small bounce buffers force multi-chunk transfers (faults need frames to
    hit); small backoff keeps chaos tests fast. SHUFFLE_FAULTS_CODEC runs
    the whole chaos matrix over compressed payloads (ci/nightly.sh sets
    lz4, so corrupt-frame recovery is exercised on compressed frames)."""
    import os
    conf = TpuConf({
        "spark.rapids.tpu.shuffle.transport.class": FAULT_TRANSPORT,
        "spark.rapids.tpu.shuffle.faults.plan": plan,
        "spark.rapids.tpu.shuffle.faults.seed": seed,
        **SMALL_BOUNCE,
        "spark.rapids.tpu.shuffle.retryBackoffMs": 5,
        "spark.rapids.tpu.shuffle.compression.codec":
            os.environ.get("SHUFFLE_FAULTS_CODEC", "none"),
        **(extra or {})})
    envs = [ShuffleEnv(f"exec-{i}", conf, disk_dir=str(tmp_path / f"e{i}"))
            for i in range(n)]
    return (ShuffleManager(), *envs)


# ---------------------------------------------------------------------------------
# unit: backoff schedule + checksum round-trip + plan parsing
# ---------------------------------------------------------------------------------

def test_backoff_schedule_deterministic_and_exponential():
    a = backoff_schedule(4, base_ms=50, seed=3, key="transfer:b1")
    b = backoff_schedule(4, base_ms=50, seed=3, key="transfer:b1")
    assert a == b                                   # same seed+key replays
    assert a != backoff_schedule(4, 50, seed=4, key="transfer:b1")
    assert a != backoff_schedule(4, 50, seed=3, key="transfer:b2")
    for i, d in enumerate(a):
        lo, hi = 50 * (2 ** i) * 0.5, 50 * (2 ** i) * 1.5
        assert lo <= d <= hi                        # exponential + jitter band
    # the cap bounds runaway exponents
    assert backoff_ms(30, 50, 0, "k") == 10_000


def test_checksum_roundtrip_and_mismatch():
    buf = np.arange(10_000, dtype=np.int64).tobytes()
    crc = checksum_of(buf)
    verify_checksum(buf, crc)                       # clean round trip
    verify_checksum(buf, 0)                         # 0 = not computed
    corrupted = bytearray(buf)
    corrupted[1234] ^= 0xFF
    with pytest.raises(ChecksumError, match="checksum mismatch"):
        verify_checksum(bytes(corrupted), crc)


def test_table_meta_carries_checksum():
    from spark_rapids_tpu.columnar.host import HostBatch
    from spark_rapids_tpu.shuffle.table_meta import TableMeta, pack_host_batch
    buf, meta = pack_host_batch(HostBatch.from_arrow(sample_table(64)))
    assert meta.checksum == checksum_of(buf) != 0
    assert TableMeta.from_bytes(meta.to_bytes()).checksum == meta.checksum


def test_fault_plan_parsing():
    plan = FaultPlan.parse(
        "drop_conn:peer=exec-1,after=3;corrupt_frame:after=1,count=2;"
        "fail_request:req_type=metadata;delay_frame:delay_ms=25", seed=9)
    kinds = [s.kind for s in plan.specs]
    assert kinds == ["drop_conn", "corrupt_frame", "fail_request",
                     "delay_frame"]
    assert plan.specs[0].peer == "exec-1" and plan.specs[0].after == 3
    assert plan.specs[1].count == 2
    assert plan.specs[3].delay_ms == 25
    assert FaultPlan.parse("").empty
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultPlan.parse("meteor_strike")
    # windowed firing: after=2,count=2 fires on events 2 and 3 only
    spec = FaultSpec("fail_request", after=2, count=2)
    assert [spec.fires(n) for n in (1, 2, 3, 4)] == [False, True, True, False]


def test_kill_peer_plan_counts_requests_and_frames_separately():
    """kill_peer (the failover chaos matrix's fault kind) is seeded and
    phase-targetable: req_type filters pick the submit/stream/drain phase
    (``data`` = the Nth outgoing data frame, mid-stream death), and the
    per-peer counters replay identically under a fixed plan."""
    plan = FaultPlan.parse("kill_peer:req_type=data,after=3", seed=7)
    assert [plan.on_kill_frame("p")
            for _ in range(3)] == [False, False, True]
    # request events with another req_type never advance the data spec
    assert not plan.on_kill_request("p", "serve.submit")
    replay = FaultPlan.parse("kill_peer:req_type=data,after=3", seed=7)
    assert [replay.on_kill_frame("p")
            for _ in range(3)] == [False, False, True]
    assert replay.fired == plan.fired == [("kill_peer", "p", 3)]
    # phase targeting: a submit-phase kill ignores stream traffic
    sub = FaultPlan.parse("kill_peer:req_type=serve.submit,after=1")
    assert not sub.on_kill_frame("p")
    assert sub.on_kill_request("p", "serve.submit")


def test_kill_peer_leaves_registry_entry_for_gc(tmp_path):
    """kill() is SIGKILL-shaped: the listener and sockets die, the
    heartbeat stops, but the registry file LINGERS — exactly the stale
    entry scan_registry's liveness-window GC must skip and collect."""
    import os
    import socket
    from spark_rapids_tpu.shuffle.tcp import TcpTransport, scan_registry
    reg = str(tmp_path / "reg")
    conf = TpuConf({"spark.rapids.tpu.shuffle.tcp.registryDir": reg})
    t = TcpTransport("exec-victim", conf)
    path = os.path.join(reg, "exec-victim")
    assert os.path.exists(path)
    mtime0 = os.path.getmtime(path)
    t.heartbeat()
    assert os.path.getmtime(path) >= mtime0
    host, port = t.address
    t.kill()
    # dead to the outside: new dials are refused...
    with pytest.raises(OSError):
        socket.create_connection((host, port), timeout=2)
    # ...the heartbeat is a no-op...
    old = time.time() - 120
    os.utime(path, (old, old))
    t.heartbeat()
    assert os.path.getmtime(path) == old, "killed transport heartbeat"
    # ...but the entry lingers (SIGKILL cannot retract it) until a
    # liveness-windowed scan garbage-collects it
    assert os.path.exists(path)
    assert scan_registry(reg, stale_after_s=5.0) == {}
    assert not os.path.exists(path)


# ---------------------------------------------------------------------------------
# chaos: one deterministic test per fault class
# ---------------------------------------------------------------------------------

def test_conn_drop_mid_fetch_recovers_via_retry(tmp_path):
    """THE acceptance bar: each remote peer's connection drops once
    mid-fetch; the reader reconnects (evicted client), re-fetches only the
    undelivered blocks, and the shuffle read completes with correct results
    — no ShuffleFetchFailedError."""
    mgr, e0, e1, e2 = fault_cluster(
        tmp_path, plan="drop_conn:after=2", n=3)
    sid, _ = mgr.register_shuffle(2)
    t1 = sample_table(800, seed=1)      # >1 KiB packed -> multi-chunk
    t2 = sample_table(600, seed=2)
    write_partitioned(mgr, e1, sid, 0, t1, 2)
    write_partitioned(mgr, e2, sid, 1, t2, 2)

    got = collect_partition(mgr, e0, sid, 0)    # both peers remote to e0
    expected = pa.concat_tables([t1.take(list(range(0, 800, 2))),
                                 t2.take(list(range(0, 600, 2)))])
    assert got.sort_by("f").equals(expected.sort_by("f"))
    # the drop actually fired on each remote peer and recovery engaged
    dropped = {p for k, p, _ in e0.transport.plan.fired if k == "drop_conn"}
    assert dropped == {"exec-1", "exec-2"}
    assert e0.metrics[mt.SHUFFLE_FETCH_RETRIES].value >= 2
    assert e0.metrics[mt.SHUFFLE_PEER_EVICTIONS].value >= 2


def test_corrupted_frame_caught_by_checksum_and_retried(tmp_path):
    """A flipped byte in one data frame surfaces as a checksum mismatch,
    counted and retried — the query still returns correct rows."""
    mgr, e0, e1 = fault_cluster(tmp_path, plan="corrupt_frame:after=2")
    sid, _ = mgr.register_shuffle(1)
    t = sample_table(700, seed=3)
    write_partitioned(mgr, e1, sid, 0, t, 1)
    got = collect_partition(mgr, e0, sid, 0)
    assert sorted(got["f"].to_pylist()) == sorted(t["f"].to_pylist())
    assert e0.metrics[mt.SHUFFLE_CHECKSUM_FAILURES].value >= 1
    assert e0.metrics[mt.SHUFFLE_TRANSFER_RETRIES].value >= 1
    assert any(k == "corrupt_frame" for k, _, _ in e1.transport.plan.fired)


def test_corruption_without_checksum_would_pass_silently(tmp_path):
    """Negative control: with verification disabled the corrupted buffer is
    NOT caught (wrong bytes decode or error out downstream) — documents
    that the checksum is what stands between corruption and wrong answers."""
    mgr, e0, e1 = fault_cluster(
        tmp_path, plan="corrupt_frame:after=2",
        extra={"spark.rapids.tpu.shuffle.checksum.enabled": "false",
               # pinned to the copy codec: a real codec's decompressor can
               # catch the flip incidentally and retry, defeating this
               # negative control (the lz4 matrix run sets the codec env)
               "spark.rapids.tpu.shuffle.compression.codec": "none"})
    sid, _ = mgr.register_shuffle(1)
    t = sample_table(700, seed=3)
    write_partitioned(mgr, e1, sid, 0, t, 1)
    try:
        got = collect_partition(mgr, e0, sid, 0)
        # full-row comparison: the flipped byte lands in SOME column
        silently_wrong = not got.sort_by("f").equals(t.sort_by("f"))
    except Exception:  # noqa: BLE001 — a downstream decode error also proves it
        silently_wrong = True
    assert silently_wrong
    assert e0.metrics[mt.SHUFFLE_CHECKSUM_FAILURES].value == 0


def test_failed_request_handler_retried(tmp_path):
    """A request that fails once (dead handler / lost RPC) is retried with
    backoff and the fetch completes."""
    mgr, e0, e1 = fault_cluster(
        tmp_path, plan="fail_request:req_type=metadata;"
                       "fail_request:req_type=transfer")
    sid, _ = mgr.register_shuffle(1)
    t = sample_table(300, seed=4)
    write_partitioned(mgr, e1, sid, 0, t, 1)
    got = collect_partition(mgr, e0, sid, 0)
    assert sorted(got["f"].to_pylist()) == sorted(t["f"].to_pylist())
    assert e0.metrics[mt.SHUFFLE_RPC_RETRIES].value >= 1
    assert e0.metrics[mt.SHUFFLE_TRANSFER_RETRIES].value >= 1


def test_slow_peer_and_duplicated_frames_absorbed(tmp_path):
    """Delayed frames ride out the (overall) fetch deadline and duplicated
    frames are absorbed without duplicate rows."""
    mgr, e0, e1 = fault_cluster(
        tmp_path, plan="delay_frame:after=1,count=3,delay_ms=40;"
                       "dup_frame:after=2,count=2")
    sid, _ = mgr.register_shuffle(1)
    t = sample_table(700, seed=5)
    write_partitioned(mgr, e1, sid, 0, t, 1)
    got = collect_partition(mgr, e0, sid, 0)
    assert got.num_rows == t.num_rows            # no dup rows, none missing
    assert sorted(got["f"].to_pylist()) == sorted(t["f"].to_pylist())
    fired = {k for k, _, _ in e1.transport.plan.fired}
    assert {"delay_frame", "dup_frame"} <= fired


def test_unrecoverable_fault_names_executor_and_blocks(tmp_path):
    """Past maxRetries the error is scoped: it carries the failing executor
    and the undelivered blocks so callers recompute only those map outputs."""
    mgr, e0, e1 = fault_cluster(
        tmp_path, plan="fail_request:req_type=metadata,count=0",   # always
        extra={"spark.rapids.tpu.shuffle.maxRetries": 1})
    sid, _ = mgr.register_shuffle(1)
    t = sample_table(50, seed=6)
    write_partitioned(mgr, e1, sid, 0, t, 1)
    with pytest.raises(ShuffleFetchFailedError) as ei:
        collect_partition(mgr, e0, sid, 0)
    assert ei.value.executor_id == "exec-1"
    assert ei.value.blocks and all(b.shuffle_id == sid
                                   for b in ei.value.blocks)


# ---------------------------------------------------------------------------------
# scoped failure domains + eviction + deadline
# ---------------------------------------------------------------------------------

def test_peer_loss_scoped_to_failing_peer(tmp_path):
    """Losing one peer mid-read fails only ITS transactions: blocks from
    the healthy peer still arrive (TCP transport, per-peer pending tables)."""
    import pyarrow as pa
    from spark_rapids_tpu.shuffle.tcp import TcpTransport
    from spark_rapids_tpu.shuffle.transport import (AddressLengthTag,
                                                    TransactionStatus)
    conf = TpuConf({
        "spark.rapids.tpu.shuffle.transport.class":
            "spark_rapids_tpu.shuffle.tcp.TcpTransport",
        "spark.rapids.tpu.shuffle.tcp.registryDir": str(tmp_path / "reg"),
        **SMALL_BOUNCE})
    a = TcpTransport("exec-a", conf)
    b = TcpTransport("exec-b", conf)
    c = TcpTransport("exec-c", conf)
    try:
        conn_b = a.connect("exec-b")
        conn_c = a.connect("exec-c")
        lost = []
        a.add_peer_lost_listener(lost.append)
        # one pending receive per peer; kill b — only b's must fail
        rb = conn_b.receive(AddressLengthTag(bytearray(5), 5, tag=0x10),
                            lambda t: None)
        alt_c = AddressLengthTag(bytearray(5), 5, tag=0x20)
        rc = conn_c.receive(alt_c, lambda t: None)
        b.shutdown()
        rb.wait(10)
        assert rb.status is TransactionStatus.ERROR
        assert "lost" in rb.error_message
        # c's receive is untouched and still completes
        assert rc.status is TransactionStatus.IN_PROGRESS
        # the server sends on the socket exec-a opened, known to c once
        # c's reader has read exec-a's hello
        wait_for(lambda: c._peer_by_id("exec-a") is not None)
        c.server.send("exec-a", AddressLengthTag.for_bytes(b"hello", 0x20),
                      lambda t: None).wait(10)
        rc.wait(10)
        assert rc.status is TransactionStatus.SUCCESS
        assert bytes(alt_c.buffer) == b"hello"
        # _peer_lost queues the transactions' failure, then notifies
        wait_for(lambda: lost)
        assert lost == ["exec-b"]
    finally:
        a.shutdown()
        c.shutdown()


@pytest.mark.parametrize("stop", ["shutdown", "kill"])
def test_stopped_transport_closes_connection_without_hello(tmp_path, stop):
    """A peer that has dialled but whose hello the transport has not read
    yet (here: never sent) is in no peer table; shutdown() and kill() close
    its socket all the same, so the remote sees the loss at once."""
    import socket
    from spark_rapids_tpu.shuffle.tcp import TcpTransport
    conf = TpuConf({
        "spark.rapids.tpu.shuffle.tcp.registryDir": str(tmp_path / "reg"),
        **SMALL_BOUNCE})
    b = TcpTransport("exec-b", conf)
    sock = socket.create_connection(b.address, timeout=30)
    try:
        wait_for(lambda: b._live)           # accepted
        getattr(b, stop)()
        assert sock.recv(1) == b""          # closed by b, not timed out
    finally:
        sock.close()
        b.shutdown()


def test_dead_client_evicted_and_reconnect_possible(tmp_path):
    """ShuffleEnv drops the cached client when the peer dies (in-process
    fabric kill), so client_for() can build a fresh one. The per-peer
    connect lock survives — replacing it mid-connect could let a second
    caller dial a duplicate connection."""
    mgr, e0, e1 = fault_cluster(tmp_path)
    c1 = e0.client_for("exec-1")
    assert e0.client_for("exec-1") is c1            # cached
    _Fabric.get().kill("exec-1")
    assert e0.metrics[mt.SHUFFLE_PEER_EVICTIONS].value == 1
    assert "exec-1" not in e0._clients
    assert "exec-1" in e0._connect_locks            # lock kept, reusable
    # revive the executor on the fabric; a fresh client connects
    e1b = ShuffleEnv("exec-1", e0.conf, disk_dir=str(tmp_path / "e1b"))
    c2 = e0.client_for("exec-1")
    assert c2 is not c1


def test_lost_blocks_fail_fast_without_retry(tmp_path):
    """Lost blocks are PERMANENT (only a map recompute brings them back):
    the reader must not burn its retry budget re-asking for them."""
    mgr, e0, e1 = fault_cluster(tmp_path)
    sid, _ = mgr.register_shuffle(1)
    t = sample_table(40, seed=12)
    write_partitioned(mgr, e1, sid, 0, t, 1)
    e1.shuffle_catalog.remove_shuffle(sid)      # data gone, tracker stale
    with pytest.raises(ShuffleFetchFailedError, match="lost blocks") as ei:
        collect_partition(mgr, e0, sid, 0)
    assert ei.value.executor_id == "exec-1" and ei.value.blocks
    assert e0.metrics[mt.SHUFFLE_FETCH_RETRIES].value == 0


def test_unreachable_peer_surfaces_scoped_fetch_failure(tmp_path):
    """A peer that cannot even be dialed (dead executor) surfaces as a
    scoped ShuffleFetchFailedError, never a bare ConnectionError."""
    mgr, e0, e1 = fault_cluster(
        tmp_path, extra={"spark.rapids.tpu.shuffle.maxRetries": 1,
                         "spark.rapids.tpu.shuffle.fetch.timeoutSeconds": 30})
    sid, _ = mgr.register_shuffle(1)
    t = sample_table(40, seed=10)
    write_partitioned(mgr, e1, sid, 0, t, 1)
    _Fabric.get().kill("exec-1")                # endpoint gone: connect fails
    with pytest.raises(ShuffleFetchFailedError) as ei:
        collect_partition(mgr, e0, sid, 0)
    assert ei.value.executor_id == "exec-1" and ei.value.blocks


def test_registry_file_removed_on_shutdown(tmp_path):
    """A restarted executor must not be resolvable at its dead address."""
    import os
    from spark_rapids_tpu.shuffle.tcp import TcpTransport
    reg = tmp_path / "reg"
    conf = TpuConf({
        "spark.rapids.tpu.shuffle.tcp.registryDir": str(reg),
        "spark.rapids.tpu.shuffle.maxRetries": 0,
        "spark.rapids.tpu.shuffle.connectTimeout": 0.2})
    t = TcpTransport("exec-gone", conf)
    assert (reg / "exec-gone").exists()
    t.shutdown()
    assert not (reg / "exec-gone").exists()
    other = TcpTransport("exec-live", conf)
    try:
        with pytest.raises(ConnectionError, match="never registered"):
            other.connect("exec-gone")
    finally:
        other.shutdown()


def test_reader_timeout_is_overall_deadline(tmp_path):
    """A trickling-but-stuck fetch (events keep arriving, one block never
    does) times out at the overall deadline instead of resetting per event."""
    mgr, e0, e1 = fault_cluster(tmp_path)
    sid, _ = mgr.register_shuffle(1)
    t = sample_table(50, seed=8)
    write_partitioned(mgr, e1, sid, 0, t, 1)
    # sabotage AFTER metadata registration: blocks exist in the tracker but
    # e1 will never answer (handlers replaced by a black hole that only
    # keeps the connection chatty)
    e1.transport.server.register_request_handler(
        "transfer", lambda peer, payload: time.sleep(3600))
    from spark_rapids_tpu.shuffle.manager import CachingShuffleReader
    reader = CachingShuffleReader(e0, mgr.tracker, sid, 0, timeout=1.0)
    start = time.monotonic()
    with pytest.raises(ShuffleFetchFailedError, match="timed out"):
        list(reader.read())
    assert time.monotonic() - start < 10            # not 3600, not per-event


def test_connect_retries_until_peer_registers(tmp_path):
    """TCP connect outlasts a slow registry: the peer registers while the
    client is inside its backoff schedule."""
    from spark_rapids_tpu.shuffle.tcp import TcpTransport
    conf = TpuConf({
        "spark.rapids.tpu.shuffle.tcp.registryDir": str(tmp_path / "reg"),
        "spark.rapids.tpu.shuffle.connectTimeout": 0.3,
        "spark.rapids.tpu.shuffle.retryBackoffMs": 50,
        # the schedule, not the clock, bounds the client: a loaded host
        # may take seconds to bring the late peer up
        "spark.rapids.tpu.shuffle.maxRetries": 30})
    a = TcpTransport("exec-early", conf)
    result = {}

    def late_start():
        # only once the first connect attempt has failed
        deadline = time.monotonic() + 30
        while (a.metrics[mt.SHUFFLE_CONNECT_RETRIES].value < 1
               and time.monotonic() < deadline):
            time.sleep(0.01)
        result["b"] = TcpTransport("exec-late", conf)
        result["b"].server.register_request_handler(
            "ping", lambda peer, payload: b"pong")
    th = threading.Thread(target=late_start)
    th.start()
    try:
        conn = a.connect("exec-late")   # first attempt times out, retry wins
        tx = conn.request("ping", b"", lambda t: None).wait(10)
        assert tx.response == b"pong"
        assert a.metrics[mt.SHUFFLE_CONNECT_RETRIES].value >= 1
    finally:
        th.join()
        a.shutdown()
        if "b" in result:
            result["b"].shutdown()
