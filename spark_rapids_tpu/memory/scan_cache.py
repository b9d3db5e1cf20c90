"""Device-resident cache of scanned in-memory tables.

Repeated actions over the same DataFrame re-run the whole physical plan,
including the host->device upload of the scanned arrow table — by far the
dominant cost on a remote-attached chip. This cache keeps the uploaded
batch alive across actions, keyed by the identity of the (immutable)
arrow table, with LRU eviction over a device-byte budget that is read from
the device (``derived_budget``), not from a constant.

An entry is a ``DeviceBatch`` (one device holds all of it) or, under a
mesh plan, a ``MeshBatch`` (each of the mesh's devices holds a shard). The
budget is one device's, so an entry is charged what it puts on its fullest
device (``charged_bytes``): a DeviceBatch all of its bytes, a MeshBatch its
bytes over the mesh's devices. The first device of a mesh is the process's
default device, so the sum of the charges is what that device holds.

Reference analog: the device tier of the spillable buffer store
(RapidsDeviceMemoryStore.scala / RapidsBufferCatalog.scala) which keeps hot
columnar batches resident in device memory; this is its scan-side
specialization (there is no JVM-side BlockManager here to hand buffers to).
"""
from __future__ import annotations

import threading
import weakref
from collections import OrderedDict
from typing import Optional

from spark_rapids_tpu.utils import tracing as _tracing


def charged_bytes(batch) -> int:
    """What the batch puts on the fullest device that holds part of it."""
    return -(-batch.device_size_bytes // getattr(batch, "n_dev", 1))


class DeviceScanCache:
    """LRU over (table identity, string width, mesh) -> DeviceBatch, or
    MeshBatch where ``mesh`` is one (None: the single-device form), so a
    mesh session and a single-device one never share an entry.

    Identity is checked with a weakref to the arrow table: a dead or replaced
    object at the same address can never produce a false hit, and a table
    being garbage-collected drops its entry's bytes from the budget on the
    next eviction sweep. All operations lock: the OOM recovery path clears
    the cache from whatever thread hit the allocation failure.
    """

    def __init__(self, max_bytes: int):
        self.max_bytes = max_bytes
        self._lock = threading.Lock()
        # (table id, string width, mesh or None) ->
        # (weakref to table, batch, bytes charged)
        self._entries: "OrderedDict[tuple, tuple]" = OrderedDict()
        #: per-key in-flight upload latch: two queries missing on the same
        #: table concurrently must share ONE upload, not pay the host link
        #: twice (the concurrent-miss double-insert fix)
        self._inflight: dict = {}

    def get_or_put(self, table, smax: int, builder, cancel_check=None,
                   mesh=None):
        """Hit -> cached batch. Miss -> exactly one caller runs ``builder``
        (the upload) while concurrent missers wait on the key's latch and
        then read the inserted entry. If the builder fails, its exception
        propagates to the builder caller and a waiter takes over the build
        on its next loop — no key is ever latched forever.

        ``cancel_check`` (typically QueryHandle.check_cancelled) runs
        periodically while blocked on another query's upload, so a
        cancelled query unwinds instead of waiting out a transfer it will
        never use — the same contract as semaphore admission."""
        key = (id(table), smax, mesh)
        while True:
            mine = False
            with self._lock:
                got = self._get_locked(table, key)
                if got is None:
                    ev = self._inflight.get(key)
                    if ev is None:
                        ev = threading.Event()
                        # released in the mine-branch finally below: the
                        # store and the release correlate through `mine`
                        # (set True in this branch only), one hop beyond
                        # what path-insensitive dataflow can prove
                        self._inflight[key] = ev  # tpu-lint: disable=R008
                        mine = True
            if got is not None:
                self._instant("scan_cache.hit", got)
                return got
            if mine:
                try:
                    batch = builder()
                    kept = self.put(table, smax, batch, mesh)
                    # not_kept: built, but over the budget — the next
                    # query (and any waiter on this latch) uploads again
                    self._instant("scan_cache.miss" if kept
                                  else "scan_cache.not_kept", batch)
                    return batch
                finally:
                    with self._lock:
                        self._inflight.pop(key, None)
                    ev.set()
            with _tracing.span("scan_cache.wait", _tracing.LAYER_TRANSFER):
                while not ev.wait(0.05):
                    if cancel_check is not None:
                        cancel_check()

    def _instant(self, name: str, batch) -> None:
        """``held`` (bytes charged in the cache after the call) beside
        ``budget`` says why an entry was or was not kept; ``bytes`` is the
        entry's own charge."""
        if _tracing.TRACER.on:
            _tracing.instant(name, _tracing.LAYER_TRANSFER,
                             {"bytes": charged_bytes(batch),
                              "held": self.total_bytes(),
                              "budget": self.max_bytes})

    def _get_locked(self, table, key):
        entry = self._entries.get(key)
        if entry is None:
            return None
        ref, batch, _ = entry
        if ref() is not table:  # address reused by a different table
            del self._entries[key]
            return None
        self._entries.move_to_end(key)
        return batch

    def get(self, table, smax: int, mesh=None):
        with self._lock:
            return self._get_locked(table, (id(table), smax, mesh))

    def put(self, table, smax: int, batch, mesh=None) -> bool:
        """Insert; False when the batch was not kept (its charge is over
        the whole budget, or the table cannot be weakly referenced)."""
        try:
            ref = weakref.ref(table)
        except TypeError:  # object not weakref-able: skip caching
            return False
        nbytes = charged_bytes(batch)
        if nbytes > self.max_bytes:
            return False
        with self._lock:
            self._entries[(id(table), smax, mesh)] = (ref, batch, nbytes)
            self._evict_locked()
        return True

    def _evict(self) -> None:
        with self._lock:
            self._evict_locked()

    def _evict_locked(self) -> None:
        # drop dead entries first, then LRU until under budget
        for key in [k for k, (r, _, _) in self._entries.items()
                    if r() is None]:
            del self._entries[key]
        while self._entries and self._total() > self.max_bytes:
            self._entries.popitem(last=False)

    def _total(self) -> int:
        return sum(n for _, _, n in self._entries.values())

    def total_bytes(self) -> int:
        """Bytes the entries are charged, which is what the fullest device
        holds of them — the device store counts these toward its budget so
        proactive spill decisions see cached scans of either kind."""
        with self._lock:
            return self._total()

    def shrink_by(self, nbytes: int) -> int:
        """Evict LRU entries, of either kind, until at least nbytes of
        charge are freed (or the cache is empty); returns the charge freed.
        Called by the device store's admission path — cached scans are
        re-uploadable, so they go before real spills."""
        freed = 0
        with self._lock:
            while self._entries and freed < nbytes:
                _, (_, _, n) = self._entries.popitem(last=False)
                freed += n
        return freed

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()


def derived_budget(conf) -> int:
    """The cache's byte budget on the device this process runs on.

    An explicit ``sql.scanCache.maxBytes`` is the budget. Unset (0), it is
    half of what the out-of-core contract lets one operator's working set
    occupy — ``memory.outOfCore.headroomFraction`` of the device budget
    the store does not hold already (``GraceController.threshold_bytes``
    is the same expression). A cached table is an operator's input, and
    an input stands in HBM twice when it matters: chunks beside the
    assembled batch while it is uploaded, the table beside what the
    operators carry of it while they run. Half of the working-set share is
    therefore what resident inputs can take and still be both built and
    read in one pass."""
    from spark_rapids_tpu import config as cfg
    explicit = conf.get(cfg.SCAN_CACHE_BYTES)
    if explicit:
        return explicit
    from spark_rapids_tpu.memory.device_manager import DeviceManager
    from spark_rapids_tpu.plan.footprint import device_budget_estimate
    dm = DeviceManager.peek()
    used = dm.device_store.used_bytes if dm is not None else 0
    free = max(device_budget_estimate(conf) - used, 0)
    return int(free * conf.get(cfg.OOC_HEADROOM)) // 2


_cache: Optional[DeviceScanCache] = None
_cache_lock = threading.Lock()


def peek_cache() -> Optional[DeviceScanCache]:
    """The live cache, if any — without creating one."""
    return _cache


def get_cache(max_bytes: int) -> DeviceScanCache:
    """Process-wide cache (one budget, a device's, like the executor-wide
    device store; a mesh plan's entries are charged per device against
    it); the budget follows the most recent session's conf. The
    eviction sweep runs here too, so dead tables and budget shrinks are
    reclaimed even on hit-only workloads."""
    global _cache
    with _cache_lock:
        if _cache is None:
            _cache = DeviceScanCache(max_bytes)
        else:
            _cache.max_bytes = max_bytes
            _cache._evict()
        return _cache
