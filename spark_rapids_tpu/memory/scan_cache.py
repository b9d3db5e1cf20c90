"""Device-resident cache of scanned in-memory tables.

Repeated actions over the same DataFrame re-run the whole physical plan,
including the host->device upload of the scanned arrow table — by far the
dominant cost on a remote-attached chip. This cache keeps the uploaded
DeviceBatch alive across actions, keyed by the identity of the (immutable)
arrow table, with LRU eviction over a device-byte budget that is read from
the device (``derived_budget``), not from a constant.

Reference analog: the device tier of the spillable buffer store
(RapidsDeviceMemoryStore.scala / RapidsBufferCatalog.scala) which keeps hot
columnar batches resident in device memory; this is its scan-side
specialization (there is no JVM-side BlockManager here to hand buffers to).
"""
from __future__ import annotations

import threading
import weakref
from collections import OrderedDict
from typing import Optional, Tuple

from spark_rapids_tpu.utils import tracing as _tracing


class DeviceScanCache:
    """LRU over (table identity, string width) -> DeviceBatch.

    Identity is checked with a weakref to the arrow table: a dead or replaced
    object at the same address can never produce a false hit, and a table
    being garbage-collected drops its entry's bytes from the budget on the
    next eviction sweep. All operations lock: the OOM recovery path clears
    the cache from whatever thread hit the allocation failure.
    """

    def __init__(self, max_bytes: int):
        self.max_bytes = max_bytes
        self._lock = threading.Lock()
        # key -> (weakref to table, DeviceBatch, nbytes)
        self._entries: "OrderedDict[Tuple[int, int], tuple]" = OrderedDict()
        #: per-key in-flight upload latch: two queries missing on the same
        #: table concurrently must share ONE upload, not pay the host link
        #: twice (the concurrent-miss double-insert fix)
        self._inflight: dict = {}

    def get_or_put(self, table, smax: int, builder, cancel_check=None):
        """Hit -> cached batch. Miss -> exactly one caller runs ``builder``
        (the upload) while concurrent missers wait on the key's latch and
        then read the inserted entry. If the builder fails, its exception
        propagates to the builder caller and a waiter takes over the build
        on its next loop — no key is ever latched forever.

        ``cancel_check`` (typically QueryHandle.check_cancelled) runs
        periodically while blocked on another query's upload, so a
        cancelled query unwinds instead of waiting out a transfer it will
        never use — the same contract as semaphore admission."""
        key = (id(table), smax)
        while True:
            mine = False
            with self._lock:
                got = self._get_locked(table, smax)
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
                    kept = self.put(table, smax, batch)
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
        """``held`` (bytes in the cache after the call) beside ``budget``
        says why an entry was or was not kept."""
        if _tracing.TRACER.on:
            _tracing.instant(name, _tracing.LAYER_TRANSFER,
                             {"bytes": batch.device_size_bytes,
                              "held": self.total_bytes(),
                              "budget": self.max_bytes})

    def _get_locked(self, table, smax: int):
        key = (id(table), smax)
        entry = self._entries.get(key)
        if entry is None:
            return None
        ref, batch, _ = entry
        if ref() is not table:  # address reused by a different table
            del self._entries[key]
            return None
        self._entries.move_to_end(key)
        return batch

    def get(self, table, smax: int):
        with self._lock:
            return self._get_locked(table, smax)

    def put(self, table, smax: int, batch) -> bool:
        """Insert; False when the batch was not kept (over the whole
        budget, or the table cannot be weakly referenced)."""
        try:
            ref = weakref.ref(table)
        except TypeError:  # object not weakref-able: skip caching
            return False
        nbytes = batch.device_size_bytes
        if nbytes > self.max_bytes:
            return False
        with self._lock:
            self._entries[(id(table), smax)] = (ref, batch, nbytes)
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
        """Device bytes currently held — the device store counts these toward
        its budget so proactive spill decisions see cached scans."""
        with self._lock:
            return self._total()

    def shrink_by(self, nbytes: int) -> int:
        """Evict LRU entries until at least nbytes are freed (or the cache is
        empty); returns bytes freed. Called by the device store's admission
        path — cached scans are re-uploadable, so they go before real spills."""
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
    """Process-wide cache (one device per process, like the executor-wide
    device store); the budget follows the most recent session's conf. The
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
