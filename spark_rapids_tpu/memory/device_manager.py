"""Device/runtime initialization (reference: GpuDeviceManager.scala — executor
GPU acquisition, RMM pool init with allocFraction checks, pinned-pool init; and
Plugin.scala RapidsExecutorPlugin.init wiring the semaphore + stores).

One singleton per process: detects HBM capacity (jax memory stats when the
backend exposes them), derives the buffer-arena budget from
memory.tpu.allocFraction / poolSizeBytes, builds the DEVICE->HOST->DISK store
chain and the admission semaphore.
"""
from __future__ import annotations

import threading
from typing import Optional

from spark_rapids_tpu import config as cfg
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.memory.semaphore import TpuSemaphore
from spark_rapids_tpu.memory.store import (BufferCatalog, DeviceMemoryStore,
                                           DiskStore, HostMemoryStore,
                                           build_store_chain)

_CPU_BACKEND_HBM_BYTES = 16 << 30  # the CPU backend reports no memory stats


class DeviceManager:
    _instance: Optional["DeviceManager"] = None
    _lock = threading.Lock()

    def __init__(self, conf: TpuConf):
        self.conf = conf
        self.catalog = BufferCatalog()
        device_budget = self._derive_device_budget(conf)
        host_budget = conf.get(cfg.HOST_SPILL_STORAGE_SIZE)
        self.device_store, self.host_store, self.disk_store = build_store_chain(
            self.catalog, device_budget, host_budget)
        self.semaphore = TpuSemaphore(conf.concurrent_tpu_tasks)
        self.device_budget = device_budget

    @staticmethod
    def _detect_hbm_bytes() -> int:
        import jax
        dev = jax.devices()[0]
        stats = dev.memory_stats() or {}
        limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
        if limit:
            return int(limit)
        if dev.platform == "tpu":
            raise RuntimeError(
                f"{dev} reports no bytes_limit in memory_stats(); set "
                f"{cfg.DEVICE_POOL_BYTES.key} to size the device pool")
        return _CPU_BACKEND_HBM_BYTES

    def _derive_device_budget(self, conf: TpuConf) -> int:
        explicit = conf.get(cfg.DEVICE_POOL_BYTES)
        if explicit:
            return explicit
        frac = conf.get(cfg.DEVICE_POOL_FRACTION)
        return int(self._detect_hbm_bytes() * frac)

    def _memory_conf_key(self) -> tuple:
        c = self.conf
        return (c.get(cfg.DEVICE_POOL_BYTES), c.get(cfg.DEVICE_POOL_FRACTION),
                c.get(cfg.HOST_SPILL_STORAGE_SIZE), c.concurrent_tpu_tasks)

    @property
    def _is_idle(self) -> bool:
        return (len(self.device_store) == 0 and len(self.host_store) == 0
                and len(self.disk_store) == 0
                and self.semaphore.active_holders == 0)

    # ---- lifecycle -----------------------------------------------------------
    @classmethod
    def initialize(cls, conf: Optional[TpuConf] = None) -> "DeviceManager":
        """Process singleton. A new conf with different memory settings
        reconfigures the manager when it is idle; when busy the existing
        settings win (executor-level init semantics, like the reference's
        once-per-executor RMM pool)."""
        conf = conf or TpuConf()
        with cls._lock:
            if cls._instance is None:
                cls._instance = DeviceManager(conf)
                return cls._instance
            inst = cls._instance
            fresh = DeviceManager.__new__(DeviceManager)
            fresh.conf = conf
            if inst._memory_conf_key() != fresh._memory_conf_key():
                if inst._is_idle:
                    inst.device_store.close()
                    inst.host_store.close()
                    inst.disk_store.close()
                    cls._instance = DeviceManager(conf)
                else:
                    import logging
                    logging.getLogger(__name__).warning(
                        "DeviceManager busy; ignoring new memory settings %s",
                        fresh._memory_conf_key())
            return cls._instance

    @classmethod
    def get(cls) -> "DeviceManager":
        return cls.initialize()

    @classmethod
    def peek(cls) -> Optional["DeviceManager"]:
        """Current instance WITHOUT creating one (safe from finalizers)."""
        with cls._lock:
            return cls._instance

    @classmethod
    def shutdown(cls) -> None:
        with cls._lock:
            inst, cls._instance = cls._instance, None
        if inst is not None:
            inst.device_store.close()
            inst.host_store.close()
            inst.disk_store.close()
