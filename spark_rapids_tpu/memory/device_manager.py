"""Device manager — device acquisition and memory arena sizing.

Reference analogue: GpuDeviceManager.scala (one-GPU-per-executor
acquisition, RMM pool init as fraction of device memory, pinned pool) and
the executor-plugin init path (Plugin.scala:219-247).

On TPU the runtime owns physical HBM; the manager tracks a *logical*
arena — ``allocFraction`` × device memory — that the spill framework and
admission control budget against, and installs the alloc-failure -> spill
hook (reference: DeviceMemoryEventHandler)."""
from __future__ import annotations

import logging
import threading
from typing import Optional

from ..config import (
    CONCURRENT_TPU_TASKS,
    DEVICE_MEMORY_DEBUG,
    DEVICE_MEMORY_FRACTION,
    FAULT_SEMAPHORE_TIMEOUT_MS,
    TpuConf,
)
from .semaphore import DeviceSemaphore

log = logging.getLogger(__name__)

#: arena basis on the CPU backend (tests), which reports no memory
#: limit; an accelerator that reports none is an error
_CPU_BACKEND_ARENA_BYTES = 16 * 1024 ** 3


class DeviceManager:
    """Process singleton (reference: one GPU per executor —
    GpuDeviceManager.scala:98-112 throws on more; here one process drives
    one local device set)."""

    _instance: Optional["DeviceManager"] = None
    _lock = threading.Lock()

    def __init__(self, conf: TpuConf):
        import jax

        self.conf = conf
        self.devices = jax.devices()
        self.device = self.devices[0]
        self.platform = self.device.platform
        from ..utils import compile_cache

        compile_cache.enable()
        total = self._query_memory()
        self.arena_bytes = int(total * conf.get(DEVICE_MEMORY_FRACTION))
        self.debug = conf.get(DEVICE_MEMORY_DEBUG)
        # acquire watchdog: fault.semaphoreTimeoutMs (0 = the class's
        # built-in default) — its DeviceSemaphoreTimeout is a retryable
        # fault the degradation ladder recovers on
        sem_timeout_ms = conf.get(FAULT_SEMAPHORE_TIMEOUT_MS)
        self.semaphore = DeviceSemaphore(
            conf.get(CONCURRENT_TPU_TASKS),
            acquire_timeout=(sem_timeout_ms / 1000.0
                             if sem_timeout_ms and sem_timeout_ms > 0
                             else None))
        self._allocated = 0
        self._alloc_lock = threading.Lock()
        self._peak = 0
        self._reserved = 0
        self.event_handler = None  # installed by spill framework
        if self.debug:
            log.info("DeviceManager: %s, arena=%d bytes",
                     self.device, self.arena_bytes)

    @classmethod
    def get_or_create(cls, conf: TpuConf) -> "DeviceManager":
        with cls._lock:
            if cls._instance is None:
                cls._instance = DeviceManager(conf)
            return cls._instance

    @classmethod
    def reset(cls):
        with cls._lock:
            cls._instance = None

    def _query_memory(self) -> int:
        if self.platform == "cpu":
            return _CPU_BACKEND_ARENA_BYTES
        stats = self.device.memory_stats()
        if not stats or "bytes_limit" not in stats:
            raise RuntimeError(
                f"device {self.device.device_kind!r} ({self.platform}) "
                f"reports no bytes_limit in memory_stats() ({stats!r}): "
                "the arena cannot be sized")
        return int(stats["bytes_limit"])

    # ----- logical arena accounting (RMM-pool analogue) -------------------
    def track_alloc(self, nbytes: int) -> None:
        """Record a device allocation; fires the event handler (spill) when
        the logical arena would overflow (reference:
        DeviceMemoryEventHandler.onAllocFailure).

        Raises :class:`~.retry.TpuRetryOOM` — the typed signal the retry
        framework recovers from — when the arena is over budget and the
        spill handler could not free anything (everything pinned); the
        allocation is rolled back so a retried attempt re-tracks it.
        Also an OOM-injection checkpoint (fires BEFORE any accounting)."""
        from .retry import TpuRetryOOM, maybe_inject_oom

        maybe_inject_oom("DeviceManager.track_alloc", nbytes)
        with self._alloc_lock:
            self._allocated += nbytes
            self._peak = max(self._peak, self._allocated)
            over = self._allocated - self.arena_bytes
        if over > 0 and self.event_handler is not None:
            freed = self.event_handler.on_alloc_threshold(over)
            with self._alloc_lock:
                still_over = self._allocated - self.arena_bytes
            if still_over > 0 and not freed:
                with self._alloc_lock:
                    self._allocated = max(0, self._allocated - nbytes)
                from ..telemetry.events import emit_event

                emit_event("admission_reject", requested=nbytes,
                           over_bytes=still_over,
                           arena_bytes=self.arena_bytes)
                raise TpuRetryOOM(
                    f"device arena exhausted: allocation of {nbytes} "
                    f"bytes leaves usage {still_over} bytes over the "
                    f"{self.arena_bytes}-byte arena and nothing could "
                    "be spilled (all device buffers pinned)")
        if self.debug:
            log.info("alloc %d (total %d)", nbytes, self._allocated)

    def track_free(self, nbytes: int) -> None:
        with self._alloc_lock:
            self._allocated = max(0, self._allocated - nbytes)

    # ----- admission-side reservations (scheduler) ------------------------
    # A lifetime HBM reservation per *running* query: the scheduler only
    # dispatches a query when its reservation fits, so the sum of
    # running reservations never exceeds the arena.  Reservations are a
    # dispatch gate, not an allocation — running queries' real
    # allocations still flow through track_alloc against the full
    # arena (the retry/spill machinery arbitrates inside the budget).
    def try_reserve(self, nbytes: int) -> bool:
        """Atomically reserve admission budget; False when it does not
        fit (the caller keeps the query queued)."""
        if nbytes <= 0:
            return True
        with self._alloc_lock:
            if self._reserved + nbytes > self.arena_bytes:
                return False
            self._reserved += nbytes
            return True

    def release_reservation(self, nbytes: int) -> None:
        if nbytes <= 0:
            return
        with self._alloc_lock:
            self._reserved = max(0, self._reserved - nbytes)

    def headroom(self) -> int:
        """Unallocated logical-arena bytes (may be negative while the
        spiller catches up) — the ``shuffle.mode=auto`` admission
        signal: a device-resident shuffle write only starts while the
        arena has room, otherwise it degrades to the host-staged
        path up front instead of thrashing the spiller."""
        with self._alloc_lock:
            return self.arena_bytes - self._allocated

    @property
    def reserved_bytes(self) -> int:
        return self._reserved

    @property
    def allocated_bytes(self) -> int:
        return self._allocated

    @property
    def peak_bytes(self) -> int:
        return self._peak
