"""paddle.device surface (reference: python/paddle/device/__init__.py,
set_device:291)."""
from ..core.place import (
    set_device, get_device, CPUPlace, TPUPlace, Place,
    is_compiled_with_cuda, is_compiled_with_tpu, get_device_place,
)
import jax as _jax


def get_all_device_type():
    return sorted({d.platform for d in _jax.devices()})


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in _jax.devices()]


def get_available_custom_device():
    return []


def cuda_device_count():
    return 0


def tpu_device_count():
    return len([d for d in _jax.devices() if d.platform == "tpu"])


def synchronize(device=None):
    """Block until all queued device work completes (cudaDeviceSynchronize
    analog). jax dispatch is async; this drains it."""
    (_jax.device_put(0.0) + 0).block_until_ready()


# -- memory stats (reference: paddle.device.cuda.memory_allocated /
# max_memory_allocated / memory_stats over the fluid/memory allocator
# STAT_ADD counters; here PJRT device.memory_stats() with a
# jax.live_arrays() census fallback — monitor/memory.py owns the
# implementation and the mem/{allocated,peak}_bytes gauges) ----------

def memory_allocated(device=None):
    """Bytes currently allocated on the device (PJRT bytes_in_use;
    live-array census total where the backend has no memory stats)."""
    from ..monitor import memory as _mem

    return _mem.memory_allocated(device)


def max_memory_allocated(device=None):
    """High-water mark of allocated bytes since process start or the
    last reset_max_memory_allocated()."""
    from ..monitor import memory as _mem

    return _mem.max_memory_allocated(device)


def reset_max_memory_allocated(device=None):
    """Reset the high-water mark to the current allocated bytes."""
    from ..monitor import memory as _mem

    return _mem.reset_max_memory_allocated(device)


def memory_stats(device=None):
    """Full device-memory stat dict: raw PJRT stats plus normalized
    allocated_bytes / peak_bytes / source keys."""
    from ..monitor import memory as _mem

    return _mem.memory_stats(device)


class Event:
    """Minimal device event (reference platform/device_event.h).

    enable_timing=False (the default, matching the reference) makes
    record() a cheap ordering marker: no device synchronization, no
    timestamp — and elapsed_time() on such an event raises instead of
    returning garbage. enable_timing=True records a host timestamp
    AFTER draining queued device work (the single-stream analog of a
    timed CUDA event)."""

    def __init__(self, device=None, enable_timing=False):
        self._enable_timing = bool(enable_timing)
        self._t = None
        self._recorded = False

    def record(self):
        if not self._enable_timing:
            # untimed events must not hard-synchronize the device —
            # they only mark stream position, and XLA's single-stream
            # ordering already guarantees it
            self._recorded = True
            return
        import time

        synchronize()
        self._t = time.perf_counter()
        self._recorded = True

    def query(self):
        return self._recorded

    def elapsed_time(self, end):
        if self._t is None or getattr(end, "_t", None) is None:
            raise RuntimeError(
                "Event.elapsed_time needs both events recorded with "
                "enable_timing=True (construct the Event with "
                "enable_timing=True and call record() first)")
        return (end._t - self._t) * 1000.0


class Stream:
    """Single-stream model: XLA orders ops; kept for API parity."""

    def __init__(self, device=None, priority=2):
        pass

    def synchronize(self):
        synchronize()


def current_stream(device=None):
    return Stream()


def set_stream(stream):
    return stream

from . import plugin  # CustomDevice/PJRT plugin registry
