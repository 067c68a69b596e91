"""JAX's persistent compilation cache, placed and counted.

The one compile cache that outlives a process. `arm_native()` arms it
at `native_cache_dir()`: `$JAX_COMPILATION_CACHE_DIR` when the
environment sets it (code then sets no directory at all), else the
fixed `.jax_cache/` at the root of the checkout that holds this
package. Never a temp, pid or timestamp name: the next process can
only hit a directory it can find again. The library arms it before a
compile on an accelerator backend (`jit.program.arm_compile_cache`, by
`jit.Program` and `LLMEngine`), so a second process starts warm with
no option to set; an entry point that wants it on the CPU
(`chip_smoke.py --preflight`, a test) calls `arm_native()` itself.
A program that holds a Pallas kernel carries the kernel's source
locations in its text, hence in its key: `arm_native()` has JAX strip
the checkout's root from them (`jax_hlo_source_file_canonicalization_
regex`), so a checkout unpacked at another path finds the entries it
wrote at the first (PERF.md, PR 33).
Counters: jit/native_cache/{requests,hits}; `native_cache_stats()`
reads them.
"""
from __future__ import annotations

import os
import re

from ..core import monitor as _monitor

__all__ = ["native_cache_dir", "arm_native", "native_cache_stats"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_CHECKOUT_CACHE = os.path.join(_CHECKOUT, ".jax_cache")

_native_armed = False


def native_cache_dir():
    """The directory JAX's persistent compilation cache uses."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_CACHE


def _on_jax_event(event, **kwargs):
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        _monitor.stat_add("jit/native_cache/requests", 1)
    elif event == "/jax/compilation_cache/cache_hits":
        _monitor.stat_add("jit/native_cache/hits", 1)


def arm_native():
    """Arm JAX's persistent compilation cache at native_cache_dir()
    (idempotent) and return that directory. Every program is cached,
    however quick its compile: an eager run is hundreds of sub-second
    programs whose sum is what a warm start saves. Call it before the
    first lowering: the keys of what was lowered earlier still hold
    the checkout's path."""
    global _native_armed
    import jax

    d = native_cache_dir()
    if not _native_armed:
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", d)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
        # file names in a lowered module are relative to the checkout:
        # the same program has the same key wherever the tree lies
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          "^" + re.escape(_CHECKOUT + os.sep))
        jax.monitoring.register_event_listener(_on_jax_event)
        _native_armed = True
    return d


def native_cache_stats():
    """{dir, requests, hits, misses} of the native cache in this
    process (misses = requests that found no entry)."""
    req = _monitor.stat_get("jit/native_cache/requests")
    hits = _monitor.stat_get("jit/native_cache/hits")
    return {"dir": native_cache_dir(), "armed": _native_armed,
            "requests": req, "hits": hits, "misses": req - hits}
