"""Persistent on-disk XLA compile caches.

Two layers, each placed from OUTSIDE the program:

* JAX's own persistent compilation cache, armed by `arm_native()` at
  `native_cache_dir()`: `$JAX_COMPILATION_CACHE_DIR` when the
  environment sets it (code then sets no directory at all), else the
  fixed `.jax_cache/` at the root of the checkout that holds this
  package. Never a temp, pid or timestamp name — the next process can
  only hit a directory it can find again. `chip_smoke.py` arms it
  itself; the library's compile sites arm it on accelerator backends
  (`jit.arm_compile_cache`), which is how `bench.py` and user scripts
  get it on the chip; `bench.py` reports `native_cache_stats()`. All
  go through this one resolver. Counters: jit/native_cache/{requests,
  hits}.
* the `.pdx` executable store below, on only where
  `PADDLE_COMPILE_CACHE_DIR` is set.

The `.pdx` store:

Reference capability: the reference framework's compiled-program cache
(CompiledProgram / ExecutorCache) keeps programs across steps; here we
keep them across PROCESSES — fleet rollouts, bench reruns and the
elastic reshape-resume path skip the XLA backend compile entirely.

Design: callers hand over a `jax.stages.Lowered` (tracing+lowering is
cheap and process-local; the backend compile is the expensive leg) and
`load_or_compile` keys the serialized executable by a sha256 over

    (schema, label, jax/jaxlib version, backend, device kind,
     device/process counts, the lowered StableHLO module text,
     extra caller legs)

— the module text captures everything about the program (shapes,
dtypes, static args, donation, GSPMD shardings), so two programs can
share an entry only if XLA itself would compile them identically.

Entries are single files under PADDLE_COMPILE_CACHE_DIR, published
with framework._atomic_write (a crash mid-write leaves no torn entry;
the chaos `cache_write` site injects exactly that torn artifact to
prove the read side tolerates it). Reads that fail for ANY reason
(truncated pickle, schema drift, an executable the runtime refuses to
load) count jit/persistent_cache/errors, evict the bad entry and fall
through to a fresh compile — the cache can only ever cost a miss.
LRU-by-mtime eviction keeps the directory under
PADDLE_COMPILE_CACHE_MAX_BYTES (hits touch mtime).

Counters: jit/persistent_cache/{hits,misses,bytes,errors}; flight
events `compile_cache` with the outcome + entry size, and the program
span `cache/load/<label>` inside the caller's `compile/<program>`.
"""
from __future__ import annotations

import hashlib
import os
import pickle

from ..core import monitor as _monitor
from ..monitor import chaos as _chaos
from ..monitor import flight as _flight

__all__ = ["enabled", "cache_dir", "max_bytes", "load_or_compile",
           "cache_stats", "clear", "native_cache_dir", "arm_native",
           "native_cache_stats"]

_SCHEMA = "paddle_tpu.compile_cache/2"
_SUFFIX = ".pdx"


_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

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
    programs whose sum is what a warm start saves."""
    global _native_armed
    import jax

    d = native_cache_dir()
    if not _native_armed:
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", d)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
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


def cache_dir():
    return os.environ.get("PADDLE_COMPILE_CACHE_DIR") or None


def enabled():
    return cache_dir() is not None


def max_bytes():
    try:
        return int(os.environ.get("PADDLE_COMPILE_CACHE_MAX_BYTES",
                                  str(2 << 30)))
    except ValueError:
        return 2 << 30


def _env_legs():
    import jax
    import jaxlib

    try:
        kind = getattr(jax.devices()[0], "device_kind", "")
    except Exception:
        kind = ""
    return (jax.__version__, jaxlib.__version__, jax.default_backend(),
            kind, jax.device_count(), jax.process_count())


def _digest(label, lowered, extra):
    h = hashlib.sha256()
    h.update(repr((_SCHEMA, label, _env_legs(), extra)).encode())
    h.update(lowered.as_text().encode())
    return h.hexdigest()


def _entry_files(d):
    out = []
    try:
        for name in os.listdir(d):
            if not name.endswith(_SUFFIX):
                continue
            p = os.path.join(d, name)
            try:
                st = os.stat(p)
                out.append((p, st.st_mtime, st.st_size))
            except OSError:
                pass
    except OSError:
        pass
    return out


def _sync_bytes_gauge(d):
    total = sum(sz for _, _, sz in _entry_files(d))
    _monitor.stat_set("jit/persistent_cache/bytes", total)
    return total


def _evict_lru(d):
    """Drop oldest entries until the directory fits max_bytes."""
    cap = max_bytes()
    files = sorted(_entry_files(d), key=lambda t: t[1])
    total = sum(sz for _, _, sz in files)
    for p, _, sz in files:
        if total <= cap:
            break
        try:
            os.remove(p)
            total -= sz
        except OSError:
            pass
    _monitor.stat_set("jit/persistent_cache/bytes", max(0, total))


def _drop(path):
    try:
        os.remove(path)
    except OSError:
        pass


def _read_entry(path):
    """The pickled entry dict, or None (missing/corrupt — corrupt
    entries are evicted and counted)."""
    try:
        with open(path, "rb") as f:
            ent = pickle.load(f)
        if not isinstance(ent, dict) or ent.get("schema") != _SCHEMA:
            raise ValueError("schema mismatch")
        return ent
    except FileNotFoundError:
        return None
    except Exception as e:
        _monitor.stat_add("jit/persistent_cache/errors", 1)
        _flight.record("compile_cache", event="corrupt",
                       err=type(e).__name__)
        _drop(path)
        return None


def _write_entry(path, label, payload, in_tree, out_tree, device_ids):
    from .. import framework

    blob = pickle.dumps({
        "schema": _SCHEMA, "label": label, "env": _env_legs(),
        "payload": payload, "in_tree": in_tree, "out_tree": out_tree,
        "device_ids": device_ids,
    }, protocol=4)
    # chaos site "cache_write": enospc/delay/stall enact inside hit();
    # "torn" comes back for us to enact — a PARTIAL entry written
    # non-atomically (the crash-mid-write artifact the atomic writer
    # exists to prevent), then the raise is swallowed by the caller's
    # best-effort contract and the next read must classify it corrupt
    if _chaos._armed:
        act = _chaos.hit("cache_write", label=label)
        if act is not None and act.fault == "torn":
            with open(path, "wb") as f:
                f.write(blob[:max(1, len(blob) // 2)])
            raise OSError("chaos: torn compile-cache write (injected)")
    framework._atomic_write(path, lambda f: f.write(blob))
    return len(blob)


def load_or_compile(lowered, label, extra=()):
    """compiled executable for `lowered`, via the on-disk cache.

    Returns (compiled, outcome) with outcome in {"off", "hit",
    "miss"}. Never raises on cache trouble — worst case is a plain
    lowered.compile(). The whole of it is the span
    `cache/load/<label>`: the load, or the compile and the write
    that a miss costs."""
    with _flight.span(f"cache/load/{label}", program=label):
        return _load_or_compile(lowered, label, extra)


def _load_or_compile(lowered, label, extra):
    d = cache_dir()
    if d is None:
        return lowered.compile(), "off"
    try:
        os.makedirs(d, exist_ok=True)
        key = _digest(label, lowered, tuple(extra))
    except Exception as e:
        _monitor.stat_add("jit/persistent_cache/errors", 1)
        _flight.record("compile_cache", event="error", phase="digest",
                       err=type(e).__name__)
        return lowered.compile(), "off"
    path = os.path.join(d, key + _SUFFIX)

    ent = _read_entry(path)
    if ent is not None:
        try:
            import jax
            from jax.experimental.serialize_executable import (
                deserialize_and_load)

            # load onto the devices the program was compiled for, not
            # every device of the backend: a one-device executable
            # loaded over an 8-device client fails at dispatch
            by_id = {dv.id: dv for dv in jax.devices()}
            compiled = deserialize_and_load(
                ent["payload"], ent["in_tree"], ent["out_tree"],
                execution_devices=[by_id[i]
                                   for i in ent["device_ids"]])
            _monitor.stat_add("jit/persistent_cache/hits", 1)
            _flight.record("compile_cache", event="hit", fn=label,
                           bytes=len(ent["payload"]))
            try:
                os.utime(path)  # LRU: a hit is a touch
            except OSError:
                pass
            # keep the bytes gauge live on all-hit runs too (a warm
            # bench record should still carry the cache size)
            _sync_bytes_gauge(d)
            return compiled, "hit"
        except Exception as e:
            # an entry the runtime refuses to load (version skew a
            # digest leg missed, torn payload) must cost a miss, not
            # a crash
            _monitor.stat_add("jit/persistent_cache/errors", 1)
            _flight.record("compile_cache", event="error",
                           phase="load", err=type(e).__name__)
            _drop(path)

    compiled = lowered.compile()
    _monitor.stat_add("jit/persistent_cache/misses", 1)
    try:
        from jax.experimental.serialize_executable import serialize

        payload, in_tree, out_tree = serialize(compiled)
        device_ids = [dv.id for dv in
                      compiled.runtime_executable().local_devices()]
        n = _write_entry(path, label, payload, in_tree, out_tree,
                         device_ids)
        _flight.record("compile_cache", event="miss", fn=label, bytes=n)
        _evict_lru(d)
    except Exception as e:
        # best-effort publish: serialization unsupported on this
        # backend, disk full, injected torn write — the compile
        # itself already succeeded
        _monitor.stat_add("jit/persistent_cache/errors", 1)
        _flight.record("compile_cache", event="error", phase="write",
                       err=type(e).__name__)
    return compiled, "miss"


def cache_stats():
    """{entries, bytes} of the live cache dir (also refreshes the
    bytes gauge)."""
    d = cache_dir()
    if d is None:
        return {"entries": 0, "bytes": 0}
    files = _entry_files(d)
    total = sum(sz for _, _, sz in files)
    _monitor.stat_set("jit/persistent_cache/bytes", total)
    return {"entries": len(files), "bytes": total}


def clear():
    d = cache_dir()
    if d is None:
        return
    for p, _, _ in _entry_files(d):
        _drop(p)
    _monitor.stat_set("jit/persistent_cache/bytes", 0)
