"""Persistent XLA compilation cache + process-wide compile observability.

This is the one place the repo touches ``jax.experimental.compilation_cache``
semantics.  Two independent services live here:

* :func:`enable` turns on JAX's persistent compilation cache, so a
  restarted process — or a subprocess fleet host sharing the same
  directory — deserializes XLA executables instead of recompiling them.
  The directory is ``$JAX_COMPILATION_CACHE_DIR`` whenever that is set
  (placed from outside, it wins over everything in code), else the
  argument, else ``$AIDW_CACHE_DIR``, else the fixed in-checkout
  :data:`DEFAULT_CACHE_DIR`.  The path is part of every cache key, so it
  is never a temporary, per-process or time-based name.  The two
  persistence thresholds (``min_compile_time_secs``,
  ``min_entry_size_bytes``) are forced to zero:
  the default 1-second floor would silently skip most CPU-backend compiles,
  which are exactly the ones our CI cold-start gates measure.

* :func:`install_listeners` hooks ``jax._src.monitoring`` so the process
  keeps live counters of persistent-cache hits, cache-eligible compile
  requests, and backend compiles (count + wall seconds).  The backend
  counter fires on every dispatch that reaches the XLA compile layer —
  including persistent-cache *retrievals* — but NOT on in-memory jit-cache
  hits or on calls to AOT ``Compiled`` executables, which makes its delta
  the exact "did the hot path compile?" predicate the serving layer's
  post-warmup anomaly detection needs.

:func:`sync_registry` folds the since-last-sync deltas into an
``obs.Registry`` as ``compile_cache_hits`` / ``compile_cache_misses`` /
``backend_compiles`` counters, so fleet-level ``merge_states`` stays
additive (each host contributes its own deltas, never absolute totals
twice).

``python -m repro.runtime.compile_cache --cache-dir DIR [--min-hits N]``
runs a self-test: compile one canonical jit signature against the cache and
print the stats as JSON; with ``--min-hits`` it exits nonzero unless the
persistent cache served at least N hits — CI uses two successive runs to
assert a second process start actually hits the shared cache.
"""

from __future__ import annotations

import json
import os
import threading
import weakref
from pathlib import Path

__all__ = ["enable", "install_listeners", "cache_stats", "backend_compiles",
           "sync_registry", "background_compile_options"]

# <checkout>/.jax_cache, resolved from this file (src/repro/runtime/...)
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")

_LOCK = threading.Lock()
_LISTENERS_INSTALLED = False
_COUNTS = {
    "persistent_cache_hits": 0,     # executables deserialized from disk
    "cache_requests": 0,            # compile requests while cache enabled
    "backend_compiles": 0,          # dispatches reaching the compile layer
    "backend_compile_s": 0.0,       # wall seconds spent in that layer
}
# per-Registry baseline of the last sync_registry() fold, keyed weakly so a
# dropped registry doesn't pin its baseline forever
_SYNCED: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_COMPILE_DURATION_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_event(event: str, **kwargs) -> None:
    with _LOCK:
        if event == _HIT_EVENT:
            _COUNTS["persistent_cache_hits"] += 1
        elif event == _REQUEST_EVENT:
            _COUNTS["cache_requests"] += 1


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    if event != _COMPILE_DURATION_EVENT:
        return
    with _LOCK:
        _COUNTS["backend_compiles"] += 1
        _COUNTS["backend_compile_s"] += float(duration_secs)


def install_listeners() -> None:
    """Idempotently register the jax monitoring hooks that feed
    :func:`cache_stats`.  Safe to call before or after ``enable``; compiles
    that happened before the first call are not counted."""
    global _LISTENERS_INSTALLED
    with _LOCK:
        if _LISTENERS_INSTALLED:
            return
        _LISTENERS_INSTALLED = True
    from jax._src import monitoring

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)


def enable(cache_dir: str | None = None) -> str:
    """Enable the persistent compilation cache and install the compile
    listeners; returns the resolved cache directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is used as is: neither
    ``cache_dir`` nor ``$AIDW_CACHE_DIR`` overrides it.  Otherwise the
    directory is ``cache_dir``, then ``$AIDW_CACHE_DIR``, then
    :data:`DEFAULT_CACHE_DIR`."""
    install_listeners()
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR") or cache_dir
                 or os.environ.get("AIDW_CACHE_DIR") or DEFAULT_CACHE_DIR)
    cache_dir = os.path.abspath(os.path.expanduser(cache_dir))
    os.makedirs(cache_dir, exist_ok=True)
    import jax

    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # the defaults (1s floor, nonzero size floor) skip fast CPU compiles —
    # exactly the executables the cold-start gates need persisted
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def background_compile_options() -> dict | None:
    """Compiler options for compiles running CONCURRENTLY with serving.

    On the CPU backend, XLA's parallel LLVM codegen (default split count
    32) fans compile work out across every core — a background prewarm
    would steal the very cores the worker is executing on and double the
    foreground p99.  ``split_count=1`` keeps codegen on the (deprioritized)
    compiling thread, and on small-core boxes is FASTER outright (the
    parallel-split overhead is pure waste there).  Non-CPU backends return
    ``None``: device compiles don't contend with host-side serving.

    Note the trade-off: compiler options are part of the persistent-cache
    key, so entries written under these options are only shared with other
    *prewarm* compiles — a lazily-compiling process misses them (and vice
    versa).  The prewarm paths all use this same function, so fleet hosts
    still share one set of entries."""
    import jax

    if jax.default_backend() == "cpu":
        return {"xla_cpu_parallel_codegen_split_count": 1}
    return None


def cache_stats() -> dict:
    """Point-in-time copy of the process compile counters.  ``misses`` is
    derived (requests that reached the compile layer without a persistent
    hit); all fields are 0 until :func:`install_listeners` ran."""
    with _LOCK:
        snap = dict(_COUNTS)
    snap["persistent_cache_misses"] = max(
        0, snap["cache_requests"] - snap["persistent_cache_hits"])
    return snap


def backend_compiles() -> int:
    """Number of dispatches that reached the XLA compile layer so far.
    Deltas of this value bracket hot-path work: in-memory jit-cache hits and
    AOT ``Compiled`` calls do not move it."""
    with _LOCK:
        return _COUNTS["backend_compiles"]


def sync_registry(registry) -> dict:
    """Fold the counter deltas since this registry's last sync into it as
    ``compile_cache_hits`` / ``compile_cache_misses`` / ``backend_compiles``
    counters.  Delta-based so per-host registries stay additive under the
    fleet's ``Registry.merge_states``.  Returns the deltas applied."""
    snap = cache_stats()
    base = _SYNCED.get(registry) or {k: 0 for k in snap}
    delta = {k: snap[k] - base.get(k, 0) for k in snap}
    _SYNCED[registry] = snap
    registry.inc("compile_cache_hits", int(delta["persistent_cache_hits"]))
    registry.inc("compile_cache_misses",
                 int(max(0, delta["persistent_cache_misses"])))
    registry.inc("backend_compiles", int(delta["backend_compiles"]))
    return delta


def _selftest(argv=None) -> int:
    import argparse
    import time

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--cache-dir", default=None,
                   help="cache directory (default: $AIDW_CACHE_DIR, else "
                        "the checkout's .jax_cache; "
                        "$JAX_COMPILATION_CACHE_DIR overrides both)")
    p.add_argument("--min-hits", type=int, default=None, metavar="N",
                   help="exit nonzero unless the persistent cache served "
                        ">= N hits (use on the second of two runs)")
    args = p.parse_args(argv)

    resolved = enable(args.cache_dir)
    import jax
    import jax.numpy as jnp

    # one canonical signature: stable across runs so the second process's
    # compile request is a byte-identical cache key
    @jax.jit
    def probe(x):
        return jnp.tanh(x @ x.T).sum()

    t0 = time.perf_counter()
    probe(jnp.arange(4096, dtype=jnp.float32).reshape(64, 64)) \
        .block_until_ready()
    stats = cache_stats()
    stats["cache_dir"] = resolved
    stats["probe_s"] = time.perf_counter() - t0
    print(json.dumps(stats, indent=1))
    if args.min_hits is not None and \
            stats["persistent_cache_hits"] < args.min_hits:
        print(f"FAIL: {stats['persistent_cache_hits']} persistent cache "
              f"hits < required {args.min_hits}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(_selftest())
