"""JAX's persistent compilation cache, placed in one directory.

The directory is part of what makes an entry findable again, so it never
moves: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX
reads the variable itself and nothing here overrides it), otherwise
``.jax_cache/`` at the checkout root.  Entry points (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks.run``) call :func:`enable` before
their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"

# JAX's monitoring events: every compile that consults the cache, the
# lookups it answered, and the entries written (JAX writes only compiles
# slower than ``jax_persistent_cache_min_compile_time_secs``)
_EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "lookups",
           "/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "writes"}


def enable() -> str:
    """Turn the persistent cache on and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


# wraps each compile-or-load of an XLA program (a cache hit included)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def counter() -> dict:
    """A live count from now on of persistent-cache traffic
    (``lookups``, ``hits``, ``writes``) and of the programs compiled or
    loaded (``compiles``), the seconds they took (``compile_s``) and the
    five slowest as ``(seconds, name)`` (``slowest``)."""
    counts = dict.fromkeys(_EVENTS.values(), 0)
    counts.update(compiles=0, compile_s=0.0, slowest=[])

    def _listen(event: str, **_):
        key = _EVENTS.get(event)
        if key is not None:
            counts[key] += 1

    def _listen_duration(event: str, duration_secs: float, **kw):
        if event == _COMPILE_EVENT:
            counts["compiles"] += 1
            counts["compile_s"] += duration_secs
            counts["slowest"] = sorted(
                counts["slowest"] + [(duration_secs, kw.get("fun_name"))],
                reverse=True)[:5]

    jax.monitoring.register_event_listener(_listen)
    jax.monitoring.register_event_duration_secs_listener(_listen_duration)
    return counts
