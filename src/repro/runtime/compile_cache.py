"""Where JAX keeps its persistent compilation cache, and what compiling costs.

When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it.  Otherwise the entry points call ``configure()`` to keep
the cache at a fixed directory inside the checkout, ``<repo>/.jax_cache``
(git-ignored): the path is part of the cache key, so it must never be built
from a temp name, a pid or the time.

``configure()`` also listens, once per process, to JAX's compile events
(``jax.monitoring``).  They feed these counters, in seconds or in compiles,
which ``totals()`` returns for the whole process and which also feed the
KronScope counters of the same names while telemetry is active:

* ``compile.trace_s``: tracing to a jaxpr and lowering it to MLIR
  (``/jax/core/compile/jaxpr_trace_duration``,
  ``/jax/core/compile/jaxpr_to_mlir_module_duration``); a trace nested in
  another counts once, and a compile inside a trace counts as compiling;
* ``compile.backend_s``: XLA compiling (``/jax/core/compile/
  backend_compile_duration``), less the cache loads it includes;
* ``compile.cache_load_s``: executables loaded from the persistent cache
  (``/jax/compilation_cache/cache_retrieval_time_sec``);
* ``compile.cache_hits``, ``compile.cache_misses``: programs served from
  the cache, and compiled programs written to it.

The cache key is left as JAX makes it: it strips debug information, named
scopes included, so a key that kept them would keep file paths too and miss
in every fresh checkout.
"""
from __future__ import annotations

import os
import pathlib
import threading

import jax

from . import telemetry

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"

TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration")
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
COUNTED_EVENTS = {"/jax/compilation_cache/cache_hits": "compile.cache_hits",
                  "/jax/compilation_cache/cache_misses": "compile.cache_misses"}

_listening = False
_local = threading.local()
_lock = threading.Lock()
_totals: dict[str, float] = {}


def _state():
    st = getattr(_local, "st", None)
    if st is None:
        # depth of open trace/lower events; compile and load seconds inside
        # the open outermost one; cache loads inside the open backend compile
        st = _local.st = {"depth": 0, "nested_s": 0.0, "load_s": 0.0}
    return st


def _count(name: str, value: float = 1) -> None:
    with _lock:
        _totals[name] = _totals.get(name, 0) + value
    telemetry.counter_inc(name, value)


def totals() -> dict[str, float]:
    """The compile counters of this process so far (only those that have
    counted anything)."""
    with _lock:
        return dict(_totals)


def _on_start(event, value, **kw):
    """JAX marks the start of each timed compile phase with a scalar."""
    if event in TRACE_EVENTS:
        _state()["depth"] += 1


def _on_duration(event, secs, **kw):
    st = _state()
    if event in TRACE_EVENTS:
        st["depth"] = max(0, st["depth"] - 1)
        if st["depth"] == 0:
            _count("compile.trace_s", max(0.0, secs - st["nested_s"]))
            st["nested_s"] = 0.0
    elif event == LOAD_EVENT:
        _count("compile.cache_load_s", secs)
        st["load_s"] += secs
    elif event == BACKEND_EVENT:
        _count("compile.backend_s", max(0.0, secs - st["load_s"]))
        st["load_s"] = 0.0
        if st["depth"]:
            st["nested_s"] += secs


def _on_event(event, **kw):
    name = COUNTED_EVENTS.get(event)
    if name is not None:
        _count(name)


def listen() -> None:
    """Register the compile listeners (once per process)."""
    global _listening
    if _listening:
        return
    _listening = True
    jax.monitoring.register_scalar_listener(_on_start)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def configure() -> str:
    """Listen to compiles, point JAX at the cache directory in force and
    return it."""
    listen()
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


__all__ = ["configure", "listen", "totals", "DEFAULT_DIR"]
