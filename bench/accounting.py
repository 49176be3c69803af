"""Compile and compile-cache accounting from JAX's own monitoring events.

A copy of the bring-up smoke's listeners: seconds spent tracing, lowering
and compiling, and the persistent cache's hits and writes.  ``snapshot()``
before and after a region gives what compiled inside it.
"""
from __future__ import annotations

import threading

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
# a program read back from the persistent cache / compiled and written
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                "/jax/compilation_cache/cache_misses": "cache_writes"}
# one lowering to MLIR per program built: a jit call that misses the
# in-memory cache, whether or not the persistent cache then serves it
BUILD_EVENT = COMPILE_EVENTS[1]

_totals: dict = {}
_lock = threading.Lock()
_installed = False


def _on_duration(event: str, secs: float, **_):
    with _lock:
        _totals[event] = _totals.get(event, 0.0) + secs
        if event == BUILD_EVENT:
            _totals["programs"] = _totals.get("programs", 0) + 1


def _on_event(event: str, **_):
    if event in CACHE_EVENTS:
        with _lock:
            _totals[event] = _totals.get(event, 0) + 1


def install() -> None:
    """Register the listeners once per process (before the first compile)."""
    global _installed
    if _installed:
        return
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    _installed = True


def snapshot() -> dict:
    with _lock:
        return dict(_totals)


def delta(before: dict, after: dict) -> dict:
    """Programs built, compile seconds and persistent-cache traffic between
    two snapshots."""
    d = {k: after.get(k, 0) - before.get(k, 0)
         for k in (*COMPILE_EVENTS, *CACHE_EVENTS, "programs")}
    return {"programs_built": int(d["programs"]),
            "compile_s": sum(d[k] for k in COMPILE_EVENTS),
            "backend_compile_s": d[COMPILE_EVENTS[2]],
            **{name: int(d[k]) for k, name in CACHE_EVENTS.items()}}
