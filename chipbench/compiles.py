"""Compilations and compile-cache traffic, from JAX's own monitoring
events, counted per phase of a run (set-up, window, after)."""

from __future__ import annotations

from typing import Dict

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"


def _empty() -> Dict[str, float]:
    return {"backend_compiles": 0, "compile_s": 0.0, "cache_requests": 0,
            "cache_hits": 0, "cache_load_s": 0.0, "traces": 0,
            "trace_s": 0.0, "lower_s": 0.0}


class CompileLog:
    """Listens to JAX's monitoring events. ``compile_s`` is the time
    spent in backend compilation, which for a program found in the
    persistent cache is the time to load it; ``cache_hits`` of
    ``cache_requests`` were found there. ``traces`` / ``trace_s`` and
    ``lower_s`` are the Python-side tracing to jaxprs and their lowering
    to MLIR, which a warm cache does not save."""

    def __init__(self):
        self.phase = "setup"
        self.by_phase: Dict[str, Dict[str, float]] = {}

    def _row(self):
        return self.by_phase.setdefault(self.phase, _empty())

    def on_event(self, event, **_kw):
        if event == CACHE_REQUEST:
            self._row()["cache_requests"] += 1
        elif event == CACHE_HIT:
            self._row()["cache_hits"] += 1

    def on_duration(self, event, duration, **_kw):
        row = self._row()
        if event == BACKEND_COMPILE:
            row["backend_compiles"] += 1
            row["compile_s"] += float(duration)
        elif event == CACHE_LOAD:
            row["cache_load_s"] += float(duration)
        elif event == TRACE:
            row["traces"] += 1
            row["trace_s"] += float(duration)
        elif event == LOWER:
            row["lower_s"] += float(duration)

    def install(self) -> "CompileLog":
        import jax
        jax.monitoring.register_event_listener(self.on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self.on_duration)
        return self

    def get(self, phase: str) -> Dict[str, float]:
        return dict(self.by_phase.get(phase, _empty()))
