"""Counts and times of XLA compiles, from ``jax.monitoring``.

A copy of ``chip_smoke.py``'s listener.  JAX records
``/jax/core/compile/backend_compile_duration`` around every backend
compile, whether XLA compiles the program or the persistent cache
supplies it; ``cache_retrieval_time_sec`` marks the second kind.
"""

from __future__ import annotations

import jax

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
TRACE_AND_LOWER = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)


class CompileLog:
    """Running totals since the listener was registered."""

    def __init__(self):
        self.count = 0  # backend compiles, from XLA or the cache
        self.seconds = 0.0  # their durations
        self.cache_loads = 0
        self.lower_seconds = 0.0  # tracing and lowering to MLIR
        self.names = []  # program of each backend compile, in order

    def __call__(self, event, duration, **kw):
        if event == BACKEND_COMPILE:
            self.count += 1
            self.seconds += duration
            self.names.append(str(kw.get("fun_name", "")))
        elif event == CACHE_RETRIEVAL:
            self.cache_loads += 1
        elif event in TRACE_AND_LOWER:
            self.lower_seconds += duration

    def snapshot(self) -> dict:
        return dict(count=self.count, seconds=self.seconds,
                    cache_loads=self.cache_loads,
                    lower_seconds=self.lower_seconds)


def listen() -> CompileLog:
    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)
    return log
