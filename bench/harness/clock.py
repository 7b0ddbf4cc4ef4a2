"""JAX's own compile events, so that set-up and window keep apart."""
from __future__ import annotations


class CompileClock:
    """Sums JAX's compile-phase durations (trace, lowering, backend
    compile) and counts backend compiles and persistent-cache hits."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
            if event == self.EVENTS[-1]:
                self.compiles += 1

    def _on_count(self, event, **_):
        if event == self.CACHE_HIT:
            self.cache_hits += 1
