"""JAX compile events, summed and counted (copied from ``chip_smoke.py``)."""
from __future__ import annotations

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileClock:
    """Sums JAX's compile-duration events (trace, lowering, backend compile
    or persistent-cache read), counts backend compiles and persistent-cache
    hits."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.events = 0
        self.cache_hits = 0
        self.by_event = {}

    def on_duration(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration
            self.events += 1
            key = event.rsplit("/", 1)[-1]
            self.by_event[key] = self.by_event.get(key, 0.0) + duration
            if event == COMPILE_EVENTS[-1]:
                self.compiles += 1

    def on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def install(self) -> "CompileClock":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self
