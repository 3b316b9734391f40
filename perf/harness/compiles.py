"""Counts XLA backend compiles (persistent-cache loads included) while
open: a window during which this is not zero was not measured warm."""

EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    def __init__(self):
        self.n = 0

    def _on(self, event, _secs, **_kw):
        self.n += event == EVENT

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)
