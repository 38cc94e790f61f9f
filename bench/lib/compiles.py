"""Count XLA backend compiles (copied from ``chip_smoke._compile_seconds``):
set-up should hold every compile, the measured window none."""
from __future__ import annotations

EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts backend compiles and their seconds while registered."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def _listener(self, event, duration, **_):
        if event == EVENT:
            self.count += 1
            self.seconds += duration

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listener)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listener)
        return False
