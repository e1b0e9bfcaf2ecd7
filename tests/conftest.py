import os
import sys

# Tests run on the real (1-device) CPU platform -- the 512-device override
# belongs to launch/dryrun.py ONLY. Keep XLA quiet and deterministic.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import pytest  # noqa: E402


class CompileCounter:
    """Counts XLA backend compiles via jax.monitoring's
    ``/jax/core/compile/backend_compile_duration`` event -- every lowering
    that reaches the backend fires it exactly once, cache hits fire
    nothing. ``reset()`` after warmup, then assert ``count == 0`` across
    the region that must not recompile (e.g. ServingEngine.swap cycles)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def _listener(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.count += 1

    def reset(self):
        self.count = 0


@pytest.fixture
def compile_counter():
    """Yields a live CompileCounter; the listener is removed on teardown."""
    from jax import monitoring

    counter = CompileCounter()
    monitoring.register_event_duration_secs_listener(counter._listener)
    try:
        yield counter
    finally:
        monitoring.unregister_event_duration_listener(counter._listener)
