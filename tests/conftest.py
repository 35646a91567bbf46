import os
import sys

# NOTE: do NOT set xla_force_host_platform_device_count here — smoke tests
# and benches must see 1 device (the dry-run sets its own flag).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def gathered_backend():
    """A registered ``ref`` backend that does not advertise
    ``tp_serving``: a ``tp > 1`` engine over it takes the exact
    single-device gather lowering.  Yields its registered name."""
    from repro.ops import register_backend, unregister_backend
    from repro.ops.backends.ref import RefBackend

    class GatheredRef(RefBackend):
        name = "ref_gathered"
        tp_serving = False

    register_backend(GatheredRef.name, GatheredRef(), overwrite=True)
    yield GatheredRef.name
    unregister_backend(GatheredRef.name)
