"""Built-in backend implementations (registered by ``repro.ops``)."""
from repro.ops.backends.ref import RefBackend
from repro.ops.backends.pallas_fused import PallasFusedBackend

__all__ = ["RefBackend", "PallasFusedBackend"]
