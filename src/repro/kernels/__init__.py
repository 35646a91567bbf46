"""Pallas TPU kernels for the SwiftTron integer datapath.

One module per op (``int8_matmul``, ``int_gelu``, ``int_layernorm``,
``int_attention_fused`` — bit-exact attention+requant,
``int_decode_attention`` — fused ragged-cache decode with valid_len
scalar-prefetch masking), the Shiftmax tile helpers those attention
kernels share (``int_softmax``), plus the pure-jnp oracles (``ref``)
they are tested against.  Models never import these directly: dispatch goes
through the ``repro.ops`` backend registry (see docs/KERNELS.md for the
contract, docs/OPS_API.md for the API).  The old ``ops.py``
string-dispatch shims are removed; importing them raises with a pointer
to ``repro.ops``.

Every kernel entry point takes ``interpret=None`` and resolves it through
:func:`resolve_interpret`: compiled on a TPU, the Pallas interpreter
only where the platform is the CPU.  A direct caller that omits the
argument therefore never runs interpreted on the chip.
"""
from __future__ import annotations

from typing import Optional


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The one interpret-mode policy for every Pallas launch: an explicit
    bool wins; ``None`` means interpret iff JAX's default platform is
    the CPU (so a TPU — or any other accelerator — compiles the kernel,
    and fails loudly if it cannot)."""
    if interpret is not None:
        return bool(interpret)
    import jax
    return jax.default_backend() == "cpu"
