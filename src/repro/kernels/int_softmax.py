"""Shiftmax on a VMEM tile (SwiftTron §III-F), inside the attention kernels.

The fused prefill, paged-prefill and decode attention kernels share these
helpers: i-exp on a tile, the int8 attention weights, and the per-row
division after P·V that ``core.softmax.normalize_rows`` defines.  Each is
bit-identical to its ``core.softmax`` twin.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.softmax import ISoftmaxPlan, PROB_SHIFT, U_MAX, U_SHIFT


def _rshift_round(x, s: int):
    if s == 0:
        return x
    return (x + (1 << (s - 1))) >> s


def _exp16_tile(q_sub, plan: ISoftmaxPlan):
    """Inlined core.softmax._exp16 on a tile (all constants static)."""
    q = jnp.maximum(q_sub, jnp.int32(-plan.q_band))
    q = _rshift_round(_rshift_round(q, plan.dn_in.pre) *
                      jnp.int32(plan.dn_in.b),
                      plan.dn_in.c - plan.dn_in.pre)
    # i-exp: x = p - z*ln2
    q = jnp.minimum(q, 0)
    ie = plan.iexp
    qn = jnp.maximum(q, jnp.int32(-ie.z_max * ie.q_ln2))
    z = (-qn) // jnp.int32(ie.q_ln2)
    q_p = qn + z * jnp.int32(ie.q_ln2)
    t = q_p + jnp.int32(ie.q_b)
    q_l = t * t + jnp.int32(ie.q_c)
    e = jax.lax.shift_right_arithmetic(q_l, z)
    d = plan.dn_e16
    return _rshift_round(_rshift_round(e, d.pre) * jnp.int32(d.b),
                         d.c - d.pre)


def normalize_tile(acc, s):
    """``core.softmax.normalize_rows`` in a kernel, without an integer
    division: ``round_half_up(acc * 2^7 / s)`` per row.

    A float32 estimate of the quotient lies within 0.01 of the true one
    (``|quotient| <= 128 * 127 + 1``; the operands' and the reciprocal's
    rounding are a few parts in 2^24 of it), so its floor is off by at
    most one.  The remainder ``2^7 * acc + s // 2 - q * s`` of the
    estimate is exact in int32 even where the products wrap (the true
    remainder lies within ``(-s, 2s)``), and one compare each way
    settles the floor."""
    s = jnp.maximum(s, 1)
    half = s >> 1
    inv = 1.0 / s.astype(jnp.float32)
    est = (acc.astype(jnp.float32) * float(1 << PROB_SHIFT)
           + half.astype(jnp.float32)) * inv
    q = jnp.floor(est).astype(jnp.int32)
    rem = (acc << PROB_SHIFT) + half - q * s
    return q + (rem >= s).astype(jnp.int32) - (rem < 0).astype(jnp.int32)


def attn_weights_tile(e16):
    """``core.softmax.attn_weights`` on a tile."""
    return jnp.minimum(_rshift_round(e16, U_SHIFT), U_MAX)
