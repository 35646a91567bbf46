"""Pallas TPU kernel: integer-only softmax (SwiftTron §III-F).

The ASIC instantiates m row-parallel Softmax units, each running three
phases (max search, i-exp, divide).  On TPU the m-way row parallelism
becomes the grid's row-block dimension, and the three phases become three
vectorised passes over a VMEM-resident (block_rows, row_len) tile — the
scores are read from HBM exactly once.

Rows are assumed int32 at the plan's score scale; output is int8
probabilities at 2^-7 (see core.softmax for the scale plan).

Also here, shared by every attention kernel: the Shiftmax tile helpers —
i-exp on a tile, the int8 attention weights, and the per-row division
after P·V that ``core.softmax.normalize_rows`` defines.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import trace_names
from repro.analysis.contracts import fit_block
from repro.core.softmax import (ISoftmaxPlan, PROB_SHIFT, RECIP_BITS,
                                U_MAX, U_SHIFT)
from repro.kernels import resolve_interpret


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


def _softmax_kernel(x_ref, o_ref, *, plan: ISoftmaxPlan, masked: bool,
                    valid_len: int):
    q = x_ref[...].astype(jnp.int32)
    if masked:
        pos = jax.lax.broadcasted_iota(jnp.int32, q.shape, q.ndim - 1)
        live = pos < valid_len
        q = jnp.where(live, q, jnp.int32(-(2 ** 30)))
    q_max = jnp.max(q, axis=-1, keepdims=True)
    e16 = _exp16_tile(q - q_max, plan)
    if masked:
        e16 = jnp.where(live, e16, 0)
    s = jnp.sum(e16, axis=-1, keepdims=True)
    r = jnp.int32(1 << RECIP_BITS) // jnp.maximum(s, 1)
    p = _rshift_round(e16 * r, RECIP_BITS - PROB_SHIFT)
    o_ref[...] = jnp.clip(p, 0, 127).astype(jnp.int8)


def int_softmax_pallas(scores, plan: ISoftmaxPlan, valid_len: int = -1,
                       block_rows: int = 8,
                       interpret: Optional[bool] = None):
    """scores: (..., rows, row_len) int32 -> int8 probs, same shape.

    ``valid_len`` >= 0 masks trailing positions (static padding mask);
    data-dependent masks are handled by the attention kernel instead.
    Rows are zero-padded to a multiple of 8 and blocked ``(br, row_len)``
    with ``br`` a multiple of 8 (chip-legal for any row count; padding
    is sliced off).
    """
    shape = scores.shape
    rows = 1
    for d in shape[:-1]:
        rows *= d
    row_len = shape[-1]
    rows_pad = -(-rows // 8) * 8
    x2 = jnp.pad(scores.reshape(rows, row_len),
                 ((0, rows_pad - rows), (0, 0)))
    br = fit_block(max(block_rows, 8), rows_pad, 8)
    kernel = functools.partial(_softmax_kernel, plan=plan,
                               masked=valid_len >= 0, valid_len=valid_len)
    out = pl.pallas_call(
        kernel,
        grid=(rows_pad // br,),
        in_specs=[pl.BlockSpec((br, row_len), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, row_len), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, row_len), jnp.int8),
        name=trace_names.kernel("int_softmax"),
        interpret=resolve_interpret(interpret),
    )(x2)
    return out[:rows].reshape(shape)
