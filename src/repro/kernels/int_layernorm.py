"""Pallas TPU kernel: integer LayerNorm / RMSNorm (SwiftTron §III-I).

Each grid step takes a ``(br, d)`` block of rows, ``br`` chosen from the
shape (:func:`norm_block_rows`, about 2 MiB of int32 input), and runs the
ASIC's three phases on the whole block: integer mean (dyadic 1/d),
variance with each row's own shift (``core.norms.row_shift``), then per row the integer
square root (:func:`isqrt_tile`: digit by digit, no division) and the
reciprocal ``2^(k+pre) // sigma`` (:func:`recip_tile`: a float32
estimate corrected to the exact floor quotient), and the per-channel
gamma/beta output phase.  The per-row statistics are a ``(br, 1)``
column: ``br / 8`` independent vregs, so no chain of dependent
divisions sets the pace (a lane-dense view of them measured no faster).

Bit-identical to ``core.norms.i_norm``: its Babylonian ``i_sqrt`` and
this kernel's square root are both exact ``floor(sqrt(n))``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import trace_names
from repro.analysis.contracts import fit_block
from repro.core.norms import INormPlan, row_shift, shift_by
from repro.kernels import resolve_interpret

#: int32 input bytes a grid step aims for: 512 rows at d = 768, 256 at
#: d = 2048.  The input and output blocks are double-buffered and the
#: body's intermediates are block-sized; twice this no longer fits the
#: v5e's 16 MiB of scoped VMEM at d = 2048 (the topology compile).
BLOCK_BYTES = 2 << 20


def norm_block_rows(rows: int, d: int) -> int:
    """Rows per grid step for ``rows`` (a multiple of 8) of width ``d``:
    the largest multiple of 8 that divides ``rows`` and keeps the int32
    block within :data:`BLOCK_BYTES`, or all the rows if they fit."""
    return fit_block(max(8, BLOCK_BYTES // (4 * d) // 8 * 8), rows, 8)


def _rshift_round(x, s: int):
    if s == 0:
        return x
    return (x + (1 << (s - 1))) >> s


def _apply_dn(x, dn):
    return _rshift_round(_rshift_round(x, dn.pre) * jnp.int32(dn.b),
                         dn.c - dn.pre)


def isqrt_tile(n):
    """floor(sqrt(n)) of int32 ``n``, 0 where ``n <= 0``: 16 rounds of the
    digit-by-digit method (shift, compare, subtract), exact on all of
    int32 and free of division.  ``floor(sqrt(2^31 - 1))`` is 46340."""
    root = jnp.zeros_like(n)
    rem = n
    for s in range(15, -1, -1):
        bit = jnp.int32(1 << (2 * s))
        t = root + bit
        ge = rem >= t
        rem = jnp.where(ge, rem - t, rem)
        root = jnp.where(ge, (root >> 1) + bit, root >> 1)
    return root


def recip_tile(num: int, sigma):
    """``num // max(sigma, 1)``, exactly, for ``num <= 2^30`` and ``0 <=
    sigma <= 46340``, without integer division.  A float32 quotient
    within a relative ``e`` of the true one (``e`` a few ulps, whatever
    the division's rounding) gives an estimate off by at most ``num * e
    + 1``; the remainder that estimate leaves is exact in int32, and its
    own float32 quotient brings the estimate within one of the floor,
    which the last compare settles."""
    s = jnp.maximum(sigma, 1)
    sf = s.astype(jnp.float32)
    n = jnp.int32(num)
    q = jnp.floor(jnp.float32(num) / sf).astype(jnp.int32)
    q = q + jnp.floor((n - q * s).astype(jnp.float32) / sf).astype(jnp.int32)
    rem = n - q * s
    return q + (rem >= s).astype(jnp.int32) - (rem < 0).astype(jnp.int32)


def _ln_kernel(x_ref, g_ref, b_ref, o_ref, *, plan: INormPlan,
               out_lo: int, out_hi: int):
    q = x_ref[...]
    if plan.subtract_mean:
        mu = _apply_dn(jnp.sum(q, axis=-1, keepdims=True), plan.dn_mean)
        y = q - mu
    else:
        y = q
    sh = row_shift(y, plan)
    ys = shift_by(y, sh)
    var = _apply_dn(jnp.sum(ys * ys, axis=-1, keepdims=True), plan.dn_var)
    sigma_s = isqrt_tile(var)
    # an all-equal row (sigma 0) normalizes to 0: r = 0 makes y * r = 0
    r = jnp.where(sigma_s == 0, 0, recip_tile(
        1 << (plan.recip_bits + plan.pre_shift), sigma_s))
    out = shift_by(y * r, plan.pre_shift + sh) \
        * g_ref[...].astype(jnp.int32)[None, :]
    if b_ref is not None:
        out = out + b_ref[...].astype(jnp.int32)[None, :]
    out = _apply_dn(out, plan.dn_out)
    o_ref[...] = jnp.clip(out, out_lo, out_hi)


def int_layernorm_pallas(q, q_gamma, q_beta, plan: INormPlan,
                         out_bits: int = 8, block_rows: Optional[int] = None,
                         interpret: Optional[bool] = None):
    """q: (..., d) int32 at plan.s_in -> int32 clipped to out_bits.

    Rows are zero-padded to a multiple of 8 and blocked ``(br, d)``:
    ``br`` is :func:`norm_block_rows` of the shape, or the largest
    multiple of 8 dividing the padded rows up to ``block_rows`` where
    that is given — chip-legal for any row count (a zero row normalizes
    to ``beta``; padding is sliced off)."""
    shape = q.shape
    d = shape[-1]
    assert d == plan.d, (d, plan.d)
    assert plan.recip_bits + plan.pre_shift <= 30, plan   # recip_tile
    rows = q.size // d
    rows_pad = -(-rows // 8) * 8
    x2 = jnp.pad(q.reshape(rows, d).astype(jnp.int32),
                 ((0, rows_pad - rows), (0, 0)))
    br = norm_block_rows(rows_pad, d) if block_rows is None \
        else fit_block(max(block_rows, 8), rows_pad, 8)
    has_beta = q_beta is not None
    args = [x2, q_gamma] + ([q_beta] if has_beta else [])
    in_specs = [pl.BlockSpec((br, d), lambda i: (i, 0)),
                pl.BlockSpec((d,), lambda i: (0,))]
    if has_beta:
        in_specs.append(pl.BlockSpec((d,), lambda i: (0,)))

    def kernel(*refs):
        if not has_beta:
            refs = refs[:2] + (None,) + refs[2:]
        _ln_kernel(*refs, plan=plan, out_lo=-(1 << (out_bits - 1)),
                   out_hi=(1 << (out_bits - 1)) - 1)

    out = pl.pallas_call(
        kernel,
        grid=(rows_pad // br,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, d), jnp.int32),
        name=trace_names.kernel("int_norm"),
        interpret=resolve_interpret(interpret),
    )(*args)
    return out[:rows].reshape(shape)
