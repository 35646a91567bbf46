"""Pallas TPU kernel: integer LayerNorm / RMSNorm (SwiftTron §III-I).

One (block_rows, d) VMEM tile per grid step runs the ASIC's three phases —
integer mean (dyadic 1/d), variance with the design-time pre-shift, the
iterative integer square root (fixed 16 Newton steps, see
core.intmath.i_sqrt for why the early-exit became a fixed trip count), and
the reciprocal + per-channel gamma/beta output phase.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import trace_names
from repro.analysis.contracts import fit_block
from repro.core.norms import INormPlan
from repro.kernels import resolve_interpret


def _rshift_round(x, s: int):
    if s == 0:
        return x
    return (x + (1 << (s - 1))) >> s


def _apply_dn(x, dn):
    return _rshift_round(_rshift_round(x, dn.pre) * jnp.int32(dn.b),
                         dn.c - dn.pre)


def _i_sqrt_tile(n, iters: int = 16):
    """In-kernel integer sqrt (mirror of core.intmath.i_sqrt)."""
    b = jnp.zeros_like(n)
    v = n
    for s in (16, 8, 4, 2, 1):
        t = v >> s
        go = t > 0
        b = jnp.where(go, b + s, b)
        v = jnp.where(go, t, v)
    bl = b + (v > 0).astype(n.dtype)
    x = jnp.maximum(jnp.left_shift(jnp.int32(1), (bl + 1) >> 1), 1)
    for _ in range(iters):
        nx = (x + n // x) >> 1
        x = jnp.minimum(x, jnp.maximum(nx, 1))
    x = jnp.minimum(x, 46340)
    for _ in range(2):
        x = jnp.where(x * x > n, x - 1, x)
    x = jnp.where((x < 46340) & ((x + 1) * (x + 1) <= n), x + 1, x)
    return jnp.where(n <= 0, 0, x)


def _ln_kernel(x_ref, g_ref, b_ref, o_ref, *, plan: INormPlan,
               has_beta: bool, out_lo: int, out_hi: int):
    q = x_ref[...].astype(jnp.int32)
    if plan.subtract_mean:
        mu = _apply_dn(jnp.sum(q, axis=-1, keepdims=True), plan.dn_mean)
        y = q - mu
    else:
        y = q
    ys = _rshift_round(y, plan.pre_shift)
    var = _apply_dn(jnp.sum(ys * ys, axis=-1, keepdims=True), plan.dn_var)
    sigma_s = _i_sqrt_tile(var)
    r = jnp.int32(1 << (plan.recip_bits + plan.pre_shift)) \
        // jnp.maximum(sigma_s, 1)
    n_q = _rshift_round(y * r, 2 * plan.pre_shift)
    n_q = jnp.where(sigma_s == 0, 0, n_q)
    out = n_q * g_ref[...].astype(jnp.int32)[None, :]
    if has_beta:
        out = out + b_ref[...].astype(jnp.int32)[None, :]
    out = _apply_dn(out, plan.dn_out)
    o_ref[...] = jnp.clip(out, out_lo, out_hi).astype(jnp.int32)


def int_layernorm_pallas(q, q_gamma, q_beta, plan: INormPlan,
                         out_bits: int = 8, block_rows: int = 8,
                         interpret: Optional[bool] = None):
    """q: (..., d) int32 at plan.s_in -> int32 clipped to out_bits.

    Rows are zero-padded to a multiple of 8 and blocked ``(br, d)`` with
    ``br`` a multiple of 8 — chip-legal for any row count (a zero row
    normalizes to ``beta``; padding is sliced off)."""
    shape = q.shape
    d = shape[-1]
    assert d == plan.d, (d, plan.d)
    rows = q.size // d
    rows_pad = -(-rows // 8) * 8
    x2 = jnp.pad(q.reshape(rows, d), ((0, rows_pad - rows), (0, 0)))
    br = fit_block(max(block_rows, 8), rows_pad, 8)
    has_beta = q_beta is not None
    args = [x2, q_gamma] + ([q_beta] if has_beta else [])
    in_specs = [pl.BlockSpec((br, d), lambda i: (i, 0)),
                pl.BlockSpec((d,), lambda i: (0,))]
    if has_beta:
        in_specs.append(pl.BlockSpec((d,), lambda i: (0,)))

    def kernel(*refs):
        if has_beta:
            x_ref, g_ref, b_ref, o_ref = refs
        else:
            (x_ref, g_ref, o_ref), b_ref = refs, None
        _ln_kernel(x_ref, g_ref, b_ref, o_ref, plan=plan, has_beta=has_beta,
                   out_lo=-(1 << (out_bits - 1)),
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
