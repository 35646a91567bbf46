"""Pallas TPU kernel: integer-only GELU (SwiftTron §III-H, Fig. 14).

Pure VPU elementwise tile: i-erf second-order polynomial with sign
handling, the x*(erf+1) product, and the output dyadic requant — all int32
adds/multiplies/shifts, constants baked at design time (q5..q8 in the
paper's Fig. 14).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.analysis.contracts import fit_block
from repro.core.dyadic import Dyadic
from repro.core.intmath import IGeluPlan
from repro.kernels import resolve_interpret


def _rshift_round(x, s: int):
    if s == 0:
        return x
    return (x + (1 << (s - 1))) >> s


def _gelu_kernel(x_ref, o_ref, *, plan: IGeluPlan, dn_out: Dyadic,
                 out_lo: int, out_hi: int):
    q = x_ref[...].astype(jnp.int32)
    erf = plan.erf
    sgn = jnp.sign(q).astype(jnp.int32)
    q_abs = jnp.minimum(jnp.abs(q), jnp.int32(erf.q_clip))
    t = q_abs + jnp.int32(erf.q_bneg)
    bracket = t * t + jnp.int32(erf.q_c)
    q_erf = sgn * (-bracket)
    out = q * (q_erf + jnp.int32(plan.q_one))
    out = _rshift_round(_rshift_round(out, dn_out.pre) * jnp.int32(dn_out.b),
                        dn_out.c - dn_out.pre)
    o_ref[...] = jnp.clip(out, out_lo, out_hi).astype(o_ref.dtype)


#: lane width of the flattened elementwise layout (a multiple of 128)
LANES = 512


def int_gelu_pallas(q, plan: IGeluPlan, dn_out: Dyadic, out_bits: int = 8,
                    block: int = 4096, interpret: Optional[bool] = None):
    """q: int32 (...,) any shape; returns int32 clipped to out_bits.

    The flattened input is zero-padded to whole ``(8, LANES)`` tiles and
    viewed as ``(rows, LANES)``; each grid step takes ``(br, LANES)``
    rows with ``br`` a multiple of 8 (about ``block`` elements) — the
    chip-legal layout for an elementwise kernel.  Padding is sliced off
    the result, so any shape works."""
    shape = q.shape
    n = q.size
    lanes = LANES if n >= 8 * LANES else 128    # small: one narrow tile
    tile = 8 * lanes
    n_pad = -(-n // tile) * tile
    rows = n_pad // lanes
    x2 = jnp.pad(q.reshape(-1), (0, n_pad - n)).reshape(rows, lanes)
    br = fit_block(max(block // lanes, 8), rows, 8)
    kernel = functools.partial(
        _gelu_kernel, plan=plan, dn_out=dn_out,
        out_lo=-(1 << (out_bits - 1)), out_hi=(1 << (out_bits - 1)) - 1)
    out = pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, lanes), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(x2)
    return out.reshape(-1)[:n].reshape(shape)
