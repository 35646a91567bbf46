"""Pallas TPU kernel: integer-only GELU (SwiftTron §III-H, Fig. 14).

Pure VPU elementwise tile: i-erf second-order polynomial with sign
handling, the x*(erf+1) product, and the output dyadic requant — all int32
adds/multiplies/shifts, constants baked at design time (q5..q8 in the
paper's Fig. 14).

The launch runs on the input's own layout where its last dim is a multiple
of 128 — ``(prod(lead), n)``, a merge of leading dims that costs no copy —
in row blocks taken from the shape (:func:`gelu_layout`), and writes int8
when ``out_bits <= 8``: the layout the up projection writes and the down
projection reads.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import trace_names
from repro.core.dyadic import Dyadic
from repro.core.intmath import IGeluPlan
from repro.kernels import resolve_interpret
from repro.kernels.int_layernorm import BLOCK_BYTES, norm_block_rows


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


#: lane width of the flattened layout (a multiple of 128), for inputs
#: whose last dim is not a multiple of 128
LANES = 512


def gelu_layout(shape) -> tuple[int, int, int, bool]:
    """``(rows, lanes, br, natural)``: the 2-D int32 view a launch over
    ``shape`` runs on, and its rows per grid step.

    ``natural``: the view is ``(prod(lead), n)``, taken where the last
    dim ``n`` is a multiple of 128 and the rows are a multiple of 8 or
    fit one block.  Otherwise the flattened input is zero-padded to
    whole ``(8, lanes)`` tiles.  ``br`` follows ``int_norm``'s rule
    (:func:`norm_block_rows`): the largest multiple of 8 that divides
    the rows and keeps the int32 block within ``BLOCK_BYTES``, or all
    the rows if they fit."""
    size = math.prod(shape)
    n = shape[-1] if shape else 1
    rows = size // n
    if n % 128 == 0 and (rows % 8 == 0 or 4 * size <= BLOCK_BYTES):
        return rows, n, norm_block_rows(rows, n), True
    lanes = LANES if size >= 8 * LANES else 128
    rows = -(-size // (8 * lanes)) * 8
    return rows, lanes, norm_block_rows(rows, lanes), False


def int_gelu_pallas(q, plan: IGeluPlan, dn_out: Dyadic, out_bits: int = 8,
                    interpret: Optional[bool] = None):
    """q: int32 (...,) any shape; returns ``q``'s shape clipped to
    out_bits, int8 where ``out_bits <= 8`` and int32 otherwise.

    One launch over :func:`gelu_layout`'s ``(br, lanes)`` row blocks;
    padding of the flattened layout is sliced off the result."""
    shape = q.shape
    rows, lanes, br, natural = gelu_layout(shape)
    if natural:
        x2 = q.reshape(rows, lanes)
    else:
        x2 = jnp.pad(q.reshape(-1), (0, rows * lanes - q.size)
                     ).reshape(rows, lanes)
    out_dtype = jnp.int8 if out_bits <= 8 else jnp.int32
    kernel = functools.partial(
        _gelu_kernel, plan=plan, dn_out=dn_out,
        out_lo=-(1 << (out_bits - 1)), out_hi=(1 << (out_bits - 1)) - 1)
    out = pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, lanes), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), out_dtype),
        name=trace_names.kernel("int_gelu"),
        interpret=resolve_interpret(interpret),
    )(x2)
    if natural:
        return out.reshape(shape)
    return out.reshape(-1)[:q.size].reshape(shape)
