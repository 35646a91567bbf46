"""Pallas TPU kernel: fused integer attention (beyond-paper, DESIGN.md §3).

SwiftTron's Attention unit (Fig. 10) streams Q*K^T -> Softmax -> Requant ->
P*V through separate hardware blocks, writing the O(m^2) INT32 score matrix
between them.  On TPU that materialisation is pure HBM traffic, so we fuse
the whole flow into one VMEM-resident kernel with an **integer online
softmax**:

  * running row max is kept in the exact raw score scale (int32 compare),
  * when the max moves, previous partial sums and the int32 P*V accumulator
    are rescaled by ``exp16(m_old - m_new)`` — an i-exp evaluation plus a
    split 32x16 multiply (all int32-safe),
  * the int8 weights that enter the MXU are the fused kernel's
    (``core.softmax.attn_weights``: e16 rounded to 2^-7 of the running
    max, at most 127), their sum is kept beside the accumulator, and the
    output is normalised once at the end by that sum with the fused
    kernel's exact per-row division (``normalize_tile``).

Only the running rescale (``exp16(m_old - m_new)`` on the sum and the
accumulator) separates it from the bit-exact kernels: it rounds, so the
result is within a few LSB of the oracle.

A nice inversion of the paper's cost model: the ASIC normalises all m
probabilities per row (m divider uses); this kernel normalises the
d-dimensional *output* instead — head_dim << seq_len divider uses per row.

Bit budget: the weight sum stays at most ``L * 127`` and the accumulator
``L * 127 * 127`` (rescales only shrink them), int32-safe for rows up to
2^16 (the wrapper asserts L <= 65536).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import trace_names
from repro.analysis.contracts import check_launch, require_launch
from repro.core.attention import IAttnPlan
from repro.kernels import resolve_interpret
from repro.kernels.int_softmax import (_exp16_tile, _rshift_round,
                                       attn_weights_tile, normalize_tile)

NEG = -(2 ** 30)


def _rescale32(x, corr16):
    """(x * corr16) >> 15 via hi/lo split (x up to 2^30, corr16 <= 2^15)."""
    return (x >> 15) * corr16 + _rshift_round((x & 0x7FFF) * corr16, 15)


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, s_ref, acc_ref, *,
                 plan: IAttnPlan, n_kv: int, bq: int, bkv: int,
                 causal: bool, window: int, out_lo: int, out_hi: int):
    kv_step = pl.program_id(3)
    q_blk = pl.program_id(2)

    @pl.when(kv_step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        s_ref[...] = jnp.zeros_like(s_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q8 = q_ref[0, :, 0, :]                      # (bq, d) int8
    k8 = k_ref[0, :, 0, :]                      # (bkv, d) int8
    v8 = v_ref[0, :, 0, :]

    qi = q_blk * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    ki = kv_step * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    live = jnp.ones((bq, bkv), jnp.bool_)
    if causal:
        live = live & (ki <= qi)
    if window > 0:
        live = live & (ki > qi - window)

    def _update():
        scores = jax.lax.dot_general(
            q8, k8, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)       # (bq, bkv) raw scale
        scores = jnp.where(live, scores, jnp.int32(NEG))
        m_c = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_ref[...], m_c)
        corr16 = _exp16_tile(m_ref[...] - m_new, plan.sm)
        e16 = _exp16_tile(scores - m_new, plan.sm)
        u = jnp.where(live, attn_weights_tile(e16), 0)   # unnormalised
        s_ref[...] = _rescale32(s_ref[...], corr16) \
            + jnp.sum(u, axis=-1, keepdims=True)
        acc_ref[...] = _rescale32(acc_ref[...], corr16) + \
            jax.lax.dot_general(u.astype(jnp.int8), v8,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
        m_ref[...] = m_new

    if causal:
        # skip fully-masked blocks (upper triangle)
        pl.when(kv_step * bkv <= q_blk * bq + bq - 1)(_update)
    else:
        _update()

    @pl.when(kv_step == n_kv - 1)
    def _finalize():
        out7 = normalize_tile(acc_ref[...], s_ref[...])  # s_v * 2^-7
        dn = plan.dn_out
        out = _rshift_round(_rshift_round(out7, dn.pre) * jnp.int32(dn.b),
                            dn.c - dn.pre)
        out = jnp.clip(out, out_lo, out_hi)
        o_ref[0, :, 0, :] = out.astype(jnp.int8)


def int_attention_pallas(q8, k8, v8, plan: IAttnPlan, causal: bool = True,
                         window: int = 0, bq: int = 128, bkv: int = 128,
                         out_bits: int = 8,
                         interpret: Optional[bool] = None):
    """q8: (B, Sq, H, D) int8; k8/v8: (B, Skv, Hkv, D) int8 (GQA: Hkv | H).

    Returns int8 (B, Sq, H, D) at plan.s_out.
    """
    b, sq, h, d = q8.shape
    _, skv, hkv, _ = k8.shape
    require_launch(check_launch(
        "int_attention", b=b, sq=sq, skv=skv, h=h, hkv=hkv, d=d,
        bq=bq, bkv=bkv, out_bits=out_bits, online=True))
    group = h // hkv
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    n_kv = skv // bkv
    kernel = functools.partial(
        _attn_kernel, plan=plan, n_kv=n_kv, bq=bq, bkv=bkv, causal=causal,
        window=window, out_lo=-(1 << (out_bits - 1)),
        out_hi=(1 << (out_bits - 1)) - 1)

    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel,
        grid=(b, h, sq // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, 1, d), lambda bi, hi, qi, ki: (bi, qi, hi, 0)),
            pl.BlockSpec((1, bkv, 1, d),
                         lambda bi, hi, qi, ki: (bi, ki, hi // group, 0)),
            pl.BlockSpec((1, bkv, 1, d),
                         lambda bi, hi, qi, ki: (bi, ki, hi // group, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, d),
                               lambda bi, hi, qi, ki: (bi, qi, hi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sq, h, d), jnp.int8),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.int32),
                        pltpu.VMEM((bq, 1), jnp.int32),
                        pltpu.VMEM((bq, d), jnp.int32)],
        name=trace_names.kernel("int_attention"),
        interpret=resolve_interpret(interpret),
    )(q8, k8, v8)
