"""Integer-only softmax (SwiftTron §III-F, Figs. 11-12).

Pipeline per row (the ASIC's three phases):
  1. maximum search                         -> comparator tree
  2. i-exp of (x - max)                     -> polynomial + shift (intmath)
  3. output generation: e_i / sum(e)        -> the one integer divider

The divider is realised as one reciprocal per row (r = 2^30 // sum) followed
by multiplies — the paper's "most complex operator is the divider" appears
exactly once per row.

Scale plan (all frozen at design time):
  * the max is subtracted in the RAW score scale (exact integer subtract),
    then the non-positive difference is clipped to the i-exp band
    (-z_max*ln2, 0] and requantized to the shared ``S_SM = 2^-14`` — the
    clip bounds the requant input range so the dyadic keeps full precision,
  * exp values are requantized to ``2^-15`` so a row sum of up to 2^15
    elements fits int32,
  * probabilities leave as int8 at scale ``2^-7`` (ready for the P*V INT8
    matmul, Fig. 10's Requantization block).

Attention does not take ``i_softmax``'s probabilities (the MoE gate
does).  A probability rounded to 2^-7
before P·V is ``128 / L`` steps over ``L`` comparable keys: a 25% error
per weight at 64 keys, and a row of zeros past 256.  Attention instead
weights V by the exponentials themselves, rounded to 2^-7 *of the row
max* (:func:`attn_weights`, at most 127, never all zero), sums those
weights, and divides once per row after P·V (:func:`normalize_rows`) —
see ``core.attention``.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax.numpy as jnp

# the row-length budget lives with the other bit budgets in the analysis
# package (single source of truth); re-exported here for compatibility
from repro.analysis.budgets import MAX_ROWSUM_LEN  # noqa: F401
from repro.core import intmath
from repro.core.dyadic import Dyadic, fit_dyadic, rshift_round

S_SM = 2.0 ** -14        # shared i-exp input scale
S_EXP16 = 2.0 ** -15     # exp values as 16-bit fractions
S_PROB = 2.0 ** -7       # int8 probability scale
PROB_SHIFT = 7
RECIP_BITS = 30
Z_MAX = 30               # exp(-z_max*ln2) == 2^-30 ~ 0
U_SHIFT = 8              # e16 (2^-15) -> attention weight at 2^-7 of the max
U_MAX = 127              # int8 attention weight ceiling


class ISoftmaxPlan(NamedTuple):
    dn_in: Dyadic                 # (score - max) scale -> S_SM
    iexp: intmath.IExpPlan
    dn_e16: Dyadic                # iexp out -> S_EXP16
    s_in: float
    q_band: int                   # clip: q - max >= -q_band (raw units)

    @property
    def s_out(self) -> float:
        return S_PROB


def make_isoftmax(s_score: float, qmax_score: int) -> ISoftmaxPlan:
    """``s_score``: scale of the int32 attention scores; ``qmax_score``:
    design-time bound on |q_score| (used only for the exact max-subtract,
    which needs headroom: 2*qmax_score must fit int32)."""
    if 2 * qmax_score > intmath.INT32_MAX:
        raise ValueError(f"score range too wide: {qmax_score}")
    q_band = int(math.ceil(Z_MAX * intmath.LN2 / s_score))
    dn_in = fit_dyadic(s_score / S_SM, q_band)
    iexp = intmath.make_iexp(S_SM, z_max=Z_MAX)
    dn_e16 = fit_dyadic(iexp.s_out / S_EXP16, iexp.q_one + 1)
    return ISoftmaxPlan(dn_in, iexp, dn_e16, s_score, q_band)


def _exp16(q_sub, plan: ISoftmaxPlan):
    """(q - rowmax) in raw scale (<= 0) -> exp as 2^-15 fraction."""
    q_sub = jnp.maximum(q_sub, jnp.int32(-plan.q_band))
    q_sm = plan.dn_in(q_sub)                            # -> S_SM
    e = intmath.i_exp(q_sm, plan.iexp)
    return plan.dn_e16(e)                               # scale 2^-15


def i_softmax(q_scores, plan: ISoftmaxPlan, axis: int = -1, where=None):
    """int32 scores -> int8 probabilities (scale 2^-7) along ``axis``.

    ``where``: optional boolean mask (True = attend). Masked positions get
    probability 0 and are excluded from max/sum — the integer analogue of
    additive -inf masking.
    """
    q = q_scores.astype(jnp.int32)
    neg = jnp.int32(-(2 ** 30))
    if where is not None:
        q = jnp.where(where, q, neg)
    q_max = jnp.max(q, axis=axis, keepdims=True)
    e16 = _exp16(q - q_max, plan)
    if where is not None:
        e16 = jnp.where(where, e16, 0)
    s = jnp.sum(e16, axis=axis, keepdims=True)          # <= rowlen * 2^15
    r = jnp.int32(1 << RECIP_BITS) // jnp.maximum(s, 1)
    p = rshift_round(e16 * r, RECIP_BITS - PROB_SHIFT)  # prob * 2^7
    return jnp.clip(p, 0, 127).astype(jnp.int8)


def rescale_sum(s, corr16):
    """(s * corr16) >> 15 via a hi/lo split so the int32 product never
    overflows even for s up to 2^30 (split 32x16 multiply, as the ASIC's
    wide product register would)."""
    s_hi = s >> 15
    s_lo = s & 0x7FFF
    return s_hi * corr16 + rshift_round(s_lo * corr16, 15)


def attn_weights(e16):
    """Unnormalised int8 attention weights: ``e16`` (exp of score minus
    the row max, at 2^-15) rounded half up to 2^-7 of the row max.  e16
    peaks at 32755 (``i_exp(0)``), which rounds to 128, so the weight is
    capped at 127; the row max itself always weighs 127, so a live row's
    weights never all vanish.  int32 in, int32 values in [0, 127] out."""
    return jnp.minimum(rshift_round(e16, U_SHIFT), U_MAX)


def normalize_rows(acc, s):
    """The row's one division, after P·V: ``round_half_up(acc * 2^7 /
    s)``, exactly, in int32.

    ``acc`` (..., D): the P·V accumulator ``sum_k u_k * v_k`` over int8
    weights ``u`` (:func:`attn_weights`); ``s`` (..., 1): ``sum_k u_k``.
    The result is the normalised accumulator at scale ``2^-7 * s_v`` —
    what every attention requant epilogue takes (``|out| <= 128 * |v|``
    max, as ``|acc| <= s * |v|`` max).  Floor division into a whole part
    and a remainder ``0 <= rem < s`` keeps ``128 * rem + s // 2`` int32
    while ``s <= PV_SUM_BUDGET``.  A fully masked row (``s = 0``,
    ``acc = 0``) gives 0."""
    s = jnp.maximum(s, 1)
    whole = jnp.floor_divide(acc, s)
    rem = acc - whole * s
    frac = jnp.floor_divide((rem << PROB_SHIFT) + (s >> 1), s)
    return (whole << PROB_SHIFT) + frac
