"""Integer LayerNorm / RMSNorm (SwiftTron §III-I, Fig. 15).

Three phases, matching the ASIC pipeline:
  1. mean      — integer sum, dyadic multiply by 1/d
  2. std       — centred squares (each row shifted, by at most the
                 design-time pre-shift either way, so that its largest
                 value is as large as the INT32 accumulator allows),
                 dyadic 1/d, iterative i-sqrt
  3. output    — one reciprocal per row (2^k // sigma), per-channel gamma,
                 folded beta, dyadic requant to the int8 output scale

RMSNorm (llama-family extension, DESIGN.md §4) is phase 2+3 only.
All bit budgets are solved at design time in ``make_inorm``.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax.numpy as jnp

from repro.core import intmath
from repro.core.dyadic import (Dyadic, bits_for, clip_to_bits, fit_dyadic,
                               rshift_round)


class INormPlan(NamedTuple):
    d: int                  # normalised dimension
    s_in: float             # input scale (int32 values, |q| <= qmax_in)
    qmax_in: int
    dn_mean: Dyadic         # 1/d on the sum
    dn_var: Dyadic          # 1/d on the squared sum
    pre_shift: int          # s: the largest row shift (either way)
    recip_bits: int         # k: reciprocal precision (n at scale 2^-k)
    s_gamma: float
    s_out: float
    dn_out: Dyadic          # (2^-k * s_gamma) -> s_out  (applied to n*gamma)
    q_beta_scale: float     # scale at which beta is folded in
    subtract_mean: bool


def make_inorm(d: int, s_in: float, qmax_in: int, s_gamma: float,
               s_out: float, subtract_mean: bool = True) -> INormPlan:
    dn_mean = fit_dyadic(1.0 / d, d * qmax_in)
    # pre-shift so sum((y>>s)^2) fits int32: d * (y_max >> s)^2 < 2^31
    y_max = 2 * qmax_in
    s = 0
    while d * ((y_max >> s) ** 2) > intmath.INT32_MAX:
        s += 1
    dn_var = fit_dyadic(1.0 / d, d * ((y_max >> s) ** 2))
    # reciprocal precision: product y * r must fit int32 with
    # r <= 2^(k + s)  ->  bits(y_max) + k + s <= 31
    k = min(15, 31 - bits_for(y_max) - s)
    if k < 8:
        raise ValueError(f"i-norm reciprocal precision too low (k={k}); "
                         f"reduce qmax_in={qmax_in}")
    # |n| <= sqrt(d) theoretically; size the output requant for that
    nmax = min(math.sqrt(d), 128.0)
    n_q_max = int(nmax * (1 << k))
    dn_out = fit_dyadic((2.0 ** -k) * s_gamma / s_out, n_q_max * 127)
    q_beta_scale = (2.0 ** -k) * s_gamma
    return INormPlan(d, s_in, qmax_in, dn_mean, dn_var, s, k, s_gamma,
                     s_out, dn_out, q_beta_scale, subtract_mean)


def quantize_norm_weights(gamma, beta, plan: INormPlan):
    """Float gamma/beta -> integer-side constants (design time)."""
    q_gamma = jnp.clip(jnp.round(gamma / plan.s_gamma), -127, 127
                       ).astype(jnp.int32)
    if beta is None:
        q_beta = None
    else:
        q_beta = jnp.round(beta / plan.q_beta_scale).astype(jnp.int32)
    return q_gamma, q_beta


def row_shift_limits(plan: INormPlan) -> tuple:
    """Design-time thresholds of the per-row shift before squaring,
    ``(up, down)``.  The shift makes a row's largest value as large as
    it can be while the squared sum stays within the ``d Y^2`` that
    ``dn_var`` was sized for, ``Y = (2 qmax_in) >> pre_shift``, and
    moves by at most ``pre_shift`` bits either way:

      * ``down[t]`` (``t < pre_shift``) is the largest row max
        ``m = max|y|`` with ``rshift_round(m, t) <= Y``: a row past
        ``down[t - 1]`` is shifted right by ``t`` or more;
      * ``up[u - 1]`` (``u = 1 .. pre_shift``) is ``Y >> u``: a row
        within it is shifted left by ``u`` or more, exactly.

    The design-time shift alone squared a small residual stream at a
    few LSB (Granite's embedding, 51 LSB under 5 bits), where the floor
    of the square root read its sigma far low (1 for 1.6)."""
    y_cap = (2 * plan.qmax_in) >> plan.pre_shift
    up = tuple(y_cap >> u for u in range(1, plan.pre_shift + 1))
    down = tuple((y_cap + 1) * (1 << t) - ((1 << t) >> 1) - 1
                 for t in range(plan.pre_shift))
    return up, down


def row_shift(y, plan: INormPlan):
    """Per-row shift (int32, ``y``'s shape with a last axis of 1) of
    :func:`row_shift_limits`: positive right, negative left."""
    up, down = row_shift_limits(plan)
    m = jnp.max(jnp.abs(y), axis=-1, keepdims=True)
    sh = jnp.zeros_like(m)
    for lim in down:
        sh = sh + (m > lim).astype(jnp.int32)
    for lim in up:
        sh = sh - (m <= lim).astype(jnp.int32)
    return sh


def shift_by(x, sh):
    """``x`` shifted by an int32 array ``sh`` (broadcast against ``x``):
    right with round-half-up, ``(x + 2^(sh-1)) >> sh``, where ``sh >
    0``; left, exactly, where ``sh < 0``."""
    right = jnp.maximum(sh, 0)
    down = (x + (jnp.left_shift(jnp.int32(1), right) >> 1)) >> right
    return jnp.where(sh < 0, jnp.left_shift(x, jnp.maximum(-sh, 0)), down)


def i_norm(q, q_gamma, q_beta, plan: INormPlan, out_bits: int = 8):
    """LayerNorm/RMSNorm over the last axis. q: int32 at plan.s_in.

    Returns int32 clipped to the signed ``out_bits`` range, scale plan.s_out.
    """
    q = q.astype(jnp.int32)
    if plan.subtract_mean:
        mu = plan.dn_mean(jnp.sum(q, axis=-1, keepdims=True))
        y = q - mu
    else:
        y = q
    sh = row_shift(y, plan)                      # per row, |sh| <= pre
    ys = shift_by(y, sh)
    var = plan.dn_var(jnp.sum(ys * ys, axis=-1, keepdims=True))
    sigma_s = intmath.i_sqrt(var)               # scale s_in * 2^sh
    # n = y / (sigma_s * 2^sh) at scale 2^-k:
    #   r   = 2^(k+pre) / sigma_s
    #   y*r = n * 2^(k + pre + sh)  ->  shift back by pre + sh >= 0
    # (r's numerator is the design-time one, so |y*r| keeps its bound)
    r = jnp.int32(1 << (plan.recip_bits + plan.pre_shift)) \
        // jnp.maximum(sigma_s, 1)
    n_q = shift_by(y * r, plan.pre_shift + sh)
    n_q = jnp.where(sigma_s == 0, 0, n_q)        # all-equal row -> 0
    out = n_q * q_gamma                          # scale 2^-k * s_gamma
    if q_beta is not None:
        out = out + q_beta
    out = plan.dn_out(out)
    return clip_to_bits(out, out_bits)
