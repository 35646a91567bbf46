"""Integer activation functions.

i-GELU is the paper's §III-H unit (see ``intmath.i_gelu``); this module adds
the activation plans and the two extensions required by the assigned
architecture pool (DESIGN.md §4): **i-SiLU** for SwiGLU FFNs,
**i-softplus** for Mamba's Δt and **i-sigmoid** (i-SiLU's sigmoid,
factored out) for sigmoid MoE routers — built from the same primitives
the paper uses (i-exp, one integer division, dyadic requants).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax.numpy as jnp

from repro.core import intmath
from repro.core.dyadic import Dyadic, clip_to_bits, fit_dyadic

SIG_FRAC = 15                     # sigmoid as a 16-bit fraction
RECIP_BITS = 30


class IGeluActPlan(NamedTuple):
    gelu: intmath.IGeluPlan
    dn_out: Dyadic
    s_in: float
    s_out: float


def make_igelu_act(s_in: float, qmax_in: int, s_out: float) -> IGeluActPlan:
    g = intmath.make_igelu(s_in, qmax_in)
    dn_out = fit_dyadic(g.s_out / s_out, qmax_in * (2 * g.q_one))
    return IGeluActPlan(g, dn_out, s_in, s_out)


def i_gelu_act(q, plan: IGeluActPlan, out_bits: int = 8):
    out = intmath.i_gelu(q.astype(jnp.int32), plan.gelu)
    return clip_to_bits(plan.dn_out(out), out_bits)


class ISiluPlan(NamedTuple):
    iexp: intmath.IExpPlan
    dn_e16: Dyadic            # iexp out -> 2^-15 fraction
    s_in: float
    s_out: float              # = s_in * 2^-SIG_FRAC before dn_out
    dn_out: Dyadic
    qmax_in: int


def make_isilu(s_in: float, qmax_in: int, s_out: float) -> ISiluPlan:
    """sigma(x) = e/(1+e), e = i_exp(-|x|); SiLU = x * sigma(x).

    Bit budget: e16, one16 <= 2^15; den <= 2^16; r = 2^30//den <= 2^15;
    num*r <= den*r <= 2^30; q * sig16 needs bits(qmax_in) + 16 <= 31.
    """
    if intmath.bits_for(qmax_in) + SIG_FRAC + 1 > 31:
        raise ValueError(f"i-silu qmax_in too large: {qmax_in}")
    iexp = intmath.make_iexp(s_in)
    dn_e16 = fit_dyadic(iexp.s_out / 2.0 ** -SIG_FRAC, iexp.q_one + 1)
    s_mid = s_in * 2.0 ** -SIG_FRAC
    dn_out = fit_dyadic(s_mid / s_out, qmax_in << SIG_FRAC)
    return ISiluPlan(iexp, dn_e16, s_in, s_mid, dn_out, qmax_in)


def i_sigmoid16(q, iexp: intmath.IExpPlan, dn_e16: Dyadic):
    """sigma(x) as an int32 fraction at 2^-SIG_FRAC, for int32 ``q`` at
    ``iexp.s_in``: sigma = 1/(1+e) for x >= 0 and e/(1+e) below, with
    e = i_exp(-|x|) and one integer division.  |q| may take any int32
    value (i-exp clips far tails to 0)."""
    e = intmath.i_exp(-jnp.abs(q), iexp)
    e16 = jnp.clip(dn_e16(e), 0, 1 << SIG_FRAC)
    one16 = jnp.int32(1 << SIG_FRAC)
    den = one16 + e16
    r = jnp.int32(1 << RECIP_BITS) // den
    num = jnp.where(q >= 0, one16, e16)
    return (num * r) >> (RECIP_BITS - SIG_FRAC)


class ISigmoidPlan(NamedTuple):
    """i-sigmoid of an int32 input at a wide scale: ``dn_in`` brings it
    to the ``iexp.s_in`` grid, where i-exp's constants are exact."""
    dn_in: Dyadic
    iexp: intmath.IExpPlan
    dn_e16: Dyadic
    s_in: float


#: the finest grid an i-sigmoid evaluates on: i-exp's polynomial
#: constant ``(1.353 / s)^2`` stays inside int32 down to about 2.9e-5,
#: and a step of 2^-14 moves sigma by at most 2^-16
S_SIGMOID_MIN = 2.0 ** -14


def make_isigmoid(s_in: float, qmax_in: int) -> ISigmoidPlan:
    """i-sigmoid of int32 ``q`` at ``s_in`` (``|q| <= qmax_in``), as a
    2^-SIG_FRAC fraction (:func:`i_sigmoid`).  An input finer than
    :data:`S_SIGMOID_MIN` is first shifted right by the fewest bits that
    bring it to at least that grid (an exact power of two, so nothing
    but the dropped bits rounds)."""
    p = max(0, math.ceil(math.log2(S_SIGMOID_MIN / s_in)))
    dn_in = fit_dyadic(2.0 ** -p, qmax_in)
    iexp = intmath.make_iexp(s_in * 2.0 ** p)
    dn_e16 = fit_dyadic(iexp.s_out / 2.0 ** -SIG_FRAC, iexp.q_one + 1)
    return ISigmoidPlan(dn_in, iexp, dn_e16, s_in)


def i_sigmoid(q, plan: ISigmoidPlan):
    """sigma(q * s_in) as an int32 fraction at 2^-SIG_FRAC."""
    return i_sigmoid16(plan.dn_in(q.astype(jnp.int32)), plan.iexp,
                       plan.dn_e16)


def i_silu(q, plan: ISiluPlan, out_bits: int = 8):
    q = q.astype(jnp.int32)
    sig16 = i_sigmoid16(q, plan.iexp, plan.dn_e16)     # sigmoid * 2^15
    out = q * sig16                                    # scale s_in * 2^-15
    return clip_to_bits(plan.dn_out(out), out_bits)


class ISoftplusPlan(NamedTuple):
    iexp: intmath.IExpPlan
    dn_e16: Dyadic
    ln1p: intmath.ILn1pPlan    # emits directly at s_out (fine grid)
    s_in: float
    dn_relu: Dyadic            # s_in -> s_out for the max(x,0) branch
    s_out: float


def make_isoftplus(s_in: float, qmax_in: int, s_out: float) -> ISoftplusPlan:
    """softplus(x) = max(x, 0) + ln1p(exp(-|x|)), emitted at ``s_out``.

    Both branches are computed directly on the (typically much finer)
    output grid — Mamba Δt values live in [1e-3, 1], far below the input
    grid's resolution, so computing ln1p at s_in would zero them out."""
    iexp = intmath.make_iexp(s_in)
    dn_e16 = fit_dyadic(iexp.s_out / 2.0 ** -SIG_FRAC, iexp.q_one + 1)
    ln1p = intmath.make_iln1p(2.0 ** -SIG_FRAC, s_out, 1 << SIG_FRAC)
    dn_relu = fit_dyadic(s_in / s_out, qmax_in)
    return ISoftplusPlan(iexp, dn_e16, ln1p, s_in, dn_relu, s_out)


def i_softplus(q, plan: ISoftplusPlan, out_bits: int = 16):
    q = q.astype(jnp.int32)
    e = intmath.i_exp(-jnp.abs(q), plan.iexp)
    e16 = jnp.clip(plan.dn_e16(e), 0, 1 << SIG_FRAC)
    lq = intmath.i_ln1p(e16, plan.ln1p)                # scale s_out
    out = plan.dn_relu(jnp.maximum(q, 0)) + lq
    return clip_to_bits(out, out_bits)
