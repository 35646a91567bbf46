"""Integer attention (SwiftTron §III-D/E, Figs. 8-10).

The ASIC streams Q*K^T -> Scale -> Softmax -> Requant -> P*V through
dedicated blocks.  Here the same integer flow is expressed over MXU-shaped
einsums:

  * scores  = int8 Q x int8 K -> int32 (MXU, accumulate int32)
  * scale   = the score scale (1/sqrt(head_dim), or the architecture's
              ``attention_multiplier``) folded into the softmax input
              dyadic (the paper folds its /d scale into a shift when
              d = 2^k — same idea, one constant, §III-E)
  * weights = i-exp of (score - row max) as int8 weights at 2^-7 of the
              row max (``core.softmax.attn_weights``), and their int32 sum
  * out     = int8 weights x int8 V -> int32, divided once per row by the
              weight sum (``core.softmax.normalize_rows``) to the
              normalised accumulator at ``2^-7 * s_v``, then requantized
              to the output scale.

Departure from the paper's dataflow, which normalises every probability
to int8 *before* P*V: over L comparable keys a probability is 128/L
steps of 2^-7, so past 256 keys the whole row rounds to zero and the
output collapses.  Normalising after P*V keeps every weight at 7 bits of
its ratio to the row max whatever L is, and costs one division per
output element instead of one per probability.

Bit budget: the weight sum is at most ``L * 127`` and the accumulator at
most ``L * 127 * 127``, int32 up to ``MAX_PV_KEYS = 2^17`` keys in one
pass.  Longer rows stream in chunks and the running (accumulator, sum)
pair is halved whenever its sum could leave ``STREAM_SUM_BUDGET``
(:func:`fold_pv`), as the running sums of a two-pass softmax are rescaled
between chunks.

Variants:
  * ``i_attention_full``     — materialises the score matrix (tests, decode)
  * ``i_attention_chunked``  — two-pass streaming over KV chunks: pass 1
    the exact running max, pass 2 the weights against the global max,
    their sum and P*V; O(chunk) memory, used for 32k prefill.  Identical
    to the full form up to ``MAX_PV_KEYS`` keys.
  * ``i_attention_decode``   — one query row against an int8 KV cache.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.analysis.budgets import MAX_PV_KEYS, STREAM_SUM_BUDGET
from repro.core import softmax as ism
from repro.core.dyadic import Dyadic, clip_to_bits, fit_dyadic
from repro.core.softmax import ISoftmaxPlan, make_isoftmax

NEG = -(2 ** 30)         # masked score: below every live one


class IAttnPlan(NamedTuple):
    head_dim: int
    sm: ISoftmaxPlan
    dn_out: Dyadic          # (2^-7 * s_v) -> s_out
    s_q: float
    s_k: float
    s_v: float
    s_out: float


def make_iattention(head_dim: int, s_q: float, s_k: float, s_v: float,
                    s_out: float, score_scale: float = None) -> IAttnPlan:
    """``score_scale``: the real factor on Q·Kᵀ before the softmax —
    ``None`` is 1/sqrt(head_dim); an architecture that sets its own
    (Granite's ``attention_multiplier``) passes it, and it folds into
    the softmax input dyadic like the default does."""
    s_score = s_q * s_k / math.sqrt(head_dim) if score_scale is None \
        else s_q * s_k * score_scale
    qmax_score = head_dim * 127 * 127
    sm = make_isoftmax(s_score, qmax_score)
    # the epilogue takes the normalised accumulator (normalize_rows):
    # at scale 2^-7 * s_v, |acc| <= 128 * 127; fitted at twice that, the
    # constant every plan was built with before the division moved
    dn_out = fit_dyadic(ism.S_PROB * s_v / s_out, 127 * (1 << 7) * 2)
    return IAttnPlan(head_dim, sm, dn_out, s_q, s_k, s_v, s_out)


def _scores(q8, k8):
    """int8 (B,Sq,H,D) x int8 (B,Sk,H,D) -> int32 (B,H,Sq,Sk)."""
    return jnp.einsum("bqhd,bkhd->bhqk", q8, k8,
                      preferred_element_type=jnp.int32)


def _weights(scores, row_max, plan: IAttnPlan, mask=None):
    """int8 attention weights of int32 ``scores`` against ``row_max``
    (masked keys weigh 0)."""
    q = scores if mask is None else jnp.where(mask, scores, NEG)
    u = ism.attn_weights(ism._exp16(q - row_max, plan.sm))
    if mask is not None:
        u = jnp.where(mask, u, 0)
    return u


def _pv(u, v8):
    """int32 weights (B,H,Sq,Sk) x int8 V (B,Sk,H,D) -> (acc (B,Sq,H,D),
    weight sum (B,Sq,H,1))."""
    acc = jnp.einsum("bhqk,bkhd->bqhd", u.astype(jnp.int8), v8,
                     preferred_element_type=jnp.int32)
    s = jnp.sum(u, axis=-1).transpose(0, 2, 1)[..., None]
    return acc, s


def _rshift_round_var(x, sh):
    """Round-half-up right shift by a per-row int32 ``sh >= 0``."""
    return (x + ((jnp.int32(1) << sh) >> 1)) >> sh


def fold_pv(run, part, keys: int):
    """Add one chunk's ``part = (acc, s)`` to the running ``run = (acc,
    s, level)`` of a row longer than ``MAX_PV_KEYS`` keys.

    The running pair holds the true sums divided by ``2^level``.  When
    its sum could pass ``STREAM_SUM_BUDGET`` with the chunk's ``keys *
    127`` more (``keys <= 2^15``), the pair is halved first (round half
    up) and the level rises; the chunk enters at the running level.  The
    ratio ``acc / s`` that the row's division reads moves by rounding
    only: nothing rounds before the first halving, and after it the sum
    stays above a quarter of the budget."""
    acc, s, lvl = run
    acc_c, s_c = part
    up = (s > STREAM_SUM_BUDGET - keys * ism.U_MAX).astype(jnp.int32)
    acc, s, lvl = (_rshift_round_var(acc, up), _rshift_round_var(s, up),
                   lvl + up)
    return (acc + _rshift_round_var(acc_c, lvl),
            s + _rshift_round_var(s_c, lvl), lvl)


def _add_pv(run, part, keys: int, streamed: bool):
    """One chunk into the row's running pair: a plain int32 sum up to
    ``MAX_PV_KEYS`` keys a row (``streamed`` false), :func:`fold_pv`
    past it."""
    if streamed:
        assert keys * ism.U_MAX <= STREAM_SUM_BUDGET // 2, keys
        return fold_pv(run, part, keys)
    return run[0] + part[0], run[1] + part[1], run[2]


def i_attention_full(q8, k8, v8, plan: IAttnPlan, mask=None,
                     out_bits: int = 8):
    """mask: bool (B,H,Sq,Sk) or broadcastable; True = attend."""
    out = i_attention_acc(q8, k8, v8, plan, mask=mask)
    return clip_to_bits(plan.dn_out(out), out_bits)


def i_attention_acc(q8, k8, v8, plan: IAttnPlan, mask=None):
    """Full-matrix attention stopping at the normalised P·V accumulator
    (scale ``2^-7 * s_v``) — the input of the requant epilogue; what a
    ``RequantSpec.raw()`` attention returns."""
    scores = _scores(q8, k8)
    q = scores if mask is None else jnp.where(mask, scores, NEG)
    u = _weights(scores, jnp.max(q, axis=-1, keepdims=True), plan, mask)
    sk = scores.shape[-1]
    if sk <= MAX_PV_KEYS:
        acc, s = _pv(u, v8)
        return ism.normalize_rows(acc, s)
    # past the one-pass budget: the streaming fold over key blocks
    blk = MAX_PV_KEYS // 4
    acc, s = _pv(u[..., :blk], v8[:, :blk])
    run = (acc, s, jnp.zeros_like(s))
    for k0 in range(blk, sk, blk):
        run = fold_pv(run, _pv(u[..., k0:k0 + blk], v8[:, k0:k0 + blk]),
                      blk)
    return ism.normalize_rows(run[0], run[1])


def causal_mask(sq: int, sk: int, q_offset: int = 0, window: int = 0):
    """(Sq, Sk) bool; ``window`` > 0 adds sliding-window banding."""
    qi = jnp.arange(sq)[:, None] + q_offset
    ki = jnp.arange(sk)[None, :]
    m = ki <= qi
    if window > 0:
        m = m & (ki > qi - window)
    return m


def i_attention_chunked(q8, k8, v8, plan: IAttnPlan, chunk: int,
                        causal: bool = True, window: int = 0,
                        out_bits: int = 8):
    """Two-pass streaming attention over KV chunks (int8 in/out).

    Pass 1 scans KV chunks for each row's exact max (int32 compare).
    Pass 2 recomputes each chunk's weights against that max and adds
    their sum and their P·V product (MXU) to the row's running pair —
    exactly up to ``MAX_PV_KEYS`` keys, through :func:`fold_pv` past
    them.  One division per row at the end.
    """
    b, sq, h, d = q8.shape
    sk = k8.shape[1]
    assert sk % chunk == 0, (sk, chunk)
    n_chunks = sk // chunk
    k8c = k8.reshape(b, n_chunks, chunk, h, d).transpose(1, 0, 2, 3, 4)
    v8c = v8.reshape(b, n_chunks, chunk, h, d).transpose(1, 0, 2, 3, 4)

    def chunk_mask_dyn(ci):
        if not causal and window <= 0:
            return None
        qi = jnp.arange(sq)[:, None]
        ki = jnp.arange(chunk)[None, :] + ci * chunk
        m = ki <= qi
        if window > 0:
            m = m & (ki > qi - window)
        return m[None, None]

    def pass1(m_run, xs):
        ci, kc = xs
        scores = _scores(q8, kc)
        mask = chunk_mask_dyn(ci)
        q = scores if mask is None else jnp.where(mask, scores, NEG)
        return jnp.maximum(m_run, jnp.max(q, axis=-1, keepdims=True)), None

    m0 = jnp.full((b, h, sq, 1), NEG, jnp.int32)
    g_max, _ = jax.lax.scan(pass1, m0, (jnp.arange(n_chunks), k8c))

    def pass2(run, xs):
        ci, kc, vc = xs
        u = _weights(_scores(q8, kc), g_max, plan, chunk_mask_dyn(ci))
        return _add_pv(run, _pv(u, vc), chunk, sk > MAX_PV_KEYS), None

    zero = jnp.zeros((b, sq, h, 1), jnp.int32)
    run0 = (jnp.zeros((b, sq, h, d), jnp.int32), zero, zero)
    (acc, s, _), _ = jax.lax.scan(pass2, run0,
                                  (jnp.arange(n_chunks), k8c, v8c))
    return clip_to_bits(plan.dn_out(ism.normalize_rows(acc, s)), out_bits)


def i_attention_decode(q8, k8_cache, v8_cache, plan: IAttnPlan,
                       valid_len, out_bits: int = 8):
    """One new token per sequence against an int8 KV cache.

    q8: (B, 1, H, D); caches: (B, L, Hkv, D) already head-repeated or
    grouped by the caller; valid_len: (B,) int32 number of live positions.
    """
    pos = jnp.arange(k8_cache.shape[1])[None, None, None, :]
    mask = pos < valid_len[:, None, None, None]
    return i_attention_full(q8, k8_cache, v8_cache, plan, mask=mask,
                            out_bits=out_bits)
