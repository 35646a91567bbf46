"""Integer attention composition (paper Figs. 8-10)."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import attention as iattn


def _rand_qkv(rng, b, s, h, d, hkv=None):
    hkv = hkv or h
    q = rng.integers(-127, 128, (b, s, h, d)).astype(np.int8)
    k = rng.integers(-127, 128, (b, s, hkv, d)).astype(np.int8)
    v = rng.integers(-127, 128, (b, s, hkv, d)).astype(np.int8)
    return q, k, v


def _float_oracle(q8, k8, v8, plan, causal=True, window=0):
    d = q8.shape[-1]
    h, hkv = q8.shape[2], k8.shape[2]
    rep = h // hkv
    kf = np.repeat(k8, rep, 2) * plan.s_k
    vf = np.repeat(v8, rep, 2) * plan.s_v
    qf = q8 * plan.s_q
    sc = np.einsum("bqhd,bkhd->bhqk", qf, kf) / math.sqrt(d)
    s = q8.shape[1]
    mask = np.tril(np.ones((s, s), bool))
    if window:
        mask &= ~np.tril(np.ones((s, s), bool), -window)
    if causal or window:
        sc = np.where(mask, sc, -1e9)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, vf)


def test_full_attention_vs_float(rng):
    b, s, h, d = 2, 128, 4, 64
    plan = iattn.make_iattention(d, 8/127, 8/127, 4/127, 4/127)
    q8, k8, v8 = _rand_qkv(rng, b, s, h, d)
    mask = iattn.causal_mask(s, s)[None, None]
    got = np.asarray(iattn.i_attention_full(
        jnp.asarray(q8), jnp.asarray(k8), jnp.asarray(v8), plan,
        mask=mask)) * plan.s_out
    ref = _float_oracle(q8, k8, v8, plan)
    assert np.abs(got - ref).max() < 0.12           # ~3 int8 LSB


@pytest.mark.parametrize("window", [0, 48])
def test_chunked_matches_full(rng, window):
    b, s, h, d = 2, 192, 2, 32
    plan = iattn.make_iattention(d, 8/127, 8/127, 4/127, 4/127)
    q8, k8, v8 = _rand_qkv(rng, b, s, h, d)
    mask = iattn.causal_mask(s, s, window=window)[None, None]
    full = np.asarray(iattn.i_attention_full(
        jnp.asarray(q8), jnp.asarray(k8), jnp.asarray(v8), plan,
        mask=mask))
    chk = np.asarray(iattn.i_attention_chunked(
        jnp.asarray(q8), jnp.asarray(k8), jnp.asarray(v8), plan,
        chunk=64, causal=True, window=window))
    # the weights' sum and P·V add the same integers in another order
    assert np.array_equal(chk, full)


def test_decode_matches_full_last_row(rng):
    b, s, h, d = 2, 64, 2, 32
    plan = iattn.make_iattention(d, 8/127, 8/127, 4/127, 4/127)
    q8, k8, v8 = _rand_qkv(rng, b, s, h, d)
    mask = iattn.causal_mask(s, s)[None, None]
    full = np.asarray(iattn.i_attention_full(
        jnp.asarray(q8), jnp.asarray(k8), jnp.asarray(v8), plan,
        mask=mask))
    dec = np.asarray(iattn.i_attention_decode(
        jnp.asarray(q8[:, -1:]), jnp.asarray(k8), jnp.asarray(v8), plan,
        valid_len=jnp.full((b,), s, jnp.int32)))
    assert np.array_equal(dec[:, 0], full[:, -1])
