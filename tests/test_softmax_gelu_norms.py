"""Integer softmax / activations / norms vs float oracles."""
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis",
                    reason="property tests need hypothesis "
                           "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core import activations as act
from repro.core import norms
from repro.core import softmax as ism


def test_isoftmax_close_to_float(rng):
    sp = ism.make_isoftmax(s_score=0.01, qmax_score=2**21)
    logits = rng.normal(0, 3, (16, 64)) / 0.01
    q = jnp.asarray(np.round(logits).astype(np.int32))
    p = np.asarray(ism.i_softmax(q, sp)) * ism.S_PROB
    x = logits * 0.01
    ref = np.exp(x - x.max(-1, keepdims=True))
    ref /= ref.sum(-1, keepdims=True)
    assert np.abs(p - ref).max() < 8e-3            # int8 prob granularity
    # int8 prob rows under-sum by the truncated tail mass (paper-faithful)
    assert abs(p.sum(-1).mean() - 1.0) < 0.05


def test_isoftmax_masking(rng):
    sp = ism.make_isoftmax(s_score=0.01, qmax_score=2**21)
    q = jnp.asarray(rng.integers(-1000, 1000, (4, 32)), jnp.int32)
    mask = jnp.asarray(rng.random((4, 32)) > 0.5)
    p = np.asarray(ism.i_softmax(q, sp, where=mask))
    assert (p[~np.asarray(mask)] == 0).all()


@settings(max_examples=50, deadline=None)
@given(st.integers(min_value=2, max_value=256))
def test_isoftmax_rowsum_bounded(rowlen):
    """Rows up to the 256-element int8 representability limit sum to ~1
    (beyond that see test_isoftmax_uniform_row_limitation)."""
    rng = np.random.default_rng(rowlen)
    sp = ism.make_isoftmax(s_score=3.5e-4, qmax_score=128 * 127 * 127)
    q = jnp.asarray(rng.integers(-60000, 60000, (2, rowlen)), jnp.int32)
    p = np.asarray(ism.i_softmax(q, sp)).astype(np.int64)
    s = p.sum(-1) * ism.S_PROB
    assert (np.abs(s - 1.0) < 0.07).all()


def test_isoftmax_uniform_row_limitation():
    """Documented int8 limitation (paper-faithful INT8 probs): a near-
    uniform row longer than ~256 cannot be represented — every probability
    rounds to zero.  Real attention rows are peaked; the e16-domain sums
    used inside the fused attention kernel keep normalisation correct."""
    sp = ism.make_isoftmax(s_score=3.5e-4, qmax_score=128 * 127 * 127)
    q = jnp.zeros((1, 512), jnp.int32)
    p = np.asarray(ism.i_softmax(q, sp))
    assert p.max() == 0


def test_inorm_layernorm(rng):
    d, s_in = 768, 8 / 1024
    plan = norms.make_inorm(d, s_in, 1024, 2 / 127, 8 / 127)
    gamma = rng.normal(1, 0.2, d).astype(np.float32)
    beta = rng.normal(0, 0.2, d).astype(np.float32)
    qg, qb = norms.quantize_norm_weights(jnp.asarray(gamma),
                                         jnp.asarray(beta), plan)
    x = rng.normal(0, 2, (16, d)).astype(np.float32)
    q = np.clip(np.round(x / s_in), -1024, 1024).astype(np.int32)
    xc = q * s_in
    got = np.asarray(norms.i_norm(jnp.asarray(q), qg, qb, plan)) \
        * plan.s_out
    mu = xc.mean(-1, keepdims=True)
    sd = xc.std(-1, keepdims=True)
    ref = (xc - mu) / sd * gamma + beta
    assert np.abs(got - ref).max() < 0.1


def test_inorm_rmsnorm(rng):
    d, s_in = 512, 8 / 1024
    plan = norms.make_inorm(d, s_in, 1024, 2 / 127, 8 / 127,
                            subtract_mean=False)
    gamma = rng.normal(1, 0.2, d).astype(np.float32)
    qg, _ = norms.quantize_norm_weights(jnp.asarray(gamma), None, plan)
    x = rng.normal(0, 2, (8, d)).astype(np.float32)
    q = np.clip(np.round(x / s_in), -1024, 1024).astype(np.int32)
    xc = q * s_in
    got = np.asarray(norms.i_norm(jnp.asarray(q), qg, None, plan)) \
        * plan.s_out
    ref = xc / np.sqrt((xc ** 2).mean(-1, keepdims=True)) * gamma
    assert np.abs(got - ref).max() < 0.1


def _old_rmsnorm(q, plan):
    """The norm as it squared every row after the design-time shift."""
    y = q.astype(np.int64)
    s = plan.pre_shift
    ys = (y + (1 << (s - 1))) >> s
    var = np.asarray(plan.dn_var(jnp.asarray(
        (ys * ys).sum(-1, keepdims=True).astype(np.int32))))
    sigma = np.floor(np.sqrt(var))
    return np.where(sigma == 0, 0, y / (np.maximum(sigma, 1) * (1 << s)))


@pytest.mark.parametrize("std", [12, 51, 400, 3000])
def test_inorm_small_stream(rng, std):
    """A residual stream a few LSB wide under the design-time pre-shift
    (Granite-3-2B's RMSNorm: d 2048, qmax 8192, a 5-bit shift; its
    embedding reaches the first norm at 51 LSB) is normalised to within
    2.5% of its float RMSNorm in norm.  Each row is shifted so its
    largest value is as large as the squared sum allows; squared after
    the fixed shift, the floor of the square root of a few LSB read sigma
    0 at 12 LSB (the row normalised to 0) and 1 for 1.6 at 51 (the old
    arithmetic is checked to miss by more than 30% at both).  2.5%: gamma = 1
    itself rounds to 64 / 63.5 on the int8 grid of 2/127 (0.8%); the
    shift leaves the row's max at 256-512, so its sigma at 64 or more
    (a max of 2048 normal values lies within 4 sigma), whose square
    root's floor reads it low by less than 1/64 (1.6%); the reciprocal
    and the int8 output grid add less than 0.1%."""
    d, s_in, qmax = 2048, 2.0 ** -9, 1 << 13
    plan = norms.make_inorm(d, s_in, qmax, 2 / 127, 8 / 127,
                            subtract_mean=False)
    assert plan.pre_shift == 5
    qg, _ = norms.quantize_norm_weights(jnp.ones(d), None, plan)
    q = np.round(rng.normal(0, std, (8, d))).astype(np.int32)
    ref = q / np.sqrt((q.astype(np.float64) ** 2).mean(-1, keepdims=True))
    got = np.asarray(norms.i_norm(jnp.asarray(q), qg, None, plan)) \
        * plan.s_out
    ratio = np.linalg.norm(got, axis=-1) / np.linalg.norm(ref, axis=-1)
    assert np.abs(ratio - 1).max() < 0.025, ratio
    if std <= 51:
        old = np.linalg.norm(_old_rmsnorm(q, plan), axis=-1) \
            / np.linalg.norm(ref, axis=-1)
        assert np.abs(old - 1).min() > 0.3, old


def test_inorm_constant_row():
    d, s_in = 64, 8 / 1024
    plan = norms.make_inorm(d, s_in, 1024, 2 / 127, 8 / 127)
    qg, qb = norms.quantize_norm_weights(jnp.ones(d), jnp.zeros(d), plan)
    q = jnp.full((2, d), 37, jnp.int32)
    got = np.asarray(norms.i_norm(q, qg, qb, plan))
    assert np.abs(got).max() == 0                   # zero variance -> 0


def test_isilu(rng):
    s = 16 / 1024
    plan = act.make_isilu(s, 1024, s_out=8 / 127)
    x = np.linspace(-8, 8, 2001)
    q = np.round(x / s).astype(np.int32)
    got = np.asarray(act.i_silu(jnp.asarray(q), plan)) * (8 / 127)
    ref = x / (1 + np.exp(-x))
    assert np.abs(got - ref).max() < 6e-2


def test_isoftplus(rng):
    s = 16 / 1024
    plan = act.make_isoftplus(s, 1024, s_out=16 / 2**13)
    x = np.linspace(-10, 10, 2001)
    q = np.round(x / s).astype(np.int32)
    got = np.asarray(act.i_softplus(jnp.asarray(q), plan)) * plan.s_out
    ref = np.log1p(np.exp(x))
    assert np.abs(got - ref).max() < 4e-2


def test_igelu_act(rng):
    s = 16 / 1024
    plan = act.make_igelu_act(s, 1024, s_out=8 / 127)
    import math
    x = np.linspace(-8, 8, 2001)
    q = np.round(x / s).astype(np.int32)
    got = np.asarray(act.i_gelu_act(jnp.asarray(q), plan)) * (8 / 127)
    erf = np.vectorize(math.erf)
    ref = 0.5 * x * (1 + erf(x / np.sqrt(2)))
    assert np.abs(got - ref).max() < 7e-2
