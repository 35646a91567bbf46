"""Attention normalised after P·V (core.softmax.attn_weights /
normalize_rows, core.attention): the fault it fixes, its exact division,
its bit-exactness across the ref and pallas_fused paths where scores are
flat, and its streaming past MAX_PV_KEYS."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.budgets import MAX_PV_KEYS, STREAM_SUM_BUDGET
from repro.configs.registry import get_config
from repro.core import attention as iattn
from repro.core import softmax as ism
from repro.core.dyadic import fit_dyadic
from repro.kernels import ref as kref
from repro.kernels.int_attention_fused import int_paged_prefill_fused
from repro.kernels.int_softmax import normalize_tile
from repro.ops import RequantSpec, get_backend
from repro.ops.paged import scatter_chunk

FUSED = get_backend("pallas_fused")
REF = get_backend("ref")

#: Granite 3.0's score scale, 1/64 in place of 1/sqrt(64): flat rows
GRANITE = dict(score_scale=1 / 64)


def _plan(d, **kw):
    return iattn.make_iattention(d, 8 / 127, 8 / 127, 8 / 127, 8 / 127,
                                 **kw)


def _i8(rng, shape, sd):
    return jnp.asarray(np.clip(np.round(rng.normal(0, sd, shape)), -127,
                               127), jnp.int8)


# ------------------------------------------------------ the fault ------

def _flat_rows(rng, keys=512, rows=16, d=64):
    """Rows whose scores lie within a band where every key weighs at
    least half the row max: a constant query against keys drawn from a
    narrow range."""
    q8 = jnp.full((1, rows, 1, d), 8, jnp.int8)
    k8 = jnp.asarray(rng.integers(-40, 41, (1, keys, 1, d)), jnp.int8)
    v8 = jnp.asarray(rng.integers(-127, 128, (1, keys, 1, d)), jnp.int8)
    return q8, k8, v8


def _float_pv(q8, k8, v8, plan):
    """Softmax of the exact integer scores at the plan's score scale, in
    float64, times V in integer units: the float twin of P·V."""
    sc = np.einsum("bqhd,bkhd->bhqk", np.asarray(q8, np.int64),
                   np.asarray(k8, np.int64)) * plan.sm.s_in
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, np.asarray(v8, np.float64))


def test_flat_rows_over_512_keys_track_the_float_softmax(rng):
    """Every weight is the key's exp rounded to 2^-7 of the row max.  Where
    each key weighs at least half the max (``u >= 64``), a weight is off
    by at most 0.5/64 for the rounding, 1/128 for the cap at 127 and
    0.4% for i-exp: ``e < 2%``.  Normalising by the same weights' sum
    moves each normalised weight by at most ``2e/(1-e)`` of itself, so
    the output is within 4% of max|V| of the float softmax's, plus half
    of the 2^-7 step the division rounds to."""
    plan = _plan(64, **GRANITE)
    q8, k8, v8 = _flat_rows(rng)
    sc = np.einsum("bqhd,bkhd->bhqk", np.asarray(q8, np.int64),
                   np.asarray(k8, np.int64))
    assert np.exp((sc.min() - sc.max()) * plan.sm.s_in) > 0.5
    got = np.asarray(iattn.i_attention_acc(q8, k8, v8, plan)) / 128.0
    want = _float_pv(q8, k8, v8, plan)
    vmax = np.abs(np.asarray(v8)).max()
    err = np.abs(got - want).max(axis=-1) / vmax          # per row
    assert err.max() < 0.04 + 0.5 / 128 / vmax
    assert err.max() < 0.01                  # what it reads here


def test_normalising_before_pv_loses_flat_rows(rng):
    """The paper's dataflow on the same rows: each probability, about
    1/512, rounds to 0 at 2^-7, the whole row of P is zero and so is the
    output — off by the float output's whole size."""
    plan = _plan(64, **GRANITE)
    q8, k8, v8 = _flat_rows(rng)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q8, k8,
                        preferred_element_type=jnp.int32)
    p8 = ism.i_softmax(scores, plan.sm)
    assert not np.asarray(p8).any()
    old = np.asarray(jnp.einsum("bhqk,bkhd->bqhd", p8, v8,
                                preferred_element_type=jnp.int32)) / 128.0
    want = _float_pv(q8, k8, v8, plan)
    vmax = np.abs(np.asarray(v8)).max()
    err = np.abs(old - want).max(axis=-1) / vmax
    assert err.min() > 0.04


# ----------------------------------------------- the exact division ----

def _division_cases(rng, rows=512):
    s = rng.integers(1, STREAM_SUM_BUDGET * 2 + 1, (rows, 1))
    s[:6, 0] = [1, 2, 127, 128, STREAM_SUM_BUDGET * 2, 3]
    acc = np.round(rng.uniform(-1, 1, (rows, 64)) * 127 * s).astype(np.int64)
    acc[:6, :3] = np.array([127, -127, 0])[None] * s[:6]
    return acc, s


def test_normalize_rows_is_the_rounded_quotient(rng):
    acc, s = _division_cases(rng)
    want = (acc * 128 + s // 2) // s
    got = ism.normalize_rows(jnp.asarray(acc, jnp.int32),
                             jnp.asarray(s, jnp.int32))
    assert np.array_equal(np.asarray(got), want)
    # a fully masked row: no weight, no accumulator
    z = ism.normalize_rows(jnp.zeros((1, 4), jnp.int32),
                           jnp.zeros((1, 1), jnp.int32))
    assert not np.asarray(z).any()


def test_kernel_division_matches_the_integer_one(rng):
    """``normalize_tile`` (float32 estimate, one exact int32 remainder)
    equals ``normalize_rows`` (integer floor division) on the whole
    admissible range, the budget's edge included."""
    acc, s = _division_cases(rng, rows=4096)
    a, sj = jnp.asarray(acc, jnp.int32), jnp.asarray(s, jnp.int32)
    assert np.array_equal(np.asarray(normalize_tile(a, sj)),
                          np.asarray(ism.normalize_rows(a, sj)))


# ------------------------------------- bit-exact where rows are flat ----

@pytest.mark.parametrize("sq,h,hkv,causal,window", [
    (384, 4, 2, True, 0),           # causal GQA, past 256 keys
    (256, 4, 4, True, 96),          # sliding window
    (256, 2, 1, False, 0),          # bidirectional
])
def test_flat_prefill_fused_equals_ref(rng, sq, h, hkv, causal, window):
    plan = _plan(64, **GRANITE)
    q8, k8, v8 = (_i8(rng, (2, sq, h, 64), 30),
                  _i8(rng, (2, sq, hkv, 64), 30),
                  _i8(rng, (2, sq, hkv, 64), 60))
    for spec, bv in ((RequantSpec.per_tensor(plan.dn_out), None),
                     (RequantSpec.raw(), None),
                     (RequantSpec.per_channel(c=22, pre=0),
                      jnp.asarray(rng.integers(100, 400, (h * 64,)),
                                  jnp.int32))):
        got = FUSED.int_attention(q8, k8, v8, plan, causal=causal,
                                  window=window, requant=spec, b_vec=bv,
                                  bq=128, bkv=128)
        want = REF.int_attention(q8, k8, v8, plan, causal=causal,
                                 window=window, requant=spec, b_vec=bv)
        assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("window", [0, 100])
def test_flat_chunked_equals_full(rng, window):
    """The streaming two-pass form sums the same integers in another
    order: equal to the full form, bit for bit, below MAX_PV_KEYS."""
    plan = _plan(64, **GRANITE)
    q8, k8, v8 = (_i8(rng, (1, 384, 2, 64), 30),
                  _i8(rng, (1, 384, 2, 64), 30),
                  _i8(rng, (1, 384, 2, 64), 60))
    mask = iattn.causal_mask(384, 384, window=window)[None, None]
    full = iattn.i_attention_full(q8, k8, v8, plan, mask=mask)
    chk = iattn.i_attention_chunked(q8, k8, v8, plan, chunk=128,
                                    causal=True, window=window)
    assert np.array_equal(np.asarray(chk), np.asarray(full))


def test_flat_decode_fused_equals_ref_ragged(rng):
    """Ragged occupancy — empty, one key, block edges, full — with a
    speculative block of three query rows (stepped mask)."""
    plan = _plan(64, **GRANITE)
    L, bkv = 384, 128
    q8 = _i8(rng, (7, 3, 4, 64), 30)
    k8, v8 = _i8(rng, (7, L, 2, 64), 30), _i8(rng, (7, L, 2, 64), 60)
    vl = jnp.asarray([0, 1, 127, 128, 129, 300, L], jnp.int32)
    got = FUSED.int_decode_attention(q8, k8, v8, plan, vl, bkv=bkv)
    want = REF.int_decode_attention(q8, k8, v8, plan, vl)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_flat_paged_prefill_fused_equals_ref(rng):
    """Unaligned chunk bases over permuted pages, 384-key rows."""
    plan = _plan(64, **GRANITE)
    b, c, ps = 3, 128, 64
    q8 = _i8(rng, (b, c, 4, 64), 30)
    kn, vn = _i8(rng, (b, c, 2, 64), 30), _i8(rng, (b, c, 2, 64), 60)
    kp, vp = _i8(rng, (20, ps, 2, 64), 30), _i8(rng, (20, ps, 2, 64), 60)
    pages = jnp.asarray([[3, 7, 1, 0, 0, 0], [2, 4, 5, 6, 9, 11],
                         [8, 10, 12, 13, 14, 15]], jnp.int32)
    base = jnp.asarray([0, 200, 256], jnp.int32)
    want, kpr, vpr = kref.ref_int_paged_prefill(q8, kn, vn, kp, vp, plan,
                                                base, pages, ps)
    got = int_paged_prefill_fused(q8, scatter_chunk(kp, kn, base, pages, ps),
                                  scatter_chunk(vp, vn, base, pages, ps),
                                  plan, base + c, pages, ps)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------- past MAX_PV_KEYS keys -----

def test_rows_past_the_one_pass_budget_stream_without_overflow(rng):
    """A decode row of 2^17 + 2^15 equal keys: one pass would overflow the
    accumulator (``127 * 127`` a key), the streamed fold halves it and
    reads V's mean exactly; with random V it stays within one 2^-7 step
    of the float mean, and the chunked form agrees to that step."""
    L = MAX_PV_KEYS + (1 << 15)
    assert L * 127 * 127 > 2 ** 31 - 1
    plan = _plan(8)
    q8 = jnp.zeros((1, 1, 1, 8), jnp.int8)
    k8 = jnp.zeros((1, L, 1, 8), jnp.int8)
    v8 = jnp.full((1, L, 1, 8), 127, jnp.int8)
    out = iattn.i_attention_acc(q8, k8, v8, plan)
    assert np.all(np.asarray(out) == 127 * 128)
    v8 = jnp.asarray(rng.integers(-127, 128, (1, L, 1, 8)), jnp.int8)
    out = np.asarray(iattn.i_attention_acc(q8, k8, v8, plan))
    mean = np.asarray(v8, np.float64).mean(axis=1, keepdims=True) * 128
    assert np.abs(out - mean).max() <= 1.0
    chk = np.asarray(iattn.i_attention_chunked(
        jnp.zeros((1, 1, 1, 8), jnp.int8), k8, v8, plan, chunk=1024,
        causal=False, out_bits=32))
    full = np.asarray(iattn.i_attention_full(q8, k8, v8, plan, out_bits=32))
    assert np.abs(chk.astype(int) - full.astype(int)).max() <= 1


def test_fold_keeps_the_running_sum_in_budget():
    run = (jnp.zeros((1, 1), jnp.int32), jnp.zeros((1, 1), jnp.int32),
           jnp.zeros((1, 1), jnp.int32))
    part = (jnp.full((1, 1), 127 * 127 * 1024, jnp.int32),
            jnp.full((1, 1), 127 * 1024, jnp.int32))
    for _ in range(600):                    # 614,400 keys of weight 127
        run = iattn.fold_pv(run, part, 1024)
        assert int(run[1][0, 0]) <= STREAM_SUM_BUDGET
    acc, s, lvl = (int(x[0, 0]) for x in run)
    assert lvl >= 3 and abs(acc - 127 * s) <= 127 * lvl


def test_epilogue_dyadic_fits_the_normalised_range():
    """The per-tensor epilogue was fitted for |acc| <= 2 * 127 * 128;
    the normalised accumulator never passes 128 * 127."""
    plan = _plan(64)
    assert plan.dn_out.qmax_in >= 128 * 127
    dn = fit_dyadic(plan.dn_out.value, 128 * 127)
    assert dn.value == pytest.approx(plan.dn_out.value, rel=1e-3)


# ----------------------------------------------------- certification --

def test_certifier_bounds_the_normalised_accumulator(rng):
    """``t_attention_acc``'s range holds what ``normalize_rows`` returns
    for any admissible row, and the weight cap matters: an uncapped
    weight of 128 would take the division past int32 at MAX_PV_KEYS."""
    from repro.analysis.budgets import BitBudgetError
    from repro.analysis.ranges import t_attention_acc
    r = t_attention_acc(MAX_PV_KEYS)
    acc, s = _division_cases(rng)
    s = np.minimum(s, MAX_PV_KEYS * 127)
    acc = np.clip(acc, -127 * s, 127 * s)
    out = np.asarray(ism.normalize_rows(jnp.asarray(acc, jnp.int32),
                                        jnp.asarray(s, jnp.int32)))
    assert r.lo <= out.min() and out.max() <= r.hi == 128 * 127
    with pytest.raises(BitBudgetError, match="division"):
        t_attention_acc(MAX_PV_KEYS, u_max=128)
    streamed = t_attention_acc(1 << 19)
    assert streamed.hi > r.hi


def test_every_config_certifies_at_its_longest_rows():
    """Jamba runs ``long_500k``: its attention rows reach 524,288 keys,
    past MAX_PV_KEYS, and certify on the streamed fold."""
    from repro.analysis.interpret import certify_config, longest_key_count
    cfg = get_config("jamba-v0.1-52b")
    assert longest_key_count(cfg, 32768) == 1 << 19
    rep = certify_config(cfg)
    longest = [o for o in rep.ops if o.layer == "attn.decode[524288]"]
    assert len(longest) == 1 and longest[0].bits <= 32
