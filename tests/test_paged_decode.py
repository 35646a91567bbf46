"""Paged decode attention + folded wo projection: exact-integer parity.

The contract under test (docs/KERNELS.md "decode kernel contract"):

  * the page-table operand (``pages: int32[B, max_pages]`` riding
    scalar-prefetch next to ``valid_len``) is bit-exact against
    gathering the pages into the contiguous layout first, for every
    backend — natively on ``pallas_fused`` (``paged_decode``), via the
    dispatch layer's gather lowering everywhere else;
  * the folded o-projection (``wo=``/``wo_spec=``) is bit-exact against
    the unfolded attention-then-``int8_matmul`` composition;
  * the engine's paged cache mode produces bit-identical token streams
    to the contiguous mode across admit → evict → re-admit schedules,
    preemption/resume included, and pool exhaustion raises the typed
    :class:`~repro.serving.kvcache.PagePoolExhausted`.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core import attention as iattn
from repro.kernels import ref as kref
from repro.kernels.int_decode_attention import int_decode_attention_fused
from repro.models import model as M
from repro.models import transformer as tf
from repro.ops import (QuantLinearParams, RequantSpec, get_backend,
                       resolve_ops)
from repro.ops.paged import gather_pages
from repro.quant import convert
from repro.serving import PagePoolExhausted, Request, ServingEngine

FUSED = get_backend("pallas_fused")
REF = get_backend("ref")


def _plan(d):
    return iattn.make_iattention(d, 8 / 127, 8 / 127, 4 / 127, 4 / 127)


def _pool(rng, num_pages, ps, hkv, d):
    k = jnp.asarray(rng.integers(-127, 128, (num_pages, ps, hkv, d)),
                    jnp.int8)
    v = jnp.asarray(rng.integers(-127, 128, (num_pages, ps, hkv, d)),
                    jnp.int8)
    return k, v


# ------------------------------------------------- kernel-level parity ----

@pytest.mark.parametrize("sq", [1, 4])
def test_paged_kernel_matches_gathered_oracle_ragged(rng, sq):
    """Arbitrary (permuted, partially-mapped) page tables + ragged
    occupancies: the in-kernel block->page translation must match the
    gather-into-contiguous definition bit-for-bit, empty slots and the
    speculative stepped mask included."""
    b, h, hkv, d, ps, m, num_pages = 4, 4, 2, 32, 16, 4, 11
    plan = _plan(d)
    q8 = jnp.asarray(rng.integers(-127, 128, (b, sq, h, d)), jnp.int8)
    kp, vp = _pool(rng, num_pages, ps, hkv, d)
    pages = jnp.asarray([[0, 0, 0, 0],          # empty slot: all null
                         [7, 3, 0, 0],          # 2 pages, out of order
                         [10, 1, 5, 2],         # full, permuted
                         [4, 6, 8, 9]], jnp.int32)
    vl = jnp.asarray([0, 23, 64, 49], jnp.int32)
    kc, vc = (gather_pages(p, pages, ps) for p in (kp, vp))
    want = np.asarray(kref.ref_int_decode_attention(q8, kc, vc, plan, vl))
    got = np.asarray(int_decode_attention_fused(
        q8, kp, vp, plan, vl, pages=pages, page_size=ps, bkv=16))
    assert np.array_equal(got, want)
    assert not got[0].any()                     # empty slot -> requant(0)
    # sub-page tiling: bkv < page_size walks sub-blocks through the table
    got8 = np.asarray(int_decode_attention_fused(
        q8, kp, vp, plan, vl, pages=pages, page_size=ps, bkv=8))
    assert np.array_equal(got8, want)


def test_paged_dispatch_parity_all_backends(rng):
    """OpSet capability negotiation: pallas_fused consumes the table
    natively, ref gets the exact gather lowering — both return identical
    integers."""
    b, h, hkv, d, ps, m, num_pages = 3, 2, 1, 16, 16, 3, 7
    plan = _plan(d)
    q8 = jnp.asarray(rng.integers(-127, 128, (b, 1, h, d)), jnp.int8)
    kp, vp = _pool(rng, num_pages, ps, hkv, d)
    pages = jnp.asarray(
        np.stack([rng.permutation(np.arange(1, m + 1)) for _ in range(b)]),
        jnp.int32)
    vl = jnp.asarray([1, 17, 48], jnp.int32)
    outs = {}
    for name in ("ref", "pallas_fused"):
        ops = resolve_ops(name)
        outs[name] = np.asarray(ops.int_decode_attention(
            q8, kp, vp, plan, vl, pages=pages, page_size=ps))
    assert np.array_equal(outs["ref"], outs["pallas_fused"])
    want = np.asarray(kref.ref_int_paged_decode_attention(
        q8, kp, vp, plan, vl, pages, ps))
    assert np.array_equal(outs["ref"], want)


def test_paged_untileable_page_size_falls_back_exactly(rng):
    """page_size below the kernel's min block: the backend must gather
    + oracle with identical numerics rather than enter the kernel."""
    b, h, d, ps, m, num_pages = 2, 2, 16, 8, 4, 9
    plan = _plan(d)
    q8 = jnp.asarray(rng.integers(-127, 128, (b, 1, h, d)), jnp.int8)
    kp, vp = _pool(rng, num_pages, ps, h, d)
    pages = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    vl = jnp.asarray([5, 32], jnp.int32)
    got = np.asarray(FUSED.int_decode_attention(
        q8, kp, vp, plan, vl, pages=pages, page_size=ps))
    want = np.asarray(kref.ref_int_paged_decode_attention(
        q8, kp, vp, plan, vl, pages, ps))
    assert np.array_equal(got, want)


# ----------------------------------------------------- wo-fold parity -----

@pytest.mark.parametrize("form", ["per_channel", "per_tensor", "raw"])
@pytest.mark.parametrize("paged", [False, True])
def test_wo_fold_matches_unfolded_composition(rng, form, paged):
    """The folded o-projection epilogue — in-kernel on pallas_fused,
    dispatch-composed on ref — is bit-exact against attention followed
    by the per-channel int8 matmul, for every wo RequantSpec form."""
    from repro.core.dyadic import fit_dyadic
    b, h, hkv, d, L = 3, 4, 2, 16, 64
    n_out = h * d
    plan = _plan(d)
    q8 = jnp.asarray(rng.integers(-127, 128, (b, 1, h, d)), jnp.int8)
    if paged:
        ps, num_pages = 16, 13
        kp, vp = _pool(rng, num_pages, ps, hkv, d)
        pages = jnp.asarray(np.stack(
            [rng.permutation(np.arange(1, 5)) for _ in range(b)]),
            jnp.int32)
        kw = dict(pages=pages, page_size=ps)
        kc, vc = (gather_pages(p, pages, ps) for p in (kp, vp))
    else:
        kp = kc = jnp.asarray(rng.integers(-127, 128, (b, L, hkv, d)),
                              jnp.int8)
        vp = vc = jnp.asarray(rng.integers(-127, 128, (b, L, hkv, d)),
                              jnp.int8)
        kw = {}
    vl = jnp.asarray([0, 21, 64], jnp.int32)
    wo_w8 = jnp.asarray(rng.integers(-127, 128, (h * d, n_out)), jnp.int8)
    bias32 = jnp.asarray(rng.integers(-500, 500, (n_out,)), jnp.int32)
    if form == "per_channel":
        spec = RequantSpec.per_channel(c=28, pre=7, out_bits=14)
        wo = QuantLinearParams(wo_w8, jnp.asarray(
            rng.integers(1000, 30000, (n_out,)), jnp.int32), bias32)
    elif form == "per_tensor":
        spec = RequantSpec.per_tensor(fit_dyadic(1 / 64.0, 1 << 24),
                                      out_bits=14)
        wo = QuantLinearParams(wo_w8, None, bias32)
    else:
        spec = RequantSpec.raw()
        wo = QuantLinearParams(wo_w8, None, bias32)
    o8 = kref.ref_int_decode_attention(q8, kc, vc, plan, vl)
    want = np.asarray(kref.ref_apply_wo(o8, wo.w8, wo.bias32, wo.b_mult,
                                        spec))
    for name in ("ref", "pallas_fused"):
        got = np.asarray(resolve_ops(name).int_decode_attention(
            q8, kp, vp, plan, vl, wo=wo, wo_spec=spec, **kw))
        assert np.array_equal(got, want), (name, form, paged)
    assert want.shape == (b, 1, n_out)


@pytest.mark.parametrize("paged", [False, True])
def test_wo_too_wide_to_fold_runs_unfolded(rng, monkeypatch, paged):
    """A ``wo`` block past the chip's VMEM budget is not folded: the
    kernel runs unfolded and the projection goes through the matmul
    kernel — the same integers as the folded epilogue and the oracle."""
    from repro.analysis import contracts
    b, h, hkv, d, L = 2, 4, 2, 16, 64
    n_out = h * d
    plan = _plan(d)
    q8 = jnp.asarray(rng.integers(-127, 128, (b, 1, h, d)), jnp.int8)
    if paged:
        ps, num_pages = 16, 9
        kp, vp = _pool(rng, num_pages, ps, hkv, d)
        pages = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
        kw = dict(pages=pages, page_size=ps)
    else:
        kp = jnp.asarray(rng.integers(-127, 128, (b, L, hkv, d)), jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, (b, L, hkv, d)), jnp.int8)
        kw = {}
    vl = jnp.asarray([21, 64], jnp.int32)
    spec = RequantSpec.per_channel(c=28, pre=7, out_bits=8)
    wo = QuantLinearParams(
        jnp.asarray(rng.integers(-127, 128, (h * d, n_out)), jnp.int8),
        jnp.asarray(rng.integers(1000, 30000, (n_out,)), jnp.int32),
        jnp.asarray(rng.integers(-500, 500, (n_out,)), jnp.int32))
    folded = np.asarray(FUSED.int_decode_attention(
        q8, kp, vp, plan, vl, wo=wo, wo_spec=spec, **kw))
    monkeypatch.setattr(contracts, "can_fold_wo", lambda *a, **k: False)
    unfolded = FUSED.int_decode_attention(q8, kp, vp, plan, vl, wo=wo,
                                          wo_spec=spec, **kw)
    want = np.asarray(resolve_ops("ref").int_decode_attention(
        q8, kp, vp, plan, vl, wo=wo, wo_spec=spec, **kw))
    assert unfolded.dtype == jnp.int8 and unfolded.shape == (b, 1, n_out)
    assert np.array_equal(np.asarray(unfolded), want)
    assert np.array_equal(folded, want)


def test_wo_fold_rejects_non_int8_attention_epilogue(rng):
    plan = _plan(16)
    q8 = jnp.asarray(rng.integers(-127, 128, (1, 1, 2, 16)), jnp.int8)
    kc = jnp.asarray(rng.integers(-127, 128, (1, 32, 2, 16)), jnp.int8)
    vl = jnp.asarray([4], jnp.int32)
    wo = QuantLinearParams(
        jnp.asarray(rng.integers(-127, 128, (32, 32)), jnp.int8))
    ops = resolve_ops("ref")
    with pytest.raises(ValueError, match="int8 attention epilogue"):
        ops.int_decode_attention(q8, kc, kc, plan, vl,
                                 requant=RequantSpec.raw(), wo=wo,
                                 wo_spec=RequantSpec.raw())
    with pytest.raises(ValueError, match="wo_spec"):
        ops.int_decode_attention(q8, kc, kc, plan, vl, wo=wo)
    # a wide *default* epilogue (out_bits > 8, requant=None) must be
    # rejected too — the int8 lowering would otherwise silently wrap —
    # on the dispatch layer and on the folding backend alike
    with pytest.raises(ValueError, match="int8 attention epilogue"):
        ops.int_decode_attention(q8, kc, kc, plan, vl, out_bits=16,
                                 wo=wo, wo_spec=RequantSpec.raw())
    with pytest.raises(ValueError, match="int8 attention epilogue"):
        FUSED.int_decode_attention(q8, kc, kc, plan, vl, out_bits=16,
                                   wo=wo, wo_spec=RequantSpec.raw())


# ------------------------------------------------------- engine parity ----

@pytest.fixture(scope="module")
def engine_setup():
    cfg = M.reduce_config(get_config("llama3-8b"), dtype="float32",
                          capacity_factor=8.0)
    params = tf.init_params(jax.random.key(0), cfg)
    qp, plans = convert.quantize_params(params, cfg)
    return cfg, qp, plans


PROMPTS = [[1, 7, 42], [9, 3], [17, 2, 5, 11], [4], [23, 8, 31]]


def _drive(engine_setup, prompts=PROMPTS, max_new=4, **kw):
    cfg, qp, plans = engine_setup
    eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64, **kw)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    assert all(r.done for r in reqs)
    return eng, [r.out_tokens for r in reqs]


def test_engine_paged_token_parity_across_admit_evict_readmit(
        engine_setup):
    """The acceptance schedule: 5 requests through 2 lanes — every lane
    is retired and re-admitted with recycled (never-zeroed) pages at
    least once — must produce bit-identical streams in all four
    (cache_mode × backend) combinations, fused decode running the
    page-table kernel natively."""
    ref_c, toks_c = _drive(engine_setup, ops="ref",
                           cache_mode="contiguous")
    ref_p, toks_p = _drive(engine_setup, ops="ref", cache_mode="paged")
    fus_p, toks_fp = _drive(engine_setup, ops="pallas_fused",
                            cache_mode="paged")
    fus_c, toks_fc = _drive(engine_setup, ops="pallas_fused",
                            cache_mode="contiguous")
    assert toks_p == toks_c
    assert toks_fp == toks_c
    assert toks_fc == toks_c
    assert fus_p.decode_fused and fus_p.decode_paged_native
    assert not ref_p.decode_paged_native       # served via gather lowering
    # after the drain only the prefix index still holds pages (cached
    # prompt prefixes); clearing it returns every page to the allocator
    ref_p.prefix.clear()
    assert ref_p.kv.allocator.used_pages == 0
    ref_p.kv.allocator.check()


def test_engine_fold_wo_token_parity(engine_setup):
    """fold_wo folds each attention sublayer's o-projection requant into
    the decode epilogue — token streams must be bit-identical to the
    unfolded path on both backends."""
    _, base = _drive(engine_setup, ops="ref", fold_wo=False)
    for name in ("ref", "pallas_fused"):
        _, toks = _drive(engine_setup, ops=name, fold_wo=True)
        assert toks == base, name


def test_engine_undersubscribed_pool_serves_all(engine_setup):
    """A pool far smaller than batch x cache_len still serves the whole
    queue (memory O(live tokens)) with unchanged tokens."""
    _, base = _drive(engine_setup, ops="ref", cache_mode="contiguous")
    eng, toks = _drive(engine_setup, ops="ref", cache_mode="paged",
                       page_size=8, num_pages=5)
    assert toks == base
    stats = eng.describe()["cache"]
    assert stats["capacity_tokens"] < 2 * 64   # genuinely undersubscribed


def test_engine_preempt_resume_is_bit_exact(engine_setup):
    cfg, qp, plans = engine_setup
    base = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                         ops="ref")
    r0 = Request(uid=0, prompt=[5, 9, 13], max_new_tokens=8)
    base.submit(r0)
    base.run_until_done()

    eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                        ops="ref")
    ra = Request(uid=1, prompt=[5, 9, 13], max_new_tokens=8)
    sa = eng.submit(ra)
    for _ in range(3):
        eng.step()
    mid = list(ra.out_tokens)
    eng.preempt(sa)
    assert sa.state == "preempted" and sa.pages   # lane freed, pages kept
    eng.submit(Request(uid=2, prompt=[100, 3], max_new_tokens=3))
    eng.run_until_done()
    assert ra.out_tokens[:len(mid)] == mid
    assert ra.out_tokens == r0.out_tokens         # resumed bit-exactly


def test_engine_sliding_window_wrap_parity():
    """Sliding-window arch with cache_len > window: decode positions
    wrap (slot = pos % window), so page-table writes revisit earlier
    pages — paged and contiguous streams must still agree bit-for-bit
    well past the wrap point."""
    cfg = M.reduce_config(get_config("h2o-danube-3-4b"), dtype="float32",
                          vocab=128, num_layers=1)
    assert cfg.window == 64
    params = tf.init_params(jax.random.key(0), cfg)
    qp, plans = convert.quantize_params(params, cfg)

    def drive(**kw):
        eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=80,
                            **kw)
        reqs = [Request(uid=i, prompt=[1 + i, 7, 3], max_new_tokens=70)
                for i in range(2)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_done(max_steps=300)
        assert all(r.done for r in reqs)
        return [r.out_tokens for r in reqs]

    toks_c = drive(ops="ref", cache_mode="contiguous", fold_wo=False)
    toks_p = drive(ops="ref", cache_mode="paged", fold_wo=True)
    assert toks_p == toks_c
    assert len(toks_c[0]) == 70                 # decoded past the wrap


def test_engine_pool_exhaustion_raises_typed(engine_setup):
    cfg, qp, plans = engine_setup
    # a prompt that can never fit the pool fails fast
    eng = ServingEngine(qp, plans, cfg, batch_size=1, cache_len=64,
                        ops="ref", page_size=16, num_pages=2)
    eng.submit(Request(uid=0, prompt=list(range(1, 40)),
                       max_new_tokens=2))
    with pytest.raises(PagePoolExhausted):
        eng.run_until_done()
    # two long decodes over a 2-page pool exhaust it mid-stream
    eng2 = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                         ops="ref", page_size=8, num_pages=3)
    for i in range(2):
        eng2.submit(Request(uid=i, prompt=[1 + i, 2], max_new_tokens=30))
    with pytest.raises(PagePoolExhausted):
        eng2.run_until_done()
    eng2.kv.allocator.check()                    # invariants survive

    with pytest.raises(ValueError, match="empty prompt"):
        eng2.submit(Request(uid=9, prompt=[], max_new_tokens=1))


def test_engine_rejects_prompt_longer_than_cache(engine_setup):
    """A prompt that cannot fit the logical cache fails typed at submit
    (paged and contiguous): prefill would otherwise write past the page
    table / cache slab and silently corrupt positions valid_len still
    marks live."""
    cfg, qp, plans = engine_setup
    for mode in ("paged", "contiguous"):
        eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=32,
                            ops="ref", cache_mode=mode)
        with pytest.raises(ValueError, match="exceeds the"):
            eng.submit(Request(uid=0, prompt=list(range(1, 40)),
                               max_new_tokens=2))
        # a prompt that exactly fills the cache is still admissible
        eng.submit(Request(uid=1, prompt=list(range(1, 33)),
                           max_new_tokens=1))
        eng.run_until_done()
