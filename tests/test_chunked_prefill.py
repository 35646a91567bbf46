"""Chunked batched prefill + cross-session prefix sharing: exact parity.

The contract under test (docs/KERNELS.md "paged prefill" section and
docs/ARCHITECTURE.md scheduler):

  * the ``int_paged_prefill`` op — scatter a prompt chunk's K/V through
    the page table, attend causally over history + chunk — is bit-exact
    against the ``ref_int_paged_prefill`` oracle for every backend:
    natively on ``pallas_fused`` (``paged_prefill`` capability, the
    fused kernel reading K/V through the scalar-prefetched table), via
    the dispatch layer's scatter/gather lowering everywhere else;
  * the folded o-projection (``prefill_wo_fold``) is bit-exact against
    the unfolded composition for all three RequantSpec forms;
  * the engine's chunked prefill pipeline produces token streams
    bit-identical to token streaming across cache_mode × backend ×
    chunk/budget, interleaves with decode under ``prefill_budget``, and
    survives mid-prefill preemption;
  * sessions sharing a prompt prefix map the same physical pages
    (allocator refcounts), produce identical streams, diverge safely
    through copy-on-write, and hit again after evict → re-admit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core import attention as iattn
from repro.kernels import ref as kref
from repro.kernels.int_attention_fused import int_paged_prefill_fused
from repro.models import model as M
from repro.models import transformer as tf
from repro.ops import (QuantLinearParams, RequantSpec, get_backend,
                       resolve_ops)
from repro.ops.paged import scatter_chunk
from repro.quant import convert
from repro.serving import Request, ServingEngine

FUSED = get_backend("pallas_fused")


def _plan(d):
    return iattn.make_iattention(d, 8 / 127, 8 / 127, 4 / 127, 4 / 127)


def _pool(rng, num_pages, ps, hkv, d):
    k = jnp.asarray(rng.integers(-127, 128, (num_pages, ps, hkv, d)),
                    jnp.int8)
    v = jnp.asarray(rng.integers(-127, 128, (num_pages, ps, hkv, d)),
                    jnp.int8)
    return k, v


def _chunk(rng, b, c, h, d):
    return jnp.asarray(rng.integers(-127, 128, (b, c, h, d)), jnp.int8)


# ------------------------------------------------- kernel-level parity ----

def test_paged_prefill_kernel_matches_oracle_ragged(rng):
    """Permuted, partially-mapped tables + ragged (page-unaligned) chunk
    bases: the kernel's block->page translation and stepped
    causal-over-history mask must match the scatter+gather+decode-oracle
    definition bit-for-bit, sub-page tiling included."""
    b, h, hkv, d, ps, num_pages, c = 3, 4, 2, 32, 16, 11, 32
    plan = _plan(d)
    q8 = _chunk(rng, b, c, h, d)
    kn, vn = _chunk(rng, b, c, hkv, d), _chunk(rng, b, c, hkv, d)
    kp, vp = _pool(rng, num_pages, ps, hkv, d)
    pages = jnp.asarray([[3, 7, 1, 0],      # fresh session: no history
                         [2, 4, 5, 6],      # one page of history
                         [8, 9, 10, 1]], jnp.int32)
    base = jnp.asarray([0, 16, 23], jnp.int32)     # 23: unaligned base
    want, kpr, vpr = kref.ref_int_paged_prefill(
        q8, kn, vn, kp, vp, plan, base, pages, ps)
    kps = scatter_chunk(kp, kn, base, pages, ps)
    vps = scatter_chunk(vp, vn, base, pages, ps)
    assert np.array_equal(np.asarray(kps), np.asarray(kpr))
    assert np.array_equal(np.asarray(vps), np.asarray(vpr))
    got = int_paged_prefill_fused(q8, kps, vps, plan, base + c, pages, ps,
                                  bkv=16)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # sub-page tiling: bkv < page_size walks sub-blocks through the
    # table; smaller query blocks exercise the q grid dimension
    got8 = int_paged_prefill_fused(q8, kps, vps, plan, base + c, pages,
                                   ps, bkv=8, bq=16)
    assert np.array_equal(np.asarray(got8), np.asarray(want))


def test_paged_prefill_scatter_routes_overflow_to_null_page(rng):
    """Chunk positions past the table span (padded tails) and positions
    on unmapped rows land on the reserved null page — a chunk write can
    never touch a live page it does not own."""
    ps, num_pages = 8, 5
    kp, _ = _pool(rng, num_pages, ps, 1, 4)
    chunk = _chunk(rng, 2, 8, 1, 4)
    pages = jnp.asarray([[1, 2], [0, 0]], jnp.int32)   # row 1 unmapped
    base = jnp.asarray([12, 0], jnp.int32)   # row 0 pads past 16
    out = scatter_chunk(kp, chunk, base, pages, ps)
    # row 0: positions 12..15 hit page 2 offsets 4..7; 16..19 -> null
    assert np.array_equal(np.asarray(out[2, 4:]),
                          np.asarray(chunk[0, :4]))
    # pages 1..4 untouched by row 1 (all writes absorbed by null page 0)
    assert np.array_equal(np.asarray(out[1]), np.asarray(kp[1]))
    assert np.array_equal(np.asarray(out[3:]), np.asarray(kp[3:]))


def test_paged_prefill_dispatch_parity_all_backends(rng):
    """OpSet capability negotiation: pallas_fused consumes the table
    natively, ref gets the exact scatter/gather lowering — both return
    identical attention outputs AND identical pool bytes."""
    b, h, hkv, d, ps, num_pages, c = 2, 2, 1, 16, 16, 7, 16
    plan = _plan(d)
    q8 = _chunk(rng, b, c, h, d)
    kn, vn = _chunk(rng, b, c, hkv, d), _chunk(rng, b, c, hkv, d)
    kp, vp = _pool(rng, num_pages, ps, hkv, d)
    pages = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    base = jnp.asarray([5, 32], jnp.int32)
    want, kpr, vpr = kref.ref_int_paged_prefill(
        q8, kn, vn, kp, vp, plan, base, pages, ps)
    for name in ("ref", "pallas_fused"):
        o, kk, vv = resolve_ops(name).int_paged_prefill(
            q8, kn, vn, kp, vp, plan, base, pages, ps)
        assert np.array_equal(np.asarray(o), np.asarray(want)), name
        assert np.array_equal(np.asarray(kk), np.asarray(kpr)), name
        assert np.array_equal(np.asarray(vv), np.asarray(vpr)), name


def test_paged_prefill_untileable_falls_back_exactly(rng):
    """Pages below the kernel's min block (and odd chunk sizes) must
    gather + oracle with identical numerics rather than enter the
    kernel."""
    b, h, d, ps, num_pages, c = 2, 2, 16, 8, 9, 24
    plan = _plan(d)
    q8 = _chunk(rng, b, c, h, d)
    kn, vn = _chunk(rng, b, c, h, d), _chunk(rng, b, c, h, d)
    kp, vp = _pool(rng, num_pages, ps, h, d)
    pages = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    base = jnp.asarray([0, 8], jnp.int32)
    want, kpr, vpr = kref.ref_int_paged_prefill(
        q8, kn, vn, kp, vp, plan, base, pages, ps)
    o, kk, vv = FUSED.int_paged_prefill(q8, kn, vn, kp, vp, plan, base,
                                        pages, ps)
    assert np.array_equal(np.asarray(o), np.asarray(want))
    assert np.array_equal(np.asarray(kk), np.asarray(kpr))


# ----------------------------------------------------- wo-fold parity -----

@pytest.mark.parametrize("form", ["per_channel", "per_tensor", "raw"])
def test_prefill_wo_fold_matches_unfolded_composition(rng, form):
    """The folded o-projection epilogue of the prefill launch —
    in-kernel on pallas_fused (``prefill_wo_fold``), dispatch-composed
    on ref — is bit-exact against attention followed by the int8
    matmul, for every wo RequantSpec form."""
    from repro.core.dyadic import fit_dyadic
    b, h, hkv, d, ps, num_pages, c = 2, 4, 2, 16, 16, 9, 16
    n_out = h * d
    plan = _plan(d)
    q8 = _chunk(rng, b, c, h, d)
    kn, vn = _chunk(rng, b, c, hkv, d), _chunk(rng, b, c, hkv, d)
    kp, vp = _pool(rng, num_pages, ps, hkv, d)
    pages = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    base = jnp.asarray([0, 21], jnp.int32)
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
    o_attn, _, _ = kref.ref_int_paged_prefill(q8, kn, vn, kp, vp, plan,
                                              base, pages, ps)
    want = np.asarray(kref.ref_apply_wo(o_attn, wo.w8, wo.bias32,
                                        wo.b_mult, spec))
    for name in ("ref", "pallas_fused"):
        got, _, _ = resolve_ops(name).int_paged_prefill(
            q8, kn, vn, kp, vp, plan, base, pages, ps, wo=wo,
            wo_spec=spec)
        assert np.array_equal(np.asarray(got), want), (name, form)
    assert want.shape == (b, c, n_out)


def test_prefill_wo_too_wide_to_fold_runs_unfolded(rng, monkeypatch):
    """Past the chip's VMEM budget the prefill launch runs unfolded and
    ``wo`` goes through the matmul kernel, with the folded integers."""
    from repro.analysis import contracts
    b, h, hkv, d, ps, num_pages, c = 2, 4, 2, 16, 16, 9, 16
    n_out = h * d
    plan = _plan(d)
    q8 = _chunk(rng, b, c, h, d)
    kn, vn = _chunk(rng, b, c, hkv, d), _chunk(rng, b, c, hkv, d)
    kp, vp = _pool(rng, num_pages, ps, hkv, d)
    pages = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    base = jnp.asarray([0, 21], jnp.int32)
    spec = RequantSpec.per_channel(c=28, pre=7, out_bits=8)
    wo = QuantLinearParams(
        jnp.asarray(rng.integers(-127, 128, (h * d, n_out)), jnp.int8),
        jnp.asarray(rng.integers(1000, 30000, (n_out,)), jnp.int32),
        jnp.asarray(rng.integers(-500, 500, (n_out,)), jnp.int32))
    args = (q8, kn, vn, kp, vp, plan, base, pages, ps)
    folded, _, _ = FUSED.int_paged_prefill(*args, wo=wo, wo_spec=spec)
    monkeypatch.setattr(contracts, "can_fold_wo", lambda *a, **k: False)
    unfolded, kk, _ = FUSED.int_paged_prefill(*args, wo=wo, wo_spec=spec)
    want, kr, _ = resolve_ops("ref").int_paged_prefill(*args, wo=wo,
                                                        wo_spec=spec)
    assert unfolded.dtype == jnp.int8 and unfolded.shape == (b, c, n_out)
    assert np.array_equal(np.asarray(unfolded), np.asarray(want))
    assert np.array_equal(np.asarray(folded), np.asarray(want))
    assert np.array_equal(np.asarray(kk), np.asarray(kr))


def test_prefill_wo_fold_rejects_non_int8_attention_epilogue(rng):
    plan = _plan(16)
    q8 = _chunk(rng, 1, 16, 2, 16)
    kn = _chunk(rng, 1, 16, 2, 16)
    kp, vp = _pool(rng, 3, 16, 2, 16)
    pages = jnp.asarray([[1, 2]], jnp.int32)
    base = jnp.asarray([0], jnp.int32)
    wo = QuantLinearParams(
        jnp.asarray(rng.integers(-127, 128, (32, 32)), jnp.int8))
    for ops in (resolve_ops("ref"), FUSED):
        call = ops.int_paged_prefill
        with pytest.raises(ValueError, match="int8 attention epilogue"):
            call(q8, kn, kn, kp, vp, plan, base, pages, 16,
                 requant=RequantSpec.raw(), wo=wo,
                 wo_spec=RequantSpec.raw())
        with pytest.raises(ValueError, match="wo_spec"):
            call(q8, kn, kn, kp, vp, plan, base, pages, 16, wo=wo)


# ------------------------------------------------------- engine parity ----

@pytest.fixture(scope="module")
def engine_setup():
    cfg = M.reduce_config(get_config("llama3-8b"), dtype="float32")
    params = tf.init_params(jax.random.key(0), cfg)
    qp, plans = convert.quantize_params(params, cfg)
    return cfg, qp, plans


RNG = np.random.default_rng(7)
PROMPTS = [list(map(int, RNG.integers(1, 64, n))) for n in
           (40, 3, 25, 1, 33)]


def _drive(engine_setup, prompts=PROMPTS, max_new=4, **kw):
    cfg, qp, plans = engine_setup
    eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64, **kw)
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    assert all(r.done for r in reqs)
    return eng, [r.out_tokens for r in reqs]


def test_engine_chunked_prefill_token_parity(engine_setup):
    """The acceptance matrix: chunked prefill must be bit-exact vs the
    token-streaming path across cache_mode × backend, for chunk sizes
    above/at/below the page size and with a budget that forces
    prefill/decode interleaving."""
    _, base = _drive(engine_setup, ops="ref", cache_mode="contiguous")
    combos = [
        dict(ops="ref"),                                  # chunked @32
        dict(ops="pallas_fused"),
        dict(ops="ref", prefill_chunk=16),                # == page size
        dict(ops="ref", prefill_chunk=8),                 # sub-page
        dict(ops="pallas_fused", prefill_chunk=64),
        dict(ops="ref", prefill_chunk=0),                 # streaming paged
        dict(ops="ref", prefill_budget=8),                # interleaved
        dict(ops="pallas_fused", prefill_chunk=16, prefill_budget=4),
        dict(ops="ref", fold_wo=False),
        dict(ops="ref", prefix_cache=False),
    ]
    for kw in combos:
        eng, toks = _drive(engine_setup, **kw)
        assert toks == base, kw
    # the fused engine runs the paged prefill kernel natively
    eng, _ = _drive(engine_setup, ops="pallas_fused")
    assert eng.prefill_paged_native
    assert eng.describe()["prefill"]["mode"] == "chunked"


def test_engine_prefix_sharing_maps_same_pages(engine_setup):
    """Two staggered same-prompt sessions: the second must hit the
    prefix table, physically share the first session's pages (allocator
    refcounts > 1 while both hold them), and emit an identical stream."""
    cfg, qp, plans = engine_setup
    _, solo = _drive(engine_setup, prompts=[PROMPTS[0]], max_new=4,
                     ops="ref", prefix_cache=False)
    eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                        ops="ref")
    a = Request(uid=0, prompt=list(PROMPTS[0]), max_new_tokens=4)
    sa = eng.submit(a)
    eng.step()                              # a prefilled + first token
    b = Request(uid=1, prompt=list(PROMPTS[0]), max_new_tokens=4)
    sb = eng.submit(b)
    eng.step()                              # b admitted via prefix hit
    px = eng.describe()["cache"]["prefix"]
    assert px["hits"] == 1 and px["tokens_reused"] == len(PROMPTS[0]) - 1
    # physical sharing, observable in the allocator refcounts
    shared = set(sa.pages) & set(sb.pages)
    assert shared, "same-prompt sessions must map the same pages"
    assert all(eng.kv.allocator.refcount[p] > 1 for p in shared)
    assert eng.describe()["cache"]["shared_pages"] >= len(shared)
    eng.run_until_done()
    assert a.out_tokens == b.out_tokens == solo[0]
    eng.kv.allocator.check()


def test_engine_prefix_share_evict_readmit_bit_exact(engine_setup):
    """Prefix-share → evict → re-admit: the index outlives the session,
    so a re-admitted prompt hits the cached pages and reproduces the
    stream bit-exactly; clearing the index returns every page."""
    cfg, qp, plans = engine_setup
    eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                        ops="ref")
    a = Request(uid=0, prompt=list(PROMPTS[0]), max_new_tokens=4)
    sa = eng.submit(a)
    eng.step()
    eng.evict(sa)                           # mid-generation cancel
    partial = list(a.out_tokens)
    hits0 = eng.prefix.hits
    b = Request(uid=1, prompt=list(PROMPTS[0]), max_new_tokens=4)
    eng.submit(b)
    eng.run_until_done()
    assert eng.prefix.hits > hits0          # re-admit hit the cache
    assert b.out_tokens[:len(partial)] == partial
    _, solo = _drive(engine_setup, prompts=[PROMPTS[0]], max_new=4,
                     ops="ref", prefix_cache=False)
    assert b.out_tokens == solo[0]
    eng.prefix.clear()
    assert eng.kv.allocator.used_pages == 0
    eng.kv.allocator.check()


def test_engine_copy_on_write_divergence(engine_setup):
    """Sessions sharing a prefix then diverging: the first write into a
    shared page copies it (cow_copies > 0), streams match the unshared
    engine for BOTH prompts, and the cached prefix stays intact."""
    cfg, qp, plans = engine_setup
    p1 = list(PROMPTS[0])
    p2 = p1[:-1] + [int(p1[-1]) % 60 + 1]   # same prefix, last differs
    _, base = _drive(engine_setup, prompts=[p1, p2], max_new=4,
                     ops="ref", prefix_cache=False)
    eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                        ops="ref")
    a = Request(uid=0, prompt=p1, max_new_tokens=4)
    eng.submit(a)
    eng.step()
    b = Request(uid=1, prompt=p2, max_new_tokens=4)
    eng.submit(b)
    eng.run_until_done()
    d = eng.describe()["cache"]
    assert d["prefix"]["hits"] >= 1         # p2 reused p1's prefix pages
    assert d["cow_copies"] > 0              # ... and diverged via CoW
    assert a.out_tokens == base[0]
    assert b.out_tokens == base[1]
    eng.kv.allocator.check()


def test_engine_preempt_mid_prefill_resumes_bit_exact(engine_setup):
    """A session preempted while its prompt is still prefilling keeps
    prefill_pos + pages and resumes the remaining chunks bit-exactly."""
    cfg, qp, plans = engine_setup
    _, solo = _drive(engine_setup, prompts=[PROMPTS[0]], max_new=4,
                     ops="ref")
    eng = ServingEngine(qp, plans, cfg, batch_size=1, cache_len=64,
                        ops="ref", prefill_chunk=16, prefill_budget=16)
    a = Request(uid=0, prompt=list(PROMPTS[0]), max_new_tokens=4)
    sa = eng.submit(a)
    eng.step()                              # one 16-token chunk only
    assert sa.state == "prefilling" and 0 < sa.prefill_pos < 39
    eng.preempt(sa)
    assert sa.state == "preempted" and sa.pages
    eng.submit(Request(uid=1, prompt=[7, 8], max_new_tokens=2))
    eng.run_until_done()
    assert a.out_tokens == solo[0]


def test_engine_prefill_budget_interleaves_decode(engine_setup):
    """With a budget, an already-decoding session keeps emitting a token
    every engine step while a long prompt prefills in the background."""
    cfg, qp, plans = engine_setup
    eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                        ops="ref", prefill_chunk=8, prefill_budget=8)
    a = Request(uid=0, prompt=[3, 1], max_new_tokens=30)
    eng.submit(a)
    eng.step()
    eng.submit(Request(uid=1, prompt=list(PROMPTS[0]), max_new_tokens=2))
    before = len(a.out_tokens)
    for _ in range(4):                      # prompt needs ~5 chunk rounds
        eng.step()
        assert len(a.out_tokens) == before + 1  # one token per step
        before += 1
    eng.run_until_done()


def test_engine_never_fits_with_prefix_hit_raises_without_leaking(
        engine_setup):
    """A prompt whose TOTAL block count exceeds the pool can never fit,
    prefix hit or not (shared pages are pool pages too): admission must
    raise the typed error immediately AND must not leak the refcounts
    the prefix lookup retained, even when the caller keeps stepping."""
    from repro.serving import PagePoolExhausted
    cfg, qp, plans = engine_setup
    eng = ServingEngine(qp, plans, cfg, batch_size=1, cache_len=64,
                        ops="ref", page_size=16, num_pages=3)
    short = Request(uid=0, prompt=list(PROMPTS[0][:17]), max_new_tokens=1)
    eng.submit(short)                       # caches a 16-token prefix
    eng.run_until_done()
    long = Request(uid=1, prompt=list(PROMPTS[0][:17]) + [1] * 40,
                   max_new_tokens=1)
    eng.submit(long)
    before = eng.kv.allocator.refcount.copy()
    for _ in range(3):                      # every retry must be clean
        with pytest.raises(PagePoolExhausted):
            eng.step()
        assert np.array_equal(eng.kv.allocator.refcount, before)
    eng.prefix.clear()
    assert eng.kv.allocator.used_pages == 0
    eng.kv.allocator.check()


def test_engine_prefill_budget_caps_lanes_per_round(engine_setup):
    """The budget caps prompt tokens per engine step at chunk
    granularity: with budget == chunk, two co-admitted long prompts
    advance ONE lane per step, not both — and still finish bit-exactly."""
    cfg, qp, plans = engine_setup
    _, base = _drive(engine_setup, prompts=[PROMPTS[0], PROMPTS[2]],
                     max_new=4, ops="ref")
    eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                        ops="ref", prefill_chunk=8, prefill_budget=8)
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=4)
            for i, p in enumerate([PROMPTS[0], PROMPTS[2]])]
    sess = [eng.submit(r) for r in reqs]
    eng.step()
    advanced = sum(s.prefill_pos for s in sess)
    assert advanced <= 8                    # one chunk, one lane
    eng.run_until_done()
    assert [r.out_tokens for r in reqs] == base


def test_engine_typed_prefill_chunk_errors(engine_setup):
    cfg, qp, plans = engine_setup
    with pytest.raises(ValueError, match="divide or be a multiple"):
        ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                      ops="ref", prefill_chunk=24)
    with pytest.raises(ValueError, match="cache_mode='paged'"):
        ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                      ops="ref", cache_mode="contiguous", prefill_chunk=16)
    with pytest.raises(ValueError, match=">= 0"):
        ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                      ops="ref", prefill_chunk=-8)
    with pytest.raises(ValueError, match="prefill_budget"):
        ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                      ops="ref", prefill_budget=0)


def test_engine_sliding_window_arch_streams_and_rejects_chunked():
    """Sliding-window archs keep token-streaming prefill (a batched
    chunk write would clobber rolling-buffer positions earlier rows
    still need): the default silently streams, an explicit chunk is a
    typed error."""
    cfg = M.reduce_config(get_config("h2o-danube-3-4b"), dtype="float32",
                          vocab=128, num_layers=1)
    assert cfg.window > 0
    params = tf.init_params(jax.random.key(0), cfg)
    qp, plans = convert.quantize_params(params, cfg)
    eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=80,
                        ops="ref")
    assert eng.describe()["prefill"]["mode"] == "streaming"
    assert eng.prefix is None               # prefix needs window == 0
    with pytest.raises(ValueError, match="unsupported for arch"):
        ServingEngine(qp, plans, cfg, batch_size=2, cache_len=80,
                      ops="ref", prefill_chunk=16)


# ------------------------------------------------------- bench schema -----

def test_bench_json_schema_checker(tmp_path):
    """The CI schema gate: a valid BENCH_serving.json document passes;
    a field drop or type change is caught.  (The artifact itself is
    generated, not checked in — when a local bench run left one behind,
    validate it too.)"""
    import json
    import os
    from benchmarks.check_bench_json import check_file
    data = {
        "configs": {"paged_chunked": {
            "tokens": 8, "tokens_per_s": 1.5, "kv_bytes": 1024,
            "kv_pack": "int8", "weight_bytes": 4096,
            "pages": {"page_size": 16, "num_pages": 7}, "mode": "paged",
            "prefill": {"mode": "chunked", "chunk": 32,
                        "ttft_s": 0.01, "tokens_per_s": 100.0},
            "prefix_hit_rate": None,
        }},
        "parity": True, "arch": "llama3-8b", "quick": True,
        "spec": {
            "k0": {"tokens_per_s": 10.0, "accept_rate": None,
                   "drafted": 0, "accepted": 0},
            "k2": {"tokens_per_s": 15.0, "accept_rate": 0.9,
                   "drafted": 100, "accepted": 90},
            "k4": {"tokens_per_s": 14.0, "accept_rate": 0.8,
                   "drafted": 200, "accepted": 160},
            "parity": True, "speedup": 1.5,
        },
        "latency": {
            "arrival_rate_per_s": 20.0, "submitted": 8,
            "terminal": {"completed": 7, "cancelled": 0, "timeout": 0,
                         "rejected": 1},
            "ttft_s": {"n": 7, "mean": 0.01, "p50": 0.008, "p99": 0.02},
            "inter_token_s": {"n": 21, "mean": 0.002, "p50": 0.001,
                              "p99": 0.007},
            "queue_wait_s": {"n": 7, "mean": 0.005, "p50": 0.004,
                             "p99": 0.01},
            "occupancy": {"mean": 1.5, "max": 2},
            "queue_depth": {"mean": 0.5, "max": 2},
        },
    }
    good = tmp_path / "BENCH_serving.json"
    good.write_text(json.dumps(data))
    assert check_file(str(good)) == []
    real = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "BENCH_serving.json")
    if os.path.exists(real):                # generated by bench runs
        assert check_file(real) == []
    del data["parity"]
    del data["spec"]["k2"]["accepted"]
    for cfg in data["configs"].values():
        cfg["tokens_per_s"] = "fast"
    # semantic violations the structural pass can't see: inverted
    # percentiles, terminal counts not reconciling with submitted
    data["latency"]["ttft_s"]["p50"] = 0.5          # > p99 = 0.02
    data["latency"]["terminal"]["completed"] = 3    # sums to 4 != 8
    # the int4 KV tier gate: a paged_kv4 config that neither halves the
    # bytes nor tags itself int4 must be flagged
    data["configs"]["paged_kv4"] = dict(
        data["configs"]["paged_chunked"], kv_bytes=1000)
    bad = tmp_path / "BENCH_bad" / "BENCH_serving.json"
    bad.parent.mkdir()
    bad.write_text(json.dumps(data))
    errors = check_file(str(bad))
    assert any("parity" in e for e in errors)
    assert any("tokens_per_s" in e for e in errors)
    assert any("accepted" in e for e in errors)
    assert any("p50" in e and "p99" in e for e in errors)
    assert any("submitted" in e for e in errors)
    assert any("1.8x gate" in e for e in errors)
    assert any("kv_pack" in e for e in errors)
    assert check_file(str(tmp_path / "BENCH_missing.json"))
