"""Speculative decoding through the scheduler: drafts, batched verify,
bit-exact acceptance, rollback — plus the serving-loop bugfix sweep.

The contract under test (docs/ARCHITECTURE.md "Speculative decoding"):

  * greedy token streams are **bit-exact** with speculation on vs off,
    across backend (ref / pallas_fused) x cache mode (paged-chunked /
    paged-streaming / contiguous) — speculation changes *when* tokens
    are computed, never *which*;
  * the verify launch packs per-lane variable-length drafts
    right-aligned into one ``Sq = spec_k + 1`` ``int_decode_attention``
    call; rejected drafts roll back as a page-table truncation with
    exact refcount accounting (CoW / prefix sharing included);
  * the prompt-lookup proposer accepts > 0 drafts on repeated-structure
    traffic;
  * bugfixes: sessions retire at ``pos >= cache_len`` (the final cache
    slot is usable), ``run_until_done`` raises the typed
    :class:`EngineStalled` instead of silently returning, and
    ``temperature > 0`` requests get a typed rejection under spec mode.
"""
import jax
import pytest

from repro.analysis.budgets import MAX_SQ
from repro.analysis.contracts import check_launch
from repro.configs.registry import get_config
from repro.models import model as M
from repro.models import transformer as tf
from repro.models.inttransformer import speculative_decode_supported
from repro.quant import convert
from repro.serving import (EngineStalled, NgramProposer, Request,
                           ServingEngine, SpeculationError,
                           SpeculationUnsupported, get_proposer,
                           validate_spec)

# ---------------------------------------------------------- proposer ----


def test_ngram_proposer_continues_most_recent_occurrence():
    p = NgramProposer(max_n=3)
    # trailing 3-gram [7, 8, 9] re-occurs; propose its continuation
    assert p.propose([7, 8, 9, 1, 2, 7, 8, 9], 2) == [1, 2]
    # cycle: the latest occurrence whose continuation spans a full k
    # tokens wins; only when every match truncates at the context end
    # does the latest partial continuation get used (no wrap-around)
    assert p.propose([5, 6, 5, 6, 5, 6], 3) == [5, 6]
    assert p.propose([5, 6, 5, 6, 5, 6, 5], 3) == [6, 5, 6]
    # no earlier occurrence of any suffix -> empty draft
    assert p.propose([1, 2, 3, 4], 2) == []
    # k caps the draft
    assert p.propose([9, 9, 9, 9, 9], 1) == [9]
    assert p.propose([1, 2, 3], 0) == []
    assert p.propose([], 4) == []


def test_ngram_proposer_prefers_longer_suffix_match():
    p = NgramProposer(max_n=3)
    # 1-gram [2] occurs at index 0 (-> 7) and via the 2-gram [1, 2] at
    # index 3 (-> 8): the longer suffix wins over the shorter
    assert p.propose([2, 7, 3, 1, 2, 8, 1, 2], 1) == [8]


def test_proposer_registry_typed_errors():
    assert get_proposer("ngram").name == "ngram"
    with pytest.raises(SpeculationError, match="unknown spec_mode"):
        get_proposer("draft-model")
    with pytest.raises(SpeculationError, match="min_n"):
        NgramProposer(max_n=2, min_n=3)


# ---------------------------------------------------------- validation ----


def test_validate_spec_budget_and_arch_gating():
    ok = M.reduce_config(get_config("llama3-8b"), dtype="float32")
    validate_spec(ok, 0, "ngram")
    validate_spec(ok, MAX_SQ - 1, "ngram")
    with pytest.raises(SpeculationError, match="spec_k must be >= 0"):
        validate_spec(ok, -1, "ngram")
    with pytest.raises(SpeculationError, match="MAX_SQ"):
        validate_spec(ok, MAX_SQ, "ngram")
    with pytest.raises(SpeculationError, match="unknown spec_mode"):
        validate_spec(ok, 2, "medusa")
    # spec_k = 0 never probes the proposer registry
    validate_spec(ok, 0, "medusa")
    # arch gating: sliding-window and SSM/hybrid archs are rejected
    # with the typed subclass (their rolling / lane-indexed state can't
    # roll a rejected draft back)
    for arch in ("h2o-danube-3-4b", "mamba2-130m", "jamba-v0.1-52b",
                 "seamless-m4t-large-v2"):
        cfg = get_config(arch)
        assert not speculative_decode_supported(cfg)
        with pytest.raises(SpeculationUnsupported):
            validate_spec(cfg, 2, "ngram")
    assert speculative_decode_supported(get_config("qwen2-moe-a2.7b"))


def test_verify_launch_passes_decode_contract():
    # the engine asserts this at construction; pin it independently so
    # a budget change shows up here, not as an engine crash
    for sq in (2, MAX_SQ):
        r = check_launch("int_decode_attention", b=2, sq=sq, h=4, hkv=4,
                         d=64, L=64)
        assert r.ok, r.reason
    r = check_launch("int_decode_attention", b=2, sq=MAX_SQ + 1, h=4,
                     hkv=4, d=64, L=64)
    assert not r.ok


# ------------------------------------------------------------ engines ----


@pytest.fixture(scope="module")
def setup():
    cfg = M.reduce_config(get_config("llama3-8b"), dtype="float32",
                          vocab=128, num_layers=1, n_heads=4,
                          n_kv_heads=4)
    params = tf.init_params(jax.random.key(0), cfg)
    qp, plans = convert.quantize_params(params, cfg)
    return cfg, qp, plans


# a prompt whose continuation the model pushes into short cycles, and
# whose own structure repeats — both feed the n-gram proposer
REP = [3, 5, 7, 3, 5, 7, 3, 5]
PROMPTS = [REP, [11, 2, 11, 2, 11], [40, 41, 42]]


def _drive(setup, spec_k, prompts=PROMPTS, max_new=12, batch=2,
           cache_len=64, **kw):
    cfg, qp, plans = setup
    eng = ServingEngine(qp, plans, cfg, batch_size=batch,
                        cache_len=cache_len, spec_k=spec_k, **kw)
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    assert all(r.done for r in reqs)
    return eng, [list(r.out_tokens) for r in reqs]


MATRIX = [
    ("ref", dict(cache_mode="paged")),                       # chunked
    ("ref", dict(cache_mode="paged", prefill_chunk=0)),      # streaming
    ("ref", dict(cache_mode="contiguous")),
    ("pallas_fused", dict(cache_mode="paged")),
    ("pallas_fused", dict(cache_mode="paged", prefill_chunk=0)),
    ("pallas_fused", dict(cache_mode="contiguous")),
]


def test_spec_streams_bit_exact_across_backend_and_cache_mode(setup):
    """The acceptance matrix: spec_k in {0, 2, MAX_SQ-1} must produce
    bit-identical greedy streams in every backend x cache-mode combo,
    and every combo must agree with every other."""
    base = None
    for ops, kw in MATRIX:
        eng0, out0 = _drive(setup, 0, ops=ops, **kw)
        assert eng0.describe()["spec"]["k"] == 0
        if base is None:
            base = out0
        assert out0 == base, (ops, kw)
        for k in (2, MAX_SQ - 1):
            eng, out = _drive(setup, k, ops=ops, **kw)
            assert out == base, (ops, kw, k)
            spec = eng.describe()["spec"]
            assert spec["drafted"] >= spec["accepted"] >= 0
            assert spec["wasted"] == spec["drafted"] - spec["accepted"]


def _digests(setup, spec_k, ops, **kw):
    cfg, qp, plans = setup
    eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                        spec_k=spec_k, ops=ops, record_logits=True, **kw)
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=12)
            for i, p in enumerate(PROMPTS)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    return [r.logits_sha.hexdigest() for r in reqs]


def test_logit_digests_agree_across_backend_and_spec(setup):
    """``record_logits``: the per-request digest of every logits row a
    token came from is identical across backends and with speculation
    on (verify rows are the sequential rows), and it sees the logits
    themselves — a change to one weight that keeps every argmax still
    changes it."""
    base = _digests(setup, 0, "ref")
    assert len(set(base)) == len(PROMPTS)
    assert _digests(setup, 0, "pallas_fused") == base
    assert _digests(setup, 2, "ref") == base
    assert _digests(setup, 2, "pallas_fused",
                    cache_mode="contiguous") == base
    cfg, qp, plans = setup
    # token 0's logit moves by a part in 2^20: no argmax changes
    nudged = (cfg, dict(qp, head_scale=qp["head_scale"].at[0].multiply(
        1 + 2 ** -20)), plans)
    assert _drive(nudged, 0, ops="ref")[1] == _drive(setup, 0, ops="ref")[1]
    assert all(a != b for a, b in zip(_digests(nudged, 0, "ref"), base))


def test_spec_accepts_drafts_on_repeated_structure(setup):
    """Prompt-lookup must actually land drafts on repetitive traffic —
    accept-rate > 0, and accepted drafts shorten the step count."""
    eng, out = _drive(setup, 3, prompts=[REP], max_new=24)
    spec = eng.describe()["spec"]
    assert spec["drafted"] > 0
    assert spec["accepted"] > 0
    assert spec["accept_rate"] > 0
    assert f"spec=ngram:k3" in eng.describe_str()
    _, out0 = _drive(setup, 0, prompts=[REP], max_new=24)
    assert out == out0


def test_spec_stats_zero_before_any_draft(setup):
    cfg, qp, plans = setup
    eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                        ops="ref", spec_k=2)
    spec = eng.describe()["spec"]
    assert spec == {"k": 2, "mode": "ngram", "drafted": 0,
                    "accepted": 0, "accept_rate": None, "wasted": 0}


def test_spec_rollback_keeps_exact_refcounts(setup):
    """Rejected drafts truncate the session's page list; after every
    run the allocator's refcounts must equal the live holders exactly
    (prefix entries included) and pool accounting must balance."""
    import collections
    cfg, qp, plans = setup
    eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                        ops="ref", spec_k=3, page_size=8)
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=16)
            for i, p in enumerate(PROMPTS)]
    sessions = [eng.submit(r) for r in reqs]
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()
        eng.kv.allocator.check()
        held = collections.Counter()
        for sess in sessions:
            held.update(sess.pages)
        if eng.prefix is not None:
            for entry in eng.prefix.entries.values():
                held.update(entry.pages)
        for page in range(1, eng.layout.num_pages):
            assert eng.kv.allocator.refcount[page] == held.get(page, 0)
    assert eng.describe()["spec"]["drafted"] > 0


def test_paged_truncate_releases_trailing_pages(setup):
    cfg, qp, plans = setup
    eng = ServingEngine(qp, plans, cfg, batch_size=1, cache_len=64,
                        ops="ref", page_size=8, prefix_cache=False)
    sess = eng.submit(Request(uid=0, prompt=[1] * 20, max_new_tokens=4))
    eng.run_until_done()
    # re-grow a dedicated session by hand: 3 pages -> keep 9 tokens
    sess2 = eng.submit(Request(uid=1, prompt=[2] * 20,
                               max_new_tokens=2))
    eng.step()                               # prefill allocates pages
    n_pages = len(sess2.pages)
    assert n_pages >= 3
    freed = eng.kv.truncate(sess2, 9)        # ceil(9/8) = 2 pages kept
    assert freed == n_pages - 2
    assert len(sess2.pages) == 2
    eng.kv.allocator.check()
    with pytest.raises(ValueError):
        eng.kv.truncate(sess2, -1)
    assert eng.kv.truncate(sess2, 16) == 0   # no-op: already short


# ------------------------------------------------- bugfix regressions ----


def test_final_cache_slot_usable_exact_full_cache(setup):
    """Regression (PR 8): sessions used to retire at ``pos >=
    cache_len - 1``, wasting the last slot — a prompt + continuation
    that exactly fills the cache must emit every token, spec on & off.
    """
    prompts = [[1, 2, 3, 4, 5, 6, 7, 8]]
    outs = {}
    for mode in ("contiguous", "paged"):
        for k in (0, 3):
            eng, out = _drive(setup, k, prompts=prompts, max_new=8,
                              batch=1, cache_len=16, ops="ref",
                              cache_mode=mode)
            assert len(out[0]) == 8, (mode, k, out)
            outs[(mode, k)] = out
    assert len(set(map(tuple, (o[0] for o in outs.values())))) == 1


def test_spec_never_overruns_cache_or_token_budget(setup):
    """Near the cache end the per-lane draft clamp must shrink k so a
    multi-token commit can't write past the last slot or past
    max_new_tokens."""
    eng, out = _drive(setup, MAX_SQ - 1, prompts=[REP, REP[:5]],
                      max_new=7, batch=2, cache_len=16, ops="ref")
    assert all(len(o) == 7 for o in out)
    _, out0 = _drive(setup, 0, prompts=[REP, REP[:5]], max_new=7,
                     batch=2, cache_len=16, ops="ref")
    assert out == out0


def test_run_until_done_raises_typed_stall(setup):
    cfg, qp, plans = setup
    eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                        ops="ref")
    eng.submit(Request(uid=7, prompt=[1, 2, 3], max_new_tokens=50))
    with pytest.raises(EngineStalled) as ei:
        eng.run_until_done(max_steps=3)
    e = ei.value
    assert e.max_steps == 3 and e.queue_depth == 0
    assert any(s and s["uid"] == 7 for s in e.slots)
    assert "uid=7" in str(e) and "prefill_pos" in str(e)
    # draining normally afterwards still works and returns the request
    done = eng.run_until_done()
    assert [r.uid for r in done] == [7]


def test_run_until_done_zero_work_never_stalls(setup):
    cfg, qp, plans = setup
    eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                        ops="ref")
    assert eng.run_until_done(max_steps=0) == []


def test_temperature_requests_rejected_under_spec(setup):
    cfg, qp, plans = setup
    eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                        ops="ref", spec_k=2)
    with pytest.raises(SpeculationUnsupported, match="greedy"):
        eng.submit(Request(uid=0, prompt=[1, 2], max_new_tokens=4,
                           temperature=0.7))
    # greedy requests still admitted; temperature on a spec-free engine
    # still works (and is reproducible for a fixed engine seed)
    eng.submit(Request(uid=1, prompt=[1, 2], max_new_tokens=4))
    eng.run_until_done()


def test_temperature_sampling_reproducible_across_engines(setup):
    cfg, qp, plans = setup
    streams = []
    for _ in range(2):
        eng = ServingEngine(qp, plans, cfg, batch_size=1, cache_len=64,
                            ops="ref", seed=11)
        r = Request(uid=0, prompt=[1, 2, 3], max_new_tokens=8,
                    temperature=0.8)
        eng.submit(r)
        eng.run_until_done()
        streams.append(list(r.out_tokens))
    assert streams[0] == streams[1]
    assert all(0 <= t < cfg.vocab for t in streams[0])


def test_spec_constructor_rejects_unsupported(setup):
    cfg, qp, plans = setup
    with pytest.raises(SpeculationError, match="MAX_SQ"):
        ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                      ops="ref", spec_k=MAX_SQ)
    with pytest.raises(SpeculationError, match="unknown spec_mode"):
        ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                      ops="ref", spec_k=2, spec_mode="medusa")


def test_spec_composes_with_preempt_and_evict(setup):
    """Mid-stream preemption/resume under spec must keep the committed
    stream identical to the uninterrupted spec-off stream."""
    cfg, qp, plans = setup
    eng = ServingEngine(qp, plans, cfg, batch_size=1, cache_len=64,
                        ops="ref", spec_k=3)
    r0 = Request(uid=0, prompt=list(REP), max_new_tokens=16)
    r1 = Request(uid=1, prompt=[11, 2, 11, 2, 11], max_new_tokens=8)
    s0 = eng.submit(r0)
    eng.submit(r1)
    for _ in range(4):
        eng.step()
    eng.preempt(s0)                    # r1 takes the lane
    eng.run_until_done()
    assert r0.done and r1.done
    _, want = _drive(setup, 0, prompts=[list(REP),
                                        [11, 2, 11, 2, 11]],
                     max_new=16, batch=2, ops="ref")
    assert list(r0.out_tokens) == want[0]
    assert list(r1.out_tokens) == want[1][:8]
