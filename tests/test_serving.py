"""Serving engine: batched generation == sequential decode."""
import jax
import pytest

from repro.configs.registry import get_config
from repro.models import model as M
from repro.models import transformer as tf
from repro.quant import convert
from repro.serving import Request, ServingEngine


@pytest.fixture(scope="module")
def engine_setup():
    cfg = M.reduce_config(get_config("llama3-8b"), dtype="float32")
    params = tf.init_params(jax.random.key(0), cfg)
    qp, plans = convert.quantize_params(params, cfg)
    return cfg, qp, plans


def test_engine_generates(engine_setup):
    cfg, qp, plans = engine_setup
    eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64)
    reqs = [Request(uid=i, prompt=[1 + i, 7, 42], max_new_tokens=5)
            for i in range(3)]
    for r in reqs:
        eng.submit(r)
    finished = eng.run_until_done()
    assert sorted(r.uid for r in finished) == [r.uid for r in reqs]
    for r in reqs:
        assert r.done and len(r.out_tokens) == 5
        assert all(0 <= t < cfg.vocab for t in r.out_tokens)


def test_engine_batch_independence(engine_setup):
    """A request's greedy output must not depend on its batch neighbours."""
    cfg, qp, plans = engine_setup
    eng1 = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64)
    solo = Request(uid=0, prompt=[5, 9, 13], max_new_tokens=4)
    eng1.submit(solo)
    eng1.run_until_done()

    eng2 = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64)
    a = Request(uid=1, prompt=[5, 9, 13], max_new_tokens=4)
    b = Request(uid=2, prompt=[100, 3], max_new_tokens=4)
    eng2.submit(a)
    eng2.submit(b)
    eng2.run_until_done()
    assert a.out_tokens == solo.out_tokens


def _drive(engine_setup, ops, prompts, max_new=5, batch_size=2,
           cache_len=64):
    cfg, qp, plans = engine_setup
    eng = ServingEngine(qp, plans, cfg, batch_size=batch_size,
                        cache_len=cache_len, ops=ops)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    assert all(r.done for r in reqs)
    return eng, [r.out_tokens for r in reqs]


def test_engine_fused_decode_token_parity_across_slot_recycling(
        engine_setup):
    """Prefill-then-decode token streams must be identical between the
    `pallas_fused` (fused valid_len-masked decode kernel) and `ref`
    (full-matrix oracle) engines — across admit/evict/re-admit cycles,
    where a recycled slot's cache tail holds the previous occupant's
    stale K/V.  A stale-tail read shows up as a token divergence here
    long before any shape test would notice."""
    # 5 requests through 2 slots with different prompt lengths: every
    # slot is evicted and re-admitted at a different position at least
    # once, with ragged per-slot valid_len throughout
    prompts = [[1, 7, 42], [9, 3], [17, 2, 5, 11], [4], [23, 8, 31]]
    eng_ref, toks_ref = _drive(engine_setup, "ref", prompts)
    eng_fused, toks_fused = _drive(engine_setup, "pallas_fused", prompts)
    assert not eng_ref.decode_fused and eng_fused.decode_fused
    assert toks_fused == toks_ref


def test_describe_is_structured_with_derived_string(engine_setup):
    """describe() returns the structured dict (backend ids, decode mode,
    page-pool stats); describe_str() is derived from it — drivers print
    the string, tooling consumes the dict (no more string matching)."""
    from repro.ops import OP_NAMES

    cfg, qp, plans = engine_setup
    eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                        ops="ref")
    d = eng.describe()
    assert d["ops"] == "ref" and d["decode"] == "oracle"
    assert set(d["backends"]) == set(OP_NAMES)
    assert all(name == "ref" for name in d["backends"].values())
    assert d["cache"]["mode"] == "paged"
    for key in ("page_size", "num_pages", "pages_used", "pages_free",
                "kv_bytes", "live_tokens"):
        assert key in d["cache"], key
    # the dict is the source of truth; the one-liner derives from it
    s = eng.describe_str()
    assert "ops=ref" in s and "decode=oracle" in s and "paged" in s
    # pool stats are live: admitting a request consumes pages
    eng.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=2))
    eng.step()
    assert eng.describe()["cache"]["pages_used"] > 0
    eng.run_until_done()
    # the prefix index keeps the prompt's pages cached after the drain;
    # clearing it returns them all
    eng.prefix.clear()
    assert eng.describe()["cache"]["pages_used"] == 0

    cont = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                         ops="ref", cache_mode="contiguous")
    dc = cont.describe()
    assert dc["cache"]["mode"] == "contiguous"
    # the paged pool is provisioned lane-for-lane by default (plus the
    # null page), so it spends no less than the contiguous layout;
    # undersubscribing num_pages is where the O(live tokens) saving
    # comes from (see test_paged_decode)
    assert dc["cache"]["kv_bytes"] <= d["cache"]["kv_bytes"]


def test_engine_decode_dispatches_through_backend(engine_setup):
    """No hardcoded oracle call on the decode path: every engine step's
    attention goes through the configured backend's
    ``int_decode_attention`` (here: a recording override)."""
    from repro.ops import OpSet, get_backend

    calls = []

    class Recording:
        name = "recording-decode"
        fused_attention = False

        def __getattr__(self, op):
            return getattr(get_backend("ref"), op)

        def int_decode_attention(self, *a, **kw):
            calls.append("int_decode_attention")
            return get_backend("ref").int_decode_attention(*a, **kw)

    cfg, qp, plans = engine_setup
    opset = OpSet("ref", {"int_decode_attention": Recording()})
    eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                        ops=opset)
    eng.submit(Request(uid=0, prompt=[1, 2], max_new_tokens=2))
    eng.run_until_done()
    # dispatched at trace time (the engine jits the step): >= once
    assert calls
