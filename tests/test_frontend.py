"""Async serving front end: streaming bit-exactness, lifecycle
(cancel / deadline / backpressure), typed admission errors, the
dispatch/commit step split, and the metrics surface.

The load-bearing claim is that the front end never touches the
datapath: a request streamed through ``ServingFrontend`` — under
concurrency, cancellation of its batch neighbours, speculative
decoding, sharding — must produce the byte-identical token stream of a
solo synchronous ``run_until_done`` of the same prompt.  The matrix
test pins that across {ref, pallas_fused} x {paged, contiguous} x
spec_k in {0, 2} with 16 concurrent streams; the lifecycle tests pin
refcount-exact page reclaim on cancel/timeout (mid-prefill and
mid-decode) against the allocator's own accounting.

Random arrival/cancel/timeout schedules live in
``test_frontend_props.py``; both modules run in the multi-device CI
matrix, so lifecycle ops are exercised under tp > 1 as well.
"""
import asyncio
import collections

import jax
import numpy as np
import pytest

from repro.analysis.contracts import RequestInfeasible
from repro.configs.registry import get_config
from repro.models import model as M
from repro.models import transformer as tf
from repro.quant import convert
from repro.serving import (EngineStalled, QueueFull, Request,
                           ServingEngine, ServingFrontend, StepInFlight)

MAX_NEW = 4


@pytest.fixture(scope="module")
def setup():
    cfg = M.reduce_config(get_config("llama3-8b"), dtype="float32",
                          vocab=128, num_layers=1)
    params = tf.init_params(jax.random.key(0), cfg)
    qp, plans = convert.quantize_params(params, cfg)
    return cfg, qp, plans, {}               # {} = expected-stream cache


def _prompts(n=16):
    rng = np.random.default_rng(7)
    stem = [int(t) for t in rng.integers(1, 100, 12)]
    out = []
    for i in range(n):
        if i % 3 == 0:
            out.append(stem[: 4 + (i % 8)] + [101 + i])  # shared prefix
        else:
            out.append([int(t)
                        for t in rng.integers(1, 100, 3 + (i % 9))])
    return out


def _expected(setup, prompt, max_new=MAX_NEW):
    """Solo synchronous greedy reference (contiguous, ref ops) —
    memoized across tests."""
    cfg, qp, plans, cache = setup
    key = (tuple(prompt), max_new)
    if key not in cache:
        eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                            ops="ref", cache_mode="contiguous")
        req = Request(uid=0, prompt=list(prompt), max_new_tokens=max_new)
        eng.submit(req)
        eng.run_until_done()
        cache[key] = list(req.out_tokens)
    return cache[key]


def _check_refcounts(eng, sessions):
    eng.kv.allocator.check()
    held = collections.Counter()
    for sess in sessions:
        held.update(sess.pages)
    if eng.prefix is not None:
        for entry in eng.prefix.entries.values():
            held.update(entry.pages)
    for page in range(1, eng.layout.num_pages):
        assert eng.kv.allocator.refcount[page] == held.get(page, 0), \
            f"page {page}: refcount {eng.kv.allocator.refcount[page]} " \
            f"vs holders {held.get(page, 0)}"


def _frontend(setup, batch_size=4, cache_len=64, **kw):
    cfg, qp, plans, _ = setup
    fe_kw = {k: kw.pop(k) for k in ("max_pending", "clock", "stall_steps")
             if k in kw}
    eng = ServingEngine(qp, plans, cfg, batch_size=batch_size,
                        cache_len=cache_len, ops=kw.pop("ops", "ref"),
                        **kw)
    return ServingFrontend(eng, **fe_kw)


# ---------------------------------------------------------------------------
# streaming bit-exactness


@pytest.mark.parametrize("ops,engine_kw", [
    ("ref", dict(cache_mode="paged")),
    ("ref", dict(cache_mode="contiguous")),
    ("pallas_fused", dict(cache_mode="paged")),
    ("pallas_fused", dict(cache_mode="contiguous")),
])
@pytest.mark.parametrize("spec_k", [0, 2])
def test_16_concurrent_streams_bit_exact(setup, ops, engine_kw, spec_k):
    """16 requests streamed concurrently through the async front end
    must each reproduce the solo synchronous reference stream — across
    backend x cache mode x speculation."""
    prompts = _prompts(16)

    async def main():
        fe = _frontend(setup, ops=ops, spec_k=spec_k,
                       max_pending=32, **engine_kw)
        runner = asyncio.create_task(fe.run())
        handles = [fe.submit(p, MAX_NEW) for p in prompts]
        streams = await asyncio.gather(
            *[h.result() for h in handles])
        fe.close()
        await runner
        return fe, handles, streams

    fe, handles, streams = asyncio.run(main())
    for h, toks, prompt in zip(handles, streams, prompts):
        assert h.terminal == "completed"
        assert toks == _expected(setup, prompt), prompt
    d = fe.describe()
    assert d["terminal"]["completed"] == 16
    assert d["pending"] == 0 and d["submitted"] == 16
    if fe.engine.paged:
        _check_refcounts(fe.engine, [h.session for h in handles])


def test_streaming_is_incremental(setup):
    """Tokens arrive per engine step, not in one burst at completion:
    a consumer sees the first token while its request is still live."""

    async def main():
        fe = _frontend(setup, batch_size=2)
        h = fe.submit([3, 1, 4], max_new_tokens=6)
        runner = asyncio.create_task(fe.run())
        states = []
        async for _ in h.stream():
            states.append(h.state)
        fe.close()
        await runner
        return states

    states = asyncio.run(main())
    assert len(states) == 6
    assert states[0] == "active"            # mid-generation, not done


def test_frontend_tp2_streams_match_solo(setup, gathered_backend):
    """Lifecycle ops compose with a tp=2 engine: frontend streams match
    the unsharded solo reference — sharded over ``ref`` where the
    process has two devices (the 4-device CI matrix), else the exact
    gathered lowering of a backend without ``tp_serving`` (a sharded
    engine on one device is refused, not silently gathered)."""
    prompts = _prompts(6)
    ops = "ref" if jax.device_count() >= 2 else gathered_backend

    async def main():
        fe = _frontend(setup, tp=2, ops=ops, max_pending=8)
        runner = asyncio.create_task(fe.run())
        handles = [fe.submit(p, MAX_NEW) for p in prompts]
        streams = await asyncio.gather(*[h.result() for h in handles])
        fe.close()
        await runner
        return streams

    for toks, prompt in zip(asyncio.run(main()), prompts):
        assert toks == _expected(setup, prompt), prompt


# ---------------------------------------------------------------------------
# lifecycle: cancel / deadline / backpressure


def test_cancel_mid_decode_releases_pages_exactly(setup):
    """Cancel a decoding request: its stream ends with terminal
    'cancelled', its pages return to the allocator, and the surviving
    neighbour's stream is untouched."""

    async def main():
        fe = _frontend(setup, batch_size=2, page_size=8)
        victim = fe.submit([9, 9, 2], max_new_tokens=32)
        keeper = fe.submit([3, 1, 4], max_new_tokens=6)
        while victim.metrics.n_tokens < 2:
            await fe.step()
        assert victim.state == "active"
        victim.cancel()
        await fe.step()                     # applied at the boundary
        assert victim.terminal == "cancelled"
        while await fe.step():
            pass
        keep = await keeper.result()        # queue already drained: EOS
        return fe, victim, keeper, keep

    fe, victim, keeper, keep = asyncio.run(main())
    assert 2 <= len(victim.tokens) < 32
    assert victim.tokens == _expected(setup, [9, 9, 2], 32)[
        : len(victim.tokens)]               # a prefix of the reference
    assert keep == _expected(setup, [3, 1, 4], 6)
    assert keeper.terminal == "completed"
    _check_refcounts(fe.engine, [victim.session, keeper.session])


def test_cancel_mid_prefill_releases_pages_exactly(setup):
    """Cancel while the prompt is still prefilling (prefill_budget
    stretches it over many steps): the half-prefilled pages must all
    come back."""
    prompt = [int(t) for t in
              np.random.default_rng(11).integers(1, 100, 40)]

    async def main():
        fe = _frontend(setup, batch_size=2, page_size=8,
                       prefill_budget=4, prefix_cache=False)
        h = fe.submit(prompt, max_new_tokens=4)
        await fe.step()
        assert h.state == "prefilling"
        h.cancel()
        await fe.step()
        return fe, h

    fe, h = asyncio.run(main())
    assert h.terminal == "cancelled" and h.tokens == []
    assert fe.engine.kv.allocator.used_pages == 0   # all pages came back
    _check_refcounts(fe.engine, [h.session])


def test_cancel_queued_request_never_admitted(setup):
    """A request cancelled while still queued (no lane, no pages) ends
    'cancelled' without the engine ever touching it."""

    async def main():
        fe = _frontend(setup, batch_size=2)
        hogs = [fe.submit([7 + i, 5], max_new_tokens=8)
                for i in range(2)]
        queued = fe.submit([1, 2, 3], max_new_tokens=4)
        await fe.step()
        assert queued.state == "queued"
        queued.cancel()
        await fe.step()
        assert queued.terminal == "cancelled"
        while await fe.step():
            pass
        return fe, hogs, queued

    fe, hogs, queued = asyncio.run(main())
    assert queued.tokens == []
    assert all(h.terminal == "completed" for h in hogs)
    _check_refcounts(fe.engine,
                     [h.session for h in hogs] + [queued.session])


def test_deadline_expiry_times_out(setup):
    """An expired deadline_s evicts the request with terminal 'timeout'
    — driven by an injected fake clock, so no real waiting."""
    t = [0.0]

    async def main():
        fe = _frontend(setup, batch_size=2, clock=lambda: t[0])
        slow = fe.submit([9, 9, 2], max_new_tokens=48, deadline_s=5.0)
        fast = fe.submit([3, 1, 4], max_new_tokens=6)
        while slow.metrics.n_tokens < 1:
            await fe.step()
        t[0] = 4.9
        await fe.step()
        assert slow.terminal is None        # not yet expired
        t[0] = 5.0
        await fe.step()
        assert slow.terminal == "timeout"
        while await fe.step():
            pass
        return fe, slow, fast

    fe, slow, fast = asyncio.run(main())
    assert 1 <= len(slow.tokens) < 48       # partial stream kept
    assert fast.terminal == "completed"
    assert fast.tokens == _expected(setup, [3, 1, 4], 6)
    _check_refcounts(fe.engine, [slow.session, fast.session])
    assert fe.describe()["terminal"]["timeout"] == 1


def test_queue_full_backpressure(setup):
    """Past max_pending, submit() raises typed QueueFull and counts the
    rejection; capacity frees once requests finish."""

    async def main():
        fe = _frontend(setup, batch_size=2, max_pending=3)
        handles = [fe.submit([5 + i, 9], max_new_tokens=2)
                   for i in range(3)]
        with pytest.raises(QueueFull) as exc:
            fe.submit([1, 2], max_new_tokens=2)
        assert exc.value.max_pending == 3 and exc.value.pending == 3
        while await fe.step():
            pass
        late = fe.submit([1, 2], max_new_tokens=2)   # capacity is back
        while await fe.step():
            pass
        return fe, handles, late

    fe, handles, late = asyncio.run(main())
    assert all(h.terminal == "completed" for h in handles + [late])
    d = fe.describe()
    assert d["terminal"]["rejected"] == 1
    assert d["submitted"] == 5
    assert sum(d["terminal"].values()) == d["submitted"]


# ---------------------------------------------------------------------------
# typed admission errors


def test_infeasible_request_rejected_at_submit(setup):
    """prompt + max_new_tokens overrunning cache_len is a typed error
    at submit() — frontend and bare engine alike — not a failure deep
    inside a step."""
    cfg, qp, plans, _ = setup
    fe = _frontend(setup, batch_size=2, cache_len=32)
    with pytest.raises(RequestInfeasible, match="exceeds the"):
        fe.submit([1] * 8, max_new_tokens=64)       # 8-1+64 > 32
    assert fe.describe()["terminal"]["rejected"] == 1
    eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=32)
    with pytest.raises(RequestInfeasible):
        eng.submit(Request(uid=0, prompt=[1] * 8, max_new_tokens=64))
    # the boundary case is admissible: prompt fills the cache, prefill
    # writes len-1 positions, the last decode lands exactly at the end
    h = fe.submit([1] * 8, max_new_tokens=32 - 8 + 1)
    assert h.state == "queued"
    with pytest.raises(RequestInfeasible):
        fe.submit([1] * 8, max_new_tokens=32 - 8 + 2)
    with pytest.raises(RequestInfeasible, match="empty prompt"):
        fe.submit([], max_new_tokens=4)


def test_never_fits_pool_rejected_at_frontend_submit(setup):
    """A prompt needing more pages than the pool can ever provide is
    RequestInfeasible at the *frontend* boundary; the bare engine keeps
    its legacy contract (admit, then typed PagePoolExhausted from the
    step), so the frontend check is strictly earlier."""
    fe = _frontend(setup, batch_size=2, cache_len=64, page_size=8,
                   num_pages=4)            # 3 usable pages = 24 tokens
    with pytest.raises(RequestInfeasible, match="pages but the pool"):
        fe.submit([1] * 30, max_new_tokens=2)
    h = fe.submit([1] * 20, max_new_tokens=2)       # 3 pages: fits
    assert h.state == "queued"


# ---------------------------------------------------------------------------
# engine step split (dispatch / commit)


@pytest.mark.parametrize("spec_k", [0, 2])
def test_dispatch_commit_split_matches_step(setup, spec_k):
    """step() == commit_step(dispatch_step()) by construction; driving
    the halves explicitly produces the same streams."""
    cfg, qp, plans, _ = setup
    prompts = _prompts(4)

    def run(split):
        eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                            ops="ref", spec_k=spec_k)
        reqs = [Request(uid=i, prompt=list(p), max_new_tokens=MAX_NEW)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        for _ in range(400):
            if not eng.queue and all(s is None for s in eng.slots):
                break
            if split:
                eng.commit_step(eng.dispatch_step())
            else:
                eng.step()
        return [r.out_tokens for r in reqs]

    assert run(split=True) == run(split=False)


def test_step_in_flight_guards_lifecycle_ops(setup):
    """evict/preempt between dispatch and commit is a typed error —
    the launch captured the session state; mutating it mid-flight
    would commit against stale snapshots."""
    cfg, qp, plans, _ = setup
    eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                        ops="ref")
    sess = eng.submit(Request(uid=0, prompt=[3, 1, 4],
                              max_new_tokens=8))
    eng.step()                              # prefill; decoding now
    pending = eng.dispatch_step()
    with pytest.raises(StepInFlight):
        eng.evict(sess)
    with pytest.raises(StepInFlight):
        eng.dispatch_step()
    eng.commit_step(pending)
    eng.evict(sess)                         # legal again after commit
    with pytest.raises(StepInFlight):       # stale pending is typed too
        eng.commit_step(pending)


def test_frontend_stall_detection_raises_typed(setup):
    """The front end carries run_until_done's EngineStalled contract:
    consecutive no-progress steps with work still queued raise instead
    of spinning forever."""
    fe = _frontend(setup, batch_size=2, stall_steps=2)
    fe.submit([3, 1, 4], max_new_tokens=2)
    stamp = fe._progress_stamp()
    fe._check_stall(stamp)                  # 1st no-progress step: armed
    with pytest.raises(EngineStalled):
        fe._check_stall(stamp)


# ---------------------------------------------------------------------------
# metrics surface


def test_describe_metrics_surface(setup):
    """describe() exposes the full lifecycle-metrics contract: latency
    percentiles (p50 <= p99), occupancy/queue-depth aggregates, and
    terminal accounting summing to submitted."""

    async def main():
        fe = _frontend(setup, batch_size=2, max_pending=4)
        handles = [fe.submit(p, MAX_NEW) for p in _prompts(4)]
        runner = asyncio.create_task(fe.run())
        await asyncio.gather(*[h.result() for h in handles])
        fe.close()
        await runner
        return fe, handles

    fe, handles = asyncio.run(main())
    d = fe.describe()
    for metric in ("ttft_s", "inter_token_s", "queue_wait_s"):
        p = d["latency"][metric]
        assert p["n"] > 0 and p["p50"] <= p["p99"] and p["mean"] >= 0
    assert d["occupancy"]["max"] <= fe.engine.batch
    assert d["queue_depth"]["max"] >= 2     # 4 requests through 2 lanes
    assert sum(d["terminal"].values()) + d["pending"] == d["submitted"]
    for h in handles:
        m = h.metrics
        assert m.ttft_s is not None and m.ttft_s >= 0
        assert m.queue_wait_s is not None and m.queue_wait_s <= m.ttft_s
        assert m.tbt_s is not None and m.n_tokens == MAX_NEW
