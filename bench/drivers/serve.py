"""Serving cells: closed-loop clients against ``ServingFrontend`` over a
paged ``ServingEngine``.

Each of ``clients`` clients submits a request, drains its stream and
submits its next as soon as the stream ends; the requests come from
``lib/traffic.py``.  Set-up draws the weights, quantizes them, builds
the engine, serves one request whose prompt spans a prefill chunk (that
compiles the chunked-prefill and the decode step, the only two shapes
the engine runs), and, where the mix has documents, serves each document
once so the prefix cache holds it.  The ramp then starts every client;
the window opens once every lane has emitted its first token and lasts
``--seconds``.  All timing is client-side (``time.perf_counter`` as each
token arrives in its stream):

  * ``output_tokens_per_s``: tokens that arrived in the window over it;
  * ``ttft_p50_ms``: median submit-to-first-token time of the requests
    whose first token arrived in the window;
  * ``itl_p99_ms``: 99th percentile of every gap between two tokens of a
    request, the later one in the window.

Correctness: a sample of the requests that completed, drawn from the
seed and holding the one with the most tokens, at least
``sample_tokens`` served tokens in all.  Once the engine is freed the
plain reference runs each prompt with its served tokens and reads the
top-1 gap of every served token (``lib/compare.py``).

The per-layer readers take the engine's own counts (``describe()``) and
two records of the benchmark's: every chunked-prefill launch (lanes,
base positions, real tokens) and every decode launch (live lanes and
their positions).  Both records wrap private attributes of the engine,
``_prefill_step`` and ``_decode``: the program has no counters for them
yet (see PERF.md, Open questions).
"""
from __future__ import annotations

import asyncio
import os
import shutil
import time
from typing import Dict, List

import numpy as np

import compare
import costs
import devtrace
import harness
import traffic
from peaks import peaks


def program_config(cell):
    """The program's ``ArchConfig`` at the sizes the file states."""
    import dataclasses
    from repro.configs.registry import get_config
    g = cell.graph
    cfg = get_config(cell.config["deployment"]["arch"])
    return dataclasses.replace(
        cfg, num_layers=g["num_layers"], d_model=g["d_model"],
        n_heads=g["n_heads"], n_kv_heads=g["n_kv_heads"],
        head_dim=g["head_dim"], d_ff=g["d_ff"], vocab=g["vocab_size"])


class Recorder:
    """Host records of the engine's launches (see the module docstring)."""

    def __init__(self, eng):
        self.eng = eng
        self.prefill: List[list] = []     # [(base, real tokens), ...]
        self.decode: List[list] = []      # [position, ...]
        self.tracing = False
        self.traced_prefill: List[list] = []
        self.traced_decode: List[list] = []
        # the jitted steps with their last arguments, to read their
        # compiled programs for the trace reduction
        self.calls: Dict[str, tuple] = {}
        pf, dec = eng._prefill_step, eng._decode

        def prefill_step(qp, caches, toks, base, view):
            t, b = np.asarray(toks), np.asarray(base)
            real = (t != 0).sum(axis=1)
            rec = [(int(b[i]), int(real[i])) for i in range(len(real))
                   if real[i]]
            self.prefill.append(rec)
            if self.tracing:
                self.traced_prefill.append(rec)
                self.calls["prefill"] = (pf, shapes(qp, caches, toks, base,
                                                    view))
            return pf(qp, caches, toks, base, view)

        def decode(*args):
            rec = [int(eng.pos[i]) for i, s in enumerate(eng.slots)
                   if s is not None and s.state == "active"]
            self.decode.append(rec)
            if self.tracing:
                self.traced_decode.append(rec)
                self.calls["decode"] = (dec, shapes(*args))
            return dec(*args)
        eng._prefill_step, eng._decode = prefill_step, decode


def shapes(*args):
    """Abstract stand-ins of ``args``: enough to lower a step again."""
    import jax
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=getattr(x, "sharding", None)), args)


class Program:
    """The system under test at one seed: the engine over quantized
    weights, compiled and warmed, with the cell's requests."""

    def __init__(self, cell, seed: int, setup=None):
        import jax
        from repro.quant import convert
        from repro.serving import ServingEngine
        g, dep = cell.graph, cell.config["deployment"]
        self.cell, self.seed = cell, seed
        cfg = program_config(cell)
        ref = cell.reference()
        self.wkey = compare.seed_key(seed)
        params = jax.jit(lambda k: ref.make_weights(k, g))(self.wkey)
        jax.block_until_ready(params)
        setup and setup.mark("weights")
        qp, plans = convert.quantize_params(params, cfg)
        del params
        jax.block_until_ready(qp)
        setup and setup.mark("quantize")
        self.eng = ServingEngine(
            qp, plans, cfg, batch_size=dep["lanes"],
            cache_len=dep["cache_len"], ops=dep["backend"],
            cache_mode="paged", page_size=dep["page_size"],
            prefill_chunk=dep["prefill_chunk"],
            prefix_cache=dep["prefix_cache"])
        del qp
        self.traffic = traffic.closed_loop(cell.traffic, seed,
                                           g["vocab_size"])
        setup and setup.mark("build")
        from repro.serving.engine import Request
        rng = traffic.rng_for(seed, 1)
        warm = rng.integers(1, g["vocab_size"], dep["prefill_chunk"] + 1)
        self.eng.submit(Request(uid=-1, prompt=warm.tolist(),
                                max_new_tokens=2))
        self.eng.run_until_done()
        setup and setup.mark("compile_warm")
        for i, doc in enumerate(self.traffic["documents"]):
            self.eng.submit(Request(uid=-2 - i, prompt=doc + [1],
                                    max_new_tokens=1))
        self.eng.run_until_done()
        setup and setup.mark("prefix_warmup")
        self.rec = Recorder(self.eng)


class Client:
    def __init__(self, reqs):
        self.reqs = reqs
        self.started = False              # its first token has come
        self.done: List[dict] = []        # completed requests

    async def loop(self, fe, state: dict):
        for prompt, max_new in self.reqs:
            if state["stop"]:
                return
            submit = time.perf_counter()
            h = fe.submit(prompt, max_new)
            state["handles"].append(h)
            times = []
            async for _ in h.stream():
                times.append(time.perf_counter())
                self.started = True
            r = {"prompt": prompt, "tokens": h.tokens, "submit": submit,
                 "times": times, "terminal": h.terminal}
            state["requests"].append(r)
            if h.terminal == "completed":
                self.done.append(r)


async def serve(prog: Program, seconds: float, trace_dir, trace_s: float,
                counter) -> dict:
    """Ramp, window and wind-down; returns the window's bounds and
    every request the clients saw."""
    import jax
    from repro.serving import ServingFrontend
    eng, rec = prog.eng, prog.rec
    fe = ServingFrontend(eng)
    clients = [Client(c) for c in prog.traffic["clients"]]
    state = {"stop": False, "handles": [], "requests": []}
    runner = asyncio.create_task(fe.run())
    tasks = [asyncio.create_task(c.loop(fe, state)) for c in clients]
    while not all(c.started for c in clients):
        await asyncio.sleep(0.001)
    counter.armed = True
    t_open = time.perf_counter()
    px0 = dict(eng.describe()["cache"]["prefix"] or {})
    n_pf0, n_dec0 = len(rec.prefill), len(rec.decode)
    dispatch, commit = eng.dispatch_step, eng.commit_step
    sl = {"span": None, "stopped": False}

    def traced_dispatch():
        # the traced slice starts and ends on step boundaries
        if trace_dir and sl["span"] is None and not sl["stopped"]:
            jax.profiler.start_trace(trace_dir)
            sl["span"] = jax.profiler.TraceAnnotation(devtrace.SLICE)
            sl["span"].__enter__()
            sl["t0"] = time.perf_counter()
            rec.tracing = True
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            return dispatch()

    def traced_commit(p):
        with jax.profiler.TraceAnnotation("bench.commit"):
            out = commit(p)
        if sl["span"] is not None and \
                time.perf_counter() - sl["t0"] >= trace_s:
            rec.tracing = False
            sl["span"].__exit__(None, None, None)
            sl["span"], sl["stopped"] = None, True
            jax.profiler.stop_trace()
        return out
    eng.dispatch_step, eng.commit_step = traced_dispatch, traced_commit
    await asyncio.sleep(seconds)
    t_close = time.perf_counter()
    counter.armed = False
    px1 = dict(eng.describe()["cache"]["prefix"] or {})
    n_pf1, n_dec1 = len(rec.prefill), len(rec.decode)
    state["stop"] = True
    for h in list(state["handles"]):
        h.cancel()
    await asyncio.gather(*tasks)
    eng.dispatch_step, eng.commit_step = dispatch, commit
    if sl["span"] is not None:
        sl["span"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    fe.close()
    await runner
    return {"open": t_open, "close": t_close, "clients": clients,
            "requests": state["requests"], "prefix": (px0, px1),
            "prefill": rec.prefill[n_pf0:n_pf1],
            "decode": rec.decode[n_dec0:n_dec1]}


def window_metrics(w: dict) -> Dict[str, float]:
    lo, hi = w["open"], w["close"]
    toks, ttft, gaps = 0, [], []
    for r in w["requests"]:
        t = r["times"]
        toks += sum(lo <= x <= hi for x in t)
        if t and lo <= t[0] <= hi:
            ttft.append(t[0] - r["submit"])
        gaps += [b - a for a, b in zip(t, t[1:]) if lo <= b <= hi]
    return {"output_tokens_per_s": toks / (hi - lo),
            "ttft_p50_ms": 1e3 * float(np.percentile(ttft, 50)),
            "itl_p99_ms": 1e3 * float(np.percentile(gaps, 99)),
            "n_ttft": len(ttft), "n_gaps": len(gaps), "tokens": toks}


def sample(done: List[dict], seed: int, tokens: int) -> List[dict]:
    """Completed requests drawn from the seed, the longest among them,
    until ``tokens`` served tokens are in."""
    if not done:
        return []
    rng = traffic.rng_for(seed, 2)
    longest = max(range(len(done)), key=lambda i: len(done[i]["tokens"]))
    order = [longest] + [i for i in rng.permutation(len(done))
                         if i != longest]
    out, n = [], 0
    for i in order:
        out.append(done[i])
        n += len(done[i]["tokens"])
        if n >= tokens:
            break
    return out


def gaps(cell, seed: int, reqs: List[dict], bits=None, program=True):
    """Top-1 gap of every served token of ``reqs`` against the reference
    run over the prompt and the served tokens.  ``program=False``: the
    control's picks, the reference at ``bits`` on the same tokens."""
    import jax
    import jax.numpy as jnp
    ref, g = cell.reference(), cell.graph
    params = jax.jit(lambda k: ref.make_weights(k, g))(
        compare.seed_key(seed))

    def at(p, seq, lo, n, q):
        hs = ref.hidden(p, seq, g, q)
        return ref.logits(p, jax.lax.dynamic_slice_in_dim(hs, lo, n), g, q)
    # the weights go in as an argument: closed over, they would be
    # compiled in as constants
    f = jax.jit(at, static_argnums=(3, 4))
    out = []
    with jax.default_matmul_precision("highest"):
        for r in reqs:
            seq = r["prompt"] + r["tokens"][:-1]
            n, lo = len(r["tokens"]), len(r["prompt"]) - 1
            # pad the rows read to a multiple of 64 and the sequence to
            # one of 256, so few shapes compile, and so that the slice
            # of rows [lo, lo + n + n_pad) lies inside the sequence (a
            # slice past its end would be moved back); the causal
            # reference never lets a padded position reach a real one
            n_pad = -n % 64
            pad = -(len(seq) + n_pad) % 256 + n_pad
            arr = jnp.asarray(seq + [0] * pad, jnp.int32)
            want = np.asarray(f(params, arr, lo, n + n_pad, None))[:n]
            if program:
                picks = np.asarray(r["tokens"])
            else:
                picks = np.asarray(f(params, arr, lo, n + n_pad, bits))[:n] \
                    .argmax(-1)
            out.append(compare.top1_gaps(want, picks))
    return np.concatenate(out) if out else np.zeros(0)


def run(cell, seed: int, seconds: float, trace: bool, setup, devices,
        wrap=None):
    import jax
    prog = Program(cell, seed, setup)
    if wrap is not None:
        wrap(prog)
    lim = cell.limits
    counter = harness.CompileCounter()
    trace_dir = os.path.join(harness.OUT, "trace", cell.name) \
        if trace else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    t_ramp = time.perf_counter()
    w = asyncio.run(serve(prog, seconds, trace_dir,
                          cell.traffic.get("trace_seconds", 3.0), counter))
    # the ramp, from the clients' start to the window's opening, is set-up
    setup.parts["ramp"] = w["open"] - t_ramp
    setup_s = setup.total + setup.parts["ramp"]
    harness.log("setup_s split: " + ", ".join(
        f"{k} {v:.3f}" for k, v in setup.parts.items()))
    if counter.count:
        raise SystemExit("bench: a program was built inside the window")
    m = window_metrics(w)
    thirds = [sum(w["open"] + k * (w["close"] - w["open"]) / 3 <= r["times"][-1]
                  < w["open"] + (k + 1) * (w["close"] - w["open"]) / 3
                  for r in w["requests"] if r["times"]
                  and r["terminal"] == "completed") for k in range(3)]
    harness.log(f"window: {m['tokens']} tokens, {m['n_ttft']} first tokens,"
                f" {m['n_gaps']} gaps; completions by third {thirds}; "
                f"{len(w['prefill'])} prefill and {len(w['decode'])} "
                "decode launches")
    mem = harness.memory_peak(devices)
    done = [r for c in w["clients"] for r in c.done]
    picked = sample(done, seed, lim["sample_tokens"])
    rec, eng = prog.rec, prog.eng
    fold = eng.fold_wo
    if trace:
        programs = [devtrace.Program.from_hlo(n, f.lower(*a).compile()
                                              .as_text())
                    for n, (f, a) in rec.calls.items()]
    lanes, chunk = eng.batch, eng.prefill_chunk
    del prog, eng
    gap = gaps(cell, seed, picked)
    harness.log(f"compared {gap.size} served tokens of {len(picked)} "
                "requests")
    attempted = len(w["requests"])
    failed = sum(r["terminal"] not in ("completed", "cancelled")
                 for r in w["requests"])
    out = {"end_to_end": {k: m[k] for k in (
                "output_tokens_per_s", "ttft_p50_ms", "itl_p99_ms")},
           "checks": compare.checks(gap, lim),
           "attempted": attempted, "failed": failed, "memory_peak": mem}
    out["end_to_end"]["setup_s"] = setup_s
    # host counts over the window, for the scheduler and cache readers
    px0, px1 = w["prefix"]
    prompt_toks = sum(len(r["prompt"]) - 1 for r in w["requests"]
                      if w["open"] <= r["submit"] <= w["close"])
    out["counts"] = {
        "prompt_tokens": prompt_toks,
        "reused_tokens": px1.get("tokens_reused", 0)
        - px0.get("tokens_reused", 0),
        "prefill_real": sum(r for rnd in w["prefill"] for _, r in rnd),
        "prefill_slots": len(w["prefill"]) * lanes * chunk}
    if trace:
        g = cell.graph
        out["peak"] = peaks(devices[0].device_kind)
        out["slice"] = devtrace.reduce(devtrace.load(trace_dir),
                                       [d.id for d in devices], programs)
        # the launches the trace holds: where the profiler missed some at
        # the slice's start, each program's recorded work is scaled to
        # the executions the trace shows
        work: Dict[str, object] = {}
        for name, recs, fn in (
                ("decode", rec.traced_decode,
                 lambda r: costs.decode_step(g, r, fold_wo=fold)),
                ("prefill", rec.traced_prefill,
                 lambda r: costs.prefill_chunk(g, r, fold_wo=fold))):
            part: Dict[str, object] = {}
            for r in recs:
                add(part, fn(r))
            k = out["slice"].count(name) / len(recs) if recs else 0.0
            add(work, {key: (v[0] * k, v[1] * k) if isinstance(v, tuple)
                       else v * k for key, v in part.items()})
        out["slice_work"] = work
    return out


def add(acc: dict, work: dict):
    for k, v in work.items():
        if isinstance(v, tuple):
            a = acc.get(k, (0, 0))
            acc[k] = (a[0] + v[0], a[1] + v[1])
        else:
            acc[k] = acc.get(k, 0) + v
