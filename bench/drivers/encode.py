"""Encoder cells: a closed loop of ``int_prefill`` calls.

One client sends a batch of ``batch`` rows of ``seq_len`` tokens, waits
for its logits, and sends the next; every batch is drawn on the device
from the seed, so no two are alike and the host sends nothing but an
index.  Set-up draws the weights, quantizes them with the program's
``quantize_params``, compiles the one call shape and runs it once.  The
window then runs whole calls until ``--seconds`` have passed;
``encode_tokens_per_s`` is every token of every call over the time they
took together.

Correctness: a sample of the window's calls, drawn from the seed by
reservoir sampling, keeps its logits.  Once the window has closed and the
program's state is freed, the plain reference recomputes those rows from
the same floats and token ids, and the widest top-1 gap is compared
(``lib/compare.py``).
"""
from __future__ import annotations

import os
import shutil
import time

import numpy as np

import compare
import costs
import devtrace
import harness
from peaks import peaks


def program_config(cell):
    """The program's ``ArchConfig`` for the cell: its registered
    architecture at the sizes the configuration file states."""
    import dataclasses
    from repro.configs.registry import get_config
    g = cell.graph
    cfg = get_config(cell.config["deployment"]["arch"])
    return dataclasses.replace(
        cfg, num_layers=g["num_layers"], d_model=g["d_model"],
        n_heads=g["n_heads"], n_kv_heads=g["n_kv_heads"],
        head_dim=g["head_dim"], d_ff=g["d_ff"], vocab=g["vocab_size"])


class Program:
    """The system under test at one seed: quantized weights, the jitted
    encoder call, and the device-side batch generator."""

    def __init__(self, cell, seed: int, setup=None):
        import jax
        from repro.models import inttransformer as it
        from repro.quant import convert
        self.cell, self.seed = cell, seed
        g, tr = cell.graph, cell.traffic
        self.batch, self.seq = tr["batch"], tr["seq_len"]
        cfg = program_config(cell)
        ref = cell.reference()
        self.wkey, tkey = jax.random.split(compare.seed_key(seed))
        params = jax.jit(lambda k: ref.make_weights(k, g))(self.wkey)
        jax.block_until_ready(params)
        setup and setup.mark("weights")
        self.qp, plans = convert.quantize_params(params, cfg)
        del params
        jax.block_until_ready(self.qp)
        setup and setup.mark("quantize")
        ops = cell.config["deployment"]["backend"]
        self.encode = jax.jit(lambda q, t: it.int_prefill(
            q, {"tokens": t}, plans, cfg, ops=ops))
        b, s, v = self.batch, self.seq, g["vocab_size"]
        self.tokens = jax.jit(lambda i: jax.random.randint(
            jax.random.fold_in(tkey, i), (b, s), 1, v))
        setup and setup.mark("build")
        self.call(0).block_until_ready()
        setup and setup.mark("compile_warm")

    def call(self, i: int):
        import jax.numpy as jnp
        return self.encode(self.qp, self.tokens(jnp.int32(i)))


def window(prog: Program, seconds: float, keep: int, rng,
           trace_dir=None, trace_s: float = 0.0):
    """Whole calls until ``seconds`` have passed; with ``trace_dir``, the
    calls of the first ``trace_s`` seconds are traced.  Returns (calls,
    elapsed, kept {call index: logits}, calls in the traced slice)."""
    import jax
    kept, n = {}, [0]

    def until(t_end):
        while True:
            i = n[0]
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                out = prog.call(i)
            with jax.profiler.TraceAnnotation("bench.wait"):
                out.block_until_ready()
            # reservoir sample of ``keep`` calls, drawn from the seed
            if i < keep:
                kept[i] = out
            else:
                j = int(rng.integers(0, i + 1))
                if j < keep:
                    kept.pop(sorted(kept)[j])
                    kept[i] = out
            n[0] = i + 1
            if time.perf_counter() >= t_end:
                return

    traced = 0
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    if trace_dir:
        with jax.profiler.TraceAnnotation(devtrace.SLICE):
            until(t0 + min(trace_s, seconds))
        jax.profiler.stop_trace()
        traced = n[0]
    if time.perf_counter() < t0 + seconds:
        until(t0 + seconds)
    return n[0], time.perf_counter() - t0, kept, traced


def gaps(cell, seed: int, kept: dict, bits=None, program=True):
    """Top-1 gaps of the kept calls' rows against the reference.  With
    ``program=False`` the picks are the control's: the reference computed
    at ``bits``."""
    import jax
    ref = cell.reference()
    g = cell.graph
    wkey, tkey = jax.random.split(compare.seed_key(seed))
    params = jax.jit(lambda k: ref.make_weights(k, g))(wkey)
    b, s, v = cell.traffic["batch"], cell.traffic["seq_len"], g["vocab_size"]
    toks = jax.jit(lambda i: jax.random.randint(
        jax.random.fold_in(tkey, i), (b, s), 1, v))
    block = cell.limits.get("reference_rows", 8)

    def last_logits(p, rows, q):
        hs = jax.vmap(lambda t: ref.hidden(p, t, g, q)[-1])(rows)
        return ref.logits(p, hs, g, q)
    # the weights go in as arguments: closed over, they would be
    # compiled in as constants
    f_ref = jax.jit(lambda p, r: last_logits(p, r, None))
    f_ctl = jax.jit(lambda p, r: last_logits(p, r, bits))
    with jax.default_matmul_precision("highest"):
        out = []
        for i in sorted(kept):
            rows = np.asarray(toks(np.int32(i)))
            want = compare.in_blocks(lambda r: f_ref(params, r), rows,
                                     block)
            if program:
                picks = np.asarray(kept[i])[:, :v].argmax(-1)
            else:
                picks = compare.in_blocks(lambda r: f_ctl(params, r), rows,
                                          block).argmax(-1)
            out.append(compare.top1_gaps(want, picks))
    return np.concatenate(out)


def run(cell, seed: int, seconds: float, trace: bool, setup, devices,
        wrap=None):
    """One run; ``wrap(prog, call)`` may put another computation in the
    program's place (the control, or a planted fault, in the tests)."""
    prog = Program(cell, seed, setup)
    if wrap is not None:
        prog.call = wrap(prog, prog.call)
        prog.call(0).block_until_ready()
    tr = cell.traffic
    rng = np.random.default_rng([seed % (1 << 63), 1])
    counter = harness.CompileCounter()
    trace_dir = os.path.join(harness.OUT, "trace", cell.name) \
        if trace else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    setup.mark("ramp")
    setup_s = setup.total
    harness.log("setup_s split: " + ", ".join(
        f"{k} {v:.3f}" for k, v in setup.parts.items()))
    counter.armed = True
    calls, elapsed, kept, traced = window(
        prog, seconds, cell.limits["sampled_calls"], rng, trace_dir,
        tr.get("trace_seconds", 2.0))
    counter.armed = False
    harness.log(f"window: {calls} calls in {elapsed:.3f} s; programs built "
                f"in the window: {counter.count}")
    if counter.count:
        raise SystemExit("bench: a program was built inside the window")
    mem = harness.memory_peak(devices)
    kept = {i: np.asarray(v) for i, v in kept.items()}
    if trace:
        programs = [devtrace.Program.from_hlo("encode", prog.encode.lower(
            prog.qp, prog.tokens(0)).compile().as_text())]
    del prog
    gap = gaps(cell, seed, kept)
    tokens = calls * tr["batch"] * tr["seq_len"]
    out = {"end_to_end": {"encode_tokens_per_s": tokens / elapsed,
                          "setup_s": setup_s},
           "checks": compare.checks(gap, cell.limits),
           "attempted": calls, "failed": 0, "memory_peak": mem}
    if trace:
        out["peak"] = peaks(devices[0].device_kind)
        sl = devtrace.reduce(devtrace.load(trace_dir),
                             [d.id for d in devices], programs)
        # the calls as the trace holds them: the profiler can miss the
        # start of the slice, so the host's count can be one more
        n = sl.count("encode")
        harness.log(f"traced slice: {n:.4f} calls in the trace, {traced} "
                    "on the host")
        work = costs.encoder_call(cell.graph, tr["batch"], tr["seq_len"])
        out["slice"] = sl
        out["slice_work"] = {
            k: tuple(n * x for x in v) if isinstance(v, tuple) else n * v
            for k, v in work.items()}
    return out
