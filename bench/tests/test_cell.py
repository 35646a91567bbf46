"""Whole runs at a CPU size, past the harness's look for a chip: a cell
added from files alone, and ``correct`` coming out false with the control
or a planted fault in the program's place."""
import io
import json
import time
from contextlib import redirect_stdout

import harness

SEED = 2


def _run(base, spec, name, wrap=None, trace=False, seconds=0.3):
    import jax
    cell = harness.Cell(name, spec, base=str(base))
    run = cell.driver().run(cell, SEED, seconds, trace,
                            harness.Setup(time.perf_counter()),
                            jax.devices()[:1], wrap=wrap)
    out = io.StringIO()
    with redirect_stdout(out):
        harness.emit(cell, run, trace, {"platform": "cpu", "kind": "cpu"})
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_cell_from_files_alone(tiny_bench):
    res = _run(*tiny_bench, "tiny.docs")
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"encode_tokens_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["top1_gap"]["limit"] == 0.55


def _control(bits=4):
    """The reference computed in int4, put in the program's place."""
    def wrap(prog, call):
        import jax
        import jax.numpy as jnp
        cell = prog.cell
        ref, g = cell.reference(), cell.graph
        params = jax.jit(lambda k: ref.make_weights(k, g))(prog.wkey)

        def last(t):
            return ref.hidden(params, t, g, bits)[-1]
        f = jax.jit(lambda t: ref.logits(params, jax.vmap(last)(t), g,
                                         bits))

        def control(i):
            toks = prog.tokens(jnp.int32(i))
            return f(toks)
        return control
    return wrap


def test_control_fails(tiny_bench):
    assert _run(*tiny_bench, "tiny.docs", wrap=_control())["correct"] \
        is False


def test_answer_altered_fails(tiny_bench):
    def wrap(prog, call):
        import jax.numpy as jnp

        def altered(i):
            out = call(i)
            return out.at[:, :].set(jnp.roll(out, 7, axis=1))
        return altered
    assert _run(*tiny_bench, "tiny.docs", wrap=wrap)["correct"] is False


def test_half_batch_left_out_fails(tiny_bench):
    def wrap(prog, call):
        def half(i):
            out = call(i)
            h = out.shape[0] // 2
            return out.at[h:].set(out[:h].mean(0))
        return half
    assert _run(*tiny_bench, "tiny.docs", wrap=wrap)["correct"] is False


def _add_decoder_cell(base, spec):
    """A Granite-shaped serving cell small enough for the CPU, added from
    files and entries alone."""
    from conftest import TINY_GRAPH
    g = dict(TINY_GRAPH, norm="rmsnorm", activation="swiglu",
             positions="rope", causal=True, n_heads=4, n_kv_heads=2,
             head_dim=32)
    (base / "configs" / "tinydec.json").write_text(json.dumps({
        "driver": "serve", "reference": "plain_transformer",
        "deployment": {"arch": "granite-3-2b", "backend": "ref",
                       "lanes": 4, "cache_len": 256, "page_size": 16,
                       "prefill_chunk": 32, "prefix_cache": True},
        "graph": g}))
    (base / "traffic" / "tinyrag.json").write_text(json.dumps({
        "kind": "closed_loop", "clients": 4, "requests_per_client": 6,
        "documents": {"count": 2, "length": 32, "zipf_s": 1.0},
        "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                   "min": 4, "max": 40},
        "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                   "min": 2, "max": 20}}))
    (base / "limits" / "tinydec.rag.json").write_text(json.dumps(
        {"top1_gap": LIMIT_DEC, "sample_tokens": 30}))
    spec["workloads"].append({"name": "tinydec.rag", "config": "tinydec",
                              "traffic": "tinyrag", "chips": 1,
                              "why": "CPU test"})
    spec["end_to_end"] += [
        {"name": n, "unit": u, "workloads": ["tinydec.rag"]}
        for n, u in (("output_tokens_per_s", "tokens/s"),
                     ("ttft_p50_ms", "ms"), ("itl_p99_ms", "ms"))]
    for m in spec["end_to_end"]:
        if m["name"] == "encode_tokens_per_s":
            m["workloads"] = ["tiny.docs"]


#: the tiny decoder's program reads a widest gap of LIMIT_DEC or less at
#: SEED (its integer path is far from the float graph even at this size,
#: as at Granite's: PERF.md, Open questions)
LIMIT_DEC = 1.5


def test_serving_cell_from_files_alone(tiny_bench):
    base, spec = tiny_bench
    _add_decoder_cell(base, spec)
    res = _run(base, spec, "tinydec.rag", seconds=1.0)
    assert set(res["metrics"]) == {"output_tokens_per_s", "ttft_p50_ms",
                                   "itl_p99_ms", "setup_s"}
    assert res["correct"] is True
    assert res["attempted"] >= 4 and res["failed"] == 0


def test_serving_token_altered_fails(tiny_bench):
    base, spec = tiny_bench
    _add_decoder_cell(base, spec)

    def wrap(prog):
        sample = prog.eng._sample
        v = prog.cell.graph["vocab_size"]
        prog.eng._sample = lambda req, row: (sample(req, row) + 1) % v
    res = _run(base, spec, "tinydec.rag", wrap=wrap, seconds=1.0)
    assert res["correct"] is False


def test_no_tpu_exits_nonzero_without_a_result():
    import os
    import subprocess
    import sys
    root = os.path.dirname(harness.BENCH)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell,
         "--seed", "1", "--seconds", "1"], cwd=root, capture_output=True,
        text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_served_tokens_compare_at_their_own_positions(tiny_bench):
    """A stream of the reference's own greedy tokens reads gap 0, also
    where the sequence ends on a padding boundary."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import compare
    base, spec = tiny_bench
    _add_decoder_cell(base, spec)
    cell = harness.Cell("tinydec.rag", spec, base=str(base))
    ref, g = cell.reference(), cell.graph
    params = jax.jit(lambda k: ref.make_weights(k, g))(
        compare.seed_key(SEED))
    prompt = list(np.random.default_rng(0).integers(1, 512, 252))
    seq = list(prompt)
    for _ in range(5):
        h = ref.hidden(params, jnp.asarray(seq, jnp.int32), g)[-1:]
        seq.append(int(np.asarray(ref.logits(params, h, g))[0].argmax()))
    req = {"prompt": prompt, "tokens": seq[len(prompt):]}
    assert len(req["prompt"]) + len(req["tokens"]) - 1 == 256
    gaps = cell.driver().gaps(cell, SEED, [req])
    assert gaps.shape == (5,) and np.allclose(gaps, 0.0)
