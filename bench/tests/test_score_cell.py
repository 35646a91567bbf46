"""The prefill-scoring cell at a CPU size: a Granite-shaped decoder with
Granite 3.0's four multipliers, added from files alone, reading
``correct`` true for the program and false for the int4 control, for
the old normalise-before-P·V attention, and for an altered answer."""
import io
import json
import time
from contextlib import redirect_stdout

import pytest

import harness

SEED = 2

#: a Granite-shaped decoder small enough for the CPU, at the published
#: multipliers; 384-token rows put the attention past the length where
#: probabilities rounded to 2^-7 before P·V vanish
TINY_DEC = {"d_model": 128, "n_heads": 4, "n_kv_heads": 2, "head_dim": 32,
            "d_ff": 256, "num_layers": 2, "vocab_size": 512,
            "vocab_multiple": 16, "norm": "rmsnorm", "norm_eps": 1e-05,
            "activation": "swiglu", "positions": "rope",
            "rope_theta": 10000.0, "causal": True,
            "embedding_multiplier": 12.0, "attention_multiplier": 0.015625,
            "residual_multiplier": 0.22, "logits_scaling": 8.0}

#: over 32 rows (4 calls of 8) at seeds 1-5 the program reads a mean gap
#: of 0.0002-0.0008 here, the int4 control 0.0033-0.0068 and the old
#: normalise-before-P·V attention 0.0051-0.0073 (CPU readings); at SEED,
#: over every 4 of calls 0-15 (a 1-s window makes fewer, the run keeps
#: 4 of them), the program reads at most 0.00097 and the control at
#: least 0.00178, so the check holds whatever calls the window keeps
LIMIT = 0.0013


@pytest.fixture
def score_bench(tiny_bench):
    base, spec = tiny_bench
    (base / "configs" / "tinyscore.json").write_text(json.dumps({
        "driver": "score", "reference": "granite_score",
        "deployment": {"arch": "granite-3-2b", "backend": "ref"},
        "graph": TINY_DEC}))
    (base / "traffic" / "tinypassages.json").write_text(json.dumps(
        {"kind": "encode_closed_loop", "batch": 8, "seq_len": 384}))
    (base / "limits" / "tinyscore.passages.json").write_text(json.dumps(
        {"mean_top1_gap": LIMIT, "sampled_calls": 4,
         "reference_rows": 8}))
    spec["workloads"].append({"name": "tinyscore.passages",
                              "config": "tinyscore",
                              "traffic": "tinypassages", "chips": 1,
                              "why": "CPU test"})
    return base, spec


def _run(base, spec, wrap=None, seconds=1.0):
    import jax
    cell = harness.Cell("tinyscore.passages", spec, base=str(base))
    run = cell.driver().run(cell, SEED, seconds, False,
                            harness.Setup(time.perf_counter()),
                            jax.devices()[:1], wrap=wrap)
    out = io.StringIO()
    with redirect_stdout(out):
        harness.emit(cell, run, False, {"platform": "cpu", "kind": "cpu"})
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_score_cell_from_files_alone(score_bench):
    res = _run(*score_bench)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"encode_tokens_per_s", "setup_s"}
    assert res["checks"]["mean_top1_gap"]["limit"] == LIMIT


def test_program_runs_the_files_multipliers(score_bench):
    base, spec = score_bench
    cell = harness.Cell("tinyscore.passages", spec, base=str(base))
    cfg = cell.driver().program_config(cell)
    for k in ("embedding_multiplier", "attention_multiplier",
              "residual_multiplier", "logits_scaling"):
        assert getattr(cfg, k) == TINY_DEC[k]
    assert (cfg.d_model, cfg.num_layers) == (128, 2)


def _control(bits=4):
    def wrap(prog, call):
        import jax
        import jax.numpy as jnp
        cell = prog.cell
        ref, g = cell.reference(), cell.graph
        params = jax.jit(lambda k: ref.make_weights(k, g))(prog.wkey)
        f = jax.jit(lambda t: ref.logits(params, jax.vmap(
            lambda r: ref.hidden(params, r, g, bits)[-1])(t), g, bits))
        return lambda i: f(prog.tokens(jnp.int32(i)))
    return wrap


def test_control_fails(score_bench):
    assert _run(*score_bench, wrap=_control())["correct"] is False


def _old_attention_backend():
    """The ``ref`` backend with the attention of the paper's dataflow:
    each probability normalised to int8 at 2^-7 before P·V."""
    import jax.numpy as jnp
    from repro.core import attention as iattn
    from repro.core import softmax as ism
    from repro.core.dyadic import clip_to_bits
    from repro.ops.backends.ref import RefBackend

    class OldSoftmaxPV(RefBackend):
        name = "old_softmax_pv"

        def int_attention(self, q8, k8, v8, plan, causal=True, window=0,
                          out_bits=8, requant=None, b_vec=None, **opts):
            h, hkv = q8.shape[2], k8.shape[2]
            k8 = jnp.repeat(k8, h // hkv, axis=2)
            v8 = jnp.repeat(v8, h // hkv, axis=2)
            sq, sk = q8.shape[1], k8.shape[1]
            mask = iattn.causal_mask(sq, sk)[None, None]
            scores = jnp.einsum("bqhd,bkhd->bhqk", q8, k8,
                                preferred_element_type=jnp.int32)
            p8 = ism.i_softmax(scores, plan.sm, where=mask)
            acc = jnp.einsum("bhqk,bkhd->bqhd", p8, v8,
                             preferred_element_type=jnp.int32)
            return clip_to_bits(plan.dn_out(acc), out_bits)
    return OldSoftmaxPV()


def test_old_normalise_before_pv_fails(score_bench):
    def wrap(prog, call):
        import jax
        import jax.numpy as jnp
        from repro.models import inttransformer as it
        from repro.quant import convert
        cell = prog.cell
        cfg = cell.driver().program_config(cell)
        params = cell.reference().make_weights(prog.wkey, cell.graph)
        qp, plans = convert.quantize_params(params, cfg)
        old = _old_attention_backend()
        f = jax.jit(lambda q, t: it.int_prefill(q, {"tokens": t}, plans,
                                                cfg, ops=old))
        return lambda i: f(qp, prog.tokens(jnp.int32(i)))
    assert _run(*score_bench, wrap=wrap)["correct"] is False


def test_answer_altered_fails(score_bench):
    def wrap(prog, call):
        import jax.numpy as jnp
        return lambda i: jnp.roll(call(i), 5, axis=1)
    assert _run(*score_bench, wrap=wrap)["correct"] is False


def test_causal_work_counts_the_triangle(score_bench):
    base, spec = score_bench
    cell = harness.Cell("tinyscore.passages", spec, base=str(base))
    drv = cell.driver()
    g = cell.graph
    w = drv.causal_call(g, 3, 10)
    pairs = sum(i + 1 for i in range(10))          # row i sees i + 1 keys
    per_layer = 2 * 2 * 3 * g["n_heads"] * g["head_dim"] * pairs
    assert w["attention"][0] == g["num_layers"] * per_layer
    enc = drv.costs.encoder_call(g, 3, 10)
    assert w["matmul"] == enc["matmul"]
    assert w["attention"][1] == enc["attention"][1]


def test_limit_readings_keep_the_windows_calls(score_bench):
    """``limit_readings.kept_calls`` names the calls a run's window keeps
    at a seed, for the window's number of calls."""
    import os
    import numpy as np
    base, spec = score_bench
    cell = harness.Cell("tinyscore.passages", spec, base=str(base))
    enc = cell.driver().encode
    readings = harness.load_module(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "limit_readings.py"))

    class Done:
        def __init__(self, i):
            self.i = i

        def block_until_ready(self):
            time.sleep(0.001)

    class Prog:
        def call(self, i):
            return Done(i)
    for seed in (SEED, 3037000493):
        rng = np.random.default_rng([seed % (1 << 63), 1])
        calls, _, kept, _ = enc.window(Prog(), 0.1, 4, rng)
        assert calls > 8
        assert sorted(kept) == readings.kept_calls(seed, 4, calls)
        assert [kept[i].i for i in sorted(kept)] == sorted(kept)
