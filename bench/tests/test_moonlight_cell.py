"""The MLA + MoE scoring cell at a CPU size: a Moonlight-shaped decoder
(latent attention, a dense first layer, sigmoid-routed experts of which
half are held here, shared experts) added from files alone, reading
``correct`` true for the program and false for the int4 control; its
work counted by hand; its readers on a hand-built trace; the counter
read on a kept call."""
import io
import json
import time
from contextlib import redirect_stdout

import pytest

import harness
import scopes

SEED = 2

#: a Moonlight-shaped decoder small enough for the CPU: every mechanism
#: of the block, half of the 16 experts held
TINY_MOON = {"d_model": 128, "n_heads": 4, "n_kv_heads": 4, "head_dim": 48,
             "d_ff": 256, "num_layers": 3, "vocab_size": 512,
             "vocab_multiple": 16, "norm": "rmsnorm", "norm_eps": 1e-05,
             "activation": "swiglu", "positions": "rope",
             "rope_theta": 50000.0, "causal": True,
             "kv_lora_rank": 64, "qk_nope_head_dim": 32,
             "qk_rope_head_dim": 16, "v_head_dim": 32, "first_k_dense": 1,
             "n_experts": 16, "top_k": 4, "moe_d_ff": 64,
             "n_shared_experts": 2, "router": "sigmoid",
             "routed_scaling": 2.446, "expert_first": 0, "experts_held": 8}

#: over the 16 rows of calls 0-1 at seeds 1-5 the program reads a mean
#: gap of 0.045-0.199 here and the int4 control 0.648-1.159 (CPU
#: readings); at SEED 0.065 and 0.655
LIMIT = 0.35


@pytest.fixture
def moon_bench(tiny_bench):
    base, spec = tiny_bench
    (base / "configs" / "tinymoon.json").write_text(json.dumps({
        "driver": "mla_moe_score", "reference": "moonlight_score",
        "deployment": {"arch": "moonlight-16b-a3b", "backend": "ref"},
        "graph": TINY_MOON}))
    (base / "traffic" / "tinychunks.json").write_text(json.dumps(
        {"kind": "encode_closed_loop", "batch": 8, "seq_len": 64}))
    (base / "limits" / "tinymoon.chunks.json").write_text(json.dumps(
        {"mean_top1_gap": LIMIT, "sampled_calls": 2, "reference_rows": 8}))
    spec["workloads"].append({"name": "tinymoon.chunks",
                              "config": "tinymoon",
                              "traffic": "tinychunks", "chips": 1,
                              "why": "CPU test"})
    return base, spec


def _cell(base, spec):
    return harness.Cell("tinymoon.chunks", spec, base=str(base))


def _run(base, spec, wrap=None, seconds=0.5):
    import jax
    cell = _cell(base, spec)
    run = cell.driver().run(cell, SEED, seconds, False,
                            harness.Setup(time.perf_counter()),
                            jax.devices()[:1], wrap=wrap)
    out = io.StringIO()
    with redirect_stdout(out):
        harness.emit(cell, run, False, {"platform": "cpu", "kind": "cpu"})
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_moonlight_cell_from_files_alone(moon_bench):
    res = _run(*moon_bench)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"encode_tokens_per_s", "setup_s"}
    assert res["checks"]["mean_top1_gap"]["limit"] == LIMIT


def test_program_runs_the_files_block(moon_bench):
    cell = _cell(*moon_bench)
    cfg = cell.driver().program_config(cell)
    assert (cfg.kv_lora_rank, cfg.v_head_dim, cfg.first_k_dense) == \
        (64, 32, 1)
    assert (cfg.router, cfg.routed_scaling, cfg.experts_held) == \
        ("sigmoid", 2.446, 8)
    assert (cfg.d_model, cfg.num_layers, cfg.hd) == (128, 3, 48)


def test_control_fails(moon_bench):
    def wrap(prog, call):
        import jax
        import jax.numpy as jnp
        cell = prog.cell
        ref, g = cell.reference(), cell.graph
        params = jax.jit(lambda k: ref.make_weights(k, g))(prog.wkey)
        f = jax.jit(lambda t: ref.logits(params, jax.vmap(
            lambda r: ref.hidden(params, r, g, 4)[-1])(t), g, 4))
        return lambda i: f(prog.tokens(jnp.int32(i)))
    assert _run(*moon_bench, wrap=wrap)["correct"] is False


def test_counter_reads_the_held_rows(moon_bench):
    """The kept call's counter: per MoE layer, the (token, choice) rows
    on held experts; all 8 x 64 x 4 pairs where every expert is held."""
    cell = _cell(*moon_bench)
    drv = cell.driver()
    prog = drv.encode.Program(cell, SEED)
    st = drv.moe_stats(prog, 0)
    pairs = 8 * 64 * 4
    assert len(st["moe_rows"]) == 2
    assert all(0 < r < pairs for r in st["moe_rows"])
    assert all(m <= r for m, r in zip(st["moe_rows_max"], st["moe_rows"]))
    cell.graph["experts_held"] = 16
    prog = drv.encode.Program(cell, SEED)
    assert drv.moe_stats(prog, 0)["moe_rows"] == [pairs, pairs]


def test_work_counted_by_hand(moon_bench):
    """d 4, 2 heads of 2 + 1 (rope) queries/keys and 2-wide values, a
    latent of 2, one dense layer of width 8 and one MoE layer (4 experts,
    2 held, width 2, one shared expert), one row of 3 tokens."""
    drv = _cell(*moon_bench).driver()
    g = {"d_model": 4, "n_heads": 2, "kv_lora_rank": 2,
         "qk_nope_head_dim": 2, "qk_rope_head_dim": 1, "v_head_dim": 2,
         "num_layers": 2, "first_k_dense": 1, "d_ff": 8, "moe_d_ff": 2,
         "n_shared_experts": 1, "n_experts": 4, "experts_held": 2,
         "vocab_size": 16}
    w = drv.mla_moe_call(g, 1, 3, [5])
    # a layer's MLA: q 2*3*4*6, latent 2*3*4*2, rope key 2*3*4*1,
    # expansion 2*3*2*8, output 2*3*4*4 = 408, two layers
    # dense SwiGLU: 2*3*4*8 twice and 2*3*8*4 = 576, one layer
    # router 2*3*4*4, shared 2*3*4*2 twice and 2*3*2*4 = 240, one layer
    assert w["matmul"][0] == 2 * 408 + 576 + 240
    # 5 rows through w13 (4 -> 2 x 2) and w2 (2 -> 4)
    assert w["grouped_matmul"][0] == 5 * 2 * (4 * 4 + 2 * 4)
    # 2 heads, (3 + 2) wide, 6 query-key pairs, 2 per multiply-add, 2 layers
    assert w["attention"][0] == 2 * (2 * 1 * 2 * (3 + 2) * 6)
    assert w["model_ops"] == (w["matmul"][0] + w["grouped_matmul"][0]
                              + w["attention"][0] + 2 * 1 * 4 * 16)
    # bytes: 2 held experts' weights once, the 5 rows' ins and outs, and
    # the multipliers of both launches
    assert w["grouped_matmul"][1] == (2 * (4 * 4 + 2 * 4) + 5 * 4
                                      + 5 * 4 * 4 + 5 * 2 + 5 * 4 * 4
                                      + 2 * 4 * (2 * 2 + 4))


class _Slice:
    lo, hi = 0.0, 1.0
    kernels = {"int8_matmul": 0.002}
    idle_share, window_s = 0.05, 1.0

    def count(self, program):
        return 2.0


def _reader(base, name):
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(str(base), "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_readers_on_a_hand_built_trace(moon_bench):
    base, _ = moon_bench

    def reader(name):
        return _reader(base, name)
    root = "jit(encode)/int_prefill/while/body"
    ops = [scopes.Op(f"{root}/moe/moe.experts/int_grouped_matmul/"
                     "pallas_call", "custom-call.1", 0.0, 0.002),
           scopes.Op(f"{root}/moe/moe.combine", "fusion.3", 0.002, 0.0005),
           scopes.Op(f"{root}/moe/ffn.up/int8_matmul/pallas_call",
                     "custom-call.2", 0.0025, 0.0005),
           scopes.Op(f"{root}/moe", "while.4", 0.0, 0.003),
           scopes.Op(f"{root}/attn/int_attention_fused/pallas_call",
                     "custom-call.3", 0.003, 0.004)]
    peak = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
    run = {"slice": _Slice(), "path_ops": ops, "peak": peak,
           "scopes": scopes.Attribution(kernels={"int8_matmul": 0.0005}),
           "slice_work": {"grouped_matmul": (393e9, 819e6)}}
    # 3 ms under moe (the while loop left out) over 2 calls
    assert reader("moe_ms.moonlight")(run) == pytest.approx(1.5)
    # 1 ms of least time (either bound) in 2 ms of the grouped launch
    assert reader("grouped_matmul_roofline.moonlight")(run) == \
        pytest.approx(50.0)
    assert reader("grouped_matmul_roofline.moonlight")(
        dict(run, path_ops=ops[1:])) is None


def test_matmul_idle_and_norm_readers(moon_bench):
    """The int8 matmuls' roofline against the matmul work the driver
    counted, the slice's idle share, and the ``int_norm`` launches' time
    per call."""
    base, _ = moon_bench
    peak = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
    run = {"slice": _Slice(), "peak": peak,
           "scopes": scopes.Attribution(kernels={"int_norm": 0.004}),
           "slice_work": {"matmul": (393e9, 819e6)}}
    # 1 ms of least time in the slice's 2 ms of int8_matmul launches
    assert _reader(base, "matmul_roofline.moonlight")(run) == \
        pytest.approx(50.0)
    assert _reader(base, "idle_share.moonlight")(run) == pytest.approx(5.0)
    # 4 ms of int_norm over 2 calls
    assert _reader(base, "norm_ms.moonlight")(run) == pytest.approx(2.0)


def test_route_flips_on_the_tiny_cell(moon_bench):
    """``route_flips.py`` reads a share in [0, 1] per MoE layer, and the
    reference against itself with nothing rounded would read 0 gaps."""
    import os
    rf = harness.load_module(os.path.join(harness.BENCH, "route_flips.py"))
    out = rf.readings(_cell(*moon_bench), SEED, 0, 2)
    assert len(out["flip8"]) == len(out["near"]) == 2
    assert all(0.0 <= x <= 1.0 for x in out["flip8"] + out["near"])
    assert 0.0 <= out["router8_mean_gap"] <= out["router8_gap"]
    assert out["all4_mean_gap"] > out["all8_mean_gap"]
