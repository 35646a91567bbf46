"""Moonlight-16B-A3B (DeepSeek-V3's block) through ``int_prefill``: the
fused attention with a value head narrower than its queries and keys,
the grouped int8 matmul of the dropless MoE, the integer sigmoid router,
the chip's share of the experts, and the whole model against the
benchmark's plain float reference — all at CPU sizes, on seeded random
weights.  ``pallas_fused`` runs its kernels in interpret mode here."""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core import activations as iact
from repro.core import attention as iattn
from repro.kernels.int_grouped_matmul import int_grouped_matmul_pallas
from repro.kernels.ref import ref_int_grouped_matmul
from repro.models import intlayers as il
from repro.models import inttransformer as it
from repro.models import model as M
from repro.models import transformer as tf
from repro.ops import RequantSpec, get_backend
from repro.quant import convert
from repro.serving import ServingEngine

FUSED, REF = get_backend("pallas_fused"), get_backend("ref")


def _reference():
    path = os.path.join(os.path.dirname(__file__), "..", "bench",
                        "reference", "moonlight_score.py")
    spec = importlib.util.spec_from_file_location("moonlight_score", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------- attention, dv != d -------

@pytest.mark.parametrize("d,dv", [(192, 128), (48, 32)])
@pytest.mark.parametrize("causal", [True, False])
def test_attention_value_width_exact(rng, d, dv, causal):
    """q/k heads ``d`` wide, v heads ``dv``: the fused launch against
    the full-matrix oracle, per-tensor and per-channel epilogues."""
    b, s, h = 1, 64, 2
    q8, k8, v8 = (jnp.asarray(np.clip(rng.normal(0, 40, (b, s, h, w)),
                                      -127, 127).astype(np.int8))
                  for w in (d, d, dv))
    plan = iattn.make_iattention(d, 8 / 127, 8 / 127, 4 / 127, 4 / 127)
    got = FUSED.int_attention(q8, k8, v8, plan, causal=causal, bq=32,
                              bkv=32)
    want = REF.int_attention(q8, k8, v8, plan, causal=causal)
    assert got.shape == (b, s, h, dv)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    spec = RequantSpec.per_channel(20, 6)
    b_vec = jnp.asarray(rng.integers(1 << 12, 1 << 13, (h * dv,)),
                        jnp.int32)
    got = FUSED.int_attention(q8, k8, v8, plan, causal=causal, bq=32,
                              bkv=32, requant=spec, b_vec=b_vec)
    want = REF.int_attention(q8, k8, v8, plan, causal=causal,
                             requant=spec, b_vec=b_vec)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------ grouped matmul ---

#: (group sizes, rows M): uneven with an empty group; one group over
#: four row blocks; live rows not a multiple of the block; nothing live;
#: every row live
GROUPS = [([3, 0, 20, 5], 40), ([0, 37, 0, 2], 48), ([5, 6, 7, 3], 32),
          ([0, 0, 0, 0], 32), ([10, 10, 10, 2], 32)]


@pytest.mark.parametrize("sizes,m", GROUPS)
@pytest.mark.parametrize("out_bits", [8, 14, 32])
def test_grouped_matmul_exact(rng, sizes, m, out_bits):
    g, k, n = len(sizes), 128, 256
    x8 = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    w8 = jnp.asarray(rng.integers(-127, 128, (g, k, n)), jnp.int8)
    b_vec = None if out_bits == 32 else jnp.asarray(
        rng.integers(1 << 13, 1 << 14, (g, n)), jnp.int32)
    gs = jnp.asarray(sizes, jnp.int32)
    got = int_grouped_matmul_pallas(x8, w8, gs, b_vec, c=24, pre=8,
                                    out_bits=out_bits, tm=8, tn=128)
    want = ref_int_grouped_matmul(x8, w8, gs, b_vec, 24, 8, out_bits)
    live = sum(sizes)
    assert got.dtype == want.dtype
    assert np.array_equal(np.asarray(got)[:live], np.asarray(want)[:live])
    # every live row is its own group's product
    rows = np.repeat(np.arange(g), sizes)
    acc = np.einsum("mk,mkn->mn", np.asarray(x8[:live], np.int64),
                    np.asarray(w8, np.int64)[rows])
    if out_bits == 32:
        assert np.array_equal(np.asarray(got)[:live], acc)


def test_grouped_matmul_through_the_opset(rng):
    """``OpSet.int_grouped_matmul`` on both backends, and the oracle for
    a backend that lacks the op."""
    from repro.ops import OpSet
    from repro.ops.backends.ref import RefBackend

    class Plain(RefBackend):
        name = "plain"
        grouped_matmul = False
    x8 = jnp.asarray(rng.integers(-127, 128, (32, 128)), jnp.int8)
    w8 = jnp.asarray(rng.integers(-127, 128, (3, 128, 128)), jnp.int8)
    b_vec = jnp.asarray(rng.integers(1 << 13, 1 << 14, (3, 128)),
                        jnp.int32)
    gs = jnp.asarray([9, 0, 17], jnp.int32)
    spec = RequantSpec.per_channel(24, 8, 8)
    outs = [np.asarray(OpSet(be).int_grouped_matmul(x8, w8, gs, spec,
                                                    b_vec=b_vec))[:26]
            for be in ("ref", "pallas_fused", Plain())]
    assert all(np.array_equal(outs[0], o) for o in outs[1:])


# -------------------------------------------------------- tiny models ----

def _tiny_moonlight(**over):
    return M.reduce_config(get_config("moonlight-16b-a3b"),
                           dtype="float32", n_experts=16, top_k=4,
                           **over)


@pytest.fixture(scope="module")
def tiny_moonlight():
    cfg = _tiny_moonlight()
    params = tf.init_params(jax.random.key(0), cfg, served=True)
    qp, plans = convert.quantize_params(params, cfg)
    return cfg, params, qp, plans


def test_tiny_moonlight_pallas_fused_bit_exact(tiny_moonlight):
    cfg, _, qp, plans = tiny_moonlight
    assert cfg.is_mla and cfg.first_k_dense == 1 and cfg.router == "sigmoid"
    toks = jax.random.randint(jax.random.key(1), (2, 32), 1, cfg.vocab)
    got = it.int_prefill(qp, {"tokens": toks}, plans, cfg,
                         ops="pallas_fused", return_stats=True)
    want = it.int_prefill(qp, {"tokens": toks}, plans, cfg, ops="ref",
                          return_stats=True)
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for key in ("moe_rows", "moe_rows_max"):
        assert np.array_equal(np.asarray(got[1][key]),
                              np.asarray(want[1][key]))
    # every pair is held (the tiny config holds all its experts)
    assert np.asarray(want[1]["moe_rows"]).tolist() == \
        [2 * 32 * cfg.top_k] * (cfg.num_layers - 1)
    plain = it.int_prefill(qp, {"tokens": toks}, plans, cfg, ops="ref")
    assert np.array_equal(np.asarray(plain), np.asarray(want[0]))


@pytest.mark.parametrize("arch", ["qwen2-moe-a2.7b", "qwen3-moe-235b-a22b"])
def test_existing_moe_dropless_and_bit_exact(arch):
    """The softmax-routed MoE configs on the dropless path: pallas_fused
    equals ref, and however unbalanced the routing — here every token's
    top-2 are experts 0 and 1 — a batch gives each token what it gives
    alone (a capacity-bounded router would drop most of them)."""
    cfg = M.reduce_config(get_config(arch), dtype="float32")
    params = tf.init_params(jax.random.key(0), cfg)
    qp, plans = convert.quantize_params(params, cfg)
    moe = jax.tree.map(lambda t: t[0], qp["layers"][0]["moe"])
    w8 = np.zeros(moe["router"].w8.shape, np.int8)
    w8[:, 0], w8[:, 1] = 100, 50
    moe["router"] = moe["router"]._replace(w8=jnp.asarray(w8))
    x8 = jax.random.randint(jax.random.key(2), (2, 32, cfg.d_model), 1,
                            127).astype(jnp.int8)
    got = il.int_moe_fwd(moe, x8, plans.moe, cfg, "pallas_fused")
    want = il.int_moe_fwd(moe, x8, plans.moe, cfg, "ref")
    assert np.array_equal(np.asarray(got), np.asarray(want))
    alone = jnp.concatenate([
        il.int_moe_fwd(moe, x8[:, t:t + 1], plans.moe, cfg, "ref")
        for t in range(32)], axis=1)
    assert np.array_equal(np.asarray(alone), np.asarray(want))


# --------------------------------------------- against the float graph ---

#: Moonlight-shaped graph of the benchmark reference at a CPU size
TINY_GRAPH = {"d_model": 128, "n_heads": 4, "n_kv_heads": 4, "head_dim": 48,
              "d_ff": 256, "num_layers": 3, "vocab_size": 512,
              "vocab_multiple": 16, "norm": "rmsnorm", "norm_eps": 1e-05,
              "activation": "swiglu", "positions": "rope",
              "rope_theta": 50000.0, "causal": True, "kv_lora_rank": 64,
              "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
              "v_head_dim": 32, "first_k_dense": 1, "n_experts": 16,
              "top_k": 4, "moe_d_ff": 64, "n_shared_experts": 2,
              "router": "sigmoid", "routed_scaling": 2.446,
              "expert_first": 0, "experts_held": 8}

#: the program's mean top-1 gap (how far the reference's logit of the
#: program's first token lies below the reference's best) over 16 rows
#: at seeds 1-5 reads 0.045-0.199 here and the int4 control's
#: 0.648-1.159 (CPU readings): the limit lies between, so computing in
#: int4 fails it
GAP_LIMIT = 0.35


def _program_config(g):
    keys = ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "first_k_dense", "n_experts", "top_k", "moe_d_ff",
            "n_shared_experts", "router", "routed_scaling", "expert_first",
            "experts_held", "rope_theta")
    return dataclasses.replace(
        get_config("moonlight-16b-a3b"), num_layers=g["num_layers"],
        d_model=g["d_model"], n_heads=g["n_heads"],
        n_kv_heads=g["n_kv_heads"], head_dim=g["head_dim"],
        d_ff=g["d_ff"], vocab=g["vocab_size"], norm_eps=g["norm_eps"],
        dtype="float32", **{k: g[k] for k in keys})


@pytest.fixture(scope="module")
def reference_setup():
    ref, g = _reference(), TINY_GRAPH
    params = jax.jit(lambda k: ref.make_weights(k, g, jnp.float32))(
        jax.random.key(2))
    toks = jax.random.randint(jax.random.key(3), (8, 64), 1,
                              g["vocab_size"])

    def ref_logits(bits):
        with jax.default_matmul_precision("highest"):
            h = jax.vmap(lambda t: ref.hidden(params, t, g, bits)[-1])(toks)
            return np.asarray(ref.logits(params, h, g, bits))
    return ref, g, params, toks, ref_logits


def _gaps(want, got):
    picks = got.argmax(-1)
    return want.max(-1) - want[np.arange(len(want)), picks]


def test_int_prefill_tracks_the_float_reference(reference_setup):
    ref, g, params, toks, ref_logits = reference_setup
    cfg = _program_config(g)
    qp, plans = convert.quantize_params(params, cfg)
    got = np.asarray(it.int_prefill(qp, {"tokens": toks}, plans, cfg,
                                    ops="ref"))[:, :g["vocab_size"]]
    want = ref_logits(None)
    assert _gaps(want, got).mean() < GAP_LIMIT
    assert _gaps(want, ref_logits(4)).mean() > GAP_LIMIT
    assert np.corrcoef(got.ravel(), want.ravel())[0, 1] > 0.9


def test_forward_float_equals_the_reference(reference_setup):
    """``models.transformer.forward_float`` (latent attention, the dense
    first layer, the dropless sigmoid MoE on held experts) against the
    plain reference on the same weights: float32 rounding apart."""
    ref, g, params, toks, ref_logits = reference_setup
    cfg = _program_config(g)
    with jax.default_matmul_precision("highest"):
        lg, _ = tf.forward_float(params, {"tokens": toks}, cfg)
    got = np.asarray(lg[:, -1, :g["vocab_size"]])
    np.testing.assert_allclose(got, ref_logits(None), rtol=2e-3, atol=2e-3)


# --------------------------------------------------------- the share ----

def test_four_shares_add_up_to_the_uncut_layer(reference_setup):
    """Four chips of 4 experts each (of 16): their MoE outputs, with the
    shared experts counted once, add up to the uncut layer's — bit for
    bit in the program's int32 before the residual add, to float32
    rounding in the reference."""
    ref, g, params, toks, _ = reference_setup
    g = dict(g, experts_held=16)
    cfg = _program_config(g)
    full = jax.jit(lambda k: ref.make_weights(k, g, jnp.float32))(
        jax.random.key(4))
    qp, plans = convert.quantize_params(full, cfg)
    moe_q = jax.tree.map(lambda t: t[0], qp["layers"][0]["moe"])
    moe_f = jax.tree.map(lambda t: t[0], full["layers"][0]["moe"])
    x8 = jax.random.randint(jax.random.key(5), (2, 16, cfg.d_model), -60,
                            60).astype(jnp.int8)
    whole = np.asarray(il.int_moe_fwd(moe_q, x8, plans.moe, cfg, "ref"))
    shared = np.asarray(il.int_ffn_fwd(moe_q["shared"], x8,
                                       plans.moe.shared, cfg, "ref"))
    xf = np.asarray(x8, np.float32)[0] * cfg.s_act8
    whole_f = np.asarray(ref.moe(xf, moe_f, g, None))
    shared_f = np.asarray(ref._swiglu(xf, moe_f["shared"], None))
    parts, parts_f = [], []
    for first in range(0, 16, 4):
        c = dataclasses.replace(cfg, expert_first=first, experts_held=4)
        q = dict(moe_q, w13=jax.tree.map(lambda t: t[first:first + 4],
                                         moe_q["w13"]),
                 w2=jax.tree.map(lambda t: t[first:first + 4],
                                 moe_q["w2"]))
        parts.append(np.asarray(il.int_moe_fwd(q, x8, plans.moe, c, "ref")))
        f = dict(moe_f, **{k: moe_f[k][first:first + 4]
                           for k in ("w1", "w3", "w2")})
        gs = dict(g, expert_first=first, experts_held=4)
        parts_f.append(np.asarray(ref.moe(xf, f, gs, None)))
    assert np.array_equal(sum(parts) - 3 * shared, whole)
    np.testing.assert_allclose(sum(parts_f) - 3 * shared_f, whole_f,
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------- the router ---

@pytest.mark.parametrize("bias_std", [0.02, 0.0])
def test_integer_router_matches_float_top_k_off_the_grid(rng, bias_std):
    """The integer sigmoid router chooses the float router's top 6 of 64
    wherever the 6th and 7th biased scores lie further apart than the
    i-sigmoid's error, with the correction bias drawn or zero; its gates
    (normalised over the six) then match to the gate grid."""
    from repro.quant import plans as qplans
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b"),
                              n_experts=64, top_k=6)
    s_logit = 8 / 127 * 0.001
    gp = qplans.make_sigmoid_gate(s_logit, 2048 * 127 * 127, 2.446)
    plan = qplans.MoePlan(None, gp, None, None)
    logits = jnp.asarray(rng.normal(0, 1.5 / s_logit, (512, 64)),
                         jnp.int32)
    bias = jnp.asarray(rng.normal(0, bias_std, (64,)) * 2 ** 15, jnp.int32)
    ids, gates, shift = il.int_route(logits, {"router_bias_q": bias}, plan,
                                     cfg)
    sig = 1 / (1 + np.exp(-np.asarray(logits, np.float64) * s_logit))
    err = np.abs(np.asarray(iact.i_sigmoid(logits, gp.sig)) / 2 ** 15
                 - sig).max()
    choice = sig + np.asarray(bias) / 2 ** 15
    order = np.argsort(-choice, axis=-1)
    margin = np.take_along_axis(choice, order[:, 5:6], -1)[:, 0] \
        - np.take_along_axis(choice, order[:, 6:7], -1)[:, 0]
    clear = margin > 2 * err + 2 ** -15
    assert clear.mean() > 0.5, (err, clear.mean())
    got = np.sort(np.asarray(ids), -1)[clear]
    want = np.sort(order[:, :6], -1)[clear]
    assert np.array_equal(got, want)
    g = np.take_along_axis(sig, order[:, :6], -1)
    g = g / g.sum(-1, keepdims=True) * 2.446
    gi = np.take_along_axis(np.asarray(gates) / 2 ** shift,
                            np.argsort(np.asarray(ids), -1), -1)
    gw = np.take_along_axis(g, np.argsort(order[:, :6], -1), -1)
    np.testing.assert_allclose(gi[clear], gw[clear], atol=2.446 * 4 * err
                               + 2 ** -shift)


# ---------------------------------------------------------- refusals ----

@pytest.mark.parametrize("entry", ["decode", "verify", "chunked_prefill",
                                   "cache", "serving"])
def test_mla_decode_paths_refuse(tiny_moonlight, entry):
    cfg, _, qp, plans = tiny_moonlight
    b = jnp.zeros((2,), jnp.int32)
    calls = {
        "decode": lambda: it.int_decode_step(qp, [], b, b, plans, cfg),
        "verify": lambda: it.int_verify_step(qp, [], b[:, None], b, b + 1,
                                             plans, cfg),
        "chunked_prefill": lambda: it.int_prefill_chunk_step(
            qp, [], jnp.zeros((2, 8), jnp.int32), b, plans, cfg),
        "cache": lambda: it.int_prefill(
            qp, {"tokens": jnp.zeros((2, 8), jnp.int32)}, plans, cfg,
            return_cache=True),
        "serving": lambda: ServingEngine(qp, plans, cfg, batch_size=2,
                                         cache_len=32, ops="ref"),
    }
    with pytest.raises(it.MLADecodeError, match="latent"):
        calls[entry]()


def test_certifier_covers_moonlight():
    from repro.analysis.interpret import certify_config
    rep = certify_config(get_config("moonlight-16b-a3b"), seq_len=512,
                         cache_len=512)
    layers = {o.layer for o in rep.ops}
    assert {"attn.kv_a", "attn.kv_norm", "attn.kv_b", "moe.gate"} <= layers
    assert rep.worst_bits <= 32
