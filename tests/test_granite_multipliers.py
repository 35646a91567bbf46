"""Granite 3.0's four scalar multipliers (``ArchConfig``): folded into
the integer path's constants, applied by the float graph, neutral by
default, and the float graph equal to the benchmark's plain reference."""
import dataclasses
import hashlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS, get_config
from repro.models import inttransformer as it
from repro.models import model as M
from repro.models import transformer as tf
from repro.quant import convert
from repro.quant.plans import build_layer_plans

NEUTRAL = dict(embedding_multiplier=1.0, attention_multiplier=None,
               residual_multiplier=1.0, logits_scaling=1.0)

#: sha256 (first 16 hex digits) of ``repr(build_layer_plans(cfg))`` of
#: every registry config before the multipliers existed; Granite's is of
#: its config with the multipliers set neutral; Moonlight's (MLA, a
#: sigmoid router) is of its plans as they were when it was added.  The
#: MoE configs' are of those plans with ``MoePlan.gate_sm`` named
#: ``gate`` and the combine's dyadic (``dn_combine``, which the dropless
#: combine no longer runs) left out: the same text otherwise
PLAN_DIGESTS = {
    "codeqwen1.5-7b": "533c62a3a3c17f0d",
    "deit-s": "5386a9dff2b17097",
    "granite-3-2b": "47cb8622f6a3eab0",
    "h2o-danube-3-4b": "3c4e66cef24e8bff",
    "jamba-v0.1-52b": "9a12434b25566691",
    "llama-3.2-vision-90b": "c58ecb4401a73001",
    "llama3-8b": "f0c355138321c44d",
    "mamba2-130m": "a42475b8adce15a4",
    "moonlight-16b-a3b": "7f00e2f72b13432a",
    "qwen2-moe-a2.7b": "f57501c516b9237b",
    "qwen3-moe-235b-a22b": "9fe49051530df8d9",
    "roberta-base": "434aed00a2c504e3",
    "roberta-large": "2c36005659424a3d",
    "seamless-m4t-large-v2": "1efbe59a5c8062e2",
}


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_neutral_defaults_leave_every_plan_bit_for_bit(name):
    cfg = ARCHS[name]
    if name == "granite-3-2b":
        cfg = dataclasses.replace(cfg, **NEUTRAL)
    else:
        assert all(getattr(cfg, k) == v for k, v in NEUTRAL.items())
    digest = hashlib.sha256(
        repr(build_layer_plans(cfg)).encode()).hexdigest()[:16]
    assert digest == PLAN_DIGESTS[name]


def test_granite_runs_the_published_multipliers():
    cfg = get_config("granite-3-2b")
    assert (cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == \
        (12.0, 0.015625, 0.22, 8.0)
    plans = build_layer_plans(cfg, {"s_emb": 0.01})
    base = build_layer_plans(dataclasses.replace(cfg, **NEUTRAL),
                             {"s_emb": 0.01})
    # each multiplier lands in its constant, and nowhere else
    assert plans.embed.dn_res.value == pytest.approx(
        12 * base.embed.dn_res.value, rel=1e-3)
    assert plans.attn.attn.sm.s_in == pytest.approx(
        base.attn.attn.sm.s_in / 8)
    assert plans.attn.out.s_out == pytest.approx(base.attn.out.s_out / 0.22)
    assert plans.ffn.down.s_out == pytest.approx(base.ffn.down.s_out / 0.22)
    assert plans.head.s_in == pytest.approx(base.head.s_in / 8)
    assert plans.attn.qkv == base.attn.qkv and plans.ffn.up == base.ffn.up
    assert plans.norm == base.norm


def _small_granite(layers=2):
    return dataclasses.replace(
        M.reduce_config(get_config("granite-3-2b"), dtype="float32",
                        vocab=512), num_layers=layers)


def test_integer_graph_tracks_forward_float_with_the_multipliers():
    """Served random weights, 2 layers, 256 causal tokens (rows of up to
    256 keys at Granite's flat 1/64 score scale): the integer path's
    last-position logits against ``forward_float``'s.  Correlation over
    the vocab, where a multiplier left out of either path (x12 on the
    embedding, x0.22 on each branch, 1/8 on the scores) reads far lower
    (checked below by leaving one out of the float graph)."""
    cfg = _small_granite()
    params = tf.init_params(jax.random.key(0), cfg, served=True)
    qp, plans = convert.quantize_params(params, cfg)
    toks = jax.random.randint(jax.random.key(1), (4, 256), 1, cfg.vocab)
    got = np.asarray(it.int_prefill(qp, {"tokens": toks}, plans, cfg,
                                    ops="ref"))[:, :cfg.vocab]

    def float_logits(c):
        lg, _ = tf.forward_float(params, {"tokens": toks}, c)
        return np.asarray(lg[:, -1, :cfg.vocab])
    want = float_logits(cfg)
    corr = np.corrcoef(got.ravel(), want.ravel())[0, 1]
    assert corr > 0.9, corr
    # the integer logits are on the float graph's scale (1/8 included)
    assert np.std(got) == pytest.approx(np.std(want), rel=0.2)
    for k, v in (("residual_multiplier", 1.0),
                 ("attention_multiplier", None)):
        other = float_logits(dataclasses.replace(cfg, **{k: v}))
        assert np.corrcoef(got.ravel(), other.ravel())[0, 1] < corr - 0.05


def _granite_score():
    path = os.path.join(os.path.dirname(__file__), "..", "bench",
                        "reference", "granite_score.py")
    spec = importlib.util.spec_from_file_location("granite_score", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_forward_float_equals_the_benchmark_reference():
    """``models.transformer.forward_float`` and the benchmark's plain
    float32 reference (``bench/reference/granite_score.py``) on the same
    weights: the same function, to float32 rounding, once the reference
    takes the float graph's norm epsilon (1e-6; Granite's 1e-5 moves
    these logits by 1e-3 of their size, the served stream's RMS being
    about 0.1 at the embedding)."""
    ref = _granite_score()
    cfg = _small_granite()
    g = {"d_model": cfg.d_model, "n_heads": cfg.n_heads,
         "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.hd, "d_ff": cfg.d_ff,
         "num_layers": cfg.num_layers, "vocab_size": cfg.vocab,
         "vocab_multiple": 16, "norm": "rmsnorm", "norm_eps": 1e-6,
         "activation": "swiglu", "positions": "rope",
         "rope_theta": cfg.rope_theta, "causal": True,
         "embedding_multiplier": cfg.embedding_multiplier,
         "attention_multiplier": cfg.attention_multiplier,
         "residual_multiplier": cfg.residual_multiplier,
         "logits_scaling": cfg.logits_scaling}
    params = ref.make_weights(jax.random.key(3), g, dtype=jnp.float32)
    toks = jax.random.randint(jax.random.key(4), (2, 64), 1, cfg.vocab)
    lg, _ = tf.forward_float(params, {"tokens": toks}, cfg)
    got = np.asarray(lg[:, -1, :cfg.vocab])
    with jax.default_matmul_precision("highest"):
        h = jax.vmap(lambda t: ref.hidden(params, t, g)[-1])(toks)
        want = np.asarray(ref.logits(params, h, g))
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
