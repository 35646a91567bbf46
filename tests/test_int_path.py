"""Integer serving path: convert -> prefill -> decode consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import intlayers as il
from repro.models import inttransformer as it
from repro.models import model as M
from repro.models import transformer as tf
from repro.quant import convert

FAMS = ["llama3-8b", "h2o-danube-3-4b", "mamba2-130m", "qwen2-moe-a2.7b"]


def _setup(name, b=2, s=16):
    cfg = M.reduce_config(get_config(name), dtype="float32")
    params = tf.init_params(jax.random.key(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (b, s), 0,
                                          cfg.vocab)}
    if cfg.family == "vlm":
        batch["img_embeds"] = jax.random.normal(
            jax.random.key(2), (b, cfg.n_img_tokens, cfg.d_model))
    if cfg.family == "encdec":
        batch["src_embeds"] = jax.random.normal(
            jax.random.key(2), (b, s, cfg.d_model))
    qp, plans = convert.quantize_params(params, cfg)
    return cfg, params, batch, qp, plans


@pytest.mark.parametrize("name", FAMS)
def test_int_prefill_correlates_with_float(name):
    cfg, params, batch, qp, plans = _setup(name)
    batch_f = dict(batch, labels=batch["tokens"])
    logits_f, _ = tf.forward_float(params, batch_f, cfg, qat=False)
    lg_int = np.asarray(it.int_prefill(qp, batch, plans, cfg))
    lg_f = np.asarray(logits_f[:, -1], np.float32)
    corr = np.corrcoef(lg_int.ravel(), lg_f.ravel())[0, 1]
    # random-init floors: SSM recurrence quantization compounds (DESIGN.md
    # §6) and random-init MoE routing ties break differently between the
    # paths; trained-model agreement is much higher (test_e2e_quant).
    floor = {"ssm": 0.35, "hybrid": 0.35, "moe": 0.25}.get(cfg.family, 0.5)
    assert corr > floor, f"{name}: int/float corr {corr}"
    assert np.isfinite(lg_int).all()


@pytest.mark.parametrize("name", FAMS)
def test_decode_matches_prefill_exactly(name):
    cfg, params, batch, qp, plans = _setup(name)
    b, s = batch["tokens"].shape
    lg_pre = np.asarray(it.int_prefill(qp, batch, plans, cfg))
    memory8 = None
    caches = it.init_decode_cache(cfg, b, 32, memory8, qp, plans)
    rope_tab = il.build_rope_table(33, cfg.hd, cfg.rope_theta) \
        if cfg.pos == "rope" else None
    step = jax.jit(lambda qp_, c, t, p: it.int_decode_step(
        qp_, c, t, p, plans, cfg, rope_tab))
    lg = None
    for t in range(s):
        pos = jnp.full((b,), t, jnp.int32)
        lg, caches = step(qp, caches, batch["tokens"][:, t], pos)
    assert np.abs(np.asarray(lg) - lg_pre).max() < 1e-4, \
        f"{name}: decode != prefill"


def test_sliding_window_decode_rolls():
    """SWA decode with cache shorter than the sequence still matches a
    windowed prefill (rolling buffer semantics)."""
    cfg = M.reduce_config(get_config("h2o-danube-3-4b"), dtype="float32",
                          window=8)
    params = tf.init_params(jax.random.key(0), cfg)
    b, s = 1, 24
    batch = {"tokens": jax.random.randint(jax.random.key(1), (b, s), 0,
                                          cfg.vocab)}
    qp, plans = convert.quantize_params(params, cfg)
    lg_pre = np.asarray(it.int_prefill(qp, batch, plans, cfg))
    caches = it.init_decode_cache(cfg, b, s, None, qp, plans)
    rope_tab = il.build_rope_table(s + 1, cfg.hd, cfg.rope_theta)
    lg = None
    for t in range(s):
        pos = jnp.full((b,), t, jnp.int32)
        lg, caches = it.int_decode_step(qp, caches, batch["tokens"][:, t],
                                        pos, plans, cfg, rope_tab)
    corr = np.corrcoef(np.asarray(lg).ravel(), lg_pre.ravel())[0, 1]
    assert corr > 0.98
