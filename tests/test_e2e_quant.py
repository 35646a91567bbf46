"""End-to-end reproduction of the paper's central claim (Table II, in
miniature): a model trained in float and converted to the SwiftTron
integer-only datapath loses almost no task accuracy.

Train a small decoder on the synthetic bigram language, quantize, and
compare next-token accuracy of the integer path vs the float path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.data.pipeline import SyntheticLMDataset
from repro.models import inttransformer as it
from repro.models import model as M
from repro.models import transformer as tf
from repro.optim import adamw_init, adamw_update
from repro.optim.adamw import AdamWConfig
from repro.quant import convert, qat


@pytest.fixture(scope="module")
def trained():
    cfg = M.reduce_config(get_config("llama3-8b"), dtype="float32",
                          vocab=256, num_layers=2)
    data = SyntheticLMDataset(cfg.vocab, 32, 16, seed=3)
    params = tf.init_params(jax.random.key(0), cfg)
    opt_cfg = AdamWConfig(lr=3e-3, weight_decay=0.01)
    opt = adamw_init(params, opt_cfg)

    @jax.jit
    def step(params, opt, batch):
        (loss, _), g = jax.value_and_grad(qat.loss_fn, has_aux=True)(
            params, batch, cfg, qat=True)
        params, opt, _ = adamw_update(g, opt, params, opt_cfg)
        return params, opt, loss

    losses = []
    for i in range(60):
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        params, opt, loss = step(params, opt, batch)
        losses.append(float(loss))
    return cfg, params, data, losses


def test_training_learns(trained):
    cfg, params, data, losses = trained
    assert losses[-1] < losses[0] - 0.5, (losses[0], losses[-1])


def _accuracy(logits, labels):
    pred = np.argmax(logits, axis=-1)
    return float((pred == labels).mean())


def test_integer_path_preserves_accuracy(trained):
    """The paper's Table II: quantized accuracy within ~1pt of float."""
    cfg, params, data, _ = trained
    qp, plans = convert.quantize_params(params, cfg)
    batch = next(data)
    toks = jnp.asarray(batch["tokens"])
    logits_f, _ = tf.forward_float(params, {"tokens": toks,
                                            "labels": toks}, cfg)
    # per-position integer logits via repeated prefill on prefixes is slow;
    # evaluate last-position accuracy over many examples instead
    acc_f, acc_i, n = 0.0, 0.0, 0
    for i in range(8):
        b = next(data)
        toks = jnp.asarray(b["tokens"])
        lf, _ = tf.forward_float(params, {"tokens": toks, "labels": toks},
                                 cfg)
        li = it.int_prefill(qp, {"tokens": toks}, plans, cfg)
        labels = b["labels"][:, -1]
        acc_f += _accuracy(np.asarray(lf[:, -1, :cfg.vocab]), labels)
        acc_i += _accuracy(np.asarray(li[:, :cfg.vocab]), labels)
        n += 1
    acc_f, acc_i = acc_f / n, acc_i / n
    assert acc_f > 0.25, f"float model failed to learn ({acc_f})"
    assert acc_i > acc_f - 0.05, \
        f"integer path lost accuracy: float {acc_f:.3f} int {acc_i:.3f}"


@pytest.mark.parametrize("arch", ["granite-3-2b", "roberta-base",
                                  "jamba-v0.1-52b"])
def test_layerwise_quantization_matches_stacked(arch):
    """``quantize_params`` quantizes a layer stack one layer at a time,
    several layers at once on host threads (bounded host memory at
    published depth); the integers must equal quantizing the whole stack
    at once — scales are per layer along the stack axis, so slicing
    cannot change a single value, nor can the order the threads take."""
    from repro.models.transformer import layer_group_spec
    cfg = M.reduce_config(get_config(arch), dtype="float32", vocab=256)
    params = tf.init_params(jax.random.key(1), cfg)
    qp, plans = convert.quantize_params(params, cfg)
    gl, ng, kinds = layer_group_spec(cfg)
    assert ng > 1                       # a real stack, not one layer
    for j in range(gl):
        whole = convert._q_sublayer(params["layers"][j], plans, cfg,
                                    kinds[j], {})
        got_leaves, got_def = jax.tree.flatten(qp["layers"][j])
        want_leaves, want_def = jax.tree.flatten(whole)
        assert got_def == want_def
        for g, w in zip(got_leaves, want_leaves):
            g, w = np.asarray(g), np.asarray(w)
            assert g.dtype == w.dtype and g.shape == w.shape
            assert np.array_equal(g, w)
