"""End-to-end system behaviour: the full SwiftTron flow (paper Fig. 17)
float train -> calibrate/convert -> integer serve, plus cell accounting."""

import jax
import numpy as np

from repro.configs.registry import ASSIGNED, get_config
from repro.launch.cells import cell_supported
from repro.models import inttransformer as it
from repro.models import model as M
from repro.models import transformer as tf
from repro.quant import convert


def test_full_flow_dense():
    cfg = M.reduce_config(get_config("granite-3-2b"), dtype="float32")
    params = tf.init_params(jax.random.key(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 24), 0,
                                          cfg.vocab)}
    qp, plans = convert.quantize_params(params, cfg)
    logits = it.int_prefill(qp, batch, plans, cfg)
    assert logits.shape == (2, cfg.padded_vocab())
    assert np.isfinite(np.asarray(logits)).all()


def test_cell_matrix_accounting():
    """All 40 assigned cells are either runnable or documented skips."""
    from repro.models.common import SHAPES
    runnable, skipped = 0, 0
    for arch in ASSIGNED:
        for shape in SHAPES:
            if cell_supported(arch, shape):
                skipped += 1
            else:
                runnable += 1
    assert runnable + skipped == 40
    assert skipped == 7          # 7 documented long_500k skips


def test_kernel_backend_flag(monkeypatch):
    """Models run with the Pallas kernel backend (interpret mode on CPU)
    that ``kernel_backend`` names, bit-identical to the ref oracle."""
    from repro import ops
    monkeypatch.delenv(ops.ENV_VAR, raising=False)
    cfg = M.reduce_config(get_config("llama3-8b"), dtype="float32",
                          kernel_backend="pallas_fused")
    params = tf.init_params(jax.random.key(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (1, 16), 0,
                                          cfg.vocab)}
    qp, plans = convert.quantize_params(params, cfg)
    assert ops.resolve_ops(None, cfg).name == "pallas_fused"
    ref_logits = it.int_prefill(qp, batch, plans, cfg, ops="ref")
    pl_logits = it.int_prefill(qp, batch, plans, cfg)
    assert np.array_equal(np.asarray(ref_logits), np.asarray(pl_logits))
