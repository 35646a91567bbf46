"""Quickstart: the complete SwiftTron flow on a small model (paper Fig. 17).

  float init -> QAT fine-tune (few steps) -> convert to integer-only
  parameters -> integer prefill + greedy decode -> compare to float path.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import sys
sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.data.pipeline import SyntheticLMDataset
from repro.models import inttransformer as it
from repro.models import model as M
from repro.models import transformer as tf
from repro import ops as rops
from repro.optim import adamw_init, adamw_update
from repro.optim.adamw import AdamWConfig
from repro.quant import convert, qat


def main():
    cfg = M.reduce_config(get_config("llama3-8b"), dtype="float32",
                          vocab=256, num_layers=2)
    print(f"arch={cfg.name} (reduced) d={cfg.d_model} L={cfg.num_layers}")
    data = SyntheticLMDataset(cfg.vocab, 32, 8, seed=0)
    params = tf.init_params(jax.random.key(0), cfg)

    opt_cfg = AdamWConfig(lr=3e-3)
    opt = adamw_init(params, opt_cfg)

    @jax.jit
    def step(params, opt, batch):
        (loss, _), g = jax.value_and_grad(qat.loss_fn, has_aux=True)(
            params, batch, cfg, qat=True)
        params, opt, _ = adamw_update(g, opt, params, opt_cfg)
        return params, opt, loss

    for i in range(30):
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        params, opt, loss = step(params, opt, batch)
        if i % 10 == 0:
            print(f"  QAT step {i:3d}  loss {float(loss):.3f}")

    print("converting to integer-only parameters ...")
    qp, plans = convert.quantize_params(params, cfg)
    n_int8 = sum(l.size for l in jax.tree.leaves(qp)
                 if hasattr(l, "dtype") and l.dtype == jnp.int8)
    print(f"  int8 weights: {n_int8 / 1e6:.2f} M params")

    batch = next(data)
    toks = jnp.asarray(batch["tokens"])
    # integer ops dispatch through the repro.ops backend registry; the
    # use_backend context (or REPRO_BACKEND=...) swaps implementations —
    # "ref" (the oracle) / "pallas_fused" (the TPU kernels), docs/OPS_API.md
    with rops.use_backend("ref"):
        logits_int = it.int_prefill(qp, {"tokens": toks}, plans, cfg)
    logits_f, _ = tf.forward_float(params, {"tokens": toks,
                                            "labels": toks}, cfg)
    corr = np.corrcoef(np.asarray(logits_int).ravel(),
                       np.asarray(logits_f[:, -1], np.float32).ravel())[0, 1]
    agree = float((np.argmax(np.asarray(logits_int), -1)
                   == np.argmax(np.asarray(logits_f[:, -1]), -1)).mean())
    print(f"integer vs float logits: corr={corr:.4f} "
          f"argmax agreement={agree:.2%}")
    assert corr > 0.9
    print("OK")


if __name__ == "__main__":
    main()
