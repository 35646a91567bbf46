"""Batched integer serving: train briefly, convert, then serve a batch of
requests through the INT8 engine (int8 KV cache, greedy + sampled).

Run:  PYTHONPATH=src python examples/serve_quantized.py
"""
import sys
sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config
from repro.data.pipeline import SyntheticLMDataset
from repro.models import model as M
from repro.models import transformer as tf
from repro.optim import adamw_init, adamw_update
from repro.optim.adamw import AdamWConfig
from repro import ops as rops
from repro.quant import convert, qat
from repro.serving import Request, ServingEngine


def main():
    cfg = M.reduce_config(get_config("h2o-danube-3-4b"), dtype="float32",
                          vocab=256, num_layers=2)
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

    for _ in range(20):
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        params, opt, _ = step(params, opt, batch)

    qp, plans = convert.quantize_params(params, cfg)
    # the engine takes one OpSet handle at construction (repro.ops
    # registry); swap "ref" for "pallas_fused" (the TPU kernels)
    # — or set the REPRO_BACKEND env var — without touching the model
    # code (docs/OPS_API.md lists the built-ins).  The default cache is
    # the paged pool; num_pages undersubscribes it so KV memory tracks
    # live tokens, not batch x cache_len (repro.serving.kvcache)
    engine = ServingEngine(qp, plans, cfg, batch_size=4, cache_len=64,
                           ops=rops.resolve_ops("ref"),
                           page_size=16, num_pages=9)
    print(f"engine: {engine.describe_str()}")
    reqs = [Request(uid=i, prompt=[1 + 3 * i, 7, 42, 5],
                    max_new_tokens=12,
                    temperature=0.0 if i % 2 == 0 else 0.8)
            for i in range(6)]
    for r in reqs:
        engine.submit(r)
    steps = 0
    while engine.queue or any(s is not None for s in engine.slots):
        engine.step()
        steps += 1
    print(f"served {len(reqs)} requests in {steps} batched decode steps "
          f"(batch={engine.batch}, int8 KV cache, window="
          f"{cfg.window})")
    for r in reqs:
        mode = "greedy" if r.temperature == 0 else "sampled"
        print(f"  req {r.uid} ({mode}): {r.prompt} -> {r.out_tokens}")


if __name__ == "__main__":
    main()
