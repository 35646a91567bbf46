"""Production training driver.

Wires every substrate together: config registry -> sharded QAT train step
(SP/TP/ZeRO-1/FSDP rules) -> fault-tolerant loop (async checkpoints,
straggler detection, restart) -> data pipeline.  Runs on whatever devices
exist (1 CPU locally, a v5e pod in production — the mesh shape adapts).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b \
      --reduced --steps 100 --batch 8 --seq 256 [--ckpt-dir DIR]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs.registry import get_config
from repro.data.pipeline import make_train_iterator
from repro.distributed.fault import FaultTolerantLoop, StragglerDetector
from repro.launch import shardings as shd
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.models import transformer as tf
from repro.optim import adamw_init
from repro.optim.adamw import AdamWConfig
from repro.optim.schedule import linear_warmup_cosine


def choose_mesh():
    n = len(jax.devices())
    model = 1
    for m in (16, 8, 4, 2, 1):
        if n % m == 0 and m <= n:
            model = m
            break
    return make_mesh((n // model, model), ("data", "model"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data", default=None,
                    help="token file (memory-mapped); default synthetic")
    ap.add_argument("--int-eval", action="store_true",
                    help="after training, quantize and run one integer "
                         "prefill through the configured op backend")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = M.reduce_config(cfg, dtype="float32", vocab=1024)
    mesh = choose_mesh()
    print(f"arch={cfg.name} params~{cfg.param_count()/1e6:.1f}M "
          f"mesh={dict(zip(mesh.axis_names, mesh.axis_sizes))}")

    data = make_train_iterator(cfg, args.seq, args.batch, path=args.data,
                               host_id=jax.process_index(),
                               n_hosts=jax.process_count())
    opt_cfg = AdamWConfig(lr=args.lr, zero1=True)
    lr_fn = linear_warmup_cosine(max(args.steps // 10, 1), args.steps)

    with jax.set_mesh(mesh):
        params = tf.init_params(jax.random.key(0), cfg)
        p_sh = shd.param_pspecs(params, mesh,
                                fsdp=cfg.param_count() > 2e10)
        step = steps_mod.make_train_step(cfg, opt_cfg, lr_fn,
                                         param_specs=p_sh)
        opt = adamw_init(params, opt_cfg)
        train_step = jax.jit(step, donate_argnums=(0, 1))

        def step_fn(state, batch):
            params, opt = state
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            params, opt, metrics = train_step(params, opt, batch)
            return (params, opt), metrics

        mgr = CheckpointManager(args.ckpt_dir)
        start = mgr.latest_step() or 0
        state = (params, opt)
        if start:
            print(f"resuming from step {start}")
            state, meta = mgr.restore(state)
            data.load_state_dict(meta["extra"]["data"])
        loop = FaultTolerantLoop(step_fn, mgr, data,
                                 ckpt_every=args.ckpt_every,
                                 straggler=StragglerDetector())
        t0 = time.time()
        state, log = loop.run(state, args.steps, start_step=start)
        dt = time.time() - t0
    tok_s = args.batch * args.seq * (args.steps - start) / max(dt, 1e-9)
    print(f"loss {log[0]['loss']:.3f} -> {log[-1]['loss']:.3f}  "
          f"({tok_s:,.0f} tok/s, restarts={loop.restarts}, "
          f"stragglers={loop.straggler.flagged})")
    if args.int_eval:
        from repro import ops as rops
        from repro.models import inttransformer as it
        from repro.quant import convert
        params = state[0]
        qp, plans = convert.quantize_params(params, cfg)
        ops = rops.resolve_ops(None, cfg)
        batch = next(data)
        logits = it.int_prefill(
            qp, {"tokens": jnp.asarray(batch["tokens"])}, plans, cfg,
            ops=ops)
        print(f"int-eval ({ops.name}): logits {logits.shape} "
              f"max|.|={float(jnp.abs(logits).max()):.2f}")


if __name__ == "__main__":
    main()
