import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (the two lines above MUST precede any jax import).

For every (architecture x input shape) cell this lowers + compiles the
real step function — QAT train step for train shapes, the integer
prefill / decode for serving shapes — against the production mesh
(16x16 single pod, 2x16x16 multi-pod), prints memory_analysis() and
cost_analysis(), and records everything benchmarks/roofline.py needs.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
      --shape train_4k [--multi-pod] [--layers-probe] [--out DIR]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.registry import ASSIGNED, get_config
from repro.launch import shardings as shd
from repro.launch import steps as steps_mod
from repro import ops as rops
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.models.common import SHAPES, ShapeConfig
from repro.optim.adamw import AdamWConfig
from repro.quant import plans as qplans

SDS = jax.ShapeDtypeStruct


from repro.launch.cells import cell_supported  # noqa: E402 (re-export)


def _train_variant(cfg, n_groups):
    """Probe variant: n_groups layer groups, UNROLLED (a lax.scan body is
    cost-counted once regardless of trip count, so the probe must unroll
    to expose the per-group delta)."""
    from repro.models.transformer import layer_group_spec
    gl, ng, _ = layer_group_spec(cfg)
    upd = {"num_layers": gl * n_groups, "scan_layers": False}
    if cfg.family == "encdec":
        upd.update(enc_layers=n_groups, dec_layers=n_groups,
                   num_layers=n_groups)
    return dataclasses.replace(cfg, **upd)


def lower_cell(cfg, shape: ShapeConfig, mesh, zero1=None):
    """Returns (lowered, jit_fn, arg_specs) for one cell."""
    zero1 = True if zero1 is None else zero1
    fsdp = cfg.param_count() > 2e10
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt_cfg = AdamWConfig(zero1=zero1)
            pspec = M.params_spec(cfg)
            p_sh = shd.param_pspecs(pspec, mesh, fsdp=fsdp)
            accum = 4 if fsdp else 1
            step = steps_mod.make_train_step(cfg, opt_cfg,
                                             param_specs=p_sh,
                                             accum_steps=accum)
            from repro.optim import adamw_init
            ospec = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), pspec)
            o_sh = _opt_pspecs(ospec, p_sh)
            batch = M.input_specs(cfg, shape)
            b_sh = shd.batch_pspecs(batch, mesh)
            from jax.sharding import PartitionSpec as P
            metrics_sh = {"grad_norm": P(), "loss": P(), "ce": P(),
                          "aux": P()}
            fn = jax.jit(
                step,
                in_shardings=(p_sh, o_sh, b_sh),
                out_shardings=(p_sh, o_sh, metrics_sh),
                donate_argnums=(0, 1))
            lowered = fn.lower(pspec, ospec, batch)
            return lowered
        plans = qplans.build_layer_plans(cfg)
        qspec = M.qparams_spec(cfg, plans)
        q_sh = shd.param_pspecs(qspec, mesh)
        ops = rops.resolve_ops(None, cfg)
        if shape.kind == "prefill":
            step = steps_mod.make_prefill_step(cfg, plans, ops)
            batch = M.input_specs(cfg, shape)
            b_sh = shd.batch_pspecs(batch, mesh)
            args = [qspec, batch]
            shards = [q_sh, b_sh]
            if cfg.pos == "rope":
                rspec = steps_mod.rope_table_spec(cfg, shape.seq_len)
                args.append(rspec)
                shards.append(jax.tree.map(
                    lambda _: jax.sharding.PartitionSpec(), rspec))
            fn = jax.jit(step, in_shardings=tuple(shards))
            return fn.lower(*args)
        # decode
        step = steps_mod.make_decode_step(cfg, plans, shape.seq_len, ops)
        b = shape.global_batch
        with_mem = cfg.family in ("vlm", "encdec")
        cache = _decode_cache_spec(cfg, b, shape.seq_len, with_mem)
        c_sh = shd.cache_pspecs(cache, mesh, cfg)
        batch = M.input_specs(cfg, shape)
        tok, pos = batch["tokens"], batch["pos"]
        tp_sh = shd.batch_pspecs({"tokens": tok, "pos": pos}, mesh)
        args = [qspec, cache, tok, pos]
        shards = [q_sh, c_sh, tp_sh["tokens"], tp_sh["pos"]]
        if cfg.pos == "rope":
            rspec = steps_mod.rope_table_spec(cfg, shape.seq_len)
            args.append(rspec)
            shards.append(jax.tree.map(
                lambda _: jax.sharding.PartitionSpec(), rspec))
        fn = jax.jit(step, in_shardings=tuple(shards),
                     donate_argnums=(1,))
        return fn.lower(*args)


def _decode_cache_spec(cfg, batch, cache_len, with_mem):
    from repro.models import inttransformer as it

    def build():
        mem8 = None
        if with_mem:
            n = cfg.n_img_tokens if cfg.family == "vlm" else 4096
            mem8 = jnp.zeros((batch, n, cfg.d_model), jnp.int8)
        plans = qplans.build_layer_plans(cfg)
        qspec_real = None
        if mem8 is not None:
            # cross K/V need qparams; use zeros-like from spec
            qs = M.qparams_spec(cfg, plans)
            qspec_real = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), qs)
        return it.init_decode_cache(cfg, batch, cache_len, mem8,
                                    qspec_real, plans)
    return jax.eval_shape(build)


def _opt_pspecs(ospec, p_sh):
    """ZeRO-1 moment shardings: the param spec plus 'data' on the first
    still-unsharded divisible dim — optimizer state spreads over the DP
    axis (scalars replicated)."""
    from jax.sharding import PartitionSpec as P

    def zero1(spec, leaf):
        if leaf.ndim == 0:
            return P()
        out = list(spec) + [None] * (leaf.ndim - len(spec))
        flat = [a for s in out if s for a in
                (s if isinstance(s, tuple) else (s,))]
        if "data" in flat:                 # already data-sharded (2-D MoE)
            return P(*out)
        for i, (s, dim) in enumerate(zip(out, leaf.shape)):
            if s is None and dim % 16 == 0 and dim >= 16:
                out[i] = "data"
                break
        return P(*out)

    m_sh = jax.tree.map(zero1, p_sh, ospec.m)
    return type(ospec)(step=P(), m=m_sh, v=m_sh)


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             layers_probe: bool = False, tag: str = ""):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    skip = cell_supported(arch, shape_name)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16", "tag": tag}
    if skip:
        rec["skipped"] = skip
        _dump(rec, out_dir)
        print(f"[SKIP] {arch} x {shape_name}: {skip}")
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        lowered = lower_cell(cfg, shape, mesh)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_gib": ma.argument_size_in_bytes / 2**30,
            "output_gib": ma.output_size_in_bytes / 2**30,
            "temp_gib": ma.temp_size_in_bytes / 2**30,
            "alias_gib": ma.alias_size_in_bytes / 2**30,
            "peak_gib": (ma.argument_size_in_bytes
                         + ma.output_size_in_bytes
                         + ma.temp_size_in_bytes
                         - ma.alias_size_in_bytes) / 2**30,
        }
        ca = compiled.cost_analysis() or {}
        rec["cost"] = {"flops": ca.get("flops", 0.0),
                       "bytes": ca.get("bytes accessed", 0.0)}
        from benchmarks.roofline import collective_wire_bytes
        wire, by_kind = collective_wire_bytes(compiled.as_text())
        rec["collective_bytes_dev"] = wire
        rec["collective_by_kind"] = by_kind
        print(f"[OK]   {arch} x {shape_name} ({rec['mesh']}): "
              f"peak {rec['memory']['peak_gib']:.2f} GiB/dev, "
              f"flops/dev {rec['cost']['flops']:.3e}, "
              f"coll {wire/2**30:.3f} GiB/dev  "
              f"(lower {rec['lower_s']}s compile {rec['compile_s']}s)")
        if layers_probe and not multi_pod:
            rec["probe"] = _probe_layers(cfg, shape, mesh)
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[FAIL] {arch} x {shape_name}: {rec['error'][:200]}")
    _dump(rec, out_dir)
    return rec


def _probe_layers(cfg, shape, mesh):
    """Compile 1-group and 2-group UNROLLED variants at reduced batch ->
    per-layer-group flops/bytes for the scan-undercount correction
    (benchmarks/roofline.py).  Flops/bytes scale linearly in batch, so the
    probe batch is shrunk to one sequence per data shard and the report
    rescales by ``batch_scale``."""
    out = {}
    b_probe = min(shape.global_batch, 16)
    out["batch_scale"] = shape.global_batch / b_probe
    out["b_probe"] = b_probe
    batches = [b_probe]
    if shape.global_batch >= 32:
        batches.append(32)        # second point: affine-in-batch fit
    for bp in batches:
        pshape = dataclasses.replace(shape, global_batch=bp)
        for ng in (1, 2):
            c = _train_variant(cfg, ng)
            comp = lower_cell(c, pshape, mesh).compile()
            ca = comp.cost_analysis() or {}
            key = f"ng{ng}" if bp == b_probe else f"ng{ng}b{bp}"
            out[key] = {"flops": ca.get("flops", 0.0),
                        "bytes": ca.get("bytes accessed", 0.0)}
    return out


def _dump(rec, out_dir):
    os.makedirs(out_dir, exist_ok=True)
    name = f"{rec['arch']}_{rec['shape']}_{rec['mesh']}"
    if rec.get("tag"):
        name += f"_{rec['tag']}"
    with open(os.path.join(out_dir, name + ".json"), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--layers-probe", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    if args.all:
        ok = fail = 0
        for arch in ASSIGNED:
            for shape in SHAPES:
                r = run_cell(arch, shape, args.multi_pod, args.out,
                             args.layers_probe, args.tag)
                if "error" in r:
                    fail += 1
                else:
                    ok += 1
        print(f"done: {ok} ok, {fail} failed")
        sys.exit(1 if fail else 0)
    assert args.arch and args.shape
    r = run_cell(args.arch, args.shape, args.multi_pod, args.out,
                 args.layers_probe, args.tag)
    sys.exit(1 if "error" in r else 0)


if __name__ == "__main__":
    main()
