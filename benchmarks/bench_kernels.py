"""Kernel microbenchmarks: ref (jnp) path timing + Pallas interpret-mode
validation cost, per kernel.  On real TPU the same harness times the
compiled kernels; on CPU it documents the oracle path and asserts
ref/pallas_fused agreement as a by-product."""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import ops
from repro.core import attention as iattn
from repro.core import norms
from repro.core.dyadic import fit_dyadic
from repro.ops import RequantSpec


def _t(f, *args, iters=5):
    f(*args)
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(f(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def run():
    rng = np.random.default_rng(0)
    rows = []

    be = ops.resolve_ops("ref")
    m, k, n = 512, 2048, 512
    x = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    w = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    spec = RequantSpec.per_tensor(fit_dyadic(1 / 4000.0, k * 127 * 127))
    f = jax.jit(lambda a, b: be.int8_matmul(a, b, spec))
    us = _t(f, x, w)
    flops = 2 * m * k * n
    rows.append(("kernel_int8_matmul_us", round(us, 1),
                 f"{flops / us / 1e3:.1f} GOP/s (ref path, CPU)"))

    d = 4096
    pl = norms.make_inorm(d, 2**-9, 1 << 13, 2 / 127, 8 / 127)
    g = jnp.ones((d,), jnp.int32) * 64
    q = jnp.asarray(rng.integers(-8192, 8192, (64, d)), jnp.int32)
    f = jax.jit(lambda a: be.int_layernorm(a, g, None, pl))
    rows.append(("kernel_int_layernorm_us", round(_t(f, q), 1), "64x4096"))

    b, s, h, hd = 1, 1024, 8, 128
    ap = iattn.make_iattention(hd, 8/127, 8/127, 4/127, 4/127)
    q8 = jnp.asarray(rng.integers(-127, 128, (b, s, h, hd)), jnp.int8)
    k8 = jnp.asarray(rng.integers(-127, 128, (b, s, h, hd)), jnp.int8)
    f = jax.jit(lambda a, kk: be.int_attention(a, kk, kk, ap))
    rows.append(("kernel_int_attention_us", round(_t(f, q8, k8), 1),
                 "1x1024x8x128 causal (ref path)"))

    # fused-vs-unfused attention: the single-launch pallas_fused kernel
    # against the two-pass reference on the same problem (modest shape —
    # interpret mode on CPU; on TPU the same harness times the compiled
    # kernel).  bench_fused_attention sweeps more shapes.
    b, s, h, hd = 1, 256, 4, 64
    q8 = jnp.asarray(rng.integers(-127, 128, (b, s, h, hd)), jnp.int8)
    k8 = jnp.asarray(rng.integers(-127, 128, (b, s, h, hd)), jnp.int8)
    ap = iattn.make_iattention(hd, 8/127, 8/127, 4/127, 4/127)
    fused_be = ops.resolve_ops("pallas_fused")
    f_ref = jax.jit(lambda a, kk: be.int_attention(a, kk, kk, ap))
    f_fused = jax.jit(lambda a, kk: fused_be.int_attention(a, kk, kk, ap))
    us_ref = _t(f_ref, q8, k8, iters=3)
    us_fused = _t(f_fused, q8, k8, iters=3)
    rows.append(("kernel_attn_two_pass_us", round(us_ref, 1),
                 "1x256x4x64 causal (ref two-pass)"))
    rows.append(("kernel_attn_fused_us", round(us_fused, 1),
                 "1x256x4x64 causal (pallas_fused, one launch)"))
    rows.append(("kernel_attn_fused_vs_two_pass", round(us_fused / us_ref, 2),
                 "wall-clock ratio (interpret mode on CPU; <1 on TPU)"))
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(str(x) for x in r))
