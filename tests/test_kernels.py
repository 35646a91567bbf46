"""Per-kernel shape/dtype sweeps: pallas_fused (interpret) vs ref.py
oracles."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import attention as iattn
from repro.core import intmath, norms
from repro.core import softmax as ism
from repro.core.dyadic import fit_dyadic
from repro.kernels import ref
from repro.ops import RequantSpec, get_backend

PALLAS = get_backend("pallas_fused")
REF = get_backend("ref")


def _epilogue(form, rng, k, n):
    """A matmul epilogue named ``<requant><out bits>[_bias]``: the
    RequantSpec and the bias and per-channel multipliers it takes."""
    bias = rng.integers(-2**18, 2**18, (n,)).astype(np.int32) \
        if form.endswith("_bias") else None
    bits = 8 if "8" in form else 16
    if form.startswith("tensor"):
        dn = fit_dyadic(1 / 4000.0, k * 127 * 127 + 2**18)
        return RequantSpec.per_tensor(dn, bits), bias, None
    bvec = rng.integers(1000, 30000, (n,)).astype(np.int32)
    return RequantSpec.per_channel(28, 7, bits), bias, bvec


# blocks None take the shape rule (kernels.int8_matmul.matmul_blocks)
@pytest.mark.parametrize("m,k,n,bm,bn,bk,form", [
    pytest.param(128, 256, 128, 128, 128, 256, "tensor8_bias",
                 id="128-256-128-128-128-256"),
    pytest.param(256, 1024, 384, 128, 128, 256, "tensor8_bias",
                 id="256-1024-384-128-128-256"),
    pytest.param(64, 128, 512, 64, 128, 128, "tensor8_bias",
                 id="64-128-512-64-128-128"),
    pytest.param(128, 896, 128, 128, 128, 128, "tensor8_bias",
                 id="128-896-128-128-128-128"),
    # the rule: one K step, so no accumulator (RoBERTa's q, up, down)
    (256, 768, 768, None, None, None, "tensor8"),
    (64, 768, 3072, None, None, None, "channel16_bias"),
    (64, 3072, 768, None, None, None, "channel16"),
    # the rule splits K = 8192 into two steps (a decode-sized w2)
    (16, 8192, 2048, None, None, None, "channel16_bias"),
    # an odd N takes its whole width as one block
    (8, 512, 300, None, None, None, "channel8"),
    # explicit blocks: split K and one K step, both epilogues
    (64, 1024, 256, 64, 128, 256, "channel8_bias"),
    (128, 512, 256, 128, 256, 512, "tensor16_bias"),
])
def test_int8_matmul_shapes(rng, m, k, n, bm, bn, bk, form):
    x = jnp.asarray(rng.integers(-127, 128, (m, k)).astype(np.int8))
    w = jnp.asarray(rng.integers(-127, 128, (k, n)).astype(np.int8))
    spec, bias, bvec = (jnp.asarray(v) if isinstance(v, np.ndarray) else v
                        for v in _epilogue(form, rng, k, n))
    blocks = {key: v for key, v in zip(("bm", "bn", "bk"), (bm, bn, bk))
              if v is not None}
    got = np.asarray(PALLAS.int8_matmul(x, w, spec, bias32=bias,
                                        b_vec=bvec, **blocks))
    want = np.asarray(REF.int8_matmul(x, w, spec, bias32=bias, b_vec=bvec))
    assert got.dtype == spec.out_dtype
    assert np.array_equal(got, want)
    # most outputs lie inside the clip, so the requant itself is compared
    assert (np.abs(want) < (1 << (spec.out_bits - 1)) - 1).mean() > 0.5
    assert np.abs(want).max() > 0


# (M, K, N, output bits, most grid steps): the benchmark cells' dense
# projections (RoBERTa-base over 512 x 64 tokens, Granite-3-2B over
# 8 x 1024), a decode step, one tp = 4 shard, and an odd N
@pytest.mark.parametrize("m,k,n,out_bits,max_steps", [
    (32768, 768, 768, 8, 64),          # RoBERTa q, k, v, o
    (32768, 768, 3072, 16, 192),       # RoBERTa up
    (32768, 3072, 768, 16, 64),        # RoBERTa down
    (8192, 2048, 2048, 8, 32),         # Granite q, o
    (8192, 2048, 512, 8, 16),          # Granite k, v
    (8192, 2048, 8192, 16, 128),       # Granite w1, w3
    (8192, 8192, 2048, 16, 128),       # Granite w2
    (8, 2048, 8192, 16, None),         # Granite w1, decode
    (256, 2048, 8192 // 4, 16, None),  # Granite w1, a tp = 4 shard
    (8, 2048, 12292, 16, None),        # N with no 128-multiple divisor
])
def test_int8_matmul_block_rule(m, k, n, out_bits, max_steps):
    from repro.analysis.contracts import check_launch, tpu_block_violations
    from repro.kernels.int8_matmul import BLOCK_BYTES, matmul_blocks
    bm, bn, bk = matmul_blocks(m, n, k, out_bits, True, True)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    assert not tpu_block_violations("x8", (bm, bk), (m, k))
    assert not tpu_block_violations("w8", (bk, bn), (k, n))
    rep = check_launch("int8_matmul", m=m, n=n, k=k, bm=bm, bn=bn, bk=bk,
                       out_bits=out_bits, has_bias=True, per_channel=True)
    assert rep.ok and rep.vmem_bytes <= BLOCK_BYTES
    if max_steps is not None:
        assert math.prod(rep.grid) <= max_steps


def test_int8_matmul_perchannel(rng):
    m, k, n = 128, 512, 256
    x = rng.integers(-127, 128, (m, k)).astype(np.int8)
    w = rng.integers(-127, 128, (k, n)).astype(np.int8)
    bvec = rng.integers(1000, 30000, (n,)).astype(np.int32)
    got = np.asarray(PALLAS.int8_matmul(
        jnp.asarray(x), jnp.asarray(w), RequantSpec.per_channel(28, 7),
        b_vec=jnp.asarray(bvec)))
    want = np.asarray(ref.ref_int8_matmul_perchannel(
        jnp.asarray(x), jnp.asarray(w), None, jnp.asarray(bvec), 28, 7))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("rows,rowlen", [(8, 128), (32, 256), (5, 96)])
def test_exp16_tile_matches_core(rows, rowlen):
    """The attention kernels' inlined i-exp (``_exp16_tile``) equals
    ``core.softmax._exp16`` elementwise on (rows, rowlen) tiles that
    sweep every score offset of the clamped band, and offsets past it."""
    from jax.experimental import pallas as pl
    from repro.kernels.int_softmax import _exp16_tile
    sp = ism.make_isoftmax(s_score=3.5e-4, qmax_score=128 * 127 * 127)
    q = np.arange(-sp.q_band - 4096, 1)
    steps = -(-len(q) // (rows * rowlen))
    x = np.pad(q, (steps * rows * rowlen - len(q), 0), mode="edge")
    x = jnp.asarray(x.reshape(steps * rows, rowlen), jnp.int32)

    def kernel(x_ref, o_ref):
        o_ref[...] = _exp16_tile(x_ref[...], sp)
    block = pl.BlockSpec((rows, rowlen), lambda i: (i, 0))
    got = pl.pallas_call(kernel, grid=(steps,), in_specs=[block],
                         out_specs=block,
                         out_shape=jax.ShapeDtypeStruct(x.shape, jnp.int32),
                         interpret=True)(x)
    want = np.asarray(ism._exp16(x, sp))
    assert np.array_equal(np.asarray(got), want)
    assert want.max() == want[-1, -1] > 0         # e^0 at offset 0
    assert len(np.unique(want)) > 1000


@pytest.mark.parametrize("shape", [
    (512,), (3, 7, 512), (16, 1024),
    (2, 64, 3072),      # the up projection's own layout
    (16, 3072),         # a decode step: one block
    (5, 1536),          # rows not a multiple of 8
    (3, 7, 100),        # last dim not a multiple of 128: flattened
])
def test_int_gelu_kernel(rng, shape):
    s = 16 / 1024
    plan = intmath.make_igelu(s, 1024)
    dn = fit_dyadic(plan.s_out / (8 / 127), 1024 * 2 * plan.q_one)
    q = rng.integers(-1024, 1025, shape).astype(np.int32)
    got = PALLAS.int_gelu(jnp.asarray(q), plan, dn, out_bits=8)
    want = REF.int_gelu(jnp.asarray(q), plan, dn, out_bits=8)
    assert got.dtype == want.dtype == jnp.int8
    assert got.shape == shape
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_int_gelu_layout():
    """The view and row block ``int_gelu`` launches with, per shape."""
    from repro.kernels.int_gelu import gelu_layout
    # RoBERTa-base's up projection at 512 x 64 tokens: (br, n) blocks of
    # the natural layout, 256 grid steps a layer
    rows, lanes, br, natural = gelu_layout((512, 64, 3072))
    assert (rows, lanes, br, natural) == (32768, 3072, 128, True)
    assert rows // br == 256
    assert gelu_layout((32768, 3072)) == (32768, 3072, 128, True)
    assert gelu_layout((16, 3072)) == (16, 3072, 16, True)
    assert gelu_layout((5, 1536)) == (5, 1536, 5, True)
    # 2,100 elements padded to three (8, 128) tiles, one block
    assert gelu_layout((3, 7, 100)) == (24, 128, 24, False)


def _norm_case(rng, d, subtract_mean, qmax=1024):
    plan = norms.make_inorm(d, 8 / qmax, qmax, 2 / 127, 8 / 127,
                            subtract_mean=subtract_mean)
    gamma = rng.normal(1, 0.2, d).astype(np.float32)
    beta = rng.normal(0, 0.2, d).astype(np.float32) if subtract_mean \
        else None
    return (plan,) + norms.quantize_norm_weights(
        jnp.asarray(gamma), jnp.asarray(beta) if beta is not None else
        None, plan)


@pytest.mark.parametrize("rows,d,subtract_mean", [
    (16, 768, True), (16, 512, False), (16, 384, True),
    # several shape-chosen row blocks, ragged tails zero-padded
    (1000, 768, True), (4104, 768, True), (4096, 2048, False),
], ids=["768-True", "512-False", "384-True", "1000x768", "4104x768",
        "4096x2048-rms"])
def test_int_layernorm_kernel(rng, rows, d, subtract_mean):
    from repro.kernels.int_layernorm import norm_block_rows
    if rows > 16:
        assert norm_block_rows(-(-rows // 8) * 8, d) < rows
    plan, qg, qb = _norm_case(rng, d, subtract_mean)
    q = rng.integers(-1024, 1025, (rows, d)).astype(np.int32)
    got = np.asarray(PALLAS.int_layernorm(jnp.asarray(q), qg, qb, plan))
    want = np.asarray(REF.int_layernorm(jnp.asarray(q), qg, qb, plan))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("d,subtract_mean", [(768, True), (2048, False)])
def test_int_layernorm_kernel_edge_rows(rng, d, subtract_mean):
    """Rows with sigma = 0, rows at +-qmax_in, and the largest variance a
    row in range can have (alternating +-qmax_in: the squared sum's
    largest reachable value), between ordinary rows, over several
    blocks."""
    qmax = 1 << 13      # the served residual stream's qmax_res
    plan, qg, qb = _norm_case(rng, d, subtract_mean, qmax)
    alt = np.where(np.arange(d) % 2, qmax, -qmax)
    one_up = np.full(d, -qmax).astype(np.int64)
    one_up[d // 3] = qmax
    edge = np.stack([np.zeros(d), np.full(d, 37), np.full(d, qmax),
                     np.full(d, -qmax), alt, -alt, one_up,
                     np.full(d, 1)]).astype(np.int32)
    q = rng.integers(-qmax, qmax + 1, (1024, d)).astype(np.int32)
    q[::5][:len(edge) * 25] = np.tile(edge, (25, 1))
    got = np.asarray(PALLAS.int_layernorm(jnp.asarray(q), qg, qb, plan))
    want = np.asarray(REF.int_layernorm(jnp.asarray(q), qg, qb, plan))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("d,subtract_mean", [(768, True), (2048, False)])
def test_int_layernorm_kernel_row_shifts(rng, d, subtract_mean):
    """Rows from a few LSB to the bus's edge take the per-row shifts
    before squaring, left and right (``core.norms.row_shift``), and the
    kernel stays bit-identical to ``i_norm`` at each."""
    qmax = 1 << 13
    plan, qg, qb = _norm_case(rng, d, subtract_mean, qmax)
    scale = 2.0 ** rng.uniform(-1, 14, (512, 1))
    q = np.clip(np.round(rng.normal(0, 1, (512, d)) * scale),
                -qmax, qmax).astype(np.int32)
    sh = np.asarray(norms.row_shift(jnp.asarray(q), plan)).ravel()
    # every shift a row within the bus reaches (a shift of pre_shift
    # itself needs a centred value past qmax_in)
    assert set(sh) == set(range(-plan.pre_shift, plan.pre_shift))
    got = np.asarray(PALLAS.int_layernorm(jnp.asarray(q), qg, qb, plan))
    want = np.asarray(REF.int_layernorm(jnp.asarray(q), qg, qb, plan))
    assert np.array_equal(got, want)


def _run_tile(fn, *xs):
    """Apply an in-kernel helper to (8, n) int32 tiles in interpret mode."""
    from jax.experimental import pallas as pl

    def kernel(*refs):
        refs[-1][...] = fn(*(r[...] for r in refs[:-1]))
    n = -(-len(xs[0]) // 1024) * 1024
    tiles = [jnp.asarray(np.pad(np.asarray(x, np.int32), (0, n - len(x)))
                         .reshape(8, n // 8)) for x in xs]
    out = pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(
        tiles[0].shape, jnp.int32), interpret=True)(*tiles)
    return np.asarray(out).reshape(-1)[:len(xs[0])]


def test_int_layernorm_isqrt_exact(rng):
    """The kernel's division-free square root is floor(sqrt(n)) on all of
    int32's non-negative range, and 0 for n <= 0 (as core.intmath.i_sqrt)."""
    from repro.kernels.int_layernorm import isqrt_tile
    k = np.concatenate([np.arange(1, 300), rng.integers(300, 46341, 2000),
                        [46339, 46340]]).astype(np.int64)
    n = np.concatenate([[0, 1, 2, 3, 46340 ** 2, 46340 ** 2 - 1,
                         2 ** 31 - 1, 2 ** 31 - 2, 2 ** 30, -1, -5,
                         -2 ** 31], k * k - 1, k * k,
                        np.minimum(k * k + 1, 2 ** 31 - 1),
                        rng.integers(0, 2 ** 31, 4000)])
    got = _run_tile(isqrt_tile, n)
    want = [math.isqrt(int(v)) if v > 0 else 0 for v in n]
    assert np.array_equal(got, want)


@pytest.mark.parametrize("m", [8, 15, 16, 20, 24, 30])
def test_int_layernorm_recip_exact(m):
    """The kernel's reciprocal is the floor quotient 2^m // sigma for
    every sigma an int32 square root can give (1..46340)."""
    from repro.kernels.int_layernorm import recip_tile
    sigma = np.arange(1, 46341)
    got = _run_tile(lambda s: recip_tile(1 << m, s), sigma)
    assert np.array_equal(got, (1 << m) // sigma)


@pytest.mark.parametrize("h,hkv,window", [(4, 2, 0), (4, 4, 0), (2, 1, 96),
                                          (8, 2, 0)])
def test_fused_attention_kernel(rng, h, hkv, window):
    b, s, d = 2, 256, 64
    plan = iattn.make_iattention(d, 8/127, 8/127, 4/127, 4/127)
    q8 = np.clip(rng.normal(0, 40, (b, s, h, d)), -127, 127).astype(np.int8)
    k8 = np.clip(rng.normal(0, 40, (b, s, hkv, d)), -127, 127) \
        .astype(np.int8)
    v8 = np.clip(rng.normal(0, 40, (b, s, hkv, d)), -127, 127) \
        .astype(np.int8)
    got = np.asarray(PALLAS.int_attention(
        jnp.asarray(q8), jnp.asarray(k8), jnp.asarray(v8), plan,
        causal=True, window=window, bq=64, bkv=64))
    want = np.asarray(REF.int_attention(
        jnp.asarray(q8), jnp.asarray(k8), jnp.asarray(v8), plan,
        causal=True, window=window))
    assert np.array_equal(got, want)


def test_int8_matmul_wide_output_bits(rng):
    """Regression: out_bits=11 results must stay int32 (the FFN up-proj);
    an int8 out_dtype silently truncated them (see ops.int8_matmul)."""
    from repro.quant.plans import make_linear_plan
    import repro.models.intlayers as il
    plan = make_linear_plan(8 / 127, 2 / 127, 16 / 1024, 128, out_bits=11)
    x8 = jnp.asarray(rng.integers(-127, 128, (16, 128)), jnp.int8)
    w = rng.normal(0, 0.1, (128, 256))
    from repro.quant.convert import _q_linear
    qw, _ = _q_linear(jnp.asarray(w), plan)
    a = np.asarray(il.int_linear(x8, qw, plan, ops="ref"))
    b = np.asarray(il.int_linear(x8, qw, plan, ops="pallas_fused"))
    assert a.dtype == b.dtype == np.int32
    assert np.array_equal(a, b)
    assert np.abs(a).max() > 127          # exercises the >int8 range
