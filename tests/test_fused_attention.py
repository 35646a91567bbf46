"""pallas_fused: exact-integer parity with the two-pass reference.

The contract under test (docs/KERNELS.md): the single-launch fused
attention+requant kernel is *bit-exact* against
``kernels.ref.ref_int_attention`` for every RequantSpec epilogue form,
on self- and cross-attention, across head dims / sequence lengths /
masks, including shapes that force the backend's two-pass fallback.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import attention as iattn
from repro.core.dyadic import fit_dyadic
from repro.ops import RequantSpec, get_backend, resolve_ops

FUSED = get_backend("pallas_fused")
REF = get_backend("ref")


def _qkv(rng, b, sq, skv, h, hkv, d):
    q8 = np.clip(rng.normal(0, 40, (b, sq, h, d)), -127, 127).astype(np.int8)
    k8 = np.clip(rng.normal(0, 40, (b, skv, hkv, d)), -127, 127) \
        .astype(np.int8)
    v8 = np.clip(rng.normal(0, 40, (b, skv, hkv, d)), -127, 127) \
        .astype(np.int8)
    return jnp.asarray(q8), jnp.asarray(k8), jnp.asarray(v8)


def _plan(d):
    return iattn.make_iattention(d, 8 / 127, 8 / 127, 4 / 127, 4 / 127)


@pytest.mark.parametrize("sq,skv,h,hkv,d,causal,window", [
    (256, 256, 4, 2, 64, True, 0),      # causal GQA
    (256, 256, 4, 4, 64, True, 96),     # sliding window
    (128, 256, 4, 4, 64, False, 0),     # cross-attention (rect, no mask)
    (64, 192, 8, 2, 32, False, 0),      # cross + GQA + non-128 seq
    (128, 128, 2, 2, 128, True, 0),     # wide head dim
    (192, 192, 2, 1, 48, True, 0),      # non-multiple-of-block seq + d
])
def test_exact_parity_per_tensor(rng, sq, skv, h, hkv, d, causal, window):
    plan = _plan(d)
    q8, k8, v8 = _qkv(rng, 2, sq, skv, h, hkv, d)
    got = np.asarray(FUSED.int_attention(q8, k8, v8, plan, causal=causal,
                                         window=window, bq=64, bkv=64))
    want = np.asarray(REF.int_attention(q8, k8, v8, plan, causal=causal,
                                        window=window))
    assert np.array_equal(got, want)
    assert got.dtype == np.int8


@pytest.mark.parametrize("form", ["per_tensor", "per_channel", "raw"])
@pytest.mark.parametrize("cross", [False, True])
def test_exact_parity_all_requant_forms(rng, form, cross):
    h, hkv, d = 4, 2, 64
    sq, skv = (64, 192) if cross else (128, 128)
    causal = not cross
    plan = _plan(d)
    q8, k8, v8 = _qkv(rng, 1, sq, skv, h, hkv, d)
    b_vec = None
    if form == "per_tensor":
        spec = RequantSpec.per_tensor(fit_dyadic(plan.dn_out.value * 1.7,
                                                 127 * (1 << 8)))
    elif form == "per_channel":
        spec = RequantSpec.per_channel(c=28, pre=7)
        b_vec = jnp.asarray(np.random.default_rng(1).integers(
            1000, 30000, (h * d,)), jnp.int32)
    else:
        spec = RequantSpec.raw()
    got = np.asarray(FUSED.int_attention(q8, k8, v8, plan, causal=causal,
                                         requant=spec, b_vec=b_vec,
                                         bq=64, bkv=64))
    want = np.asarray(REF.int_attention(q8, k8, v8, plan, causal=causal,
                                        requant=spec, b_vec=b_vec))
    assert np.array_equal(got, want)
    if form == "raw":
        assert got.dtype == np.int32
        # raw == the int32 P*V accumulator, untouched
        assert np.abs(got).max() > 127


@pytest.mark.parametrize("sq,skv", [
    (131, 131),    # prime > 128: largest divisor block is 1
    (8, 128),      # decode-sized query: oracle wins
    (64, 262),     # 2*131 KV: largest usable divisor (2) under min_block
])
def test_untileable_shapes_fall_back_exactly(rng, sq, skv):
    """Divisor-starved / tiny lengths: the backend falls back to the
    two-pass path and stays exact (the kernel is never entered —
    _can_tile refuses the shape)."""
    h, hkv, d = 4, 2, 64
    plan = _plan(d)
    assert not FUSED._can_tile(sq, skv, *_fit2(sq, skv))
    q8, k8, v8 = _qkv(rng, 1, sq, skv, h, hkv, d)
    got = np.asarray(FUSED.int_attention(q8, k8, v8, plan, causal=False))
    want = np.asarray(REF.int_attention(q8, k8, v8, plan, causal=False))
    assert np.array_equal(got, want)


def _fit2(sq, skv):
    from repro.analysis.contracts import fit_block as _fit_block
    return _fit_block(128, sq), _fit_block(128, skv)


def test_oversized_rows_use_chunked_streaming(rng):
    """Skv beyond the exact row-sum budget (2^15) routes to the chunked
    two-pass streaming path; per-channel/raw epilogues raise there — the
    model datapath only carries per-tensor at such lengths."""
    from repro.kernels.int_attention_fused import MAX_SKV
    assert not FUSED._can_tile(128, MAX_SKV + 1, 128, 1)
    h, d = 2, 32
    plan = _plan(d)
    q8 = jnp.zeros((1, 64, h, d), jnp.int8)
    k8 = jnp.zeros((1, MAX_SKV + 64, h, d), jnp.int8)
    with pytest.raises(NotImplementedError):
        FUSED._two_pass_fallback(q8, k8, k8, plan, False, 0,
                                 RequantSpec.raw(), None)


# --------------------------------------------- model-level equivalence ----

def _tiny_attn(rng, arch="llama3-8b", **red):
    import jax
    from repro.configs.registry import get_config
    from repro.models import model as M
    from repro.models import transformer as tf
    from repro.quant import convert

    cfg = M.reduce_config(get_config(arch), dtype="float32", vocab=64,
                          num_layers=1, **red)
    params = tf.init_params(jax.random.key(0), cfg)
    _, plans = convert.quantize_params(params, cfg)
    attn_qp = jax.tree.map(lambda t: t[0], params["layers"][0])["attn"]
    attn_qp = convert._q_attn(attn_qp, plans.attn)
    return cfg, plans, attn_qp


@pytest.mark.parametrize("seq", [64, 96, 127])
def test_fuse_attention_flag_exact_equivalence(rng, seq):
    """fuse_attention=True on pallas_fused == fuse_attention=False (the
    exact two-pass oracle), bit-for-bit, at the model layer — including a
    non-multiple-of-block and a prime (fallback) sequence length."""
    from repro.models import intlayers as il

    cfg, plans, attn_qp = _tiny_attn(rng)
    x8 = jnp.asarray(rng.integers(-127, 128, (2, seq, cfg.d_model)),
                     jnp.int8)
    fused = il.int_attn_fwd(attn_qp, x8, plans.attn, cfg,
                            ops="pallas_fused", fuse_attention=True)
    exact = il.int_attn_fwd(attn_qp, x8, plans.attn, cfg,
                            ops="pallas_fused", fuse_attention=False)
    assert np.array_equal(np.asarray(fused), np.asarray(exact))


def test_fuse_attention_cross_memory8_equivalence(rng):
    """The memory8 (cross-attention) path through int_attn_fwd: fused
    backend == ref oracle exactly."""
    from repro.models import intlayers as il

    cfg, plans, attn_qp = _tiny_attn(rng)
    x8 = jnp.asarray(rng.integers(-127, 128, (1, 32, cfg.d_model)),
                     jnp.int8)
    mem8 = jnp.asarray(rng.integers(-127, 128, (1, 64, cfg.d_model)),
                       jnp.int8)
    fused = il.int_attn_fwd(attn_qp, x8, plans.attn, cfg, memory8=mem8,
                            causal=False, ops="pallas_fused")
    exact = il.int_attn_fwd(attn_qp, x8, plans.attn, cfg, memory8=mem8,
                            causal=False, ops="ref")
    assert np.array_equal(np.asarray(fused), np.asarray(exact))


def test_opset_override_routes_fused_attention():
    """Per-op override: everything on ref, attention on pallas_fused —
    the registry pattern the fused backend was built for."""
    opset = resolve_ops("ref").with_overrides(int_attention="pallas_fused")
    assert opset.backend_for("int_attention").name == "pallas_fused"
    assert opset.backend_for("int8_matmul").name == "ref"
    assert opset.name == "ref[int_attention=pallas_fused]"
