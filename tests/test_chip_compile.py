"""The main-path Pallas kernels compile for a TPU v5e, at real widths.

Interpret mode (every other kernel test) checks numerics but none of the
chip compiler's rules — block shapes whose last two dims break the
(8, 128) tile rule, 1-d partial blocks, layouts Mosaic cannot lower.
These tests lower and compile each kernel for a described-but-absent
``v5e:2x2`` chip (one of its devices) at Granite-3-2B and RoBERTa-base
widths and assert the kernel is in the compiled program
(``tpu_custom_call``).  Nothing runs; a compile that passes here is not
a chip run.

The topology is described inside a module-scoped fixture (never at
import time): only the worker that runs these tests loads the TPU
compiler, and every worker collects the same tests.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import attention as iattn
from repro.core import intmath, norms
from repro.core.dyadic import fit_dyadic
from repro.ops import RequantSpec

# Granite-3-2B: H 32, Hkv 8, head_dim 64, d 2048, d_ff 8192
G_H, G_HKV, G_D, G_MODEL, G_FF = 32, 8, 64, 2048, 8192
# RoBERTa-base: H 12, head_dim 64, d 768, d_ff 3072
R_H, R_D, R_MODEL, R_FF = 12, 64, 768, 3072
# the widest served projections: H2O-Danube-3-4B (head_dim 120, d 3840)
# still folds wo into the attention launch, Llama-3-8B (head_dim 128,
# d 4096) is past the VMEM budget and runs it unfolded
DANUBE_D, DANUBE_MODEL, LLAMA_D, LLAMA_MODEL = 120, 3840, 128, 4096
LLAMA_FF = 14336
BATCH, PAGE, CACHE = 8, 128, 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    """Lower + compile ``fn`` for the described chip; return its HLO."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _attn_plan(d):
    return iattn.make_iattention(d, 8 / 127, 8 / 127, 4 / 127, 4 / 127)


I8, I32 = jnp.int8, jnp.int32


# ------------------------------------------------------------- matmul --

# form -> (M, K, N, output bits); blocks from the shape rule
# (``kernels.int8_matmul.matmul_blocks``).  The cells' widest launches
# pin its byte budget; Llama-3-8B's down projection, split into four K
# steps, needs more than the chip's default 16 MiB of scoped VMEM and
# pins ``int8_matmul.VMEM_LIMIT``.
MATMUL_FORMS = {
    "per_tensor": (256, G_MODEL, G_FF, 8),
    "per_channel_bias": (256, G_MODEL, G_FF, 8),
    "packed": (256, G_MODEL, G_FF, 8),
    "decode_m8": (8, G_MODEL, G_FF, 8),
    "roberta_up": (32768, R_MODEL, R_FF, 16),
    "roberta_down": (32768, R_FF, R_MODEL, 16),
    "granite_w1": (8192, G_MODEL, G_FF, 16),
    "granite_w2": (8192, G_FF, G_MODEL, 16),
    "llama_split_k": (2048, LLAMA_FF, LLAMA_MODEL, 8),
}


@pytest.mark.parametrize("form", list(MATMUL_FORMS))
def test_int8_matmul_compiles(one_chip, form):
    from repro.kernels.int8_matmul import int8_matmul_pallas
    from repro.ops.backends.pallas_fused import _matmul_blocks
    m, k, n, out_bits = MATMUL_FORMS[form]
    out_dtype = I8 if out_bits <= 8 else I32

    if form not in ("per_tensor", "packed", "decode_m8"):
        # how every model projection runs: per-channel multipliers + bias
        def fn(x, w, bias, bvec):
            return int8_matmul_pallas(x, w, bias, b_vec=bvec, c=28, pre=7,
                                      out_bits=out_bits,
                                      out_dtype=out_dtype, interpret=False)
        _compile(one_chip, fn, ((m, k), I8), ((k, n), I8), ((n,), I32),
                 ((n,), I32))
        return

    packed = form == "packed"
    bm, bn, bk = _matmul_blocks({}, m, n, k, packed=packed)
    dn = fit_dyadic(1 / 4000.0, k * 127 * 127)

    def fn(x, w):
        return int8_matmul_pallas(x, w, None, dn=dn, bm=bm, bn=bn, bk=bk,
                                  packed=packed, interpret=False)
    w_shape = (k // 2, n) if packed else (k, n)
    _compile(one_chip, fn, ((m, k), I8), (w_shape, I8))


# ----------------------------------------------------- decode attention --

@pytest.mark.parametrize("variant", ["contiguous", "paged",
                                     "paged_per_channel", "paged_int4_kv",
                                     "paged_fold_wo", "paged_spec_sq8",
                                     "paged_tp4_shard",
                                     "paged_fold_wo_danube"])
def test_decode_attention_compiles(one_chip, variant):
    """``paged_tp4_shard``: one device's launch under tp=4 head-sharded
    serving (H/4 query heads, Hkv/4 KV heads of every page).
    ``paged_fold_wo_danube``: the widest folded ``wo`` block a served
    config launches (3840 x 3840)."""
    from repro.kernels.int_decode_attention import int_decode_attention_fused
    danube = variant == "paged_fold_wo_danube"
    d, n_model = (DANUBE_D, DANUBE_MODEL) if danube else (G_D, G_MODEL)
    plan = _attn_plan(d)
    sq = 8 if variant == "paged_spec_sq8" else 1
    h, hkv = (G_H // 4, G_HKV // 4) if variant == "paged_tp4_shard" \
        else (G_H, G_HKV)
    q = ((BATCH, sq, h, d), I8)
    vl = ((BATCH,), I32)
    if variant == "contiguous":
        kv = ((BATCH, CACHE, hkv, d), I8)

        def fn(q8, k8, v8, valid):
            return int_decode_attention_fused(q8, k8, v8, plan, valid,
                                              interpret=False)
        _compile(one_chip, fn, q, kv, kv, vl)
        return
    n_pages = BATCH * CACHE // PAGE + 1
    int4 = variant == "paged_int4_kv"
    pool = ((n_pages, PAGE, hkv, d // 2 if int4 else d), I8)
    pages = ((BATCH, CACHE // PAGE), I32)
    shapes = [q, pool, pool, vl, pages]
    if variant == "paged_per_channel":
        shapes.append(((G_H * d,), I32))
    if int4:
        shapes += [((n_pages,), I32), ((n_pages,), I32)]
    fold = variant in ("paged_fold_wo", "paged_fold_wo_danube")
    if fold:
        shapes += [((G_H * d, n_model), I8), ((n_model,), I32),
                   ((n_model,), I32)]

    def fn(q8, kp, vp, valid, pt, *extra):
        kw = {}
        if variant == "paged_per_channel":
            kw.update(requant=RequantSpec.per_channel(c=28, pre=7),
                      b_vec=extra[0])
        if int4:
            kw.update(kv_shifts=extra)
        if fold:
            kw.update(wo_w8=extra[0], wo_bias32=extra[1],
                      wo_b_vec=extra[2],
                      wo_spec=RequantSpec.per_channel(c=28, pre=7))
        return int_decode_attention_fused(q8, kp, vp, plan, valid,
                                          pages=pt, page_size=PAGE,
                                          interpret=False, **kw)
    _compile(one_chip, fn, *shapes)


# ---------------------------------------------------- prefill attention --

@pytest.mark.parametrize("causal", [False, True])
def test_fused_prefill_compiles(one_chip, causal):
    """RoBERTa-base encoder attention (bidirectional) and a causal
    Granite-width prefill, per-channel epilogue."""
    from repro.kernels.int_attention_fused import int_attention_fused
    h, hkv, s = (G_H, G_HKV, 512) if causal else (R_H, R_H, 256)
    plan = _attn_plan(R_D)

    def fn(q8, k8, v8, bvec):
        return int_attention_fused(
            q8, k8, v8, plan, requant=RequantSpec.per_channel(c=28, pre=7),
            b_vec=bvec, causal=causal, interpret=False)
    _compile(one_chip, fn, ((BATCH, s, h, R_D), I8),
             ((BATCH, s, hkv, R_D), I8), ((BATCH, s, hkv, R_D), I8),
             ((h * R_D,), I32))


@pytest.mark.parametrize("variant", ["plain", "fold_wo", "int4_kv"])
def test_paged_prefill_compiles(one_chip, variant):
    from repro.kernels.int_attention_fused import int_paged_prefill_fused
    plan = _attn_plan(G_D)
    c = 256
    n_pages = BATCH * CACHE // PAGE + 1
    int4 = variant == "int4_kv"
    pool = ((n_pages, PAGE, G_HKV, G_D // 2 if int4 else G_D), I8)
    shapes = [((BATCH, c, G_H, G_D), I8), pool, pool, ((BATCH,), I32),
              ((BATCH, CACHE // PAGE), I32)]
    if int4:
        shapes += [((n_pages,), I32), ((n_pages,), I32)]
    if variant == "fold_wo":
        shapes += [((G_H * G_D, G_MODEL), I8), ((G_MODEL,), I32),
                   ((G_MODEL,), I32)]

    def fn(q8, kp, vp, pos_end, pt, *extra):
        kw = {}
        if int4:
            kw.update(kv_shifts=extra)
        if variant == "fold_wo":
            kw.update(wo_w8=extra[0], wo_bias32=extra[1],
                      wo_b_vec=extra[2],
                      wo_spec=RequantSpec.per_channel(c=28, pre=7))
        return int_paged_prefill_fused(q8, kp, vp, plan, pos_end, pt, PAGE,
                                       interpret=False, **kw)
    _compile(one_chip, fn, *shapes)


@pytest.mark.parametrize("launch", ["decode", "paged_prefill"])
def test_wide_wo_compiles_unfolded(one_chip, launch):
    """Llama-3-8B's ``wo`` (4096 x 4096) ran the folded launch out of
    VMEM: the backend asked to fold it runs the attention kernel
    unfolded and the projection through the matmul kernel, and that
    compiles."""
    from repro.analysis import contracts
    from repro.ops import QuantLinearParams
    from repro.ops.backends.pallas_fused import PallasFusedBackend
    h, hkv, d, n = G_H, G_HKV, LLAMA_D, LLAMA_MODEL
    rows = 1 if launch == "decode" else 256
    assert not contracts.can_fold_wo(rows, h, hkv, d, PAGE, n)
    be = PallasFusedBackend(interpret=False)
    plan = _attn_plan(d)
    spec = RequantSpec.per_channel(c=28, pre=7)
    n_pages = BATCH * CACHE // PAGE + 1
    pool = ((n_pages, PAGE, hkv, d), I8)
    pages = ((BATCH, CACHE // PAGE), I32)
    wo = [((h * d, n), I8), ((n,), I32), ((n,), I32)]
    if launch == "decode":
        def fn(q8, kp, vp, valid, pt, w, b, bv):
            return be.int_decode_attention(
                q8, kp, vp, plan, valid, pages=pt, page_size=PAGE,
                wo=QuantLinearParams(w, bv, b), wo_spec=spec)
        text = _compile(one_chip, fn, ((BATCH, 1, h, d), I8), pool, pool,
                        ((BATCH,), I32), pages, *wo)
    else:
        def fn(q8, kn, vn, kp, vp, base, pt, w, b, bv):
            return be.int_paged_prefill(
                q8, kn, vn, kp, vp, plan, base, pt, PAGE,
                wo=QuantLinearParams(w, bv, b), wo_spec=spec)[0]
        chunk = ((BATCH, rows, hkv, d), I8)
        text = _compile(one_chip, fn, ((BATCH, rows, h, d), I8), chunk,
                        chunk, pool, pool, ((BATCH,), I32), pages, *wo)
    # the attention kernel and the o-projection matmul: two launches
    assert text.count("tpu_custom_call") >= 2


# --------------------------------------------------------- elementwise --

@pytest.mark.parametrize("shape", [
    (BATCH * 256, R_FF),
    (512 * 64, R_FF),      # the encoder benchmark's batch
    (16, R_FF),            # a decode step: one block
], ids=["3072", "roberta-512x64", "decode16"])
def test_gelu_compiles(one_chip, shape):
    """Under the shape-chosen layout and row block (``gelu_layout``)."""
    from repro.kernels.int_gelu import int_gelu_pallas
    plan = intmath.make_igelu(16 / 1024, 1024)
    dn = fit_dyadic(plan.s_out / (8 / 127), 1024 * 2 * plan.q_one)
    _compile(one_chip,
             lambda q: int_gelu_pallas(q, plan, dn, interpret=False),
             (shape, I32))


@pytest.mark.parametrize("lead,d,subtract_mean", [
    ((BATCH, 256), R_MODEL, True), ((BATCH, 256), G_MODEL, False),
    ((512, 64), R_MODEL, True),    # the encoder benchmark's batch
    ((16,), G_MODEL, False),       # a Granite decode step: one block
], ids=["768-True", "2048-False", "roberta-512x64", "granite-decode16"])
def test_layernorm_compiles(one_chip, lead, d, subtract_mean):
    """Under the shape-chosen row block (``norm_block_rows``)."""
    from repro.kernels.int_layernorm import int_layernorm_pallas
    plan = norms.make_inorm(d, 8 / 1024, 1024, 2 / 127, 8 / 127,
                            subtract_mean=subtract_mean)
    shapes = [(lead + (d,), I32), ((d,), I32)]
    if subtract_mean:
        shapes.append(((d,), I32))

    def fn(q, g, *beta):
        return int_layernorm_pallas(q, g, beta[0] if beta else None, plan,
                                    interpret=False)
    _compile(one_chip, fn, *shapes)

