"""The sub-8-bit storage tier: pack/unpack exactness + matmul/KV parity.

Property coverage of the compression tier's three contracts:

  * ``quant.pack`` → ``ops.packed.unpack_weights`` is the identity for
    **every** int8 weight value under msr4 (−128 included), and for the
    ±7 grid under plain int4 — with typed refusals outside it;
  * the packed matmul is bit-exact against the dense int8 matmul on the
    same plan, for every backend and every ``RequantSpec`` form (the
    msr4 distributivity ``acc_nib + correction == x @ w`` makes the
    fused path exact, not approximate);
  * int4 KV pages: the in-kernel unpack of the decode / paged-prefill
    launches is bit-exact against the declared dequant reference
    ``ops.packed.unpack_kv_pool`` on every backend.

Deterministic seeds; the randomised shapes sweep odd/even geometry the
fixed-shape unit tests don't.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import ranges
from repro.core import attention as iattn
from repro.core.dyadic import fit_dyadic
from repro.ops import QuantLinearParams, RequantSpec, packed, resolve_ops
from repro.ops.paged import gather_pages
from repro.quant.pack import pack_int4, pack_linear, pack_msr4, pack_tree

BACKENDS = ("ref", "pallas_fused")


# ------------------------------------------------- pack -> unpack ---------

def test_msr4_roundtrip_is_identity_for_all_int8():
    """Every int8 value — the −128 container corner included — survives
    pack_msr4 → unpack_weights exactly (delta = −121 fits int8)."""
    all_vals = np.arange(-128, 128, dtype=np.int8)
    w = np.stack([all_vals, all_vals[::-1], np.roll(all_vals, 7)], axis=1)
    for group in (0, 16, 64, 256, 100):      # 100 doesn't divide K -> g=K
        packed_w, meta, idx, val = pack_msr4(w, group=group)
        assert packed_w.shape == (128, 3) and packed_w.dtype == np.int8
        assert idx.dtype == np.int16 and val.dtype == np.int8
        qw = QuantLinearParams(w8=None, w_packed=jnp.asarray(packed_w),
                               pack_meta=meta, out_idx=jnp.asarray(idx),
                               out_val=jnp.asarray(val))
        back = np.asarray(packed.unpack_weights(qw))
        assert np.array_equal(back, w), group


def test_msr4_roundtrip_random_and_stacked(rng):
    """Random int8 weights, 2-D and stacked (ng, K, N), random groups."""
    for shape, group in (((64, 5), 16), ((30, 7), 8), ((2, 32, 4), 16),
                         ((128, 3), 0), ((3, 16, 9), 4)):
        w = rng.integers(-128, 128, shape).astype(np.int8)
        qw = pack_linear(QuantLinearParams(w8=jnp.asarray(w)),
                         scheme="msr4", group=group)
        assert qw.is_packed and qw.w8 is None
        assert qw.w_packed.shape[-2] == shape[-2] // 2
        back = np.asarray(packed.unpack_weights(qw))
        assert np.array_equal(back, w), (shape, group)


def test_msr4_outlier_lanes_are_static_and_minimal(rng):
    """Lane arrays are static-shaped (max count over columns), filler
    lanes carry delta 0, and a pure-nibble weight needs zero lanes."""
    w = rng.integers(-128, 128, (64, 8)).astype(np.int8)
    _, meta, idx, val = pack_msr4(w, group=16)
    d = w.astype(np.int32) - np.clip(w, -7, 7).astype(np.int32)
    per_col = (d.reshape(4, 16, 8) != 0).sum(axis=1)
    assert meta.n_outliers == per_col.max()
    assert np.abs(val.astype(np.int32)).max() <= ranges.MSR4_DELTA_MAX + 1
    # within each (group, column) the lane rows are distinct
    for g in range(idx.shape[0]):
        for n in range(idx.shape[2]):
            col = idx[g, :, n]
            assert len(set(col.tolist())) == len(col)
    small = rng.integers(-7, 8, (32, 4)).astype(np.int8)
    _, meta0, idx0, val0 = pack_msr4(small, group=8)
    assert meta0.n_outliers == 0 and idx0.shape[1] == 0


def test_int4_roundtrip_and_refusals(rng):
    w = rng.integers(-7, 8, (48, 6)).astype(np.int8)
    p = pack_int4(w)
    assert np.array_equal(np.asarray(packed.nibble_unpack(p, axis=-2)), w)
    with pytest.raises(ValueError, match="int4 packing"):
        pack_int4(np.full((4, 2), 8, np.int8))
    with pytest.raises(ValueError, match="K must be even"):
        pack_int4(np.zeros((5, 2), np.int8))
    with pytest.raises(ValueError, match="unknown pack scheme"):
        pack_linear(QuantLinearParams(w8=jnp.asarray(w)), scheme="int3")


def test_msr4_distributivity_identity(rng):
    """``x @ nibbles + msr4_correction(x, qw) == x @ w`` exactly — the
    identity the fused packed matmul relies on."""
    w = rng.integers(-128, 128, (64, 12)).astype(np.int8)
    x = rng.integers(-127, 128, (9, 64)).astype(np.int32)
    qw = pack_linear(QuantLinearParams(w8=jnp.asarray(w)),
                     scheme="msr4", group=16)
    nib = np.asarray(packed.nibble_unpack(qw.w_packed, axis=-2))
    acc_nib = x @ nib
    corr = np.asarray(packed.msr4_correction(jnp.asarray(x), qw))
    assert np.array_equal(acc_nib + corr, x @ w.astype(np.int32))


def test_pack_tree_skips_unpackable_leaves(rng):
    """Odd-K, 4-D expert stacks and non-linear leaves pass through."""
    odd = QuantLinearParams(w8=jnp.asarray(
        rng.integers(-128, 128, (7, 4)).astype(np.int8)))
    expert = QuantLinearParams(w8=jnp.asarray(
        rng.integers(-128, 128, (2, 3, 8, 4)).astype(np.int8)))
    ok = QuantLinearParams(w8=jnp.asarray(
        rng.integers(-128, 128, (8, 4)).astype(np.int8)))
    tree = {"a": odd, "b": expert, "c": ok,
            "emb": jnp.zeros((4, 4), jnp.int8)}
    out = pack_tree(tree, scheme="msr4", group=4)
    assert not out["a"].is_packed and not out["b"].is_packed
    assert out["c"].is_packed
    assert out["emb"] is tree["emb"]


# ------------------------------------------------- matmul parity ----------

@pytest.mark.parametrize("form", ["per_tensor", "per_channel", "raw"])
@pytest.mark.parametrize("scheme", ["int4", "msr4"])
def test_packed_matmul_parity_all_backends(rng, form, scheme):
    """Packed-vs-dense matmul bit-parity across random shapes, requant
    forms and backends: the packed path must reproduce the dense int8
    accumulator (and its epilogue) exactly."""
    for m, k, n in ((8, 32, 16), (5, 64, 8), (16, 128, 128), (1, 16, 4)):
        lo, hi = (-7, 8) if scheme == "int4" else (-128, 128)
        w = rng.integers(lo, hi, (k, n)).astype(np.int8)
        x = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
        bias = jnp.asarray(rng.integers(-2 ** 14, 2 ** 14, (n,)),
                           jnp.int32)
        b_vec = None
        if form == "per_tensor":
            spec = RequantSpec.per_tensor(
                fit_dyadic(1 / 4000.0, k * 127 * 127 + 2 ** 14))
        elif form == "per_channel":
            spec = RequantSpec.per_channel(c=28, pre=7)
            b_vec = jnp.asarray(rng.integers(1000, 30000, (n,)),
                                jnp.int32)
        else:
            spec = RequantSpec.raw()
        dense = QuantLinearParams(w8=jnp.asarray(w), b_mult=b_vec,
                                  bias32=bias)
        qw = pack_linear(dense, scheme=scheme, group=16)
        assert qw.is_packed
        want = np.asarray(resolve_ops("ref").int8_matmul(
            jnp.asarray(x), jnp.asarray(w), spec, bias32=bias,
            b_vec=b_vec))
        for name in BACKENDS:
            got = np.asarray(
                resolve_ops(name).int8_matmul_packed(x, qw, spec))
            assert np.array_equal(got, want), (name, form, scheme,
                                               (m, k, n))


def test_packed_matmul_dense_fallthrough(rng):
    """A dense QuantLinearParams through int8_matmul_packed is plain
    int8_matmul — no silent repack."""
    w = rng.integers(-128, 128, (32, 8)).astype(np.int8)
    x = jnp.asarray(rng.integers(-127, 128, (4, 32)), jnp.int8)
    qw = QuantLinearParams(w8=jnp.asarray(w))
    spec = RequantSpec.raw()
    got = np.asarray(resolve_ops("ref").int8_matmul_packed(x, qw, spec))
    want = np.asarray(resolve_ops("ref").int8_matmul(
        x, jnp.asarray(w), spec))
    assert np.array_equal(got, want)


# ------------------------------------------------- int4 KV pages ----------

def test_kv_pack_roundtrip_and_idempotence(rng):
    """``unpack_kv_pool`` is the declared reference: packing its output
    again must reproduce the same codes (the tier is a fixed point)."""
    pool = jnp.asarray(rng.integers(-127, 128, (5, 4, 2, 8)), jnp.int8)
    p = packed.pack_kv(pool)
    assert p.shape == (5, 4, 2, 4)
    shifts = jnp.full((5,), packed.KV_SHIFT, jnp.int32)
    deq = packed.unpack_kv_pool(p, shifts)
    assert deq.dtype == jnp.int8
    assert int(jnp.abs(deq.astype(jnp.int32)).max()) <= 7 << packed.KV_SHIFT
    again = packed.pack_kv(deq)
    assert np.array_equal(np.asarray(again), np.asarray(p))


def test_ranges_kv4_constants_twin():
    """The analysis layer's import-cycle-free twins of the runtime
    constants must stay equal to the real ones."""
    assert ranges.KV4_SHIFT == packed.KV_SHIFT
    assert ranges.INT4_KV.qmax == 7 << packed.KV_SHIFT
    assert ranges.INT4.qmax == 7
    assert ranges.MSR4_DELTA_MAX == 127 - 7


def _packed_pool(rng, num_pages, ps, hkv, d):
    kp = jnp.asarray(rng.integers(-127, 128, (num_pages, ps, hkv, d)),
                     jnp.int8)
    vp = jnp.asarray(rng.integers(-127, 128, (num_pages, ps, hkv, d)),
                     jnp.int8)
    shifts = jnp.full((num_pages,), packed.KV_SHIFT, jnp.int32)
    return (packed.pack_kv(kp), packed.pack_kv(vp), shifts)


def test_packed_decode_matches_dequant_reference(rng):
    """int4 KV decode on every backend == the dense decode over
    ``unpack_kv_pool`` (the declared dequant reference), ragged
    occupancies and the empty slot included."""
    b, sq, h, hkv, d, ps, num_pages = 3, 1, 4, 2, 32, 16, 9
    plan = iattn.make_iattention(d, 8 / 127, 8 / 127, 4 / 127, 4 / 127)
    q8 = jnp.asarray(rng.integers(-127, 128, (b, sq, h, d)), jnp.int8)
    kp, vp, shifts = _packed_pool(rng, num_pages, ps, hkv, d)
    pages = jnp.asarray([[0, 0, 0], [5, 2, 0], [7, 1, 4]], jnp.int32)
    vl = jnp.asarray([0, 19, 48], jnp.int32)
    kd = packed.unpack_kv_pool(kp, shifts)
    vd = packed.unpack_kv_pool(vp, shifts)
    want = np.asarray(resolve_ops("ref").int_decode_attention(
        q8, kd, vd, plan, vl, pages=pages, page_size=ps))
    for name in BACKENDS:
        got = np.asarray(resolve_ops(name).int_decode_attention(
            q8, kp, vp, plan, vl, pages=pages, page_size=ps,
            kv_shifts=(shifts, shifts)))
        assert np.array_equal(got, want), name
    assert not np.asarray(want)[0].any()        # empty slot -> requant(0)


def test_packed_prefill_matches_dequant_reference(rng):
    """Paged prefill with packed pools: the scatter quantizes the new
    chunk to int4 codes and the attention runs on the dequantized
    values — bit-equal to scattering pre-quantized values into the
    dequantized dense pools, on every backend."""
    b, c, h, hkv, d, ps, num_pages = 2, 8, 4, 2, 32, 16, 7
    plan = iattn.make_iattention(d, 8 / 127, 8 / 127, 4 / 127, 4 / 127)
    q8 = jnp.asarray(rng.integers(-127, 128, (b, c, h, d)), jnp.int8)
    knew = jnp.asarray(rng.integers(-127, 128, (b, c, hkv, d)), jnp.int8)
    vnew = jnp.asarray(rng.integers(-127, 128, (b, c, hkv, d)), jnp.int8)
    kp, vp, shifts = _packed_pool(rng, num_pages, ps, hkv, d)
    pages = jnp.asarray([[3, 1, 0], [5, 2, 6]], jnp.int32)
    base = jnp.asarray([9, 0], jnp.int32)
    outs, pools = {}, {}
    for name in BACKENDS:
        o, k2, v2 = resolve_ops(name).int_paged_prefill(
            q8, knew, vnew, kp, vp, plan, base, pages, ps,
            kv_shifts=(shifts, shifts))
        outs[name] = np.asarray(o)
        pools[name] = (np.asarray(k2), np.asarray(v2))
    for name in BACKENDS[1:]:
        assert np.array_equal(outs[name], outs["ref"]), name
        assert np.array_equal(pools[name][0], pools["ref"][0]), name
        assert np.array_equal(pools[name][1], pools["ref"][1]), name
    # the updated pools hold int4 codes: dequantizing them reproduces
    # the reference composition (quantize chunk -> scatter -> attend)
    k2 = jnp.asarray(pools["ref"][0])
    deq = packed.unpack_kv_pool(k2, shifts)
    rows = gather_pages(deq, pages, ps)
    q4 = packed.quantize_kv(knew)
    assert np.array_equal(
        np.asarray(rows[0, 9:9 + c]),
        np.asarray((q4[0] << packed.KV_SHIFT).astype(jnp.int8)))


def test_certify_packed_tier_reports():
    """certify_config carries the packed-tier ops with headroom."""
    from repro.analysis.interpret import certify_config
    from repro.configs.registry import get_config
    rep = certify_config(get_config("llama3-8b"), seq_len=256,
                         cache_len=2048)
    layers = {o.layer: o for o in rep.ops}
    assert "attn.qkv[msr4]" in layers
    assert "attn.decode[kv4]" in layers
    assert "attn.prefill[kv4]" in layers
    assert layers["attn.qkv[msr4]"].op == "int8_matmul_packed"
    assert all(layers[k].headroom_bits >= 0 for k in layers)
    # the int4 KV operand (<=112) can never certify worse than int8
    assert layers["attn.decode[kv4]"].worst <= layers["attn.decode"].worst
