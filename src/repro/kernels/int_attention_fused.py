"""Pallas TPU kernel: fused integer attention + requant, bit-exact.

One kernel launch computes the whole SwiftTron attention datapath
(§III-D/E, Figs. 8-10): int8 Q·Kᵀ → dyadic-scaled integer softmax (the
``core.softmax`` Shiftmax numerics) → int8 P·V → requant epilogue —
streaming over KV blocks with int32 accumulators, so the O(Sq·Skv) score
matrix never exists in HBM.

The kernel makes **two streaming sweeps** over the KV blocks per query
block and is *bit-exact* against the reference
(``kernels.ref.ref_int_attention``):

  sweep 0  row max       m = max_k(scores)                 (int32 compare)
  sweep 1  weights + AV  u8 = min(⌊e16(scores - m) + 2⁷⌋»8, 127);
                         s += Σ_k u8;  acc += u8·v8 (MXU)   (int32 adds)
  epilogue divide        acc7 = ⌊(2⁷·acc + ⌊s/2⌋) / s⌋  per row

Each sweep recomputes the int8 Q·Kᵀ block product instead of storing it —
the FlashAttention recompute-over-store trade, paid once more here to
buy exactness (integer maxima and sums are associative; an online
rescale is not).  Normalising after P·V
(``core.softmax.normalize_rows``) instead of before it is what lets the
weight sum and P·V share one sweep; the division is exact integer
floor division, done here from a float32 estimate that one exact int32
remainder corrects (``int_softmax.normalize_tile``).

Epilogue: the normalised accumulator (scale ``2⁻⁷·s_v``) takes any of
the three :class:`repro.ops.RequantSpec` forms —

  * per-tensor  — ``clip(rshift_round(rshift_round(acc, pre)·b, c-pre))``
  * per-channel — same staging with an int32 multiplier vector over the
    flattened (head, head_dim) output channels
  * raw         — the normalised accumulator is written untouched

Tiling: the caches keep their public ``(..., L, Hkv, D)`` layout, and
every block takes *all* heads — queries ``(1, bq, H, D)``, K/V ``(1, bkv,
Hkv, D)``, per-channel multipliers ``(H, D)`` — so each block's last two
dims equal its array's, which is what the chip's compiler demands (a
one-head ``(1, bkv, 1, D)`` block is refused; see
``analysis.contracts.tpu_block_violations``).  The kernel body loops over
the heads, loading each KV head's tile once per block for its whole GQA
group.  ``bq`` / ``bkv`` are free of the (8, 128) tile rule.

Bit budgets (``analysis.budgets``): the weight sum is at most
``Skv·127`` and the accumulator ``Skv·127·127``, int32 up to
``MAX_PV_KEYS = 2¹⁷`` keys.  The launch itself is held to ``MAX_SKV =
2¹⁵`` keys (the lengths its tiling was checked at); backends take the
chunked path beyond it (see ``ops.backends.pallas_fused``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import trace_names
from repro.analysis.budgets import MAX_ROWSUM_LEN
from repro.analysis.contracts import check_launch, require_launch
from repro.core.attention import IAttnPlan
from repro.kernels import resolve_interpret
from repro.kernels.int_softmax import (_exp16_tile, _rshift_round,
                                       attn_weights_tile, normalize_tile)
from repro.ops.spec import PER_CHANNEL, PER_TENSOR, RequantSpec

NEG = -(2 ** 30)

# the launch limit is owned by repro.analysis.budgets (one source of
# truth shared with the decode kernel and the tiling policy)
MAX_SKV = MAX_ROWSUM_LEN


def _streaming_attn_body(phase, kv_step, n_kv, live, blk_live, m_ref,
                         s_ref, acc_ref, b_ref, *, n_heads: int, group: int,
                         q_of, k_of, v_of, emit, plan: IAttnPlan,
                         requant: RequantSpec, finish=None):
    """The shared two-sweep streaming datapath + requant epilogue, over
    every head of one query block.

    The launches block K/V as ``(1, bkv, Hkv, D)`` and queries as
    ``(1, rows, H, D)`` — all heads at once, so the blocks' last two
    dims equal the arrays' (the chip's block rule) — and this body
    loops over the heads inside the kernel: KV head ``g`` is loaded
    once per block (``k_of(g)`` / ``v_of(g)``, an int8 ``(bkv, D)``
    tile) and serves its ``group`` query heads (``q_of(h)``, ``(rows,
    D)``).  Scratch is per head: ``m_ref`` / ``s_ref`` ``(H, rows, 1)``,
    ``acc_ref`` ``(H, rows, Dv)``, the value width.  On the last step ``emit(h, tile)``
    receives each head's requantized tile, then ``finish()`` runs.

    Everything downstream of mask construction is identical between the
    prefill kernels and the decode kernel (``int_decode_attention.py``)
    — only ``live`` (element mask, shared by all heads) and
    ``blk_live`` (whole-block skip predicate) differ, so a numerics
    change lands in exactly one place.
    """
    n_kv_heads = n_heads // group

    @pl.when((phase == 0) & (kv_step == 0))
    def _init_max():
        m_ref[...] = jnp.full_like(m_ref, NEG)

    @pl.when((phase == 1) & (kv_step == 0))
    def _init_acc():
        s_ref[...] = jnp.zeros_like(s_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _scores(h, k8):
        s = jax.lax.dot_general(q_of(h), k8, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.int32)
        return jnp.where(live, s, jnp.int32(NEG))

    def _heads(g):
        return range(g * group, (g + 1) * group)

    @pl.when((phase == 0) & blk_live)
    def _sweep_max():
        for g in range(n_kv_heads):
            k8 = k_of(g)
            for h in _heads(g):
                m_ref[h] = jnp.maximum(
                    m_ref[h], jnp.max(_scores(h, k8), axis=-1,
                                      keepdims=True))

    @pl.when((phase == 1) & blk_live)
    def _sweep_av():
        for g in range(n_kv_heads):
            k8, v8 = k_of(g), v_of(g)
            for h in _heads(g):
                e16 = _exp16_tile(_scores(h, k8) - m_ref[h], plan.sm)
                u = jnp.where(live, attn_weights_tile(e16), 0)
                s_ref[h] = s_ref[h] + jnp.sum(u, axis=-1, keepdims=True)
                acc_ref[h] = acc_ref[h] + jax.lax.dot_general(
                    u.astype(jnp.int8), v8, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)

    @pl.when((phase == 1) & (kv_step == n_kv - 1))
    def _epilogue():
        for h in range(n_heads):
            acc = normalize_tile(acc_ref[h], s_ref[h])   # at 2^-7 * s_v
            if requant.is_raw:
                emit(h, acc)
                continue
            b_row = None if b_ref is None \
                else b_ref[h:h + 1, :].astype(jnp.int32)
            emit(h, _requant_tile(acc, requant, b_row))
        if finish is not None:
            finish()


def _head_store(o_ref):
    """``emit`` for an unfolded launch: head ``h``'s tile into the
    head-major ``(1, H, rows, D)`` output block (the wrappers swap the
    ``(B, H, rows, D)`` result back to ``(B, rows, H, D)`` in XLA — a
    head-strided int8 store is not something the chip's compiler can
    lay out)."""
    def emit(h, tile):
        o_ref[0, h] = tile.astype(o_ref.dtype)
    return emit


def _wo_fold(o_ref, wo_ref, wob_ref, wobv_ref, wacc_ref, *, d: int,
             wo_spec: RequantSpec):
    """``(emit, finish)`` for a launch with the o-projection folded in:
    each head's int8 tile is contracted against its ``(D, N)`` row slab
    of the whole-``wo`` block and summed over heads in the ``(rows, N)``
    int32 scratch; ``finish`` adds ``bias32`` and applies the wo
    ``RequantSpec`` into the ``(1, rows, N)`` output block."""
    def emit(h, tile):
        part = jax.lax.dot_general(tile.astype(jnp.int8),
                                   wo_ref[h * d:(h + 1) * d, :],
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.int32)
        wacc_ref[...] = part if h == 0 else wacc_ref[...] + part

    def finish():
        acc = wacc_ref[...]
        if wob_ref is not None:
            acc = acc + wob_ref[...]
        b_row = None if wobv_ref is None else wobv_ref[...]
        o_ref[0] = _requant_tile(acc, wo_spec, b_row).astype(o_ref.dtype)
    return emit, finish


def _requant_tile(acc, requant: RequantSpec, b_row=None):
    """The in-kernel requant epilogue on an int32 tile: the exact
    two-stage rounding of docs/KERNELS.md for the per-tensor and
    per-channel forms (``b_row``: int32 ``(1, N)`` multipliers, required
    iff per-channel).  Shared by the prefill/decode epilogues and the
    decode kernel's folded wo projection, so the rounding exists once."""
    if requant.is_raw:
        return acc
    lo = -(1 << (requant.out_bits - 1))
    hi = (1 << (requant.out_bits - 1)) - 1
    if requant.kind == PER_TENSOR:
        dn = requant.dn
        out = _rshift_round(_rshift_round(acc, dn.pre) * jnp.int32(dn.b),
                            dn.c - dn.pre)
    else:                                       # per-channel over N
        out = _rshift_round(_rshift_round(acc, requant.pre) * b_row,
                            requant.c - requant.pre)
    return jnp.clip(out, lo, hi)


def _unpack_kv_tile(p8, shift):
    """In-register int4 KV expansion of a ``(rows, d // 2)`` packed tile
    to ``(rows, d)`` int8: low nibble = even head-dim lane, high = odd,
    then a per-page requant left-shift.  All arithmetic in int32 with
    explicit sign extension — bit-exact twin of
    ``repro.ops.packed.unpack_kv_pool`` on the gathered layout.  The
    shifted magnitudes stay ≤ 7·2⁴ = 112, int8-safe by construction.

    The lane interleave is two exact 0/1 selection matmuls (nibble
    ``i`` → lane ``2i`` / ``2i + 1``) rather than a stack + reshape: the
    chip's compiler lowers a lane interleave into a long shuffle
    sequence (minutes of compile per kernel), the MXU does it in one
    pass each."""
    rows, half = p8.shape
    p32 = p8.astype(jnp.int32)
    lo = (((p32 & 15) ^ 8) - 8).astype(jnp.int8)
    hi = ((((p32 >> 4) & 15) ^ 8) - 8).astype(jnp.int8)
    src = jax.lax.broadcasted_iota(jnp.int32, (half, 2 * half), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (half, 2 * half), 1)
    dims = (((1,), (0,)), ((), ()))
    q = (jax.lax.dot_general(lo, (dst == 2 * src).astype(jnp.int8), dims,
                             preferred_element_type=jnp.int32)
         + jax.lax.dot_general(hi, (dst == 2 * src + 1).astype(jnp.int8),
                               dims, preferred_element_type=jnp.int32))
    return (q << shift).astype(jnp.int8)


def _epilogue_setup(requant, plan: IAttnPlan, out_bits: int, b_vec,
                    h: int, d: int):
    """Shared wrapper-side epilogue policy (prefill and decode kernels):
    default requant, per-channel b_vec validation + (h, d) reshape, and
    the output container rule.  Returns (requant, has_bvec, b2,
    out_dtype)."""
    if requant is None:
        requant = RequantSpec.per_tensor(plan.dn_out, out_bits)
    has_bvec = requant.kind == PER_CHANNEL
    b2 = None
    if has_bvec:
        if b_vec is None:
            raise ValueError("per-channel RequantSpec needs the b_vec "
                             "multiplier vector")
        b2 = jnp.asarray(b_vec, jnp.int32).reshape(h, d)
    out_dtype = jnp.int8 if (not requant.is_raw
                             and requant.out_bits <= 8) else jnp.int32
    return requant, has_bvec, b2, out_dtype


def _const_map(*_):
    return (0, 0)


def _wo_fold_setup(requant: RequantSpec, wo_w8, wo_bias32, wo_b_vec,
                   wo_spec, h: int, d: int):
    """Wrapper side of the folded o-projection (decode and paged
    prefill): validate, and build the whole-``wo`` operands — the
    ``(H·D, N)`` weight and the ``(1, N)`` bias / per-channel
    multipliers, each one constant-index block (fetched once per
    launch; their last two dims equal the arrays').  Returns ``(specs,
    args, n_out, out_dtype, has_bias, has_bvec)``."""
    assert wo_spec is not None, "folded wo projection needs wo_spec"
    assert not requant.is_raw and requant.out_bits <= 8, \
        "wo folding needs an int8 attention epilogue"
    wo_w8 = jnp.asarray(wo_w8)
    n_out = wo_w8.shape[-1]
    assert wo_w8.shape == (h * d, n_out), (wo_w8.shape, h, d)
    has_bias = wo_bias32 is not None
    has_bvec = wo_spec.kind == PER_CHANNEL
    if has_bvec and wo_b_vec is None:
        raise ValueError("per-channel wo_spec needs the wo_b_vec "
                         "multiplier vector")
    specs = [pl.BlockSpec((h * d, n_out), _const_map)]
    args = [wo_w8]
    for vec, on in ((wo_bias32, has_bias), (wo_b_vec, has_bvec)):
        if on:
            specs.append(pl.BlockSpec((1, n_out), _const_map))
            args.append(jnp.asarray(vec, jnp.int32).reshape(1, n_out))
    out_dtype = jnp.int8 if (not wo_spec.is_raw
                             and wo_spec.out_bits <= 8) else jnp.int32
    return specs, args, n_out, out_dtype, has_bias, has_bvec


def _attn_scratch(h: int, rows: int, d: int):
    from jax.experimental.pallas import tpu as pltpu
    return [pltpu.VMEM((h, rows, 1), jnp.int32),     # row max, per head
            pltpu.VMEM((h, rows, 1), jnp.int32),     # row weight sum
            pltpu.VMEM((h, rows, d), jnp.int32)]     # P·V accumulator


def _fused_kernel(q_ref, k_ref, v_ref, *rest, plan: IAttnPlan,
                  requant: RequantSpec, has_bvec: bool, n_kv: int,
                  bq: int, bkv: int, causal: bool, window: int,
                  n_heads: int, group: int):
    if has_bvec:
        b_ref, o_ref, m_ref, s_ref, acc_ref = rest
    else:
        b_ref = None
        o_ref, m_ref, s_ref, acc_ref = rest
    q_blk = pl.program_id(1)
    phase = pl.program_id(2)
    kv_step = pl.program_id(3)

    qi = q_blk * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    ki = kv_step * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    live = jnp.ones((bq, bkv), jnp.bool_)
    if causal or window > 0:
        # mirror core.attention.causal_mask: a window implies causality
        live = live & (ki <= qi)
    if window > 0:
        live = live & (ki > qi - window)

    # upper-triangle blocks contribute NEG to the max and 0 to the sum
    # and the accumulator — skip them entirely under a causal mask
    if causal or window > 0:
        blk_live = kv_step * bkv <= q_blk * bq + bq - 1
    else:
        blk_live = True

    _streaming_attn_body(
        phase, kv_step, n_kv, live, blk_live, m_ref, s_ref, acc_ref, b_ref,
        n_heads=n_heads, group=group,
        q_of=lambda h: q_ref[0, :, h, :],           # (bq, d) int8
        k_of=lambda g: k_ref[0, :, g, :],           # (bkv, d) int8
        v_of=lambda g: v_ref[0, :, g, :],
        emit=_head_store(o_ref), plan=plan, requant=requant)


def int_attention_fused(q8, k8, v8, plan: IAttnPlan, requant=None,
                        b_vec=None, causal: bool = True, window: int = 0,
                        bq: int = 128, bkv: int = 128, out_bits: int = 8,
                        interpret: Optional[bool] = None):
    """q8: (B, Sq, H, D) int8; k8: (B, Skv, Hkv, D) int8; v8: (B, Skv,
    Hkv, Dv) int8 (GQA: Hkv | H; MLA's values are narrower than its
    queries and keys, ``Dv`` 128 against ``D`` 192).

    ``requant``: a :class:`RequantSpec` for the epilogue (default: the
    plan's per-tensor ``dn_out``); ``b_vec``: int32 per-channel
    multipliers, shape (H*Dv,) or (H, Dv), required iff per-channel.

    Grid ``(B, Sq/bq, 2, Skv/bkv)``: every step takes all heads of a
    ``(bq, H, D)`` query block and ``(bkv, Hkv, D)`` / ``(bkv, Hkv,
    Dv)`` K / V blocks and loops over the heads in-kernel
    (``_streaming_attn_body``); the accumulator and the output are
    ``Dv`` wide.

    Returns (B, Sq, H, Dv): int8 when the epilogue clips to ≤ 8 bits,
    int32 otherwise (raw / wide output).  Bit-exact against
    ``kernels.ref.ref_int_attention`` for the same arguments.

    Under tensor-parallel serving (``distributed.tp_serving``) the
    wrapper runs inside a shard_map body on head-sliced operands, so
    ``require_launch`` validates the local (H/tp, Hkv/tp) launch;
    ``analysis.contracts.check_tp_launch`` is its offline twin.
    """
    b, sq, h, d = q8.shape
    _, skv, hkv, _ = k8.shape
    dv = v8.shape[-1]
    require_launch(check_launch(
        "int_attention", b=b, sq=sq, skv=skv, h=h, hkv=hkv, d=d,
        bq=bq, bkv=bkv, out_bits=out_bits,
        per_channel=requant is not None and requant.kind == PER_CHANNEL,
        dv=dv))
    group = h // hkv
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    n_kv = skv // bkv

    requant, has_bvec, b2, out_dtype = _epilogue_setup(
        requant, plan, out_bits, b_vec, h, dv)

    kernel = functools.partial(
        _fused_kernel, plan=plan, requant=requant, has_bvec=has_bvec,
        n_kv=n_kv, bq=bq, bkv=bkv, causal=causal, window=window,
        n_heads=h, group=group)

    in_specs = [
        pl.BlockSpec((1, bq, h, d), lambda bi, qi, ph, ki: (bi, qi, 0, 0)),
        pl.BlockSpec((1, bkv, hkv, d),
                     lambda bi, qi, ph, ki: (bi, ki, 0, 0)),
        pl.BlockSpec((1, bkv, hkv, dv),
                     lambda bi, qi, ph, ki: (bi, ki, 0, 0)),
    ]
    args = [q8, k8, v8]
    if has_bvec:
        in_specs.append(pl.BlockSpec((h, dv), _const_map))
        args.append(b2)

    out = pl.pallas_call(
        kernel,
        grid=(b, sq // bq, 2, n_kv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, bq, dv),
                               lambda bi, qi, ph, ki: (bi, 0, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dv), out_dtype),
        scratch_shapes=_attn_scratch(h, bq, dv),
        name=trace_names.kernel("int_attention_fused"),
        interpret=resolve_interpret(interpret),
    )(*args)
    return jnp.swapaxes(out, 1, 2)


# ===================================================== paged prefill =======
#
# The chunked-prefill variant of the kernel above: C chunk queries per
# slot (the serving engine's prompt chunk) against a *paged* KV cache —
# history plus the chunk itself, already scattered into the physical
# pools through the page table (``repro.ops.paged.scatter_chunk``).
# Two scalar-prefetch operands steer the launch, exactly as in the
# decode kernel (``int_decode_attention.py``):
#
#   pos_end : int32 (B,)          = base_pos + C, the logical occupancy
#                                   after the chunk (the decode kernel's
#                                   ``valid_len``);
#   pages   : int32 (B, max_pages) logical block -> physical page.
#
# Masking is the decode kernel's stepped occupancy mask with Sq = C:
# chunk row ``i`` (global position ``pos_end - C + i``) sees cache
# positions ``< pos_end - C + i + 1`` — which *is* causal attention over
# history + chunk.  Unlike the decode kernel (Sq <= 8 rows in scratch for
# the whole launch) the chunk is tiled over query blocks like prefill,
# so C is bounded by VMEM tiling only, not by MAX_SQ.
#
# The folded wo projection (``wo_w8=``) mirrors the decode kernel's:
# every grid step holds all heads of its query block, so the last step
# sums the block's per-head o-projection slabs in a ``(bq, N)`` VMEM
# accumulator and applies bias + the wo RequantSpec (``_wo_fold``).


def _paged_prefill_kernel(*refs, plan: IAttnPlan, requant: RequantSpec,
                          has_bvec: bool, n_kv: int, c: int, bq: int,
                          bkv: int, fold: bool, wo_spec,
                          wo_has_bias: bool, wo_has_bvec: bool,
                          n_heads: int, group: int, d: int,
                          packed_kv: bool = False, sub: int = 1):
    refs = list(refs)
    vl_ref = refs.pop(0)
    # page table: read by index maps only — except under packed KV,
    # where the body re-derives the physical page for the shift lookup
    pt_ref = refs.pop(0)
    ks_ref = vs_ref = None
    if packed_kv:
        ks_ref, vs_ref = refs.pop(0), refs.pop(0)
    q_ref, k_ref, v_ref = refs.pop(0), refs.pop(0), refs.pop(0)
    b_ref = refs.pop(0) if has_bvec else None
    wo_ref = wob_ref = wobv_ref = None
    if fold:
        wo_ref = refs.pop(0)
        if wo_has_bias:
            wob_ref = refs.pop(0)
        if wo_has_bvec:
            wobv_ref = refs.pop(0)
    o_ref = refs.pop(0)
    m_ref, s_ref, acc_ref = refs.pop(0), refs.pop(0), refs.pop(0)
    wacc_ref = refs.pop(0) if fold else None

    bi = pl.program_id(0)
    q_blk = pl.program_id(1)
    phase = pl.program_id(2)
    kv_step = pl.program_id(3)
    vl = vl_ref[bi]
    base = vl - c                       # chunk's first global position

    k_of, v_of = _kv_loaders(k_ref, v_ref, pt_ref, ks_ref, vs_ref, vl,
                             kv_step, bkv, sub, packed_kv)

    # causal-over-history mask: chunk row i at global position base +
    # q_blk*bq + i sees logical cache positions <= its own.  ki is the
    # *logical* position — the index map already translated the block
    # through the page table, the mask math is unchanged.
    qpos = base + q_blk * bq \
        + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    ki = kv_step * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    live = ki <= qpos

    # a KV block whose first position is past this query block's last
    # row is entirely dead (upper triangle / beyond occupancy: qpos is
    # always <= vl - 1, so the causal bound subsumes the vl bound)
    blk_live = kv_step * bkv <= base + q_blk * bq + bq - 1

    if fold:
        emit, finish = _wo_fold(o_ref, wo_ref, wob_ref, wobv_ref, wacc_ref,
                                d=d, wo_spec=wo_spec)
    else:
        emit, finish = _head_store(o_ref), None
    _streaming_attn_body(
        phase, kv_step, n_kv, live, blk_live, m_ref, s_ref, acc_ref, b_ref,
        n_heads=n_heads, group=group, q_of=lambda h: q_ref[0, :, h, :],
        k_of=k_of, v_of=v_of, emit=emit, finish=finish, plan=plan,
        requant=requant)


def _kv_loaders(k_ref, v_ref, pt_ref, ks_ref, vs_ref, vl, kv_step,
                bkv: int, sub: int, packed_kv: bool):
    """``(k_of, v_of)``: KV head ``g``'s ``(bkv, D)`` int8 tile of the
    current ``(1, bkv, Hkv, D)`` block.  Under packed int4 KV the
    physical page is re-derived exactly as the KV index map did (same
    dead-block clamp) and the nibble tile dequantizes with that page's
    requant shift, in-register — packed pages never exist as dense int8
    outside the launch."""
    if not packed_kv:
        return (lambda g: k_ref[0, :, g, :]), (lambda g: v_ref[0, :, g, :])
    last = jnp.maximum(pl.cdiv(vl, bkv) - 1, 0)
    page = pt_ref[pl.program_id(0), jnp.minimum(kv_step, last) // sub]
    k_shift, v_shift = ks_ref[page], vs_ref[page]
    return (lambda g: _unpack_kv_tile(k_ref[0, :, g, :], k_shift),
            lambda g: _unpack_kv_tile(v_ref[0, :, g, :], v_shift))


def int_paged_prefill_fused(q8, k_pool, v_pool, plan: IAttnPlan, pos_end,
                            pages, page_size: int, requant=None,
                            b_vec=None, bq: int = 128, bkv: int = 128,
                            out_bits: int = 8,
                            interpret: Optional[bool] = None,
                            wo_w8=None, wo_bias32=None, wo_b_vec=None,
                            wo_spec=None, kv_shifts=None):
    """q8: (B, C, H, D) int8 chunk queries; k_pool/v_pool: physical
    ``(num_pages, page_size, Hkv, D)`` int8 pools *already containing
    the chunk's K/V* (``repro.ops.paged.scatter_chunk``); ``pos_end``:
    (B,) int32 logical occupancy after the chunk (``base_pos + C``);
    ``pages``: int32 (B, max_pages) page table.

    ``kv_shifts``: a ``(k_shift, v_shift)`` pair of int32
    ``(num_pages,)`` per-page requant shifts switches the pools to the
    **packed int4** layout ``(num_pages, page_size, Hkv, D // 2)`` —
    two head-dim nibbles per byte, expanded and left-shifted in-register
    (``_unpack_kv_tile``); packed pages never materialize as int8 in
    HBM.  The shifts ride as two extra scalar-prefetch operands.

    ``requant``/``b_vec``: the attention epilogue, exactly as
    :func:`int_attention_fused`.  ``wo_w8`` (+ ``wo_bias32`` /
    ``wo_b_vec`` / ``wo_spec``): fold the o-projection into the launch,
    exactly as the decode kernel — the attention epilogue must clip to
    ≤ 8 bits, and the return becomes ``(B, C, N)``.

    Grid ``(B, C/bq, 2, L/bkv)``; each step takes all heads of a query
    block and of a KV block (``_streaming_attn_body``), so the folded
    projection sums a query block's heads within its last step.

    Returns (B, C, H, D) — or (B, C, N) folded.  Bit-exact against
    ``kernels.ref.ref_int_paged_prefill``'s attention output for the
    same (post-scatter) pools.

    Under tensor-parallel serving the pools arrive head-sliced (each
    device owns Hkv/tp heads of every page — page *ids* are global), so
    ``require_launch`` validates the local launch;
    ``analysis.contracts.check_tp_launch`` is its offline twin.
    """
    b, c, h, d = q8.shape
    ps, hkv = k_pool.shape[1], k_pool.shape[2]
    assert page_size == ps, (page_size, ps)
    pages = jnp.asarray(pages, jnp.int32)
    assert pages.ndim == 2 and pages.shape[0] == b, pages.shape
    L = pages.shape[1] * ps
    packed_kv = kv_shifts is not None
    num_pages = k_pool.shape[0]
    if packed_kv:
        assert k_pool.shape[3] == d // 2, (k_pool.shape, d)
        k_shift = jnp.asarray(kv_shifts[0], jnp.int32)
        v_shift = jnp.asarray(kv_shifts[1], jnp.int32)
        assert k_shift.shape == v_shift.shape == (num_pages,), \
            (k_shift.shape, v_shift.shape, num_pages)
    fold = wo_w8 is not None
    require_launch(check_launch(
        "int_paged_prefill", b=b, c=c, h=h, hkv=hkv, d=d,
        max_pages=pages.shape[1], page_size=ps, bq=bq, bkv=bkv,
        out_bits=out_bits, kv_pack=packed_kv, num_pages=num_pages,
        per_channel=requant is not None and requant.kind == PER_CHANNEL,
        fold=fold, n_out=jnp.shape(wo_w8)[-1] if fold else 0))
    group = h // hkv
    bq = min(bq, c)
    bkv = min(bkv, ps)
    sub = ps // bkv                     # KV sub-blocks per physical page
    n_kv = L // bkv
    pos_end = jnp.asarray(pos_end, jnp.int32)

    requant, has_bvec, b2, out_dtype = _epilogue_setup(
        requant, plan, out_bits, b_vec, h, d)

    wo_specs, wo_args, n_out = [], [], 0
    wo_has_bias = wo_has_bvec = False
    if fold:
        (wo_specs, wo_args, n_out, out_dtype, wo_has_bias,
         wo_has_bvec) = _wo_fold_setup(requant, wo_w8, wo_bias32, wo_b_vec,
                                       wo_spec, h, d)

    kernel = functools.partial(
        _paged_prefill_kernel, plan=plan, requant=requant,
        has_bvec=has_bvec, n_kv=n_kv, c=c, bq=bq, bkv=bkv,
        fold=fold, wo_spec=wo_spec, wo_has_bias=wo_has_bias,
        wo_has_bvec=wo_has_bvec, n_heads=h, group=group, d=d,
        packed_kv=packed_kv, sub=sub)

    def _kv_block(ki, vl):
        # clamp dead blocks to the slot's last live one before table
        # translation, exactly as the decode kernel (unmapped entries
        # hold the resident null page anyway; the clamp keeps the DMA
        # on this lane's own pages)
        last = jnp.maximum(pl.cdiv(vl, bkv) - 1, 0)
        return jnp.minimum(ki, last)

    # index maps: grid is (b, q_blk, phase, kv); scalar-prefetch refs
    # (pos_end, pages[, k_shift, v_shift]) arrive as trailing args
    # (``*_`` absorbs the shift refs under the packed layout).
    def q_map(bi, qi, ph, ki, vl, pt, *_):
        return (bi, qi, 0, 0)

    def kv_map(bi, qi, ph, ki, vl, pt, *_):
        kc = _kv_block(ki, vl[bi])
        return (pt[bi, kc // sub], kc % sub, 0, 0)

    def out_map(bi, qi, ph, ki, vl, pt, *_):
        return (bi, qi, 0) if fold else (bi, 0, qi, 0)

    kv_blk = (1, bkv, hkv, d // 2 if packed_kv else d)
    in_specs = [
        pl.BlockSpec((1, bq, h, d), q_map),
        pl.BlockSpec(kv_blk, kv_map),
        pl.BlockSpec(kv_blk, kv_map),
    ]
    args = [q8, k_pool, v_pool]
    if has_bvec:
        in_specs.append(pl.BlockSpec((h, d), _const_map))
        args.append(b2)
    in_specs += wo_specs
    args += wo_args

    from jax.experimental.pallas import tpu as pltpu
    scratch = _attn_scratch(h, bq, d)
    if fold:
        # the (bq, N) o-projection accumulator summed over the heads
        scratch.append(pltpu.VMEM((bq, n_out), jnp.int32))
        out_specs = pl.BlockSpec((1, bq, n_out), out_map)
        out_shape = jax.ShapeDtypeStruct((b, c, n_out), out_dtype)
    else:
        out_specs = pl.BlockSpec((1, h, bq, d), out_map)    # head-major
        out_shape = jax.ShapeDtypeStruct((b, h, c, d), out_dtype)

    scalar_args = (pos_end, pages, k_shift, v_shift) if packed_kv \
        else (pos_end, pages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalar_args),
        grid=(b, c // bq, 2, n_kv),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        name=trace_names.kernel("int_paged_prefill_fused"),
        interpret=resolve_interpret(interpret),
    )(*scalar_args, *args)
    return out if fold else jnp.swapaxes(out, 1, 2)
