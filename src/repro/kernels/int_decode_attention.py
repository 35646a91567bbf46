"""Pallas TPU kernel: fused integer *decode* attention, bit-exact.

The serving hot path: one (or a few speculative) new query tokens per
sequence against an int8 KV cache whose per-slot occupancy differs —
slot ``b`` has ``valid_len[b]`` live positions, the rest of the cache is
stale.  One kernel launch runs the whole SwiftTron datapath (int8 Q·Kᵀ →
Shiftmax → int8 P·V → RequantSpec epilogue) streaming over KV-cache
blocks, with **data-dependent ``valid_len`` masking**:

  * ``valid_len`` (B,) int32 rides as a *scalar-prefetch* operand
    (``pltpu.PrefetchScalarGridSpec``), so it is resident before the
    kernel body runs and may steer the block pipeline;
  * KV blocks that are entirely dead for a slot are **skipped, not
    computed-and-discarded**: the block index map clamps to the last
    live block (the pipeline re-reads a resident block instead of
    fetching a dead one) and every sweep is predicated off with
    ``pl.when`` — per-step work is O(valid_len), not O(cache_len);
  * inside the boundary block, dead positions contribute ``-2³⁰`` to the
    row max and 0 to the sum and the P·V accumulator, exactly like the
    prefill kernel's causal masking.

**Paged KV caches** (``pages=``): instead of a contiguous per-slot cache
``(B, L, Hkv, D)``, the K/V operands may be a physical page pool
``(num_pages, page_size, Hkv, D)`` plus a page table ``pages: int32[B,
max_pages]`` riding as a *second* scalar-prefetch operand next to
``valid_len``.  The kernel body is unchanged — masking works in logical
positions — only the KV block index map differs: logical block ``k`` of
slot ``b`` resolves to physical page ``pages[b, k·bkv // page_size]``
(sub-block ``k·bkv % page_size // bkv``).  Dead logical blocks clamp to
the last live block *before* translation, so the DMA always lands on a
resident page; unmapped table entries hold the null page 0, which every
pool reserves (see ``repro.serving.kvcache``).  Numerics are
bit-identical to gathering the pages into the contiguous layout first.

**Folded wo projection** (``wo_w8=``): the decode epilogue can absorb
the attention output projection — per head, the requantized int8
``(Sq, D)`` tile is contracted against that head's ``(D, N)`` row slab
of the whole-``wo`` block and summed over the heads in VMEM scratch;
then ``bias32`` is added and the wo ``RequantSpec`` applied
(typically per-channel over the N output channels, the same two-stage
rounding the attention epilogue already implements).  The launch then
returns the ``(B, Sq, N)`` projected output directly — one kernel for
attention *and* o-projection, bit-exact against the unfolded
attention-then-``int8_matmul`` composition.

Like ``int_attention_fused`` this buys bit-exactness with three
streaming sweeps over the live KV blocks (max → sum → normalise+AV) —
integer maxima and sums are associative, so the result is bit-identical
to the full-matrix decode oracle ``kernels.ref.ref_int_decode_attention``
for every RequantSpec epilogue form.

Speculative queries (1 < Sq ≤ 8): query row ``i`` attends to cache
positions ``< valid_len − (Sq − 1 − i)`` — the *last* row sees exactly
``valid_len`` positions, earlier speculative rows one fewer each (the
stepped causal mask of draft verification).  ``Sq = 1`` reduces to the
plain ``pos < valid_len`` occupancy mask.

Accumulator budget (Sq ≤ 8 rows live in VMEM scratch the whole launch):
row sums need ``valid_len ≤ 2¹⁵`` so ``Σ e16 ≤ 2³⁰`` stays int32-exact —
the same ``MAX_SKV`` budget as the prefill kernel, asserted on the
*logical cache length* here because ``valid_len ≤ L`` by construction.
The folded-wo scratch adds ``(Sq, N)`` int32 (N = H·D out channels).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import trace_names
from repro.analysis.budgets import MAX_ROWSUM_LEN
from repro.analysis.budgets import MAX_SQ as _MAX_SQ
from repro.analysis.contracts import check_launch, require_launch
from repro.core.attention import IAttnPlan
from repro.kernels import resolve_interpret
from repro.kernels.int_attention_fused import (_attn_scratch, _const_map,
                                               _epilogue_setup, _head_store,
                                               _kv_loaders,
                                               _streaming_attn_body,
                                               _wo_fold, _wo_fold_setup)
from repro.ops.spec import PER_CHANNEL, RequantSpec

# both budgets are owned by repro.analysis.budgets; re-exported here
# because callers (and tests) import them from the kernel module
MAX_SQ = _MAX_SQ            # speculative query budget (scratch rows/head)
MAX_SKV = MAX_ROWSUM_LEN    # row-sum int32 budget: L * 2^15 <= 2^30


def _decode_kernel(*refs, plan: IAttnPlan, requant: RequantSpec,
                   has_bvec: bool, n_kv: int, sq: int, bkv: int,
                   paged: bool, fold: bool, wo_spec, wo_has_bias: bool,
                   wo_has_bvec: bool, n_heads: int, group: int, d: int,
                   packed_kv: bool = False, sub: int = 1):
    refs = list(refs)
    vl_ref = refs.pop(0)
    pt_ref = ks_ref = vs_ref = None
    if paged:
        # page table: read by index maps only — except under packed KV,
        # where the body re-derives the physical page for the shift
        # lookup
        pt_ref = refs.pop(0)
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
    phase = pl.program_id(1)
    kv_step = pl.program_id(2)
    vl = vl_ref[bi]

    k_of, v_of = _kv_loaders(k_ref, v_ref, pt_ref, ks_ref, vs_ref, vl,
                             kv_step, bkv, sub, packed_kv)

    # stepped occupancy mask: row i sees vl - (sq-1-i) positions (sq=1:
    # the plain pos < valid_len cache-occupancy mask).  ki is the
    # *logical* position — under paging the index map already translated
    # the block to its physical page, the mask math is unchanged.
    qi = jax.lax.broadcasted_iota(jnp.int32, (sq, bkv), 0)
    ki = kv_step * bkv + jax.lax.broadcasted_iota(jnp.int32, (sq, bkv), 1)
    live = ki < vl - (sq - 1 - qi)

    # data-dependent block skip: a block whose first position is already
    # past the widest row's occupancy (the last query row sees vl) is
    # entirely dead — contribute nothing, in any sweep.  The epilogue
    # inside the shared body still runs on the last step, so a slot with
    # valid_len == 0 writes requant(0) (matching the all-masked oracle).
    blk_live = kv_step * bkv < vl

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


def int_decode_attention_fused(q8, k8_cache, v8_cache, plan: IAttnPlan,
                               valid_len, requant=None, b_vec=None,
                               bkv: int = 128, out_bits: int = 8,
                               interpret: Optional[bool] = None,
                               pages=None, page_size: int = 0,
                               wo_w8=None, wo_bias32=None, wo_b_vec=None,
                               wo_spec=None, kv_shifts=None):
    """q8: (B, Sq, H, D) int8, Sq ≤ 8; valid_len: (B,) int32 live
    positions per slot.  Caches, either layout:

      * contiguous — k8/v8 ``(B, L, Hkv, D)`` int8 (GQA: Hkv | H);
      * paged      — k8/v8 ``(num_pages, page_size, Hkv, D)`` pools plus
        ``pages: int32 (B, max_pages)`` (logical block → physical page;
        unmapped entries = null page 0) and ``page_size``.  The logical
        length is ``max_pages · page_size``.

    ``kv_shifts``: a ``(k_shift, v_shift)`` pair of int32
    ``(num_pages,)`` per-page requant shifts switches the paged pools to
    the **packed int4** layout ``(num_pages, page_size, Hkv, D // 2)`` —
    two head-dim nibbles per byte, expanded and left-shifted in-register
    (``kernels.int_attention_fused._unpack_kv_tile``); packed pages
    never materialize as dense int8 in HBM.  Paged layout only.

    ``requant``: a :class:`RequantSpec` for the epilogue (default: the
    plan's per-tensor ``dn_out``); ``b_vec``: int32 per-channel
    multipliers, shape (H*D,) or (H, D), required iff per-channel.

    ``wo_w8`` (+ ``wo_bias32`` / ``wo_b_vec`` / ``wo_spec``): fold the
    output projection into the launch — ``wo_w8 (H·D, N)`` int8,
    ``wo_spec`` its epilogue (``wo_b_vec (N,)`` iff per-channel).  The
    attention epilogue must clip to ≤ 8 bits (it feeds the int8 MXU
    contraction); the return becomes ``(B, Sq, N)``.

    Returns (B, Sq, H, D) — or (B, Sq, N) when folded: int8 when the
    final epilogue clips to ≤ 8 bits, int32 otherwise.  Bit-exact
    against ``kernels.ref.ref_int_decode_attention`` (+ the unfolded
    per-channel matmul when folding) for the same arguments.

    Under tensor-parallel serving this wrapper runs inside a shard_map
    body with the head axes already sliced, so the ``require_launch``
    below validates the *local* (H/tp, Hkv/tp) launch each device
    makes; ``analysis.contracts.check_tp_launch`` is its offline twin.
    """
    b, sq, h, d = q8.shape
    paged = pages is not None
    packed_kv = kv_shifts is not None
    if packed_kv and not paged:
        raise ValueError("kv_shifts (packed int4 KV) needs the paged "
                         "cache layout")
    if paged:
        ps, hkv = k8_cache.shape[1], k8_cache.shape[2]
        assert page_size == ps, (page_size, ps)
        pages = jnp.asarray(pages, jnp.int32)
        assert pages.ndim == 2 and pages.shape[0] == b, pages.shape
        L = pages.shape[1] * ps
    else:
        _, L, hkv, _ = k8_cache.shape
    num_pages = k8_cache.shape[0] if paged else 0
    k_shift = v_shift = None
    if packed_kv:
        assert k8_cache.shape[3] == d // 2, (k8_cache.shape, d)
        k_shift = jnp.asarray(kv_shifts[0], jnp.int32)
        v_shift = jnp.asarray(kv_shifts[1], jnp.int32)
        assert k_shift.shape == v_shift.shape == (num_pages,), \
            (k_shift.shape, v_shift.shape, num_pages)
    fold = wo_w8 is not None
    require_launch(check_launch(
        "int_decode_attention", b=b, sq=sq, h=h, hkv=hkv, d=d,
        L=None if paged else L, bkv=bkv,
        max_pages=pages.shape[1] if paged else 0,
        page_size=page_size, out_bits=out_bits, kv_pack=packed_kv,
        num_pages=num_pages,
        per_channel=requant is not None and requant.kind == PER_CHANNEL,
        fold=fold, n_out=jnp.shape(wo_w8)[-1] if fold else 0))
    group = h // hkv
    bkv = min(bkv, ps if paged else L)
    sub = ps // bkv if paged else 1     # KV sub-blocks per physical page
    n_kv = L // bkv
    valid_len = jnp.asarray(valid_len, jnp.int32)

    requant, has_bvec, b2, out_dtype = _epilogue_setup(
        requant, plan, out_bits, b_vec, h, d)

    wo_specs, wo_args, n_out = [], [], 0
    wo_has_bias = wo_has_bvec = False
    if fold:
        (wo_specs, wo_args, n_out, out_dtype, wo_has_bias,
         wo_has_bvec) = _wo_fold_setup(requant, wo_w8, wo_bias32, wo_b_vec,
                                       wo_spec, h, d)

    kernel = functools.partial(
        _decode_kernel, plan=plan, requant=requant, has_bvec=has_bvec,
        n_kv=n_kv, sq=sq, bkv=bkv, paged=paged, fold=fold, wo_spec=wo_spec,
        wo_has_bias=wo_has_bias, wo_has_bvec=wo_has_bvec, n_heads=h,
        group=group, d=d, packed_kv=packed_kv, sub=sub)

    def _kv_block(ki, vl):
        # clamp dead blocks to the slot's last live block: the pipeline
        # re-reads a resident block instead of DMA-ing a dead one (the
        # compute for those steps is pl.when-ed off anyway)
        last = jnp.maximum(pl.cdiv(vl, bkv) - 1, 0)
        return jnp.minimum(ki, last)

    # index maps over the (b, phase, kv) grid: scalar-prefetch refs
    # arrive as trailing args — one (valid_len) for the contiguous
    # layout, two (valid_len, pages) for the paged layout, where the KV
    # map translates logical block → physical (page, sub-block) through
    # the prefetched table (``*_`` absorbs the k_shift/v_shift refs
    # under the packed int4 layout; the kernel body reads those).
    if paged:
        def kv_map(bi, ph, ki, vl, pt, *_):
            kc = _kv_block(ki, vl[bi])
            return (pt[bi, kc // sub], kc % sub, 0, 0)
    else:
        def kv_map(bi, ph, ki, vl, *_):
            return (bi, _kv_block(ki, vl[bi]), 0, 0)

    def q_map(bi, *_):
        return (bi, 0, 0, 0)

    def out_map(bi, *_):
        return (bi, 0, 0) if fold else (bi, 0, 0, 0)     # head-major

    kv_blk = (1, bkv, hkv, d // 2 if packed_kv else d)
    in_specs = [
        pl.BlockSpec((1, sq, h, d), q_map),
        pl.BlockSpec(kv_blk, kv_map),
        pl.BlockSpec(kv_blk, kv_map),
    ]
    args = [q8, k8_cache, v8_cache]
    if has_bvec:
        in_specs.append(pl.BlockSpec((h, d), _const_map))
        args.append(b2)
    in_specs += wo_specs
    args += wo_args

    from jax.experimental.pallas import tpu as pltpu
    scratch = _attn_scratch(h, sq, d)
    if fold:
        # the (Sq, N) o-projection accumulator summed over the heads
        scratch.append(pltpu.VMEM((sq, n_out), jnp.int32))
        out_specs = pl.BlockSpec((1, sq, n_out), out_map)
        out_shape = jax.ShapeDtypeStruct((b, sq, n_out), out_dtype)
    else:
        out_specs = pl.BlockSpec((1, h, sq, d), out_map)
        out_shape = jax.ShapeDtypeStruct((b, h, sq, d), out_dtype)

    if packed_kv:
        scalar_args = (valid_len, pages, k_shift, v_shift)
    elif paged:
        scalar_args = (valid_len, pages)
    else:
        scalar_args = (valid_len,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalar_args),
        grid=(b, 3, n_kv),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        name=trace_names.kernel("int_decode_attention"),
        interpret=resolve_interpret(interpret),
    )(*scalar_args, *args)
    return out if fold else jnp.swapaxes(out, 1, 2)
