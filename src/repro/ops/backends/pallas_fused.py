"""`pallas_fused` backend: the TPU kernels, interpret-mode on CPU.

Every op is bit-exact against the ``ref`` oracle.  ``int8_matmul``,
``int_gelu`` and ``int_layernorm`` launch their kernels
(``kernels.int8_matmul`` / ``int_gelu`` / ``int_layernorm``), each
taking its blocks from the launch's shape unless the call names them.
``int_attention`` routes to ``kernels.int_attention_fused`` — one kernel
launch for Q·Kᵀ → Shiftmax → P·V → requant, streaming over KV blocks.
``int_decode_attention`` routes to ``kernels.int_decode_attention`` —
the same fused datapath for the serving hot path (Sq ≤ 8 queries over a
ragged KV cache, per-slot ``valid_len`` as a scalar-prefetch operand,
dead blocks skipped).  The backend advertises every optional capability
(docs/KERNELS.md): ``paged_decode`` (the page table rides as a second
scalar-prefetch operand and KV blocks translate through it in the index
map) and ``decode_wo_fold`` (the o-projection + its per-channel requant
run as the launch's epilogue), their chunked-prefill twins, packed
weights and KV pages, and tensor-parallel serving.  ``wo`` folds only
while its whole ``(H·D, N)`` block fits the chip's VMEM budget
(``analysis.contracts.can_fold_wo``); a wider projection runs after an
unfolded launch, through the matmul kernel, with the same integers.

Shapes the kernel can't tile fall back to the existing two-pass path
with identical numerics:

  * ``Skv > 2^15`` — the exact row sum would leave the int32 budget; the
    chunked two-pass streaming formulation takes over (per-tensor
    epilogues only, which is all the model datapath uses at such
    lengths);
  * awkward sequence lengths (no block divisor ≥
    ``contracts.MIN_BLOCK`` — e.g. a prime Sq) and tiny problems, where
    a grid of degenerate blocks would be slower than the full-matrix
    oracle.

See docs/KERNELS.md for the kernel contract this backend satisfies.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.analysis import contracts as _contracts
from repro.analysis.budgets import MAX_ROWSUM_LEN as MAX_SKV
from repro.analysis.contracts import fit_block as _fit_block
from repro.kernels import ref as _ref
from repro.kernels import resolve_interpret
from repro.kernels.int8_matmul import PACKED_BLOCKS, int8_matmul_pallas
from repro.kernels.int_gelu import int_gelu_pallas
from repro.kernels.int_layernorm import int_layernorm_pallas
from repro.ops import spec as _spec
from repro.ops.paged import gather_pages as _gather

# NOTE: the fused kernel modules (kernels.int_attention_fused /
# kernels.int_decode_attention) are imported lazily inside the methods:
# this module runs during ``repro.ops`` package init, and both kernel
# modules themselves import ``repro.ops.spec`` — a top-level import here
# would re-enter a half-initialised kernel module whenever a caller
# imports a kernel before the ops package.


def _matmul_blocks(opts: dict, m: int, n: int, k: int, packed=False):
    """The call's requested matmul blocks fitted to chip-legal divisors:
    rows a multiple of 8, lanes (bn, and bk — the x block's lane dim) a
    multiple of 128, else the whole dim (``contracts.fit_block``).  A
    dense block not requested stays None: the kernel takes it from the
    launch's shape (``kernels.int8_matmul.matmul_blocks``).  Packed
    weights default to ``PACKED_BLOCKS`` and pair nibbles along K, so bk
    is fitted on K/2 pairs and doubled (its half is the packed block's
    row dim)."""
    want = PACKED_BLOCKS if packed else (None, None, None)
    bm, bn, bk = (opts.pop(key, w) for key, w in zip(("bm", "bn", "bk"),
                                                     want))
    if bm is not None:
        bm = _fit_block(bm, m, 8)
    if bn is not None:
        bn = _fit_block(bn, n, 128)
    if packed:
        bk = 2 * _fit_block(max(bk // 2, 1), k // 2, 64)
    elif bk is not None:
        bk = _fit_block(bk, k, 128)
    return bm, bn, bk


class PallasFusedBackend:
    name = "pallas_fused"
    fused_attention = True
    fused_decode = True       # single-launch valid_len-masked decode kernel
    paged_decode = True       # consumes page-table KV pools directly
    decode_wo_fold = True     # folds the o-projection into the launch
    paged_prefill = True      # chunked prefill straight over the page table
    prefill_wo_fold = True    # ... with the o-projection folded in too
    packed_matmul = True      # int4/msr4 weights unpacked inside the launch
    packed_kv = True          # int4 KV pages dequantized inside the launch
    tp_serving = True         # kernels launch per-shard under shard_map
    #   (the wrapper's require_launch then validates the LOCAL h/tp,
    #   hkv/tp shapes; analysis.contracts.check_tp_launch is its
    #   offline twin)

    def __init__(self, interpret: Optional[bool] = None):
        self._interpret = interpret

    def _interp(self) -> bool:
        return resolve_interpret(self._interpret)

    # ---------------------------------------------- matmul, GELU, norm --

    def int8_matmul(self, x8, w8, spec, *, bias32=None, b_vec=None, **opts):
        if spec.is_raw:
            # no requant epilogue to fuse -> nothing for the kernel to
            # add over XLA's int8 dot, and raw consumers (lm head,
            # router, dt proj) often have odd N where divisor-fitted
            # blocks would degenerate; keep the MXU dot
            acc = jnp.dot(x8, w8, preferred_element_type=jnp.int32)
            if bias32 is not None:
                acc = acc + bias32[None, :]
            return acc
        m, k = x8.shape
        n = w8.shape[-1]
        bm, bn, bk = _matmul_blocks(opts, m, n, k)
        if spec.kind == _spec.PER_TENSOR:
            return int8_matmul_pallas(x8, w8, bias32, dn=spec.dn,
                                      out_bits=spec.out_bits,
                                      out_dtype=spec.out_dtype,
                                      bm=bm, bn=bn, bk=bk,
                                      interpret=self._interp(), **opts)
        if b_vec is None:
            raise ValueError("per-channel RequantSpec needs the b_vec "
                             "multiplier vector (QuantLinearParams.b_mult)")
        return int8_matmul_pallas(x8, w8, bias32, b_vec=b_vec, c=spec.c,
                                  pre=spec.pre, out_bits=spec.out_bits,
                                  out_dtype=spec.out_dtype,
                                  bm=bm, bn=bn, bk=bk,
                                  interpret=self._interp(), **opts)

    def int_gelu(self, q, plan, dn_out, out_bits: int = 8, **opts):
        return int_gelu_pallas(q, plan, dn_out, out_bits,
                               interpret=self._interp(), **opts)

    def int_layernorm(self, q, q_gamma, q_beta, plan, out_bits: int = 8,
                      **opts):
        return int_layernorm_pallas(q, q_gamma, q_beta, plan, out_bits,
                                    interpret=self._interp(), **opts)

    # --------------------------------------------------- packed matmul --

    def int8_matmul_packed(self, x8, qw, spec, **opts):
        """Matmul over int4/msr4 packed weights, nibbles expanded
        in-register — the dense int8 weight matrix never exists in HBM.

        * plain **int4** (and msr4 with zero outliers): one fused launch
          of the packed matmul kernel carrying the full typed epilogue;
        * **msr4** with outlier lanes: a *raw* packed launch accumulates
          the nibble contraction, the outlier lanes apply as an exact
          sparse correction (`x @ scatter(out_val)`), and integer
          distributivity makes ``acc_nib + corr == x @ w8`` exactly —
          the identical int32 accumulator then takes the identical
          dyadic epilogue, so the result is bit-exact vs the unpacked
          reference for every RequantSpec form.
        """
        from repro.ops.packed import msr4_correction
        from repro.core.dyadic import (apply_dyadic,
                                       apply_dyadic_perchannel,
                                       clip_to_bits)
        qw = _spec.QuantLinearParams.of(qw)
        meta = qw.pack_meta
        m, k = x8.shape
        n = qw.n_dim
        bm, bn, bk = _matmul_blocks(opts, m, n, k, packed=True)
        msr = meta.scheme == "msr4" and meta.n_outliers > 0
        if not msr:
            # pure-nibble weights: one launch, full fused epilogue
            if spec.is_raw:
                return int8_matmul_pallas(
                    x8, qw.w_packed, qw.bias32, out_bits=32,
                    out_dtype=jnp.int32, bm=bm, bn=bn, bk=bk,
                    packed=True, interpret=self._interp(), **opts)
            if spec.kind == _spec.PER_TENSOR:
                return int8_matmul_pallas(
                    x8, qw.w_packed, qw.bias32, dn=spec.dn,
                    out_bits=spec.out_bits, out_dtype=spec.out_dtype,
                    bm=bm, bn=bn, bk=bk, packed=True,
                    interpret=self._interp(), **opts)
            return int8_matmul_pallas(
                x8, qw.w_packed, qw.bias32, b_vec=qw.b_mult,
                c=spec.c, pre=spec.pre, out_bits=spec.out_bits,
                out_dtype=spec.out_dtype, bm=bm, bn=bn, bk=bk,
                packed=True, interpret=self._interp(), **opts)
        # msr4: raw nibble launch + exact sparse outlier correction,
        # then the same staged dyadic epilogue the kernel would fuse
        acc = int8_matmul_pallas(
            x8, qw.w_packed, None, out_bits=32, out_dtype=jnp.int32,
            bm=bm, bn=bn, bk=bk, packed=True,
            interpret=self._interp(), **opts)
        acc = acc + msr4_correction(x8.astype(jnp.int32), qw)
        if qw.bias32 is not None:
            acc = acc + qw.bias32.astype(jnp.int32)[None, :]
        if spec.is_raw:
            return acc
        if spec.kind == _spec.PER_TENSOR:
            out = apply_dyadic(acc, spec.dn)
        else:
            out = apply_dyadic_perchannel(acc, qw.b_mult, spec.c,
                                          spec.pre)
        return clip_to_bits(out, spec.out_bits).astype(spec.out_dtype)

    # ------------------------------------------------------- attention --

    def int_attention(self, q8, k8, v8, plan, causal: bool = True,
                      window: int = 0, out_bits: int = 8, requant=None,
                      b_vec=None, **opts):
        from repro.kernels.int_attention_fused import int_attention_fused
        if requant is None:
            requant = _spec.RequantSpec.per_tensor(plan.dn_out, out_bits)
        sq, skv = q8.shape[1], k8.shape[1]
        # the head-major output block's row dim: a multiple of 8 or Sq
        bq = _fit_block(opts.pop("bq", 128), sq, 8)
        bkv = _fit_block(opts.pop("bkv", 128), skv)
        if not self._can_tile(sq, skv, bq, bkv):
            return self._two_pass_fallback(q8, k8, v8, plan, causal,
                                           window, requant, b_vec)
        return int_attention_fused(q8, k8, v8, plan, requant=requant,
                                   b_vec=b_vec, causal=causal,
                                   window=window, bq=bq, bkv=bkv,
                                   interpret=self._interp(), **opts)

    # -------------------------------------------------- decode attention --

    def int_decode_attention(self, q8, k8_cache, v8_cache, plan, valid_len,
                             out_bits: int = 8, requant=None, b_vec=None,
                             pages=None, page_size: int = 0, wo=None,
                             wo_spec=None, kv_shifts=None, **opts):
        from repro.kernels.int_decode_attention import \
            int_decode_attention_fused
        if requant is None:
            requant = _spec.RequantSpec.per_tensor(plan.dn_out, out_bits)
        sq, d = q8.shape[1], q8.shape[3]
        paged = pages is not None
        # under paging the KV block must tile a physical page (the index
        # map translates whole sub-blocks through the table); otherwise
        # it tiles the contiguous cache length
        blk_dim = page_size if paged else k8_cache.shape[1]
        L = pages.shape[1] * page_size if paged else k8_cache.shape[1]
        bkv = _fit_block(opts.pop("bkv", 128), blk_dim)
        can = self._can_tile_decode(sq, L, d, bkv)
        if wo is not None:
            wo = _spec.QuantLinearParams.of(wo)
            if wo_spec is None:
                raise ValueError("folded wo projection needs wo_spec")
            # the folded projection feeds the attention tile to an int8
            # MXU contraction — a non-int8 epilogue can't fold, in the
            # kernel or in the fallback composition (which would wrap)
            if requant.is_raw or requant.out_bits > 8:
                raise ValueError("wo folding needs an int8 attention "
                                 f"epilogue, got {requant}")
        if not can:
            # exact fallback: dequantize packed pools (declared
            # reference) + gather pages (if paged) + full-matrix
            # oracle + unfolded o-projection
            if kv_shifts is not None:
                from repro.ops.packed import unpack_kv_pool
                k8_cache = unpack_kv_pool(k8_cache, kv_shifts[0])
                v8_cache = unpack_kv_pool(v8_cache, kv_shifts[1])
            if paged:
                k8_cache = _gather(k8_cache, pages, page_size)
                v8_cache = _gather(v8_cache, pages, page_size)
            o = _ref.ref_int_decode_attention(
                q8, k8_cache, v8_cache, plan, valid_len,
                requant=requant, b_vec=b_vec)
            if wo is None:
                return o
            return _ref.ref_apply_wo(o, wo.w8, wo.bias32, wo.b_mult,
                                     wo_spec)
        kw = {}
        if paged:
            kw.update(pages=pages, page_size=page_size)
        if kv_shifts is not None:
            kw.update(kv_shifts=kv_shifts)
        fold = self._folds(wo, requant, sq, q8, k8_cache, bkv)
        if fold:
            kw.update(wo_w8=wo.w8, wo_bias32=wo.bias32, wo_b_vec=wo.b_mult,
                      wo_spec=wo_spec)
        o = int_decode_attention_fused(q8, k8_cache, v8_cache, plan,
                                       valid_len, requant=requant,
                                       b_vec=b_vec, bkv=bkv,
                                       interpret=self._interp(),
                                       **kw, **opts)
        return o if fold or wo is None else self._apply_wo(o, wo, wo_spec)

    # ---------------------------------------------------- paged prefill --

    def int_paged_prefill(self, q8, k8_new, v8_new, k_pool, v_pool, plan,
                          base_pos, pages, page_size: int,
                          out_bits: int = 8, requant=None, b_vec=None,
                          wo=None, wo_spec=None, kv_shifts=None, **opts):
        """Chunked paged prefill: scatter the chunk's K/V through the
        page table (``repro.ops.paged.scatter_chunk`` — shared with the
        oracle, so every path writes identical pool bytes), then run the
        fused prefill attention kernel reading K/V through the
        scalar-prefetched table (``kernels.int_attention_fused.
        int_paged_prefill_fused``).  With ``kv_shifts`` (int4 KV pages)
        the chunk quantizes + nibble-packs through
        ``repro.ops.packed.pack_kv`` before the scatter — one
        quantization policy shared with the OpSet lowering, so pool
        bytes stay backend-independent — and the fused kernel
        dequantizes in-register.  Untileable shapes gather + take the
        stepped-mask decode oracle with identical numerics."""
        from repro.kernels.int_attention_fused import \
            int_paged_prefill_fused
        from repro.ops.paged import scatter_chunk
        if requant is None:
            requant = _spec.RequantSpec.per_tensor(plan.dn_out, out_bits)
        c, d = q8.shape[1], q8.shape[3]
        pages = jnp.asarray(pages, jnp.int32)
        L = pages.shape[1] * page_size
        if wo is not None:
            wo = _spec.QuantLinearParams.of(wo)
            if wo_spec is None:
                raise ValueError("folded wo projection needs wo_spec")
            if requant.is_raw or requant.out_bits > 8:
                raise ValueError("wo folding needs an int8 attention "
                                 f"epilogue, got {requant}")
        if kv_shifts is not None:
            from repro.ops.packed import pack_kv
            k8_new = pack_kv(k8_new)
            v8_new = pack_kv(v8_new)
        k_pool = scatter_chunk(k_pool, k8_new, base_pos, pages, page_size)
        v_pool = scatter_chunk(v_pool, v8_new, base_pos, pages, page_size)
        pos_end = jnp.asarray(base_pos, jnp.int32) + c
        bq = _fit_block(opts.pop("bq", 128), c, 8)
        bkv = _fit_block(opts.pop("bkv", 128), page_size)
        if not self._can_tile_prefill(L, d, bq, bkv):
            # exact fallback: dequantize the (post-scatter) packed pools
            # (declared reference), gather, then the stepped-mask oracle
            # + unfolded o-projection
            if kv_shifts is not None:
                from repro.ops.packed import unpack_kv_pool
                kc = _gather(unpack_kv_pool(k_pool, kv_shifts[0]),
                             pages, page_size)
                vc = _gather(unpack_kv_pool(v_pool, kv_shifts[1]),
                             pages, page_size)
            else:
                kc = _gather(k_pool, pages, page_size)
                vc = _gather(v_pool, pages, page_size)
            o = _ref.ref_int_decode_attention(q8, kc, vc, plan, pos_end,
                                              requant=requant, b_vec=b_vec)
            if wo is not None:
                o = _ref.ref_apply_wo(o, wo.w8, wo.bias32, wo.b_mult,
                                      wo_spec)
            return o, k_pool, v_pool
        kw = {}
        if kv_shifts is not None:
            kw.update(kv_shifts=kv_shifts)
        fold = self._folds(wo, requant, bq, q8, k_pool, bkv)
        if fold:
            kw.update(wo_w8=wo.w8, wo_bias32=wo.bias32, wo_b_vec=wo.b_mult,
                      wo_spec=wo_spec)
        o = int_paged_prefill_fused(q8, k_pool, v_pool, plan, pos_end,
                                    pages, page_size, requant=requant,
                                    b_vec=b_vec, bq=bq, bkv=bkv,
                                    interpret=self._interp(), **kw, **opts)
        if not (fold or wo is None):
            o = self._apply_wo(o, wo, wo_spec)
        return o, k_pool, v_pool

    # ------------------------------------------------ folded wo policy --

    @staticmethod
    def _folds(wo, requant, rows, q8, kv, bkv) -> bool:
        """Whether ``wo`` folds into this launch: only while the whole
        ``(H·D, N)`` block keeps it inside the chip's VMEM budget
        (``contracts.can_fold_wo``)."""
        return wo is not None and _contracts.can_fold_wo(
            rows, q8.shape[2], kv.shape[2], q8.shape[3], bkv,
            wo.w8.shape[-1], kv_d=kv.shape[3],
            per_channel=requant.kind == _spec.PER_CHANNEL)

    def _apply_wo(self, o8, wo, wo_spec):
        """The o-projection of an unfolded launch through this backend's
        matmul kernel — the integers the folded epilogue would give."""
        b, rows = o8.shape[0], o8.shape[1]
        acc = self.int8_matmul(o8.reshape(b * rows, -1), wo.w8, wo_spec,
                               bias32=wo.bias32, b_vec=wo.b_mult)
        if not wo_spec.is_raw and wo_spec.out_bits <= 8:
            acc = acc.astype("int8")
        return acc.reshape(b, rows, -1)

    # the fused-vs-fallback tiling policy is owned declaratively by
    # repro.analysis.contracts so offline certification predicts the
    # exact same dispatch this backend takes
    _can_tile_prefill = staticmethod(_contracts.can_tile_prefill)
    _can_tile_decode = staticmethod(_contracts.can_tile_decode)
    _can_tile = staticmethod(_contracts.can_tile)

    def _two_pass_fallback(self, q8, k8, v8, plan, causal, window,
                           requant, b_vec):
        """The pre-fusion formulation, numerics preserved exactly."""
        sq, skv = q8.shape[1], k8.shape[1]
        if skv > MAX_SKV:
            # memory-bounded chunked streaming (per-tensor epilogue: the
            # only form the model datapath carries at such lengths)
            if requant.kind != _spec.PER_TENSOR:
                raise NotImplementedError(
                    f"Skv={skv} needs the chunked streaming path, which "
                    "supports per-tensor requant only")
            from repro.core import attention as iattn
            h, hkv = q8.shape[2], k8.shape[2]
            if hkv != h:
                k8 = jnp.repeat(k8, h // hkv, axis=2)
                v8 = jnp.repeat(v8, h // hkv, axis=2)
            p = plan._replace(dn_out=requant.dn)
            out = iattn.i_attention_chunked(
                q8, k8, v8, p, chunk=_fit_block(1024, skv), causal=causal,
                window=window, out_bits=requant.out_bits)
            return out.astype(jnp.int8) if requant.out_bits <= 8 else out
        return _ref.ref_int_attention(q8, k8, v8, plan, causal=causal,
                                      window=window, requant=requant,
                                      b_vec=b_vec)
