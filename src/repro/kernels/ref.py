"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``ref_*`` mirrors the exact integer semantics of its kernel (same
rounding, same staging) by delegating to ``repro.core`` — the kernels are
*implementations* of the core numerics with explicit VMEM tiling, so any
kernel vs. ref mismatch is a bug.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import attention as iattn
from repro.core import norms as inorms
from repro.core.dyadic import Dyadic, apply_dyadic, clip_to_bits
from repro.core.intmath import IGeluPlan, i_gelu


def ref_int8_matmul(x8, w8, bias32, dn: Dyadic, out_bits: int = 8):
    """int8 (M,K) x int8 (K,N) -> int32, +bias, dyadic requant, clip.

    bias32: int32 (N,) at the accumulator scale (s_x * s_w), or None.
    """
    acc = jnp.dot(x8, w8, preferred_element_type=jnp.int32)
    if bias32 is not None:
        acc = acc + bias32[None, :]
    return clip_to_bits(apply_dyadic(acc, dn), out_bits)


def ref_int8_matmul_perchannel(x8, w8, bias32, b_vec, c: int, pre: int,
                               out_bits: int = 8):
    from repro.core.dyadic import apply_dyadic_perchannel
    acc = jnp.dot(x8, w8, preferred_element_type=jnp.int32)
    if bias32 is not None:
        acc = acc + bias32[None, :]
    out = apply_dyadic_perchannel(acc, b_vec, c, pre, axis=-1)
    return clip_to_bits(out, out_bits)


def ref_int_grouped_matmul(x8, w8, group_sizes, b_vec=None, c: int = 0,
                           pre: int = 0, out_bits: int = 8):
    """Oracle for the grouped matmul kernel: row ``r`` of ``x8`` (M, K),
    in group ``g`` by the sorted ``group_sizes`` (G,), takes ``x8[r] @
    w8[g]`` (``w8`` (G, K, N)), then group ``g``'s per-channel
    multipliers ``b_vec[g]`` with the shared ``(c, pre)`` staging and a
    clip to ``out_bits`` — or, with no ``b_vec``, the raw int32
    accumulator.  Rows past ``sum(group_sizes)`` are 0.  One full matmul
    per group, each masked to its rows: an oracle, not a fast path."""
    from repro.core.dyadic import rshift_round
    m = x8.shape[0]
    sizes = jnp.asarray(group_sizes, jnp.int32)
    ends = jnp.cumsum(sizes)
    row = jnp.arange(m, dtype=jnp.int32)[:, None]
    out = jnp.zeros((m, w8.shape[-1]), jnp.int32)
    for g in range(w8.shape[0]):
        acc = jnp.dot(x8, w8[g], preferred_element_type=jnp.int32)
        if b_vec is not None:
            b = jnp.asarray(b_vec, jnp.int32)[g][None, :]
            acc = clip_to_bits(rshift_round(rshift_round(acc, pre) * b,
                                            c - pre), out_bits)
        out = jnp.where((row >= ends[g] - sizes[g]) & (row < ends[g]),
                        acc, out)
    return out.astype(jnp.int8) if out_bits <= 8 else out


def ref_int_gelu(q, plan: IGeluPlan, dn_out: Dyadic, out_bits: int = 8):
    """int8 where ``out_bits <= 8``, int32 otherwise."""
    out = clip_to_bits(apply_dyadic(i_gelu(q.astype(jnp.int32), plan),
                                    dn_out), out_bits)
    return out.astype(jnp.int8) if out_bits <= 8 else out


def ref_int_layernorm(q, q_gamma, q_beta, plan: inorms.INormPlan,
                      out_bits: int = 8):
    return inorms.i_norm(q, q_gamma, q_beta, plan, out_bits)


def ref_int_attention(q8, k8, v8, plan: iattn.IAttnPlan, causal: bool = True,
                      window: int = 0, out_bits: int = 8, requant=None,
                      b_vec=None):
    """Oracle for the fused attention kernels: full-matrix integer attention.

    ``requant``: optional :class:`repro.ops.RequantSpec` epilogue applied
    to the int32 P·V accumulator (scale ``2^-7 * s_v``).  ``None`` keeps
    the historical behaviour — the plan's per-tensor ``dn_out``.  For the
    per-channel form, ``b_vec`` holds int32 multipliers over the
    flattened (head, head_dim) output channels, shape (H*D,) or (H, D).
    """
    sq, sk = q8.shape[1], k8.shape[1]
    mask = iattn.causal_mask(sq, sk, window=window)[None, None] \
        if (causal or window > 0) else None
    # GQA: repeat kv heads if needed
    h, hkv = q8.shape[2], k8.shape[2]
    if hkv != h:
        rep = h // hkv
        k8 = jnp.repeat(k8, rep, axis=2)
        v8 = jnp.repeat(v8, rep, axis=2)
    if requant is None:
        return iattn.i_attention_full(q8, k8, v8, plan, mask=mask,
                                      out_bits=out_bits)
    acc = iattn.i_attention_acc(q8, k8, v8, plan, mask=mask)
    return apply_attn_requant(acc, requant, b_vec)


def ref_int_decode_attention(q8, k8_cache, v8_cache, plan: iattn.IAttnPlan,
                             valid_len, out_bits: int = 8, requant=None,
                             b_vec=None):
    """Oracle for the fused decode kernel: full-matrix attention of a few
    query rows against a ragged int8 KV cache.

    q8: (B, Sq, H, D); caches: (B, L, Hkv, D) (GQA: Hkv | H);
    ``valid_len``: (B,) int32 live cache positions per slot.  Query row
    ``i`` attends to positions ``< valid_len − (Sq − 1 − i)`` — the
    stepped mask of speculative decode; Sq = 1 is the plain
    ``pos < valid_len`` occupancy mask.  ``requant``/``b_vec``: epilogue
    exactly as :func:`ref_int_attention` (default: the plan's per-tensor
    ``dn_out``).
    """
    b, sq, h, d = q8.shape
    L, hkv = k8_cache.shape[1], k8_cache.shape[2]
    if hkv != h:
        rep = h // hkv
        k8_cache = jnp.repeat(k8_cache, rep, axis=2)
        v8_cache = jnp.repeat(v8_cache, rep, axis=2)
    valid_len = jnp.asarray(valid_len, jnp.int32)
    pos = jnp.arange(L)[None, None, None, :]
    limit = valid_len[:, None, None, None] \
        - (sq - 1 - jnp.arange(sq))[None, None, :, None]
    mask = pos < limit                                   # (B,1,Sq,L)
    if requant is None:
        return iattn.i_attention_full(q8, k8_cache, v8_cache, plan,
                                      mask=mask, out_bits=out_bits)
    acc = iattn.i_attention_acc(q8, k8_cache, v8_cache, plan, mask=mask)
    return apply_attn_requant(acc, requant, b_vec)


def ref_int_paged_decode_attention(q8, k_pool, v_pool, plan, valid_len,
                                   pages, page_size: int,
                                   out_bits: int = 8, requant=None,
                                   b_vec=None):
    """Decode oracle for the *paged* cache layout: gather the page pool
    ``(num_pages, page_size, Hkv, D)`` through ``pages (B, max_pages)``
    into the contiguous per-slot layout, then delegate to
    :func:`ref_int_decode_attention` — paged decode is *defined* as
    bit-identical to this composition."""
    from repro.ops.paged import gather_pages
    k8 = gather_pages(k_pool, pages, page_size)
    v8 = gather_pages(v_pool, pages, page_size)
    return ref_int_decode_attention(q8, k8, v8, plan, valid_len, out_bits,
                                    requant=requant, b_vec=b_vec)


def ref_int_paged_prefill(q8, k8_new, v8_new, k_pool, v_pool, plan,
                          base_pos, pages, page_size: int,
                          out_bits: int = 8, requant=None, b_vec=None,
                          wo_w8=None, wo_bias32=None, wo_b_vec=None,
                          wo_spec=None):
    """Oracle for the chunked paged-prefill op: scatter the chunk's new
    K/V through the page table, gather the updated pools into the
    contiguous layout, and run the stepped-mask decode oracle with
    ``valid_len = base_pos + C`` — chunk row ``i`` (global position
    ``base_pos[b] + i``) then attends to exactly the positions
    ``≤ base_pos[b] + i``, the causal-over-history mask of chunked
    prefill.  Paged prefill is *defined* as bit-identical to this
    composition.

    ``q8``/``k8_new``/``v8_new``: ``(B, C, H|Hkv, D)`` int8 chunk
    projections (RoPE already applied); pools ``(num_pages, page_size,
    Hkv, D)``; ``base_pos (B,) int32``; ``wo_*``: the optional folded
    o-projection, exactly as :func:`ref_apply_wo`.  Returns
    ``(o, k_pool, v_pool)`` — the chunk attention output plus the
    updated pools.
    """
    from repro.ops.paged import gather_pages, scatter_chunk
    c = q8.shape[1]
    k_pool = scatter_chunk(k_pool, k8_new, base_pos, pages, page_size)
    v_pool = scatter_chunk(v_pool, v8_new, base_pos, pages, page_size)
    kc = gather_pages(k_pool, pages, page_size)
    vc = gather_pages(v_pool, pages, page_size)
    vl = jnp.asarray(base_pos, jnp.int32) + c
    o = ref_int_decode_attention(q8, kc, vc, plan, vl, out_bits,
                                 requant=requant, b_vec=b_vec)
    if wo_w8 is not None:
        o = ref_apply_wo(o, wo_w8, wo_bias32, wo_b_vec, wo_spec)
    return o, k_pool, v_pool


def ref_apply_wo(o8, wo_w8, wo_bias32, wo_b_vec, wo_spec):
    """The unfolded o-projection a folded decode launch must match:
    int8 attention output ``(B, Sq, H, D)`` × ``wo_w8 (H·D, N)`` with
    bias and the wo :class:`RequantSpec` epilogue → ``(B, Sq, N)``.
    Exactly ``models.intlayers.int_linear``'s math on the ref backend."""
    from repro.core.dyadic import apply_dyadic_perchannel
    from repro.ops.spec import PER_TENSOR
    b, sq = o8.shape[0], o8.shape[1]
    x8 = o8.astype(jnp.int8).reshape(b * sq, -1)
    acc = jnp.dot(x8, wo_w8, preferred_element_type=jnp.int32)
    if wo_bias32 is not None:
        acc = acc + wo_bias32[None, :]
    if wo_spec.is_raw:
        return acc.reshape(b, sq, -1)
    if wo_spec.kind == PER_TENSOR:
        out = apply_dyadic(acc, wo_spec.dn)
    else:
        if wo_b_vec is None:
            raise ValueError("per-channel wo_spec needs the wo_b_vec "
                             "multiplier vector")
        out = apply_dyadic_perchannel(acc, jnp.asarray(wo_b_vec, jnp.int32),
                                      wo_spec.c, wo_spec.pre, axis=-1)
    out = clip_to_bits(out, wo_spec.out_bits)
    out = out.astype(jnp.int8) if wo_spec.out_bits <= 8 else out
    return out.reshape(b, sq, -1)


def apply_attn_requant(acc, requant, b_vec=None):
    """Apply a RequantSpec epilogue to the (B, Sq, H, D) int32 P·V
    accumulator — the exact rounding the fused kernel replicates.  The
    per-channel axis is the flattened (head, head_dim) output channel."""
    from repro.core.dyadic import apply_dyadic_perchannel
    from repro.ops.spec import PER_TENSOR
    if requant.is_raw:
        return acc
    if requant.kind == PER_TENSOR:
        out = apply_dyadic(acc, requant.dn)
    else:
        if b_vec is None:
            raise ValueError("per-channel RequantSpec needs the b_vec "
                             "multiplier vector")
        b, sq, h, d = acc.shape
        out = apply_dyadic_perchannel(
            acc.reshape(b, sq, h * d),
            jnp.asarray(b_vec, jnp.int32).reshape(h * d),
            requant.c, requant.pre, axis=-1).reshape(b, sq, h, d)
    out = clip_to_bits(out, requant.out_bits)
    return out.astype(jnp.int8) if requant.out_bits <= 8 else out
