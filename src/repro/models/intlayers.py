"""Integer-path transformer layers — the SwiftTron datapath in JAX.

Every function here consumes int8/int32 tensors and the design-time plans
from ``repro.quant.plans``; no float enters the computation (RoPE tables,
polynomial constants and dyadic multipliers are integer design constants).

Residual stream: int32 at ``cfg.s_res`` clipped to ``cfg.qmax_res``
(14-bit) — the ASIC's inter-block INT32 bus.  Matmul operands: int8.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import trace_names
from repro.core import activations as iact
from repro.core import attention as iattn
from repro.core import intmath, norms
from repro.core import softmax as ism
from repro.core.dyadic import apply_dyadic_perchannel, clip_to_bits, \
    rshift_round
from repro.distributed.collectives import psum_int32
from repro.distributed.sharding import shard
from repro.models.common import ArchConfig
from repro.ops import QuantLinearParams, RequantSpec
from repro.ops import get_backend, resolve_ops
from repro.ops.packed import pack_kv
from repro.quant import plans as qplans


# ------------------------------------------------------------- linear -----

def int_linear(x8, qw, plan: qplans.LinearPlan, ops=None):
    """x8: (..., K) int8; qw: QuantLinearParams (or legacy dict).

    Returns (..., N): int8 when plan.s_out > 0 (requantized) else int32
    accumulator.
    """
    ops = resolve_ops(ops)
    qw = QuantLinearParams.of(qw)
    lead = x8.shape[:-1]
    k = x8.shape[-1]
    n = qw.n_dim
    x2 = x8.reshape(-1, k)
    spec = RequantSpec.for_linear(plan)
    if qw.is_packed:
        out = ops.int8_matmul_packed(x2, qw, spec)
    else:
        out = ops.int8_matmul(x2, qw.w8, spec, bias32=qw.bias32,
                              b_vec=qw.b_mult)
    out = out.reshape(*lead, n)
    if not spec.is_raw and plan.out_bits <= 8:
        out = out.astype(jnp.int8)
    return out


def _tp_wo_project(o8, qw, plan: qplans.LinearPlan, tp_axis: str,
                   ops=None):
    """Head-sharded o-projection (tensor-parallel serving).

    ``o8``: (..., H_local·hd) int8 — this device's slice of the
    attention output; ``qw.w8``: the matching *row* slice of wo.  Each
    device computes the raw int32 partial product over its head slice,
    :func:`~repro.distributed.collectives.psum_int32` combines the
    partial slabs exactly, and only then do bias and the per-channel
    requant epilogue apply — once, on the full-sum accumulator — so the
    requant rounds exactly as it would on a single device (mirroring
    ``kernels.ref.ref_apply_wo``).
    """
    ops = resolve_ops(ops)
    qw = QuantLinearParams.of(qw)
    lead = o8.shape[:-1]
    n = qw.n_dim
    x2 = o8.reshape(-1, o8.shape[-1])
    if qw.is_packed:
        # raw partial product only — bias must be added once, after the
        # psum, so strip it from the packed epilogue operands
        acc = ops.int8_matmul_packed(
            x2, qw._replace(bias32=None, b_mult=None), RequantSpec.raw())
    else:
        acc = ops.int8_matmul(x2, qw.w8, RequantSpec.raw())
    acc = psum_int32(acc, tp_axis)
    if qw.bias32 is not None:
        acc = acc + qw.bias32[None, :]
    spec = RequantSpec.for_linear(plan)
    if spec.is_raw:
        out = acc
    else:
        out = apply_dyadic_perchannel(acc, qw.b_mult, spec.c, spec.pre,
                                      axis=-1)
        out = clip_to_bits(out, spec.out_bits)
        if spec.out_bits <= 8:
            out = out.astype(jnp.int8)
    return out.reshape(*lead, n)


# ------------------------------------------------------------- norms ------

def int_expert_linear(x8, qw, plan: qplans.LinearPlan):
    """Batched-per-expert linear: x8 (G,E,C,K) x w8 (E,K,N) -> (G,E,C,N).

    Per-channel requant with b_mult (E,N); shared static (c, pre)."""
    qw = QuantLinearParams.of(qw)
    acc = jnp.einsum("geck,ekn->gecn", x8, qw.w8,
                     preferred_element_type=jnp.int32)
    if qw.bias32 is not None:
        acc = acc + qw.bias32[None, :, None, :]
    b = qw.b_mult[None, :, None, :].astype(jnp.int32)
    out = rshift_round(rshift_round(acc, plan.pre) * b, plan.c - plan.pre)
    out = clip_to_bits(out, plan.out_bits)
    return out.astype(jnp.int8) if plan.out_bits <= 8 else out


def int_norm(qnorm, q32, plan: norms.INormPlan, ops=None):
    """q32 (..., D) int32 at s_res -> int8 at s_act8."""
    ops = resolve_ops(ops)
    out = ops.int_layernorm(q32, qnorm["gamma_q"], qnorm.get("beta_q"),
                            plan, out_bits=8)
    return out.astype(jnp.int8)


# ------------------------------------------------------------- rope -------

ROPE_FRAC = 14


def build_rope_table(max_seq: int, hd: int, theta: float):
    """Design-time int16 cos/sin tables at 2^-14 (integer RoPE)."""
    pos = np.arange(max_seq, dtype=np.float64)[:, None]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = pos * freqs[None, :]
    cos = np.round(np.cos(ang) * (1 << ROPE_FRAC)).astype(np.int32)
    sin = np.round(np.sin(ang) * (1 << ROPE_FRAC)).astype(np.int32)
    return jnp.asarray(cos), jnp.asarray(sin)


def apply_int_rope(q8, positions, rope_tab):
    """q8: (B,S,H,hd) int8; positions: (B,S) or (S,) int32."""
    cos_t, sin_t = rope_tab
    cos = jnp.take(cos_t, positions, axis=0)     # (B,S,hd/2) or (S,hd/2)
    sin = jnp.take(sin_t, positions, axis=0)
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    q = q8.astype(jnp.int32)
    q1, q2 = jnp.split(q, 2, axis=-1)
    r1 = rshift_round(q1 * cos - q2 * sin, ROPE_FRAC)
    r2 = rshift_round(q1 * sin + q2 * cos, ROPE_FRAC)
    out = jnp.concatenate([r1, r2], axis=-1)
    return jnp.clip(out, -127, 127).astype(jnp.int8)


# --------------------------------------------------------- attention ------

def int_attn_fwd(qp, x8, plans: qplans.AttnPlan, cfg: ArchConfig,
                 rope_tab=None, positions=None, causal=True, window: int = 0,
                 memory8=None, ops=None, fuse_attention=True):
    """Self/cross attention.  x8: (B,S,D) int8 -> (B,S,D) int32 at s_res."""
    ops = resolve_ops(ops, cfg)
    b, s, d = x8.shape
    kv_src = memory8 if memory8 is not None else x8
    sk = kv_src.shape[1]
    q8 = int_linear(x8, qp["wq"], plans.qkv, ops) \
        .reshape(b, s, cfg.n_heads, cfg.hd)
    k8 = int_linear(kv_src, qp["wk"], plans.qkv, ops) \
        .reshape(b, sk, cfg.n_kv_heads, cfg.hd)
    v8 = int_linear(kv_src, qp["wv"], plans.qkv, ops) \
        .reshape(b, sk, cfg.n_kv_heads, cfg.hd)
    if rope_tab is not None and memory8 is None:
        pos = positions if positions is not None else jnp.arange(s)
        q8 = apply_int_rope(q8, pos, rope_tab)
        k8 = apply_int_rope(k8, pos, rope_tab)
    q8 = shard(q8, "batch", "seq", "heads", None)
    k8 = shard(k8, "batch", "seq", "kv_heads", None)
    v8 = shard(v8, "batch", "seq", "kv_heads", None)

    # the configured backend handles attention in every branch: backends
    # without a fused kernel fall back to chunked streaming on long
    # sequences, and fused backends fall back internally on shapes their
    # kernel can't tile (see ops.backends.pallas_fused).  The epilogue
    # travels as a typed RequantSpec, same as the matmul call sites.
    attn_backend = ops.backend_for("int_attention")
    if fuse_attention and attn_backend.fused_attention:
        o8 = ops.int_attention(q8, k8, v8, plans.attn,
                               causal=causal and memory8 is None,
                               window=window,
                               requant=RequantSpec.per_tensor(
                                   plans.attn.dn_out))
    elif s * sk > (4096 * 4096) // 4 and memory8 is None:
        # memory-bounded two-pass streaming path
        rep = cfg.q_group
        k8r = jnp.repeat(k8, rep, 2) if rep > 1 else k8
        v8r = jnp.repeat(v8, rep, 2) if rep > 1 else v8
        o8 = iattn.i_attention_chunked(q8, k8r, v8r, plans.attn,
                                       chunk=min(1024, sk), causal=causal,
                                       window=window)
        o8 = o8.astype(jnp.int8)
    else:
        # fuse_attention=False asks for the exact two-pass numerics, so
        # a fused backend must not be re-entered here — use the oracle
        be = (get_backend("ref") if attn_backend.fused_attention
              else attn_backend)
        o8 = be.int_attention(q8, k8, v8, plans.attn,
                              causal=causal and memory8 is None,
                              window=window)
    o8 = shard(o8, "batch", "seq", "heads", None)
    out32 = int_linear(o8.reshape(b, s, cfg.n_heads * cfg.hd), qp["wo"],
                       plans.out, ops)
    return shard(out32, "batch", "seq", "embed")


def int_attn_decode(qp, x8, cache, pos, plans: qplans.AttnPlan,
                    cfg: ArchConfig, rope_tab=None, window: int = 0,
                    ops=None, pages=None, page_size: int = 0,
                    max_len: int = 0, fold_wo: bool = False,
                    tp_axis: Optional[str] = None, n_new=None):
    """One-token decode.  x8: (B,1,D); cache: {"k8","v8"}.

    ``pos``: (B,) current position (tokens written at logical slot
    ``pos``, or ``pos % window`` for sliding-window caches).  Returns
    (out32, new_cache).

    Cache layouts: contiguous ``(B, L, Hkv, hd)`` by default; with
    ``pages`` (int32 ``(B, max_pages)`` page table) the cache is a
    physical page pool ``(num_pages, page_size, Hkv, hd)`` and the
    logical slot resolves to ``(pages[b, slot // page_size],
    slot % page_size)`` — unmapped lanes write into the reserved null
    page 0, whose contents are never valid (repro.serving.kvcache).
    ``max_len`` bounds the logical occupancy under paging (defaults to
    the page-table span).

    The ragged-cache attention dispatches through the configured
    backend's ``int_decode_attention`` (per-slot ``valid_len`` masking;
    ``pallas_fused`` runs it as one kernel launch skipping dead cache
    blocks, translating paged blocks through the scalar-prefetched
    table) — the backend owns GQA head-repeat, so the KV cache is
    handed over in its compact Hkv form.  With ``fold_wo`` the output
    projection's per-channel requant rides in the decode epilogue
    (``wo=``/``wo_spec=`` operands; bit-exact vs the unfolded path).

    ``tp_axis``: when tracing under a tensor-parallel shard_map (see
    ``repro.distributed.tp_serving``), ``cfg`` carries the *local* head
    counts and the o-projection runs as partial-matmul → exact int32
    psum across ``tp_axis`` → requant-once epilogue
    (:func:`_tp_wo_project`).  Incompatible with ``fold_wo`` — the fold
    would requant each device's partial slab before the all-reduce,
    rounding more than once.

    ``n_new``: the speculative-verify generalization.  When given,
    ``x8`` is (B, S, D) with each lane's real tokens **right-aligned**
    in the S rows — row ``i`` is real iff ``i >= S - n_new[b]`` and
    covers logical position ``pos[b] + n_new[b] - S + i`` (the last row
    always lands on ``pos + n_new - 1``; full causal only, so the
    engine gates speculation to ``window == 0``).  Pad rows write
    nothing (paged: routed to the null page; contiguous: out-of-bounds
    scatter explicitly dropped) and their garbage outputs are discarded
    by the caller.  ``valid_len = pos + n_new`` then gives every row
    ``i`` the stepped-mask visibility ``positions <= pos + n_new - S +
    i`` — exactly the positions a sequential one-token decode of the
    same tokens would see, which is why the verify launch is bit-exact
    against ``n_new`` single-token steps.  Precondition (engine-
    enforced): ``pos + n_new <= L``, so every real write lands in
    bounds and ``valid_len`` never clips a real row's mask limit.
    """
    ops = resolve_ops(ops, cfg)
    if tp_axis is not None and fold_wo:
        raise ValueError("fold_wo cannot cross the tensor-parallel "
                         "all-reduce: the wo requant must round once, "
                         "after psum (pass fold_wo=False under tp)")
    b, s, d = x8.shape
    paged = pages is not None
    packed_kv = "k_shift" in cache
    if packed_kv and not paged:
        raise ValueError("int4 KV pages (k_shift/v_shift in the cache) "
                         "need the paged layout")
    if paged:
        L = max_len or pages.shape[1] * page_size
    else:
        L = cache["k8"].shape[1]
    q8 = int_linear(x8, qp["wq"], plans.qkv, ops) \
        .reshape(b, s, cfg.n_heads, cfg.hd)
    k8 = int_linear(x8, qp["wk"], plans.qkv, ops) \
        .reshape(b, s, cfg.n_kv_heads, cfg.hd)
    v8 = int_linear(x8, qp["wv"], plans.qkv, ops) \
        .reshape(b, s, cfg.n_kv_heads, cfg.hd)
    if n_new is None:
        if rope_tab is not None:
            q8 = apply_int_rope(q8, pos[:, None], rope_tab)
            k8 = apply_int_rope(k8, pos[:, None], rope_tab)
        if window > 0:
            slot = pos % window
        else:
            slot = pos
        if paged:
            pages = jnp.asarray(pages, jnp.int32)
            bidx = jnp.arange(b)
            page = pages[bidx, slot // page_size]
            off = slot % page_size
            k_w, v_w = k8[:, 0], v8[:, 0]
            if packed_kv:
                # quantize + nibble-pack before the write: pool bytes
                # always hold the packed representation (one
                # quantization policy — repro.ops.packed.pack_kv)
                k_w, v_w = pack_kv(k_w), pack_kv(v_w)
            k_cache = cache["k8"].at[page, off].set(k_w)
            v_cache = cache["v8"].at[page, off].set(v_w)
        else:
            bidx = jnp.arange(b)
            k_cache = cache["k8"].at[bidx, slot].set(k8[:, 0])
            v_cache = cache["v8"].at[bidx, slot].set(v8[:, 0])
        valid = jnp.minimum(pos + 1, L) if (window > 0 or paged) \
            else pos + 1
    else:
        assert window == 0, \
            "speculative verify needs full causal attention"
        n_new = jnp.asarray(n_new, jnp.int32)
        rows = jnp.arange(s, dtype=jnp.int32)[None, :]       # (1, S)
        rpos = pos[:, None] + n_new[:, None] - s + rows      # (B, S)
        write_ok = rows >= s - n_new[:, None]
        # pad-row positions clamp to 0 (their rope rotation and writes
        # are masked/discarded; a negative gather index would clamp to
        # the table's LAST entry and silently alias a live position)
        rpos_c = jnp.maximum(rpos, 0)
        if rope_tab is not None:
            q8 = apply_int_rope(q8, rpos_c, rope_tab)
            k8 = apply_int_rope(k8, rpos_c, rope_tab)
        if paged:
            pages = jnp.asarray(pages, jnp.int32)
            bidx = jnp.arange(b)[:, None]
            page = pages[bidx, rpos_c // page_size]          # (B, S)
            # pad rows write into the reserved null page 0, whose
            # contents are never valid (repro.serving.kvcache)
            page = jnp.where(write_ok, page, 0)
            off = rpos_c % page_size
            k_w, v_w = k8, v8
            if packed_kv:
                k_w, v_w = pack_kv(k_w), pack_kv(v_w)
            k_cache = cache["k8"].at[page, off].set(k_w)
            v_cache = cache["v8"].at[page, off].set(v_w)
        else:
            bidx = jnp.arange(b)[:, None]
            # pad rows scatter out of bounds and are explicitly
            # dropped (scatter OOB is unspecified without a mode)
            slot_w = jnp.where(write_ok, rpos_c, L)
            k_cache = cache["k8"].at[bidx, slot_w].set(k8, mode="drop")
            v_cache = cache["v8"].at[bidx, slot_w].set(v8, mode="drop")
        valid = pos + n_new
    kw = {}
    if paged:
        kw.update(pages=pages, page_size=page_size)
    if packed_kv:
        kw.update(kv_shifts=(cache["k_shift"], cache["v_shift"]))
    if fold_wo:
        out32 = ops.int_decode_attention(
            q8, k_cache, v_cache, plans.attn, valid,
            requant=RequantSpec.per_tensor(plans.attn.dn_out),
            wo=QuantLinearParams.of(qp["wo"]),
            wo_spec=RequantSpec.for_linear(plans.out), **kw)
    else:
        o8 = ops.int_decode_attention(
            q8, k_cache, v_cache, plans.attn, valid,
            requant=RequantSpec.per_tensor(plans.attn.dn_out), **kw)
        o8 = o8.astype(jnp.int8).reshape(b, s, cfg.n_heads * cfg.hd)
        if tp_axis is not None:
            out32 = _tp_wo_project(o8, qp["wo"], plans.out, tp_axis, ops)
        else:
            out32 = int_linear(o8, qp["wo"], plans.out, ops)
    return out32, {"k8": k_cache, "v8": v_cache}


def int_attn_prefill_chunk(qp, x8, cache, base_pos, plans: qplans.AttnPlan,
                           cfg: ArchConfig, rope_tab=None, ops=None,
                           pages=None, page_size: int = 0,
                           fold_wo: bool = False,
                           tp_axis: Optional[str] = None):
    """Chunked prefill attention over a *paged* KV cache.

    x8: (B, C, D) — one prompt chunk per lane, covering that lane's
    logical positions ``[base_pos[b], base_pos[b] + C)``; cache:
    ``{"k8", "v8"}`` physical page pools ``(num_pages, page_size, Hkv,
    hd)``; ``pages``: int32 (B, max_pages) page table.  The op writes
    the chunk's K/V through the table and runs causal attention over
    history + chunk (``ops.int_paged_prefill`` — one fused kernel launch
    on ``pallas_fused``, exact scatter/gather lowering elsewhere).
    Returns (out32 (B, C, D) at s_res, new_cache).

    Full (non-windowed) causal attention only — the rolling
    sliding-window buffer interleaves writes and reads token-by-token,
    which a batched chunk write cannot reproduce (the serving engine
    keeps token streaming for ``cfg.window > 0``).  Bit-exact against
    streaming the same tokens through :func:`int_attn_decode` one at a
    time.  With ``fold_wo`` the o-projection's per-channel requant rides
    in the prefill launch's epilogue (``prefill_wo_fold``).

    ``tp_axis``: tensor-parallel tracing, exactly as in
    :func:`int_attn_decode` (local-head ``cfg``, partial o-projection,
    exact psum, requant-once; ``fold_wo`` must be off).
    """
    assert cfg.window == 0, "chunked prefill needs full causal attention"
    ops = resolve_ops(ops, cfg)
    if tp_axis is not None and fold_wo:
        raise ValueError("fold_wo cannot cross the tensor-parallel "
                         "all-reduce: the wo requant must round once, "
                         "after psum (pass fold_wo=False under tp)")
    b, c, d = x8.shape
    q8 = int_linear(x8, qp["wq"], plans.qkv, ops) \
        .reshape(b, c, cfg.n_heads, cfg.hd)
    k8 = int_linear(x8, qp["wk"], plans.qkv, ops) \
        .reshape(b, c, cfg.n_kv_heads, cfg.hd)
    v8 = int_linear(x8, qp["wv"], plans.qkv, ops) \
        .reshape(b, c, cfg.n_kv_heads, cfg.hd)
    if rope_tab is not None:
        positions = base_pos[:, None] + jnp.arange(c, dtype=jnp.int32)
        q8 = apply_int_rope(q8, positions, rope_tab)
        k8 = apply_int_rope(k8, positions, rope_tab)
    requant = RequantSpec.per_tensor(plans.attn.dn_out)
    kw = {}
    if "k_shift" in cache:
        # int4 KV pools: the dispatch layer quantizes + packs the
        # chunk's K/V before the scatter (one policy for every backend)
        kw.update(kv_shifts=(cache["k_shift"], cache["v_shift"]))
    if fold_wo:
        out32, k_pool, v_pool = ops.int_paged_prefill(
            q8, k8, v8, cache["k8"], cache["v8"], plans.attn, base_pos,
            pages, page_size, requant=requant,
            wo=QuantLinearParams.of(qp["wo"]),
            wo_spec=RequantSpec.for_linear(plans.out), **kw)
    else:
        o8, k_pool, v_pool = ops.int_paged_prefill(
            q8, k8, v8, cache["k8"], cache["v8"], plans.attn, base_pos,
            pages, page_size, requant=requant, **kw)
        o8 = o8.astype(jnp.int8).reshape(b, c, cfg.n_heads * cfg.hd)
        if tp_axis is not None:
            out32 = _tp_wo_project(o8, qp["wo"], plans.out, tp_axis, ops)
        else:
            out32 = int_linear(o8, qp["wo"], plans.out, ops)
    return out32, {"k8": k_pool, "v8": v_pool}


# --------------------------------------------------------------- ffn ------

def int_ffn_fwd(qp, x8, plans: qplans.FfnPlan, cfg: ArchConfig,
                ops=None):
    """x8 (B,S,D) int8 -> int32 at s_res."""
    ops = resolve_ops(ops, cfg)
    swiglu = cfg.activation == "swiglu"
    with trace_names.scope("ffn.up"):
        h1 = int_linear(x8, qp["w1"], plans.up, ops)        # 10-bit int32
        if swiglu:
            h3 = int_linear(x8, qp["w3"], plans.up, ops)
    with trace_names.scope("ffn.act"):
        if swiglu:
            a8 = iact.i_silu(h1, plans.act_silu, out_bits=8)
            prod = a8 * h3                                  # s8 * s10
            h = clip_to_bits(plans.dn_gate(prod), 8).astype(jnp.int8)
        else:
            h = ops.int_gelu(h1, plans.act_gelu.gelu,
                             plans.act_gelu.dn_out, out_bits=8)
    with trace_names.scope("ffn.down"):
        h = shard(h, "batch", "seq", "ffn")
        return shard(int_linear(h, qp["w2"], plans.down, ops),
                     "batch", "seq", "embed")


# --------------------------------------------------------------- moe ------

def int_moe_fwd(qp, x8, plans: qplans.MoePlan, cfg: ArchConfig,
                ops=None, group_size: int = 512):
    """Integer MoE: int32 router logits, integer top-k gates (i-softmax
    over the selected k logits), int8 expert FFNs, integer combine."""
    ops = resolve_ops(ops, cfg)
    b, s, d = x8.shape
    e = cfg.padded_experts()
    k = cfg.top_k
    g = max(1, s // group_size)
    tg = s // g
    cap = max(4, int(cfg.capacity_factor * tg * k / e))
    xg = x8.reshape(b * g, tg, d)

    logits = int_linear(xg, qp["router"], plans.router, ops)      # int32
    if e != cfg.n_experts:
        padmask = jnp.arange(e) >= cfg.n_experts
        logits = jnp.where(padmask[None, None], jnp.int32(-(2 ** 30)),
                           logits)
    top_logits, expert_ids = jax.lax.top_k(logits, k)       # (g,t,k)
    gates8 = ism.i_softmax(top_logits, plans.gate_sm, axis=-1)  # 2^-7 int8

    dispatch = jnp.zeros((b * g, tg, e, cap), jnp.int8)
    counts = jnp.zeros((b * g, e), jnp.int32)
    slot_oh = []
    for slot in range(k):
        a = jax.nn.one_hot(expert_ids[..., slot], e, dtype=jnp.int32)
        pos = counts[:, None, :] + jnp.cumsum(a, axis=1) - a
        keep = (pos < cap) & (a > 0)
        oh = (jax.nn.one_hot(pos, cap, dtype=jnp.int32)
              * keep[..., None]).astype(jnp.int8)           # (g,t,e,cap)
        slot_oh.append(oh)
        dispatch = dispatch + oh
        counts = counts + jnp.sum(a, axis=1)

    buf = jnp.einsum("gtd,gtec->gecd", xg, dispatch,
                     preferred_element_type=jnp.int32).astype(jnp.int8)
    buf = shard(buf, "batch", "experts", None, "embed")
    h1 = int_expert_linear(buf, qp["w1"], plans.expert.up)
    if cfg.activation == "swiglu":
        h3 = int_expert_linear(buf, qp["w3"], plans.expert.up)
        a8 = iact.i_silu(h1, plans.expert.act_silu, out_bits=8)
        h = clip_to_bits(plans.expert.dn_gate(a8 * h3), 8).astype(jnp.int8)
    else:
        h = ops.int_gelu(h1, plans.expert.act_gelu.gelu,
                         plans.expert.act_gelu.dn_out, out_bits=8)
    y8 = int_expert_linear(h, qp["w2"], plans.expert.down)   # s_res int32
    y8 = shard(y8, "batch", "experts", None, "embed")

    out32 = jnp.zeros((b * g, tg, d), jnp.int32)
    for slot in range(k):
        y_slot = jnp.einsum("gecd,gtec->gtd", y8, slot_oh[slot],
                            preferred_element_type=jnp.int32)
        gate = gates8[..., slot].astype(jnp.int32)[..., None]
        out32 = out32 + rshift_round(y_slot * gate, ism.PROB_SHIFT)
    out32 = out32.reshape(b, s, d)
    if plans.shared is not None:
        out32 = out32 + int_ffn_fwd(qp["shared"], x8, plans.shared, cfg,
                                    ops)
    return shard(out32, "batch", "seq", "embed")


# -------------------------------------------------------------- mamba -----

class IntMambaState(NamedTuple):
    h: jnp.ndarray        # (B, H, N, P) int32 at s_h
    conv: jnp.ndarray     # (B, K-1, C) int8


def init_int_mamba_state(cfg: ArchConfig, batch: int) -> IntMambaState:
    h = jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim),
                  jnp.int32)
    conv_ch = cfg.ssm_d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
    conv = jnp.zeros((batch, cfg.ssm_conv - 1, conv_ch), jnp.int8)
    return IntMambaState(h, conv)


def _int_conv_step(xbc8_t, conv_state, qconv_w8, mp: qplans.MambaPlan):
    """Depthwise causal conv, one step.  xbc8_t: (B,C) int8."""
    window = jnp.concatenate([conv_state, xbc8_t[:, None, :]], axis=1)
    acc = jnp.sum(window.astype(jnp.int32)
                  * qconv_w8.astype(jnp.int32)[None], axis=1)
    new_state = window[:, 1:]
    h10 = clip_to_bits(mp.dn_conv(acc), 11)
    out8 = iact.i_silu(h10, mp.silu_conv, out_bits=8).astype(jnp.int8)
    return out8, new_state


def int_mamba_step(qp, u8_t, state: IntMambaState, mp: qplans.MambaPlan,
                   cfg: ArchConfig, ops=None):
    """One token.  u8_t: (B, D) int8 -> (out32 (B,D) at s_res, new state)."""
    ops = resolve_ops(ops, cfg)
    b = u8_t.shape[0]
    di, gq, n, hh, p = (cfg.ssm_d_inner, cfg.ssm_groups, cfg.ssm_state,
                        cfg.ssm_heads, cfg.ssm_head_dim)
    zxbc8 = int_linear(u8_t, qp["in_proj"], mp.in_proj, ops)
    dt_acc = int_linear(u8_t, qp["dt_proj"], _INT32_PLAN(mp), ops)
    z8, xbc8 = zxbc8[:, :di], zxbc8[:, di:]
    xbc8, conv_new = _int_conv_step(xbc8, state.conv, qp["conv_w8"], mp)
    x8 = xbc8[:, :di].reshape(b, hh, p)
    B8 = xbc8[:, di:di + gq * n].reshape(b, gq, n)
    C8 = xbc8[:, di + gq * n:].reshape(b, gq, n)

    dt_in = clip_to_bits(mp.dn_dt_in(dt_acc + qp["dt_bias_q"][None]), 11)
    dt = iact.i_softplus(dt_in, mp.softplus, out_bits=13)    # s_dt, (B,H)
    dtA = mp.dn_dtA(dt * qp["A_q"][None])                    # -> 2^-14
    decay16 = mp.dn_decay16(intmath.i_exp(-dtA, mp.iexp_decay))
    decay16 = jnp.clip(decay16, 0, 1 << 15)                  # (B,H)

    rep = hh // gq
    B8h = jnp.repeat(B8, rep, axis=1)                        # (B,H,N)
    # contribution: dt * B * x  (s_dt*s8*s8) -> s_h
    contrib = (dt[:, :, None, None] *
               (B8h[:, :, :, None].astype(jnp.int32)
                * x8[:, :, None, :].astype(jnp.int32)))
    contrib = mp.dn_h(contrib)
    h = state.h
    h = ism.rescale_sum(h, decay16[:, :, None, None]) + contrib
    h = jnp.clip(h, -mp.qmax_h, mp.qmax_h)

    # dynamic block-floating-point h -> int8 (one exponent per batch row,
    # shared across heads so the downstream RMSNorm shift cancels exactly)
    h_max = jnp.max(jnp.abs(h), axis=(1, 2, 3), keepdims=True)
    sd = jnp.maximum(intmath.int_bit_length(h_max) - 7, 0)    # (B,1,1,1)
    half_h = jnp.where(sd > 0, jnp.left_shift(
        jnp.int32(1), jnp.maximum(sd - 1, 0)), 0)
    h8 = jnp.clip(jax.lax.shift_right_arithmetic(h + half_h, sd),
                  -127, 127)                                   # (B,H,N,P)
    C8h = jnp.repeat(C8, rep, axis=1)                          # (B,H,N)
    y_acc = jnp.einsum("bhn,bhnp->bhp", C8h.astype(jnp.int32),
                       h8.astype(jnp.int32))
    # D*x on the same (shifted) h grid: D_q at 2^-16, >> sd
    d_term = jax.lax.shift_right_arithmetic(
        qp["D_q"][None, :, None] * x8.astype(jnp.int32), sd[:, :, 0])
    y_acc = y_acc + d_term
    y32 = y_acc.reshape(b, di)                # unnormalised, wide range

    z10 = mp.dn_z10(z8.astype(jnp.int32))
    sig16 = _silu16(z10, mp.silu_z)
    gated = ism.rescale_sum(y32, sig16)       # y * sigmoid(z), int32
    # per-row dynamic block-floating-point shift into the RMSNorm: the
    # norm is scale-invariant so the shift cancels exactly, and the
    # 12-bit mantissa satisfies the i-norm bit budget.
    row_max = jnp.max(jnp.abs(gated), axis=-1, keepdims=True)
    s_dyn = jnp.maximum(intmath.int_bit_length(row_max) - 11, 0)
    half = jnp.where(s_dyn > 0,
                     jnp.left_shift(jnp.int32(1),
                                    jnp.maximum(s_dyn - 1, 0)), 0)
    y12 = jax.lax.shift_right_arithmetic(gated + half, s_dyn)
    y8 = int_norm({"gamma_q": qp["norm_gamma_q"]}, y12, mp.norm,
                  ops).astype(jnp.int8)
    out32 = int_linear(y8, qp["out_proj"], mp.out_proj, ops)
    return out32, IntMambaState(h, conv_new)


def _silu16(zq, plan: iact.ISiluPlan):
    """sigmoid(z) as a 2^-15 fraction (int32), z int32 at plan.s_in."""
    q = zq.astype(jnp.int32)
    e = intmath.i_exp(-jnp.abs(q), plan.iexp)
    e16 = jnp.clip(plan.dn_e16(e), 0, 1 << 15)
    one16 = jnp.int32(1 << 15)
    den = one16 + e16
    r = jnp.int32(1 << 30) // den
    num = jnp.where(q >= 0, one16, e16)
    return (num * r) >> 15


def int_mamba_prefill(qp, u8, mp: qplans.MambaPlan, cfg: ArchConfig,
                      state: Optional[IntMambaState] = None, ops=None):
    """Integer prefill with the token-parallel stages hoisted out of the
    recurrence: projections / conv / Δt / decays / contributions batch over
    the whole sequence (MXU-shaped, HLO-countable); only the O(L) h-state
    update and the per-token read-out stay in the scan (cheap elementwise).
    """
    ops = resolve_ops(ops, cfg)
    b, l, d = u8.shape
    di, gq, n, hh, p = (cfg.ssm_d_inner, cfg.ssm_groups, cfg.ssm_state,
                        cfg.ssm_heads, cfg.ssm_head_dim)
    if state is None:
        state = init_int_mamba_state(cfg, b)

    # --- token-parallel stages -------------------------------------------
    zxbc8 = int_linear(u8, qp["in_proj"], mp.in_proj, ops)       # (B,L,*)
    dt_acc = int_linear(u8, qp["dt_proj"], _INT32_PLAN(mp), ops)
    z8, xbc8 = zxbc8[..., :di], zxbc8[..., di:]
    # causal depthwise conv over the sequence, seeded by the carried tail
    km1 = state.conv.shape[1]
    full = jnp.concatenate([state.conv, xbc8], axis=1)
    w = qp["conv_w8"].astype(jnp.int32)
    acc = sum(full[:, i:i + l].astype(jnp.int32) * w[i]
              for i in range(km1 + 1))
    conv_tail = full[:, -km1:]
    h10 = clip_to_bits(mp.dn_conv(acc), 11)
    xbc8a = iact.i_silu(h10, mp.silu_conv, out_bits=8).astype(jnp.int8)
    x8 = xbc8a[..., :di].reshape(b, l, hh, p)
    B8 = xbc8a[..., di:di + gq * n].reshape(b, l, gq, n)
    C8 = xbc8a[..., di + gq * n:].reshape(b, l, gq, n)

    dt_in = clip_to_bits(mp.dn_dt_in(dt_acc + qp["dt_bias_q"][None, None]),
                         11)
    dt = iact.i_softplus(dt_in, mp.softplus, out_bits=13)        # (B,L,H)
    dtA = mp.dn_dtA(dt * qp["A_q"][None, None])
    decay16 = jnp.clip(mp.dn_decay16(intmath.i_exp(-dtA, mp.iexp_decay)),
                       0, 1 << 15)                               # (B,L,H)
    rep = hh // gq
    B8h = jnp.repeat(B8, rep, axis=2)                            # (B,L,H,N)
    contrib = mp.dn_h(dt[..., None, None] *
                      (B8h[..., :, None].astype(jnp.int32)
                       * x8[..., None, :].astype(jnp.int32)))    # (B,L,H,N,P)
    C8h = jnp.repeat(C8, rep, axis=2)

    # --- sequential state recurrence + read-out --------------------------
    def step(h, xs):
        dec_t, con_t, c_t, x_t = xs
        h = ism.rescale_sum(h, dec_t[:, :, None, None]) + con_t
        h = jnp.clip(h, -mp.qmax_h, mp.qmax_h)
        h_max = jnp.max(jnp.abs(h), axis=(1, 2, 3), keepdims=True)
        sd = jnp.maximum(intmath.int_bit_length(h_max) - 7, 0)
        half = jnp.where(sd > 0, jnp.left_shift(
            jnp.int32(1), jnp.maximum(sd - 1, 0)), 0)
        h8 = jnp.clip(jax.lax.shift_right_arithmetic(h + half, sd),
                      -127, 127)
        y = jnp.einsum("bhn,bhnp->bhp", c_t.astype(jnp.int32),
                       h8.astype(jnp.int32))
        y = y + jax.lax.shift_right_arithmetic(
            qp["D_q"][None, :, None] * x_t.astype(jnp.int32), sd[:, :, 0])
        return h, y

    xs = (decay16.transpose(1, 0, 2), contrib.transpose(1, 0, 2, 3, 4),
          C8h.transpose(1, 0, 2, 3), x8.transpose(1, 0, 2, 3))
    h, ys = jax.lax.scan(step, state.h, xs)
    y32 = ys.transpose(1, 0, 2, 3).reshape(b, l, di)

    # --- gate + BFP norm + out-projection (token-parallel) ---------------
    z10 = mp.dn_z10(z8.astype(jnp.int32))
    sig16 = _silu16(z10, mp.silu_z)
    gated = ism.rescale_sum(y32, sig16)
    row_max = jnp.max(jnp.abs(gated), axis=-1, keepdims=True)
    s_dyn = jnp.maximum(intmath.int_bit_length(row_max) - 11, 0)
    half = jnp.where(s_dyn > 0, jnp.left_shift(
        jnp.int32(1), jnp.maximum(s_dyn - 1, 0)), 0)
    y12 = jax.lax.shift_right_arithmetic(gated + half, s_dyn)
    y8 = int_norm({"gamma_q": qp["norm_gamma_q"]}, y12, mp.norm,
                  ops).astype(jnp.int8)
    out32 = int_linear(y8, qp["out_proj"], mp.out_proj, ops)
    return out32, IntMambaState(h, conv_tail)


class _INT32_PLAN:
    """dt projection keeps the raw int32 accumulator (requant happens after
    the dt_bias add)."""
    def __new__(cls, mp):
        return qplans.LinearPlan(mp.in_proj.s_in, 0.0, 32, 0, 0,
                                 mp.in_proj.k_dim)
