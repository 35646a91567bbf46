"""Integer-path model assembly (prefill + decode) for every family.

The serving datapath of the framework: everything from embedding lookup to
the last requant is SwiftTron integer arithmetic; only the final logits are
dequantized (host-side sampling boundary).

Caches:
  attention  — int8 KV at s_act8; sliding-window archs keep a rolling
               ``window``-sized buffer (slot = pos % window)
  mamba      — int32 SSD state + int8 conv tail
  cross      — int8 K/V of the encoder/image memory, computed at prefill
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro import trace_names
from repro.distributed.sharding import shard
from repro.models import intlayers as il
from repro.models.common import ArchConfig
from repro.models.transformer import layer_group_spec
from repro.ops import resolve_ops
from repro.quant import plans as qplans

Pytree = Any


def _residual_add(x32, delta32, cfg: ArchConfig):
    with trace_names.scope("residual"):
        return jnp.clip(x32 + delta32, -cfg.qmax_res, cfg.qmax_res)


def _norm(qp, key: str, x32, plan, ops):
    """The integer norm of parameters ``qp[key]``, scoped by ``key``."""
    with trace_names.scope(key):
        return il.int_norm(qp[key], x32, plan, ops)


def _sub_plans(plans: qplans.LayerPlans, kind):
    mix, ff, has_cross = kind
    return plans


def _int_sublayer_fwd(qp, x32, plans: qplans.LayerPlans, cfg: ArchConfig,
                      kind, rope_tab, positions, causal, memory8, ops):
    """Pre-norm integer sublayer.  x32: (B,S,D) int32 at s_res."""
    mix, ff, has_cross = kind
    h8 = _norm(qp, "norm1", x32, plans.norm, ops)
    if mix == "attn":
        with trace_names.scope("attn"):
            a32 = il.int_attn_fwd(qp["attn"], h8, plans.attn, cfg,
                                  rope_tab, positions, causal=causal,
                                  window=cfg.window, ops=ops)
    elif mix == "cross":
        with trace_names.scope("cross_attn"):
            a32 = il.int_attn_fwd(qp["attn"], h8, plans.cross, cfg, None,
                                  positions, causal=False, memory8=memory8,
                                  ops=ops)
    else:
        with trace_names.scope("ssm"):
            a32, _ = il.int_mamba_prefill(qp["ssm"], h8, plans.mamba, cfg,
                                          ops=ops)
    x32 = _residual_add(x32, a32, cfg)
    if has_cross:
        h8 = _norm(qp, "norm_cross", x32, plans.norm, ops)
        with trace_names.scope("cross_attn"):
            c32 = il.int_attn_fwd(qp["cross"], h8, plans.cross, cfg, None,
                                  positions, causal=False, memory8=memory8,
                                  ops=ops)
        x32 = _residual_add(x32, c32, cfg)
    if ff is not None:
        h8 = _norm(qp, "norm2", x32, plans.norm, ops)
        f32 = _int_ff(qp, h8, plans, cfg, ff, ops)
        x32 = _residual_add(x32, f32, cfg)
    return x32


def _int_ff(qp, h8, plans: qplans.LayerPlans, cfg: ArchConfig, ff, ops,
            **moe_kw):
    """The feed-forward sublayer: an MoE (scoped ``moe``) or a dense FFN
    (scoped ``ffn.*`` inside ``int_ffn_fwd``)."""
    if ff == "moe":
        with trace_names.scope("moe"):
            return il.int_moe_fwd(qp["moe"], h8, plans.moe, cfg, ops,
                                  **moe_kw)
    return il.int_ffn_fwd(qp["ffn"], h8, plans.ffn, cfg, ops)


def embed_int(qparams, tokens, plans: qplans.LayerPlans, cfg: ArchConfig):
    with trace_names.scope("embed"):
        e8 = jnp.take(qparams["embed_w8"], tokens, axis=0).astype(jnp.int32)
        x32 = plans.embed.dn_res(e8)
        return shard(x32, "batch", "seq", "embed")


def quantize_memory(mem_f, cfg: ArchConfig):
    """Float boundary for stubbed frontends: img/audio embeddings -> int8."""
    q = jnp.round(mem_f.astype(jnp.float32) / cfg.s_act8)
    return jnp.clip(q, -127, 127).astype(jnp.int8)


def logits_int(qparams, x32, plans: qplans.LayerPlans, cfg: ArchConfig,
               ops=None):
    ops = resolve_ops(ops, cfg)
    h8 = _norm(qparams, "final_norm", x32, plans.final_norm, ops)
    head_plan = qplans.LinearPlan(cfg.s_act8, 0.0, 32, 0, 0, cfg.d_model)
    with trace_names.scope("head"):
        acc = il.int_linear(h8, qparams["head"], head_plan, ops)
        # host-side dequant boundary: float per-channel scales
        return acc.astype(jnp.float32) * qparams["head_scale"][None] \
            * plans.head.s_in


@trace_names.entry_point
def int_prefill(qparams, batch, plans: qplans.LayerPlans, cfg: ArchConfig,
                ops=None, return_cache=False, cache_len: int = 0,
                rope_tab=None):
    """Full-sequence integer forward; returns last-position float logits
    (+ decode caches when ``return_cache``).

    ``ops``: an ``repro.ops.OpSet`` (or backend name) resolved once here
    and handed down — per-call backend strings are gone.
    ``rope_tab``: int32 (cos, sin) design tables passed as *arguments* so
    they are inputs, not multi-MB HLO constants."""
    ops = resolve_ops(ops, cfg)
    gl, ng, kinds = layer_group_spec(cfg)
    tokens = batch["tokens"]
    b, s = tokens.shape
    memory8 = None
    if cfg.family == "encdec":
        memory8 = _int_encoder(qparams, batch["src_embeds"], plans, cfg,
                               ops)
    elif cfg.family == "vlm":
        memory8 = quantize_memory(batch["img_embeds"], cfg)
    if rope_tab is None and cfg.pos == "rope":
        rope_tab = il.build_rope_table(max(s, cache_len) + 1, cfg.hd,
                                       cfg.rope_theta)
    positions = jnp.arange(s)
    x32 = embed_int(qparams, tokens, plans, cfg)

    def body(x32, qp_group):
        for j, kind in enumerate(kinds):
            x32 = _int_sublayer_fwd(qp_group[j], x32, plans, cfg, kind,
                                    rope_tab, positions, cfg.is_causal,
                                    memory8, ops)
        return x32, None

    x32, _ = jax.lax.scan(body, x32, tuple(qparams["layers"]))
    last = x32[:, -1:, :]
    logits = logits_int(qparams, last, plans, cfg, ops)[:, 0]
    if not return_cache:
        return logits
    cache = build_cache_from_prefill(qparams, batch, plans, cfg, ops,
                                     cache_len or s)
    return logits, cache


def _int_encoder(qparams, src_embeds, plans, cfg: ArchConfig, ops):
    mem8 = quantize_memory(src_embeds, cfg)
    # boundary embeddings are on the s_act8 grid -> bring to the residual bus
    dn = qplans.fit_dyadic(cfg.s_act8 / cfg.s_res, 127)
    x32 = dn(mem8.astype(jnp.int32))
    positions = jnp.arange(mem8.shape[1])

    def body(x32, qp):
        x32 = _int_sublayer_fwd(qp, x32, plans, cfg,
                                ("attn", "ffn", False), None, positions,
                                False, None, ops)
        return x32, None

    enc = qparams["enc_layers"]
    x32, _ = jax.lax.scan(body, x32, enc[0] if isinstance(enc, list)
                          else enc)
    return _norm(qparams, "enc_final_norm", x32, plans.norm, ops)


# ============================================================ decode =======

def init_decode_cache(cfg: ArchConfig, batch: int, cache_len: int,
                      memory8=None, qparams=None, plans=None,
                      ops=None, layout=None):
    """Per-sublayer-position stacked caches (scan-compatible).

    ``layout``: an optional ``repro.serving.kvcache.CacheLayout`` — the
    attention K/V become physical *page pools* ``(ng, num_pages,
    page_size, Hkv, hd)`` addressed through a page table instead of
    per-lane contiguous buffers; every other cache kind (Mamba state,
    cross-attention memory) stays lane-indexed.  Pool memory is
    ``num_pages × page_size`` tokens per sublayer — O(provisioned
    pages), not O(batch × cache_len).  With ``layout.kv_dtype ==
    "int4"`` the pools pack two head-dim nibbles per byte (last dim
    ``hd // 2``) and carry per-page requant shift arrays ``k_shift`` /
    ``v_shift`` ``(ng, num_pages)`` int32 (``repro.ops.packed.KV_SHIFT``
    everywhere — the static shift the write-side quantizer uses)."""
    ops = resolve_ops(ops, cfg)
    gl, ng, kinds = layer_group_spec(cfg)
    L = min(cache_len, cfg.window) if cfg.window > 0 else cache_len
    kv_packed = layout is not None and layout.kv_dtype == "int4"
    if kv_packed and cfg.hd % 2:
        raise ValueError("int4 KV pages pair head-dim nibbles: hd must "
                         f"be even, got {cfg.hd}")
    caches = []
    for j, (mix, ff, has_cross) in enumerate(kinds):
        c: Dict[str, Any] = {}
        if mix == "attn":
            if layout is None:
                kv_shape = (ng, batch, L, cfg.n_kv_heads, cfg.hd)
            else:
                hd = cfg.hd // 2 if kv_packed else cfg.hd
                kv_shape = (ng, layout.num_pages, layout.page_size,
                            cfg.n_kv_heads, hd)
            c["k8"] = jnp.zeros(kv_shape, jnp.int8)
            c["v8"] = jnp.zeros_like(c["k8"])
            if kv_packed:
                from repro.ops.packed import KV_SHIFT
                c["k_shift"] = jnp.full((ng, layout.num_pages),
                                        KV_SHIFT, jnp.int32)
                c["v_shift"] = jnp.full_like(c["k_shift"], KV_SHIFT)
        elif mix == "ssm":
            st = il.init_int_mamba_state(cfg, batch)
            c["h"] = jnp.broadcast_to(st.h, (ng,) + st.h.shape)
            c["conv"] = jnp.broadcast_to(st.conv, (ng,) + st.conv.shape)
        if (mix == "cross" or has_cross) and memory8 is not None:
            # precompute cross K/V once per sublayer position
            kv = []
            for g in range(ng):
                qp = jax.tree.map(lambda t: t[g], qparams["layers"][j])
                src = qp["cross"] if has_cross else qp["attn"]
                sk = memory8.shape[1]
                k8 = il.int_linear(memory8, src["wk"],
                                   plans.cross.qkv, ops)
                v8 = il.int_linear(memory8, src["wv"],
                                   plans.cross.qkv, ops)
                kv.append((k8.reshape(batch, sk, cfg.n_kv_heads, cfg.hd),
                           v8.reshape(batch, sk, cfg.n_kv_heads, cfg.hd)))
            c["ck8"] = jnp.stack([a for a, _ in kv])
            c["cv8"] = jnp.stack([b for _, b in kv])
        caches.append(c)
    return caches


def _int_sublayer_decode(qp, cache, x32, plans, cfg: ArchConfig, kind,
                         rope_tab, pos, ops, pages=None,
                         page_size: int = 0, max_len: int = 0,
                         fold_wo: bool = False, tp_axis=None):
    mix, ff, has_cross = kind
    new_cache = dict(cache)
    h8 = _norm(qp, "norm1", x32, plans.norm, ops)
    if mix == "attn":
        with trace_names.scope("attn"):
            a32, kv = il.int_attn_decode(qp["attn"], h8, cache, pos,
                                         plans.attn, cfg, rope_tab,
                                         window=cfg.window, ops=ops,
                                         pages=pages, page_size=page_size,
                                         max_len=max_len, fold_wo=fold_wo,
                                         tp_axis=tp_axis)
        new_cache.update(kv)
    elif mix == "cross":
        with trace_names.scope("cross_attn"):
            a32 = _cross_decode(qp["attn"], h8, cache, plans, cfg, pos,
                                ops)
    else:
        with trace_names.scope("ssm"):
            st = il.IntMambaState(cache["h"], cache["conv"])
            a32_t, st = il.int_mamba_step(qp["ssm"], h8[:, 0], st,
                                          plans.mamba, cfg, ops)
            a32 = a32_t[:, None]
        new_cache.update({"h": st.h, "conv": st.conv})
    x32 = _residual_add(x32, a32, cfg)
    if has_cross:
        h8 = _norm(qp, "norm_cross", x32, plans.norm, ops)
        with trace_names.scope("cross_attn"):
            c32 = _cross_decode(qp["cross"], h8, cache, plans, cfg, pos,
                                ops)
        x32 = _residual_add(x32, c32, cfg)
    if ff is not None:
        h8 = _norm(qp, "norm2", x32, plans.norm, ops)
        f32 = _int_ff(qp, h8, plans, cfg, ff, ops, group_size=1)
        x32 = _residual_add(x32, f32, cfg)
    return x32, new_cache


def _cross_decode(qp, h8, cache, plans, cfg, pos, ops):
    # cross memory is fully valid at decode time: decode attention with
    # valid_len pinned to the full memory length — through the configured
    # backend's fused decode path (one kernel launch on pallas_fused;
    # GQA head-repeat is the backend's job).  Bit-identical to plain
    # non-causal attention over the same K/V.
    b = h8.shape[0]
    sk = cache["ck8"].shape[1]
    q8 = il.int_linear(h8, qp["wq"], plans.cross.qkv, ops) \
        .reshape(b, 1, cfg.n_heads, cfg.hd)
    valid = jnp.full((b,), sk, jnp.int32)
    o8 = ops.int_decode_attention(q8, cache["ck8"], cache["cv8"],
                                  plans.cross.attn, valid)
    return il.int_linear(o8.astype(jnp.int8).reshape(b, 1, -1), qp["wo"],
                         plans.cross.out, ops)


@trace_names.entry_point
def int_decode_step(qparams, caches, tokens, pos, plans, cfg: ArchConfig,
                    rope_tab=None, ops=None, pages=None,
                    page_size: int = 0, max_len: int = 0,
                    fold_wo: bool = False, tp_axis=None):
    """tokens: (B,) int32; pos: (B,) int32.  Returns (logits, caches).

    One scan over layer groups; inside the body the ``gl`` sublayers run in
    architectural order (same traversal as prefill).

    ``pages``/``page_size``/``max_len``: the paged KV-cache operands
    (page table int32 (B, max_pages); see ``init_decode_cache(layout=)``
    and repro.serving.kvcache).  ``fold_wo`` folds each attention
    sublayer's o-projection requant into the decode epilogue
    (bit-exact either way).  ``tp_axis``: tensor-parallel tracing under
    shard_map — ``qparams``/``caches`` are head-sharded, ``cfg`` carries
    the local head counts, and each attention o-projection all-reduces
    its int32 partials before requanting once (see
    ``repro.distributed.tp_serving``)."""
    ops = resolve_ops(ops, cfg)
    gl, ng, kinds = layer_group_spec(cfg)
    x32 = embed_int(qparams, tokens[:, None], plans, cfg)

    def body(x32, xs):
        qp_group, cache_group = xs
        new_group = []
        for j, kind in enumerate(kinds):
            x32, nc = _int_sublayer_decode(qp_group[j], cache_group[j],
                                           x32, plans, cfg, kind, rope_tab,
                                           pos, ops, pages=pages,
                                           page_size=page_size,
                                           max_len=max_len,
                                           fold_wo=fold_wo,
                                           tp_axis=tp_axis)
            new_group.append(nc)
        return x32, tuple(new_group)

    x32, new_caches = jax.lax.scan(
        body, x32, (tuple(qparams["layers"]), tuple(caches)))
    logits = logits_int(qparams, x32, plans, cfg, ops)[:, 0]
    return logits, list(new_caches)


def speculative_decode_supported(cfg: ArchConfig) -> bool:
    """Whether :func:`int_verify_step` serves this arch: full
    (non-windowed) causal attention, no lane-indexed sublayer state.
    Sliding windows interleave rolling-buffer writes and reads token by
    token (a batched multi-position write would clobber slots earlier
    verify rows still need), and SSM / cross-attention state advances
    destructively per token — a rejected draft could not roll it back.
    Dense FFN *and* MoE sublayers are fine: decode routes MoE with
    ``group_size=1`` (one token per routing group), so each verify row
    routes independently, bit-exact against sequential decode."""
    _, _, kinds = layer_group_spec(cfg)
    return cfg.window == 0 and all(mix == "attn" and not has_cross
                                   for (mix, ff, has_cross) in kinds)


@trace_names.entry_point
def int_verify_step(qparams, caches, tokens, pos, n_new, plans,
                    cfg: ArchConfig, rope_tab=None, ops=None, pages=None,
                    page_size: int = 0, max_len: int = 0,
                    fold_wo: bool = False, tp_axis=None):
    """One speculative verify step: score S = spec_k + 1 candidate
    positions per lane in a single stepped-mask decode launch.

    ``tokens``: (B, S) int32, each lane's real tokens (last committed
    token + its drafts) **right-aligned**; ``pos``: (B,) the lane's
    current position (the first real row writes there); ``n_new``: (B,)
    count of real rows, ``1 <= n_new <= S`` with ``pos + n_new <= L``
    (idle lanes pass ``n_new = 1`` with token 0 — the same discarded
    garbage row the plain decode step gives them).  Returns
    ``(logits (B, S, V), caches)`` — the caller reads rows
    ``S - n_new ..`` and commits the longest argmax-matching draft
    prefix plus the bonus token.

    Row ``i`` of lane ``b`` covers logical position ``pos[b] +
    n_new[b] - S + i`` and the ``valid_len = pos + n_new`` stepped mask
    (``ops.int_decode_attention``; built for exactly this in PR 3)
    limits it to positions ``<= pos + n_new - S + i`` — the visibility
    a sequential decode of the same tokens would have.  Embedding,
    norms, FFN/MoE(``group_size=1``) and the residual stream are
    position-independent, and the attention rows are masked
    identically, so each real row's logits are **bit-exact** against
    feeding its token through :func:`int_decode_step` — greedy
    acceptance therefore reproduces the non-speculative stream token
    for token.  Supported archs: :func:`speculative_decode_supported`.
    """
    ops = resolve_ops(ops, cfg)
    if not speculative_decode_supported(cfg):
        raise ValueError("speculative verify unsupported for arch "
                         f"{cfg.name!r} (needs window == 0 and "
                         "attention+ffn/moe sublayers only)")
    gl, ng, kinds = layer_group_spec(cfg)
    x32 = embed_int(qparams, tokens, plans, cfg)

    def body(x32, xs):
        qp_group, cache_group = xs
        new_group = []
        for j, kind in enumerate(kinds):
            qp, cache = qp_group[j], cache_group[j]
            new_cache = dict(cache)
            h8 = _norm(qp, "norm1", x32, plans.norm, ops)
            with trace_names.scope("attn"):
                a32, kv = il.int_attn_decode(
                    qp["attn"], h8, cache, pos, plans.attn, cfg, rope_tab,
                    window=0, ops=ops, pages=pages, page_size=page_size,
                    max_len=max_len, fold_wo=fold_wo, tp_axis=tp_axis,
                    n_new=n_new)
            new_cache.update(kv)
            x32 = _residual_add(x32, a32, cfg)
            _, ff, _ = kind
            if ff is not None:
                h8 = _norm(qp, "norm2", x32, plans.norm, ops)
                f32 = _int_ff(qp, h8, plans, cfg, ff, ops, group_size=1)
                x32 = _residual_add(x32, f32, cfg)
            new_group.append(new_cache)
        return x32, tuple(new_group)

    x32, new_caches = jax.lax.scan(
        body, x32, (tuple(qparams["layers"]), tuple(caches)))
    logits = logits_int(qparams, x32, plans, cfg, ops)
    return logits, list(new_caches)


def chunked_prefill_supported(cfg: ArchConfig) -> bool:
    """Whether :func:`int_prefill_chunk_step` serves this arch: full
    (non-windowed) causal attention + dense FFN sublayers only.  Sliding
    windows interleave rolling-buffer writes and reads token-by-token
    (a batched chunk write would clobber positions earlier chunk rows
    still need), SSM state updates are inherently sequential per lane,
    MoE capacity-based routing drops tokens per *group* (so chunked
    grouping would diverge from token streaming), and cross-attention
    archs carry lane-indexed memory — all of those keep the engine's
    token-streaming prefill."""
    _, _, kinds = layer_group_spec(cfg)
    return cfg.window == 0 and all(kind == ("attn", "ffn", False)
                                   for kind in kinds)


@trace_names.entry_point
def int_prefill_chunk_step(qparams, caches, tokens, base_pos, plans,
                           cfg: ArchConfig, rope_tab=None, ops=None,
                           pages=None, page_size: int = 0,
                           fold_wo: bool = False, tp_axis=None):
    """One chunked-prefill step: advance every prefilling lane by one
    C-token prompt chunk, writing K/V straight into the paged pools.

    ``tokens``: (B, C) int32 chunk tokens (pad lanes/positions with 0 —
    their writes land on pages the table routes to the reserved null
    page, or on positions a later decode step overwrites before
    ``valid_len`` ever marks them live); ``base_pos``: (B,) int32 first
    logical position of each lane's chunk; ``pages``: the *prefill view*
    of the page table — rows of lanes not being prefilled must be
    nulled, so their (discarded) chunk writes cannot touch live pages.

    Returns the new caches only — chunked prefill fills the cache, it
    does not sample (the engine feeds the prompt's last token through
    the decode step, exactly as the token-streaming path).  Bit-exact
    against streaming the same tokens through :func:`int_decode_step`
    one at a time (same ops, same epilogues, row-independent integer
    math).  Supported archs: :func:`chunked_prefill_supported`.
    """
    ops = resolve_ops(ops, cfg)
    if not chunked_prefill_supported(cfg):
        raise ValueError("chunked prefill unsupported for arch "
                         f"{cfg.name!r} (needs window == 0 and "
                         "attention+ffn sublayers only)")
    gl, ng, kinds = layer_group_spec(cfg)
    x32 = embed_int(qparams, tokens, plans, cfg)

    def body(x32, xs):
        qp_group, cache_group = xs
        new_group = []
        for j in range(len(kinds)):
            qp, cache = qp_group[j], cache_group[j]
            new_cache = dict(cache)
            h8 = _norm(qp, "norm1", x32, plans.norm, ops)
            with trace_names.scope("attn"):
                a32, kv = il.int_attn_prefill_chunk(
                    qp["attn"], h8, cache, base_pos, plans.attn, cfg,
                    rope_tab, ops=ops, pages=pages, page_size=page_size,
                    fold_wo=fold_wo, tp_axis=tp_axis)
            new_cache.update(kv)
            x32 = _residual_add(x32, a32, cfg)
            h8 = _norm(qp, "norm2", x32, plans.norm, ops)
            f32 = il.int_ffn_fwd(qp["ffn"], h8, plans.ffn, cfg, ops)
            x32 = _residual_add(x32, f32, cfg)
            new_group.append(new_cache)
        return x32, tuple(new_group)

    _, new_caches = jax.lax.scan(
        body, x32, (tuple(qparams["layers"]), tuple(caches)))
    return list(new_caches)


def build_cache_from_prefill(qparams, batch, plans, cfg, ops,
                             cache_len):
    """Serving-engine helper: run prefill token-by-token into the decode
    cache (kept simple; the engine uses it for short prompts)."""
    ops = resolve_ops(ops, cfg)
    gl, ng, kinds = layer_group_spec(cfg)
    tokens = batch["tokens"]
    b, s = tokens.shape
    memory8 = None
    if cfg.family == "vlm":
        memory8 = quantize_memory(batch["img_embeds"], cfg)
    elif cfg.family == "encdec":
        memory8 = _int_encoder(qparams, batch["src_embeds"], plans, cfg,
                               ops)
    caches = init_decode_cache(cfg, b, cache_len, memory8, qparams, plans,
                               ops)
    rope_tab = il.build_rope_table(cache_len + 1, cfg.hd, cfg.rope_theta) \
        if cfg.pos == "rope" else None

    def step(carry, t):
        caches = carry
        tok = jax.lax.dynamic_index_in_dim(tokens, t, 1, keepdims=False)
        pos = jnp.full((b,), t, jnp.int32)
        logits, caches = int_decode_step(qparams, caches, tok, pos, plans,
                                         cfg, rope_tab, ops)
        return caches, logits

    caches, _ = jax.lax.scan(step, caches, jnp.arange(s))
    return caches
