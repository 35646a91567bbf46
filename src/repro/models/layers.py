"""Float-path transformer layers with optional fake-quant (QAT).

This is the *producer* side of the SwiftTron flow (DESIGN.md §3): training
runs in bf16/f32 with straight-through fake quantization on every tensor
the accelerator would see in INT8, so converted checkpoints execute on the
integer path (intlayers.py) with matching numerics.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quant import fake_quant, per_channel_absmax
from repro.distributed.sharding import (comm_quant_gather, shard,
                                        shard_residual)
from repro.models.common import ArchConfig, apply_rope


# ---------------------------------------------------------------- init ----

def _init(key, shape, dtype, scale=1.0):
    fan_in = shape[0] if len(shape) > 1 else 1
    std = scale / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_norm(cfg: ArchConfig, dtype):
    p = {"gamma": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm == "layernorm":
        p["beta"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def init_attn(key, cfg: ArchConfig, dtype, cross: bool = False):
    ks = jax.random.split(key, 4)
    d, hd = cfg.d_model, cfg.hd
    p = {
        "wq": _init(ks[0], (d, cfg.n_heads, hd), dtype),
        "wk": _init(ks[1], (d, cfg.n_kv_heads, hd), dtype),
        "wv": _init(ks[2], (d, cfg.n_kv_heads, hd), dtype),
        "wo": _init(ks[3], (cfg.n_heads, hd, d), dtype),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((cfg.n_heads, hd), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads, hd), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads, hd), dtype)
    return p


def init_ffn(key, cfg: ArchConfig, dtype, d_ff: Optional[int] = None):
    ks = jax.random.split(key, 3)
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    p = {"w1": _init(ks[0], (d, f), dtype),
         "w2": _init(ks[1], (f, d), dtype)}
    if cfg.activation == "swiglu":
        p["w3"] = _init(ks[2], (d, f), dtype)
    else:
        p["b1"] = jnp.zeros((f,), dtype)
        p["b2"] = jnp.zeros((d,), dtype)
    return p


def init_mla(key, cfg: ArchConfig, dtype):
    """Multi-head latent attention with no q LoRA: ``wq`` (D, H, nope +
    rope), ``wkv_a`` (D, latent + rope) — the K/V latent and the one
    RoPE key shared by every head —, the latent's RMSNorm, ``wkv_b``
    (latent, H, nope + v) and ``wo`` (H, v, D)."""
    ks = jax.random.split(key, 4)
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {"wq": _init(ks[0], (d, h, dn + dr), dtype),
            "wkv_a": _init(ks[1], (d, r + dr), dtype),
            "kv_norm": {"gamma": jnp.ones((r,), dtype)},
            "wkv_b": _init(ks[2], (r, h, dn + dv), dtype),
            "wo": _init(ks[3], (h, dv, d), dtype)}


def init_moe(key, cfg: ArchConfig, dtype):
    """The router scores every (padded) expert; the expert weights are
    the ``cfg.held_experts`` this chip holds."""
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    e = cfg.padded_experts()
    eh = cfg.held_experts
    f = cfg.moe_d_ff or cfg.d_ff
    def expert(k, shape):
        # fan-in over each expert's own input (shape[-2]), not over the
        # expert axis
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(shape[-2])).astype(dtype)
    p = {
        "router": _init(ks[0], (d, e), dtype),
        "w1": expert(ks[1], (eh, d, f)),
        "w2": expert(ks[2], (eh, f, d)),
    }
    if cfg.router == "sigmoid":
        p["router_bias"] = (jax.random.normal(ks[5], (e,), jnp.float32)
                            * ROUTER_BIAS_STD).astype(dtype)
    if cfg.activation == "swiglu":
        p["w3"] = expert(ks[3], (eh, d, f))
    if cfg.n_shared_experts:
        p["shared"] = init_ffn(ks[4], cfg, dtype,
                               d_ff=f * cfg.n_shared_experts)
    return p


#: the spread of a random sigmoid router's correction bias (the
#: published ``e_score_correction_bias`` is trained; random weights draw
#: it at about the spread of the sigmoid scores' top-k margins)
ROUTER_BIAS_STD = 0.02


# ------------------------------------------------------------- helpers ----

def maybe_fq(x, scale, bits=8, enabled=False):
    return fake_quant(x, scale, bits) if enabled else x


def fq_weight(w, axis=-1, enabled=False):
    """Per-out-channel fake quant (axis = out-channel dim)."""
    if not enabled:
        return w
    s = jnp.maximum(per_channel_absmax(w, axis), 1e-6) / 127.0
    shape = [1] * w.ndim
    shape[axis] = -1
    return fake_quant(w, s.reshape(shape), 8)


def norm_fwd(p, x, cfg: ArchConfig, eps: Optional[float] = None):
    """f32 only for the row statistics; the (B,S,D) tensor stays in the
    input dtype — otherwise XLA fuses the seq-parallel all-gather into the
    f32 upcast and moves 2x the bytes (EXPERIMENTS.md §Perf C8)."""
    eps = cfg.norm_eps if eps is None else eps
    stats_in = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(stats_in, -1, keepdims=True)
        var = jnp.var(stats_in, -1, keepdims=True)
        inv = (1.0 / jnp.sqrt(var + eps)).astype(x.dtype)
        out = (x - mu.astype(x.dtype)) * inv * p["gamma"] + p["beta"]
    else:
        rms = jnp.sqrt(jnp.mean(stats_in * stats_in, -1, keepdims=True)
                       + eps)
        out = x * (1.0 / rms).astype(x.dtype) * p["gamma"]
    return out.astype(x.dtype)


# ----------------------------------------------------------- attention ----

def _repeat_kv(k, group: int):
    return jnp.repeat(k, group, axis=2) if group > 1 else k


def attn_fwd(p, x, cfg: ArchConfig, positions=None, causal=True,
             window: int = 0, memory=None, qat=False, q_chunk: int = 1024):
    """Self- or cross-attention. x: (B,S,D); memory: (B,Sm,D) for cross."""
    b, s, d = x.shape
    kv_src = memory if memory is not None else x
    sk = kv_src.shape[1]
    xq = comm_quant_gather(x, cfg.s_act8, enabled=qat) if qat \
        else maybe_fq(x, cfg.s_act8, enabled=qat)
    kq = comm_quant_gather(kv_src, cfg.s_act8, enabled=qat) if qat \
        else maybe_fq(kv_src, cfg.s_act8, enabled=qat)

    q = jnp.einsum("bsd,dhk->bshk", xq, fq_weight(p["wq"], 1, qat))
    k = jnp.einsum("bsd,dhk->bshk", kq, fq_weight(p["wk"], 1, qat))
    v = jnp.einsum("bsd,dhk->bshk", kq, fq_weight(p["wv"], 1, qat))
    if cfg.attn_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.pos == "rope" and memory is None and positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)
    k = _repeat_kv(k, cfg.q_group)
    v = _repeat_kv(v, cfg.q_group)

    scale = cfg.score_scale
    qc = min(q_chunk, s)
    while s % qc:
        qc -= 1
    n_chunks = s // qc

    def one_chunk(qi, q_blk):
        sc = jnp.einsum("bqhk,bthk->bhqt", q_blk, k,
                        preferred_element_type=jnp.float32) * scale
        if causal or window > 0:
            rows = qi * qc + jnp.arange(qc)[:, None]
            cols = jnp.arange(sk)[None, :]
            m = jnp.ones((qc, sk), bool)
            if causal:
                m = m & (cols <= rows)
            if window > 0:
                m = m & (cols > rows - window)
            sc = jnp.where(m[None, None], sc, -1e30)
        pr = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
        pr = maybe_fq(pr, 1.0 / 127.0, enabled=qat)   # int8 prob grid
        return jnp.einsum("bhqt,bthk->bqhk", pr, v)

    if n_chunks == 1:
        o = one_chunk(0, q)
    else:
        # remat per chunk: the backward recomputes one chunk's scores at a
        # time instead of saving every chunk's (b,h,qc,sk) linearisation
        chunk_fn = jax.remat(lambda args: one_chunk(*args))
        qs = q.reshape(b, n_chunks, qc, cfg.n_heads, cfg.hd) \
              .transpose(1, 0, 2, 3, 4)
        o = jax.lax.map(chunk_fn, (jnp.arange(n_chunks), qs))
        o = o.transpose(1, 0, 2, 3, 4).reshape(b, s, cfg.n_heads, cfg.hd)
    o = maybe_fq(o, cfg.s_act8, enabled=qat)
    out = jnp.einsum("bqhk,hkd->bqd", o, fq_weight(p["wo"], 2, qat))
    return shard_residual(out)


def rms_gamma(x, gamma, eps: float):
    """RMSNorm of ``x`` over its last axis with gain ``gamma``."""
    xf = x.astype(jnp.float32)
    rms = jnp.sqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (x * (1.0 / rms).astype(x.dtype) * gamma).astype(x.dtype)


def mla_fwd(p, x, cfg: ArchConfig, positions=None, causal=True, qat=False):
    """Multi-head latent attention, prefill form (``init_mla``): the
    latent is normed and expanded to per-head K/V; RoPE turns the query
    heads' last ``qk_rope_head_dim`` lanes and the one shared RoPE key,
    which every head reads.  x: (B,S,D)."""
    b, s, d = x.shape
    h, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    xq = maybe_fq(x, cfg.s_act8, enabled=qat)
    q = jnp.einsum("bsd,dhk->bshk", xq, fq_weight(p["wq"], 1, qat))
    kva = jnp.einsum("bsd,dk->bsk", xq, fq_weight(p["wkv_a"], 1, qat))
    c = maybe_fq(rms_gamma(kva[..., :r], p["kv_norm"]["gamma"],
                           cfg.norm_eps), cfg.s_act8, enabled=qat)
    kv = jnp.einsum("bsr,rhk->bshk", c, fq_weight(p["wkv_b"], 1, qat))
    k_pe = kva[..., None, r:]
    if positions is not None:
        q = jnp.concatenate([q[..., :dn], apply_rope(
            q[..., dn:], positions, cfg.rope_theta)], -1)
        k_pe = apply_rope(k_pe, positions, cfg.rope_theta)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_pe, (b, s, h, dr))], -1)
    v = kv[..., dn:]
    sc = jnp.einsum("bqhk,bthk->bhqt", q, k,
                    preferred_element_type=jnp.float32) * cfg.score_scale
    if causal:
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None, None], sc,
                       -1e30)
    pr = maybe_fq(jax.nn.softmax(sc, axis=-1).astype(x.dtype), 1.0 / 127.0,
                  enabled=qat)
    o = maybe_fq(jnp.einsum("bhqt,bthk->bqhk", pr, v), cfg.s_act8,
                 enabled=qat)
    out = jnp.einsum("bqhk,hkd->bqd", o, fq_weight(p["wo"], 2, qat))
    return shard_residual(out)


# ----------------------------------------------------------------- ffn ----

def ffn_fwd(p, x, cfg: ArchConfig, qat=False):
    xq = comm_quant_gather(x, cfg.s_act8, enabled=qat) if qat \
        else maybe_fq(x, cfg.s_act8, enabled=qat)
    if cfg.activation == "swiglu":
        h1 = jnp.einsum("bsd,df->bsf", xq, fq_weight(p["w1"], 1, qat))
        h3 = jnp.einsum("bsd,df->bsf", xq, fq_weight(p["w3"], 1, qat))
        h1 = maybe_fq(h1, cfg.s_act10, bits=10, enabled=qat)
        h3 = maybe_fq(h3, cfg.s_act10, bits=10, enabled=qat)
        h = jax.nn.silu(h1) * h3
    else:
        h1 = jnp.einsum("bsd,df->bsf", xq, fq_weight(p["w1"], 1, qat))
        h1 = h1 + p["b1"]
        h1 = maybe_fq(h1, cfg.s_act10, bits=10, enabled=qat)
        h = jax.nn.gelu(h1, approximate=False)
    h = shard(h, "batch", "seq", "ffn")
    h = maybe_fq(h, cfg.s_act8, enabled=qat)
    out = jnp.einsum("bsf,fd->bsd", h, fq_weight(p["w2"], 1, qat))
    if cfg.activation != "swiglu":
        out = out + p["b2"]
    return shard_residual(out)


# ----------------------------------------------------------------- moe ----

def route(logits, p, cfg: ArchConfig):
    """(expert ids (T, k), gates (T, k), load-balance loss) of float
    router logits (T, E).  Softmax routers take the top-k of the
    softmax, renormalised; sigmoid routers (``noaux_tc``) choose on the
    sigmoid scores plus the correction bias and gate the unbiased
    scores, normalised over the k, times ``routed_scaling``."""
    e, k = cfg.padded_experts(), cfg.top_k
    if e != cfg.n_experts:                          # mask padding experts
        logits = jnp.where(jnp.arange(e) >= cfg.n_experts, -1e30, logits)
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, ids = jax.lax.top_k(
            scores + p["router_bias"].astype(jnp.float32), k)
        gates = jnp.take_along_axis(scores, ids, axis=-1)
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
        return ids, gates * cfg.routed_scaling, jnp.zeros((), jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    # load-balance auxiliary loss (Switch): E * mean(frac_tokens * frac_prob)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(ids[:, 0], e), axis=0)
    return ids, gates, e * jnp.sum(me * ce)


def sort_pairs(ids, cfg: ArchConfig):
    """Dropless dispatch order of the (token, choice) pairs of ``ids``
    (T, k): pairs of held experts first, grouped by expert in order,
    pairs of experts held elsewhere last.  Returns (order (T*k,), held
    (T*k,) in pair order, group sizes (held_experts,))."""
    n = cfg.held_experts
    local = ids.reshape(-1) - cfg.expert_first
    held = (local >= 0) & (local < n)
    key = jnp.where(held, local, n)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((n + 1,), jnp.int32).at[key].add(1)[:n]
    return order, held, sizes


def moe_fwd(p, x, cfg: ArchConfig, qat=False):
    """Dropless top-k routing: every (token, choice) pair whose expert
    this chip holds is computed, through ``jax.lax.ragged_dot`` over the
    pairs sorted by expert, and gated back onto its token; pairs of
    experts held elsewhere add nothing.  Returns (out, aux_loss)."""
    b, s, d = x.shape
    k = cfg.top_k
    x2 = maybe_fq(x.reshape(b * s, d), cfg.s_act8, enabled=qat)
    logits = jnp.einsum("td,de->te", x2, fq_weight(p["router"], 1, qat)
                        ).astype(jnp.float32)
    ids, gates, aux = route(logits, p, cfg)
    order, held, sizes = sort_pairs(ids, cfg)
    xs = x2[order // k]

    def grouped(a, w):
        return jax.lax.ragged_dot(a, fq_weight(w, 2, qat).astype(a.dtype),
                                  sizes)
    if cfg.activation == "swiglu":
        h = jax.nn.silu(maybe_fq(grouped(xs, p["w1"]), cfg.s_act10, 10,
                                 qat)) \
            * maybe_fq(grouped(xs, p["w3"]), cfg.s_act10, 10, qat)
    else:
        h = jax.nn.gelu(maybe_fq(grouped(xs, p["w1"]), cfg.s_act10, 10,
                                 qat), approximate=False)
    y = grouped(maybe_fq(h, cfg.s_act8, enabled=qat), p["w2"])
    g = jnp.where(held, gates.reshape(-1), 0.0)[order]
    contrib = (y.astype(jnp.float32) * g[:, None]).astype(x.dtype)
    out = contrib[jnp.argsort(order)].reshape(b * s, k, d).sum(1)
    out = out.reshape(b, s, d)
    if cfg.n_shared_experts:
        out = out + ffn_fwd(p["shared"], x, cfg, qat=qat)
    return shard_residual(out), aux
