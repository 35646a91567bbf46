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


def init_moe(key, cfg: ArchConfig, dtype):
    ks = jax.random.split(key, 5)
    d = cfg.d_model
    e = cfg.padded_experts()
    f = cfg.moe_d_ff or cfg.d_ff
    p = {
        "router": _init(ks[0], (d, e), dtype),
        "w1": _init(ks[1], (e, d, f), dtype),
        "w2": _init(ks[2], (e, f, d), dtype),
    }
    if cfg.activation == "swiglu":
        p["w3"] = _init(ks[3], (e, d, f), dtype)
    if cfg.n_shared_experts:
        p["shared"] = init_ffn(ks[4], cfg, dtype,
                               d_ff=f * cfg.n_shared_experts)
    return p


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


def norm_fwd(p, x, cfg: ArchConfig, eps: float = 1e-6):
    """f32 only for the row statistics; the (B,S,D) tensor stays in the
    input dtype — otherwise XLA fuses the seq-parallel all-gather into the
    f32 upcast and moves 2x the bytes (EXPERIMENTS.md §Perf C8)."""
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

def moe_fwd(p, x, cfg: ArchConfig, qat=False, group_size: int = 512):
    """Capacity-based top-k routing with dispatch/combine einsums.

    Tokens are processed in groups (sequence slices) so the dispatch mask
    stays small; experts shard over the ``model`` axis (EP).  Returns
    (out, aux_loss).
    """
    b, s, d = x.shape
    e = cfg.padded_experts()
    k = cfg.top_k
    g = max(1, s // group_size)
    tg = s // g
    cap = max(4, int(cfg.capacity_factor * tg * k / e))
    xg = x.reshape(b * g, tg, d)

    xq = maybe_fq(xg, cfg.s_act8, enabled=qat)
    logits = jnp.einsum("gtd,de->gte", xq,
                        fq_weight(p["router"], 1, qat)).astype(jnp.float32)
    if cfg.padded_experts() != cfg.n_experts:       # mask padding experts
        pad = jnp.arange(e) >= cfg.n_experts
        logits = jnp.where(pad[None, None], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)          # (g,t,k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    # load-balance auxiliary loss (Switch): E * mean(frac_tokens * frac_prob)
    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean(
        (jax.nn.one_hot(expert_ids[..., 0], e)), axis=(0, 1))
    aux = e * jnp.sum(me * ce)

    # capacity assignment, slot-by-slot (k is small)
    dispatch = jnp.zeros((b * g, tg, e, cap), x.dtype)
    combine = jnp.zeros((b * g, tg, e, cap), jnp.float32)
    counts = jnp.zeros((b * g, e), jnp.int32)
    for slot in range(k):
        a = jax.nn.one_hot(expert_ids[..., slot], e, dtype=jnp.int32)
        pos = counts[:, None, :] + jnp.cumsum(a, axis=1) - a
        keep = (pos < cap) & (a > 0)
        oh = jax.nn.one_hot(pos, cap, dtype=x.dtype) \
            * keep[..., None].astype(x.dtype)
        dispatch = dispatch + a[..., None].astype(x.dtype) * oh
        combine = combine + (gate_vals[..., slot][..., None, None]
                             * oh.astype(jnp.float32))
        counts = counts + jnp.sum(a, axis=1)

    buf = jnp.einsum("gtd,gtec->gecd", xg, dispatch).astype(x.dtype)
    buf = shard(buf, "batch", "experts", None, "embed")
    bq = maybe_fq(buf, cfg.s_act8, enabled=qat)
    if cfg.activation == "swiglu":
        h1 = jnp.einsum("gecd,edf->gecf", bq, fq_weight(p["w1"], 2, qat))
        h3 = jnp.einsum("gecd,edf->gecf", bq, fq_weight(p["w3"], 2, qat))
        h = jax.nn.silu(maybe_fq(h1, cfg.s_act10, 10, qat)) \
            * maybe_fq(h3, cfg.s_act10, 10, qat)
    else:
        h1 = jnp.einsum("gecd,edf->gecf", bq, fq_weight(p["w1"], 2, qat))
        h = jax.nn.gelu(maybe_fq(h1, cfg.s_act10, 10, qat),
                        approximate=False)
    h = maybe_fq(h, cfg.s_act8, enabled=qat)
    y = jnp.einsum("gecf,efd->gecd", h, fq_weight(p["w2"], 2, qat))
    y = shard(y, "batch", "experts", None, "embed")
    out = jnp.einsum("gecd,gtec->gtd", y.astype(x.dtype),
                     combine.astype(x.dtype))
    out = out.reshape(b, s, d)
    if cfg.n_shared_experts:
        out = out + ffn_fwd(p["shared"], x, cfg, qat=qat)
    return shard_residual(out), aux
