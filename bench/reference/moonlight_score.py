"""Plain float32 reference of Moonlight-16B-A3B (DeepSeek-V3's block) for
the prefill-scoring cell, at ``precision="highest"``.

It follows ``graph`` in ``bench/configs/moonlight-16b-a3b-score.json``:

  * multi-head latent attention with no q LoRA: ``q = W_q h`` gives
    ``n_heads`` heads of ``qk_nope_head_dim + qk_rope_head_dim``;
    ``W_kva h`` gives the ``kv_lora_rank`` latent (RMSNormed with its
    own gain) and one ``qk_rope_head_dim`` RoPE key that every head
    shares; ``W_kvb`` expands the normed latent to per-head ``k_nope``
    and ``v`` (``v_head_dim``); RoPE (rotate-half pairing) turns the
    queries' rope lanes and the shared key; scores scale by
    1/sqrt(nope + rope);
  * the first ``first_k_dense`` layers a dense SwiGLU of width ``d_ff``;
  * every later layer an MoE: sigmoid scores of ``n_experts`` router
    logits, the top ``top_k`` chosen on the scores plus the correction
    bias, gates the unbiased scores normalised over the k (the published
    ``norm_topk_prob``) times ``routed_scaling``, the experts SwiGLUs
    of width ``moe_d_ff``, and ``n_shared_experts`` shared experts run as
    one SwiGLU of that many times the width;
  * an untied head.

The chip's share (``expert_first``, ``experts_held``): the router scores
all ``n_experts``; only pairs whose expert lies in the held range add
their expert's output, exactly as the program computes them.

Weights: the program's float layout (``models.transformer.init_params``
with ``lead_layers``), at the served scale of ``plain_transformer``:
embedding std 0.1, every matrix at its fan-in, and the residual
branches' output projections (``wo``, every ``w2``) at gain 0.5 over
their fan-in.  The correction bias is drawn at std ``ROUTER_BIAS_STD``
(the published one is trained).  ``bits`` selects the control, as in
``plain_transformer``.
"""
from __future__ import annotations

import importlib.util
import math
import os

import jax
import jax.numpy as jnp


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_reference_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


pt = _sibling("plain_transformer")
HIGHEST = pt.HIGHEST
padded_vocab = pt.padded_vocab

#: the spread of the drawn correction bias
ROUTER_BIAS_STD = 0.02


def held(g: dict) -> int:
    return g.get("experts_held") or g["n_experts"]


# ---------------------------------------------------------------- weights --

def make_weights(key, g: dict, dtype=jnp.bfloat16):
    """Random weights in the program's layout.  Call under ``jax.jit``
    with ``g`` static: the whole tree is drawn on the device."""
    d, h, r = g["d_model"], g["n_heads"], g["kv_lora_rank"]
    dn, dr, dv = g["qk_nope_head_dim"], g["qk_rope_head_dim"], \
        g["v_head_dim"]
    f, fe, e, eh = g["d_ff"], g["moe_d_ff"], g["n_experts"], held(g)
    fs = fe * g["n_shared_experts"]
    n_lead = g["first_k_dense"]
    n_moe = g["num_layers"] - n_lead
    v = padded_vocab(g)
    ks = iter(jax.random.split(key, 32))
    gain = pt.BRANCH_GAIN

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32) * std
                ).astype(dtype)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    def mla(n):
        return {"wq": normal((n, d, h, dn + dr), 1 / math.sqrt(d)),
                "wkv_a": normal((n, d, r + dr), 1 / math.sqrt(d)),
                "kv_norm": {"gamma": ones(n, r)},
                "wkv_b": normal((n, r, h, dn + dv), 1 / math.sqrt(r)),
                "wo": normal((n, h, dv, d), gain / math.sqrt(h * dv))}

    def swiglu(lead, width):
        return {"w1": normal(lead + (d, width), 1 / math.sqrt(d)),
                "w3": normal(lead + (d, width), 1 / math.sqrt(d)),
                "w2": normal(lead + (width, d), gain / math.sqrt(width))}

    lead = {"norm1": {"gamma": ones(n_lead, d)}, "attn": mla(n_lead),
            "norm2": {"gamma": ones(n_lead, d)}, "ffn": swiglu((n_lead,), f)}
    moe = dict(swiglu((n_moe, eh), fe),
               router=normal((n_moe, d, e), 1 / math.sqrt(d)),
               router_bias=normal((n_moe, e), ROUTER_BIAS_STD),
               shared=swiglu((n_moe,), fs))
    layer = {"norm1": {"gamma": ones(n_moe, d)}, "attn": mla(n_moe),
             "norm2": {"gamma": ones(n_moe, d)}, "moe": moe}
    return {"embed": normal((v, d), pt.EMBED_STD),
            "final_norm": {"gamma": ones(d)},
            "lm_head": normal((d, v), 1 / math.sqrt(d)),
            "lead_layers": lead, "layers": [layer]}


# ---------------------------------------------------------------- forward --

def _rms(x, gamma, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * gamma


def _mla(x, a, g, bits):
    """Causal multi-head latent attention over one sequence x (S, D)."""
    s, d = x.shape
    h, r = g["n_heads"], g["kv_lora_rank"]
    dn, dr, dv = g["qk_nope_head_dim"], g["qk_rope_head_dim"], \
        g["v_head_dim"]
    q = pt._mm(x, a["wq"].reshape(d, -1), bits, 0).reshape(s, h, dn + dr)
    kva = pt._mm(x, a["wkv_a"], bits, 0)
    c = _rms(kva[:, :r], a["kv_norm"]["gamma"], g["norm_eps"])
    kv = pt._mm(c, a["wkv_b"].reshape(r, -1), bits, 0).reshape(
        s, h, dn + dv)
    pos = jnp.arange(s)
    q_pe = pt._rope(q[..., dn:], pos, g["rope_theta"])
    k_pe = pt._rope(kva[:, None, r:], pos, g["rope_theta"])
    q = jnp.concatenate([q[..., :dn], q_pe], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (s, h, dr))],
                        -1)
    v = kv[..., dn:]
    q, k, v = pt._fq(q, bits, -1), pt._fq(k, bits, -1), pt._fq(v, bits, -1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        / math.sqrt(dn + dr)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v,
                   precision=HIGHEST).reshape(s, h * dv)
    return pt._mm(o, a["wo"].reshape(h * dv, d), bits, 0)


def _swiglu(x, f, bits):
    u = jax.nn.silu(pt._mm(x, f["w1"], bits, 0)) * pt._mm(x, f["w3"],
                                                          bits, 0)
    return pt._mm(u, f["w2"], bits, 0)


def route(logits, bias, g):
    """(expert ids (S, k), gates (S, k)) of router logits (S, E)."""
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + bias, g["top_k"])
    gates = jnp.take_along_axis(scores, ids, -1)
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return ids, gates * g["routed_scaling"]


def moe(x, m, g, bits):
    """The MoE sublayer over one sequence x (S, D): every held expert on
    every token, weighted by its gate (0 where not chosen), plus the
    shared experts."""
    logits = pt._mm(x, m["router"], bits, 0)
    ids, gates = route(logits, m["router_bias"], g)
    local = ids - g.get("expert_first", 0)
    w = jnp.zeros((x.shape[0], held(g)), jnp.float32)
    w = jax.vmap(lambda wr, lr, gr: wr.at[lr].add(gr, mode="drop"))(
        w, jnp.where(local >= 0, local, held(g)), gates)

    def expert(f):
        return _swiglu(x, f, bits)
    ys = jax.vmap(expert)({k: m[k] for k in ("w1", "w3", "w2")})
    return jnp.einsum("se,esd->sd", w, ys, precision=HIGHEST) \
        + _swiglu(x, m["shared"], bits)


def hidden(params, tokens, g, bits=None):
    """Final normed hidden states (S, D) of one sequence ``tokens`` (S,)."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    eps = g["norm_eps"]
    x = f32(params["embed"])[tokens]

    def lead(x, lp):
        x = x + _mla(_rms(x, lp["norm1"]["gamma"], eps), lp["attn"], g, bits)
        return x + _swiglu(_rms(x, lp["norm2"]["gamma"], eps), lp["ffn"],
                           bits), None

    def body(x, lp):
        lp = f32(lp)
        x = x + _mla(_rms(x, lp["norm1"]["gamma"], eps), lp["attn"], g, bits)
        return x + moe(_rms(x, lp["norm2"]["gamma"], eps), lp["moe"], g,
                       bits), None
    x, _ = jax.lax.scan(lambda x, lp: lead(x, f32(lp)), x,
                        params["lead_layers"])
    x, _ = jax.lax.scan(body, x, params["layers"][0])
    return _rms(x, f32(params["final_norm"])["gamma"], eps)


def logits(params, h, g, bits=None):
    """Untied head: h (N, D) -> logits (N, vocab) over the real vocab."""
    w = params["lm_head"][:, :g["vocab_size"]].astype(jnp.float32)
    return pt._mm(h, w, bits, 0)
