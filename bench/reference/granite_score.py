"""Plain float32 reference of Granite 3.0's decoder, with its four scalar
multipliers, for the prefill-scoring cell.

``plain_transformer.py`` (imported, not edited: the RoBERTa cell reads
it) gives the weight generator, the control's rounding, the norms, RoPE
and the tied head.  Granite 3.0 changes four things in its block, each a
published scalar in ``graph`` (``bench/configs/granite-3-2b-score.json``):

  * ``embedding_multiplier``: the embedding is scaled before layer 0;
  * ``attention_multiplier``: the factor on Q·Kᵀ, in place of
    1/sqrt(head_dim);
  * ``residual_multiplier``: every residual branch (attention and FFN
    output) is scaled before it is added;
  * ``logits_scaling``: the logits are divided by it.

Weights: ``plain_transformer.make_weights`` at its served scale, with the
embedding drawn that many times smaller and the branches' output
projections that many times larger, so that once multiplied the stream
is the served one (embedding std 0.1, branch gain 0.5).  The query and
key projections keep their fan-in scale, so the published 1/64 on Q·Kᵀ
leaves attention nearly flat over up to 1024 keys: the regime where an
integer softmax that rounds each probability to 2^-7 loses the row.

``bits`` selects the control, as in ``plain_transformer``.
"""
from __future__ import annotations

import importlib.util
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


def make_weights(key, g: dict, dtype=jnp.bfloat16):
    """``plain_transformer.make_weights`` with the embedding divided by
    ``embedding_multiplier`` and ``wo`` / ``w2`` divided by
    ``residual_multiplier`` (see the module's docstring)."""
    p = pt.make_weights(key, g, dtype)
    e, r = g["embedding_multiplier"], g["residual_multiplier"]
    layer = dict(p["layers"][0])
    layer["attn"] = dict(layer["attn"],
                         wo=(layer["attn"]["wo"] / r).astype(dtype))
    layer["ffn"] = dict(layer["ffn"],
                        w2=(layer["ffn"]["w2"] / r).astype(dtype))
    return dict(p, embed=(p["embed"] / e).astype(dtype), layers=[layer])


def _layer(x, lp, g, bits):
    """One pre-norm Granite block over one sequence x (S, D)."""
    s, d = x.shape
    h, kv, hd = g["n_heads"], g["n_kv_heads"], g["head_dim"]
    r = g["residual_multiplier"]
    a = lp["attn"]
    hn = pt._norm(lp["norm1"], x, g)
    q = pt._mm(hn, a["wq"].reshape(d, h * hd), bits, 0).reshape(s, h, hd)
    k = pt._mm(hn, a["wk"].reshape(d, kv * hd), bits, 0).reshape(s, kv, hd)
    v = pt._mm(hn, a["wv"].reshape(d, kv * hd), bits, 0).reshape(s, kv, hd)
    pos = jnp.arange(s)
    q, k = pt._rope(q, pos, g["rope_theta"]), pt._rope(k, pos,
                                                       g["rope_theta"])
    q, k, v = pt._fq(q, bits, -1), pt._fq(k, bits, -1), pt._fq(v, bits, -1)
    rep = h // kv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        * g["attention_multiplier"]
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v,
                   precision=HIGHEST).reshape(s, h * hd)
    x = x + r * pt._mm(o, a["wo"].reshape(h * hd, d), bits, 0)
    f = lp["ffn"]
    hn = pt._norm(lp["norm2"], x, g)
    u = jax.nn.silu(pt._mm(hn, f["w1"], bits, 0)) * pt._mm(hn, f["w3"],
                                                           bits, 0)
    return x + r * pt._mm(u, f["w2"], bits, 0)


def hidden(params, tokens, g, bits=None):
    """Final normed hidden states (S, D) of one sequence ``tokens`` (S,)."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    x = f32(params["embed"])[tokens] * g["embedding_multiplier"]

    def body(x, lp):
        return _layer(x, f32(lp), g, bits), None
    x, _ = jax.lax.scan(body, x, params["layers"][0])
    return pt._norm(f32(params["final_norm"]), x, g)


def logits(params, h, g, bits=None):
    """Tied head over the real vocab, divided by ``logits_scaling``."""
    return pt.logits(params, h, g, bits) / g["logits_scaling"]
