"""Plain float32 reference of the pre-norm transformers the benchmark serves.

Straightforward ``jax.numpy`` at ``precision="highest"``: no kernels, no
cache, no batching tricks, nothing imported from the program.  It follows
the graph that the configuration file states (``graph`` in
``bench/configs/<name>.json``), which lists every departure from the
published model.

Also here: the weight generator.  The benchmark, not the program, draws
the float weights from ``--seed``, in the program's parameter layout, so
the program quantizes them and this reference reads the very same
floats.  The scale is the "served" scale: embedding std 0.1 and the
residual branches' output projections at gain 0.5 over their whole
fan-in, at which a random integer model neither flushes to zero nor
clips (see ``PERF.md``).

``bits`` selects the control: every matmul operand that the integer
program holds in int8 (weights per output channel, activations per row,
queries, keys and values per row and head) is rounded to a symmetric
grid of that many bits first.  ``bits=None`` is the reference itself.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EMBED_STD = 0.1
BRANCH_GAIN = 0.5
HIGHEST = jax.lax.Precision.HIGHEST


def padded_vocab(g: dict) -> int:
    m = g.get("vocab_multiple", 1)
    return -(-g["vocab_size"] // m) * m


# ---------------------------------------------------------------- weights --

def make_weights(key, g: dict, dtype=jnp.bfloat16):
    """Random weights in the program's layout (``embed``, ``final_norm``,
    ``layers`` = one stacked group).  Call under ``jax.jit`` with ``g``
    static: the whole tree is drawn on the device in one program."""
    d, h, kv, hd = g["d_model"], g["n_heads"], g["n_kv_heads"], g["head_dim"]
    f, n, v = g["d_ff"], g["num_layers"], padded_vocab(g)
    ks = iter(jax.random.split(key, 8))

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32) * std
                ).astype(dtype)

    def norm(*lead):
        p = {"gamma": jnp.ones(lead + (d,), dtype)}
        if g["norm"] == "layernorm":
            p["beta"] = jnp.zeros(lead + (d,), dtype)
        return p

    attn = {"wq": normal((n, d, h, hd), 1 / math.sqrt(d)),
            "wk": normal((n, d, kv, hd), 1 / math.sqrt(d)),
            "wv": normal((n, d, kv, hd), 1 / math.sqrt(d)),
            "wo": normal((n, h, hd, d), BRANCH_GAIN / math.sqrt(h * hd))}
    ffn = {"w1": normal((n, d, f), 1 / math.sqrt(d)),
           "w2": normal((n, f, d), BRANCH_GAIN / math.sqrt(f))}
    if g["activation"] == "swiglu":
        ffn["w3"] = normal((n, d, f), 1 / math.sqrt(d))
    else:
        ffn["b1"] = jnp.zeros((n, f), dtype)
        ffn["b2"] = jnp.zeros((n, d), dtype)
    layer = {"norm1": norm(n), "attn": attn, "norm2": norm(n), "ffn": ffn}
    return {"embed": normal((v, d), EMBED_STD), "final_norm": norm(),
            "layers": [layer]}


# ---------------------------------------------------------------- forward --

def _fq(x, bits, axis):
    """Symmetric round-to-grid of ``x`` with its absmax over ``axis``."""
    if bits is None:
        return x
    q = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-12) / q
    return jnp.clip(jnp.round(x / s), -q, q) * s


def _mm(x, w, bits, w_axes):
    """x (..., K) @ w; both rounded to ``bits`` for the control."""
    return jnp.einsum("...k,kn->...n", _fq(x, bits, -1),
                      _fq(w, bits, w_axes), precision=HIGHEST)


def _norm(p, x, g):
    if g["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + g["norm_eps"]) * p["gamma"] \
            + p["beta"]
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + g["norm_eps"]) \
        * p["gamma"]


def _rope(x, pos, theta):
    """Rotate-half RoPE; x (S, H, hd), pos (S,)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(x, lp, g, bits):
    """One pre-norm block over one sequence x (S, D)."""
    s, d = x.shape
    h, kv, hd = g["n_heads"], g["n_kv_heads"], g["head_dim"]
    a = lp["attn"]
    hn = _norm(lp["norm1"], x, g)
    q = _mm(hn, a["wq"].reshape(d, h * hd), bits, 0).reshape(s, h, hd)
    k = _mm(hn, a["wk"].reshape(d, kv * hd), bits, 0).reshape(s, kv, hd)
    v = _mm(hn, a["wv"].reshape(d, kv * hd), bits, 0).reshape(s, kv, hd)
    if g["positions"] == "rope":
        pos = jnp.arange(s)
        q, k = _rope(q, pos, g["rope_theta"]), _rope(k, pos, g["rope_theta"])
    q, k, v = _fq(q, bits, -1), _fq(k, bits, -1), _fq(v, bits, -1)
    rep = h // kv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / math.sqrt(hd)
    if g["causal"]:
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v,
                   precision=HIGHEST).reshape(s, h * hd)
    x = x + _mm(o, a["wo"].reshape(h * hd, d), bits, 0)
    f = lp["ffn"]
    hn = _norm(lp["norm2"], x, g)
    if g["activation"] == "swiglu":
        u = jax.nn.silu(_mm(hn, f["w1"], bits, 0)) * _mm(hn, f["w3"], bits, 0)
        return x + _mm(u, f["w2"], bits, 0)
    u = jax.nn.gelu(_mm(hn, f["w1"], bits, 0) + f["b1"], approximate=False)
    return x + _mm(u, f["w2"], bits, 0) + f["b2"]


def hidden(params, tokens, g, bits=None):
    """Final normed hidden states (S, D) of one sequence ``tokens`` (S,)."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    x = f32(params["embed"])[tokens]

    def body(x, lp):
        return _layer(x, f32(lp), g, bits), None
    x, _ = jax.lax.scan(body, x, params["layers"][0])
    return _norm(f32(params["final_norm"]), x, g)


def logits(params, h, g, bits=None):
    """Tied head: h (N, D) -> logits (N, vocab) over the real vocab."""
    w = params["embed"][:g["vocab_size"]].astype(jnp.float32)
    return _mm(h, w.T, bits, 0)
