#!/usr/bin/env python3
"""Where the integer path leaves its float graph, one attention sublayer
at a time, on the CPU.

    JAX_PLATFORMS=cpu python3 bench/sublayer_error.py \\
        --config roberta-base --keys 64,512 [--seed 0] [--rows 2]

One layer of the configuration (``bench/configs/<config>.json``) at its
published widths, with weights from its reference's generator at the
served scale.  The integer path (``ops="ref"``, bit-exact with
``pallas_fused``) and its float twin start from the same tokens; each
stage of the attention branch is compared with the float twin's
(correlation, relative error, and the ratio of their norms):

  * ``norm``   the normed input of the block;
  * ``scores`` Q·Kᵀ times the score scale;
  * ``P``      the attention weights the output is an average under
    (the int8 probabilities over 2^7, or the int8 weights over their
    row sum where the row is divided after P·V);
  * ``PV``     the attention output, before the output projection;
  * ``oproj``  the branch that is added to the residual stream.

Prints one JSON line per (config, keys): each stage's correlation with
its twin and its relative error (``|int - float| / |float|`` over the
whole tensor).  Reads the program as it is: run it on two checkouts to
compare their arithmetic.
"""
import argparse
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, os.path.join(HERE, "lib"))

import harness  # noqa: E402


def _cmp(a, b) -> dict:
    import numpy as np
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    return {"corr": round(float(np.corrcoef(a, b)[0, 1]), 4),
            "rel_err": round(float(np.linalg.norm(a - b)
                                   / np.linalg.norm(b)), 4),
            "scale": round(float(np.linalg.norm(a) / np.linalg.norm(b)),
                           4)}


def sublayer(config: str, keys: int, seed: int, rows: int) -> dict:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.registry import get_config
    from repro.core import attention as iattn
    from repro.core import softmax as ism
    from repro.models import intlayers as il
    from repro.models import inttransformer as it
    from repro.quant import convert

    spec = harness.read_json(HERE, "configs", config + ".json")
    g = dict(spec["graph"], num_layers=1)
    ref = harness.load_module(os.path.join(HERE, "reference",
                                           spec["reference"] + ".py"))
    cfg = dataclasses.replace(
        get_config(spec["deployment"]["arch"]), num_layers=1,
        d_model=g["d_model"], n_heads=g["n_heads"],
        n_kv_heads=g["n_kv_heads"], head_dim=g["head_dim"],
        d_ff=g["d_ff"], vocab=g["vocab_size"], dtype="float32")
    mult = {k: g[k] for k in ("embedding_multiplier", "attention_multiplier",
                              "residual_multiplier", "logits_scaling")
            if k in g}
    if mult:
        cfg = dataclasses.replace(cfg, **mult)
    e_mult = g.get("embedding_multiplier", 1.0)
    r_mult = g.get("residual_multiplier", 1.0)
    a_mult = g.get("attention_multiplier", 1 / math.sqrt(g["head_dim"]))
    causal = g["causal"]

    params = ref.make_weights(jax.random.key(seed), g, dtype=jnp.float32)
    qp, plans = convert.quantize_params(params, cfg)
    toks = jax.random.randint(jax.random.key(seed + 1), (rows, keys), 1,
                              g["vocab_size"])
    lay = jax.tree.map(lambda a: a[0], params["layers"][0])
    qlay = jax.tree.map(lambda a: a[0], qp["layers"][0])
    h, hkv, hd, d = g["n_heads"], g["n_kv_heads"], g["head_dim"], \
        g["d_model"]
    mask = jnp.tril(jnp.ones((keys, keys), bool)) if causal else None
    out = {}

    # float twin (the reference's own helpers, on the same weights)
    pt = getattr(ref, "pt", ref)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][toks] * e_mult
        hn = pt._norm(lay["norm1"], x, g)
        a = lay["attn"]
        q = jnp.einsum("bsd,dhk->bshk", hn, a["wq"])
        k = jnp.einsum("bsd,dhk->bshk", hn, a["wk"])
        v = jnp.einsum("bsd,dhk->bshk", hn, a["wv"])
        if g["positions"] == "rope":
            pos = jnp.arange(keys)
            q = jax.vmap(lambda t: pt._rope(t, pos, g["rope_theta"]))(q)
            k = jax.vmap(lambda t: pt._rope(t, pos, g["rope_theta"]))(k)
        k = jnp.repeat(k, h // hkv, axis=2)
        v = jnp.repeat(v, h // hkv, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * a_mult
        if causal:
            sc = jnp.where(mask, sc, -jnp.inf)
        p = jax.nn.softmax(sc, -1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        branch = r_mult * jnp.einsum("bqhd,hdn->bqn", o, a["wo"])

    # the integer path, stage by stage
    x32 = it.embed_int(qp, toks, plans, cfg)
    h8 = il.int_norm(qlay["norm1"], x32, plans.norm, "ref")
    out["norm"] = _cmp(h8 * cfg.s_act8, hn)
    q8 = il.int_linear(h8, qlay["attn"]["wq"], plans.attn.qkv, "ref") \
        .reshape(rows, keys, h, hd)
    k8 = il.int_linear(h8, qlay["attn"]["wk"], plans.attn.qkv, "ref") \
        .reshape(rows, keys, hkv, hd)
    v8 = il.int_linear(h8, qlay["attn"]["wv"], plans.attn.qkv, "ref") \
        .reshape(rows, keys, hkv, hd)
    if g["positions"] == "rope":
        tab = il.build_rope_table(keys + 1, hd, g["rope_theta"])
        q8 = il.apply_int_rope(q8, jnp.arange(keys), tab)
        k8 = il.apply_int_rope(k8, jnp.arange(keys), tab)
    k8 = jnp.repeat(k8, h // hkv, axis=2)
    v8 = jnp.repeat(v8, h // hkv, axis=2)
    ia = plans.attn.attn
    scores = jnp.einsum("bqhd,bkhd->bhqk", q8, k8,
                        preferred_element_type=jnp.int32)
    live = mask if causal else jnp.ones((keys, keys), bool)
    out["scores"] = _cmp(np.asarray(scores)[:, :, np.asarray(live)]
                         * ia.sm.s_in, np.asarray(sc)[:, :, np.asarray(live)])
    if hasattr(ism, "attn_weights"):            # divided after P·V
        qm = jnp.where(live, scores, -(2 ** 30))
        u = ism.attn_weights(ism._exp16(qm - qm.max(-1, keepdims=True),
                                        ia.sm))
        u = jnp.where(live, u, 0)
        p_int = u / jnp.maximum(u.sum(-1, keepdims=True), 1)
    else:                                        # normalised before P·V
        p_int = ism.i_softmax(scores, ia.sm, where=live) * ism.S_PROB
    out["P"] = _cmp(p_int, p)
    out["P_zero_rows"] = round(float(
        (np.asarray(p_int).max(-1) == 0).mean()), 4)
    o8 = iattn.i_attention_full(q8, k8, v8, ia,
                                mask=live[None, None] if causal else None)
    out["PV"] = _cmp(o8 * ia.s_out, o)
    a32 = il.int_linear(o8.reshape(rows, keys, h * hd).astype(jnp.int8),
                        qlay["attn"]["wo"], plans.attn.out, "ref")
    out["oproj"] = _cmp(a32 * cfg.s_res, branch)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--keys", default="64,512")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=2)
    args = ap.parse_args(argv)
    for keys in [int(k) for k in args.keys.split(",")]:
        print(json.dumps({"config": args.config, "keys": keys,
                          **sublayer(args.config, keys, args.seed,
                                     args.rows)}), flush=True)


if __name__ == "__main__":
    main()
