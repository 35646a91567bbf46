#!/usr/bin/env python3
"""Where an MoE scoring cell's top-1 gap comes from: routing flips
against the rest of the rounding, read from the plain reference alone.

    python3 bench/route_flips.py --workload <cell> --seeds 1,2 \\
        [--call 0] [--rows 8]

For each seed, the first ``--rows`` rows of call ``--call`` (the tokens
the cell draws at that seed) run through the cell's float reference one
layer at a time.  At each MoE layer, on the float path's own input:

  * ``flip8``: the share of tokens whose chosen experts change when the
    router's matmul alone is rounded to 8 bits (activations per token,
    weights per expert, as the controls round);
  * ``near``: the share whose k-th and (k+1)-th biased scores lie within
    2^-10 of each other.

Then the mean and widest top-1 gap, against the plain reference, of
three variants of it: the router's matmul alone at 8 bits (``router8``),
every matmul at 8 bits (``all8``) and at 4 bits (``all4``, the cell's
control).  The benchmark's runs never run this.  Prints one JSON line
per seed.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, os.path.join(HERE, "lib"))

import harness  # noqa: E402


def _moe_router8(ref):
    """``ref.moe`` with the router's matmul alone rounded to 8 bits."""
    import jax
    import jax.numpy as jnp

    def moe(x, m, g, bits):
        ids, gates = ref.route(ref.pt._mm(x, m["router"], 8, 0),
                               m["router_bias"], g)
        local = ids - g.get("expert_first", 0)
        w = jnp.zeros((x.shape[0], ref.held(g)), jnp.float32)
        w = jax.vmap(lambda wr, lr, gr: wr.at[lr].add(gr, mode="drop"))(
            w, jnp.where(local >= 0, local, ref.held(g)), gates)
        ys = jax.vmap(lambda f: ref._swiglu(x, f, bits))(
            {k: m[k] for k in ("w1", "w3", "w2")})
        return jnp.einsum("se,esd->sd", w, ys, precision=ref.HIGHEST) \
            + ref._swiglu(x, m["shared"], bits)
    return moe


def readings(cell, seed: int, call: int, rows: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import compare
    ref, g = cell.reference(), cell.graph
    pt, eps, k = ref.pt, g["norm_eps"], g["top_k"]
    wkey, tkey = jax.random.split(compare.seed_key(seed))
    params = jax.jit(lambda key: ref.make_weights(key, g))(wkey)
    b, s, v = cell.traffic["batch"], cell.traffic["seq_len"], g["vocab_size"]
    toks = np.asarray(jax.jit(lambda i: jax.random.randint(
        jax.random.fold_in(tkey, i), (b, s), 1, v))(np.int32(call)))[:rows]
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)

    def attn(x, lp):
        return x + ref._mla(ref._rms(x, lp["norm1"]["gamma"], eps),
                            lp["attn"], g, None)

    @jax.jit
    def lead(x, lp):
        lp = f32(lp)
        return jax.vmap(lambda x: (lambda y: y + ref._swiglu(ref._rms(
            y, lp["norm2"]["gamma"], eps), lp["ffn"], None))(attn(x, lp)))(x)

    @jax.jit
    def moe_layer(x, lp):
        lp = f32(lp)
        m = lp["moe"]

        def row(x):
            x = attn(x, lp)
            xn = ref._rms(x, lp["norm2"]["gamma"], eps)
            logits = pt._mm(xn, m["router"], None, 0)
            ids, _ = ref.route(logits, m["router_bias"], g)
            ids8, _ = ref.route(pt._mm(xn, m["router"], 8, 0),
                                m["router_bias"], g)
            flip = jnp.any(jnp.sort(ids, -1) != jnp.sort(ids8, -1), -1)
            top = jax.lax.top_k(jax.nn.sigmoid(logits) + m["router_bias"],
                                k + 1)[0]
            near = top[:, k - 1] - top[:, k] < 2.0 ** -10
            return x + ref.moe(xn, m, g, None), flip, near
        return jax.vmap(row)(x)

    def layer(tree, i):
        return jax.tree.map(lambda a: a[i], tree)
    out = {"seed": seed, "call": call, "rows": rows}
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"])[toks]
        lead_p, moe_p = params["lead_layers"], params["layers"][0]
        for i in range(g["first_k_dense"]):
            x = lead(x, layer(lead_p, i))
        flip8, near = [], []
        for i in range(g["num_layers"] - g["first_k_dense"]):
            x, fl, nr = moe_layer(x, layer(moe_p, i))
            flip8.append(round(float(np.mean(fl)), 5))
            near.append(round(float(np.mean(nr)), 5))
        out.update(flip8=flip8, near=near)

        def last_logits(bits):
            f = jax.jit(lambda p, t: ref.logits(p, jax.vmap(
                lambda r: ref.hidden(p, r, g, bits)[-1])(t), g, bits))
            return np.asarray(f(params, toks))
        want = last_logits(None)
        plain_moe = ref.moe
        ref.moe = _moe_router8(ref)
        try:
            variants = {"router8": last_logits(None)}
        finally:
            ref.moe = plain_moe
        variants.update(all8=last_logits(8), all4=last_logits(4))
        for name, got in variants.items():
            gaps = compare.top1_gaps(want, got.argmax(-1))
            out[name + "_mean_gap"] = float(gaps.mean())
            out[name + "_gap"] = float(gaps.max())
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--call", type=int, default=0)
    ap.add_argument("--rows", type=int, default=8)
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload)
    harness.device_check(cell.chips)
    for seed in [int(x) for x in args.seeds.split(",")]:
        print(json.dumps({"workload": cell.name, **readings(
            cell, seed, args.call, args.rows)}), flush=True)


if __name__ == "__main__":
    main()
