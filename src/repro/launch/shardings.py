"""Parameter / input / cache sharding rules (DESIGN.md §7).

Rules are path-pattern based over the param pytree: TP on the ``model``
axis for heads / d_ff / vocab / experts, replication for norms and small
tensors, with divisibility guards (e.g. GQA kv heads replicate when
kv < model-axis size; mamba2-130m's fused in_proj width 3352 replicates
while jamba's 16544 shards).
"""
from __future__ import annotations

import fnmatch
from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.treepath import path_parts

Pytree = Any


def _path_str(path) -> str:
    return "/".join(path_parts(path))


# (pattern, spec-template) — template entries: "model" | None | "div:<dim>"
# means: shard dim on model only when divisible.  Matched against the
# flattened path; first match wins.  Shapes are handled by _fit().
PARAM_RULES = [
    # ---- quantized params ----
    ("embed_w8", ("model", None)),
    ("head/w8", (None, "model")),
    ("head_scale", ("model",)),
    ("*attn/wq/w8", (..., None, "model")),
    ("*attn/wq/b_mult", (..., "model")),
    ("*attn/wq/bias32", (..., "model")),
    ("*attn/wk/*", (..., None, "model")),
    ("*attn/wv/*", (..., None, "model")),
    ("*cross/wq/w8", (..., None, "model")),
    ("*cross/wq/b_mult", (..., "model")),
    ("*cross/wk/*", (..., None, "model")),
    ("*cross/wv/*", (..., None, "model")),
    ("*attn/wo/w8", (..., "model", None)),
    ("*cross/wo/w8", (..., "model", None)),
    ("*attn/wo/b_mult", (..., None)),
    ("*moe/router/w8", (..., None, "model")),
    ("*moe/w13/w8", (..., "model", None, "data")),
    ("*moe/w13/b_mult", (..., "model", "data")),
    ("*moe/w2/w8", (..., "model", "data", None)),
    ("*moe/w2/b_mult", (..., "model", None)),
    ("*moe/shared/w1/*", (..., None, "model")),
    ("*moe/shared/w3/*", (..., None, "model")),
    ("*moe/shared/w2/w8", (..., "model", None)),
    ("*moe/shared/w2/b_mult", (..., None)),
    ("*ffn/w1/*", (..., None, "model")),
    ("*ffn/w3/*", (..., None, "model")),
    ("*ffn/w2/w8", (..., "model", None)),
    ("*ffn/w2/b_mult", (..., None)),
    ("*ssm/in_proj/w8", (..., None, "model")),
    ("*ssm/in_proj/b_mult", (..., "model")),
    ("*ssm/out_proj/w8", (..., "model", None)),
    ("*ssm/out_proj/b_mult", (..., None)),
    ("*ssm/norm_gamma_q", (..., "model")),
    # ---- float params (same geometry, head dims unflattened) ----
    ("embed", ("model", None)),
    ("lm_head", (None, "model")),
    ("pos_embed", (None, None)),
    ("*attn/wq", (..., None, "model", None)),
    ("*attn/wk", (..., None, "model", None)),
    ("*attn/wv", (..., None, "model", None)),
    ("*attn/wo", (..., "model", None, None)),
    ("*attn/bq", (..., "model", None)),
    ("*attn/bk", (..., "model", None)),
    ("*attn/bv", (..., "model", None)),
    ("*cross/wq", (..., None, "model", None)),
    ("*cross/wk", (..., None, "model", None)),
    ("*cross/wv", (..., None, "model", None)),
    ("*cross/wo", (..., "model", None, None)),
    ("*moe/router", (..., None, "model")),
    ("*moe/w1", (..., "model", None, "data")),
    ("*moe/w2", (..., "model", "data", None)),
    ("*moe/w3", (..., "model", None, "data")),
    ("*moe/shared/w1", (..., None, "model")),
    ("*moe/shared/w3", (..., None, "model")),
    ("*moe/shared/w2", (..., "model", None)),
    ("*ffn/w1", (..., None, "model")),
    ("*ffn/w3", (..., None, "model")),
    ("*ffn/w2", (..., "model", None)),
    ("*ffn/b1", (..., "model")),
    ("*ssm/in_proj", (..., None, "model")),
    ("*ssm/out_proj", (..., "model", None)),
    ("*ssm/norm_gamma", (..., "model")),
]


def _fit(template, shape, sizes: dict) -> P:
    """Expand a template against a concrete shape with divisibility guards."""
    tpl = list(template)
    if tpl and tpl[0] is Ellipsis:
        tpl = [None] * (len(shape) - (len(tpl) - 1)) + tpl[1:]
    if len(tpl) != len(shape):        # rank mismatch -> replicate
        return P(*([None] * len(shape)))
    out = []
    for dim, t in zip(shape, tpl):
        sz = sizes.get(t, 1) if isinstance(t, str) else 1
        if isinstance(t, str) and sz > 1 and dim % sz == 0 and dim >= sz:
            out.append(t)
        else:
            out.append(None)
    return P(*out)


def param_pspecs(tree: Pytree, mesh, fsdp: bool = False) -> Pytree:
    """PartitionSpec pytree for a (float or quantized) param tree.

    ``fsdp``: additionally spread every large weight over the ``data``
    axis (first unsharded divisible dim) — per-layer all-gather in
    exchange for /DP-degree parameter memory (used for >20B models)."""
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    dsize = sizes.get("data", 1)

    def spec_for(path, leaf):
        ps = _path_str(path)
        spec = P(*([None] * len(leaf.shape)))
        for pat, tpl in PARAM_RULES:
            if fnmatch.fnmatch(ps, pat) or fnmatch.fnmatch(ps, "*" + pat):
                spec = _fit(tpl, leaf.shape, sizes)
                break
        if fsdp and leaf.size >= (1 << 24) and dsize > 1:
            flat = [a for s in spec if s for a in
                    (s if isinstance(s, tuple) else (s,))]
            if "data" not in flat:
                out = list(spec)
                best, best_dim = None, 0
                for i, (s, dim) in enumerate(zip(out, leaf.shape)):
                    if s is None and dim % dsize == 0 and dim > best_dim:
                        best, best_dim = i, dim
                if best is not None:
                    out[best] = "data"
                    spec = P(*out)
        return spec

    return jax.tree_util.tree_map_with_path(spec_for, tree)


def batch_pspecs(batch: Pytree, mesh) -> Pytree:
    """Inputs: batch dim over (pod, data); everything else replicated.
    Batch-1 (long-context) inputs replicate."""
    daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dsize = 1
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    for a in daxes:
        dsize *= sizes[a]

    def spec_for(path, leaf):
        if not leaf.shape:
            return P()
        b = leaf.shape[0]
        first = daxes if (b % dsize == 0 and b >= dsize) else None
        if isinstance(first, tuple) and len(first) == 1:
            first = first[0]
        return P(first, *([None] * (len(leaf.shape) - 1)))

    return jax.tree_util.tree_map_with_path(spec_for, batch)


def cache_pspecs(cache: Pytree, mesh, cfg) -> Pytree:
    """Decode caches: (ng, B, L, Hkv, hd) — batch over data axes when
    divisible, kv-heads / mamba-heads / conv channels over model when
    divisible."""
    msize = dict(zip(mesh.axis_names, mesh.axis_sizes)).get("model", 1)
    daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    dsize = 1
    for a in daxes:
        dsize *= sizes[a]
    dax = daxes[0] if len(daxes) == 1 else daxes

    def spec_for(path, leaf):
        ps = _path_str(path)
        shape = leaf.shape
        spec = [None] * len(shape)
        if len(shape) >= 2 and shape[1] % dsize == 0 and shape[1] >= dsize:
            spec[1] = dax
        # shard the "heads"-like dim on model when divisible
        name = ps.rsplit("/", 1)[-1]
        head_dim_idx = {"k8": 3, "v8": 3, "ck8": 3, "cv8": 3, "h": 2,
                        "conv": 3}.get(name)
        if head_dim_idx is not None and head_dim_idx < len(shape):
            if shape[head_dim_idx] % msize == 0 \
                    and shape[head_dim_idx] >= msize and msize > 1:
                spec[head_dim_idx] = "model"
            elif name in ("k8", "v8") and len(shape) >= 3 \
                    and shape[2] % msize == 0 and msize > 1:
                # GQA kv heads too few to shard -> shard the sequence dim
                # of the cache instead (long-context decode)
                spec[2] = "model"
        return P(*spec)

    return jax.tree_util.tree_map_with_path(spec_for, cache)


def named(tree: Pytree, mesh) -> Pytree:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree)
