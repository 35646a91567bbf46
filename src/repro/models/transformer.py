"""Model assembly for all six architecture families.

Float path (training / QAT producer) and integer path (SwiftTron serving
datapath) share the same parameter layout so ``quant.convert`` is a pure
per-tensor transformation and ``lax.scan`` stacks stay homogeneous.

Layer grouping for scan:
  dense / moe / ssm / encoder : all layers identical -> one stacked scan
  vlm                         : blocks of (cross_every-1 self + 1 cross)
  hybrid (jamba)              : blocks of ``attn_every`` sublayers
                                (1 attn + rest mamba; MoE per moe_every)
  encdec                      : separate encoder and decoder stacks; the
                                decoder sublayer = self-attn + cross + ffn
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard, shard_residual
from repro.models import layers as fl
from repro.models import mamba as mb
from repro.models.common import ArchConfig, sinusoidal_pos

Pytree = Any


# ============================================================ init =========

def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def layer_group_spec(cfg: ArchConfig):
    """(group_len, n_groups, kinds); kinds[j] = (mixer, ffn_kind, cross?)."""
    if cfg.family == "vlm" and cfg.cross_every > 0:
        gl = cfg.cross_every
        kinds = [("attn", "ffn", False)] * (gl - 1) + [("cross", "ffn",
                                                        False)]
    elif cfg.family == "hybrid" and cfg.attn_every > 0:
        gl = cfg.attn_every
        kinds = []
        for j in range(gl):
            mix = "attn" if j == cfg.attn_offset else "ssm"
            ff = "moe" if (cfg.n_experts and j % cfg.moe_every
                           == cfg.moe_offset) else "ffn"
            kinds.append((mix, ff, False))
    elif cfg.family == "ssm":
        gl, kinds = 1, [("ssm", None, False)]
    elif cfg.family == "encdec":
        gl, kinds = 1, [("attn", "ffn", True)]     # decoder sublayer
    else:
        gl = 1
        ff = "moe" if (cfg.n_experts and cfg.moe_every == 1) else "ffn"
        kinds = [("attn", ff, False)]
    n = cfg.dec_layers if cfg.family == "encdec" else cfg.num_layers
    assert n % gl == 0, (n, gl)
    return gl, n // gl, kinds


def _init_sublayer(key, cfg: ArchConfig, mix: str, ff: Optional[str],
                   cross: bool, dtype):
    ks = jax.random.split(key, 4)
    p = {"norm1": fl.init_norm(cfg, dtype)}
    if mix in ("attn", "cross"):
        p["attn"] = fl.init_attn(ks[0], cfg, dtype, cross=(mix == "cross"))
    elif mix == "ssm":
        p["ssm"] = mb.init_mamba(ks[0], cfg, dtype)
    if cross:
        p["cross"] = fl.init_attn(ks[2], cfg, dtype, cross=True)
        p["norm_cross"] = fl.init_norm(cfg, dtype)
    if ff is not None:
        p["norm2"] = fl.init_norm(cfg, dtype)
        p[ff] = fl.init_moe(ks[1], cfg, dtype) if ff == "moe" \
            else fl.init_ffn(ks[1], cfg, dtype)
    return p


#: the scale of random weights that are served rather than trained
#: (``init_params(..., served=True)``)
SERVED_EMBED_STD = 0.1
SERVED_BRANCH_GAIN = 0.5


def init_params(key, cfg: ArchConfig, served: bool = False) -> Pytree:
    """Random parameters: a fan-in init, which training starts from.

    ``served=True`` rescales the same draw for random weights that are
    served instead (``launch.serve`` without a checkpoint,
    ``chip_smoke.py``), so the integer datapath computes on a living
    stream at published widths and depths:

      * the embedding at std ``SERVED_EMBED_STD`` once multiplied by
        the architecture's ``embedding_multiplier`` — the fan-in
        1/sqrt(vocab), about 0.004 at a published vocab, sits under the
        integer norms' pre-shift on the residual grid, so every integer
        activation is 0 and all logits are equal; drawn at 0.1 under
        Granite's x12, the embedding outweighs 40 layers' branches and
        with tied embeddings the stream repeats its input token;
      * the residual branches' output projections (``wo`` at its whole
        H·hd fan-in, ``w2``) at gain ``SERVED_BRANCH_GAIN`` once
        multiplied by the ``residual_multiplier`` — at 40
        layers the fan-in draw (``wo``'s fan-in is taken over heads
        alone) saturates the residual bus at ±16, and with tied
        embeddings a stream that the current token's embedding
        dominates repeats one token; at this scale the layers pick
        the next token and nothing clips.

    So the stream the layers see is the same whatever an
    architecture's embedding and residual multipliers are: the integer
    path folds them into its constants, and the float graph applies
    them.  Queries and keys keep their fan-in draw, so an
    ``attention_multiplier`` below 1/sqrt(head_dim) (Granite's 1/64)
    leaves served attention nearly flat, as it is at that scale.
    """
    dtype = jnp.dtype(cfg.dtype)
    gl, ng, kinds = layer_group_spec(cfg)
    keys = jax.random.split(key, ng * gl + 8)
    v = cfg.padded_vocab()
    params: Dict[str, Pytree] = {
        "embed": fl._init(keys[-1], (v, cfg.d_model), dtype, scale=1.0),
        "final_norm": fl.init_norm(cfg, dtype),
    }
    if not cfg.tie_embeddings and cfg.family != "encoder":
        params["lm_head"] = fl._init(keys[-2], (cfg.d_model, v), dtype)
    if cfg.pos == "learned":
        params["pos_embed"] = fl._init(keys[-3], (65536, cfg.d_model),
                                       dtype)
    params["layers"] = [
        _stack([_init_sublayer(keys[i * gl + j], cfg, *kinds[j], dtype)
                for i in range(ng)])
        for j in range(gl)
    ]
    if cfg.family == "encdec":
        ekeys = jax.random.split(keys[-4], cfg.enc_layers)
        params["enc_layers"] = [_stack([
            _init_sublayer(ekeys[i], cfg, "attn", "ffn", False, dtype)
            for i in range(cfg.enc_layers)])]
        params["enc_final_norm"] = fl.init_norm(cfg, dtype)
    return _served_scale(params, cfg) if served else params


def _served_scale(params, cfg: ArchConfig):
    """The ``served=True`` rescaling of :func:`init_params`."""
    v = cfg.padded_vocab()
    gain = SERVED_BRANCH_GAIN / cfg.residual_multiplier
    gains = {"wo": gain / math.sqrt(cfg.hd or 1), "w2": gain}

    def scale(path, leaf):
        g = gains.get(getattr(path[-1], "key", None), 1.0)
        return leaf if g == 1.0 else (leaf * g).astype(leaf.dtype)
    out = dict(params)
    std = SERVED_EMBED_STD / cfg.embedding_multiplier
    out["embed"] = (params["embed"] * (std * math.sqrt(v))
                    ).astype(params["embed"].dtype)
    for name in ("layers", "enc_layers"):
        if name in params:
            out[name] = jax.tree_util.tree_map_with_path(scale,
                                                         params[name])
    return out


# ===================================================== float forward ======

def _sublayer_fwd_float(p, x, cfg: ArchConfig, kind, positions, qat,
                        causal=True, memory=None):
    mix, ff, has_cross = kind
    window = cfg.window if mix == "attn" else 0
    aux = jnp.zeros((), jnp.float32)

    def mixer(h):
        if mix in ("attn", "cross"):
            return fl.attn_fwd(p["attn"], h, cfg, positions, causal=causal,
                               window=window,
                               memory=memory if mix == "cross" else None,
                               qat=qat)
        return mb.mamba_fwd(p["ssm"], h, cfg, qat=qat)

    def ffn(h):
        if ff == "moe":
            return fl.moe_fwd(p["moe"], h, cfg, qat=qat)
        return fl.ffn_fwd(p["ffn"], h, cfg, qat=qat), None

    def branch(y):
        m = cfg.residual_multiplier
        return y if m == 1.0 else y * jnp.asarray(m, y.dtype)

    if cfg.post_norm:
        x = fl.norm_fwd(p["norm1"], x + branch(mixer(x)), cfg)
        if has_cross:
            c = fl.attn_fwd(p["cross"], x, cfg, positions, causal=False,
                            memory=memory, qat=qat)
            x = fl.norm_fwd(p["norm_cross"], x + branch(c), cfg)
        if ff is not None:
            f, a = ffn(x)
            x = fl.norm_fwd(p["norm2"], x + branch(f), cfg)
            if a is not None:
                aux = aux + a
        return x, aux
    x = x + branch(mixer(fl.norm_fwd(p["norm1"], x, cfg)))
    if has_cross:
        h = fl.norm_fwd(p["norm_cross"], x, cfg)
        x = x + branch(fl.attn_fwd(p["cross"], h, cfg, positions,
                                   causal=False, memory=memory, qat=qat))
    if ff is not None:
        f, a = ffn(fl.norm_fwd(p["norm2"], x, cfg))
        x = x + branch(f)
        if a is not None:
            aux = aux + a
    return x, aux


def _run_stack_float(layer_params: List, x, cfg: ArchConfig, kinds,
                     positions, qat, causal=True, memory=None):
    from repro.distributed.sharding import constrain_like_params

    def body(carry, xs):
        x, aux = carry
        xs = constrain_like_params(xs)
        for j, kind in enumerate(kinds):
            x, a = _sublayer_fwd_float(xs[j], x, cfg, kind, positions, qat,
                                       causal=causal, memory=memory)
            aux = aux + a
        return (x, aux), None

    carry0 = (x, jnp.zeros((), jnp.float32))
    if not cfg.scan_layers:
        # unrolled: keeps FSDP weight gathers per-layer (XLA hoists
        # loop-invariant stack gathers out of while loops — DESIGN.md §7)
        ng = jax.tree.leaves(layer_params[0])[0].shape[0]
        fn = jax.remat(body) if cfg.remat else body
        carry = carry0
        for i in range(ng):
            xs_i = jax.tree.map(lambda t: t[i], tuple(layer_params))
            carry, _ = fn(carry, xs_i)
        return carry
    fn = jax.remat(body) if cfg.remat else body
    (x, aux), _ = jax.lax.scan(fn, carry0, tuple(layer_params))
    return x, aux


def embed_tokens(params, tokens, cfg: ArchConfig):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    if cfg.pos == "learned":
        s = tokens.shape[1]
        x = x + params["pos_embed"][:s][None]
    elif cfg.pos == "sinusoidal":
        x = x + sinusoidal_pos(tokens.shape[1], cfg.d_model, x.dtype)[None]
    return shard_residual(x)


def logits_fwd(params, x, cfg: ArchConfig, qat=False):
    x = fl.norm_fwd(params["final_norm"], x, cfg)
    x = fl.maybe_fq(x, cfg.s_act8, enabled=qat)
    # tied, or an encoder (its MLM head shares the word embedding)
    w = params["lm_head"] if "lm_head" in params else params["embed"].T
    logits = jnp.einsum("bsd,dv->bsv", x, fl.fq_weight(w, 1, qat))
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    return shard(logits, "batch", "seq", "vocab")


def forward_float(params, batch, cfg: ArchConfig, qat: bool = False,
                  return_hidden: bool = False):
    """Returns (logits | final hidden, aux_loss) for every family.

    batch: tokens (B,S) [+ img_embeds (B,Ni,D) | src_embeds (B,Sf,D)].
    """
    gl, ng, kinds = layer_group_spec(cfg)
    memory = None
    if cfg.family == "encdec":
        src = batch["src_embeds"].astype(jnp.dtype(cfg.dtype))
        epos = jnp.arange(src.shape[1])[None]
        enc_x, _ = _run_stack_float(params["enc_layers"], src, cfg,
                                    [("attn", "ffn", False)], epos, qat,
                                    causal=False)
        memory = fl.norm_fwd(params["enc_final_norm"], enc_x, cfg)
    elif cfg.family == "vlm":
        memory = batch["img_embeds"].astype(jnp.dtype(cfg.dtype))
    x = embed_tokens(params, batch["tokens"], cfg)
    positions = jnp.arange(x.shape[1])[None]
    x, aux = _run_stack_float(params["layers"], x, cfg, kinds, positions,
                              qat, causal=cfg.is_causal, memory=memory)
    if return_hidden:
        return x, aux
    return logits_fwd(params, x, cfg, qat), aux


def encoder_fwd_float(params, embeds, cfg: ArchConfig, qat: bool = False):
    """Encoder-only forward from pre-embedded inputs (RoBERTa/DeiT benches)."""
    gl, ng, kinds = layer_group_spec(cfg)
    positions = jnp.arange(embeds.shape[1])[None]
    x, _ = _run_stack_float(params["layers"], embeds, cfg, kinds,
                            positions, qat, causal=False)
    return fl.norm_fwd(params["final_norm"], x, cfg)
