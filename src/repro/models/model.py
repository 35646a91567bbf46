"""Public model API: configs -> params/specs -> train & serve entry points.

``input_specs`` / ``qparams_spec`` produce ShapeDtypeStruct stand-ins so
the multi-pod dry-run lowers full-size architectures without allocating
a byte (the 235B MoE's int8 weights exist only as avals).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import inttransformer as it
from repro.models import transformer as tf
from repro.models.common import ArchConfig, ShapeConfig
from repro.models.transformer import layer_group_spec
from repro.quant import plans as qplans
from repro.ops import QuantLinearParams

Pytree = Any
SDS = jax.ShapeDtypeStruct


def reduce_config(cfg: ArchConfig, **over) -> ArchConfig:
    """Smoke-test-sized config of the same family (structure preserved)."""
    gl, ng, kinds = layer_group_spec(cfg)
    upd = dict(
        num_layers=gl * min(ng, 2),
        d_model=128,
        n_heads=min(cfg.n_heads, 4) if cfg.n_heads else 0,
        n_kv_heads=min(cfg.n_kv_heads, 2) if cfg.n_kv_heads else 0,
        head_dim=32 if cfg.n_heads else 0,
        d_ff=256 if cfg.d_ff else 0,
        vocab=512,
        window=min(cfg.window, 64) if cfg.window else 0,
        n_experts=min(cfg.n_experts, 8) if cfg.n_experts else 0,
        top_k=min(cfg.top_k, 2) if cfg.top_k else 0,
        moe_d_ff=64 if cfg.moe_d_ff else 0,
        n_shared_experts=min(cfg.n_shared_experts, 2),
        ssm_state=min(cfg.ssm_state, 16) if cfg.ssm_state else 0,
        ssm_head_dim=16 if cfg.ssm_state else 64,
        n_img_tokens=min(cfg.n_img_tokens, 16) if cfg.n_img_tokens else 0,
        enc_layers=min(cfg.enc_layers, 2) if cfg.enc_layers else 0,
        dec_layers=min(cfg.dec_layers, 2) if cfg.dec_layers else 0,
    )
    if cfg.first_k_dense:
        upd["num_layers"] += cfg.first_k_dense
    if cfg.is_mla:
        upd.update(kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
                   v_head_dim=32, head_dim=48)
    upd.update(over)
    return dataclasses.replace(cfg, **upd)


def input_specs(cfg: ArchConfig, shape: ShapeConfig,
                dtype=jnp.bfloat16) -> Dict[str, SDS]:
    """ShapeDtypeStruct stand-ins for every model input of one cell."""
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        spec = {"tokens": SDS((b, s), jnp.int32),
                "labels": SDS((b, s), jnp.int32)}
    elif shape.kind == "prefill":
        spec = {"tokens": SDS((b, s), jnp.int32)}
    else:  # decode: one new token against a cache of length s
        spec = {"tokens": SDS((b,), jnp.int32),
                "pos": SDS((b,), jnp.int32)}
    if cfg.family == "vlm" and shape.kind != "decode":
        spec["img_embeds"] = SDS((b, cfg.n_img_tokens, cfg.d_model), dtype)
    if cfg.family == "encdec" and shape.kind != "decode":
        spec["src_embeds"] = SDS((b, s, cfg.d_model), dtype)
    return spec


# ------------------------------------------------------ qparams specs -----

def _lin_spec(ng, k, n, plan: qplans.LinearPlan, bias=False, lead=()):
    base = (ng,) + lead if ng else lead
    return QuantLinearParams(
        w8=SDS(base + (k, n), jnp.int8),
        b_mult=SDS(base + (n,), jnp.int32) if plan.s_out != 0.0 else None,
        bias32=SDS(base + (n,), jnp.int32) if bias else None)


def _norm_spec(ng, d, cfg):
    out = {"gamma_q": SDS((ng, d) if ng else (d,), jnp.int32)}
    if cfg.norm == "layernorm":
        out["beta_q"] = SDS((ng, d) if ng else (d,), jnp.int32)
    return out


def _attn_spec(ng, cfg: ArchConfig, plans: qplans.AttnPlan):
    d, hd = cfg.d_model, cfg.hd
    return {
        "wq": _lin_spec(ng, d, cfg.n_heads * hd, plans.qkv, cfg.attn_bias),
        "wk": _lin_spec(ng, d, cfg.n_kv_heads * hd, plans.qkv,
                        cfg.attn_bias),
        "wv": _lin_spec(ng, d, cfg.n_kv_heads * hd, plans.qkv,
                        cfg.attn_bias),
        "wo": _lin_spec(ng, cfg.n_heads * hd, d, plans.out),
    }


def _ffn_spec(ng, cfg: ArchConfig, plans: qplans.FfnPlan, f=None):
    d = cfg.d_model
    f = f or cfg.d_ff
    gelu_bias = cfg.activation != "swiglu"
    out = {"w1": _lin_spec(ng, d, f, plans.up, gelu_bias),
           "w2": _lin_spec(ng, f, d, plans.down, gelu_bias)}
    if cfg.activation == "swiglu":
        out["w3"] = _lin_spec(ng, d, f, plans.up)
    return out


def _mla_spec(ng, cfg: ArchConfig, plans: qplans.MlaPlan):
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    return {
        "wq": _lin_spec(ng, d, h * cfg.hd, plans.qkv),
        "wkv_a": _lin_spec(ng, d, r, plans.kv_a),
        "wk_pe": _lin_spec(ng, d, cfg.qk_rope_head_dim, plans.qkv),
        "kv_norm": {"gamma_q": SDS((ng, r) if ng else (r,), jnp.int32)},
        "wkv_b": _lin_spec(ng, r, h * (dn + dv), plans.kv_b),
        "wo": _lin_spec(ng, h * dv, d, plans.out),
    }


def _moe_spec(ng, cfg: ArchConfig, plans: qplans.MoePlan):
    d, e, eh = cfg.d_model, cfg.padded_experts(), cfg.held_experts
    f = cfg.moe_d_ff or cfg.d_ff
    f13 = 2 * f if cfg.activation == "swiglu" else f
    out = {
        "router": QuantLinearParams(
            SDS((ng, d, e) if ng else (d, e), jnp.int8)),
        "w13": _lin_spec(ng, d, f13, plans.expert.up, lead=(eh,)),
        "w2": _lin_spec(ng, f, d, plans.expert.down, lead=(eh,)),
    }
    if cfg.router == "sigmoid":
        out["router_bias_q"] = SDS((ng, e) if ng else (e,), jnp.int32)
    if cfg.n_shared_experts:
        out["shared"] = _ffn_spec(ng, cfg, plans.shared,
                                  f=f * cfg.n_shared_experts)
    return out


def _mamba_spec(ng, cfg: ArchConfig, mp: qplans.MambaPlan):
    d, di, h = cfg.d_model, cfg.ssm_d_inner, cfg.ssm_heads
    w = 2 * di + 2 * cfg.ssm_groups * cfg.ssm_state
    conv_ch = di + 2 * cfg.ssm_groups * cfg.ssm_state
    lead = (ng,) if ng else ()
    return {
        "in_proj": _lin_spec(ng, d, w, mp.in_proj),
        "dt_proj": QuantLinearParams(SDS(lead + (d, h), jnp.int8)),
        "conv_w8": SDS(lead + (cfg.ssm_conv, conv_ch), jnp.int8),
        "A_q": SDS(lead + (h,), jnp.int32),
        "D_q": SDS(lead + (h,), jnp.int32),
        "dt_bias_q": SDS(lead + (h,), jnp.int32),
        "norm_gamma_q": SDS(lead + (di,), jnp.int32),
        "out_proj": _lin_spec(ng, di, d, mp.out_proj),
    }


def _sublayer_spec(ng, cfg, plans, kind):
    mix, ff, has_cross = kind
    out = {"norm1": _norm_spec(ng, cfg.d_model, cfg)}
    if mix == "attn" and cfg.is_mla:
        out["attn"] = _mla_spec(ng, cfg, plans.attn)
    elif mix == "attn":
        out["attn"] = _attn_spec(ng, cfg, plans.attn)
    elif mix == "cross":
        out["attn"] = _attn_spec(ng, cfg, plans.cross)
    else:
        out["ssm"] = _mamba_spec(ng, cfg, plans.mamba)
    if has_cross:
        out["cross"] = _attn_spec(ng, cfg, plans.cross)
        out["norm_cross"] = _norm_spec(ng, cfg.d_model, cfg)
    if ff == "moe":
        out["moe"] = _moe_spec(ng, cfg, plans.moe)
        out["norm2"] = _norm_spec(ng, cfg.d_model, cfg)
    elif ff == "ffn":
        out["ffn"] = _ffn_spec(ng, cfg, plans.ffn)
        out["norm2"] = _norm_spec(ng, cfg.d_model, cfg)
    return out


def qparams_spec(cfg: ArchConfig,
                 plans: Optional[qplans.LayerPlans] = None) -> Pytree:
    plans = plans or qplans.build_layer_plans(cfg)
    gl, ng, kinds = layer_group_spec(cfg)
    v, d = cfg.padded_vocab(), cfg.d_model
    spec: Dict[str, Pytree] = {
        "embed_w8": SDS((v, d), jnp.int8),
        "final_norm": _norm_spec(0, d, cfg),
        "head": QuantLinearParams(SDS((d, v), jnp.int8)),
        "head_scale": SDS((v,), jnp.float32),
        "layers": [_sublayer_spec(ng, cfg, plans, kinds[j])
                   for j in range(gl)],
    }
    if cfg.first_k_dense:
        spec["lead_layers"] = _sublayer_spec(cfg.first_k_dense, cfg, plans,
                                             tf.LEAD_KIND)
    if cfg.family == "encdec":
        spec["enc_layers"] = [_sublayer_spec(cfg.enc_layers, cfg, plans,
                                             ("attn", "ffn", False))]
        spec["enc_final_norm"] = _norm_spec(0, d, cfg)
    return spec


def params_spec(cfg: ArchConfig) -> Pytree:
    return jax.eval_shape(
        lambda k: tf.init_params(k, cfg), jax.random.key(0))


def decode_cache_spec(cfg: ArchConfig, batch: int, cache_len: int,
                      with_memory: bool = False):
    def build():
        mem8 = jnp.zeros((batch,
                          cfg.n_img_tokens or 1, cfg.d_model), jnp.int8) \
            if with_memory else None
        return it.init_decode_cache(cfg, batch, cache_len, memory8=mem8)
    return jax.eval_shape(build)
