"""Float checkpoint -> SwiftTron integer parameters (design-time flow,
paper Fig. 17: HuggingFace/PyTorch models + I-BERT quantization -> the
accelerator's constants).

Every weight becomes int8 with per-out-channel scales folded into int32
dyadic multiplier vectors; biases become int32 at the accumulator scale;
norm gammas/betas become the integer constants of the i-LayerNorm unit.
The result is (qparams, plans): the pytree of integer arrays and the
frozen static plan set.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import activations as iact
from repro.core import norms
from repro.models.common import ArchConfig
from repro.models.transformer import LEAD_KIND, layer_group_spec
from repro.ops import QuantLinearParams
from repro.quant import plans as qplans

Pytree = Any

#: layers of a stack quantized at once on host threads (numpy releases
#: the interpreter lock in the float64 work; each thread holds one
#: layer's float64 working copy)
QUANT_WORKERS = 4


def _pc_scales(w: np.ndarray, out_axis: int) -> np.ndarray:
    axes = tuple(i for i in range(w.ndim) if i != out_axis % w.ndim)
    return np.maximum(np.abs(w).max(axis=axes), 1e-8) / 127.0


def _q_linear(w, plan: qplans.LinearPlan, bias=None, stacked: bool = False):
    """w: (K, N) or stacked (..., K, N) -> QuantLinearParams.

    Per-channel scales along the last axis; leading axes (layer-stack /
    expert) keep their own scale vectors.
    """
    w = np.asarray(jax.device_get(w), np.float64)
    s = np.maximum(np.abs(w).max(axis=-2), 1e-8) / 127.0       # (..., N)
    w8 = np.clip(np.round(w / s[..., None, :]), -127, 127).astype(np.int8)
    b_mult = bias32 = None
    if plan.s_out != 0.0:
        ratios = plan.s_in * s / plan.s_out
        b = np.round(ratios * (1 << plan.c))
        assert (np.abs(b) < 2 ** 31).all(), "per-channel multiplier overflow"
        b_mult = jnp.asarray(b.astype(np.int32))
    if bias is not None:
        bias = np.asarray(jax.device_get(bias), np.float64)
        bias32 = jnp.asarray(
            np.round(bias / (plan.s_in * s)).astype(np.int32))
    return QuantLinearParams(jnp.asarray(w8), b_mult, bias32), s


def _q_attn_w(w, plan):
    """(D,H,hd) or stacked (G,D,H,hd) -> flatten head dims."""
    w = np.asarray(jax.device_get(w), np.float64)
    flat = w.reshape(*w.shape[:-2], -1)
    q, _ = _q_linear(flat, plan)
    return q


def _q_norm(p, plan: norms.INormPlan):
    g, b = norms.quantize_norm_weights(
        jnp.asarray(np.asarray(jax.device_get(p["gamma"]), np.float32)),
        jnp.asarray(np.asarray(jax.device_get(p["beta"]), np.float32))
        if "beta" in p else None, plan)
    out = {"gamma_q": g}
    if b is not None:
        out["beta_q"] = b
    return out


def _q_attn(p, plans: qplans.AttnPlan):
    out = {
        "wq": _q_attn_w(p["wq"], plans.qkv),
        "wk": _q_attn_w(p["wk"], plans.qkv),
        "wv": _q_attn_w(p["wv"], plans.qkv),
    }
    wo = np.asarray(jax.device_get(p["wo"]), np.float64)
    wo = wo.reshape(*wo.shape[:-3], -1, wo.shape[-1])
    out["wo"], _ = _q_linear(wo, plans.out)
    for name in ("bq", "bk", "bv"):
        if name in p:
            w_key = "w" + name[1]
            bias = np.asarray(jax.device_get(p[name]), np.float64)
            bias = bias.reshape(*bias.shape[:-2], -1)
            w = np.asarray(jax.device_get(p[w_key]), np.float64)
            w = w.reshape(*w.shape[:-2], -1)
            s = np.maximum(np.abs(w).max(axis=-2), 1e-8) / 127.0
            out[w_key] = out[w_key]._replace(bias32=jnp.asarray(
                np.round(bias / (plans.qkv.s_in * s)).astype(np.int32)))
    return out


def _q_mla(p, plans: qplans.MlaPlan, cfg: ArchConfig):
    """MLA weights: the query and latent-expansion heads flattened, the
    latent projection split into the latent (``wkv_a``, to the residual
    grid for its RMSNorm) and the shared RoPE key (``wk_pe``, int8) —
    per-channel scales are per column, so the split quantizes each
    column as the whole would."""
    r = cfg.kv_lora_rank
    kva = np.asarray(jax.device_get(p["wkv_a"]), np.float64)
    wo = np.asarray(jax.device_get(p["wo"]), np.float64)
    out = {"wq": _q_attn_w(p["wq"], plans.qkv),
           "wkv_a": _q_linear(kva[..., :r], plans.kv_a)[0],
           "wk_pe": _q_linear(kva[..., r:], plans.qkv)[0],
           "kv_norm": _q_norm(p["kv_norm"], plans.kv_norm),
           "wkv_b": _q_attn_w(p["wkv_b"], plans.kv_b),
           "wo": _q_linear(wo.reshape(*wo.shape[:-3], -1, wo.shape[-1]),
                           plans.out)[0]}
    return out


def _q_ffn(p, plans: qplans.FfnPlan):
    out = {}
    out["w1"], s1 = _q_linear(p["w1"], plans.up,
                              bias=p.get("b1"))
    if "w3" in p:
        out["w3"], _ = _q_linear(p["w3"], plans.up)
    out["w2"], _ = _q_linear(p["w2"], plans.down, bias=p.get("b2"))
    return out


def _q_moe(p, plans: qplans.MoePlan, s_router=None):
    out = {}
    w = np.asarray(jax.device_get(p["router"]), np.float64)
    if s_router is None:
        s_router = np.abs(w).max() / 127.0
    out["router"] = QuantLinearParams(jnp.asarray(
        np.clip(np.round(w / s_router), -127, 127).astype(np.int8)))
    if "router_bias" in p:
        # the correction bias adds to sigmoid scores at 2^-SIG_FRAC
        out["router_bias_q"] = jnp.asarray(np.round(
            np.asarray(jax.device_get(p["router_bias"]), np.float64)
            * (1 << iact.SIG_FRAC)).astype(np.int32))
    # w1 and w3 side by side (one grouped launch): per-channel scales
    # are per column, so each half quantizes as it would alone
    w1 = np.asarray(jax.device_get(p["w1"]), np.float64)
    if "w3" in p:
        w1 = np.concatenate(
            [w1, np.asarray(jax.device_get(p["w3"]), np.float64)], -1)
    out["w13"], _ = _q_linear(w1, plans.expert.up)
    out["w2"], _ = _q_linear(p["w2"], plans.expert.down)
    if "shared" in p:
        out["shared"] = _q_ffn(p["shared"], plans.shared)
    return out, s_router


def _q_mamba(p, mp: qplans.MambaPlan, cfg: ArchConfig, s_dtw=None,
             s_conv=None):
    w = np.asarray(jax.device_get(p["in_proj"]), np.float64)
    n_zxbc = w.shape[-1] - cfg.ssm_heads
    out = {}
    out["in_proj"], _ = _q_linear(w[..., :n_zxbc], mp.in_proj)
    wdt = w[..., n_zxbc:]
    if s_dtw is None:
        s_dtw = float(np.abs(wdt).max()) / 127.0
    out["dt_proj"] = QuantLinearParams(jnp.asarray(
        np.clip(np.round(wdt / s_dtw), -127, 127).astype(np.int8)))
    cw = np.asarray(jax.device_get(p["conv_w"]), np.float64)
    if s_conv is None:
        s_conv = float(np.abs(cw).max()) / 127.0
    out["conv_w8"] = jnp.asarray(
        np.clip(np.round(cw / s_conv), -127, 127).astype(np.int8))
    a = np.exp(np.asarray(jax.device_get(p["A_log"]), np.float64))
    out["A_q"] = jnp.asarray(np.round(a / mp.s_A).astype(np.int32))
    # D on the 2^-16 state grid (D*x enters y in h units)
    out["D_q"] = jnp.asarray(np.round(
        np.asarray(jax.device_get(p["D"]), np.float64) / mp.s_h)
        .astype(np.int32))
    out["dt_bias_q"] = jnp.asarray(np.round(
        np.asarray(jax.device_get(p["dt_bias"]), np.float64)
        / (mp.in_proj.s_in * s_dtw)).astype(np.int32))
    g, _ = norms.quantize_norm_weights(
        jnp.asarray(np.asarray(jax.device_get(p["norm_gamma"]),
                               np.float32)), None, mp.norm)
    out["norm_gamma_q"] = g
    out["out_proj"], _ = _q_linear(p["out_proj"], mp.out_proj)
    return out, s_dtw, s_conv


def _q_sublayer(p, plans: qplans.LayerPlans, cfg: ArchConfig, kind,
                calib_sink: dict, scales=None):
    """``scales``: per-tensor scales fixed over a whole layer stack
    (``_stack_scales``) — absent, they are measured on ``p`` itself."""
    mix, ff, has_cross = kind
    scales = scales or {}
    out = {"norm1": _q_norm(p["norm1"], plans.norm)}
    if mix == "attn" and cfg.is_mla:
        out["attn"] = _q_mla(p["attn"], plans.attn, cfg)
    elif mix in ("attn", "cross"):
        out["attn"] = _q_attn(p["attn"],
                              plans.attn if mix == "attn" else plans.cross)
    else:
        out["ssm"], s_dtw, s_conv = _q_mamba(
            p["ssm"], plans.mamba, cfg, scales.get("s_dtw"),
            scales.get("s_conv"))
        calib_sink["s_dtw"] = s_dtw
        calib_sink["s_conv"] = s_conv
    if has_cross:
        out["cross"] = _q_attn(p["cross"], plans.cross)
        out["norm_cross"] = _q_norm(p["norm_cross"], plans.norm)
    if ff == "moe":
        out["moe"], s_router = _q_moe(p["moe"], plans.moe,
                                      scales.get("s_router"))
        calib_sink["s_router"] = s_router
    elif ff == "ffn":
        out["norm2"] = _q_norm(p["norm2"], plans.norm)
        out["ffn"] = _q_ffn(p["ffn"], plans.ffn)
    if ff == "moe":
        out["norm2"] = _q_norm(p["norm2"], plans.norm)
    return out


def _q_stacked(p_stack, plans: qplans.LayerPlans, cfg: ArchConfig, kind):
    """Quantize a layer stack (every leaf has the stack on axis 0) one
    layer at a time and restack the integer results on the host.

    Identical to ``_q_sublayer`` on the whole stack — per-channel scales
    are per layer along the leading axis, and the few per-tensor scales
    (MoE router, Mamba dt / conv) are measured once over the stack
    first — but the float64 working copy is one layer's, not the
    stack's: at published widths a stacked FFN matrix alone is
    gigabytes of float64 (40 x 2048 x 8192 x 8 bytes for Granite-3-2B).
    The layers run :data:`QUANT_WORKERS` at a time on host threads: the
    same functions on the same inputs, each layer's result its own.
    """
    n = jax.tree.leaves(p_stack)[0].shape[0]
    scales = _stack_scales(p_stack, cfg, kind)

    def one(i):
        return jax.tree.map(np.asarray, _q_sublayer(
            jax.tree.map(lambda t: t[i], p_stack), plans, cfg, kind, {},
            scales))
    with ThreadPoolExecutor(min(QUANT_WORKERS, n)) as pool:
        slices = list(pool.map(one, range(n)))
    return jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)), *slices)


def _stack_scales(p_stack, cfg: ArchConfig, kind) -> dict:
    """The per-tensor scales ``_q_moe`` / ``_q_mamba`` take over a whole
    stack, measured without a float64 copy (max |w| is exact in any
    float format, so the value equals the float64 one)."""
    mix, ff, _ = kind

    def amax(x):
        return float(jnp.abs(x).max()) / 127.0
    out = {}
    if ff == "moe":
        out["s_router"] = amax(p_stack["moe"]["router"])
    if mix not in ("attn", "cross"):
        w = p_stack["ssm"]["in_proj"]
        out["s_dtw"] = amax(w[..., w.shape[-1] - cfg.ssm_heads:])
        out["s_conv"] = amax(p_stack["ssm"]["conv_w"])
    return out


def quantize_params(params: Pytree, cfg: ArchConfig
                    ) -> Tuple[Pytree, qplans.LayerPlans]:
    """Float params -> (qparams, plans).  Two passes: measure the per-tensor
    calibration scales, freeze the plans, then quantize everything."""
    emb = np.asarray(jax.device_get(params["embed"]), np.float64)
    calib = {"s_emb": float(np.abs(emb).max()) / 127.0}
    # first pass purely to collect s_router / s_dtw / s_conv
    probe_plans = qplans.build_layer_plans(cfg, calib)
    gl, ng, kinds = layer_group_spec(cfg)
    sink: Dict[str, float] = {}
    for j, kind in enumerate(kinds):
        _q_sublayer(jax.tree.map(lambda t: t[:1], params["layers"][j]),
                    probe_plans, cfg, kind, sink)
    calib.update(sink)
    plans = qplans.build_layer_plans(cfg, calib)

    qparams: Dict[str, Pytree] = {}
    qparams["embed_w8"] = jnp.asarray(np.clip(
        np.round(emb / plans.embed.s_emb), -127, 127).astype(np.int8))
    qparams["final_norm"] = _q_norm(params["final_norm"], plans.final_norm)
    # no lm_head (tied, or an encoder whose MLM head shares the word
    # embedding, as RoBERTa's does): the head is the embedding
    head_w = emb.T if "lm_head" not in params else np.asarray(
        jax.device_get(params["lm_head"]), np.float64)
    s_head = _pc_scales(head_w, 1)
    qparams["head"] = QuantLinearParams(jnp.asarray(np.clip(
        np.round(head_w / s_head[None, :]), -127, 127).astype(np.int8)))
    qparams["head_scale"] = jnp.asarray(s_head.astype(np.float32))
    qparams["layers"] = [
        _q_stacked(params["layers"][j], plans, cfg, kinds[j])
        for j in range(gl)
    ]
    if cfg.first_k_dense:
        qparams["lead_layers"] = _q_stacked(params["lead_layers"], plans,
                                            cfg, LEAD_KIND)
    if cfg.family == "encdec":
        qparams["enc_layers"] = [
            _q_stacked(params["enc_layers"][0], plans, cfg,
                       ("attn", "ffn", False))]
        qparams["enc_final_norm"] = _q_norm(params["enc_final_norm"],
                                            plans.norm)
    return qparams, plans
