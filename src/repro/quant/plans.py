"""Design-time quantization plans (SwiftTron §III-A "Scaling Factor Design").

A *plan* is the frozen set of integer constants one layer kind needs:
dyadic requant pairs, i-exp/i-erf polynomial constants, reciprocal widths.
Plans are plain NamedTuples of Python ints/floats — they are **static**
(closed over by the traced functions, appearing as scalar constants in the
lowered HLO), exactly like the ASIC's design-time q_{1..8} registers.

Activation scales are shared across layers of the same kind (DESIGN.md §4)
so stacked-parameter ``lax.scan`` layers stay homogeneous; per-channel
weight scales live in the quantized parameter pytree as int32 multiplier
vectors with a plan-level shared shift.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Union

import numpy as np

from repro.core import activations as iact
from repro.core import attention as iattn
from repro.core import intmath, norms
from repro.core import softmax as ism
from repro.core.dyadic import Dyadic, fit_dyadic
from repro.models.common import ArchConfig


class LinearPlan(NamedTuple):
    """INT8 matmul + per-channel dyadic requant epilogue."""
    s_in: float
    s_out: float            # 0.0 -> keep int32 accumulator (no requant)
    out_bits: int
    c: int                  # shared shift for the per-channel multipliers
    pre: int
    k_dim: int              # contraction size (accumulator bound)

    @property
    def acc_qmax(self) -> int:
        return self.k_dim * 127 * 127


def make_linear_plan(s_in: float, s_w_max: float, s_out: float, k_dim: int,
                     out_bits: int = 8) -> LinearPlan:
    """Size the shared (c, pre) for the worst-case channel ratio."""
    acc_qmax = k_dim * 127 * 127
    if s_out == 0.0:
        return LinearPlan(s_in, 0.0, 32, 0, 0, k_dim)
    ratio_max = s_in * s_w_max / s_out
    dn = fit_dyadic(ratio_max, acc_qmax)
    return LinearPlan(s_in, s_out, out_bits, dn.c, dn.pre, k_dim)


def perchannel_multipliers(plan: LinearPlan, s_w: np.ndarray) -> np.ndarray:
    """int32 multiplier per out-channel for the plan's shared (c, pre)."""
    ratios = plan.s_in * np.asarray(s_w, np.float64) / plan.s_out
    b = np.round(ratios * (1 << plan.c)).astype(np.int64)
    assert (b >= 0).all() and (b < 2 ** 31).all()
    return b.astype(np.int32)


class AttnPlan(NamedTuple):
    qkv: LinearPlan
    attn: iattn.IAttnPlan
    out: LinearPlan          # o-proj: s_act8 -> s_res


class MlaPlan(NamedTuple):
    """Multi-head latent attention (no q LoRA), prefill form."""
    qkv: LinearPlan          # W_q and the RoPE key W_kpe: s_act8 -> s_act8
    kv_a: LinearPlan         # the K/V latent W_kva: s_act8 -> s_res
    kv_norm: norms.INormPlan  # RMSNorm of the latent: s_res -> s_act8
    kv_b: LinearPlan         # W_kvb: latent s_act8 -> k_nope, v at s_act8
    attn: iattn.IAttnPlan    # 16 x (nope + rope) queries/keys, v heads
    out: LinearPlan          # o-proj over H * v_head_dim: -> s_branch


class SigmoidGatePlan(NamedTuple):
    """The gates of a sigmoid router (DeepSeek-V3's ``noaux_tc``): the
    i-sigmoid of the int32 logits at 2^-15, the routed scaling as an
    integer at 2^-``rs_frac``, and gates at 2^-``gate_frac``."""
    sig: iact.ISigmoidPlan
    rs_b: int
    rs_frac: int
    gate_frac: int


#: a sigmoid router's gate grid and routed-scaling precision: gates at
#: 2^-12 times a residual branch of at most 14 bits stay within int32
#: for a routed scaling below 8
GATE_FRAC, RS_FRAC = 12, 10


def make_sigmoid_gate(s_logit: float, qmax_logit: int,
                      routed_scaling: float) -> SigmoidGatePlan:
    rs_b = int(round(routed_scaling * (1 << RS_FRAC)))
    if not 0 < rs_b < 8 << RS_FRAC:
        raise ValueError(f"routed_scaling {routed_scaling} outside (0, 8)")
    return SigmoidGatePlan(iact.make_isigmoid(s_logit, qmax_logit), rs_b,
                           RS_FRAC, GATE_FRAC)


class FfnPlan(NamedTuple):
    up: LinearPlan           # w1 (and w3): s_act8 -> s_act10
    act_gelu: Optional[iact.IGeluActPlan]
    act_silu: Optional[iact.ISiluPlan]
    dn_gate: Optional[Dyadic]   # silu(h1)*h3 product -> s_act8
    down: LinearPlan         # w2: s_act8 -> s_res


class MoePlan(NamedTuple):
    router: LinearPlan       # s_act8 -> int32 logits
    gate: Union[ism.ISoftmaxPlan, SigmoidGatePlan]   # by ``cfg.router``
    expert: FfnPlan
    shared: Optional[FfnPlan]


class NormPlan(NamedTuple):
    plan: norms.INormPlan    # s_res int32 -> s_act8 int8


class EmbedPlan(NamedTuple):
    s_emb: float             # int8 embedding table scale
    dn_res: Dyadic           # s_emb * embedding_multiplier -> s_res


class HeadPlan(NamedTuple):
    s_in: float              # logits stay int32 at s_in * s_w (dequant
    #                          host-side); s_act8 / logits_scaling


class MambaPlan(NamedTuple):
    in_proj: LinearPlan      # s_act8 -> s_act8 (z,x,B,C) ; dt handled below
    dn_dt_in: Dyadic         # accumulator -> s_dt_in (10 bit)
    s_dt_in: float
    softplus: iact.ISoftplusPlan     # -> s_dt
    s_dt: float
    s_A: float
    dn_dtA: Dyadic                   # (s_dt * s_A) -> 2^-14 i-exp grid
    iexp_decay: intmath.IExpPlan     # at 2^-14
    dn_decay16: Dyadic
    dn_h: Dyadic             # dt*B*x contribution -> s_h
    s_h: float
    qmax_h: int
    dn_h8: Dyadic            # h -> int8 at s_h8
    s_h8: float
    dn_y: Dyadic             # C*h8 acc -> s_act8
    silu_z: iact.ISiluPlan
    dn_z10: Dyadic           # z (int8, s_act8) -> 10-bit grid for i-exp
    dn_gate: Dyadic          # y * sig16 -> s_act8
    norm: norms.INormPlan
    out_proj: LinearPlan
    dn_conv: Dyadic          # conv acc (s8 * s_conv) -> conv grid (+-32)
    silu_conv: iact.ISiluPlan    # conv activation -> s_xbc
    s_xbc: float             # x/B/C grid after conv+silu (wider than s8)


class LayerPlans(NamedTuple):
    """Everything the integer path of one architecture needs."""
    cfg_name: str
    embed: EmbedPlan
    norm: norms.INormPlan
    attn: Optional[Union[AttnPlan, MlaPlan]]    # MlaPlan where MLA
    ffn: Optional[FfnPlan]
    moe: Optional[MoePlan]
    mamba: Optional[MambaPlan]
    cross: Optional[AttnPlan]
    head: HeadPlan
    final_norm: norms.INormPlan


S_W8 = 2.0 / 127.0          # nominal per-channel weight scale bound


def _ffn_plan(cfg: ArchConfig, d_in: int, d_ff: int) -> FfnPlan:
    s8, s10 = cfg.s_act8, cfg.s_act10
    up = make_linear_plan(s8, S_W8, s10, d_in, out_bits=11)
    if cfg.activation == "swiglu":
        silu = iact.make_isilu(s10, 1024, s_out=s8)
        # gate: silu_out(int8, s8) * h3(10bit, s10) -> requant to s8
        dn_gate = fit_dyadic(s8 * s10 / s8, 127 * 1024)
        gelu = None
    else:
        gelu = iact.make_igelu_act(s10, 1024, s_out=s8)
        silu, dn_gate = None, None
    down = make_linear_plan(s8, S_W8, cfg.s_branch, d_ff, out_bits=14)
    return FfnPlan(up, gelu, silu, dn_gate, down)


def build_layer_plans(cfg: ArchConfig, calib: Optional[dict] = None
                      ) -> LayerPlans:
    """``calib``: measured per-tensor scales from quant.convert — keys
    s_emb / s_router / s_conv / s_dtw (defaults are the design nominals)."""
    calib = dict(calib or {})
    s8 = cfg.s_act8
    d = cfg.d_model
    norm_plan = norms.make_inorm(d, cfg.s_res, cfg.qmax_res,
                                 s_gamma=2.0 / 127.0, s_out=s8,
                                 subtract_mean=(cfg.norm == "layernorm"))
    s_emb = calib.get("s_emb", s8)
    embed = EmbedPlan(s_emb, fit_dyadic(
        s_emb * cfg.embedding_multiplier / cfg.s_res, 127))

    attn = cross = None
    if cfg.family in ("dense", "encdec", "vlm", "moe", "hybrid", "encoder"):
        qkv = make_linear_plan(s8, S_W8, s8, d)
        ia = iattn.make_iattention(cfg.hd, s8, s8, s8, s8,
                                   score_scale=cfg.attention_multiplier)
        out = make_linear_plan(s8, S_W8, cfg.s_branch,
                               cfg.n_heads * cfg.vd, out_bits=14)
        if cfg.is_mla:
            r = cfg.kv_lora_rank
            attn = MlaPlan(
                qkv, make_linear_plan(s8, S_W8, cfg.s_res, d, out_bits=14),
                norms.make_inorm(r, cfg.s_res, cfg.qmax_res,
                                 s_gamma=2.0 / 127.0, s_out=s8,
                                 subtract_mean=False),
                make_linear_plan(s8, S_W8, s8, r), ia, out)
        else:
            attn = AttnPlan(qkv, ia, out)
        if cfg.family in ("encdec", "vlm"):
            cross = attn

    ffn = moe = None
    if cfg.n_experts > 0:
        router = make_linear_plan(s8, S_W8, 0.0, d)
        # router logits int32 at s8 * s_router (per-tensor router weights)
        s_router = calib.get("s_router", S_W8)
        if cfg.router == "sigmoid":
            gate = make_sigmoid_gate(s8 * s_router, router.acc_qmax,
                                     cfg.routed_scaling)
        else:
            gate = ism.make_isoftmax(s8 * s_router, router.acc_qmax)
        f = cfg.moe_d_ff or cfg.d_ff
        expert = _ffn_plan(cfg, d, f)
        shared = _ffn_plan(cfg, d, f * cfg.n_shared_experts) \
            if cfg.n_shared_experts else None
        moe = MoePlan(router, gate, expert, shared)
    if cfg.family != "ssm" and not (cfg.n_experts and cfg.moe_every == 1
                                    and not cfg.first_k_dense):
        ffn = _ffn_plan(cfg, d, cfg.d_ff)

    mamba = None
    if cfg.family in ("ssm", "hybrid"):
        mamba = _mamba_plan(cfg, calib)

    head = HeadPlan(s8 / cfg.logits_scaling)
    return LayerPlans(cfg.name, embed, norm_plan, attn, ffn, moe, mamba,
                      cross, head, norm_plan)


def _mamba_plan(cfg: ArchConfig, calib: Optional[dict] = None) -> MambaPlan:
    calib = dict(calib or {})
    s8, s10 = cfg.s_act8, cfg.s_act10
    d = cfg.d_model
    in_proj = make_linear_plan(s8, S_W8, s8, d)
    acc_q = in_proj.acc_qmax
    s_dt_in = 16.0 / 1024.0
    s_dtw = calib.get("s_dtw", S_W8)
    dn_dt_in = fit_dyadic(s8 * s_dtw / s_dt_in, acc_q)
    # Δt grid: fine resolution over [0, 2] (typical trained Δt is 1e-3..1;
    # i_softplus clips at out_bits=13 -> saturation at 8191*s_dt = 2.0)
    s_dt = 1.0 / (1 << 12)
    softplus = iact.make_isoftplus(s_dt_in, 1024, s_out=s_dt)
    s_A = 16.0 / 1024.0
    # bring dt*A onto the shared 2^-14 i-exp grid (its own scale is too
    # fine for representable polynomial constants)
    qmax_dtA = (1 << 13) * 1024
    dn_dtA = fit_dyadic(s_dt * s_A / 2.0 ** -14, qmax_dtA)
    iexp_decay = intmath.make_iexp(2.0 ** -14)
    dn_decay16 = fit_dyadic(iexp_decay.s_out / 2.0 ** -15,
                            iexp_decay.q_one + 1)
    # SSD state: typical |h| is O(1) (geometric sum ~ B*x/A); keep 2^-16
    # resolution with saturation at +-32 (qmax 2^21)
    s_h = 2.0 ** -16
    qmax_h = 1 << 27          # +-2048 head-state range before saturation
    # contribution dt * B * x: scale s_dt * s8 * s8, |q| <= 2^13*127*127
    dn_h = fit_dyadic(s_dt * s8 * s8 / s_h, (1 << 13) * 127 * 127)
    s_h8 = 4.0 / 127.0
    dn_h8 = fit_dyadic(s_h / s_h8, qmax_h)
    # y = C * h8 over ssm_state: acc <= N*127*127, scale s8*s_h8 -> s8
    dn_y = fit_dyadic(s_h8, cfg.ssm_state * 127 * 127)
    silu_z = iact.make_isilu(s10, 1024, s_out=s8)   # gate on the 10-bit grid
    dn_z10 = fit_dyadic(s8 / s10, 127)
    dn_gate = fit_dyadic(2.0 ** -15, 127 << 15)     # (unused on the BFP path)
    # pre-norm y is unnormalised by construction (mamba2 applies RMSNorm
    # exactly because y = C*h grows); the integer path feeds the norm a
    # per-row dynamic block-floating-point value at <=12 bits — RMSNorm is
    # scale-invariant so the row shift cancels exactly.
    norm = norms.make_inorm(cfg.ssm_d_inner, 1.0, 1 << 11,
                            s_gamma=2.0 / 127.0, s_out=s8,
                            subtract_mean=False)
    out_proj = make_linear_plan(s8, S_W8, cfg.s_branch, cfg.ssm_d_inner,
                                out_bits=14)
    s_conv = calib.get("s_conv", S_W8)
    # conv+silu outputs (x/B/C) have a wider dynamic range than the s8
    # grid: accumulate at +-32 (10-bit) and emit int8 on a +-16 grid
    s_conv_grid = 32.0 / 1024.0
    s_xbc = 16.0 / 127.0
    dn_conv = fit_dyadic(s8 * s_conv / s_conv_grid,
                         cfg.ssm_conv * 127 * 127)
    silu_conv = iact.make_isilu(s_conv_grid, 1024, s_out=s_xbc)
    # refit the state-path dyadics for the s_xbc operand grid
    dn_h = fit_dyadic(s_dt * s_xbc * s_xbc / s_h, (1 << 13) * 127 * 127)
    dn_y = fit_dyadic(s_xbc * s_h8 / s8, cfg.ssm_state * 127 * 127)
    return MambaPlan(in_proj, dn_dt_in, s_dt_in, softplus, s_dt, s_A,
                     dn_dtA, iexp_decay, dn_decay16, dn_h, s_h, qmax_h,
                     dn_h8, s_h8, dn_y, silu_z, dn_z10, dn_gate, norm,
                     out_proj, dn_conv, silu_conv, s_xbc)
