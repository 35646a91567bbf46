"""Whole-model integer-range certification (the abstract interpreter).

:func:`certify_config` walks one architecture's design-time plans
(``quant.plans.build_layer_plans``) layer-kind by layer-kind, pushing
worst-case :class:`~repro.analysis.ranges.IntRange` intervals through the
transfer functions of every op in the ``repro.ops`` API — ``int8_matmul``,
``int8_matmul_packed``, ``int_gelu``, ``int_layernorm``,
``int_attention``, ``int_decode_attention`` / ``int_paged_prefill``
(both also at their int4-KV-page operand ranges), and the MoE gate's
``i_softmax`` — at a given
``(seq_len, cache_len)``, and raises a typed, location-bearing
:class:`~repro.analysis.budgets.BitBudgetError` if *any* intermediate of
the exact integer computation could leave int32.  On success it returns
a :class:`ConfigReport` with per-op worst-case bits, headroom and the
predicted kernel path (fused vs fallback, via
:mod:`repro.analysis.contracts`).

On top of the op walk, :func:`~repro.analysis.ranges.audit_dyadics`
re-proves the ``fit_dyadic`` staging invariant of **every** dyadic in the
plan tree (including the ~20 Mamba-branch constants) at its declared
``qmax_in`` — so a hand-edited constant that drifts from the fit contract
fails certification even if no op-level transfer touches it.

What is *assumed* rather than proven is returned in
``ConfigReport.assumptions`` (and documented in docs/ANALYSIS.md): the
residual-stream calibration bound ``qmax_res``, the nominal folded-bias
bound, and the ±127 design operand grid.
"""
from __future__ import annotations

import dataclasses

from repro.analysis import contracts
from repro.analysis.budgets import (MAX_ROWSUM_LEN, MAX_SQ, bits_for,
                                    static_check)
from repro.analysis.ranges import (INT4, INT4_KV, INT8, MSR4_DELTA_MAX,
                                   IntRange, audit_dyadics, pv_bounds,
                                   t_attention_acc, t_attention_weights,
                                   t_clip,
                                   t_dyadic, t_dyadic_perchannel, t_gelu,
                                   t_layernorm, t_matmul_acc,
                                   t_requant_spec, t_silu, t_softmax)

#: nominal folded-bias bound at accumulator scale: |B| <= 4 real units
#: over s_act8 * S_W8 ~ 1e-3 -> ~4e3; listed as an assumption per config
BIAS_QMAX = 1 << 12


@dataclasses.dataclass(frozen=True)
class OpReport:
    """One certified op instance at one model-walk location."""

    op: str                 # the repro.ops API name
    layer: str              # model-walk location, e.g. "attn.qkv"
    worst: int              # worst-case |q| across the op's intermediates
    path: str = "exact"     # predicted kernel path (fused / fallback / ...)
    note: str = ""

    @property
    def bits(self) -> int:
        return bits_for(self.worst) + 1     # sign bit included

    @property
    def headroom_bits(self) -> int:
        return 32 - self.bits


@dataclasses.dataclass
class ConfigReport:
    """Certification result for one registry config."""

    name: str
    seq_len: int
    cache_len: int
    ops: list
    n_dyadics: int          # plan-tree dyadics whose staging was re-proved
    assumptions: list

    @property
    def worst_bits(self) -> int:
        return max(o.bits for o in self.ops)

    @property
    def min_headroom_bits(self) -> int:
        return min(o.headroom_bits for o in self.ops)


class _Track:
    """Collect named intermediates; ``worst`` is the certified maximum."""

    def __init__(self):
        self.vals = []

    def __call__(self, name: str, r):
        q = r.qmax if isinstance(r, IntRange) else int(r)
        self.vals.append((name, q))
        return r

    @property
    def worst(self) -> int:
        return max(q for _, q in self.vals) if self.vals else 0


# ======================================================================
# the seven per-op checkers
# ======================================================================

def plan_b_max(plan) -> int:
    """The sound per-channel multiplier bound for a ``LinearPlan``.

    The plan's shared ``(c, pre)`` come from ``fit_dyadic`` at the
    worst-case channel ratio (``s_w <= S_W8``, the design's nominal
    weight-scale bound — listed as an assumption), so every channel's
    ``perchannel_multipliers`` entry is bounded by that fit's own ``b``
    — typically in [2^14, 2^15), far tighter than the generic 2^15-1."""
    from repro.core.dyadic import fit_dyadic
    from repro.quant.plans import S_W8
    dn = fit_dyadic(plan.s_in * S_W8 / plan.s_out, plan.acc_qmax)
    assert (dn.c, dn.pre) == (plan.c, plan.pre), (dn, plan)
    return dn.b


def check_int8_matmul(plan, layer: str, x: IntRange = INT8,
                      bias_qmax: int = BIAS_QMAX, op: str = "int8_matmul"):
    """A ``quant.plans.LinearPlan`` matmul: int8·int8 → int32 acc (+bias)
    → per-channel dyadic requant (or raw when ``s_out == 0``)."""
    t = _Track()
    acc = t("accumulator", t_matmul_acc(
        plan.k_dim, x, bias=IntRange.symmetric(bias_qmax),
        op=op, layer=layer))
    if plan.s_out == 0.0:                      # raw int32 logits
        out = acc
    else:
        out = t_clip(t("requant staging", t_dyadic_perchannel(
            acc, plan.c, plan.pre, b_max=plan_b_max(plan),
            op=op, layer=layer)), plan.out_bits)
    return out, OpReport(op, layer, t.worst, path="pallas")


def check_int8_matmul_packed(plan, layer: str, x: IntRange = INT8,
                             bias_qmax: int = BIAS_QMAX,
                             op: str = "int8_matmul_packed"):
    """The sub-8-bit weight tier: the packed matmul accumulates the
    nibble operand (``|w| <= 7``) and — for msr4 — the outlier-lane
    correction (``|delta| <= 120``, distinct rows per group) as separate
    int32 partials whose sum is the dense accumulator.  Element-wise
    ``|nib| + |delta| == |w| <= 127``, so the combined range is exactly
    the dense ``k·|x|·127`` budget; the split pieces are certified
    individually because the kernels materialize them."""
    t = _Track()
    t("nibble accumulator", t_matmul_acc(
        plan.k_dim, x, w_qmax=INT4.qmax,
        what="packed nibble accumulator", op=op, layer=layer))
    t("outlier correction", t_matmul_acc(
        plan.k_dim, x, w_qmax=MSR4_DELTA_MAX,
        what="msr4 outlier correction", op=op, layer=layer))
    acc = t("accumulator", t_matmul_acc(
        plan.k_dim, x, bias=IntRange.symmetric(bias_qmax),
        op=op, layer=layer))
    if plan.s_out == 0.0:
        out = acc
    else:
        out = t_clip(t("requant staging", t_dyadic_perchannel(
            acc, plan.c, plan.pre, b_max=plan_b_max(plan),
            op=op, layer=layer)), plan.out_bits)
    return out, OpReport(op, layer, t.worst, path="pallas", note="msr4")


def check_int_softmax(sm, score: IntRange, rowlen: int, layer: str,
                      exact: bool = True, op: str = "int_softmax"):
    t = _Track()
    t("scores", score)
    out = t_softmax(sm, score, rowlen, exact_rowsum=exact,
                    op=op, layer=layer)
    if exact:
        t("row sum", rowlen * (1 << 15))
    return out, OpReport(op, layer, t.worst,
                         path="exact" if exact else "streaming")


def check_int_gelu(ffn, x: IntRange, layer: str, op: str = "int_gelu"):
    """The FFN activation stage (i-GELU, or i-SiLU + gate for SwiGLU)."""
    t = _Track()
    if ffn.act_gelu is not None:
        t("i-gelu product", x.qmax * 2 * ffn.act_gelu.gelu.q_one)
        out = t_gelu(ffn.act_gelu, x, op=op, layer=layer)
        note = "i-gelu"
    else:
        t("i-silu product", x.qmax << 15)
        gate8 = t_silu(ffn.act_silu, x, op=op, layer=layer)
        prod = IntRange.symmetric(
            static_check(gate8.qmax * x.qmax, "swiglu gate product",
                         op=op, layer=layer))
        t("swiglu gate product", prod)
        out = t_clip(t_dyadic(prod, ffn.dn_gate, what="swiglu gate dyadic",
                              op=op, layer=layer), 8)
        note = "i-silu + swiglu gate"
    return out, OpReport(op, layer, t.worst, note=note)


def check_int_layernorm(plan, layer: str, x: IntRange = None,
                        op: str = "int_layernorm"):
    t = _Track()
    x = IntRange.symmetric(plan.qmax_in) if x is None else x
    y_max = x.qmax * 2 if plan.subtract_mean else x.qmax
    t("normalisation product",
      y_max << (plan.recip_bits + plan.pre_shift))
    out = t_layernorm(plan, x, op=op, layer=layer)
    return out, OpReport(op, layer, t.worst,
                         note="layernorm" if plan.subtract_mean
                         else "rmsnorm")


def _attention_core(ia, rowlen: int, layer: str, op: str, t: _Track,
                    kv_qmax: int = 127):
    """Shared Q·Kᵀ → Shiftmax weights → P·V → row division → dn_out
    epilogue range walk.

    ``kv_qmax`` is the K/V operand magnitude: 127 on the int8 grid, or
    ``INT4_KV.qmax`` (7 << KV4_SHIFT = 112) when the pages store packed
    nibbles that the kernel dequantizes in-launch — strictly inside the
    int8 grid, so the packed tier certifies wherever the dense one does."""
    score = t("scores", t_matmul_acc(
        ia.head_dim, w_qmax=kv_qmax,
        what="attention score accumulator", op=op, layer=layer))
    exact = rowlen <= MAX_ROWSUM_LEN
    u = t_attention_weights(ia.sm, score, op=op, layer=layer)
    t("P*V accumulator", pv_bounds(rowlen, kv_qmax, u.hi)[1])
    acc = t("normalised accumulator", t_attention_acc(
        rowlen, v_qmax=kv_qmax, u_max=u.hi, op=op, layer=layer))
    out = t_clip(t("epilogue staging", t_dyadic(
        acc, ia.dn_out, what="attention epilogue dyadic",
        op=op, layer=layer)), 8)
    return out, exact


def check_int_attention(ia, seq_len: int, layer: str,
                        op: str = "int_attention"):
    t = _Track()
    out, exact = _attention_core(ia, seq_len, layer, op, t)
    bq = contracts.fit_block(128, seq_len, 8)
    bkv = contracts.fit_block(128, seq_len)
    fused = contracts.can_tile(seq_len, seq_len, bq, bkv)
    path = "fused" if fused else \
        ("fallback:two-pass-streaming" if not exact else "fallback:oracle")
    return out, OpReport(op, layer, t.worst, path=path)


def check_int_decode_attention(ia, cache_len: int, layer: str,
                               sq: int = MAX_SQ, kv_pack: bool = False,
                               page_size: int = 0,
                               op: str = "int_decode_attention"):
    """``page_size``: the paged pool's page (the KV block tiles a page);
    0 = the contiguous layout (the block tiles ``cache_len``)."""
    t = _Track()
    kv_qmax = INT4_KV.qmax if kv_pack else 127
    out, exact = _attention_core(ia, cache_len, layer, op, t,
                                 kv_qmax=kv_qmax)
    bkv = contracts.fit_block(128, page_size or cache_len)
    fused = contracts.can_tile_decode(sq, cache_len, ia.head_dim, bkv)
    path = "fused" if fused else \
        ("fallback:two-pass-streaming" if not exact else "fallback:oracle")
    return out, OpReport(op, layer, t.worst, path=path,
                         note="int4 kv pages" if kv_pack else "")


def check_int_paged_prefill(ia, cache_len: int, layer: str,
                            chunk: int = 256, page_size: int = 64,
                            wo=None, n_heads: int = 0,
                            n_kv_heads: int = 0, n_out: int = 0,
                            kv_pack: bool = False,
                            op: str = "int_paged_prefill"):
    """``wo``: the o-projection ``LinearPlan`` when certifying the
    folded-wo launch epilogue (int8 attention tile → int8 matmul →
    per-channel requant inside the same kernel).  With the head counts
    and ``n_out`` the report notes a projection too wide to fold into
    the chip's VMEM (the backend then runs it unfolded, same integers:
    ``contracts.can_fold_wo``)."""
    t = _Track()
    kv_qmax = INT4_KV.qmax if kv_pack else 127
    out, exact = _attention_core(ia, cache_len, layer, op, t,
                                 kv_qmax=kv_qmax)
    if wo is not None:
        t("folded wo accumulator", t_matmul_acc(
            wo.k_dim, out, bias=IntRange.symmetric(BIAS_QMAX),
            what="folded wo accumulator", op=op, layer=layer))
        t("folded wo staging", t_dyadic_perchannel(
            IntRange.symmetric(t.vals[-1][1]), wo.c, wo.pre,
            b_max=plan_b_max(wo), what="folded wo requant",
            op=op, layer=layer))
    bq = contracts.fit_block(128, chunk, 8)
    bkv = contracts.fit_block(128, page_size)
    fused = contracts.can_tile_prefill(cache_len, ia.head_dim, bq, bkv)
    path = "fused" if fused else \
        ("fallback:two-pass-streaming" if not exact else "fallback:oracle")
    notes = ["int4 kv pages"] if kv_pack else []
    if wo is not None and n_out and not contracts.can_fold_wo(
            bq, n_heads, n_kv_heads, ia.head_dim, bkv, n_out,
            kv_d=ia.head_dim // 2 if kv_pack else ia.head_dim):
        notes.append("wo unfolded (VMEM)")
    return out, OpReport(op, layer, t.worst, path=path,
                         note="; ".join(notes))


def check_requant_spec(spec, r: IntRange, op: str, layer: str,
                       b_max: int = None) -> IntRange:
    """Certify one :class:`repro.ops.RequantSpec` epilogue against an
    incoming range — the entry point the regression tests drive with
    deliberately-unsafe specs."""
    kw = {} if b_max is None else {"b_max": b_max}
    return t_requant_spec(r, spec, op=op, layer=layer, **kw)


# ======================================================================
# the model walk
# ======================================================================

def _check_ffn(ffn, prefix: str, ops):
    h10, rep = check_int8_matmul(ffn.up, f"{prefix}.up")
    ops.append(rep)
    a8, rep = check_int_gelu(ffn, h10, f"{prefix}.act")
    ops.append(rep)
    y, rep = check_int8_matmul(ffn.down, f"{prefix}.down")
    ops.append(rep)
    return y


def _check_mamba(m, cfg, ops, assumptions):
    """Targeted checks on the Mamba2/SSD integer path; the plan-tree
    audit covers the remaining dyadics at their declared ranges."""
    _, rep = check_int8_matmul(m.in_proj, "mamba.in_proj")
    ops.append(rep)
    t = _Track()
    lyr = "mamba.ssd"
    opn = "int8_matmul"
    conv_acc = t("conv accumulator", t_matmul_acc(
        cfg.ssm_conv, what="conv accumulator", op=opn, layer=lyr))
    conv10 = t_clip(t_dyadic(conv_acc, m.dn_conv, what="conv dyadic",
                             op=opn, layer=lyr), 11)
    t_silu(m.silu_conv, conv10, op="int_gelu", layer=f"{lyr}.conv_silu")
    # dt path: accumulator -> 10-bit dt_in -> softplus -> 13-bit dt
    t_dyadic(IntRange.symmetric(m.in_proj.acc_qmax), m.dn_dt_in,
             what="dt dyadic", op=opn, layer=f"{lyr}.dt")
    dt = IntRange(0, (1 << 13) - 1)           # softplus clip at out_bits=13
    # decay: dt*A on the 2^-14 grid -> i-exp -> 2^-15 fraction
    t_dyadic(IntRange.symmetric(dt.hi * 1024), m.dn_dtA,
             what="dt*A dyadic", op=opn, layer=f"{lyr}.decay")
    # state update: dt * B * x contribution and the h8/y readout
    xbc = 127                                  # s_xbc int8 grid
    contrib = t("dt*B*x product", static_check(
        dt.hi * xbc * xbc, "dt*B*x product", op=opn, layer=lyr))
    t_dyadic(IntRange.symmetric(contrib), m.dn_h, what="state dyadic",
             op=opn, layer=f"{lyr}.state")
    t_dyadic(IntRange.symmetric(m.qmax_h), m.dn_h8, what="h8 dyadic",
             op=opn, layer=f"{lyr}.h8")
    y_acc = t("C*h8 accumulator", t_matmul_acc(
        cfg.ssm_state, what="C*h8 accumulator", op=opn, layer=lyr))
    t_dyadic(y_acc, m.dn_y, what="y dyadic", op=opn, layer=f"{lyr}.y")
    ops.append(OpReport(opn, lyr, t.worst, note="ssd state path"))
    _, rep = check_int_layernorm(m.norm, "mamba.norm")
    ops.append(rep)
    _, rep = check_int8_matmul(m.out_proj, "mamba.out_proj")
    ops.append(rep)
    assumptions.append(
        f"mamba head state saturates at qmax_h={m.qmax_h} "
        "(runtime clip in the SSD scan)")


def longest_key_count(cfg, cache_len: int) -> int:
    """The most keys one attention row of ``cfg`` sees where it runs:
    the sweep's ``cache_len``, or the registry's long-context shape
    (``models.common.SHAPES["long_500k"]``) for the configs that run it
    (``configs.registry.LONG_OK``), capped by a sliding window."""
    from repro.configs.registry import LONG_OK
    from repro.models.common import SHAPES
    n = max(cache_len, SHAPES["long_500k"].seq_len
            if cfg.name in LONG_OK else 0)
    return min(n, cfg.window) if cfg.window else n


def certify_config(cfg, seq_len: int = 4096, cache_len: int = 32768,
                   calib: dict = None, page_size: int = 64,
                   chunk: int = 256) -> ConfigReport:
    """Statically certify one :class:`repro.models.common.ArchConfig`:
    every op of the integer datapath at worst case, at ``(seq_len,
    cache_len)``, with the serving engine's paged pool geometry
    (``page_size``, prefill ``chunk``) for the predicted kernel paths.
    Raises :class:`BitBudgetError` (typed: op + layer + worst value) on
    any int32 overflow; returns the report otherwise."""
    from repro.quant.plans import LinearPlan, build_layer_plans
    plans = build_layer_plans(cfg, calib)
    ops, assumptions = [], [
        f"residual stream bounded by qmax_res={cfg.qmax_res} "
        "(calibration contract — residual adds carry no runtime clip)",
        f"folded biases bounded by {BIAS_QMAX} at accumulator scale "
        "(|B| <= 4 real units over the nominal weight/act scales)",
        "int8 operands certified on the +-127 design grid "
        "(docs/ANALYSIS.md: 'The -128 corner')",
        "per-channel weight scales bounded by S_W8 (the nominal "
        "worst-case channel ratio every LinearPlan's (c, pre) is "
        "fitted at)",
        "i-norm output stage certified at the |n| <= sqrt(d) design "
        "bound (sigma^2 >= y_i^2/d; make_inorm's declared n_q_max)",
        "packed weight tier: nibbles on the +-7 grid, msr4 outlier "
        "deltas <= 120, element-wise |nib| + |delta| == |w| <= 127 "
        "(quant.pack contract)",
        "int4 KV pages dequantize to q4 << 4 (|kv| <= 112, inside the "
        "int8 grid; repro.ops.packed.KV_SHIFT)",
    ]
    # embedding -> residual stream
    t_dyadic(INT8, plans.embed.dn_res, what="embed residual dyadic",
             op="int8_matmul", layer="embed")
    # pre-attention / final norm (the same plan; certified once per site)
    _, rep = check_int_layernorm(plans.norm, "norm")
    ops.append(rep)
    if plans.attn is not None:
        _, rep = check_int8_matmul(plans.attn.qkv, "attn.qkv")
        ops.append(rep)
        _, rep = check_int8_matmul_packed(plans.attn.qkv,
                                          "attn.qkv[msr4]")
        ops.append(rep)
        _, rep = check_int_attention(plans.attn.attn, seq_len, "attn.core")
        ops.append(rep)
        out8 = IntRange.symmetric(127)
        y, rep = check_int8_matmul(plans.attn.out, "attn.out", x=out8)
        ops.append(rep)
        static_check(y.qmax, "attention residual write",
                     budget=cfg.qmax_res, op="int8_matmul",
                     layer="attn.out")
        if cfg.is_causal:
            _, rep = check_int_decode_attention(
                plans.attn.attn, cache_len, "attn.decode",
                page_size=page_size)
            ops.append(rep)
            _, rep = check_int_decode_attention(
                plans.attn.attn, cache_len, "attn.decode[kv4]",
                kv_pack=True, page_size=page_size)
            ops.append(rep)
            longest = longest_key_count(cfg, cache_len)
            if longest > cache_len:
                # the long-context shape: rows past MAX_PV_KEYS stream
                # their P·V pair through core.attention.fold_pv
                _, rep = check_int_decode_attention(
                    plans.attn.attn, longest, f"attn.decode[{longest}]",
                    page_size=page_size)
                ops.append(rep)
            _, rep = check_int_paged_prefill(
                plans.attn.attn, cache_len, "attn.prefill",
                chunk=chunk, page_size=page_size,
                wo=plans.attn.out, n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads, n_out=cfg.d_model)
            ops.append(rep)
            _, rep = check_int_paged_prefill(
                plans.attn.attn, cache_len, "attn.prefill[kv4]",
                chunk=chunk, page_size=page_size,
                wo=plans.attn.out, n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads, n_out=cfg.d_model, kv_pack=True)
            ops.append(rep)
    elif plans.ffn is not None:
        # no attention projections: certify the packed weight tier on
        # the FFN up-projection so every config proves the sub-8-bit
        # matmul path
        _, rep = check_int8_matmul_packed(plans.ffn.up, "ffn.up[msr4]")
        ops.append(rep)
    elif plans.mamba is not None:
        _, rep = check_int8_matmul_packed(plans.mamba.in_proj,
                                          "mamba.in_proj[msr4]")
        ops.append(rep)
    if plans.cross is not None and plans.cross is not plans.attn:
        _, rep = check_int_attention(plans.cross.attn, seq_len,
                                     "cross.core")
        ops.append(rep)
    if plans.ffn is not None:
        y = _check_ffn(plans.ffn, "ffn", ops)
        static_check(y.qmax, "ffn residual write", budget=cfg.qmax_res,
                     op="int8_matmul", layer="ffn.down")
    if plans.moe is not None:
        logits, rep = check_int8_matmul(plans.moe.router, "moe.router")
        ops.append(rep)
        _, rep = check_int_softmax(plans.moe.gate_sm, logits,
                                   cfg.n_experts, "moe.gate")
        ops.append(rep)
        _check_ffn(plans.moe.expert, "moe.expert", ops)
        if plans.moe.shared is not None:
            _check_ffn(plans.moe.shared, "moe.shared", ops)
        combine = IntRange.symmetric(
            static_check(cfg.top_k * 127 * 127, "moe combine sum",
                         op="int8_matmul", layer="moe.combine"))
        t_dyadic(combine, plans.moe.dn_combine, what="moe combine dyadic",
                 op="int8_matmul", layer="moe.combine")
    if plans.mamba is not None:
        _check_mamba(plans.mamba, cfg, ops, assumptions)
    _, rep = check_int8_matmul(
        LinearPlan(cfg.s_act8, 0.0, 32, 0, 0, cfg.d_model), "head")
    ops.append(rep)
    n_dyadics = audit_dyadics(plans, prefix=cfg.name)
    return ConfigReport(cfg.name, seq_len, cache_len, ops, n_dyadics,
                        assumptions)


__all__ = [
    "BIAS_QMAX", "ConfigReport", "OpReport", "certify_config",
    "longest_key_count",
    "check_int8_matmul", "check_int8_matmul_packed", "check_int_attention",
    "check_int_decode_attention", "check_int_gelu",
    "check_int_layernorm", "check_int_paged_prefill",
    "check_int_softmax", "check_requant_spec",
]
