"""Prefill-scoring cells of an MLA + MoE decoder (DeepSeek-V3's block):
the encoder cells' closed loop of ``int_prefill`` calls.

Everything but three things is ``encode.py``'s, by import, through this
module's own copy of it: the program (which here also keeps its plans),
the window, the sampled calls and their comparison with the reference.

  * The program's ``ArchConfig`` takes the latent-attention and expert
    keys the configuration's ``graph`` states (:data:`ARCH_KEYS`), so the
    file, not the registry, says what the program runs; a program
    without them cannot build the configuration and the run ends before
    any weight is drawn.
  * In a traced run, once the window has closed and before the
    reference runs, one kept call is run again through ``int_prefill(...,
    return_stats=True)``: the (token, choice) rows each MoE layer
    computed on the experts this chip holds.  The timed window and
    ``setup_s`` never build that second program.
  * The work a call requires (:func:`mla_moe_call`): every projection's
    matmul, the routed experts over the rows the counter read, attention
    over the causal triangle with query/key width ``d`` and value width
    ``dv`` (``2·B·H·(d + dv)·S(S+1)/2`` operations a layer), and the
    head.
"""
from __future__ import annotations

import dataclasses
import os

import numpy as np

import costs
import harness

encode = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "encode.py"), "bench_driver_encode_mla_moe")
_sizes = encode.program_config

#: the ``graph`` keys that the program's ``ArchConfig`` takes as they are
ARCH_KEYS = ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "first_k_dense", "n_experts", "top_k",
             "moe_d_ff", "n_shared_experts", "router", "routed_scaling",
             "expert_first", "experts_held", "rope_theta")


def program_config(cell):
    """``encode.program_config`` with the keys of :data:`ARCH_KEYS`."""
    g = cell.graph
    return dataclasses.replace(_sizes(cell), **{k: g[k] for k in ARCH_KEYS})


# this module's own copy of encode.py (loaded under its own name, apart
# from the other cells') builds its programs with this configuration
encode.program_config = program_config



class Program(encode.Program):
    """``encode.Program`` that also keeps ``plans``, the plans its weights
    were quantized with, for the counter's program (:func:`moe_stats`).
    They hold scales calibrated on the weights (the embedding's, the
    router's), so ``build_layer_plans(cfg)`` alone would not give them;
    ``convert.quantize_params`` is wrapped while the program is built."""

    def __init__(self, cell, seed: int, setup=None):
        from repro.quant import convert
        quantize = convert.quantize_params

        def keeping(params, cfg):
            qp, self.plans = quantize(params, cfg)
            return qp, self.plans
        convert.quantize_params = keeping
        try:
            super().__init__(cell, seed, setup)
        finally:
            convert.quantize_params = quantize


encode.Program = Program
_window, _gaps = encode.window, encode.gaps
#: the traced run's program, held from the window's end to the first
#: comparison (``_stats``); the counters it read
_held: dict = {}


def _window_keeping(prog, seconds, keep, rng, trace_dir=None,
                    trace_s=0.0):
    out = _window(prog, seconds, keep, rng, trace_dir, trace_s)
    if trace_dir:
        _held["prog"] = prog
    return out


def _gaps_after_stats(cell, seed, kept, bits=None, program=True):
    prog = _held.pop("prog", None)
    if prog is not None:
        _held["stats"] = moe_stats(prog, min(kept))
        del prog
    return _gaps(cell, seed, kept, bits=bits, program=program)


encode.window, encode.gaps = _window_keeping, _gaps_after_stats


def moe_stats(prog, i: int) -> dict:
    """The MoE counters of call ``i`` of ``prog``: ``int_prefill`` with
    ``return_stats`` on the same weights, plans and tokens."""
    import jax
    from repro.models import inttransformer as it
    cell = prog.cell
    cfg, plans = program_config(cell), prog.plans
    ops = cell.config["deployment"]["backend"]
    fn = jax.jit(lambda q, t: it.int_prefill(
        q, {"tokens": t}, plans, cfg, ops=ops, return_stats=True)[1])
    st = jax.device_get(fn(prog.qp, prog.tokens(np.int32(i))))
    out = {k: np.asarray(v, np.int64).tolist() for k, v in st.items()}
    harness.log(f"moe rows of call {i}: {out['moe_rows']} (largest held "
                f"expert: {out['moe_rows_max']})")
    return out


def mla_moe_call(g: dict, batch: int, seq: int, moe_rows) -> dict:
    """Required work of one ``int_prefill`` call over ``batch`` rows of
    ``seq`` tokens, the head on the last position of each row;
    ``moe_rows``: the rows each MoE layer computed on held experts.

    Matmuls (int8 operands; int8 out where the program writes int8,
    else the least container of its bits): the latent attention's q,
    latent, RoPE key, latent expansion and output projections; the dense
    layers' SwiGLU; each MoE layer's router (raw int32 logits) and its
    shared experts.  The routed experts (``grouped_matmul``) over the
    counted rows: their held experts' weights read once, the rows' int8
    inputs read and their outputs written.  Attention over the causal
    triangle; its bytes the queries, keys and values of each sequence
    read once and the outputs written."""
    d, h, r = g["d_model"], g["n_heads"], g["kv_lora_rank"]
    dn, dr, dv = g["qk_nope_head_dim"], g["qk_rope_head_dim"], \
        g["v_head_dim"]
    dq = dn + dr
    n, n_lead = g["num_layers"], g["first_k_dense"]
    f, fe = g["d_ff"], g["moe_d_ff"]
    fs = fe * g["n_shared_experts"]
    t = batch * seq

    def mm(shapes):
        ops = nbytes = 0
        for k, m_out, ob in shapes:
            o, b = costs.matmul(t, k, m_out, ob)
            ops, nbytes = ops + o, nbytes + b
        return ops, nbytes
    attn_mm = mm([(d, h * dq, 1), (d, r, 2), (d, dr, 1), (r, h * (dn + dv),
                                                        1), (h * dv, d, 2)])
    dense_mm = mm([(d, f, 2), (d, f, 2), (f, d, 2)])
    moe_mm = mm([(d, g["n_experts"], 4), (d, fs, 2), (d, fs, 2),
                 (fs, d, 2)])
    mm_ops = n * attn_mm[0] + n_lead * dense_mm[0] + (n - n_lead) * \
        moe_mm[0]
    mm_bytes = n * attn_mm[1] + n_lead * dense_mm[1] + (n - n_lead) * \
        moe_mm[1]
    eh = g.get("experts_held") or g["n_experts"]
    gm_ops = gm_bytes = 0
    for rows in moe_rows:
        gm_ops += 2 * rows * (d * 2 * fe + fe * d)
        gm_bytes += (eh * (d * 2 * fe + fe * d)        # held weights
                     + rows * d + rows * 2 * fe * 4    # w13: in, out
                     + rows * fe + rows * d * 4        # w2: in, out
                     + eh * 4 * (2 * fe + d))          # multipliers
    at_ops = n * 2 * batch * h * (dq + dv) * seq * (seq + 1) // 2
    at_bytes = n * batch * (2 * seq * h * dq + seq * h * dv
                            + seq * h * dv)
    return {"matmul": (mm_ops, mm_bytes),
            "grouped_matmul": (gm_ops, gm_bytes),
            "attention": (at_ops, at_bytes),
            "model_ops": mm_ops + gm_ops + at_ops
            + costs.head_ops(g, batch)}


def run(cell, seed: int, seconds: float, trace: bool, setup, devices,
        wrap=None):
    """``encode.run`` with this file's program configuration; a traced
    run also reads the MoE counters and recounts the slice's work."""
    program_config(cell)            # refuse a program without the keys
    _held.clear()
    out = encode.run(cell, seed, seconds, trace, setup, devices, wrap=wrap)
    if trace:
        stats = _held.pop("stats")
        out["moe_stats"] = stats
        n = out["slice"].count("encode")
        tr = cell.traffic
        work = mla_moe_call(cell.graph, tr["batch"], tr["seq_len"],
                            stats["moe_rows"])
        out["slice_work"] = {
            k: tuple(n * x for x in v) if isinstance(v, tuple) else n * v
            for k, v in work.items()}
    return out
