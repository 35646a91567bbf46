"""Prefill-scoring cells: the encoder cells' closed loop of
``int_prefill`` calls, run on a causal decoder.

Everything but two things is ``encode.py``'s, by import: the program,
the window, the sampled calls and their comparison with the reference.

  * The program's ``ArchConfig`` also takes the scalar multipliers the
    configuration's ``graph`` states (Granite 3.0's four), so the file,
    not the registry, says what the program runs; a program without
    them cannot build the configuration and the run ends before any
    weight is drawn.
  * The work a call requires counts attention as the causal triangle a
    decoder computes: row ``i`` of a sequence attends to ``i + 1`` keys,
    ``S (S + 1) / 2`` query-key pairs a sequence and head, where an
    encoder's call counts ``S^2``.
"""
from __future__ import annotations

import dataclasses
import os

import costs
import harness

encode = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "encode.py"), "bench_driver_encode")
_sizes = encode.program_config

#: the ``graph`` keys that the program's ``ArchConfig`` takes as they are
MULTIPLIERS = ("embedding_multiplier", "attention_multiplier",
               "residual_multiplier", "logits_scaling")


def program_config(cell):
    """``encode.program_config`` with the multipliers the file states."""
    g = cell.graph
    return dataclasses.replace(_sizes(cell),
                               **{k: g[k] for k in MULTIPLIERS if k in g})


# this module's own copy of encode.py (loaded under its own name, apart
# from the encoder cells') builds its programs with this configuration
encode.program_config = program_config


def causal_call(g: dict, batch: int, seq: int) -> dict:
    """Required work of one ``int_prefill`` call of a causal decoder over
    ``batch`` rows of ``seq`` tokens, the head on the last position of
    each row: the matmuls as an encoder's, attention over the causal
    triangle (QKᵀ and PV, two operations a multiply-add each), its bytes
    the queries, outputs and K/V of each sequence read or written once."""
    n, h, kv, hd = g["num_layers"], g["n_heads"], g["n_kv_heads"], \
        g["head_dim"]
    mm_ops, mm_bytes = costs.block_matmul_work(g, batch * seq)
    at_ops = 2 * 2 * batch * h * hd * seq * (seq + 1) // 2
    at_bytes = batch * costs.attention_bytes(seq, seq, h, kv, hd)
    return {"matmul": (n * mm_ops, n * mm_bytes),
            "attention": (n * at_ops, n * at_bytes),
            "model_ops": n * (mm_ops + at_ops) + costs.head_ops(g, batch)}


def run(cell, seed: int, seconds: float, trace: bool, setup, devices,
        wrap=None):
    """``encode.run`` with this file's program configuration, and the
    traced slice's work recounted as causal."""
    program_config(cell)            # refuse a program without the keys
    out = encode.run(cell, seed, seconds, trace, setup, devices, wrap=wrap)
    if trace:
        n = out["slice"].count("encode")
        tr = cell.traffic
        work = causal_call(cell.graph, tr["batch"], tr["seq_len"])
        out["slice_work"] = {
            k: tuple(n * x for x in v) if isinstance(v, tuple) else n * v
            for k, v in work.items()}
    return out
