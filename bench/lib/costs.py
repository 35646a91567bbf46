"""Operations and bytes that a kernel call or a token requires, computed
from the shapes the benchmark drove.

They count the work the traffic asks for: real rows, real tokens and
live keys, never padded slots, so a later change to how the program
shapes its launches does not move the yardstick.  Integer operations
count a multiply and an add as two.  Bytes count each operand read once
from HBM and each result written once.
"""
from __future__ import annotations

from typing import Iterable, List, Tuple


def matmul(m: int, k: int, n: int, out_bytes: int = 1) -> Tuple[int, int]:
    """int8 (m, k) @ (k, n) with a requant epilogue: ops, bytes.  The
    per-channel multiplier vector is int32."""
    return 2 * m * k * n, m * k + k * n + m * n * out_bytes + 4 * n


def attention_bytes(rows: int, kv_len: int, h: int, hkv: int, hd: int,
                    new_kv: int = 0) -> int:
    """Bytes of one sequence's attention: queries in and outputs out, the
    ``kv_len`` cached K/V rows read once, ``new_kv`` K/V rows written."""
    return 2 * rows * h * hd + 2 * (kv_len + new_kv) * hkv * hd


def least_time(ops: float, nbytes: float, peak: dict) -> Tuple[float, str]:
    """The chip's least time for the work and which bound sets it."""
    t_ops = ops / peak["int8_ops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_mem else (t_mem, "bytes")


# ------------------------------------------------------ whole-model work --

def layer_matmuls(g: dict) -> List[Tuple[int, int, int]]:
    """(k, n, out_bytes) of every int8 matmul in one block, in order."""
    d, h, kv, hd, f = (g["d_model"], g["n_heads"], g["n_kv_heads"],
                       g["head_dim"], g["d_ff"])
    # q, k, v come out as int8; every other projection has more than 8
    # bits and at most 16, counted in the least container, two bytes
    mm = [(d, h * hd, 1), (d, kv * hd, 1), (d, kv * hd, 1), (h * hd, d, 2)]
    if g["activation"] == "swiglu":
        mm += [(d, f, 2), (d, f, 2), (f, d, 2)]
    else:
        mm += [(d, f, 2), (f, d, 2)]
    return mm


def block_matmul_work(g: dict, rows: int, skip_wo: bool = False
                      ) -> Tuple[int, int]:
    """ops, bytes of every matmul of one block over ``rows`` tokens
    (``skip_wo``: without the attention output projection)."""
    ops = nbytes = 0
    mms = layer_matmuls(g)
    for k, n, ob in (mms[:3] + mms[4:] if skip_wo else mms):
        o, b = matmul(rows, k, n, ob)
        ops, nbytes = ops + o, nbytes + b
    return ops, nbytes


def head_ops(g: dict, rows: int) -> int:
    return 2 * rows * g["d_model"] * g["vocab_size"]


def encoder_call(g: dict, batch: int, seq: int) -> dict:
    """Required work of one ``int_prefill`` call of an encoder over
    ``batch`` rows of ``seq`` tokens (bidirectional; the head on the last
    position of each row)."""
    n, h, kv, hd = g["num_layers"], g["n_heads"], g["n_kv_heads"], \
        g["head_dim"]
    mm_ops, mm_bytes = block_matmul_work(g, batch * seq)
    at_ops = 2 * 2 * batch * seq * seq * h * hd
    at_bytes = batch * attention_bytes(seq, seq, h, kv, hd)
    return {"matmul": (n * mm_ops, n * mm_bytes),
            "attention": (n * at_ops, n * at_bytes),
            "model_ops": n * (mm_ops + at_ops) + head_ops(g, batch)}


def _wo(g: dict, rows: int) -> Tuple[int, int]:
    k, n, ob = layer_matmuls(g)[3]
    return matmul(rows, k, n, ob)


def decode_step(g: dict, positions: Iterable[int], fold_wo: bool = False
                ) -> dict:
    """Required work of one decode step over the live lanes, each lane
    at ``position`` (its new token attends to ``position + 1`` keys).
    ``fold_wo``: the output projection runs inside the attention
    kernel, so its work counts there and not among the matmuls."""
    pos = list(positions)
    n, h, kv, hd = g["num_layers"], g["n_heads"], g["n_kv_heads"], \
        g["head_dim"]
    m = len(pos)
    mm_ops, mm_bytes = block_matmul_work(g, m, skip_wo=fold_wo)
    at_ops = sum(2 * 2 * (p + 1) * h * hd for p in pos)
    at_bytes = sum(attention_bytes(1, p, h, kv, hd, new_kv=1) for p in pos)
    if fold_wo:
        wo_ops, wo_bytes = _wo(g, m)
        at_ops, at_bytes = at_ops + wo_ops, at_bytes + wo_bytes
    return {"matmul": (n * mm_ops, n * mm_bytes),
            "decode_attention": (n * at_ops, n * at_bytes),
            "model_ops": n * (mm_ops + at_ops) + head_ops(g, m)}


def prefill_chunk(g: dict, lanes: Iterable[Tuple[int, int]],
                  fold_wo: bool = False) -> dict:
    """Required work of one chunked-prefill launch: ``lanes`` holds
    (base position, real tokens) of each lane in the round; row ``j``
    of a lane attends to ``base + j + 1`` keys.  ``fold_wo`` as for
    :func:`decode_step`."""
    lanes = list(lanes)
    n, h, kv, hd = g["num_layers"], g["n_heads"], g["n_kv_heads"], \
        g["head_dim"]
    rows = sum(r for _, r in lanes)
    mm_ops, mm_bytes = block_matmul_work(g, rows, skip_wo=fold_wo)
    keys = sum(r * b + r * (r + 1) // 2 for b, r in lanes)
    at_ops = 2 * 2 * keys * h * hd
    at_bytes = sum(attention_bytes(r, b, h, kv, hd, new_kv=r)
                   for b, r in lanes)
    if fold_wo:
        wo_ops, wo_bytes = _wo(g, rows)
        at_ops, at_bytes = at_ops + wo_ops, at_bytes + wo_bytes
    return {"matmul": (n * mm_ops, n * mm_bytes),
            "prefill_attention": (n * at_ops, n * at_bytes),
            "model_ops": n * (mm_ops + at_ops)}
