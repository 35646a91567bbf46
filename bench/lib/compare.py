"""The comparison that decides ``correct``.

The number compared is built on the top-1 gap: for each answer, how far
the reference's logit of the token the program put first lies below the
reference's best logit (0 where they agree).  A run reads the widest gap
over every answer it compares (``top1_gap``) and their mean
(``mean_top1_gap``); a cell's limits file names the ones it compares.
The control reads the same gaps for the tokens that the reference
computed in int4 puts first.
"""
from __future__ import annotations

from typing import Callable, Sequence

import numpy as np


def seed_key(seed: int):
    """A JAX key from any whole ``seed`` (also past 32 bits)."""
    import jax
    seed %= 1 << 64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def top1_gaps(ref_logits: np.ndarray, picks: np.ndarray) -> np.ndarray:
    """ref_logits (N, V) float, picks (N,) token ids -> gaps (N,)."""
    ref = np.asarray(ref_logits, np.float64)
    return ref.max(-1) - ref[np.arange(len(ref)), np.asarray(picks)]


def in_blocks(fn: Callable, rows: Sequence, block: int) -> np.ndarray:
    """Apply ``fn`` to ``rows`` ``block`` at a time and stack the results
    on the host, so a reference over many rows fits on the chip."""
    out = [np.asarray(fn(rows[i:i + block]))
           for i in range(0, len(rows), block)]
    return np.concatenate(out)


#: the numbers a limits file may name, from a run's top-1 gaps
NUMBERS = {"top1_gap": np.max, "mean_top1_gap": np.mean}


def checks(gaps: np.ndarray, limits: dict) -> dict:
    """{number: {"value", "limit"}} for every number ``limits`` names;
    no answer to compare reads as infinitely far off."""
    import harness
    out = {}
    for name, fn in NUMBERS.items():
        value = float(fn(gaps)) if gaps.size else float("inf")
        harness.log(f"{name}: {value!r} over {gaps.size} answers")
        if name in limits:
            out[name] = {"value": value, "limit": float(limits[name])}
    return out
