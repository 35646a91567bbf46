"""Arithmetic shared by the per-layer metric readers in ``bench/metrics``.

A reader gets the driver's run record and returns a number, or None where
its cell gave it nothing to read (the harness then leaves the metric out).
"""
from __future__ import annotations

from typing import Optional

import costs
import harness


def roofline(run: dict, kernel: str, work: str) -> Optional[float]:
    """Share (%) of the least time the chip needs for the traced slice's
    ``work`` in the time its ``kernel`` events took: sum of least times
    over sum of measured times.  ``run["slice_work"]`` holds the summed
    (ops, bytes) of each kind of work in the slice."""
    sl = run.get("slice")
    measured = sl.kernels.get(kernel, 0.0) if sl else 0.0
    ops, nbytes = run.get("slice_work", {}).get(work, (0, 0))
    if measured <= 0 or ops <= 0:
        return None
    least, bound = costs.least_time(ops, nbytes, run["peak"])
    harness.log(f"roofline {kernel}: least {least:.6f} s ({bound}-bound) "
                f"in {measured:.6f} s of kernel time")
    return 100.0 * least / measured


def idle_share(run: dict) -> Optional[float]:
    sl = run.get("slice")
    return None if sl is None else 100.0 * sl.idle_share


def mfu(run: dict) -> Optional[float]:
    """Model int8 operations of the tokens computed in the traced slice,
    per second of the slice, over the chip's int8 peak (%)."""
    sl = run.get("slice")
    ops = run.get("slice_work", {}).get("model_ops", 0)
    if sl is None or ops <= 0:
        return None
    return 100.0 * ops / sl.window_s / run["peak"]["int8_ops_per_s"]


def step_ms(run: dict, program: str) -> Optional[float]:
    """Mean device time (ms) of one execution of ``program``."""
    sl = run.get("slice")
    ex = sl.executions(program) if sl else []
    return 1e3 * sum(e.dur for e in ex) / len(ex) if ex else None


def share(run: dict, part: str, whole: str, complement: bool = False
          ) -> Optional[float]:
    """``part / whole`` of the run's host counts (%), or one minus it."""
    c = run.get("counts", {})
    if not c.get(whole):
        return None
    r = c[part] / c[whole]
    return 100.0 * (1.0 - r if complement else r)
