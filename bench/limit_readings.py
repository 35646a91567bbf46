#!/usr/bin/env python3
"""Readings for the limits of a cell's correctness check, many seeds in
one process, for cells whose set-up quantizes a large model.

    python3 bench/limit_readings.py --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 1,2,3] [--window-calls 36]

For each of ``--seeds``: the cell's program built as its driver builds
it, the calls a run at that seed would keep from a window of
``--window-calls`` calls (the cell driver's reservoir, drawn from the seed
as the run draws it; the limits' ``sampled_calls`` of them), and the
mean and widest top-1 gap of their rows against the reference.  For each of
``--control-seeds``, the same for the int4 control (the reference
computed in int4 in the program's place), as ``control.py`` reads
it.  Each seed's program is built once and the cell driver's set-up is
shared, so a large model's seeds cost one process start.  The
benchmark's runs never run this.  Prints one JSON line per seed.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, os.path.join(HERE, "lib"))

import harness  # noqa: E402


def kept_calls(seed: int, keep: int, calls: int) -> list:
    """The call indices ``encode.window``'s reservoir keeps at ``seed``
    from a window of ``calls`` calls."""
    import numpy as np
    rng = np.random.default_rng([seed % (1 << 63), 1])
    kept = list(range(min(keep, calls)))
    for i in range(keep, calls):
        j = int(rng.integers(0, i + 1))
        if j < keep:
            kept.pop(j)
            kept.append(i)
    return sorted(kept)


def readings(cell, seed: int, calls: list, program: bool,
             control: bool) -> dict:
    """One seed's readings: the program's (``program``) and the int4
    control's (``control``; it needs no program, only the calls' ids)."""
    import numpy as np
    drv = cell.driver()
    enc = getattr(drv, "encode", drv)
    out = {"seed": seed, "calls": calls}
    kept = dict.fromkeys(calls)
    if program:
        t0 = time.perf_counter()
        prog = enc.Program(cell, seed)
        out["setup_s"] = time.perf_counter() - t0
        kept = {i: np.asarray(prog.call(i)) for i in calls}
        del prog
        gaps = enc.gaps(cell, seed, kept)
        out.update(rows=int(gaps.size),
                   program_mean_gap=float(gaps.mean()),
                   program_gap=float(gaps.max()))
    if control:
        gaps = enc.gaps(cell, seed, kept, bits=4, program=False)
        out.update(rows=int(gaps.size),
                   control_mean_gap=float(gaps.mean()),
                   control_gap=float(gaps.max()))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="",
                    help="seeds to read the program at")
    ap.add_argument("--control-seeds", default="",
                    help="seeds to read the int4 control at")
    ap.add_argument("--window-calls", type=int, default=0,
                    help="calls in the window a run makes (default: "
                    "the limits' sampled_calls, all of them kept)")
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload)
    harness.device_check(cell.chips)
    harness.enable_compile_cache()
    keep = cell.limits["sampled_calls"]
    window = args.window_calls or keep
    prog = [int(s) for s in args.seeds.split(",") if s]
    ctl = [int(s) for s in args.control_seeds.split(",") if s]
    for seed in prog + [s for s in ctl if s not in prog]:
        print(json.dumps({"workload": cell.name, **readings(
            cell, seed, kept_calls(seed, keep, window), seed in prog,
            seed in ctl)}), flush=True)


if __name__ == "__main__":
    main()
