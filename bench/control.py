#!/usr/bin/env python3
"""Readings for the limits of a cell's correctness check.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 3]

For each seed, in one process: a short window of the cell's own calls at
its own size, then the widest top-1 gap of the sampled rows for the
program and for the control, the reference computed in int4 and put in
the program's place (``bits=4``).  The benchmark's runs never run this.
Prints one JSON line per seed.
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


def readings(cell, seed: int, seconds: float, bits: int = 4) -> dict:
    import numpy as np
    drv = cell.driver()
    prog = drv.Program(cell, seed)
    rng = np.random.default_rng([seed % (1 << 63), 1])
    calls, _, kept, _ = drv.window(prog, seconds,
                                   cell.limits["sampled_calls"], rng)
    kept = {i: np.asarray(v) for i, v in kept.items()}
    del prog
    t0 = time.perf_counter()
    program = drv.gaps(cell, seed, kept)
    t_ref = time.perf_counter() - t0
    control = drv.gaps(cell, seed, kept, bits=bits, program=False)
    return {"seed": seed, "calls": calls, "rows": int(program.size),
            "program_gap": float(program.max()),
            "program_mean_gap": float(program.mean()),
            "control_gap": float(control.max()),
            "control_mean_gap": float(control.mean()),
            "reference_s": t_ref}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload)
    harness.device_check(cell.chips)
    harness.enable_compile_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        print(json.dumps({"workload": cell.name,
                          **readings(cell, seed, args.seconds)}), flush=True)


if __name__ == "__main__":
    main()
