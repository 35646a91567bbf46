#!/usr/bin/env python3
"""Run one benchmark cell once; see ``bench/lib/harness.py``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
import os
import sys
import time

T0 = time.perf_counter()        # set-up is timed from the process start
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, os.path.join(HERE, "lib"))

import harness  # noqa: E402

if __name__ == "__main__":
    harness.main(t0=T0)
