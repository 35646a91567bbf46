"""The benchmark's harness: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It finds everything by name.  ``BENCHMARK.json`` names the cell's
configuration and traffic; ``bench/configs/<config>.json`` holds the
sizes, the deployment, and which driver (``bench/drivers/<driver>.py``)
and reference (``bench/reference/<reference>.py``) serve it;
``bench/traffic/<traffic>.json`` holds the traffic's parameters;
``bench/limits/<cell>.json`` the limits of the correctness check; each
per-layer metric is read by ``bench/metrics/<metric>.py``.  A new cell,
mix or metric is new files and entries, never an edit.

The last line of standard output is the result; the numbers compared
for ``correct`` are also the last lines of standard error.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time
from typing import Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
#: run output (traces) lives here; bench/.gitignore lists it
OUT = os.path.join(BENCH, "out")


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def load_module(path: str, name: Optional[str] = None):
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + os.path.basename(path).replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(base: str, *parts) -> dict:
    with open(os.path.join(base, *parts)) as f:
        return json.load(f)


class Cell:
    """One workload of ``BENCHMARK.json`` with everything its name finds."""

    def __init__(self, name: str, spec: Optional[dict] = None,
                 base: str = BENCH):
        if spec is None:
            with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
                spec = json.load(f)
        by_name = {w["name"]: w for w in spec["workloads"]}
        if name not in by_name:
            raise SystemExit(f"bench: no workload {name!r} in "
                             f"BENCHMARK.json ({sorted(by_name)})")
        self.spec = spec
        self.base = base
        self.name = name
        self.entry = by_name[name]
        self.chips = self.entry["chips"]
        self.config = read_json(base, "configs",
                                self.entry["config"] + ".json")
        self.traffic = read_json(base, "traffic",
                                 self.entry["traffic"] + ".json")
        self.limits = read_json(base, "limits", name + ".json")
        self.graph = self.config["graph"]

    def end_to_end(self) -> List[dict]:
        return [m for m in self.spec["end_to_end"]
                if "workloads" not in m or self.name in m["workloads"]]

    def per_layer(self) -> List[dict]:
        """The cell's per-layer metrics: those listing it, and those
        without a list whose end-to-end metric it reports."""
        names = [m["name"] for m in self.end_to_end()]
        return [m for m in self.spec["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in names)]

    def driver(self):
        return load_module(os.path.join(self.base, "drivers",
                                        self.config["driver"] + ".py"))

    def reference(self):
        return load_module(os.path.join(self.base, "reference",
                                        self.config["reference"] + ".py"))

    def reader(self, metric: str) -> Callable:
        return load_module(os.path.join(self.base, "metrics",
                                        metric + ".py")).read


# ------------------------------------------------------------- device --

def device_check(chips: int) -> dict:
    """The device as JAX reports it; exits non-zero, before anything is
    measured, without a TPU or with fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise SystemExit(f"bench: no TPU (JAX platform {dev['platform']!r})"
                         "; nothing was measured")
    if dev["count"] < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{dev['count']}")
    return dev


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at the fixed ``<checkout>/
    .jax_cache`` (``JAX_COMPILATION_CACHE_DIR`` wins where it is set), with
    every program cached, so only a cell's first run compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts programs built (compiled or read from the cache) while
    ``armed``: the measured window must build none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.armed and event == self.EVENT:
            self.count += 1


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


# ------------------------------------------------------------- result --

class Setup:
    """The parts of ``setup_s``, in order, for the earlier stderr line."""

    def __init__(self, t0: float):
        self.t0 = t0
        self.at = t0
        self.parts: Dict[str, float] = {}

    def mark(self, part: str):
        now = time.perf_counter()
        self.parts[part] = self.parts.get(part, 0.0) + now - self.at
        self.at = now

    @property
    def total(self) -> float:
        return self.at - self.t0


def metric(value: float, unit: str) -> dict:
    return {"value": float(value), "unit": unit}


def emit(cell: Cell, run: dict, trace: bool, dev: dict):
    """Print the checks on stderr and the one result line on stdout.
    ``run`` is what the driver returned."""
    metrics = {}
    if trace:
        for m in cell.per_layer():
            v = cell.reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = metric(v, m["unit"])
    else:
        for m in cell.end_to_end():
            metrics[m["name"]] = metric(run["end_to_end"][m["name"]],
                                        m["unit"])
    checks = run["checks"]
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r}) "
            f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}")
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": cell.chips, "memory_peak_bytes": run["memory_peak"]}
    result = {"correct": correct, "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics, "device": device}
    if trace:
        sl = run["slice"]
        device.update(busy_s=sl.busy_s, window_s=sl.window_s)
        result["breakdown"] = sl.breakdown()
    result["checks"] = checks
    print(json.dumps(result), flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t0: Optional[float] = None):
    t0 = time.perf_counter() if t0 is None else t0
    args = parse(argv)
    cell = Cell(args.workload)
    dev = device_check(cell.chips)
    import jax
    log(f"bench: {cell.name} seed {args.seed} on {dev['kind']} x"
        f"{cell.chips}; compile cache {enable_compile_cache()}")
    setup = Setup(t0)
    setup.mark("start")
    run = cell.driver().run(cell, seed=args.seed, seconds=args.seconds,
                            trace=bool(args.trace), setup=setup,
                            devices=jax.devices()[:cell.chips])
    emit(cell, run, bool(args.trace), dev)
