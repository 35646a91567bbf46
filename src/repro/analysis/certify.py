"""Certification CLI: sweep every registry config, emit CERTIFY.json.

``python -m repro.analysis.certify`` runs
:func:`repro.analysis.interpret.certify_config` over all registry
architectures at a given ``(seq_len, cache_len)`` and writes the
machine-readable report to ``benchmarks/CERTIFY.json`` (schema-checked
by ``benchmarks/check_bench_json.py``).  Exit status is non-zero if any
config fails — the CI ``static-analysis`` job gates on it, so an unsafe
plan constant cannot merge.

Per config the report carries: certification status, worst-case bits and
minimum int32 headroom across all ops, per-op worst-case magnitude /
bits / predicted kernel path, the number of plan-tree dyadics whose
staging invariant was re-proved, and the list of assumptions (what is
taken on contract rather than proven — see docs/ANALYSIS.md).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from repro.analysis.budgets import (INT32_MAX, MAX_PV_KEYS,
                                    MAX_ROWSUM_LEN, MAX_SQ, BitBudgetError)
from repro.analysis.interpret import certify_config

SCHEMA = "repro/certify-v1"

DEFAULT_JSON = os.path.join("benchmarks", "CERTIFY.json")


def _op_entry(o):
    return {
        "op": o.op,
        "layer": o.layer,
        "worst": o.worst,
        "bits": o.bits,
        "headroom_bits": o.headroom_bits,
        "path": o.path,
        "note": o.note,
    }


def certify_all(seq_len: int, cache_len: int, names=None):
    """Certify the selected (default: all) registry configs.  Returns
    ``(report_dict, n_failed)`` — never raises on certification failure,
    so one bad config still reports every other."""
    from repro.configs.registry import ARCHS
    names = list(names) if names else sorted(ARCHS)
    configs = {}
    n_failed = 0
    for name in names:
        cfg = ARCHS[name]            # KeyError on unknown names: intended
        try:
            r = certify_config(cfg, seq_len=seq_len, cache_len=cache_len)
        except BitBudgetError as e:
            n_failed += 1
            configs[name] = {
                "ok": False,
                "error": {
                    "what": e.what,
                    "value": e.value,
                    "budget": e.budget,
                    "op": e.op or "",
                    "layer": e.layer or "",
                    "message": str(e),
                },
            }
            continue
        configs[name] = {
            "ok": True,
            "worst_bits": r.worst_bits,
            "min_headroom_bits": r.min_headroom_bits,
            "n_ops": len(r.ops),
            "n_dyadics": r.n_dyadics,
            "ops": [_op_entry(o) for o in r.ops],
            "assumptions": list(r.assumptions),
        }
    report = {
        "schema": SCHEMA,
        "seq_len": seq_len,
        "cache_len": cache_len,
        "budgets": {
            "INT32_MAX": INT32_MAX,
            "MAX_ROWSUM_LEN": MAX_ROWSUM_LEN,
            "MAX_PV_KEYS": MAX_PV_KEYS,
            "MAX_SQ": MAX_SQ,
        },
        "n_configs": len(configs),
        "n_failed": n_failed,
        "configs": configs,
    }
    return report, n_failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.certify",
        description="Statically certify every registry config "
                    "overflow-free (docs/ANALYSIS.md).")
    ap.add_argument("--seq-len", type=int, default=4096,
                    help="prefill sequence length to certify at")
    ap.add_argument("--cache-len", type=int, default=32768,
                    help="decode/paged-prefill cache length to certify at")
    ap.add_argument("--arch", action="append", default=None,
                    help="certify only this config (repeatable)")
    ap.add_argument("--json", default=DEFAULT_JSON, metavar="PATH",
                    help="report path ('-' to skip writing)")
    args = ap.parse_args(argv)

    report, n_failed = certify_all(args.seq_len, args.cache_len, args.arch)
    for name, entry in report["configs"].items():
        if entry["ok"]:
            print(f"  ok    {name}: {entry['n_ops']} ops, worst "
                  f"{entry['worst_bits']} bits (headroom "
                  f"{entry['min_headroom_bits']}), "
                  f"{entry['n_dyadics']} dyadics audited")
        else:
            print(f"  FAIL  {name}: {entry['error']['message']}")
    if args.json != "-":
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w", encoding="utf-8") as fh:
            json.dump(report, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"wrote {args.json}")
    if n_failed:
        print(f"{n_failed} config(s) failed certification",
              file=sys.stderr)
        return 1
    print(f"all {report['n_configs']} configs certified overflow-free "
          f"at seq_len={args.seq_len}, cache_len={args.cache_len}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
