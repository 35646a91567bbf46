"""Schema sanity check for the machine-readable JSON artifacts
(``BENCH_*.json`` and the static-certification ``CERTIFY.json``).

CI's bench-smoke job runs this right after ``run.py --quick`` (and the
static-analysis job right after ``repro.analysis.certify``): the JSON
artifacts are consumed by tooling tracking the perf/certification
trajectory per commit, so a refactor that silently changes or drops a
field should fail the build, not the downstream dashboards.

The validator is a ~30-line structural checker (no external jsonschema
dependency): a schema is a dict mapping field name -> type | nested
schema | tuple of allowed types; ``...`` as a dict key validates every
value of an open-ended mapping against one sub-schema.  Unknown extra
fields are allowed (benches may grow columns), missing or mistyped
required fields are errors.
"""
from __future__ import annotations

import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

NUM = (int, float)

SERVING_CONFIG = {
    "tokens": int,
    "tokens_per_s": NUM,
    "kv_bytes": int,
    "kv_pack": str,                 # stored KV element dtype: int8 / int4
    "weight_bytes": int,            # quantized-parameter bytes as stored
    "pages": dict,
    "mode": str,
    "prefill": {
        "mode": str,
        "chunk": int,
        "ttft_s": NUM,
        "tokens_per_s": NUM,
    },
    "prefix_hit_rate": (int, float, type(None)),
}

# one spec_k point of the speculative-decoding measurement
SPEC_CONFIG = {
    "tokens_per_s": NUM,
    "accept_rate": (int, float, type(None)),   # None at spec_k = 0
    "drafted": int,
    "accepted": int,
}

# one percentile summary of the latency section (front-end _pct shape)
PCT = {
    "n": int,
    "mean": NUM,
    "p50": NUM,
    "p99": NUM,
}

# request-latency distribution under open-loop load (async front end)
LATENCY = {
    "arrival_rate_per_s": NUM,
    "submitted": int,
    "terminal": {
        "completed": int,
        "cancelled": int,
        "timeout": int,
        "rejected": int,
    },
    "ttft_s": PCT,
    "inter_token_s": PCT,
    "queue_wait_s": PCT,
    "occupancy": {"mean": NUM, "max": int},
    "queue_depth": {"mean": NUM, "max": int},
}

# per-config entry of CERTIFY.json: only "ok" is shared between the
# certified shape (worst_bits/ops/assumptions) and the failed shape
# (error {what, value, budget, op, layer, message}) — the checker has
# no conditionals, so require the common field and let extras pass
CERTIFY_CONFIG = {
    "ok": bool,
}

SCHEMAS = {
    "BENCH_serving.json": {
        "configs": {...: SERVING_CONFIG},
        "parity": bool,
        "spec": {
            "k0": SPEC_CONFIG,
            "k2": SPEC_CONFIG,
            "k4": SPEC_CONFIG,
            "parity": bool,
            "speedup": NUM,
        },
        "latency": LATENCY,
        "arch": str,
        "quick": bool,
    },
    "CERTIFY.json": {
        "schema": str,
        "seq_len": int,
        "cache_len": int,
        "budgets": {
            "INT32_MAX": int,
            "MAX_ROWSUM_LEN": int,
            "MAX_PV_KEYS": int,
            "MAX_SQ": int,
        },
        "n_configs": int,
        "n_failed": int,
        "configs": {...: CERTIFY_CONFIG},
    },
}


def _check(value, schema, path: str, errors: list):
    if isinstance(schema, dict):
        if not isinstance(value, dict):
            errors.append(f"{path}: expected object, got "
                          f"{type(value).__name__}")
            return
        if ... in schema:
            for key, sub in value.items():
                _check(sub, schema[...], f"{path}.{key}", errors)
            return
        for key, sub in schema.items():
            if key not in value:
                errors.append(f"{path}.{key}: missing")
            else:
                _check(value[key], sub, f"{path}.{key}", errors)
        return
    if isinstance(schema, tuple):
        if not isinstance(value, schema) or isinstance(value, bool) \
                and bool not in schema:
            errors.append(f"{path}: expected one of "
                          f"{[t.__name__ for t in schema]}, got "
                          f"{type(value).__name__}")
        return
    if schema is bool:
        if not isinstance(value, bool):
            errors.append(f"{path}: expected bool, got "
                          f"{type(value).__name__}")
        return
    if not isinstance(value, schema) or isinstance(value, bool):
        errors.append(f"{path}: expected {schema.__name__}, got "
                      f"{type(value).__name__}")


def _semantic_serving(data: dict, errors: list):
    """Invariants the structural check can't express: percentile order,
    terminal-state accounting of the latency section, and the int4 KV
    tier's byte-reduction gate."""
    lat = data.get("latency")
    if not isinstance(lat, dict):
        return                      # structural check already flagged it
    for metric in ("ttft_s", "inter_token_s", "queue_wait_s"):
        p = lat.get(metric)
        if isinstance(p, dict) and isinstance(p.get("p50"), NUM) \
                and isinstance(p.get("p99"), NUM) and p["p50"] > p["p99"]:
            errors.append(f"latency.{metric}: p50 {p['p50']} > p99 "
                          f"{p['p99']}")
    term = lat.get("terminal")
    sub = lat.get("submitted")
    if isinstance(term, dict) and isinstance(sub, int):
        counts = [v for v in term.values() if isinstance(v, int)]
        if sum(counts) != sub:
            errors.append(f"latency.terminal: counts {term} sum to "
                          f"{sum(counts)}, expected submitted={sub}")
    # the sub-8-bit KV tier: on the equal-page-count schedule the int4
    # pool must actually halve the bytes (the bench's 1.8x gate), and
    # its kv_pack tag must say so
    cfgs = data.get("configs")
    if isinstance(cfgs, dict):
        base, kv4 = cfgs.get("paged_chunked"), cfgs.get("paged_kv4")
        if isinstance(base, dict) and isinstance(kv4, dict) \
                and isinstance(base.get("kv_bytes"), int) \
                and isinstance(kv4.get("kv_bytes"), int) \
                and kv4["kv_bytes"] > 0:
            ratio = base["kv_bytes"] / kv4["kv_bytes"]
            if ratio < 1.8:
                errors.append(f"configs.paged_kv4: kv_bytes reduction "
                              f"{ratio:.2f}x below the 1.8x gate")
            if kv4.get("kv_pack") != "int4":
                errors.append("configs.paged_kv4: kv_pack is "
                              f"{kv4.get('kv_pack')!r}, expected 'int4'")


SEMANTIC = {
    "BENCH_serving.json": _semantic_serving,
}


def check_file(path: str) -> list:
    """Validate one BENCH_*.json; returns a list of error strings."""
    name = os.path.basename(path)
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"{name}: unreadable ({e})"]
    errors: list = []
    if not isinstance(data, dict):
        return [f"{name}: top level must be an object"]
    schema = SCHEMAS.get(name)
    if schema is not None:
        _check(data, schema, name, errors)
    semantic = SEMANTIC.get(name)
    if semantic is not None:
        semantic(data, errors)
    return errors


def main(argv=None) -> int:
    paths = list(argv if argv is not None else sys.argv[1:])
    if not paths:
        paths = sorted(glob.glob(os.path.join(HERE, "BENCH_*.json")))
        certify = os.path.join(HERE, "CERTIFY.json")
        if os.path.exists(certify):
            paths.append(certify)
    if not paths:
        print("check_bench_json: no BENCH_*.json files found",
              file=sys.stderr)
        return 1
    failed = False
    for path in paths:
        errors = check_file(path)
        status = "FAIL" if errors else "ok"
        print(f"{os.path.basename(path)}: {status}")
        for err in errors:
            failed = True
            print(f"  {err}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
