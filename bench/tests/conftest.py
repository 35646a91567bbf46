import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, os.path.join(BENCH, "lib"))

#: a RoBERTa-shaped encoder small enough for the CPU
TINY_GRAPH = {"d_model": 128, "n_heads": 2, "n_kv_heads": 2, "head_dim": 64,
              "d_ff": 256, "num_layers": 2, "vocab_size": 512,
              "vocab_multiple": 16, "norm": "layernorm", "norm_eps": 1e-05,
              "activation": "gelu", "positions": "none",
              "rope_theta": 10000.0, "causal": False}


@pytest.fixture
def tiny_bench(tmp_path):
    """A copy of the benchmark with one more cell, ``tiny.docs``, added
    the way a later change adds one: new files and new entries only."""
    base = tmp_path / "bench"
    shutil.copytree(BENCH, base, ignore=shutil.ignore_patterns(
        "out", "__pycache__", "tests"))
    (base / "configs" / "tiny.json").write_text(json.dumps({
        "driver": "encode", "reference": "plain_transformer",
        "deployment": {"arch": "roberta-base", "backend": "ref"},
        "graph": TINY_GRAPH}))
    (base / "traffic" / "tinydocs.json").write_text(json.dumps(
        {"kind": "encode_closed_loop", "batch": 8, "seq_len": 16}))
    # at this size the program reads gaps up to 0.46 over a dozen seeds
    # and the int4 control 0.62 to 1.95 (PERF.md): the tests use seed 2
    (base / "limits" / "tiny.docs.json").write_text(json.dumps(
        {"top1_gap": 0.55, "sampled_calls": 2, "reference_rows": 8}))
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "CPU test"})
    spec["workloads"].append({"name": "tiny.docs", "config": "tiny",
                              "traffic": "tinydocs", "chips": 1,
                              "why": "CPU test"})
    return base, spec
