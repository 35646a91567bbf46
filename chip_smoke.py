#!/usr/bin/env python3
"""Chip smoke test: the integer serving path, compiled, on one TPU chip.

    python chip_smoke.py           # one chip: RoBERTa-base + Granite-3-2B
    python chip_smoke.py --tp 4    # four chips: tp=4 sharded vs tp=1

Runs the ``pallas_fused`` path through the entry points a user calls, at
published widths and with seeded random weights, and checks it against
the ``ref`` integer oracle on the same chip:

  1. device check — a TPU must be present (exit non-zero otherwise;
     never continues on the CPU);
  2. RoBERTa-base (12 layers): ``int_prefill`` on a batch of 8 x 256
     tokens; logits bit-identical to ``ops="ref"``; the correlation with
     a float32 forward is printed as information;
  3. Granite-3-2B (all 40 layers): ``launch.serve.main`` — ServingEngine
     + ServingFrontend, paged cache, chunked prefill, prefix cache — on
     8 requests of 512-token prompts (4 share a 256-token prefix), 32
     greedy tokens each, batch 8, cache 1024; every request completes
     with a stream of more than one distinct token, every logits row a
     token came from bit-identical to ``ops="ref"`` (a per-request
     digest), fused decode and native paged prefill in ``describe()``,
     and the offline certifier predicts no fallback path at these
     shapes.

``--tp 4`` runs only the sharded phase: Granite-3-2B served at tp=4
(head-sharded over four devices) and at tp=1 on the first device, in
this one process; logits digests and streams must be identical.

Random weights are drawn at the served scale
(``init_params(..., served=True)``): at published widths and depths the
training init either flushes every integer activation to 0 or, with
tied embeddings, repeats one token per stream — comparisons that a
wrong kernel could still pass.

No phase's failure is caught: any exception exits non-zero.  The last
line of standard output is the JSON result, and only on success.  The
tok/s and TTFT lines are smoke output, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

# the serving phases' model and traffic (see the module docstring)
ARCH = "granite-3-2b"
GRANITE = dict(requests=8, prompt_len=512, shared_prefix=256, max_new=32,
               batch=8, cache_len=1024, page_size=128, prefill_chunk=256)


def device_check(need: int = 1) -> dict:
    """The device as JAX reports it; exits non-zero without a TPU."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU present (JAX platform is "
                         f"{dev['platform']!r}); nothing was run")
    if dev["count"] < need:
        raise SystemExit(f"chip_smoke: needs {need} TPU devices, found "
                         f"{dev['count']}")
    return dev


def _no_fallback(cfg, seq_len: int, cache_len: int, page_size: int,
                 chunk: int):
    """The offline certifier's predicted kernel path for every op at
    these shapes: a ``fallback:`` path means a silent oracle fallback."""
    from repro.analysis.interpret import certify_config
    rep = certify_config(cfg, seq_len=seq_len, cache_len=cache_len,
                         page_size=page_size, chunk=chunk)
    bad = [(o.op, o.layer, o.path) for o in rep.ops
           if o.path.startswith("fallback")]
    assert not bad, f"{cfg.name}: predicted fallback paths {bad}"


def roberta_phase(cfg, batch: int = 8, seq: int = 256, seed: int = 0):
    """``int_prefill`` under pallas_fused vs ref: bit-identical logits.

    The float32 reference runs the graph the integer path implements —
    pre-LN sublayers, no learned positions (the integer datapath has
    neither post-LN nor position embeddings yet) — at RoBERTa-base's
    widths, so the printed correlation measures quantization error."""
    import jax
    import numpy as np
    from repro.models import inttransformer as it
    from repro.models import transformer as tf
    from repro.quant import convert

    _no_fallback(cfg, seq, seq, 64, seq)
    cfg = dataclasses.replace(cfg, dtype="float32")
    # random weights at the served scale, as ``launch.serve`` draws them
    params = tf.init_params(jax.random.key(seed), cfg, served=True)
    tokens = jax.random.randint(jax.random.key(seed + 1), (batch, seq), 1,
                                cfg.vocab)
    t0 = time.time()
    qp, plans = convert.quantize_params(params, cfg)
    print(f"[roberta] {cfg.name}: {cfg.num_layers} layers, d={cfg.d_model}"
          f", batch {batch} x {seq} tokens; quantized in "
          f"{time.time() - t0:.1f}s", flush=True)
    logits = {}
    for ops in ("pallas_fused", "ref"):
        fn = jax.jit(lambda q, t, ops=ops: it.int_prefill(
            q, {"tokens": t}, plans, cfg, ops=ops))
        t0 = time.time()
        exe = fn.lower(qp, tokens).compile()
        t1 = time.time()
        logits[ops] = np.asarray(jax.block_until_ready(exe(qp, tokens)))
        t2 = time.time()
        print(f"[roberta] ops={ops}: compile {t1 - t0:.1f}s, run "
              f"{t2 - t1:.3f}s (smoke output, not a benchmark)",
              flush=True)
    got, want = logits["pallas_fused"], logits["ref"]
    assert got.shape[0] == batch and got.shape[1] >= cfg.vocab
    assert np.isfinite(got).all()
    # constant logits (every integer activation flushed to 0) would
    # compare equal too: each row must vary over the vocab
    assert (got.max(axis=1) > got.min(axis=1)).all(), "constant logits"
    assert np.array_equal(got, want), \
        f"pallas_fused logits differ from ref in {(got != want).sum()} places"
    print("[roberta] pallas_fused logits bit-identical to ref: True",
          flush=True)
    ref_cfg = dataclasses.replace(cfg, post_norm=False, pos="none")
    with jax.default_matmul_precision("highest"):
        flog, _ = jax.jit(lambda p, t: tf.forward_float(
            p, {"tokens": t}, ref_cfg))(params, tokens)
    flog = np.asarray(flog[:, -1, :cfg.vocab], np.float64)
    corr = float(np.corrcoef(got[:, :cfg.vocab].astype(np.float64).ravel(),
                             flog.ravel())[0, 1])
    assert np.isfinite(corr), corr
    print(f"[roberta] int-vs-float32 logit correlation: {corr:.4f} "
          "(information; float32 reference of the pre-LN graph the "
          "integer path runs)", flush=True)
    return {"bit_exact": True, "corr": corr}


def _serve_args(ops: str, t: dict, tp: int = 1,
                reduced: bool = False) -> list:
    argv = ["--arch", ARCH, "--backend", ops, "--requests",
            str(t["requests"]), "--prompt-len", str(t["prompt_len"]),
            "--shared-prefix", str(t["shared_prefix"]), "--max-new",
            str(t["max_new"]), "--batch", str(t["batch"]), "--cache-len",
            str(t["cache_len"]), "--cache-mode", "paged", "--page-size",
            str(t["page_size"]), "--prefill-chunk", str(t["prefill_chunk"]),
            "--warmup", "1", "--tp", str(tp), "--record-logits"]
    return argv + (["--reduced"] if reduced else [])


def _serve(tag: str, argv: list, t: dict, model) -> dict:
    """One ``launch.serve.main`` run of the quantized ``model``: every
    request completed with its full token budget, each stream holds more
    than one distinct token (a stream repeating one token — degenerate
    random weights — would compare equal across backends however the
    kernels misread the cache), and every request has a logits digest."""
    from repro.launch import serve
    d = serve.main(argv, model=model)
    lat = d["latency"]["ttft_s"]
    print(f"[{tag}] warmup (compiles) {d['warmup_s']:.1f}s; served "
          f"window {d['window_s']:.2f}s, {d['tokens']} tokens, "
          f"{d['tokens'] / d['window_s']:.1f} tok/s, TTFT p50 "
          f"{lat['p50'] * 1e3:.0f} ms (smoke output, not benchmark "
          "numbers)", flush=True)
    assert d["terminal"]["completed"] == t["requests"], d["terminal"]
    assert all(s is not None and len(s) == t["max_new"]
               for s in d["streams"]), d["streams"]
    distinct = [len(set(s)) for s in d["streams"]]
    print(f"[{tag}] distinct tokens per stream: {distinct}; logits "
          f"digest of request 0: {d['digests'][0][:16]}", flush=True)
    assert min(distinct) > 1, d["streams"]
    assert all(d["digests"]), d["digests"]
    return d


def _same(a: dict, b: dict) -> bool:
    """Two runs gave identical logits at every committed token (per-
    request digests) and so identical streams."""
    return a["digests"] == b["digests"] and a["streams"] == b["streams"]


def granite_phase(traffic: dict = None, reduced: bool = False):
    """``launch.serve.main`` under pallas_fused vs ref: identical
    logits and streams, all requests completed, fused paths in
    ``describe()``."""
    from repro.launch import serve
    t = dict(GRANITE, **(traffic or {}))
    model = serve.load_quantized(ARCH, reduced)
    _no_fallback(model[0], t["prompt_len"], t["cache_len"], t["page_size"],
                 t["prefill_chunk"])
    out = {}
    for ops in ("pallas_fused", "ref"):
        out[ops] = _serve(f"{ARCH} ops={ops}",
                          _serve_args(ops, t, reduced=reduced), t, model)
    eng = out["pallas_fused"]["engine"]
    assert eng["decode"] == "fused", eng
    assert eng["prefill"]["mode"] == "chunked", eng["prefill"]
    assert eng["prefill"]["paged_native"], eng["prefill"]
    px = eng["cache"]["prefix"]
    assert px is not None and px["hits"] > 0, px
    same = _same(out["pallas_fused"], out["ref"])
    assert same, "pallas_fused logits or streams differ from ref"
    print(f"[{ARCH}] {t['requests']} requests completed; logits and "
          f"streams bit-identical to ref: {same}; decode={eng['decode']}, "
          f"paged prefill native={eng['prefill']['paged_native']}, "
          f"prefix hits={px['hits']}", flush=True)
    return out


def tp_phase(tp: int = 4, traffic: dict = None, reduced: bool = False):
    """tp-way head-sharded serving vs tp=1 on the first device."""
    import jax
    from repro.launch import serve
    t = dict(GRANITE, **(traffic or {}))
    model = serve.load_quantized(ARCH, reduced)
    out = {}
    for deg in (tp, 1):
        out[deg] = _serve(f"{ARCH} tp={deg}", _serve_args(
            "pallas_fused", t, tp=deg, reduced=reduced), t, model)
    mode = out[tp]["engine"]["tp"]
    assert mode["mode"] == "sharded", mode
    devices = mode["mesh"]["devices"]
    assert len(set(devices)) == tp == len(devices), devices
    assert set(devices) <= {d.id for d in jax.devices()}
    same = _same(out[tp], out[1])
    assert same, f"tp={tp} logits or streams differ from tp=1"
    print(f"[{ARCH}] tp={tp} mode={mode['mode']} over devices {devices}; "
          f"logits and streams bit-identical to tp=1: {same}", flush=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tp", type=int, default=1,
                    help="run only the tp-way sharded phase (needs tp "
                         "devices); 1 = the one-chip phases")
    args = ap.parse_args(argv)
    dev = device_check(need=args.tp)
    from repro.configs.registry import get_config
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.tp > 1:
        tp_phase(args.tp)
    else:
        roberta_phase(get_config("roberta-base"))
        granite_phase()
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
