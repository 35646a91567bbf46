"""Production serving driver: float checkpoint -> SwiftTron integer
parameters -> batched INT8 engine behind the async front end.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-3-4b \
      --reduced --requests 8 --max-new 16 [--ckpt-dir DIR]

Without --ckpt-dir the driver quantizes a fresh (random-init) model —
useful for throughput measurement; with one it restores the trained
params saved by launch.train.  Prompts are ``--prompt-len`` random
tokens (seeded); ``--shared-prefix N`` makes the first half of them
start with one common N-token prefix (the prefix cache's traffic).
``--warmup N`` serves the schedule's first N prompts once before the
timed window, so the compiles land there (and the prefix cache holds
those prompts, as on a server that has seen them).  ``main(argv)``
returns the front end's ``describe()`` plus the engine's
(``"engine"``), every request's tokens (``"streams"``), with
``--record-logits`` a digest of each request's logits (``"digests"``),
and the two timings (``"warmup_s"``, ``"window_s"``).

Requests flow through :class:`repro.serving.ServingFrontend` — the
asyncio admission/streaming layer — rather than a hand-rolled drain
loop, so the driver gets backpressure (``--max-pending``), per-request
deadlines (``--timeout-s``), open-loop Poisson load (``--arrival-rate``
requests/s; 0 = submit everything up front) and p50/p99 TTFT /
inter-token latency in the summary, with the engine's ``EngineStalled``
detection intact.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import ops as rops
from repro.analysis import contracts
from repro.checkpoint import load_checkpoint
from repro.configs.registry import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.models import transformer as tf
from repro.quant import convert
from repro.serving import QueueFull, ServingEngine, ServingFrontend


def _fmt_pct(p: dict | None, unit_ms: bool = True) -> str:
    if p is None:
        return "n/a"
    k = 1e3 if unit_ms else 1.0
    u = "ms" if unit_ms else "s"
    return (f"p50 {p['p50'] * k:.1f}{u} / p99 {p['p99'] * k:.1f}{u} "
            f"(n={p['n']})")


async def _serve(fe: ServingFrontend, prompts, args) -> list:
    """Open-loop client: submit ``prompts`` at ``--arrival-rate`` req/s
    (exp-distributed gaps; 0 = all at once), drain every stream, return
    the handles (None where admission rejected)."""
    rng = np.random.default_rng(1)
    runner = asyncio.create_task(fe.run())
    handles, drains = [], []
    for prompt in prompts:
        if args.arrival_rate > 0:
            await asyncio.sleep(rng.exponential(1.0 / args.arrival_rate))
        try:
            h = fe.submit(prompt, args.max_new,
                          temperature=args.temperature,
                          deadline_s=args.timeout_s)
        except QueueFull as e:
            print(f"  rejected (queue full, {e.pending} in flight)")
            handles.append(None)
            continue
        handles.append(h)
        drains.append(asyncio.create_task(h.result()))
    await asyncio.gather(*drains)
    fe.close()
    await runner
    return handles


def served_config(arch: str, reduced: bool = False, ckpt_dir=None):
    """The config ``--arch`` / ``--reduced`` serve.  Random weights (no
    ``ckpt_dir``) of a decoder draw its head apart from the embedding: a
    tied random head predicts the current token, so greedy streams fall
    into one repeated token (Granite-3-2B's reduced float graph does so
    in some stream of the smoke's prompts at each of 8 weight seeds),
    and a stream of one token could not show a misread cache.  A checkpoint keeps the config it was
    trained at."""
    cfg = get_config(arch)
    if reduced:
        cfg = M.reduce_config(cfg, dtype="float32", vocab=1024)
    if not ckpt_dir and cfg.tie_embeddings and cfg.family != "encoder":
        cfg = dataclasses.replace(cfg, tie_embeddings=False)
    return cfg


def load_quantized(arch: str, reduced: bool = False, ckpt_dir=None):
    """``(cfg, qparams, plans)`` for one model: the checkpoint in
    ``ckpt_dir`` or, without one, seeded random weights drawn at the
    served scale (``init_params(..., served=True)``), quantized to the
    integer datapath."""
    cfg = served_config(arch, reduced, ckpt_dir)
    params = tf.init_params(jax.random.key(0), cfg, served=not ckpt_dir)
    if ckpt_dir:
        params, meta = load_checkpoint(ckpt_dir, (params, None))
        params = params[0]
        print(f"restored step {meta['step']} from {ckpt_dir}")
    print("quantizing to the integer datapath ...")
    qp, plans = convert.quantize_params(params, cfg)
    return cfg, qp, plans


def _prompts(args, vocab: int) -> list:
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(1, vocab, args.prompt_len)]
               for _ in range(args.requests)]
    n = args.shared_prefix
    for p in prompts[1:args.requests // 2]:
        p[:n] = prompts[0][:n]
    return prompts


def main(argv=None, model=None) -> dict:
    """Serve the prompts ``argv`` describes; see the module docstring.
    ``model``: an already-quantized ``(cfg, qparams, plans)`` from
    :func:`load_quantized` for the same ``--arch`` / ``--reduced``, so a
    caller serving one model several times quantizes it once."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube-3-4b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=4,
                    help="tokens per (random, seeded) prompt")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="the first half of the prompts share one "
                         "common prefix of this many tokens")
    ap.add_argument("--warmup", type=int, default=0,
                    help="serve the schedule's first N prompts once "
                         "before the timed window (the compiles land "
                         "there; the prefix cache keeps those prompts)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--cache-mode", default="paged",
                    choices=["paged", "contiguous"],
                    help="KV layout: paged pool (memory O(live tokens)) "
                         "or one contiguous slab per lane")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per physical KV page (paged mode)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="physical pool size incl. the null page "
                         "(default: fully provisioned; smaller values "
                         "undersubscribe the pool)")
    ap.add_argument("--no-fold-wo", action="store_true",
                    help="keep the o-projection requant outside the "
                         "decode/prefill epilogues (numerics identical)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="prompt tokens per batched prefill launch "
                         "(paged mode; must divide or be a multiple of "
                         "--page-size; 0 = token-streaming prefill; "
                         "default: auto ~32 on eligible archs)")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="max prompt tokens prefilled per engine step, "
                         "so decoding sessions keep emitting a token "
                         "every step (default: unbounded)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable cross-session prompt-prefix sharing "
                         "(shared prefixes otherwise map the same "
                         "physical KV pages)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard attention heads "
                         "over a tp-device mesh (must divide the "
                         "arch's KV head count; the process needs tp "
                         "devices); backends without the tp_serving "
                         "capability serve through an exact single-"
                         "device lowering instead")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft up to K tokens per "
                         "live lane and verify all K+1 positions in one "
                         "decode launch (greedy acceptance; streams stay "
                         "bit-exact with --spec-k 0); bounded by the "
                         "kernel's MAX_SQ query budget; 0 = off")
    ap.add_argument("--spec-mode", default="ngram",
                    help="draft proposer (self-speculative, no draft "
                         "model); 'ngram' = prompt-lookup over the "
                         "session's own context")
    ap.add_argument("--max-pending", type=int, default=None,
                    help="admission bound: requests in flight before "
                         "submit() raises QueueFull (default: 4x batch)")
    ap.add_argument("--timeout-s", type=float, default=None,
                    help="per-request deadline in seconds; an expired "
                         "request is evicted (pages reclaimed) and its "
                         "stream ends with terminal state 'timeout'")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="open-loop Poisson arrival rate in requests/s "
                         "(exp-distributed gaps); 0 = submit every "
                         "request up front (closed batch)")
    ap.add_argument("--record-logits", action="store_true",
                    help="fold every logits row a token was chosen from "
                         "into one SHA-256 per request (returned as "
                         "\"digests\"): holds two runs to identical "
                         "logits, not only identical tokens")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--backend", default=None,
                    help="registered op backend (default: REPRO_BACKEND "
                         "env or the arch's kernel_backend); one of "
                         f"{rops.available_backends()}")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    # resolve up front: a typo'd --backend should fail before the
    # (slow) quantization pass, not after
    ops = rops.resolve_ops(args.backend, cfg)
    # ... and reject incoherent prefill flags just as early, with a
    # typed error instead of a kernel-shape failure deep in a launch
    if args.prefill_chunk is not None and args.prefill_chunk > 0:
        if args.cache_mode != "paged":
            ap.error("--prefill-chunk needs --cache-mode paged (chunked "
                     "prefill writes K/V through the page table)")
        if args.prefill_chunk % args.page_size \
                and args.page_size % args.prefill_chunk:
            ap.error(f"--prefill-chunk {args.prefill_chunk} must divide "
                     f"or be a multiple of --page-size {args.page_size} "
                     "so chunk writes tile physical pages")
    if args.prefill_budget is not None and args.prefill_budget < 1:
        ap.error("--prefill-budget must be >= 1 token/step")
    if args.max_pending is not None and args.max_pending < 1:
        ap.error("--max-pending must be >= 1 request")
    if args.timeout_s is not None and args.timeout_s <= 0:
        ap.error("--timeout-s must be > 0 seconds")
    if args.arrival_rate < 0:
        ap.error("--arrival-rate must be >= 0 requests/s")
    if not 0 <= args.shared_prefix <= args.prompt_len:
        ap.error("--shared-prefix must be within [0, --prompt-len]")
    cfg = served_config(args.arch, args.reduced, args.ckpt_dir)
    # --tp validates against the FINAL config (--reduced shrinks the
    # head counts), same early-typed-error policy as the flags above
    try:
        from repro.distributed.tp_serving import validate_tp
        validate_tp(cfg, args.tp)
    except ValueError as e:
        ap.error(f"--tp {args.tp}: {e}")
    # --spec-k likewise validates against the FINAL config: sliding-
    # window / SSM / cross-attention archs (and unknown proposers, and
    # K beyond the kernel's MAX_SQ budget) fail here as an argparse
    # error, not as a shape error inside the verify launch
    if args.spec_k:
        if args.temperature > 0:
            ap.error("--spec-k needs --temperature 0: greedy longest-"
                     "prefix acceptance is only bit-exact against the "
                     "argmax stream; a sampled stream would silently "
                     "diverge")
        try:
            from repro.serving.speculate import validate_spec
            validate_spec(cfg, args.spec_k, args.spec_mode)
        except ValueError as e:
            ap.error(f"--spec-k {args.spec_k}: {e}")
    # the request shape every client will submit must be feasible on
    # the cache geometry this engine is about to build — reject at the
    # CLI boundary with the same typed check frontend.submit() applies
    try:
        contracts.require_request(args.prompt_len, args.max_new,
                                  args.cache_len, window=cfg.window)
    except contracts.RequestInfeasible as e:
        ap.error(f"--prompt-len {args.prompt_len} / --max-new "
                 f"{args.max_new} with --cache-len {args.cache_len}: {e}")
    if model is not None and model[0] != cfg:
        raise ValueError(f"model= holds {model[0].name}, not the config "
                         f"--arch {args.arch} describes")
    cfg, qp, plans = model if model is not None else load_quantized(
        args.arch, args.reduced, args.ckpt_dir)
    n_int8 = sum(l.size for l in jax.tree.leaves(qp)
                 if hasattr(l, "dtype") and l.dtype == jnp.int8)
    print(f"  {n_int8/1e6:.1f}M int8 weights "
          f"({n_int8/2**20:.0f} MiB vs {n_int8*2/2**20:.0f} MiB bf16)")

    eng = ServingEngine(qp, plans, cfg, batch_size=args.batch,
                        cache_len=args.cache_len, ops=ops,
                        cache_mode=args.cache_mode,
                        page_size=args.page_size,
                        num_pages=args.num_pages,
                        fold_wo=not args.no_fold_wo,
                        prefill_chunk=args.prefill_chunk,
                        prefill_budget=args.prefill_budget,
                        prefix_cache=not args.no_prefix_cache,
                        tp=args.tp, spec_k=args.spec_k,
                        spec_mode=args.spec_mode,
                        record_logits=args.record_logits)
    print(f"engine: {eng.describe_str()}")
    prompts = _prompts(args, cfg.vocab)
    t0 = time.time()
    if args.warmup:
        # the schedule's first prompts, served once: the compiles land
        # here, and the prefix cache holds them afterwards, as it would
        # on a server that has seen them
        asyncio.run(_serve(ServingFrontend(eng), prompts[:args.warmup],
                           args))
    warmup_s = time.time() - t0
    fe = ServingFrontend(eng, max_pending=args.max_pending)
    t0 = time.time()
    handles = asyncio.run(_serve(fe, prompts, args))
    dt = time.time() - t0

    d = fe.describe()
    n_tok = d["tokens"]
    print(f"served {d['submitted']} requests / {n_tok} tokens in "
          f"{d['steps']} batched steps, {dt:.1f}s ({n_tok/dt:.1f} tok/s, "
          "int8 KV cache)")
    term = d["terminal"]
    print("  terminal: " + ", ".join(f"{k}={v}" for k, v in term.items()))
    lat = d["latency"]
    print(f"  ttft: {_fmt_pct(lat['ttft_s'])}   inter-token: "
          f"{_fmt_pct(lat['inter_token_s'])}   queue-wait: "
          f"{_fmt_pct(lat['queue_wait_s'])}")
    print(f"  occupancy: mean {d['occupancy']['mean']:.2f}/"
          f"{args.batch} lanes, queue depth: mean "
          f"{d['queue_depth']['mean']:.2f} max {d['queue_depth']['max']}")
    sp = eng.describe()["spec"]
    if sp["k"]:
        rate = f"{sp['accept_rate']:.0%}" \
            if sp["accept_rate"] is not None else "n/a"
        print(f"speculation ({sp['mode']}, k={sp['k']}): "
              f"{sp['accepted']}/{sp['drafted']} drafts accepted "
              f"({rate}), {sp['wasted']} wasted verify rows")
    px = eng.describe()["cache"].get("prefix")
    if px:
        print(f"prefix cache: {px['hits']} hits / {px['misses']} misses, "
              f"{px['tokens_reused']} prompt tokens reused")
    for h in [h for h in handles if h is not None][:4]:
        r = h.request
        print(f"  req {h.uid} [{h.terminal}]: {r.prompt[:8]}... -> "
              f"{r.out_tokens[:10]}...")
    sha = [h and h.request.logits_sha for h in handles]
    return {**d, "engine": eng.describe(),
            "streams": [None if h is None else list(h.tokens)
                        for h in handles],
            "digests": [x.hexdigest() if x else None for x in sha]
            if args.record_logits else None,
            "warmup_s": warmup_s, "window_s": dt}


if __name__ == "__main__":
    main()
