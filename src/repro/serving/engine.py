"""Batched integer serving engine over a paged KV cache.

The serving counterpart of the ASIC's control unit (§III-J): a
continuous-batching scheduler that admits requests into fixed batch
*lanes*, runs the INT8 prefill/decode datapath (int8 KV caches = the
paper's quantization applied to the cache), and retires finished
sequences — all in the fixed-shape XLA world.

Prefill (``prefill_chunk``):

  * **chunked** (default on paged, full-causal, attention+ffn archs) —
    prompts advance ``prefill_chunk`` tokens at a time through ONE
    batched launch of the fused prefill attention kernel, writing K/V
    straight into physical pages through the page table
    (``models.inttransformer.int_prefill_chunk_step`` →
    ``ops.int_paged_prefill``).  A prefill queue interleaves with decode
    steps: ``prefill_budget`` caps the prompt tokens advanced per engine
    step, so decoding sessions keep emitting a token every step while
    long prompts stream in.  Bit-exact against token streaming.
  * **streaming** — the PR 4 path: prompt tokens one at a time through
    the decode step (sliding-window / SSM / MoE / cross archs, and the
    contiguous layout).

Prefix sharing (``prefix_cache``): prompts hash into a per-engine
:class:`~repro.serving.kvcache.PrefixIndex` keyed by token prefixes —
a session whose prompt starts with a previously prefilled prefix maps
the *same physical pages* (allocator refcounts) and skips recomputing
them; the first write into a shared page copy-on-writes it, so sharers
can never corrupt each other and shared-prefix sessions produce token
streams identical to unshared ones.  Under pool pressure the allocator
reclaims cached prefix pages LRU-first.

Cache layouts (``cache_mode``):

  * ``"paged"`` (default) — K/V live in a physical page pool addressed
    through a per-lane page table (``repro.serving.kvcache``).  A
    *session* owns its page list; lanes are just decode positions, so
    cache memory is O(live tokens), pages recycle through a ref-counted
    allocator without zeroing (``valid_len`` masking makes stale
    contents unobservable), and a session can be **preempted** (pages
    kept, lane freed — mid-prefill included) and later resumed
    bit-exactly.  The page table rides into the decode and prefill
    kernels as a scalar-prefetch operand next to ``valid_len``; backends
    without the ``paged_decode`` / ``paged_prefill`` capabilities get
    exact gather/scatter lowerings (repro.ops dispatch).
  * ``"contiguous"`` — the PR 3 layout: one ``cache_len`` slab per lane.

Every decode step dispatches through the configured backend's
``int_decode_attention`` — on ``pallas_fused`` one valid_len-masked
kernel launch that skips dead cache blocks — and, with ``fold_wo``
(default), folds each attention sublayer's output-projection per-channel
requant into that launch's epilogue (``decode_wo_fold``; the chunked
prefill launch folds it too via ``prefill_wo_fold``) — bit-exact vs the
unfolded path.

Tensor parallelism (``tp``): the engine shards its attention datapath
head-wise over a 1-D device mesh (``distributed.tp_serving``) — each
device owns ``Hkv/tp`` KV heads of every physical page and the matching
query-head slice of wq/wk/wv, wo combines int32 partial o-projections
with an exact :func:`~repro.distributed.collectives.psum_int32` *before*
the requant epilogue (so it rounds once), and everything host-side
(allocator, page table, prefix index, scheduler) stays replicated
because page ids are device-agnostic.  Sharding engages when every
backend advertises the ``tp_serving`` capability (the process must then
have ``tp`` devices, or construction raises); with a backend that does
not, the engine serves ``tp > 1`` through an exact single-device gather
lowering (same API, same tokens).  Token streams
are bit-exact across tp degrees: the datapath is all-integer, so the
psum is order-independent and the replicated non-attention sublayers
see identical inputs on every device.

Speculative decoding (``spec_k``): each decode step drafts up to
``spec_k`` tokens per live lane from a self-speculative proposer
(``serving.speculate`` — prompt-lookup over the lane's own prompt +
output, no draft model) and verifies all ``spec_k + 1`` positions in ONE
``int_decode_attention`` launch with the Sq = K+1 stepped mask (fused on
``pallas_fused``, exact oracle elsewhere).  Greedy acceptance commits
the longest draft prefix matching the model's own argmax stream plus
one bonus token; rejected tokens roll back as a position decrement plus
:meth:`~repro.serving.kvcache.PagedKVCache.truncate` (now-empty pages
return to the allocator; stale K/V is hidden by ``valid_len`` and
overwritten by the next step).  Token streams are bit-exact with
``spec_k = 0`` — speculation changes *when* tokens are computed, never
*which* — so it composes with every cache layout, prefill mode and tp
degree.  Greedy only: ``temperature > 0`` requests are rejected with a
typed :class:`~repro.serving.speculate.SpeculationUnsupported`.

Logit digests (``record_logits``): each request folds every logits row
one of its tokens was chosen from into a running SHA-256
(``Request.logits_sha``), so two engines — backends, tp degrees — can
be held to identical logits, not only to identical argmaxes.

Shapes (batch lanes, page pool, logical cache length, prefill chunk) are
fixed at engine construction, so lanes and pages recycle without
recompiling.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
import warnings
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import trace_names
from repro.analysis import contracts
from repro.distributed import tp_serving
from repro.models import intlayers as il
from repro.models import inttransformer as it
from repro.models.common import ArchConfig
from repro.models.transformer import layer_group_spec
from repro.ops import OP_NAMES, resolve_ops
from repro.quant import plans as qplans
from repro.serving import speculate
from repro.serving.kvcache import (NULL_PAGE, CacheLayout,
                                   PagePoolExhausted, PagedKVCache,
                                   PrefixIndex, Session)


class StepInFlight(RuntimeError):
    """A lifecycle operation (``evict`` / ``preempt`` / another
    ``dispatch_step``) was attempted between :meth:`ServingEngine.
    dispatch_step` and :meth:`ServingEngine.commit_step`.  The dispatched
    launch captured snapshots of ``pos`` and the page table, but the
    *scheduler* state (slots, sessions, allocator) it will be committed
    against must not move underneath it — commit the pending step first
    (the async front end's run loop applies cancellations only between
    commit and the next dispatch for exactly this reason)."""


class EngineStalled(RuntimeError):
    """``run_until_done`` exhausted its step budget with sessions still
    queued or on lanes — a stall (pool livelock, starved prefill, a
    budget too small for the workload), not completion.  Carries the
    scheduler state a caller needs to diagnose it: ``max_steps``,
    ``queue_depth``, and per-lane ``slots`` dicts (uid / state / pos /
    prefill_pos)."""

    def __init__(self, max_steps: int, slots, queue_depth: int):
        self.max_steps = max_steps
        self.slots = slots
        self.queue_depth = queue_depth
        lanes = ", ".join(
            "lane %d: uid=%s %s pos=%s prefill_pos=%s" % (
                i, s["uid"], s["state"], s["pos"], s["prefill_pos"])
            for i, s in enumerate(slots) if s is not None) or "all idle"
        super().__init__(
            f"engine stalled: {max_steps} steps exhausted with "
            f"{queue_depth} queued session(s) and unfinished lanes "
            f"({lanes}); raise max_steps, relieve pool pressure, or "
            "evict a session")

# Process-level cache of compiled engine steps (decode and chunked
# prefill), keyed by everything the traced closure captures (cfg, plans,
# shapes, cache geometry, chunk size, the resolved backend per op).  Two
# engines with the same key share ONE executable, so (a) engine
# construction stops paying an XLA recompile and (b) identical request
# streams produce identical tokens across engine instances — separately
# compiled executables of the same program are not guaranteed to agree
# to the last integer on every input (XLA CPU compile variance), which
# shows up as cross-engine token divergence in parity tests.  Bounded
# LRU (insertion order): a process sweeping many distinct (shape, plan)
# combinations evicts the oldest executable instead of pinning one per
# combination forever.
_STEP_CACHE: Dict[tuple, Callable] = {}
_STEP_CACHE_MAX = 16


def _cached_step(key, build: Callable[[], Callable]) -> Callable:
    try:
        hash(key)
    except TypeError:
        return build()              # private: key can't be shared
    fn = _STEP_CACHE.pop(key, None)
    if fn is None:
        fn = build()
    _STEP_CACHE[key] = fn           # (re-)insert most recent
    while len(_STEP_CACHE) > _STEP_CACHE_MAX:
        _STEP_CACHE.pop(next(iter(_STEP_CACHE)))
    return fn


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new_tokens: int = 32
    temperature: float = 0.0
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # running SHA-256 of the logits rows the tokens came from (engines
    # built with record_logits=True)
    logits_sha: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False)


@dataclasses.dataclass
class PendingStep:
    """An in-flight engine step: scheduling (admit / prefill / draft) ran
    and the decode or verify launch was **dispatched** — its ``logits``
    are an unmaterialized device array — but nothing has been sampled or
    committed.  Produced by :meth:`ServingEngine.dispatch_step`, consumed
    exactly once by :meth:`ServingEngine.commit_step`; the window between
    the two is where an async driver overlaps host work (detokenizing /
    distributing the *previous* step's tokens) with the device
    computation.  The launch itself read snapshots (``_snap_pos`` /
    ``_snap_pages``), so host bookkeeping in that window is safe as long
    as the scheduler state commit will walk — ``slots`` and the captured
    ``sessions`` — is left alone (:class:`StepInFlight` guards the
    mutating lifecycle ops)."""

    occupied: int
    kind: str                       # "idle" | "decode" | "verify"
    live: List[int] = dataclasses.field(default_factory=list)
    sessions: List[Session] = dataclasses.field(default_factory=list)
    logits: object = None           # device array, (B, V) or (B, S, V)
    n_new: Optional[np.ndarray] = None
    drafts: Optional[Dict[int, List[int]]] = None


class ServingEngine:
    def __init__(self, qparams, plans: qplans.LayerPlans, cfg: ArchConfig,
                 batch_size: int = 8, cache_len: int = 512,
                 ops=None, seed: int = 0, backend=None,
                 cache_mode: str = "paged", page_size: int = 16,
                 num_pages: Optional[int] = None, kv_dtype: str = "int8",
                 fold_wo: bool = True,
                 prefill_chunk: Optional[int] = None,
                 prefill_budget: Optional[int] = None,
                 prefix_cache: bool = True, tp: int = 1,
                 spec_k: int = 0, spec_mode: str = "ngram",
                 record_logits: bool = False):
        if backend is not None:
            warnings.warn("ServingEngine(backend=...) is deprecated; pass "
                          "ops= (an OpSet or backend name)",
                          DeprecationWarning, stacklevel=2)
            ops = backend if ops is None else ops
        if cache_mode not in ("paged", "contiguous"):
            raise ValueError("cache_mode must be 'paged' or 'contiguous',"
                             f" got {cache_mode!r}")
        if kv_dtype != "int8" and cache_mode != "paged":
            raise ValueError("kv_dtype='int4' needs cache_mode='paged' "
                             "(the packed tier stores per-page requant "
                             "shifts next to the page pools)")
        if prefill_budget is not None and prefill_budget < 1:
            raise ValueError("prefill_budget must be >= 1 token/step, "
                             f"got {prefill_budget}")
        it.refuse_mla(cfg, "ServingEngine")
        self.cfg = cfg
        self.plans = plans
        self.qparams = qparams
        self.batch = batch_size
        self.cache_len = cache_len
        self.fold_wo = fold_wo
        self.ops = resolve_ops(ops, cfg)
        # tensor parallelism: typed validation always (tp must divide
        # Hkv, arch must be head-shardable), then capability negotiation
        # picks the lowering — shard_map over a ("tp",) mesh when every
        # backend advertises ``tp_serving``, else the exact single-
        # device gather lowering (tokens identical either way).  A
        # sharded engine on a process with fewer than ``tp`` devices is
        # an error, never a silent single-device fallback
        tp_serving.validate_tp(cfg, tp)
        # speculative decoding: typed validation at the boundary (k in
        # budget, arch verify-able, proposer registered) — the Sq=K+1
        # launch contract is checked below, once the cache geometry is
        # known
        speculate.validate_spec(cfg, spec_k, spec_mode)
        self.spec_k = spec_k
        self.spec_mode = spec_mode if spec_k else "off"
        self.proposer = speculate.get_proposer(spec_mode) if spec_k \
            else None
        self._spec_drafted = 0
        self._spec_accepted = 0
        self.tp = tp
        self.tp_sharded = tp > 1 and tp_serving.backends_support_tp(
            self.ops)
        if self.tp_sharded and jax.device_count() < tp:
            raise ValueError(
                f"tp={tp} needs {tp} devices for head-sharded serving, "
                f"but this process sees {jax.device_count()} "
                f"({jax.default_backend()}); run on a host with the "
                "devices or serve with tp=1")
        self.mesh = tp_serving.make_tp_mesh(tp) if self.tp_sharded \
            else None
        if self.tp_sharded and self.fold_wo:
            # the folded epilogue requants inside the kernel — before
            # the cross-device psum — which would round per-shard; the
            # sharded step always runs unfolded (requant-rounds-once)
            self.fold_wo = False
        # whether prefill/cross attention runs as one fused kernel launch
        # (pallas / pallas_fused) or the two-pass oracle path (ref)
        self.attn_fused = \
            self.ops.backend_for("int_attention").fused_attention
        # whether the per-step decode attention over the ragged KV cache
        # runs as the backend's single-launch valid_len-masked kernel
        # (the ``fused_decode`` capability flag; pallas_fused only) or
        # the full-matrix oracle; either way the step dispatches through
        # the backend — there is no hardcoded oracle call on the decode
        # path (models.intlayers.int_attn_decode)
        decode_be = self.ops.backend_for("int_decode_attention")
        self.decode_fused = getattr(decode_be, "fused_decode", False)
        self.decode_paged_native = getattr(decode_be, "paged_decode", False)
        self.prefill_paged_native = getattr(
            self.ops.backend_for("int_paged_prefill"), "paged_prefill",
            False)
        self.rng = np.random.default_rng(seed)
        self.record_logits = record_logits
        self.rope_tab = il.build_rope_table(cache_len + 1, cfg.hd,
                                            cfg.rope_theta) \
            if cfg.pos == "rope" else None
        # logical per-session cache length (the attention window bounds
        # it, mirroring init_decode_cache)
        self.L = min(cache_len, cfg.window) if cfg.window > 0 else cache_len
        gl, ng, kinds = layer_group_spec(cfg)
        self._has_ssm = any(k[0] == "ssm" for k in kinds)
        self.paged = cache_mode == "paged"
        if self.paged:
            self.layout = CacheLayout.fit(batch_size, self.L, page_size,
                                          num_pages, kv_dtype=kv_dtype)
            self.kv = PagedKVCache(self.layout)
            self.caches = it.init_decode_cache(cfg, batch_size, cache_len,
                                               layout=self.layout)
        else:
            self.layout = None
            self.kv = None
            self.caches = it.init_decode_cache(cfg, batch_size, cache_len)
        self.prefill_chunk = self._resolve_prefill_chunk(prefill_chunk)
        self._use_chunked = self.prefill_chunk > 0
        self.prefill_budget = prefill_budget
        self._chunkable = self.paged and it.chunked_prefill_supported(cfg)
        if self._chunkable and prefix_cache:
            self.prefix: Optional[PrefixIndex] = PrefixIndex(
                self.kv.allocator, self.layout.page_size)
            # pool pressure reclaims cached-but-unreferenced prefix
            # pages before any allocation fails
            self.kv.allocator.reclaim = self._reclaim_prefix
        else:
            self.prefix = None
        self._cow_copies = 0
        if self.spec_k:
            # construction-time twin of the verify launch's own
            # require_launch: the Sq = spec_k + 1 stepped-mask decode
            # must satisfy the kernel contract on this cache geometry
            # (policy declines are fine — the backend falls back
            # exactly; contract violations raise here, typed)
            contracts.require_launch(contracts.check_launch(
                "int_decode_attention", b=self.batch,
                sq=self.spec_k + 1, h=cfg.n_heads, hkv=cfg.n_kv_heads,
                d=cfg.hd, **self._decode_geom()))
        if self.tp_sharded:
            # static per-shard launch contracts first (shape errors name
            # the tp clause, not a kernel assert three layers down),
            # then lay the params and pools out over the mesh
            self._check_tp_launches()
            self._qspecs = tp_serving.qparam_pspecs(qparams)
            self._cspecs = tp_serving.cache_pspecs(self.caches)
            self.qparams = tp_serving.shard_put(self.qparams,
                                                self._qspecs, self.mesh)
            self.caches = tp_serving.shard_put(self.caches,
                                               self._cspecs, self.mesh)
        self.pos = np.zeros(batch_size, np.int32)
        self.slots: List[Optional[Session]] = [None] * batch_size
        self.queue: List[Session] = []
        self._finished: List[Request] = []
        self._uid = 0
        self._inflight: Optional[PendingStep] = None
        self._decode = self._shared_decode_step()
        self._prefill_step = self._shared_prefill_step() \
            if self._use_chunked else None
        self._verify = self._shared_verify_step() if self.spec_k \
            else None

    def _resolve_prefill_chunk(self, prefill_chunk: Optional[int]) -> int:
        """Validate/auto-size the prefill chunk.  0 disables chunked
        prefill (token streaming); None auto-sizes it for eligible
        engines.  Typed errors here, not kernel-shape failures later."""
        chunkable = self.paged and it.chunked_prefill_supported(self.cfg)
        if prefill_chunk is None:
            if not chunkable:
                return 0
            ps = self.layout.page_size
            # ~32-token chunks, page-compatible by construction
            return min(ps * max(1, 32 // ps), self.layout.logical_len)
        if prefill_chunk == 0:
            return 0
        if prefill_chunk < 0:
            raise ValueError("prefill_chunk must be >= 0, got "
                             f"{prefill_chunk}")
        if not self.paged:
            raise ValueError("prefill_chunk needs cache_mode='paged' "
                             "(chunked prefill writes K/V through the "
                             "page table)")
        if not chunkable:
            raise ValueError(
                "chunked prefill is unsupported for arch "
                f"{self.cfg.name!r}: it needs window == 0 and "
                "attention+ffn sublayers only (sliding-window, SSM, MoE "
                "and cross-attention archs keep token-streaming "
                "prefill); pass prefill_chunk=0")
        ps = self.layout.page_size
        if prefill_chunk % ps and ps % prefill_chunk:
            raise ValueError(
                f"prefill_chunk={prefill_chunk} must divide or be a "
                f"multiple of page_size={ps} so chunk writes tile "
                "physical pages")
        return min(prefill_chunk, self.layout.logical_len)

    def _decode_geom(self) -> dict:
        """The decode launch's cache-geometry params for
        :func:`~repro.analysis.contracts.check_launch`."""
        if self.paged:
            geom = dict(max_pages=self.layout.max_pages,
                        page_size=self.layout.page_size)
            if self.layout.kv_dtype == "int4":
                geom.update(kv_pack=True,
                            num_pages=self.layout.num_pages)
            return geom
        return dict(L=self.L)

    def _check_tp_launches(self):
        """Per-shard launch contracts for the sharded step: under
        shard_map every device launches the attention kernels with
        ``H/tp`` / ``Hkv/tp`` heads, and :func:`~repro.analysis.
        contracts.check_tp_launch` is the offline twin of the
        ``require_launch`` each wrapper will run on those local
        shapes.  Policy declines are fine (the backend falls back
        exactly, per shard); contract violations raise here, at
        construction."""
        cfg, tp = self.cfg, self.tp
        geom = self._decode_geom()
        # one check per decode-launch Sq the engine will issue: 1 for
        # the plain step, spec_k + 1 for the speculative verify (Sq is
        # replicated under the mesh — only the head counts shard)
        sqs = (1,) if not self.spec_k else (1, self.spec_k + 1)
        for sq in sqs:
            contracts.require_launch(contracts.check_tp_launch(
                "int_decode_attention", tp=tp, b=self.batch, sq=sq,
                h=cfg.n_heads, hkv=cfg.n_kv_heads, d=cfg.hd, **geom))
        if self._use_chunked:
            pf = dict(max_pages=self.layout.max_pages,
                      page_size=self.layout.page_size)
            if self.layout.kv_dtype == "int4":
                pf.update(kv_pack=True, num_pages=self.layout.num_pages)
            contracts.require_launch(contracts.check_tp_launch(
                "int_paged_prefill", tp=tp, b=self.batch,
                c=self.prefill_chunk, h=cfg.n_heads, hkv=cfg.n_kv_heads,
                d=cfg.hd, **pf))

    # ------------------------------------------------------ compiled step --

    def _step_key(self, tag: str, *extra) -> tuple:
        geometry = ("paged", self.layout.page_size, self.layout.num_pages,
                    self.layout.max_pages, self.L,
                    self.layout.kv_dtype) if self.paged \
            else ("contiguous",)
        # mesh geometry: sharded engines key on (tp, device ids) — a
        # differently-sized or differently-placed mesh must not share
        # an executable; every unsharded engine (tp=1 AND the tp>1
        # gather fallback, which traces the identical single-device
        # program) collapses onto one ("mesh", 1) entry
        mesh = ("mesh", self.tp,
                tuple(int(d.id) for d in self.mesh.devices.flat)) \
            if self.tp_sharded else ("mesh", 1)
        return (tag, self.cfg, self.plans, self.batch, self.cache_len,
                geometry, self.fold_wo, mesh, *extra,
                tuple(id(self.ops.backend_for(op)) for op in OP_NAMES))

    def _shared_decode_step(self) -> Callable:
        """The jitted decode step, shared across same-shaped engines via
        ``_STEP_CACHE`` (falls back to a private jit when the key is
        unhashable, e.g. exotic plan objects).

        The callable closes over (plans, cfg, rope_tab, ops, cache
        geometry) only — never ``self`` — so a retired engine's weights,
        caches and sessions are not pinned by the process-global cache.
        The key carries the page-pool shape and mesh geometry: engines
        over differently-provisioned pools or meshes must not share an
        executable."""
        plans, cfg, rope_tab, ops = (self.plans, self.cfg,
                                     self.rope_tab, self.ops)
        page_size = self.layout.page_size if self.paged else 0
        max_len = self.L if self.paged else 0
        fold_wo = self.fold_wo
        tp_axis = None
        if self.tp_sharded:
            cfg = tp_serving.local_cfg(cfg, self.tp)
            tp_axis = tp_serving.TP_AXIS

        def decode_step(qparams, caches, tokens, pos, pages=None):
            return it.int_decode_step(
                qparams, caches, tokens, pos, plans, cfg, rope_tab,
                ops=ops, pages=pages, page_size=page_size,
                max_len=max_len, fold_wo=fold_wo, tp_axis=tp_axis)

        if self.tp_sharded:
            decode_step = self._tp_wrap(
                decode_step, n_host_args=3 if self.paged else 2)
        return _cached_step(self._step_key("decode"),
                            lambda: jax.jit(decode_step))

    def _shared_prefill_step(self) -> Callable:
        """The jitted chunked-prefill step (tokens (B, C), base_pos (B,),
        prefill-view page table) -> new caches; cached exactly like the
        decode step, with the chunk size in the key."""
        plans, cfg, rope_tab, ops = (self.plans, self.cfg,
                                     self.rope_tab, self.ops)
        page_size = self.layout.page_size
        fold_wo = self.fold_wo
        tp_axis = None
        if self.tp_sharded:
            cfg = tp_serving.local_cfg(cfg, self.tp)
            tp_axis = tp_serving.TP_AXIS

        def prefill_chunk_step(qparams, caches, tokens, base_pos, pages):
            return it.int_prefill_chunk_step(qparams, caches, tokens,
                                             base_pos, plans, cfg,
                                             rope_tab, ops=ops,
                                             pages=pages,
                                             page_size=page_size,
                                             fold_wo=fold_wo,
                                             tp_axis=tp_axis)

        if self.tp_sharded:
            prefill_chunk_step = self._tp_wrap(
                prefill_chunk_step, n_host_args=3, caches_only=True)
        return _cached_step(self._step_key("prefill", self.prefill_chunk),
                            lambda: jax.jit(prefill_chunk_step))

    def _shared_verify_step(self) -> Callable:
        """The jitted speculative verify step (tokens (B, S = spec_k+1)
        right-aligned, pos (B,), n_new (B,), page table) -> (logits
        (B, S, V), new caches); cached exactly like the decode step,
        with a ("spec", S) element in the key — a spec engine and a
        plain engine (or two different spec_k) must not share an
        executable."""
        plans, cfg, rope_tab, ops = (self.plans, self.cfg,
                                     self.rope_tab, self.ops)
        page_size = self.layout.page_size if self.paged else 0
        max_len = self.L if self.paged else 0
        fold_wo = self.fold_wo
        tp_axis = None
        if self.tp_sharded:
            cfg = tp_serving.local_cfg(cfg, self.tp)
            tp_axis = tp_serving.TP_AXIS

        def verify_step(qparams, caches, tokens, pos, n_new, pages=None):
            return it.int_verify_step(
                qparams, caches, tokens, pos, n_new, plans, cfg,
                rope_tab, ops=ops, pages=pages, page_size=page_size,
                max_len=max_len, fold_wo=fold_wo, tp_axis=tp_axis)

        if self.tp_sharded:
            verify_step = self._tp_wrap(
                verify_step, n_host_args=4 if self.paged else 3)
        return _cached_step(self._step_key("spec", self.spec_k + 1),
                            lambda: jax.jit(verify_step))

    def _tp_wrap(self, step: Callable, n_host_args: int,
                 caches_only: bool = False) -> Callable:
        """shard_map a local step over the engine's ``("tp",)`` mesh:
        qparams and caches flow in under their head-sharding specs,
        the ``n_host_args`` scheduler operands (tokens, positions, page
        table) replicate, and the returned caches keep their sharding so
        the next step consumes them in place.  Logits come back
        replicated — every device computed the identical full-width
        value after the exact wo psum (``check_vma=False``: the
        replication invariant is by integer-exactness construction, and
        the varying-axes check doesn't trace through the pallas
        launches)."""
        host = tuple(P() for _ in range(n_host_args))
        in_specs = (self._qspecs, self._cspecs) + host
        out_specs = self._cspecs if caches_only else (P(), self._cspecs)
        return jax.shard_map(step, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    # ------------------------------------------------------ scheduling ---

    def submit(self, req: Request) -> Session:
        """Queue a request; returns the Session that owns its cache
        pages for the rest of its life (evict/preempt take Sessions).

        Impossible requests fail HERE, typed, not deep inside a step:
        :func:`~repro.analysis.contracts.require_request` rejects a
        prompt longer than the logical cache (prefill would write past
        the page table and silently corrupt live positions) and — for
        full-causal archs — a ``prompt + max_new_tokens`` stream that
        overruns ``cache_len`` (the engine retires lanes at ``pos >=
        cache_len``, so such a request is guaranteed to come back short;
        the exact bound is ``len(prompt) - 1 + max_new_tokens <=
        cache_len``).  Transient *pool* pressure is not checked — that
        is an admission-time concern (``PagePoolExhausted`` when the
        prompt can never fit the pool; requeue-and-retry otherwise)."""
        if self.spec_k and req.temperature > 0:
            raise speculate.SpeculationUnsupported(
                f"spec_k={self.spec_k} serves greedy requests only: "
                "acceptance keeps the longest draft prefix matching the "
                "argmax stream, so a temperature="
                f"{req.temperature} sampled stream would silently "
                "diverge from the non-speculative engine; sample with "
                "spec_k=0")
        contracts.require_request(len(req.prompt), req.max_new_tokens,
                                  self.cache_len, window=self.cfg.window)
        sess = Session(uid=self._uid, request=req)
        self._uid += 1
        self.queue.append(sess)
        return sess

    def _admit(self):
        for slot in range(self.batch):
            if self.slots[slot] is None and self.queue:
                sess = self.queue[0]
                if sess.state == "preempted":
                    self.queue.pop(0)
                    self._rebind(sess, slot)
                    continue
                if not self._try_bind_new(sess, slot):
                    break           # pool pressure: retry next step

    @staticmethod
    def _n_pre(sess: Session) -> int:
        return len(sess.request.prompt) - 1

    def _try_bind_new(self, sess: Session, slot: int) -> bool:
        """Admit a queued session: longest-prefix lookup, all-or-nothing
        page reservation for the rest of the prompt, lane binding.
        Returns False under transient pool pressure (session stays
        queued); raises :class:`PagePoolExhausted` when the prompt can
        never fit."""
        n_pre = self._n_pre(sess)
        shared: List[int] = []
        if self.prefix is not None and n_pre > 0:
            hit = self.prefix.lookup(sess.request.prompt, n_pre)
            if hit is not None:
                shared = list(hit.pages)    # retained for this session
                sess.prefill_pos = hit.count
        if self.paged:
            try:
                reserved = self._reserve_prefill(sess, n_pre, shared)
            except PagePoolExhausted:
                # the never-fits raise must not leak the refcounts the
                # prefix lookup retained (the caller may keep stepping)
                for page in shared:
                    self.kv.allocator.release(page)
                sess.prefill_pos = 0
                raise
            if not reserved:
                for page in shared:
                    self.kv.allocator.release(page)
                sess.prefill_pos = 0
                return False
        self.queue.pop(0)
        self.slots[slot] = sess
        self.pos[slot] = sess.prefill_pos
        sess.pos = sess.prefill_pos
        if self.paged:
            self.kv.bind(sess, slot)
        else:
            sess.slot = slot
        sess.state = "prefilling"
        self._reset_slot_cache(slot)
        if sess.prefill_pos >= n_pre:
            # nothing to prefill (single-token prompt or a full prefix
            # hit): straight to decode
            self._finish_prefill(slot, sess)
        return True

    def _reserve_prefill(self, sess: Session, n_pre: int,
                         shared: List[int]) -> bool:
        """Reserve the pages the prompt prefill will write, so admission
        is all-or-nothing (no half-prefilled session stuck on a lane);
        ``shared`` prefix pages already cover ``sess.prefill_pos``
        tokens.  Chunk padding past the prompt needs no pages — the
        scatter routes writes through unmapped table entries to the
        null page.  Returns False under transient pool pressure; raises
        :class:`PagePoolExhausted` when the prompt can never fit."""
        span = min(n_pre, self.L)
        blocks = -(-span // self.layout.page_size) if span > 0 else 0
        need = blocks - len(shared)
        # never-fits is judged on TOTAL blocks, shared pages included —
        # they are pool pages too, so a prompt whose block count exceeds
        # the pool can never fit no matter how much of it is cached
        if blocks > self.layout.num_pages - 1:
            raise PagePoolExhausted(
                f"prompt needs {blocks} pages, pool only has "
                f"{self.layout.num_pages - 1}")
        acquired: List[int] = []
        try:
            while len(acquired) < need:
                acquired.append(self.kv.allocator.alloc())
        except PagePoolExhausted:
            for page in acquired:
                self.kv.allocator.release(page)
            return False
        sess.pages = shared + acquired
        return True

    def _rebind(self, sess: Session, slot: int):
        """Resume a preempted session: reattach its page-table row and
        position — its K/V pages were never touched, so decode (or the
        remaining prefill, for mid-prefill preemption) continues
        bit-exactly where it stopped."""
        self.slots[slot] = sess
        self.pos[slot] = sess.pos
        self.kv.bind(sess, slot)
        if sess.last_token is None:
            sess.state = "prefilling"   # preempted mid-prefill

    def _finish_prefill(self, slot: int, sess: Session):
        n_pre = self._n_pre(sess)
        sess.prefill_pos = n_pre
        sess.state = "active"
        self.pos[slot] = n_pre
        sess.pos = n_pre
        sess.last_token = sess.request.prompt[-1]
        if self.prefix is not None and n_pre > 0:
            self.prefix.register(sess.request.prompt, n_pre, sess.pages)

    # --------------------------------------------------------- prefill ---

    def _advance_prefill(self):
        """Advance prefilling lanes, at most ``prefill_budget`` prompt
        tokens per engine step (None = finish them all, the
        pre-scheduler semantics; the cap is chunk-granular — one chunk
        minimum per step so the scheduler always progresses).  Chunked
        engines batch the included lanes into one fused-kernel launch
        per round; streaming engines feed tokens through the decode
        step."""
        budget = math.inf if self.prefill_budget is None \
            else self.prefill_budget
        while budget > 0:
            lanes = [i for i, s in enumerate(self.slots)
                     if s is not None and s.state == "prefilling"]
            if not lanes:
                return
            if self._use_chunked:
                budget -= self._prefill_chunk_round(lanes, budget)
            else:
                budget -= self._prefill_stream_round(lanes, budget)

    def _prefill_stream_round(self, lanes: List[int], budget) -> int:
        """Token-streaming prefill through the decode step (slot-local;
        keeps every shape static)."""
        spent = 0
        for i in lanes:
            sess = self.slots[i]
            prompt = sess.request.prompt
            n_pre = self._n_pre(sess)
            while sess.prefill_pos < n_pre and spent < budget:
                self._step_one(i, prompt[sess.prefill_pos])
                sess.prefill_pos += 1
                spent += 1
            if sess.prefill_pos >= n_pre:
                self._finish_prefill(i, sess)
        return max(spent, 1)

    def _prefill_chunk_round(self, lanes: List[int], budget) -> int:
        """One batched chunk round through a single fused-prefill
        launch.  Lanes are included while the remaining ``budget``
        allows (chunk granularity, one lane minimum so the scheduler
        always progresses); the rest wait for the next engine step.
        Returns the real prompt tokens advanced (pad tokens are free —
        their K/V writes land on positions decode overwrites before
        ``valid_len`` marks them live, or on the null page)."""
        C = self.prefill_chunk
        ps = self.layout.page_size
        logical = self.layout.logical_len
        toks = np.zeros((self.batch, C), np.int32)
        base = np.zeros(self.batch, np.int32)
        spent = 0
        included: List[int] = []
        for i in lanes:
            if included and spent >= budget:
                break               # chunk-granularity budget cap
            sess = self.slots[i]
            prompt = sess.request.prompt
            b0 = sess.prefill_pos
            base[i] = b0
            real = min(C, self._n_pre(sess) - b0)
            toks[i, :real] = prompt[b0:b0 + real]
            spent += real
            included.append(i)
            # copy-on-write any shared (prefix-index / multi-session)
            # page this chunk will write into — only the partially
            # filled page at an unaligned prefix boundary can be shared
            blk_hi = (min(b0 + C, logical) - 1) // ps
            for blk in range(b0 // ps, min(blk_hi + 1, len(sess.pages))):
                if self.kv.allocator.refcount[sess.pages[blk]] > 1:
                    self._cow(sess, blk)
        lanes = included
        # the prefill *view* of the page table: rows of lanes not in
        # this round (idle, decoding, or budgeted out) are nulled, so
        # their (discarded) chunk writes land on the null page instead
        # of live pages
        view = self.kv.page_table.snapshot()
        for slot in range(self.batch):
            if slot not in lanes:
                view[slot] = NULL_PAGE
        self.caches = self._prefill_step(self.qparams, self.caches,
                                         jnp.asarray(toks),
                                         jnp.asarray(base),
                                         jnp.asarray(view))
        for i in lanes:
            sess = self.slots[i]
            n_pre = self._n_pre(sess)
            sess.prefill_pos = min(sess.prefill_pos + C, n_pre)
            self.pos[i] = sess.prefill_pos
            sess.pos = sess.prefill_pos
            if sess.prefill_pos >= n_pre:
                self._finish_prefill(i, sess)
        return max(spent, 1)

    def _reset_slot_cache(self, slot: int):
        """Zero a recycled lane's lane-indexed cache state (Mamba SSD
        state, conv tails, cross memory).  Paged attention pools are
        *not* lane-indexed and are never zeroed — ``valid_len`` masking
        makes stale page contents unobservable (the bit-exact-reuse
        invariant of repro.serving.kvcache)."""
        new_caches = []
        for c in self.caches:
            nc = dict(c)
            for key, leaf in c.items():
                # page-pool state is never lane-indexed: the pools stay
                # (valid_len masking) and the per-page requant shifts
                # must survive too — their (ng, num_pages) shape could
                # coincidentally match the batch test below
                if self.paged and key in ("k8", "v8",
                                          "k_shift", "v_shift"):
                    continue
                if leaf.ndim >= 2 and leaf.shape[1] == self.batch:
                    nc[key] = leaf.at[:, slot].set(0)
            new_caches.append(nc)
        self.caches = new_caches

    # --------------------------------------------------- paged bookkeeping

    def _reclaim_prefix(self):
        """Allocator pressure hook: evict prefix-index entries LRU-first
        until a page frees (or the index drains) — cached prefixes cost
        only otherwise-idle pages."""
        while self.kv.allocator.free_pages == 0 and self.prefix is not None \
                and self.prefix.evict_lru():
            pass

    def _cow(self, sess: Session, blk: int):
        """Copy-on-write: give ``sess`` a private copy of a shared page
        before a write lands on it.  Shared pages arise from the prefix
        index (and sessions sharing a prefix through it); copying before
        the first divergent write keeps every sharer's — and the cached
        prefix's — K/V bit-exact."""
        old = sess.pages[blk]
        try:
            new = self.kv.allocator.alloc()
        except PagePoolExhausted:
            # the allocator's pressure reclaim may have just evicted the
            # prefix entries that shared this page — if the session is
            # now its only holder, write in place instead of copying
            if self.kv.allocator.refcount[old] == 1:
                return
            raise
        new_caches = []
        for c in self.caches:
            nc = dict(c)
            # the per-page requant shifts are page-indexed on the same
            # axis, so a CoW copies the source page's shift along with
            # its bytes (today every page shares the static KV_SHIFT;
            # the copy keeps the invariant if shifts ever diverge)
            for key in ("k8", "v8", "k_shift", "v_shift"):
                if key in c:
                    nc[key] = c[key].at[:, new].set(c[key][:, old])
            new_caches.append(nc)
        self.caches = new_caches
        self.kv.allocator.release(old)
        sess.pages[blk] = new
        if sess.slot is not None:
            self.kv.page_table.table[sess.slot, blk] = new
        self._cow_copies += 1

    def _ensure_write_pages(self, n_new=None):
        """Before a decode step, make the page under every live lane's
        write position resident (append-only allocation; raises
        :class:`PagePoolExhausted` when the pool is out) and exclusively
        owned (copy-on-write for pages shared through the prefix
        index).  ``n_new`` (B,) widens the per-lane write span to
        ``[pos, pos + n_new)`` for the speculative verify launch —
        every block the span touches is made resident and CoW'd, so a
        draft write can never land on a page the prefix index (or a
        prefix-sharing sibling) still reads."""
        if not self.paged:
            return
        for slot, sess in enumerate(self.slots):
            if sess is None:
                continue
            p = int(self.pos[slot])
            span = 1 if n_new is None else int(n_new[slot])
            for j in range(span):
                q = p + j
                wslot = q % self.cfg.window if self.cfg.window > 0 else q
                wslot = min(wslot, self.L - 1)
                self.kv.ensure(sess, wslot)
                blk = wslot // self.layout.page_size
                if self.kv.allocator.refcount[sess.pages[blk]] > 1:
                    self._cow(sess, blk)

    def _require_committed(self, op: str):
        if self._inflight is not None:
            raise StepInFlight(
                f"{op} while a dispatched step is uncommitted: call "
                "commit_step(pending) first — the pending launch will "
                "be committed against the sessions it captured")

    def evict(self, sess: Session):
        """Cancel a session: free its lane and release every page it
        owns (they return to the allocator at refcount zero — pages the
        prefix index also holds stay cached for future prompts)."""
        self._require_committed("evict")
        if sess in self.queue:
            self.queue.remove(sess)
        if sess.slot is not None:
            self.pos[sess.slot] = 0
            self.slots[sess.slot] = None
        if self.paged:
            self.kv.release(sess)
        else:
            sess.slot = None
            sess.state = "done"

    def preempt(self, sess: Session):
        """Take a live session off its lane but keep its pages: it goes
        back to the queue head and resumes bit-exactly (same physical
        K/V) when a lane frees up — decoding sessions resume decode,
        mid-prefill sessions resume the prompt at ``prefill_pos``.
        Paged mode only — the contiguous layout ties cache contents to
        the lane."""
        self._require_committed("preempt")
        if not self.paged:
            raise ValueError("preempt needs cache_mode='paged' (the "
                             "contiguous layout ties K/V to the lane)")
        if self._has_ssm:
            raise ValueError("preempt is unsupported for SSM/hybrid "
                             "archs: Mamba state is lane-indexed")
        if sess.state not in ("active", "prefilling") or sess.slot is None:
            raise ValueError("cannot preempt session in state "
                             f"{sess.state!r}")
        slot = sess.slot
        sess.pos = int(self.pos[slot])
        self.kv.unbind(sess)
        self.slots[slot] = None
        self.pos[slot] = 0
        self.queue.insert(0, sess)

    def _retire(self, slot: int):
        sess = self.slots[slot]
        sess.request.done = True
        self.slots[slot] = None
        self.pos[slot] = 0
        if self.paged:
            self.kv.release(sess)
        else:
            sess.slot = None
            sess.state = "done"
        self._finished.append(sess.request)

    # ---------------------------------------------------------- decode ---

    def _snap_pos(self):
        """Snapshot ``self.pos`` for a decode call.

        ``jnp.asarray`` on a numpy array may alias its buffer (zero-copy)
        while dispatch is asynchronous; the engine then mutates
        ``self.pos`` in place (``+= 1``), racing the executing step and
        intermittently decoding at the wrong position.  An explicit copy
        makes the hand-off a snapshot.  (This was a real, observed ~1/10
        token-stream flake on CPU.)  The page table gets the same
        treatment in ``_snap_pages``.
        """
        return jnp.asarray(self.pos.copy())

    def _snap_pages(self):
        return jnp.asarray(self.kv.page_table.snapshot())

    def _run_decode(self, toks):
        if self.paged:
            return self._decode(self.qparams, self.caches,
                                jnp.asarray(toks), self._snap_pos(),
                                self._snap_pages())
        return self._decode(self.qparams, self.caches, jnp.asarray(toks),
                            self._snap_pos())

    def _run_verify(self, toks, n_new):
        n_new = jnp.asarray(n_new.copy())      # same snapshot rule as pos
        if self.paged:
            return self._verify(self.qparams, self.caches,
                                jnp.asarray(toks), self._snap_pos(),
                                n_new, self._snap_pages())
        return self._verify(self.qparams, self.caches, jnp.asarray(toks),
                            self._snap_pos(), n_new)

    def _step_one(self, slot: int, token: int):
        toks = np.zeros(self.batch, np.int32)
        toks[slot] = token
        self._ensure_write_pages()
        logits, self.caches = self._run_decode(toks)
        self.pos[slot] += 1
        self.slots[slot].pos = int(self.pos[slot])
        return np.asarray(logits[slot])

    def _at_cache_end(self, slot: int) -> bool:
        """Whether the lane's NEXT token has nowhere to go: emitting it
        would need a K/V write at logical slot ``pos`` (``pos ≤ L - 1``
        for full-causal caches) and a RoPE rotation at ``pos`` (the
        table spans ``cache_len + 1`` positions).  Retiring at
        ``pos >= cache_len`` makes the final cache slot usable — the
        old ``>= cache_len - 1`` boundary retired one token early,
        wasting it."""
        return self.pos[slot] >= self.cache_len

    def step(self) -> int:
        """One engine step: admit, advance prefill (budgeted), and one
        batched decode for lanes whose prefill is complete (with
        ``spec_k > 0``, one batched draft-verify launch committing up to
        ``spec_k + 1`` tokens per lane).  Returns the number of occupied
        lanes.

        ``step()`` is exactly ``commit_step(dispatch_step())`` — the
        split exists so an async driver can overlap host work with the
        device computation; the synchronous composition is bit-exact
        with the pre-split engine by construction."""
        return self.commit_step(self.dispatch_step())

    def dispatch_step(self) -> PendingStep:
        """The scheduling + dispatch half of :meth:`step`: admit queued
        sessions, advance prefill (budgeted), draft (``spec_k > 0``) and
        dispatch the batched decode / verify launch WITHOUT materializing
        its logits.  Returns the :class:`PendingStep` the caller must
        pass to :meth:`commit_step` — between the two the device is
        computing while the host is free (the launch consumed snapshots
        of ``pos`` and the page table, so host-side reads are safe), but
        ``evict`` / ``preempt`` / another dispatch raise
        :class:`StepInFlight` until the commit lands."""
        self._require_committed("dispatch_step")
        with trace_names.host_span("engine.dispatch"):
            return self._dispatch()

    def _dispatch(self) -> PendingStep:
        self._admit()
        self._advance_prefill()
        occupied = sum(s is not None for s in self.slots)
        live = [i for i, s in enumerate(self.slots)
                if s is not None and s.state == "active"]
        if not live:
            return PendingStep(occupied, "idle")
        sessions = list(self.slots)
        if self.spec_k:
            toks, n_new, drafts = self._build_spec_batch(live)
            self._ensure_write_pages(n_new)
            logits, self.caches = self._run_verify(toks, n_new)
            pending = PendingStep(occupied, "verify", live, sessions,
                                  logits, n_new, drafts)
        else:
            toks = np.zeros(self.batch, np.int32)
            for i in live:
                toks[i] = self.slots[i].last_token
            self._ensure_write_pages()
            logits, self.caches = self._run_decode(toks)
            pending = PendingStep(occupied, "decode", live, sessions,
                                  logits)
        self._inflight = pending
        return pending

    def commit_step(self, pending: PendingStep) -> int:
        """The sampling + bookkeeping half of :meth:`step`: materialize
        the dispatched logits (this is where the host blocks on the
        device), sample / greedily accept, advance positions, retire
        finished lanes.  Returns the occupied-lane count, mirroring
        ``step()``."""
        if pending.kind == "idle":
            return pending.occupied
        if self._inflight is not pending:
            raise StepInFlight(
                "commit_step got a PendingStep that is not the one in "
                "flight: each dispatch_step() result is committed "
                "exactly once, in order")
        self._inflight = None
        with trace_names.host_span("engine.commit"):
            if pending.kind == "verify":
                self._commit_spec(pending)
            else:
                self._commit_decode(pending)
        return pending.occupied

    def _commit_decode(self, pending: PendingStep):
        with trace_names.host_span("engine.sample"):
            logits = np.asarray(pending.logits)[:, :self.cfg.vocab]
            picks = {i: self._sample(self.slots[i].request, logits[i])
                     for i in pending.live}
        for i in pending.live:
            sess = self.slots[i]
            req = sess.request
            self.pos[i] += 1
            sess.pos = int(self.pos[i])
            self._record(req, logits[i])
            nxt = picks[i]
            req.out_tokens.append(nxt)
            sess.last_token = nxt
            if len(req.out_tokens) >= req.max_new_tokens \
                    or self._at_cache_end(i):
                self._retire(i)

    def _record(self, req: Request, rows: np.ndarray):
        """Fold the logits rows ``req``'s committed tokens were chosen
        from into its running digest (``record_logits``)."""
        if not self.record_logits:
            return
        if req.logits_sha is None:
            req.logits_sha = hashlib.sha256()
        req.logits_sha.update(np.ascontiguousarray(rows).tobytes())

    def _sample(self, req: Request, row: np.ndarray) -> int:
        """Next token from one lane's logits row.

        ``temperature <= 0``: greedy argmax.  Otherwise a softmax
        sample: the row is the head's *dequantized* float logits (int32
        accumulator × per-channel ``head_scale`` × ``s_act8`` —
        ``models.inttransformer.logits_int``), so ``temperature`` acts
        on that documented scale, pinned to float64 so the distribution
        is platform-reproducible.  Randomness comes from the engine's
        own seeded ``np.random.default_rng(seed)`` Generator — the
        sampled stream is a pure function of (seed, schedule), and two
        engines stepping identical schedules reproduce each other
        token for token."""
        if req.temperature <= 0:
            return int(np.argmax(row))
        z = row.astype(np.float64)
        p = np.exp((z - z.max()) / req.temperature)
        p /= p.sum()
        return int(self.rng.choice(len(p), p=p))

    def _build_spec_batch(self, live: List[int]):
        """The draft half of a speculative decode round.

        Per live lane: the proposer drafts ``k_b = min(spec_k,
        remaining - 1, L - pos - 1)`` tokens (never past the request's
        budget or the cache), and the lane's ``[last_token, *draft]``
        rows go right-aligned into one (B, spec_k + 1) verify launch
        (idle/prefilling lanes ride along as the same discarded
        token-0 row the plain step gives them)."""
        S = self.spec_k + 1
        toks = np.zeros((self.batch, S), np.int32)
        n_new = np.ones(self.batch, np.int32)
        drafts: Dict[int, List[int]] = {}
        for i in live:
            sess = self.slots[i]
            req = sess.request
            remaining = req.max_new_tokens - len(req.out_tokens)
            room = self.L - int(self.pos[i]) - 1
            k_b = max(0, min(self.spec_k, remaining - 1, room))
            draft = self.proposer.propose(
                req.prompt + req.out_tokens, k_b) if k_b else []
            drafts[i] = draft
            n = 1 + len(draft)
            n_new[i] = n
            toks[i, S - n:] = [sess.last_token] + draft
        return toks, n_new, drafts

    def _commit_spec(self, pending: PendingStep):
        """The acceptance half: greedy acceptance commits the longest
        draft prefix matching the model's argmax rows plus the bonus
        token — bit-exact against ``a + 1`` plain steps — then rollback
        truncates the page list to the committed positions, releasing
        pages only rejected drafts touched."""
        S = self.spec_k + 1
        live, n_new, drafts = pending.live, pending.n_new, pending.drafts
        with trace_names.host_span("engine.sample"):
            logits = np.asarray(pending.logits)[..., :self.cfg.vocab]
            argmax = np.argmax(logits, axis=-1)
        for i in live:
            sess = self.slots[i]
            req = sess.request
            draft = drafts[i]
            n = int(n_new[i])
            rows = logits[i, S - n:]
            preds = argmax[i, S - n:]
            a = 0
            while a < len(draft) and int(preds[a]) == draft[a]:
                a += 1
            commit = [int(t) for t in preds[:a + 1]]
            self._record(req, rows[:a + 1])
            self._spec_drafted += len(draft)
            self._spec_accepted += a
            req.out_tokens.extend(commit)
            sess.last_token = commit[-1]
            self.pos[i] += len(commit)
            sess.pos = int(self.pos[i])
            if self.paged and len(commit) < n:
                # rejected drafts wrote past the committed positions:
                # release any page only they touched (valid_len hides
                # the stale K/V in the kept tail page)
                self.kv.truncate(sess, int(self.pos[i]))
            if len(req.out_tokens) >= req.max_new_tokens \
                    or self._at_cache_end(i):
                self._retire(i)

    # ------------------------------------------------------ introspection --

    def describe(self) -> dict:
        """Structured engine signature: backend ids, decode/prefill
        modes, cache geometry, live page-pool and prefix-cache stats.
        ``describe_str()`` derives the one-line log form from this
        dict."""
        if self.paged:
            cache = dict(mode="paged", kv_pack=self.layout.kv_dtype,
                         **self.kv.stats())
            cache["live_tokens"] = int(sum(
                s.live_tokens for s in self.slots if s is not None)
                + sum(s.live_tokens for s in self.queue))
            cache["shared_pages"] = int(
                (self.kv.allocator.refcount[1:] > 1).sum())
            cache["cow_copies"] = self._cow_copies
            cache["prefix"] = self.prefix.stats() \
                if self.prefix is not None else None
        else:
            cache = {"mode": "contiguous", "kv_pack": "int8"}
        # derived from the stored element width: packed pools carry half
        # the elements per token, so this halves under kv_dtype="int4"
        cache["kv_bytes"] = int(sum(
            c[key].size * c[key].dtype.itemsize
            for c in self.caches for key in ("k8", "v8") if key in c))
        tp = {
            "tp": self.tp,
            # "sharded": shard_map over the mesh; "gathered": tp > 1 but
            # a backend lacks tp_serving (or the process lacks devices)
            # — the exact single-device lowering; "off": tp == 1
            "mode": ("sharded" if self.tp_sharded
                     else "gathered" if self.tp > 1 else "off"),
            "mesh": None if self.mesh is None else {
                "axis": tp_serving.TP_AXIS,
                "shape": [self.tp],
                "devices": [int(d.id) for d in self.mesh.devices.flat],
            },
            # each device holds Hkv/tp of every page, so its pool slice
            # is exactly 1/tp of the global KV bytes
            "per_device_kv_bytes": cache["kv_bytes"] // self.tp
            if self.tp_sharded else cache["kv_bytes"],
        }
        drafted, accepted = self._spec_drafted, self._spec_accepted
        spec = {
            "k": self.spec_k,
            "mode": self.spec_mode,
            "drafted": drafted,
            "accepted": accepted,
            "accept_rate": round(accepted / drafted, 4) if drafted
            else None,
            "wasted": drafted - accepted,
        }
        return {
            "ops": self.ops.name,
            "backends": {op: self.ops.backend_for(op).name
                         for op in OP_NAMES},
            "attn": "fused" if self.attn_fused else "two-pass",
            "decode": "fused" if self.decode_fused else "oracle",
            "spec": spec,
            "prefill": {
                "mode": "chunked" if self._use_chunked else "streaming",
                "chunk": self.prefill_chunk,
                "budget": self.prefill_budget,
                "paged_native": self.prefill_paged_native,
            },
            "fold_wo": self.fold_wo,
            "tp": tp,
            "batch": self.batch,
            "cache_len": self.cache_len,
            "cache": cache,
        }

    def describe_str(self) -> str:
        """One-line engine signature for drivers/logs, derived from
        :meth:`describe`."""
        d = self.describe()
        c = d["cache"]
        if c["mode"] == "paged":
            pack = "" if c.get("kv_pack", "int8") == "int8" \
                else f", {c['kv_pack']}"
            cache = (f"paged[{c['page_size']}tok x {c['num_pages']}pg"
                     f"{pack}, "
                     f"{c['pages_used']}/{c['num_pages'] - 1} used]")
        else:
            cache = "contiguous"
        pf = d["prefill"]
        prefill = f"chunked:{pf['chunk']}" if pf["mode"] == "chunked" \
            else "streaming"
        if c.get("prefix") is not None:
            prefill += f"+prefix[{c['prefix']['entries']}]"
        tp = "" if d["tp"]["tp"] == 1 \
            else f" tp={d['tp']['tp']}:{d['tp']['mode']}"
        sp = d["spec"]
        spec = "" if not sp["k"] else (
            f" spec={sp['mode']}:k{sp['k']}"
            + (f"@{sp['accept_rate']:.2f}"
               if sp["accept_rate"] is not None else ""))
        return (f"ops={d['ops']} attn={d['attn']} decode={d['decode']} "
                f"prefill={prefill} fold_wo={str(d['fold_wo']).lower()}"
                f"{tp}{spec} cache={cache} batch={d['batch']} "
                f"cache_len={d['cache_len']}")

    def run_until_done(self, max_steps: int = 10000) -> List[Request]:
        """Step until queue and lanes drain; returns the requests that
        retired since the last call (completion order).

        Raises :class:`EngineStalled` if ``max_steps`` elapse with
        sessions still queued or resident — a silent partial return
        here let callers mistake a stalled schedule (admission
        deadlock, runaway generation) for completion.
        """
        for _ in range(max_steps):
            if not self.queue and all(s is None for s in self.slots):
                break
            self.step()
        else:
            if self.queue or any(s is not None for s in self.slots):
                slots = [
                    None if s is None else {
                        "uid": s.request.uid,
                        "state": s.state,
                        "pos": int(self.pos[i]),
                        "prefill_pos": s.prefill_pos,
                    }
                    for i, s in enumerate(self.slots)
                ]
                raise EngineStalled(max_steps, slots, len(self.queue))
        finished, self._finished = self._finished, []
        return finished
