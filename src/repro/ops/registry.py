"""Backend protocol, registry, and the OpSet dispatch handle.

Every integer operator (INT8 matmul, attention, decode attention, GELU,
LayerNorm) is implemented by a *backend* — an object with the five
methods of :class:`Backend`.  Backends register under a name
(``register_backend``) and models receive a resolved :class:`OpSet`
handle once at construction instead of threading ``backend="ref"``
strings through every call.

Resolution order for ``resolve_ops(spec, cfg)``:

  1. an explicit ``spec`` argument (OpSet / Backend / name);
  2. the innermost active :func:`use_backend` context;
  3. the ``REPRO_BACKEND`` environment variable;
  4. ``cfg.kernel_backend`` when an ArchConfig is supplied;
  5. the ``"ref"`` default.
"""
from __future__ import annotations

import contextlib
import os
import threading
from typing import Any, Callable, Dict, Optional, Protocol, Union, \
    runtime_checkable

ENV_VAR = "REPRO_BACKEND"
DEFAULT_BACKEND = "ref"

# the five methods every backend MUST implement
REQUIRED_OPS = ("int8_matmul", "int_gelu", "int_layernorm",
                "int_attention", "int_decode_attention")
# ... plus ops that are pure capabilities: a backend advertising the
# matching flag implements them natively, everyone else is served by an
# exact lowering in OpSet (so OP_NAMES is what dispatch/overrides/
# describe() route on, REQUIRED_OPS is what the protocol demands)
OP_NAMES = REQUIRED_OPS + ("int_paged_prefill", "int8_matmul_packed",
                           "int_grouped_matmul")


@runtime_checkable
class Backend(Protocol):
    """The five integer ops every backend implements.

    ``fused_attention`` advertises a single-kernel attention path (the
    model layer falls back to the streaming/chunked formulation when the
    backend only offers the full-matrix oracle).

    ``int_attention`` and ``int_decode_attention`` additionally accept
    ``requant=`` (a :class:`~repro.ops.spec.RequantSpec` epilogue;
    default: the plan's per-tensor ``dn_out``) and ``b_vec=`` (the
    per-channel multiplier vector) via ``**opts`` — see docs/KERNELS.md
    for the exact contract.  ``int_decode_attention`` serves the ragged
    KV-cache hot path: ``valid_len`` (B,) int32 is the per-slot cache
    occupancy (see the "Decode kernel contract" section there); an
    optional ``fused_decode`` flag (default False) advertises a
    single-launch kernel for it — the numerics are identical either way.

    Two further *optional* decode capabilities, negotiated by
    :meth:`OpSet.int_decode_attention` so plain backends never see the
    operands:

      * ``paged_decode`` — the backend consumes the paged KV layout
        directly (``pages: int32[B, max_pages]`` page table +
        ``page_size``, K/V as physical ``(num_pages, page_size, Hkv,
        D)`` pools).  Without the flag the dispatch layer gathers the
        pages into the contiguous layout first (bit-identical).
      * ``decode_wo_fold`` — the backend folds the output projection
        (``wo=`` a QuantLinearParams, ``wo_spec=`` its RequantSpec)
        into the decode launch, returning ``(B, Sq, N)``.  Without the
        flag the dispatch layer composes the backend's decode attention
        with its ``int8_matmul`` (bit-identical).

    A third pair of optional capabilities serves the *chunked prefill*
    path (:meth:`OpSet.int_paged_prefill` — scatter a prompt chunk's
    K/V through the page table, then attend causally over history +
    chunk):

      * ``paged_prefill`` — the backend implements
        ``int_paged_prefill`` natively (the fused prefill attention
        kernel reading K/V through the page-table scalar-prefetch
        operand).  Without the flag the dispatch layer lowers exactly:
        ``scatter_chunk`` + ``gather_pages`` + the backend's own
        ``int_decode_attention`` with ``valid_len = base_pos + C``
        (whose stepped mask *is* the chunked causal mask).
      * ``prefill_wo_fold`` — the backend folds the o-projection into
        the prefill launch's epilogue, mirroring ``decode_wo_fold``.
        Without it, decode-then-``int8_matmul`` (bit-identical).
    The sub-8-bit storage tier adds two more negotiated capabilities:

      * ``packed_matmul`` — the backend implements
        ``int8_matmul_packed`` natively (nibbles unpacked *inside* the
        matmul launch, msr4 outlier lanes applied as an exact sparse
        correction).  Without the flag the dispatch layer unpacks to
        dense int8 first (``repro.ops.packed.unpack_weights`` — the
        declared reference) and calls the backend's ``int8_matmul``:
        bit-identical either way.
      * ``packed_kv`` — the backend's paged decode/prefill launches
        consume int4-packed KV page pools directly (``kv_shifts=`` a
        pair of per-page int32 shift arrays; the kernel dequantizes
        ``q4 << shift`` in-register).  Without the flag the dispatch
        layer dequantizes the pools to int8
        (``repro.ops.packed.unpack_kv_pool``) and proceeds on the
        plain paged path — the declared reference numerics.

      * ``grouped_matmul`` — the backend implements
        ``int_grouped_matmul`` (a dropless MoE's expert matmuls: rows
        sorted by group, a per-group per-channel requant) natively.
        Without the flag the dispatch layer runs the oracle,
        ``kernels.ref.ref_int_grouped_matmul``.

      * ``tp_serving`` — the backend's ops trace inside a ``shard_map``
        body, so the serving engine may head-shard its decode/prefill
        launches tensor-parallel over a device mesh
        (``distributed.tp_serving``; each shard launches with ``H/tp``
        query and ``Hkv/tp`` KV heads).  Without the flag — on ANY
        backend in the OpSet — a ``tp > 1`` engine takes the exact
        single-device gather lowering instead: same API, bit-identical
        tokens, no mesh.
    """

    name: str
    fused_attention: bool

    def int8_matmul(self, x8, w8, spec, *, bias32=None, b_vec=None,
                    **opts): ...

    def int_gelu(self, q, plan, dn_out, out_bits: int = 8, **opts): ...

    def int_layernorm(self, q, q_gamma, q_beta, plan, out_bits: int = 8,
                      **opts): ...

    def int_attention(self, q8, k8, v8, plan, causal: bool = True,
                      window: int = 0, out_bits: int = 8, **opts): ...

    def int_decode_attention(self, q8, k8_cache, v8_cache, plan, valid_len,
                             out_bits: int = 8, **opts): ...


def _is_backend(obj) -> bool:
    """A backend *instance*: the five required ops plus
    name/fused_attention (capability ops like ``int_paged_prefill`` are
    optional — OpSet lowers them for backends without the flag).

    Classes are excluded — a registered class is a factory, and calling
    its unbound methods would misbind ``self``.
    """
    if isinstance(obj, type):
        return False
    return (all(callable(getattr(obj, op, None)) for op in REQUIRED_OPS)
            and isinstance(getattr(obj, "name", None), str)
            and hasattr(obj, "fused_attention"))


_REGISTRY: Dict[str, Union[Backend, Callable[[], Backend]]] = {}
_LOCK = threading.Lock()


def register_backend(name: str, backend, *, overwrite: bool = False):
    """Register a backend instance or zero-arg factory under ``name``."""
    if not (_is_backend(backend) or callable(backend)):
        raise TypeError(f"{backend!r} implements neither the Backend "
                        "protocol nor a factory for one")
    with _LOCK:
        if name in _REGISTRY and not overwrite:
            raise ValueError(f"backend {name!r} already registered "
                             "(pass overwrite=True to replace)")
        _REGISTRY[name] = backend


def unregister_backend(name: str):
    with _LOCK:
        _REGISTRY.pop(name, None)


def get_backend(name: str) -> Backend:
    """Look up a registered backend, instantiating lazy factories once."""
    with _LOCK:
        entry = _REGISTRY.get(name)
    if entry is None:
        raise KeyError(f"unknown backend {name!r}; registered: "
                       f"{available_backends()}")
    if not _is_backend(entry):
        entry = entry()
        if not _is_backend(entry):
            raise TypeError(f"factory for {name!r} returned a "
                            "non-Backend")
        with _LOCK:
            _REGISTRY[name] = entry
    return entry


def available_backends():
    with _LOCK:
        return sorted(_REGISTRY)


def _as_backend(spec) -> Backend:
    if isinstance(spec, str):
        return get_backend(spec)
    if _is_backend(spec):
        return spec
    raise TypeError(f"cannot interpret {spec!r} as a backend")


class OpSet:
    """A resolved operator bundle: one default backend + per-op overrides.

    Models hold exactly one of these; every integer op dispatches through
    it, so swapping backends (or overriding a single op, e.g. fused
    attention on Pallas with everything else on ref) never touches model
    code.
    """

    __slots__ = ("default", "overrides")

    def __init__(self, default, overrides: Optional[Dict[str, Any]] = None):
        self.default = _as_backend(default)
        ov = {}
        for op, b in (overrides or {}).items():
            if op not in OP_NAMES:
                raise KeyError(f"unknown op {op!r}; valid ops: {OP_NAMES}")
            ov[op] = _as_backend(b)
        self.overrides = ov

    # ------------------------------------------------------------ admin --

    @property
    def name(self) -> str:
        if not self.overrides:
            return self.default.name
        ov = ",".join(f"{op}={b.name}"
                      for op, b in sorted(self.overrides.items()))
        return f"{self.default.name}[{ov}]"

    def backend_for(self, op: str) -> Backend:
        if op not in OP_NAMES:
            raise KeyError(f"unknown op {op!r}; valid ops: {OP_NAMES}")
        return self.overrides.get(op, self.default)

    def with_overrides(self, **per_op) -> "OpSet":
        merged = dict(self.overrides)
        merged.update(per_op)
        return OpSet(self.default, merged)

    def __repr__(self):
        return f"OpSet({self.name})"

    # --------------------------------------------------------- dispatch --

    def int8_matmul(self, x8, w8, spec, *, bias32=None, b_vec=None, **opts):
        return self.backend_for("int8_matmul").int8_matmul(
            x8, w8, spec, bias32=bias32, b_vec=b_vec, **opts)

    def int_gelu(self, q, plan, dn_out, out_bits: int = 8, **opts):
        return self.backend_for("int_gelu").int_gelu(
            q, plan, dn_out, out_bits=out_bits, **opts)

    def int_layernorm(self, q, q_gamma, q_beta, plan, out_bits: int = 8,
                      **opts):
        return self.backend_for("int_layernorm").int_layernorm(
            q, q_gamma, q_beta, plan, out_bits=out_bits, **opts)

    def int_attention(self, q8, k8, v8, plan, causal: bool = True,
                      window: int = 0, out_bits: int = 8, **opts):
        return self.backend_for("int_attention").int_attention(
            q8, k8, v8, plan, causal=causal, window=window,
            out_bits=out_bits, **opts)

    def int8_matmul_packed(self, x8, qw, spec, **opts):
        """Matmul against packed (int4/msr4) weights, with negotiation.

        ``qw`` is a packed :class:`~repro.ops.spec.QuantLinearParams`
        (``w_packed`` nibbles + optional msr4 outlier lanes); its
        ``bias32``/``b_mult`` feed the epilogue exactly as on the dense
        path.  Backends advertising ``packed_matmul`` unpack inside the
        launch; for the rest this method lowers exactly — dense
        reconstruction via ``repro.ops.packed.unpack_weights`` (the
        declared reference) followed by the backend's own
        ``int8_matmul`` — so callers get identical integers from every
        backend.  A dense ``qw`` falls through to plain ``int8_matmul``.
        """
        from repro.ops.spec import QuantLinearParams
        qw = QuantLinearParams.of(qw)
        if not qw.is_packed:
            return self.int8_matmul(x8, qw.w8, spec, bias32=qw.bias32,
                                    b_vec=qw.b_mult, **opts)
        be = self.backend_for("int8_matmul_packed")
        if getattr(be, "packed_matmul", False):
            return be.int8_matmul_packed(x8, qw, spec, **opts)
        from repro.ops.packed import unpack_weights
        return be.int8_matmul(x8, unpack_weights(qw), spec,
                              bias32=qw.bias32, b_vec=qw.b_mult, **opts)

    def int_grouped_matmul(self, x8, w8, group_sizes, spec, *,
                           b_vec=None, **opts):
        """Grouped int8 matmul: ``x8`` (M, K) rows sorted by group,
        ``w8`` (G, K, N), ``group_sizes`` (G,) int32 with ``sum <= M``;
        row ``r`` of group ``g`` takes ``x8[r] @ w8[g]`` and the
        ``spec`` epilogue — per-channel with group ``g``'s row of
        ``b_vec`` (G, N), or raw.  Rows past ``sum(group_sizes)`` are
        not specified (callers read the live rows only).  Backends
        advertising ``grouped_matmul`` launch their kernel; the rest get
        the oracle, so every backend gives the same live rows."""
        be = self.backend_for("int_grouped_matmul")
        if not getattr(be, "grouped_matmul", False):
            be = get_backend("ref")
        return be.int_grouped_matmul(x8, w8, group_sizes, spec,
                                     b_vec=b_vec, **opts)

    def _compose_wo(self, be, o8, wo, wo_spec):
        """Exact unfolded wo composition: decode output → o-projection.

        Packed wo never folds into an attention launch — it routes
        through :meth:`int8_matmul_packed` (same negotiated numerics).
        """
        import jax.numpy as jnp
        b, sq = o8.shape[0], o8.shape[1]
        x8 = o8.astype(jnp.int8).reshape(b * sq, -1)
        if wo.is_packed:
            acc = self.int8_matmul_packed(x8, wo, wo_spec)
        else:
            acc = be.int8_matmul(x8, wo.w8, wo_spec, bias32=wo.bias32,
                                 b_vec=wo.b_mult)
        if not wo_spec.is_raw and wo_spec.out_bits <= 8:
            acc = acc.astype(jnp.int8)     # match the folded kernel's dtype
        return acc.reshape(b, sq, -1)

    def int_decode_attention(self, q8, k8_cache, v8_cache, plan, valid_len,
                             out_bits: int = 8, pages=None,
                             page_size: int = 0, wo=None, wo_spec=None,
                             kv_shifts=None, **opts):
        """Decode attention with capability negotiation.

        ``pages``/``page_size`` select the paged KV layout (k8/v8 are
        physical page pools); ``wo``/``wo_spec`` ask for the folded
        output projection; ``kv_shifts`` marks the pools as int4-packed
        (nibbles along the head dim + per-page requant shifts — the
        ``kv_dtype="int4"`` cache tier).  Backends advertising
        ``paged_decode`` / ``decode_wo_fold`` / ``packed_kv`` get the
        operands verbatim; for the rest this method lowers them exactly
        — gather-into-contiguous for pages, decode-then-``int8_matmul``
        for the fold, pool dequantization for packed KV — so callers
        get identical integers from every backend.
        """
        be = self.backend_for("int_decode_attention")
        kw = {}
        if kv_shifts is not None and pages is None:
            raise ValueError("int4 KV (kv_shifts=) requires the paged "
                             "layout")
        if pages is not None:
            paged_native = getattr(be, "paged_decode", False)
            if kv_shifts is not None:
                if paged_native and getattr(be, "packed_kv", False):
                    kw.update(kv_shifts=kv_shifts)
                else:
                    from repro.ops.packed import unpack_kv_pool
                    k8_cache = unpack_kv_pool(k8_cache, kv_shifts[0])
                    v8_cache = unpack_kv_pool(v8_cache, kv_shifts[1])
            if paged_native:
                kw.update(pages=pages, page_size=page_size)
            else:
                from repro.ops.paged import gather_pages
                k8_cache = gather_pages(k8_cache, pages, page_size)
                v8_cache = gather_pages(v8_cache, pages, page_size)
        if wo is None:
            return be.int_decode_attention(q8, k8_cache, v8_cache, plan,
                                           valid_len, out_bits=out_bits,
                                           **kw, **opts)
        wo = _validate_wo(wo, wo_spec, opts.get("requant"), out_bits)
        if getattr(be, "decode_wo_fold", False) and not wo.is_packed:
            return be.int_decode_attention(q8, k8_cache, v8_cache, plan,
                                           valid_len, out_bits=out_bits,
                                           wo=wo, wo_spec=wo_spec,
                                           **kw, **opts)
        # exact unfolded composition through the backend's own matmul
        o8 = be.int_decode_attention(q8, k8_cache, v8_cache, plan,
                                     valid_len, out_bits=out_bits,
                                     **kw, **opts)
        return self._compose_wo(be, o8, wo, wo_spec)

    def int_paged_prefill(self, q8, k8_new, v8_new, k_pool, v_pool, plan,
                          base_pos, pages, page_size: int,
                          out_bits: int = 8, wo=None, wo_spec=None,
                          kv_shifts=None, **opts):
        """Chunked paged prefill with capability negotiation.

        Scatter the chunk's new K/V (``k8_new``/``v8_new``: ``(B, C,
        Hkv, D)`` int8, RoPE applied) into the physical pools through
        the page table, then run the chunk queries ``q8 (B, C, H, D)``
        against history + chunk under the causal-over-history mask —
        chunk row ``i`` of slot ``b`` sees positions ``≤ base_pos[b] +
        i``.  Returns ``(o, k_pool, v_pool)``.

        Backends advertising ``paged_prefill`` get the operands verbatim
        (the fused prefill kernel reads K/V through the scalar-prefetched
        table; ``prefill_wo_fold`` additionally folds ``wo=``/``wo_spec=``
        into the launch).  For the rest this method lowers exactly —
        ``scatter_chunk`` + ``gather_pages`` + the stepped-mask
        :meth:`int_decode_attention` with ``valid_len = base_pos + C``
        (which also negotiates the wo fold) — so callers get identical
        integers from every backend.  Oracle:
        ``kernels.ref.ref_int_paged_prefill``.

        ``kv_shifts`` marks the pools as int4-packed (kv_dtype="int4"):
        the chunk's K/V are quantized + nibble-packed before the
        scatter (``repro.ops.packed.pack_kv`` — one quantization policy
        for every path, so pool bytes are backend-independent), and a
        backend without ``packed_kv`` is served by dequantizing the
        updated pools and running the plain lowering.
        """
        be = self.backend_for("int_paged_prefill")
        if wo is not None:
            wo = _validate_wo(wo, wo_spec, opts.get("requant"), out_bits)
        packed_kv_native = (kv_shifts is not None
                            and getattr(be, "packed_kv", False))
        if getattr(be, "paged_prefill", False) \
                and (kv_shifts is None or packed_kv_native):
            kw = {}
            if kv_shifts is not None:
                kw.update(kv_shifts=kv_shifts)
            if wo is not None and getattr(be, "prefill_wo_fold", False) \
                    and not wo.is_packed:
                kw.update(wo=wo, wo_spec=wo_spec)
                wo = None
            o, k_pool, v_pool = be.int_paged_prefill(
                q8, k8_new, v8_new, k_pool, v_pool, plan, base_pos,
                pages, page_size, out_bits=out_bits, **kw, **opts)
            if wo is None:
                return o, k_pool, v_pool
            # fold requested but the backend only does paged prefill:
            # exact unfolded composition through its own matmul
            return self._compose_wo(be, o, wo, wo_spec), k_pool, v_pool
        from repro.ops.paged import gather_pages, scatter_chunk
        import jax.numpy as jnp
        c = q8.shape[1]
        if kv_shifts is not None:
            from repro.ops.packed import pack_kv, unpack_kv_pool
            k_pool = scatter_chunk(k_pool, pack_kv(k8_new), base_pos,
                                   pages, page_size)
            v_pool = scatter_chunk(v_pool, pack_kv(v8_new), base_pos,
                                   pages, page_size)
            kc = gather_pages(unpack_kv_pool(k_pool, kv_shifts[0]),
                              pages, page_size)
            vc = gather_pages(unpack_kv_pool(v_pool, kv_shifts[1]),
                              pages, page_size)
        else:
            k_pool = scatter_chunk(k_pool, k8_new, base_pos, pages,
                                   page_size)
            v_pool = scatter_chunk(v_pool, v8_new, base_pos, pages,
                                   page_size)
            kc = gather_pages(k_pool, pages, page_size)
            vc = gather_pages(v_pool, pages, page_size)
        vl = jnp.asarray(base_pos, jnp.int32) + c
        o = self.int_decode_attention(q8, kc, vc, plan, vl,
                                      out_bits=out_bits, wo=wo,
                                      wo_spec=wo_spec, **opts)
        return o, k_pool, v_pool


def _validate_wo(wo, wo_spec, requant, out_bits: int):
    """Shared wo-fold operand validation (decode and paged prefill):
    normalizes ``wo`` to QuantLinearParams and rejects epilogues the
    int8 fold/lowering would silently wrap on."""
    from repro.ops.spec import QuantLinearParams
    wo = QuantLinearParams.of(wo)
    if wo_spec is None:
        raise ValueError("folded wo projection needs wo_spec (the "
                         "o-projection's RequantSpec)")
    # the effective attention epilogue must clip to int8 — it feeds
    # the int8 wo contraction (a wider epilogue would silently wrap
    # in the lowering's astype)
    if requant is not None and (requant.is_raw or requant.out_bits > 8):
        raise ValueError("wo folding needs an int8 attention "
                         f"epilogue, got {requant}")
    if requant is None and out_bits > 8:
        raise ValueError("wo folding needs an int8 attention "
                         f"epilogue, got out_bits={out_bits}")
    return wo


# ------------------------------------------------------------ resolution --

_TLS = threading.local()


def _stack():
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    return stack


def current_opset() -> Optional[OpSet]:
    """The innermost active ``use_backend`` OpSet, if any."""
    stack = _stack()
    return stack[-1] if stack else None


@contextlib.contextmanager
def use_backend(spec, **per_op):
    """Scope a backend choice: ``with use_backend("pallas_fused"): ...``.

    ``per_op`` overrides route individual ops elsewhere, e.g.
    ``use_backend("ref", int_attention="pallas_fused")``.
    """
    ops = OpSet(_as_backend(spec),
                per_op or None) if not isinstance(spec, OpSet) \
        else (spec.with_overrides(**per_op) if per_op else spec)
    stack = _stack()
    stack.append(ops)
    try:
        yield ops
    finally:
        stack.pop()


def resolve_ops(spec=None, cfg=None) -> OpSet:
    """Resolve ``spec`` (OpSet / Backend / name / None) to an OpSet."""
    if isinstance(spec, OpSet):
        return spec
    if spec is not None:
        return OpSet(_as_backend(spec))
    active = current_opset()
    if active is not None:
        return active
    env = os.environ.get(ENV_VAR)
    if env:
        return OpSet(get_backend(env))
    if cfg is not None and getattr(cfg, "kernel_backend", None):
        return OpSet(get_backend(cfg.kernel_backend))
    return OpSet(get_backend(DEFAULT_BACKEND))
