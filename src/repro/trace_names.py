"""The stable names a profile of the program shows.

Every Pallas launch passes one of :data:`KERNELS` as
``pl.pallas_call(name=...)``; every sublayer runs inside a
``jax.named_scope`` from :data:`SCOPES`; every model entry point runs
inside a scope of its own name (:data:`ENTRY_POINTS`); the serving
engine brackets its host phases with ``jax.profiler.TraceAnnotation``
spans (:data:`HOST_SPANS`).  The compiler carries launch names and
scopes into each device op's ``op_name`` metadata, e.g.
``jit(encode)/int_prefill/while/body/norm1/int_norm/pallas_call``, and
the device trace keeps it.  Layers run under ``lax.scan``, so paths
hold ``while/body``: read them by component, never as a whole.

Names and scopes change metadata and instruction names only: the
compiled program is otherwise the same instruction for instruction.  A
renamed entry here renames what the benchmark's per-kernel and
per-sublayer readers count (docs/KERNELS.md, "Names in a profile").
"""
from __future__ import annotations

import functools

import jax

#: one name per Pallas kernel
KERNELS = (
    "int8_matmul",              # kernels/int8_matmul.py
    "int_norm",                 # kernels/int_layernorm.py
    "int_gelu",                 # kernels/int_gelu.py
    "int_softmax",              # kernels/int_softmax.py
    "int_attention",            # kernels/int_attention.py (two-pass)
    "int_attention_fused",      # kernels/int_attention_fused.py
    "int_paged_prefill_fused",  # kernels/int_attention_fused.py
    "int_decode_attention",     # kernels/int_decode_attention.py
)

#: sublayer scopes; a norm's scope is its parameter's name
SCOPES = (
    "embed",
    "norm1", "norm2", "norm_cross", "final_norm", "enc_final_norm",
    "attn", "cross_attn", "ssm",
    "residual",
    "ffn.up", "ffn.act", "ffn.down", "moe",
    "head",
)

#: model entry points, each the outer scope of its own ops
ENTRY_POINTS = ("int_prefill", "int_decode_step", "int_prefill_chunk_step",
                "int_verify_step")

#: the serving engine's host spans (``jax.profiler.TraceAnnotation``)
HOST_SPANS = ("engine.dispatch", "engine.commit", "engine.sample")


def kernel(name: str) -> str:
    """``name`` for ``pl.pallas_call(name=)``, checked against
    :data:`KERNELS`."""
    if name not in KERNELS:
        raise ValueError(f"{name!r} is not in trace_names.KERNELS")
    return name


def scope(name: str):
    """``jax.named_scope(name)``, checked against :data:`SCOPES`."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not in trace_names.SCOPES")
    return jax.named_scope(name)


def host_span(name: str):
    """A ``jax.profiler.TraceAnnotation`` host span, checked against
    :data:`HOST_SPANS`; it costs a flag check while no profiler runs."""
    if name not in HOST_SPANS:
        raise ValueError(f"{name!r} is not in trace_names.HOST_SPANS")
    return jax.profiler.TraceAnnotation(name)


def entry_point(fn):
    """Run ``fn`` inside a scope of its own name (:data:`ENTRY_POINTS`)."""
    if fn.__name__ not in ENTRY_POINTS:
        raise ValueError(f"{fn.__name__!r} is not in "
                         "trace_names.ENTRY_POINTS")

    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope(fn.__name__):
            return fn(*args, **kwargs)
    return scoped
