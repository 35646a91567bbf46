"""repro.ops — the unified operator API for the integer datapath.

Single entry point for SwiftTron's integer ops (INT8 matmul, Attention,
Decode Attention, GELU, LayerNorm; Shiftmax runs inside the attention
ops):

  * :class:`RequantSpec` — typed, validated union of the three requant
    epilogue forms (per-tensor dyadic / per-channel vector / raw int32);
  * :class:`QuantLinearParams` — typed quantized-linear parameter pytree;
  * :class:`Backend` protocol + registry (``register_backend`` /
    ``get_backend``), the ``REPRO_BACKEND`` env override and the
    :func:`use_backend` context;
  * :class:`OpSet` — the handle models take once at construction
    (default backend + per-op overrides).  Its ``int_decode_attention``
    negotiates the optional decode capabilities (``paged_decode`` /
    ``decode_wo_fold``), and ``int_paged_prefill`` the chunked-prefill
    ones (``paged_prefill`` / ``prefill_wo_fold``) — lowering the
    page-table, chunk-scatter and folded-wo operands exactly for
    backends without them (see ``repro.ops.paged``).

See docs/OPS_API.md for the full API (the old ``repro.kernels.ops``
string-dispatch wrappers are gone; the migration table lives there).
"""
from __future__ import annotations

from repro.ops.registry import (Backend, OpSet, available_backends,
                                current_opset, get_backend,
                                register_backend, resolve_ops,
                                unregister_backend, use_backend,
                                DEFAULT_BACKEND, ENV_VAR, OP_NAMES,
                                REQUIRED_OPS)
from repro.ops.spec import (PER_CHANNEL, PER_TENSOR, RAW, PackMeta,
                            QuantLinearParams, RequantSpec)

__all__ = [
    "Backend", "OpSet", "PackMeta", "QuantLinearParams", "RequantSpec",
    "available_backends", "current_opset", "get_backend",
    "register_backend", "resolve_ops", "unregister_backend",
    "use_backend", "DEFAULT_BACKEND", "ENV_VAR", "OP_NAMES",
    "REQUIRED_OPS", "PER_CHANNEL", "PER_TENSOR", "RAW",
    "int8_matmul", "int8_matmul_packed", "int_gelu",
    "int_layernorm", "int_attention", "int_decode_attention",
    "int_paged_prefill", "int_grouped_matmul",
]


def _register_builtin_backends():
    from repro.ops.backends.pallas_fused import PallasFusedBackend
    from repro.ops.backends.ref import RefBackend
    register_backend("ref", RefBackend(), overwrite=True)
    # the TPU kernels, bit-exact vs ref — see docs/KERNELS.md
    register_backend("pallas_fused", PallasFusedBackend, overwrite=True)


_register_builtin_backends()


# Module-level convenience entry points: dispatch through the ambient
# OpSet (use_backend context > REPRO_BACKEND env > "ref"), or an explicit
# ``ops=`` handle.

def int8_matmul(x8, w8, spec, *, bias32=None, b_vec=None, ops=None, **opts):
    return resolve_ops(ops).int8_matmul(x8, w8, spec, bias32=bias32,
                                        b_vec=b_vec, **opts)


def int8_matmul_packed(x8, qw, spec, *, ops=None, **opts):
    return resolve_ops(ops).int8_matmul_packed(x8, qw, spec, **opts)


def int_gelu(q, plan, dn_out, out_bits: int = 8, *, ops=None, **opts):
    return resolve_ops(ops).int_gelu(q, plan, dn_out, out_bits=out_bits,
                                     **opts)


def int_layernorm(q, q_gamma, q_beta, plan, out_bits: int = 8, *,
                  ops=None, **opts):
    return resolve_ops(ops).int_layernorm(q, q_gamma, q_beta, plan,
                                          out_bits=out_bits, **opts)


def int_attention(q8, k8, v8, plan, causal: bool = True, window: int = 0,
                  out_bits: int = 8, *, ops=None, **opts):
    return resolve_ops(ops).int_attention(q8, k8, v8, plan, causal=causal,
                                          window=window, out_bits=out_bits,
                                          **opts)


def int_decode_attention(q8, k8_cache, v8_cache, plan, valid_len,
                         out_bits: int = 8, *, ops=None, **opts):
    return resolve_ops(ops).int_decode_attention(
        q8, k8_cache, v8_cache, plan, valid_len, out_bits=out_bits, **opts)


def int_paged_prefill(q8, k8_new, v8_new, k_pool, v_pool, plan, base_pos,
                      pages, page_size: int, out_bits: int = 8, *,
                      ops=None, **opts):
    return resolve_ops(ops).int_paged_prefill(
        q8, k8_new, v8_new, k_pool, v_pool, plan, base_pos, pages,
        page_size, out_bits=out_bits, **opts)


def int_grouped_matmul(x8, w8, group_sizes, spec, *, b_vec=None, ops=None,
                       **opts):
    return resolve_ops(ops).int_grouped_matmul(x8, w8, group_sizes, spec,
                                               b_vec=b_vec, **opts)
