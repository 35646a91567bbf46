"""Reference backend: pure-jnp oracles from ``repro.kernels.ref``.

What the multi-pod dry-run compiles (XLA-visible FLOPs/bytes for the
roofline) and what every other backend is tested against.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.ops import spec as _spec


class RefBackend:
    name = "ref"
    fused_attention = False   # full-matrix oracle, not a fused kernel
    fused_decode = False      # decode runs the full-matrix oracle too
    # no paged/wo-fold decode or chunked-prefill capabilities: OpSet
    # lowers all four operands (gather-into-contiguous / unfolded
    # matmul / chunk scatter+gather) before dispatching here
    paged_decode = False
    decode_wo_fold = False
    paged_prefill = False
    prefill_wo_fold = False
    # the pure-jnp oracles trace cleanly inside a shard_map body, so the
    # serving engine may head-shard its launches across a tp mesh
    tp_serving = True

    def int8_matmul(self, x8, w8, spec, *, bias32=None, b_vec=None, **opts):
        if spec.is_raw:
            acc = jnp.dot(x8, w8, preferred_element_type=jnp.int32)
            if bias32 is not None:
                acc = acc + bias32[None, :]
            return acc
        if spec.kind == _spec.PER_TENSOR:
            return _ref.ref_int8_matmul(x8, w8, bias32, spec.dn,
                                        spec.out_bits)
        if b_vec is None:
            raise ValueError("per-channel RequantSpec needs the b_vec "
                             "multiplier vector (QuantLinearParams.b_mult)")
        return _ref.ref_int8_matmul_perchannel(x8, w8, bias32, b_vec,
                                               spec.c, spec.pre,
                                               spec.out_bits)

    def int_gelu(self, q, plan, dn_out, out_bits: int = 8, **opts):
        return _ref.ref_int_gelu(q, plan, dn_out, out_bits)

    def int_layernorm(self, q, q_gamma, q_beta, plan, out_bits: int = 8,
                      **opts):
        return _ref.ref_int_layernorm(q, q_gamma, q_beta, plan, out_bits)

    def int_attention(self, q8, k8, v8, plan, causal: bool = True,
                      window: int = 0, out_bits: int = 8, requant=None,
                      b_vec=None, **opts):
        return _ref.ref_int_attention(q8, k8, v8, plan, causal, window,
                                      out_bits, requant=requant,
                                      b_vec=b_vec)

    def int_decode_attention(self, q8, k8_cache, v8_cache, plan, valid_len,
                             out_bits: int = 8, requant=None, b_vec=None,
                             **opts):
        return _ref.ref_int_decode_attention(q8, k8_cache, v8_cache, plan,
                                             valid_len, out_bits,
                                             requant=requant, b_vec=b_vec)
