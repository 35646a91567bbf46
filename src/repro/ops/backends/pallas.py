"""Pallas backend: the TPU kernels, interpret-mode on CPU.

Block shapes are a per-op *configuration* of the backend instance —
``PallasBackend(name="pallas_tuned", blocks={"int8_matmul": dict(bm=256,
bn=256, bk=256)})`` registers a differently-tiled variant without
touching the kernels or the models (the registry's whole point).
Requested blocks are shrunk to the largest divisor of the actual dim so
a tuned profile never trips the kernels' divisibility asserts on odd
shapes.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax.numpy as jnp

from repro.analysis.contracts import fit_block as _fit_block
from repro.kernels import resolve_interpret
from repro.kernels.int8_matmul import PACKED_BLOCKS, int8_matmul_pallas
from repro.kernels.int_attention import int_attention_pallas
from repro.kernels.int_gelu import int_gelu_pallas
from repro.kernels.int_layernorm import int_layernorm_pallas
from repro.kernels.int_softmax import int_softmax_pallas
from repro.ops import spec as _spec


def _matmul_blocks(opts: dict, m: int, n: int, k: int, packed=False):
    """Requested matmul blocks (the call's, or a profile's such as
    ``pallas_tuned``) fitted to chip-legal divisors: rows a multiple of
    8, lanes (bn, and bk — the x block's lane dim) a multiple of 128,
    else the whole dim (``contracts.fit_block``).  A dense block not
    requested stays None: the kernel takes it from the launch's shape
    (``kernels.int8_matmul.matmul_blocks``).  Packed weights default to
    ``PACKED_BLOCKS`` and pair nibbles along K, so bk is fitted on K/2
    pairs and doubled (its half is the packed block's row dim)."""
    want = PACKED_BLOCKS if packed else (None, None, None)
    bm, bn, bk = (opts.pop(key, w) for key, w in zip(("bm", "bn", "bk"),
                                                     want))
    if bm is not None:
        bm = _fit_block(bm, m, 8)
    if bn is not None:
        bn = _fit_block(bn, n, 128)
    if packed:
        bk = 2 * _fit_block(max(bk // 2, 1), k // 2, 64)
    elif bk is not None:
        bk = _fit_block(bk, k, 128)
    return bm, bn, bk


class PallasBackend:
    fused_attention = True
    fused_decode = False      # no ragged-cache decode kernel (see below)
    # no paged/wo-fold decode or chunked-prefill capabilities either:
    # OpSet lowers the operands exactly before dispatching here
    # (docs/KERNELS.md)
    paged_decode = False
    decode_wo_fold = False
    paged_prefill = False
    prefill_wo_fold = False
    # deliberately does NOT advertise tp_serving: this backend is the
    # serving engine's fallback exerciser — a tp > 1 engine over it
    # takes the exact single-device gather lowering, which is what
    # keeps that path tested (tp_serving on ref + pallas_fused)
    tp_serving = False

    def __init__(self, name: str = "pallas",
                 interpret: Optional[bool] = None,
                 blocks: Optional[Dict[str, Dict[str, int]]] = None):
        self.name = name
        self._interpret = interpret
        self.blocks = {op: dict(kw) for op, kw in (blocks or {}).items()}

    def _interp(self) -> bool:
        return resolve_interpret(self._interpret)

    def _opts(self, op: str, call_opts: dict) -> dict:
        merged = dict(self.blocks.get(op, {}))
        merged.update(call_opts)
        return merged

    # ------------------------------------------------------------- ops --

    def int8_matmul(self, x8, w8, spec, *, bias32=None, b_vec=None, **opts):
        if spec.is_raw:
            # no requant epilogue to fuse -> nothing for the kernel to
            # add over XLA's int8 dot, and raw consumers (lm head,
            # router, dt proj) often have odd N where divisor-fitted
            # blocks would degenerate; keep the MXU dot
            acc = jnp.dot(x8, w8, preferred_element_type=jnp.int32)
            if bias32 is not None:
                acc = acc + bias32[None, :]
            return acc
        opts = self._opts("int8_matmul", opts)
        m, k = x8.shape
        n = w8.shape[-1]
        bm, bn, bk = _matmul_blocks(opts, m, n, k)
        if spec.kind == _spec.PER_TENSOR:
            out = int8_matmul_pallas(x8, w8, bias32, dn=spec.dn,
                                     out_bits=spec.out_bits,
                                     out_dtype=spec.out_dtype,
                                     bm=bm, bn=bn, bk=bk,
                                     interpret=self._interp(), **opts)
        else:
            if b_vec is None:
                raise ValueError("per-channel RequantSpec needs the b_vec "
                                 "multiplier vector "
                                 "(QuantLinearParams.b_mult)")
            out = int8_matmul_pallas(x8, w8, bias32, b_vec=b_vec,
                                     c=spec.c, pre=spec.pre,
                                     out_bits=spec.out_bits,
                                     out_dtype=spec.out_dtype,
                                     bm=bm, bn=bn, bk=bk,
                                     interpret=self._interp(), **opts)
        return out

    def int_softmax(self, scores, plan, **opts):
        opts = self._opts("int_softmax", opts)
        opts.pop("where", None)   # oracle-only kwarg; kernel masks inline
        return int_softmax_pallas(scores, plan, interpret=self._interp(),
                                  **opts)

    def int_gelu(self, q, plan, dn_out, out_bits: int = 8, **opts):
        opts = self._opts("int_gelu", opts)
        return int_gelu_pallas(q, plan, dn_out, out_bits,
                               interpret=self._interp(), **opts)

    def int_layernorm(self, q, q_gamma, q_beta, plan, out_bits: int = 8,
                      **opts):
        opts = self._opts("int_layernorm", opts)
        return int_layernorm_pallas(q, q_gamma, q_beta, plan, out_bits,
                                    interpret=self._interp(), **opts)

    def int_attention(self, q8, k8, v8, plan, causal: bool = True,
                      window: int = 0, out_bits: int = 8, requant=None,
                      b_vec=None, **opts):
        opts = self._opts("int_attention", opts)
        if requant is not None:
            # this kernel hardcodes the per-tensor epilogue; fold the
            # spec's dyadic into the plan (pallas_fused takes all forms)
            if requant.kind != _spec.PER_TENSOR:
                raise NotImplementedError(
                    f"{self.name!r} attention supports per-tensor requant "
                    "only; use the 'pallas_fused' backend for "
                    f"{requant.kind!r}")
            plan = plan._replace(dn_out=requant.dn)
            out_bits = requant.out_bits
        sq, skv = q8.shape[1], k8.shape[1]
        if sq < 16 or skv < 16:
            # decode-sized problems: a degenerate (bq<16) grid costs more
            # than the oracle, which is also exact — same escape hatch as
            # pallas_fused's _can_tile
            from repro.kernels import ref as _ref
            return _ref.ref_int_attention(q8, k8, v8, plan, causal=causal,
                                          window=window, out_bits=out_bits)
        bq = _fit_block(opts.pop("bq", 128), sq)
        bkv = _fit_block(opts.pop("bkv", 128), skv)
        return int_attention_pallas(q8, k8, v8, plan, causal=causal,
                                    window=window, bq=bq, bkv=bkv,
                                    out_bits=out_bits,
                                    interpret=self._interp(), **opts)

    def int_decode_attention(self, q8, k8_cache, v8_cache, plan, valid_len,
                             out_bits: int = 8, requant=None, b_vec=None,
                             **opts):
        # the online-softmax kernel has no ragged-cache decode variant;
        # decode-sized problems take the exact full-matrix oracle here
        # (the 'pallas_fused' backend has the single-launch decode kernel)
        from repro.kernels import ref as _ref
        return _ref.ref_int_decode_attention(q8, k8_cache, v8_cache, plan,
                                             valid_len, out_bits,
                                             requant=requant, b_vec=b_vec)
