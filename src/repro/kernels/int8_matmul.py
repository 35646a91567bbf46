"""Pallas TPU kernel: INT8 x INT8 -> INT32 matmul with fused requantization.

This is the SwiftTron MatMul block (§III-B) + Requantization unit (§III-C)
re-targeted to the TPU MXU:

  * the MAC array becomes a (bm, bn) MXU tile accumulating int32 over
    K-steps of ``bk`` (INT8 operands feed the MXU at 2x bf16 throughput);
  * the "read output column-by-column, adding the bias" epilogue becomes a
    fused bias + dyadic-requant + clip on the *last* K-step while the tile
    is still VMEM-resident — the INT32 accumulator never round-trips HBM;
  * per-channel weight scales are a (N,) vector of dyadic multipliers
    blocked along with the output columns — passed as ``(1, N)`` with
    ``(1, bn)`` blocks, like the bias, because the chip's compiler
    refuses partial 1-d blocks (``analysis.contracts.
    tpu_block_violations``).

Blocks come from the launch's shape (:func:`matmul_blocks`): a row
block of up to 512, a column block of up to 1024, and the deepest K step
whose pipelined blocks stay within :data:`BLOCK_BYTES` of VMEM — all of K
where it fits, so each output tile takes one grid step and the int32 dot
is requantized as it leaves the MXU, with no accumulator scratch.  A
grid step costs about 0.4 us on a v5e whatever its size: more than ten
times the MXU time of a 128 x 384 x 128 step.
"""
from __future__ import annotations

import functools
import itertools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import trace_names
from repro.analysis.contracts import check_launch, fit_block, require_launch
from repro.core.dyadic import Dyadic
from repro.kernels import resolve_interpret


#: the VMEM estimate (``analysis.contracts`` ``vmem_bytes``: pipelined
#: blocks twice, plus the int32 tile) a launch's blocks may come to
BLOCK_BYTES = 14 << 20

#: the scoped VMEM the launch asks the compiler for (v5e default: 16
#: MiB).  The estimate leaves out the compiler's own int32 tiles: a
#: split-K launch at Llama-3-8B's widths estimated at 13.5 MiB needs
#: 17.3 MiB (topology compile); a 36 MiB estimate compiles under 32.
VMEM_LIMIT = 32 << 20

#: the tallest row block and the widest column block the rule takes.  A
#: weight block is read again for every row block, so 512 rows keep the
#: weight reads under the MXU time (1,024 int8 ops a byte read)
MAX_BM, MAX_BN = 512, 1024

#: the packed (int4 nibble) launch keeps fixed blocks: its in-register
#: unpack is a different cost, not yet measured at larger blocks
PACKED_BLOCKS = (128, 128, 512)


def _block_sizes(dim: int, cap: int, align: int):
    """Chip-legal blocks of ``dim`` up to ``cap``, largest first: the
    fitted divisors (``fit_block``) of ``cap``, ``cap / 2``, ... down to
    ``align``."""
    sizes, c = [], cap
    while True:
        b = fit_block(c, dim, align)
        if b not in sizes:
            sizes.append(b)
        if c <= align:
            return sizes
        c = max(c // 2, align)


def matmul_blocks(m: int, n: int, k: int, out_bits: int = 8,
                  has_bias: bool = False, per_channel: bool = False):
    """``(bm, bn, bk)`` for an ``(M, K) x (K, N)`` launch, from its shape.

    Takes the tallest row block (up to :data:`MAX_BM`), then the widest
    column block (up to :data:`MAX_BN`), then the deepest K step —
    all of K where it fits — whose VMEM estimate stays within
    :data:`BLOCK_BYTES`; where nothing fits, the smallest blocks.  Every
    block is chip-legal: ``bm`` a multiple of 8, ``bn`` and ``bk``
    multiples of 128, each dividing its dim, or the whole dim."""
    for blocks in itertools.product(_block_sizes(m, MAX_BM, 8),
                                    _block_sizes(n, MAX_BN, 128),
                                    _block_sizes(k, k, 128)):
        bm, bn, bk = blocks
        rep = check_launch("int8_matmul", m=m, n=n, k=k, bm=bm, bn=bn,
                           bk=bk, out_bits=out_bits, has_bias=has_bias,
                           per_channel=per_channel)
        if rep.vmem_bytes <= BLOCK_BYTES:
            break
    return blocks


def _rshift_round(x, s: int):
    if s == 0:
        return x
    return (x + (1 << (s - 1))) >> s


def _requant_tile(acc, b_mult, c: int, pre: int):
    """Dyadic requant of an int32 tile; b_mult scalar int32 or (1,bn)."""
    return _rshift_round(_rshift_round(acc, pre) * b_mult, c - pre)


def _unpack_nibbles_k(w_ref, bk: int, bn: int):
    """In-register nibble expansion of a (bk // 2, bn) packed weight
    block to (bk, bn) int8: low nibble = even K row, high = odd.  All
    arithmetic in int32 with explicit sign extension — bit-exact twin of
    ``repro.ops.packed.nibble_unpack(axis=-2)``."""
    p32 = w_ref[...].astype(jnp.int32)
    lo = ((p32 & 15) ^ 8) - 8
    hi = (((p32 >> 4) & 15) ^ 8) - 8
    return jnp.stack([lo, hi], axis=1).reshape(bk, bn).astype(jnp.int8)


def _mm_kernel(*refs, n_k: int, has_bias: bool, has_bvec: bool,
               dn_b: Optional[int], dn_c: int, dn_pre: int,
               out_lo: int, out_hi: int, out_dtype, raw: bool = False,
               packed: bool = False, bk: int = 0, bn: int = 0):
    it = iter(refs)
    x_ref, w_ref = next(it), next(it)
    bias_ref = next(it) if has_bias else None
    bvec_ref = next(it) if has_bvec else None
    o_ref = next(it)

    def epilogue(acc):
        if has_bias:
            acc = acc + bias_ref[...].astype(jnp.int32)     # (1, bn)
        if raw:                                        # int32 accumulator out
            o_ref[...] = acc.astype(out_dtype)
            return
        if has_bvec:                                   # per-channel requant
            b = bvec_ref[...].astype(jnp.int32)            # (1, bn)
            out = _requant_tile(acc, b, dn_c, dn_pre)
        else:                                          # per-tensor requant
            out = _requant_tile(acc, jnp.int32(dn_b), dn_c, dn_pre)
        out = jnp.clip(out, out_lo, out_hi)
        o_ref[...] = out.astype(out_dtype)

    def dot():
        w = _unpack_nibbles_k(w_ref, bk, bn) if packed else w_ref[...]
        return jax.lax.dot_general(
            x_ref[...], w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)

    if n_k == 1:                      # one step covers K: no accumulator
        epilogue(dot())
        return

    acc_ref = next(it)
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += dot()

    @pl.when(k_step == n_k - 1)
    def _last():
        epilogue(acc_ref[...])


def int8_matmul_pallas(x8, w8, bias32=None, dn: Dyadic = None,
                       b_vec=None, c: int = 0, pre: int = 0,
                       out_bits: int = 8, out_dtype=jnp.int8,
                       bm: Optional[int] = None,
                       bn: Optional[int] = None,
                       bk: Optional[int] = None,
                       packed: bool = False,
                       interpret: Optional[bool] = None):
    """x8: (M, K) int8; w8: (K, N) int8; bias32: (N,) int32 or None.

    Epilogue: ``dn`` (per-tensor) / (``b_vec``, c, pre) (per-channel) /
    neither (**raw**: the int32 accumulator plus bias is written out,
    ``out_dtype`` must be int32).  A block left None is the shape
    rule's (:func:`matmul_blocks`; :data:`PACKED_BLOCKS` for packed
    weights).  M/K/N must divide by the (clamped) block shapes, and the
    blocks must be chip-legal: bm a multiple of 8, bn and bk multiples
    of 128, or the whole dim (``ops.backends.pallas_fused._matmul_blocks``
    fits explicit ones).

    ``packed=True`` switches the weight operand to int4 nibbles:
    ``w8`` is the ``(K // 2, N)`` packed array
    (``QuantLinearParams.w_packed``), streamed as ``(bk // 2, bn)``
    blocks and expanded in-register — packed weights never materialize
    as dense int8 in HBM.  Bit-exact vs unpacking first (msr4 outlier
    lanes are the *caller's* sparse correction on a raw launch).
    """
    m, k = x8.shape
    if packed:
        k_half, n = w8.shape
        assert k == 2 * k_half, (x8.shape, w8.shape)
    else:
        k2, n = w8.shape
        assert k == k2, (x8.shape, w8.shape)
    raw = dn is None and b_vec is None
    if raw:
        assert out_bits == 32 and out_dtype == jnp.int32, \
            "raw epilogue returns the int32 accumulator"
    if None in (bm, bn, bk):
        rule = PACKED_BLOCKS if packed else matmul_blocks(
            m, n, k, out_bits, bias32 is not None, b_vec is not None)
        bm, bn, bk = (r if b is None else b
                      for b, r in zip((bm, bn, bk), rule))
    require_launch(check_launch(
        "int8_matmul", m=m, n=n, k=k, bm=bm, bn=bn, bk=bk,
        out_bits=out_bits, has_bias=bias32 is not None,
        per_channel=b_vec is not None, packed=packed))
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    n_k = k // bk
    if dn is not None:
        dn_b, dn_c, dn_pre = dn.b, dn.c, dn.pre
    else:
        dn_b, dn_c, dn_pre = None, c, pre
    out_lo, out_hi = -(1 << (out_bits - 1)), (1 << (out_bits - 1)) - 1

    kernel = functools.partial(
        _mm_kernel, n_k=n_k, has_bias=bias32 is not None,
        has_bvec=b_vec is not None, dn_b=dn_b, dn_c=dn_c, dn_pre=dn_pre,
        out_lo=out_lo, out_hi=out_hi, out_dtype=out_dtype, raw=raw,
        packed=packed, bk=bk, bn=bn)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
        pl.BlockSpec((bk // 2 if packed else bk, bn),
                     lambda i, j, s: (s, j)),
    ]
    args = [x8, w8]
    for vec in (bias32, b_vec):
        if vec is not None:
            in_specs.append(pl.BlockSpec((1, bn), lambda i, j, s: (0, j)))
            args.append(jnp.asarray(vec, jnp.int32).reshape(1, n))

    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[] if n_k == 1 else [
            pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        name=trace_names.kernel("int8_matmul"),
        interpret=resolve_interpret(interpret),
    )(*args)
