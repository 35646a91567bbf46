"""Pallas TPU kernel: grouped int8 matmul with a per-group, per-channel
requant epilogue — the expert FFNs of a dropless MoE.

The rows of ``x (M, K)`` arrive sorted by group (expert): the first
``group_sizes[0]`` rows belong to group 0, the next ``group_sizes[1]`` to
group 1, and so on; ``sum(group_sizes) <= M`` and the rows past it are
dead (a dropless MoE sizes ``M`` for the worst case and fills the live
prefix).  Row ``r`` of group ``g`` computes ``x[r] @ w[g]`` into an int32
tile that takes its group's requant multipliers ``b_vec[g]`` with the
shared ``(c, pre)`` of a per-channel :class:`repro.ops.RequantSpec`, or
is written raw.

The tiling follows Megablox's grouped matmul (``jax.experimental.pallas.
ops.tpu.megablox.gmm``), which cannot be used as it is because it
accumulates in float32.  The grid is ``(N / tn, visits)``: a *visit* is
one (row tile, group) pair that holds live rows, so a tile that straddles
a group boundary is visited once per group in it, and a tile past the
live rows is never visited — its MXU work and its HBM traffic are both
skipped.  Five scalar-prefetch arrays, one entry per visit, steer it
(:func:`visit_plan`): the group (for the weight and multiplier blocks),
the row tile (for the x and output blocks), the live row range inside the
tile, and whether the visit is its tile's first.  Visits to one tile are
consecutive, so its output block stays resident across them: the first
visit writes its rows and zeros elsewhere, each later one writes its own
rows over the block.  The static number of visits is the most any split
can need, ``M / tm + G - 1``; spare visits repeat the last live one and
write nothing.

Rows past ``sum(group_sizes)`` are not specified: the kernel never
writes the tiles that hold none of the live rows (the oracle,
``kernels.ref.ref_int_grouped_matmul``, gives 0 there).  A caller reads
live rows only.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import trace_names
from repro.analysis.contracts import check_launch, fit_block, require_launch
from repro.kernels import resolve_interpret

#: the largest row tile and the VMEM estimate (``analysis.contracts``)
#: the blocks may come to; the launch asks the compiler for
#: :data:`VMEM_LIMIT` of scoped VMEM
MAX_TM = 512
BLOCK_BYTES = 20 << 20
VMEM_LIMIT = 48 << 20


def gmm_blocks(m: int, k: int, n: int, out_bits: int = 8,
               per_channel: bool = True):
    """``(tm, tn)``: the tallest row tile up to :data:`MAX_TM` (a
    multiple of 8 dividing M, or M), then the widest column tile (all of
    N, else a multiple of 128 dividing it) whose VMEM estimate stays
    within :data:`BLOCK_BYTES`; K is never split."""
    tm = fit_block(MAX_TM, m, 8)
    cands = [n] + [b for b in range(n - n % 128, 0, -128)
                   if b < n and n % b == 0]
    for tn in cands:
        rep = check_launch("int_grouped_matmul", m=m, k=k, n=n, groups=1,
                           tm=tm, tn=tn, out_bits=out_bits,
                           per_channel=per_channel)
        if rep.vmem_bytes <= BLOCK_BYTES:
            return tm, tn
    return tm, cands[-1]


def visit_plan(group_sizes, m: int, tm: int):
    """The scalar-prefetch arrays of a launch over ``m`` rows in tiles of
    ``tm``: ``(gid, tid, lo, hi, first)``, each int32 ``(M / tm + G -
    1,)``.  Visit ``v`` multiplies row tile ``tid[v]`` by group
    ``gid[v]``'s weights and keeps rows ``lo[v] <= r < hi[v]``; ``first``
    marks the first visit of a tile.  Spare visits (and all of them when
    no row is live) repeat the last live visit's tile with an empty row
    range."""
    sizes = jnp.asarray(group_sizes, jnp.int32)
    g = sizes.shape[0]
    n_vis = m // tm + g - 1
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    t0 = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - t0 + 1, 0)
    cum = jnp.cumsum(tiles)
    live = cum[-1]
    v = jnp.arange(n_vis, dtype=jnp.int32)
    vl = jnp.minimum(v, jnp.maximum(live - 1, 0))     # spare -> last live
    grp = jnp.minimum(jnp.searchsorted(cum, vl, side="right"), g - 1)
    grp = grp.astype(jnp.int32)
    tid = t0[grp] + vl - (cum[grp] - tiles[grp])
    is_live = v < live
    lo = jnp.where(is_live, jnp.maximum(starts[grp], tid * tm), 0)
    hi = jnp.where(is_live, jnp.minimum(ends[grp], (tid + 1) * tm), 0)
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), tid[:-1]])
    first = (tid != prev).astype(jnp.int32)
    return (grp.astype(jnp.int32), tid.astype(jnp.int32),
            lo.astype(jnp.int32), hi.astype(jnp.int32), first)


def _gmm_kernel(gid_ref, tid_ref, lo_ref, hi_ref, first_ref, x_ref, w_ref,
                *rest, tm: int, tn: int, has_bvec: bool, c: int, pre: int,
                out_lo: int, out_hi: int, raw: bool):
    b_ref = rest[0] if has_bvec else None
    o_ref = rest[-1]
    v = pl.program_id(1)
    lo, hi = lo_ref[v], hi_ref[v]

    @pl.when(first_ref[v] == 1)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(hi > lo)
    def _visit():
        acc = jax.lax.dot_general(x_ref[...], w_ref[0],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        if raw:
            out = acc
        else:
            b = b_ref[0].astype(jnp.int32)                   # (1, tn)
            out = _rshift_round(_rshift_round(acc, pre) * b, c - pre)
            out = jnp.clip(out, out_lo, out_hi)
        rows = tid_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, tn), 0)
        keep = (rows >= lo) & (rows < hi)
        o_ref[...] = jnp.where(keep, out.astype(o_ref.dtype), o_ref[...])


def _rshift_round(x, s: int):
    if s == 0:
        return x
    return (x + (1 << (s - 1))) >> s


def int_grouped_matmul_pallas(x8, w8, group_sizes, b_vec=None, c: int = 0,
                              pre: int = 0, out_bits: int = 8,
                              tm: Optional[int] = None,
                              tn: Optional[int] = None,
                              interpret: Optional[bool] = None):
    """x8: (M, K) int8, rows sorted by group; w8: (G, K, N) int8;
    group_sizes: (G,) int32 with ``sum <= M``; b_vec: (G, N) int32
    per-group per-channel multipliers (with the shared ``c``/``pre``),
    or None for the raw int32 accumulator (``out_bits`` 32).

    Returns (M, N): int8 where ``out_bits <= 8``, int32 otherwise; rows
    past ``sum(group_sizes)`` are not specified (module docstring).
    Bit-exact against ``kernels.ref.ref_int_grouped_matmul`` on the live
    rows."""
    m, k = x8.shape
    g, k2, n = w8.shape
    assert k == k2, (x8.shape, w8.shape)
    raw = b_vec is None
    if raw:
        assert out_bits == 32, "the raw accumulator is int32"
    if tm is None or tn is None:
        rule = gmm_blocks(m, k, n, out_bits, not raw)
        tm, tn = tm or rule[0], tn or rule[1]
    require_launch(check_launch(
        "int_grouped_matmul", m=m, k=k, n=n, groups=g, tm=tm, tn=tn,
        out_bits=out_bits, per_channel=not raw))
    out_dtype = jnp.int8 if out_bits <= 8 else jnp.int32
    meta = visit_plan(group_sizes, m, tm)
    n_vis = meta[0].shape[0]

    kernel = functools.partial(
        _gmm_kernel, tm=tm, tn=tn, has_bvec=not raw, c=c, pre=pre,
        out_lo=-(1 << (out_bits - 1)), out_hi=(1 << (out_bits - 1)) - 1,
        raw=raw)

    def x_map(j, v, gid, tid, *_):
        return (tid[v], 0)

    def w_map(j, v, gid, tid, *_):
        return (gid[v], 0, j)

    def o_map(j, v, gid, tid, *_):
        return (tid[v], j)

    in_specs = [pl.BlockSpec((tm, k), x_map),
                pl.BlockSpec((1, k, tn), w_map)]
    args = [x8, w8]
    if not raw:
        in_specs.append(pl.BlockSpec((1, 1, tn), w_map))
        args.append(jnp.asarray(b_vec, jnp.int32).reshape(g, 1, n))

    from jax.experimental.pallas import tpu as pltpu
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(n // tn, n_vis), in_specs=in_specs,
        out_specs=pl.BlockSpec((tm, tn), o_map))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        name=trace_names.kernel("int_grouped_matmul"),
        interpret=resolve_interpret(interpret),
    )(*meta, *args)
