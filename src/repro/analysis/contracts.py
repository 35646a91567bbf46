"""Offline Pallas kernel-contract checking: :func:`check_launch`.

Every kernel wrapper in ``repro.kernels`` guards its launch with
preconditions — tile divisibility, the ``MAX_SKV``/``MAX_SQ`` budgets,
page-size constraints, scalar-prefetch operand shapes.  This module
states those contracts *declaratively and without executing anything*
(pure Python, no jax import), so they can be

  * checked offline — "would this shape take the fused kernel or fall
    back, and why?" (:func:`check_launch` returns a
    :class:`LaunchReport` with the predicted grid, block shapes,
    scalar-prefetch operands and a VMEM footprint estimate);
  * enforced in-kernel — the wrappers call :func:`require_launch`,
    which raises :class:`KernelContractError` (an ``AssertionError``
    subclass, so pre-existing ``assert``-expecting callers and tests
    keep working) with every violated clause named;
  * consulted by the dispatching backends — :func:`can_tile`,
    :func:`can_tile_decode` and :func:`can_tile_prefill` are the
    fused-vs-fallback tiling policy ``ops.backends.pallas_fused``
    delegates to.

The contract clauses mirror the kernel wrappers clause-for-clause; a
report with ``ok=False`` predicts an ``AssertionError`` from the kernel,
``fused=False`` predicts the backend's exact fallback path.
"""
from __future__ import annotations

import dataclasses

from repro.analysis.budgets import MAX_ROWSUM_LEN, MAX_SQ

#: the tiling policy's smallest attention block (ops.backends.pallas_fused)
MIN_BLOCK = 16


class KernelContractError(AssertionError):
    """A kernel launch precondition is violated.

    Subclasses ``AssertionError``: the kernels historically ``assert``-ed
    these clauses, and callers/tests relying on that contract must keep
    working.  Fields: ``op`` (kernel name), ``reasons`` (every violated
    clause, human-readable, location-bearing).
    """

    def __init__(self, op: str, reasons):
        self.op = op
        self.reasons = tuple(reasons)
        super().__init__(
            f"{op} launch contract violated: " + "; ".join(self.reasons))


@dataclasses.dataclass(frozen=True)
class LaunchReport:
    """What a kernel launch would look like, statically.

    ``ok``     — the kernel's own preconditions hold (False predicts an
                 in-wrapper assertion);
    ``fused``  — the backend tiling policy would take the fused kernel
                 (False predicts the documented exact fallback);
    ``reasons``— every violated / declining clause;
    ``grid``   — the Pallas grid the launch would use;
    ``blocks`` — resolved block shapes (after ``_fit_block`` clamping);
    ``vmem_bytes`` — per-grid-step VMEM estimate (operand blocks +
                 output block + scratch);
    ``scalar_prefetch`` — ``(name, shape)`` for each scalar-prefetch
                 operand the launch consumes.
    """

    op: str
    ok: bool
    fused: bool
    reasons: tuple = ()
    grid: tuple = ()
    blocks: dict = dataclasses.field(default_factory=dict)
    vmem_bytes: int = 0
    scalar_prefetch: tuple = ()


def fit_block(blk: int, dim: int, align: int = 1) -> int:
    """The block the backends launch with: the largest divisor of
    ``dim`` that is <= ``blk`` and a multiple of ``align`` — or ``dim``
    itself when none is (a whole-dim block is always chip-legal, see
    :func:`tpu_block_violations`).  ``align=1`` is the plain largest
    divisor <= ``blk``."""
    if dim <= blk:
        return dim
    for b in range(blk - blk % align, 0, -align):
        if dim % b == 0:
            return b
    return dim


#: VMEM one kernel launch may use on a TPU v5e, as the topology
#: compile measures it (``tests/test_chip_compile.py`` rehearses the
#: edges): a matmul whose pipelined blocks come to about 50 MiB
#: compiles and one of about 67 MiB runs out of VMEM; a folded-wo
#: attention launch with a 15 MiB ``wo`` block compiles and one with
#: 16 MiB (Llama-3-8B's 4096 x 4096) does not.  ``vmem_bytes`` below is
#: the estimate held to it.  The int8 matmul's own limit is lower:
#: under the chip's default 16 MiB of scoped VMEM, launches estimated at
#: 24 MiB (int8 out) and 36 MiB (int32 out) run out, and so does a
#: split-K one at 13.5 MiB (the compiler's int32 tiles come on top).  So
#: that kernel asks for ``kernels.int8_matmul.VMEM_LIMIT`` of scoped
#: VMEM and holds its blocks to ``BLOCK_BYTES`` of this estimate.
VMEM_BUDGET = 64 << 20

#: the TPU's native (sublane, lane) tile: a block's last two dims must
#: each divide by these — or equal the array's own dims
TPU_TILE = (8, 128)


def tpu_block_violations(name: str, block, array) -> list:
    """The chip compiler's block-shape rule (Mosaic), stated offline.

    A ``BlockSpec`` block of an N-d operand is legal only if its last
    two dims are each a multiple of the native tile — 8 sublanes, 128
    lanes — or equal to the array's dims there.  A 1-d block must span
    the whole array (the compiler lays 1-d blocks out as one row and
    refuses partial ones).  Interpret mode checks none of this, which
    is why the kernels once passed every CPU test and failed to compile
    on the chip.  Returns one human-readable reason per violation."""
    block, array = tuple(block), tuple(array)
    if len(block) != len(array):
        return [f"{name}: block {block} has a different rank from its "
                f"array {array}"]
    if len(block) == 1:
        if block != array:
            return [f"{name}: 1-d block {block} must span the whole "
                    f"array {array}"]
        return []
    out = []
    for pos, tile in zip((-2, -1), TPU_TILE):
        b, a = block[pos], array[pos]
        if b != a and b % tile:
            out.append(f"{name}: block {block} over {array} — dim {pos} "
                       f"({b}) must be a multiple of {tile} or equal "
                       f"{a} (TPU last-two-dims rule)")
    return out


def vmem_violations(op: str, vmem: int) -> list:
    """The VMEM clause: a launch whose estimate exceeds
    :data:`VMEM_BUDGET` is refused here, before the chip's compiler
    refuses it."""
    if vmem <= VMEM_BUDGET:
        return []
    return [f"{op}: VMEM estimate {vmem / 2**20:.1f} MiB exceeds the "
            f"{VMEM_BUDGET >> 20} MiB budget"]


def check_blocks(op: str, blocks: dict) -> LaunchReport:
    """Validate a launch's operand blocks against the chip's block
    rule alone: ``blocks`` maps operand name -> ``(block_shape,
    array_shape)``.  The per-kernel checks below run every block they
    launch through this rule; it is public so a layout can be judged
    before a kernel exists for it."""
    reasons = []
    for name, (block, array) in blocks.items():
        reasons += tpu_block_violations(name, block, array)
    return LaunchReport(op=op, ok=not reasons, fused=not reasons,
                        reasons=tuple(reasons))


# ---------------------------------------------------------------- policy --

def can_tile(sq: int, skv: int, bq: int, bkv: int) -> bool:
    """Fused prefill-attention tiling policy (pallas_fused backend)."""
    if skv > MAX_ROWSUM_LEN:
        return False          # exact row sum leaves the int32 budget
    if sq < MIN_BLOCK or skv < MIN_BLOCK:
        return False          # tiny problem (e.g. decode): oracle wins
    if bq < MIN_BLOCK or bkv < MIN_BLOCK:
        return False          # no usable divisor (e.g. prime Sq)
    return True


def can_tile_decode(sq: int, L: int, d: int, bkv: int) -> bool:
    """Fused decode tiling policy (pallas_fused backend)."""
    if sq > MAX_SQ:
        return False          # scratch holds at most MAX_SQ query rows
    if L > MAX_ROWSUM_LEN:
        return False          # exact row sum leaves the int32 budget
    if bkv < MIN_BLOCK:
        return False          # no usable cache-block divisor
    if d % 2:
        return False          # odd head dims: lane-hostile, oracle wins
    return True


def can_tile_prefill(L: int, d: int, bq: int, bkv: int) -> bool:
    """Fused paged-prefill tiling policy (pallas_fused backend)."""
    if L > MAX_ROWSUM_LEN:
        return False          # exact row sum leaves the int32 budget
    if bq < MIN_BLOCK or bkv < MIN_BLOCK:
        return False          # tiny chunk / page: oracle wins
    if d % 2:
        return False          # odd head dims: lane-hostile, oracle wins
    return True


# ----------------------------------------------------------- per-kernel --

def _check_int8_matmul(m, n, k, bm=128, bn=128, bk=512, out_bits=8,
                       has_bias=False, per_channel=False, packed=False):
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    reasons = []
    if m % bm or n % bn or k % bk:
        reasons.append("blocks must divide the problem: "
                       f"(M,N,K)=({m},{n},{k}) %% (bm,bn,bk)="
                       f"({bm},{bn},{bk})")
    if packed and (k % 2 or bk % 2):
        reasons.append("packed weights pair nibbles along K: K and bk "
                       f"must be even (got K={k}, bk={bk})")
    kw, bkw = (k // 2, bk // 2) if packed else (k, bk)
    blocks = {"x8": ((bm, bk), (m, k)), "w": ((bkw, bn), (kw, n)),
              "out": ((bm, bn), (m, n))}
    if has_bias:
        blocks["bias32"] = ((1, bn), (1, n))
    if per_channel:
        blocks["b_vec"] = ((1, bn), (1, n))
    reasons += check_blocks("int8_matmul", blocks).reasons
    # pipelined blocks are double-buffered; packed operands halve the
    # weight block: (bk // 2, bn) int8 nibbles, unpacked in-register
    blk = bm * bk + (bk // 2 if packed else bk) * bn \
        + bm * bn * (1 if out_bits <= 8 else 4)     # x8 + w + out
    blk += bn * 4 * (int(has_bias) + int(per_channel))
    vmem = 2 * blk + bm * bn * 4                    # + acc scratch
    reasons += vmem_violations("int8_matmul", vmem)
    return LaunchReport(
        op="int8_matmul_packed" if packed else "int8_matmul",
        ok=not reasons, fused=not reasons,
        reasons=tuple(reasons),
        grid=(m // bm, n // bn, k // bk) if not reasons else (),
        blocks={"bm": bm, "bn": bn, "bk": bk}, vmem_bytes=vmem)


def _check_int8_matmul_packed(m, n, k, bm=128, bn=128, bk=512, out_bits=8,
                              has_bias=False, per_channel=False):
    return _check_int8_matmul(m, n, k, bm=bm, bn=bn, bk=bk,
                              out_bits=out_bits, has_bias=has_bias,
                              per_channel=per_channel, packed=True)


def _attn_common(h, hkv, reasons):
    if h % hkv:
        reasons.append(f"GQA requires Hkv | H: got H={h}, Hkv={hkv}")


def _attn_blocks(q_rows, rows, h, hkv, d, kv_lead, kv_rows, bkv, kv_d,
                 per_channel, fold, n_out):
    """The fused attention launches' operand blocks (all heads per
    block; head-major output), as ``check_blocks`` input.  ``q_rows`` /
    ``rows``: the query axis length and its block; ``kv_lead`` /
    ``kv_rows``: the cache's leading dims (batch or pages, length or
    page size)."""
    blocks = {
        "q": ((1, rows, h, d), (1, q_rows, h, d)),
        "k": ((1, bkv, hkv, kv_d), (kv_lead, kv_rows, hkv, kv_d)),
        "v": ((1, bkv, hkv, kv_d), (kv_lead, kv_rows, hkv, kv_d)),
    }
    if per_channel:
        blocks["b_vec"] = ((h, d), (h, d))
    if fold:
        blocks["wo"] = ((h * d, n_out), (h * d, n_out))
        blocks["wo_vec"] = ((1, n_out), (1, n_out))
        blocks["out"] = ((1, rows, n_out), (1, q_rows, n_out))
    else:
        blocks["out"] = ((1, h, rows, d), (1, h, q_rows, d))
    return blocks


def _attn_vmem(rows, h, hkv, d, bkv, kv_d, out_elem, per_channel, fold,
               n_out):
    """Per-step VMEM estimate of a fused attention launch: q / k / v /
    output blocks and epilogue operands, each double-buffered, plus the
    per-head m/s/acc scratch.  A folded ``wo`` block counts four times:
    two pipeline buffers, and the per-head ``(D, N)`` slab reads stage
    about as much again (the topology compile of a 16 MiB block runs
    out of VMEM where a 50 MiB matmul does not)."""
    blk = rows * h * d + 2 * bkv * hkv * kv_d       # q + k + v blocks
    if per_channel:
        blk += h * d * 4
    if fold:
        blk += 2 * n_out * 4 + rows * n_out * out_elem  # vectors + out
    else:
        blk += h * rows * d * out_elem
    vmem = 2 * blk + 2 * h * rows * 4 + h * rows * d * 4  # + m/s/acc
    if fold:
        vmem += 4 * h * d * n_out + rows * n_out * 4  # wo + accumulator
    return vmem


def can_fold_wo(rows, h, hkv, d, bkv, n_out, kv_d=None,
                per_channel=False) -> bool:
    """Folded-wo policy (pallas_fused backend): fold the o-projection
    into the attention launch only while the whole ``(H·D, N)`` block
    keeps the launch inside :data:`VMEM_BUDGET`; otherwise the backend
    runs the attention kernel unfolded and ``wo`` through its own
    matmul, with identical integers."""
    return _attn_vmem(rows, h, hkv, d, bkv, d if kv_d is None else kv_d,
                      1, per_channel, True, n_out) <= VMEM_BUDGET


def _check_int_attention(b, sq, skv, h, hkv, d, bq=128, bkv=128,
                         out_bits=8, per_channel=False):
    """The fused prefill launch."""
    op = "int_attention"
    bq, bkv = min(bq, sq), min(bkv, skv)    # the kernels' own clamping
    reasons, policy = [], []
    _attn_common(h, hkv, reasons)
    if skv > MAX_ROWSUM_LEN:
        reasons.append(f"row-sum int32 budget: Skv <= {MAX_ROWSUM_LEN} "
                       f"(got {skv})")
    if sq % bq or skv % bkv:
        reasons.append(f"blocks must divide (Sq,Skv)=({sq},{skv}): "
                       f"(bq,bkv)=({bq},{bkv})")
    reasons += check_blocks(op, _attn_blocks(
        sq, bq, h, hkv, d, b, skv, bkv, d, per_channel, False, 0)).reasons
    if not can_tile(sq, skv, bq, bkv):
        policy.append(f"tiling policy declines: sq={sq}, skv={skv}, "
                      f"bq={bq}, bkv={bkv}, min_block={MIN_BLOCK}")
    out_elem = 1 if out_bits <= 8 else 4
    vmem = _attn_vmem(bq, h, hkv, d, bkv, d, out_elem, per_channel,
                      False, 0)
    reasons += vmem_violations(op, vmem)
    grid = () if sq % bq or skv % bkv else (b, sq // bq, 2, skv // bkv)
    return LaunchReport(
        op=op, ok=not reasons, fused=not (reasons or policy),
        reasons=tuple(reasons + policy), grid=grid,
        blocks={"bq": bq, "bkv": bkv}, vmem_bytes=vmem)


def _check_int_decode_attention(b, sq, h, hkv, d, L=None, bkv=128,
                                max_pages=0, page_size=0, out_bits=8,
                                per_channel=False, fold=False, n_out=0,
                                kv_pack=False, num_pages=0):
    paged = page_size > 0
    if paged:
        L = max_pages * page_size
    assert L is not None, "need L (contiguous) or max_pages+page_size"
    reasons, policy = [], []
    _attn_common(h, hkv, reasons)
    if kv_pack:
        if not paged:
            reasons.append("int4 KV pages require the paged layout "
                           "(kv_pack without page_size)")
        if d % 2:
            reasons.append("int4 KV pages pair nibbles along the head "
                           f"dim: d must be even (got {d})")
    if sq > MAX_SQ:
        reasons.append(f"decode kernel holds Sq <= {MAX_SQ} query rows "
                       f"in scratch (got {sq})")
    if L > MAX_ROWSUM_LEN:
        reasons.append("row-sum int32 budget: cache_len <= "
                       f"{MAX_ROWSUM_LEN} (got {L})")
    bkv = min(bkv, page_size if paged else L)
    if paged:
        if page_size % bkv:
            reasons.append("KV block must tile the physical page: "
                           f"page_size={page_size}, bkv={bkv}")
    elif L % bkv:
        reasons.append(f"KV block must tile the cache: L={L}, bkv={bkv}")
    if fold and not n_out:
        reasons.append("folded wo projection needs n_out (= wo_w8 "
                       "output channels)")
    kv_d = d // 2 if kv_pack else d
    reasons += check_blocks("int_decode_attention", _attn_blocks(
        sq, sq, h, hkv, d, max(num_pages, 1) if paged else b,
        page_size if paged else L, bkv, kv_d, per_channel, fold,
        n_out)).reasons
    if not can_tile_decode(sq, L, d, bkv):
        policy.append(f"tiling policy declines: sq={sq}, L={L}, d={d}, "
                      f"bkv={bkv}, min_block={MIN_BLOCK}")
    prefetch = [("valid_len", (b,))]
    if paged:
        prefetch.append(("pages", (b, max_pages)))
    if kv_pack:
        # per-page dequant shifts ride as two more scalar-prefetch
        # operands; K/V blocks hold (bkv, Hkv, d // 2) nibbles
        prefetch.append(("k_shift", (num_pages,)))
        prefetch.append(("v_shift", (num_pages,)))
    vmem = _attn_vmem(sq, h, hkv, d, bkv, kv_d,
                      1 if out_bits <= 8 else 4, per_channel, fold, n_out)
    reasons += vmem_violations("int_decode_attention", vmem)
    grid = (b, 2, L // bkv) if not (L % bkv if not paged
                                    else page_size % bkv) else ()
    return LaunchReport(
        op="int_decode_attention", ok=not reasons,
        fused=not (reasons or policy), reasons=tuple(reasons + policy),
        grid=grid, blocks={"bkv": bkv}, vmem_bytes=vmem,
        scalar_prefetch=tuple(prefetch))


def _check_int_paged_prefill(b, c, h, hkv, d, max_pages, page_size,
                             bq=128, bkv=128, out_bits=8,
                             per_channel=False, fold=False, n_out=0,
                             kv_pack=False, num_pages=0):
    L = max_pages * page_size
    reasons, policy = [], []
    _attn_common(h, hkv, reasons)
    if kv_pack and d % 2:
        reasons.append("int4 KV pages pair nibbles along the head dim: "
                       f"d must be even (got {d})")
    if L > MAX_ROWSUM_LEN:
        reasons.append("row-sum int32 budget: logical cache <= "
                       f"{MAX_ROWSUM_LEN} (got {L})")
    bq = min(bq, c)
    bkv = min(bkv, page_size)
    if c % bq:
        reasons.append(f"query block must tile the chunk: c={c}, bq={bq}")
    if page_size % bkv:
        reasons.append("KV block must tile the physical page: "
                       f"page_size={page_size}, bkv={bkv}")
    if fold and not n_out:
        reasons.append("folded wo projection needs n_out (= wo_w8 "
                       "output channels)")
    kv_d = d // 2 if kv_pack else d
    reasons += check_blocks("int_paged_prefill", _attn_blocks(
        c, bq, h, hkv, d, max(num_pages, 1), page_size, bkv, kv_d,
        per_channel, fold, n_out)).reasons
    if not can_tile_prefill(L, d, bq, bkv):
        policy.append(f"tiling policy declines: L={L}, d={d}, bq={bq}, "
                      f"bkv={bkv}, min_block={MIN_BLOCK}")
    vmem = _attn_vmem(bq, h, hkv, d, bkv, kv_d,
                      1 if out_bits <= 8 else 4, per_channel, fold, n_out)
    reasons += vmem_violations("int_paged_prefill", vmem)
    prefetch = [("pos_end", (b,)), ("pages", (b, max_pages))]
    if kv_pack:
        prefetch.append(("k_shift", (num_pages,)))
        prefetch.append(("v_shift", (num_pages,)))
    grid = (b, c // bq, 2, L // bkv) \
        if not (c % bq or page_size % bkv) else ()
    return LaunchReport(
        op="int_paged_prefill", ok=not reasons,
        fused=not (reasons or policy), reasons=tuple(reasons + policy),
        grid=grid, blocks={"bq": bq, "bkv": bkv}, vmem_bytes=vmem,
        scalar_prefetch=tuple(prefetch))


_CHECKS = {
    "int8_matmul": _check_int8_matmul,
    "int8_matmul_packed": _check_int8_matmul_packed,
    "int_attention": _check_int_attention,
    "int_decode_attention": _check_int_decode_attention,
    "int_paged_prefill": _check_int_paged_prefill,
}


def check_launch(op: str, **params) -> LaunchReport:
    """Statically validate a kernel launch.  ``op`` is one of
    ``int8_matmul`` / ``int8_matmul_packed`` / ``int_attention`` /
    ``int_decode_attention`` / ``int_paged_prefill``;
    ``params`` are the launch shapes (see the per-kernel helpers).
    Never executes or imports jax — safe anywhere, including CI."""
    if op not in _CHECKS:
        raise KeyError(f"unknown kernel op {op!r}; known: "
                       f"{sorted(_CHECKS)}")
    return _CHECKS[op](**params)


def check_tp_launch(op: str, tp: int = 1, **params) -> LaunchReport:
    """Statically validate the *per-shard* kernel launch of a
    tensor-parallel serving step: under ``shard_map`` head sharding
    (``distributed.tp_serving``) each device launches the attention
    kernel with ``h/tp`` query heads and ``hkv/tp`` KV heads of the
    global problem — every other shape (batch, chunk, cache geometry,
    head dim) is unchanged.  This is the offline twin of the in-wrapper
    ``require_launch`` call, which under shard_map sees (and validates)
    exactly these local shapes.  Shard-divisibility violations come back
    as a failed report, same as any other contract clause."""
    if op not in ("int_attention", "int_decode_attention",
                  "int_paged_prefill"):
        raise KeyError(f"check_tp_launch covers the attention launches "
                       f"of the tp serving path, not {op!r}")
    reasons = []
    if tp < 1:
        reasons.append(f"tp must be >= 1 (got {tp})")
    h, hkv = params.get("h"), params.get("hkv")
    if h is None or hkv is None:
        reasons.append("per-shard check needs the global h and hkv")
    elif tp >= 1:
        if hkv % tp:
            reasons.append(f"tp={tp} must divide the KV head count "
                           f"(hkv={hkv}): each shard owns hkv/tp heads")
        if h % tp:
            reasons.append(f"tp={tp} must divide the query head count "
                           f"(h={h})")
    if reasons:
        return LaunchReport(op=op, ok=False, fused=False,
                            reasons=tuple(reasons))
    return check_launch(op, **{**params, "h": h // tp, "hkv": hkv // tp})


def require_launch(report: LaunchReport) -> LaunchReport:
    """Raise :class:`KernelContractError` unless the kernel's own
    preconditions hold (``report.ok``).  Policy declines (``fused=False``
    with ``ok=True``) pass — the backend handles those by falling back."""
    if not report.ok:
        raise KernelContractError(report.op, report.reasons)
    return report


# ------------------------------------------------- request feasibility --


class RequestInfeasible(ValueError):
    """A request that can NEVER complete on the engine's cache geometry.

    Admitting it anyway would either corrupt live cache positions
    (prompt longer than the logical cache) or burn pool pages and lane
    time on a stream guaranteed to retire short of ``max_new_tokens``
    (prompt + continuation overrunning ``cache_len``) — and the failure
    would only surface deep inside a step, or never.  Raised at the
    submit / CLI boundary instead.  Fields: ``prompt_len``,
    ``max_new_tokens``, ``cache_len``, ``reasons`` (every violated
    clause)."""

    def __init__(self, prompt_len: int, max_new_tokens: int,
                 cache_len: int, reasons):
        self.prompt_len = prompt_len
        self.max_new_tokens = max_new_tokens
        self.cache_len = cache_len
        self.reasons = tuple(reasons)
        super().__init__(
            f"infeasible request (prompt_len={prompt_len}, "
            f"max_new_tokens={max_new_tokens}, cache_len={cache_len}): "
            + "; ".join(self.reasons))


def check_request(prompt_len: int, max_new_tokens: int, cache_len: int,
                  window: int = 0, page_size: int = 0,
                  num_pages: int = 0) -> tuple:
    """Statically validate one serving request against a cache geometry;
    returns the tuple of violated clauses (empty = feasible).

    The exact feasibility bound for full-causal archs (``window == 0``):
    prefill writes ``prompt_len - 1`` K/V positions and every decoded
    token writes one more, so the request reaches ``max_new_tokens``
    only if ``prompt_len - 1 + max_new_tokens <= cache_len`` (the engine
    retires lanes at ``pos >= cache_len``).  Sliding-window archs wrap,
    so only the prompt-fits clause applies.  With a paged pool
    (``page_size`` / ``num_pages`` given), a prompt whose block count
    exceeds the allocatable pool can never be admitted either — that
    used to surface as :class:`~repro.serving.kvcache.PagePoolExhausted`
    from deep inside a scheduler step.  Pure Python, no jax — safe at
    any CLI / server boundary."""
    reasons = []
    if prompt_len < 1:
        reasons.append("empty prompt: a request needs at least one token")
    if max_new_tokens < 1:
        reasons.append(f"max_new_tokens must be >= 1 (got "
                       f"{max_new_tokens})")
    L = min(cache_len, window) if window > 0 else cache_len
    if window == 0 and prompt_len > L:
        reasons.append(
            f"prompt of {prompt_len} tokens exceeds the cache_len={L} "
            "logical cache: prefill would write past the page table / "
            "cache slab and silently corrupt live positions")
    elif window == 0 and prompt_len - 1 + max_new_tokens > cache_len:
        reasons.append(
            f"prompt_len + max_new_tokens exceeds the cache: the stream "
            f"needs {prompt_len - 1 + max_new_tokens} K/V positions but "
            f"cache_len={cache_len} — the request would silently retire "
            f"after {cache_len - prompt_len + 1} token(s); shrink "
            "max_new_tokens or raise cache_len")
    if window == 0 and page_size > 0 and num_pages > 0:
        span = min(max(prompt_len - 1, 0), L)
        blocks = -(-span // page_size)
        if blocks > num_pages - 1:
            reasons.append(
                f"prompt prefill needs {blocks} pages but the pool only "
                f"has {num_pages - 1} allocatable (page 0 is the null "
                "page): the admission can never succeed")
    return tuple(reasons)


def require_request(prompt_len: int, max_new_tokens: int, cache_len: int,
                    window: int = 0, page_size: int = 0,
                    num_pages: int = 0) -> None:
    """Raise :class:`RequestInfeasible` if :func:`check_request` finds
    any violated clause."""
    reasons = check_request(prompt_len, max_new_tokens, cache_len,
                            window=window, page_size=page_size,
                            num_pages=num_pages)
    if reasons:
        raise RequestInfeasible(prompt_len, max_new_tokens, cache_len,
                                reasons)


__all__ = [
    "KernelContractError", "LaunchReport", "MIN_BLOCK",
    "RequestInfeasible", "TPU_TILE", "can_tile", "can_tile_decode",
    "can_tile_prefill", "check_blocks", "check_launch", "check_request",
    "check_tp_launch", "fit_block", "require_launch", "require_request",
    "tpu_block_violations",
]
