"""The repo's integer bit budgets, in exactly one place.

SwiftTron solves every scaling constant at design time so no int32
accumulator can overflow on the ASIC.  The reproduction's equivalents of
those design-time registers used to be scattered (``core.intmath``,
``core.softmax``, two per-kernel ``MAX_SKV`` copies); they live here now
— a dependency-leaf module (pure Python, no jax) that ``core``, the
kernels and the analyzer can all import without cycles.

Budgets:

  * ``INT32_MAX``       — the accumulator container every static check
    proves against;
  * ``MAX_ROWSUM_LEN``  — longest softmax row whose exact e16 sum stays
    int32: ``rowlen * 2^15 <= 2^30`` (``core.softmax`` requantizes exp
    values to 2^-15 fractions) — the budget of the standalone
    ``i_softmax`` op.  The fused attention kernels keep it as their
    launch limit (``MAX_SKV``; longer rows take the chunked path), inside
    their own arithmetic's ``MAX_PV_KEYS``;
  * ``MAX_PV_KEYS``     — longest attention row whose unnormalised P·V
    accumulator and final per-row division stay int32 in one pass:
    the row sum of int8 weights is at most ``keys * 127``, the
    accumulator at most ``keys * 127 * 127`` (``< 2^31`` at ``2^17``
    keys) and the division's ``128 * rem + sum / 2`` at most ``128.5 *
    keys * 127`` (also ``< 2^31``).  Longer rows stream in chunks of
    at most ``2^15`` keys that halve the running pair whenever its sum
    could pass ``STREAM_SUM_BUDGET``, half the one-pass sum, which
    leaves the accumulator room for the halvings' rounding
    (``core.attention.fold_pv``);
  * ``MAX_SQ``          — speculative query rows the decode kernel holds
    in VMEM scratch for a whole launch.

:class:`BitBudgetError` is the typed diagnostic the analyzer and the
plan constructors raise: a ``ValueError`` (so legacy ``except
ValueError`` call sites keep working) carrying the offending op, layer,
worst-case value and budget as fields.
"""
from __future__ import annotations

INT32_MAX = 2 ** 31 - 1

# longest row whose e16 sum is int32-exact: rowlen * 2^15 <= 2^30 — the
# budget every exact (non-streaming-corrected) attention kernel asserts
MAX_ROWSUM_LEN = 1 << 15

# longest attention row whose int8-weight sum (<= keys * 127), P·V
# accumulator (<= keys * 127 * 127) and final per-row division all stay
# int32 in a single pass; past it the streaming path keeps its running
# sum under STREAM_SUM_BUDGET
MAX_PV_KEYS = 1 << 17
PV_SUM_BUDGET = MAX_PV_KEYS * 127
STREAM_SUM_BUDGET = PV_SUM_BUDGET // 2

# speculative query budget: decode-kernel scratch rows per head
MAX_SQ = 8


class BitBudgetError(ValueError):
    """A worst-case integer range left its budget.

    Subclasses ``ValueError`` so the pre-existing ``_static_check``
    contract (and callers catching ``ValueError``) is preserved; the
    typed fields are what the certifier and CI surface:

      * ``what``   — which intermediate overflowed (human label);
      * ``value``  — its worst-case magnitude;
      * ``budget`` — the bound it had to stay under;
      * ``op``     — the ``repro.ops`` op being certified (or None);
      * ``layer``  — the model-walk location, e.g. ``"ffn.down"``.
    """

    def __init__(self, what: str, value: int, budget: int = INT32_MAX,
                 op: str | None = None, layer: str | None = None):
        self.what = what
        self.value = int(value)
        self.budget = int(budget)
        self.op = op
        self.layer = layer
        where = "".join(
            f" [{k}={v}]" for k, v in (("op", op), ("layer", layer)) if v)
        if budget == INT32_MAX:
            msg = (f"int32 overflow in {what}: worst case {value} > "
                   f"2^31-1{where}")
        else:
            msg = f"budget exceeded in {what}: {value} > {budget}{where}"
        super().__init__(msg)


def static_check(val: int, what: str, budget: int = INT32_MAX,
                 op: str | None = None, layer: str | None = None) -> int:
    """Design-time bound check; returns ``val`` so checks can inline."""
    if val > budget:
        raise BitBudgetError(what, val, budget, op=op, layer=layer)
    return val


def bits_for(v: int) -> int:
    """Bits needed for magnitude ``v`` (pure-Python twin of
    ``core.dyadic.bits_for``, kept here so this module stays a leaf)."""
    v = int(v)
    return 0 if v <= 0 else v.bit_length()
