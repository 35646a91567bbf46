"""Static analysis for the quantized datapath (the design-time proof layer).

Three tools, one goal — *prove* properties before anything runs:

  * :mod:`repro.analysis.budgets`    — the single home of the repo's bit
    budgets (``INT32_MAX``, ``MAX_ROWSUM_LEN``, ``MAX_PV_KEYS``, ``MAX_SQ``)
    and the typed
    :class:`BitBudgetError`;
  * :mod:`repro.analysis.ranges`     — the :class:`IntRange` abstract
    domain + sound transfer functions for the integer primitives
    (dyadic requant, matmul accumulation, Shiftmax, i-GELU, i-norm);
  * :mod:`repro.analysis.interpret`  — per-op certification walking a
    whole model config layer-by-layer (the seven ``repro.ops`` ops);
  * :mod:`repro.analysis.contracts`  — :func:`check_launch`, the
    offline Pallas kernel-contract checker (tile divisibility, budget,
    scalar-prefetch shapes, VMEM footprint) and the fused-vs-fallback
    tiling policy the backends consult;
  * :mod:`repro.analysis.lint`       — the AST repo-rule linter
    (``python -m repro.analysis.lint``);
  * :mod:`repro.analysis.certify`    — the CLI sweeping every registry
    config into ``benchmarks/CERTIFY.json``
    (``python -m repro.analysis.certify``).

See docs/ANALYSIS.md for the abstract-domain contract.
"""
from repro.analysis.budgets import (BitBudgetError, INT32_MAX,
                                    MAX_PV_KEYS, MAX_ROWSUM_LEN, MAX_SQ,
                                    static_check)
from repro.analysis.contracts import (KernelContractError, LaunchReport,
                                      can_tile, can_tile_decode,
                                      can_tile_prefill, check_launch,
                                      require_launch)
from repro.analysis.ranges import IntRange
