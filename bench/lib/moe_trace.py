"""Device time by path component, for the per-layer metrics of the MLA +
MoE scoring cells (``bench/drivers/mla_moe_score.py``).

``scopes.py`` gives each op one launch name and one owner, out of name
tables it copies.  The MoE sublayer needs two readings those tables
cannot give: everything under ``moe`` (its shared experts run inside it,
under ``ffn.*`` scopes that ``scopes.owner_of`` would pick instead), and
the grouped expert launch, ``int_grouped_matmul``, which is in no copied
table.  Both are read here from each op's whole path, by component.
"""
from __future__ import annotations

from typing import List, Optional

import costs
import devtrace
import harness
import scopes


def path_ops(run: dict) -> List[scopes.Op]:
    """The traced slice's ops with their paths (``scopes.load``), read
    once per run; empty without a slice."""
    if "path_ops" not in run:
        sl = run.get("slice")
        run["path_ops"] = [] if sl is None else scopes.load(
            scopes.newest_xplane(scopes.TRACE_ROOT), sl.lo, sl.hi)
    return run["path_ops"]


def seconds_under(run: dict, component: str) -> float:
    """Device seconds of the slice's ops whose path holds ``component``
    (control-flow ops, which span their bodies, left out)."""
    return sum(op.dur for op in path_ops(run)
               if component in op.path.split("/")
               and not devtrace.CONTAINERS.match(op.name))


def roofline_under(run: dict, component: str, work: str
                   ) -> Optional[float]:
    """Share (%) of the least time of the slice's ``work`` (the driver's
    ``slice_work``) in the device time of the ops under ``component``."""
    measured = seconds_under(run, component)
    ops, nbytes = run.get("slice_work", {}).get(work, (0, 0))
    if measured <= 0 or ops <= 0:
        return None
    least, bound = costs.least_time(ops, nbytes, run["peak"])
    harness.log(f"roofline {component}: least {least:.6f} s ({bound}-bound)"
                f" in {measured:.6f} s under {component}")
    return 100.0 * least / measured
