"""Device trace capture and its reduction to per-layer numbers.

The profiler writes an ``.xplane.pb`` under ``<dir>/plugins/profile/``;
``jax.profiler.ProfileData`` reads it.  Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
operation that ran, their ``XLA Modules`` line one event per program
execution.  The benchmark's own host spans (``jax.profiler.
TraceAnnotation``, names starting with ``bench.``) sit on the host plane,
on the same clock.

Everything here is pure arithmetic on (name, start, duration) events,
so the CPU self-tests check it on hand-built traces.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

def _kernel_of_signature(operands: List[str]) -> Optional[str]:
    """The kernel behind one ``tpu_custom_call`` from its operand and
    result types.  The program's Pallas launches carry no names, so the
    table was written from the compiled programs of each cell, read by
    hand (PERF.md, "Where the time goes"):

      * int8 matmul: ``s8[M,K]``, ``s8[K,N]`` and int32 vectors;
      * fused attention (encoder): three ``s8[B,S,H,D]`` (q, k, v);
      * integer norm: ``s32[R,D]``, ``s32[D]``, ``s32[D]``;
      * i-GELU: one ``s32[R,L]``;
      * paged attention (serving): ``s32[B]`` lengths, ``s32[B,P]`` page
        table, ``s8[B,Sq,H,D]`` queries and two ``s8`` page pools, then
        the folded output projection: decode where ``Sq <= 8`` (one token,
        or a verify block), chunked prefill above.
    """
    def dims(t):
        m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", t)
        return (m.group(1), [int(x) for x in m.group(2).split(",") if x]) \
            if m else (t, [])
    ops = [dims(o) for o in operands]
    kinds = [k for k, _ in ops]
    if len(ops) >= 3 and kinds[:2] == ["s8", "s8"] and \
            len(ops[0][1]) == 2 and len(ops[1][1]) == 2 and \
            ops[0][1][1] == ops[1][1][0]:
        return "int8_matmul"
    if len(ops) == 3 and kinds == ["s8"] * 3 and \
            all(len(d) == 4 for _, d in ops):
        return "attention"
    if len(ops) >= 5 and kinds[:5] == ["s32", "s32", "s8", "s8", "s8"] \
            and [len(d) for _, d in ops[:5]] == [1, 2, 4, 4, 4]:
        return "decode_attention" if ops[2][1][1] <= 8 \
            else "prefill_attention"
    if len(ops) in (2, 3) and kinds[0] == "s32" and len(ops[0][1]) == 2 \
            and all(len(d) == 1 for _, d in ops[1:]):
        return "int_norm"
    if len(ops) == 1 and kinds == ["s32"] and len(ops[0][1]) == 2:
        return "int_gelu"
    return None


def kernel_table(hlo_text: str) -> Dict[str, str]:
    """{HLO instruction name: kernel} for every Pallas launch of a
    compiled program (``compiled.as_text()``): trace events carry the
    instruction's name."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line)
        at = line.find("operand_layout_constraints={")
        if not name or at < 0:
            continue
        depth, end = 0, at + len("operand_layout_constraints=")
        for end in range(end, len(line)):
            depth += {"{": 1, "}": -1}.get(line[end], 0)
            if depth == 0:
                break
        operands = re.findall(r"([a-z0-9]+\[[0-9,]*\])", line[at:end])
        kernel = _kernel_of_signature(operands)
        if kernel:
            out[name.group(1)] = kernel
    return out


@dataclass
class Program:
    """One compiled program of the run: its instruction names and the
    kernel behind each of its Pallas launches."""
    name: str
    table: Dict[str, str]
    names: frozenset

    @classmethod
    def from_hlo(cls, name: str, hlo_text: str) -> "Program":
        return cls(name, kernel_table(hlo_text), frozenset(
            re.findall(r"%([\w.\-]+) = ", hlo_text)))


@dataclass
class Event:
    name: str
    start: float        # seconds
    dur: float          # seconds

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Trace:
    ops: Dict[int, List[Event]] = field(default_factory=dict)      # chip
    modules: Dict[int, List[Event]] = field(default_factory=dict)  # chip
    host: List[Event] = field(default_factory=list)                # spans


def op_name(event_name: str) -> str:
    """A device op's event is named by its whole HLO instruction
    (``%closed_call.35 = s8[...] custom-call(...), ...``): keep the
    instruction's name."""
    m = re.match(r"%([\w.\-]+) = ", event_name)
    return m.group(1) if m else event_name


#: control-flow ops span the ops of their bodies
CONTAINERS = re.compile(r"(while|conditional|call)(\.\d+)?$")


def load(trace_dir: str) -> Trace:
    """Read the newest xplane under ``trace_dir``."""
    import jax
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"no trace under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    tr = Trace()
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        for line in plane.lines:
            evs = [Event(op_name(e.name), e.start_ns * 1e-9,
                         e.duration_ns * 1e-9) for e in line.events]
            if m and line.name == "XLA Ops":
                tr.ops[int(m.group(1))] = evs
            elif m and line.name == "XLA Modules":
                tr.modules[int(m.group(1))] = evs
            elif not m and plane.name.startswith("/host"):
                tr.host += [e for e in evs if e.name.startswith("bench.")]
    return tr


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    """The parts of ``events`` that lie in [lo, hi]."""
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(Event(e.name, s, t - s))
    return out


def union(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Merged busy intervals."""
    spans = sorted((e.start, e.end) for e in events)
    out: List[List[float]] = []
    for s, t in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def busy_s(events: Sequence[Event]) -> float:
    return sum(t - s for s, t in union(events))


def gaps(events: Sequence[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Idle intervals of [lo, hi] that no event covers."""
    out, at = [], lo
    for s, t in union(events):
        if s > at:
            out.append((at, s))
        at = max(at, t)
    if hi > at:
        out.append((at, hi))
    return out


def assign(ops: Sequence[Event], modules: Sequence[Event],
           programs: Sequence[Program]) -> List[Tuple[Event, Optional[str],
                                                      Optional[str]]]:
    """(module execution, program, kernel) of every op.  Programs of one
    run can share instruction names, so each execution is matched to the
    program that holds the most of its ops' names (at least nine in
    ten), and its ops are read with that program's table."""
    mods = sorted(modules, key=lambda e: e.start)
    inside: Dict[int, List[Event]] = {}
    j = 0
    owner = []
    for e in sorted(ops, key=lambda e: e.start):
        while j < len(mods) and mods[j].end < e.start:
            j += 1
        k = j if j < len(mods) and mods[j].start <= e.start else None
        owner.append((e, k))
        if k is not None:
            inside.setdefault(k, []).append(e)
    prog_of: Dict[int, Optional[Program]] = {}
    for k, evs in inside.items():
        best, score = None, 0.9
        for p in programs:
            hit = sum(e.name in p.names for e in evs) / len(evs)
            if hit >= score:
                best, score = p, hit
        prog_of[k] = best
    out = []
    for e, k in owner:
        p = prog_of.get(k) if k is not None else None
        out.append((mods[k] if k is not None else None,
                    p.name if p else None,
                    p.table.get(e.name) if p else None))
    return out


def top_ops(events: Sequence[Event], n: int = 10,
            kinds: Optional[Dict[str, str]] = None) -> List[list]:
    """The ``n`` operations that took most device time (control-flow
    ops, which span their bodies, left out), each named with its kernel
    where ``kinds`` knows it."""
    kinds = kinds or {}
    tot: Dict[str, float] = {}
    for e in events:
        if CONTAINERS.match(e.name):
            continue
        name = f"{e.name} ({kinds[e.name]})" if e.name in kinds else e.name
        tot[name] = tot.get(name, 0.0) + e.dur
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_by_host(events: Sequence[Event], host: Sequence[Event], lo: float,
                 hi: float, n: int = 10) -> List[list]:
    """Idle device time by the innermost host span open at each gap's
    middle (``idle`` where none is), longest total first."""
    tot: Dict[str, float] = {}
    for s, t in gaps(events, lo, hi):
        mid = (s + t) / 2
        open_ = [h for h in host if h.start <= mid <= h.end
                 and h.name != SLICE]
        name = min(open_, key=lambda h: h.dur).name if open_ else "idle"
        tot[name] = tot.get(name, 0.0) + (t - s)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


#: the host span that brackets the traced slice of the window
SLICE = "bench.slice"


@dataclass
class Slice:
    """One traced slice, reduced: the window, device busy time averaged
    over ``chips``, per-kernel device seconds, program executions."""
    window_s: float
    busy_s: float
    kernels: Dict[str, float]
    runs: Dict[str, List[Event]]       # program -> its executions
    ops: List[Event]
    host: List[Event]
    lo: float
    hi: float
    kinds: Dict[str, str] = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def executions(self, program: str) -> List[Event]:
        """The executions of ``program`` that overlap the slice, whole."""
        return self.runs.get(program, [])

    def count(self, program: str) -> float:
        """How many executions of ``program`` the slice holds, an
        execution cut by the slice's edge counted by the share of it
        inside (the host's and the device's clocks can disagree by a
        little at the edges)."""
        return sum((min(e.end, self.hi) - max(e.start, self.lo)) / e.dur
                   for e in self.executions(program) if e.dur > 0)

    def breakdown(self) -> dict:
        return {"device_ops": top_ops(self.ops, kinds=self.kinds),
                "idle_gaps": idle_by_host(self.ops, self.host, self.lo,
                                          self.hi)}


def reduce(tr: Trace, chips: Sequence[int], programs: Sequence[Program]
           ) -> Slice:
    """Reduce the traced slice of ``tr``; ``programs`` are the compiled
    programs the window ran (:meth:`Program.from_hlo`)."""
    spans = [h for h in tr.host if h.name == SLICE]
    if not spans:
        raise RuntimeError("the trace holds no bench.slice span")
    lo, hi = spans[0].start, spans[0].end
    ops = {c: clip(tr.ops.get(c, []), lo, hi) for c in chips}
    busy = sum(busy_s(ops[c]) for c in chips) / len(chips)
    first = ops[chips[0]]
    mods = [e for e in tr.modules.get(chips[0], [])
            if e.start < hi and e.end > lo]
    kernels: Dict[str, float] = {}
    kinds: Dict[str, str] = {}
    runs: Dict[str, Dict[int, Event]] = {}
    for e, (m, prog, kernel) in zip(sorted(first, key=lambda e: e.start),
                                    assign(first, mods, programs)):
        if kernel:
            kernels[kernel] = kernels.get(kernel, 0.0) + e.dur
            kinds[e.name] = kernel
        if prog and m is not None:
            runs.setdefault(prog, {})[id(m)] = m
    return Slice(hi - lo, busy, kernels,
                 {p: sorted(r.values(), key=lambda e: e.start)
                  for p, r in runs.items()}, first,
                 [h for h in tr.host if lo <= h.start <= hi], lo, hi,
                 kinds)
