"""Device time by the program's own names: kernel launches and sublayers.

The program passes each Pallas launch a stable name
(``pl.pallas_call(name=...)``) and runs each sublayer inside a
``jax.named_scope``; the compiler writes both into every device op's
``op_name``, e.g. ``jit(<lambda>)/int_prefill/while/body/norm1/int_norm/
pallas_call``, and the profiler keeps that path in the ``tf_op`` stat
of the op's event metadata on the ``XLA Ops`` line of
``/device:TPU:<n>`` (as ``<path>:<type>``).  This module
re-opens the traced slice's xplane, reads each op's path, and assigns
the op to the launch name in the path (if any) and to its innermost
sublayer scope.  Paths are read by component, never whole: layers run
under ``lax.scan``, so paths hold ``while/body``.

A program without names gives no launch, and the readers built on this
module then return None.  Everything but :func:`load` is arithmetic on
(path, instruction, start, duration) records, so the CPU self-tests
check it on hand-built ones.
"""
from __future__ import annotations

import functools
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import devtrace
import harness

#: the program's launch names, sublayer scopes and entry points
#: (``src/repro/trace_names.py``); copied, so that a checkout whose
#: program has none still runs this module
KERNELS = ("int8_matmul", "int_norm", "int_gelu", "int_softmax",
           "int_attention", "int_attention_fused",
           "int_paged_prefill_fused", "int_decode_attention")
SCOPES = ("embed", "norm1", "norm2", "norm_cross", "final_norm",
          "enc_final_norm", "attn", "cross_attn", "ssm", "residual",
          "ffn.up", "ffn.act", "ffn.down", "moe", "head")
ENTRY_POINTS = ("int_prefill", "int_decode_step", "int_prefill_chunk_step",
                "int_verify_step")

#: where ``bench/drivers/`` write their traces
TRACE_ROOT = os.path.join(harness.OUT, "trace")

#: the xplane stat that holds a device op's ``op_name`` path
PATH_STAT = "tf_op"

#: an op under an entry point and no sublayer, and an op under neither
ENTRY_ONLY, NO_OWNER = "(entry)", "(none)"

#: launch name -> devtrace's signature-based kind, for the cross-check
SIGNATURE_KIND = {"int8_matmul": "int8_matmul",
                  "int_attention_fused": "attention",
                  "int_norm": "int_norm", "int_gelu": "int_gelu"}


@dataclass
class Op:
    """One device op in the slice: its path, instruction and interval."""
    path: str
    name: str
    start: float        # seconds
    dur: float          # seconds

    @property
    def end(self) -> float:
        return self.start + self.dur


def kernel_of(path: str) -> Optional[str]:
    """The launch name in ``path``, innermost first."""
    for c in reversed(path.split("/")):
        if c in KERNELS:
            return c
    return None


def owner_of(path: str) -> str:
    """The innermost sublayer scope of ``path``; :data:`ENTRY_ONLY` for an
    op under an entry point alone, :data:`NO_OWNER` for one under
    neither."""
    comps = path.split("/")
    for c in reversed(comps):
        if c in SCOPES:
            return c
    return ENTRY_ONLY if any(c in ENTRY_POINTS for c in comps) \
        else NO_OWNER


@dataclass
class Attribution:
    """Device seconds of one slice by launch name and by owner.

    ``kernels``: by launch name; ``owners``: every op by its owner
    (:func:`owner_of`); ``glue``: the ops outside any launch, by owner;
    ``busy_s``: union of every op's interval; ``unassigned_s``: the part
    of ``busy_s`` that no op with a launch name or a sublayer scope
    covers.  Control-flow ops, which span their bodies, are counted in
    ``busy_s`` only."""
    kernels: Dict[str, float] = field(default_factory=dict)
    owners: Dict[str, float] = field(default_factory=dict)
    glue: Dict[str, float] = field(default_factory=dict)
    busy_s: float = 0.0
    unassigned_s: float = 0.0

    @property
    def named(self) -> bool:
        return bool(self.kernels)

    @property
    def glue_s(self) -> float:
        """Device seconds of the program's ops outside any launch."""
        return sum(v for k, v in self.glue.items() if k != NO_OWNER)


def _add(d: Dict[str, float], k: str, v: float):
    d[k] = d.get(k, 0.0) + v


def attribute(ops: Sequence[Op]) -> Attribution:
    """Assign every op to its launch name and owner."""
    out = Attribution(busy_s=devtrace.busy_s(ops))
    assigned = []
    for op in ops:
        if devtrace.CONTAINERS.match(op.name):
            continue
        kernel, owner = kernel_of(op.path), owner_of(op.path)
        _add(out.owners, owner, op.dur)
        if kernel:
            _add(out.kernels, kernel, op.dur)
        else:
            _add(out.glue, owner, op.dur)
        if kernel or owner in SCOPES:
            assigned.append(op)
    out.unassigned_s = out.busy_s - devtrace.busy_s(assigned)
    return out


# ---------------------------------------------------------------- trace --

def newest_xplane(root: str) -> str:
    paths = glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"no trace under {root}")
    return max(paths, key=os.path.getmtime)


#: the parts of XLA's ``tsl/profiler/protobuf/xplane.proto`` read here;
#: a map field is read in its wire form, a repeated (key, value) entry
XPLANE_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, "string"), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True)],
    "XLine": [("name", 2, "string"), ("timestamp_ns", 3, "int64"),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
               ("duration_ps", 3, "int64")],
    "XStat": [("metadata_id", 1, "int64"), ("str_value", 5, "string"),
              ("ref_value", 7, "uint64")],
    "XEventMetadata": [("name", 2, "string"), ("stats", 5, "XStat", True)],
    "XStatMetadata": [("name", 2, "string")],
    "EventMetadataEntry": [("key", 1, "int64"),
                           ("value", 2, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "int64"), ("value", 2, "XStatMetadata")],
}


@functools.lru_cache(maxsize=None)
def xspace_class():
    """The message class of an ``.xplane.pb`` file.  ``jax.profiler.
    ProfileData`` gives each event's own stats only; a device op's
    ``tf_op`` is a stat of the event's metadata, so the file is read
    with ``google.protobuf`` from :data:`XPLANE_SCHEMA`."""
    from google.protobuf import descriptor_pb2, descriptor_pool, \
        message_factory
    F = descriptor_pb2.FieldDescriptorProto
    scalar = {"string": F.TYPE_STRING, "int64": F.TYPE_INT64,
              "uint64": F.TYPE_UINT64}
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    for name, fields in XPLANE_SCHEMA.items():
        msg = fdp.message_type.add(name=name)
        for fname, number, kind, *repeated in fields:
            f = msg.field.add(name=fname, number=number, label=(
                F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL))
            if kind in scalar:
                f.type = scalar[kind]
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, ".bench_xplane." + kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _events(plane, line):
    """(metadata, start s, duration s) of each event of ``line``, on
    ``jax.profiler.ProfileData``'s whole-nanosecond clock (devtrace's)."""
    meta = {m.key: m.value for m in plane.event_metadata}
    return [(meta[e.metadata_id], (line.timestamp_ns + e.offset_ps // 1000)
             * 1e-9, (e.duration_ps // 1000) * 1e-9) for e in line.events]


def path_of(meta, stat_names: Dict[int, str]) -> str:
    """The first ``op_name`` path of an op's :data:`PATH_STAT`
    (``<path>[;<path>...]:<type>``), or ``""``."""
    for st in meta.stats:
        if stat_names.get(st.metadata_id) == PATH_STAT:
            value = st.str_value or stat_names.get(st.ref_value, "")
            return value.rsplit(":", 1)[0].split(";")[0]
    return ""


def load(path: str, lo: float, hi: float) -> List[Op]:
    """The ops of the first chip in ``path``, clipped to [lo, hi], which
    must be the file's own ``bench.slice`` span: a trace of another run
    is an error, not a reading."""
    with open(path, "rb") as f:
        space = xspace_class().FromString(f.read())
    spans, chips = [], {}
    for plane in space.planes:
        chip = re.match(r"/device:TPU:(\d+)$", plane.name)
        for line in plane.lines:
            if chip and line.name == "XLA Ops":
                chips[int(chip.group(1))] = (plane, line)
            elif not chip and plane.name.startswith("/host"):
                spans += [(s, d) for m, s, d in _events(plane, line)
                          if m.name == devtrace.SLICE]
    if len(spans) != 1 or abs(spans[0][0] - lo) > 1e-6 \
            or abs(spans[0][0] + spans[0][1] - hi) > 1e-6:
        raise RuntimeError(f"{path} is not the traced slice's trace: its "
                           f"{devtrace.SLICE} spans {spans}, the slice is "
                           f"({lo}, {hi - lo})")
    if not chips:
        raise RuntimeError(f"{path} holds no device ops")
    plane, line = chips[min(chips)]
    stat_names = {m.key: m.value.name for m in plane.stat_metadata}
    ops = []
    for meta, start, dur in _events(plane, line):
        s, t = max(start, lo), min(start + dur, hi)
        if t > s:
            ops.append(Op(path_of(meta, stat_names),
                          devtrace.op_name(meta.name), s, t - s))
    return ops


# ---------------------------------------------------------------- runs --

def of_run(run: dict) -> Optional[Attribution]:
    """The attribution of the run's traced slice, computed once per run
    from the newest trace under :data:`TRACE_ROOT`; None without a
    slice.  The first call prints the glue by owner and the cross-check
    against devtrace's signature-based kernels on standard error."""
    if "scopes" not in run:
        sl = run.get("slice")
        att = None
        if sl is not None:
            att = attribute(load(newest_xplane(TRACE_ROOT), sl.lo, sl.hi))
            if att.named:
                report(att, sl.kernels)
        run["scopes"] = att
    return run["scopes"]


def report(att: Attribution, by_signature: Dict[str, float]):
    harness.log("glue by owner (s): " + ", ".join(
        f"{k} {v:.6f}" for k, v in sorted(att.glue.items(),
                                           key=lambda kv: -kv[1])))
    for name, kind in SIGNATURE_KIND.items():
        a, b = att.kernels.get(name, 0.0), by_signature.get(kind, 0.0)
        rel = abs(a - b) / b if b else float("nan")
        harness.log(f"cross-check {name}: {a:.6f} s by name, {b:.6f} s "
                    f"by signature ({kind}), {100 * rel:.3f}% apart")
    harness.log(f"unassigned: {att.unassigned_s:.6f} s of {att.busy_s:.6f}"
                f" s busy ({100 * att.unassigned_s / att.busy_s:.3f}%) "
                "under no launch name and no sublayer scope")


def per_call_ms(run: dict, seconds: Callable[[Attribution], float]
                ) -> Optional[float]:
    """``seconds`` of the slice's attribution per encoder call in the
    slice (ms); None where the trace holds no named launch."""
    att = of_run(run)
    n = run["slice"].count("encode") if att is not None else 0
    if att is None or not att.named or n <= 0:
        return None
    return 1e3 * seconds(att) / n
