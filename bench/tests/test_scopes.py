"""Device time by the program's names, on hand-built ops and on the
op_name table of a CPU-compiled encoder."""
import os
import re

import pytest

import devtrace
import harness
import scopes
from scopes import Op

PRE = "jit(<lambda>)/int_prefill/while/body"


def _ops():
    """One layer's worth of ops: launches, glue, a loop, a token draw."""
    return [
        Op(f"{PRE}/norm1/int_norm/pallas_call", "int_norm.1", 0.0, 0.3),
        Op(f"{PRE}/attn/int8_matmul/pallas_call", "int8_matmul.2", 0.3, 0.1),
        Op(f"{PRE}/attn/int_attention_fused/pallas_call",
           "int_attention_fused.3", 0.4, 0.2),
        Op(f"{PRE}/residual/clamp", "fusion.4", 0.6, 0.05),
        Op(f"{PRE}/ffn.act/int_gelu/pallas_call", "int_gelu.5", 0.65, 0.1),
        Op(f"{PRE}/ffn.act/reshape", "reshape.6", 0.75, 0.05),
        Op(f"{PRE}/ffn.down/convert_element_type", "fusion.7", 0.8, 0.02),
        Op(f"{PRE}/dynamic_slice", "fusion.8", 0.82, 0.03),   # entry only
        Op("jit(<lambda>)/int_prefill/while", "while.9", 0.0, 0.9),
        Op("jit(<lambda>)/random_bits", "fusion.10", 0.95, 0.05),
    ]


def test_attribution_by_launch_and_owner():
    att = scopes.attribute(_ops())
    assert att.kernels == pytest.approx({
        "int_norm": 0.3, "int8_matmul": 0.1, "int_attention_fused": 0.2,
        "int_gelu": 0.1})
    assert att.owners == pytest.approx({
        "norm1": 0.3, "attn": 0.3, "residual": 0.05, "ffn.act": 0.15,
        "ffn.down": 0.02, scopes.ENTRY_ONLY: 0.03, scopes.NO_OWNER: 0.05})
    # the loop spans its body: busy, never attributed
    assert sum(att.owners.values()) == pytest.approx(0.9)
    assert att.glue == pytest.approx({
        "residual": 0.05, "ffn.act": 0.05, "ffn.down": 0.02,
        scopes.ENTRY_ONLY: 0.03, scopes.NO_OWNER: 0.05})
    # the program's glue leaves out the benchmark's own token draw
    assert att.glue_s == pytest.approx(0.15)
    assert att.busy_s == pytest.approx(0.95)
    # the loop's tail (0.85-0.9), the entry-only op and the token draw
    assert att.unassigned_s == pytest.approx(0.03 + 0.05 + 0.05)
    assert att.named


@pytest.mark.parametrize("path,kernel,owner", [
    (f"{PRE}/norm2/int_norm/pallas_call", "int_norm", "norm2"),
    # the innermost scope wins: an MoE's shared expert
    (f"{PRE}/moe/ffn.act/int_gelu/pallas_call", "int_gelu", "ffn.act"),
    # interpret mode repeats the path inside a cond branch
    (f"{PRE}/head/int8_matmul/while/body/cond/{PRE}/head/int8_matmul/"
     "while/body/cond/branch_1_fun/add", "int8_matmul", "head"),
    ("jit(decode_step)/int_decode_step/while/body/attn/"
     "int_decode_attention/pallas_call", "int_decode_attention", "attn"),
    # names match whole components only
    (f"{PRE}/norm1x/jit(int_norm)/add", None, scopes.ENTRY_ONLY),
    ("jit(<lambda>)/while/body/closed_call", None, scopes.NO_OWNER),
    ("", None, scopes.NO_OWNER)])
def test_kernel_and_innermost_scope(path, kernel, owner):
    assert scopes.kernel_of(path) == kernel
    assert scopes.owner_of(path) == owner


class _Slice:
    lo, hi = 0.0, 1.0
    kernels = {"int_norm": 0.3, "int8_matmul": 0.1, "attention": 0.2,
               "int_gelu": 0.1}

    def count(self, program):
        return 2.5 if program == "encode" else 0


def _reader(metric):
    return harness.load_module(os.path.join(
        harness.BENCH, "metrics", metric + ".py")).read


def test_readers_per_call_and_once_per_run(monkeypatch, capsys):
    loads = []

    def load(path, lo, hi):
        loads.append(path)
        return _ops()
    monkeypatch.setattr(scopes, "newest_xplane", lambda root: "trace.pb")
    monkeypatch.setattr(scopes, "load", load)
    run = {"slice": _Slice()}
    assert _reader("norm_ms.encode")(run) == pytest.approx(1e3 * 0.3 / 2.5)
    assert _reader("gelu_ms.encode")(run) == pytest.approx(1e3 * 0.15 / 2.5)
    assert _reader("glue_ms.encode")(run) == pytest.approx(1e3 * 0.15 / 2.5)
    assert loads == ["trace.pb"]
    err = capsys.readouterr().err
    assert re.search(r"cross-check int_norm: 0\.300000 s by name, "
                     r"0\.300000 s by signature \(int_norm\), 0\.000% apart",
                     err)
    assert "cross-check int_attention_fused: 0.200000 s by name" in err
    assert "unassigned: 0.130000 s of 0.950000 s busy (13.684%)" in err


def test_readers_return_none_without_names(monkeypatch):
    """A program that names nothing, or a run without a trace."""
    unnamed = [Op("jit(<lambda>)/while/body/closed_call", "closed_call.31",
                  0.0, 0.5), Op("jit(<lambda>)/while/body/add", "fusion.2",
                                0.5, 0.1)]
    monkeypatch.setattr(scopes, "newest_xplane", lambda root: "trace.pb")
    monkeypatch.setattr(scopes, "load", lambda path, lo, hi: unnamed)
    for metric in ("norm_ms.encode", "gelu_ms.encode", "glue_ms.encode"):
        assert _reader(metric)({"slice": _Slice()}) is None
        assert _reader(metric)({}) is None


def _cpu_trace(path):
    """Record a CPU trace holding one ``bench.slice`` span."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(path))
    with jax.profiler.TraceAnnotation(devtrace.SLICE):
        jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    tr = devtrace.load(str(path))
    (span,) = [h for h in tr.host if h.name == devtrace.SLICE]
    return scopes.newest_xplane(str(path)), span


def test_a_trace_of_another_slice_is_an_error(tmp_path):
    older, _ = _cpu_trace(tmp_path / "a")
    newest, span = _cpu_trace(tmp_path / "b")
    assert scopes.newest_xplane(str(tmp_path)) == newest != older
    with pytest.raises(RuntimeError, match="not the traced slice's trace"):
        scopes.load(newest, span.start + 0.5, span.end + 0.5)
    with pytest.raises(RuntimeError, match="not the traced slice's trace"):
        scopes.load(older, span.start, span.end)
    # the right file passes the check; a CPU trace has no chip to read
    with pytest.raises(RuntimeError, match="holds no device ops"):
        scopes.load(newest, span.start, span.end)


def _xplane(path, slice_ps=(1_000, 8_000_000)):
    """An xplane file with one chip's three ops and a host slice span,
    built from the module's own schema, laid out as the TPU profiler
    lays them out: the path is a stat of the event's metadata, plain
    (``str_value``) or interned (``ref_value``)."""
    space = scopes.xspace_class()()
    chip = space.planes.add(name="/device:TPU:0")
    for key, name in [(1, "tf_op"), (2, "flops"),
                      (3, "jit(f)/int_prefill/while/body/attn/reshape;"
                          "attn/reshape:")]:
        chip.stat_metadata.add(key=key).value.name = name
    for key, name, stat in [
            (10, "%int_norm.3 = s32[8,768]{1,0} custom-call(...)",
             dict(str_value=f"{PRE}/norm1/int_norm/pallas_call:")),
            (11, "%reshape.4 = s8[8,768]{1,0} reshape(...)",
             dict(ref_value=3)),
            (12, "%copy-done.1 = s8[8]{0} copy-done(...)", None)]:
        meta = chip.event_metadata.add(key=key).value
        meta.name = name
        meta.stats.add(metadata_id=2, str_value="0")
        if stat:
            meta.stats.add(metadata_id=1, **stat)
    line = chip.lines.add(name="XLA Ops", timestamp_ns=1_000)
    for key, offset_ps, duration_ps in [(10, 0, 2_000_000_700),
                                        (11, 2_500_000_999, 1_000_000_000),
                                        (12, 6_000_000_000, 4_000_000_000)]:
        line.events.add(metadata_id=key, offset_ps=offset_ps,
                        duration_ps=duration_ps)
    host = space.planes.add(name="/host:CPU")
    host.event_metadata.add(key=1).value.name = devtrace.SLICE
    host.lines.add(name="python", timestamp_ns=0).events.add(
        metadata_id=1, offset_ps=slice_ps[0] * 1000,
        duration_ps=slice_ps[1] * 1000)
    path.write_bytes(space.SerializeToString())
    return str(path)


def test_paths_and_times_from_the_xplane(tmp_path):
    path = _xplane(tmp_path / "t.xplane.pb")
    ops = scopes.load(path, 1e-6, 8.001e-3)
    assert [(o.name, o.path) for o in ops] == [
        ("int_norm.3", f"{PRE}/norm1/int_norm/pallas_call"),
        ("reshape.4", "jit(f)/int_prefill/while/body/attn/reshape"),
        ("copy-done.1", "")]
    # whole nanoseconds from the line's start, clipped to the slice
    assert [o.start for o in ops] == pytest.approx([1e-6, 2.501e-3,
                                                    6.001e-3], abs=1e-12)
    assert [o.dur for o in ops] == pytest.approx([2e-3, 1e-3, 2e-3],
                                                 abs=1e-12)
    with pytest.raises(RuntimeError, match="not the traced slice's trace"):
        scopes.load(path, 1e-6, 9e-3)


def test_cpu_compiled_encoder_is_attributed_by_name():
    """The op_name table of a tiny ``int_prefill`` (pallas_fused, Pallas
    in interpret mode) through the same assignment, one op per
    instruction."""
    import jax
    import jax.numpy as jnp
    from repro.configs.registry import get_config
    from repro.models import inttransformer as it
    from repro.models import model as M
    from repro.models import transformer as tf
    from repro.quant import convert
    cfg = M.reduce_config(get_config("roberta-base"), dtype="float32")
    qp, plans = convert.quantize_params(
        tf.init_params(jax.random.key(0), cfg), cfg)
    text = jax.jit(lambda q, t: it.int_prefill(
        q, {"tokens": t}, plans, cfg, ops="pallas_fused")).lower(
        qp, jax.ShapeDtypeStruct((2, 16), jnp.int32)).compile().as_text()
    # every instruction but the parameters, which never run
    table = re.findall(
        r'%([\w.\-]+) = \S+ (?!parameter\()[^\n]*?op_name="([^"]*)"', text)
    ops = [Op(path, name, float(i), 1.0)
           for i, (name, path) in enumerate(table)]
    att = scopes.attribute(ops)
    assert set(att.kernels) == {"int8_matmul", "int_norm", "int_gelu",
                                "int_attention_fused"}
    assert {"embed", "norm1", "attn", "residual", "norm2", "ffn.up",
            "ffn.act", "ffn.down", "final_norm", "head"} <= set(att.owners)
    # every op lies under the entry point's scope at least
    assert scopes.NO_OWNER not in att.owners
    assert att.glue_s > 0
