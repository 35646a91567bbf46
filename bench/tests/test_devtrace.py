"""The trace reduction on a hand-built trace."""
import pytest

import devtrace
from devtrace import Event, Program, Trace

#: two programs that share an instruction name, as jitted steps do
PROGS = [Program("a", {"_mm.1": "int8_matmul", "_at.2": "attention"},
                 frozenset({"_mm.1", "_mm.2", "_at.2", "fusion.3",
                            "while.7"})),
         Program("b", {"_mm.1": "int_norm"},
                 frozenset({"_mm.1", "copy.1"}))]


def _trace():
    ops = [Event("_mm.1", 1.0, 0.2),                  # 1.0 - 1.2
           Event("_mm.1", 1.1, 0.2),                  # overlaps: to 1.3
           Event("_at.2", 1.5, 0.1),                  # 1.5 - 1.6
           Event("_mm.1", 1.7, 0.05),                 # program b's
           Event("copy.1", 1.75, 0.05),
           Event("while.7", 1.0, 0.3),                # spans its body
           Event("fusion.3", 1.9, 0.3),               # runs past the slice
           Event("_mm.1", 3.0, 0.1)]                  # after the slice
    mods = [Event("jit_step(1)", 1.0, 0.65), Event("jit_step(1)", 1.7, 0.1),
            Event("jit_step(2)", 1.85, 0.3)]
    host = [Event(devtrace.SLICE, 1.0, 1.0),          # slice: 1.0 - 2.0
            Event("bench.wait", 1.2, 0.4),
            Event("bench.dispatch", 1.6, 0.3),
            Event("bench.other", 2.5, 0.1)]
    return Trace(ops={0: ops}, modules={0: mods}, host=host)


def test_busy_union_idle_and_kernels():
    sl = devtrace.reduce(_trace(), [0], PROGS)
    assert sl.window_s == pytest.approx(1.0)
    # 1.0-1.3, 1.5-1.6, 1.7-1.8, 1.9-2.0 (clipped at the slice's end)
    assert sl.busy_s == pytest.approx(0.6)
    assert sl.idle_share == pytest.approx(0.4)
    assert sl.kernels["int8_matmul"] == pytest.approx(0.4)
    assert sl.kernels["attention"] == pytest.approx(0.1)
    # the same name in program b is another kernel
    assert sl.kernels["int_norm"] == pytest.approx(0.05)


def test_executions_are_told_by_their_instruction_names():
    sl = devtrace.reduce(_trace(), [0], PROGS)
    # jit_step(2) (1.85-2.15) runs fusion.3 of program a, half inside
    assert [m.start for m in sl.executions("a")] == [1.0, 1.85]
    assert sl.count("a") == pytest.approx(1.5)
    assert [m.start for m in sl.executions("b")] == [1.7]
    assert sl.count("b") == pytest.approx(1.0)
    assert sl.executions("c") == [] and sl.count("c") == 0


def test_idle_gaps_by_host_span():
    sl = devtrace.reduce(_trace(), [0], PROGS)
    b = sl.breakdown()
    idle = dict(b["idle_gaps"])
    # gap 1.3-1.5 (mid 1.4: bench.wait), gaps 1.6-1.7 and 1.8-1.9 (mids
    # 1.65 and 1.85: bench.dispatch)
    assert idle["bench.wait"] == pytest.approx(0.2)
    assert idle["bench.dispatch"] == pytest.approx(0.2)
    top = dict(b["device_ops"])
    assert sum(v for k, v in top.items() if k.startswith("_mm.1")) == \
        pytest.approx(0.45)
    assert "_at.2 (attention)" in top
    assert not any(k.startswith("while") for k in top)


def test_op_names_from_hlo_text():
    assert devtrace.op_name("%closed_call.35 = s8[64,12]{1,0} custom-call("
                            "s8[64] %a), x=1") == "closed_call.35"
    assert devtrace.op_name("jit_step(123)") == "jit_step(123)"


def test_union_and_gaps():
    evs = [Event("a", 0, 2), Event("b", 1, 2), Event("c", 5, 1)]
    assert devtrace.union(evs) == [(0, 3), (5, 6)]
    assert devtrace.busy_s(evs) == 4
    assert devtrace.gaps(evs, -1, 7) == [(-1, 0), (3, 5), (6, 7)]


def test_a_missing_slice_is_an_error():
    tr = _trace()
    tr.host = [h for h in tr.host if h.name != devtrace.SLICE]
    with pytest.raises(RuntimeError):
        devtrace.reduce(tr, [0], PROGS)


def test_kernel_table_from_compiled_text():
    hlo = "\n".join([
        '  %closed_call.3 = s8[64,768]{1,0} custom-call(%a, %b, %c), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{s8[64,768]{1,0}, s8[768,768]{1,0}, s32[1,768]{1,0}}, x=1',
        '  %closed_call.4 = s8[2,12,8,64]{3,2,1,0} custom-call(%q, %k, %v), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{s8[2,8,12,64]{3,2,1,0}, s8[2,8,12,64]{3,2,1,0}, '
        's8[2,8,12,64]{3,2,1,0}}, x=1',
        '  %step.1 = s32[16,1,2048]{2,1,0} custom-call(%a), custom_call_'
        'target="tpu_custom_call", operand_layout_constraints={s32[16]{0}, '
        's32[16,32]{1,0}, s8[16,1,32,64]{3,2,1,0}, s8[9,128,8,64]{3,2,1,0}'
        ', s8[9,128,8,64]{3,2,1,0}, s8[2048,2048]{1,0}}, x=1',
        '  %fusion.5 = s32[4] fusion(%a), kind=kLoop'])
    p = Program.from_hlo("x", hlo)
    assert p.table == {"closed_call.3": "int8_matmul",
                       "closed_call.4": "attention",
                       "step.1": "decode_attention"}
    assert "fusion.5" in p.names
