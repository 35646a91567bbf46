"""The static bit-budget certifier and repo-rule linter.

Covers, deterministically: the budgets leaf (typed ``BitBudgetError``),
the ``IntRange`` domain and its dyadic transfer functions, the
kernel-contract checker (``check_launch`` / ``require_launch``) against
the kernels' real preconditions, the deliberately-unsafe-spec regression
(a bad constant must be *rejected with a typed, location-bearing
error*), the AST repo-rule linter (RR001-RR004), and a registry-config
certification smoke + the ``CERTIFY.json`` schema gate.  Randomised
soundness properties live in ``test_analysis_props.py``.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.analysis import (BitBudgetError, INT32_MAX, IntRange,
                            KernelContractError, MAX_ROWSUM_LEN, MAX_SQ,
                            check_launch, require_launch, static_check)
from repro.analysis import contracts, interpret, lint, ranges
from repro.core.dyadic import Dyadic, fit_dyadic
from repro.ops.spec import RequantSpec

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


# ---------------------------------------------------------------- budgets --

def test_static_check_passes_through_value():
    assert static_check(123, "x") == 123
    assert static_check(INT32_MAX, "x") == INT32_MAX


def test_bit_budget_error_is_typed_and_located():
    with pytest.raises(BitBudgetError) as ei:
        static_check(INT32_MAX + 1, "ffn accumulator", op="int8_matmul",
                     layer="ffn.down")
    e = ei.value
    assert isinstance(e, ValueError)          # legacy contract
    assert (e.what, e.value) == ("ffn accumulator", INT32_MAX + 1)
    assert e.budget == INT32_MAX
    assert (e.op, e.layer) == ("int8_matmul", "ffn.down")
    assert "int32 overflow in ffn accumulator" in str(e)
    assert "[op=int8_matmul]" in str(e) and "[layer=ffn.down]" in str(e)


def test_non_int32_budget_message():
    with pytest.raises(BitBudgetError, match="budget exceeded"):
        static_check(MAX_ROWSUM_LEN + 1, "softmax row length",
                     budget=MAX_ROWSUM_LEN)


# --------------------------------------------------------------- IntRange --

def test_intrange_properties():
    r = IntRange.symmetric(127)
    assert (r.lo, r.hi, r.qmax, r.bits) == (-127, 127, 127, 8)
    assert r.headroom_bits == 24
    assert IntRange.const(5).qmax == 5
    with pytest.raises(ValueError):
        IntRange(3, 2)


def test_clip_design_grid_vs_container():
    wide = IntRange.symmetric(1 << 20)
    assert ranges.t_clip(wide, 8) == IntRange(-127, 127)
    assert ranges.t_clip(wide, 8, design_grid=False) == IntRange(-128, 127)


def test_rshift_round_int_matches_jax_twin():
    import jax.numpy as jnp
    from repro.core.dyadic import rshift_round
    vals = [-(1 << 30), -12345, -1, 0, 1, 7, 12345, 1 << 30]
    for s in (0, 1, 3, 15):
        got = [ranges.rshift_round_int(v, s) for v in vals]
        ref = rshift_round(jnp.asarray(vals, jnp.int32), s).tolist()
        assert got == ref, (s, got, ref)


def test_t_dyadic_endpoints_are_exact():
    dn = fit_dyadic(0.003, 10_000)
    r = ranges.t_dyadic(IntRange.symmetric(10_000), dn)
    f = lambda v: ranges.rshift_round_int(
        ranges.rshift_round_int(v, dn.pre) * dn.b, dn.c - dn.pre)
    assert (r.lo, r.hi) == (f(-10_000), f(10_000))


# ----------------------------------------------- unsafe-spec regression --

def test_overflowing_requant_spec_rejected_with_location():
    """An intentionally-unsafe constant: a raw per-tensor multiplier with
    no pre-shift against a wide accumulator overflows the int32 staging
    product — certification must refuse it, naming op and layer."""
    bad = Dyadic(b=(1 << 15) - 1, c=20, pre=0, qmax_in=1 << 30)
    spec = RequantSpec.per_tensor(bad, out_bits=8)
    with pytest.raises(BitBudgetError) as ei:
        interpret.check_requant_spec(spec, IntRange.symmetric(1 << 30),
                                     op="int8_matmul", layer="attn.qkv")
    e = ei.value
    assert e.op == "int8_matmul" and e.layer == "attn.qkv"
    assert e.value > INT32_MAX
    assert "[layer=attn.qkv]" in str(e)


def test_safe_requant_spec_accepted():
    dn = fit_dyadic(1e-4, 1 << 22)
    spec = RequantSpec.per_tensor(dn, out_bits=8)
    out = interpret.check_requant_spec(spec, IntRange.symmetric(1 << 22),
                                       op="int8_matmul", layer="x")
    assert -128 <= out.lo <= out.hi <= 127


def test_overflowing_perchannel_spec_rejected():
    spec = RequantSpec.per_channel(c=16, pre=0, out_bits=8)
    with pytest.raises(BitBudgetError, match=r"\[op=int8_matmul\]"):
        interpret.check_requant_spec(spec, IntRange.symmetric(1 << 20),
                                     op="int8_matmul", layer="ffn.up")


# ---------------------------------------------------------- check_launch --

def test_check_launch_ok_and_grid():
    rep = check_launch("int8_matmul", m=256, n=256, k=1024)
    assert rep.ok and rep.fused
    assert rep.grid == (2, 2, 2)
    assert rep.blocks == {"bm": 128, "bn": 128, "bk": 512}
    assert rep.vmem_bytes > 0
    assert require_launch(rep) is rep


def test_check_launch_divisibility_violation():
    rep = check_launch("int8_matmul", m=100, n=30, k=64, bm=128, bn=28)
    assert not rep.ok
    with pytest.raises(KernelContractError) as ei:
        require_launch(rep)
    assert isinstance(ei.value, AssertionError)   # legacy assert contract
    assert ei.value.op == "int8_matmul"
    assert any("divide" in r for r in ei.value.reasons)


def test_check_launch_attention_budget():
    rep = check_launch("int_attention", b=1, sq=128, skv=MAX_ROWSUM_LEN + 1,
                       h=4, hkv=4, d=64)
    assert not rep.ok
    assert any("row-sum int32 budget" in r for r in rep.reasons)
    # the longest row inside the budget passes
    rep = check_launch("int_attention", b=1, sq=128, skv=MAX_ROWSUM_LEN,
                       h=4, hkv=4, d=64)
    assert rep.ok and rep.fused


def test_check_launch_policy_decline_is_not_an_error():
    """Tiny decode shapes: the kernel would accept, the backend falls
    back to the oracle — ok=True, fused=False."""
    rep = check_launch("int_attention", b=1, sq=8, skv=8, h=2, hkv=2, d=64)
    assert rep.ok and not rep.fused
    require_launch(rep)                           # must not raise


def test_check_launch_decode_paged_prefetch():
    rep = check_launch("int_decode_attention", b=3, sq=1, h=4, hkv=2,
                       d=64, max_pages=8, page_size=64)
    assert rep.ok and rep.fused
    assert rep.scalar_prefetch == (("valid_len", (3,)), ("pages", (3, 8)))
    rep = check_launch("int_decode_attention", b=1, sq=MAX_SQ + 1, h=4,
                       hkv=4, d=64, L=512)
    assert not rep.ok and any("Sq <=" in r for r in rep.reasons)


def test_tpu_block_rule_refuses_one_head_kv_layout():
    """Regression: the attention kernels once blocked the cache one head
    at a time, ``(1, bkv, 1, d)`` over ``(..., L, Hkv, d)`` — legal in
    interpret mode, refused by the chip's compiler (a last-two-dims
    block of (1, d) over (Hkv, d)).  The offline rule refuses it too;
    the all-heads block the kernels use now passes."""
    pool = (65, 128, 8, 64)
    old = contracts.check_blocks("int_decode_attention",
                                 {"k": ((1, 128, 1, 64), pool)})
    assert not old.ok
    assert any("last-two-dims" in r and "(1)" in r for r in old.reasons)
    new = contracts.check_blocks("int_decode_attention",
                                 {"k": ((1, 128, 8, 64), pool)})
    assert new.ok
    # the kernels' own launches at Granite widths pass the rule
    rep = check_launch("int_decode_attention", b=8, sq=1, h=32, hkv=8,
                       d=64, max_pages=8, page_size=128, num_pages=65,
                       per_channel=True, fold=True, n_out=2048)
    assert rep.ok and rep.fused and rep.grid == (8, 2, 8)


def test_vmem_budget_refuses_a_fold_too_wide_for_the_chip():
    """Llama-3-8B's folded ``wo`` block (4096 x 4096) ran the v5e
    topology compile out of VMEM: the contract refuses that launch, the
    backend policy unfolds it, and the certifier notes it.  Granite and
    H2O-Danube widths still fold; the unfolded launch fits."""
    from repro.configs.registry import get_config
    kw = dict(b=8, sq=1, h=32, hkv=8, max_pages=8, page_size=128,
              num_pages=65, per_channel=True)
    llama = check_launch("int_decode_attention", d=128, fold=True,
                         n_out=4096, **kw)
    assert not llama.ok and any("VMEM" in r for r in llama.reasons)
    assert check_launch("int_decode_attention", d=128, **kw).ok
    assert check_launch("int_decode_attention", d=120, fold=True,
                        n_out=3840, **kw).ok
    assert not contracts.can_fold_wo(1, 32, 8, 128, 128, 4096)
    assert contracts.can_fold_wo(256, 32, 8, 64, 128, 2048)
    # a matmul whose whole-vocab N block ran out of VMEM is refused too
    assert not check_launch("int8_matmul", m=8, n=65552, k=2048, bm=8,
                            bn=65552, bk=512).ok
    rep = interpret.certify_config(get_config("llama3-8b"), seq_len=512,
                                   cache_len=1024, page_size=128,
                                   chunk=256)
    pre = [o for o in rep.ops if o.layer.startswith("attn.prefill")]
    assert pre and all("wo unfolded" in o.note for o in pre)
    assert not any(o.path.startswith("fallback") for o in pre)


def test_tpu_block_rule_matmul_and_1d_blocks():
    # a 1-d partial block (the old (bn,) bias block) is refused
    assert contracts.tpu_block_violations("bias32", (128,), (2048,))
    assert not contracts.tpu_block_violations("gamma", (768,), (768,))
    # rows not a multiple of 8 (and not the whole dim) are refused
    rep = check_launch("int8_matmul", m=64, n=256, k=256, bm=4, bn=128,
                       bk=256)
    assert not rep.ok and any("multiple of 8" in r for r in rep.reasons)
    # fit_block with alignment picks a legal divisor, else the whole dim
    assert contracts.fit_block(128, 200, 8) == 40
    assert contracts.fit_block(128, 7 * 256, 8) == 128
    assert contracts.fit_block(512, 768, 128) == 384
    assert contracts.fit_block(128, 100, 128) == 100
    assert contracts.fit_block(128, 1000) == 125     # plain divisor


def test_check_launch_unknown_op():
    with pytest.raises(KeyError, match="unknown kernel op"):
        check_launch("int_conv", x=1)


def test_backend_policy_delegates_to_contracts():
    from repro.ops import get_backend
    be = get_backend("pallas_fused")
    cases = [(128, 128, 128, 128), (8, 8, 8, 8),
             (128, MAX_ROWSUM_LEN + 128, 128, 128)]
    for sq, skv, bq, bkv in cases:
        assert be._can_tile(sq, skv, bq, bkv) == \
            contracts.can_tile(sq, skv, bq, bkv)
    assert be._can_tile_decode(1, 256, 64, 128) == \
        contracts.can_tile_decode(1, 256, 64, 128)
    assert be._can_tile_prefill(512, 64, 128, 64) == \
        contracts.can_tile_prefill(512, 64, 128, 64)


def test_kernel_wrapper_raises_contract_error():
    import jax.numpy as jnp
    from repro.kernels.int8_matmul import int8_matmul_pallas
    with pytest.raises(AssertionError, match="launch contract violated"):
        int8_matmul_pallas(jnp.zeros((100, 64), jnp.int8),
                           jnp.zeros((64, 30), jnp.int8),
                           dn=fit_dyadic(0.01, 64 * 127 * 127),
                           bm=128, bn=28)


# ------------------------------------------------------------------ lint --

def test_lint_rr001_kernel_import_scoping():
    src = "from repro.kernels.int8_matmul import int8_matmul_pallas\n"
    bad = lint.lint_source(src, "src/repro/models/model.py")
    assert [f.code for f in bad] == ["RR001"]
    assert "backend registry" in bad[0].message
    # allowed scopes: kernels themselves and the backends
    assert lint.lint_source(src, "src/repro/ops/backends/pallas_fused.py") == []
    assert lint.lint_source(src, "src/repro/kernels/ref.py") == []
    # tests/ and benchmarks/ are out of scope entirely
    assert lint.lint_source(src, "tests/test_kernels.py") == []


def test_lint_rr002_asarray_on_engine_state():
    bad = lint.lint_source("x = jnp.asarray(self.pos)\n",
                           "src/repro/serving/engine.py")
    assert [f.code for f in bad] == ["RR002"]
    assert "snapshot" in bad[0].message
    # snapshotted forms pass (the call result is not an ast.Attribute)
    ok = "a = jnp.asarray(self.pos.copy())\nb = jnp.asarray(t.snapshot())\n"
    assert lint.lint_source(ok, "src/repro/serving/engine.py") == []
    # outside serving/ the rule is silent
    assert lint.lint_source("x = jnp.asarray(self.pos)\n",
                            "src/repro/models/model.py") == []


def test_lint_rr003_float_dtype_in_core():
    bad = lint.lint_source("y = q.astype(jnp.float32)\n",
                           "src/repro/core/norms.py")
    assert [f.code for f in bad] == ["RR003"]
    # the dequant boundary is sanctioned
    assert lint.lint_source("y = q.astype(jnp.float32)\n",
                            "src/repro/core/quant.py") == []


def test_lint_rr004_unpack_above_backend_boundary():
    src = ("from repro.ops import packed\n"
           "w = packed.unpack_weights(qw)\n"
           "p = unpack_kv_pool(pool, shifts)\n")
    bad = lint.lint_source(src, "src/repro/models/intlayers.py")
    assert [f.code for f in bad] == ["RR004", "RR004"]
    bad = lint.lint_source(src, "src/repro/serving/engine.py")
    assert [f.code for f in bad] == ["RR004", "RR004"]
    # the kernel / backend tiers are the sanctioned unpack sites
    assert lint.lint_source(src, "src/repro/kernels/int8_matmul.py") == []
    assert lint.lint_source(
        src, "src/repro/ops/backends/pallas_fused.py") == []
    # packing on write is legal everywhere — the rule is unpack-prefixed
    assert lint.lint_source("k = pack_kv(v8)\n",
                            "src/repro/models/intlayers.py") == []


def test_lint_finding_format_is_location_bearing():
    f = lint.lint_source("import repro.kernels.ref\n",
                         "src/repro/serving/engine.py")[0]
    assert str(f).startswith("src/repro/serving/engine.py:1:0 RR001")


def test_repo_tree_lints_clean():
    assert lint.lint_paths([os.path.join(ROOT, "src", "repro")]) == []


# --------------------------------------------------------------- certify --

def test_certify_config_smoke():
    from repro.configs.registry import ARCHS
    name = sorted(ARCHS)[0]
    rep = interpret.certify_config(ARCHS[name], seq_len=256, cache_len=512)
    assert rep.name == name and rep.ops
    assert 0 < rep.worst_bits <= 32
    assert rep.min_headroom_bits >= 0
    assert rep.n_dyadics > 0
    assert any("qmax_res" in a for a in rep.assumptions)
    layers = {o.layer for o in rep.ops}
    assert "norm" in layers and "head" in layers


def test_certify_all_registry_configs():
    from repro.analysis.certify import certify_all
    report, n_failed = certify_all(seq_len=1024, cache_len=4096)
    assert n_failed == 0, [c.get("error") for c in
                           report["configs"].values() if not c["ok"]]
    assert report["schema"] == "repro/certify-v1"
    assert report["n_configs"] == len(report["configs"]) > 0
    assert report["budgets"]["MAX_ROWSUM_LEN"] == MAX_ROWSUM_LEN


def test_certify_cli_single_arch(tmp_path):
    from repro.analysis.certify import main
    from repro.configs.registry import ARCHS
    out = tmp_path / "CERTIFY.json"
    rc = main(["--arch", sorted(ARCHS)[0], "--seq-len", "256",
               "--cache-len", "512", "--json", str(out)])
    assert rc == 0
    data = json.loads(out.read_text())
    assert data["n_failed"] == 0 and len(data["configs"]) == 1


def test_certify_json_artifact_schema():
    """The committed benchmarks/CERTIFY.json must satisfy the same schema
    gate CI applies via benchmarks/check_bench_json.py."""
    path = os.path.join(ROOT, "benchmarks", "CERTIFY.json")
    assert os.path.exists(path), "run python -m repro.analysis.certify"
    from benchmarks.check_bench_json import check_file
    assert check_file(path) == []


def test_lint_cli_exit_status(tmp_path):
    bad = tmp_path / "src" / "repro" / "core" / "z.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import jnp\ny = jnp.float32\n")
    rc = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", str(bad)],
        capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")})
    assert rc.returncode == 1
    assert "RR003" in rc.stdout
