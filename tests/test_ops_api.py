"""The unified operator API: RequantSpec forms, backend registry dispatch,
ref<->pallas_fused parity across the ops, and the removed deprecation
shims."""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro import ops
from repro.core import attention as iattn
from repro.core import intmath, norms
from repro.core.dyadic import fit_dyadic
from repro.ops import (OpSet, QuantLinearParams, RequantSpec, get_backend,
                       register_backend, resolve_ops, unregister_backend,
                       use_backend)


# ------------------------------------------------------ RequantSpec -------

def test_requant_spec_forms():
    dn = fit_dyadic(1 / 100.0, 2 ** 20)
    pt = RequantSpec.per_tensor(dn, out_bits=8)
    assert pt.kind == ops.PER_TENSOR and pt.dn is dn
    assert pt.out_dtype == jnp.int8
    pc = RequantSpec.per_channel(c=28, pre=7, out_bits=11)
    assert pc.kind == ops.PER_CHANNEL and (pc.c, pc.pre) == (28, 7)
    assert pc.out_dtype == jnp.int32
    raw = RequantSpec.raw()
    assert raw.is_raw and raw.out_bits == 32


def test_requant_spec_validation():
    dn = fit_dyadic(1 / 100.0, 2 ** 20)
    with pytest.raises(ValueError):
        RequantSpec("per_tensor", 8)               # missing Dyadic
    with pytest.raises(ValueError):
        RequantSpec("per_channel", 8, dn=dn)       # Dyadic on per-channel
    with pytest.raises(ValueError):
        RequantSpec.per_channel(c=4, pre=9)        # pre > c
    with pytest.raises(ValueError):
        RequantSpec("raw", 8)                      # raw must be 32-bit
    with pytest.raises(ValueError):
        RequantSpec("volumetric", 8)               # unknown kind


def test_requant_spec_for_linear():
    from repro.quant.plans import make_linear_plan
    plan = make_linear_plan(8 / 127, 2 / 127, 8 / 127, 256)
    spec = RequantSpec.for_linear(plan)
    assert spec.kind == ops.PER_CHANNEL
    assert (spec.c, spec.pre, spec.out_bits) == (plan.c, plan.pre,
                                                 plan.out_bits)
    raw_plan = make_linear_plan(8 / 127, 2 / 127, 0.0, 256)
    assert RequantSpec.for_linear(raw_plan).is_raw


def test_quant_linear_params_of():
    qw = QuantLinearParams.of({"w8": 1, "b_mult": 2})
    assert (qw.w8, qw.b_mult, qw.bias32) == (1, 2, None)
    assert QuantLinearParams.of(qw) is qw
    with pytest.raises(TypeError):
        QuantLinearParams.of([1, 2])


# ------------------------------------------------- registry dispatch ------

class _Recorder:
    """Delegating backend that counts dispatched ops."""

    fused_attention = False

    def __init__(self, inner, name="recorder"):
        self._inner = inner
        self.name = name
        self.calls = []

    def __getattr__(self, op):
        inner_fn = getattr(self._inner, op)
        if op in ops.OP_NAMES:
            def wrapper(*a, **kw):
                self.calls.append(op)
                return inner_fn(*a, **kw)
            return wrapper
        return inner_fn


@pytest.fixture
def recorder():
    rec = _Recorder(get_backend("ref"))
    register_backend("recorder", rec, overwrite=True)
    yield rec
    unregister_backend("recorder")


def _tiny_matmul(opset):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(-127, 128, (8, 32)), jnp.int8)
    w = jnp.asarray(rng.integers(-127, 128, (32, 16)), jnp.int8)
    dn = fit_dyadic(1 / 4000.0, 32 * 127 * 127)
    return opset.int8_matmul(x, w, RequantSpec.per_tensor(dn))


def test_use_backend_context_changes_dispatch(recorder, monkeypatch):
    monkeypatch.delenv(ops.ENV_VAR, raising=False)
    assert resolve_ops(None).name == "ref"
    with use_backend("recorder"):
        _tiny_matmul(resolve_ops(None))
    assert recorder.calls == ["int8_matmul"]
    # context popped: default again
    assert resolve_ops(None).name == "ref"


def test_env_override_changes_dispatch(recorder, monkeypatch):
    monkeypatch.setenv(ops.ENV_VAR, "recorder")
    _tiny_matmul(resolve_ops(None))
    assert recorder.calls == ["int8_matmul"]
    # explicit argument and context both beat the env
    assert resolve_ops("ref").name == "ref"
    with use_backend("ref"):
        assert resolve_ops(None).name == "ref"


def test_per_op_override_routes_single_op(recorder):
    opset = OpSet("ref", {"int_gelu": "recorder"})
    _tiny_matmul(opset)                      # default backend
    plan = intmath.make_igelu(16 / 1024, 1024)
    dn = fit_dyadic(plan.s_out / (8 / 127), 1024 * 2 * plan.q_one)
    opset.int_gelu(jnp.arange(-32, 32, dtype=jnp.int32), plan, dn)
    assert recorder.calls == ["int_gelu"]    # matmul did NOT go through
    assert opset.name == "ref[int_gelu=recorder]"


def test_resolve_ops_cfg_and_errors(monkeypatch):
    monkeypatch.delenv(ops.ENV_VAR, raising=False)
    from repro.configs.registry import get_config
    from repro.models import model as M
    cfg = M.reduce_config(get_config("llama3-8b"), dtype="float32",
                          kernel_backend="pallas_fused")
    assert resolve_ops(None, cfg).name == "pallas_fused"
    with pytest.raises(KeyError):
        get_backend("no-such-backend")
    with pytest.raises(KeyError):
        OpSet("ref", {"int_conv": "ref"})    # unknown op name


def test_register_backend_class_as_factory():
    """A registered class is a factory: instantiated once, not called
    with misbound self."""
    from repro.ops.backends.ref import RefBackend

    class MyBackend(RefBackend):
        name = "my_class_backend"

    register_backend("my_class_backend", MyBackend, overwrite=True)
    try:
        be = get_backend("my_class_backend")
        assert isinstance(be, MyBackend)
        _tiny_matmul(resolve_ops("my_class_backend"))   # self bound right
    finally:
        unregister_backend("my_class_backend")


def test_fuse_attention_false_uses_exact_oracle(rng):
    """fuse_attention=False must not re-enter a fused backend — it asks
    for the exact two-pass numerics."""
    import jax
    from repro.configs.registry import get_config
    from repro.models import intlayers as il
    from repro.models import model as M
    from repro.models import transformer as tf
    from repro.quant import convert

    cfg = M.reduce_config(get_config("llama3-8b"), dtype="float32",
                          vocab=64, num_layers=1)
    params = tf.init_params(jax.random.key(0), cfg)
    qp, plans = convert.quantize_params(params, cfg)
    attn_qp = jax.tree.map(lambda t: t[0], params["layers"][0])["attn"]
    attn_qp = convert._q_attn(attn_qp, plans.attn)
    x8 = jnp.asarray(rng.integers(-127, 128, (1, 16, cfg.d_model)),
                     jnp.int8)
    unfused = il.int_attn_fwd(attn_qp, x8, plans.attn, cfg,
                              ops="pallas_fused", fuse_attention=False)
    exact = il.int_attn_fwd(attn_qp, x8, plans.attn, cfg, ops="ref")
    assert np.array_equal(np.asarray(unfused), np.asarray(exact))


# -------------------------------------- ref<->pallas_fused parity ---------

@pytest.mark.parametrize("form", ["per_tensor", "per_channel", "raw"])
def test_matmul_parity_all_requant_forms(rng, form):
    m, k, n = 64, 256, 128
    x = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    w = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    bias = jnp.asarray(rng.integers(-2 ** 16, 2 ** 16, (n,)), jnp.int32)
    b_vec = None
    if form == "per_tensor":
        spec = RequantSpec.per_tensor(
            fit_dyadic(1 / 4000.0, k * 127 * 127 + 2 ** 16))
    elif form == "per_channel":
        spec = RequantSpec.per_channel(c=28, pre=7)
        b_vec = jnp.asarray(rng.integers(1000, 30000, (n,)), jnp.int32)
    else:
        spec = RequantSpec.raw()
    got = {}
    for name in ("ref", "pallas_fused"):
        got[name] = np.asarray(resolve_ops(name).int8_matmul(
            x, w, spec, bias32=bias, b_vec=b_vec))
    assert np.array_equal(got["ref"], got["pallas_fused"])
    if form == "raw":
        assert got["pallas_fused"].dtype == np.int32
        # raw == plain int32 accumulator + bias
        acc = np.asarray(x, np.int64) @ np.asarray(w, np.int64) \
            + np.asarray(bias)[None, :]
        assert np.array_equal(got["ref"], acc)


def test_all_five_ops_parity_through_registry(rng):
    """The prefill ops of the Backend protocol, ref vs pallas_fused via
    the registry: bit-identical."""
    ref, pall = resolve_ops("ref"), resolve_ops("pallas_fused")

    gplan = intmath.make_igelu(16 / 1024, 1024)
    gdn = fit_dyadic(gplan.s_out / (8 / 127), 1024 * 2 * gplan.q_one)
    q = jnp.asarray(rng.integers(-1024, 1025, (4, 512)), jnp.int32)
    assert np.array_equal(ref.int_gelu(q, gplan, gdn),
                          pall.int_gelu(q, gplan, gdn))

    d = 512
    nplan = norms.make_inorm(d, 8 / 1024, 1024, 2 / 127, 8 / 127)
    qg, _ = norms.quantize_norm_weights(
        jnp.ones((d,), jnp.float32), None, nplan)
    qn = jnp.asarray(rng.integers(-1024, 1025, (8, d)), jnp.int32)
    assert np.array_equal(ref.int_layernorm(qn, qg, None, nplan),
                          pall.int_layernorm(qn, qg, None, nplan))

    plan = iattn.make_iattention(64, 8 / 127, 8 / 127, 4 / 127, 4 / 127)
    q8 = jnp.asarray(np.clip(rng.normal(0, 40, (1, 128, 4, 64)), -127,
                             127), jnp.int8)
    k8 = jnp.asarray(np.clip(rng.normal(0, 40, (1, 128, 2, 64)), -127,
                             127), jnp.int8)
    a_ref = np.asarray(ref.int_attention(q8, k8, k8, plan))
    a_pl = np.asarray(pall.int_attention(q8, k8, k8, plan, bq=64, bkv=64))
    assert np.array_equal(a_ref, a_pl)

    mm = _tiny_matmul(ref), _tiny_matmul(pall)
    assert np.array_equal(np.asarray(mm[0]), np.asarray(mm[1]))


def test_explicit_matmul_blocks_parity(rng):
    """Blocks named by the call win over the shape rule: fitted to
    chip-legal divisors of odd shapes, bit-identical to ref."""
    x = jnp.asarray(rng.integers(-127, 128, (96, 192)), jnp.int8)   # odd
    w = jnp.asarray(rng.integers(-127, 128, (192, 48)), jnp.int8)   # shapes
    spec = RequantSpec.per_channel(c=28, pre=7)
    bv = jnp.asarray(rng.integers(1000, 30000, (48,)), jnp.int32)
    a = resolve_ops("ref").int8_matmul(x, w, spec, b_vec=bv)
    b = resolve_ops("pallas_fused").int8_matmul(x, w, spec, b_vec=bv,
                                                bm=32, bn=256, bk=1024)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_builtin_backends():
    """A fresh process registers the oracle and the one Pallas backend."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [src] + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    out = subprocess.run(
        [sys.executable, "-c", "from repro import ops; "
         "print(ops.available_backends())"],
        capture_output=True, text=True, check=True, env=env)
    assert out.stdout.strip() == "['pallas_fused', 'ref']"


# ------------------------------------------------- deprecation shims ------

def test_kernels_ops_shims_removed_with_pointer():
    """The old string-dispatch import path is gone (it warned for one
    release); the tombstone must point migrators at repro.ops."""
    with pytest.raises(ImportError, match=r"repro\.ops"):
        import repro.kernels.ops  # noqa: F401


def test_engine_backend_kwarg_deprecated():
    import inspect
    from repro.serving import ServingEngine
    sig = inspect.signature(ServingEngine.__init__)
    assert sig.parameters["backend"].default is None   # shim, not a string
    assert "ops" in sig.parameters
