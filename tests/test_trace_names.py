"""The names a profile shows: every Pallas launch carries its kernel's
stable name, every sublayer its scope, every engine step its own jitted
name, and the engine brackets its host phases with named spans
(``repro.trace_names``).

The names live in the compiled program's ``op_name`` metadata, which a
device trace keeps; these tests read that metadata from CPU compiles
(interpret-mode Pallas).  Paths are read by component, as the
benchmark's readers read them.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro import trace_names
from repro.configs.registry import get_config
from repro.core import attention as iattn
from repro.core import intmath, norms
from repro.core.dyadic import fit_dyadic
from repro.models import inttransformer as it
from repro.models import model as M
from repro.models import transformer as tf
from repro.quant import convert
from repro.serving import Request, ServingEngine

I8, I32 = jnp.int8, jnp.int32


def _paths(fn, *shapes):
    """The ``op_name`` path of every op of ``fn`` compiled on the CPU."""
    args = [jax.ShapeDtypeStruct(s, dt) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


def _kernels_in(path):
    """The distinct kernel names in a path (interpret mode repeats the
    whole path inside a ``cond`` branch)."""
    return sorted({c for c in path.split("/") if c in trace_names.KERNELS})


def _attn_plan(d):
    return iattn.make_iattention(d, 8 / 127, 8 / 127, 4 / 127, 4 / 127)


def _int8_matmul():
    from repro.kernels.int8_matmul import int8_matmul_pallas
    dn = fit_dyadic(1 / 4000.0, 128 * 127 * 127)
    return (lambda x, w: int8_matmul_pallas(x, w, None, dn=dn, bk=128,
                                            interpret=True),
            ((128, 128), I8), ((128, 128), I8))


def _int_norm():
    from repro.kernels.int_layernorm import int_layernorm_pallas
    plan = norms.make_inorm(128, 8 / 1024, 1024, 2 / 127, 8 / 127)
    return (lambda q, g, b: int_layernorm_pallas(q, g, b, plan,
                                                 interpret=True),
            ((16, 128), I32), ((128,), I32), ((128,), I32))


def _int_gelu():
    from repro.kernels.int_gelu import int_gelu_pallas
    plan = intmath.make_igelu(16 / 1024, 1024)
    dn = fit_dyadic(plan.s_out / (8 / 127), 1024 * 2 * plan.q_one)
    return (lambda q: int_gelu_pallas(q, plan, dn, interpret=True),
            ((16, 128), I32))


def _int_attention_fused():
    from repro.kernels.int_attention_fused import int_attention_fused
    plan = _attn_plan(32)
    qkv = ((1, 64, 2, 32), I8)
    return (lambda q, k, v: int_attention_fused(q, k, v, plan, bq=64,
                                                bkv=64, interpret=True),
            qkv, qkv, qkv)


def _int_paged_prefill_fused():
    from repro.kernels.int_attention_fused import int_paged_prefill_fused
    plan = _attn_plan(32)
    pool = ((5, 16, 1, 32), I8)
    return (lambda q, kp, vp, end, pt: int_paged_prefill_fused(
                q, kp, vp, plan, end, pt, 16, bkv=16, interpret=True),
            ((1, 16, 2, 32), I8), pool, pool, ((1,), I32), ((1, 2), I32))


def _int_decode_attention():
    from repro.kernels.int_decode_attention import \
        int_decode_attention_fused
    plan = _attn_plan(32)
    cache = ((2, 32, 1, 32), I8)
    return (lambda q, k, v, valid: int_decode_attention_fused(
                q, k, v, plan, valid, bkv=32, interpret=True),
            ((2, 1, 2, 32), I8), cache, cache, ((2,), I32))


def _int_grouped_matmul():
    from repro.kernels.int_grouped_matmul import int_grouped_matmul_pallas
    return (lambda x, w, gs, b: int_grouped_matmul_pallas(
                x, w, gs, b, c=20, pre=4, tm=16, tn=128, interpret=True),
            ((64, 128), I8), ((4, 128, 256), I8), ((4,), I32),
            ((4, 256), I32))


LAUNCHES = {"int8_matmul": _int8_matmul, "int_norm": _int_norm,
            "int_gelu": _int_gelu,
            "int_attention_fused": _int_attention_fused,
            "int_paged_prefill_fused": _int_paged_prefill_fused,
            "int_decode_attention": _int_decode_attention,
            "int_grouped_matmul": _int_grouped_matmul}


def test_every_kernel_has_a_launch_case():
    assert sorted(LAUNCHES) == sorted(trace_names.KERNELS)


@pytest.mark.parametrize("kernel", trace_names.KERNELS)
def test_kernel_launch_carries_its_name(kernel):
    fn, *shapes = LAUNCHES[kernel]()
    named = [_kernels_in(p) for p in _paths(fn, *shapes)]
    assert [kernel] in named
    assert all(k in ([], [kernel]) for k in named)


@pytest.mark.parametrize("table,check", [
    ("KERNELS", trace_names.kernel), ("SCOPES", trace_names.scope),
    ("HOST_SPANS", trace_names.host_span)])
def test_names_outside_their_table_are_refused(table, check):
    with pytest.raises(ValueError, match=table):
        check("not_a_name")


# ------------------------------------------------------- model scopes --

#: the sublayers of a pre-norm block, both benchmark configurations
MODEL_SCOPES = ("embed", "norm1", "attn", "residual", "norm2", "ffn.up",
                "ffn.act", "ffn.down", "final_norm", "head")

#: the launches ``pallas_fused`` makes per configuration, each with the
#: sublayers it may run in.  RoBERTa: LayerNorm, i-GELU, bidirectional
#: attention.  Granite: RMSNorm, RoPE, GQA, causal attention, and i-SiLU
#: under ``ffn.act`` with no kernel of its own; its raw head is XLA's dot.
LAUNCH_OWNERS = {
    "roberta-base": {"int_norm": {"norm1", "norm2", "final_norm"},
                     "int_gelu": {"ffn.act"},
                     "int_attention_fused": {"attn"},
                     "int8_matmul": {"attn", "ffn.up", "ffn.down",
                                     "head"}},
    "granite-3-2b": {"int_norm": {"norm1", "norm2", "final_norm"},
                     "int_attention_fused": {"attn"},
                     "int8_matmul": {"attn", "ffn.up", "ffn.down"}},
}


@pytest.fixture(scope="module")
def tiny_models():
    built = {}

    def build(arch):
        if arch not in built:
            cfg = M.reduce_config(get_config(arch), dtype="float32")
            params = tf.init_params(jax.random.key(0), cfg)
            built[arch] = (cfg,) + convert.quantize_params(params, cfg)
        return built[arch]
    return build


@pytest.mark.parametrize("arch,ops", [
    pytest.param("roberta-base", "pallas_fused", id="pallas_fused"),
    pytest.param("roberta-base", "ref", id="ref"),
    pytest.param("granite-3-2b", "pallas_fused",
                 id="granite-3-2b-pallas_fused"),
    pytest.param("granite-3-2b", "ref", id="granite-3-2b-ref"),
])
def test_int_prefill_names_every_sublayer_and_launch(tiny_models, arch,
                                                     ops):
    cfg, qp, plans = tiny_models(arch)
    if arch == "granite-3-2b":
        assert cfg.pos == "rope" and cfg.is_causal
        assert cfg.n_kv_heads < cfg.n_heads

    def encode(q, t):
        return it.int_prefill(q, {"tokens": t}, plans, cfg, ops=ops)
    text = jax.jit(encode).lower(
        qp, jax.ShapeDtypeStruct((2, 16), I32)).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', text)
    comps = {c for p in paths for c in p.split("/")}
    assert set(MODEL_SCOPES) <= comps
    assert "int_prefill" in comps
    launches = [_kernels_in(p) for p in paths if _kernels_in(p)]
    assert all(len(k) == 1 for k in launches)
    found = {k[0] for k in launches}
    owner = LAUNCH_OWNERS[arch]
    if ops == "ref":
        assert not found
    else:
        assert found == set(owner)
    # each launch runs inside a sublayer that owns it
    for p in paths:
        k = _kernels_in(p)
        if k:
            assert owner[k[0]] & set(p.split("/")), p


def test_moe_stages_nest_inside_moe(tiny_models):
    """Moonlight's MoE sublayer: its four stages are scopes inside
    ``moe`` (so a reader of ``moe`` alone still sees all of it), the
    grouped launch runs under ``moe.experts``, and the MLA attention
    launch under ``attn``."""
    cfg, qp, plans = tiny_models("moonlight-16b-a3b")

    def encode(q, t):
        return it.int_prefill(q, {"tokens": t}, plans, cfg,
                              ops="pallas_fused")
    text = jax.jit(encode).lower(
        qp, jax.ShapeDtypeStruct((2, 32), I32)).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', text)
    stages = ("moe.route", "moe.dispatch", "moe.experts", "moe.combine")
    for st in stages:
        inside = [p.split("/") for p in paths if st in p.split("/")]
        assert inside, st
        assert all("moe" in c[:c.index(st)] for c in inside), st
    found = {k[0] for k in map(_kernels_in, paths) if k}
    assert {"int_grouped_matmul", "int_attention_fused"} <= found
    for p in paths:
        comps = p.split("/")
        if "int_grouped_matmul" in _kernels_in(p):
            assert "moe.experts" in comps, p
        if "int_attention_fused" in _kernels_in(p):
            assert "attn" in comps, p


# ------------------------------------------------------- engine names --

@pytest.fixture(scope="module")
def spec_engine():
    cfg = M.reduce_config(get_config("llama3-8b"), dtype="float32")
    params = tf.init_params(jax.random.key(0), cfg)
    qp, plans = convert.quantize_params(params, cfg)
    return ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                         ops="ref", prefill_chunk=16, spec_k=2)


def _step_args(eng, step):
    b = eng.batch
    lanes = jnp.zeros((b,), I32)
    pages = eng._snap_pages()
    if step == "decode_step":
        return eng._decode, (lanes, lanes, pages)
    if step == "prefill_chunk_step":
        return eng._prefill_step, (jnp.zeros((b, eng.prefill_chunk), I32),
                                   lanes, pages)
    return eng._verify, (jnp.zeros((b, eng.spec_k + 1), I32), lanes,
                         jnp.ones((b,), I32), pages)


@pytest.mark.parametrize("step", ["decode_step", "prefill_chunk_step",
                                  "verify_step"])
def test_engine_steps_compile_under_their_names(spec_engine, step):
    eng = spec_engine
    fn, args = _step_args(eng, step)
    text = fn.lower(eng.qparams, eng.caches, *args).compile().as_text()
    assert text.startswith(f"HloModule jit_{step}")
    entry = {"decode_step": "int_decode_step",
             "prefill_chunk_step": "int_prefill_chunk_step",
             "verify_step": "int_verify_step"}[step]
    assert f'op_name="jit({step})/{entry}/' in text


class _Spans:
    """Records ``TraceAnnotation`` spans as (name, depth) on entry."""

    def __init__(self):
        self.open, self.seen = [], []

    def __call__(self, name, **_):
        spans = self

        class Span:
            def __enter__(self):
                spans.seen.append((name, len(spans.open)))
                spans.open.append(name)

            def __exit__(self, *exc):
                spans.open.pop()
        return Span()


@pytest.mark.parametrize("spec_k", [0, 2])
def test_engine_host_spans(spec_engine, spec_k, monkeypatch):
    spans = _Spans()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", spans)
    e = spec_engine
    eng = ServingEngine(e.qparams, e.plans, e.cfg, batch_size=2,
                        cache_len=64, ops="ref", prefill_chunk=16,
                        spec_k=spec_k)
    req = Request(uid=0, prompt=[3, 1, 4, 1, 5], max_new_tokens=4)
    eng.submit(req)
    eng.run_until_done()
    assert req.done and len(req.out_tokens) == 4
    names = {n for n, _ in spans.seen}
    assert names == set(trace_names.HOST_SPANS)
    assert not spans.open
    # sampling nests inside a commit; dispatch and commit alternate
    assert all(d == 1 for n, d in spans.seen if n == "engine.sample")
    top = [n for n, d in spans.seen if d == 0]
    assert top[0] == "engine.dispatch"
    assert all(a != b or a == "engine.dispatch"
               for a, b in zip(top, top[1:]))
    assert sum(n == "engine.sample" for n, _ in spans.seen) == \
        top.count("engine.commit")
