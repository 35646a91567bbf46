"""The process-level compiled-step cache (PR 3), asserted directly.

Until now the cache was only exercised implicitly (engines happened to
share executables in the serving suites).  Locked in here:

  * same-geometry engines share ONE jitted decode step (identity, not
    just equal keys) — the cross-engine bit-determinism story depends
    on it;
  * differing pool geometry / cache mode / chunk size / fold_wo miss;
  * the new mesh element: every unsharded engine keys ``("mesh", 1)``
    — including a ``tp > 1`` engine in gathered-fallback mode, which
    traces the identical single-device program and so must share the
    tp=1 executable (sharded mesh-keyed entries are asserted on the
    forced-4-device mesh in ``test_serving_sharded``).
"""
import jax
import pytest

from repro.configs.registry import get_config
from repro.models import model as M
from repro.models import transformer as tf
from repro.quant import convert
from repro.serving import ServingEngine


@pytest.fixture(scope="module")
def setup():
    cfg = M.reduce_config(get_config("llama3-8b"), dtype="float32",
                          vocab=128, num_layers=1)
    params = tf.init_params(jax.random.key(0), cfg)
    qp, plans = convert.quantize_params(params, cfg)
    return cfg, qp, plans


def _engine(setup, **kw):
    cfg, qp, plans = setup
    kw.setdefault("batch_size", 2)
    kw.setdefault("cache_len", 64)
    kw.setdefault("ops", "ref")
    return ServingEngine(qp, plans, cfg, **kw)


def test_same_geometry_engines_share_one_step(setup):
    e1 = _engine(setup)
    e2 = _engine(setup)
    assert e1._decode is e2._decode
    assert e1._prefill_step is e2._prefill_step


def test_differing_geometry_misses(setup):
    base = _engine(setup)
    assert _engine(setup, num_pages=base.layout.num_pages + 3) \
        ._decode is not base._decode
    assert _engine(setup, page_size=8)._decode is not base._decode
    assert _engine(setup, cache_mode="contiguous")._decode \
        is not base._decode
    assert _engine(setup, fold_wo=False)._decode is not base._decode


def test_prefill_chunk_keyed_separately(setup):
    e1 = _engine(setup, prefill_chunk=16)
    e2 = _engine(setup, prefill_chunk=32)
    # the decode step doesn't depend on the chunk size — shared ...
    assert e1._decode is e2._decode
    # ... the prefill step does — distinct executables
    assert e1._prefill_step is not e2._prefill_step


def test_step_key_carries_mesh_element(setup):
    eng = _engine(setup)
    assert ("mesh", 1) in eng._step_key("decode")


def test_gathered_tp_fallback_shares_tp1_executable(setup,
                                                    gathered_backend):
    """A tp=2 engine in gathered-fallback mode traces the identical
    single-device program, so it must hit the tp=1 entry (its key
    carries the same ("mesh", 1) element).  Pinned to a backend that
    never advertises ``tp_serving``, so the engine gathers regardless
    of how many devices this process happens to have (the multi-device
    CI job runs this file under a forced 4-device count)."""
    e1 = _engine(setup, ops=gathered_backend)
    e2 = _engine(setup, ops=gathered_backend, tp=2)
    assert e2.describe()["tp"]["mode"] == "gathered"
    assert ("mesh", 1) in e2._step_key("decode")
    assert e1._decode is e2._decode
    assert e1._prefill_step is e2._prefill_step
