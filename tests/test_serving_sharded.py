"""Tensor-parallel sharded serving: bit-exact vs the single-device
engine on a forced-4-device CPU mesh.

Each test hands a script to ``mesh_runner.run_with_devices`` (subprocess
isolation: ``conftest.py``'s no-multi-device rule for smoke tests still
holds, and the child asserts the device count it actually got).  Locked
in here:

  * token-stream parity sharded-vs-single-device for tp ∈ {2, 4} across
    backend × cache_mode × chunked/streaming prefill;
  * the ``tp_serving`` capability negotiation — a backend that does not
    advertise it (a ``ref`` subclass registered in the child) makes a
    tp=4 engine take the exact single-device gather lowering (same
    tokens, no mesh, no API change);
  * ``describe()`` reporting mesh geometry and per-device KV bytes;
  * mesh geometry in the compiled-step cache key: tp=2 / tp=4 / unsharded
    engines land distinct entries, same-mesh engines share one;
  * prefix sharing and mid-prefill preempt/resume making identical
    scheduler decisions (hits, CoW copies) and identical tokens at every
    tp degree — the replicated-scheduler invariant.
"""
from mesh_runner import run_with_devices

_SETUP = """
from repro.configs.registry import get_config
from repro.models import model as M, transformer as tf
from repro.quant import convert
from repro.serving import Request, ServingEngine

# tp=4 must divide Hkv: lift the reduced config's head counts to 4/4
cfg = M.reduce_config(get_config("llama3-8b"), dtype="float32",
                      vocab=128, num_layers=1, n_heads=4, n_kv_heads=4)
params = tf.init_params(jax.random.key(0), cfg)
qp, plans = convert.quantize_params(params, cfg)
"""

BODY_PARITY = _SETUP + """
import repro.serving.engine as eng_mod
# the matrix below compiles more distinct steps than the default LRU
# bound keeps; widen it so the cache-key assertions at the end see
# every entry (correctness never depends on the bound)
eng_mod._STEP_CACHE_MAX = 64

PROMPTS = [[1, 7, 42, 9, 3], [2, 7, 42], [11] * 18, [5]]

def serve(tp, ops, **kw):
    eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                        ops=ops, tp=tp, **kw)
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=5)
            for i, p in enumerate(PROMPTS)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    assert all(r.done for r in reqs)
    return [r.out_tokens for r in reqs], eng

MODES = {
    "chunked":   dict(cache_mode="paged", prefill_chunk=16),
    "streaming": dict(cache_mode="paged", prefill_chunk=0),
    "contig":    dict(cache_mode="contiguous"),
}
MATRIX = [("ref", "chunked"), ("ref", "contig"),
          ("pallas_fused", "chunked"), ("pallas_fused", "streaming")]
base = {}
for ops, mode in MATRIX:
    base[(ops, mode)], _ = serve(1, ops, **MODES[mode])
for ops, mode in MATRIX:
    for tp in (2, 4):
        got, eng = serve(tp, ops, **MODES[mode])
        assert got == base[(ops, mode)], (ops, mode, tp, got)
        d = eng.describe()
        assert d["tp"]["mode"] == "sharded", (ops, mode, tp, d["tp"])
        assert d["tp"]["mesh"] == {"axis": "tp", "shape": [tp],
                                   "devices": list(range(tp))}
        assert d["tp"]["per_device_kv_bytes"] \
            == d["cache"]["kv_bytes"] // tp
        assert d["fold_wo"] is False        # requant-rounds-once
        assert f"tp={tp}:sharded" in eng.describe_str()

# a backend that does not advertise tp_serving: a tp=4 engine over it
# takes the exact single-device gather lowering — same API, same tokens,
# no mesh
from repro.ops import register_backend, unregister_backend
from repro.ops.backends.ref import RefBackend

class GatheredRef(RefBackend):
    name = "ref_gathered"
    tp_serving = False

register_backend(GatheredRef.name, GatheredRef())
try:
    b_gat, _ = serve(1, GatheredRef.name, **MODES["chunked"])
    got, eng = serve(4, GatheredRef.name, **MODES["chunked"])
    assert eng.describe()["tp"]["mode"] == "gathered"
    assert eng.mesh is None and got == b_gat
finally:
    unregister_backend(GatheredRef.name)

# mesh geometry is part of the compiled-step cache key: sharded tp=2 /
# tp=4 engines and every unsharded engine (tp=1 AND the gathered
# fallback) landed on distinct mesh key elements ...
mesh_keys = set()
for key in eng_mod._STEP_CACHE:
    mesh_keys.update(k for k in key if isinstance(k, tuple)
                     and len(k) >= 2 and k[0] == "mesh")
assert ("mesh", 1) in mesh_keys, mesh_keys
assert any(k[:2] == ("mesh", 2) for k in mesh_keys), mesh_keys
assert any(k[:2] == ("mesh", 4) for k in mesh_keys), mesh_keys
# ... and rebuilding a same-geometry same-mesh engine hits its entry
n = len(eng_mod._STEP_CACHE)
_, e2 = serve(4, "ref", **MODES["chunked"])
assert len(eng_mod._STEP_CACHE) == n
"""

BODY_SCENARIO = _SETUP + """
import numpy as np

rng = np.random.default_rng(3)
stem = list(map(int, rng.integers(1, 100, 20)))
p1 = stem                                   # registers its prefix
p2 = stem[:-1] + [101]                      # shares 19, then diverges
long = list(map(int, rng.integers(1, 100, 40)))

def scenario(tp):
    eng = ServingEngine(qp, plans, cfg, batch_size=2, cache_len=64,
                        ops="ref", tp=tp, prefill_chunk=16,
                        prefill_budget=16)
    a = Request(uid=0, prompt=list(p1), max_new_tokens=4)
    eng.submit(a)
    eng.run_until_done()
    b = Request(uid=1, prompt=list(p2), max_new_tokens=4)
    eng.submit(b)
    eng.run_until_done()
    d = eng.describe()["cache"]
    hits, cow = d["prefix"]["hits"], d["cow_copies"]
    # mid-prefill preempt: the 40-token prompt needs 3 budgeted chunk
    # rounds; stop it after the first, bump it off the lane, resume
    c = Request(uid=2, prompt=list(long), max_new_tokens=4)
    sc = eng.submit(c)
    eng.step()
    assert sc.state == "prefilling" and 0 < sc.prefill_pos < len(long) - 1
    eng.preempt(sc)
    assert sc.state == "preempted" and sc.pages
    eng.submit(Request(uid=3, prompt=[7, 8], max_new_tokens=2))
    eng.run_until_done()
    eng.kv.allocator.check()
    return [a.out_tokens, b.out_tokens, c.out_tokens], (hits, cow)

base, acct1 = scenario(1)
assert acct1[0] >= 1 and acct1[1] > 0       # sharing + CoW exercised
for tp in (2, 4):
    got, acct = scenario(tp)
    assert got == base, (tp, got, base)
    # the scheduler is replicated host-side: identical prefix hits and
    # copy-on-write decisions at every tp degree
    assert acct == acct1, (tp, acct, acct1)
"""


def test_sharded_stream_parity(tmp_path):
    run_with_devices(BODY_PARITY, 4, tmp_path)


def test_sharded_prefix_sharing_and_preempt(tmp_path):
    run_with_devices(BODY_SCENARIO, 4, tmp_path)
