"""``chip_smoke.py`` rehearsed on the CPU, plus the compile-cache helper.

The smoke's phases run here at a reduced config (interpret-mode Pallas),
which checks their control flow and assertions without a chip; the
script itself must refuse to run anywhere but on a TPU.
"""
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from repro.configs.registry import get_config
from repro.launch import compile_cache
from repro.models import model as M

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

# the Granite phase's traffic shape, cut to CPU size: half the prompts
# share a prefix, the window serves after a one-prompt warmup
SMALL = dict(requests=4, prompt_len=64, shared_prefix=32, max_new=8,
             batch=4, cache_len=96, page_size=16, prefill_chunk=16)


def test_roberta_phase_reduced():
    cfg = M.reduce_config(get_config("roberta-base"), dtype="float32",
                          vocab=512)
    out = chip_smoke.roberta_phase(cfg, batch=2, seq=32)
    assert out["bit_exact"]
    assert -1.0 <= out["corr"] <= 1.0


def test_granite_phase_reduced():
    out = chip_smoke.granite_phase(traffic=SMALL, reduced=True)
    fused, ref = out["pallas_fused"], out["ref"]
    assert fused["streams"] == ref["streams"]
    assert fused["digests"] == ref["digests"]
    assert len(set(fused["digests"])) == SMALL["requests"]
    assert fused["terminal"]["completed"] == SMALL["requests"]
    assert fused["engine"]["decode"] == "fused"
    assert ref["engine"]["decode"] == "oracle"
    assert fused["warmup_s"] > 0 and fused["window_s"] > 0


def test_serve_main_refuses_a_model_of_another_config():
    """``serve.main(argv, model=...)`` serves the caller's quantized
    model only if it is the one ``--arch`` / ``--reduced`` describe."""
    from repro.launch import serve
    other = M.reduce_config(get_config("llama3-8b"), dtype="float32",
                            vocab=1024)
    with pytest.raises(ValueError, match="not the config"):
        serve.main(["--arch", "granite-3-2b", "--reduced"],
                   model=(other, None, None))


@pytest.mark.parametrize("arch", ["roberta-base", "granite-3-2b"])
def test_random_weights_alive_at_published_widths(arch):
    """Random weights at the served scale, as ``launch.serve`` and the
    smoke draw them, keep the integer datapath alive at the published
    d_model and vocab (one layer): logits vary over the vocab.  With a
    1/sqrt(vocab) embedding every integer activation was 0 and the
    chip's bit-exact comparison compared constants."""
    import numpy as np
    from repro.models import inttransformer as it
    from repro.models import transformer as tf
    from repro.quant import convert
    full = get_config(arch)
    cfg = M.reduce_config(full, dtype="float32", num_layers=1,
                          d_model=full.d_model, n_heads=full.n_heads,
                          n_kv_heads=full.n_kv_heads, head_dim=full.hd,
                          d_ff=256, vocab=full.vocab)
    params = tf.init_params(jax.random.key(0), cfg, served=True)
    qp, plans = convert.quantize_params(params, cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 1, cfg.vocab)
    logits = np.asarray(it.int_prefill(qp, {"tokens": tokens}, plans, cfg,
                                       ops="ref"))[:, :cfg.vocab]
    assert (logits.max(axis=1) > logits.min(axis=1)).all()
    assert len(np.unique(logits)) > cfg.vocab // 2


def test_served_weights_keep_the_residual_stream_off_the_rails():
    """At Granite's depth (40 layers, cut to d_model 128) served random
    weights neither saturate the residual bus at ±16 (a unit embedding
    over the fan-in layers clipped about 14% of it) nor leave it to the
    current token's embedding: the layers add several times the
    embedding's scale.  The embedding is drawn at the served std once
    Granite's ``embedding_multiplier`` has scaled it."""
    import jax.numpy as jnp
    import numpy as np
    from repro.models import inttransformer as it
    from repro.models import transformer as tf
    from repro.models.transformer import layer_group_spec
    from repro.quant import convert
    full = get_config("granite-3-2b")
    cfg = M.reduce_config(full, dtype="float32", num_layers=40,
                          head_dim=64, n_heads=2, n_kv_heads=1)
    params = tf.init_params(jax.random.key(0), cfg, served=True)
    emb = np.asarray(params["embed"])
    assert abs(emb.std() * cfg.embedding_multiplier
               - tf.SERVED_EMBED_STD) < 0.01
    qp, plans = convert.quantize_params(params, cfg)
    _, ng, kinds = layer_group_spec(cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 1, cfg.vocab)
    x32 = it.embed_int(qp, tokens, plans, cfg)
    x0 = float(jnp.std(x32.astype(jnp.float32)))
    rope = it.il.build_rope_table(17, cfg.hd, cfg.rope_theta)
    for layer in range(ng):
        q = jax.tree.map(lambda a: a[layer], qp["layers"][0])
        x32 = it._int_sublayer_fwd(q, x32, plans, cfg, kinds[0], rope,
                                   jnp.arange(16), True, None, "ref")
    assert not (jnp.abs(x32) >= cfg.qmax_res).any()
    assert float(jnp.std(x32.astype(jnp.float32))) > 5 * x0


def test_tp_phase_refused_on_one_device():
    """A sharded engine on too few devices raises (no quiet fallback to
    one device); the real tp phase runs on four chips."""
    if jax.device_count() >= 2:
        pytest.skip("this process has the devices for tp=2")
    with pytest.raises(ValueError, match="needs 2 devices"):
        chip_smoke.tp_phase(2, traffic=SMALL, reduced=True)


def test_main_exits_nonzero_on_cpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert "no TPU" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_script_refuses_without_tpu(tmp_path, where):
    """As run from a checkout, and copied into a directory that holds
    nothing else of the repo: non-zero exit, no result line."""
    script = os.path.join(ROOT, "chip_smoke.py")
    if where == "alone":
        alone = tmp_path / "chip_smoke.py"
        alone.write_text(open(script).read())
        script = str(alone)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, script], cwd=str(tmp_path),
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_compile_cache_location(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = open(os.path.join(ROOT, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored
