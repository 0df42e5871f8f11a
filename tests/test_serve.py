"""Serving: engine, quantized weights/KV-cache, fidelity across schemes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kvwire
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.serve import Engine, EngineConfig

TINY = ModelConfig(name="tiny", family="dense", n_layers=3, d_model=64,
                   vocab_size=256, n_heads=4, n_kv_heads=2, head_dim=16,
                   d_ff=128, dtype="float32", remat="none")


@pytest.fixture(scope="module")
def setup():
    params = transformer.init_params(TINY, jax.random.key(0))
    batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 12), 0,
                                          256, jnp.int32)}
    return params, batch


# ---------------------------------------------------------------------------
# kv wire format
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits,gs", [(8, 16), (4, 16), (2, 8), (1, 8)])
def test_kv_roundtrip_error(bits, gs):
    x = jax.random.normal(jax.random.key(0), (2, 5, 2, 32))
    q = kvwire.quantize_kv(x, bits, gs)
    xr = kvwire.dequantize_kv(q, 32)
    step = float(np.asarray(q["scale"]).max())
    assert float(jnp.abs(x - xr).max()) <= step * 0.5 + 1e-6
    assert kvwire.kv_bits_of(q, 32) == bits


def test_kv_bytes_shrink():
    shape = (2, 64, 2, 64)
    fp = int(np.prod(shape)) * 2                      # bf16 baseline
    for bits in (8, 4, 2, 1):
        q = kvwire.make_quant_kv(shape, bits, 64)
        nbytes = kvwire.cache_nbytes(q)
        assert nbytes < fp * bits / 8 + np.prod(shape[:-1]) * 8 + 1


def test_kv_update_slot():
    q = kvwire.make_quant_kv((1, 8, 2, 32), 8, 16)
    new = jax.random.normal(jax.random.key(2), (1, 1, 2, 32))
    q2 = kvwire.update_quant_kv(q, new, 3, axis=1, bits=8, group_size=16)
    xr = kvwire.dequantize_kv(q2, 32)
    np.testing.assert_allclose(np.asarray(xr[:, 3]), np.asarray(new[:, 0]),
                               rtol=0.05, atol=0.05)
    assert float(jnp.abs(xr[:, 0]).max()) == 0        # untouched slots


# ---------------------------------------------------------------------------
# engine fidelity
# ---------------------------------------------------------------------------

def test_engine_greedy_deterministic(setup):
    params, batch = setup
    eng = Engine(TINY, params, EngineConfig(max_len=32))
    a, _ = eng.generate(batch, steps=6)
    b, _ = eng.generate(batch, steps=6)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("scheme", ["lq8w", "lq8"])
def test_engine_8bit_matches_fp_greedy(setup, scheme):
    """Paper Table 1: 8-bit has no accuracy drop — greedy tokens match."""
    params, batch = setup
    fp = Engine(TINY, params, EngineConfig(max_len=32))
    q = Engine(TINY, params, EngineConfig(max_len=32, weight_scheme=scheme,
                                          backend="ref"))
    a, _ = fp.generate(batch, steps=8)
    b, _ = q.generate(batch, steps=8)
    assert (np.asarray(a) == np.asarray(b)).mean() > 0.9


def test_engine_kv8_matches_fp(setup):
    params, batch = setup
    fp = Engine(TINY, params, EngineConfig(max_len=32))
    q = Engine(TINY, params, EngineConfig(max_len=32, kv_bits=8,
                                          kv_group=16))
    a, _ = fp.generate(batch, steps=8)
    b, _ = q.generate(batch, steps=8)
    assert (np.asarray(a) == np.asarray(b)).mean() > 0.9


def test_engine_cache_bytes_ordering(setup):
    params, _ = setup
    sizes = []
    for bits in (None, 8, 4, 2):
        eng = Engine(TINY, params, EngineConfig(
            max_len=64, kv_bits=bits, kv_group=16))
        sizes.append(eng.cache_bytes(2))
    assert sizes == sorted(sizes, reverse=True)


def test_temperature_sampling_runs(setup):
    params, batch = setup
    eng = Engine(TINY, params, EngineConfig(max_len=32, temperature=0.8,
                                            top_k=16))
    out, _ = eng.generate(batch, steps=5)
    assert out.shape == (2, 6)
    assert int(out.max()) < 256


def test_serving_loop_traces_nothing_between_programs(setup):
    """After eager vmapped ops elsewhere in the process (the kind
    tests/test_expert_matmul.py runs), a warm Server's steps record no
    jaxpr trace: the sampling key is folded inside the step programs and
    host arrays go to them as they are, so the loop between programs
    makes no eager JAX call."""
    from repro.kernels import ref as kref
    from repro.serve import PagedConfig, RequestParams, Server
    params, _ = setup
    w = jax.random.normal(jax.random.key(3), (4, 128, 32), jnp.float32)
    packed, scale, zmin = jax.vmap(
        lambda ww: kref.quantize_weight(ww, 4, 128))(w)
    jax.block_until_ready(jax.vmap(
        lambda p, s, z: kref.dequantize_weight(p, s, z, 4, 128))(
            packed, scale, zmin))
    srv = Server(TINY, params, EngineConfig(max_len=32, backend="ref"),
                 PagedConfig(max_slots=2, page_size=4, n_pages=24,
                             max_context=32), seed=0)
    srv.submit([1, 2, 3, 4, 5], RequestParams(max_new_tokens=3))
    srv.drain(max_steps=50)                   # compiles both programs
    traced = []

    def on_event(name, *_, **__):
        if name == "/jax/core/compile/jaxpr_trace_duration":
            traced.append(name)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        for prompt in ([7, 8, 9], [10, 11, 12, 13, 14, 15]):
            srv.submit(prompt, RequestParams(max_new_tokens=4))
        srv.drain(max_steps=50)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert traced == []


def test_lut_serving_path(setup):
    """Paper section V: 8-bit weights + 2-bit LUT activations serve."""
    params, batch = setup
    eng = Engine(TINY, params, EngineConfig(
        max_len=32, weight_scheme="lq2_lut", backend="ref"))
    out, _ = eng.generate(batch, steps=4)
    assert np.isfinite(np.asarray(out)).all()


# ---------------------------------------------------------------------------
# ssm state quantization (mamba: the attention-free cache)
# ---------------------------------------------------------------------------

def test_mamba_state_quant_close_to_fp():
    cfg = ModelConfig(name="tssm", family="ssm", n_layers=2, d_model=64,
                      vocab_size=256, d_ff=0, rope=False,
                      pattern=(("mamba2", "none"),), ssm_state=16,
                      ssm_head_dim=16, dtype="float32")
    params = transformer.init_params(cfg, jax.random.key(0))
    batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 12), 0,
                                          256, jnp.int32)}
    fp = Engine(cfg, params, EngineConfig(max_len=32))
    q8 = Engine(cfg, params, EngineConfig(max_len=32, kv_bits=8,
                                          kv_group=16))
    a, _ = fp.generate(batch, steps=8)
    b, _ = q8.generate(batch, steps=8)
    assert (np.asarray(a) == np.asarray(b)).mean() > 0.8


# ---------------------------------------------------------------------------
# launcher: compile-cache placement (subprocesses: the setting is global)
# ---------------------------------------------------------------------------

_CACHE_PROBE = (
    "import jax, jax.numpy as jnp\n"
    "from repro.launch.serve import enable_compile_cache\n"
    "print(enable_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
    "import sys\n"
    "if len(sys.argv) > 1:\n"
    "    jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n")


def _probe_cache(env_dir, compile_: bool):
    import os
    import subprocess
    import sys
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE] + (["compile"] if compile_
                                                 else []),
        env=env, capture_output=True, text=True, check=True)
    return out.stdout.split()


def test_compile_cache_uses_env_dir_when_set(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: compiled programs land there and
    the launcher sets no directory of its own."""
    cache = tmp_path / "xla"
    returned, configured = _probe_cache(cache, compile_=True)
    assert returned == configured == str(cache)
    assert any(cache.iterdir())


def test_compile_cache_defaults_to_ignored_checkout_dir():
    """Unset: one fixed directory at the root of the checkout, listed in
    .gitignore (no temp name, pid or time in the path)."""
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    returned, configured = _probe_cache(None, compile_=False)
    assert returned == configured == str(root / ".jax_cache")
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()


def test_full_is_refused_with_fleet(capsys):
    """--full builds one published config; the fleet path keeps the
    reduced configs, so the pair is a usage error, not a silent smoke run."""
    from repro.launch import serve
    with pytest.raises(SystemExit) as e:
        serve.main(["--fleet", "examples/fleet_smoke.json", "--full"])
    assert e.value.code == 2
    assert "--full" in capsys.readouterr().err
