"""Per-arch smoke tests: reduced config, one forward + one train step on
CPU, asserting output shapes and no NaNs (assignment requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs import shapes as shp
from repro.models import transformer
from repro.train import TrainHParams, make_train_step


@pytest.mark.parametrize("arch", configs.names())
def test_smoke_forward_and_train_step(arch):
    cfg = configs.smoke(arch)
    batch = shp.demo_batch(cfg, batch=2, seq_len=16)

    params = transformer.init_params(cfg, jax.random.key(0))
    logits, aux = transformer.forward(params, cfg, batch)
    lt = batch["tokens"].shape[1] + \
        (cfg.n_patches if cfg.frontend == "patch_stub" else 0)
    assert logits.shape == (2, lt, cfg.padded_vocab)
    assert not bool(jnp.isnan(logits).any()), "NaN in logits"
    assert not bool(jnp.isnan(aux).any())

    init_state, train_step = make_train_step(cfg, TrainHParams(lr=1e-3))
    state = init_state(jax.random.key(1))
    state, metrics = jax.jit(train_step)(state, batch)
    assert np.isfinite(float(metrics["loss"])), "non-finite loss"
    assert int(state.step) == 1


@pytest.mark.parametrize("arch", configs.names())
def test_smoke_decode_matches_prefill_continuation(arch):
    """prefill(t[:n]) + decode(t[n]) logits == forward(t[:n+1]) tail."""
    cfg = configs.smoke(arch)
    batch = shp.demo_batch(cfg, batch=2, seq_len=12)
    params = transformer.init_params(cfg, jax.random.key(0))

    full_logits, _ = transformer.forward(params, cfg, batch, training=False)

    pre = dict(batch)
    toks = batch["tokens"]
    pre["tokens"] = toks[:, :-1]
    pre.pop("labels", None)
    cache = transformer.init_cache(cfg, 2, 24)
    logits_pre, cache = transformer.prefill(params, cfg, pre, cache)
    logits_dec, cache = transformer.decode_step(
        params, cfg, toks[:, -1:], cache)

    np.testing.assert_allclose(
        np.asarray(logits_pre[:, 0]), np.asarray(full_logits[:, -2]),
        rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(
        np.asarray(logits_dec[:, 0]), np.asarray(full_logits[:, -1]),
        rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("arch", configs.names())
def test_full_config_exact_spec(arch):
    """The full configs carry the exact published hyperparameters."""
    cfg = configs.get(arch)
    spec = {
        "whisper-large-v3": (32, 1280, 20, 20, 5120, 51866),
        "granite-3-2b": (40, 2048, 32, 8, 8192, 49155),
        "llama3.2-1b": (16, 2048, 32, 8, 8192, 128256),
        "qwen3-8b": (36, 4096, 32, 8, 12288, 151936),
        "qwen3-14b": (40, 5120, 40, 8, 17408, 151936),
        "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 1536, 151936),
        "llama4-scout-17b-a16e": (48, 5120, 40, 8, 8192, 202048),
        "internvl2-1b": (24, 896, 14, 2, 4864, 151655),
        "mamba2-130m": (24, 768, 0, 0, 0, 50280),
        "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256000),
        "mellum2-12b-a2.5b": (28, 2304, 32, 4, 896, 98304),
    }[arch]
    n_layers, d_model, n_heads, n_kv, d_ff, vocab = spec
    assert cfg.n_layers == n_layers
    assert cfg.d_model == d_model
    assert cfg.n_heads == n_heads
    assert cfg.n_kv_heads == n_kv
    assert cfg.vocab_size == vocab
    if cfg.family == "moe":
        assert cfg.moe_d_ff == d_ff
    elif arch != "mamba2-130m":
        assert cfg.d_ff == d_ff


def test_param_counts_match_published():
    assert abs(configs.get("qwen3-moe-235b-a22b").param_count()
               - 235e9) / 235e9 < 0.02
    assert abs(configs.get("qwen3-moe-235b-a22b").active_param_count()
               - 22e9) / 22e9 < 0.02
    assert abs(configs.get("llama3.2-1b").param_count()
               - 1.24e9) / 1.24e9 < 0.02
    assert abs(configs.get("qwen3-8b").param_count() - 8.2e9) / 8.2e9 < 0.02
    assert abs(configs.get("mamba2-130m").param_count()
               - 0.13e9) / 0.13e9 < 0.05
    scout = configs.get("llama4-scout-17b-a16e")
    assert abs(scout.active_param_count() - 17e9) / 17e9 < 0.05


def test_moe_aux_loss_balanced_router():
    """A uniform router gives aux ~= 1 (Switch normalization)."""
    cfg = configs.smoke("qwen3-moe-235b-a22b")
    params = transformer.init_params(cfg, jax.random.key(0))
    batch = shp.demo_batch(cfg, batch=2, seq_len=32)
    _, aux = transformer.forward(params, cfg, batch)
    assert 0.5 < float(aux) < 3.0


def test_scan_tail_layers():
    """recurrentgemma smoke (5 layers, pattern 3) exercises the tail."""
    cfg = configs.smoke("recurrentgemma-2b")
    assert cfg.n_super == 1 and cfg.n_tail == 2
    params = transformer.init_params(cfg, jax.random.key(0))
    assert len(params["decoder"]["tail"]) == 2


@pytest.mark.parametrize("arch", ["granite-3-2b", "recurrentgemma-2b",
                                  "whisper-large-v3"])
def test_packed_init_matches_quantize_params(arch):
    """init_params(qcfg=...) draws and packs one layer at a time; the
    result is quantize_params(init_params(...)) up to float rounding of
    the per-region affine (same tree, same dequantized weights)."""
    from repro.core import schemes
    from repro.kernels import ops
    cfg = configs.smoke(arch)
    qcfg = schemes.get("lq4w")
    want = transformer.quantize_params(
        transformer.init_params(cfg, jax.random.key(3)), cfg, qcfg)
    got = transformer.init_params(cfg, jax.random.key(3), qcfg=qcfg)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    is_q = lambda x: isinstance(x, ops.QWeight)

    def dense(q):
        """A (possibly stacked) QWeight dequantized to (..., K, N)."""
        if not is_q(q):
            return q
        if q.packed.ndim == 2:
            return ops.dequantize_weight(q)
        return jax.vmap(dense)(q)

    for g, w in zip(jax.tree.leaves(got, is_leaf=is_q),
                    jax.tree.leaves(want, is_leaf=is_q)):
        np.testing.assert_allclose(np.asarray(dense(g)),
                                   np.asarray(dense(w)), rtol=1e-5,
                                   atol=1e-6)
