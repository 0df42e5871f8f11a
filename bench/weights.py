"""Seeded weights for a benchmark cell, in the layout they are served in.

One jitted call draws every leaf on the device from ``--seed``:

  * packed projections: uint8 codes, two 4-bit codes per byte along the
    contraction axis K (byte ``j`` of column ``n`` holds row ``2j`` in its
    low nibble and row ``2j+1`` in its high nibble), with one f32 ``scale``
    and ``zmin`` per 128-row local region and column, so that
    ``w[k, n] = code[k, n] * scale[k // 128, n] + zmin[k // 128, n]``;
  * norm scales and the token embedding in f32.

Uniform random codes with ``scale = sigma / 4.61`` and ``zmin = -7.5 scale``
give weights of standard deviation ``sigma = K ** -0.5`` (a uniform code on
0..15 has standard deviation 4.61), as a ``K ** -0.5`` normal init does.

This module imports nothing of the program: the harness hands these arrays
to the program's public parameter types, and the plain reference
(``bench/reference.py``) reads the same arrays after the program has gone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

GROUP = 128      # local quantization region along K (the lq4w scheme)


def dims(cfg: dict) -> dict:
    """Model sizes from a configuration file (Hugging Face key names)."""
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // heads
    vocab = cfg["vocab_size"]
    return {"d": d, "heads": heads, "kv": cfg["num_key_value_heads"],
            "hd": hd, "ff": cfg["intermediate_size"], "vocab": vocab,
            "vocab_pad": -(-vocab // 256) * 256,
            "layers": cfg["num_hidden_layers"],
            "tied": bool(cfg["tie_word_embeddings"]),
            "qk_norm": bool(cfg.get("qk_norm", False)),
            "rope_theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"])}


def projections(m: dict) -> dict:
    """(K, N) of every packed projection of one decoder layer."""
    d, hq, hkv, ff = m["d"], m["heads"] * m["hd"], m["kv"] * m["hd"], m["ff"]
    return {"wq": (d, hq), "wk": (d, hkv), "wv": (d, hkv), "wo": (hq, d),
            "wi_gate": (d, ff), "wi_up": (d, ff), "wo_ffn": (ff, d)}


def _packed(key, lead: tuple, k: int, n: int) -> dict:
    kc, ks, kz = jax.random.split(key, 3)
    sigma = k ** -0.5
    g = (*lead, k // GROUP, n)
    scale = (sigma / 4.61) * jnp.exp(0.1 * jax.random.normal(ks, g))
    zmin = -7.5 * scale + 0.05 * sigma * jax.random.normal(kz, g)
    codes = jax.random.bits(kc, (*lead, k // 2, n), jnp.uint8)
    return {"packed": codes, "scale": scale.astype(jnp.float32),
            "zmin": zmin.astype(jnp.float32)}


def _norm(key, shape) -> jnp.ndarray:
    return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)


def seed_key(seed: int):
    """A key for any whole-number seed, 32 bits or more."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def make(m: dict, seed: int) -> dict:
    """Every weight of the model, drawn on the default device."""
    @jax.jit
    def draw(key):
        names = ["embed", "final_norm", "lm_head", "norm1", "norm2",
                 "q_norm", "k_norm", *projections(m)]
        ks = dict(zip(names, jax.random.split(key, len(names))))
        layers, d = m["layers"], m["d"]
        out = {"embed": jax.random.normal(ks["embed"], (m["vocab_pad"], d),
                                          jnp.float32) * d ** -0.5,
               "final_norm": _norm(ks["final_norm"], (d,))}
        if not m["tied"]:
            out["lm_head"] = _packed(ks["lm_head"], (), d, m["vocab_pad"])
        lay = {"norm1": _norm(ks["norm1"], (layers, d)),
               "norm2": _norm(ks["norm2"], (layers, d))}
        if m["qk_norm"]:
            lay["q_norm"] = _norm(ks["q_norm"], (layers, m["hd"]))
            lay["k_norm"] = _norm(ks["k_norm"], (layers, m["hd"]))
        for name, (k, n) in projections(m).items():
            lay[name] = _packed(ks[name], (layers,), k, n)
        out["layers"] = lay
        return out

    return draw(seed_key(seed))

