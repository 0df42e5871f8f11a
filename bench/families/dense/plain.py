"""The dense decoder family, plain side: everything of the pre-norm SwiGLU
GQA decoder that the benchmark needs without the program.

  * ``dims`` and ``draw``: the model's sizes from its configuration file,
    and every weight drawn from ``--seed`` in one jitted call, in the
    layout of ``bench/weights.py``;
  * ``forward``: the plain reference's forward pass, which
    ``bench/reference.py`` runs for ``correct``;
  * the work counts the per-layer readers take (``quant_matmul_calls``,
    ``attention_calls``, ``decode_flops``, ``prefill_flops``), built on
    ``bench/work.py``'s arithmetic;
  * ``tiny``: the configuration cut to a size the CPU test run can hold.

The reference follows the configuration as it is run
(``bench/configs/<config>.json``):

  * pre-norm decoder blocks, RMSNorm (``rms_norm_eps``) with a learned
    scale; SwiGLU feed-forward;
  * grouped-query attention, query head ``h`` reading key/value head
    ``h // (heads / kv_heads)``; optional per-head RMSNorm of q and k
    (``qk_norm``) before rotary embedding; rotary embedding over the two
    halves of each head (``rope_theta``); scores scaled by ``head_dim**-0.5``;
  * 4-bit local-region weights dequantized to f32;
  * the 4-bit key/value cache the configuration serves with: keys (after
    rotary embedding) and values are rounded per token and head through
    ``kv_group``-wide regions.  A prompt is processed in one pass that
    attends to its own unrounded keys and values; every later token
    attends to the rounded cache, its own entry included;
  * the output head: the tied embedding, or the packed ``lm_head``.

Imports nothing of the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import weights, work
from bench.reference import dequant, kv_round, rms, rope


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


def projections(md: dict) -> dict:
    """(K, N) of every packed projection of one decoder layer."""
    d, hd, ff = md["d"], md["hd"], md["ff"]
    hq, hkv = md["heads"] * hd, md["kv"] * hd
    return {"wq": (d, hq), "wk": (d, hkv), "wv": (d, hkv), "wo": (hq, d),
            "wi_gate": (d, ff), "wi_up": (d, ff), "wo_ffn": (ff, d)}


def draw(md: dict, seed: int) -> dict:
    """Every weight of the model, drawn on the default device."""
    @jax.jit
    def leaves(key):
        names = ["embed", "final_norm", "lm_head", "norm1", "norm2",
                 "q_norm", "k_norm", *projections(md)]
        ks = dict(zip(names, jax.random.split(key, len(names))))
        layers, d = md["layers"], md["d"]
        out = {"embed": jax.random.normal(ks["embed"], (md["vocab_pad"], d),
                                          jnp.float32) * d ** -0.5,
               "final_norm": weights.norm(ks["final_norm"], (d,))}
        if not md["tied"]:
            out["lm_head"] = weights.packed(ks["lm_head"], (), d,
                                            md["vocab_pad"])
        lay = {"norm1": weights.norm(ks["norm1"], (layers, d)),
               "norm2": weights.norm(ks["norm2"], (layers, d))}
        if md["qk_norm"]:
            lay["q_norm"] = weights.norm(ks["q_norm"], (layers, md["hd"]))
            lay["k_norm"] = weights.norm(ks["k_norm"], (layers, md["hd"]))
        for name, (k, n) in projections(md).items():
            lay[name] = weights.packed(ks[name], (layers,), k, n)
        out["layers"] = lay
        return out

    return leaves(weights.seed_key(seed))


def forward(w, m: dict, tokens, n_prompt, rows, *, kv_bits: int,
            kv_group: int, ops):
    """Logits (R, vocab) at positions ``rows`` (R,) of ``tokens`` (S,);
    every matrix product through ``ops`` (``bench.reference.Ops``)."""
    mm, ein = ops.mm, ops.ein
    s_len = tokens.shape[0]
    heads, kvh, hd, eps = m["heads"], m["kv"], m["hd"], m["eps"]
    grp = heads // kvh
    pos = jnp.arange(s_len)
    prompt_row = (pos < n_prompt)[:, None]                    # (S, 1)
    causal = pos[None, :] <= pos[:, None]                     # (S, S)

    def layer(x, lw):
        h = rms(x, lw["norm1"], eps)
        q = mm(h, dequant(lw["wq"])).reshape(s_len, heads, hd)
        k = mm(h, dequant(lw["wk"])).reshape(s_len, kvh, hd)
        v = mm(h, dequant(lw["wv"])).reshape(s_len, kvh, hd)
        if m["qk_norm"]:
            q = rms(q, lw["q_norm"], eps)
            k = rms(k, lw["k_norm"], eps)
        q = rope(q, pos, m["rope_theta"]).reshape(s_len, kvh, grp, hd)
        k = rope(k, pos, m["rope_theta"])
        kq, vq = kv_round(k, kv_bits, kv_group), kv_round(v, kv_bits, kv_group)
        scale = hd ** -0.5
        s = jnp.where(prompt_row[None, None],
                      ein("skgd,tkd->kgst", q, k),
                      ein("skgd,tkd->kgst", q, kq)) * scale
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.where(prompt_row[:, None, None],
                      ein("kgst,tkd->skgd", p, v),
                      ein("kgst,tkd->skgd", p, vq))
        x = x + mm(o.reshape(s_len, heads * hd), dequant(lw["wo"]))
        h = rms(x, lw["norm2"], eps)
        f = jax.nn.silu(mm(h, dequant(lw["wi_gate"]))) * mm(
            h, dequant(lw["wi_up"]))
        return x + mm(f, dequant(lw["wo_ffn"])), None

    x = w["embed"][tokens]
    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = rms(x[rows], w["final_norm"], eps)
    head = w["embed"].T if m["tied"] else dequant(w["lm_head"])
    return mm(x, head)[:, :m["vocab"]]


def quant_matmul_calls(md: dict, rows: int,
                       head_rows: int) -> list[tuple[float, float]]:
    """(FLOPs, bytes) of every ``quant_matmul`` call of one forward pass
    over ``rows`` real rows: each layer's projections, then an untied,
    packed output head over ``head_rows`` (a prefill reads one position;
    a tied head is an XLA matmul with the embedding, not this kernel)."""
    calls = [work.matmul(rows, k, n)
             for k, n in projections(md).values()] * md["layers"]
    if not md["tied"]:
        calls.append(work.matmul(head_rows, md["d"], md["vocab"]))
    return calls


def attention_calls(md: dict, contexts: list[int],
                    serving: dict) -> list[tuple[float, float]]:
    """(FLOPs, bytes) of the paged-attention kernel's call in each layer
    at decode, one entry a layer: each slot's query against its live
    context (QK and PV), over its live pages.  Every layer reads the
    whole context."""
    hq, hd = md["heads"], md["hd"]
    flops = sum(4.0 * hq * hd * c for c in contexts)
    pages = sum(math.ceil(c / serving["page_size"]) for c in contexts)
    io = work.ACT_BYTES * 2 * len(contexts) * hq * hd
    return [(flops, pages * work.page_bytes(md, serving) + io)] * md["layers"]


def decode_flops(md: dict, contexts: list[int]) -> float:
    """Model FLOPs one decode step needs for its real slots."""
    rows = len(contexts)
    proj = sum(2.0 * rows * k * n for k, n in projections(md).values())
    attn = sum(4.0 * md["heads"] * md["hd"] * c for c in contexts)
    return md["layers"] * (proj + attn) + 2.0 * rows * md["d"] * md["vocab"]


def prefill_flops(md: dict, length: int) -> float:
    """Model FLOPs a prefill of ``length`` real tokens needs: every
    projection over the prompt, causal attention, one row of the head."""
    proj = sum(2.0 * length * k * n for k, n in projections(md).values())
    attn = 2.0 * md["heads"] * md["hd"] * length * (length + 1)
    return md["layers"] * (proj + attn) + 2.0 * md["d"] * md["vocab"]


def tiny(cfg: dict) -> dict:
    """The configuration at a 2-layer, 256-wide size the CPU test run can
    hold, its kind (tied head, qk_norm, head_dim given or not) kept."""
    out = dict(cfg, hidden_size=256, num_attention_heads=4,
               num_key_value_heads=2, intermediate_size=512,
               num_hidden_layers=2, vocab_size=1000)
    if "head_dim" in out:
        out["head_dim"] = 64
    return out
