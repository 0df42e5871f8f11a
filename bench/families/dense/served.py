"""The dense decoder family, served side: the program's ``ModelConfig`` and
parameter tree for a configuration file.

``repro`` is what ``bench/program.py`` hands in: the program's registry
(``configs``), its types (``QWeight``, ``ModelConfig``), ``serve`` and
``qweight(p, bits, group)``, which wraps one packed leaf of
``plain.draw`` as a ``QWeight``.  This module imports nothing of the
program itself.
"""
from __future__ import annotations

import dataclasses


def model_config(cfg: dict, repro):
    """The program's ModelConfig for a configuration file, checked field
    by field against what the file states."""
    base = repro.configs.get(cfg["arch"])
    mc = dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        vocab_size=cfg["vocab_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or (cfg["hidden_size"]
                                         // cfg["num_attention_heads"]),
        d_ff=cfg["intermediate_size"], rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        qk_norm=bool(cfg.get("qk_norm", False)), dtype=cfg["torch_dtype"])
    if (mc.ffn_kind, mc.pattern, mc.norm_kind, mc.rope, mc.attn_bias) != (
            "swiglu", (("attn", "swiglu"),), "rms", True, False):
        raise ValueError(f"{cfg['arch']}: not the dense SwiGLU GQA decoder "
                         f"the configuration file describes")
    if cfg["rms_norm_eps"] != 1e-6 or cfg["hidden_act"] != "silu":
        raise ValueError("the program's RMSNorm eps is 1e-6 and its "
                         "activation silu; the file states otherwise")
    return mc


def params(w: dict, mc, repro, *, bits: int, group: int) -> dict:
    """The program's parameter tree over the seeded arrays (no copies)."""
    lay = w["layers"]

    def qw(name):
        return {"w": repro.qweight(lay[name], bits, group)}

    mixer = {"wq": qw("wq"), "wk": qw("wk"), "wv": qw("wv"), "wo": qw("wo")}
    if mc.qk_norm:
        mixer["q_norm"] = {"scale": lay["q_norm"]}
        mixer["k_norm"] = {"scale": lay["k_norm"]}
    block = {"norm1": {"scale": lay["norm1"]}, "mixer": mixer,
             "norm2": {"scale": lay["norm2"]},
             "ffn": {"wi_gate": qw("wi_gate"), "wi_up": qw("wi_up"),
                     "wo": qw("wo_ffn")}}
    p = {"embed": {"table": w["embed"]},
         "final_norm": {"scale": w["final_norm"]},
         "decoder": {"super": (block,), "tail": []}}
    if not mc.tie_embeddings:
        p["lm_head"] = {"w": repro.qweight(w["lm_head"], bits, group)}
    return p
