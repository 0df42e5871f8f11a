"""The system under test, built through the program's public API.

The only module of the benchmark that imports the program (``repro``,
under ``src/``).  It turns a configuration file into the program's
``ModelConfig``, hands the seeded weights of ``bench/weights.py`` to the
program as its own parameter types (packed ``QWeight`` projections, f32
norms and embedding), and builds a ``Server`` over a paged 4-bit pool.
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _repro():
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro  # noqa: F401  (fails in a checkout without the program)
    from repro import configs
    from repro.kernels.ops import QWeight
    from repro.models.config import ModelConfig
    from repro import serve
    return configs, QWeight, ModelConfig, serve


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file, checked field
    by field against what the file states."""
    configs, _, _, _ = _repro()
    base = configs.get(cfg["arch"])
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


def _qweight(QWeight, p: dict, bits: int, group: int):
    k = p["packed"].shape[-2] * 8 // bits
    return QWeight(packed=p["packed"], scale=p["scale"], zmin=p["zmin"],
                   bits=bits, group_size=group, k=k, n=p["packed"].shape[-1])


def params(w: dict, mc, *, bits: int = 4, group: int = 128) -> dict:
    """The program's parameter tree over the seeded arrays (no copies)."""
    _, QWeight, _, _ = _repro()
    lay = w["layers"]

    def qw(name):
        return {"w": _qweight(QWeight, lay[name], bits, group)}

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
        p["lm_head"] = {"w": _qweight(QWeight, w["lm_head"], bits, group)}
    return p


def server(mc, p: dict, serving: dict, *, on_token=None):
    """A Server over a paged pool with the configuration's geometry."""
    _, _, _, serve = _repro()
    ecfg = serve.EngineConfig(
        max_len=serving["max_context"], kv_bits=serving["kv_bits"],
        kv_group=serving["kv_group"], weight_scheme=serving["weight_scheme"],
        fused_attention=serving["fused_attention"])
    pcfg = serve.PagedConfig(
        max_slots=serving["max_slots"], page_size=serving["page_size"],
        n_pages=serving["n_pages"], max_context=serving["max_context"])
    return serve.Server(mc, p, ecfg, pcfg, on_token=on_token)


def request_params(max_new_tokens: int):
    _, _, _, serve = _repro()
    return serve.RequestParams(max_new_tokens=max_new_tokens)
