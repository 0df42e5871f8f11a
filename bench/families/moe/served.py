"""The routed-expert decoder family, served side: the program's
``ModelConfig`` and parameter tree for a configuration file.

``repro`` is what ``bench/program.py`` hands in: the program's registry
(``configs``), its types (``QWeight``, ``ModelConfig``), ``serve`` and
``qweight(p, bits, group)``, which wraps one packed leaf of
``plain.draw`` as a ``QWeight``.  This module imports nothing of the
program itself.
"""
from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path


def _plain():
    spec = importlib.util.spec_from_file_location(
        "bench_family_moe_plain_dims", Path(__file__).with_name("plain.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_config(cfg: dict, repro):
    """The program's ModelConfig for a configuration file, checked field
    by field against what the file states."""
    md = _plain().dims(cfg)
    base = repro.configs.get(cfg["arch"])
    kinds = tuple("attn_local" if s else "attn" for s in md["sliding"])
    mc = dataclasses.replace(
        base, n_layers=md["layers"], d_model=md["d"],
        vocab_size=md["vocab"], n_heads=md["heads"], n_kv_heads=md["kv"],
        head_dim=md["hd"], n_experts=md["experts"], top_k=md["top_k"],
        moe_d_ff=md["eff"], window=md["window"],
        rope_theta=md["theta_full"], yarn=md["yarn"],
        tie_embeddings=md["tied"], qk_norm=md["qk_norm"],
        dtype=cfg.get("torch_dtype", "bfloat16"))
    rp = cfg["rope_parameters"]
    if (mc.pattern != tuple((k, "moe") for k in kinds)
            or set(cfg["mlp_layer_types"]) != {"sparse"}
            or mc.shared_ff or mc.norm_kind != "rms" or not mc.rope
            or mc.attn_bias or cfg["attention_bias"]):
        raise ValueError(f"{cfg['arch']}: not the routed-expert decoder "
                         f"with the layer pattern the file describes")
    if (rp["sliding_attention"]["rope_type"] != "default"
            or rp["full_attention"]["rope_type"] != "yarn"
            or md["theta_sliding"] != md["theta_full"]):
        raise ValueError("the program runs default RoPE on sliding layers "
                         "and YaRN on full layers at one theta; the file "
                         "states otherwise")
    if not cfg["norm_topk_prob"]:
        raise ValueError("the program renormalises the top-k gates")
    if md["eps"] != 1e-6 or cfg["hidden_act"] != "silu":
        raise ValueError("the program's RMSNorm eps is 1e-6 and its "
                         "activation silu; the file states otherwise")
    return mc


def params(w: dict, mc, repro, *, bits: int, group: int) -> dict:
    """The program's parameter tree over the seeded arrays (no copies):
    position j of the period is the program's scan-stacked superblock
    position j."""
    def qw(p):
        return {"w": repro.qweight(p, bits, group)}

    blocks = []
    for lay in w["layers"]:
        mixer = {"wq": qw(lay["wq"]), "wk": qw(lay["wk"]),
                 "wv": qw(lay["wv"]), "wo": qw(lay["wo"])}
        if mc.qk_norm:
            mixer["q_norm"] = {"scale": lay["q_norm"]}
            mixer["k_norm"] = {"scale": lay["k_norm"]}
        ffn = {"router": {"w": lay["router"]},
               "wi_gate": repro.qweight(lay["wi_gate"], bits, group),
               "wi_up": repro.qweight(lay["wi_up"], bits, group),
               "wo": repro.qweight(lay["wo_ffn"], bits, group)}
        blocks.append({"norm1": {"scale": lay["norm1"]}, "mixer": mixer,
                       "norm2": {"scale": lay["norm2"]}, "ffn": ffn})
    return {"embed": {"table": w["embed"]},
            "final_norm": {"scale": w["final_norm"]},
            "decoder": {"super": tuple(blocks), "tail": []},
            "lm_head": {"w": repro.qweight(w["lm_head"], bits, group)}}
