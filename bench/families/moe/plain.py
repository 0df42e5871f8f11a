"""The routed-expert decoder family, plain side: everything of a decoder
whose every layer is a sparse mixture of experts, with sliding-window and
full attention layers in a fixed period, that the benchmark needs without
the program.

  * ``dims`` and ``draw``: the model's sizes from its configuration file,
    and every weight drawn from ``--seed`` in one jitted call, in the
    layout of ``bench/weights.py``, one stack of layers per position of
    the period (position j holds layers j, j + P, ...), as served;
  * ``forward``: the plain reference's forward pass, which
    ``bench/reference.py`` runs for ``correct``;
  * the work counts the per-layer readers take (``quant_matmul_calls``,
    ``expert_matmul_calls``, ``attention_calls``, ``decode_flops``,
    ``prefill_flops``), built on ``bench/work.py``'s arithmetic;
  * ``tiny``: the configuration cut to a size the CPU test run can hold.

The reference follows the configuration as it is run
(``bench/configs/<config>.json``, Hugging Face key names):

  * pre-norm decoder blocks, RMSNorm (``rms_norm_eps``) with a learned
    scale;
  * grouped-query attention, query head ``h`` reading key/value head
    ``h // (heads / kv_heads)``; per-head RMSNorm of q and k
    (``qk_norm``) before rotary embedding over the two halves of each
    head; scores scaled by ``head_dim**-0.5``;
  * ``layer_types``: a ``sliding_attention`` layer sees keys in
    ``(pos - sliding_window, pos]`` with default rotary embedding at its
    ``rope_theta``; a ``full_attention`` layer sees every earlier key,
    with YaRN (``rope_parameters.full_attention``, as Hugging Face's
    ``_compute_yarn_parameters``): pair i's frequency ``1/theta^(2i/D)``
    blends linearly into itself over ``factor`` between the pairs that
    turn ``beta_fast`` and ``beta_slow`` times over
    ``original_max_position_embeddings`` (floored, ceiled, clamped to
    [0, D-1]), and cos and sin are scaled by ``attention_factor``;
  * every layer's feed-forward: an f32 router over ``num_experts``
    experts, softmax, the ``num_experts_per_tok`` largest renormalised
    to sum to one (``norm_topk_prob``), each token's output the weighted
    sum of its chosen SwiGLU experts (``moe_intermediate_size`` wide),
    from its own hidden state; computed one expert at a time over every
    position, an expert's weight zero where the token did not choose it;
  * 4-bit local-region weights dequantized to f32 (one expert at a time);
  * the 4-bit key/value cache the configuration serves with: keys (after
    rotary embedding) and values are rounded per token and head through
    ``kv_group``-wide regions.  A prompt is processed in one pass that
    attends to its own unrounded keys and values; every later token
    attends to the rounded cache, its own entry included;
  * the packed, untied output head.

Imports nothing of the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import weights, work
from bench.reference import dequant, kv_round, rms

SLIDING, FULL = "sliding_attention", "full_attention"


def _period(cfg: dict) -> int:
    """The length of the repeating run of ``layer_types``."""
    kinds, layers = tuple(cfg["layer_types"]), cfg["num_hidden_layers"]
    return next(p for p in range(1, layers + 1)
                if layers % p == 0 and kinds == kinds[:p] * (layers // p))


def dims(cfg: dict) -> dict:
    """Model sizes from a configuration file; every value hashable."""
    kinds, layers, period = cfg["layer_types"], cfg["num_hidden_layers"], \
        _period(cfg)
    rp = cfg["rope_parameters"]
    full, slid = rp[FULL], rp[SLIDING]
    vocab = cfg["vocab_size"]
    return {"d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "vocab": vocab, "vocab_pad": -(-vocab // 256) * 256,
            "layers": layers, "tied": bool(cfg["tie_word_embeddings"]),
            "qk_norm": bool(cfg.get("qk_norm", False)),
            "eps": float(cfg["rms_norm_eps"]),
            "experts": cfg["num_experts"], "top_k":
            cfg["num_experts_per_tok"], "eff": cfg["moe_intermediate_size"],
            "window": cfg["sliding_window"],
            "sliding": tuple(k == SLIDING for k in kinds[:period]),
            "theta_sliding": float(slid["rope_theta"]),
            "theta_full": float(full["rope_theta"]),
            "yarn": (float(full["factor"]),
                     float(full["original_max_position_embeddings"]),
                     float(full["beta_fast"]), float(full["beta_slow"]),
                     float(full["attention_factor"]))}


def attention_projections(md: dict) -> dict:
    """(K, N) of every packed attention projection of one layer."""
    d, hd = md["d"], md["hd"]
    hq, hkv = md["heads"] * hd, md["kv"] * hd
    return {"wq": (d, hq), "wk": (d, hkv), "wv": (d, hkv), "wo": (hq, d)}


def expert_projections(md: dict) -> dict:
    """(K, N) of each expert's packed projections."""
    d, f = md["d"], md["eff"]
    return {"wi_gate": (d, f), "wi_up": (d, f), "wo_ffn": (f, d)}


def draw(md: dict, seed: int) -> dict:
    """Every weight of the model, drawn on the default device:
    ``layers`` is one dict per position of the period, each leaf stacked
    over the ``layers / period`` layers at that position."""
    period = len(md["sliding"])
    names = ["norm1", "norm2", "q_norm", "k_norm", "router",
             *attention_projections(md), *expert_projections(md)]

    @jax.jit
    def leaves(key):
        ks = jax.random.split(key, 3 + period * len(names))
        d, n_stack, e = md["d"], md["layers"] // period, md["experts"]
        out = {"embed": jax.random.normal(ks[0], (md["vocab_pad"], d),
                                          jnp.float32) * d ** -0.5,
               "final_norm": weights.norm(ks[1], (d,)),
               "lm_head": weights.packed(ks[2], (), d, md["vocab_pad"])}
        lays = []
        for j in range(period):
            k = dict(zip(names, ks[3 + j * len(names):
                                   3 + (j + 1) * len(names)]))
            lay = {"norm1": weights.norm(k["norm1"], (n_stack, d)),
                   "norm2": weights.norm(k["norm2"], (n_stack, d)),
                   "router": jax.random.normal(k["router"], (n_stack, d, e),
                                               jnp.float32) * d ** -0.5}
            if md["qk_norm"]:
                lay["q_norm"] = weights.norm(k["q_norm"], (n_stack, md["hd"]))
                lay["k_norm"] = weights.norm(k["k_norm"], (n_stack, md["hd"]))
            for name, (kk, nn) in attention_projections(md).items():
                lay[name] = weights.packed(k[name], (n_stack,), kk, nn)
            for name, (kk, nn) in expert_projections(md).items():
                lay[name] = weights.packed(k[name], (n_stack, e), kk, nn)
            lays.append(lay)
        out["layers"] = tuple(lays)
        return out

    return leaves(weights.seed_key(seed))


def inv_freq(hd: int, theta: float, yarn: tuple | None = None):
    """(D/2,) rotary frequencies, and the cos/sin scale: plain, or YaRN's
    (``yarn`` = factor, original positions, beta_fast, beta_slow,
    attention_factor)."""
    base = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    if yarn is None:
        return base, 1.0
    factor, orig, beta_fast, beta_slow, att = yarn

    def pair(rot):
        return hd * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair(beta_fast)), 0)
    high = min(math.ceil(pair(beta_slow)), hd - 1)
    if low == high:
        high += 0.001
    extrapolate = 1.0 - jnp.clip(
        (jnp.arange(hd // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    return base / factor * (1 - extrapolate) + base * extrapolate, att


def _rope(x, pos, freqs, scale):
    """x (S, H, D): rotary embedding over the two halves of D."""
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None] * scale, jnp.sin(ang)[:, None] * scale
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm_packed(mm, x, p):
    """x (S, K) @ dequant(p), as the sum over the two nibbles of each
    byte: rows 2j (low nibble) and 2j + 1 (high) of the K axis."""
    codes = p["packed"].astype(jnp.int32)
    kh, n = codes.shape
    g = p["scale"].shape[0]

    def half(c):
        return (c.astype(jnp.float32).reshape(g, -1, n) * p["scale"][:, None]
                + p["zmin"][:, None]).reshape(kh, n)

    return mm(x[:, 0::2], half(codes & 15)) + mm(x[:, 1::2], half(codes >> 4))


def forward(w, m: dict, tokens, n_prompt, rows, *, kv_bits: int,
            kv_group: int, ops):
    """Logits (R, vocab) at positions ``rows`` (R,) of ``tokens`` (S,);
    every matrix product through ``ops`` (``bench.reference.Ops``)."""
    mm, ein = ops.mm, ops.ein
    s_len = tokens.shape[0]
    heads, kvh, hd, eps = m["heads"], m["kv"], m["hd"], m["eps"]
    grp, e, k_top = heads // kvh, m["experts"], m["top_k"]
    pos = jnp.arange(s_len)
    prompt_row = (pos < n_prompt)[:, None]                    # (S, 1)
    causal = pos[None, :] <= pos[:, None]                     # (S, S)
    windowed = causal & (pos[None, :] > pos[:, None] - m["window"])
    rope_of = {True: inv_freq(hd, m["theta_sliding"]),
               False: inv_freq(hd, m["theta_full"], m["yarn"])}

    def attention(x, lw, sliding):
        h = rms(x, lw["norm1"], eps)
        q = mm(h, dequant(lw["wq"])).reshape(s_len, heads, hd)
        k = mm(h, dequant(lw["wk"])).reshape(s_len, kvh, hd)
        v = mm(h, dequant(lw["wv"])).reshape(s_len, kvh, hd)
        if m["qk_norm"]:
            q = rms(q, lw["q_norm"], eps)
            k = rms(k, lw["k_norm"], eps)
        freqs, scale = rope_of[sliding]
        q = _rope(q, pos, freqs, scale).reshape(s_len, kvh, grp, hd)
        k = _rope(k, pos, freqs, scale)
        kq, vq = kv_round(k, kv_bits, kv_group), kv_round(v, kv_bits, kv_group)
        s = jnp.where(prompt_row[None, None],
                      ein("skgd,tkd->kgst", q, k),
                      ein("skgd,tkd->kgst", q, kq)) * hd ** -0.5
        s = jnp.where((windowed if sliding else causal)[None, None], s,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.where(prompt_row[:, None, None],
                      ein("kgst,tkd->skgd", p, v),
                      ein("kgst,tkd->skgd", p, vq))
        return x + mm(o.reshape(s_len, heads * hd), dequant(lw["wo"]))

    def experts(x, lw):
        h = rms(x, lw["norm2"], eps)
        probs = jax.nn.softmax(mm(h, lw["router"]), axis=-1)    # (S, E)
        top, idx = jax.lax.top_k(probs, k_top)
        top = top / top.sum(-1, keepdims=True)
        gate = (jax.nn.one_hot(idx, e) * top[..., None]).sum(1)  # (S, E)

        def one(i, acc):
            wg, wu, wo = (jax.tree.map(lambda a: a[i], lw[n])
                          for n in ("wi_gate", "wi_up", "wo_ffn"))
            f = jax.nn.silu(_mm_packed(mm, h, wg)) * _mm_packed(mm, h, wu)
            return acc + gate[:, i][:, None] * _mm_packed(mm, f, wo)

        return x + jax.lax.fori_loop(0, e, one, jnp.zeros_like(x))

    def period(x, lws):
        for lw, sliding in zip(lws, m["sliding"]):
            x = experts(attention(x, lw, sliding), lw)
        return x, None

    x = w["embed"][tokens]
    x, _ = jax.lax.scan(period, x, w["layers"])
    x = rms(x[rows], w["final_norm"], eps)
    head = w["lm_head"]
    cols = 8192 if head["packed"].shape[-1] % 8192 == 0 else \
        head["packed"].shape[-1]
    parts = jax.lax.map(
        lambda c: mm(x, dequant(jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, c * cols, cols, -1),
            head))), jnp.arange(head["packed"].shape[-1] // cols))
    return jnp.moveaxis(parts, 0, 1).reshape(x.shape[0], -1)[:, :m["vocab"]]


def _kinds(md: dict) -> list[bool]:
    """Whether each layer, in order, is a sliding-window layer."""
    return list(md["sliding"]) * (md["layers"] // len(md["sliding"]))


def quant_matmul_calls(md: dict, rows: int,
                       head_rows: int) -> list[tuple[float, float]]:
    """(FLOPs, bytes) of every ``quant_matmul`` call of one forward pass
    over ``rows`` real rows: each layer's attention projections, then the
    packed output head over ``head_rows`` (a prefill reads one position).
    The experts go through ``expert_matmul`` instead."""
    calls = [work.matmul(rows, k, n)
             for k, n in attention_projections(md).values()] * md["layers"]
    calls.append(work.matmul(head_rows, md["d"], md["vocab"]))
    return calls


def experts_hit(md: dict, rows: int) -> float:
    """Expected distinct experts ``rows`` tokens choose under uniform
    routing: each of E is missed by a token with probability 1 - k/E."""
    e, k = md["experts"], md["top_k"]
    return e * (1.0 - (1.0 - k / e) ** rows)


def expert_matmul_calls(md: dict, rows: int) -> list[tuple[float, float]]:
    """(FLOPs, bytes) of every ``expert_matmul`` call of one forward pass
    over ``rows`` real tokens: gate, up and down in each layer, each over
    the ``rows * top_k`` routed rows.  FLOPs are those rows' products;
    bytes are the packed weights of the experts expected to be hit under
    uniform routing (``experts_hit``, each read once), plus the rows'
    activations in and out.  Uniform routing is an assumption: the hits
    a step really makes are the ``decode`` span's ``experts_hit``."""
    routed, hit = rows * md["top_k"], experts_hit(md, rows)
    calls = []
    for k, n in expert_projections(md).values():
        calls.append((2.0 * routed * k * n,
                      hit * work.packed_bytes(k, n)
                      + work.ACT_BYTES * routed * (k + n)))
    return calls * md["layers"]


def _live(md: dict, c: int, sliding: bool, page_size: int) -> tuple:
    """(keys attended, live pages) of a query at context ``c`` (its own
    key included): a sliding layer reads keys and pages from the first
    that lies inside the window to the last."""
    last = (c - 1) // page_size
    if not sliding:
        return c, last + 1
    return min(c, md["window"]), last - max(c - md["window"], 0) // page_size + 1


def attention_calls(md: dict, contexts: list[int],
                    serving: dict) -> list[tuple[float, float]]:
    """(FLOPs, bytes) of the paged-attention kernel's call in each layer
    at decode, one entry a layer: each slot's query against its live
    context (QK and PV), over its live pages; a sliding layer's context
    is capped at the window, and its pages run from the first live page
    to the last."""
    hq, hd, ps = md["heads"], md["hd"], serving["page_size"]
    io = work.ACT_BYTES * 2 * len(contexts) * hq * hd
    out = []
    for sliding in _kinds(md):
        live = [_live(md, c, sliding, ps) for c in contexts]
        out.append((sum(4.0 * hq * hd * keys for keys, _ in live),
                    sum(p for _, p in live) * work.page_bytes(md, serving)
                    + io))
    return out


def _layer_flops(md: dict, rows: int) -> float:
    """Projection, router and routed-expert FLOPs of one layer over
    ``rows`` tokens."""
    proj = sum(2.0 * rows * k * n
               for k, n in attention_projections(md).values())
    ffn = sum(2.0 * rows * md["top_k"] * k * n
              for k, n in expert_projections(md).values())
    return proj + ffn + 2.0 * rows * md["d"] * md["experts"]


def decode_flops(md: dict, contexts: list[int]) -> float:
    """Model FLOPs one decode step needs for its real slots: top-k
    experts a row, attention over each layer's live keys."""
    rows, hq, hd = len(contexts), md["heads"], md["hd"]
    attn = sum(4.0 * hq * hd * (min(c, md["window"]) if sliding else c)
               for sliding in _kinds(md) for c in contexts)
    return (md["layers"] * _layer_flops(md, rows) + attn
            + 2.0 * rows * md["d"] * md["vocab"])


def prefill_flops(md: dict, length: int) -> float:
    """Model FLOPs a prefill of ``length`` real tokens needs: every
    projection and top-k experts over the prompt, causal (or windowed)
    attention, one row of the head."""
    hq, hd, win = md["heads"], md["hd"], md["window"]
    full = length * (length + 1) / 2
    n = min(length, win)
    slid = n * (n + 1) / 2 + (length - n) * win
    attn = sum(4.0 * hq * hd * (slid if sliding else full)
               for sliding in _kinds(md))
    return (md["layers"] * _layer_flops(md, length) + attn
            + 2.0 * md["d"] * md["vocab"])


def tiny(cfg: dict) -> dict:
    """The configuration at a one-period, 256-wide size the CPU test run
    can hold, its kinds kept (sliding and full layers in their order,
    YaRN, qk_norm, routed experts, untied head), with a 32-token window
    that the test traffic's contexts pass.  A configuration file of
    another family (no ``layer_types``) is given this family's kinds:
    three sliding layers, then a full one with YaRN over 32 positions."""
    if "layer_types" not in cfg:
        theta = float(cfg.get("rope_theta", 10000.0))
        cfg = dict(cfg, layer_types=[SLIDING] * 3 + [FULL],
                   mlp_layer_types=["sparse"] * 4, norm_topk_prob=True,
                   tie_word_embeddings=False, rope_parameters={
                       SLIDING: {"rope_type": "default", "rope_theta": theta},
                       FULL: {"rope_type": "yarn", "rope_theta": theta,
                              "factor": 4.0,
                              "original_max_position_embeddings": 32,
                              "beta_fast": 32.0, "beta_slow": 1.0,
                              "attention_factor": 1.0 + 0.1 * math.log(4)}})
    n = _period(dict(cfg, num_hidden_layers=len(cfg["layer_types"])))
    return dict(cfg, hidden_size=256, num_attention_heads=4,
                num_key_value_heads=2, head_dim=64, num_hidden_layers=n,
                layer_types=cfg["layer_types"][:n],
                mlp_layer_types=cfg["mlp_layer_types"][:n],
                num_experts=8, num_experts_per_tok=2,
                moe_intermediate_size=128, intermediate_size=512,
                sliding_window=32, vocab_size=1000)
