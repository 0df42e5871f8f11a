"""Per-layer quantization-sensitivity profiling over a calibration stream.

For each decoder layer ``i`` and each candidate scheme ``c`` the profiler
runs the model with fake-quant (STE numerics — exactly the rounding the
packed kernels apply) on layer ``i`` ONLY, everything else fp, and scores
the damage against the fp logits:

  * ``mse`` — mean squared logit error,
  * ``kl``  — mean KL(softmax(fp) || softmax(quantized)), the
    accuracy-proxy the search optimizes (standard mixed-precision
    sensitivity proxy, cf. 1808.04752 §V).

Each (layer, scheme) cell is one jitted forward per calibration batch —
L x C traces of the smoke-scale model, which is what the planner targets.

The profiler also records each layer's output activation range with the
``core/calibration.py`` observers (min/max, EMA or percentile over the
same stream): wide-range layers are exactly where low-bit local regions
clip, so the ranges ship in the profile for diagnosis and for freezing
LUT affine params offline.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import calibration, schemes
from repro.models import transformer
from repro.models.layers import NO_QUANT, PlanPolicy

from .plan import layer_name


@dataclasses.dataclass(frozen=True)
class SensitivityProfile:
    """``losses[layer_name][scheme_name] -> {"kl": ..., "mse": ...}`` plus
    per-layer calibrated output ranges."""
    losses: dict
    act_ranges: dict
    n_batches: int

    def loss(self, layer: str, scheme: str, metric: str = "kl") -> float:
        return self.losses[layer][scheme][metric]

    def to_dict(self) -> dict:
        return {"losses": self.losses, "act_ranges": self.act_ranges,
                "n_batches": self.n_batches}


def _metrics(fp_logits, q_logits) -> dict:
    fp = fp_logits.astype(jnp.float32)
    q = q_logits.astype(jnp.float32)
    mse = jnp.mean((fp - q) ** 2)
    p = jax.nn.softmax(fp, axis=-1)
    kl = jnp.sum(p * (jax.nn.log_softmax(fp, -1)
                      - jax.nn.log_softmax(q, -1)), axis=-1).mean()
    return {"mse": mse, "kl": kl}


def _one_hot_policy(n_layers: int, i: int, cand: schemes.QuantConfig,
                    mode: str = "qat") -> PlanPolicy:
    cfgs = tuple(cand if j == i else schemes.FP32 for j in range(n_layers))
    return PlanPolicy(mode, cfgs)


def profile_sensitivity(params, model_cfg, batches, candidates: dict,
                        *, observer: str = "minmax",
                        **observer_kw) -> SensitivityProfile:
    """Profile every (layer, candidate scheme) cell over ``batches``.

    ``batches``: list of forward-compatible batch dicts ({'tokens': ...});
    ``candidates``: ``{scheme_name: QuantConfig}``.
    """
    if model_cfg.n_enc_layers:
        raise ValueError("sensitivity profiling supports decoder-only "
                         "models (plans cover the decoder stack)")
    n = model_cfg.n_layers

    @jax.jit
    def fp_fn(p, b):
        return transformer.forward(p, model_cfg, b, policy=NO_QUANT,
                                   training=False)[0]

    fp_logits = [fp_fn(params, b) for b in batches]

    losses = {}
    for i in range(n):
        row = {}
        for sname, cand in candidates.items():
            pol = _one_hot_policy(n, i, cand)
            q_fn = jax.jit(lambda p, b: transformer.forward(
                p, model_cfg, b, policy=pol, training=False)[0])
            acc = {"mse": 0.0, "kl": 0.0}
            for b, fp in zip(batches, fp_logits):
                m = _metrics(fp, q_fn(params, b))
                acc = {k: acc[k] + float(v) for k, v in m.items()}
            row[sname] = {k: v / len(batches) for k, v in acc.items()}
        losses[layer_name(i)] = row

    ranges = layer_output_ranges(params, model_cfg, batches,
                                 kind=observer, **observer_kw)
    act_ranges = {layer_name(i): [float(lo), float(hi)]
                  for i, (lo, hi) in enumerate(ranges)}
    return SensitivityProfile(losses=losses, act_ranges=act_ranges,
                              n_batches=len(batches))


# ---------------------------------------------------------------------------
# KV-cache sensitivity: one-hot fake-quant of each layer's K/V stream
# ---------------------------------------------------------------------------

def profile_kv_sensitivity(params, model_cfg, batches, bits_options,
                           *, kv_group: int = 64) -> dict:
    """Per-layer cache-quantization damage over the calibration stream.

    For each decoder layer ``i`` and candidate cache bitwidth ``b`` the
    model runs with layer ``i``'s post-rope K/V rounded through the wire
    format (``QuantPolicy.kv_fq`` — exactly the grid the paged pool's
    scatter applies at decode), everything else fp, scored against the fp
    logits.  Returns ``{layer_name: {kv_label: {"kl", "mse"}}}`` keyed
    with :func:`repro.plan.costmodel.kv_label`; the fp option scores an
    exact 0.0 without a forward, and layers without a searchable cache
    (rglru, mamba2 — see :func:`repro.plan.costmodel.kv_searchable`)
    carry only that fp cell, mirroring ``kv_candidate_costs``.
    """
    from .costmodel import kv_label, kv_layer_options

    if model_cfg.n_enc_layers:
        raise ValueError("kv sensitivity profiling supports decoder-only "
                         "models (plans cover the decoder stack)")
    n = model_cfg.n_layers
    if model_cfg.head_dim % kv_group:
        raise ValueError(f"kv_group {kv_group} does not divide head_dim "
                         f"{model_cfg.head_dim}")

    @jax.jit
    def fp_fn(p, b):
        return transformer.forward(p, model_cfg, b, policy=NO_QUANT,
                                   training=False)[0]

    fp_logits = [fp_fn(params, b) for b in batches]
    fp_cfgs = (schemes.FP32,) * n

    losses = {}
    for i in range(n):
        row = {}
        for bits in kv_layer_options(model_cfg, i, bits_options):
            if bits is None:
                row[kv_label(bits)] = {"mse": 0.0, "kl": 0.0}
                continue
            kv = tuple(bits if j == i else None for j in range(n))
            pol = PlanPolicy("qat", fp_cfgs, kv_bits=kv, kv_group=kv_group)
            q_fn = jax.jit(lambda p, b, pol=pol: transformer.forward(
                p, model_cfg, b, policy=pol, training=False)[0])
            acc = {"mse": 0.0, "kl": 0.0}
            for b, fp in zip(batches, fp_logits):
                m = _metrics(fp, q_fn(params, b))
                acc = {k: acc[k] + float(v) for k, v in m.items()}
            row[kv_label(bits)] = {k: v / len(batches) for k, v in acc.items()}
        losses[layer_name(i)] = row
    return losses


# ---------------------------------------------------------------------------
# per-layer activation ranges (calibration observers over an unrolled pass)
# ---------------------------------------------------------------------------

def _iter_layer_params(params, model_cfg):
    """Yield (block_params, spec) per decoder layer, unstacking the scan."""
    dec = params["decoder"]
    p_len = len(model_cfg.pattern)
    for run in transformer.stack_runs(dec):
        for s in range(jax.tree.leaves(run)[0].shape[0]):
            for j, spec in enumerate(model_cfg.pattern):
                yield jax.tree.map(lambda a, s=s: a[s], run[j]), spec
    for t, blk in enumerate(dec["tail"]):
        yield blk, model_cfg.pattern[t % p_len]


def layer_output_ranges(params, model_cfg, batches, *, kind: str = "minmax",
                        **observer_kw) -> list:
    """Calibrated (lo, hi) of every decoder layer's output stream."""
    states = [calibration.init(kind, **observer_kw)
              for _ in range(model_cfg.n_layers)]
    for batch in batches:
        x, _ = transformer._embed_inputs(params, model_cfg, batch, NO_QUANT)
        if model_cfg.pos_embed == "learned":
            from repro.models import layers as _layers
            x = _layers.posembed_apply(params["pos"], x)
        x = x.astype(model_cfg.activation_dtype)
        for i, (blk, spec) in enumerate(_iter_layer_params(params,
                                                           model_cfg)):
            x, _, _ = transformer.block_apply(blk, x, spec, model_cfg,
                                              policy=NO_QUANT)
            states[i] = calibration.update(states[i], x)
    return [calibration.bounds(s) for s in states]
