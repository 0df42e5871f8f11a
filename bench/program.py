"""The system under test, built through the program's public API.

The only module of the benchmark that imports the program (``repro``,
under ``src/``).  It hands the program's registry and types to a
configuration's family (``bench/families/<family>/served.py``), which
turns the configuration file into the program's ``ModelConfig`` and the
seeded weights of its ``plain.draw`` into the program's parameter tree
(packed ``QWeight`` projections, f32 norms and embedding), and builds a
``Server`` over a paged 4-bit pool.
"""
from __future__ import annotations

import functools
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


@functools.lru_cache(maxsize=None)
def _repro() -> types.SimpleNamespace:
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro  # noqa: F401  (fails in a checkout without the program)
    from repro import configs
    from repro.kernels.ops import QWeight
    from repro.models.config import ModelConfig
    from repro import serve
    return types.SimpleNamespace(
        configs=configs, QWeight=QWeight, ModelConfig=ModelConfig,
        serve=serve, qweight=functools.partial(_qweight, QWeight))


def model_config(cfg: dict, family):
    """The program's ModelConfig for a configuration file."""
    return family.served.model_config(cfg, _repro())


def _qweight(QWeight, p: dict, bits: int, group: int):
    k = p["packed"].shape[-2] * 8 // bits
    return QWeight(packed=p["packed"], scale=p["scale"], zmin=p["zmin"],
                   bits=bits, group_size=group, k=k, n=p["packed"].shape[-1])


def params(w: dict, mc, family, *, bits: int = 4, group: int = 128) -> dict:
    """The program's parameter tree over the seeded arrays (no copies)."""
    return family.served.params(w, mc, _repro(), bits=bits, group=group)


def server(mc, p: dict, serving: dict, *, on_token=None):
    """A Server over a paged pool with the configuration's geometry."""
    serve = _repro().serve
    ecfg = serve.EngineConfig(
        max_len=serving["max_context"], kv_bits=serving["kv_bits"],
        kv_group=serving["kv_group"], weight_scheme=serving["weight_scheme"],
        fused_attention=serving["fused_attention"])
    pcfg = serve.PagedConfig(
        max_slots=serving["max_slots"], page_size=serving["page_size"],
        n_pages=serving["n_pages"], max_context=serving["max_context"])
    return serve.Server(mc, p, ecfg, pcfg, on_token=on_token)


def request_params(max_new_tokens: int):
    return _repro().serve.RequestParams(max_new_tokens=max_new_tokens)
