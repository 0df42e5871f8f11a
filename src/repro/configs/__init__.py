"""Architecture config registry (``--arch <id>``).

One module per assigned architecture exports ``CONFIG`` (the exact published
configuration) and ``SMOKE`` (a reduced same-family config for CPU tests).
The paper's own CNNs (AlexNet / VGG-16) live in ``alexnet.py`` / ``vgg16.py``
as :class:`repro.models.convnet.ConvConfig` and feed the accuracy benchmarks;
they are not part of the 40 dry-run cells.

``get(name)`` accepts both hyphen and underscore spellings.
"""
from __future__ import annotations

import importlib

from repro.models.config import ModelConfig

ARCHS = (
    "whisper-large-v3",
    "granite-3-2b",
    "llama3.2-1b",
    "qwen3-8b",
    "qwen3-14b",
    "qwen3-moe-235b-a22b",
    "llama4-scout-17b-a16e",
    "internvl2-1b",
    "mamba2-130m",
    "recurrentgemma-2b",
    "mellum2-12b-a2.5b",
)


def _modname(name: str) -> str:
    return name.replace("-", "_").replace(".", "_")


def _load(name: str):
    key = _modname(name)
    for arch in ARCHS:
        if _modname(arch) == key:
            return importlib.import_module(f"repro.configs.{key}")
    raise KeyError(f"unknown arch {name!r}; known: {list(ARCHS)}")


def get(name: str) -> ModelConfig:
    """Full published config for ``--arch <name>``."""
    return _load(name).CONFIG


def smoke(name: str) -> ModelConfig:
    """Reduced same-family config for CPU smoke tests."""
    return _load(name).SMOKE


def names() -> tuple:
    return ARCHS
