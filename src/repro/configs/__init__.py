"""Architecture registry: ``get_config(name)`` / ``get_smoke_config(name)``.

The paper's own models: CosmoFlow at three input widths and the 3D
U-Net. Each has a module ``repro.configs.<id>`` exporting the published
spec (source cited) and ``SMOKE``, a reduced same-family variant used by
the CPU tests.
"""
from __future__ import annotations

import importlib

from repro.configs.base import ConvNetConfig

_MODULES = {
    "cosmoflow-128": "cosmoflow",
    "cosmoflow-256": "cosmoflow",
    "cosmoflow-512": "cosmoflow",
    "unet3d-256": "unet3d",
}

PAPER_ARCHS = list(_MODULES)


def _module(name: str):
    return importlib.import_module(f"repro.configs.{_MODULES[name]}")


def get_config(name: str) -> ConvNetConfig:
    mod = _module(name)
    if name.startswith("cosmoflow-"):
        width = int(name.split("-")[1])
        return mod.config_for_width(width)
    return mod.CONFIG


def get_smoke_config(name: str) -> ConvNetConfig:
    return _module(name).SMOKE
