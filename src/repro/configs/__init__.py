"""Config registry: ``get_config(arch_id)`` resolves the exact assigned config.

Arch ids use the assignment spelling (e.g. ``llama4-maverick-400b-a17b``);
module names use underscores.
"""

from __future__ import annotations

import importlib

from repro.configs.base import SHAPE_NAMES, SHAPES, ArchConfig, ShapeConfig

_ARCH_MODULES = {
    "llama4-maverick-400b-a17b": "llama4_maverick_400b_a17b",
    "dbrx-132b": "dbrx_132b",
    "xlstm-350m": "xlstm_350m",
    "granite-8b": "granite_8b",
    "smollm-360m": "smollm_360m",
    "gemma-2b": "gemma_2b",
    "qwen3-32b": "qwen3_32b",
    "llama-3.2-vision-90b": "llama32_vision_90b",
    "jamba-v0.1-52b": "jamba_v01_52b",
    "musicgen-large": "musicgen_large",
    # Served on the chip, not part of the assigned matrix: the paper's
    # own serving backend and a hybrid small enough for one chip.
    "gemma3-4b-edge": "gemma3_4b_edge",
    "jamba2-3b": "jamba2_3b",
}

# The ten assigned architectures (dry-run matrix rows).
ARCH_NAMES = tuple(n for n in _ARCH_MODULES
                   if n not in ("gemma3-4b-edge", "jamba2-3b"))
ALL_ARCH_NAMES = tuple(_ARCH_MODULES)


def get_config(name: str) -> ArchConfig:
    """The published config for ``name``; ``<name>-reduced`` resolves to
    its tiny same-family test config (``ArchConfig.reduced``)."""
    base = name.removesuffix("-reduced")
    if base != name and base in _ARCH_MODULES:
        return get_config(base).reduced()
    if name not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_ARCH_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[name]}")
    return mod.CONFIG


def get_shape(name: str) -> ShapeConfig:
    return SHAPES[name]


def matrix_cells(include_skips: bool = False):
    """Yield (arch, shape) cells of the 10x4 assignment matrix.

    With ``include_skips=False`` (default) the 8 structural long_500k skips for
    pure full-attention archs are omitted (32 runnable cells).
    """
    for arch_name in ARCH_NAMES:
        cfg = get_config(arch_name)
        for shape_name in SHAPE_NAMES:
            if include_skips or cfg.supports_shape(shape_name):
                yield arch_name, shape_name


__all__ = [
    "ArchConfig",
    "ShapeConfig",
    "SHAPES",
    "SHAPE_NAMES",
    "ARCH_NAMES",
    "ALL_ARCH_NAMES",
    "get_config",
    "get_shape",
    "matrix_cells",
]
