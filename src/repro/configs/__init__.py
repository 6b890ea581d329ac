"""Architecture registry: ``get_config(arch_id)``.

Every entry cites its source in the module docstring.  ``--arch <id>`` in
the launchers resolves through this registry.
"""

from __future__ import annotations

import importlib

from repro.models.config import ModelConfig

_MODULES = {
    "llama3.2-3b": "llama3_2_3b",
    "whisper-tiny": "whisper_tiny",
    "granite-3-2b": "granite_3_2b",
    "h2o-danube-1.8b": "h2o_danube_1_8b",
    "mixtral-8x7b": "mixtral_8x7b",
    "dbrx-132b": "dbrx_132b",
    "llava-next-34b": "llava_next_34b",
    "xlstm-350m": "xlstm_350m",
    "zamba2-2.7b": "zamba2_2_7b",
    "starcoder2-7b": "starcoder2_7b",
    "granite-4.0-h-micro": "granite_4_0_h_micro",
    "llama2-7b": "llama2_7b_paper",
}

ALL_ARCHS = list(_MODULES)


def get_config(arch: str) -> ModelConfig:
    """The published configuration of architecture ``arch``."""
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    return importlib.import_module(f"repro.configs.{_MODULES[arch]}").CONFIG


__all__ = ["ALL_ARCHS", "ModelConfig", "get_config"]
