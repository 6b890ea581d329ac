"""Model zoo: configs, layers, SSM blocks, and the unified Model."""

from repro.models.config import ModelConfig
from repro.models.transformer import Model

__all__ = ["ModelConfig", "Model"]
