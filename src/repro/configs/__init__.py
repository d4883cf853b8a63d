from .base import (ModelConfig, MoEConfig, ParallelConfig, RGLRUConfig,
                   SSMConfig)
from .registry import get, names, reduced

__all__ = [
    "ModelConfig", "MoEConfig", "SSMConfig", "RGLRUConfig", "ParallelConfig",
    "get", "reduced", "names",
]
