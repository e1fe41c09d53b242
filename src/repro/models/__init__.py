"""Unified model zoo: one config system covering dense GQA, SWA hybrids,
MLA+MoE, classic MoE, Mamba-2 SSD, RG-LRU hybrids, enc-dec and VLM
backbones, and Mamba-2/attention hybrids (the registered architectures)."""
from repro.models.config import (
    BlockKind,
    MLACfg,
    ModelConfig,
    MoECfg,
    RGLRUCfg,
    SSMCfg,
)
from repro.models.model import (
    cache_defs,
    decode_state_bytes,
    decode_step,
    forward_train,
    loss_fn,
    param_defs,
)
from repro.models.spec import (
    ParamDef,
    abstract,
    logical_axes,
    materialize,
    param_bytes,
    param_count,
)

__all__ = [
    "ModelConfig", "MoECfg", "MLACfg", "SSMCfg", "RGLRUCfg", "BlockKind",
    "param_defs", "cache_defs", "decode_state_bytes", "forward_train",
    "loss_fn", "decode_step",
    "ParamDef", "abstract", "logical_axes", "materialize",
    "param_count", "param_bytes",
]
