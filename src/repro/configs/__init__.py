"""Config registry: --arch <id> resolves here."""
from .base import ModelConfig
from .gemma2_27b import CONFIG as GEMMA2_27B
from .granite_20b import CONFIG as GRANITE_20B
from .granite_moe_3b_a800m import CONFIG as GRANITE_MOE_3B_A800M
from .jamba_v01_52b import CONFIG as JAMBA_V01_52B
from .mistral_large_123b import CONFIG as MISTRAL_LARGE_123B
from .moonlight_16b_a3b import CONFIG as MOONLIGHT_16B_A3B
from .qwen2_vl_7b import CONFIG as QWEN2_VL_7B
from .qwen3_moe_235b_a22b import CONFIG as QWEN3_MOE_235B_A22B
from .seamless_m4t_large_v2 import CONFIG as SEAMLESS_M4T_LARGE_V2
from .transformer_100m import CONFIG as TRANSFORMER_100M
from .xlstm_350m import CONFIG as XLSTM_350M
from .yi_34b import CONFIG as YI_34B

REGISTRY = {c.name: c for c in [
    MISTRAL_LARGE_123B, SEAMLESS_M4T_LARGE_V2, GEMMA2_27B, GRANITE_20B,
    QWEN3_MOE_235B_A22B, XLSTM_350M, YI_34B, GRANITE_MOE_3B_A800M,
    QWEN2_VL_7B, JAMBA_V01_52B, TRANSFORMER_100M, MOONLIGHT_16B_A3B,
]}

ASSIGNED = [c.name for c in [
    MISTRAL_LARGE_123B, SEAMLESS_M4T_LARGE_V2, GEMMA2_27B, GRANITE_20B,
    QWEN3_MOE_235B_A22B, XLSTM_350M, YI_34B, GRANITE_MOE_3B_A800M,
    QWEN2_VL_7B, JAMBA_V01_52B,
]]

# assigned input shapes: (seq_len, global_batch, kind)
SHAPES = {
    "train_4k": (4096, 256, "train"),
    "prefill_32k": (32768, 32, "prefill"),
    "decode_32k": (32768, 128, "decode"),
    "long_500k": (524288, 1, "decode"),
}


def get_config(name: str) -> ModelConfig:
    if name not in REGISTRY:
        raise KeyError(f"unknown arch '{name}'; available: {sorted(REGISTRY)}")
    return REGISTRY[name]


__all__ = ["ModelConfig", "REGISTRY", "ASSIGNED", "SHAPES", "get_config"]
