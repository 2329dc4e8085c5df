"""Architecture config registry. ``load_all()`` imports every config module
(side-effect registration); ``get_config(name)`` resolves one."""
from repro.configs.base import (ArchConfig, BlockKind, MLAConfig, MoEConfig,
                                RopeScaling, all_configs, get_config,
                                register)

_LOADED = False

_MODULES = (
    "bert_base",
    "deepseek_coder_33b",
    "qwen2_0_5b",
    "gemma2_2b",
    "granite_20b",
    "deepseek_v2_236b",
    "mixtral_8x22b",
    "paligemma_3b",
    "xlstm_125m",
    "hubert_xlarge",
    "recurrentgemma_9b",
    "deepseek_v2_lite",
)


def load_all() -> None:
    global _LOADED
    if _LOADED:
        return
    _LOADED = True
    import importlib
    for m in _MODULES:
        importlib.import_module(f"repro.configs.{m}")


ARCH_IDS = (
    "bert-base",
    "deepseek-coder-33b",
    "qwen2-0.5b",
    "gemma2-2b",
    "granite-20b",
    "deepseek-v2-236b",
    "mixtral-8x22b",
    "paligemma-3b",
    "xlstm-125m",
    "hubert-xlarge",
    "recurrentgemma-9b",
    "deepseek-v2-lite",
    "deepseek-v2-lite-ep4",
)

__all__ = ["ArchConfig", "BlockKind", "MLAConfig", "MoEConfig", "RopeScaling",
           "register",
           "get_config", "all_configs", "load_all", "ARCH_IDS"]
