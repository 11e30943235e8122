from repro.configs.base import (  # noqa: F401
    ALL_SHAPES,
    DECODE_32K,
    LONG_500K,
    MLAConfig,
    MoEConfig,
    ModelConfig,
    PREFILL_32K,
    RopeScaling,
    RunConfig,
    SHAPES,
    ShapeConfig,
    SSMConfig,
    TRAIN_4K,
    valid_cells,
)
from repro.configs.registry import ARCH_IDS, all_configs, get_config  # noqa: F401
