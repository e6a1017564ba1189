from generativeaiexamples_tpu.models.llama import (
    PRESETS,
    LlamaConfig,
    forward,
    init_params,
)
from generativeaiexamples_tpu.models.sampling import sample_tokens

__all__ = [
    "LlamaConfig",
    "PRESETS",
    "forward",
    "init_params",
    "sample_tokens",
]
