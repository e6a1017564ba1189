from generativeaiexamples_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    SEQ_AXIS,
    create_mesh,
    single_device_mesh,
)
from generativeaiexamples_tpu.parallel.ring_attention import (
    reference_attention,
    ring_attention,
)
from generativeaiexamples_tpu.parallel.sharding import (
    activation_spec,
    param_specs,
    shard_params,
    token_spec,
)

__all__ = [
    "DATA_AXIS",
    "SEQ_AXIS",
    "MODEL_AXIS",
    "create_mesh",
    "single_device_mesh",
    "param_specs",
    "activation_spec",
    "token_spec",
    "shard_params",
    "ring_attention",
    "reference_attention",
]
