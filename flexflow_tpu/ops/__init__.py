"""Operator implementations.

Each op is pure-jax/Pallas: shape inference + weight specs + forward function,
registered by OpType. Importing this package registers all ops.
"""

from flexflow_tpu.ops import base  # noqa: F401
from flexflow_tpu.ops import (  # noqa: F401
    attention,
    cache,
    constants,
    conv,
    dropout,
    elementwise,
    embedding,
    inc_attention,
    latent_attention,
    linear,
    loop,
    matmul,
    moe,
    norm,
    reduction_ops,
    sampling_ops,
    shape_ops,
    softmax,
)
from flexflow_tpu.parallel import ops as parallel_ops  # noqa: F401  (registers)
from flexflow_tpu.ops.base import OpContext, get_op_impl, register_op
