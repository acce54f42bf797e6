"""Linear (dense) operator.

Capability parity with reference src/ops/linear.cc (1,617 LoC) +
src/ops/kernels/linear_kernels.cu (cublasGemmEx + fused activation). On TPU
the matmul maps directly onto the MXU via XLA dot_general and the activation
fuses for free. Tensor-parallel variants (column/row sharded kernels) are
expressed as NamedSharding on the weight (see flexflow_tpu/parallel), not as a
different kernel.

Weight layout: kernel [in_dim, out_dim] (activations @ kernel), bias [out_dim].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from flexflow_tpu.core.layer import WeightSpec
from flexflow_tpu.core.initializer import (
    default_bias_initializer,
    default_kernel_initializer,
)
from flexflow_tpu.ffconst import ActiMode, DataType, OpType
from flexflow_tpu.ops.base import OpImpl, register_op


def apply_activation(x, mode: ActiMode):
    if mode == ActiMode.AC_MODE_NONE:
        return x
    if mode == ActiMode.AC_MODE_RELU:
        return jax.nn.relu(x)
    if mode == ActiMode.AC_MODE_SIGMOID:
        return jax.nn.sigmoid(x)
    if mode == ActiMode.AC_MODE_TANH:
        return jnp.tanh(x)
    if mode == ActiMode.AC_MODE_GELU:
        return jax.nn.gelu(x, approximate=False)  # torch.nn.GELU parity
    raise ValueError(mode)


@register_op
class Linear(OpImpl):
    op_type = OpType.LINEAR
    quant_aware = True

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        (shape, dtype) = input_specs[0]
        out_dim = attrs["out_dim"]
        out_dtype = attrs.get("data_type") or dtype
        if attrs.get("keep_f32_logits"):
            out_dtype = DataType.DT_FLOAT   # forward emits f32 logits
        return [(tuple(shape[:-1]) + (out_dim,), out_dtype)]

    @staticmethod
    def weight_specs(attrs, input_specs):
        (shape, dtype) = input_specs[0]
        in_dim = shape[-1]
        out_dim = attrs["out_dim"]
        wdtype = attrs.get("data_type") or dtype
        if "tied_to" in attrs:      # the embedding's table is its kernel
            assert not attrs.get("use_bias", True), "a tied head has no bias"
            return []
        specs = [
            WeightSpec("kernel", (in_dim, out_dim), wdtype,
                       attrs.get("kernel_initializer")
                       or default_kernel_initializer(),
                       sharding_dims=(None, "model")),
        ]
        if attrs.get("use_bias", True):
            specs.append(
                WeightSpec("bias", (out_dim,), wdtype,
                           attrs.get("bias_initializer")
                           or default_bias_initializer(),
                           sharding_dims=("model",)))
        return specs

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        from flexflow_tpu.quant import is_quantized, qmatmul

        x = inputs[0]
        compute_dtype = ctx.compute_dtype or x.dtype
        if "table" in params:
            # tied to an embedding (FFModel._apply_layer hands the table
            # over): x . table^T on the one array, float32 logits
            from flexflow_tpu.quant import qmatmul_t

            return [qmatmul_t(
                x, params["table"], compute_dtype,
                jnp.float32 if attrs.get("keep_f32_logits") else None)]
        kernel = params["kernel"]
        out_dtype = None
        if attrs.get("keep_f32_logits"):
            # logits heads keep the gemm's f32 ACCUMULATOR instead of
            # rounding to bf16: exact bf16 ties between near-equal logits
            # made greedy argmax flip between the width-1 decode and
            # width-k verify programs (XLA tiles them differently) on
            # close distributions. Only the result dtype changes — the
            # gemm operands stay bf16, so the MXU cost is unchanged and
            # the cast skipped was the last op before argmax/sampling.
            out_dtype = jnp.float32
        if is_quantized(kernel) or compute_dtype != jnp.float64:
            y = qmatmul(x, kernel, compute_dtype, out_dtype=out_dtype)
        else:
            y = jax.lax.dot_general(
                x.astype(compute_dtype), kernel.astype(compute_dtype),
                dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float64,
            ).astype(compute_dtype)
        if attrs.get("use_bias", True):
            y = y + params["bias"].astype(compute_dtype)
        return [apply_activation(y, attrs.get("activation",
                                              ActiMode.AC_MODE_NONE))]
