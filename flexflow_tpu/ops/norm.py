"""Normalization operators.

Capability parity with reference src/ops/layer_norm.cc (946),
residual_layer_norm.cc (851), add_bias_residual_layer_norm.cc (814),
rms_norm.cc (491), residual_rms_norm.cc (514), batch_norm.cc (322),
sigmoid_silu_multi.cc (401). All are bandwidth-bound elementwise+reduce
patterns that XLA fuses well on TPU; no custom kernels needed.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from flexflow_tpu.core.layer import WeightSpec
from flexflow_tpu.ffconst import DataType, OpType
from flexflow_tpu.ops.base import OpImpl, register_op


def _layer_norm(x, gamma, beta, eps, axes):
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    if gamma is not None:
        y = y * gamma
    if beta is not None:
        y = y + beta
    return y


def _rms_norm(x, weight, eps):
    # Compute in fp32 for stability regardless of activation dtype
    # (matches HF LLaMA semantics the serving oracle aligns against).
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y.astype(dtype) * weight).astype(dtype)


@register_op
class LayerNorm(OpImpl):
    op_type = OpType.LAYERNORM

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        return [input_specs[0]]

    @staticmethod
    def weight_specs(attrs, input_specs):
        if not attrs.get("elementwise_affine", True):
            return []
        (shape, dtype) = input_specs[0]
        axes = attrs["axes"]
        norm_shape = tuple(shape[a] for a in axes)
        from flexflow_tpu.core.initializer import ConstantInitializer, ZeroInitializer

        specs = [WeightSpec("gamma", norm_shape, dtype, ConstantInitializer(1.0))]
        if attrs.get("use_bias", True):
            specs.append(WeightSpec("beta", norm_shape, dtype, ZeroInitializer()))
        return specs

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        x = inputs[0]
        axes = tuple(attrs["axes"])
        gamma = params.get("gamma")
        beta = params.get("beta")
        return [_layer_norm(x, gamma, beta, attrs.get("eps", 1e-5), axes)]


@register_op
class ResidualLayerNorm(OpImpl):
    """out = layer_norm(x + residual1 [+ residual2]); also returns the sum.

    Reference src/ops/residual_layer_norm.cc: returns (added, normed).
    """

    op_type = OpType.RESIDUAL_LAYERNORM

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        return [input_specs[0], input_specs[0]]

    @staticmethod
    def weight_specs(attrs, input_specs):
        return LayerNorm.weight_specs(attrs, input_specs)

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        added = inputs[0]
        for r in inputs[1:]:
            added = added + r
        normed = _layer_norm(added, params.get("gamma"), params.get("beta"),
                             attrs.get("eps", 1e-5), tuple(attrs["axes"]))
        return [added, normed]


@register_op
class AddBiasResidualLayerNorm(OpImpl):
    """out = layer_norm(x + attn_bias + residual); returns (added, normed).

    Reference src/ops/add_bias_residual_layer_norm.cc (OPT/Falcon/MPT fusion).
    """

    op_type = OpType.ADD_BIAS_RESIDUAL_LAYERNORM

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        return [input_specs[0], input_specs[0]]

    @staticmethod
    def weight_specs(attrs, input_specs):
        from flexflow_tpu.core.initializer import ZeroInitializer

        (shape, dtype) = input_specs[0]
        axes = attrs["axes"]
        norm_shape = tuple(shape[a] for a in axes)
        specs = [WeightSpec("attn_bias", (shape[-1],), dtype, ZeroInitializer())]
        specs += LayerNorm.weight_specs(attrs, input_specs)
        return specs

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        x, residual = inputs[0], inputs[1]
        added = x + params["attn_bias"] + residual
        normed = _layer_norm(added, params.get("gamma"), params.get("beta"),
                             attrs.get("eps", 1e-5), tuple(attrs["axes"]))
        return [added, normed]


def _rms_norm_unit_offset(x, g, eps, out_dtype):
    """``x / sqrt(mean(x^2) + eps) * (1 + g)`` (EvaByte's
    ``norm_add_unit_offset``): the stored weight is the offset from one.
    Computed in float32 whole, rounded once, to ``out_dtype``."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * (1.0 + g.astype(jnp.float32))
    return y.astype(out_dtype)


@register_op
class RMSNorm(OpImpl):
    """``attrs["unit_offset"]`` (absent: the plain norm, as it was): the
    weight is an offset from one, zero-initialised, and the output takes
    ``attrs["data_type"]`` where that is set (a float32 residual stream
    normed into the compute dtype)."""

    op_type = OpType.RMS_NORM

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        (shape, dtype) = input_specs[0]
        return [(shape, attrs.get("data_type") or dtype)]

    @staticmethod
    def weight_specs(attrs, input_specs):
        from flexflow_tpu.core.initializer import (ConstantInitializer,
                                                   ZeroInitializer)

        (shape, dtype) = input_specs[0]
        if attrs.get("unit_offset"):
            return [WeightSpec("weight", (attrs.get("dim", shape[-1]),),
                               DataType.DT_FLOAT, ZeroInitializer())]
        return [WeightSpec("weight", (attrs.get("dim", shape[-1]),), dtype,
                           attrs.get("initializer")
                           or ConstantInitializer(1.0))]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        if attrs.get("unit_offset"):
            dt = attrs.get("data_type")
            return [_rms_norm_unit_offset(
                inputs[0], params["weight"], attrs.get("eps", 1e-6),
                inputs[0].dtype if dt is None else dt.to_jnp())]
        return [_rms_norm(inputs[0], params["weight"], attrs.get("eps", 1e-6))]


@register_op
class ResidualRMSNorm(OpImpl):
    """Returns (x + residual, rms_norm(x + residual)).

    Reference src/ops/residual_rms_norm.cc (LLaMA block fusion).
    """

    op_type = OpType.RESIDUAL_RMS_NORM

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        return [input_specs[0], input_specs[0]]

    @staticmethod
    def weight_specs(attrs, input_specs):
        return RMSNorm.weight_specs(attrs, input_specs)

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        added = inputs[0] + inputs[1]
        return [added, _rms_norm(added, params["weight"], attrs.get("eps", 1e-6))]


@register_op
class SigmoidSiluMulti(OpImpl):
    """silu(x1) * x2 — the SwiGLU gate fusion (reference sigmoid_silu_multi.cc)."""

    op_type = OpType.SIGMOID_SILU_MULTI

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        return [input_specs[0]]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        return [jax.nn.silu(inputs[0]) * inputs[1]]


@register_op
class BatchNorm(OpImpl):
    """Batch normalization over NCHW input (reference src/ops/batch_norm.cc).

    Running statistics live in op state (threaded via ctx.state_* like KV
    caches) so the forward stays pure.
    """

    op_type = OpType.BATCHNORM

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        return [input_specs[0]]

    @staticmethod
    def weight_specs(attrs, input_specs):
        from flexflow_tpu.core.initializer import ConstantInitializer, ZeroInitializer

        (shape, dtype) = input_specs[0]
        c = shape[1]
        if not attrs.get("relu", False) and not attrs.get("affine", True):
            return []
        return [
            WeightSpec("scale", (c,), dtype, ConstantInitializer(1.0)),
            WeightSpec("bias", (c,), dtype, ZeroInitializer()),
        ]

    @staticmethod
    def init_state(attrs, input_specs):
        import numpy as np

        (shape, dtype) = input_specs[0]
        c = shape[1]
        return {
            "running_mean": jnp.zeros((c,), jnp.float32),
            "running_var": jnp.ones((c,), jnp.float32),
        }

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        x = inputs[0]
        eps = attrs.get("eps", 1e-5)
        momentum = attrs.get("momentum", 0.1)
        reduce_axes = (0, 2, 3) if x.ndim == 4 else (0,)
        bshape = (1, -1, 1, 1) if x.ndim == 4 else (1, -1)
        state = ctx.state_in.get(ctx.layer_name)
        if ctx.training or state is None:
            # statistics in f32: a bf16 reduction accumulator over
            # B*H*W-sized channels loses the mean outright. One-pass form
            # (E[x^2] - mean^2): both reductions fuse into the producing
            # conv's epilogue instead of forcing a second activation read
            # the two-pass jnp.var form needs. The raw one-pass form
            # cancels catastrophically when |mean| >> std, so statistics
            # are computed about the RUNNING mean c (one pass still:
            # E[(x-c)^2] - (mean-c)^2) — the cancellation then scales
            # with the batch-to-running drift, which shrinks as training
            # stabilizes, exactly when tight precision starts mattering.
            xf = x.astype(jnp.float32)
            c = (state["running_mean"].reshape(bshape)
                 if state is not None else jnp.float32(0.0))
            xs = xf - c
            dmean = jnp.mean(xs, axis=reduce_axes)
            mean = dmean + (state["running_mean"] if state is not None
                            else 0.0)
            var = jnp.maximum(
                jnp.mean(jnp.square(xs), axis=reduce_axes)
                - jnp.square(dmean), 0.0)
            if state is not None:
                ctx.state_out[ctx.layer_name] = {
                    "running_mean": (1 - momentum) * state["running_mean"]
                    + momentum * mean,
                    "running_var": (1 - momentum) * state["running_var"]
                    + momentum * var,
                }
        else:
            mean = state["running_mean"]
            var = state["running_var"]
        # fold normalization + affine into one scale/shift in f32, then a
        # single fused multiply-add pass over the activation in its dtype
        rstd = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
        scale = rstd
        shift = -mean.astype(jnp.float32) * rstd
        if "scale" in params:
            g = params["scale"].astype(jnp.float32)
            scale = rstd * g
            shift = shift * g + params["bias"].astype(jnp.float32)
        y = x * scale.astype(x.dtype).reshape(bshape) \
            + shift.astype(x.dtype).reshape(bshape)
        if attrs.get("relu", True):
            y = jax.nn.relu(y)
        return [y]
