"""Reduction / indexing operators: reduce_sum, reduce_mean, mean, gather, topk,
arg_topk (reference src/ops/{reduce,mean,gather,topk,arg_topk}.cc)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from flexflow_tpu.ffconst import DataType, OpType
from flexflow_tpu.ops.base import OpImpl, register_op, register_op_as


def _reduced_shape(shape, axes, keepdims):
    axes = tuple(a % len(shape) for a in axes)
    if keepdims:
        return tuple(1 if i in axes else d for i, d in enumerate(shape))
    return tuple(d for i, d in enumerate(shape) if i not in axes)


@register_op_as(OpType.REDUCE_SUM, OpType.REDUCE_MEAN)
class Reduce(OpImpl):
    op_type = OpType.REDUCE_SUM

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        (s, d) = input_specs[0]
        return [(_reduced_shape(s, attrs["axes"], attrs.get("keepdims", False)), d)]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        fn = jnp.sum if attrs["op_type"] == OpType.REDUCE_SUM else jnp.mean
        return [fn(inputs[0], axis=tuple(attrs["axes"]),
                   keepdims=attrs.get("keepdims", False))]


@register_op
class Mean(OpImpl):
    op_type = OpType.MEAN

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        (s, d) = input_specs[0]
        return [(_reduced_shape(s, attrs["dims"], attrs.get("keepdims", False)), d)]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        return [jnp.mean(inputs[0], axis=tuple(attrs["dims"]),
                         keepdims=attrs.get("keepdims", False))]


@register_op
class Gather(OpImpl):
    """Gather along a dim with an index tensor (reference src/ops/gather.cc,
    torch.gather semantics)."""

    op_type = OpType.GATHER

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        (_si, di) = input_specs[0]
        (sidx, _didx) = input_specs[1]
        return [(sidx, di)]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        x, idx = inputs
        axis = attrs["dim"]
        return [jnp.take_along_axis(x, idx.astype(jnp.int32), axis=axis)]


@register_op
class TopK(OpImpl):
    """Returns (values, indices) of the top-k along the last dim
    (reference src/ops/topk.cc)."""

    op_type = OpType.TOPK

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        (s, d) = input_specs[0]
        k = attrs["k"]
        out_shape = tuple(s[:-1]) + (k,)
        return [(out_shape, d), (out_shape, DataType.DT_INT32)]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        # On the scores as ``[rows, E]``: XLA:TPU sorts one ``[8, 128]`` slab
        # of the leading dimensions at a time, so a pass's ``[32, 8, 128]``
        # took it 75 us where ``[256, 128]`` takes 4.6 (PERF.md 6, PR 59).
        x, k = inputs[0], attrs["k"]
        out_shape = (*x.shape[:-1], k)
        values, indices = jax.lax.top_k(x.reshape(-1, x.shape[-1]), k)
        return [values.reshape(out_shape),
                indices.reshape(out_shape).astype(jnp.int32)]


@register_op
class ArgTopK(OpImpl):
    """Top-k indices only; optional speculative-decoding variant also returns
    probabilities (reference src/ops/arg_topk.cc)."""

    op_type = OpType.ARG_TOPK

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        (s, _d) = input_specs[0]
        k = attrs["k"]
        out_shape = tuple(s[:-1]) + (k,)
        if attrs.get("speculative_decoding", False):
            return [(out_shape, DataType.DT_FLOAT), (out_shape, DataType.DT_INT32)]
        return [(out_shape, DataType.DT_INT32)]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        x = inputs[0]
        values, indices = jax.lax.top_k(x, attrs["k"])  # always sorted on TPU
        if attrs.get("speculative_decoding", False):
            probs = jax.nn.softmax(x, axis=-1)
            p = jnp.take_along_axis(probs, indices, axis=-1)
            return [p, indices.astype(jnp.int32)]
        return [indices.astype(jnp.int32)]
