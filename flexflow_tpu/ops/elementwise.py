"""Elementwise unary/binary/scalar operators.

Capability parity with reference src/ops/element_unary.cc (875 LoC) and
element_binary.cc (1,163 LoC): broadcast-aware binary ops, unary activations,
scalar ops. On TPU these are single XLA HLO ops the compiler fuses into
neighbors; there is nothing to hand-write.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from flexflow_tpu.ffconst import DataType, OpType
from flexflow_tpu.ops.base import OpImpl, register_op_as


def _broadcast_shape(a, b):
    return tuple(jnp.broadcast_shapes(tuple(a), tuple(b)))


_BINARY_FNS = {
    OpType.EW_ADD: jnp.add,
    OpType.EW_SUB: jnp.subtract,
    OpType.EW_MUL: jnp.multiply,
    OpType.EW_DIV: jnp.divide,
    OpType.EW_MAX: jnp.maximum,
    OpType.EW_MIN: jnp.minimum,
}

_UNARY_FNS = {
    OpType.RELU: jax.nn.relu,
    OpType.SIGMOID: jax.nn.sigmoid,
    OpType.TANH: jnp.tanh,
    OpType.ELU: jax.nn.elu,
    # Exact (erf) form — matches torch.nn.GELU() which the HF alignment
    # oracle uses; the tanh approximation is selected via attrs["approximate"].
    OpType.GELU: lambda x: jax.nn.gelu(x, approximate=False),
    OpType.EXP: jnp.exp,
    OpType.SIN: jnp.sin,
    OpType.COS: jnp.cos,
    OpType.RSQRT: jax.lax.rsqrt,
    OpType.IDENTITY: lambda x: x,
}


@register_op_as(*_BINARY_FNS.keys())
class ElementBinary(OpImpl):
    op_type = OpType.EW_ADD  # representative; registered for all binary types

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        (s0, d0), (s1, _d1) = input_specs
        return [(_broadcast_shape(s0, s1), d0)]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        fn = _BINARY_FNS[attrs["op_type"]]
        return [fn(inputs[0], inputs[1])]


@register_op_as(*_UNARY_FNS.keys())
class ElementUnary(OpImpl):
    op_type = OpType.RELU

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        return [input_specs[0]]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        if attrs["op_type"] == OpType.GELU and attrs.get("approximate", False):
            return [jax.nn.gelu(inputs[0], approximate=True)]
        fn = _UNARY_FNS[attrs["op_type"]]
        return [fn(inputs[0])]


@register_op_as(OpType.POW)
class Pow(OpImpl):
    op_type = OpType.POW

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        return [input_specs[0]]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        return [jnp.power(inputs[0], attrs["exponent"])]


_SCALAR_FNS = {
    OpType.SCALAR_MULTIPLY: lambda x, s: x * s,
    OpType.SCALAR_ADD: lambda x, s: x + s,
    OpType.SCALAR_SUB: lambda x, s: x - s,
    OpType.SCALAR_TRUE_DIV: lambda x, s: x / s,
}


@register_op_as(*_SCALAR_FNS.keys())
class ScalarOp(OpImpl):
    op_type = OpType.SCALAR_MULTIPLY

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        return [input_specs[0]]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        fn = _SCALAR_FNS[attrs["op_type"]]
        x = inputs[0]
        if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype.itemsize < 4:
            # in float32, rounded once: a 16-bit scalar would be rounded
            # first (0.22 is 0.2197 in bfloat16, 0.1% off on every term)
            return [fn(x.astype(jnp.float32), attrs["scalar"]).astype(
                x.dtype)]
        return [fn(x, attrs["scalar"])]
