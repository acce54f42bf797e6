"""Constant / selection / free-parameter operators.

Lowering targets for the torch fx frontend's constant-folding interpreter
(torch/model.py): folded subgraphs (position-bias index matrices, causal
masks, arange/triu products) become CONSTANT nodes; tensor selections
become WHERE/COMPARE; ``Tensor.expand`` becomes BROADCAST_TO; and a bare
``nn.Parameter`` read (fx ``get_attr``, e.g. T5LayerNorm.weight) becomes a
trainable WEIGHT op — the reference PCG's Weight node (reference
src/ops/noop.cc NoOp/Input/Weight sources).
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from flexflow_tpu.core.layer import WeightSpec
from flexflow_tpu.ffconst import DataType, OpType
from flexflow_tpu.ops.base import OpImpl, register_op


@register_op
class Constant(OpImpl):
    """Embedded literal tensor (attrs: value nested-list, dtype, shape)."""

    op_type = OpType.CONSTANT

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        return [(tuple(attrs["shape"]), DataType(attrs["dtype"]))]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        val = np.asarray(attrs["value"],
                         dtype=DataType(attrs["dtype"]).to_jnp())
        return [jnp.asarray(val.reshape(tuple(attrs["shape"])))]


@register_op
class WeightParam(OpImpl):
    """Free-standing trainable parameter (attrs: shape, dtype)."""

    op_type = OpType.WEIGHT

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        return [(tuple(attrs["shape"]), DataType(attrs["dtype"]))]

    @staticmethod
    def weight_specs(attrs, input_specs):
        from flexflow_tpu.core.initializer import ConstantInitializer

        return [WeightSpec("weight", tuple(attrs["shape"]),
                           DataType(attrs["dtype"]),
                           attrs.get("initializer")
                           or ConstantInitializer(attrs.get("init", 1.0)))]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        return [params["weight"]]


@register_op
class Where(OpImpl):
    """out = where(cond, a, b), broadcast like jnp.where."""

    op_type = OpType.WHERE

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        (sc, _), (sa, da), (sb, _) = input_specs
        shape = tuple(jnp.broadcast_shapes(tuple(sc), tuple(sa), tuple(sb)))
        return [(shape, da)]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        return [jnp.where(inputs[0], inputs[1], inputs[2])]


_CMP = {
    "eq": jnp.equal, "ne": jnp.not_equal, "lt": jnp.less,
    "le": jnp.less_equal, "gt": jnp.greater, "ge": jnp.greater_equal,
}


@register_op
class Compare(OpImpl):
    """Elementwise comparison (attrs["cmp"] in eq/ne/lt/le/gt/ge); the
    second operand is a tensor input or attrs["scalar"]. Output bool."""

    op_type = OpType.COMPARE

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        if len(input_specs) == 2:
            (s0, _), (s1, _) = input_specs
            shape = tuple(jnp.broadcast_shapes(tuple(s0), tuple(s1)))
        else:
            shape = tuple(input_specs[0][0])
        return [(shape, DataType.DT_BOOLEAN)]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        rhs = inputs[1] if len(inputs) > 1 else attrs["scalar"]
        return [_CMP[attrs["cmp"]](inputs[0], rhs)]


@register_op
class BroadcastTo(OpImpl):
    """Materialized broadcast (torch Tensor.expand)."""

    op_type = OpType.BROADCAST_TO

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        (s, d) = input_specs[0]
        return [(tuple(attrs["shape"]), d)]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        return [jnp.broadcast_to(inputs[0], tuple(attrs["shape"]))]
