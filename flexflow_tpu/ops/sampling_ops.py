"""Token-selection operators for serving: ArgMax, Sampling (top-p), BeamTopK.

Capability parity with reference src/ops/argmax.cu (greedy, beam variant
returns parent ids), sampling.cu (top-p via sort + prefix-sum + draw, cub
based), beam_topk.cu (per-request beam_width children with parent tracking).
On TPU these are whole-array sort/scan patterns XLA compiles well; the
renormalized top-p draw is expressed with sorted cumulative probabilities.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from flexflow_tpu.ffconst import DataType, OpType
from flexflow_tpu.ops.base import OpImpl, register_op


@register_op
class ArgMax(OpImpl):
    op_type = OpType.ARGMAX

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        (s, _d) = input_specs[0]
        out_shape = tuple(s[:-1])
        if attrs.get("beam_search", False):
            # beam variant also returns parent ids (reference argmax.cc)
            return [(out_shape, DataType.DT_INT32), (out_shape, DataType.DT_INT32)]
        if attrs.get("confidence", False):
            # the pick and its float32 softmax probability (the head of a
            # block-diffusion model: FFModel.unmasking_head)
            return [(out_shape, DataType.DT_INT32), (out_shape, DataType.DT_FLOAT)]
        return [(out_shape, DataType.DT_INT32)]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        idx = jnp.argmax(inputs[0], axis=-1).astype(jnp.int32)
        if attrs.get("beam_search", False):
            return [idx, jnp.zeros_like(idx)]
        if attrs.get("confidence", False):
            # softmax(x)[argmax] = 1 / sum(exp(x - max)), in float32
            x = inputs[0].astype(jnp.float32)
            top = jnp.max(x, axis=-1, keepdims=True)
            return [idx, 1.0 / jnp.sum(jnp.exp(x - top), axis=-1)]
        return [idx]


def top_p_sampling(logits, key, top_p: float, temperature: float = 1.0):
    """Top-p (nucleus) sampling over the last dim.

    Same semantics as reference src/ops/sampling.cu: sort descending, keep the
    smallest prefix with cumulative prob >= top_p, renormalize, draw.
    """
    if temperature != 1.0:
        logits = logits / temperature
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    sorted_probs, sorted_idx = jax.lax.top_k(probs, probs.shape[-1])
    cum = jnp.cumsum(sorted_probs, axis=-1)
    # Keep tokens whose *preceding* cumulative mass is < top_p (always >=1 kept)
    keep = (cum - sorted_probs) < top_p
    filtered = jnp.where(keep, sorted_probs, 0.0)
    filtered = filtered / jnp.sum(filtered, axis=-1, keepdims=True)
    draw = jax.random.categorical(key, jnp.log(filtered + 1e-30), axis=-1)
    return jnp.take_along_axis(sorted_idx, draw[..., None], axis=-1)[..., 0]


@register_op
class Sampling(OpImpl):
    op_type = OpType.SAMPLING

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        (s, _d) = input_specs[0]
        return [(tuple(s[:-1]), DataType.DT_INT32)]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        key = ctx.layer_rng()
        if key is None:
            key = jax.random.PRNGKey(0)
        tok = top_p_sampling(inputs[0], key, attrs.get("top_p", 1.0),
                             attrs.get("temperature", 1.0))
        return [tok.astype(jnp.int32)]


@register_op
class BeamTopK(OpImpl):
    """Per-request beam expansion: top-`beam_width` children with parent ids.

    Reference src/ops/beam_topk.cu: given per-beam next-token distributions,
    pick the best beam_width (token, parent-beam) pairs per request. Here the
    input is [num_beams, vocab] log-probs (already beam-prior-weighted by the
    caller); output value/token/parent arrays of length max_width.
    """

    op_type = OpType.BEAM_TOPK

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        (s, _d) = input_specs[0]
        w = attrs["max_beam_width"]
        out = tuple(s[:-2]) + (w,)
        return [(out, DataType.DT_FLOAT), (out, DataType.DT_INT32),
                (out, DataType.DT_INT32)]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        logprobs = inputs[0]  # [..., num_beams, vocab]
        w = attrs["max_beam_width"]
        vocab = logprobs.shape[-1]
        flat = logprobs.reshape(logprobs.shape[:-2] + (-1,))
        values, idx = jax.lax.top_k(flat, w)
        parents = (idx // vocab).astype(jnp.int32)
        tokens = (idx % vocab).astype(jnp.int32)
        return [values, tokens, parents]
