"""A loop region's ends, and the gate that reads its passes.

A LOOP REGION is a span of the layer list that runs ``steps`` times over
ONE set of weights (``FFModel.loop_begin`` / ``loop_end``): the graph holds
the span once, the parameters are the span's layers' as ever, and
``FFModel._run_graph`` runs it as one device loop (``lax.scan``) whose body
is the span, so a compiled serving program holds it once too. The pass
index is a traced scalar, ``OpContext.loop_step``; a plain-cache attention
layer inside the span keeps a cache plane a PASS,
``inc_attention.cache_plane``: pass ``t`` of the span's layer ``l`` reads
and writes plane ``base + t * planes + l`` of the one stack, and no other
pass's. The two ops here only mark the span's ends (shapes for the
builder); the region itself is ``core/model.LoopRegion``.

``LoopExit`` is the rule that picks, a token, the pass whose state the head
reads (a looped model's early-exit gate): every pass of every position is
computed whatever it says, because a later token attends every pass's plane
at every earlier position.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from flexflow_tpu.ffconst import OpType
from flexflow_tpu.ops.base import OpImpl, register_op

# The region's counter: ONE uint32 array in the model's op state, a row a
# phase of LOOP_PHASES, two fields: the layer-applications the steps' real
# tokens went through (tokens x the span's layers, added ONCE A PASS inside
# the device loop, so a pass that did not run for a row would not count) and
# those tokens themselves (added in the first pass), so that the two are of
# the same steps whenever they are read. Kept on the device and read with
# the registry's snapshot (as ops/moe.MOE_COUNTERS is).
LOOP_COUNTERS = "loop_layer_steps"
LOOP_PHASES = ("decode", "prefill")


def refuse_looped(model, what: str):
    """A model with a loop region (``FFModel.loop_region``) keeps a cache
    plane a pass and runs its span in a device loop: what walks the layer
    list a layer a cache, a stage a layer, or a draft a model, says so."""
    if getattr(model, "loop_region", None) is not None:
        raise NotImplementedError(
            f"{what} is not supported over a loop region: its span of "
            "layers runs several times over one set of weights inside one "
            "device loop and keeps a cache plane a pass (ops/loop.py), so a "
            "layer is not one use of one cache")


def init_counters(model):
    """Give ``model``'s loop region its counter, if it has a region and
    ``FFConfig.telemetry`` is on (FFModel.compile calls this)."""
    if model.loop_region is not None and model.config.telemetry:
        model.op_state[LOOP_COUNTERS] = jnp.zeros((len(LOOP_PHASES), 2),
                                                  jnp.uint32)


def count_pass(ctx, h, layers: int):
    """One pass of a region's span ran over the step's tokens ``h``
    [R, Q, ...]: add real tokens x ``layers``, and in the first pass the
    tokens, to the phase's row of ``LOOP_COUNTERS`` (FFModel._run_loop
    calls this inside the device loop, with the pass's op state and index
    in ``ctx``; without the counter, nothing)."""
    st = ctx.state_in.get(LOOP_COUNTERS) if ctx.state_in else None
    if st is None or ctx.batch_config is None:
        return
    from flexflow_tpu.ops.moe import _step_tokens

    _, valid, phase = _step_tokens(ctx, h)
    if phase in LOOP_PHASES:
        n = valid.sum().astype(jnp.uint32)
        ctx.state_out[LOOP_COUNTERS] = st.at[LOOP_PHASES.index(phase)].add(
            jnp.stack([n * jnp.uint32(layers),
                       jnp.where(ctx.loop_step == 0, n, jnp.uint32(0))]))


@register_op
class LoopBegin(OpImpl):
    """The span's input: the value before the first pass, then each pass's
    carried value (``FFModel._run_loop``). Alone it is the identity."""

    op_type = OpType.LOOP_BEGIN

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        return [input_specs[0]]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        return [inputs[0]]


@register_op
class LoopEnd(OpImpl):
    """The span's end: input 0 is what a pass hands the next (the shape and
    dtype of the span's input), the others are collected. Outputs: the last
    pass's carried value, then every pass's value of each collected tensor,
    ``[steps, ...]``. Run by ``FFModel._run_loop``, never alone."""

    op_type = OpType.LOOP_END

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        steps = attrs["steps"]
        return [input_specs[0]] + [((steps,) + tuple(shape), d)
                                   for shape, d in input_specs[1:]]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        raise NotImplementedError(
            "a loop region's end runs inside FFModel._run_graph's device "
            "loop, not as a layer of its own")


def exit_pdf(gates, threshold: float):
    """``gates`` [T, ...] (a pass's gate logit a token) -> (``p`` [T, ...],
    the probability that a token exits behind pass ``t``: ``lambda_t *
    prod_{j<t}(1 - lambda_j)``, the last pass taking what is left; ``exit``
    [...] int32, the first pass at which the running sum reaches
    ``threshold``, else the last), in float32."""
    lam = jax.nn.sigmoid(gates.astype(jnp.float32))
    T = lam.shape[0]
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    p = jnp.concatenate([(lam * before)[:-1], before[-1:]], axis=0)
    if T == 1:
        return p, jnp.zeros(lam.shape[1:], jnp.int32)
    crossed = jnp.cumsum(p, axis=0)[:-1] >= threshold
    first = jnp.argmax(crossed, axis=0).astype(jnp.int32)
    return p, jnp.where(crossed.any(axis=0), first, T - 1)


@register_op
class LoopExit(OpImpl):
    """Inputs: every pass's state ``h`` [T, R, Q, E] and gate logit ``g``
    [T, R, Q, 1] (a loop region's collected tensors). Output [R, Q, E]: each
    token's state behind the pass its gate exits at (``exit_pdf``;
    ``attrs["threshold"]``)."""

    op_type = OpType.LOOP_EXIT

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        (shape, d) = input_specs[0]
        return [(tuple(shape[1:]), d)]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        h, g = inputs
        _, at = exit_pdf(g[..., 0], attrs["threshold"])
        out = jnp.take_along_axis(h, at[None, :, :, None], axis=0)[0]
        return [out]
