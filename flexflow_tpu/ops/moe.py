"""Mixture-of-Experts operators: GroupBy, Aggregate, AggregateSpec, Experts.

Capability parity with reference src/ops/{group_by,aggregate,aggregate_spec,
experts}.cc. The reference routes tokens through CUDA scatter/gather buckets;
the TPU-idiomatic formulation is dense one-hot dispatch/combine einsums
(GShard-style), which keep shapes static for XLA and put the FLOPs on the MXU.
Expert parallelism shards the expert axis over the mesh "expert" axis
(see flexflow_tpu/parallel).

Those four are the reference's training formulation: a capacity that drops
tokens, and every token times every expert. ``MoeExperts`` below is the
serving path's: dropless SwiGLU experts over the step's real tokens only,
through the grouped kernel of kernels/moe.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from flexflow_tpu.core.layer import WeightSpec
from flexflow_tpu.core.initializer import default_kernel_initializer
from flexflow_tpu.ffconst import ActiMode, DataType, OpType
from flexflow_tpu.ops.base import OpImpl, register_op
from flexflow_tpu.ops.linear import apply_activation


def make_dispatch(assign, n_experts, capacity):
    """assign: [tokens, k] int expert ids -> dispatch one-hot
    [tokens, n_experts, capacity] respecting per-expert capacity (first-come)."""
    tokens, k = assign.shape
    onehot = jax.nn.one_hot(assign, n_experts, dtype=jnp.int32)  # [T,k,E]
    # position of each (token, slot) within its expert queue
    flat = onehot.reshape(tokens * k, n_experts)
    pos = jnp.cumsum(flat, axis=0) - flat  # [T*k, E]
    pos = pos.reshape(tokens, k, n_experts)
    in_cap = pos < capacity
    disp = (onehot * in_cap).astype(jnp.float32)  # [T,k,E]
    pos_capped = jnp.clip(pos, 0, capacity - 1)
    pos_onehot = jax.nn.one_hot(pos_capped, capacity, dtype=jnp.float32)  # [T,k,E,C]
    # [T, k, E, C]: 1 where token t's slot j goes to expert e position c
    return disp[..., None] * pos_onehot


@register_op
class GroupBy(OpImpl):
    """Route tokens into per-expert buckets (reference src/ops/group_by.cc).

    Inputs: data [tokens, d], assign [tokens, k] (top-k expert indices).
    Outputs: n_experts tensors of [capacity, d] (zero-padded).
    """

    op_type = OpType.GROUP_BY

    @staticmethod
    def _capacity(attrs, tokens):
        k = attrs["k"]
        n = attrs["n"]
        factor = attrs.get("alpha", 1.0)
        cap = int(max(1, factor * k * tokens / n))
        return cap

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        (sd, dd) = input_specs[0]
        tokens = sd[0]
        cap = GroupBy._capacity(attrs, tokens)
        return [((cap,) + tuple(sd[1:]), dd) for _ in range(attrs["n"])]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        data, assign = inputs[0], inputs[1].astype(jnp.int32)
        n, cap = attrs["n"], GroupBy._capacity(attrs, data.shape[0])
        disp = make_dispatch(assign, n, cap)  # [T,k,E,C]
        buckets = jnp.einsum("tkec,td->ecd", disp, data)
        return [buckets[e] for e in range(n)]


@register_op
class Aggregate(OpImpl):
    """Weighted combine of expert outputs back to token order
    (reference src/ops/aggregate.cc).

    Inputs: gate_preds [tokens, k], gate_assign [tokens, k],
    then n expert outputs [capacity, d]. Output: [tokens, d].
    """

    op_type = OpType.AGGREGATE

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        (sg, _dg) = input_specs[0]
        (se, de) = input_specs[2]
        return [((sg[0],) + tuple(se[1:]), de)]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        gate_preds, gate_assign = inputs[0], inputs[1].astype(jnp.int32)
        experts = jnp.stack(inputs[2:], axis=0)  # [E, C, d]
        n, cap = experts.shape[0], experts.shape[1]
        disp = make_dispatch(gate_assign, n, cap)  # [T,k,E,C]
        combine = disp * gate_preds[..., None, None]
        out = jnp.einsum("tkec,ecd->td", combine, experts)
        return [out]


@register_op
class AggregateSpec(OpImpl):
    """Training-label variant of Aggregate (reference aggregate_spec.cc) —
    combines with the *true* gate assignment for auxiliary loss computation."""

    op_type = OpType.AGG_SPEC

    infer_output_specs = Aggregate.infer_output_specs
    forward = Aggregate.forward


@register_op
class Experts(OpImpl):
    """Fused MoE expert FFN batch for inference (reference src/ops/experts.cc
    1,176 / experts.cu 1,447: group tokens by expert, batched gemms).

    Inputs: x [tokens, d], indices [tokens, k], gate weights [tokens, k].
    Computes a one-layer expert FFN per expert and combines top-k outputs.
    """

    op_type = OpType.EXPERTS

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        (sx, dx) = input_specs[0]
        return [((sx[0], attrs["experts_output_dim_size"]), dx)]

    @staticmethod
    def weight_specs(attrs, input_specs):
        (sx, dx) = input_specs[0]
        n = attrs["num_experts"]
        d_in = attrs.get("experts_internal_dim_size", sx[-1])
        d_out = attrs["experts_output_dim_size"]
        init = attrs.get("kernel_initializer") or default_kernel_initializer()
        specs = [WeightSpec("kernel", (n, sx[-1], d_out), dx, init,
                            sharding_dims=("expert", None, None))]
        if attrs.get("use_bias", False):
            from flexflow_tpu.core.initializer import ZeroInitializer

            specs.append(WeightSpec("bias", (n, d_out), dx, ZeroInitializer(),
                                    sharding_dims=("expert", None)))
        return specs

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        x, idx, gates = inputs[0], inputs[1].astype(jnp.int32), inputs[2]
        n = attrs["num_experts"]
        start = attrs.get("experts_start_idx", 0)
        local = idx - start
        onehot = jax.nn.one_hot(local, n, dtype=x.dtype)  # [T,k,E]
        weighted = jnp.einsum("tke,tk->te", onehot, gates)  # [T,E]
        # y_t = sum_e w_te * (x_t @ W_e)  — dense dispatch, MXU-friendly
        per_expert = jnp.einsum("td,edo->teo", x, params["kernel"])
        if "bias" in params:
            per_expert = per_expert + params["bias"][None, :, :]
        act = attrs.get("activation", ActiMode.AC_MODE_NONE)
        per_expert = apply_activation(per_expert, act)
        out = jnp.einsum("teo,te->to", per_expert, weighted)
        return [out]


# The expert layers' counters: ONE uint32 array in the model's op state,
# a row a layer: routed pairs per expert, then, for each of MOE_FIELDS, one
# count per phase a serving program is traced for. One leaf and not a dict
# per layer: the serving loop donates the op state to every call and its
# traced prefill fences it leaf by leaf (seen on the chip: 80 leaves more
# idled the device 131 ms a prefill step).
MOE_COUNTERS = "moe_counters"
MOE_PHASES = ("decode", "prefill", "verify")
MOE_FIELDS = ("calls", "tokens", "routed", "touched", "resident")
# one field more, for a model whose router has picks that are no expert
# (``zero_experts``): the pairs a phase that cost nothing. No other model's
# counter row has it
ZERO_FIELD = "zero"


def counter_fields(layers) -> tuple:
    """The fields of a counter row of a model whose expert layers are
    ``layers`` (its MOE_EXPERTS layers, or their attrs)."""
    attrs = [getattr(ly, "attrs", ly) for ly in layers]
    (zero,) = {a.get("zero_experts") is not None for a in attrs}
    return MOE_FIELDS + ((ZERO_FIELD,) if zero else ())


def init_counters(model):
    """Give ``model``'s expert layers their counters, if it has such layers
    and ``FFConfig.telemetry`` is on (FFModel.compile calls this; without
    either, nothing is built and the op counts nothing)."""
    layers = [ly for ly in model.layers if ly.op_type == OpType.MOE_EXPERTS]
    for ly in layers:
        ly.attrs.pop("counter_row", None)
    if not layers or not model.config.telemetry:
        return
    (E,) = {ly.attrs["num_experts"] for ly in layers}
    for i, ly in enumerate(layers):
        ly.attrs["counter_row"] = i
    model.op_state[MOE_COUNTERS] = jnp.zeros(
        (len(layers), E + len(counter_fields(layers)) * len(MOE_PHASES)),
        jnp.uint32)


def _step_tokens(ctx, x):
    """(columns that can hold a real token, valid [R, q], phase) of a
    serving step, from the batch descriptor: a padded position or an
    inactive slot is not a token. The phase is what the program that runs
    the step says it is (``ctx.step_phase``: a block-diffusion model's
    decode block, whose pass is a block of real tokens wide), else what the
    descriptor shows."""
    meta = ctx.batch_config
    R, Q = x.shape[0], x.shape[1]
    tree = hasattr(meta, "ancestor")
    n = meta.num_nodes if tree else meta.num_tokens
    # verify-consistent wide decode: only the first kv_append_q columns of
    # a row are real (serve/engine.forward_with_meta)
    append_q = getattr(ctx, "kv_append_q", None)
    q = append_q if (append_q is not None and Q > append_q) else Q
    valid = (jnp.arange(q)[None, :] < n[:, None]) & meta.active[:, None]
    phase = getattr(ctx, "step_phase", None) or (
        "verify" if tree else ("decode" if q == 1 else "prefill"))
    return q, valid, phase


def _in_chunks(x, idx, w, valid, cap: int, run, num_experts: int):
    """``run`` over the real tokens of a wide step, ``cap`` at a time: the
    real tokens are moved to the front, and a step that holds no more than
    the scheduler's token budget is one pass (a step may hold more: tree
    verification fills every row)."""
    T, H = x.shape
    order = jnp.argsort(~valid, stable=True).astype(jnp.int32)
    order = jnp.pad(order, (0, (-T) % cap), constant_values=T)
    n_real = jnp.sum(valid, dtype=jnp.int32)

    def body(c, carry):
        y, sizes = carry
        tok = jax.lax.dynamic_slice(order, (c * cap,), (cap,))
        ok = c * cap + jnp.arange(cap, dtype=jnp.int32) < n_real
        at = jnp.minimum(tok, T - 1)
        yc, sc = run(x[at], idx[at], w[at], ok)
        return y.at[jnp.where(ok, tok, T)].set(yc, mode="drop"), sizes + sc

    return jax.lax.fori_loop(
        0, (n_real + cap - 1) // cap, body,
        (jnp.zeros((T, H), x.dtype), jnp.zeros((num_experts,), jnp.int32)))


@register_op
class MoeExperts(OpImpl):
    """Routed SwiGLU experts of a serving model, dropless.

    Inputs: x [R, Q, d], indices [R, Q, k], gate weights [R, Q, k] (the
    router's top-k, graph values). Output [R, Q, d]:
    ``sum_j w_j * W_down[e_j] (silu(W_gate[e_j] x) * W_up[e_j] x)`` at the
    step's real tokens (``ctx.batch_config``), zero elsewhere. The weights
    are three stacks ``[E, in, out]``, int8 or not (quant.py). With
    ``FFConfig.telemetry`` the op keeps cumulative counters on the device,
    in its row of the op state's ``MOE_COUNTERS``: routed pairs per expert
    and, per phase of ``MOE_PHASES``, ``calls``, ``tokens``, ``routed``
    (pairs), ``touched`` (distinct experts, summed over calls) and
    ``resident`` (the calls whose rows the kernel gathered and whose
    results it weighted and added itself, in VMEM: every call of a program
    whose step fits there, ``kernels/moe.rows_fit``, and none of one whose
    step does not, which stages the tiles' rows through HBM).
    ``ServingTelemetry`` reads them at a snapshot and nowhere else.

    With ``attrs["router_width"]`` the layer is one chip's share of an
    expert-parallel layer: its ``num_experts`` are experts
    ``[first_expert, first_expert + num_experts)`` of that many, the
    indices are the router's over all of them, and the output is the held
    experts' part of the sum (a pair routed elsewhere is no pair: it is
    neither computed nor counted, and experts count by held index).

    With ``attrs["zero_experts"]`` ``(first, count)`` the router's indices
    ``[first, first + count)`` name NO expert on any chip: such a pick adds
    ``w * x`` where the token lives (the identity, no arithmetic of an
    expert), so on every chip for its own tokens, like a shared expert and
    unlike a routed one. It is never a row of the kernel and never
    ``routed``; the layer's counter row has one field more, ``zero``: those
    picks a phase."""

    op_type = OpType.MOE_EXPERTS
    quant_aware = True

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        return [input_specs[0]]

    @staticmethod
    def weight_specs(attrs, input_specs):
        (sx, dx) = input_specs[0]
        E, inter, d = attrs["num_experts"], attrs["expert_width"], sx[-1]
        dt = attrs.get("data_type") or dx
        init = attrs.get("kernel_initializer") or default_kernel_initializer()
        shard = ("expert", None, None)
        return [WeightSpec("gate", (E, d, inter), dt, init, shard),
                WeightSpec("up", (E, d, inter), dt, init, shard),
                WeightSpec("down", (E, inter, d), dt, init, shard)]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        from flexflow_tpu import kernels as ffk
        from flexflow_tpu.kernels import moe as K

        x, idx, w = inputs
        assert ctx.batch_config is not None, "serving ops need ctx.batch_config"
        R, Q, H = x.shape
        E = attrs["num_experts"]
        q, valid, phase = _step_tokens(ctx, x)
        mesh = ctx.mesh
        pallas = ffk.use_pallas(ctx.config)
        if pallas and mesh is not None and mesh.devices.size > 1:
            pallas = False
            K.record_fallback("a mesh of several chips")
        elif not pallas:
            K.record_fallback("backend without Mosaic")
        else:
            K.record_fast_path()
        width = attrs.get("router_width")
        T = R * q
        cap = max(R, getattr(ctx.config, "max_tokens_per_batch", T))
        resident = pallas and K.step_fits(min(T, cap), H, params["gate"],
                                          x.dtype)
        run = functools.partial(
            K.moe_experts, gate=params["gate"], up=params["up"],
            down=params["down"], pallas=pallas,
            interpret=ffk.pallas_interpret_forced(), resident=resident,
            held=None if width is None else (attrs["first_expert"], width))
        flat = (x[:, :q].reshape(T, H), idx[:, :q].reshape(T, -1),
                w[:, :q].reshape(T, -1), valid.reshape(T))
        if T <= cap:
            y, sizes = run(*flat)
        else:
            y, sizes = _in_chunks(*flat, cap, run, E)
        y = y.reshape(R, q, H)
        zero = attrs.get("zero_experts")
        if zero is not None:
            f32 = jnp.float32
            at_zero = ((idx[:, :q] >= zero[0])
                       & (idx[:, :q] < zero[0] + zero[1]) & valid[..., None])
            zw = jnp.sum(jnp.where(at_zero, w[:, :q].astype(f32), 0.0), -1)
            y = (y.astype(f32)
                 + x[:, :q].astype(f32) * zw[..., None]).astype(y.dtype)
        y = jnp.pad(y, ((0, 0), (0, Q - q), (0, 0)))
        row = attrs.get("counter_row")
        if row is not None:
            u32, n = jnp.uint32, len(MOE_PHASES)
            step = [jnp.uint32(1), jnp.sum(valid, dtype=u32),
                    jnp.sum(sizes, dtype=u32), jnp.sum(sizes > 0, dtype=u32),
                    jnp.uint32(resident)]
            if zero is not None:
                step.append(jnp.sum(at_zero, dtype=u32))
            step = jnp.stack(step)
            at = E + n * jnp.arange(len(step)) + MOE_PHASES.index(phase)
            st = ctx.state_out.get(MOE_COUNTERS)
            if st is None:
                st = ctx.state_in[MOE_COUNTERS]
            ctx.state_out[MOE_COUNTERS] = st.at[row, :E].add(
                sizes.astype(u32)).at[row, at].add(step)
        return [y]
