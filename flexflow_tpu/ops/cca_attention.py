"""Serving attention in a compressed latent, mixed along the sequence
("compressed convolutional attention", CCA; models/zaya.py).

Queries and keys are projected into a latent narrower than the hidden state,
mixed ALONG THE SEQUENCE by two causal convolutions of kernel two, given the
mean of the unmixed query and key latents, normalised a head, rotated over
the first ``rotary_dim`` of a head's dims and attended as plain grouped k/v
heads (``inc_attention._attend``: the flash kernel on the k/v cache every
other model keeps). The value is half this token's and half the token's
before. With ``u_t = [Wq x_t ; Wk x_t]`` and ``H`` query, ``G`` key heads:

    c0_t    = w0[0] * u_{t-1} + w0[1] * u_t + b0           (a channel apart)
    c1_t[j] = B[j, 0] c0_{t-1}[j] + B[j, 1] c0_t[j] + b1[j]   (a head apart)
    q_t[h]  = c1_t[h]     + (qt[h] + kt[g(h)]) / 2
    k_t[g]  = c1_t[H + g] + (mean_{h in g} qt[h] + kt[g]) / 2
    q_t[h] <- sqrt(D) q_t[h] / |q_t[h]|;   k_t[g] <- tau_g sqrt(D) k_t[g] / |k_t[g]|
    v_t     = [Wv1 x_t ; Wv2 x_{t-1}]

So position ``t`` needs ``u_{t-1}``, ``u_{t-2}`` (``c0_{t-1}`` is made of
both) and ``Wv2 x_{t-1}``: **a row's tail**, ``2 C + Dv`` values a layer
(``C = (H + G) D``), zeros where a request starts. It is the op's state
beside its k/v cache, ``op_state[TAIL_STACK]["t"]`` of ``[layers, slots, 2 C +
Dv]``, and unlike a cache position it is OVERWRITTEN: every step that gives a
slot tokens leaves there the tail its last token ends with.

A step's row takes its tail (``take_tails``)

* as zeros where it starts a request (``start_pos == 0``, whatever the slot
  held: a slot given to a new request is cleared by that, and a preempted
  request, prefilled again from 0, rebuilds its own);
* from ANOTHER ROW OF THE SAME STEP where that row is the same slot's and
  ends where this one starts (the compact prefill batch carries several
  consecutive segments of one slot, all computed in one forward:
  serve/request_manager._prefill_rows), rows in ascending order of
  ``start_pos`` as the scheduler gives them;
* from the state otherwise (a decode step; a segment whose predecessor was
  another step's),

and the step writes back the tail of each slot's LAST row only.

What stages, moves, shares or rolls back cache positions cannot carry the
tail along (a rejected draft's tokens have overwritten it, and a pooled
prefix has none): ``inc_attention.refuse_windowed`` refuses them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from flexflow_tpu.core.initializer import (NormInitializer,
                                           default_kernel_initializer)
from flexflow_tpu.core.layer import WeightSpec
from flexflow_tpu.ffconst import OpType
from flexflow_tpu.ops.base import OpImpl, register_op
from flexflow_tpu.ops.inc_attention import (TAIL_STACK, _attend, _init_kv_state,
                                            _stack, append_and_ref,
                                            apply_partial_rotary, carried_rows,
                                            write_kv)


def _dims(attrs):
    """(H, G, D, C, Dv): query heads, key/value heads, a head's dims, the
    mixed latent's channels, the width of each value half."""
    H, G, D = attrs["num_q_heads"], attrs["num_kv_heads"], attrs["head_dim"]
    return H, G, D, (H + G) * D, G * D // 2


def tail_width(attrs) -> int:
    _, _, _, C, Dv = _dims(attrs)
    return 2 * C + Dv


class _ConvVectors:
    """Rows of ``conv_vec``: conv0's taps N(0, 0.5^2), the two biases N(0,
    0.02^2)."""

    def __call__(self, key, shape, dtype):
        return (jnp.asarray([0.5, 0.5, 0.02, 0.02], dtype)[:, None]
                * jax.random.normal(key, shape, dtype))


def _weight_specs(attrs, input_specs):
    (shape, d) = input_specs[0]
    E = shape[-1]
    H, G, D, C, Dv = _dims(attrs)
    dt = attrs.get("data_type") or d
    init = attrs.get("kernel_initializer") or default_kernel_initializer()
    # seeded so that a missing tap, bias or tau is seen: each conv tap about
    # as heavy as the q-k mean beside it
    tap1 = NormInitializer(stddev=0.5 / math.sqrt(D))
    return [
        # [Wq | Wk | Wv1 | Wv2], one array and one gemm: a column's int8
        # scale is its own either way, and a step fetches one weight and
        # one scale where four were eight
        WeightSpec("wqkv", (E, C + 2 * Dv), dt, init),
        # conv0's two taps (tap 0 weighs the position before, tap 1 the
        # position itself), then its bias, then conv1's: one array
        WeightSpec("conv_vec", (4, C), dt, _ConvVectors()),
        WeightSpec("conv1_w", (H + G, 2, D, D), dt, tap1),
        WeightSpec("tau", (G,), dt, NormInitializer(mean=1.0, stddev=0.1)),
        WeightSpec("wo", (H * D, E), dt, init),
    ]


def _init_state(attrs, input_specs):
    st = _init_kv_state(attrs, input_specs)
    st["tail"] = jnp.zeros((attrs["max_requests"], tail_width(attrs)),
                           st["k_cache"].dtype)
    return st


def take_tails(stored, slots, start, n, u, v2):
    """The tails a step's rows start from, and what the step leaves behind.

    ``stored`` ``[slots, 2 C + Dv]``: a layer's tails as the last step left
    them; ``slots`` ``[R]`` or None (the slot grid: row r is slot r);
    ``start``, ``n`` ``[R]``: each row's first position and real tokens (0:
    an idle row); ``u`` ``[R, Q, C]``, ``v2`` ``[R, Q, Dv]``: this step's
    unmixed latents and shifted-value halves. Returns ``(ext_u [R, Q + 2,
    C], ext_v2 [R, Q + 1, Dv], new stored)``: each row's run with its tail
    in front (``u_{-2}, u_{-1}, u_0 ..``; ``v2_{-1}, v2_0 ..``)."""
    R, Q, C = u.shape

    def fresh(t):        # at a request's start nothing came before
        return jnp.where((start == 0)[:, None], 0, t).astype(u.dtype)

    def end_of(eu, ev, n_i):
        """(u_{n-1}, u_{n-2}, v2_{n-1}) of one row's run as one tail."""
        return jnp.concatenate([
            jax.lax.dynamic_index_in_dim(eu, n_i + 1, 0, keepdims=False),
            jax.lax.dynamic_index_in_dim(eu, n_i, 0, keepdims=False),
            jax.lax.dynamic_index_in_dim(ev, n_i, 0, keepdims=False)])

    def with_head(t, run_u, run_v):
        """A run with the tail ``t [.., 2 C + Dv]`` in front."""
        return (jnp.concatenate([t[..., None, C:2 * C], t[..., None, :C],
                                 run_u], axis=-2),
                jnp.concatenate([t[..., None, 2 * C:], run_v], axis=-2))

    if slots is None and Q == 1:
        # a decode step, the hot one: the new tail is this token in front
        # of the old one's newer half, with no index to look up
        old = fresh(stored)
        ext_u, ext_v = with_head(old, u, v2)
        ends = jnp.concatenate([u[:, 0], old[:, :C], v2[:, 0]], axis=-1)
        return ext_u, ext_v, jnp.where((n > 0)[:, None],
                                       ends.astype(stored.dtype), stored)
    if slots is None:
        ext_u, ext_v = with_head(fresh(stored), u, v2)
        ends = jax.vmap(end_of)(ext_u, ext_v, n).astype(stored.dtype)
        return ext_u, ext_v, jnp.where((n > 0)[:, None], ends, stored)
    # a handful of rows (the step's segments), in order: a row that
    # continues an earlier row of its slot starts from that row's end, not
    # from the state (the rule any carried state obeys: carried_rows)
    def run(i, t):
        ext = with_head(t.astype(u.dtype), u[i], v2[i])
        return ext, end_of(*ext, n[i])

    runs, kept = carried_rows(stored, slots, start, n, run)
    return (jnp.stack([r[0] for r in runs]), jnp.stack([r[1] for r in runs]),
            kept)


def mix(attrs, params, ext_u, positions):
    """A run's unmixed latents with its tail in front, ``ext_u [R, Q + 2,
    C]`` -> the queries and keys to attend and to store, ``q [R, Q, H, D]``,
    ``k [R, Q, G, D]`` (normalised, rotated), in ``ext_u``'s dtype. The
    convolutions, the mean and the norms in float32."""
    H, G, D, _, _ = _dims(attrs)
    R, Q = ext_u.shape[0], ext_u.shape[1] - 2
    f32 = jnp.float32
    eu = ext_u.astype(f32)
    w0_before, w0_here, b0, b1 = params["conv_vec"].astype(f32)
    c0 = w0_before * eu[:, :-1] + w0_here * eu[:, 1:] + b0
    c0 = c0.reshape(R, Q + 1, H + G, D)
    B = params["conv1_w"].astype(f32)
    with jax.named_scope("cca_mix"):
        c1 = (jnp.einsum("rqjd,jde->rqje", c0[:, :-1], B[:, 0],
                         preferred_element_type=f32)
              + jnp.einsum("rqjd,jde->rqje", c0[:, 1:], B[:, 1],
                           preferred_element_type=f32)
              + b1.reshape(H + G, D))
    ut = eu[:, 2:].reshape(R, Q, H + G, D)
    qt, kt = ut[:, :, :H], ut[:, :, H:]
    grouped = qt.reshape(R, Q, G, H // G, D)
    q = c1[:, :, :H] + 0.5 * (grouped + kt[:, :, :, None]).reshape(R, Q, H, D)
    k = c1[:, :, H:] + 0.5 * (grouped.mean(axis=3) + kt)

    def unit(x):
        return x * (math.sqrt(D) * jax.lax.rsqrt(
            jnp.sum(x * x, axis=-1, keepdims=True)))

    q = unit(q)
    k = unit(k) * params["tau"].astype(f32)[:, None]
    rot, theta = attrs["rotary_dim"], attrs["rope_theta"]
    return (apply_partial_rotary(q.astype(ext_u.dtype), positions, rot, theta),
            apply_partial_rotary(k.astype(ext_u.dtype), positions, rot, theta))


@register_op
class IncMultiHeadCCAttention(OpImpl):
    """Incremental-decoding attention in a convolved latent, with a per-slot
    k/v cache and a per-slot tail."""

    op_type = OpType.INC_MULTIHEAD_CCA_ATTENTION
    quant_aware = True

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        (shape, d) = input_specs[0]
        return [(tuple(shape[:-1]) + (attrs["embed_dim"],),
                 attrs.get("data_type") or d)]

    weight_specs = staticmethod(_weight_specs)
    init_state = staticmethod(_init_state)

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        from flexflow_tpu.quant import qmatmul

        x = inputs[0]
        meta = ctx.batch_config
        assert meta is not None, "serving ops need ctx.batch_config"
        if (hasattr(meta, "ancestor")
                or getattr(ctx, "kv_override", None) is not None
                or getattr(ctx, "kv_append_q", None) is not None):
            raise NotImplementedError(
                "an attention layer that carries a tail is served by "
                "incremental decoding on one chip, a token a row a step: a "
                "tree's nodes, a verify-wide decode step and a pipeline "
                "stage's microbatch have no one token before each token")
        _, G, D, C, Dv = _dims(attrs)
        R, Q = x.shape[0], x.shape[1]
        proj = qmatmul(x, params["wqkv"])
        u, v1, v2 = (proj[..., :C], proj[..., C:C + Dv], proj[..., C + Dv:])
        n = jnp.where(meta.active, meta.num_tokens, 0)
        tails = (ctx.state_out.get(TAIL_STACK) or ctx.state_in[TAIL_STACK])["t"]
        lidx = attrs["tail_layer_idx"]
        ext_u, ext_v, kept = take_tails(tails[lidx], meta.slots,
                                        meta.start_pos, n, u, v2)
        ctx.state_out[TAIL_STACK] = {"t": tails.at[lidx].set(kept)}
        q, k = mix(attrs, params, ext_u, meta.positions)
        # the first half of the key/value heads carry this token's value,
        # the second half the token's before
        v = jnp.concatenate([v1, ext_v[:, :-1].astype(v1.dtype)],
                            axis=-1).reshape(R, Q, G, D)
        q_abs = meta.start_pos[:, None] + jnp.arange(Q)[None, :]
        lengths = jnp.where(meta.active, meta.start_pos + meta.num_tokens, 0)
        idx = attrs.get("cache_layer_idx")
        if meta.slots is None and Q == 1:
            # a decode step: the attention kernel appends the one position
            # itself (IncMultiHeadSelfAttention.forward has the reasoning)
            if idx is None:
                st = ctx.state_in[ctx.layer_name]
                k0, v0 = st["k_cache"], st["v_cache"]
            else:
                key, st = _stack(ctx, attrs)
                k0, v0 = st["k"], st["v"]
            appos = jnp.where(
                meta.active & (meta.num_tokens > 0)
                & (meta.start_pos < attrs["max_seq_length"]),
                meta.start_pos, -1)
            out, knew, vnew = _attend(
                attrs, q, k0, v0, lengths, q_abs, x.dtype, ctx, causal=True,
                layer_idx=idx, append_kv=(k, v, appos))
            if idx is None:
                write_kv(ctx, attrs, knew, vnew)
            else:
                ctx.state_out[key] = {"k": knew, "v": vnew}
        else:
            k_ref, v_ref, layer_idx = append_and_ref(
                ctx, attrs, k, v, meta.start_pos, meta.num_tokens,
                meta.active, meta.slots)
            out = _attend(attrs, q, k_ref, v_ref, lengths, q_abs, x.dtype,
                          ctx, causal=True, layer_idx=layer_idx,
                          rows=meta.slots)
        return [qmatmul(out, params["wo"])]
