"""What the serving ops that keep a RECURRENT STATE a row share
(ops/kda_attention.py: a gated delta rule; ops/ssd_mixer.py: a state-space
mixer), and the contract by which the cache manager finds such a layer
without naming its op.

**The contract.** An op's ``init_state`` names two members, once:

    {STATE: [slots, ...] float32,          what the recurrence carries a row
     TAIL:  [slots, taps - 1, channels]}   the causal convolution's last inputs

``FFModel._consolidate_kv_caches`` stacks every layer of a model that has
them into ``op_state[RECURRENT_STACK] = {"s": [layers, slots, ...], "u":
[layers, slots, taps - 1, channels]}`` (the layers of one model must agree
in shape), gives each layer ``attrs["state_layer_idx"]``, and asks the op's
class, by its ``OpType``, which of its forms run as kernels:
``takes_chunk_kernel(attrs, config)``. Both members are OVERWRITTEN by every
step that gives a slot tokens, so what stages, moves, shares, rolls back or
shards cache positions refuses such a model
(``inc_attention.refuse_windowed``).

Where a row's state and tail come from (the rules of
``cca_attention.take_tails``, through ``inc_attention.carried_rows``): zeros
where the row starts a request (``start_pos == 0``, whatever the slot held);
the END of another row of the same step where that row is the same slot's
and ends where this one starts (the compact prefill batch's consecutive
segments, all in one forward); the stored one otherwise; and the step writes
back each slot's LAST row's.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from flexflow_tpu.ops.inc_attention import RECURRENT_STACK, carried_rows

# the members of an op's own state that say "this layer keeps a recurrent
# state" (FFModel._consolidate_kv_caches reads them and nothing else)
STATE, TAIL = "recurrent_s", "recurrent_u"


class DecayInitializer:
    """``A_log`` and ``dt_bias`` as the published layers seed them (Mamba-2,
    and the gated delta rule after it): ``A ~ U(1, 16)`` a head, ``dt``
    log-uniform in [1e-3, 1e-1], ``dt_bias = softplus^-1(dt)``. ``what``:
    "A_log" or "dt_bias"."""

    def __init__(self, what: str):
        self.what = what

    def __call__(self, key, shape, dtype):
        u = jax.random.uniform(key, shape, jnp.float32)
        if self.what == "A_log":
            return jnp.log(1.0 + 15.0 * u).astype(dtype)
        dt = jnp.exp(math.log(1e-3) + u * (math.log(1e-1) - math.log(1e-3)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def kernel_path(attrs, config):
    """Whether the layer's recurrence runs its Pallas kernels
    (kernels/linear_attention.py), by what the process can observe: None
    (the jnp forms), else whether they are interpreted."""
    from flexflow_tpu import kernels as ffk

    if not (attrs.get("use_pallas", True) and ffk.use_pallas(config)):
        return None
    return ffk.pallas_interpret_forced()


def refuse_staged(meta, ctx):
    """A step that stages tokens which may be rejected or handed on: not
    over a state that every step overwrites."""
    if (hasattr(meta, "ancestor")
            or getattr(ctx, "kv_override", None) is not None
            or getattr(ctx, "kv_append_q", None) is not None):
        raise NotImplementedError(
            "an attention layer that keeps a recurrent state is served "
            "by incremental decoding on one chip, a token a row a "
            "step: a tree's nodes, a verify-wide decode step and a "
            "pipeline stage's microbatch would each overwrite a state "
            "that cannot be rolled back or handed on")


def stack_of(ctx):
    """``(S_all, U_all)``: the model's stacks as the step has them so far."""
    st = ctx.state_out.get(RECURRENT_STACK) or ctx.state_in[RECURRENT_STACK]
    return st["s"], st["u"]


def fresh(t, start):
    """At a request's start nothing came before: ``t [R, ...]`` with the
    rows at position 0 zeroed."""
    keep = (start != 0).reshape((-1,) + (1,) * (t.ndim - 1))
    return jnp.where(keep, t, 0)


def wrote(new, old, n):
    """``new`` for the rows that had a token this step, else ``old``."""
    keep = (n > 0).reshape((-1,) + (1,) * (new.ndim - 1))
    return jnp.where(keep, new.astype(old.dtype), old)


def end_of(ext_u, n_i, width: int):
    """The last ``width`` positions before position ``n_i`` of one row's run
    with its tail in front: the tail its last real token leaves."""
    return jax.lax.dynamic_slice_in_dim(ext_u, n_i, width, axis=0)


def runs_with_tails(U_all, lidx, u, start, slots, n):
    """A step's rows ``u [R, Q, C]`` (float32, before the convolution) each
    behind its tail, and the tails the step leaves in layer ``lidx`` of
    ``U_all [layers, slots, taps - 1, C]``. ``slots`` None: the slot grid
    (row r is slot r); else the compact batch's rows, by the three rules
    above. Returns ``(ext_u [R, Q + taps - 1, C], U_all)``."""
    Q = u.shape[1]
    width = U_all.shape[-2]

    def with_tail(t, run):
        return jnp.concatenate([t, run], axis=-2)

    if slots is None:
        old_u = U_all[lidx]
        ext_u = with_tail(fresh(old_u, start), u)
        if Q == 1:      # the new tail: this token behind the old one's
            ends = ext_u[:, 1:]
        else:
            ends = jax.vmap(lambda e, n_i: end_of(e, n_i, width))(ext_u, n)
        return ext_u, U_all.at[lidx].set(wrote(ends, old_u, n))

    def run_of(i, t):
        ext = with_tail(t, u[i])
        return ext, end_of(ext, n[i], width)

    runs, U_all = carried_rows(U_all, slots, start, n, run_of, layer=lidx)
    return jnp.stack(runs), U_all


def depthwise_conv(taps, ext_u, Q: int, bias=None):
    """``ext_u [R, Q + taps - 1, C]``, a run with its tail in front -> the
    mixed, activated ``[R, Q, C]`` in float32: ``silu(bias + sum_j taps[j] *
    u_{t - (taps - 1) + j})``."""
    taps = taps.astype(jnp.float32)
    eu = ext_u.astype(jnp.float32)
    mixed = sum(taps[j] * eu[:, j:j + Q] for j in range(taps.shape[0]))
    if bias is not None:
        mixed = mixed + bias.astype(jnp.float32)
    return jax.nn.silu(mixed)
