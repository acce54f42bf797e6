"""Pipeline parallelism: GPipe-style SPMD schedule over the "pipe" mesh axis.

Capability parity with the reference's pipeline parallelism (reference
inference_manager.cc:91-132: per-transformer-layer stage placement via
``start_device_id = degree * (layer / layers_per_stage)``, plus the depth-4
in-flight batch pipeline in request_manager.cc:1829). The TPU-native design
follows the scaling-book recipe instead of task placement:

* the L homogeneous blocks' weights are **stacked** on a leading layer dim
  and sharded over the ``pipe`` mesh axis — each stage holds L/P contiguous
  blocks in its HBM (the moral equivalent of ``start_device_id`` placement);
* inside ``jax.shard_map`` every stage scans its local blocks and hands its
  activations to the next stage with ``lax.ppermute`` over ICI;
* microbatches stream through the classic P+M-1-tick schedule — the pipeline
  bubble is (P-1)/(M+P-1), amortized by more microbatches;
* the loop is differentiable (ppermute has a transpose), so the same
  primitive serves training — unlike the reference, whose PP is
  serving-only (SURVEY §2.3).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from flexflow_tpu.parallel.collectives import ppermute_shift


def stack_stage_params(per_layer_params: list):
    """Stack a list of identical per-block pytrees along a new leading
    layer dim — the layout pipeline_spmd expects (shard dim 0 on "pipe")."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *per_layer_params)


def shard_stacked_params(params, mesh, axis: str = "pipe"):
    """Place stacked params so dim 0 (layers) is split across stages."""
    def put(x):
        spec = P(axis, *([None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))
    return jax.tree.map(put, params)


def pipeline_spmd(block_fn: Callable, mesh, num_microbatches: int,
                  axis: str = "pipe"):
    """Build a pipelined forward: ``fn(stacked_params, x) -> y``.

    block_fn(params_i, x) -> x      one block applied to one microbatch
    stacked_params                  leaves [L, ...], L % P == 0, sharded on
                                    dim 0 over ``axis``
    x                               [B, ...] batch; B % num_microbatches == 0

    Stage s processes microbatch (t - s) at tick t; activations ppermute
    s -> s+1 between ticks; outputs are psum-broadcast from the last stage.

    ``mesh`` may be any mesh containing ``axis`` — in particular the
    FFModel mesh built by make_mesh when
    ``FFConfig.pipeline_parallelism_degree > 1`` (its "pipe" axis): specs
    here only name ``axis``, so other mesh axes see replicated data and
    compose (e.g. pp x dp). Layer-graph models use this primitive over
    stacked homogeneous blocks (stack_stage_params / shard_stacked_params).
    """
    P_axis = axis
    M = num_microbatches

    def run(stacked_params, x):
        nstages = jax.lax.psum(1, P_axis)
        stage = jax.lax.axis_index(P_axis)
        B = x.shape[0]
        mb = B // M
        xs = x.reshape((M, mb) + x.shape[1:])

        def local_blocks(carry, layer_params):
            return block_fn(layer_params, carry), None

        def stage_apply(v):
            out, _ = jax.lax.scan(local_blocks, v, local_params)
            return out

        def tick(carry, t):
            buf, outputs = carry
            # stage 0 ingests microbatch t; others take last tick's handoff
            x_in = xs[jnp.clip(t, 0, M - 1)]
            cur = jnp.where(stage == 0, x_in, buf)
            y = stage_apply(cur)
            # the last stage finished microbatch t - (P-1) this tick
            out_idx = t - (nstages - 1)
            take = (stage == nstages - 1) & (out_idx >= 0)
            outputs = jnp.where(
                take, outputs.at[jnp.clip(out_idx, 0, M - 1)].set(y),
                outputs)
            buf = ppermute_shift(y, P_axis)
            return (buf, outputs), None

        local_params = stacked_params      # [L/P, ...] after shard_map split
        buf0 = jnp.zeros((mb,) + x.shape[1:], x.dtype)
        out0 = jnp.zeros_like(xs)
        (_, outputs), _ = jax.lax.scan(
            tick, (buf0, out0), jnp.arange(M + nstages - 1))
        # broadcast the last stage's outputs to every stage
        outputs = jax.lax.psum(
            jnp.where(stage == nstages - 1, outputs, jnp.zeros_like(outputs)),
            P_axis)
        return outputs.reshape((B,) + x.shape[1:])

    def fn(stacked_params, x):
        param_specs = jax.tree.map(
            lambda l: P(P_axis, *([None] * (l.ndim - 1))), stacked_params)
        return jax.shard_map(
            run, mesh=mesh,
            in_specs=(param_specs, P()),     # x replicated across stages
            out_specs=P(),
            check_vma=False)(stacked_params, x)

    return fn
