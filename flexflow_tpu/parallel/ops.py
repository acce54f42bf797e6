"""Parallel operators — the parallelism vocabulary as graph nodes.

Capability parity with reference src/parallel_ops/{partition,combine,replicate,
reduction,allreduce,fused_parallel_op}.cc (SURVEY §2.3): in the reference these
are PCG nodes with real data-movement kernels (Legion region copies, strided
add, ncclAllReduce). On TPU each becomes a GSPMD sharding annotation:

  Repartition(dim, degree) -> constraint placing a mesh axis on `dim`
  Combine(dim)             -> constraint removing the axis from `dim` only
                              (other dims left UNCONSTRAINED for GSPMD)
  Replicate()              -> fully-replicated constraint (XLA broadcasts;
                              reverse-mode grad is the psum the reference
                              implements by hand)
  Reduction(dim)           -> reduce partial values and scatter along `dim`
                              (reference: sum-reduce the replica dim); XLA
                              lowers to reduce-scatter where profitable
  AllReduce                -> replicated constraint at a TP boundary; XLA
                              inserts the psum (explicit shard_map forms live
                              in parallel/collectives.py)

The nodes exist so graphs (and later the Unity search, which *inserts* these
nodes) can express where layout changes happen, exactly like the reference.
Degree arguments are validated against the mesh: GSPMD shards over whole named
axes, so a degree that disagrees with the axis size is an error rather than a
silent different layout.
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from flexflow_tpu.ffconst import OpType
from flexflow_tpu.ops.base import OpImpl, register_op, register_op_as

UNC = P.UNCONSTRAINED


def _unconstrained_spec(ndim):
    return [UNC] * ndim


def _constrain(x, mesh, spec_list):
    if mesh is None or mesh.devices.size == 1:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec_list)))


def _check_degree(attrs, key, mesh, axis):
    """Degree must match the mesh axis size (or be 0/None = 'use the axis')."""
    degree = attrs.get(key) or 0
    if degree and mesh is not None and axis in mesh.axis_names \
            and degree != mesh.shape[axis]:
        raise ValueError(
            f"{key}={degree} does not match mesh axis '{axis}' of size "
            f"{mesh.shape[axis]}; GSPMD shards over whole named axes")


class _ParallelOp(OpImpl):
    @staticmethod
    def infer_output_specs(attrs, input_specs):
        return [input_specs[0]]


@register_op
class Repartition(_ParallelOp):
    op_type = OpType.REPARTITION

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        x = inputs[0]
        dim = attrs["repartition_dim"] % x.ndim
        axis = attrs.get("axis_name", "data")
        mesh = ctx.mesh
        _check_degree(attrs, "repartition_degree", mesh, axis)
        if (mesh is None or axis not in mesh.axis_names
                or x.shape[dim] % mesh.shape[axis] != 0):
            return [x]  # precondition failed: leave sharding untouched
        spec = _unconstrained_spec(x.ndim)
        spec[dim] = axis
        return [_constrain(x, mesh, spec)]


@register_op
class Combine(_ParallelOp):
    op_type = OpType.COMBINE

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        x = inputs[0]
        dim = attrs.get("combine_dim", 0) % x.ndim
        spec = _unconstrained_spec(x.ndim)
        spec[dim] = None  # gather this dim only; others left to GSPMD
        return [_constrain(x, ctx.mesh, spec)]


@register_op
class Reduction(_ParallelOp):
    """Sum partial values and leave the result scattered along reduction_dim
    (the reference's post-row-parallel-linear reduce, reduction.cc)."""

    op_type = OpType.REDUCTION

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        x = inputs[0]
        dim = attrs.get("reduction_dim", 0) % x.ndim
        axis = attrs.get("axis_name", "model")
        mesh = ctx.mesh
        _check_degree(attrs, "reduction_degree", mesh, axis)
        if (mesh is None or axis not in mesh.axis_names
                or x.shape[dim] % mesh.shape[axis] != 0):
            return [x]
        spec = _unconstrained_spec(x.ndim)
        spec[dim] = axis
        return [_constrain(x, mesh, spec)]


@register_op_as(OpType.REPLICATE, OpType.ALLREDUCE)
class ReplicateOrAllReduce(_ParallelOp):
    """Both lower to a fully-replicated constraint: Replicate broadcasts a
    value to all shards; AllReduce marks the boundary where XLA must psum
    partial results into a replicated tensor."""

    op_type = OpType.ALLREDUCE

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        x = inputs[0]
        return [_constrain(x, ctx.mesh, [None] * x.ndim)]


def branch_parallel_apply(mesh, axis, branch_fns, out_channels, x,
                          allocs=None):
    """Execute independent branch subgraphs on DISJOINT device slices of a
    mesh axis — the runtime form of a searched nonsequence split
    (reference NonsequenceSplit, include/flexflow/graph.h:156;
    search/graph_search.py _try_nonsequence_splits produces the
    OpStrategy.branch tags this realizes).

    Inside ``jax.shard_map`` over ``axis`` every device slice evaluates
    only ITS branch via ``lax.switch`` on its axis index; branch outputs
    are zero-padded on the channel dim to a common width, all-gathered,
    and returned as per-branch arrays with their true channel counts (the
    caller concats/consumes them). Branches must agree on every dim
    except dim 1 (channels). ``x`` is consumed replicated.

    ``allocs`` (optional): per-branch device counts summing to the axis
    size — the reference's UNEQUAL vertical(i)/horizontal(i) resource
    partitions (graph.cc:220-244); default one device per branch.
    NOTE (PARITY r5): under XLA SPMD the switch lowers to every device
    executing every branch, so this form is numerics-correct but cannot
    beat DP inside one program — it exists for search-space execution
    parity, not as the fast path."""
    import numpy as _np

    import jax.numpy as jnp

    d = mesh.shape[axis]
    nb = len(branch_fns)
    if allocs is None:
        assert nb == d == len(out_channels)
        allocs = [1] * nb
    assert sum(allocs) == d and len(allocs) == nb == len(out_channels)
    starts = _np.cumsum([0] + list(allocs))[:-1]
    cmax = max(out_channels)

    def padded(f, c):
        def g(v):
            y = f(v)
            pad = [(0, 0)] * y.ndim
            pad[1] = (0, cmax - c)
            return jnp.pad(y, pad)
        return g

    fns = [padded(f, c) for f, c in zip(branch_fns, out_channels)]

    def local(xl):
        j = jax.lax.axis_index(axis)
        # branch owning device j: number of starts <= j, minus one
        bi = jnp.sum(jnp.asarray(starts) <= j) - 1
        y = jax.lax.switch(bi, fns, xl)          # [B, Cmax, ...]
        return jax.lax.all_gather(y, axis)       # [d, B, Cmax, ...]

    out = jax.shard_map(local, mesh=mesh, in_specs=P(), out_specs=P(),
                        check_vma=False)(x)
    return [out[int(starts[i]), :, :c] for i, c in enumerate(out_channels)]


def branch_data_parallel_apply(mesh, axis, branch_fns, branch_params,
                               out_channels, x):
    """Nonsequence-split execution with data parallelism INSIDE each
    branch slice — the form the search's cost model actually assumes
    (search/graph_search.py _try_nonsequence_splits re-optimizes each
    branch under data degree d//nb).

    The ``axis`` (size d) is viewed as nb slices of k = d // nb devices.
    Device j runs branch ``j // k`` on batch rows
    ``[(j % k) * B/k, (j % k + 1) * B/k)``, so per-device FLOPs equal
    pure DP while each device executes only ITS branch's ops at an
    nb-times larger per-op batch — the regime where nonsequence splits
    win (many small ops whose per-op overhead dominates; reference
    NonsequenceSplit, include/flexflow/graph.h:156). Branch outputs are
    zero-padded on dim 1 to a common width, all-gathered once, and
    returned per-branch at full batch with true channel counts.

    ``branch_fns[i]`` takes ``(x_local, branch_params[i])``; params ride
    in replicated (their grads psum over the axis via the shard_map
    transpose, matching DP grad sync). Requires ``d % nb == 0`` and
    ``B % (d // nb) == 0``; the caller falls back to sequential
    execution otherwise."""
    import jax.numpy as jnp

    nb = len(branch_fns)
    d = mesh.shape[axis]
    assert d % nb == 0, (d, nb)
    k = d // nb
    B = x.shape[0]
    assert B % k == 0, (B, k)
    mb = B // k
    cmax = max(out_channels)

    def padded(f, c, i):
        def g(operand):
            xl, bp = operand
            y = f(xl, bp[i])
            pad = [(0, 0)] * y.ndim
            pad[1] = (0, cmax - c)
            return jnp.pad(y, pad)
        return g

    fns = [padded(f, c, i)
           for i, (f, c) in enumerate(zip(branch_fns, out_channels))]

    def local(xf, bp):
        j = jax.lax.axis_index(axis)
        xl = jax.lax.dynamic_slice_in_dim(xf, (j % k) * mb, mb, axis=0)
        y = jax.lax.switch(j // k, fns, (xl, bp))   # [mb, Cmax, ...]
        g = jax.lax.all_gather(y, axis)             # [d, mb, Cmax, ...]
        # device order along the axis is j = branch * k + shard, so the
        # leading [d, mb] axes reshape to per-branch full batches
        return g.reshape((nb, k * mb) + g.shape[2:])

    out = jax.shard_map(local, mesh=mesh, in_specs=(P(), P()),
                        out_specs=P(),
                        check_vma=False)(x, tuple(branch_params))
    return [out[i, :, :c] for i, c in enumerate(out_channels)]
