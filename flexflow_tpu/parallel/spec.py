"""Sharding policy: ParallelTensor metadata → jax NamedSharding.

The reference's ParallelTensor carries per-dim {size, degree, parallel_idx,
is_replica_dim} (reference include/flexflow/parallel_tensor.h:36) and its
parallel ops {Repartition, Combine, Replicate, Reduction, AllReduce}
(src/parallel_ops/) are PCG nodes that change that metadata with real data
movement. On TPU the same vocabulary maps to sharding annotations:

  Repartition(dim, degree)  -> PartitionSpec puts a mesh axis on `dim`
  Combine(dim)              -> PartitionSpec removes the axis (all-gather)
  Replicate()               -> axis absent from the spec (replicated)
  Reduction()               -> psum / GSPMD-inserted reduce after partial matmul
  AllReduce                 -> psum (XLA collective over ICI)

GSPMD inserts the actual collectives when a jitted program crosses sharding
boundaries; `flexflow_tpu/parallel/ops.py` exposes the explicit forms.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class ShardingPolicy:
    """Resolves where each tensor lives on the mesh."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self.axes = set(mesh.axis_names)

    def _axis(self, name: Optional[str]) -> Optional[str]:
        return name if name in self.axes and self.mesh.shape[name] > 1 else None

    def batch_sharding(self, shape: Tuple[int, ...]) -> NamedSharding:
        """Activations/batches: shard dim 0 on 'data' (+'seq' on dim 1 when
        sequence parallelism is on). Dims that don't divide the axis stay
        replicated (e.g. tiny eval batches)."""
        shape = tuple(shape)
        spec = [None] * len(shape)
        if (shape and self._axis("data")
                and shape[0] % self.mesh.shape["data"] == 0):
            spec[0] = "data"
        if (len(shape) >= 2 and self._axis("seq")
                and shape[1] % self.mesh.shape["seq"] == 0):
            spec[1] = "seq"
        return NamedSharding(self.mesh, P(*spec))

    def weight_sharding(self, shape: Tuple[int, ...],
                        sharding_dims: Optional[Tuple[Optional[str], ...]],
                        shard_multiples: Optional[
                            Tuple[Optional[int], ...]] = None
                        ) -> NamedSharding:
        """Parameters: replicated over 'data', split per the op's hint over
        'model'/'expert'. Dims that don't divide evenly fall back to
        replication (XLA would pad; we keep it simple and correct).
        ``shard_multiples[i]``, when given, additionally requires the
        per-device chunk of dim i to be a multiple of that unit (e.g.
        head_dim, so attention TP splits at whole-head boundaries — see
        WeightSpec.shard_multiples for the RoPE/partitioner rationale)."""
        if sharding_dims is None:
            return NamedSharding(self.mesh, P())
        spec = []
        for i, (dim_size, axis_name) in enumerate(zip(shape, sharding_dims)):
            ax = self._axis(axis_name)
            unit = (shard_multiples[i] or 1) if (
                shard_multiples is not None
                and i < len(shard_multiples)) else 1
            if (ax is not None and dim_size % self.mesh.shape[ax] == 0
                    and (dim_size // self.mesh.shape[ax]) % unit == 0):
                spec.append(ax)
            else:
                spec.append(None)
        return NamedSharding(self.mesh, P(*spec))

    def kv_cache_sharding(self, shape: Tuple[int, ...]) -> NamedSharding:
        """KV-cache buffers [R, KH, S, D] (or stacked [L, R, KH, S, D]; a
        D=64 cache on the packed flash path is stored [.., S/2, 128],
        ops/kv_layout.py: the same rank, the same head axis, and a row
        holds two consecutive positions, so both rules below hold as they
        stand).

        Under tensor parallelism the KV-head dim (dim -3) splits over
        'model' when it divides — the same whole-head split as wk/wv
        (ops/inc_attention._weight_specs), so each chip holds and attends
        its own heads' cache. That is also where GSPMD moves a replicated
        cache after the first sharded append, recompiling every program
        for the new placement; committing it up front avoids both.

        The sequence dim (dim -2) splits over 'seq' when the mesh has one
        and it divides — the storage layout consumed by
        parallel.ring_attention.seq_sharded_attend, so a searched
        sequence-parallel plan holds S/deg cache rows per device instead
        of the whole context. Dims that do not divide stay replicated."""
        shape = tuple(shape)
        spec = [None] * len(shape)
        if (len(shape) >= 3 and self._axis("model")
                and shape[-3] % self.mesh.shape["model"] == 0):
            spec[-3] = "model"
        if (len(shape) >= 2 and self._axis("seq")
                and shape[-2] % self.mesh.shape["seq"] == 0):
            spec[-2] = "seq"
        return NamedSharding(self.mesh, P(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def constrain(self, value, spec):
        """Apply a searched per-op output layout (search/strategy.py Spec —
        a mesh-axis name per dim) as a GSPMD sharding constraint. Axes not in
        the mesh or not dividing the dim fall back to replicated on that dim."""
        shape = getattr(value, "shape", None)
        if shape is None:
            return value
        clean = []
        for i, ax in enumerate(tuple(spec)[: len(shape)]):
            ok = (ax is not None and self._axis(ax) is not None
                  and shape[i] % self.mesh.shape[ax] == 0)
            clean.append(ax if ok else None)
        if not any(clean):
            return value
        return jax.lax.with_sharding_constraint(
            value, NamedSharding(self.mesh, P(*clean)))
