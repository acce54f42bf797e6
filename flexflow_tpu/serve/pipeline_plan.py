"""Pipeline-parallel serving: stage-sharded execution of the layer graph.

Capability parity with the reference's pipeline-parallel serving placement
(reference src/runtime/inference_manager.cc:91-132: each transformer layer is
assigned ``start_device_id = degree * (layer_id / layers_per_stage)`` so a
contiguous block of layers lives on each pipeline stage, and the
RequestManager keeps batches in flight across stages,
request_manager.cc:1829-1845).

TPU-first redesign — no task placement, no per-stage processes:

* The serving graph's repeated transformer block is detected structurally
  (the model zoo builds ``<prefix>.{i}.<op>``-anchored blocks); per-block
  weights are **stacked** on a new leading layer dim and sharded over the
  ``pipe`` mesh axis, so each stage holds exactly its L/P contiguous blocks
  in HBM — the moral equivalent of ``start_device_id`` placement.
* The stacked KV caches (already [L, R, KH, S, D] after
  ``FFModel._consolidate_kv_caches``) shard the same way: each stage owns
  its layers' caches.
* The block segment runs inside ``jax.shard_map`` that is **manual over
  "pipe" only** — tensor-parallel sharding of the per-layer weights stays on
  GSPMD ("model" axis is auto), so TP x PP compose inside one jitted step.
* Per step the request slots split into M microbatches streaming through
  the stages on the classic GPipe M+P-1-tick schedule (``_pp_segment``);
  each tick a stage applies its layer blocks to ONE microbatch, hands the
  activation to the next stage with ``ppermute``, and commits KV only for
  that microbatch's row slice. Embedding/lm-head (pre/post segments) stay
  on the plain GSPMD path.

The (P-1)-tick bubble is the same one the reference pays per batch; its
depth-4 in-flight batch pipeline amortizes it across batches, ours
amortizes it across the microbatches of one batch — and host round-trips
amortize separately via the fused decode block (serve/engine.py): each
decode-block step pays M+P-1 ticks of ICI hops but zero host involvement.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


PP_PARAMS_KEY = "__pp_blocks__"

_BLOCK_IDX_RE = re.compile(r"\.(\d+)\.")

# attr keys that legitimately differ between structurally-identical blocks
_ATTR_IGNORE = ("cache_layer_idx", "kernel_initializer", "bias_initializer",
                "kernel_regularizer", "transformer_layer_id")


@dataclasses.dataclass
class PipelinePlan:
    """A validated stage decomposition of a serving layer graph."""

    pre: List[Any]                 # layers before the first block
    blocks: List[List[Any]]        # blocks[i] = block i's layers, graph order
    post: List[Any]                # layers after the last block
    entry_tid: int                 # tensor id entering block 0
    exit_tid: int                  # tensor id produced by the last block
    block_entry_tid: int           # template (block 0) entry tensor id
    block_exit_tid: int            # template (block 0) exit tensor id
    num_stages: int

    @property
    def num_blocks(self) -> int:
        return len(self.blocks)

    @property
    def template(self) -> List[Any]:
        return self.blocks[0]


def microbatch_count(R: int, P: int) -> int:
    """GPipe split: M = largest divisor of R that is <= P (request slots
    must split evenly for the static microbatch shapes). Shared by the
    schedule and the compile-time degeneracy warning."""
    return max(m for m in range(1, P + 1) if R % m == 0)


def _block_index(name: str) -> Optional[int]:
    m = _BLOCK_IDX_RE.search(name)
    return int(m.group(1)) if m else None


def _comparable_attrs(layer) -> Tuple:
    items = []
    for k in sorted(layer.attrs):
        if k in _ATTR_IGNORE:
            continue
        items.append((k, repr(layer.attrs[k])))
    return (layer.op_type, tuple(items),
            tuple((w.name, w.shape, w.dtype) for w in layer.weights))


def build_pipeline_plan(model, num_stages: int) -> Optional[PipelinePlan]:
    """Detect the repeated transformer block in ``model``'s layer list.

    Returns None when the graph is not a homogeneous block stack — e.g.
    hand-built graphs, MoE layers with per-layer expert counts, or
    L % num_stages != 0. FFModel.compile treats None as a hard error (the
    user asked for PP the graph can't express — silently ignoring the
    degree was the round-1 behavior and is worse).
    """
    layers = model.layers
    anchors: Dict[int, int] = {}     # block index -> first layer position
    for pos, layer in enumerate(layers):
        idx = _block_index(layer.name)
        if idx is not None and idx not in anchors:
            anchors[idx] = pos
    if not anchors:
        return None
    L = max(anchors) + 1
    if sorted(anchors) != list(range(L)) or L < 2 or L % num_stages != 0:
        return None
    start0 = anchors[0]
    n = anchors[1] - anchors[0]      # block length in layers
    if n <= 0:
        return None
    # blocks must tile the list contiguously: block i at start0 + i*n
    for i in range(L):
        if anchors.get(i) != start0 + i * n:
            return None
    end = start0 + L * n
    if end > len(layers):
        return None
    blocks = [layers[start0 + i * n: start0 + (i + 1) * n] for i in range(L)]
    template_sig = [_comparable_attrs(l) for l in blocks[0]]
    for blk in blocks[1:]:
        if [_comparable_attrs(l) for l in blk] != template_sig:
            return None
    # exactly one stacked-KV layer per block, in consolidated layer order
    for i, blk in enumerate(blocks):
        idxs = [l.attrs.get("cache_layer_idx") for l in blk
                if l.attrs.get("cache_layer_idx") is not None]
        if idxs != [i]:
            return None

    # single-crossing-tensor dataflow validation
    produced_by_block: Dict[int, int] = {}
    for bi, blk in enumerate(blocks):
        for l in blk:
            for t in l.outputs:
                produced_by_block[t.tensor_id] = bi
    entry_tid = exit_tid = None
    block_entry = block_exit = None
    for bi, blk in enumerate(blocks):
        internal = {t.tensor_id for l in blk for t in l.outputs}
        ext = []
        for l in blk:
            for t in l.inputs:
                if t.tensor_id not in internal and t.tensor_id not in ext:
                    ext.append(t.tensor_id)
        if len(ext) != 1:
            return None              # block consumes more than the crossing
        if bi == 0:
            entry_tid = block_entry = ext[0]
            if entry_tid in produced_by_block:
                return None
        elif produced_by_block.get(ext[0]) != bi - 1:
            return None
        if bi == 1:
            block_exit = ext[0]      # block 0's output feeding block 1
    # post segment must consume exactly one tensor from the blocks: the
    # last block's exit (same relative position as block_exit in block 0)
    rel = None
    for pos, l in enumerate(blocks[0]):
        for t in l.outputs:
            if t.tensor_id == block_exit:
                rel = (pos, l.outputs.index(t))
    if rel is None:
        return None
    exit_tid = blocks[-1][rel[0]].outputs[rel[1]].tensor_id
    post = layers[end:]
    block_tids = set(produced_by_block)
    for l in post:
        for t in l.inputs:
            if t.tensor_id in block_tids and t.tensor_id != exit_tid:
                return None
    # GPipe microbatching splits the R request slots into M = (largest
    # divisor of R <= P) microbatches; a poorly-chosen R degrades silently
    # (worst case prime R -> M=1: plain round-robin at 1/P utilization).
    # Warn with the math at compile so the user picks R % P == 0
    # (reference analogue: the depth-4 in-flight pipeline always engages,
    # request_manager.cc:1829).
    R = model.config.max_requests_per_batch
    P_ = num_stages
    M = microbatch_count(R, P_)
    if M < P_:
        import warnings

        util = M / (M + P_ - 1)   # fraction of ticks each stage is busy
        warnings.warn(
            f"pipeline microbatching is degenerate: max_requests_per_batch="
            f"{R} splits into only M={M} microbatches over {P_} stages "
            f"(stage utilization {util:.0%}; M=P would give "
            f"{P_ / (2 * P_ - 1):.0%}). Choose max_requests_per_batch "
            f"divisible by pipeline_parallelism_degree={P_} (e.g. "
            f"{-(-R // P_) * P_}).", stacklevel=2)
    return PipelinePlan(pre=layers[:start0], blocks=blocks, post=post,
                        entry_tid=entry_tid, exit_tid=exit_tid,
                        block_entry_tid=block_entry,
                        block_exit_tid=block_exit, num_stages=num_stages)


# ----------------------------------------------------------------------
# Weight stacking (the "placement" step — reference inference_manager.cc:131)
# ----------------------------------------------------------------------
def finalize_pipeline(model) -> None:
    """Stack per-block weights into ``params[PP_PARAMS_KEY]`` sharded on
    the pipe axis, dropping the per-layer copies. Idempotent. Must run
    after external weight loading (LLM.compile calls it post-load)."""
    plan = model._pp_plan
    if plan is None or PP_PARAMS_KEY in model.params:
        return
    if getattr(model, "_offloaded", None):
        raise RuntimeError(
            "finalize_pipeline must run BEFORE offload_weights so paging "
            "applies to the stage-stacked leaves (LLM.compile orders "
            "them; re-run offload_weights after this call)")
    from flexflow_tpu.quant import QuantizedWeight, is_quantized

    mesh = model.mesh

    def shard_spec(shape, dims):
        spec = ["pipe"]
        for dim_size, ax in zip(shape, dims):
            ok = (ax in mesh.shape and mesh.shape[ax] > 1
                  and dim_size % mesh.shape[ax] == 0)
            spec.append(ax if ok else None)
        return NamedSharding(mesh, P(*spec))

    stacked: Dict[str, Dict[str, Any]] = {}
    for pos, tlayer in enumerate(plan.template):
        if not tlayer.weights:
            continue
        per_w = {}
        for w in tlayer.weights:
            leaves = [model.params[plan.blocks[i][pos].name][w.name]
                      for i in range(plan.num_blocks)]
            dims = w.sharding_dims or (None,) * len(w.shape)
            if is_quantized(leaves[0]):
                # stack payload + scale separately (QuantizedWeight is a
                # leaf-pair pytree; lax.scan over the stacked params then
                # hands each block its own [rows, cols]/[cols] pair with
                # the static aux intact — reference composes 4/8-bit with
                # TP x PP serving too, config.h:144-163). Payload dims
                # validate against the ACTUAL q shape (int4 packs rows).
                t = leaves[0]
                q = jax.device_put(jnp.stack([l.q for l in leaves]),
                                   shard_spec(leaves[0].q.shape, dims))
                sc = jax.device_put(
                    jnp.stack([l.scale for l in leaves]),
                    shard_spec(t.scale.shape, dims[-1:]))
                per_w[w.name] = QuantizedWeight(t.qtype, q, sc, t.rows,
                                                t.dtype)
            else:
                per_w[w.name] = jax.device_put(
                    jnp.stack(leaves), shard_spec(w.shape, dims))
            for i in range(plan.num_blocks):
                del model.params[plan.blocks[i][pos].name][w.name]
        stacked[str(pos)] = per_w
    for blk in plan.blocks:
        for l in blk:
            model.params.pop(l.name, None)
    model.params[PP_PARAMS_KEY] = stacked
    # stage-shard the stacked KV caches too
    kv = model.op_state.get("kv_cache")
    if kv is not None:
        sh = NamedSharding(mesh, P("pipe"))
        model.op_state["kv_cache"] = {k: jax.device_put(v, sh)
                                      for k, v in kv.items()}


def stacked_param_lookup(model, layer_name: str, weight_name: str):
    """(pos, i) — block-local layer position (as the params key) and block
    index — for a block layer's weight post-finalize, else None."""
    plan = getattr(model, "_pp_plan", None)
    if plan is None or PP_PARAMS_KEY not in model.params:
        return None
    for i, blk in enumerate(plan.blocks):
        for pos, l in enumerate(blk):
            if l.name == layer_name:
                return (str(pos), i)
    return None


# ----------------------------------------------------------------------
# Execution
# ----------------------------------------------------------------------
def run_pp_graph(model, params, feeds: Dict[int, Any], ctx,
                 state: Optional[Dict[str, Any]]):
    """Drop-in for FFModel._run_graph on the serving path when a pipeline
    plan is finalized: pre segment (GSPMD) -> stage-sharded block segment
    (shard_map over "pipe") -> post segment (GSPMD)."""
    plan = model._pp_plan
    values: Dict[int, Any] = dict(feeds)
    ctx.state_in = state or {}
    ctx.state_out = {}
    for layer in plan.pre:
        model._apply_layer(layer, params, values, ctx)

    kv = ctx.state_out.get("kv_cache") or ctx.state_in["kv_cache"]
    x0 = values[plan.entry_tid]
    out, new_k, new_v = _pp_segment(model, plan)(
        params[PP_PARAMS_KEY], kv["k"], kv["v"], x0, ctx.batch_config,
        ctx.rng)
    ctx.state_out["kv_cache"] = {"k": new_k, "v": new_v}
    values[plan.exit_tid] = out

    for layer in plan.post:
        model._apply_layer(layer, params, values, ctx)
    new_state = dict(ctx.state_in)
    new_state.update(ctx.state_out)
    return values, new_state


def _apply_block(model, plan, ctx, lp_by_pos, k_l, v_l, x):
    """Apply one transformer block (template layers) to activation ``x``
    with this layer's params + KV slices. Returns (y, new_k, new_v)."""
    values = {plan.block_entry_tid: x}
    ctx.kv_override = (k_l, v_l)
    ctx.kv_written = None
    pp_off = (getattr(model, "_offloaded", None) or {}).get(PP_PARAMS_KEY,
                                                            {})
    for pos, layer in enumerate(plan.template):
        from flexflow_tpu.ops.base import get_op_impl

        impl = get_op_impl(layer.op_type)
        ins = [values[t.tensor_id] for t in layer.inputs]
        ctx.layer_name = layer.name
        lp = lp_by_pos.get(str(pos), {})
        off_names = pp_off.get(str(pos))
        if off_names:
            from flexflow_tpu.offload import fetch_block_params

            lp = fetch_block_params(lp, off_names)
        outs = impl.forward(layer.attrs, lp, ins, ctx)
        for t, v in zip(layer.outputs, outs):
            values[t.tensor_id] = v
    new_k, new_v = ctx.kv_written
    ctx.kv_override = None
    ctx.kv_written = None
    return values[plan.block_exit_tid], new_k, new_v


def _pp_segment(model, plan):
    """Build (and cache) the shard_map'd block-segment function.

    GPipe microbatch schedule over REQUEST SLOTS: the batch's R rows split
    into M microbatches (M = largest divisor of R <= P) that stream
    through the P stages in M+P-1 ticks — per step, each stage computes
    (M+P-1)/M microbatch-forwards instead of P full-batch forwards
    (utilization M*P/(M+P-1) vs 1/P for the naive round-robin), and KV
    commits touch only the active microbatch's row slice instead of a
    masked full-cache select. This is the request-level analogue of the
    reference's in-flight batch pipeline (request_manager.cc:1829)."""
    cached = getattr(model, "_pp_segment_fn", None)
    if cached is not None:
        return cached
    mesh = model.mesh
    n_stages = int(mesh.shape["pipe"])

    def seg(stacked, k, v, x, meta, rng):
        # fresh context for the manual-over-pipe region; ops only read
        # these fields plus layer_name
        from flexflow_tpu.ops.base import OpContext

        ctx = OpContext(training=False, rng=rng,
                        compute_dtype=jnp.dtype(model.config.compute_dtype),
                        batch_config=meta, mesh=mesh, config=model.config)
        stage = jax.lax.axis_index("pipe")
        n_p = n_stages    # NOT named P: this module aliases PartitionSpec
        R = x.shape[0]
        M = microbatch_count(R, n_p)
        rsize = R // M

        def local_apply(x_mb, k_mb, v_mb, meta_mb):
            ctx.batch_config = meta_mb

            def body(carry, xs):
                lp, kl, vl = xs
                y, k2, v2 = _apply_block(model, plan, ctx, lp, kl, vl,
                                         carry)
                return y, (k2, v2)

            y, (k2, v2) = jax.lax.scan(body, x_mb, (stacked, k_mb, v_mb))
            return y, k2, v2

        def rows(a, start):
            return jax.lax.dynamic_slice_in_dim(a, start * rsize, rsize,
                                                axis=0)

        perm = [(i, (i + 1) % n_p) for i in range(n_p)]
        buf = jnp.zeros((rsize,) + x.shape[1:], x.dtype)
        outbuf = jnp.zeros_like(x)
        for t in range(M + n_p - 1):
            mb = t - stage                       # this stage's microbatch
            valid = (mb >= 0) & (mb < M)
            mbc = jnp.clip(mb, 0, M - 1)
            # stage 0 ingests microbatch t; later stages take the handoff
            x_in = jax.lax.slice_in_dim(x, min(t, M - 1) * rsize,
                                        min(t, M - 1) * rsize + rsize,
                                        axis=0)
            cur = jnp.where(stage == 0, x_in, buf)
            meta_mb = jax.tree.map(lambda f: rows(f, mbc), meta)
            k_mb = jax.lax.dynamic_slice_in_dim(k, mbc * rsize, rsize,
                                                axis=1)
            v_mb = jax.lax.dynamic_slice_in_dim(v, mbc * rsize, rsize,
                                                axis=1)
            y, k2, v2 = local_apply(cur, k_mb, v_mb, meta_mb)
            # commit only the active microbatch's KV rows
            k2 = jnp.where(valid, k2, k_mb)
            v2 = jnp.where(valid, v2, v_mb)
            k = jax.lax.dynamic_update_slice_in_dim(k, k2, mbc * rsize,
                                                    axis=1)
            v = jax.lax.dynamic_update_slice_in_dim(v, v2, mbc * rsize,
                                                    axis=1)
            # the last stage finished microbatch mb this tick
            take = (stage == n_p - 1) & valid
            cur_rows = rows(outbuf, mbc)
            outbuf = jax.lax.dynamic_update_slice_in_dim(
                outbuf, jnp.where(take, y, cur_rows), mbc * rsize, axis=0)
            if t < M + n_p - 2:
                buf = jax.lax.ppermute(y, "pipe", perm)
        out = jax.lax.psum(
            jnp.where(stage == n_p - 1, outbuf, jnp.zeros_like(outbuf)),
            "pipe")
        return out, k, v

    pipe_spec = jax.tree.map(lambda _: P("pipe"),
                             model.params[PP_PARAMS_KEY])
    fn = jax.shard_map(
        seg, mesh=mesh,
        in_specs=(pipe_spec, P("pipe"), P("pipe"), P(), P(), P()),
        out_specs=(P(), P("pipe"), P("pipe")),
        axis_names={"pipe"}, check_vma=False)

    def wrapped(stacked, k, v, x, meta, rng):
        if rng is None:
            rng = jax.random.PRNGKey(0)
        return fn(stacked, k, v, x, meta, rng)

    model._pp_segment_fn = wrapped
    return wrapped
