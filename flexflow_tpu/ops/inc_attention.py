"""Serving attention: incremental, speculative, and tree-verify variants.

Capability parity with the reference's serving-attention op family
(reference src/ops/inc_multihead_self_attention.cu ~1,259 LoC:
fused qkv projection -> rotary -> per-request KV-cache append
(update_kv_cache_kernel :376) -> attention (compute_attention_kernel :560)
-> output projection; spec_inc_multihead_self_attention.cu for the
draft-model side; tree_inc_multihead_self_attention.cu for verification with
commit_tokens_kernel :35 and the causal tree mask).

TPU-first redesign: the KV cache is a functional array
``[max_requests, max_seq, kv_heads, head_dim]`` threaded through the jitted
step (donated, so XLA aliases it in place — no copy). The cache append is a
vectorized scatter over request slots; attention is one batched einsum over
the full cache with a position mask, which maps directly onto the MXU. GQA
and MQA (reference inc_multiquery_self_attention, model.h:746) fall out of a
``[kv_heads, group]`` reshape. All requests advance in one SPMD program —
the reference instead launches per-op Legion tasks and loops over requests
inside the kernel.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from flexflow_tpu.core.layer import WeightSpec
from flexflow_tpu.core.initializer import default_kernel_initializer, ZeroInitializer
from flexflow_tpu.ffconst import DataType, OpType
from flexflow_tpu.ops import kv_layout as kvl
from flexflow_tpu.ops.base import OpImpl, register_op, register_op_as


# ----------------------------------------------------------------------
# Rotary position embedding (reference apply_rotary_embd in
# inc_multihead_self_attention.cu; HF-LLaMA "NeoX" rotate-half convention,
# which is the alignment oracle for the model zoo).
# ----------------------------------------------------------------------
def rotary_cos_sin(positions: jnp.ndarray, head_dim: int, theta: float,
                   dtype, inv_freq=None) -> tuple:
    """positions [R, Q] -> cos/sin [R, Q, head_dim]. ``inv_freq``
    (``head_dim / 2`` numbers): the frequency table itself, where it is not
    ``theta``'s (a scaled rotary embedding such as YaRN is such a table:
    models/mistral4.yarn_inv_freq)."""
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (
            jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    else:
        inv_freq = jnp.asarray(inv_freq, jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [R,Q,D/2]
    angles = jnp.concatenate([angles, angles], axis=-1)           # [R,Q,D]
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def apply_rotary(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
                 ) -> jnp.ndarray:
    """x [R, Q, heads, D]; cos/sin [R, Q, D]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos[:, :, None, :] + rotated * sin[:, :, None, :]


def apply_partial_rotary(x: jnp.ndarray, positions: jnp.ndarray,
                         rotary_dim: int, theta: float) -> jnp.ndarray:
    """x [R, Q, heads, D] rotated over the FIRST ``rotary_dim`` of each
    head's dims (rotate-half inside them, frequencies over ``rotary_dim``:
    HF ``partial_rotary_factor``); the rest pass as they are."""
    cos, sin = rotary_cos_sin(positions, rotary_dim, theta, x.dtype)
    half, rest = rotary_dim // 2, x.shape[-1] - rotary_dim
    # one shuffle of the head's dims and full-width tables (cos 1, sin 0
    # over the dims that pass): the same numbers as rotating a slice and
    # concatenating, in fewer device operations
    pad = [(0, 0)] * (cos.ndim - 1) + [(0, rest)]
    cos = jnp.pad(cos, pad, constant_values=1)[:, :, None, :]
    sin = jnp.pad(sin, pad)[:, :, None, :]
    turned = jnp.concatenate(
        [-x[..., half:rotary_dim], x[..., :half], x[..., rotary_dim:]],
        axis=-1)
    return x * cos + turned * sin


# ----------------------------------------------------------------------
# KV cache update (reference update_kv_cache_kernel, inc_mha.cu:376)
# ----------------------------------------------------------------------
def append_kv(cache: jnp.ndarray, new: jnp.ndarray, start_pos: jnp.ndarray,
              num_tokens: jnp.ndarray, active: jnp.ndarray,
              pack: int = 1, ring: bool = False) -> jnp.ndarray:
    """Write new [R, Q, KH, D] into cache [R, KH, S, D] at per-slot offsets
    (``ring``: a windowed layer's ring of S rows, ops/kv_layout.py: a
    position lands in its ring row, and none is past the end).

    A packed cache (``pack`` > 1: [R, KH, S/pack, pack*D], ops/kv_layout.py)
    has no row to scatter a position into: it takes the exact contiguous
    append below, which drops the same tokens.

    Padding tokens and inactive slots are dropped. The head-major cache
    layout keeps each head's [S, D] block contiguous, which is what the
    Pallas decode kernel streams per KH-batched matmul.

    Decode (Q == 1) scatters one D-row per (request, head): XLA keeps the
    cache's canonical {3,2,1,0} layout for that index pattern and updates
    the donated buffer in place. The windowed [KH, D] scatter it would
    otherwise emit gets a {3,1,2,0}-permuted output layout plus a
    full-cache copy per layer per step to re-feed the (default-layout)
    Pallas kernel — ~8MB x 2 x n_layers of pure HBM traffic per decode
    step. Prefill / tree steps (Q > 1) keep the windowed scatter: the copy
    cost is amortized over the whole chunk.
    """
    if pack > 1:
        return append_kv_contiguous(cache, None, new, start_pos, active,
                                    num_tokens=num_tokens, pack=pack)
    R, Q = new.shape[0], new.shape[1]
    S = cache.shape[2]
    KH = cache.shape[1]
    if ring:
        start_pos = kvl.ring_row(start_pos, S)
    if Q == 1:
        valid = (num_tokens > 0) & active & (start_pos < S)
        rows = jnp.broadcast_to(jnp.arange(R)[:, None], (R, KH))
        heads = jnp.broadcast_to(jnp.arange(KH)[None, :], (R, KH))
        cols = jnp.where(valid[:, None],
                         jnp.broadcast_to(start_pos[:, None], (R, KH)), S)
        upd = jnp.swapaxes(new.astype(cache.dtype), 1, 2)[:, :, 0]  # [R,KH,D]
        return cache.at[rows, heads, cols].set(upd, mode="drop")
    rows = jnp.arange(R)[:, None]                                   # [R, 1]
    cols = start_pos[:, None] + jnp.arange(Q)[None, :]              # [R, Q]
    if ring:
        cols = kvl.ring_row(cols, S)
    valid = (jnp.arange(Q)[None, :] < num_tokens[:, None]) & active[:, None]
    cols = jnp.where(valid, cols, S)  # out of bounds -> dropped
    return cache.at[rows, :, cols].set(new.astype(cache.dtype), mode="drop")


_append_kv_fn = append_kv   # alias: _attend's append_kv kwarg shadows it


def append_kv_stacked(stack: jnp.ndarray, layer_idx: int, new: jnp.ndarray,
                      start_pos: jnp.ndarray, num_tokens: jnp.ndarray,
                      active: jnp.ndarray, pack: int = 1,
                      ring: bool = False) -> jnp.ndarray:
    """Write new [R, Q, KH, D] into the stacked cache [L, R, KH, S, D] at
    layer ``layer_idx``, in place (a packed stack, a ring: as ``append_kv``).

    Scattering one D-row per (layer, request, head, token) keeps the
    stack's canonical layout and updates the donated buffer with no
    slice-out/write-back round trip — the per-layer alternative
    (``stack[i]`` -> append -> ``stack.at[i].set``) costs an 8.4MB read +
    8.4MB write per cache per layer per step at bench geometry.
    """
    if pack > 1:
        return append_kv_contiguous(stack, layer_idx, new, start_pos, active,
                                    num_tokens=num_tokens, pack=pack)
    R, Q = new.shape[0], new.shape[1]
    KH, S = stack.shape[2], stack.shape[3]
    sh = (R, KH, Q)
    lidx = jnp.full(sh, layer_idx, jnp.int32)
    rows = jnp.broadcast_to(jnp.arange(R)[:, None, None], sh)
    heads = jnp.broadcast_to(jnp.arange(KH)[None, :, None], sh)
    cols = (jnp.broadcast_to(start_pos[:, None, None], sh)
            + jnp.arange(Q)[None, None, :])
    if ring:
        cols = kvl.ring_row(cols, S)
    valid = ((jnp.arange(Q)[None, None, :] < num_tokens[:, None, None])
             & active[:, None, None])
    cols = jnp.where(valid, cols, S)  # out of bounds -> dropped
    upd = jnp.swapaxes(new.astype(stack.dtype), 1, 2)       # [R, KH, Q, D]
    return stack.at[lidx, rows, heads, cols].set(upd, mode="drop")


def _qkv(attrs, params, x, compute_dtype):
    """Project x [R, Q, E] -> q [R,Q,H,D], k/v [R,Q,KH,D].

    With ``attrs["qk_norm_eps"]`` (OLMoE; absent = no such step) q and k
    are RMS-normalised over their WHOLE projection, before the split into
    heads and before the rotary embedding; where the norm weight is one
    head wide (EXAONE-MoE), over each head's ``head_dim`` instead."""
    from flexflow_tpu.quant import qmatmul

    H = attrs["num_q_heads"]
    KH = attrs["num_kv_heads"]
    D = attrs["head_dim"]
    q = qmatmul(x, params["wq"])
    k = qmatmul(x, params["wk"])
    v = qmatmul(x, params["wv"])
    n_bias = sum(k_ in params for k_ in ("bq", "bk", "bv"))
    if n_bias == 3:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    elif n_bias:
        raise ValueError(
            "attention qkv bias set must be all-present or all-absent; "
            f"got {sorted(k_ for k_ in ('bq', 'bk', 'bv') if k_ in params)}")
    eps = attrs.get("qk_norm_eps")
    if eps is not None:
        from flexflow_tpu.ops.norm import _rms_norm

        if params["q_norm"].shape[0] != q.shape[-1]:    # a head's norm
            q = q.reshape(q.shape[:-1] + (H, D))
            k = k.reshape(k.shape[:-1] + (KH, D))
        q = _rms_norm(q, params["q_norm"], eps)
        k = _rms_norm(k, params["k_norm"], eps)
    R, Q = x.shape[0], x.shape[1]
    return (q.reshape(R, Q, H, D), k.reshape(R, Q, KH, D),
            v.reshape(R, Q, KH, D))


def alibi_slopes(num_heads: int) -> jnp.ndarray:
    """ALiBi per-head slopes (press et al.; matches HF MPT build_alibi_bias
    for power-of-two head counts, which all zoo models have)."""
    closest = 2 ** math.floor(math.log2(num_heads))
    base = jnp.arange(1, closest + 1, dtype=jnp.float32)
    slopes = 2.0 ** (-8.0 * base / closest)
    if closest < num_heads:
        extra = 2.0 ** (-4.0 * base / closest)
        slopes = jnp.concatenate([slopes, extra[: num_heads - closest]])
    return slopes


def _attend(attrs, q, k_cache, v_cache, lengths, qpos, out_dtype, ctx,
            bias=None, causal=True, layer_idx=None, append_kv=None,
            rows=None):
    """q [R,Q,H,D] x cache [R,KH,S,D] -> [R, Q, H*D]. The cache comes as
    stored (packed [R,KH,S/2,128] at D=64 on the flash path, see
    ops/kv_layout.py) and goes to the kernel as it is; the jnp paths unpack
    the one layer they attend.

    ``rows`` [R] (the compact prefill batch's ``BatchMeta.slots``): batch
    row r attends cache row rows[r]. The Pallas kernel takes it as a DMA
    row map; the jnp paths gather those rows.

    With ``layer_idx`` the caches are the full stacked [L, R, KH, S, D]
    buffers and only that layer is read — the Pallas kernel DMAs straight
    out of the stack, so no per-layer slice is ever materialized in HBM.

    Dispatches to the Pallas flash kernel on TPU (kernels/attention.py) or
    the jnp oracle elsewhere. ``lengths`` [R] is the valid cache extent
    (finished/inactive slots pass 0 and cost nothing on the Pallas path);
    ``qpos`` [R, Q] absolute query positions drive causal masking + ALiBi;
    ``bias`` [R, Q, S] is the additive tree mask for verification.

    ``append_kv = (k_new [R, A, KH, D], v_new same, appos [R][, n [R]])``
    fuses the decode-step KV append into the kernel: the first n[r] (all A
    without ``n``) of each row's new K/V rows land at cache positions
    appos[r] on (appos < 0 = skip) via in-place DMA, merged into the
    stream — replacing the XLA row scatter that cost ~1.6 ms/step at 7B
    (R*KH*L scalar-unit rows a position of the run). Returns (out,
    new_k_cache, new_v_cache); the passed caches are consumed (aliased
    through the kernel). The jnp path performs the same append with the
    scatter, so semantics are identical everywhere. A run (A > 1) is the
    kernel's on a plain cache only (``takes_run``): the caller asks first.

    A windowed layer (``attrs["sliding_window"]``) hands over its ring
    (ops/kv_layout.py): the kernel is told the window and the jnp oracle
    the position every ring row holds.

    A chunked layer (``attrs["eva_window"]``) hands over its stream of two
    extents (ops/kv_layout.py): ``lengths``, ``qpos`` and the append's
    position go on as rows of that stream, with the summary rows each row
    of the batch sees, to the kernel and to the jnp oracle alike.
    """
    from flexflow_tpu import kernels as ffk
    from flexflow_tpu.kernels.attention import flash_attend, reference_attend

    D = attrs["head_dim"]
    scale = (1.0 / math.sqrt(D)) if attrs.get("qk_prod_scaling", True) else 1.0
    if attrs.get("scaling_query", False):
        scale = scale * attrs.get("scaling_factor", 1.0)
    alibi = (alibi_slopes(attrs["num_q_heads"])
             if attrs.get("position_bias", False) else None)
    S = attrs["max_seq_length"]
    window = attrs.get("sliding_window")
    if window is not None:
        S = k_cache.shape[-2]       # the ring's rows, which the kernel tiles
        assert bias is None and causal, "a windowed layer attends causally"
    chunked = attrs.get("eva_window")
    if chunked is not None:
        S = k_cache.shape[-2]       # both extents' rows
        assert bias is None and causal, "a chunked layer attends causally"
        ns = kvl.chunked_summary_rows(attrs["max_seq_length"],
                                      attrs["chunk_size"])
        summaries, lengths, qpos = kvl.chunked_view(
            lengths, qpos, ns, chunked, attrs["chunk_size"])
        if append_kv is not None:
            k_new, v_new, appos = append_kv
            append_kv = (k_new, v_new, jnp.where(
                appos >= 0, kvl.chunked_window_row(appos, ns, chunked), -1))
    pack = kvl.pack_of(k_cache, S)
    Dp = k_cache.shape[-1] // pack  # cache head dim (128-padded)
    Q = q.shape[1]
    mesh = getattr(ctx, "mesh", None) if ctx is not None else None
    seq_deg = _seq_degree(mesh)
    refusal = _kernel_refusal(attrs, ctx, k_cache, Q, bias)
    if refusal is not None:
        if refusal:                 # ("": switched off, nothing to report)
            ffk.record_fallback(refusal)
    else:
        R, H = q.shape[0], q.shape[2]
        A = None if append_kv is None else append_kv[0].shape[1]
        ffk.record_fast_path(append=A, stream=_stream_form(
            q, k_cache, S, Dp, mesh, "rows" if rows is not None else
            "grid" if A is None else "append" if A == 1 else "run",
            plain=window is None and chunked is None and bias is None))
        fkv = None
        if append_kv is not None:
            k_new, v_new, *at = append_kv             # [R, A, KH, D] each
            fkv = (_pad_d(k_new, Dp), _pad_d(v_new, Dp), *at)
        # the stack's plane: static, or traced inside a loop region
        # (``cache_plane``) and then an operand of the kernel
        which = ("layer_idx" if layer_idx is None
                 or isinstance(layer_idx, int) else "plane")
        attend = functools.partial(
            flash_attend, causal=causal, qk_scale=scale,
            out_dtype=out_dtype, **{which: layer_idx},
            interpret=ffk.pallas_interpret_forced(), window=window,
            **({} if chunked is None else
               {"summaries": summaries, "summary_rows": ns}))
        args = (_pad_d(q, Dp), k_cache, v_cache, lengths, qpos, bias, alibi,
                fkv, rows)
        if (mesh is not None and mesh.devices.size > 1
                and getattr(ctx, "kv_override", None) is None):
            attend = _attend_on_mesh(attend, mesh, *args)
        res = attend(*args)
        out, caches = (res, ()) if append_kv is None else (res[0], res[1:])
        if Dp != D:                 # drop the per-head lane padding
            out = out.reshape(R, Q, H, Dp)[..., :D].reshape(R, Q, H * D)
        return out if append_kv is None else (out,) + caches
    new_caches = ()
    if append_kv is not None:
        k_new, v_new, appos, *count = append_kv
        ffk.record_scatter_append(k_new.shape[1])
        valid = appos >= 0
        start = jnp.maximum(appos, 0)
        num = count[0] if count else valid.astype(jnp.int32)
        kp, vp = _pad_d(k_new, Dp), _pad_d(v_new, Dp)
        ring = window is not None
        if layer_idx is not None:
            k_cache = append_kv_stacked(k_cache, layer_idx, kp, start, num,
                                        valid, pack, ring)
            v_cache = append_kv_stacked(v_cache, layer_idx, vp, start, num,
                                        valid, pack, ring)
        else:
            k_cache = _append_kv_fn(k_cache, kp, start, num, valid, pack,
                                    ring)
            v_cache = _append_kv_fn(v_cache, vp, start, num, valid, pack,
                                    ring)
        new_caches = (k_cache, v_cache)
    kc, vc = k_cache, v_cache
    if layer_idx is not None:
        kc, vc = k_cache[layer_idx], v_cache[layer_idx]
    if rows is not None:
        kc, vc = kc[rows], vc[rows]
    # the jnp oracles attend position-major [R, KH, S, D]
    kc, vc = kvl.to_positions(kc, pack), kvl.to_positions(vc, pack)
    if seq_deg > 1 and S % seq_deg == 0:
        # searched sequence-parallel plan: the cache S dim is sharded over
        # the mesh's "seq" axis — score local slices, reconcile the softmax
        # with pmax/psum (parallel/ring_attention.seq_sharded_attend)
        from flexflow_tpu.parallel.ring_attention import seq_sharded_attend
        out = seq_sharded_attend(
            q, kc[..., :D], vc[..., :D], lengths, qpos, mesh, bias=bias,
            alibi=alibi, causal=causal, qk_scale=scale, out_dtype=out_dtype)
        return out if append_kv is None else (out,) + new_caches
    out = reference_attend(
        q, kc[..., :D], vc[..., :D], lengths, qpos, bias=bias,
        alibi=alibi, causal=causal, qk_scale=scale, out_dtype=out_dtype,
        window=window,
        key_pos=(kvl.chunked_key_rows(summaries, ns, S)
                 if chunked is not None else
                 None if window is None else kvl.ring_positions(lengths, S)))
    return out if append_kv is None else (out,) + new_caches


def _stream_form(q, k_cache, S: int, Dp: int, mesh, mode: str, plain: bool):
    """The (form, mode, DMA block) ``flash_attend`` takes over this cache,
    for ``kernels.stream_form_counts``: its own rule
    (``kernels/attention.stream_block``) on the shapes a chip sees (under
    tensor parallelism its own heads, as ``_attend_on_mesh`` splits them);
    a ring, a chunked stream and a biased step keep the loop."""
    from flexflow_tpu.kernels.attention import _pick_block_s, stream_block

    KH, Q, H = k_cache.shape[-3], q.shape[1], q.shape[2]
    BS = _pick_block_s(S, Dp)
    tp = (mesh.shape["model"] if mesh is not None and mesh.devices.size > 1
          and "model" in mesh.axis_names else 1)
    DB = BS if not plain else stream_block(
        KH // tp if KH % tp == 0 else KH, Dp, k_cache.dtype.itemsize,
        H // KH * Q, S)
    return ("block" if DB > BS else "loop", mode, DB)


def _seq_degree(mesh) -> int:
    return (mesh.shape["seq"] if mesh is not None
            and "seq" in getattr(mesh, "axis_names", ()) else 1)


def _kernel_refusal(attrs, ctx, k_cache, Q: int, bias=None):
    """Why ``_attend`` would attend this layer's cache (as stored; a layer
    or the stack) off the Pallas kernel at query width ``Q``: a reason to
    record, "" where the kernels are switched off and nothing is amiss, or
    None: the kernel serves it."""
    from flexflow_tpu import kernels as ffk
    from flexflow_tpu.kernels.attention import (supports_chunked,
                                                supports_shapes)

    S = attrs["max_seq_length"]
    chunked = attrs.get("eva_window")
    if attrs.get("sliding_window") is not None or chunked is not None:
        S = k_cache.shape[-2]       # the ring's rows; both extents' rows
    pack = kvl.pack_of(k_cache, S)
    Dp = k_cache.shape[-1] // pack
    mesh = getattr(ctx, "mesh", None) if ctx is not None else None
    seq_deg = _seq_degree(mesh)
    if not ffk.use_pallas(ctx.config if ctx is not None else None):
        # (a packed cache: allocated for a kernel now switched off)
        return "packed cache attended off the Pallas path" if pack > 1 else ""
    if seq_deg > 1 and S % seq_deg == 0:
        # the cache's S dim lives sharded over the mesh: the kernel streams
        # one device's HBM, so this plan attends through the jnp
        # seq_sharded_attend
        return "cache sharded over the mesh's 'seq' axis"
    if not supports_shapes(S, Dp):
        return f"cache shape S={S} D={Dp} not tileable"
    if chunked is not None:
        ns = kvl.chunked_summary_rows(attrs["max_seq_length"],
                                      attrs["chunk_size"])
        if not supports_chunked(S, ns, Dp):
            return (f"chunked stream of {ns} + {S - ns} rows, D={Dp}: the "
                    "extents are not whole blocks")
    if Q > 256:
        return f"query width {Q} > 256"
    if bias is not None and Q % 8 != 0:
        # biased (tree) attention DMAs [Q, BS] bias blocks; Mosaic needs
        # the sublane (Q) dim 8-aligned — unaligned tree widths take the
        # jnp path (MultiSpecEngine pads its tree so this never triggers)
        return f"tree width {Q} not 8-aligned"
    return None


def takes_run(attrs, ctx, k_cache, Q: int, A: int) -> bool:
    """Will ``_attend``'s kernel append a run of ``A`` positions a row of a
    step ``Q`` wide into this layer's cache? A plain position-major cache
    (no ring, no chunked stream, no packed D=64 rows) on the kernel path,
    and a run of up to ``kernels.attention.APPEND_RUN_MOST`` = 8 positions:
    a decode step's (a verify width, two diffusion blocks), which the
    kernel's two aligned write-back windows of 16 stored rows hold from any
    start; a wider step is a prefill chunk."""
    from flexflow_tpu.kernels.attention import APPEND_RUN_MOST

    return (1 < A <= APPEND_RUN_MOST
            and attrs.get("sliding_window") is None
            and attrs.get("eva_window") is None
            and kvl.pack_of(k_cache, attrs["max_seq_length"]) == 1
            and _kernel_refusal(attrs, ctx, k_cache, Q) is None)


def _attend_on_mesh(attend, mesh, q, k_cache, v_cache, lengths, qpos, bias,
                    alibi, append_kv, rows):
    """``flash_attend`` as a manual region over the whole mesh.

    XLA refuses to partition a Mosaic custom call ("cannot be
    automatically partitioned"), so on a multi-chip mesh the kernel runs
    inside ``jax.shard_map``. Under tensor parallelism every chip attends
    its own heads over its own slice of the cache: q, the cache and the
    output split on the head dim over 'model' exactly as wq/wk/wv/wo and
    parallel/spec.kv_cache_sharding place them, so no operand moves. A KV
    head count the degree does not divide keeps the cache replicated and
    every chip then attends all heads. Other mesh axes replicate the call.
    (The pipeline segment, ``kv_override``, is already manual over "pipe"
    and does not come through here.)"""
    tp = mesh.shape["model"] if "model" in mesh.axis_names else 1
    m = "model" if tp > 1 and k_cache.shape[-3] % tp == 0 else None
    heads = P(None, None, m, None)                  # [R, Q, heads, D]
    cache = P(*([None] * (k_cache.ndim - 3)), m, None, None)
    out = P(None, None, m)                          # [R, Q, H*D]
    # None operands are empty pytrees: their spec is None too
    in_specs = (heads, cache, cache, P(), P(),
                None if bias is None else P(),
                None if alibi is None else P(m),
                None if append_kv is None else (
                    (heads, heads) + (P(),) * (len(append_kv) - 2)),
                None if rows is None else P())
    return jax.shard_map(
        attend, mesh=mesh, in_specs=in_specs,
        out_specs=out if append_kv is None else (out, cache, cache),
        check_vma=False)


def _weight_specs(attrs, input_specs):
    (shape, d) = input_specs[0]
    E = shape[-1]
    H, KH, D = attrs["num_q_heads"], attrs["num_kv_heads"], attrs["head_dim"]
    dt = attrs.get("data_type") or d
    init = attrs.get("kernel_initializer") or default_kernel_initializer()
    # TP splits projections at WHOLE-head boundaries only (shard_multiples
    # = head_dim): the serving kernels consume [*, heads, D] blocks, and a
    # sub-head split puts RoPE's rotate-half slice across a shard edge
    # (wrong numerics out of the XLA SPMD partitioner — a KH that the TP
    # degree doesn't divide now replicates wk/wv instead)
    specs = [
        WeightSpec("wq", (E, H * D), dt, init, sharding_dims=(None, "model"),
                   shard_multiples=(None, D)),
        WeightSpec("wk", (E, KH * D), dt, init, sharding_dims=(None, "model"),
                   shard_multiples=(None, D)),
        WeightSpec("wv", (E, KH * D), dt, init, sharding_dims=(None, "model"),
                   shard_multiples=(None, D)),
        WeightSpec("wo", (H * D, E), dt, init, sharding_dims=("model", None),
                   shard_multiples=(D, None)),
    ]
    if attrs.get("bias", False):
        zero = ZeroInitializer()
        specs += [
            WeightSpec("bq", (H * D,), dt, zero, sharding_dims=("model",),
                       shard_multiples=(D,)),
            WeightSpec("bk", (KH * D,), dt, zero, sharding_dims=("model",),
                       shard_multiples=(D,)),
            WeightSpec("bv", (KH * D,), dt, zero, sharding_dims=("model",),
                       shard_multiples=(D,)),
            WeightSpec("bo", (E,), dt, zero),
        ]
    if attrs.get("output_gate"):
        specs.append(WeightSpec("wg", (E, H * D), dt, init,
                                sharding_dims=(None, "model"),
                                shard_multiples=(None, D)))
    if attrs.get("qk_norm_eps") is not None:
        from flexflow_tpu.core.initializer import ConstantInitializer

        one = ConstantInitializer(1.0)
        if attrs.get("qk_norm_per_head"):
            specs += [WeightSpec("q_norm", (D,), dt, one),
                      WeightSpec("k_norm", (D,), dt, one)]
        else:
            specs += [WeightSpec("q_norm", (H * D,), dt, one),
                      WeightSpec("k_norm", (KH * D,), dt, one)]
    if attrs.get("eva_window") is not None:
        # a head's two learned pooling vectors (EvaByte's adaptive_mu_k and
        # adaptive_phi, [1, KH, 1, 1, D] there), float32: drawn so that a
        # chunk's pool weights spread (mu . k of spread about 2 at keys of
        # unit spread) instead of starting at the chunk's plain mean
        from flexflow_tpu.core.initializer import NormInitializer

        pool = NormInitializer(stddev=2.0 / math.sqrt(D))
        specs += [WeightSpec("adaptive_mu_k", (KH, D), DataType.DT_FLOAT,
                             pool),
                  WeightSpec("adaptive_phi", (KH, D), DataType.DT_FLOAT,
                             pool)]
    return specs


def padded_head_dim(D: int, want_pallas: bool = True,
                    max_seq: Optional[int] = None) -> int:
    """Per-position width of the cache for the flash path. D=64
    (GPT-2-class) needs NO padding: the kernel packs two positions per
    128-lane cache row (kernels/attention.py _pack_factor) and the cache
    is stored that way, [.., S/2, 128] (``_init_kv_state`` asks
    ops/kv_layout.stored_pack), so KV memory and stream bandwidth stay 1x
    and no step relays the cache. The packed mode needs the cache length
    divisible by its 256-position block, so when ``max_seq`` can't tile it
    (e.g. S=128) the cache falls back to the pad-to-128 layout,
    [.., S, 128], rather than off the flash path entirely. Other dims
    round up to the lane tile so DMA slices stay lane-full. Configs that
    can never take the flash path (use_pallas off, non-TPU backend) keep
    the exact D, position-major."""
    if not want_pallas:
        return D
    from flexflow_tpu.kernels.attention import (LANE, _pack_factor,
                                                round_up, supports_seq_len)

    if D % LANE == 0:
        return D
    if (_pack_factor(D) > 1
            and (max_seq is None or supports_seq_len(max_seq, D))):
        return D
    padded = round_up(D, LANE)
    if max_seq is not None and not supports_seq_len(max_seq, padded):
        return D                    # no flash either way: don't waste HBM
    return padded


def _pad_d(x, D_pad: int):
    D = x.shape[-1]
    if D == D_pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, D_pad - D)])


def _init_kv_state(attrs, input_specs):
    import numpy as np

    from flexflow_tpu import kernels as ffk

    R = attrs["max_requests"]
    S = attrs["max_seq_length"]
    KH, D = attrs["num_kv_heads"], attrs["head_dim"]
    cache_dtype = jnp.dtype(attrs.get("cache_dtype", "bfloat16"))
    want_pallas = attrs.get("use_pallas", True) and ffk.use_pallas()
    window = attrs.get("sliding_window")
    if attrs.get("eva_window") is not None:
        # a chunked layer keeps one summary row a chunk and, behind them,
        # its window, position-major (never packed)
        from flexflow_tpu.kernels.attention import LANE, round_up

        shape = kvl.chunked_cache_shape(
            R, KH, S, attrs["chunk_size"], attrs["eva_window"],
            round_up(D, LANE) if want_pallas else D)
    elif window is None:
        Dp = padded_head_dim(D, want_pallas=want_pallas, max_seq=S)
        # stored as the kernel reads it: [R, KH, S, Dp], or packed
        # [R, KH, S/2, 128] where a D=64 cache takes the packed flash path
        shape = kvl.cache_shape(R, KH, S, Dp,
                                kvl.stored_pack(Dp, S, want_pallas))
    else:
        # a windowed layer keeps a ring of the window plus one step's
        # tokens, position-major (a ring is never packed)
        from flexflow_tpu.kernels.attention import LANE, round_up

        rows = kvl.ring_rows(window, attrs["max_step_tokens"], S)
        shape = (R, KH, rows, round_up(D, LANE) if want_pallas else D)
    return {
        "k_cache": jnp.zeros(shape, dtype=cache_dtype),
        "v_cache": jnp.zeros(shape, dtype=cache_dtype),
    }


def _project_out(attrs, params, ctx, attn_out, x=None):
    from flexflow_tpu.quant import qmatmul

    if "wg" in params:      # ``output_gate``: sigmoid(W_g x) * Attn, then W_o
        attn_out = attn_out * jax.nn.sigmoid(
            qmatmul(x, params["wg"]).astype(jnp.float32)).astype(
                attn_out.dtype).reshape(attn_out.shape)
    out = qmatmul(attn_out, params["wo"])
    if "bo" in params:
        out = out + params["bo"]
    return out


# ----------------------------------------------------------------------
# KV-cache state access. Two layouts:
#  * per-layer (default): op_state[layer_name] = {"k_cache", "v_cache"}
#  * stacked (consolidated by FFModel.compile when all serving-attention
#    layers share one cache shape): op_state["kv_cache"] = {"k": [L, ...],
#    "v": [L, ...]} and each layer carries attrs["cache_layer_idx"].
# Stacking cuts the donated-arg count from 2*L to 2 and lets tree-commit
# run vectorized over layers. A model with windowed layers beside full
# ones has a stack a kind: the windowed layers' rings are
# op_state[WINDOW_STACK] and each carries attrs["cache_stack"]. A latent
# layer (ops/latent_attention.py) keeps ONE stream, not a pair: its stack is
# op_state[LATENT_STACK] = {"c": [L, R, 1, S, W]}.
# ----------------------------------------------------------------------
FULL_STACK, WINDOW_STACK = "kv_cache", "kv_cache_window"
LATENT_STACK = "kv_cache_latent"
# A chunked layer (``eva_window``) keeps a summary a chunk and its window in
# one stream: its stack is op_state[CHUNKED_STACK] = {"k", "v"} of
# [L, R, KH, S / chunk + window, D], whatever the depth.
CHUNKED_STACK = "kv_cache_chunked"
# A layer whose queries, keys and values reach back along the sequence
# (ops/cca_attention.py) keeps, beside its plain k/v cache, a row's TAIL:
# op_state[TAIL_STACK] = {"t": [L, R, width]}, overwritten by every step.
TAIL_STACK = "cca_tail"
# A layer that keeps a recurrent state and no cache of positions (the
# contract of ops/recurrent.py; ops/kda_attention.py: a gated delta rule,
# ops/ssd_mixer.py: a state-space mixer): op_state[RECURRENT_STACK] = {"s":
# [L, R, ...] float32 (KDA: [H, K, V]; the mixer: [H, P, N]), "u": [L, R,
# taps - 1, channels]}, overwritten by every step. (The key's "kda" is
# historical: the benchmark's files read it by this constant.)
RECURRENT_STACK = "kda_state"


def carried_rows(stored, slots, start, n, run, layer=None):
    """The rows of a compact prefill step through a state that every step
    overwrites, in order, and what the step leaves behind: the rules of
    ``cca_attention.take_tails`` for any state.

    ``stored`` ``[slots, ...]`` (with ``layer``: ``[layers, slots, ...]``,
    and that layer's entries are read and written); ``slots``, ``start``,
    ``n`` ``[R]``: each row's slot, first position and real tokens (0: an
    idle row), rows in ascending order of ``start``; ``run(i, state) ->
    (out, end)``: row ``i`` from ``state`` to what it yields and the state
    its last real token leaves. A row starts from zeros at position 0, from
    the END of an earlier row of the step where that row is the same slot's
    and ends where this one starts, else from the store; each slot's LAST
    row's end is written back. Returns ``([out], new stored)``."""
    R = slots.shape[0]
    heads = stored[slots] if layer is None else stored[layer, slots]
    heads = jnp.where((start == 0).reshape((R,) + (1,) * (heads.ndim - 1)),
                      0, heads)
    outs, ends = [], []
    for i in range(R):      # a handful of rows, unrolled
        t = heads[i]
        for j in range(i):
            t = jnp.where((n[i] > 0) & (n[j] > 0) & (slots[j] == slots[i])
                          & (start[j] + n[j] == start[i]), ends[j], t)
        out, end = run(i, t)
        outs.append(out)
        ends.append(end)
    same = (slots[:, None] == slots[None, :]) & (n[None, :] > 0)
    last = ~jnp.any(same & (start[None, :] > start[:, None]), axis=1)
    at = jnp.where((n > 0) & last, slots,
                   stored.shape[0 if layer is None else 1])
    ends = jnp.stack(ends).astype(stored.dtype)
    where = stored.at[at] if layer is None else stored.at[layer, at]
    return outs, where.set(ends, mode="drop")


def refuse_windowed(op_state, what: str):
    """A ring holds a slot's last positions only, by ``p % rows``, a latent
    layer one shared entry a position, not a k/v pair, and a layer that
    carries a tail or a recurrent state keeps what no cache position holds:
    what moves, copies, rolls back or shards cache positions by their
    index, a pair at a time, says so. (Preemption is none of them: the
    victim is prefilled again from position 0, which rebuilds its state.)"""
    if WINDOW_STACK in (op_state or {}):
        raise NotImplementedError(
            f"{what} is not supported over a windowed attention layer: its "
            "cache is a ring (ops/kv_layout.py), not every position")
    if LATENT_STACK in (op_state or {}):
        raise NotImplementedError(
            f"{what} is not supported over a latent attention layer: its "
            "cache is one shared entry a position (ops/kv_layout.py), not "
            "a k/v pair")
    if CHUNKED_STACK in (op_state or {}):
        raise NotImplementedError(
            f"{what} is not supported over a chunked attention layer: its "
            "cache keeps a window's positions and one summary a chunk of "
            "those before (ops/kv_layout.py), so a position once left "
            "cannot be read back, moved or rolled back")
    if TAIL_STACK in (op_state or {}):
        raise NotImplementedError(
            f"{what} is not supported over an attention layer that carries "
            "a tail: beside its cache a slot keeps the last positions' "
            "unmixed latents (ops/cca_attention.py), overwritten every "
            "step, which no cache position holds, so a rejected draft "
            "cannot be rolled back and a moved, shared or sharded position "
            "has no tail to go with it")
    if RECURRENT_STACK in (op_state or {}):
        raise NotImplementedError(
            f"{what} is not supported over an attention layer that keeps a "
            "recurrent state, or a state-space mixer, which does too: a "
            "slot holds one state a layer (ops/kda_attention.py, "
            "ops/ssd_mixer.py), the sum of every position so far, "
            "overwritten every step and kept at no position, so a "
            "rejected draft cannot be rolled back, a shared prefix has no "
            "snapshot to start from, a moved position nothing to move, "
            "and a dividing mesh or a pipeline stage no hand-over for it")


def refuse_block_diffusion(model, what: str):
    """A block-diffusion model (``FFModel.block_diffusion``) sees a block
    both ways and keeps it only when it is whole: what reads a cache
    position as causal, or one token as a step, says so. (Also what the
    uncompiled builder's refusals say.)"""
    if getattr(model, "block_diffusion", None) is not None:
        raise NotImplementedError(
            f"{what} is not supported over a block-diffusion model: its "
            "decode step fills a block of positions that see each other "
            "both ways, and commits it to the cache only when it is whole")


def block_visibility(attrs, q_abs):
    """The position a query is masked by. A block-diffusion model's layer
    (``attrs["block_length"]``): the LAST position of the query's block,
    so that key ``j`` is visible to query ``i`` iff ``blk(j) <= blk(i)``,
    causal across blocks and both ways inside one; the kernels mask by
    ``key <= qpos`` and nothing else reads it here (no ALiBi, no window).
    Rotary and every append stay on true positions. Any other layer: the
    query's own position."""
    B = attrs.get("block_length")
    return q_abs if B is None else q_abs // B * B + (B - 1)


def cache_plane(ctx, attrs):
    """The plane of its stack that holds this layer's cache in this step:
    ``attrs["cache_layer_idx"]`` (a Python int; None: a cache of its own),
    and inside a loop region (``attrs["loop_planes"]``, the planes a pass;
    core/model.LoopRegion) the pass's own, ``cache_layer_idx + loop_step *
    loop_planes``, a TRACED int32 scalar: the kernels take it as an operand
    and the appends as an index, and no plane is sliced out of the stack."""
    idx = attrs.get("cache_layer_idx")
    step = getattr(ctx, "loop_step", None)
    if idx is None or step is None or "loop_planes" not in attrs:
        return idx
    return idx + step * attrs["loop_planes"]


def _stack(ctx, attrs):
    """(key, {"k", "v"}) of the stack that holds this layer's cache."""
    key = attrs.get("cache_stack", FULL_STACK)
    return key, ctx.state_out.get(key) or ctx.state_in[key]


def read_kv(ctx, attrs):
    ov = getattr(ctx, "kv_override", None)
    if ov is not None:   # pipeline-parallel block execution: the stage
        return ov        # loop hands this layer its own KV slice directly
    idx = cache_plane(ctx, attrs)
    if idx is None:
        st = ctx.state_in[ctx.layer_name]
        return st["k_cache"], st["v_cache"]
    _, st = _stack(ctx, attrs)
    return st["k"][idx], st["v"][idx]


def write_kv(ctx, attrs, k_cache, v_cache):
    if getattr(ctx, "kv_override", None) is not None:
        ctx.kv_written = (k_cache, v_cache)
        return
    idx = cache_plane(ctx, attrs)
    if idx is None:
        ctx.state_out[ctx.layer_name] = {"k_cache": k_cache,
                                         "v_cache": v_cache}
        return
    key, st = _stack(ctx, attrs)
    ctx.state_out[key] = {"k": st["k"].at[idx].set(k_cache),
                          "v": st["v"].at[idx].set(v_cache)}


def append_kv_contiguous(cache, layer_idx, new, start_pos, active,
                         slots=None, num_tokens=None, pack: int = 1,
                         ring: bool = False):
    """In-place contiguous append: per-request dynamic_update_slice of the
    [KH, Q, D] run at start_pos[r] — no scatter at all. The one writer of
    a packed cache (``pack`` > 1, ops/kv_layout.py): the run is laid into
    the window of stored rows that holds its positions (Q/pack + 1 of
    them: any parity of start_pos) and merged by position, so packing is a
    relayout of the run, never of the cache.

    Without ``num_tokens`` (the fused engines): callable ONLY under their
    guarantee that every ACTIVE row has start_pos + Q <= S (their
    live_masks enforce it). Inactive rows re-write their current region
    unchanged (a slot can be live but sitting out of an engine block —
    e.g. cramped near the cache end — so its KV must not be touched).
    Padding tokens beyond num_tokens write garbage BEYOND the valid
    extent, masked by lengths until overwritten by the next real append
    (a packed cache keeps what it held there: the merge is by position).

    With ``num_tokens`` the append is exact, as the windowed scatter's
    drop is: a padding position keeps what the cache held (a later row may
    be the same slot's next chunk), and a run that starts within Q of the
    cache's end is written through the window that ends at S with its
    tokens shifted to their own positions (the whole row, where Q > S).
    With ``slots`` besides (the compact prefill batch), batch row r's run
    lands in cache row slots[r] and the rows are unrolled. ``ring`` (a
    windowed layer's cache; always exact): the run is written through the
    two windows of ops/kv_layout.ring_pieces, to the ring's end and on
    from its start.

    This beats both scatter forms: the windowed scatter forces a permuted
    layout + full per-layer cache copies (~134MB/layer/step at 7B), and
    the row-granular scatter is scalar-unit bound (~0.1ms per 1280-row
    scatter at 7B MHA).
    """
    R, Q = new.shape[0], new.shape[1]
    rows, lanes = cache.shape[-2:]
    KH = cache.shape[-3]
    newT = jnp.swapaxes(new.astype(cache.dtype), 1, 2)    # [R, KH, Q, D]
    lead = () if layer_idx is None else (layer_idx,)
    # the eager debug dump (utils/debugging) hands numpy descriptors over
    start_pos, active = jnp.asarray(start_pos), jnp.asarray(active)
    lead1 = (1,) * (len(lead) + 1)
    if pack > 1 or ring:    # merged by position: the window is wider than Q
        num_tokens = (jnp.full((R,), Q, jnp.int32) if num_tokens is None
                      else jnp.minimum(jnp.asarray(num_tokens), Q))
    if num_tokens is None:
        def body(r, c):
            at = lead + (r, 0, jnp.clip(start_pos[r], 0, rows - Q), 0)
            cur = jax.lax.dynamic_slice(c, at, lead1 + (KH, Q, lanes))
            upd = jnp.where(active[r], newT[r][(None,) * len(lead1)], cur)
            return jax.lax.dynamic_update_slice(c, upd, at)

        return jax.lax.fori_loop(0, R, body, cache)

    num_tokens = jnp.asarray(num_tokens)
    if Q > rows * pack:
        # a chunk wider than the cache (tiny max_sequence_length): no
        # position past S exists, so the run's first S columns hold
        # every token that can land
        newT, Q = newT[:, :, :rows * pack], rows * pack
    if ring:
        W, windows = Q, kvl.ring_pieces(start_pos, Q, rows)
    else:
        row, W, off = kvl.window(start_pos, Q, rows, pack)
        windows = [(row, off)]
    pieces = []
    for row, off in windows:
        t = jnp.arange(W * pack)[None] - off[:, None]   # a column's token
        pieces.append((row, off, active[:, None] & (t >= 0)
                       & (t < num_tokens[:, None])))

    def put(c, r, slot):
        for row, off, keep in pieces:
            at = lead + (slot, 0, row[r], 0)
            cur = jax.lax.dynamic_slice(c, at, lead1 + (KH, W, lanes))
            c = jax.lax.dynamic_update_slice(
                c, kvl.merge_window(cur, newT[r], keep[r], off[r], pack), at)
        return c

    if slots is None:
        return jax.lax.fori_loop(0, R, lambda r, c: put(c, r, r), cache)
    slots = jnp.asarray(slots)
    # a handful of rows (the step's segments), in order and unrolled:
    # as a device loop each row read its scalars one operation apiece,
    # dozens a row, in every layer of every prefill step
    for r in range(R):
        cache = put(cache, r, slots[r])
    return cache


# One trace serves every layer's K and V append of a compact prefill step:
# the layer index is an operand here, where the engines' calls bake it in
# (tracing the loop 2 x layers times cost seconds of set-up at 32 layers).
_append_by_slot = jax.jit(append_kv_contiguous,
                          static_argnames=("pack", "ring"))


def _note_scatter(new, pack: int):
    """Count a step's row-granular scatter append (a packed cache has none:
    its append is the contiguous one)."""
    if pack == 1:
        from flexflow_tpu import kernels as ffk

        ffk.record_scatter_append(new.shape[1])


def append_and_ref(ctx, attrs, k, v, start_pos, num_tokens, active,
                   slots=None):
    """Append this step's KV and return (k_ref, v_ref, layer_idx) to attend
    over: layer_idx is None when the refs are this layer's own [R,KH,S,D]
    caches, or the layer's index when they are the full [L,...] stack
    (stacked caches append in place — see append_kv_stacked). New k/v pad
    to the cache's per-position width first (128-lane-tiled, or the exact
    D of a packed cache: ops/kv_layout.py), and every append is told the
    cache's pack factor.

    ``slots`` (the compact prefill batch): row r's run goes to cache row
    slots[r] by the exact in-place append of append_kv_contiguous, so a
    prefill step neither scatters nor slices a layer of the stack out.

    A decode step's short run on a plain cache does not come here where the
    Pallas path is taken: the attention kernel appends it itself
    (IncMultiHeadSelfAttention.forward, ``takes_run``). Of what does, the
    row-granular stacked path is chosen whenever its scalar-unit cost
    (~R*KH*Q index rows) beats the per-layer slice-out/write-back HBM
    round trip of the windowed path: for one position a row (a tree of one
    node), a block-diffusion pass off the kernel path, and for wider steps
    (prefill chunks, tree verify) once the per-layer cache slice is large —
    at 7B geometry the slice traffic is ~134MB per layer per step and
    dominated the whole speculation round."""
    ov = getattr(ctx, "kv_override", None)
    idx = cache_plane(ctx, attrs)
    contiguous = getattr(ctx, "kv_contiguous", False)
    # a windowed layer's ring takes the appends that are exact everywhere
    # (by slot, or the scatter)
    ring = attrs.get("sliding_window") is not None
    # a chunked layer's stream likewise (the caller has made ``start_pos``
    # the stored row of the run's first entry, in either extent)
    chunked = attrs.get("eva_window") is not None
    contiguous = contiguous and not ring and not chunked
    if ring and (k.shape[1] * (k.shape[0] if slots is not None else 1)
                 > attrs["max_step_tokens"]):
        raise ValueError(
            f"a step of {k.shape[:2]} tokens may append more to one slot "
            f"than the {attrs['max_step_tokens']} (max_tokens_per_batch) a "
            "windowed layer's ring was sized for")
    # a pipeline stage's microbatch holds a slice of the cache's rows: its
    # loops keep the slot grid (RequestManager._compact_prefill)
    assert ov is None or slots is None, "no row map inside a pipeline stage"
    if ov is not None or idx is None:
        k0, v0 = read_kv(ctx, attrs)
        pack = (1 if ring or chunked
                else kvl.pack_of(k0, attrs["max_seq_length"]))
        Dp = k0.shape[-1] // pack
        k, v = _pad_d(k, Dp), _pad_d(v, Dp)
        if slots is not None:
            kc = _append_by_slot(k0, None, k, start_pos, active, slots,
                                 num_tokens, pack=pack, ring=ring)
            vc = _append_by_slot(v0, None, v, start_pos, active, slots,
                                 num_tokens, pack=pack, ring=ring)
        elif contiguous and k.shape[1] != 1:
            kc = append_kv_contiguous(k0, None, k, start_pos, active,
                                      pack=pack)
            vc = append_kv_contiguous(v0, None, v, start_pos, active,
                                      pack=pack)
        else:
            _note_scatter(k, pack)
            kc = append_kv(k0, k, start_pos, num_tokens, active, pack, ring)
            vc = append_kv(v0, v, start_pos, num_tokens, active, pack, ring)
        write_kv(ctx, attrs, kc, vc)
        return kc, vc, None
    key, st = _stack(ctx, attrs)
    pack = (1 if ring or chunked
            else kvl.pack_of(st["k"], attrs["max_seq_length"]))
    Dp = st["k"].shape[-1] // pack
    k, v = _pad_d(k, Dp), _pad_d(v, Dp)
    if slots is not None:
        ks = _append_by_slot(st["k"], jnp.int32(idx), k, start_pos, active,
                             slots, num_tokens, pack=pack, ring=ring)
        vs = _append_by_slot(st["v"], jnp.int32(idx), v, start_pos, active,
                             slots, num_tokens, pack=pack, ring=ring)
    elif contiguous and k.shape[1] != 1:
        # wide contiguous appends (engine verify/catch-up): scatter-free
        # DUS; decode (Q == 1) stays on the per-(r,kh) row scatter — at 7B
        # the stacked 5D DUS read-modify loop defeats XLA's in-place
        # aliasing and copies the stack, while the 64-256-row scatter is
        # cheap
        ks = append_kv_contiguous(st["k"], idx, k, start_pos, active,
                                  pack=pack)
        vs = append_kv_contiguous(st["v"], idx, v, start_pos, active,
                                  pack=pack)
    elif (k.shape[1] == 1 or pack > 1 or ring or chunked
          or "block_length" in attrs or not isinstance(idx, int)):
        # (a packed stack and a ring take every width in place:
        # append_kv_stacked; so does a block-diffusion layer's pass where
        # the attention kernel does not append it itself, off the Pallas
        # path: a block's few rows a (request, head), the same cache bits;
        # so does a loop region's pass, whose plane is traced: the branch
        # below would slice the plane out and copy it back)
        _note_scatter(k, pack)
        ks = append_kv_stacked(st["k"], idx, k, start_pos, num_tokens,
                               active, pack, ring=ring)
        vs = append_kv_stacked(st["v"], idx, v, start_pos, num_tokens,
                               active, pack, ring=ring)
    else:
        # host-stepped wide appends (prefill chunks, host tree verify):
        # drop-exact windowed scatter on the per-layer slice — paid once
        # per prefill, not per speculation round
        _note_scatter(k, pack)
        kc = append_kv(st["k"][idx], k, start_pos, num_tokens, active)
        vc = append_kv(st["v"][idx], v, start_pos, num_tokens, active)
        ks = st["k"].at[idx].set(kc)
        vs = st["v"].at[idx].set(vc)
    ctx.state_out[key] = {"k": ks, "v": vs}
    return ks, vs, idx


# ----------------------------------------------------------------------
# A chunked layer's summariser (EVA; kernels/attention.pool_chunk has the
# two pools). A chunk's pair is written to its summary row as soon as the
# chunk's last position is in the cache, by the step that appends it; the
# visibility rule alone (kv_layout.chunked_view) hides a window's summaries
# until the window is left, so crossing into the next window moves nothing.
# ----------------------------------------------------------------------
def _chunked_geometry(attrs):
    """(summary rows, window, chunk) of a chunked layer."""
    c = attrs["chunk_size"]
    return (kvl.chunked_summary_rows(attrs["max_seq_length"], c),
            attrs["eva_window"], c)


def summarise_run(attrs, params, k, v, num_tokens):
    """The pairs of the whole chunks of a step's fresh runs: ``k``, ``v``
    ``[R, Q, KH, D]`` (keys rotated), each run from a position that starts a
    chunk (FFModel._check_chunked holds the prefill chunk to whole chunks) ->
    ``(kbar, vbar [R, Q // c, KH, D], n [R])``, ``n`` of them whole by
    ``num_tokens``. No cache is read: the step then appends the pairs to
    the summary extent as it appends the run to the window extent."""
    from flexflow_tpu.kernels.attention import pool_chunk

    c = attrs["chunk_size"]
    R, Q, KH, D = k.shape
    n = Q // c

    def chunks(x):                                  # [R, n, KH, c, D]
        return x[:, :n * c].reshape(R, n, c, KH, D).transpose(0, 1, 3, 2, 4)

    with jax.named_scope("eva_summarise"):
        kbar, vbar = pool_chunk(chunks(k), chunks(v),
                                params["adaptive_mu_k"],
                                params["adaptive_phi"])
    return kbar.astype(k.dtype), vbar.astype(v.dtype), num_tokens // c


def summarise_decode(attrs, params, ks, vs, layer_idx, pos, wrote, cfg):
    """After a decode step appended position ``pos[r]`` of every row with
    ``wrote[r]``: where that was the last of its chunk, pool the chunk's
    rows of the window extent into one pair and write it to the chunk's
    summary row of the stacks ``ks``, ``vs`` [L, R, KH, rows, D], layer
    ``layer_idx``. On the Pallas path one kernel in place
    (kernels/attention.summarise_chunks, device operation
    ``eva_summarise``); elsewhere the same through slices and the row
    scatter."""
    from flexflow_tpu import kernels as ffk
    from flexflow_tpu.kernels.attention import (SUBLANE, pool_chunk,
                                                summarise_chunks,
                                                supports_chunked)

    ns, window, c = _chunked_geometry(attrs)
    done = wrote & ((pos + 1) % c == 0)
    src, dst = kvl.chunked_chunk_rows(pos, ns, window, c)
    mu, phi = params["adaptive_mu_k"], params["adaptive_phi"]
    KH, rows, D = ks.shape[-3:]
    interpret = ffk.pallas_interpret_forced()
    with jax.named_scope("eva_summarise"):
        if (ffk.use_pallas(cfg) and supports_chunked(rows, ns, D)
                and (interpret or c % (2 * SUBLANE) == 0)):
            return summarise_chunks(ks, vs, mu, phi,
                                    jnp.where(done, src, -1), dst, chunk=c,
                                    layer_idx=layer_idx, interpret=interpret)

        def chunk_of(stack):                        # [R, KH, c, D]
            return jnp.stack([jax.lax.dynamic_slice(
                stack, (layer_idx, r, 0, src[r], 0), (1, 1, KH, c, D))[0, 0]
                for r in range(pos.shape[0])])

        kbar, vbar = pool_chunk(chunk_of(ks), chunk_of(vs), mu, phi)
        num = done.astype(jnp.int32)
        return (append_kv_stacked(ks, layer_idx, kbar[:, None], dst, num,
                                  done),
                append_kv_stacked(vs, layer_idx, vbar[:, None], dst, num,
                                  done))


@register_op_as(OpType.INC_MULTIHEAD_SELF_ATTENTION,
                OpType.SPEC_INC_MULTIHEAD_SELF_ATTENTION)
class IncMultiHeadSelfAttention(OpImpl):
    """Incremental-decoding attention with per-slot KV cache.

    The speculative (draft-model) variant is the same computation at
    MAX_BEAM_WIDTH=1 (the reference default, batch_config.h:125); the draft
    model simply owns its own cache state.
    """

    op_type = OpType.INC_MULTIHEAD_SELF_ATTENTION
    quant_aware = True

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        (shape, d) = input_specs[0]
        return [(tuple(shape[:-1]) + (attrs["embed_dim"],),
                 attrs.get("data_type") or d)]

    weight_specs = staticmethod(_weight_specs)
    init_state = staticmethod(_init_kv_state)

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        x = inputs[0]
        meta = ctx.batch_config
        assert meta is not None, "serving ops need ctx.batch_config"
        if hasattr(meta, "ancestor"):
            # beam-width>1 drafting stages the frontier as tree nodes on
            # the DRAFT model too (reference spec_inc_multihead_self_
            # attention.cu keeps per-beam KV; tree attention over the
            # staged region subsumes it with no cache duplication)
            return TreeIncMultiHeadSelfAttention.forward(attrs, params,
                                                         inputs, ctx)
        q, k, v = _qkv(attrs, params, x, ctx.compute_dtype)
        if attrs.get("apply_rotary_embedding", False):
            cos, sin = rotary_cos_sin(meta.positions, attrs["head_dim"],
                                      attrs.get("rope_theta", 10000.0), q.dtype)
            q = apply_rotary(q, cos, sin)
            k = apply_rotary(k, cos, sin)
        # Causal over absolute cache positions: query token i (at position
        # start+i) sees cache[s] for s <= start+i (enforced in the kernel).
        Q = x.shape[1]
        q_abs = block_visibility(
            attrs, meta.start_pos[:, None] + jnp.arange(Q)[None, :])  # [R,Q]
        lengths = jnp.where(meta.active, meta.start_pos + meta.num_tokens, 0)
        append_q = getattr(ctx, "kv_append_q", None)
        eff_q = append_q if (append_q is not None and Q > append_q) else Q
        slots = meta.slots
        idx = cache_plane(ctx, attrs)
        fused = False
        if slots is None and getattr(ctx, "kv_override", None) is None:
            if idx is None:
                st = ctx.state_in[ctx.layer_name]
                k0, v0 = st["k_cache"], st["v_cache"]
            else:          # full stacked [L, R, KH, S, D] buffers
                key, st = _stack(ctx, attrs)
                k0, v0 = st["k"], st["v"]
            # a fused engine's wide step (``kv_contiguous``) promises
            # start_pos + Q <= S and writes its padding: another contract,
            # and its own append (append_kv_contiguous)
            fused = eff_q == 1 or (
                not getattr(ctx, "kv_contiguous", False)
                and takes_run(attrs, ctx, k0, Q, eff_q))
        if fused:
            # a short run of new real tokens per row (decode: one; verify-
            # consistent wide decode: 1 real + padding tokens; a block-
            # diffusion pass: a block or two, of which ``num_tokens`` are
            # real): fuse the KV append into the attention kernel instead
            # of an XLA row scatter. Off the kernel path only a run of one
            # comes here: ``_attend`` then scatters it, as append_and_ref
            # does a wider one.
            S = attrs["max_seq_length"]
            appos = jnp.where(
                meta.active & (meta.num_tokens > 0) & (meta.start_pos < S),
                meta.start_pos, -1)
            run = (k[:, :eff_q], v[:, :eff_q], appos)
            if eff_q > 1:
                run += (jnp.minimum(meta.num_tokens, eff_q),)
            out, knew, vnew = _attend(
                attrs, q, k0, v0, lengths, q_abs, x.dtype, ctx, causal=True,
                layer_idx=idx, append_kv=run)
            if attrs.get("eva_window") is not None:
                knew, vnew = summarise_decode(
                    attrs, params, knew, vnew, idx, meta.start_pos,
                    appos >= 0, ctx.config)
            if idx is None:
                write_kv(ctx, attrs, knew, vnew)
            else:
                ctx.state_out[key] = {"k": knew, "v": vnew}
            return [_project_out(attrs, params, ctx, out, x)]
        start = meta.start_pos
        if attrs.get("eva_window") is not None:
            # the whole chunks' pairs to the summary extent first (no query
            # of this step sees them: they are its own window's), then the
            # run to the window extent
            ns, window, c = _chunked_geometry(attrs)
            kbar, vbar, n = summarise_run(attrs, params, k, v,
                                          meta.num_tokens)
            if kbar.shape[1]:
                append_and_ref(ctx, attrs, kbar, vbar, start // c, n,
                               meta.active, slots)
            start = kvl.chunked_window_row(start, ns, window)
        k_ref, v_ref, layer_idx = append_and_ref(
            ctx, attrs, k, v, start, meta.num_tokens, meta.active, slots)
        out = _attend(attrs, q, k_ref, v_ref, lengths, q_abs, x.dtype,
                      ctx, causal=True, layer_idx=layer_idx, rows=slots)
        return [_project_out(attrs, params, ctx, out, x)]


@register_op
class TreeIncMultiHeadSelfAttention(OpImpl):
    """Verification attention over a speculated token tree.

    Reference tree_inc_multihead_self_attention.cu: tree-branch KV is staged
    into the cache past the committed prefix (update_tree_branch_kv_cache
    :110) and each tree node attends to the committed prefix plus its
    ancestor chain. Accepted tokens are later compacted in place by
    ``commit_tree_kv`` (the reference's commit_tokens_kernel :35).
    """

    op_type = OpType.TREE_INC_MULTIHEAD_SELF_ATTENTION
    quant_aware = True

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        (shape, d) = input_specs[0]
        return [(tuple(shape[:-1]) + (attrs["embed_dim"],),
                 attrs.get("data_type") or d)]

    weight_specs = staticmethod(_weight_specs)
    init_state = staticmethod(_init_kv_state)

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        x = inputs[0]
        meta = ctx.batch_config  # TreeBatchMeta (or BatchMeta for prefill)
        if not hasattr(meta, "ancestor"):
            # Prompt prefill reaches the verify model as a plain causal
            # batch (a chain is a degenerate tree) — same as incremental.
            return IncMultiHeadSelfAttention.forward(attrs, params, inputs, ctx)
        q, k, v = _qkv(attrs, params, x, ctx.compute_dtype)
        if attrs.get("apply_rotary_embedding", False):
            cos, sin = rotary_cos_sin(meta.positions, attrs["head_dim"],
                                      attrs.get("rope_theta", 10000.0), q.dtype)
            q = apply_rotary(q, cos, sin)
            k = apply_rotary(k, cos, sin)
        # Stage tree KV at cache[start + node_idx] (node order is the
        # flattened tree, so this is the same scatter as incremental append).
        k_ref, v_ref, layer_idx = append_and_ref(
            ctx, attrs, k, v, meta.start_pos, meta.num_nodes, meta.active)
        # Tree mask as additive bias: committed prefix (s < start) is open by
        # default; within the tree region only ancestor-or-self is open.
        S = attrs["max_seq_length"]
        T = x.shape[1]
        key_pos = jnp.arange(S)[None, None, :]
        committed = key_pos < meta.start_pos[:, None, None]        # [R,1,S]
        committed = jnp.broadcast_to(committed, (x.shape[0], T, S))
        # ancestor[r, i, j] applies to cache position start_pos[r] + j.
        node_of_key = jnp.arange(S)[None, :] - meta.start_pos[:, None]  # [R,S]
        in_tree = (node_of_key >= 0) & (node_of_key < T)
        node_idx = jnp.clip(node_of_key, 0, T - 1)
        anc = jnp.take_along_axis(
            meta.ancestor, node_idx[:, None, :].repeat(T, axis=1), axis=2)
        key_mask = committed | (in_tree[:, None, :] & anc)
        from flexflow_tpu.kernels.attention import NEG_INF
        bias = jnp.where(key_mask, 0.0, NEG_INF).astype(jnp.float32)
        lengths = jnp.where(meta.active, meta.start_pos + meta.num_nodes, 0)
        out = _attend(attrs, q, k_ref, v_ref, lengths, meta.positions,
                      x.dtype, ctx, bias=bias, causal=False,
                      layer_idx=layer_idx)
        return [_project_out(attrs, params, ctx, out, x)]


def move_kv(cache, src, dst_start, num, active, pack: int):
    """``cache[.., r, :, dst_start[r] + i] = cache[.., r, :, src[r, i]]``
    for ``i < num[r]`` on a packed cache, one layer ``[R, KH, ..]`` or the
    stack ``[L, R, KH, ..]`` (the speculation commits): gather the sources
    first, then the exact contiguous append of the gathered run."""
    moved = jnp.swapaxes(kvl.gather_positions(cache, src, pack), -3, -2)

    def write(c, m):                                # m [R, C, KH, D]
        return append_kv_contiguous(c, None, m, dst_start, active,
                                    num_tokens=num, pack=pack)

    return write(cache, moved) if cache.ndim == 4 else jax.vmap(write)(
        cache, moved)


def commit_tree_kv(op_state: Dict[str, Any], src_node: jnp.ndarray,
                   num_commit: jnp.ndarray, start_pos: jnp.ndarray,
                   active: jnp.ndarray,
                   max_seq: Optional[int] = None) -> Dict[str, Any]:
    """Compact accepted tree nodes into the committed cache region.

    For every KV-cache layer: cache[r, start+i] = cache[r, start+src_node[r,i]]
    for i < num_commit[r]. src_node is the accepted path's node indices in
    tree order (ascending, so in-place gather/scatter never overwrites a
    yet-unread source: src_node[i] >= i always, and we gather first anyway).

    Reference: commit_tokens_kernel (tree_inc_multihead_self_attention.cu:35)
    driven by TreeVerifyBatchConfig::committed_tokens. ``max_seq`` (static)
    is the caches' length in positions, from which a packed cache's layout
    is read (ops/kv_layout.py); None: position-major caches.
    """

    refuse_windowed(op_state, "tree verification (commit_tree_kv)")

    def commit_one(cache):                          # [R, KH, S, D]
        R = cache.shape[0]
        S = cache.shape[2] if max_seq is None else max_seq
        pack = kvl.pack_of(cache, S)
        C = src_node.shape[1]
        rows = jnp.arange(R)[:, None]
        valid = (jnp.arange(C)[None, :] < num_commit[:, None]) & active[:, None]
        src = start_pos[:, None] + src_node
        src = jnp.clip(src, 0, S - 1)
        if pack > 1:
            return move_kv(cache, src, start_pos, num_commit, active, pack)
        moved = cache[rows, :, src]                                # [R,C,KH,D]
        dst = jnp.where(valid, start_pos[:, None] + jnp.arange(C)[None, :], S)
        return cache.at[rows, :, dst].set(moved, mode="drop")

    new_state = {}
    for layer_name, st in op_state.items():
        if layer_name == "kv_cache":  # stacked [L, R, KH, S, D] layout
            new_state[layer_name] = {
                "k": jax.vmap(commit_one)(st["k"]),
                "v": jax.vmap(commit_one)(st["v"]),
            }
        elif isinstance(st, dict) and "k_cache" in st:
            new_state[layer_name] = {
                "k_cache": commit_one(st["k_cache"]),
                "v_cache": commit_one(st["v_cache"]),
            }
        else:
            new_state[layer_name] = st
    return new_state
