"""Ring attention: sequence/context parallelism over the ICI ring.

A NEW capability dimension vs the reference, which has no sequence
parallelism of any kind (SURVEY §2.3: "NOT present: sequence parallelism /
context parallelism / ring attention / Ulysses"; §5 names it the greenfield
item). Design follows the public ring-attention recipe (Liu et al. 2023,
blockwise attention with online softmax + rotating KV shards) expressed the
TPU way: ``jax.shard_map`` over the mesh's "seq" axis, ``lax.ppermute`` ring
shifts riding neighboring ICI links, and a ``lax.scan`` whose carry holds the
flash-attention running (max, denominator, accumulator) so the full [S, S]
score matrix never materializes.

Differentiable end-to-end: the scan + ppermute compose with jax AD (the
transpose of a ring shift is the reverse shift), so the same code path serves
training (the usual use) and long-context prefill.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from flexflow_tpu.parallel.collectives import axis_size


def _repeat_kv_heads(k, num_q_heads):
    """GQA: expand [b, s, kv_heads, d] to num_q_heads by repetition."""
    kvh = k.shape[2]
    if kvh == num_q_heads:
        return k
    assert num_q_heads % kvh == 0, (num_q_heads, kvh)
    return jnp.repeat(k, num_q_heads // kvh, axis=2)


def ring_attention_local(q, k, v, axis_name: str, causal: bool = True,
                         scale: Optional[float] = None):
    """Per-shard ring attention body — call inside shard_map.

    q, k, v: local sequence shards [batch, s_local, heads, head_dim]
    (kv may carry fewer heads — GQA — they are repeated to match q).
    Returns [batch, s_local, heads, head_dim].
    """
    n = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    k = _repeat_kv_heads(k, h)
    v = _repeat_kv_heads(v, h)
    sk = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    qf = q.astype(jnp.float32) * scale
    qpos = idx * sq + jnp.arange(sq)

    # running flash-attention state, [b, h, sq(, d)] layout
    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)

    def step(carry, s):
        o, m, l, k_blk, v_blk = carry
        j = (idx - s) % n                    # global chunk held this step
        kpos = j * sk + jnp.arange(sk)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf,
                            k_blk.astype(jnp.float32),
                            preferred_element_type=jnp.float32)
        if causal:
            mask = qpos[:, None] >= kpos[None, :]
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
        blk_max = scores.max(axis=-1)
        m_new = jnp.maximum(m, blk_max)
        # exp(-inf - -inf) would be nan; fully-masked entries contribute 0
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(jnp.where(jnp.isfinite(scores),
                              scores - safe_m[..., None], -jnp.inf))
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
        o_new = o * corr[..., None] + pv
        # rotate KV around the ring: i -> i+1 (so we receive i-1's chunk)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return (o_new, m_new, l_new, k_nxt, v_nxt), None

    (o, m, l, _, _), _ = lax.scan(step, (o0, m0, l0, k, v), jnp.arange(n))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)   # [b, sq, h, d]


def ring_attention(q, k, v, mesh: Mesh, seq_axis: str = "seq",
                   causal: bool = True, batch_axis: Optional[str] = "data",
                   scale: Optional[float] = None):
    """Sharded entry: q, k, v are [batch, seq, heads, head_dim] global arrays
    (or already-sharded under jit); seq dim is split over `seq_axis`."""
    if seq_axis not in mesh.axis_names or mesh.shape[seq_axis] == 1:
        # no seq axis — plain dense attention
        kk = _repeat_kv_heads(k, q.shape[2])
        vv = _repeat_kv_heads(v, q.shape[2])
        s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
        scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * s,
                            kk.astype(jnp.float32))
        if causal:
            sq_, sk_ = q.shape[1], k.shape[1]
            mask = jnp.tril(jnp.ones((sq_, sk_), bool), k=sk_ - sq_)
            scores = jnp.where(mask, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, vv.astype(jnp.float32))
        return out.astype(q.dtype)

    ba = batch_axis if (batch_axis in mesh.axis_names
                        and mesh.shape[batch_axis] > 1
                        and q.shape[0] % mesh.shape[batch_axis] == 0) else None
    spec = P(ba, seq_axis, None, None)
    fn = partial(ring_attention_local, axis_name=seq_axis, causal=causal,
                 scale=scale)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


_NEG_INF = -1e30   # finite "minus infinity", matches kernels/attention.py


def seq_sharded_attend(q, k_cache, v_cache, lengths, qpos, mesh: Mesh,
                       seq_axis: str = "seq", bias=None, alibi=None, *,
                       causal=True, qk_scale=None, out_dtype=None):
    """Sequence-sharded serving attention over the KV cache.

    The execution target for a searched plan whose attention strategy
    shards the sequence dim: same contract as the dense oracle
    (``kernels.attention.reference_attend`` — q ``[R, Q, H, D]``, caches
    ``[R, KH, S, D]``, ``lengths [R]`` valid extents, ``qpos [R, Q]``
    absolute positions, optional additive ``bias [R, Q, S]`` and ALiBi
    slopes), but the cache's S dim lives sharded over ``seq_axis`` and each
    shard scores only its local slice against the replicated queries.

    The softmax is reconciled exactly: global row max via ``lax.pmax``,
    then one ``lax.psum`` for the denominator and one for the weighted-V
    numerator — so the output is token-identical to the unsharded
    reference. Decode (Q == 1) and chunked prefill (Q > 1) take the same
    path: queries are tiny relative to a 32k cache, so replicating them
    and partitioning the cache needs no ring rotation at all — three small
    collectives per step replace (deg-1) KV-shard rotations, and each
    device streams S/deg cache rows instead of S.
    """
    R, Q, H, D = q.shape
    KH = k_cache.shape[1]
    G = H // KH
    if qk_scale is None:
        qk_scale = 1.0 / math.sqrt(D)
    out_dtype = out_dtype or q.dtype
    deg = mesh.shape[seq_axis] if seq_axis in mesh.axis_names else 1
    if deg <= 1 or k_cache.shape[2] % deg != 0:
        from flexflow_tpu.kernels.attention import reference_attend

        return reference_attend(q, k_cache, v_cache, lengths, qpos,
                                bias=bias, alibi=alibi, causal=causal,
                                qk_scale=qk_scale, out_dtype=out_dtype)

    has_bias = bias is not None
    has_alibi = alibi is not None

    def local_fn(q, kc, vc, lengths, qpos, *rest):
        rest = list(rest)
        b = rest.pop(0) if has_bias else None
        al = rest.pop(0) if has_alibi else None
        idx = lax.axis_index(seq_axis)
        SL = kc.shape[2]
        qg = q.reshape(R, Q, KH, G, D)
        kcl = kc.astype(q.dtype)
        vcl = vc.astype(q.dtype)
        s = jnp.einsum("rqkgd,rksd->rkgqs", qg, kcl,
                       preferred_element_type=jnp.float32) * qk_scale
        s_ids = (idx * SL + jnp.arange(SL))[None, None, :]   # global key ids
        if al is not None:
            dist = (qpos[:, :, None] - s_ids).astype(jnp.float32)
            slopes = al.astype(jnp.float32).reshape(KH, G)
            s = s - slopes[None, :, :, None, None] * dist[:, None, None, :, :]
        if b is not None:
            s = s + b.astype(jnp.float32)[:, None, None, :, :]
        visible = jnp.ones((R, Q, SL), bool) if not causal else \
            (s_ids <= qpos[:, :, None])
        visible = visible & (s_ids < lengths[:, None, None])
        s = jnp.where(visible[:, None, None, :, :], s, _NEG_INF)
        m = lax.pmax(s.max(axis=-1), seq_axis)           # global row max
        p = jnp.exp(s - m[..., None])
        den = lax.psum(p.sum(axis=-1), seq_axis)
        p = p / jnp.maximum(den, 1e-30)[..., None]
        out = jnp.einsum("rkgqs,rksd->rqkgd", p.astype(q.dtype), vcl)
        out = lax.psum(out, seq_axis)
        return out.reshape(R, Q, H * D).astype(out_dtype)

    cache_spec = P(None, None, seq_axis, None)
    args = [q, k_cache, v_cache, lengths, qpos]
    in_specs = [P(), cache_spec, cache_spec, P(), P()]
    if has_bias:
        args.append(bias)
        in_specs.append(P(None, None, seq_axis))
    if has_alibi:
        args.append(alibi)
        in_specs.append(P())
    return jax.shard_map(local_fn, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=P(), check_vma=False)(*args)
