"""Serving attention by a gated delta rule with one decay a key channel
("Kimi Delta Attention", arXiv:2510.26692; models/solar_open2.py): a layer
that keeps, a row, a RECURRENT STATE and no cache of positions.

``H`` heads of ``K = V = head_dim``; ``x_t`` the layer's normed input:

    for s in (q, k, v):  u^s_t = W_s x_t
                         s_t   = silu(sum_{j<4} c^s[j] * u^s_{t-3+j})   depthwise causal conv
    q_t[h] <- q_t[h] / |q_t[h]| / sqrt(K);   k_t[h] <- k_t[h] / |k_t[h]|
    g_t[h] = -exp(A_log[h]) * softplus(W_fb (W_fa x_t) + dt_bias)[h]     [K], <= 0
    beta_t[h] = 2 sigmoid(w_b[h] . x_t)
    S_t[h] = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1}[h] + beta_t k_t v_t^T
    o_t[h] = S_t[h]^T q_t[h]
    y_t = W_o (RMSNorm_V(o_t[h]) * w_n * sigmoid((W_gb (W_ga x_t))[h]))

What a row carries from step to step, a layer: ``S [H, K, V]`` float32 and
the last three ``u`` (the convolutions' tails). Both are the op's state, by
the contract of ops/recurrent.py (which ops/ssd_mixer.py meets too): the
cache manager stacks them as ``op_state[RECURRENT_STACK] = {"s": [layers,
slots, H, K, V], "u": [layers, slots, 3, 3 H K]}``, both float32, OVERWRITTEN
by every step that gives a slot tokens. One recurrence, two forms:

* RECURRENT (a decode step, one token a row): ``S' = exp(g)[:, None] * S``;
  ``d = beta (v - S'^T k)``; ``S = S' + k d^T``; ``o = S^T q``. On the
  kernel path ``kernels/linear_attention.kda_state_step`` streams a live
  row's state in and back, in place; ``recurrent_step`` is the same in jnp.
* CHUNKED (a prefill step): the recurrence over chunks of ``CHUNK`` tokens
  by matrix products (the WY form of the delta rule: inside a chunk ``d = T
  (beta v - beta (k * exp(G)) S_0)`` with ``T = (I + Diag(beta) A)^-1`` a
  unit lower-triangular solve, ``A_ts = sum_c k_t[c] k_s[c] exp(G_t[c] -
  G_s[c])``, ``G`` the chunk's cumulative log decay), carrying ``S`` from
  chunk to chunk. ``exp(G_t - G_s)`` is never split into ``exp(G_t) *
  exp(-G_s)`` across a chunk, which overflows at a strong decay: between
  sub-chunks of ``SUB`` tokens both factors are taken relative to the LATER
  sub-chunk's start (both <= 1), and inside one the exponent's difference
  is formed first. On the kernel path it is ONE device operation a layer,
  ``kernels/linear_attention.kda_chunk`` (both callers: the compact batch's
  segments and the slot grid's rows): a chunk's parts never leave VMEM and
  the state stays there from chunk to chunk and from a row to the slot's
  next row. ``chunked`` (``chunk_parts`` then ``through_chunks``) is the
  same in jnp, some three hundred XLA operations a layer-step: the fallback
  (no TPU and no interpreter forced, or widths Mosaic does not take) and
  the tests' oracle.

Padding positions of a row (``t >= n``) have ``g = 0`` and ``beta = 0``: the
state passes them as it is, and they do not enter the tails.

Where a row's state and tails come from: the three rules of
ops/recurrent.py. The tails (5 KB) go through ``recurrent.runs_with_tails``
(``inc_attention.carried_rows``: a gather, selects and a scatter); the state
(4 MB) on the kernel path through
``linear_attention.chunk_sources``, the same rule as scalars: the kernel
takes a slot's rows one after another, so "the row before" is what VMEM
holds (the jnp path: ``carried_rows`` again).

What stages, moves, shares, rolls back or shards cache positions cannot
carry the state along: ``inc_attention.refuse_windowed`` refuses them.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from flexflow_tpu.core.initializer import (NormInitializer,
                                           default_kernel_initializer)
from flexflow_tpu.core.layer import WeightSpec
from flexflow_tpu.ffconst import DataType, OpType
from flexflow_tpu.ops import recurrent as REC
from flexflow_tpu.ops.base import OpImpl, register_op
from flexflow_tpu.ops.inc_attention import RECURRENT_STACK, carried_rows

# tokens a chunk of the chunked form, and a sub-chunk inside which decays
# are taken pairwise
CHUNK, SUB = 64, 16
HIGHEST = jax.lax.Precision.HIGHEST


def _dims(attrs):
    """(H, K, V, taps, rank): heads, a head's key and value dims, the
    convolution's kernel, the rank of the two low-rank gates."""
    D = attrs["head_dim"]
    return attrs["num_heads"], D, D, attrs["conv_kernel"], attrs["gate_rank"]


def _weight_specs(attrs, input_specs):
    (shape, d) = input_specs[0]
    E = shape[-1]
    H, K, V, taps, r = _dims(attrs)
    dt = attrs.get("data_type") or d
    init = attrs.get("kernel_initializer") or default_kernel_initializer()
    f32 = DataType.DT_FLOAT
    return [
        # [Wq | Wk | Wv], one array and one gemm
        WeightSpec("wqkv", (E, 3 * H * K), dt, init),
        # [W_fa | W_ga | w_b]: the two low-rank gates' first halves and
        # beta's projection, one gemm of 2 r + H columns
        WeightSpec("wlow", (E, 2 * r + H), dt, init),
        WeightSpec("wfb", (r, H * K), dt, init),
        WeightSpec("wgb", (r, H * V), dt, init),
        # the depthwise taps, tap j weighs position t - (taps - 1) + j;
        # seeded so that a missing tap is seen
        WeightSpec("conv", (taps, 3 * H * K), dt, NormInitializer(stddev=0.5)),
        WeightSpec("A_log", (H,), f32, REC.DecayInitializer("A_log")),
        WeightSpec("dt_bias", (H * K,), f32, REC.DecayInitializer("dt_bias")),
        WeightSpec("o_norm", (V,), dt, NormInitializer(mean=1.0, stddev=0.02)),
        WeightSpec("wo", (H * V, E), dt, init),
    ]


def _init_state(attrs, input_specs):
    H, K, V, taps, _ = _dims(attrs)
    R = attrs["max_requests"]
    # both float32 whatever the cache's dtype: what a recurrent layer
    # carries from step to step is summed into everything after it (the
    # tails are 0.3 MB a row a layer beside the state's 4.19)
    return {REC.STATE: jnp.zeros((R, H, K, V), jnp.float32),
            REC.TAIL: jnp.zeros((R, taps - 1, 3 * H * K), jnp.float32)}


# ----------------------------------------------------------------------
# the recurrence, two forms
# ----------------------------------------------------------------------

def recurrent_step(S, q, k, g, v, beta):
    """One token a row: ``S [R, H, K, V]`` float32; ``q, k, g [R, H, K]``,
    ``v [R, H, V]``, ``beta [R, H]``. Returns ``(o [R, H, V], S)``."""
    S = S * jnp.exp(g)[..., None]
    d = beta[..., None] * (v - jnp.einsum("rhkv,rhk->rhv", S, k,
                                          precision=HIGHEST))
    S = S + k[..., None] * d[..., None, :]
    return jnp.einsum("rhkv,rhk->rhv", S, q, precision=HIGHEST), S


def _solve_unit_lower(Ld, Lo, rhs):
    """``(I + L) X = rhs`` for a unit lower-triangular matrix given by its
    blocks: ``Ld [.., nb, c, c]`` the diagonal blocks' strictly lower
    parts, ``Lo [.., nb, c, nb, c]`` the blocks below the diagonal (zeros
    elsewhere; None where ``nb == 1``), ``rhs [.., nb, c, M]``. Forward
    substitution a row at a time inside a diagonal block (its inverse, ``c
    - 1`` small steps on every block of every chunk at once), then a block
    at a time: a dozen batched products where XLA:TPU's own triangular
    solve is some five hundred small operations a layer-step (PERF.md
    section 6, PR 54), and no series whose terms would cancel."""
    c = Ld.shape[-1]
    nb = Ld.shape[-3]
    eye = jnp.eye(c, dtype=Ld.dtype)
    T = jnp.broadcast_to(eye, Ld.shape)
    for t in range(1, c):   # rows >= t of T are still the identity's
        row = jnp.einsum("...s,...sj->...j", Ld[..., t, :], T,
                         precision=HIGHEST)
        T = T - eye[:, t, None] * row[..., None, :]
    out = []
    for i in range(nb):
        acc = rhs[..., i, :, :]
        if i:
            acc = acc - jnp.einsum(
                "...tjs,...jsm->...tm", Lo[..., i, :, :i, :],
                jnp.stack(out, axis=-3), precision=HIGHEST)
        out.append(jnp.einsum("...ts,...sm->...tm", T[..., i, :, :], acc,
                              precision=HIGHEST))
    return jnp.stack(out, axis=-3)


def _chunk_parts(q, k, g, v, beta, sub: int):
    """What a chunk's tokens give whatever state comes in. ``q, k, g [..,
    C, K]``, ``v [.., C, V]``, ``beta [.., C]`` (leading dims: row, chunk,
    head), float32. Returns ``(U [.., C, V], W [.., C, K], Qd [.., C, K], B
    [.., C, C], Kend [.., C, K], decay [.., K])`` with, for the incoming
    state ``S``: ``d = U - W S``; ``o = Qd S + B d``; ``S' = decay[:, None]
    * S + Kend^T d``."""
    C, K = k.shape[-2:]
    lead = k.shape[:-2]
    nb = C // sub
    G = jnp.cumsum(g, axis=-2)                      # inclusive, <= 0

    def blocks(x):                                  # [.., nb, sub, last]
        return x.reshape(lead + (nb, sub, x.shape[-1]))

    Gb, gb, kb, qb = blocks(G), blocks(g), blocks(k), blocks(q)
    Gs = Gb[..., 0, :] - gb[..., 0, :]              # before a block's first
    # inside a sub-chunk: the exponent's difference first
    # (k's and q's rows in one pass over the pairs' decays)
    rel = kb[..., None, :, :] * jnp.exp(jnp.minimum(
        Gb[..., :, None, :] - Gb[..., None, :, :], 0))     # [.., t, s, K]
    Ad = jnp.sum(kb[..., :, None, :] * rel, axis=-1)
    Bd = jnp.sum(qb[..., :, None, :] * rel, axis=-1)
    tri = jnp.tril(jnp.ones((sub, sub), bool))
    Ad = jnp.where(tri & ~jnp.eye(sub, dtype=bool), Ad, 0)
    Bd = jnp.where(tri, Bd, 0)
    Ao = None
    if nb > 1:
        # between sub-chunks: both factors relative to the LATER one's start
        late = jnp.exp(Gb - Gs[..., None, :])       # [.., I, t, K] <= 1
        early = jnp.exp(jnp.minimum(               # [.., I, J, s, K] <= 1
            Gs[..., :, None, None, :] - Gb[..., None, :, :, :], 0))
        k_early = kb[..., None, :, :, :] * early
        before = jnp.tril(jnp.ones((nb, nb), bool), -1)[:, None, :, None]
        Ao = jnp.where(before, jnp.einsum(
            "...itc,...ijsc->...itjs", kb * late, k_early,
            precision=HIGHEST), 0)
        Bo = jnp.where(before, jnp.einsum(
            "...itc,...ijsc->...itjs", qb * late, k_early,
            precision=HIGHEST), 0)
        B = (Bo + jnp.einsum("...its,ij->...itjs", Bd,
                             jnp.eye(nb, dtype=Bd.dtype))
             ).reshape(lead + (C, C))
    else:
        B = Bd[..., 0, :, :]
    into = jnp.exp(G)                               # from the chunk's start
    bb = blocks(beta[..., None])                    # [.., nb, sub, 1]
    X = _solve_unit_lower(
        bb * Ad, None if Ao is None else bb[..., None] * Ao,
        bb * blocks(jnp.concatenate([v, k * into], axis=-1)))
    X = X.reshape(lead + (C, X.shape[-1]))
    Vw = v.shape[-1]
    decay = into[..., -1, :]
    Kend = k * jnp.exp(G[..., -1:, :] - G)
    return X[..., :Vw], X[..., Vw:], q * into, B, Kend, decay


def chunk_parts(q, k, g, v, beta, chunk: int = CHUNK, sub: int = SUB):
    """The state-independent half of the chunked form, for every row at
    once: ``q, k, g [B, T, H, K]``, ``v [B, T, H, V]``, ``beta [B, T, H]``,
    float32; a padding position has ``g = 0`` and ``beta = 0``. Returns
    ``_chunk_parts``' arrays with leading dims ``[chunks, B, H]``."""
    Bn, T = k.shape[:2]
    C = chunk if T >= chunk else -(-T // sub) * sub
    pad = -T % C
    if pad:                 # as padding positions: the state passes them
        q, k, g, v, beta = (jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (
            x.ndim - 2)) for x in (q, k, g, v, beta))
    nc = (T + pad) // C

    def by_chunk(x):        # [B, T, H, ..] -> [nc, B, H, C, ..]
        x = x.reshape((Bn, nc, C) + x.shape[2:])
        return x.transpose((1, 0, 3, 2) + tuple(range(4, x.ndim)))

    # (the triangular solve's own products take the ambient precision)
    with jax.default_matmul_precision("highest"):
        return _chunk_parts(*(by_chunk(x) for x in (q, k, g, v, beta)), sub)


def through_chunks(S0, parts, T: int):
    """The state-dependent half: ``S0 [B, H, K, V]`` through ``parts``
    (``chunk_parts`` of the same rows), chunk after chunk. Returns ``(o [B,
    T, H, V], S_T)``."""

    def step(S, p):
        U, W, Qd, B, Kend, decay = p
        d = U - jnp.einsum("bhck,bhkv->bhcv", W, S, precision=HIGHEST)
        o = (jnp.einsum("bhck,bhkv->bhcv", Qd, S, precision=HIGHEST)
             + jnp.einsum("bhcs,bhsv->bhcv", B, d, precision=HIGHEST))
        S = decay[..., None] * S + jnp.einsum("bhck,bhcv->bhkv", Kend, d,
                                              precision=HIGHEST)
        return S, o

    with jax.named_scope("kda_chunked"):
        S, o = jax.lax.scan(step, S0, parts)        # o [nc, B, H, C, V]
    nc, Bn, H, C, V = o.shape
    o = jnp.moveaxis(o, 0, 1).swapaxes(2, 3).reshape(Bn, nc * C, H, V)
    return o[:, :T], S


def chunked(S0, q, k, g, v, beta, chunk: int = CHUNK, sub: int = SUB):
    """The recurrence over ``T`` tokens a row by chunks, rows side by side.
    ``S0 [B, H, K, V]``; the rest as ``chunk_parts`` takes them. Returns
    ``(o [B, T, H, V], S_T)``."""
    return through_chunks(S0, chunk_parts(q, k, g, v, beta, chunk, sub),
                          k.shape[1])


# ----------------------------------------------------------------------
# the op
# ----------------------------------------------------------------------

def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _gates(attrs, params, x):
    """The step's ``(g [R, Q, H, K], beta [R, Q, H], gate [R, Q, H, V])``
    from the layer's input, float32."""
    from flexflow_tpu.quant import qmatmul

    H, K, V, _, r = _dims(attrs)
    R, Q = x.shape[:2]
    f32 = jnp.float32
    # float32 out of every gemm (its accumulator): what feeds the decay
    # and the state is rounded once, going into the next gemm
    low = qmatmul(x, params["wlow"], out_dtype=f32)
    # ... but not the decay's: g is exponentiated and summed over a row's
    # whole past, so its second half runs on float32 inputs
    with jax.default_matmul_precision("highest"):
        a = qmatmul(low[..., :r], params["wfb"], f32, f32)
    gate = qmatmul(low[..., r:2 * r], params["wgb"], x.dtype, f32)
    g = -(jnp.exp(params["A_log"].astype(f32))[:, None]
          * jax.nn.softplus(a + params["dt_bias"].astype(f32)).reshape(
              R, Q, H, K))
    beta = 2.0 * jax.nn.sigmoid(low[..., 2 * r:])
    return g, beta, gate.reshape(R, Q, H, V)


def takes_chunk_kernel(attrs, config) -> bool:
    """Whether a prefill step of this layer runs ``kda_chunk`` (else the
    jnp ``chunked``)."""
    from flexflow_tpu.kernels import linear_attention as LA

    interpret = REC.kernel_path(attrs, config)
    H, K, V, _, _ = _dims(attrs)
    return interpret is not None and (interpret
                                      or LA.supports_chunk(H, K, V))


@register_op
class IncKDAttention(OpImpl):
    """Incremental-decoding attention by a gated delta rule: a per-slot
    recurrent state and convolution tails, no cache of positions."""

    op_type = OpType.INC_KDA_ATTENTION
    quant_aware = True

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        (shape, d) = input_specs[0]
        return [(tuple(shape[:-1]) + (attrs["embed_dim"],),
                 attrs.get("data_type") or d)]

    weight_specs = staticmethod(_weight_specs)
    init_state = staticmethod(_init_state)
    # (the contract of ops/recurrent.py)
    takes_chunk_kernel = staticmethod(takes_chunk_kernel)

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        from flexflow_tpu.kernels import linear_attention as LA
        from flexflow_tpu.ops.norm import _rms_norm
        from flexflow_tpu.quant import qmatmul

        x = inputs[0]
        meta = ctx.batch_config
        assert meta is not None, "serving ops need ctx.batch_config"
        REC.refuse_staged(meta, ctx)
        H, K, V, _, _ = _dims(attrs)
        R, Q = x.shape[:2]
        f32 = jnp.float32
        # [R, Q, 3 H K], float32 as the tails that carry it on
        u = qmatmul(x, params["wqkv"], out_dtype=f32)
        g, beta, gate = _gates(attrs, params, x)
        n = jnp.where(meta.active, meta.num_tokens, 0)
        real = (jnp.arange(Q)[None, :] < n[:, None])
        g = jnp.where(real[..., None, None], g, 0)
        beta = jnp.where(real[..., None], beta, 0)
        S_all, U_all = REC.stack_of(ctx)
        lidx = attrs["state_layer_idx"]
        start, slots = meta.start_pos, meta.slots
        fresh = functools.partial(REC.fresh, start=start)
        wrote = functools.partial(REC.wrote, n=n)

        # 1. the convolutions' tails, then q, k, v for every row at once
        ext_u, U_all = REC.runs_with_tails(U_all, lidx, u, start, slots, n)
        mixed = REC.depthwise_conv(params["conv"], ext_u,
                                   Q).reshape(R, Q, 3, H, K)
        q = _unit(mixed[:, :, 0]) * (1.0 / math.sqrt(K))
        k = _unit(mixed[:, :, 1])
        v = mixed[:, :, 2]

        # 2. the state
        interpret = REC.kernel_path(attrs, ctx.config)
        chunk_kernel = takes_chunk_kernel(attrs, ctx.config)
        if Q > 1 or slots is not None:
            LA.record_chunk_form("kernel" if chunk_kernel else "jnp", R, Q)
        if slots is None and Q == 1:
            live = n > 0
            args = (q[:, 0], k[:, 0], g[:, 0], v[:, 0], beta[:, 0])
            if interpret is not None and (interpret or LA.supports(H, K, V)):
                o, S_all = LA.kda_state_step(S_all, lidx, *args, live,
                                             start == 0, interpret=interpret)
            else:
                old = S_all[lidx]
                o, new = recurrent_step(fresh(old), *args)
                S_all = S_all.at[lidx].set(wrote(new, old))
            o = o[:, None]
        elif chunk_kernel:
            # the rows in the order of their slots and starts, the state in
            # VMEM from chunk to chunk and from a row to the slot's next
            o, S_all = LA.kda_chunk(
                S_all, lidx, q, k, g, v, beta,
                jnp.arange(R, dtype=jnp.int32) if slots is None else slots,
                start, n, interpret=interpret)
        elif slots is None:
            old = S_all[lidx]
            o, new = chunked(fresh(old), q, k, g, v, beta)
            S_all = S_all.at[lidx].set(wrote(new, old))
        else:
            # a handful of rows (the step's segments): what a chunk's
            # tokens give whatever state comes in for all the rows at once,
            # then the state's walk a row at a time, in order: a row that
            # continues an earlier row of its slot starts from that row's
            # end, inside this forward
            parts = chunk_parts(q, k, g, v, beta)

            def through(i, S):
                o, S = through_chunks(
                    S[None], jax.tree.map(lambda p: p[:, i:i + 1], parts), Q)
                return o[0], S[0]

            outs, S_all = carried_rows(S_all, slots, start, n, through,
                                       layer=lidx)
            o = jnp.stack(outs)
        ctx.state_out[RECURRENT_STACK] = {"s": S_all, "u": U_all}

        # 3. a head's norm, the gate, out
        o = _rms_norm(o, params["o_norm"].astype(f32), attrs["norm_eps"])
        o = (o * jax.nn.sigmoid(gate)).astype(x.dtype)
        return [qmatmul(o.reshape(R, Q, H * V), params["wo"])]
