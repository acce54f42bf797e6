"""A serving state-space mixer (Mamba-2, "state-space duality",
arXiv:2405.21060; models/granite_hybrid.py): a layer that keeps, a row, a
RECURRENT STATE and no cache of positions.

``H`` heads of ``P`` channels (``H P = d_inner``), ONE group of ``N`` state
dims whose input and output rows ``B_t``, ``C_t`` every head shares; ``x_t``
the layer's normed input:

    [z | xBC | dt] = W_in x_t                    (d_inner | d_inner + 2 N | H), one gemm
    xBC_t <- silu(b + sum_{j<4} c[j] * xBC_{t-3+j})        depthwise causal conv
    [x | B | C] = xBC_t                          x [H, P], B [N], C [N]
    dt_t[h] = softplus(dt_t[h] + dt_bias[h]);    a_t[h] = exp(-exp(A_log[h]) * dt_t[h])
    S_t[h]  = a_t[h] * S_{t-1}[h] + (dt_t[h] * x_t[h]) B_t^T          S [H, P, N]
    y_t[h]  = S_t[h] C_t + D[h] * x_t[h]
    out     = W_out (RMSNorm_{d_inner}(y_t * silu(z_t)) * w_n)         the gate FIRST

No delta term and one SCALAR decay a head: not the recurrence of
ops/kda_attention.py, but the same contract (ops/recurrent.py). What a row
carries from step to step, a layer: ``S [H, P, N]`` float32 and the last
three pre-convolution ``xBC`` (the tail), stacked by the cache manager as
``op_state[RECURRENT_STACK] = {"s": [layers, slots, H, P, N], "u": [layers,
slots, 3, d_inner + 2 N]}``, both float32, OVERWRITTEN by every step that
gives a slot tokens. One recurrence, two forms:

* RECURRENT (a decode step, one token a row): the two lines above. On the
  kernel path ``kernels/linear_attention.ssd_state_step`` streams a live
  row's state in and back, in place; ``recurrent_step`` is the same in jnp.
* CHUNKED (a prefill step): the recurrence over chunks of ``CHUNK`` tokens
  by matrix products. With ``G_t = sum_{r<=t} log a_r`` inside a chunk,

      y_t[h]   = sum_{s<=t} exp(G_t - G_s)[h] (C_t . B_s) dx_s[h]
                 + exp(G_t)[h] S_0[h] C_t
      S_end[h] = exp(G_Q)[h] S_0[h] + sum_s exp(G_Q - G_s)[h] dx_s[h] B_s^T

  every exponent a DIFFERENCE formed first (<= 0), so nothing overflows at
  a strong decay. ``C_t . B_s`` is one ``[Q, Q]`` product for all the heads.
  Plain XLA under the named scope ``ssd_chunked``, some ten operations a
  layer; ``chunk_parts`` is what a chunk's tokens give whatever state comes
  in, ``through_chunks`` the state's walk.

Padding positions of a row (``t >= n``) have ``dt = 0``: ``a = 1`` and no
write, so the state passes them as it is; they do not enter the tail. Where
a row's state and tail come from: the three rules of ops/recurrent.py, both
through ``inc_attention.carried_rows`` on the compact batch.

What stages, moves, shares, rolls back or shards cache positions cannot
carry the state along: ``inc_attention.refuse_windowed`` refuses them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from flexflow_tpu.core.initializer import (NormInitializer,
                                           default_kernel_initializer)
from flexflow_tpu.core.layer import WeightSpec
from flexflow_tpu.ffconst import DataType, OpType
from flexflow_tpu.ops import recurrent as REC
from flexflow_tpu.ops.base import OpImpl, register_op
from flexflow_tpu.ops.inc_attention import RECURRENT_STACK, carried_rows

# tokens a chunk of the chunked form: a prefill segment of the serving
# loops (128) is one chunk, so a step's rows need no scan
CHUNK = 128
HIGHEST = jax.lax.Precision.HIGHEST


def _dims(attrs):
    """(H, P, N, taps): heads, a head's channels, the state's dims, the
    convolution's kernel."""
    return (attrs["num_heads"], attrs["head_dim"], attrs["state_dim"],
            attrs["conv_kernel"])


def _weight_specs(attrs, input_specs):
    (shape, d) = input_specs[0]
    E = shape[-1]
    H, P, N, taps = _dims(attrs)
    dt = attrs.get("data_type") or d
    init = attrs.get("kernel_initializer") or default_kernel_initializer()
    f32 = DataType.DT_FLOAT
    conv = H * P + 2 * N
    return [
        # [z | xBC | dt], one array and one gemm
        WeightSpec("win", (E, 2 * H * P + 2 * N + H), dt, init),
        # the depthwise taps, tap j weighs position t - (taps - 1) + j, and
        # the bias; seeded so that a missing one is seen
        WeightSpec("conv", (taps, conv), dt, NormInitializer(stddev=0.5)),
        WeightSpec("conv_bias", (conv,), dt, NormInitializer(stddev=0.1)),
        WeightSpec("A_log", (H,), f32, REC.DecayInitializer("A_log")),
        WeightSpec("dt_bias", (H,), f32, REC.DecayInitializer("dt_bias")),
        WeightSpec("D", (H,), f32, NormInitializer(mean=1.0, stddev=0.02)),
        WeightSpec("norm", (H * P,), dt,
                   NormInitializer(mean=1.0, stddev=0.02)),
        WeightSpec("wout", (H * P, E), dt, init),
    ]


def _init_state(attrs, input_specs):
    H, P, N, taps = _dims(attrs)
    R = attrs["max_requests"]
    # both float32 whatever the cache's dtype: what a recurrent layer
    # carries from step to step is summed into everything after it
    return {REC.STATE: jnp.zeros((R, H, P, N), jnp.float32),
            REC.TAIL: jnp.zeros((R, taps - 1, H * P + 2 * N), jnp.float32)}


# ----------------------------------------------------------------------
# the recurrence, two forms
# ----------------------------------------------------------------------

def recurrent_step(S, dx, a, B, C):
    """One token a row: ``S [R, H, P, N]`` float32; ``dx [R, H, P]`` (``dt *
    x``), ``a [R, H]``, ``B, C [R, N]``. Returns ``(y [R, H, P], S)``."""
    S = a[..., None, None] * S + dx[..., None] * B[:, None, None, :]
    return jnp.einsum("rhpn,rn->rhp", S, C, precision=HIGHEST), S


def chunk_parts(dx, g, B, C, chunk: int = CHUNK):
    """The state-independent half of the chunked form, for every row at
    once: ``dx [R, T, H, P]``, ``g [R, T, H]`` (``log a`` <= 0), ``B, C [R,
    T, N]``, float32; a padding position has ``g = 0`` and ``dx = 0``.
    Returns ``(y_in [Q, H, P], into [Q, H], C [Q, N], dx_end [Q, H, P], B
    [Q, N], decay [H])`` with leading dims ``[chunks, R]``: for the incoming
    state ``S``, ``y = y_in + into * (S C)``; ``S' = decay * S + dx_end^T
    B``."""
    R, T = dx.shape[:2]
    Q = chunk if T >= chunk else -(-T // 8) * 8
    pad = -T % Q
    if pad:                 # as padding positions: the state passes them
        dx, g, B, C = (jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (
            x.ndim - 2)) for x in (dx, g, B, C))
    nc = (T + pad) // Q

    def by_chunk(x):        # [R, T, ..] -> [nc, R, Q, ..]
        return jnp.moveaxis(x.reshape((R, nc, Q) + x.shape[2:]), 1, 0)

    dx, g, B, C = (by_chunk(x) for x in (dx, g, B, C))
    G = jnp.cumsum(g, axis=2)                       # inclusive, <= 0
    # the pairs' decays, the exponent's difference first: [nc, R, H, t, s]
    Gh = jnp.moveaxis(G, -1, 2)                     # [nc, R, H, Q]
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    pair = jnp.where(causal, jnp.exp(jnp.minimum(
        Gh[..., :, None] - Gh[..., None, :], 0)), 0)
    cb = jnp.einsum("crtn,crsn->crts", C, B, precision=HIGHEST)
    y_in = jnp.einsum("crhts,crshp->crthp", pair * cb[:, :, None], dx,
                      precision=HIGHEST)
    into = jnp.exp(G)                               # from the chunk's start
    # what each token leaves in the state at the chunk's end
    dx_end = dx * jnp.exp(G[:, :, -1:] - G)[..., None]
    return y_in, into, C, dx_end, B, into[:, :, -1]


def through_chunks(S0, parts, T: int):
    """The state-dependent half: ``S0 [R, H, P, N]`` through ``parts``
    (``chunk_parts`` of the same rows), chunk after chunk. Returns ``(y [R,
    T, H, P], S_T)``."""

    def step(S, p):
        y_in, into, C, dx_end, B, decay = p
        y = y_in + into[..., None] * jnp.einsum(
            "rhpn,rtn->rthp", S, C, precision=HIGHEST)
        S = decay[..., None, None] * S + jnp.einsum(
            "rthp,rtn->rhpn", dx_end, B, precision=HIGHEST)
        return S, y

    with jax.named_scope("ssd_chunked"):
        if parts[0].shape[0] == 1:      # one chunk: no scan
            S, y = step(S0, jax.tree.map(lambda p: p[0], parts))
            y = y[None]
        else:
            S, y = jax.lax.scan(step, S0, parts)    # y [nc, R, Q, H, P]
    nc, R, Q, H, P = y.shape
    return jnp.moveaxis(y, 0, 1).reshape(R, nc * Q, H, P)[:, :T], S


def chunked(S0, dx, g, B, C, chunk: int = CHUNK):
    """The recurrence over ``T`` tokens a row by chunks, rows side by side.
    ``S0 [R, H, P, N]``; the rest as ``chunk_parts`` takes them. Returns
    ``(y [R, T, H, P], S_T)``."""
    return through_chunks(S0, chunk_parts(dx, g, B, C, chunk), dx.shape[1])


# ----------------------------------------------------------------------
# the op
# ----------------------------------------------------------------------

def takes_chunk_kernel(attrs, config) -> bool:
    """Whether a prefill step of this layer runs a kernel: never, the
    chunked form is plain XLA (``chunked``)."""
    return False


def takes_step_kernel(attrs, config):
    """Whether a decode step of this layer runs ``ssd_state_step``: None
    (``recurrent_step``), else whether the kernel is interpreted."""
    from flexflow_tpu.kernels import linear_attention as LA

    interpret = REC.kernel_path(attrs, config)
    H, P, N, _ = _dims(attrs)
    if interpret is None or not (interpret or LA.supports_ssd(H, P, N)):
        return None
    return interpret


@register_op
class IncSSDMixer(OpImpl):
    """Incremental-decoding state-space mixer: a per-slot recurrent state
    and convolution tail, no cache of positions."""

    op_type = OpType.INC_SSD_MIXER
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

        x_in = inputs[0]
        meta = ctx.batch_config
        assert meta is not None, "serving ops need ctx.batch_config"
        REC.refuse_staged(meta, ctx)
        H, P, N, _ = _dims(attrs)
        d_inner = H * P
        R, Q = x_in.shape[:2]
        f32 = jnp.float32
        # float32 out of the gemm (its accumulator): the tail carries xBC
        # on, and dt is exponentiated and summed over a row's whole past
        zxd = qmatmul(x_in, params["win"], out_dtype=f32)
        z = zxd[..., :d_inner]
        u = zxd[..., d_inner:2 * d_inner + 2 * N]
        n = jnp.where(meta.active, meta.num_tokens, 0)
        real = (jnp.arange(Q)[None, :] < n[:, None])
        dt = jax.nn.softplus(zxd[..., 2 * d_inner + 2 * N:]
                             + params["dt_bias"].astype(f32))
        dt = jnp.where(real[..., None], dt, 0)              # [R, Q, H]
        g = -jnp.exp(params["A_log"].astype(f32)) * dt      # log a, <= 0
        S_all, U_all = REC.stack_of(ctx)
        lidx = attrs["state_layer_idx"]
        start, slots = meta.start_pos, meta.slots
        fresh = functools.partial(REC.fresh, start=start)
        wrote = functools.partial(REC.wrote, n=n)

        # 1. the convolution's tail, then x, B, C for every row at once
        ext_u, U_all = REC.runs_with_tails(U_all, lidx, u, start, slots, n)
        mixed = REC.depthwise_conv(params["conv"], ext_u, Q,
                                   params["conv_bias"])
        x = mixed[..., :d_inner].reshape(R, Q, H, P)
        B = mixed[..., d_inner:d_inner + N]
        C = mixed[..., d_inner + N:]
        dx = dt[..., None] * x

        # 2. the state
        if slots is None and Q == 1:
            args = (dx[:, 0], jnp.exp(g[:, 0]), B[:, 0], C[:, 0])
            interpret = takes_step_kernel(attrs, ctx.config)
            if interpret is not None:
                y, S_all = LA.ssd_state_step(S_all, lidx, *args, n > 0,
                                             start == 0, interpret=interpret)
            else:
                old = S_all[lidx]
                y, new = recurrent_step(fresh(old), *args)
                S_all = S_all.at[lidx].set(wrote(new, old))
            y = y[:, None]
        elif slots is None:
            old = S_all[lidx]
            y, new = chunked(fresh(old), dx, g, B, C)
            S_all = S_all.at[lidx].set(wrote(new, old))
        else:
            # a handful of rows (the step's segments): what a chunk's
            # tokens give whatever state comes in for all the rows at once,
            # then the state's walk a row at a time, in order: a row that
            # continues an earlier row of its slot starts from that row's
            # end, inside this forward
            parts = chunk_parts(dx, g, B, C)

            def through(i, S):
                y, S = through_chunks(
                    S[None], jax.tree.map(lambda p: p[:, i:i + 1], parts), Q)
                return y[0], S[0]

            outs, S_all = carried_rows(S_all, slots, start, n, through,
                                       layer=lidx)
            y = jnp.stack(outs)
        ctx.state_out[RECURRENT_STACK] = {"s": S_all, "u": U_all}

        # 3. the skip term, the gate, ONE norm over all the channels, out
        y = y + params["D"].astype(f32)[:, None] * x
        y = y.reshape(R, Q, d_inner) * jax.nn.silu(z)
        y = _rms_norm(y, params["norm"].astype(f32), attrs["norm_eps"])
        return [qmatmul(y.astype(x_in.dtype), params["wout"])]
