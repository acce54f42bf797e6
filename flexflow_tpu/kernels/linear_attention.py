"""The recurrent form of a gated delta rule with one decay a key channel
(ops/kda_attention.py): one token a row, a state a row a head.

A row's state is ``S [H, K, V]`` float32 (4.19 MB at 64 heads of 128 x 128).
A decode step reads ALL of it and writes ALL of it back:

    S' = exp(g)[:, None] * S           one decay a KEY channel
    d  = beta * (v - S'^T k)
    S  = S' + k d^T
    o  = S^T q

so the kernel's bound is its stream, ``2 x`` the state's bytes a live row,
and its arithmetic (a few multiplies an element) hides behind it. It takes
the whole stack ``[layers, slots, H, K, V]`` aliased to its output, the layer
as an operand (scalar prefetch: a program's layers share one trace), and
walks the LIVE rows only, ``heads_per_block`` heads a program: a block of 16
heads is one descriptor of 1.05 MB (PERF.md section 6, PR 51: a descriptor
delivers 680 GB/s at 1.31 MB and 560 at 0.79 MB; one head's 64 KB would not).

A row with no token this step is neither fetched nor written: the grid is
``slots x head blocks``, the live rows first (``rows``, scalar prefetch), and
every program past the last live one names the block the last live program
named, which the pipeline neither fetches again nor writes back before the
end.

In a head's ``[K, V]`` tile ``K`` lies along the sublanes, so ``exp(g)``,
``k`` and ``q`` are wanted as COLUMNS. They come in as rows ``[heads, K]``;
products with the identity, contracted over both last dims, hand back
``[K, heads]`` exactly (three bfloat16 pieces of each value, each one
value times 1 plus zeros in a float32 accumulator), and a head's column is
a lane of it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# heads a program: 16 x 128 x 128 float32 = 1.05 MB a descriptor; in and out
# double-buffered 4.2 MB of VMEM
HEADS_PER_BLOCK = 16
NAME = "kda_state_step"


def heads_per_block(H: int) -> int:
    hb = min(HEADS_PER_BLOCK, H)
    while H % hb:
        hb -= 1
    return hb


def supports(H: int, K: int, V: int) -> bool:
    """Shapes Mosaic takes: whole 128-lane tiles, head blocks of whole
    8-sublane tiles (interpreted, any shape goes)."""
    return K % 128 == 0 and V % 128 == 0 and heads_per_block(H) % 8 == 0


def state_step_bytes(rows: float, H: int, K: int, V: int) -> float:
    """Bytes the kernel must move for ``rows`` live rows of one layer: the
    state in and out, and q, k, g, v, beta in and o out (float32)."""
    return rows * H * (2.0 * K * V + 3 * K + 2 * V + 1) * 4


def _kernel(lidx_ref, rows_ref, nl_ref, fresh_ref, s_ref, q_ref, k_ref,
            g_ref, v_ref, b_ref, o0_ref, so_ref, o_ref, *, hb: int):
    del lidx_ref, o0_ref            # index maps and aliasing only
    i = pl.program_id(0)
    nl = nl_ref[0]
    f32 = jnp.float32

    @pl.when(i < nl)
    def _live():
        K = s_ref.shape[1]
        # a row that starts a request starts from zeros, whatever the slot
        # held
        keep = jnp.where(fresh_ref[rows_ref[i]] != 0, 0.0, 1.0).astype(f32)
        bf16 = jnp.bfloat16
        eye = (jax.lax.broadcasted_iota(jnp.int32, (K, K), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (K, K), 1)).astype(bf16)

        def columns(x):             # [hb, K] -> [K, hb], exactly
            # three bfloat16 pieces that add up to the float32 value (8 + 8
            # + 8 bits of mantissa), each through the identity: one value
            # times 1 plus zeros in a float32 accumulator, whatever
            # precision the compiler would give a float32 product
            out, rest = None, x
            for _ in range(3):
                piece = rest.astype(bf16)
                rest = rest - piece.astype(f32)
                part = jax.lax.dot_general(
                    eye, piece, (((1,), (1,)), ((), ())),
                    preferred_element_type=f32)
                out = part if out is None else out + part
            return out

        aT = columns(jnp.exp(g_ref[...]) * keep)
        kT, qT = columns(k_ref[...]), columns(q_ref[...])
        for h in range(hb):         # a head's [K, V] tile at a time
            kc = kT[:, h:h + 1]
            S = s_ref[h] * aT[:, h:h + 1]
            d = b_ref[h:h + 1, :] * (
                v_ref[h:h + 1, :] - jnp.sum(S * kc, axis=0, keepdims=True))
            S = S + kc * d
            so_ref[h] = S
            o_ref[h:h + 1, :] = jnp.sum(S * qT[:, h:h + 1], axis=0,
                                        keepdims=True)

    @pl.when((i == 0) & (nl == 0))
    def _nobody():
        # no live row at all: program 0's block is fetched and written back
        # all the same, so it goes back as it came
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_state_step(state, layer_idx, q, k, g, v, beta, live, fresh,
                   interpret: bool = False):
    """One token a row through the state of layer ``layer_idx``.

    ``state`` ``[L, R, H, K, V]`` float32, updated in place (donate it);
    ``q, k, g`` ``[R, H, K]``, ``v`` ``[R, H, V]``, ``beta`` ``[R, H]``,
    float32; ``live`` ``[R]`` bool: rows that have a token; ``fresh`` ``[R]``
    bool: rows that start from zeros. Returns ``(o [R, H, V] float32, the
    stack)``; an idle row's ``o`` is zeros and its state untouched."""
    L, R, H, K, V = state.shape
    hb = heads_per_block(H)
    nhb = H // hb
    nl = jnp.sum(live.astype(jnp.int32))
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    rows = jnp.where(jnp.arange(R) < nl, order, order[jnp.maximum(nl - 1, 0)])
    scalars = (jnp.asarray(layer_idx, jnp.int32).reshape(1), rows,
               nl.reshape(1), fresh.astype(jnp.int32))

    def at(i, h, lidx, rows, nl, fresh):
        """The (row, head block) of program (i, h): past the last live row,
        the last live program's, so that nothing moves."""
        return rows[i], jnp.where(i < nl[0], h, nhb - 1)

    def state_map(i, h, lidx, *s):
        r, hh = at(i, h, lidx, *s)
        return lidx[0], r, hh, 0, 0

    def row_map(i, h, *s):
        r, hh = at(i, h, *s)
        return r, hh, 0

    def beta_map(i, h, *s):
        r, hh = at(i, h, *s)
        return r, hh, 0, 0

    vec = lambda w: pl.BlockSpec((None, hb, w), row_map)  # noqa: E731
    f32 = jnp.float32
    new, o = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(R, nhb),
            in_specs=[pl.BlockSpec((None, None, hb, K, V), state_map),
                      vec(K), vec(K), vec(K), vec(V),
                      pl.BlockSpec((None, None, hb, 1), beta_map),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((None, None, hb, K, V), state_map),
                       vec(V)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((R, H, V), f32)],
        # operands count the scalars: the stack is operand 4, the zeros the
        # idle rows' output keeps operand 10
        input_output_aliases={len(scalars): 0, len(scalars) + 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=int(8 * R * H * K * V), transcendentals=int(R * H * K),
            bytes_accessed=int(state_step_bytes(R, H, K, V))),
        interpret=interpret, name=NAME,
    )(*scalars, state, q.astype(f32), k.astype(f32), g.astype(f32),
      v.astype(f32), beta.astype(f32).reshape(R, nhb, hb, 1),
      jnp.zeros((R, H, V), f32))
    return o, new
