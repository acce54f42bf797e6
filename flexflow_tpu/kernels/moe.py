"""Routed-expert kernel for the serving path: dropless SwiGLU experts.

``y_t = sum_j w[t, j] * W_down[e] (silu(W_gate[e] x_t) * W_up[e] x_t)`` with
``e = idx[t, j]``, for the tokens a step really holds. The reference's
serving experts op (src/ops/experts.cu) groups tokens by expert and runs
batched gemms; ``ops/moe.Experts`` here multiplies every token by every
expert. This one sorts the (token, expert) pairs by expert and lays them out
in row tiles of ``tm`` so that every tile belongs to ONE expert (a group is
padded to whole tiles), then walks the tiles in one Pallas call:

* arithmetic is per tile, so proportional to the routed pairs (plus at most
  one partly filled tile per touched expert);
* an expert's three matrices are DMA'd when the walk reaches its first tile
  and stay in VMEM for its others; an expert no token chose has no tile and
  is never read: HBM reads are proportional to the distinct experts touched;
* int8 expert weights are read as int8 and scaled per (expert, output
  column) on the f32 accumulator: no bf16 copy of an expert exists in HBM;
* a pair of a padded position or an inactive slot is sorted past the last
  group and lands in no tile: it touches no expert and adds nothing;
* a chip that holds experts ``[first, first + E)`` of a wider router (its
  share of an expert-parallel layer) treats a pair routed elsewhere the
  same way: no tile, no bytes, no arithmetic. What it returns is its own
  experts' part of the sum.

Backends without Mosaic (the CPU tests) run the same layout through
``lax.ragged_dot``; that is counted as a fallback, the way
``kernels.fallback_counts`` counts the attention kernel's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Per-process trace counts, read by the benchmark family's warm-up check
# (the pair kernels/__init__.py keeps for the attention kernel).
fast_path_count: int = 0
fallback_counts: dict = {}

TILE_MAX = 128      # the MXU's rows: a fuller tile costs no more passes
TILE_MIN = 16       # bf16 sublane tile
COL_CHUNK = 256     # columns converted and multiplied at a time in VMEM


def record_fast_path():
    global fast_path_count
    fast_path_count += 1


def record_fallback(reason: str):
    fallback_counts[reason] = fallback_counts.get(reason, 0) + 1


def reset_dispatch_stats():
    global fast_path_count
    fast_path_count = 0
    fallback_counts.clear()


def pick_tile(pairs: int, num_experts: int) -> int:
    """Rows of a tile: the power of two at or above the mean group size,
    within [TILE_MIN, TILE_MAX]. Decode (a few rows a group) takes the
    smallest, a prefill chunk the MXU's height."""
    mean = max(1, -(-pairs // num_experts))
    return int(min(TILE_MAX, max(TILE_MIN, 1 << (mean - 1).bit_length())))


def plan_routes(idx, valid, num_experts: int, tm: int):
    """Lay the pairs of ``idx`` [T, k] out in expert-sorted row tiles.

    Returns ``row_token`` [M]: the token each row of the tiled buffer holds
    (0 for padding rows, whose results nobody reads); ``pair_row`` [T, k]:
    the row holding each pair's result (M for a pair of a token that is not
    ``valid``); ``tile_expert`` [n_tiles], ``n_active`` (tiles in use: the
    walk skips the rest) and ``sizes`` [E], the pairs each expert got.
    ``valid`` may also be [T, k], a pair at a time (a held range)."""
    T, k = idx.shape
    P, E = T * k, num_experts
    n_tiles = (P + min(E, P) * (tm - 1)) // tm
    M = n_tiles * tm
    i32 = jnp.int32
    if valid.ndim == 1:
        valid = valid[:, None]
    keys = jnp.where(valid, idx.astype(i32), E).reshape(P)
    # one single-operand sort: the pair's index rides in the low digits
    packed = jnp.sort(keys * P + jnp.arange(P, dtype=i32))
    ks, order = packed // P, packed % P
    sizes = jnp.sum(keys[:, None] == jnp.arange(E, dtype=i32)[None, :],
                    axis=0, dtype=i32)
    tiles = (sizes + (tm - 1)) // tm
    starts = jnp.cumsum(sizes) - sizes
    tile_ends = jnp.cumsum(tiles)
    n_active = tile_ends[-1]
    kc = jnp.minimum(ks, E - 1)
    dest = jnp.where(
        ks < E,
        (tile_ends - tiles)[kc] * tm + jnp.arange(P, dtype=i32) - starts[kc],
        M)
    # computed once: fused into both scatters below, the two table lookups
    # of a prefill chunk's pairs cost XLA's fusion pass seconds a layer
    dest = jax.lax.optimization_barrier(dest)
    row_token = jnp.zeros((M,), i32).at[dest].set(order // k, mode="drop")
    pair_row = jnp.zeros((P,), i32).at[order].set(dest).reshape(T, k)
    # tiles past the last active one repeat its expert, so nothing is
    # fetched for them
    at = jnp.minimum(jnp.arange(n_tiles, dtype=i32), n_active - 1)
    tile_expert = jnp.clip(
        jnp.searchsorted(tile_ends, at, side="right"), 0, E - 1).astype(i32)
    return row_token, pair_row, tile_expert, n_active.reshape(1), sizes


def _split(w):
    """(payload, scale or None) of a possibly quantized [E, in, out]."""
    from flexflow_tpu.quant import is_quantized

    if is_quantized(w):
        assert w.qtype == "int8", w.qtype
        return w.q, w.scale
    return w, None


def _tiles_kernel(te_ref, na_ref, x_ref, g_ref, u_ref, d_ref, *rest,
                  scaled: bool):
    if scaled:
        gs_ref, us_ref, ds_ref, o_ref, act_ref = rest
    else:
        o_ref, act_ref = rest
    del te_ref

    @pl.when(pl.program_id(0) < na_ref[0])
    def _():
        x = x_ref[...]
        inter, hidden = g_ref.shape[-1], d_ref.shape[-1]
        ck = min(COL_CHUNK, inter)
        for c in range(0, inter, ck):
            g = jnp.dot(x, g_ref[:, c:c + ck].astype(x.dtype),
                        preferred_element_type=jnp.float32)
            u = jnp.dot(x, u_ref[:, c:c + ck].astype(x.dtype),
                        preferred_element_type=jnp.float32)
            if scaled:
                g = g * gs_ref[:, c:c + ck]
                u = u * us_ref[:, c:c + ck]
            act_ref[:, c:c + ck] = (g * jax.nn.sigmoid(g) * u).astype(
                act_ref.dtype)
        a = act_ref[...]
        ck = min(COL_CHUNK, hidden)
        for c in range(0, hidden, ck):
            y = jnp.dot(a, d_ref[:, c:c + ck].astype(a.dtype),
                        preferred_element_type=jnp.float32)
            if scaled:
                y = y * ds_ref[:, c:c + ck]
            o_ref[:, c:c + ck] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def moe_experts_tiles(xs, tile_expert, n_active, gate, up, down, *, tm: int,
                      interpret: bool = False):
    """The Pallas walk over row tiles. ``xs`` [M, H] holds the tiles'
    rows; gate/up [E, H, I] and down [E, I, H] are arrays or int8
    ``QuantizedWeight``s. Returns [M, H]; rows of unused tiles are not
    written."""
    M, H = xs.shape
    n_tiles = M // tm
    (g, gs), (u, us), (d, ds) = _split(gate), _split(up), _split(down)
    inter = g.shape[-1]
    scaled = gs is not None

    def row(i, te, na):
        return (jnp.maximum(jnp.minimum(i, na[0] - 1), 0), 0)

    def expert(i, te, na):
        return (te[i], 0, 0)

    in_specs = [pl.BlockSpec((tm, H), row),
                pl.BlockSpec((None, H, inter), expert),
                pl.BlockSpec((None, H, inter), expert),
                pl.BlockSpec((None, inter, H), expert)]
    args = [xs, g, u, d]
    if scaled:
        in_specs += [pl.BlockSpec((None, 1, inter), expert),
                     pl.BlockSpec((None, 1, inter), expert),
                     pl.BlockSpec((None, 1, H), expert)]
        args += [gs[:, None, :], us[:, None, :], ds[:, None, :]]
    w_bytes = 3 * H * inter * g.dtype.itemsize
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=int(min(100 << 20, 2 * w_bytes + (24 << 20))))
    cost = pl.CostEstimate(flops=6 * M * H * inter,
                           bytes_accessed=n_tiles * w_bytes,
                           transcendentals=M * inter)
    return pl.pallas_call(
        functools.partial(_tiles_kernel, scaled=scaled),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_tiles,), in_specs=in_specs,
            out_specs=pl.BlockSpec((tm, H), row),
            scratch_shapes=[pltpu.VMEM((tm, inter), xs.dtype)]),
        out_shape=jax.ShapeDtypeStruct((M, H), xs.dtype),
        compiler_params=params, cost_estimate=cost, interpret=interpret,
        name="moe_experts",
    )(tile_expert, n_active, *args)


def _dense(w, dtype):
    from flexflow_tpu.quant import dequantize_array, is_quantized

    return dequantize_array(w, dtype) if is_quantized(w) else w.astype(dtype)


def moe_experts_ragged(xs, tiles_per_expert, gate, up, down, *, tm: int):
    """The same tiled rows through ``lax.ragged_dot`` (no Mosaic)."""
    sizes = tiles_per_expert * tm
    dt = xs.dtype
    g = jax.lax.ragged_dot(xs, _dense(gate, dt), sizes,
                           preferred_element_type=jnp.float32)
    u = jax.lax.ragged_dot(xs, _dense(up, dt), sizes,
                           preferred_element_type=jnp.float32)
    a = (g * jax.nn.sigmoid(g) * u).astype(dt)
    return jax.lax.ragged_dot(a, _dense(down, dt), sizes,
                              preferred_element_type=jnp.float32).astype(dt)


def moe_experts(x, idx, weights, valid, gate, up, down, *, pallas: bool,
                interpret: bool = False, held=None):
    """x [T, H], idx/weights [T, k], valid [T] -> (y [T, H], sizes [E]).

    ``sizes`` is the number of routed pairs each expert got: the op's
    counters are made of it. ``held`` ``(first, router width)``: the E
    experts here are ``[first, first + E)`` of that many; ``idx`` is over
    all of them and only the pairs of the held ones are pairs."""
    T, k = idx.shape
    E = gate.shape[0]
    pairs = T * k
    if held is not None:
        first, width = held
        idx = idx - first
        valid = valid[:, None] & (idx >= 0) & (idx < E)
        pairs = -(-pairs * E // width)      # this share's, evenly routed
    tm = pick_tile(pairs, E)
    row_token, pair_row, tile_expert, n_active, sizes = plan_routes(
        idx, valid, E, tm)
    xs = x[row_token]
    if pallas:
        out = moe_experts_tiles(xs, tile_expert, n_active, gate, up, down,
                                tm=tm, interpret=interpret)
    else:
        out = moe_experts_ragged(xs, (sizes + (tm - 1)) // tm, gate, up,
                                 down, tm=tm)
    M = out.shape[0]
    # a dead pair points past the buffer; rows of unused tiles were never
    # written: select, never multiply by a zero weight
    picked = jnp.where((pair_row < M)[..., None],
                       out[jnp.minimum(pair_row, M - 1)], 0)
    y = jnp.einsum("tkh,tk->th", picked.astype(jnp.float32),
                   weights.astype(jnp.float32))
    return y.astype(x.dtype), sizes
