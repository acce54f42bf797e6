"""Routed-expert kernel for the serving path: dropless SwiGLU experts.

``y_t = sum_j w[t, j] * W_down[e] (silu(W_gate[e] x_t) * W_up[e] x_t)`` with
``e = idx[t, j]``, for the tokens a step really holds. The reference's
serving experts op (src/ops/experts.cu) groups tokens by expert and runs
batched gemms; ``ops/moe.Experts`` here multiplies every token by every
expert. This one sorts the (token, expert) pairs by expert and cuts the
sorted list into tiles of at most ``tm`` rows so that every tile belongs to
ONE expert (a group fills whole tiles, the last one partly), then walks the
tiles in one Pallas call:

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

**The rows never pass through HBM** (the resident form, ``plan_tiles`` and
``moe_experts_resident``): the call takes the step's tokens ``x`` [T, H]
whole, in float32, and keeps them in VMEM for the walk; a tile copies its
rows out of them one at a time, by the sorted list's pair indices, which
the scalar core reads from SMEM; the tile's results, times the pairs'
weights, are added to a float32 [T, H] that stays in VMEM and is written
once, after the last tile. What XLA does around the call is the sort and a
few sums over [tiles, E]: no buffer of M = tiles x ``tm`` rows, no gather
of M rows into it, no second gather of the results and no sum over ``k``.
``rows_fit`` says, from the shapes alone, whether ``x``, the result and the
sums fit beside two buffers of an expert's matrices; a step that does not
(512 tokens of 6144 beside 2 x 37.7 MB of int8 weights) takes **the staged
form** (``plan_routes`` and ``moe_experts_tiles``), which lays the rows out
in a padded [M, H] buffer in HBM, walks that, and gathers and sums the
results in XLA. Both are the fast path; both carry the name
``moe_experts`` on the device.

Backends without Mosaic (the CPU tests) run the staged layout through
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
ROW_UNROLL = 8      # rows the resident form moves between loop tests
VMEM_LIMIT = 100 << 20      # the most the call asks of the chip's 128 MiB
VMEM_ROOM = 24 << 20        # for a tile's own values beside the buffers


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


def _sorted_pairs(idx, valid, num_experts: int, tm: int):
    """The pairs of ``idx`` [T, k] sorted by expert, a dead one (of a token
    that is not ``valid``; ``valid`` may also be [T, k], a pair at a time)
    past the last group, and the tiles the groups fill: ``order`` [P] (the
    pair ``t * k + j`` at each sorted place), ``ks`` [P] (its expert, E for
    a dead one), per expert ``sizes``, ``tiles`` and the running sums
    ``starts`` (pairs before it) and ``tile_ends`` (tiles up to it), and
    per tile ``tile_expert`` [n_tiles], with ``n_active`` the tiles in
    use: the walk skips the rest."""
    T, k = idx.shape
    P, E = T * k, num_experts
    n_tiles = (P + min(E, P) * (tm - 1)) // tm
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
    # tiles past the last active one repeat its expert, so nothing is
    # fetched for them
    at = jnp.minimum(jnp.arange(n_tiles, dtype=i32), n_active - 1)
    tile_expert = jnp.clip(
        jnp.searchsorted(tile_ends, at, side="right", method="compare_all"),
        0, E - 1).astype(i32)
    return (order, ks, sizes, tiles, starts, tile_ends, tile_expert,
            n_active.reshape(1))


def plan_routes(idx, valid, num_experts: int, tm: int):
    """Lay the pairs of ``idx`` [T, k] out in expert-sorted row tiles: the
    staged form's plan, whose tiles' rows pass through HBM.

    Returns ``row_token`` [M]: the token each row of the tiled buffer holds
    (0 for padding rows, whose results nobody reads); ``pair_row`` [T, k]:
    the row holding each pair's result (M for a pair of a token that is not
    ``valid``); ``tile_expert`` [n_tiles], ``n_active`` (tiles in use: the
    walk skips the rest) and ``sizes`` [E], the pairs each expert got.
    ``valid`` may also be [T, k], a pair at a time (a held range)."""
    T, k = idx.shape
    P, E = T * k, num_experts
    order, ks, sizes, tiles, starts, tile_ends, tile_expert, n_active = \
        _sorted_pairs(idx, valid, E, tm)
    M = tile_expert.shape[0] * tm
    i32 = jnp.int32
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
    return row_token, pair_row, tile_expert, n_active, sizes


def plan_tiles(idx, valid, num_experts: int, tm: int):
    """The resident form's plan: no row of the layout is written anywhere.
    A tile is ``tile_count`` [n_tiles] (at most ``tm``) consecutive places
    of the sorted ``order`` [P] from ``tile_start`` [n_tiles] on; the
    kernel finds its rows' tokens and weights from those. Returns
    ``(order, tile_start, tile_count, tile_expert, n_active, sizes)``."""
    E = num_experts
    order, _, sizes, tiles, starts, tile_ends, tile_expert, n_active = \
        _sorted_pairs(idx, valid, E, tm)
    i32 = jnp.int32
    mine = tile_expert[:, None] == jnp.arange(E, dtype=i32)[None, :]

    def of_expert(table):       # table[tile_expert], a sum and no gather
        return jnp.sum(jnp.where(mine, table[None, :], 0), axis=1, dtype=i32)

    # the tile's place among its expert's; a tile past the last active one
    # is past its expert's pairs and holds none
    nth = jnp.arange(tile_expert.shape[0], dtype=i32) - of_expert(
        tile_ends - tiles)
    tile_start = of_expert(starts) + nth * tm
    tile_count = jnp.clip(of_expert(sizes) - nth * tm, 0, tm)
    return order, tile_start, tile_count, tile_expert, n_active, sizes


def _split(w):
    """(payload, scale or None) of a possibly quantized [E, in, out]."""
    from flexflow_tpu.quant import is_quantized

    if is_quantized(w):
        assert w.qtype == "int8", w.qtype
        return w.q, w.scale
    return w, None


def _swiglu_tile(x, g_ref, u_ref, d_ref, scales, act_ref, put):
    """One tile's rows ``x`` [tm, H] through the expert whose matrices the
    refs hold; ``put(c, ck, y)`` takes the float32 result, ``ck`` columns
    from ``c`` at a time."""
    inter, hidden = g_ref.shape[-1], d_ref.shape[-1]
    ck = min(COL_CHUNK, inter)
    for c in range(0, inter, ck):
        g = jnp.dot(x, g_ref[:, c:c + ck].astype(x.dtype),
                    preferred_element_type=jnp.float32)
        u = jnp.dot(x, u_ref[:, c:c + ck].astype(x.dtype),
                    preferred_element_type=jnp.float32)
        if scales:
            g = g * scales[0][:, c:c + ck]
            u = u * scales[1][:, c:c + ck]
        act_ref[:, c:c + ck] = (g * jax.nn.sigmoid(g) * u).astype(
            act_ref.dtype)
    a = act_ref[...]
    ck = min(COL_CHUNK, hidden)
    for c in range(0, hidden, ck):
        y = jnp.dot(a, d_ref[:, c:c + ck].astype(a.dtype),
                    preferred_element_type=jnp.float32)
        if scales:
            y = y * scales[2][:, c:c + ck]
        put(c, ck, y)


def _tiles_kernel(te_ref, na_ref, x_ref, g_ref, u_ref, d_ref, *rest,
                  scaled: bool):
    *scales, o_ref, act_ref = rest
    assert len(scales) == (3 if scaled else 0)
    del te_ref

    @pl.when(pl.program_id(0) < na_ref[0])
    def _():
        def put(c, ck, y):
            o_ref[:, c:c + ck] = y.astype(o_ref.dtype)

        _swiglu_tile(x_ref[...], g_ref, u_ref, d_ref, scales, act_ref, put)


def _resident_kernel(te_ref, na_ref, ts_ref, tc_ref, ord_ref, w_ref, x_ref,
                     g_ref, u_ref, d_ref, *rest, scaled: bool, k: int):
    """A tile forms its rows from the step's tokens, which stay in VMEM for
    the walk, and adds its weighted results to the tokens' float32 sums,
    which leave VMEM once, after the last tile. The rows move one at a time
    on the scalar core's say: row ``r`` of tile ``i`` is the pair
    ``ord_ref[ts_ref[i] + r]`` while ``r < tc_ref[i]``, its token
    ``pair // k`` and its weight ``w_ref[pair]``."""
    *scales, o_ref, xs_ref, act_ref, y_ref, acc_ref = rest
    assert len(scales) == (3 if scaled else 0)
    del te_ref
    i = pl.program_id(0)
    P = ord_ref.shape[0]

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < na_ref[0])
    def _():
        start, count = ts_ref[i], tc_ref[i]

        def for_rows(body):
            """``body(r, pair)`` for the tile's rows, ``ROW_UNROLL`` between
            loop tests (Mosaic unrolls a loop wholly or not at all); the
            rows after the last pair of the last few repeat a pair."""
            def some(b, carry):
                for j in range(ROW_UNROLL):
                    r = b * ROW_UNROLL + j
                    body(r, ord_ref[jnp.minimum(start + r, P - 1)])
                return carry

            jax.lax.fori_loop(0, (count + ROW_UNROLL - 1) // ROW_UNROLL,
                              some, 0)

        def gather(r, pair):
            xs_ref[pl.ds(r, 1), :] = x_ref[pl.ds(jax.lax.div(pair, k), 1), :]

        for_rows(gather)

        def put(c, ck, y):
            y_ref[:, c:c + ck] = y

        _swiglu_tile(xs_ref[...].astype(act_ref.dtype), g_ref, u_ref, d_ref,
                     scales, act_ref, put)

        def add(r, pair):
            # select, never multiply by a zero weight: a row past the
            # tile's pairs may hold anything
            acc_ref[pl.ds(jax.lax.div(pair, k), 1), :] += jnp.where(
                r < count, w_ref[pair] * y_ref[pl.ds(r, 1), :], 0.0)

        for_rows(add)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _expert_operands(gate, up, down):
    """(operands, their block specs, bytes of one expert, scaled) of the
    three stacks: the block of a grid step is its tile's expert, whole."""
    (g, gs), (u, us), (d, ds) = _split(gate), _split(up), _split(down)
    H, inter = g.shape[1:]

    def expert(i, te, *_):
        return (te[i], 0, 0)

    specs = [pl.BlockSpec((None, H, inter), expert),
             pl.BlockSpec((None, H, inter), expert),
             pl.BlockSpec((None, inter, H), expert)]
    args = [g, u, d]
    if gs is not None:
        specs += [pl.BlockSpec((None, 1, inter), expert),
                  pl.BlockSpec((None, 1, inter), expert),
                  pl.BlockSpec((None, 1, H), expert)]
        args += [gs[:, None, :], us[:, None, :], ds[:, None, :]]
    return args, specs, 3 * H * inter * g.dtype.itemsize, gs is not None


def _resident_bytes(T: int, H: int, x_itemsize: int) -> int:
    """VMEM the resident form's rows take: the tokens in float32, one
    buffer (a row is loaded by a dynamic index, which Mosaic does for
    32-bit rows only; the block never changes), the result in two, as
    Pallas keeps an output block, and the float32 sums."""
    return T * H * (4 + 2 * x_itemsize + 4)


def rows_fit(T: int, H: int, inter: int, w_itemsize: int,
             x_itemsize: int) -> bool:
    """Whether a step's rows can stay in VMEM for the walk, beside two
    buffers of an expert's three matrices and the room a tile's own values
    take, within what the call asks of the chip's 128 MiB. Of the shapes
    alone, so a program is one form or the other for good."""
    return (2 * 3 * H * inter * w_itemsize + _resident_bytes(T, H, x_itemsize)
            + VMEM_ROOM <= VMEM_LIMIT)


def step_fits(T: int, H: int, gate, x_dtype) -> bool:
    """``rows_fit`` for a step of ``T`` tokens of ``H`` in ``x_dtype``
    through experts whose gate stack (an array or a ``QuantizedWeight``) is
    ``gate``."""
    g = _split(gate)[0]
    return rows_fit(T, H, g.shape[-1], g.dtype.itemsize,
                    jnp.dtype(x_dtype).itemsize)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def moe_experts_tiles(xs, tile_expert, n_active, gate, up, down, *, tm: int,
                      interpret: bool = False):
    """The Pallas walk over row tiles, staged: ``xs`` [M, H] holds the
    tiles' rows; gate/up [E, H, I] and down [E, I, H] are arrays or int8
    ``QuantizedWeight``s. Returns [M, H]; rows of unused tiles are not
    written."""
    M, H = xs.shape
    n_tiles = M // tm
    args, specs, w_bytes, scaled = _expert_operands(gate, up, down)
    inter = args[0].shape[-1]

    def row(i, te, na):
        return (jnp.maximum(jnp.minimum(i, na[0] - 1), 0), 0)

    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=int(min(VMEM_LIMIT, 2 * w_bytes + VMEM_ROOM)))
    cost = pl.CostEstimate(flops=6 * M * H * inter,
                           bytes_accessed=n_tiles * w_bytes,
                           transcendentals=M * inter)
    return pl.pallas_call(
        functools.partial(_tiles_kernel, scaled=scaled),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_tiles,),
            in_specs=[pl.BlockSpec((tm, H), row)] + specs,
            out_specs=pl.BlockSpec((tm, H), row),
            scratch_shapes=[pltpu.VMEM((tm, inter), xs.dtype)]),
        out_shape=jax.ShapeDtypeStruct((M, H), xs.dtype),
        compiler_params=params, cost_estimate=cost, interpret=interpret,
        name="moe_experts",
    )(tile_expert, n_active, xs, *args)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def moe_experts_resident(x, weights, order, tile_start, tile_count,
                         tile_expert, n_active, gate, up, down, *, tm: int,
                         interpret: bool = False):
    """The same walk with the rows gathered and the results weighted and
    added inside the call: ``x`` [T, H] and ``weights`` [T, k] whole, the
    rest from ``plan_tiles``. Returns ``y`` [T, H]."""
    T, H = x.shape
    k = weights.shape[1]
    n_tiles = tile_expert.shape[0]
    args, specs, w_bytes, scaled = _expert_operands(gate, up, down)
    inter = args[0].shape[-1]

    def whole(i, *_):
        return (0, 0)

    rows = _resident_bytes(T, H, x.dtype.itemsize)
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=int(min(VMEM_LIMIT,
                                 2 * w_bytes + rows + VMEM_ROOM)))
    cost = pl.CostEstimate(flops=6 * n_tiles * tm * H * inter,
                           bytes_accessed=n_tiles * w_bytes,
                           transcendentals=n_tiles * tm * inter)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_resident_kernel, scaled=scaled, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(n_tiles,),
            in_specs=[pl.BlockSpec((T, H), whole,
                                   pipeline_mode=pl.Buffered(1))] + specs,
            out_specs=pl.BlockSpec((T, H), whole),
            scratch_shapes=[pltpu.VMEM((tm, H), f32),
                            pltpu.VMEM((tm, inter), x.dtype),
                            pltpu.VMEM((tm, H), f32),
                            pltpu.VMEM((T, H), f32)]),
        out_shape=jax.ShapeDtypeStruct((T, H), x.dtype),
        compiler_params=params, cost_estimate=cost, interpret=interpret,
        name="moe_experts",
    )(tile_expert, n_active, tile_start, tile_count, order,
      weights.astype(f32).reshape(T * k), x.astype(f32), *args)


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
                interpret: bool = False, held=None, resident=None):
    """x [T, H], idx/weights [T, k], valid [T] -> (y [T, H], sizes [E]).

    ``sizes`` is the number of routed pairs each expert got: the op's
    counters are made of it. ``held`` ``(first, router width)``: the E
    experts here are ``[first, first + E)`` of that many; ``idx`` is over
    all of them and only the pairs of the held ones are pairs.
    ``resident``: whether the Pallas call gathers its rows and adds its
    results itself (``rows_fit`` decides where nobody says)."""
    T, k = idx.shape
    E = gate.shape[0]
    pairs = T * k
    if held is not None:
        first, width = held
        idx = idx - first
        valid = valid[:, None] & (idx >= 0) & (idx < E)
        pairs = -(-pairs * E // width)      # this share's, evenly routed
    tm = pick_tile(pairs, E)
    if resident is None:
        resident = pallas and step_fits(T, x.shape[1], gate, x.dtype)
    if resident:
        assert pallas, "only the Pallas call holds its rows"
        *plan, sizes = plan_tiles(idx, valid, E, tm)
        return moe_experts_resident(x, weights, *plan, gate, up, down,
                                    tm=tm, interpret=interpret), sizes
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
