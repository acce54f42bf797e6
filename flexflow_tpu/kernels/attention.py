"""Fused KV-cache attention as a Pallas TPU kernel.

One kernel serves all three reference serving-attention variants
(reference src/ops/inc_multihead_self_attention.cu:560
compute_attention_kernel, spec_inc_multihead_self_attention.cu,
tree_inc_multihead_self_attention.cu):

* incremental decode  — ``causal=True``, Q = 1 token per request
* prompt prefill      — ``causal=True``, Q = padded prompt length
* tree verification   — ``causal=False`` with an explicit additive ``bias``
                        [R, Q, S] carrying the prefix+ancestor tree mask
* ALiBi position bias — optional in-kernel ``-slope * (qpos - s)`` term

Design (TPU-first, not a CUDA translation):
- grid is one program per request slot; the KV cache stays in HBM and is
  streamed through VMEM in double-buffered ``BLOCK_S`` chunks (async DMA
  overlaps the MXU work on the previous chunk).
- online softmax (flash attention) in fp32 scratch, so the [Q, S] score
  matrix is never materialized in HBM.
- the per-request loop bound is ``ceil(length[r] / BLOCK_S)`` with lengths
  scalar-prefetched: finished / inactive request slots cost zero DMA and
  zero FLOPs (the jnp fallback, like the reference CUDA, pays for max_seq).
- GQA/MQA: queries are pre-packed to [KH, G*Q, D] so the kernel's inner
  matmuls are KH-batched [G*Q, D] x [D, BLOCK_S] MXU calls.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # finite "minus infinity": keeps online softmax NaN-free

# Mosaic tiling: DMA slices need the sublane (second-minor) dim 8-aligned
# and the lane (minor) dim 128-aligned — the single source of truth for
# the dispatch guards here and the width/head-dim padding at call sites.
SUBLANE = 8
LANE = 128

# The fused append of a RUN of positions (``flash_attend`` ``append_kv`` with
# more than one position a row) merges and writes back aligned windows of
# this many stored rows: a whole packed tile of a 16-bit cache (two tiles of
# a 32-bit one), which is what a vector load or store at a dynamic row of the
# streamed block needs. Two consecutive windows hold any run of up to 17
# rows from any start. A run is at most APPEND_RUN_MOST positions: the
# widest decode step there is (an engine's verify width, two diffusion
# blocks of four), one sublane of new rows and as many selects a window; a
# wider step is a prefill chunk, which appends by slot.
APPEND_WINDOW_ROWS = 16
APPEND_RUN_MOST = 8


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pack_factor(D: int) -> int:
    """Positions packed per 128-lane cache row. D >= 128 streams one
    position per row (PACK=1); D=64 packs two consecutive positions per
    row (PACK=2) so every DMA slice stays lane-full — the kernel then
    processes each block's even/odd position halves as two online-softmax
    sub-block updates, with zero-padded q variants and lane-masked v so no
    in-kernel relayout is ever needed. A packed cache is STORED packed,
    ``[.., S/PACK, 128]`` (ops/kv_layout.py owns that layout), so nothing
    outside the kernel relays it either. Unsupported D returns 0."""
    if D % LANE == 0:
        return 1
    if D == 64:
        return 2
    return 0


def _pick_block_s(S: int, D: int = LANE) -> int:
    """Cache-stream block size (in POSITIONS): the smallest supported
    tile. Decode is bandwidth-bound and reads ceil(length/BS)*BS keys per
    slot, so small tiles waste the least on short/ragged lengths; the tile
    must also be the SAME for every q-width — speculative decoding
    compares a width-1 decode against a width-(d+1) verify of the same
    positions, and a different softmax block partition would flip near-tie
    argmaxes (reference CI token-match gate,
    python_inference_tests.sh:29). Packed head dims (PACK=2) need 128
    PACKED rows per block so the [Q, S/PACK] bias slices stay
    lane-aligned, hence the 256-position floor."""
    pack = _pack_factor(D)
    if pack == 0:
        return 0
    for bs in (128 * pack, 256 * pack, 512 * pack):
        if S % bs == 0:
            return bs
    return 0  # caller falls back to the jnp path


def supports_seq_len(S: int, D: int = LANE) -> bool:
    """True iff the Pallas kernels here can tile a cache of length S."""
    return _pick_block_s(S, D) > 0


def supports_shapes(S: int, D: int) -> bool:
    """Single source of truth for dispatch guards in ops/ — Mosaic
    requires DMA slices lane-full, so head_dim must be 128-aligned or a
    supported packed size (64), with a cache length the packed block size
    tiles. Callers fall back to the jnp path otherwise."""
    return _pack_factor(D) > 0 and supports_seq_len(S, D)


# The K bytes (and as many V) a DMA block of the plain stream should reach
# before it stops growing (``stream_block``). The loop form waits for a
# block, works on it and only then asks for the one after the next, so what
# a block costs beyond its bytes (the wait's latency, the softmax state's
# round trip through scratch, a chain of small matmuls that each wait for
# the one before to drain) is paid once a block whatever its size: at 2
# key/value heads of 128 in bfloat16 a partition of 128 positions is 64 KB
# and the loop reads 250 GB/s of the HBM's 819, at 16 heads (512 KB) 580. On
# a v5e at 2 heads: 352 us a call of 86 MB at 128 positions a block, 236 at
# 256, 161 at 512, 137 at 1024, 154 at 2048 (PERF.md section 6, PR 51).
STREAM_BLOCK_TARGET = 512 * 1024
# The most a DMA block's float32 scores ``KH x GQ x DB x 4`` may take. A
# row's last block pays for all its partitions, the masked ones too, so
# where the query rows make the arithmetic the kernel's bound a larger block
# costs more than its stream saves: at 4 heads x 64 query rows over rows of
# 350 positions, 134 us a call in the loop, 101 at 256 positions a block (256
# KB of scores), 125 at 512, 143 at 1024 (the same section). A prefill
# segment's hundreds of rows a head are over it at any block.
STREAM_BLOCK_SCORES_LIMIT = 256 * 1024
# For tools/time_stream_attend.py alone, read when a kernel is traced and
# never set by the serving path: None, or the half of ``_stream_attend``
# that a timing keeps: "stream" (every copy, the append's merge and
# write-back, no scores and no softmax) or "arith" (the arithmetic on
# whatever the buffers hold: no copy of the stream started or waited for).
ABLATE = None


def stream_block(KH: int, D: int, itemsize: int, GQ: int, S: int) -> int:
    """The DMA block, in positions, of the PLAIN position-major stream (D
    fills the lanes; no ring, no chunked stream, no bias) of a call over
    ``GQ`` query rows a key/value head (group x tokens), of the call's
    shapes alone. The softmax partition stays ``BS = _pick_block_s(S, D)``
    whatever the query width (its docstring says why); the DMA block is
    ``BS`` doubled, while it divides ``S``, until its K descriptor ``KH x DB
    x D x itemsize`` reaches STREAM_BLOCK_TARGET or its float32 scores would
    pass STREAM_BLOCK_SCORES_LIMIT. ``DB > BS`` is ``_stream_attend``'s block
    form (a decode step's few rows over few key/value heads); ``DB == BS``
    the loop it always was, argument for argument (16 heads and more; a
    prefill segment's hundreds of rows)."""
    BS = DB = _pick_block_s(S, D)
    if BS == 0 or _pack_factor(D) != 1:
        return BS
    while (KH * DB * D * itemsize < STREAM_BLOCK_TARGET
           and S % (2 * DB) == 0
           and KH * GQ * 2 * DB * 4 <= STREAM_BLOCK_SCORES_LIMIT):
        DB *= 2
    return DB


def _pipe_of(refs, static):
    """The block form's one more scalar-prefetched operand behind the
    lengths, the pipeline's walk (``_latent_pipe``), split off a kernel's
    operands: (pipe_ref or None, the rest)."""
    if static["DB"] > static["BS"]:
        return refs[0], refs[1:]
    return None, refs


def _kernel(len_ref, *refs, **static):     # len_ref: scalar prefetch [R] int32
    pipe_ref, (q_ref, qp_ref, slopes_ref, bias_hbm, k_hbm, v_hbm, o_ref, acc,
               m, l, kbuf, vbuf, bbuf, sem) = _pipe_of(refs, static)
    _stream_attend(len_ref, None, q_ref, qp_ref, slopes_ref, None, None,
                   bias_hbm, k_hbm, v_hbm, o_ref, acc, m, l, kbuf, vbuf,
                   bbuf, sem, None, pipe_ref=pipe_ref, **static)


def _rows_kernel(len_ref, *refs, **static):   # scalar prefetch: [R] int32 each
    """Row-mapped variant (the compact prefill batch): grid program ``r``
    streams cache row ``rows[r]`` instead of row ``r``. The caches stay in
    HBM and are only ever indexed by DMA, so reading another row is a
    different DMA source and nothing is gathered; two programs may read
    the same row (two segments of one slot)."""
    pipe_ref, (rows_ref, q_ref, qp_ref, slopes_ref, bias_hbm, k_hbm, v_hbm,
               o_ref, acc, m, l, kbuf, vbuf, bbuf, sem) = _pipe_of(refs,
                                                                    static)
    _stream_attend(len_ref, None, q_ref, qp_ref, slopes_ref, None, None,
                   bias_hbm, k_hbm, v_hbm, o_ref, acc, m, l, kbuf, vbuf,
                   bbuf, sem, None, rows_ref=rows_ref, pipe_ref=pipe_ref,
                   **static)


def _append_kernel(len_ref, *refs,         # scalar prefetch: [R] int32 each
                   run: bool = False, **static):
    """Decode-step variant: this step's new K/V rows land at cache position
    ``appos[r]`` on IN PLACE (the caches are aliased in/out), fused with the
    attention stream. The new rows are merged into the streamed VMEM block
    (so attention sees the post-append cache with zero extra latency) and
    the aligned window of stored rows that holds them is written back
    asynchronously (Mosaic DMA slices need SUBLANE-aligned second-minor
    dims). Write-backs touch only row r's slice, so they never race the
    cross-program prefetch of other rows.

    One new token a row: replaces the XLA Q=1 row scatter that cost ~1.6
    ms/step at 7B geometry (R*KH*L = 16K scalar-unit rows). The window is
    the 8 packed rows around p: rows [pb, p) re-land bitwise-identical, row
    p gets the new K/V, rows beyond re-land whatever garbage they held
    (past ``length``, never attended).

    ``run`` (a third scalar-prefetched operand, ``napp_ref`` [R]): a RUN of
    ``napp[r]`` positions from ``appos[r]``, of the up to A the new rows'
    operand holds (a block-diffusion pass: R*KH*Q = 1024 scatter rows a
    cache a layer otherwise). The windows are the one or two of
    APPEND_WINDOW_ROWS rows that hold ``[appos, appos + napp)``, in this
    stream block or the next: every stored row outside the run re-lands
    what it held, bit for bit, the rows of the operand past ``napp``
    included (the scatter's ``mode="drop"``)."""
    napp_ref = None
    pipe_ref, (appos_ref, *refs) = _pipe_of(refs, static)
    if run:
        napp_ref, *refs = refs
    (q_ref, qp_ref, slopes_ref, knew_ref, vnew_ref, bias_hbm, _, _, o_ref,
     ok_hbm, ov_hbm, acc, m, l, kbuf, vbuf, bbuf, sem, asem) = refs
    _stream_attend(len_ref, appos_ref, q_ref, qp_ref, slopes_ref, knew_ref,
                   vnew_ref, bias_hbm, ok_hbm, ov_hbm, o_ref, acc, m, l,
                   kbuf, vbuf, bbuf, sem, asem, napp_ref=napp_ref,
                   pipe_ref=pipe_ref, **static)


def _window_kernel(len_ref, first_ref, *refs, mode, operand="first_ref",
                   **static):
    """A windowed layer's variant of the three kernels above (``mode``
    None, "rows" or "append"): one more scalar-prefetched operand,
    ``first_ref`` [R], the first cache block a row's window touches. The
    cache is a ring of whole blocks (ops/kv_layout.py): the stream starts
    at that block and block ``b`` is read from ring block ``b % n``.
    A chunked layer's variant likewise (``operand="nsum_ref"``): the operand
    is the summary rows a row sees, of a stream of two extents."""
    appos_ref = rows_ref = knew_ref = vnew_ref = asem = None
    if mode == "append":
        (appos_ref, q_ref, qp_ref, slopes_ref, knew_ref, vnew_ref, bias_hbm,
         _, _, o_ref, k_hbm, v_hbm, acc, m, l, kbuf, vbuf, bbuf, sem,
         asem) = refs
    else:
        if mode == "rows":
            rows_ref, *refs = refs
        (q_ref, qp_ref, slopes_ref, bias_hbm, k_hbm, v_hbm, o_ref, acc, m, l,
         kbuf, vbuf, bbuf, sem) = refs
    _stream_attend(len_ref, appos_ref, q_ref, qp_ref, slopes_ref, knew_ref,
                   vnew_ref, bias_hbm, k_hbm, v_hbm, o_ref, acc, m, l, kbuf,
                   vbuf, bbuf, sem, asem, rows_ref=rows_ref,
                   **{operand: first_ref}, **static)


def _plane_kernel(plane_ref, *refs, kern):
    """Any of the kernels above over a stacked cache whose plane is an
    OPERAND (``flash_attend``'s ``plane``): one more scalar-prefetched
    operand in front of the lengths, read where a static ``layer_idx`` is
    baked in. A loop region's pass index is traced (core/model.py), so its
    layers' planes are; the cache stays in HBM and is only ever indexed by
    DMA, so another plane is another DMA source and nothing is sliced out."""
    kern(*refs, layer_idx=plane_ref[0])


class _Held:
    """A value behind a ref's ``[:]``: the softmax state as the block form
    carries it through a DMA block's partitions, read and stored by the
    lines that read and store the loop form's scratch."""

    def __init__(self, ref):
        self.v = ref[:]

    def __getitem__(self, _):
        return self.v

    def __setitem__(self, _, v):
        self.v = v


def _stream_attend(len_ref, appos_ref, q_ref, qp_ref, slopes_ref, knew_ref,
                   vnew_ref, bias_hbm, k_hbm, v_hbm, o_ref,
                   acc, m, l, kbuf, vbuf, bbuf, sem, asem,
                   *, BS: int, causal: bool, has_bias: bool,
                   has_alibi: bool, qk_scale: float, G: int, Q: int,
                   layer_idx, PACK: int, D: int, rows_ref=None,
                   first_ref=None, window=None, nsum_ref=None,
                   summary_rows=None, napp_ref=None, DB: int, pipe_ref=None):
    """Shared stream-attend body.

    PACK == 1: one position per 128-lane cache row (D % 128 == 0).
    PACK == 2 (D == 64): two consecutive positions per row; each block's
    even/odd halves are processed as two online-softmax sub-block updates.
    The caller pre-builds PACK zero-padded q variants (q in lanes
    [h*D, (h+1)*D), zeros elsewhere) so the half-dot needs no lane
    slicing, v is lane-masked with a select, and the [KH, GQ, LANE]
    accumulator's halves are summed OUTSIDE the kernel — no in-kernel
    relayout anywhere.

    ``window`` (with ``first_ref``; PACK == 1): a query at position i sees
    keys ``i - window < j <= i``, masked by ABSOLUTE position; program r
    streams blocks ``first[r] .. ceil(length / BS)`` of the positions and
    reads block b from the ring's block ``b % (ring rows / SB)``.

    ``summary_rows`` (with ``nsum_ref``; PACK == 1): the stream is two
    extents, rows ``[0, summary_rows)`` and the rows behind them. Program r
    sees the first ``nsum[r]`` rows of the first, all of them, and the
    second up to ``length`` as ever (``key <= qpos``, ``key < length``, all
    three as rows of the stream); it streams ``ceil(nsum / BS)`` blocks from
    row 0, then the blocks from ``summary_rows`` (whole blocks) on.

    ``napp_ref`` (with ``appos_ref``; a plain cache: none of the above): the
    append is a run of ``napp[r]`` positions, ``_append_kernel``'s ``run``.

    ``DB`` (with ``pipe_ref``; the plain stream: PACK == 1 and none of
    ``window``, ``summary_rows``, ``has_bias``): the DMA block, ``BS`` or a
    multiple of it (``stream_block``, of the call's shapes alone). ``DB ==
    BS`` is the LOOP form: a block is a softmax partition, fetched, scored
    and folded into ``m``, ``l``, ``acc`` in scratch, one an iteration.
    ``DB > BS`` is the BLOCK form, the same arithmetic delivered in larger
    pieces (the same dot products through the same sequence of partitions:
    the same output bit for bit): one K and one V descriptor a ``DB``
    positions (a row's last only up to its last partition that holds a
    valid position), the block's scores ``q . k^T`` in ONE pass ``[KH, GQ,
    DB]`` and masked in one, then its ``DB // BS`` partitions in a loop
    unrolled at trace time, the softmax state carried through them as values and stored once
    a block; a partition with no valid position is masked, not skipped
    (``NEG_INF`` is finite: ``p`` 0, ``corr`` 1, nothing moves). The fused
    append, of one position or of a run, merges on its aligned windows of
    APPEND_WINDOW_ROWS stored rows, and the pipeline's walk over the rows'
    lengths is done once a call outside (``pipe_ref``, as
    ``_latent_kernel``'s).
    """
    has_append = appos_ref is not None
    has_run = napp_ref is not None
    block = DB > BS
    NP = DB // BS                         # softmax partitions a DMA block
    r = pl.program_id(0)
    R = len_ref.shape[0]
    length = len_ref[r]
    SB = BS // PACK                       # packed rows per partition
    RB = DB // PACK                       # and per DMA block

    def nb_of(j):                         # blocks program j streams
        nb = (len_ref[j] + jnp.asarray(DB - 1, jnp.int32)) // DB
        if first_ref is not None:
            nb = jnp.maximum(nb - first_ref[j], 0)
        if nsum_ref is not None:          # less the blocks between the two
            nb = jnp.maximum(nb - summary_rows // BS, 0) + nsb_of(j)
        return nb

    def nsb_of(j):                        # summary blocks program j streams
        return (nsum_ref[j] + jnp.asarray(BS - 1, jnp.int32)) // BS

    def row_of(j):                        # the cache row program j streams
        return j if rows_ref is None else rows_ref[j]

    nb = nb_of(r)
    acc[:] = jnp.zeros_like(acc)
    m[:] = jnp.full_like(m, NEG_INF)
    l[:] = jnp.zeros_like(l)

    # stacked-cache mode: k/v are the whole [L, R, KH, S/PACK, LANE]
    # buffers and this call streams only layer ``layer_idx`` — the caller
    # never has to materialize a per-layer slice in HBM
    if layer_idx is not None:
        k_hbm = k_hbm.at[layer_idx]
        v_hbm = v_hbm.at[layer_idx]
    ring_blocks = k_hbm.shape[-2] // SB

    def src(j, i):                        # cache block of program j's i-th
        if nsum_ref is not None:
            return jnp.where(i < nsb_of(j), i,
                             i - nsb_of(j) + summary_rows // BS)
        if first_ref is None:
            return i
        return (first_ref[j] + i) % ring_blocks

    # Cross-program DMA pipeline: the R grid programs run sequentially on
    # one core, so each program's FIRST block fetch is started by its
    # predecessor (the last live program before it) and each program's
    # last iteration hands off to the next live program. Slot parity runs
    # over the GLOBAL block sequence g (sum of predecessors' block counts
    # + local index), so producer and consumer agree on the buffer slot.
    # Without this, every program eats its first fetch's full HBM latency
    # serially — measured ~1/3 of the whole kernel time at decode shapes
    # (nb == 1-2, where in-program double buffering never engages).
    def _pipe_scan(j, carry):
        # single O(R) pass computing all three pipeline coordinates
        # (ADVICE r3: three separate fori_loops re-evaluated nb_of(j)
        # per loop — O(R^2) scalar-unit work per grid program)
        g0, prev_live, r_next = carry
        nbj = nb_of(j)
        g0 = g0 + jnp.where(j < r, nbj, 0)
        prev_live = prev_live | ((j < r) & (nbj > 0))
        r_next = jnp.where((j > r) & (nbj > 0) & (r_next == R), j, r_next)
        return g0, prev_live, r_next

    if block:                             # walked once a call, outside
        g0, r_next = pipe_ref[0, r], pipe_ref[1, r]
        prev_live = g0 > 0
    else:
        g0, prev_live, r_next = jax.lax.fori_loop(
            0, R, _pipe_scan,
            (jnp.int32(0), jnp.asarray(False), jnp.int32(R)))

    def live(j, i, ahead=0):
        """The block form's one more argument of a block's copies: the
        partitions of block ``i + ahead`` of program ``j``'s row that hold
        a valid position (a row's last block is not fetched past them)."""
        if not block:
            return ()
        return (jnp.minimum(
            (len_ref[j] - (i + ahead) * DB + (BS - 1)) // BS, NP),)

    def dmas(row, slot, i, part=None):
        # ``part`` (first, count): those partitions of the block alone
        if part is None:
            def rows():
                return pl.ds(i * RB, RB)

            def to(buf):
                return buf.at[slot]
        else:
            at = pl.multiple_of(part[0] * SB, SB)

            def rows():
                return pl.ds(i * RB + at, part[1] * SB)

            def to(buf):
                return buf.at[slot, :, pl.ds(at, part[1] * SB)]
        yield pltpu.make_async_copy(
            k_hbm.at[row, :, rows()], to(kbuf), sem.at[slot, 0])
        yield pltpu.make_async_copy(
            v_hbm.at[row, :, rows()], to(vbuf), sem.at[slot, 1])
        if has_bias:
            if PACK == 1:
                b_src = bias_hbm.at[row, :, pl.ds(i * BS, BS)]
            else:       # de-interleaved [R, PACK, Q, S/PACK] (see caller)
                b_src = bias_hbm.at[row, :, :, pl.ds(i * SB, SB)]
            yield pltpu.make_async_copy(b_src, bbuf.at[slot],
                                        sem.at[slot, 2])

    def each_dma(do, row, slot, i, n=None):
        """``do`` every copy of a block; with ``n`` (``live``) those of its
        first ``n`` partitions alone: a whole block in one descriptor a
        cache, a row's last block a partition a descriptor (a descriptor's
        size is static, and several of a partition in flight stream as fast
        as one of a block: PERF.md section 6, PR 51)."""
        def all_of(part=None):
            for d in dmas(row, slot, i, part):
                do(d)

        def one(p, _):
            all_of((p, 1))

        if ABLATE == "arith":
            return
        if n is None:
            return all_of()
        jax.lax.cond(n == NP, all_of,
                     lambda: jax.lax.fori_loop(0, n, one, None))

    def start_dmas(row, slot, i, *n):
        each_dma(lambda d: d.start(), row, slot, i, *n)

    def wait_dmas(row, slot, i, *n):
        each_dma(lambda d: d.wait(), row, slot, i, *n)

    if block:
        @pl.when(r == 0)
        def _():
            # a dead partition of a row's last block is multiplied by p =
            # 0: what no copy of this call has written must be finite
            kbuf[:] = jnp.zeros_like(kbuf)
            vbuf[:] = jnp.zeros_like(vbuf)

    @pl.when((nb > 0) & jnp.logical_not(prev_live))
    def _():                              # first live program self-starts
        start_dmas(row_of(r), g0 % 2, src(r, 0), *live(r, 0))

    GQ = q_ref.shape[-2]
    qp = qp_ref[r]                                  # [GQ] absolute positions
    if has_append:
        p_app = appos_ref[r]
    if has_append and not has_run and not block:
        bp = p_app // BS                  # block holding the new position
        p_row = pr = p_app // PACK        # its global packed row
        if first_ref is not None:         # as the stream counts and stores
            bp = bp - first_ref[r]
            pr = pr % k_hbm.shape[-2]
        if nsum_ref is not None:
            bp = bp - summary_rows // BS + nsb_of(r)

    def app_row():                        # the new row within its block
        if first_ref is None and nsum_ref is None:
            return pr - bp * SB
        return pr % SB

    # the append merged a window at a time: a run's, and in the block form
    # a single position's too (a run of one, in one window; the loop form
    # selects it over the whole block)
    by_window = has_run or (block and has_append)
    if by_window:
        W = APPEND_WINDOW_ROWS
        # 0: the row sits out
        n_app = napp_ref[r] if has_run else (p_app >= 0).astype(jnp.int32)
        p_end = p_app + n_app
        w0 = (p_app // W) * W             # the run's first window

    def run_windows(i, slot):
        """The windows of this row's run that lie in stream block ``i``
        (a window never straddles a block: whole blocks of whole windows):
        (is it here, its first row in the cache and in the block, its
        write-backs). The block form takes a window only in a partition the
        loop form would stream (below ``length``): any other is not
        fetched."""
        for t in range(2 if has_run else 1):
            wa = pl.multiple_of(w0 + t * W, W)
            off = pl.multiple_of(wa - i * DB, W)
            here = (n_app > 0) & (wa < p_end) & (wa // DB == i)
            if block:
                here = here & (wa // BS < (length + (BS - 1)) // BS)
            yield (here, wa, off, [
                pltpu.make_async_copy(
                    buf.at[slot, :, pl.ds(off, W)],
                    hbm.at[r, :, pl.ds(wa, W)], asem.at[t, c])
                for c, (buf, hbm) in enumerate(((kbuf, k_hbm),
                                                (vbuf, v_hbm)))])

    def masked(s, h, i, slot):
        """Block ``i``'s raw scores ``s [KH, GQ, RB]`` scaled, biased and
        with ``NEG_INF`` wherever a query does not see the key."""
        s = s * qk_scale
        if block:
            b_abs = i * NP
        else:
            b_abs = i if first_ref is None else first_ref[r] + i
        if nsum_ref is not None:
            b_abs = src(r, i)
        s_ids = (b_abs * BS + h
                 + PACK * jax.lax.broadcasted_iota(jnp.int32, (GQ, RB),
                                                   1))
        if has_alibi:
            dist = (qp[:, None] - s_ids).astype(jnp.float32)
            s = s - slopes_ref[:, :][:, :, None] * dist[None]
        if has_bias:
            b = bbuf[slot] if PACK == 1 else bbuf[slot, h]  # [Q, SB]
            s = s + jnp.tile(b, (G, 1))[None]   # row g*Q+q <- b[q]
        if causal:
            visible = s_ids <= qp[:, None]
        else:
            visible = jnp.ones((GQ, RB), dtype=bool)
        visible = visible & (s_ids < length)
        if window is not None:
            visible = visible & (s_ids > qp[:, None] - window)
        if nsum_ref is not None:
            visible = visible & ((s_ids < nsum_ref[r])
                                 | (s_ids >= summary_rows))
        return jnp.where(visible[None], s, NEG_INF)

    def fold(s, v, h, m, l, acc):
        """Fold one partition into the softmax state: its masked scores
        ``s [KH, GQ, SB]`` and its values ``v [KH, SB, D]``."""
        m_new = jnp.maximum(m[:], jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m[:] - m_new)
        p = jnp.exp(s - m_new)                  # [KH, GQ, SB] f32
        l[:] = l[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        if PACK == 1:
            v_h = v
        else:
            # other half's lanes zeroed so the contraction only picks
            # up this half's values (their halves' accumulator lanes
            # are summed outside the kernel)
            lane = jax.lax.broadcasted_iota(
                jnp.int32, v.shape, v.ndim - 1)
            v_h = jnp.where(lane // D == h, v, jnp.zeros_like(v))
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v_h,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)  # [KH, GQ, D|LANE]
        acc[:] = acc[:] * corr + pv
        m[:] = m_new

    def body(i, _):
        slot = (g0 + i) % 2
        nxt_slot = (g0 + i + 1) % 2

        @pl.when(i + 1 < nb)
        def _():
            start_dmas(row_of(r), nxt_slot, src(r, i + 1), *live(r, i, 1))

        @pl.when((i + 1 == nb) & (r_next < R))
        def _():                          # hand off to the next live row
            start_dmas(row_of(r_next), nxt_slot, src(r_next, 0),
                       *live(r_next, 0))

        wait_dmas(row_of(r), slot, src(r, i), *live(r, i))
        if by_window:
            # merge the part of the run that this block holds into the
            # streamed block, a window at a time, and write the window back
            for here, wa, off, copies in run_windows(i, slot):
                @pl.when(here)
                def _():
                    pos = wa + jax.lax.broadcasted_iota(jnp.int32, (W, D), 0)

                    def put(a, cur):      # the run's a-th position
                        return tuple(
                            jnp.where((pos == p_app + a)[None],
                                      new_ref[0, a][:, None, :], c)
                            for new_ref, c in zip((knew_ref, vnew_ref), cur))

                    at = (slot, slice(None), pl.ds(off, W), slice(None))
                    if has_run:
                        kbuf[at], vbuf[at] = jax.lax.fori_loop(
                            0, n_app, put, (kbuf[at], vbuf[at]))
                    else:
                        kbuf[at], vbuf[at] = put(0, (kbuf[at], vbuf[at]))
                    for d in copies:
                        d.start()
        elif has_append:
            @pl.when(i == bp)
            def _():
                # merge the new K/V row into the streamed block in VMEM
                # (bitwise-identical to appending before the stream), and
                # write back the aligned 8-packed-row window it lives in
                KH = kbuf.shape[1]
                pm_row = app_row()        # packed row within the block
                hm = p_app - p_row * PACK  # lane half within the row
                sub = jax.lax.broadcasted_iota(
                    jnp.int32, (KH, SB, LANE if PACK > 1 else D), 1)
                lane = jax.lax.broadcasted_iota(
                    jnp.int32, (KH, SB, LANE if PACK > 1 else D), 2)
                sel = (sub == pm_row) & (lane // D == hm)
                kbuf[slot] = jnp.where(sel, knew_ref[0, 0][:, None, :],
                                       kbuf[slot])
                vbuf[slot] = jnp.where(sel, vnew_ref[0, 0][:, None, :],
                                       vbuf[slot])
                wo = (pm_row // SUBLANE) * SUBLANE
                pb_abs = (pr // SUBLANE) * SUBLANE
                wk = pltpu.make_async_copy(
                    kbuf.at[slot, :, pl.ds(wo, SUBLANE)],
                    k_hbm.at[r, :, pl.ds(pb_abs, SUBLANE)], asem.at[0])
                wv = pltpu.make_async_copy(
                    vbuf.at[slot, :, pl.ds(wo, SUBLANE)],
                    v_hbm.at[r, :, pl.ds(pb_abs, SUBLANE)], asem.at[1])
                wk.start()
                wv.start()
        k = kbuf[slot]                    # [KH, RB, D or LANE]
        v = vbuf[slot]
        # the block form carries the state through its partitions as values
        state = [_Held(x) for x in (m, l, acc)] if block else (m, l, acc)
        for h in range(PACK if ABLATE != "stream" else 0):   # position halves
            qt_h = q_ref[0] if PACK == 1 else q_ref[0, h]
            # scores[kh, gq, s] = q[kh, gq, :] . k[kh, s, :] — for packed
            # halves q is zero outside lanes [h*D, (h+1)*D), so the full
            # 128-lane contraction IS the half-dot
            s = jax.lax.dot_general(
                qt_h.astype(k.dtype), k,
                dimension_numbers=(((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)     # [KH, GQ, RB]
            s = masked(s, h, i, slot)
            if block:
                for j in range(NP):
                    at = slice(j * BS, (j + 1) * BS)
                    fold(s[:, :, at], v[:, at], h, *state)
            else:
                fold(s, v, h, *state)
        if block:
            m[:], l[:], acc[:] = (x.v for x in state)
        if by_window:
            for here, _, _, copies in run_windows(i, slot):
                @pl.when(here)
                def _():                  # as the one-row form below
                    for d in copies:
                        d.wait()
        elif has_append:
            @pl.when(i == bp)
            def _():
                # the write-back must land before this program ends (the
                # buffer slot is reused two global blocks later, and the
                # next layer's kernel reads the region through the alias)
                pm_row = app_row()
                wo = (pm_row // SUBLANE) * SUBLANE
                pb_abs = (pr // SUBLANE) * SUBLANE
                pltpu.make_async_copy(
                    kbuf.at[slot, :, pl.ds(wo, SUBLANE)],
                    k_hbm.at[r, :, pl.ds(pb_abs, SUBLANE)],
                    asem.at[0]).wait()
                pltpu.make_async_copy(
                    vbuf.at[slot, :, pl.ds(wo, SUBLANE)],
                    v_hbm.at[r, :, pl.ds(pb_abs, SUBLANE)],
                    asem.at[1]).wait()
        return 0

    jax.lax.fori_loop(0, nb, body, 0)
    o_ref[:] = (acc[:] / jnp.maximum(l[:], 1e-30))[None].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "qk_scale", "interpret", "out_dtype",
                     "layer_idx", "window", "summary_rows"))
def flash_attend(q, k_cache, v_cache, lengths, qpos, bias=None,
                 alibi=None, append_kv=None, rows=None, summaries=None,
                 plane=None, *,
                 causal=True, qk_scale=None, out_dtype=None, layer_idx=None,
                 interpret=False, window=None, summary_rows=None):
    """Batched KV-cache attention.

    q        [R, Q, H, D]   new-token queries (rotary already applied)
    k/v      [R, KH, S/PACK, PACK*D]  full cache AS STORED (new tokens
                            already appended): [R, KH, S, D] where D fills
                            the lanes, the packed [R, KH, S/2, 128] at
                            D=64 (position p in row p // 2, lanes
                            [(p % 2) * 64, +64): ops/kv_layout.py). Or the
                            whole stacked [L, R, KH, ...] buffer with
                            ``layer_idx`` selecting the layer to stream
                            (static: a Python int), or with ``plane``, the
                            same index as an OPERAND (a traced int32 scalar:
                            a loop region's pass picks the plane, and one
                            trace serves every pass).
                            Taken and returned as is: no cache operand is
                            reshaped here
    lengths  [R] int32      valid cache extent per request (0 => skip slot)
    qpos     [R, Q] int32   absolute position of each query token
    bias     [R, Q, S] f32  optional additive mask (tree mask; NEG_INF=hidden)
    alibi    [H] f32        optional ALiBi slopes
    append_kv  (k_new [R, A, KH, D], v_new same, appos [R] int32[, n [R]
                            int32])  decode fused append: write the first
                            n[r] (all A without ``n``; clipped to the
                            cache's end) of each row's new K/V at cache
                            positions appos[r] on (appos < 0 = skip row) IN
                            PLACE before attending: positions of the run
                            past n[r] are written nowhere, and ``lengths``
                            counts those written. The caches are aliased
                            in/out and the call returns (out, k_cache,
                            v_cache); callers must treat the passed caches
                            as consumed (donated). A == 1 on every layout;
                            a run, 1 < A <= APPEND_RUN_MOST, on a plain
                            position-major cache (no packed D=64 rows, no
                            ``window``, no ``summaries``)
    rows     [R] int32      optional row map: batch row r attends cache row
                            rows[r] (the cache may then hold any number of
                            rows, and a row may be named twice); without it
                            the kernel is the unmapped one, argument for
                            argument. Not with append_kv.
    window   int (static)   a windowed layer: query i sees keys
                            ``i - window < j <= i``. The cache is then a
                            RING of ``S`` rows, whole blocks, position p in
                            row ``p % S`` (ops/kv_layout.py; D fills the
                            lanes), that holds each row's last positions up
                            to ``lengths`` (absolute, as ``qpos``): at least
                            from the window of its first query on. Only the
                            blocks a row's window touches are streamed, and
                            the device operation is ``flash_attend_window``.
                            Without it the kernels are what they were.
    summary_rows  int (static), with ``summaries`` [R] int32: a chunked
                            layer, whose cache is two extents in one stream
                            (ops/kv_layout.py; D fills the lanes): rows
                            ``[0, summary_rows)`` and the rows behind them.
                            Row r sees the first ``summaries[r]`` rows of
                            the first and, of the second, the rows ``<=
                            qpos`` and ``< lengths``: both, and append_kv's
                            ``appos``, are then ROWS OF THE STREAM
                            (kv_layout.chunked_view). Only the blocks that
                            hold a visible row are streamed, and the device
                            operation is ``flash_attend_chunked``. Without
                            it the kernels are what they were.
    returns  [R, Q, H*D], or (out, k_cache, v_cache) with append_kv
    """
    assert rows is None or append_kv is None, "no fused append by row map"
    assert plane is None or (layer_idx is None and k_cache.ndim == 5), (
        "a plane is an operand or static, and indexes a stacked cache")
    R, Q, H, D = q.shape
    PACK = _pack_factor(D)
    assert PACK > 0 and k_cache.shape[-1] == PACK * D, (
        f"a D={D} cache is stored [.., S/{PACK}, {PACK * D}]; got "
        f"{k_cache.shape}")
    KH, S = k_cache.shape[-3], k_cache.shape[-2] * PACK
    G = H // KH
    GQ = G * Q
    BS = _pick_block_s(S, D)
    assert BS > 0, f"S={S}/D={D} not tileable by a supported block size"
    SB = BS // PACK
    DL = D if PACK == 1 else LANE         # kernel-side lane width
    # the DMA block: the partition, or on the plain stream a multiple of it
    # where a partition's descriptor is small (``_stream_attend``'s block form)
    DB = BS
    if (PACK == 1 and window is None and summary_rows is None
            and bias is None):
        DB = stream_block(KH, D, k_cache.dtype.itemsize, GQ, S)
    block = DB > BS
    RB = DB // PACK                       # stored rows a DMA block
    if qk_scale is None:
        qk_scale = 1.0 / math.sqrt(D)
    out_dtype = out_dtype or q.dtype

    # [R, Q, H, D] -> [R, KH, G*Q, D], row index g*Q + q
    qt = q.reshape(R, Q, KH, G, D).transpose(0, 2, 3, 1, 4).reshape(
        R, KH, GQ, D)
    if PACK > 1:
        # PACK zero-padded variants: variant h holds q in lanes
        # [h*D, (h+1)*D) and zeros elsewhere, so the kernel's full-lane
        # contraction against a packed cache row IS the half-dot
        qt = jnp.stack(
            [jnp.pad(qt, ((0, 0),) * 3 + ((h * D, LANE - (h + 1) * D),))
             for h in range(PACK)], axis=1)         # [R, PACK, KH, GQ, LANE]
    qp_gq = jnp.tile(qpos.astype(jnp.int32), (1, G))            # [R, GQ]
    has_bias = bias is not None
    has_alibi = alibi is not None
    if has_alibi:
        slopes_gq = jnp.repeat(
            alibi.astype(jnp.float32).reshape(KH, G), Q, axis=1)  # [KH, GQ]
    else:
        slopes_gq = jnp.zeros((KH, GQ), jnp.float32)
    if has_bias and PACK > 1:
        # de-interleave so half h's [Q, SB] block is a contiguous slice
        bias = bias.reshape(R, Q, S // PACK, PACK).transpose(0, 3, 1, 2)
    if not has_bias:
        # Minimal placeholder to fill the operand slot; the kernel only
        # DMAs bias when has_bias=True, so no [R, 1, S] HBM buffer needed.
        bias = jnp.zeros((1, 1, 1, 1) if PACK > 1 else (1, 1, 1),
                         jnp.float32)

    if summary_rows is not None:
        assert PACK == 1 and not has_bias and window is None, (
            "a chunked layer is position-major, causal")
        assert summary_rows % BS == 0, (summary_rows, BS)
        lengths = jnp.minimum(lengths.astype(jnp.int32), S)
        first = [jnp.minimum(summaries.astype(jnp.int32), summary_rows)]
        call_name = {"name": "flash_attend_chunked"}
    elif window is None:
        # Clamp: an out-of-range length would DMA past the cache end.
        lengths = jnp.minimum(lengths.astype(jnp.int32), S)
        first, call_name = [], {}
    else:
        assert PACK == 1 and not has_bias, "a ring is position-major, causal"
        # the first block that the window of a row's first query touches
        first = [jnp.maximum(qpos[:, 0].astype(jnp.int32) - (window - 1), 0)
                 // BS]
        call_name = {"name": "flash_attend_window"}

    cache_dt = k_cache.dtype
    kv_bytes = 2 * 2 * RB * KH * DL * cache_dt.itemsize
    compiler_params = pltpu.CompilerParams(
        vmem_limit_bytes=int(min(
            128 * 1024 * 1024,
            8 * (KH * GQ * (DL + 2) * 4 + PACK * KH * GQ * DL * 2
                 + kv_bytes + 2 * PACK * Q * SB * 4
                 + block * KH * GQ * DB * 4) + 1024 * 1024)),
    )
    cost_estimate = pl.CostEstimate(
        flops=4 * R * GQ * KH * D * S,
        bytes_accessed=2 * R * S * KH * D * cache_dt.itemsize,
        transcendentals=R * KH * GQ * S,
    )
    q_block = ((1, KH, GQ, D) if PACK == 1
               else (1, PACK, KH, GQ, LANE))
    qkv_in_specs = [
        pl.BlockSpec(q_block, lambda r, *_: (r,) + (0,) * (len(q_block) - 1),
                     memory_space=pltpu.VMEM),                   # qt
        pl.BlockSpec(memory_space=pltpu.VMEM),                   # qp [R, GQ]
        pl.BlockSpec((KH, GQ), lambda r, *_: (0, 0),
                     memory_space=pltpu.VMEM),                   # slopes
    ]
    tail_in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),                       # bias (HBM)
        pl.BlockSpec(memory_space=pl.ANY),                       # k cache
        pl.BlockSpec(memory_space=pl.ANY),                       # v cache
    ]
    o_spec = pl.BlockSpec((1, KH, GQ, DL), lambda r, *_: (r, 0, 0, 0),
                          memory_space=pltpu.VMEM)
    bias_buf_shape = (2, Q, BS) if PACK == 1 else (2, PACK, Q, SB)
    scratch = [
        pltpu.VMEM((KH, GQ, DL), jnp.float32),                   # acc
        pltpu.VMEM((KH, GQ, 1), jnp.float32),                    # m
        pltpu.VMEM((KH, GQ, 1), jnp.float32),                    # l
        pltpu.VMEM((2, KH, RB, DL), cache_dt),                   # k buf
        pltpu.VMEM((2, KH, RB, DL), cache_dt),                   # v buf
        pltpu.VMEM(bias_buf_shape, jnp.float32),                 # bias buf
        pltpu.SemaphoreType.DMA((2, 3)),
    ]

    def post(out):
        if PACK > 1:
            # sum the per-half accumulator lanes back to D
            out = out.reshape(R, KH, GQ, PACK, D).sum(axis=3,
                                                      dtype=jnp.float32)
            out = out.astype(out_dtype)
        # [R, KH, G*Q, D] -> [R, Q, H*D] with h = kh*G + g
        return out.reshape(R, KH, G, Q, D).transpose(0, 3, 1, 2, 4).reshape(
            R, Q, H * D)

    # scalars every program reads behind the lengths: in the block form its
    # place in the DMA pipeline, walked once a call here
    pipe = [_latent_pipe(lengths, DB)] if block else []
    static = dict(BS=BS, causal=causal, has_bias=has_bias,
                  has_alibi=has_alibi, qk_scale=float(qk_scale), G=G, Q=Q,
                  layer_idx=layer_idx, PACK=PACK, D=D, DB=DB)
    # a traced plane goes in front of every other prefetched scalar
    lead = ([] if plane is None
            else [jnp.asarray(plane, jnp.int32).reshape(1)])

    def planed(kern):
        return kern if plane is None else functools.partial(_plane_kernel,
                                                            kern=kern)

    if append_kv is None:
        prefetch = lead + [lengths.astype(jnp.int32)] + pipe + first
        if rows is not None:
            prefetch.append(rows.astype(jnp.int32))
        if summary_rows is not None:
            kern = functools.partial(
                _window_kernel, mode=None if rows is None else "rows",
                operand="nsum_ref", summary_rows=summary_rows, **static)
        elif window is None:
            kern = functools.partial(
                _kernel if rows is None else _rows_kernel, **static)
        else:
            kern = functools.partial(
                _window_kernel, mode=None if rows is None else "rows",
                window=window, **static)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(R,),
            in_specs=qkv_in_specs + tail_in_specs,
            out_specs=o_spec, scratch_shapes=scratch)
        out = pl.pallas_call(
            planed(kern), grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                (R, KH, GQ, DL),
                jnp.float32 if PACK > 1 else out_dtype),
            compiler_params=compiler_params, cost_estimate=cost_estimate,
            interpret=interpret, **call_name,
        )(*prefetch, qt, qp_gq, slopes_gq,
          bias.astype(jnp.float32), k_cache, v_cache)
        return post(out)

    # fused decode append: write (k_new, v_new) at appos[r] in place, then
    # attend; the caches alias through to the outputs (donation-safe)
    k_new, v_new, appos, *count = append_kv
    A = k_new.shape[1]
    run = []                              # a run's one more prefetched operand
    if A > 1:
        assert (PACK == 1 and window is None and summary_rows is None
                and A <= APPEND_RUN_MOST), (
            f"a run of {A} positions is fused into a plain position-major "
            f"cache's stream, up to {APPEND_RUN_MOST}: a packed cache, a "
            "ring and a chunked stream append one position a row here, and "
            "their runs in ops/inc_attention")
        appos = appos.astype(jnp.int32)
        n = count[0].astype(jnp.int32) if count else A
        run = [jnp.where(appos >= 0,
                         jnp.clip(n, 0, jnp.minimum(A, S - appos)), 0)]
    if PACK > 1:
        # the kernel's merge select places the row in lane half p % PACK;
        # tiling the D lanes PACK times gives it the value in every half
        k_new = jnp.concatenate([k_new] * PACK, axis=-1)
        v_new = jnp.concatenate([v_new] * PACK, axis=-1)
    if summary_rows is not None:
        kern = functools.partial(_window_kernel, mode="append",
                                 operand="nsum_ref",
                                 summary_rows=summary_rows, **static)
    elif window is None:
        kern = functools.partial(_append_kernel, **static,
                                 **({"run": True} if run else {}))
    else:
        kern = functools.partial(_window_kernel, mode="append",
                                 window=window, **static)
    knew_spec = pl.BlockSpec((1, A, KH, DL), lambda r, *_: (r, 0, 0, 0),
                             memory_space=pltpu.VMEM)
    n_prefetch = len(lead) + 2 + len(pipe) + len(first) + len(run)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch, grid=(R,),
        in_specs=qkv_in_specs + [knew_spec, knew_spec] + tail_in_specs,
        out_specs=(o_spec, pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)),
        # a write-back's semaphores: (k, v), a window of a run each (the
        # block form's single position is a run of one)
        scratch_shapes=scratch + [
            pltpu.SemaphoreType.DMA((2, 2) if run or block else (2,))])
    out, k_out, v_out = pl.pallas_call(
        planed(kern), grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(
            (R, KH, GQ, DL), jnp.float32 if PACK > 1 else out_dtype),
                   jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                   jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)),
        # k/v cache operands -> outputs
        input_output_aliases={n_prefetch + 6: 1, n_prefetch + 7: 2},
        compiler_params=compiler_params, cost_estimate=cost_estimate,
        interpret=interpret, **call_name,
    )(*lead, lengths.astype(jnp.int32), *pipe, *first,
      appos.astype(jnp.int32), *run, qt,
      qp_gq, slopes_gq, k_new.astype(cache_dt), v_new.astype(cache_dt),
      bias.astype(jnp.float32), k_cache, v_cache)
    return post(out), k_out, v_out


def reference_attend(q, k_cache, v_cache, lengths, qpos, bias=None,
                     alibi=None, *, causal=True, qk_scale=None,
                     out_dtype=None, window=None, key_pos=None):
    """Pure-jnp oracle with identical semantics (used on CPU and in tests).
    ``window``: query i sees keys ``i - window < j <= i``. ``key_pos``
    [R, S]: the position each cache row holds, negative for none (a ring:
    ops/kv_layout.ring_positions); without it row s holds position s."""
    R, Q, H, D = q.shape
    KH, S = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    if qk_scale is None:
        qk_scale = 1.0 / math.sqrt(D)
    out_dtype = out_dtype or q.dtype
    qg = q.reshape(R, Q, KH, G, D)
    kc = k_cache.astype(q.dtype)
    vc = v_cache.astype(q.dtype)
    s = jnp.einsum("rqkgd,rksd->rkgqs", qg, kc,
                   preferred_element_type=jnp.float32) * qk_scale
    s_ids = jnp.arange(S)[None, None, :]                       # [1,1,S]
    if key_pos is not None:
        s_ids = key_pos[:, None, :]                            # [R,1,S]
    if alibi is not None:
        dist = (qpos[:, :, None] - s_ids).astype(jnp.float32)  # [R,Q,S]
        slopes = alibi.astype(jnp.float32).reshape(KH, G)
        s = s - slopes[None, :, :, None, None] * dist[:, None, None, :, :]
    if bias is not None:
        b = bias.astype(jnp.float32)                           # [R,Q,S]
        s = s + b[:, None, None, :, :]
    visible = jnp.ones((R, Q, S), bool) if not causal else \
        (s_ids <= qpos[:, :, None])
    visible = visible & (s_ids < lengths[:, None, None])
    if key_pos is not None:
        visible = visible & (s_ids >= 0)
    if window is not None:
        visible = visible & (s_ids > qpos[:, :, None] - window)
    s = jnp.where(visible[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("rkgqs,rksd->rqkgd", p.astype(q.dtype), vc)
    return out.reshape(R, Q, H * D).astype(out_dtype)


# ----------------------------------------------------------------------
# Chunked (EVA) attention: an exact window and one learned summary pair a
# chunk of the positions before it, two extents in one stream
# (ops/kv_layout.py ``chunked_*``). The attend is ``flash_attend`` with
# ``summary_rows``; what follows is the summariser.
# ----------------------------------------------------------------------
def supports_chunked(rows: int, summary_rows: int, D: int) -> bool:
    """True iff ``flash_attend`` can tile a chunked layer's stream of
    ``rows`` rows whose window extent starts at row ``summary_rows``: D
    fills the lanes and both extents are whole blocks."""
    bs = _pick_block_s(rows, D)
    return _pack_factor(D) == 1 and bs > 0 and summary_rows % bs == 0


def pool_chunk(k, v, mu, phi):
    """One summary pair a chunk, the learned softmax pools of
    EVA: ``kbar = sum_m softmax_m(mu . k_m) k_m`` and ``vbar = sum_m
    softmax_m(phi . k_m) v_m`` over the chunk's positions ``m`` (the rotated
    keys score both), in float32, no temperature. ``k``, ``v`` ``[.., c,
    D]`` with ``mu``, ``phi`` broadcastable to ``[.., D]`` -> two ``[..,
    D]`` in float32."""
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)

    def pool(x, w):
        s = jnp.sum(kf * w.astype(jnp.float32)[..., None, :], axis=-1,
                    keepdims=True)                          # [.., c, 1]
        e = jnp.exp(s - jnp.max(s, axis=-2, keepdims=True))
        return jnp.sum(e / jnp.sum(e, axis=-2, keepdims=True) * x, axis=-2)

    return pool(kf, mu), pool(vf, phi)


# rows of the summary extent's write-back window: a whole packed tile of a
# 16-bit cache, as LATENT_APPEND_ROWS below
SUMMARY_WINDOW_ROWS = 16


def _summarise_kernel(src_ref, dst_ref, mu_ref, phi_ref, _k_in, _v_in,
                      k_hbm, v_hbm, kbuf, vbuf, kwin, vwin, sem,
                      *, chunk: int, layer_idx):
    """Program ``r``: where ``src[r] >= 0`` pool the ``chunk`` rows from
    ``src[r]`` of cache row ``r`` (keys and values) into one pair and write
    it to row ``dst[r]`` IN PLACE: the aligned window of rows around
    ``dst[r]`` is read, merged and written back (a DMA slice is whole
    sublane tiles)."""
    r = pl.program_id(0)
    src, dst = src_ref[r], dst_ref[r]
    if layer_idx is not None:
        k_hbm, v_hbm = k_hbm.at[layer_idx], v_hbm.at[layer_idx]
    A = kwin.shape[1]

    @pl.when(src >= 0)
    def _():
        win = (dst // A) * A

        def copies(back: bool):
            for i, (hbm, buf, at, n) in enumerate((
                    (k_hbm, kbuf, src, chunk), (v_hbm, vbuf, src, chunk),
                    (k_hbm, kwin, win, A), (v_hbm, vwin, win, A))):
                if back and i < 2:
                    continue
                ref = hbm.at[r, :, pl.ds(pl.multiple_of(at, n), n)]
                yield (pltpu.make_async_copy(buf, ref, sem.at[i]) if back
                       else pltpu.make_async_copy(ref, buf, sem.at[i]))

        for d in copies(False):
            d.start()
        for d in copies(False):
            d.wait()
        kbar, vbar = pool_chunk(kbuf[:], vbuf[:], mu_ref[:], phi_ref[:])
        at = jax.lax.broadcasted_iota(jnp.int32, kwin.shape, 1) == dst - win
        kwin[:] = jnp.where(at, kbar[:, None, :].astype(kwin.dtype), kwin[:])
        vwin[:] = jnp.where(at, vbar[:, None, :].astype(vwin.dtype), vwin[:])
        for d in copies(True):
            d.start()
        for d in copies(True):
            d.wait()


@functools.partial(jax.jit,
                   static_argnames=("chunk", "layer_idx", "interpret"))
def summarise_chunks(k_cache, v_cache, mu, phi, src, dst, *, chunk: int,
                     layer_idx=None, interpret=False):
    """The decode step's summariser of a chunked layer, in place.

    k/v      [R, KH, rows, D]  the layer's stream as stored (or the stack
                            [L, R, KH, rows, D] with ``layer_idx``), aliased
                            in/out: the passed caches are consumed
    mu, phi  [KH, D] f32    the heads' two learned vectors
    src      [R] int32      the stored row of the first position of the
                            chunk that row ``r`` just completed (a multiple
                            of ``chunk``), or < 0: nothing to do for the row
    dst      [R] int32      the summary row that chunk's pair goes to
    returns  (k_cache, v_cache). The device operation is ``eva_summarise``.
    """
    R = src.shape[0]
    KH, rows, D = k_cache.shape[-3:]
    A = min(SUMMARY_WINDOW_ROWS, rows)
    dt = k_cache.dtype
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    vec = pl.BlockSpec((KH, D), lambda r, *_: (0, 0),
                       memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_summarise_kernel, chunk=chunk,
                          layer_idx=layer_idx),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(R,),
            in_specs=[vec, vec, hbm, hbm], out_specs=(hbm, hbm),
            scratch_shapes=[pltpu.VMEM((KH, chunk, D), dt),
                            pltpu.VMEM((KH, chunk, D), dt),
                            pltpu.VMEM((KH, A, D), dt),
                            pltpu.VMEM((KH, A, D), dt),
                            pltpu.SemaphoreType.DMA((4,))]),
        out_shape=(jax.ShapeDtypeStruct(k_cache.shape, dt),
                   jax.ShapeDtypeStruct(v_cache.shape, dt)),
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret, name="eva_summarise",
    )(src.astype(jnp.int32), dst.astype(jnp.int32),
      mu.astype(jnp.float32), phi.astype(jnp.float32), k_cache, v_cache)


# ----------------------------------------------------------------------
# Latent (MLA) attention: one stored stream a layer, read as keys and as
# values (ops/kv_layout.py ``latent_*``, ops/latent_attention.py).
# ----------------------------------------------------------------------
def _pick_latent_blocks(S: int):
    """(DMA block, softmax block) of the latent kernel, in positions, or
    (0, 0) where ``S`` cannot be tiled. An entry is a few hundred bytes, so
    a DMA takes as many positions as divide ``S`` (up to 1024: 0.8 MB at
    384 lanes, 1.3 at 640) to pay for its fixed cost; the online softmax
    advances in sub-blocks of that block, in BOTH forms of the kernel
    (``latent_form``): the block form scores a DMA block in one pass and
    still walks it a softmax block at a time. As ``_pick_block_s`` states,
    the softmax partition depends on ``S`` alone, never on the query width."""
    if S % LANE:
        return 0, 0
    sb = 256 if S % 256 == 0 else 128
    for db in (1024, 512, 256, 128):
        if S % db == 0 and db % sb == 0:
            return db, sb
    return 0, 0


def supports_latent(S: int, width: int, rank: int) -> bool:
    """True iff ``flash_attend_latent`` can tile a latent cache of ``S``
    positions of ``width`` stored values, the first ``rank`` the latent."""
    return (width % LANE == 0 and rank % LANE == 0
            and _pick_latent_blocks(S)[0] > 0)


# rows of a write-back window: a whole packed tile of a 16-bit cache
LATENT_APPEND_ROWS = 16
# the most a call asks of the chip's 128 MiB of VMEM
LATENT_VMEM_LIMIT = 100 * 1024 * 1024
# the most a DMA block's float32 scores may take for the block form: 256
# query rows at 1024 positions. On a v5e the block form beats the
# partition loop by a third at 16-128 rows, by 7-24% at 256 and by -2..+10%
# at 512 (PERF.md section 6, PR 49)
LATENT_BLOCK_SCORES_LIMIT = 1024 * 1024


def _scores_fit(GQ: int, DB: int) -> bool:
    """True iff a DMA block's scores ``[GQ, DB]`` in float32 fit beside the
    stream."""
    return GQ * DB * 4 <= LATENT_BLOCK_SCORES_LIMIT


def latent_form(GQ: int, S: int) -> str:
    """Which form of ``_latent_kernel`` a call over ``GQ`` query rows (heads
    x tokens) of a cache of ``S`` positions takes, of the shapes alone:
    "block" where a DMA block's scores fit beside the stream (a decode
    step's 32 or 64 rows: 128 or 256 KB), else "partition" (a prefill
    segment's 4096 rows: 16 MB)."""
    return "block" if _scores_fit(GQ, _pick_latent_blocks(S)[0]) else (
        "partition")


def _latent_vmem_bytes(GQ: int, W: int, rank: int, DB: int, SB: int,
                       q_itemsize: int, c_itemsize: int) -> int:
    """VMEM a call over ``GQ`` query rows (heads x tokens) asks for: the
    query and output blocks, the softmax state, the stream's two buffers
    (and as much again for the block as a value), a partition's scores and
    their kin and, in the block form, a DMA block's scores and their masked
    copy."""
    block = _scores_fit(GQ, DB)
    return (2 * GQ * (W + rank) * q_itemsize    # q, o double-buffered
            + GQ * (rank + 2 * LANE) * 4        # acc, m, l
            + 4 * DB * W * c_itemsize           # the stream's buffers
            + 6 * GQ * SB * 4                   # scores and their kin
            + 2 * GQ * DB * 4 * block           # a block's scores
            + 2 * GQ * rank * 4 + 4 * 1024 * 1024)


def latent_head_groups(H: int, Q: int, W: int, rank: int, S: int,
                       q_itemsize: int = 2, c_itemsize: int = 2) -> int:
    """In how many groups of heads ``flash_attend_latent`` takes ``Q``
    query tokens of ``H`` heads a row, so that a group's rows fit VMEM: the
    fewest that divide ``H``; 1 where all fit (32 heads of 128 tokens of 384
    lanes); 0 where not even one head does. Of the shapes alone."""
    DB, SB = _pick_latent_blocks(S)
    for g in range(1, H + 1):
        if H % g == 0 and _latent_vmem_bytes(
                H // g * Q, W, rank, DB, SB, q_itemsize,
                c_itemsize) <= LATENT_VMEM_LIMIT:
            return g
    return 0


def _latent_kernel(len_ref, pipe_ref, *refs, mode, block: bool,
                   stacked: bool, DB: int, SB: int, rank: int,
                   qk_scale: float):
    """Program ``r`` attends queries ``q [GQ, W]`` (all heads of all the
    row's query tokens) over cache row ``r``. Every fetched block ``[DB, W]``
    (a row's last one only up to its last partition that holds a valid
    position: a decode step is bound by the HBM, and bytes not fetched here
    are bytes XLA's prefetch of the next matrices gets) serves the scores
    (all ``W`` lanes) and the values (its first ``rank`` lanes), and the
    online softmax advances through it ``SB`` positions at a time, in one
    of two forms (``latent_form``, of the shapes alone; the same dot
    products through the same sequence of partitions, so the same output
    bit for bit):

    - ``block`` (a decode step's 32 or 64 query rows): the block's scores
      ``q . c^T`` in ONE pass ``[GQ, DB]``, then its ``DB // SB`` partitions
      in a loop unrolled at trace time. The cache block is the matmuls'
      stationary operand, so its arithmetic is a chain of fills and drains
      of the MXU whatever ``GQ`` is: unrolled, a partition's ``exp`` and
      value matmul no longer wait for the one before to drain, and a block
      costs what its bytes cost. A partition with no valid position is
      masked, not skipped (``NEG_INF`` is finite: ``p`` 0, ``corr`` 1,
      nothing moves).
    - partition loop (a prefill segment's 4096 rows, whose block scores
      would take 16 MB): scores, softmax and values a partition at a time
      under a loop with a dynamic trip count, only the partitions that hold
      a valid position.

    ``mode`` "rows": the compact prefill batch's row map, program ``r``
    reads cache row ``rows[r]``. ``mode`` "append" (decode): the row's new
    entry lands at position ``appos[r]`` IN PLACE (the cache is aliased
    in/out), merged into its ``LATENT_APPEND_ROWS``-row window of the
    streamed block in VMEM, and that window written back, as
    ``_append_kernel`` does for a k/v pair. The cross-program DMA pipeline
    is ``_stream_attend``'s, its walk over the rows' lengths done once a
    call outside (``pipe_ref``: the blocks of the rows before ``r``, the
    next live row)."""
    rows_ref = appos_ref = new_ref = asem = layer_ref = None
    if stacked:
        layer_ref, *refs = refs
    if mode == "append":
        (appos_ref, q_ref, qp_ref, new_ref, _, o_ref, c_hbm, acc, m, l, cbuf,
         sem, asem) = refs
    else:
        if mode == "rows":
            rows_ref, *refs = refs
        q_ref, qp_ref, c_hbm, o_ref, acc, m, l, cbuf, sem = refs
    r = pl.program_id(0)
    R = len_ref.shape[0]
    length = len_ref[r]

    def row_of(j):
        return j if rows_ref is None else rows_ref[j]

    nb = (length + jnp.asarray(DB - 1, jnp.int32)) // DB
    acc[:] = jnp.zeros_like(acc)
    m[:] = jnp.full_like(m, NEG_INF)
    l[:] = jnp.zeros_like(l)
    if stacked:
        c_hbm = c_hbm.at[layer_ref[0]]
    g0, r_next = pipe_ref[0, r], pipe_ref[1, r]

    def live(j, i):
        """The partitions of block ``i`` of program ``j``'s row that hold a
        valid position."""
        return jnp.minimum((len_ref[j] - i * DB + (SB - 1)) // SB, DB // SB)

    def stream(j, slot, i, start: bool):
        """Start, or wait for, the copy of block ``i`` of program ``j``'s
        row: its live partitions alone, in one descriptor (a row's last
        block is not fetched past its end; a descriptor's size is static,
        so one branch of a switch a count)."""
        def copy(k):
            dma = pltpu.make_async_copy(
                c_hbm.at[row_of(j), 0, pl.ds(i * DB, k * SB)],
                cbuf.at[slot, pl.ds(0, k * SB)], sem.at[slot])
            dma.start() if start else dma.wait()

        jax.lax.switch(live(j, i) - 1, [functools.partial(copy, k)
                                        for k in range(1, DB // SB + 1)])

    if block:
        @pl.when(r == 0)
        def _():
            # the block form multiplies a block's dead partitions by p = 0:
            # what no copy of this call has written must be finite
            cbuf[:] = jnp.zeros_like(cbuf)

    @pl.when((nb > 0) & (g0 == 0))          # no live row before this one
    def _():
        stream(r, g0 % 2, 0, True)

    GQ = q_ref.shape[-2]
    qp = qp_ref[r]                                  # [GQ] absolute positions
    q = q_ref[0]                                    # [GQ, W]
    if mode == "append":
        p_app = appos_ref[r]
        bp = p_app // DB                  # the block holding the new position
        A = LATENT_APPEND_ROWS
        # the aligned window around the new position, within the block
        at = pl.multiple_of(((p_app - bp * DB) // A) * A, A)

        def writeback(slot):
            return pltpu.make_async_copy(
                cbuf.at[slot, pl.ds(at, A)],
                c_hbm.at[r, 0, pl.ds(pl.multiple_of(bp * DB + at, A), A)],
                asem.at[0])

    def scores(c):
        return jax.lax.dot_general(
            q.astype(c.dtype), c,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # [GQ, positions]

    def partition(s, vals, first):
        """Advance the softmax by ``SB`` positions from ``first``: their raw
        scores ``s [GQ, SB]`` and values ``vals [SB, rank]``."""
        s = s * qk_scale
        s_ids = first + jax.lax.broadcasted_iota(jnp.int32, (GQ, SB), 1)
        visible = (s_ids <= qp[:, None]) & (s_ids < length)
        s = jnp.where(visible, s, NEG_INF)
        m_new = jnp.maximum(m[:], jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m[:] - m_new)
        p = jnp.exp(s - m_new)
        l[:] = l[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(vals.dtype), vals,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # [GQ, rank]
        acc[:] = acc[:] * corr + pv
        m[:] = m_new

    def body(i, _):
        slot = (g0 + i) % 2
        nxt_slot = (g0 + i + 1) % 2

        @pl.when(i + 1 < nb)
        def _():
            stream(r, nxt_slot, i + 1, True)

        @pl.when((i + 1 == nb) & (r_next < R))
        def _():
            stream(r_next, nxt_slot, 0, True)

        stream(r, slot, i, False)
        if mode == "append":
            @pl.when(i == bp)
            def _():
                # merge the new entry into its window of the streamed block
                # (attention then sees the cache as after the append) and
                # write the window back; rows before it re-land as they
                # were, rows after it hold nothing valid yet
                ids = jax.lax.broadcasted_iota(jnp.int32, (A, cbuf.shape[-1]),
                                               0)
                cbuf[slot, pl.ds(at, A), :] = jnp.where(
                    ids == p_app - bp * DB - at, new_ref[0],
                    cbuf[slot, pl.ds(at, A), :])
                writeback(slot).start()
        if block:
            c = cbuf[slot]                              # [DB, W]
            s = scores(c)
            for j in range(0, DB, SB):
                partition(s[:, j:j + SB], c[j:j + SB, :rank], i * DB + j)
        else:
            def sub(j, _):
                off = pl.multiple_of(j * SB, SB)
                c = cbuf[slot, pl.ds(off, SB), :]       # [SB, W]
                partition(scores(c), c[:, :rank], i * DB + off)
                return 0

            jax.lax.fori_loop(0, live(r, i), sub, 0)
        if mode == "append":
            @pl.when(i == bp)
            def _():
                # before the slot is reused and the next layer reads the
                # region through the alias
                writeback(slot).wait()
        return 0

    jax.lax.fori_loop(0, nb, body, 0)
    o_ref[:] = (acc[:] / jnp.maximum(l[:], 1e-30))[None].astype(o_ref.dtype)


def _latent_pipe(lengths, DB: int):
    """What a program of ``_latent_kernel`` must know of the other rows to
    take its place in the DMA pipeline, ``[2, R]`` int32: the DMA blocks of
    the rows before it (the parity of its first slot; 0: it starts the
    pipeline itself) and the next row after it with a block to fetch (``R``:
    none). One small fusion a call where every program walked all ``R``
    lengths on its scalar unit."""
    R = lengths.shape[0]
    nb = (lengths + (DB - 1)) // DB
    j = jnp.arange(R, dtype=jnp.int32)
    before = j[None, :] < j[:, None]                    # [r, j]: j < r
    g0 = jnp.sum(jnp.where(before, nb[None, :], 0), axis=1)
    r_next = jnp.min(jnp.where(before.T & (nb[None, :] > 0), j[None, :], R),
                     axis=1)
    return jnp.stack([g0, r_next]).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("rank", "qk_scale", "interpret", "out_dtype"))
def flash_attend_latent(q, cache, lengths, qpos, rows=None, append=None, *,
                        rank: int, qk_scale: float, out_dtype=None,
                        layer_idx=None, interpret=False):
    """Causal attention of absorbed queries over a latent cache.

    q        [R, Q, H, W]   a token's queries carried into the stored
                            space, laid out as an entry is
                            (ops/kv_layout.latent_query; any scale by
                            position already applied)
    cache    [R, 1, S, W]   the latent cache as stored; or the stack
                            [L, R, 1, S, W] with ``layer_idx`` (an operand:
                            the calls of a program's layers share one
                            trace). One stream:
                            each block is fetched once, scored against
                            whole and its first ``rank`` lanes taken as the
                            values
    lengths  [R] int32      valid cache extent per row (0 => skip), the new
                            entries included
    qpos     [R, Q] int32   absolute position of each query token
    rows     [R] int32      optional row map (the compact prefill batch,
                            whose entries are appended before the call)
    append   (entry [R, 1, 1, W], appos [R] int32)   decode fused append:
                            each row's new entry is written at position
                            appos[r] (< 0: skip) IN PLACE before attending;
                            the cache is aliased in/out, the call returns
                            (out, cache) and the passed cache is consumed.
                            Not with ``rows``.
    returns  [R, Q, H, rank]: softmax-weighted latents, still to be carried
                            out through the value half of the up-projection
    The device operation is ``flash_attend_latent``.
    """
    assert rows is None or append is None, "no fused append by row map"
    R, Q, H, W = q.shape
    S = cache.shape[-2]
    assert cache.shape[-1] == W and cache.shape[-3] == 1, (q.shape,
                                                           cache.shape)
    assert (layer_idx is None) == (cache.ndim == 4), cache.shape
    DB, SB = _pick_latent_blocks(S)
    assert supports_latent(S, W, rank), (S, W, rank)
    groups = latent_head_groups(H, Q, W, rank, S, q.dtype.itemsize,
                                cache.dtype.itemsize)
    assert groups > 0, ("no head of these queries fits VMEM", q.shape)
    if groups > 1:
        # the query rows of ALL heads do not fit VMEM beside the stream:
        # the heads in groups, each a call of its own over the same cache
        # (64 heads of a 128-token segment of 640 lanes: two calls of 32)
        assert append is None, "a decode step's rows fit in one call"
        step = H // groups
        return jnp.concatenate(
            [flash_attend_latent(
                q[:, :, h:h + step], cache, lengths, qpos, rows, rank=rank,
                qk_scale=qk_scale, out_dtype=out_dtype, layer_idx=layer_idx,
                interpret=interpret) for h in range(0, H, step)], axis=2)
    GQ = H * Q
    out_dtype = out_dtype or q.dtype
    # [R, Q, H, W] -> [R, H*Q, W], row index h*Q + q
    qt = q.transpose(0, 2, 1, 3).reshape(R, GQ, W)
    qp_gq = jnp.tile(qpos.astype(jnp.int32), (1, H))            # [R, GQ]
    lengths = jnp.minimum(lengths.astype(jnp.int32), S)
    mode = "rows" if rows is not None else (
        "append" if append is not None else None)
    isz = cache.dtype.itemsize
    compiler_params = pltpu.CompilerParams(
        vmem_limit_bytes=int(min(
            LATENT_VMEM_LIMIT,
            _latent_vmem_bytes(GQ, W, rank, DB, SB, q.dtype.itemsize, isz))))
    cost_estimate = pl.CostEstimate(
        flops=2 * R * GQ * S * (W + rank),
        bytes_accessed=R * S * W * isz,
        transcendentals=R * GQ * S)
    kern = functools.partial(
        _latent_kernel, mode=mode, block=_scores_fit(GQ, DB),
        stacked=layer_idx is not None, DB=DB, SB=SB, rank=rank,
        qk_scale=float(qk_scale))
    # scalars every program reads: the lengths, its place in the DMA
    # pipeline and, on a stack, the layer (an operand, not a constant: the
    # layers' calls of one program are then one trace of this function)
    prefetch = [lengths, _latent_pipe(lengths, DB)] + (
        [] if layer_idx is None
        else [jnp.asarray(layer_idx, jnp.int32).reshape(1)])
    q_specs = [
        pl.BlockSpec((1, GQ, W), lambda r, *_: (r, 0, 0),
                     memory_space=pltpu.VMEM),                   # qt
        pl.BlockSpec(memory_space=pltpu.VMEM),                   # qp [R, GQ]
    ]
    hbm = pl.BlockSpec(memory_space=pl.ANY)                      # the cache
    o_spec = pl.BlockSpec((1, GQ, rank), lambda r, *_: (r, 0, 0),
                          memory_space=pltpu.VMEM)
    scratch = [
        pltpu.VMEM((GQ, rank), jnp.float32),                     # acc
        pltpu.VMEM((GQ, 1), jnp.float32),                        # m
        pltpu.VMEM((GQ, 1), jnp.float32),                        # l
        pltpu.VMEM((2, DB, W), cache.dtype),                     # stream
        pltpu.SemaphoreType.DMA((2,)),
    ]
    o_shape = jax.ShapeDtypeStruct((R, GQ, rank), out_dtype)

    def post(out):
        return out.reshape(R, H, Q, rank).transpose(0, 2, 1, 3)

    if append is None:
        if rows is not None:
            prefetch.append(rows.astype(jnp.int32))
        out = pl.pallas_call(
            kern, grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetch), grid=(R,),
                in_specs=q_specs + [hbm], out_specs=o_spec,
                scratch_shapes=scratch),
            out_shape=o_shape, compiler_params=compiler_params,
            cost_estimate=cost_estimate, interpret=interpret,
            name="flash_attend_latent",
        )(*prefetch, qt, qp_gq, cache)
        return post(out)
    entry, appos = append
    out, cache = pl.pallas_call(
        kern, grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch) + 1, grid=(R,),
            in_specs=q_specs + [
                pl.BlockSpec((1, 1, W), lambda r, *_: (r, 0, 0),
                             memory_space=pltpu.VMEM), hbm],
            out_specs=(o_spec, hbm),
            scratch_shapes=scratch + [pltpu.SemaphoreType.DMA((1,))]),
        out_shape=(o_shape, jax.ShapeDtypeStruct(cache.shape, cache.dtype)),
        # the cache operand -> output
        input_output_aliases={len(prefetch) + 4: 1},
        compiler_params=compiler_params, cost_estimate=cost_estimate,
        interpret=interpret, name="flash_attend_latent",
    )(*prefetch, appos.astype(jnp.int32), qt, qp_gq,
      entry.reshape(R, 1, W).astype(cache.dtype), cache)
    return post(out), cache


def reference_attend_latent(q, cache, lengths, qpos, *, rank: int,
                            qk_scale: float, out_dtype=None):
    """Pure-jnp oracle of ``flash_attend_latent``: q [R, Q, H, W] over one
    layer's cache [R, 1, S, W] (rows already gathered) -> [R, Q, H, rank]."""
    S = cache.shape[-2]
    c = cache[:, 0].astype(q.dtype)                             # [R, S, W]
    s = jnp.einsum("rqhw,rsw->rhqs", q, c,
                   preferred_element_type=jnp.float32) * qk_scale
    s_ids = jnp.arange(S)[None, None, :]
    visible = (s_ids <= qpos[:, :, None]) & (s_ids < lengths[:, None, None])
    s = jnp.where(visible[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("rhqs,rsc->rqhc", p.astype(q.dtype), c[..., :rank])
    return out.astype(out_dtype or q.dtype)
