"""Who owns the KV cache's storage layout.

A cache is stored the way its attention kernel reads it. A head dim that
fills the 128 lanes (``pack == 1``) is stored position-major,
``[.., S, D]``. A head dim of 64 that takes the packed flash path
(kernels/attention.py ``_pack_factor``) is stored PACKED,
``[.., S // pack, pack * D]``: position ``p`` lives in row ``p // pack``,
lanes ``[(p % pack) * D, (p % pack + 1) * D)``, so the kernel's DMA slices
are lane-full and no step reshapes the cache (a 64-wide minor dim is tiled
half-empty on the chip, so the former ``[.., S, 64]`` -> ``[.., S/2, 128]``
view was a relayout of the whole cache, in every layer of every step).

The layout is chosen once, where the cache is allocated (``stored_pack``),
from what the code observes there: the head dim, the cache length, whether
the Pallas path is in use. Everybody else reads it back from the cache's
shape against the op's ``max_seq_length`` (``pack_of``) and addresses
positions through the functions here; this is the only place that knows
the position <-> (row, lanes) arithmetic. With ``pack == 1`` each of them
is the identity.

A WINDOWED layer (``sliding_window`` in the op's attrs: a query sees only
the last ``window`` positions) keeps a RING instead of every position:
``ring_rows`` rows, position-major, position ``p`` in row ``p % rows``. The
ring holds the window plus the most one step appends to a slot, so every
query of a step still finds its whole window after all of the step's
tokens have been written; it is whole kernel blocks, so that block ``b`` of
the positions is block ``b % (rows / block)`` of the ring and the kernel
streams it unchanged. A ring as long as the op's ``max_seq_length`` never
wraps and the same arithmetic is the identity. The functions below
(``ring_*``) are the only place that knows it.

A LATENT layer (multi-head latent attention, ops/latent_attention.py) stores
ONE entry a position, shared by all its query heads: the normed latent
(``rank`` values, which the kernel reads as keys AND as values) and beside
it the rotated key part (``rope`` values, keys only). The entry is one
stream, ``[R, 1, S, width]``, position-major: ``[latent | rope | zeros]``
with ``width`` the entry rounded up to whole 128-lane tiles where the
kernel serves it (the chip tiles a minor dim to 128 lanes anyway, so a
320-value entry costs its 384 lanes whether they are asked for or not, and
the kernel's DMA slices need them lane-full), the exact entry elsewhere.
The leading ``1`` is the one shared "head", so the appends of the other
kinds (a ``[R, Q, KH, D]`` run into ``[R, KH, S, D]``) write it unchanged.
``latent_*`` below are the only place that knows it.

A CHUNKED layer (``eva_window`` and ``chunk_size`` in the op's attrs: a
query sees its own window of ``eva_window`` positions exactly and, of every
window before, one learned summary pair for each ``chunk_size`` positions)
keeps TWO EXTENTS in one stream a (row, head), position-major: with ``S``
the slot's positions, rows ``[0, S / chunk)`` are the summaries (chunk ``n``
in row ``n``) and rows ``[S / chunk, S / chunk + window)`` the window,
position ``p`` in row ``S / chunk + p % window``. The window TUMBLES: the
position that crosses a multiple of ``window`` overwrites the window's first
row, and nothing is moved, because the summaries of the window just left are
already in their rows (a chunk's is written when its last position is) and
only the visibility changes: a query in window ``w`` sees the summary rows
below ``w * window / chunk`` and the window rows up to its own. The kernel
is handed that as stored rows (``chunked_view``): how many summary rows a
row sees, and its queries' and its length's rows in the window extent, which
it masks causally as it masks positions. ``chunked_*`` below are the only
place that knows it.
"""

from __future__ import annotations

import jax.numpy as jnp

from flexflow_tpu.kernels.attention import (LANE, _pack_factor, round_up,
                                            supports_seq_len)


def stored_pack(Dp: int, max_seq: int, want_pallas: bool) -> int:
    """Positions per stored cache row for a cache head dim ``Dp`` (as
    ``ops/inc_attention.padded_head_dim`` gives it): the kernel's pack
    factor where the packed flash path will serve this cache, else 1."""
    if want_pallas and supports_seq_len(max_seq, Dp):
        return _pack_factor(Dp)
    return 1


def cache_shape(R: int, KH: int, max_seq: int, Dp: int, pack: int):
    return (R, KH, max_seq // pack, pack * Dp)


def pack_of(cache, max_seq: int) -> int:
    """The pack factor of a stored cache ``[.., rows, lanes]`` whose op
    holds ``max_seq`` positions; its per-position width is then
    ``cache.shape[-1] // pack``."""
    pack, rem = divmod(max_seq, cache.shape[-2])
    assert rem == 0 and pack >= 1, (cache.shape, max_seq)
    return pack


def row_of(pos, pack: int):
    """The stored row of position ``pos``; its lanes start at
    ``(pos - pack * row) * D``."""
    return pos if pack == 1 else pos // pack


def to_rows(x, pack: int):
    """Position-major ``[.., C, D]`` (``C`` a multiple of ``pack``, from a
    position that starts a row) -> stored rows ``[.., C // pack, pack * D]``.
    For a run or a segment; nothing cache-sized goes through here on the
    kernel path."""
    if pack == 1:
        return x
    C, D = x.shape[-2:]
    return x.reshape(x.shape[:-2] + (C // pack, pack * D))


def to_positions(x, pack: int):
    """Stored rows ``[.., W, pack * D]`` -> position-major
    ``[.., W * pack, D]`` (numpy or jax). A whole layer comes through here
    only on the jnp attention paths."""
    if pack == 1:
        return x
    W, lanes = x.shape[-2:]
    return x.reshape(x.shape[:-2] + (W * pack, lanes // pack))


def read_positions(cache, a: int, b: int, pack: int, at=()):
    """Positions ``[a, b)`` (static) of a stored cache as ``[.., b-a, D]``;
    ``at`` indexes leading dims (a layer, a slot) in the same one slice, so
    no more than the rows asked for is ever copied."""
    r0 = a // pack
    rows = cache[tuple(at) + (Ellipsis, slice(r0, -(-b // pack)),
                              slice(None))]
    return to_positions(rows, pack)[..., a - r0 * pack:b - r0 * pack, :]


def gather_positions(cache, pos, pack: int):
    """``cache [*lead, R, KH, rows, lanes]`` at per-row positions
    ``pos [R, C]`` -> ``[*lead, R, KH, C, D]``."""
    lead = (None,) * (cache.ndim - 4)
    at = lead + (slice(None), None, slice(None), None)
    got = jnp.take_along_axis(cache, row_of(pos, pack)[at], axis=-2)
    if pack == 1:
        return got
    D = cache.shape[-1] // pack
    half = (pos % pack)[at]
    out = got[..., :D]
    for h in range(1, pack):
        out = jnp.where(half == h, got[..., h * D:(h + 1) * D], out)
    return out


def window(start, Q: int, rows: int, pack: int):
    """The stored rows through which a run of ``Q`` positions from
    ``start`` (traced, any parity, possibly past the cache's end) is
    written: ``(row, W, off)`` with rows ``[row, row + W)`` inside the
    cache and token ``t`` of the run in the window's column ``off + t``
    (of ``W * pack``). A run that would pass the end is shifted right in a
    window that ends with the cache, as far as it takes (``off`` then
    exceeds ``pack - 1``); its tokens past the end fall outside."""
    W = min((Q + 2 * pack - 2) // pack, rows)
    row = jnp.clip(row_of(start, pack), 0, rows - W)
    return row, W, start - (row if pack == 1 else pack * row)


def merge_window(cur, run, keep, off, pack: int):
    """A window of stored rows ``cur [.., KH, W, pack * D]`` with the run
    ``run [KH, Q, D]`` laid in from column ``off`` wherever
    ``keep [W * pack]`` (by window column) is set."""
    C = cur.shape[-2] * pack
    Q, D = run.shape[-2:]
    if C > Q:
        run = jnp.pad(run, ((0, 0), (0, C - Q), (0, 0)))
    run = to_rows(jnp.roll(run, off, axis=1), pack)
    if pack == 1:
        keep = keep[None, :, None]
    else:
        keep = to_rows(jnp.broadcast_to(keep[:, None], (C, D)), pack)[None]
    return jnp.where(keep, run[(None,) * (cur.ndim - 3)], cur)


def ring_rows(window: int, step_tokens: int, max_seq: int) -> int:
    """Rows of a windowed layer's cache: the window plus the most tokens
    one step appends to a slot (``step_tokens``: the batch's token
    budget), in whole blocks of the kernel's smallest tile; never more than
    the positions a slot can hold."""
    return min(max_seq, round_up(window + step_tokens, LANE))


def ring_row(pos, rows: int):
    """The ring row of position ``pos`` (any integer array or scalar)."""
    return pos % rows


def ring_positions(lengths, rows: int):
    """``[R, rows]``: the position each ring row holds once a slot's first
    ``lengths[r]`` positions are written (the largest below the length
    that lands in the row), negative where the row was never written."""
    last = lengths[:, None] - 1
    return last - (last - jnp.arange(rows)[None, :]) % rows


def read_ring(cache, a: int, b: int, at=()):
    """Positions ``[a, b)`` (static, no more than the ring's rows, all
    still held) of a ring ``[.., rows, D]`` as ``[.., b-a, D]``; ``at``
    indexes leading dims, as in ``read_positions``."""
    rows = cache.shape[-2]
    assert 0 <= b - a <= rows, (a, b, rows)
    return cache[tuple(at)][..., ring_row(jnp.arange(a, b), rows), :]


def ring_pieces(start, Q: int, rows: int):
    """The two windows of ``Q`` ring rows through which a run of ``Q``
    positions from ``start`` [R] is written, as ``window`` gives one for a
    cache that does not wrap: ``[(row, off), (row, off)]`` with token ``t``
    of the run in column ``off + t`` of its window. The first holds the
    tokens up to the ring's end, the second, at row 0, those that wrap
    (none: every column's token is then past the run)."""
    assert Q <= rows, (Q, rows)
    r0 = ring_row(start, rows)
    a = jnp.minimum(r0, rows - Q)
    return [(a, r0 - a), (jnp.zeros_like(r0), r0 - rows)]


def latent_width(rank: int, rope: int, want_pallas: bool) -> int:
    """Stored values a position of a latent layer: the entry itself, in
    whole lane tiles where the latent kernel will read it."""
    return round_up(rank + rope, LANE) if want_pallas else rank + rope


def latent_cache_shape(R: int, max_seq: int, width: int):
    return (R, 1, max_seq, width)


def _as_entry(latent, rope, width: int):
    """``[.., rank]`` and ``[.., rope]`` side by side in ``width`` values."""
    parts = [latent, rope.astype(latent.dtype)]
    pad = width - latent.shape[-1] - rope.shape[-1]
    if pad:
        parts.append(jnp.zeros(latent.shape[:-1] + (pad,), latent.dtype))
    return jnp.concatenate(parts, axis=-1)


def latent_entry(latent, rope, width: int):
    """``latent [R, Q, rank]`` and ``rope [R, Q, rope]`` as the stored run
    ``[R, Q, 1, width]`` the appends take."""
    return _as_entry(latent, rope, width)[:, :, None, :]


def latent_query(q_latent, q_rope, width: int):
    """``q_latent [R, Q, H, rank]`` and ``q_rope [R, Q, H, rope]`` laid out
    as an entry is, ``[R, Q, H, width]``: one contraction over the stored
    lanes is then the whole score."""
    return _as_entry(q_latent, q_rope, width)


def read_latent(cache, a: int, b: int, rank: int, rope: int, at=()):
    """Positions ``[a, b)`` (static) of a latent cache ``[.., 1, S, width]``
    as ``(latent [.., b-a, rank], rope [.., b-a, rope])``; ``at`` indexes
    leading dims, as in ``read_positions``."""
    rows = cache[tuple(at) + (Ellipsis, 0, slice(a, b), slice(None))]
    return rows[..., :rank], rows[..., rank:rank + rope]


def chunked_summary_rows(max_seq: int, chunk: int) -> int:
    """Rows of a chunked layer's summary extent, one a chunk of the slot's
    ``max_seq`` positions; the window extent starts behind them."""
    assert max_seq % chunk == 0, (max_seq, chunk)
    return max_seq // chunk


def chunked_cache_shape(R: int, KH: int, max_seq: int, chunk: int,
                        window: int, Dp: int):
    return (R, KH, chunked_summary_rows(max_seq, chunk) + window, Dp)


def chunked_window_row(pos, ns: int, window: int):
    """The stored row of position ``pos`` (any integer array) in the window
    extent behind ``ns`` summary rows."""
    return ns + pos % window


def chunked_chunk_rows(pos, ns: int, window: int, chunk: int):
    """For position ``pos`` [R], the LAST of its chunk where the chunk is
    whole: ``(src, dst)``, the stored row of the chunk's first position in
    the window extent and the summary row the chunk's pair goes to."""
    return ns + pos % window // chunk * chunk, pos // chunk


def chunked_view(lengths, qpos, ns: int, window: int, chunk: int):
    """What a step's rows see, as stored rows: ``(summaries, lengths,
    qpos)`` for valid extents ``lengths`` [R] (0: a row that sits out) and
    query positions ``qpos`` [R, Q], all of one window a row. Row ``r`` sees
    summary rows ``[0, summaries[r])``, every chunk of the windows before
    its own, and of the window extent the rows ``<= qpos`` and ``<
    lengths``, both returned as rows of the stream."""
    w = qpos[:, 0] // window
    live = lengths > 0
    return (jnp.where(live, w * (window // chunk), 0).astype(jnp.int32),
            jnp.where(live, ns + lengths - w * window, 0).astype(jnp.int32),
            chunked_window_row(qpos, ns, window).astype(jnp.int32))


def chunked_key_rows(summaries, ns: int, rows: int):
    """``[R, rows]``: each stored row's own index where a row of the batch
    may see it by ``chunked_view``'s causal rule, negative where it may not
    (a summary row of the row's own window or a later one)."""
    at = jnp.arange(rows)[None, :]
    return jnp.where((at < summaries[:, None]) | (at >= ns), at, -1)


def read_chunked(cache, a: int, b: int, ns: int, window: int, at=()):
    """Positions ``[a, b)`` (static, of one window, still held) of a chunked
    layer's window extent as ``[.., b-a, D]``; ``at`` as in
    ``read_positions``."""
    assert a // window == (b - 1) // window, (a, b, window)
    r0 = ns + a % window
    return cache[tuple(at) + (Ellipsis, slice(r0, r0 + b - a), slice(None))]
