"""Recurrences that keep a state a row a head, as kernels. The two forms of
a gated delta rule with one decay a key channel (ops/kda_attention.py):
``kda_state_step``, the RECURRENT form (a decode step: one token a row), and
``kda_chunk``, the CHUNKED form (a prefill step: a row's tokens in chunks of
64); and the recurrent form of a state-space mixer (ops/ssd_mixer.py):
``ssd_state_step`` (its section below: a row's 64 heads one program and
one descriptor of 2.1 MB, the decay a scalar from SMEM, ``y = S C`` summed
along the lanes by merging the heads' tiles, a rotation a register, with
no register reduced alone). The two recurrent kernels share their
scaffolding, ``live_rows_first`` and ``StepMaps``, and ``_columns``; their
bodies share no arithmetic.

**The recurrent form.** A row's state is ``S [H, K, V]`` float32 (4.19 MB
at 64 heads of 128 x 128). A decode step reads ALL of it and writes ALL of
it back:

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

**The chunked form** (``kda_chunk``; its arithmetic is
``ops/kda_attention._chunk_parts`` and ``through_chunks``, which stay as the
jnp fallback and the tests' oracle). The grid is ``head blocks x rows x
chunks``, a program one chunk of 64 tokens of ``chunk_heads_per_block``
heads, everything in float32 and every product at ``HIGHEST`` precision:

1. the chunk's cumulative log decay ``G`` (a product with the triangle of
   ones); inside each 16-token sub-chunk the pairwise decays with the
   exponent's difference formed first, one earlier token against the eight
   later ones of a sublane tile a pass, reduced over the key channels along
   the lanes; between sub-chunks both factors relative to the LATER
   sub-chunk's start, as matrix products;
2. ``(I + Diag(beta) A) X = beta [v | k exp(G)]`` by forward substitution:
   the diagonal blocks' inverses a column at a time with a chunk's four
   blocks side by side along the lanes, then block by block;
3. the state's walk ``d = U - W S; o = Qd S + B d; S' = decay * S + Kend^T
   d`` with ``S`` in a VMEM scratch.

Nothing of 1-2 goes to HBM. The rows are taken a slot after another in
ascending ``start`` (``chunk_sources``, scalar prefetch), so a row that
continues the row before it finds that row's end in the scratch; a row at
position 0 zeroes it, any other loads the stored state (the stack's block,
aliased in and out as in the recurrent form). A slot's rows name ONE block
of the output stack, which the pipeline writes back when the walk leaves
the slot: each slot's LAST row's end. Idle rows come last, name the last
live program's blocks (nothing is fetched, no state written) and zero their
own rows of ``o``. A padding position has ``g = 0`` and ``beta = 0`` and
passes the state as it is.
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


def heads_per_block(H: int, most: int = HEADS_PER_BLOCK) -> int:
    hb = min(most, H)
    while H % hb:
        hb -= 1
    return hb


def live_rows_first(live):
    """The walk of a decode step's rows, as scalars: ``live [R]`` bool ->
    ``(rows [R] int32, nl)``: the ``nl`` live rows first, in order, and every
    place past them naming the LAST live row, so that a program there names
    the block the last live program named and nothing moves."""
    R = live.shape[0]
    nl = jnp.sum(live.astype(jnp.int32))
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    rows = jnp.where(jnp.arange(R) < nl, order, order[jnp.maximum(nl - 1, 0)])
    return rows, nl


class StepMaps:
    """The index maps of a recurrent kernel's grid ``(rows, head blocks)``
    over scalars ``(layer, rows, nl, ...)``: program ``(i, h)`` names row
    ``rows[i]`` and head block ``h``; past the last live row, the last live
    program's, so that nothing moves."""

    def __init__(self, nhb: int):
        self.nhb = nhb

    def at(self, i, h, lidx, rows, nl, *_):
        return rows[i], jnp.where(i < nl[0], h, self.nhb - 1)

    def state(self, i, h, lidx, *s):
        """Into the stack ``[L, R, H, ., .]``."""
        r, hh = self.at(i, h, lidx, *s)
        return lidx[0], r, hh, 0, 0

    def row(self, i, h, *s):
        """Into a row's vectors ``[R, H, w]``."""
        r, hh = self.at(i, h, *s)
        return r, hh, 0

    def head_scalar(self, i, h, *s):
        """Into a row's scalars a head, ``[R, nhb, hb, 1]``."""
        r, hh = self.at(i, h, *s)
        return r, hh, 0, 0

    def shared(self, i, h, *s):
        """Into a row's vectors that every head shares, ``[R, 1, w]``."""
        return self.at(i, h, *s)[0], 0, 0


def supports(H: int, K: int, V: int) -> bool:
    """Shapes Mosaic takes: whole 128-lane tiles, head blocks of whole
    8-sublane tiles (interpreted, any shape goes)."""
    return K % 128 == 0 and V % 128 == 0 and heads_per_block(H) % 8 == 0


def state_step_bytes(rows: float, H: int, K: int, V: int) -> float:
    """Bytes the kernel must move for ``rows`` live rows of one layer: the
    state in and out, and q, k, g, v, beta in and o out (float32)."""
    return rows * H * (2.0 * K * V + 3 * K + 2 * V + 1) * 4


def _identity(K: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (K, K), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (K, K), 1)).astype(
                jnp.bfloat16)


def _columns(eye, x):
    """``x [hb, K]`` float32 -> ``[K, hb]``, exactly: three bfloat16 pieces
    that add up to the float32 value (8 + 8 + 8 bits of mantissa), each
    through the identity: one value times 1 plus zeros in a float32
    accumulator, whatever precision the compiler would give a float32
    product."""
    f32 = jnp.float32
    out, rest = None, x
    for _ in range(3):
        piece = rest.astype(jnp.bfloat16)
        rest = rest - piece.astype(f32)
        part = jax.lax.dot_general(
            eye, piece, (((1,), (1,)), ((), ())),
            preferred_element_type=f32)
        out = part if out is None else out + part
    return out


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
        # [hb, K] -> [K, hb], exactly
        columns = functools.partial(_columns, _identity(K))
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
    rows, nl = live_rows_first(live)
    scalars = (jnp.asarray(layer_idx, jnp.int32).reshape(1), rows,
               nl.reshape(1), fresh.astype(jnp.int32))
    maps = StepMaps(nhb)
    state_map, row_map, beta_map = maps.state, maps.row, maps.head_scalar

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


# ----------------------------------------------------------------------
# the chunked form: a prefill step's rows, chunks of 64 tokens in order
# ----------------------------------------------------------------------

CHUNK_NAME = "kda_chunk"
# tokens a chunk and a sub-chunk (ops/kda_attention.CHUNK, SUB)
CHUNK, SUB = 64, 16
# heads a program: their chains of small products are what the compiler
# interleaves; q, k, g, v, o blocks of 64 x 8 x 128 float32 = 256 KB each
CHUNK_HEADS_PER_BLOCK = 8
VMEM_LIMIT = 96 * 1024 * 1024
HIGHEST = jax.lax.Precision.HIGHEST
# where a row's state comes from (scalar prefetch)
FROM_ZEROS, FROM_STEP, FROM_STORE = 0, 1, 2
# traces of the chunked form by (form, rows, tokens a row): "kernel"
# (``kda_chunk``) or "jnp" (ops/kda_attention.chunked); read by no metric
chunk_form_counts: dict = {}
# tools/time_kda_chunk.py's own hook: a part of ``kda_chunk`` left out so
# that a timing keeps the rest (the results are then wrong): "pairs" (the
# pairwise decays inside the sub-chunks), "solve" (the forward
# substitution), "walk" (the state's products), "passes" (every product in
# one bfloat16 pass)
ABLATE = None


def record_chunk_form(form: str, rows: int, tokens: int):
    key = (form, rows, tokens)
    chunk_form_counts[key] = chunk_form_counts.get(key, 0) + 1


def chunk_summary() -> str:
    """The chunked form's traces in one line: "chunked form: kernel, 4 rows
    of 128: 2 traces"."""
    return "chunked form: " + ("; ".join(
        f"{form}, {rows} rows of {tokens}: {n} traces"
        for (form, rows, tokens), n in sorted(chunk_form_counts.items()))
        or "0 traces")


def chunk_heads_per_block(H: int) -> int:
    hb = min(CHUNK_HEADS_PER_BLOCK, H)
    while H % hb:
        hb -= 1
    return hb


def supports_chunk(H: int, K: int, V: int) -> bool:
    """Shapes Mosaic takes for ``kda_chunk``: a head's lanes whole tiles."""
    return K % 128 == 0 and V % 128 == 0


def chunk_size(T: int) -> int:
    """Tokens a chunk for rows of ``T``: ``CHUNK``, or a short row's whole
    sub-chunks (``ops/kda_attention.chunk_parts``' rule)."""
    return CHUNK if T >= CHUNK else -(-T // SUB) * SUB


def _mm(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(
        a, b, dims, precision=None if ABLATE == "passes" else HIGHEST,
        preferred_element_type=jnp.float32)


def _per_head(f, *xs):
    """``f`` on each head's 2-D slice of ``xs [hb, ., .]``, stacked."""
    return jnp.stack([f(*(x[h] for x in xs)) for h in range(xs[0].shape[0])])


def _chunk_math(q, k, g, v, beta, S, sub: int):
    """One chunk of ``hb`` heads through the state: ``q, k, g [hb, C, K]``,
    ``v [hb, C, V]``, ``beta [hb, C, 1]``, ``S [hb, K, V]``, float32. Returns
    ``(o [hb, C, V], S')``. The arithmetic of ``ops/kda_attention``'s
    ``_chunk_parts`` and ``through_chunks``, a chunk at a time."""
    f32 = jnp.float32
    hb, C, K = k.shape
    nb = C // sub
    n = hb * nb
    iota = jax.lax.broadcasted_iota
    # 1. the cumulative log decay: a product with the lower triangle of ones
    tri = (iota(jnp.int32, (C, C), 0) >= iota(jnp.int32, (C, C), 1)).astype(
        f32)
    G = _per_head(lambda x: _mm(tri, x), g)                 # [hb, C, K] <= 0
    # inside a sub-chunk: the exponent's difference first, a column of the
    # block (one earlier token s against the block's later tokens) a pass.
    # A column lands in lane s of EVERY group of ``sub`` lanes (the masks
    # below keep a block's own group), and, for the solve, in all the
    # lanes of its block's group: ``Lrep[s][h, t, (b, .)] = A_b[t, s]``
    Gd, gd, kd, qd = (x.reshape(n, sub, K) for x in (G, g, k, q))
    group = iota(jnp.int32, (1, 1, C), 2) // sub
    at = iota(jnp.int32, (1, 8, C), 2) % sub
    A_rows, B_rows, Lrep = [], [], [[] for _ in range(sub)]
    for lo in range(0, sub, 8):         # eight rows t of every block
        Gt, kt, qt = (x[:, lo:lo + 8] for x in (Gd, kd, qd))
        later = iota(jnp.int32, (1, 8, 1), 1) + lo          # t
        A = jnp.zeros((n, 8, C), f32)
        B = jnp.zeros((n, 8, C), f32)
        for s in range(lo + 8 if ABLATE != "pairs" else 0):   # s <= t
            rel = kd[:, s:s + 1] * jnp.exp(jnp.minimum(
                Gt - Gd[:, s:s + 1], 0))
            a = jnp.sum(kt * rel, axis=-1, keepdims=True)   # [n, 8, 1]
            b = jnp.sum(qt * rel, axis=-1, keepdims=True)
            A = jnp.where(at == s, a, A)
            B = jnp.where(at == s, b, B)
            if s < sub - 1:
                a = jnp.where(later > s, a, 0).reshape(hb, nb, 8, 1)
                rep = jnp.zeros((hb, 8, C), f32)
                for j in range(nb):
                    rep = jnp.where(group == j, a[:, j], rep)
                Lrep[s].append(rep)
        A_rows.append(A), B_rows.append(B)
        for s in range(lo + 8, sub - 1):    # rows t <= s: nothing
            Lrep[s].append(jnp.zeros((hb, 8, C), f32))
    row = iota(jnp.int32, (C, C), 0)
    col = iota(jnp.int32, (C, C), 1)
    same = (row // sub) == (col // sub)
    A = jnp.where(same & (row > col), jnp.concatenate(
        A_rows, axis=1).reshape(hb, C, C), 0)
    B = jnp.where(same & (row >= col), jnp.concatenate(
        B_rows, axis=1).reshape(hb, C, C), 0)
    # between sub-chunks: both factors relative to the LATER one's start
    if nb > 1:
        Gs = Gd[:, :1] - gd[:, :1]                          # [n, 1, K]
        late = jnp.exp(Gd - Gs)                             # <= 1
        kq_late = jnp.concatenate(
            [(kd * late).reshape(hb, nb, sub, K),
             (qd * late).reshape(hb, nb, sub, K)], axis=2)  # [hb, nb, 2sub, K]
        Gs = Gs.reshape(hb, nb, 1, K)
        nt = (((1,), (1,)), ((), ()))
        Ao, Bo = [jnp.zeros((hb, sub, C), f32)], [jnp.zeros((hb, sub, C), f32)]
        for i in range(1, nb):
            early = k * jnp.exp(jnp.minimum(Gs[:, i] - G, 0))      # [hb, C, K]
            both = _per_head(lambda x, y: _mm(x, y, nt),
                             kq_late[:, i], early)          # [hb, 2 sub, C]
            before = iota(jnp.int32, (sub, C), 1) < i * sub
            Ao.append(jnp.where(before, both[:, :sub], 0))
            Bo.append(jnp.where(before, both[:, sub:], 0))
        A = A + jnp.concatenate(Ao, axis=1)
        B = B + jnp.concatenate(Bo, axis=1)
    # 2. (I + Diag(beta) A) X = beta [v | k exp(G)] by forward substitution:
    # the diagonal blocks' inverses a column at a time, a chunk's ``nb``
    # blocks side by side along the lanes (``T[h, t, (b, j)]``: block b's
    # row t), then block by block
    into = jnp.exp(G)
    L = beta * A
    beta_b = beta.reshape(hb, nb, sub, 1)
    beta_rep = jnp.zeros((hb, sub, C), f32)
    for j in range(nb):
        beta_rep = jnp.where(group == j, beta_b[:, j], beta_rep)
    T = jnp.where(iota(jnp.int32, (sub, C), 1) % sub
                  == iota(jnp.int32, (sub, C), 0), 1.0, 0.0) + jnp.zeros(
                      (hb, sub, C), f32)
    for s in range(sub - 1 if ABLATE not in ("solve", "pairs") else 0):
        T = T - (beta_rep * jnp.concatenate(Lrep[s], axis=1)) * T[:, s:s + 1]
    rhs = beta * jnp.concatenate([v, k * into], axis=-1)    # [hb, C, V + K]
    M = rhs.shape[-1]
    out = []
    for i in range(nb if ABLATE != "solve" else 0):
        r = i * sub
        acc = rhs[:, r:r + sub]
        if i:
            sofar = jnp.concatenate(
                out + [jnp.zeros((hb, C - r, M), f32)], axis=1)
            acc = acc - _per_head(_mm, L[:, r:r + sub], sofar)
        # block i's inverse alone in its rows' lanes, times the rows of the
        # chunk with block i's filled
        T_i = jnp.where(group[0] == i, T, 0)                # [hb, sub, C]
        rows = jnp.concatenate(
            [jnp.zeros((hb, r, M), f32)] * (r > 0) + [acc]
            + [jnp.zeros((hb, C - r - sub, M), f32)] * (r + sub < C), axis=1)
        out.append(_per_head(_mm, T_i, rows))
    X = jnp.concatenate(out, axis=1) if out else rhs
    Vw = v.shape[-1]
    U, W = X[..., :Vw], X[..., Vw:]
    # 3. the state's walk
    if ABLATE == "walk":
        return U + W + q * into + B[..., :1], S
    d = U - _per_head(_mm, W, S)
    o = _per_head(_mm, q * into, S) + _per_head(_mm, B, d)
    Kend = k * jnp.exp(G[:, C - 1:] - G)
    tn = (((0,), (0,)), ((), ()))
    decay = _columns(_identity(K), into[:, C - 1])          # [K, hb]
    S = (jnp.stack([decay[:, h:h + 1] for h in range(hb)]) * S
         + _per_head(lambda x, y: _mm(x, y, tn), Kend, d))
    return o, S


def _chunk_kernel(lidx_ref, walk_ref, slot_ref, src_ref, nl_ref, s_ref,
                  q_ref, k_ref, g_ref, v_ref, b_ref, so_ref, o_ref, S_ref, *,
                  hb: int, sub: int):
    del lidx_ref, walk_ref, slot_ref            # index maps only
    i, c = pl.program_id(1), pl.program_id(2)
    nl = nl_ref[0]
    K, V = s_ref.shape[1:]

    @pl.when(i < nl)
    def _live():
        src = src_ref[i]

        @pl.when((c == 0) & (src == FROM_ZEROS))
        def _():
            S_ref[...] = jnp.zeros_like(S_ref)

        @pl.when((c == 0) & (src == FROM_STORE))
        def _():
            S_ref[...] = s_ref[...]

        # (FROM_STEP: the row before, the same slot's, left its end here)
        def heads(ref):             # [C, hb, w] -> [hb, C, w]
            return jnp.stack([ref[:, h, :] for h in range(hb)])

        beta = jnp.stack([b_ref[:, h:h + 1] for h in range(hb)])
        o, S = _chunk_math(heads(q_ref), heads(k_ref), heads(g_ref),
                           heads(v_ref), beta, S_ref[...], sub)
        S_ref[...] = S
        for h in range(hb):
            o_ref[:, h, :] = o[h]

        @pl.when(c == pl.num_programs(2) - 1)
        def _():                    # a slot's LAST row's is what goes back
            so_ref[...] = S

    @pl.when(i >= nl)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when((i == 0) & (nl == 0))
    def _nobody():
        # no live row at all: program 0's block of the state is fetched and
        # written back all the same, so it goes back as it came
        so_ref[...] = s_ref[...]


def chunk_sources(slots, start, n):
    """The walk of a step's rows through a state that every step overwrites,
    as scalars: ``slots, start, n [R]`` (a row's slot, first position and
    real tokens; 0: an idle row). Returns ``(walk, slot_of, src, nl)``: the
    rows in the order the kernel takes them, the ``nl`` live ones first, a
    slot after another in ascending ``start``; the slot whose state each
    names (an idle row: the last live row's, so that nothing moves); and
    where a live row's state comes from: ``FROM_ZEROS`` at position 0,
    ``FROM_STEP`` where the row before it in the walk is the same slot's
    and ends where this one starts, else ``FROM_STORE``. The rule of
    ``inc_attention.carried_rows``."""
    R = slots.shape[0]
    live = n > 0
    nl = jnp.sum(live.astype(jnp.int32))
    walk = jnp.lexsort((start, slots, ~live)).astype(jnp.int32)
    at = jnp.arange(R)
    before = jnp.roll(walk, 1)
    step = ((at > 0) & (slots[before] == slots[walk])
            & (start[before] + n[before] == start[walk]))
    src = jnp.where(step, FROM_STEP,
                    jnp.where(start[walk] == 0, FROM_ZEROS, FROM_STORE))
    slot_of = slots[jnp.where(at < nl, walk, walk[jnp.maximum(nl - 1, 0)])]
    return walk, slot_of.astype(jnp.int32), src.astype(jnp.int32), nl


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_chunk(state, layer_idx, q, k, g, v, beta, slots, start, n,
              interpret: bool = False):
    """A prefill step's rows through the state of layer ``layer_idx``, chunk
    after chunk.

    ``state`` ``[L, slots, H, K, V]`` float32, updated in place (donate
    it); ``q, k, g`` ``[R, T, H, K]``, ``v`` ``[R, T, H, V]``, ``beta`` ``[R,
    T, H]``, float32, a padding position (``t >= n``) with ``g = 0`` and
    ``beta = 0``; ``slots, start, n`` ``[R]``: a row's slot, first position
    and real tokens (``chunk_sources``). Returns ``(o [R, T, H, V], the
    stack)``: each slot's LAST row's end is written back, an idle row's
    ``o`` is zeros (it reads nothing: its blocks are the last live
    program's) and no state of its slot is touched."""
    L, _, H, K, V = state.shape
    R, T = k.shape[:2]
    f32 = jnp.float32
    hb = chunk_heads_per_block(H)
    nhb = H // hb
    C = chunk_size(T)
    pad = -T % C
    nc = (T + pad) // C

    def rows_of(x):         # [R, T, H, w] -> [R, T + pad, H, w]
        x = x.astype(f32)
        return jnp.pad(x, [(0, 0), (0, pad), (0, 0), (0, 0)]) if pad else x

    beta = jnp.pad(beta.astype(f32), [(0, 0), (0, pad), (0, 0)])
    beta = beta.reshape(R, T + pad, nhb, hb).swapaxes(1, 2)
    walk, slot_of, src, nl = chunk_sources(slots, start, n)
    scalars = (jnp.asarray(layer_idx, jnp.int32).reshape(1), walk, slot_of,
               src, nl.reshape(1))

    def read(i, c, walk, nl):
        """The (row, chunk) whose inputs program (., i, c) reads: past the
        last live row, the last live program's, so that nothing moves."""
        live = i < nl[0]
        return (walk[jnp.where(live, i, jnp.maximum(nl[0] - 1, 0))],
                jnp.where(live, c, nc - 1))

    def state_map(h, i, c, lidx, walk, slot_of, src, nl):
        return lidx[0], slot_of[i], h, 0, 0

    def row_map(h, i, c, lidx, walk, slot_of, src, nl):
        r, cc = read(i, c, walk, nl)
        return r, cc, h, 0

    def beta_map(h, i, c, lidx, walk, slot_of, src, nl):
        r, cc = read(i, c, walk, nl)
        return r, h, cc, 0

    def out_map(h, i, c, lidx, walk, slot_of, src, nl):
        return walk[i], c, h, 0

    vec = lambda w: pl.BlockSpec((None, C, hb, w), row_map)  # noqa: E731
    new, o = pl.pallas_call(
        functools.partial(_chunk_kernel, hb=hb, sub=SUB),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(nhb, R, nc),
            in_specs=[pl.BlockSpec((None, None, hb, K, V), state_map),
                      vec(K), vec(K), vec(K), vec(V),
                      pl.BlockSpec((None, None, C, hb), beta_map)],
            out_specs=[pl.BlockSpec((None, None, hb, K, V), state_map),
                       pl.BlockSpec((None, C, hb, V), out_map)],
            scratch_shapes=[pltpu.VMEM((hb, K, V), f32)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((R, T + pad, H, V), f32)],
        # operands count the scalars: the stack is operand 5
        input_output_aliases={len(scalars): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=CHUNK_NAME,
    )(*scalars, state, rows_of(q), rows_of(k), rows_of(g), rows_of(v), beta)
    return o[:, :T], new


# ----------------------------------------------------------------------
# a state-space mixer's recurrent form (ops/ssd_mixer.py): one SCALAR
# decay a head, no delta term, the input and output rows shared by the heads
# ----------------------------------------------------------------------
#
# A row's state is ``S [H, P, N]`` float32 (2.10 MB at 64 heads of 64 x
# 128), ``N`` along the lanes. A decode step reads all of it and writes all
# of it back:
#
#     S[h] = a[h] * S[h] + dx[h] B^T        dx = dt * x [H, P]; B, C [N]
#     y[h] = S[h] C
#
# (the op adds the skip term ``D[h] x[h]`` to ``y`` in the fusion that
# gates it). ``kda_state_step`` cannot compute this by any setting of its
# operands: its write is ``k (beta (v - S'^T k))^T``, and ``beta = 0``
# switches the write off with the correction. The scaffolding is the same
# (the stack aliased in and out, the layer an operand, the live rows first,
# an idle row neither fetched nor written); the body shares ``_columns``
# and nothing else. A program is ``ssd_heads_per_block`` heads, one
# descriptor of 2.1 MB at these widths (the whole row's 64 heads); in and
# out double-buffered 8.4 MB of VMEM, inside the 16 MiB a call has without
# asking. It states NO limit of its own: XLA keeps the next gemms' weights
# moving into VMEM while a kernel runs, and with 96 MiB stated here a decode
# step of Granite-4.0-H's cell took 7.61 ms where it now takes 7.09
# (PERF.md section 6, PR 57).
#
# What the body costs is its cross-lane work (PERF.md section 6, PR 57: the
# stream alone takes 208 us a layer-step of 32 rows and every multiply and
# add hides behind it; a lane reduction a register did not):
#
# * the decays ``[R, H]`` lie in SMEM: ``a[r, h]`` times the row's
#   ``keep`` is a SCALAR that multiplies a head's tile as a splat;
# * ``dx`` is wanted as a COLUMN (``_columns``, once a program) and a
#   head's column is broadcast along the lanes, one broadcast a register:
#   inherent to ``N`` on the lanes;
# * ``y = S C`` sums along the lanes. No register is reduced alone: the
#   products ``S[h] * C`` of TWO heads are merged into one tile, lane ``l``
#   taking ``x[l] + x[l - 1]`` of the first head where ``l`` is even and of
#   the second where it is odd (two selects, one rotation by a lane, one
#   add); two such tiles the same way two lanes apart, and so on
#   (``_merge``): after ``log2(m)`` levels lane ``l`` of ONE tile holds the
#   sum of ``m`` lanes ending at ``l`` for head ``l % m``, at one rotation
#   a register of state, and ``_fold_lanes`` doubles the windows up to
#   ``N``. Every lane ``l`` of the ``[P, N]`` tile left then holds
#   ``y[l % m, p]`` whole: ``y`` as columns, head ``h`` in lane ``h``,
#   handed back as rows ``[m, P]`` through ``_head_lanes`` (the identity's
#   first ``m`` rows: ``_columns`` again, exact).

SSD_NAME = "ssd_state_step"
# a program's descriptor: 64 x 64 x 128 float32
SSD_BLOCK_BYTES = 2 * 1024 * 1024


def ssd_heads_per_block(H: int, P: int, N: int) -> int:
    """Heads a program: as many as one descriptor of ``SSD_BLOCK_BYTES``
    holds, a divisor of ``H``."""
    return heads_per_block(H, max(SSD_BLOCK_BYTES // (4 * P * N), 1))


def ssd_merged_heads(hb: int, N: int) -> int:
    """Heads whose sums share one tile's lanes: the largest power of two
    that divides the program's heads and the lanes."""
    m = 1
    while hb % (2 * m) == 0 and N % (2 * m) == 0:
        m *= 2
    return m


def supports_ssd(H: int, P: int, N: int) -> bool:
    """Shapes Mosaic takes: whole 128-lane tiles of ``N``, ``P`` and the
    head blocks whole 8-sublane tiles (interpreted, any shape goes)."""
    return (N % 128 == 0 and P % 8 == 0
            and ssd_heads_per_block(H, P, N) % 8 == 0)


def ssd_step_bytes(rows: float, H: int, P: int, N: int) -> float:
    """Bytes the kernel must move for ``rows`` live rows of one layer: the
    state in and out, dx in and y out, the decays, B and C (float32)."""
    return rows * (H * (2.0 * P * N + 2 * P + 1) + 2 * N) * 4


def _head_lanes(m: int, N: int):
    """``[m, N]``: row ``h`` picks lane ``h``."""
    return (jax.lax.broadcasted_iota(jnp.int32, (m, N), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (m, N), 1)).astype(
                jnp.bfloat16)


def _merge(x0, x1, sh: int, first):
    """Two tiles ``[P, N]`` whose lanes hold sums of ``sh`` lanes ending
    there -> one whose lanes hold sums of ``2 sh``: of ``x0`` where
    ``first`` (bit ``sh`` of the lane clear), of ``x1`` elsewhere. The
    rotation is cyclic and ``N`` a multiple of ``2 sh``, so a lane's
    partner ``l - sh`` has the bit the other way and the same bits below:
    the same tile's, the same head's."""
    return jnp.where(first, x0, x1) + pltpu.roll(
        jnp.where(first, x1, x0), sh, 1)


def _fold_lanes(x, span: int):
    """Lanes that hold sums of ``span`` lanes ending there -> sums of all
    ``N``: the window doubled while the rest is even, then added up."""
    N = x.shape[-1]
    while span < N and (N // span) % 2 == 0:
        x = x + pltpu.roll(x, span, 1)
        span *= 2
    return functools.reduce(lambda u, k: u + pltpu.roll(x, k * span, 1),
                            range(1, N // span), x)


def _ssd_kernel(lidx_ref, rows_ref, nl_ref, fresh_ref, s_ref, dx_ref, a_ref,
                b_ref, c_ref, y0_ref, so_ref, y_ref, *, hb: int):
    del lidx_ref, y0_ref            # index maps and aliasing only
    i = pl.program_id(0)
    first_head = pl.program_id(1) * hb
    nl = nl_ref[0]
    f32 = jnp.float32

    @pl.when(i < nl)
    def _live():
        P, N = s_ref.shape[1:]
        r = rows_ref[i]
        # a row that starts a request starts from zeros, whatever the slot
        # held
        keep = jnp.where(fresh_ref[r] != 0, 0.0, 1.0).astype(f32)
        dxT = _columns(_identity(P), dx_ref[...])           # [P, hb]
        B, C = b_ref[...], c_ref[...]                       # [1, N]
        m = ssd_merged_heads(hb, N)
        lane = jax.lax.broadcasted_iota(jnp.int32, (P, N), 1)
        first = {sh: (lane & sh) == 0 for sh in
                 (1 << k for k in range(m.bit_length() - 1))}

        def summed(h, count):
            """Heads ``h .. h + count``: each head's tile updated and
            written, their ``S * C`` merged."""
            if count > 1:
                sh = count // 2
                return _merge(summed(h, sh), summed(h + sh, sh), sh,
                              first[sh])
            S = (s_ref[h] * (a_ref[r, first_head + h] * keep)
                 + dxT[:, h:h + 1] * B)
            so_ref[h] = S
            return S * C

        for g in range(0, hb, m):
            yT = _fold_lanes(summed(g, m), m)       # [P, N], head g + l % m
            y_ref[g:g + m, :] = _columns(_head_lanes(m, N), yT)

    @pl.when((i == 0) & (nl == 0))
    def _nobody():
        # no live row at all: program 0's block is fetched and written back
        # all the same, so it goes back as it came
        so_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_state_step(state, layer_idx, dx, a, B, C, live, fresh,
                   interpret: bool = False):
    """One token a row through the state of layer ``layer_idx``.

    ``state`` ``[L, R, H, P, N]`` float32, updated in place (donate it);
    ``dx`` ``[R, H, P]`` (``dt * x``), ``a`` ``[R, H]`` (the decay, in (0,
    1]), ``B, C`` ``[R, N]``, float32; ``live`` ``[R]`` bool: rows that have
    a token; ``fresh`` ``[R]`` bool: rows that start from zeros. Returns
    ``(y [R, H, P] float32, the stack)``; an idle row's ``y`` is zeros and
    its state untouched."""
    L, R, H, P, N = state.shape
    hb = ssd_heads_per_block(H, P, N)
    nhb = H // hb
    rows, nl = live_rows_first(live)
    scalars = (jnp.asarray(layer_idx, jnp.int32).reshape(1), rows,
               nl.reshape(1), fresh.astype(jnp.int32))
    maps = StepMaps(nhb)
    f32 = jnp.float32
    block = pl.BlockSpec((None, None, hb, P, N), maps.state)
    vec = pl.BlockSpec((None, hb, P), maps.row)
    shared = pl.BlockSpec((None, 1, N), maps.shared)
    new, y = pl.pallas_call(
        functools.partial(_ssd_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(R, nhb),
            in_specs=[block, vec, pl.BlockSpec(memory_space=pltpu.SMEM),
                      shared, shared, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[block, vec]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((R, H, P), f32)],
        # operands count the scalars: the stack is operand 4, the zeros the
        # idle rows' output keeps operand 9
        input_output_aliases={len(scalars): 0, len(scalars) + 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=int(8 * R * H * P * N), transcendentals=0,
            bytes_accessed=int(ssd_step_bytes(R, H, P, N))),
        interpret=interpret, name=SSD_NAME,
    )(*scalars, state, dx.astype(f32), a.astype(f32),
      B.astype(f32).reshape(R, 1, N), C.astype(f32).reshape(R, 1, N),
      jnp.zeros((R, H, P), f32))
    return y, new
