"""Fused on-device serving loops: multi-step decode and chain speculation.

The reference hides per-step latency by pipelining Legion futures (reference
request_manager.cc:1829-1845 keeps a depth-4 batch queue in flight, with
Legion traces replaying the task DAG). The TPU-native equivalent is to move
the loop itself onto the device: a `lax.while_loop` over decode steps (or
whole speculation rounds) runs inside ONE jitted program, so host<->device
round-trips happen once per block instead of once per token. The trip count
is a DYNAMIC device scalar bounded by a static maximum — one compiled
program serves every block size, and the device only executes the steps
asked for. The host scheduler reconciles EOS/length truncation after
reading each block — overshoot work is bounded and the KV caches self-heal
because positions are recomputed from host state at every call.

What runs fused:
* ``decode_block`` (on InferenceManager): n greedy/sampled decode steps per
  call for incremental decoding.
* ``MultiSpecEngine``: one greedy chain a draft model, verified as one
  tree of unmerged branches (one draft: the MAX_BEAM_WIDTH=1 reference
  default, batch_config.h:125; a single branch needs no KV compaction,
  its accepted nodes are already contiguous in both caches).
* ``BeamSpecEngine``: one draft model's beam tree at width > 1.

Both speculation engines drive their rounds through ``_block_impl`` and
are driven through ``run_block`` below, which states the one contract
``RequestManager._generate_spec_fused`` reads.
"""

from __future__ import annotations

import time
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from flexflow_tpu.ffconst import OpType
from flexflow_tpu.ops import kv_layout as kvl
from flexflow_tpu.ops.base import OpContext
from flexflow_tpu.ops.inc_attention import move_kv, refuse_windowed
from flexflow_tpu.serve.batch_config import BatchMeta
from flexflow_tpu.telemetry import get_telemetry


def _resolve_tel(explicit):
    """Engine-side telemetry resolution: an explicitly injected
    ServingTelemetry (RequestManager hands its own through
    ``engine.telemetry``) wins over the process-global one."""
    return explicit if explicit is not None else get_telemetry()


def _open_block(tel):
    """A fused block's ``spec_block`` span and its first leaf,
    ``call_stage`` (both None with telemetry off)."""
    if tel is None:
        return None, None
    return (tel.tracer.begin("spec_block"),
            tel.call_phase(None, "call_stage", "spec_block"))


def _report_block(engine, tel, span, wait, t0, packed, n_rounds, trace,
                  behind):
    """Telemetry after one fused block's read-back (the device fence):
    closes the call's ``call_wait`` leaf and its ``spec_block`` span,
    feeds the speculation metrics, and reports compiles the call made.
    ``t0``: the block's launch. ``behind``: the program of the call the
    block was launched behind and waited for after its own launch
    (``prefill``; None: the device had nothing queued), onto the span,
    which then holds the block's own time and not that call's
    (``ServingTelemetry.end_spec_block``).
    ``trace`` is the scheduler round's RoundTrace, if a loop drives the
    engine: its ``sched_commit`` phase opens as soon as the spans are
    closed, so the bookkeeping here counts as the round's."""
    seconds = time.perf_counter() - t0
    tel.call_phase(wait, None)
    name = type(engine).__name__
    ran = packed[:, :, -2] >= 0
    tel.end_spec_block(span, rounds_asked=n_rounds,
                       rounds=int(ran.any(axis=0).sum()),
                       rows=int(ran.any(axis=1).sum()),
                       committed=int((packed[:, :, -2][ran] + 1).sum()),
                       engine=name, behind=behind)
    if trace is not None:
        trace.phase("sched_commit")
    tel.record_spec_block(seconds, packed[:, :, -2],
                          depths=packed[:, :, -1], t0=t0)
    if engine._trace_count != engine._traces_reported:
        tel.note_retrace(name,
                         engine._trace_count - engine._traces_reported,
                         engine._trace_count)
        engine._traces_reported = engine._trace_count


def build_feeds(model, meta):
    """The ONE place feed construction / position offsets live — used by
    the jitted serving body below and the eager debug-dump path
    (utils/debugging.dump_serving_step)."""
    feeds = {model.input_tensors[0].tensor_id: meta.tokens}
    pos_t = getattr(model, "position_input_tensor", None)
    if pos_t is not None:
        feeds[pos_t.tensor_id] = (meta.positions
                                  + getattr(model, "position_offset", 0))
    return feeds


def forward_with_meta(model, params, state, meta, rng, compute_dtype,
                      kv_contiguous=False, kv_append_q=None, phase=None,
                      outputs=None, narrow=None):
    """One serving forward over a BatchMeta inside jit — the single traced
    body shared by InferenceManager.step and the fused engines.

    ``kv_contiguous=True`` (fused engines only) promises every active
    row's append region [start, start+Q) is in bounds, unlocking the
    scatter-free dynamic_update_slice KV append (inc_attention.py
    append_kv_contiguous). ``kv_append_q`` (verify-consistent decode)
    declares that only the first kv_append_q tokens per row are real, so
    the KV append can skip the padding columns entirely. ``phase``: the
    program that runs the step says what the step is where its width does
    not ("decode": a block-diffusion pass; ops/moe._step_tokens). ``outputs``:
    the tensors to hand back, as a tuple, in place of the final one: what
    no output is computed from is traced and then dropped as dead code,
    by jit before it lowers and by XLA. That is how the output-free step
    (InferenceManager._prefill_impl) leaves the graph's tail out: its
    outputs are the inputs of ``_tail_of(model)``, the last layer's hidden
    state at every position of the step, so the last layer stays whole.
    ``narrow``: a (layer, fn) whose layer is given ``fn`` of its inputs (a
    pass that is two blocks wide hands the model's tail, the head with it,
    the one block a row reads: ``_diffusion_block``)."""
    ctx = OpContext(training=False, rng=rng, compute_dtype=compute_dtype,
                    batch_config=meta, mesh=model.mesh, config=model.config)
    ctx.kv_contiguous = kv_contiguous
    ctx.kv_append_q = kv_append_q
    ctx.step_phase = phase
    values, new_state = model._run_graph(params, build_feeds(model, meta),
                                         ctx, state, narrow=narrow)
    if outputs is not None:
        return tuple(values[t.tensor_id] for t in outputs), new_state
    return values[model._final_tensor.tensor_id], new_state


def _forward_tokens(model, params, state, tokens, positions, start_pos,
                    num_tokens, active, rng, compute_dtype):
    """One forward over [R, Q] tokens inside jit; returns (out, new_state).

    All engine-issued forwards stage contiguous, bounds-guaranteed KV
    runs (each engine's live_mask reserves the full staging window), so
    the scatter-free append path applies."""
    meta = BatchMeta(tokens=tokens, positions=positions, start_pos=start_pos,
                     num_tokens=num_tokens, active=active)
    return forward_with_meta(model, params, state, meta, rng, compute_dtype,
                             kv_contiguous=True)


def _adapt_depth_rule(adapt, act_i, n_acc, depth_v, alive, min_depth,
                      max_depth):
    """Adaptive-mode in-block policy of the fused engines'
    while_loop bodies (a no-op when the host ran the block statically):

    * depth adaptation between rounds — grow on a full accept, shrink on
      a zero accept, hold otherwise, bounded by [min_depth, compiled
      depth]; the host re-anchors from its EWMA cost model at the block
      boundary;
    * give-up — once every live row is AT the floor and still accepts
      nothing the block ends, so a collapsed draft costs at most the
      shrink path (~depth rounds) before the host parks the batch on
      incremental decoding, never a whole max_rounds block. A collapsed
      row beside one that still accepts stays: the block runs on anyway,
      and leaving would cost it the verifier's token of each round left.

    Returns (depth_v, alive)."""
    collapsed = adapt & act_i & (n_acc == 0) & (depth_v == min_depth)
    give_up = collapsed & ~jnp.any(act_i & ~collapsed)
    alive = alive & ~give_up
    grown = jnp.where(n_acc >= depth_v, depth_v + 1,
                      jnp.where(n_acc == 0, depth_v - 1, depth_v))
    depth_v = jnp.where(adapt & act_i,
                        jnp.clip(grown, min_depth, max_depth), depth_v)
    return depth_v, alive


def serving_jit(config, fn, **kw):
    """``jax.jit`` for a serving program of a model under ``config``: with
    the configuration's XLA options where it states any
    (``FFConfig.compiler_options``), and as plain ``jax.jit`` where not."""
    options = getattr(config, "compiler_options", None)
    if options:
        kw["compiler_options"] = dict(options)
    return jax.jit(fn, **kw)


def make_draft_chain(model, compute_dtype, depth: int):
    """Build a fused greedy draft-chain program for one SSM.

    Signature: (params, op_state, tok [R], pos [R], active [R], rng) ->
    (chain [R, depth], new_op_state). One device call replaces ``depth``
    width-1 ``InferenceManager.step`` calls in the multi-SSM tree path
    (each step is a host round trip). KV for drafted tokens is written
    tentatively —
    the host rewinds its cache-depth bookkeeping and overwrites next round,
    exactly as the unfused path did.
    """

    def chain(params, op_state, tok, pos, active, rng):
        num = active.astype(jnp.int32)

        def body(carry, i):
            state, t, p = carry
            out, state = _forward_tokens(
                model, params, state, t[:, None], p[:, None], p, num,
                active, jax.random.fold_in(rng, i), compute_dtype)
            nxt = out[:, 0].astype(jnp.int32)
            return (state, nxt, p + 1), nxt

        (op_state, _, _), toks = jax.lax.scan(
            body, (op_state, tok, pos), jnp.arange(depth))
        return jnp.transpose(toks), op_state                # [R, depth]

    return serving_jit(model.config, chain, donate_argnums=(1,))


def make_decode_block(model, compute_dtype, max_steps: int, width: int = 1):
    """Build the jitted dynamic-length decode program for ``model``.

    Signature: (params, op_state, tok [R], pos [R], active [R], rng,
    n (device scalar <= max_steps)) -> (tokens [R, max_steps], new_op_state,
    last_tok [R]). Only the first n columns are meaningful; the rest stay 0.
    ``pos[r]`` is the sequence index of the pending token ``tok[r]``.
    One program compiles for ALL n (dynamic while_loop trip count).

    ``width > 1`` (a model that a speculation engine verifies:
    InferenceManager.decode_width) runs each step at the spec verify
    pass's token width
    with 1 real token per row (verify-consistent decode: identical gemm
    shapes and attention-kernel instantiation, so near-tie argmaxes
    resolve the same way in both paths). Only the real token's KV is
    appended (kv_append_q=1) — the padding rows' KV is never attended —
    via the attention kernel's fused in-place append (inc_attention._attend
    append_kv), so no staging window needs reserving near the cache end.

    A block-diffusion model (``model.block_diffusion``) gets the program of
    ``_diffusion_block`` under the same signature: ``tok`` is ``[R, B]``.
    """
    bd = getattr(model, "block_diffusion", None)
    if bd is not None:
        return serving_jit(
            model.config, _diffusion_block(model, compute_dtype, max_steps, bd),
            donate_argnums=(1,))

    def block(params, op_state, tok, pos, active, rng, n):
        R = tok.shape[0]
        num = active.astype(jnp.int32)
        out0 = jnp.zeros((R, max_steps), jnp.int32)

        def cond(carry):
            i = carry[0]
            return i < n

        def body(carry):
            i, state, tok, pos, out = carry
            if width == 1:
                o, state = _forward_tokens(
                    model, params, state, tok[:, None], pos[:, None], pos,
                    num, active, jax.random.fold_in(rng, i), compute_dtype)
            else:
                # verify-consistent decode: same token width as the spec
                # verify pass, 1 real token (num_tokens = active). The
                # chain tree's ancestor mask IS the causal mask, so the
                # plain causal path computes bitwise-identical row-0
                # results without building / DMA-ing the [R, Q, S] tree
                # bias (~7% of an 8-layer decode step).
                R = tok.shape[0]
                toks = jnp.zeros((R, width), jnp.int32).at[:, 0].set(tok)
                qpos = pos[:, None] + jnp.arange(width)[None, :]
                meta = BatchMeta(tokens=toks, positions=qpos, start_pos=pos,
                                 num_tokens=num, active=active)
                o, state = forward_with_meta(
                    model, params, state, meta, jax.random.fold_in(rng, i),
                    compute_dtype, kv_append_q=1)
            nxt = o[:, 0].astype(jnp.int32)
            out = jax.lax.dynamic_update_slice(out, nxt[:, None], (0, i))
            return i + 1, state, nxt, pos + 1, out

        _, op_state, tok, _, out = jax.lax.while_loop(
            cond, body, (jnp.int32(0), op_state, tok, pos, out0))
        return out, op_state, tok

    return serving_jit(model.config, block, donate_argnums=(1,))


# A diffusion block's read-back, one int32 row a slot: the tokens the row
# emitted (``BlockDiffusion.emitted_most`` columns, the first ``count``
# real), its window (2B columns, -1: still masked), then PASS_STATS.
PASS_STATS = ("count", "passes", "folded", "by_threshold", "by_floor")

# What a model's tail is made of: the layers that end the graph and work on
# each position alone (final norm, head), below the pick.
# (a scalar multiple: a head whose logits are scaled, models/granite_hybrid.py)
_POSITION_WISE = (OpType.LINEAR, OpType.RMS_NORM, OpType.SCALAR_MULTIPLY)


def _tail_of(model):
    """The first layer of ``model``'s tail: the chain of position-wise
    layers that ends in the graph's last (the final norm, the head, the
    pick). What it is given is all that those layers compute."""
    layer = model.layers[-1]
    while True:
        prev = layer.inputs[0].owner_layer
        if prev is None or prev.op_type not in _POSITION_WISE:
            return layer
        layer = prev


def diffusion_pass(model, params, state, win, pos, act, carried, rng,
                   compute_dtype, outputs=None):
    """One pass of ``_diffusion_block``: the rows' windows ``win`` [R, 2B]
    at the lengths ``pos`` their caches hold, 2B tokens a row, of which a
    row that carries no whole block (``carried`` [R]) has its first B real.
    Returns (``outputs``, new_state); the graph's tail, the head with it,
    sees the B columns of the block a row fills, so the default outputs,
    the head's pick and confidence, are [R, B]."""
    bd = model.block_diffusion
    B = bd.block_length
    first = B * carried.astype(jnp.int32)
    at = (first[:, None] + jnp.arange(B, dtype=jnp.int32))[:, :, None]
    meta = BatchMeta(
        tokens=jnp.where(win < 0, bd.mask_token_id, win),
        positions=pos[:, None] + jnp.arange(2 * B, dtype=jnp.int32),
        start_pos=pos, num_tokens=jnp.where(act, B + first, 0), active=act)
    return forward_with_meta(
        model, params, state, meta, rng, compute_dtype, phase="decode",
        outputs=model.layers[-1].outputs if outputs is None else outputs,
        narrow=(_tail_of(model),
                lambda h: jnp.take_along_axis(h, at, axis=1)))


def _diffusion_block(model, compute_dtype, max_steps: int, bd):
    """The decode block of a block-diffusion model: ``n`` passes over
    blocks of ``B = bd.block_length`` positions a row.

    (params, op_state, win [R, 2B], pos [R], active [R], rng, n) ->
    (packed [R, E + 2B + len(PASS_STATS)], new_op_state, win). ``pos[r]`` is
    the length the row's cache holds, a multiple of B, and ``win[r]`` its
    window, the positions ``[pos, pos + 2B)``: a token id, or -1 where the
    position is still masked (what a row carries from one call into the
    next; a row that begins a block hands over what is known of it, the
    prompt's remainder, then -1). A window whose first block is WHOLE
    carries that block, emitted and not stored yet, in front of the block
    the row fills; any other window holds the block it fills and B masks.

    Every pass is ONE forward of ``[R, 2B]`` at the true positions, masked
    positions holding the mask token, with the keys and values of every
    real token written at its position before the row attends: by the
    attention kernel itself, a run of B or 2B positions a row merged into
    the block it streams and written back in place
    (kernels/attention.flash_attend ``append_kv``; off the kernel path one
    row-granular scatter a layer's cache, inc_attention.append_and_ref: the
    same cache bits). A row fills its block by DENOISE passes:
    B real tokens at ``[pos, pos + B)`` (``num_tokens``: the rest of the row
    is routed to no expert, stored nowhere and counted by nothing), which
    see the cache and the block itself both ways
    (inc_attention.block_visibility); ``pos`` does not move, so what the
    pass wrote is overwritten by the next; it unmasks
    every masked position whose pick is more probable than the threshold,
    or the ``bd.floor`` most confident where fewer clear it. The pass that
    leaves the block whole EMITS it, and the row's next pass STORES it while
    it denoises the next block: 2B real tokens at ``[pos, pos + 2B)``, the
    whole block seeing itself and the cache, the new one seeing both
    besides, which is what a pass over the whole block alone followed by
    the new block's first pass would compute; then ``pos`` grows by B: the
    first B of what was written are what the cache keeps. The head reads
    the B columns of the block a row fills, gathered before the model's
    tail. No pass only stores: a block emitted by a row's last pass stays
    in its window, for the next call or for nobody (a finished row's cache
    is read by nothing). A row whose next pass would write past the cache's
    end sits out. The host reconciles ``max_new_tokens`` and
    end-of-sequence; overshoot is bounded by the call, as in the one-token
    block.

    One width, not two: a B-wide pass for the passes in which no row
    carries a block is 2-3 ms cheaper on the chip, and its second loop body
    (a second trace and executable of the graph) costs more set-up than the
    benchmark's bound allows (PERF.md section 6, PR 40)."""
    B, floor = bd.block_length, bd.floor
    E = bd.emitted_most(max_steps)
    S = model.config.max_sequence_length

    def block(params, op_state, win, pos, active, rng, n):
        R = win.shape[0]
        cols = jnp.arange(B, dtype=jnp.int32)
        masks = jnp.full((R, B), -1, jnp.int32)

        def body(carry):
            i, state, win, pos, out, stats = carry
            whole = ~(win[:, :B] < 0).any(axis=1)
            act = active & (pos + B * (1 + whole) <= S)
            carried = act & whole
            (x0, conf), state = diffusion_pass(
                model, params, state, win, pos, act, carried,
                jax.random.fold_in(rng, i), compute_dtype)
            blk = jnp.where(whole[:, None], win[:, B:], win[:, :B])
            masked = blk < 0
            # denoise: the picks above the threshold, or the floor's most
            # confident (ties to the earlier position)
            c = jnp.where(masked, conf.astype(jnp.float32), -jnp.inf)
            high = masked & (c > bd.threshold)
            ahead = ((c[:, None, :] > c[:, :, None])
                     | ((c[:, None, :] == c[:, :, None])
                        & (cols[None, None, :] < cols[None, :, None])))
            low = masked & (ahead.sum(axis=-1) < floor)
            cleared = high.sum(axis=1) >= floor
            unmask = jnp.where(cleared[:, None], high, low) & act[:, None]
            blk = jnp.where(unmask, x0.astype(jnp.int32), blk)
            # a block left whole is emitted at the row's count
            emit = act & ~(blk < 0).any(axis=1)
            count = stats[:, 0]
            at = jnp.arange(E, dtype=jnp.int32)[None, :] - count[:, None]
            out = jnp.where(
                emit[:, None] & (at >= 0) & (at < B),
                jnp.take_along_axis(blk, jnp.clip(at, 0, B - 1), axis=1),
                out)
            # the window begins with the block the row fills, past the one
            # this pass stored (a row that sat out with a whole block keeps
            # both)
            win = jnp.where((whole & ~act)[:, None], win,
                            jnp.concatenate([blk, masks], axis=1))
            took = unmask.sum(axis=1, dtype=jnp.int32)
            stats = stats + jnp.stack(
                [B * emit.astype(jnp.int32), act.astype(jnp.int32),
                 carried.astype(jnp.int32),
                 jnp.where(cleared, took, 0), jnp.where(cleared, 0, took)],
                axis=1)
            return (i + 1, state, win, pos + B * carried.astype(jnp.int32),
                    out, stats)

        _, op_state, win, _, out, stats = jax.lax.while_loop(
            lambda carry: carry[0] < n, body,
            (jnp.int32(0), op_state, win, pos,
             jnp.zeros((R, E), jnp.int32),
             jnp.zeros((R, len(PASS_STATS)), jnp.int32)))
        return jnp.concatenate([out, win, stats], axis=1), op_state, win

    return block


def _block_impl(self, llm_params, llm_state, *rest):
    """The jitted block of either speculation engine: up to ``n_rounds``
    of ``self._round`` in one while_loop, each row carried from round to
    round by its accepted block (tks, nblk, base)."""
    self._trace_count += 1          # python body == one XLA trace
    B = len(self.ssms)
    ssm_ps = [rest[2 * i] for i in range(B)]
    ssm_states = [rest[2 * i + 1] for i in range(B)]
    (tks0, nblk0, base0, active, n_rounds, remaining, depth0, min_depth,
     adaptive) = rest[2 * B:]
    R = tks0.shape[0]
    d = self.depth
    max_seq = self.llm.config.max_sequence_length
    rng0 = jax.random.fold_in(self._rng_const,
                              (base0 + nblk0 - 1).sum())
    # packed [R, max_rounds, d+3]: chain ++ bonus ++ n_acc ++ depth
    packed0 = jnp.full((R, self.max_rounds, d + 3), 0, jnp.int32)
    packed0 = packed0.at[:, :, d + 1].set(-1)
    packed0 = packed0.at[:, :, d + 2].set(-1)
    # a call starts where a round inside it starts: from each row's
    # accepted block (tks0, nblk0, base0), handed over by the host, so
    # round 0's width-(d+1) draft step is the catch-up over the LAST
    # call's final round too (see run_block)
    adapt = adaptive > 0

    Tp = self.tree_width

    def live_mask(base, nblk, remaining):
        r_pos = base + nblk - 1
        # reserve the PADDED verify width: the contiguous KV append
        # writes the whole [r_pos, r_pos + Tp) staging window
        return ((remaining > 0) & (r_pos + Tp <= max_seq - 1))

    def cond(carry):
        (i, _ls, _ss, _tks, nblk, base, remaining, act, _d, alive,
         _p) = carry
        return (i < n_rounds) & jnp.any(
            act & live_mask(base, nblk, remaining) & alive)

    def body(carry):
        (i, llm_state, ssm_states, tks, nblk, base, remaining, act,
         depth_v, alive, packed) = carry
        act_i = act & live_mask(base, nblk, remaining) & alive
        (llm_state, ssm_states, blk, new_nblk, new_base, chain, n_acc,
         bonus) = self._round(
            llm_params, llm_state, ssm_ps, list(ssm_states), tks, nblk,
            base, act_i, jax.random.fold_in(rng0, i), depth_v)
        tks = jnp.where(act_i[:, None], blk, tks)
        nblk = jnp.where(act_i, new_nblk, nblk)
        base = jnp.where(act_i, new_base, base)
        remaining = remaining - jnp.where(act_i, n_acc + 1, 0)
        row = jnp.concatenate(
            [chain, bonus[:, None],
             jnp.where(act_i, n_acc, -1)[:, None],
             jnp.where(act_i, depth_v, -1)[:, None]], axis=1)
        packed = jax.lax.dynamic_update_slice(
            packed, row[:, None, :], (0, i, 0))
        depth_v, alive = _adapt_depth_rule(adapt, act_i, n_acc,
                                           depth_v, alive, min_depth,
                                           d)
        return (i + 1, llm_state, tuple(ssm_states), tks, nblk, base,
                remaining, act, depth_v, alive, packed)

    (_, llm_state, ssm_states, _, _, _, _, _, _, _, packed) = \
        jax.lax.while_loop(
            cond, body,
            (jnp.int32(0), llm_state, tuple(ssm_states), tks0, nblk0,
             base0, remaining, active, depth0, active, packed0))
    return (llm_state, tuple(ssm_states), packed)


def run_block(self, tks: np.ndarray, nblk: np.ndarray, base: np.ndarray,
              active: np.ndarray, n_rounds: int,
              remaining: Optional[np.ndarray] = None,
              depth: Optional[np.ndarray] = None,
              min_depth: int = 1, trace=None
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Run up to ``n_rounds`` (<= max_rounds) fused rounds of either
    speculation engine: the one contract between an engine and whoever
    drives it. Updates every model's op_state.

    Each row enters with its accepted block: ``tks[r, :nblk[r]]`` are the
    committed tokens the drafts' caches still lack (1 <= nblk <= depth+1,
    the first at sequence position ``base[r]``); the last of them is the
    pending token, whose KV the verifier's cache lacks too. A row the
    drafts are level with enters with the one-token block ``nblk == 1``.
    The first draft step of every round is the catch-up over that block,
    so no prefill call sits between two blocks. A row drafts only while
    ``engine.tree_width`` positions past its pending token fit in the
    cache (live_mask) and ``remaining[r]``, its generation budget, is not
    spent: the device loop exits early once no row can, so one call
    normally finishes a whole request batch.

    Returns (toks, n_acc, depth_used). toks[r, k] holds round k's [draft
    path (depth), bonus]: the committed tokens are
    ``toks[r, k, :n_acc[r, k]]`` plus the verifier's bonus at the FIXED
    index ``toks[r, k, depth]``; n_acc == -1 marks a round the row sat
    out. Afterwards the verifier's cache holds everything but the new
    pending token; the drafts' caches are only good through the LAST
    round's root (a losing branch holds its own chain, a beam its staged
    nodes): the gap is the next call's accepted block.

    ``depth[r]`` (None = static: the compiled depth, no in-block
    adaptation) bounds row r's EFFECTIVE draft depth for the first round
    — the block is compiled once at the max depth and drafting
    early-exits at the round's deepest active row (the tree topology and
    verify width stay static), so a mixed batch runs different depths in
    one round with no retrace. Between rounds the device grows/shrinks
    each row's depth (full accept -> +1, zero accept -> -1, clipped to
    [min_depth, depth]) and once every live row accepts nothing at the
    floor the block ends (give-up) so the host controller can park the
    batch; depth_used[r, k] reports the bound each round actually ran
    under (-1 on idle rounds) so the host can attribute its acceptance
    observations.

    ``trace`` is the calling scheduler loop's RoundTrace when telemetry
    is on (None otherwise, and for direct drivers): the block settles the
    prefill step the round may have pending between its own
    ``call_launch`` and ``call_wait`` (as InferenceManager.decode_block
    does), and hands the round its ``sched_commit`` phase the moment its
    own spans close.
    """
    n_rounds = min(int(n_rounds), self.max_rounds)
    tel = _resolve_tel(self.telemetry)
    span, ph = _open_block(tel)
    if remaining is None:
        remaining = np.full(nblk.shape, np.iinfo(np.int32).max // 2,
                            np.int32)
    adaptive = depth is not None
    if depth is None:
        depth = np.full(nblk.shape, self.depth, np.int32)
    depth = np.clip(np.asarray(depth, np.int32), 1, self.depth)
    args = [self.llm.params, self.llm.op_state]
    for s in self.ssms:
        args += [s.params, s.op_state]
    args += [jnp.asarray(tks, jnp.int32), jnp.asarray(nblk, jnp.int32),
             jnp.asarray(base, jnp.int32), jnp.asarray(active),
             jnp.int32(n_rounds), jnp.asarray(remaining, jnp.int32),
             jnp.asarray(depth),
             jnp.int32(max(1, min(int(min_depth), self.depth))),
             jnp.int32(int(adaptive))]
    if tel is not None:
        ph = tel.call_phase(ph, "call_launch", "spec_block")
    t0 = time.perf_counter()
    llm_state, ssm_states, packed = self._block(*args)
    self.llm.op_state = llm_state
    for s, st in zip(self.ssms, ssm_states):
        s.op_state = st
    if tel is not None:
        tel.call_phase(ph, None)
        behind = trace.settle() if trace is not None else None
        ph = tel.call_phase(None, "call_wait", "spec_block")
    packed = np.asarray(packed)
    if tel is not None:     # the np readback above is the device fence
        _report_block(self, tel, span, ph, t0, packed, n_rounds, trace,
                      behind)
    return packed[:, :, :-2], packed[:, :, -2], packed[:, :, -1]


class MultiSpecEngine:
    """Fully-fused multi-SSM tree speculation: one device call per block.

    Per round, ALL inside one jitted while_loop (the unfused path paid a
    host round trip per drafted token per SSM plus one per verify/commit —
    reference request_manager.cc walks the same phases as separate Legion
    task batches):

    * each SSM drafts a depth-``d`` greedy chain; the first draft step is
      width-(d+1) and doubles as the CATCH-UP over last round's accepted
      block, so a draft cache whose chain lost the previous round gets the
      accepted tokens' KV rewritten before drafting (the unfused path did
      this via prefill calls). The block crosses the call boundary: the
      host hands the last call's final accepted block to the next
      run_block, so no prefill call sits between two blocks;
    * the chains verify as one token tree with B branches off the root —
      chains are NOT merged (the host path dedups shared prefixes; here
      duplicate nodes just cost verify slots), so the tree topology, its
      ancestor mask, and every node's cache slot are COMPILE-TIME
      constants. The dedup's saved verify slots were noise next to the
      per-phase dispatches the host path pays on the machine this was
      tuned on (80-100 ms each there; not measured on a local chip —
      ROADMAP S6);
    * greedy acceptance picks the branch with the longest matching prefix
      (branches are linear, so tree acceptance reduces to a per-branch
      cumprod + argmax);
    * accepted nodes' KV compacts from branch ``j``'s slots to the
      committed region in-program (the reference's commit_tokens_kernel,
      tree_inc_multihead_self_attention.cu:35), vectorized over the
      stacked layer dim.
    """

    def __init__(self, llm, ssms, depth: int = 4, max_rounds: int = 16):
        self.llm = llm
        self.ssms = list(ssms)
        llm.finalize_pipeline()
        for s in self.ssms:
            s.finalize_pipeline()
        self.depth = depth
        self.max_rounds = max_rounds
        self.telemetry = None   # explicit ServingTelemetry; None -> global
        self._compute_dtype = jnp.dtype(llm.config.compute_dtype)
        nssm = len(self.ssms)
        self._block = serving_jit(
            llm.config, self._block_impl,
            donate_argnums=(1,) + tuple(3 + 2 * i for i in range(nssm)))
        # jit-cache accounting: _block_impl's python body runs ONLY when
        # XLA (re)traces, so _trace_count is the compile count; run_block
        # reports new traces past the first as retraces (note_retrace)
        self._trace_count = 0
        self._traces_reported = 0
        self._rng_const = jax.random.PRNGKey(llm.config.seed)

    # -- static tree topology: root + B unmerged chains ----------------
    @property
    def tree_width(self) -> int:
        """Verify width: real nodes padded to a sublane multiple (Mosaic
        DMAs slice the [Q, BS] bias block, so Q must be 8-aligned; padding
        nodes are masked off via num_nodes and their outputs unread)."""
        from flexflow_tpu.kernels.attention import SUBLANE, round_up

        T = 1 + len(self.ssms) * self.depth
        return round_up(T, SUBLANE)

    @property
    def room(self) -> int:
        """Cache positions a row must have free past its tokens for the
        scheduler to send it here: live_mask's staging window. A looser
        gate would keep scheduling a row the engine masks dead every
        round, hanging the loop."""
        return self.tree_width

    def _tree_constants(self, R):
        d, B = self.depth, len(self.ssms)
        T = 1 + B * d
        Tp = self.tree_width
        parent = np.full((Tp,), -1, np.int64)
        depth_of = np.zeros((Tp,), np.int64)
        for j in range(B):
            for i in range(d):
                n = 1 + j * d + i
                parent[n] = 0 if i == 0 else n - 1
                depth_of[n] = i + 1
        anc = np.zeros((Tp, Tp), bool)
        for n in range(T):
            m = n
            while m != -1:
                anc[n, m] = True
                m = parent[m]
        return (jnp.asarray(np.broadcast_to(parent, (R, Tp))),
                jnp.asarray(depth_of),
                jnp.asarray(np.broadcast_to(anc, (R, Tp, Tp))))

    def _draft(self, j, params, state, tks, nblk, base, active, rng, d_run):
        """Catch-up + chain for SSM j. tks [R, d+1] = last round's accepted
        block (count nblk, first token at position base). Returns
        (state, chain [R, d]). ``d_run`` (device scalar, 1..depth) bounds
        the chain steps actually executed this round — the spec
        controller's early-exit; columns past it stay zero and are capped
        off in acceptance."""
        d = self.depth
        R = tks.shape[0]
        ssm = self.ssms[j]
        num = jnp.where(active, nblk, 0)
        pos = base[:, None] + jnp.arange(d + 1)[None, :]
        out, state = _forward_tokens(
            ssm, params, state, tks, pos, base, num, active,
            jax.random.fold_in(rng, 0), self._compute_dtype)
        # next token = argmax after the block's LAST real token
        t = jnp.take_along_axis(
            out, jnp.maximum(nblk - 1, 0)[:, None], axis=1)[:, 0]
        t = t.astype(jnp.int32)
        r_pos = base + nblk - 1                     # root position
        chain0 = jnp.zeros((R, d), jnp.int32).at[:, 0].set(t)

        def cond(carry):
            return carry[0] < d_run - 1

        def body(carry):
            i, state, t, p, chain = carry
            out, state = _forward_tokens(
                ssm, params, state, t[:, None], p[:, None], p,
                active.astype(jnp.int32), active,
                jax.random.fold_in(rng, 1 + i), self._compute_dtype)
            nxt = out[:, 0].astype(jnp.int32)
            chain = jax.lax.dynamic_update_slice(chain, nxt[:, None],
                                                 (0, i + 1))
            return i + 1, state, nxt, p + 1, chain

        (_, state, _, _, chain) = jax.lax.while_loop(
            cond, body, (jnp.int32(0), state, t, r_pos + 1, chain0))
        return state, chain                         # [R, d]

    def _commit(self, llm_state, best_j, n_acc, r_pos, active):
        """cache[r, :, r_pos+1+i] <- cache[r, :, r_pos+1+best_j*d+i] for
        i < n_acc, all layers (branch 0 is already contiguous)."""
        d = self.depth
        refuse_windowed(llm_state, "a speculation commit (move_kv)")
        st = llm_state["kv_cache"]

        def move(cache):                            # [L, R, KH, S, D]
            L, R, KH = cache.shape[:3]
            S = self.llm.config.max_sequence_length
            pack = kvl.pack_of(cache, S)
            i = jnp.arange(d)[None, :]              # committed index
            src = r_pos[:, None] + 1 + best_j[:, None] * d + i
            src = jnp.clip(src, 0, S - 1)
            if pack > 1:        # stored packed: ops/kv_layout.py
                return move_kv(cache, src, r_pos + 1, n_acc, active, pack)
            moved = jnp.take_along_axis(
                cache, src[None, :, None, :, None], axis=3)  # [L,R,KH,d,D]
            valid = (i < n_acc[:, None]) & active[:, None]
            dst = jnp.where(valid, r_pos[:, None] + 1 + i, S)
            lidx = jnp.broadcast_to(
                jnp.arange(L)[:, None, None, None], (L, R, KH, d))
            rows = jnp.broadcast_to(
                jnp.arange(R)[None, :, None, None], (L, R, KH, d))
            heads = jnp.broadcast_to(
                jnp.arange(KH)[None, None, :, None], (L, R, KH, d))
            dstb = jnp.broadcast_to(dst[None, :, None, :], (L, R, KH, d))
            return cache.at[lidx, rows, heads, dstb].set(moved, mode="drop")

        return {**llm_state,
                "kv_cache": {"k": move(st["k"]), "v": move(st["v"])}}

    def _round(self, llm_params, llm_state, ssm_ps, ssm_states, tks, nblk,
               base, active, rng, depth_r):
        d, B = self.depth, len(self.ssms)
        R = tks.shape[0]
        T = 1 + B * d
        # (sequence-length safety: _block_impl's live_mask gates entry)
        r_pos = base + nblk - 1
        # deepest active row bounds the draft steps this round (the tree
        # topology/verify width stay compile-time static; only the cheap
        # draft-chain steps early-exit)
        d_run = jnp.max(jnp.where(active, depth_r, 1))

        chains = []
        with jax.named_scope("draft"):
            for j in range(B):
                ssm_states[j], chain = self._draft(
                    j, ssm_ps[j], ssm_states[j], tks, nblk, base, active,
                    jax.random.fold_in(rng, 100 + j), d_run)
                chains.append(chain)

        # --- verify: root + B chains as a constant-topology tree ---
        from flexflow_tpu.serve.batch_config import TreeBatchMeta

        with jax.named_scope("verify"):
            root = jnp.take_along_axis(
                tks, jnp.maximum(nblk - 1, 0)[:, None], axis=1)[:, 0]
            tokens = jnp.concatenate([root[:, None]] + chains, axis=1)  # [R,T]
            Tp = self.tree_width
            tokens = jnp.pad(tokens, ((0, 0), (0, Tp - T)))
            parent, depth_of, anc = self._tree_constants(R)
            positions = r_pos[:, None] + depth_of[None, :]
            meta = TreeBatchMeta(
                tokens=tokens, positions=positions, parent=parent,
                ancestor=anc, start_pos=r_pos,
                num_nodes=jnp.where(active, T, 0).astype(jnp.int32),
                active=active)
            out, llm_state = forward_with_meta(
                self.llm, llm_params, llm_state, meta,
                jax.random.fold_in(rng, 7), self._compute_dtype,
                kv_contiguous=True)
            o = out.astype(jnp.int32)                   # [R, T]

        # --- per-branch greedy acceptance, best branch wins ---
        with jax.named_scope("commit"):
            n_js = []
            for j in range(B):
                pred = jnp.concatenate(
                    [o[:, :1], o[:, 1 + j * d: j * d + d]], axis=1)  # [R, d]
                # longest matching prefix = index of the first mismatch
                # (argmin of [match, 0] — cumprod lowers to a slow O(d^2)
                # reduce-window on some backends); positions past the row's
                # controller depth count as mismatches, so n_acc <= depth_r
                match = ((chains[j] == pred)
                         & (jnp.arange(d)[None, :] < depth_r[:, None])
                         ).astype(jnp.int32)
                n_js.append(jnp.argmin(
                    jnp.pad(match, ((0, 0), (0, 1))),
                    axis=1).astype(jnp.int32))
            n_mat = jnp.stack(n_js, axis=1)             # [R, B]
            best_j = jnp.argmax(n_mat, axis=1).astype(jnp.int32)
            n_acc = jnp.max(n_mat, axis=1)
            bonus_idx = jnp.where(n_acc == 0, 0, 1 + best_j * d + n_acc - 1)
            bonus = jnp.take_along_axis(o, bonus_idx[:, None], axis=1)[:, 0]
            best_chain = jnp.take_along_axis(
                jnp.stack(chains, axis=1), best_j[:, None, None], axis=1)[:, 0]

            if B > 1:
                # single-branch trees are already contiguous (branch 0's slots
                # ARE the committed region) — no compaction needed
                llm_state = self._commit(llm_state, best_j, n_acc, r_pos,
                                         active)

            # next round's accepted block: [accepted chain prefix, bonus]
            blk = jnp.zeros((R, d + 1), jnp.int32)
            idx = jnp.arange(d + 1)[None, :]
            blk = jnp.where(idx < n_acc[:, None],
                            jnp.pad(best_chain, ((0, 0), (0, 1))), blk)
            blk = jnp.where(idx == n_acc[:, None], bonus[:, None], blk)
            new_nblk = n_acc + 1
            new_base = r_pos + 1
        return (llm_state, ssm_states, blk, new_nblk, new_base, best_chain,
                n_acc, bonus)

    _block_impl = _block_impl
    run_block = run_block


class BeamSpecEngine:
    """Fused beam-width>1 single-SSM speculation: one device call per
    block of rounds (reference BeamSearchBatchConfig beam expansion +
    BeamTopK parent tracking + per-beam KV,
    spec_inc_multihead_self_attention.cu — the host-stepped twin is
    RequestManager._draft_beams / _generate_spec_tree_host).

    TPU-first: the NODE LAYOUT is compile-time static — node 0 is the
    root, beam step t's W selected children occupy indices
    [1 + t*W, 1 + (t+1)*W) — while the parent pointers, ancestor mask,
    and cumulative log-probs are DYNAMIC data on that static shape. The
    frontier is always the newest W nodes (static indices), so every
    beam step is one staged tree forward + a top-W select, all inside
    the jitted round:

    * catch-up chain pass over last round's accepted block doubles as
      the root expansion (packed [top-W probs, top-W ids] output at the
      block's last real token);
    * beam steps re-stage the accumulated tree (tree attention gives
      every frontier node its ancestor-path context — no per-beam KV);
    * candidates = W frontier x W children; jnp.log(f32) cumulative
      scores; lax.top_k picks the next level (ties resolve to the lower
      flattened (frontier, child) index, mirroring the host's stable
      sort over frontier-major candidate lists);
    * the LLM verifies the whole tree once; greedy acceptance walks the
      levels (a child survives iff its parent is on the accepted path
      and its token equals the verifier's argmax at that parent);
    * accepted nodes' KV compacts from their staged slots into the
      committed region (the reference's commit_tokens_kernel).
    """

    def __init__(self, llm, ssm, depth: int = 4, width: int = 2,
                 max_rounds: int = 16):
        self.llm = llm
        self.ssms = [ssm]
        llm.finalize_pipeline()
        ssm.finalize_pipeline()
        self.depth = depth
        self.width = width
        self.max_rounds = max_rounds
        self.telemetry = None   # explicit ServingTelemetry; None -> global
        self._compute_dtype = jnp.dtype(llm.config.compute_dtype)
        from flexflow_tpu.kernels.attention import SUBLANE, round_up

        self.T = 1 + depth * width            # real tree nodes
        self.tree_width = round_up(max(self.T, depth + 1), SUBLANE)
        # the scheduler's gate (MultiSpecEngine.room): one position more
        # than live_mask asks, the bound this engine has always been
        # scheduled under
        self.room = self.tree_width + 1
        # node depth is a static function of the layout
        nd = np.zeros((self.tree_width,), np.int32)
        for t in range(depth):
            nd[1 + t * width: 1 + (t + 1) * width] = t + 1
        self._depth_of = jnp.asarray(nd)
        self._block = serving_jit(llm.config, self._block_impl,
                                  donate_argnums=(1, 3))
        # jit-cache accounting (see MultiSpecEngine.__init__)
        self._trace_count = 0
        self._traces_reported = 0
        self._rng_const = jax.random.PRNGKey(llm.config.seed)

    def _select(self, cand, ids_flat, par_flat):
        """top-W over the flattened candidate scores; returns
        (cum [R,W], tokens [R,W], parents [R,W])."""
        W = self.width
        cum, idx = jax.lax.top_k(cand, W)
        tok = jnp.take_along_axis(ids_flat, idx, axis=1).astype(jnp.int32)
        par = jnp.take_along_axis(par_flat, idx, axis=1).astype(jnp.int32)
        return cum, tok, par

    def _round(self, llm_params, llm_state, ssm_ps, ssm_states, tks, nblk,
               base, active, rng, depth_r):
        from flexflow_tpu.serve.batch_config import TreeBatchMeta

        (ssm,), (ssm_params,), (ssm_state,) = self.ssms, ssm_ps, ssm_states

        d, W, T, Tp = self.depth, self.width, self.T, self.tree_width
        R = tks.shape[0]
        r_pos = base + nblk - 1
        # deepest active row's controller depth: beam levels past it are
        # skipped entirely (lax.cond — the node layout stays compile-time
        # static, the level's tree forward just doesn't execute)
        d_run = jnp.max(jnp.where(active, depth_r, 1))

        # --- catch-up + root expansion (one causal pass, width d+1) ---
        pos = base[:, None] + jnp.arange(d + 1)[None, :]
        num = jnp.where(active, nblk, 0)
        with jax.named_scope("draft"):
            out0, ssm_state = forward_with_meta(
                ssm, ssm_params, ssm_state,
                BatchMeta(tokens=tks, positions=pos, start_pos=base,
                          num_tokens=num, active=active),
                jax.random.fold_in(rng, 0), self._compute_dtype,
                kv_contiguous=True)                       # [R, d+1, 2W]
        root_out = jnp.take_along_axis(
            out0, jnp.maximum(nblk - 1, 0)[:, None, None], axis=1)[:, 0]
        root = jnp.take_along_axis(
            tks, jnp.maximum(nblk - 1, 0)[:, None], axis=1)[:, 0]

        tokens = jnp.zeros((R, Tp), jnp.int32).at[:, 0].set(root)
        parent = jnp.full((R, Tp), -1, jnp.int32)
        anc = jnp.zeros((R, Tp, Tp), bool)
        anc = anc.at[:, 0, 0].set(True)
        positions = r_pos[:, None] + self._depth_of[None, :]

        def place_level(t, carry, cand, ids_flat, par_flat):
            """top-W select + static-slot node placement for level t."""
            ssm_state, tokens, parent, anc, cum = carry
            cum, tok_new, par_new = self._select(cand, ids_flat, par_flat)
            lvl0 = 1 + t * W
            tokens = jax.lax.dynamic_update_slice(tokens, tok_new,
                                                  (0, lvl0))
            parent = jax.lax.dynamic_update_slice(parent, par_new,
                                                  (0, lvl0))
            # ancestor rows: child's row = parent's row | self
            par_rows = jnp.take_along_axis(
                anc, par_new[:, :, None].clip(0), axis=1)   # [R, W, Tp]
            selfhot = jax.nn.one_hot(lvl0 + jnp.arange(W), Tp,
                                     dtype=bool)[None]
            anc = jax.lax.dynamic_update_slice(
                anc, par_rows | selfhot, (0, lvl0, 0))
            return (ssm_state, tokens, parent, anc, cum)

        def expand_level(t, carry):
            """Stage the accumulated tree on the draft and grow level t
            (t >= 1; level 0 reuses the catch-up pass's root expansion)."""
            ssm_state, tokens, parent, anc, cum = carry
            meta = TreeBatchMeta(
                tokens=tokens, positions=positions, parent=parent,
                ancestor=anc, start_pos=r_pos,
                num_nodes=jnp.where(active, 1 + t * W, 0)
                .astype(jnp.int32), active=active)
            out, ssm_state = forward_with_meta(
                ssm, ssm_params, ssm_state, meta,
                jax.random.fold_in(rng, 1 + t), self._compute_dtype,
                kv_contiguous=True)               # [R, Tp, 2W]
            f0 = 1 + (t - 1) * W
            probs = out[:, f0:f0 + W, :W].astype(jnp.float32)
            ids = out[:, f0:f0 + W, W:2 * W]
            # candidate (fi, j) -> flat fi*W + j, frontier-major like
            # the host's stable sort order
            cand = (cum[:, :, None]
                    + jnp.log(jnp.maximum(probs, 1e-20))
                    ).reshape(R, W * W)
            ids_flat = ids.reshape(R, W * W)
            par_flat = jnp.broadcast_to(
                (f0 + jnp.arange(W))[None, :, None], (R, W, W)
            ).reshape(R, W * W)
            return place_level(t, (ssm_state, tokens, parent, anc, cum),
                               cand, ids_flat, par_flat)

        with jax.named_scope("draft"):
            cum = jnp.zeros((R, W), jnp.float32)
            carry = (ssm_state, tokens, parent, anc, cum)
            # level 0 always runs (d_run >= 1): candidates come straight from
            # the catch-up pass's packed root expansion
            carry = place_level(
                0, carry,
                jnp.log(jnp.maximum(root_out[:, :W].astype(jnp.float32),
                                    1e-20)),
                root_out[:, W:2 * W], jnp.zeros((R, W), jnp.int32))
            for t in range(1, d):
                # controller early-exit: levels past the round's deepest
                # active row skip their tree forward entirely (their static
                # node slots keep zeros, which the capped acceptance walk
                # below never reaches)
                carry = jax.lax.cond(d_run > t,
                                     lambda c, t=t: expand_level(t, c),
                                     lambda c: c, carry)
            (ssm_state, tokens, parent, anc, cum) = carry

        # --- verify the whole tree on the LLM ---
        with jax.named_scope("verify"):
            meta_v = TreeBatchMeta(
                tokens=tokens, positions=positions, parent=parent,
                ancestor=anc, start_pos=r_pos,
                num_nodes=jnp.where(active, T, 0).astype(jnp.int32),
                active=active)
            out_v, llm_state = forward_with_meta(
                self.llm, llm_params, llm_state, meta_v,
                jax.random.fold_in(rng, 7), self._compute_dtype,
                kv_contiguous=True)
            o = out_v.astype(jnp.int32)                   # [R, Tp]

        # --- greedy acceptance walk over the levels ---
        with jax.named_scope("commit"):
            cur = jnp.zeros((R,), jnp.int32)
            alive = active
            n_acc = jnp.zeros((R,), jnp.int32)
            path = jnp.zeros((R, d), jnp.int32)
            for t in range(d):
                lvl0 = 1 + t * W
                tok_lvl = jax.lax.dynamic_slice(tokens, (0, lvl0), (R, W))
                par_lvl = jax.lax.dynamic_slice(parent, (0, lvl0), (R, W))
                want = jnp.take_along_axis(o, cur[:, None], axis=1)[:, 0]
                # depth_r caps the accepted path per row (controller contract)
                ok = ((par_lvl == cur[:, None]) & (tok_lvl == want[:, None])
                      & alive[:, None] & (depth_r > t)[:, None])
                has = jnp.any(ok, axis=1)
                nxt = lvl0 + jnp.argmax(ok, axis=1).astype(jnp.int32)
                path = path.at[:, t].set(jnp.where(has, nxt, 0))
                cur = jnp.where(has, nxt, cur)
                n_acc = n_acc + has.astype(jnp.int32)
                alive = alive & has
            bonus = jnp.take_along_axis(o, cur[:, None], axis=1)[:, 0]

            # --- KV commit: staged slot r_pos+path[t] -> r_pos+1+t ---
            llm_state = self._commit(llm_state, path, n_acc, r_pos, active)

            chain = jnp.take_along_axis(tokens, path, axis=1)   # [R, d]
            blk = jnp.zeros((R, d + 1), jnp.int32)
            idx = jnp.arange(d + 1)[None, :]
            blk = jnp.where(idx < n_acc[:, None],
                            jnp.pad(chain, ((0, 0), (0, 1))), blk)
            blk = jnp.where(idx == n_acc[:, None], bonus[:, None], blk)
        return (llm_state, [ssm_state], blk, n_acc + 1, r_pos + 1, chain,
                n_acc, bonus)

    def _commit(self, llm_state, path, n_acc, r_pos, active):
        """cache[r, :, r_pos+1+i] <- cache[r, :, r_pos+path[r, i]] for
        i < n_acc, all layers (path holds staged NODE indices)."""
        d = self.depth
        refuse_windowed(llm_state, "a speculation commit (move_kv)")
        st = llm_state["kv_cache"]

        def move(cache):                            # [L, R, KH, S, D]
            L, R, KH = cache.shape[:3]
            S = self.llm.config.max_sequence_length
            pack = kvl.pack_of(cache, S)
            i = jnp.arange(d)[None, :]
            src = r_pos[:, None] + path
            src = jnp.clip(src, 0, S - 1)
            if pack > 1:        # stored packed: ops/kv_layout.py
                return move_kv(cache, src, r_pos + 1, n_acc, active, pack)
            moved = jnp.take_along_axis(
                cache, src[None, :, None, :, None], axis=3)  # [L,R,KH,d,D]
            valid = (i < n_acc[:, None]) & active[:, None]
            dst = jnp.where(valid, r_pos[:, None] + 1 + i, S)
            lidx = jnp.broadcast_to(
                jnp.arange(L)[:, None, None, None], (L, R, KH, d))
            rows = jnp.broadcast_to(
                jnp.arange(R)[None, :, None, None], (L, R, KH, d))
            heads = jnp.broadcast_to(
                jnp.arange(KH)[None, None, :, None], (L, R, KH, d))
            dstb = jnp.broadcast_to(dst[None, :, None, :], (L, R, KH, d))
            return cache.at[lidx, rows, heads, dstb].set(moved, mode="drop")

        return {**llm_state,
                "kv_cache": {"k": move(st["k"]), "v": move(st["v"])}}

    _block_impl = _block_impl
    run_block = run_block
