"""InferenceManager: compiles and dispatches serving step programs.

Capability parity with the reference InferenceManager (reference
src/runtime/inference_manager.cc: compile_model_and_allocate_buffer :81,
init_operators_inference :226, inference() :290 which walks operators calling
op->inference per batch). TPU-first: instead of per-op Legion index launches
with multi-copy buffers for in-flight batches, the whole forward over a batch
is ONE jitted SPMD program; the KV caches (the only cross-step mutable
buffers) are donated pytree state, so XLA aliases them in place. Distinct
per-step token widths (decode=1, prefill chunk, tree size) each trace once —
the compiled-program cache plays the role of the reference's Legion traces.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from flexflow_tpu.ops.base import OpContext


class BlockPasses(NamedTuple):
    """What a decode block of a block-diffusion model hands back, a row a
    slot: the tokens of the blocks its passes left whole
    (``tokens[r, :count[r]]``), the window it carries into its next call
    (``block`` [R, 2B], the positions from the row's stored length on, -1:
    still masked; a whole first block is emitted and not stored yet), and
    ``stats``, the row's engine.PASS_STATS (tokens emitted, passes run,
    blocks a pass stored in front of the next, positions unmasked above the
    threshold and by the floor)."""

    tokens: np.ndarray
    block: np.ndarray
    stats: Dict[str, np.ndarray]

    @property
    def count(self) -> np.ndarray:
        return self.stats["count"]

    @property
    def block_length(self) -> int:
        return self.block.shape[1] // 2

    @property
    def stored(self) -> np.ndarray:
        """Positions a row's passes added to its cache."""
        return self.stats["folded"] * self.block_length


class LaunchedBlock(NamedTuple):
    """A decode block between its two ends
    (``InferenceManager.launch_decode_block`` / ``read_decode_block``): the
    packed output, still on the device, and what unpacks it."""

    toks: Any           # int32 [R, ...], the device's future
    n_steps: int
    width: int


class InferenceManager:
    """Owns the jitted step functions for one FFModel serving graph."""

    def __init__(self, model):
        self.model = model
        model.finalize_pipeline()   # no-op unless a pipeline plan is pending
        if model._pp_plan is not None and model.config.inference_debugging:
            raise NotImplementedError(
                "inference_debugging dumps need per-layer params; not "
                "available with pipeline_parallelism_degree > 1")
        from flexflow_tpu.serve.engine import serving_jit

        cfg = model.config
        self._compute_dtype = jnp.dtype(cfg.compute_dtype)
        self._step = serving_jit(cfg, self._step_impl, donate_argnums=(1,))
        self._prefill = serving_jit(cfg, self._prefill_impl,
                                    donate_argnums=(1,))
        self._rng = jax.random.PRNGKey(cfg.seed)
        self._decode_block = None
        self._decode_block_width = 0    # the width _decode_block was built at
        self._verify_width = 0          # verified_at: 0 = no engine verifies
        self._debug_step = 0

    @property
    def decode_width(self) -> int:
        """Tokens a row of a fused decode step: ``config.decode_width``
        where it is set, else the model's block length where it fills
        blocks (``FFModel.block_diffusion``: every one of them real), else
        the verify width of the speculation engine
        that verifies this model (``verified_at``), else 1. A wider step
        carries ONE real token a row and is verify-consistent: the program
        shapes of the verify pass, so near-tie argmaxes of the incremental
        and the speculative path of one model resolve alike (the
        reference's spec-vs-incr 30-token CI gate). A model no engine
        verifies has nothing to agree with and pays for no padding."""
        bd = getattr(self.model, "block_diffusion", None)
        return (int(self.model.config.decode_width)
                or (bd.block_length if bd is not None else 0)
                or self._verify_width or 1)

    def verified_at(self, width: int):
        """Whoever builds or fetches the speculation engine over this model
        (RequestManager._engine_of: the loops, and a front door handed
        draft models) says that it verifies the model ``width`` tokens a
        row, the engine's ``tree_width``. A decode block built at another
        width is dropped by its next call."""
        self._verify_width = int(width)

    def _step_impl(self, params, op_state, meta, rng):
        from flexflow_tpu.serve.engine import forward_with_meta

        return forward_with_meta(self.model, params, op_state, meta, rng,
                                 self._compute_dtype)

    def _prefill_impl(self, params, op_state, meta, rng):
        """The output-free step's program: the graph up to its tail
        (engine._tail_of: final norm, head, pick), whose inputs it hands
        back where ``_step_impl`` hands back the pick. A result of a
        program is something XLA must compute, so a step whose pick nobody
        reads leaves the head out only as a program of its own. Handed
        back WHOLE, every position of the step: what a pipeline stage of a
        deployment hands the next, so the last layer is all there (a slice
        would let XLA thin it), and what the step's timer waits on."""
        from flexflow_tpu.serve.engine import _tail_of, forward_with_meta

        return forward_with_meta(self.model, params, op_state, meta, rng,
                                 self._compute_dtype,
                                 outputs=_tail_of(self.model).inputs)

    def step(self, meta, want_output: bool = True, tel=None, rnd=None):
        """Run one serving step; threads the model's KV caches through.

        Returns the op outputs (token ids [R, Q] for graphs ending in
        argmax/sampling). The model's op_state is replaced (old state was
        donated to the device program). ``want_output=False`` (a prefill
        chunk: the scheduler holds a prompt's last token back and the
        decode block emits the first) runs ``_prefill_impl``, a program
        without the graph's tail, which writes the caches ``_step_impl``
        writes, bit for bit, and computes no logits; it skips the blocking
        device->host readback too, so the step dispatches asynchronously
        and overlaps with the host building the next batch. Such a step
        hands back the last layer's hidden state ``[rows, chunk, hidden]``
        (a tuple of the tail's inputs) as the device's future, which is not
        donated onward as the op_state is, so whoever times the step can
        wait on it (never read it) after later calls have been launched.
        ``tel`` (a ServingTelemetry; None: no
        spans) records the call's ``call_stage`` / ``call_launch`` /
        ``call_wait`` leaves; an output-free step is program ``prefill``
        and its wait is its caller's (telemetry.PendingPrefill). ``rnd``:
        as in ``decode_block``, for a step whose output is read.
        """
        ph, prog = None, "step" if want_output else "prefill"
        if tel is not None:
            tel.watch_model(self.model)     # its on-device counters
            ph = tel.call_phase(None, "call_stage", prog)
        self._rng, step_rng = jax.random.split(self._rng)
        if self.model.config.inference_debugging:
            # reference inference_debugging mode: dump every op's
            # inputs/weights/outputs for this step (operator.cc:29) before
            # the jitted step consumes (donates) the current op_state
            from flexflow_tpu.utils.debugging import dump_serving_step

            dump_serving_step(self.model, meta, "./inference_tensors",
                              self._debug_step, rng=step_rng)
            self._debug_step += 1
        if tel is not None:
            ph = tel.call_phase(ph, "call_launch", prog)
        program = self._step if want_output else self._prefill
        out, new_state = program(self.model.params, self.model.op_state,
                                 meta, step_rng)
        self.model.op_state = new_state
        if not want_output:
            if tel is not None:
                tel.call_phase(ph, None)
            return out
        if tel is not None:
            tel.call_phase(ph, None)
            if rnd is not None:
                rnd.settle()
            ph = tel.call_phase(None, "call_wait", prog)
        out = np.asarray(out)
        if tel is not None:
            tel.call_phase(ph, None)
        return out

    def decode_block(self, tok: np.ndarray, pos: np.ndarray,
                     active: np.ndarray, n_steps: int,
                     tel=None, rnd=None) -> np.ndarray:
        """Run ``n_steps`` fused decode steps in ONE device program.

        The TPU answer to the reference's depth-4 in-flight Legion batch
        pipeline (request_manager.cc:1829): instead of pipelining host-built
        batches, the whole token-feedback loop runs on device via a
        dynamic-trip while_loop — one host round-trip AND one compiled
        program for every block size. Returns int32 [R, n_steps]. For a
        block-diffusion model a step is a pass over a block: ``tok`` is
        the rows' windows ``[R, 2 * decode_width]`` (-1: a masked
        position), ``pos`` the lengths their caches hold, and the return a
        ``BlockPasses`` (engine._diffusion_block).
        The call is its two ends, one after the other: whoever has work
        to queue behind the running block (the incremental loop: the next
        round's first prefill step) calls ``launch_decode_block``, then
        ``read_decode_block``. ``tel``, ``rnd``: as there.
        """
        return self.read_decode_block(
            self.launch_decode_block(tok, pos, active, n_steps, tel, rnd),
            tel)

    def launch_decode_block(self, tok, pos, active, n_steps: int,
                            tel=None, rnd=None) -> LaunchedBlock:
        """Stage and dispatch a decode block; nothing is waited for but
        ``rnd``'s pending prefill step. ``tel``: as in ``step`` (program
        ``decode_block``; the ``call_wait`` leaf is ``read_decode_block``'s).
        ``rnd`` (the caller's telemetry.RoundTrace; None: it has none) may
        hold a prefill step that was launched before this block and is not
        waited for yet: its wait comes once the block is queued behind it,
        after this call's ``call_launch``."""
        from flexflow_tpu.serve.engine import make_decode_block

        if self.model.config.inference_debugging:
            # debug mode serializes decode into per-step step() calls so
            # every decode token's op tensors are dumped (the fused
            # while_loop body cannot host-dump); same numerics, slower.
            return LaunchedBlock(
                self._decode_block_debug(tok, pos, active, n_steps),
                n_steps, 1)
        width = self.decode_width
        if self._decode_block_width != width:
            self._decode_block = make_decode_block(
                self.model, self._compute_dtype,
                self.model.config.decode_block_steps, width=width)
            self._decode_block_width = width
        n_steps = min(int(n_steps), self.model.config.decode_block_steps)
        ph = None
        if tel is not None:
            ph = tel.call_phase(None, "call_stage", "decode_block")
        self._rng, step_rng = jax.random.split(self._rng)
        args = (jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(active),
                step_rng, jnp.int32(n_steps))
        if tel is not None:
            ph = tel.call_phase(ph, "call_launch", "decode_block")
        toks, new_state, _last = self._decode_block(
            self.model.params, self.model.op_state, *args)
        self.model.op_state = new_state
        if tel is not None:
            tel.call_phase(ph, None)
            if rnd is not None:
                rnd.settle()
        return LaunchedBlock(toks, n_steps, width)

    def read_decode_block(self, launched: LaunchedBlock, tel=None):
        """Read a launched block back (the blocking device->host read, the
        call's ``call_wait`` leaf) and unpack it: what ``decode_block``
        returns. Calls launched after the block (a prefill step queued
        behind it) delay nothing: the device runs them in launch order."""
        toks, n_steps, width = launched
        if isinstance(toks, np.ndarray):    # the debug path's: read already
            return toks
        ph = None
        if tel is not None:
            ph = tel.call_phase(None, "call_wait", "decode_block")
        toks = np.asarray(toks)
        if tel is not None:
            tel.call_phase(ph, None)
        if getattr(self.model, "block_diffusion", None) is None:
            return toks[:, :n_steps]
        from flexflow_tpu.serve.engine import PASS_STATS

        stats = dict(zip(PASS_STATS, toks[:, -len(PASS_STATS):].T))
        emitted = toks.shape[1] - 2 * width - len(PASS_STATS)
        return BlockPasses(toks[:, :emitted],
                           toks[:, emitted:emitted + 2 * width], stats)

    def _decode_block_debug(self, tok, pos, active, n_steps: int):
        from flexflow_tpu.serve.batch_config import BatchMeta

        R = tok.shape[0]
        W = self.decode_width     # keep the fused path's step width, so
        cur = np.asarray(tok, np.int32).copy()
        p = np.asarray(pos, np.int32).copy()
        act = np.asarray(active, bool)
        out = np.zeros((R, n_steps), np.int32)
        for j in range(n_steps):
            # the dumped run reproduces the SAME tokens (a width-1 debug
            # step would re-introduce exactly the wide-vs-narrow gemm
            # tiling argmax divergence decode_width eliminates)
            toks = np.zeros((R, W), np.int32)
            toks[:, 0] = cur
            qpos = p[:, None] + np.arange(W, dtype=np.int32)[None, :]
            meta = BatchMeta(
                tokens=toks, positions=qpos, start_pos=p.copy(),
                num_tokens=act.astype(np.int32), active=act)
            step_out = self.step(meta)            # dumps + advances caches
            nxt = np.asarray(step_out).reshape(R, -1)[:, 0].astype(np.int32)
            out[:, j] = np.where(act, nxt, 0)
            cur = np.where(act, nxt, cur)
            p = p + act.astype(np.int32)
        return out
