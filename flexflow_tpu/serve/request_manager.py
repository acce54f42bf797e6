"""RequestManager: continuous batching + speculative-inference orchestration.

Capability parity with the reference RequestManager (reference
src/runtime/request_manager.cc, 1,953 LoC): register_new_request (tokenize +
queue), prepare_next_batch{,_init,_beam,_verify} scheduling, the incremental
generation loop (generate_incr_decoding :1810) and the speculative loop
(generate_spec_infer :1867 — SSM beam expansion, merge_dfs_trees, LLM tree
verification, token commit).

TPU-first: the reference chains Legion futures so batches pipeline on GPUs;
here each step is an async-dispatched jitted program (JAX dispatch returns
before the TPU finishes, giving the same overlap), and the per-step batch
descriptors are built host-side in numpy. Speculation state (per-SSM cache
validity, token trees) lives in plain Python — only the step programs and the
KV commit run on device.

Slot/convention notes:
* A request's ``tokens`` = prompt + generated. ``cache_depth`` counts tokens
  whose KV is in a model's cache. What a decode step is fed is "pending":
  the last token, which produces the next one (matching the reference's
  per-request ``token_start_offset``/depth bookkeeping,
  batch_config.h:66-75); for a block-diffusion model
  (``FFModel.block_diffusion``) the remainder of the last whole block, 0 to
  B-1 tokens, which begin the block that the row's passes fill, and once
  the row decodes whatever its window holds (``Request.block``: a block
  that is whole is emitted before the pass that stores it, so
  ``cache_depth`` may be a block behind). A decode block yields one token a
  row a step, or for such a model a count of tokens a row;
  ``_stage_decode`` and ``_commit_decode`` are the two places that know
  which.
* Speculation runs through ONE fused loop (``_generate_spec_fused``, over
  engine.MultiSpecEngine or engine.BeamSpecEngine) and one host-stepped
  reference loop (``_generate_spec_tree_host``: ``inference_debugging``
  dumps and several drafts' merged beams, committing with
  ``commit_tree_kv``); ``_spec_route`` chooses, by the request alone.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

import jax
import jax.numpy as jnp

from flexflow_tpu.serve.batch_config import (
    BatchMeta,
    TreeBatchMeta,
    GenerationConfig,
    MAX_BEAM_DEPTH,
    ancestor_mask_from_parents,
)
from flexflow_tpu.serve.inference_manager import (BlockPasses,
                                                  InferenceManager)
from flexflow_tpu.serve.step_costs import StepCosts
from flexflow_tpu.ops.inc_attention import (commit_tree_kv,
                                            refuse_block_diffusion)
from flexflow_tpu.ops.loop import refuse_looped
from flexflow_tpu.telemetry import (PendingPrefill, get_telemetry,
                                    mint_trace_id)
from flexflow_tpu.utils.profiling import device_fence


@dataclasses.dataclass
class Request:
    """One generation request (reference request_manager.h Request)."""

    guid: int
    prompt_tokens: List[int]
    max_new_tokens: int = 128
    max_sequence_length: int = 0          # 0 -> model max_sequence_length
    tokens: List[int] = dataclasses.field(default_factory=list)
    slot: int = -1
    cache_depth: int = 0                  # verifier/incr cache depth
    ssm_cache_depth: Dict[int, int] = dataclasses.field(default_factory=dict)
    finished: bool = False
    # lifecycle timestamps (time.perf_counter; always recorded — three
    # clock reads per request lifetime — so GenerationResult latency
    # fields exist even with telemetry disabled). prefill_start_s is
    # stamped when the request wins a batch slot (admission -> slot is
    # the queue wait; slot -> first token is the service time to first
    # token).
    arrival_s: float = 0.0
    prefill_start_s: float = 0.0
    first_token_s: float = 0.0
    # overload front door (ISSUE 16): tenant/priority drive admission
    # buckets and slot scheduling; deadline_s is an ABSOLUTE
    # time.perf_counter() instant (0.0 = none) — expiry and host
    # cancellation are reaped between decode rounds (_reap_expired).
    # ``status`` is the terminal disposition recorded on the result.
    tenant: str = "default"
    priority: int = 0
    deadline_s: float = 0.0
    status: str = "ok"            # ok|timed_out|cancelled|error|rejected
    error: str = ""
    cancel_requested: bool = False
    preemptions: int = 0
    # fleet failover (serve/replica.py): how many times this request was
    # re-dispatched to a surviving replica after an engine crash (the
    # pool re-registers the prompt, so a replica-level Request usually
    # carries the count it was re-created with)
    failovers: int = 0
    # fleet-wide correlation id minted at the front door
    # (telemetry.mint_trace_id); survives failover re-registration and
    # preemption re-queues, and joins this request's Chrome-trace spans
    # across replica pid rows. "" = minted locally at registration.
    trace_id: str = ""
    # shared-prefix KV cache (serve/prefix_cache.py, ISSUE 19):
    # prefix_entry holds a refcounted pool handle from the admission-time
    # radix match (released at _collect); prefix_len is how many leading
    # prompt positions the pooled segment covers (installed into the
    # slot's KV at grant, skipping those prefill FLOPs — and again after
    # a preemption re-queue resets cache_depth). prefix_hit_tokens rides
    # onto the GenerationResult for loadgen's reuse accounting.
    prefix_entry: Any = None
    prefix_len: int = 0
    prefix_hit_tokens: int = 0
    prefix_checked: bool = False
    # a block-diffusion row's window, the positions [cache_depth,
    # cache_depth + 2B), as its last decode block left it (-1: a position
    # still masked; a whole first block is in ``tokens`` already and its
    # keys and values are stored by the row's next pass); None: the row
    # begins a block from its pending tokens
    block: Any = None

    def __post_init__(self):
        if not self.tokens:
            self.tokens = list(self.prompt_tokens)

    @property
    def num_generated(self) -> int:
        return len(self.tokens) - len(self.prompt_tokens)


@dataclasses.dataclass
class GenerationResult:
    """Reference include/flexflow/inference.h GenerationResult."""

    guid: int
    input_tokens: List[int]
    output_tokens: List[int]
    input_text: str = ""
    output_text: str = ""
    # per-request latency (reference serving writes latency per request
    # to -output-file; here it rides on the result object): admission ->
    # finish, and admission -> first generated token (0.0 for a request
    # that ended before generating one)
    latency_s: float = 0.0
    ttft_s: float = 0.0
    # queue-wait vs service decomposition (SLO observability, loadgen):
    # admission -> batch-slot grant, and slot grant -> first generated
    # token. ttft_s == queue_wait_s + prefill_s wherever both are set;
    # 0.0 for a request that never won a slot or generated no token.
    queue_wait_s: float = 0.0
    prefill_s: float = 0.0
    # terminal disposition (overload front door): "ok", "timed_out"
    # (deadline expired between rounds — output_tokens holds the partial
    # prefix generated so far), "cancelled" (host-side cancel), or
    # "error" (the serving loop died; ``error`` carries the message), or
    # "rejected" (the prompt can never fit max_sequence_length; ``error``
    # says so — long-context admission instead of a silent empty result).
    # Every registered request ALWAYS gets a result with one of these —
    # the every-future-resolves invariant serve/faultinject.py checks.
    status: str = "ok"
    timed_out: bool = False
    cancelled: bool = False
    error: str = ""
    tenant: str = "default"
    preemptions: int = 0
    # times the request was re-dispatched to another replica after a
    # crash (serve/replica.py failover; re-prefilled, token-identical)
    failovers: int = 0
    # fleet-wide correlation id (see Request.trace_id)
    trace_id: str = ""
    # leading prompt tokens served from the shared-prefix KV pool
    # (serve/prefix_cache.py) — prefill FLOPs skipped; 0 = cold prefill
    prefix_hit_tokens: int = 0


class RequestManager:
    """Continuous-batching scheduler over request slots."""

    _guid_counter = itertools.count(1000000)

    def __init__(self, tokenizer=None, eos_token_id: Optional[int] = None,
                 max_requests_per_batch: Optional[int] = None,
                 telemetry=None):
        self.tokenizer = tokenizer
        self.eos_token_id = eos_token_id
        self.pending: deque = deque()
        self.results: Dict[int, GenerationResult] = {}
        # every registered-but-unfinished request, pending OR slotted —
        # the cancel/abort surface (entries removed at _collect)
        self.inflight: Dict[int, Request] = {}
        # deadline-aware preemption (ISSUE 16c): a pending request whose
        # deadline has burned down past preempt_risk of its total budget
        # may evict a strictly-lower-priority running request
        self.preempt_enabled = True
        self.preempt_risk = 0.5
        self.max_spec_depth = MAX_BEAM_DEPTH
        self._commit = jax.jit(commit_tree_kv, donate_argnums=(0,),
                               static_argnames=("max_seq",))
        self.output_filepath: Optional[str] = None
        # explicit ServingTelemetry, or None -> the process-global one
        # (resolved per loop iteration, so enabling mid-session attaches)
        self.telemetry = telemetry
        # shared-prefix KV pool (serve/prefix_cache.PrefixCache), or
        # None = feature off. Attached directly, or lazily from
        # GenerationConfig.prefix_cache at the first generate call —
        # once attached it persists across generate calls so pooled
        # prefixes survive between serving rounds.
        self.prefix_cache = None
        # which scheduler loop served the last generate call:
        # "python[:<spec loop>]"; callers that report where a run happened
        # (the benchmark's families, chip_smoke.py) read it from here.
        self.scheduler_loop: Optional[str] = None

    def _tel(self):
        return self.telemetry if self.telemetry is not None \
            else get_telemetry()

    def register_output_filepath(self, path: str):
        """Per-request output log (reference register_output_filepath :155:
        serving writes each request's text + latency to -output-file)."""
        self.output_filepath = path
        open(path, "w").close()  # truncate like the reference

    # -- registration (reference register_new_request, tokenization) -------
    def register_tokenizer(self, tokenizer, eos_token_id=None):
        self.tokenizer = tokenizer
        if eos_token_id is None:
            eos_token_id = getattr(tokenizer, "eos_token_id", None)
        self.eos_token_id = eos_token_id

    def register_new_request(self, prompt: Union[str, Sequence[int]],
                             max_new_tokens: int = 128,
                             max_sequence_length: int = 0,
                             timeout_s: Optional[float] = None,
                             deadline_s: Optional[float] = None,
                             tenant: str = "default",
                             priority: int = 0,
                             trace_id: Optional[str] = None,
                             failovers: int = 0,
                             preemptions: int = 0) -> int:
        """Register one request. ``timeout_s`` is relative to arrival;
        ``deadline_s`` is an absolute time.perf_counter() instant (wins
        when both are given). An expired request is cancelled between
        decode rounds with its partial output (``timed_out=True``).

        ``trace_id`` is the fleet-wide correlation id; the replica pool
        passes the one it minted at the front door (so a failed-over
        request keeps its id across replicas — ``failovers``/
        ``preemptions`` carry the prior-life counts the same way), and a
        standalone manager mints its own."""
        if isinstance(prompt, str):
            assert self.tokenizer is not None, "string prompts need a tokenizer"
            toks = list(self.tokenizer.encode(prompt))
        else:
            toks = list(int(t) for t in prompt)
        assert toks, "empty prompt"
        guid = next(self._guid_counter)
        arrival = time.perf_counter()
        if deadline_s is None and timeout_s is not None:
            deadline_s = arrival + timeout_s
        req = Request(guid=guid, prompt_tokens=toks,
                      max_new_tokens=max_new_tokens,
                      max_sequence_length=max_sequence_length,
                      arrival_s=arrival, tenant=tenant, priority=priority,
                      deadline_s=deadline_s or 0.0,
                      trace_id=trace_id or mint_trace_id(),
                      failovers=int(failovers),
                      preemptions=int(preemptions))
        if self.prefix_cache is not None:
            # admission-time prefix detection (ISSUE 19): the radix
            # lookup + refcount happen here so eviction pressure between
            # admission and slot grant can never pull the segment away
            self._prefix_match(req)
        self.pending.append(req)
        self.inflight[guid] = req
        tel = self._tel()
        if tel is not None:
            tel.note_admission(guid, len(toks), max_new_tokens,
                               trace_id=req.trace_id)
        return guid

    def cancel(self, guid: int) -> bool:
        """Request cancellation (LLM.cancel / ffsv_request_cancel). Safe
        from any thread: only sets a flag; the serving loop reaps it at
        the next between-rounds seam on every scheduler path. Returns
        False when the guid is unknown or already finished."""
        req = self.inflight.get(guid)
        if req is None or req.finished:
            return False
        req.cancel_requested = True
        return True

    def abort_outstanding(self, error: BaseException
                          ) -> List[GenerationResult]:
        """Fail every registered-but-unfinished request with ``error``
        (status "error", partial tokens kept). Called when the serving
        loop dies so no submitter waits on a result that will never
        arrive; leaves the manager clean for a server restart."""
        self.pending.clear()
        out = []
        for req in list(self.inflight.values()):
            if req.finished:
                continue
            req.status = "error"
            req.error = f"{type(error).__name__}: {error}"
            req.finished = True
            req.slot = -1
            out.append(self._collect(req))
        return out

    # -- scheduling helpers ------------------------------------------------
    def _finish_if_done(self, req: Request, max_seq: int) -> bool:
        limit = min(req.max_sequence_length or max_seq, max_seq)
        if len(req.tokens) > limit:
            req.tokens = req.tokens[:limit]
        if (req.num_generated >= req.max_new_tokens
                or len(req.tokens) >= limit
                or (self.eos_token_id is not None and req.num_generated > 0
                    and req.tokens[-1] == self.eos_token_id)):
            req.finished = True
        return req.finished

    def _collect(self, req: Request) -> GenerationResult:
        if req.prefix_entry is not None and self.prefix_cache is not None:
            # drop the pool refcount taken at admission (every terminal
            # path funnels through _collect, so no handle leaks)
            self.prefix_cache.release(req.prefix_entry)
            req.prefix_entry = None
        out = req.tokens[len(req.prompt_tokens):]
        now = time.perf_counter()
        res = GenerationResult(
            guid=req.guid,
            input_tokens=list(req.prompt_tokens),
            output_tokens=out,
            latency_s=(now - req.arrival_s) if req.arrival_s else 0.0,
            ttft_s=(req.first_token_s - req.arrival_s)
            if req.first_token_s and req.arrival_s else 0.0,
            queue_wait_s=(req.prefill_start_s - req.arrival_s)
            if req.prefill_start_s and req.arrival_s else 0.0,
            prefill_s=(req.first_token_s - req.prefill_start_s)
            if req.first_token_s and req.prefill_start_s else 0.0,
            status=req.status, timed_out=req.status == "timed_out",
            cancelled=req.status == "cancelled", error=req.error,
            tenant=req.tenant, preemptions=req.preemptions,
            failovers=req.failovers, trace_id=req.trace_id,
            prefix_hit_tokens=req.prefix_hit_tokens)
        self.inflight.pop(req.guid, None)
        tel = self._tel()
        if tel is not None:
            tel.note_finish(req.guid, len(out), res.latency_s, res.ttft_s,
                            queue_wait_s=res.queue_wait_s,
                            prefill_s=res.prefill_s, status=req.status,
                            failovers=req.failovers,
                            preemptions=req.preemptions)
        if self.tokenizer is not None:
            try:
                res.input_text = self.tokenizer.decode(res.input_tokens)
                res.output_text = self.tokenizer.decode(out)
            except Exception:
                pass
        self.results[req.guid] = res
        if self.output_filepath:
            with open(self.output_filepath, "a") as f:
                f.write(f"guid({res.guid})\n"
                        f"input: {res.input_text or res.input_tokens}\n"
                        f"output: {res.output_text or res.output_tokens}\n")
        return res

    def _next_pending(self) -> Optional[Request]:
        """Dequeue the next request to grant a slot: highest priority
        first, FIFO within a priority class (plain FIFO — the historical
        behavior — when every pending priority is equal)."""
        if not self.pending:
            return None
        best_i, best = 0, self.pending[0]
        for i, r in enumerate(self.pending):
            if r.priority > best.priority:
                best_i, best = i, r
        del self.pending[best_i]
        return best

    def _reject_overlong(self, req: Request, limit: int):
        """Long-context admission: a prompt that can never fit the KV cache
        is REJECTED with an explicit status + message instead of silently
        resolving as an empty "ok" result (which callers could not tell
        apart from a 0-token generation)."""
        req.status = "rejected"
        req.error = (
            f"prompt length {len(req.prompt_tokens)} cannot fit "
            f"max_sequence_length {limit}; raise max_sequence_length "
            f"(sequence-parallel serving shards the KV cache over the "
            f"mesh's 'seq' axis — see README, long-context serving) "
            f"or shorten the prompt")
        req.finished = True

    def _grant(self, req: Request, slot: int, active, max_seq: int,
               done: List[GenerationResult]) -> bool:
        """Place ``req`` in ``slot`` (rejecting over-long prompts straight
        to done, the reference behavior). True when the slot was taken."""
        limit = min(req.max_sequence_length or max_seq, max_seq)
        if len(req.prompt_tokens) >= limit:
            self._reject_overlong(req, limit)
            done.append(self._collect(req))
            return False
        req.slot = slot
        req.prefill_start_s = time.perf_counter()
        active[slot] = req
        tel = self._tel()
        if tel is not None:
            tel.note_slot_grant(req.guid, slot)
        return True

    def _fill_slots(self, active: List[Optional[Request]], max_seq: int,
                    done: List[GenerationResult], parked=()):
        for slot in range(len(active)):
            while active[slot] is None and self.pending:
                if self._grant(self._next_pending(), slot, active, max_seq,
                               done):
                    break
        if self.pending and self.preempt_enabled:
            # all slots taken and requests still waiting: deadline-aware
            # preemption may evict a lower-priority victim (ISSUE 16c)
            self._maybe_preempt(active, max_seq, done, parked)

    def _maybe_preempt(self, active, max_seq: int,
                       done: List[GenerationResult], parked=()):
        """At the slot-grant seam: if a pending high-priority request's
        deadline is at risk (more than ``preempt_risk`` of its budget
        already burned waiting), evict a strictly-lower-priority running
        request — preferring ones the speculation controller parked on
        fallback decode, then the fewest generated tokens (cheapest
        re-prefill). The victim is RE-QUEUED, not killed: its prompt +
        generated prefix re-prefill through the chunked path on the next
        grant, so its final tokens are identical (greedy decode depends
        only on the token prefix)."""
        now = time.perf_counter()
        while self.pending:
            cand = None
            for r in self.pending:
                if r.deadline_s <= 0 or r.cancel_requested:
                    continue
                total = max(r.deadline_s - r.arrival_s, 1e-9)
                if (r.deadline_s - now) > self.preempt_risk * total:
                    continue
                if cand is None or r.priority > cand.priority:
                    cand = r
            if cand is None:
                return
            victims = [r for r in active
                       if r is not None and not r.finished
                       and r.priority < cand.priority]
            if not victims:
                return
            victim = min(victims, key=lambda r: (r.guid not in parked,
                                                 r.priority,
                                                 r.num_generated))
            slot = victim.slot
            victim.slot = -1
            victim.cache_depth = 0
            victim.block = None
            victim.ssm_cache_depth.clear()
            victim.preemptions += 1
            victim.prefill_start_s = 0.0
            active[slot] = None
            self.pending.remove(cand)
            self.pending.append(victim)
            tel = self._tel()
            if tel is not None:
                tel.note_preempted(victim.guid)
            self._grant(cand, slot, active, max_seq, done)

    def _reap_expired(self, active, max_seq: int,
                      done: List[GenerationResult], ctrl=None):
        """The between-rounds timeout/cancel seam (ISSUE 16b): resolve
        every pending or slotted request whose deadline expired or whose
        host asked for cancellation — slot freed, partial result
        collected with the matching status. Runs at the top of every
        scheduler-loop iteration on all paths."""
        now = time.perf_counter()

        def expired(r):
            return r.cancel_requested or (r.deadline_s
                                          and now >= r.deadline_s)

        # (a snapshot: the front door appends from its own thread, and a
        # deque refuses to be iterated while it grows)
        if any(expired(r) for r in list(self.pending)):
            for _ in range(len(self.pending)):
                req = self.pending.popleft()
                if expired(req):
                    req.status = ("cancelled" if req.cancel_requested
                                  else "timed_out")
                    req.finished = True
                    done.append(self._collect(req))
                else:
                    self.pending.append(req)
        for slot, req in enumerate(active):
            if req is not None and not req.finished and expired(req):
                req.status = ("cancelled" if req.cancel_requested
                              else "timed_out")
                req.finished = True
                if ctrl is not None:
                    ctrl.drop(req.guid)
                done.append(self._collect(req))
                active[slot] = None

    def _remaining_budget(self, req, max_seq: int) -> int:
        limit = min(req.max_sequence_length or max_seq, max_seq)
        return max(1, min(req.max_new_tokens - req.num_generated,
                          limit - len(req.tokens)))

    # -- shared-prefix KV cache (serve/prefix_cache.py, ISSUE 19) ----------
    def _resolve_prefix_cache(self, gc: Optional[GenerationConfig]):
        """Lazily attach the pool when the generation config asks for it
        (embedded hosts attach eagerly via capi_host so admission-time
        matching covers requests registered before the loop starts)."""
        if (gc is not None and gc.prefix_cache
                and self.prefix_cache is None):
            from flexflow_tpu.serve.prefix_cache import PrefixCache

            self.prefix_cache = PrefixCache(
                max_tokens=gc.prefix_cache_tokens)

    def _prefix_match(self, req: Request):
        """Longest-prefix radix lookup for one request (admission time,
        or grant time for requests admitted before the pool existed)."""
        pc = self.prefix_cache
        req.prefix_checked = True
        if pc is None:
            return
        shared, entry = pc.match(req.prompt_tokens)
        if entry is not None:
            req.prefix_entry = entry
            req.prefix_len = shared
            req.prefix_hit_tokens = shared
        tel = self._tel()
        if tel is not None:
            tel.note_prefix_lookup(shared, pc.pool_tokens)

    def _prefix_install(self, active, pairs):
        """Grant-time KV install: any slotted request holding a pool
        handle with an empty cache (fresh grant, or a preemption
        re-queue that reset cache_depth) gets the shared positions
        copied into its slot caches, and its depth bookkeeping advanced
        past them — those prefill FLOPs are simply skipped. ``pairs``
        is the loop's ordered [("llm", ifm), ("ssm0", ifm), ...]."""
        pc = self.prefix_cache
        if pc is None:
            return
        from flexflow_tpu.serve import prefix_cache as pcm

        for req in active:
            if req is None or req.finished or req.slot < 0:
                continue
            if not req.prefix_checked:
                self._prefix_match(req)
            entry = req.prefix_entry
            if entry is None or req.cache_depth != 0:
                continue
            n = min(req.prefix_len, len(req.tokens) - 1)
            if n <= 0:
                continue
            for key, ifm in pairs:
                segs = entry.segments.get(key)
                max_seq = ifm.model.config.max_sequence_length
                if segs is None or not pcm.prefix_compatible(
                        ifm.model.op_state, segs, n, max_seq):
                    continue    # this model prefills the prefix cold
                ifm.model.op_state = pcm.install_prefix_kv(
                    ifm.model.op_state, req.slot, segs, n, max_seq)
                if key == "llm":
                    req.cache_depth = n
                else:
                    req.ssm_cache_depth[int(key[3:])] = n

    def _prefix_store(self, req: Request, pairs):
        """Insert-on-finish: pool the finished request's prompt KV
        straight out of its still-intact slot (called before the slot is
        cleared). Models whose cache never covered the whole prompt
        (e.g. a draft parked by the controller) are skipped — a later
        reuse just prefills that model cold."""
        pc = self.prefix_cache
        if pc is None or req.slot < 0 or req.status != "ok":
            return
        prompt = req.prompt_tokens
        if req.cache_depth < len(prompt) or not pc.would_store(prompt):
            return
        from flexflow_tpu.serve import prefix_cache as pcm

        segments = {}
        for key, ifm in pairs:
            depth = (req.cache_depth if key == "llm"
                     else req.ssm_cache_depth.get(int(key[3:]), 0))
            if depth < len(prompt):
                continue
            segs = pcm.extract_prefix_kv(
                ifm.model.op_state, req.slot, len(prompt),
                ifm.model.config.max_sequence_length)
            if segs is not None:
                segments[key] = segs
        if "llm" not in segments:
            return
        _entry, evicted = pc.insert(prompt, segments)
        tel = self._tel()
        if tel is not None:
            tel.note_prefix_store(evicted, pc.pool_tokens)

    # -- telemetry hooks (all no-ops when telemetry is disabled) -----------
    @staticmethod
    def _note_first_token(req: Request):
        if not req.first_token_s and req.num_generated > 0:
            req.first_token_s = time.perf_counter()

    def _timed_prefill(self, ifm, meta, tel, rows, active, rnd=None,
                       model="llm"):
        """One prefill step. Nobody reads a pick of it (the scheduler holds
        a prompt's last token back, ``_held_back``, and the decode block
        emits the first), so it runs the output-free program, which ends at
        the last layer's hidden state and computes no logits
        (InferenceManager.step, ``want_output=False``: dispatched
        asynchronously and forgotten); with telemetry on a wait on that
        hidden state, the step's own output, never read and never off the
        chip, times the step and records its spans and counters
        (telemetry.PendingPrefill).

        ``rows``/``active`` feed per-request prefill spans, which carry
        ``model`` (``llm``, or ``ssm<i>`` for draft ``i``). ``rnd`` is
        the round's RoundTrace in the loops that have one: the call's
        ``call_*`` leaves take over from the open phase, and
        ``sched_build`` resumes after them, and the step is waited for
        once the round's NEXT device call has been launched (the next
        step, here; InferenceManager.launch_decode_block or step;
        engine.run_block; failing all, RoundTrace.end; a lead step's round
        is the one after its launch), so the device has
        work queued meanwhile, as it has with telemetry off. Without a
        round (the host-stepped speculation loop) the step is waited for
        at once."""
        if tel is None:
            ifm.step(meta, want_output=False)
            return
        kinds = getattr(ifm.model, "attention_kinds", None)
        if kinds and "chunked" in kinds:
            tel.note_chunked_prefill(kinds["chunked"],
                                     [(sp, len(chunk)) for _, chunk, sp in rows])
        if kinds:       # a tail or a recurrent state: where each came from
            tel.note_prefill_tails(kinds, [(slot, sp, len(chunk))
                                           for slot, chunk, sp in rows])
        if rnd is not None:
            rnd.phase(None)
        step = PendingPrefill(tel, [(active[slot].guid, sp, len(chunk))
                                    for slot, chunk, sp in rows],
                              meta.tokens.size, leaf=rnd is not None,
                              model=model)
        step.out = ifm.step(meta, want_output=False,
                            tel=tel if rnd is not None else None)
        if rnd is None:
            step.settle()
            return
        rnd.settle()            # the step before, now that this is queued
        rnd.pending = step
        rnd.phase("sched_build")

    def _tel_tick(self, tel, live, slots: int, max_seq: int):
        """Once per scheduling tick that dispatches decode/spec work:
        queue depth, batch occupancy, KV-cache utilization."""
        if tel is None:
            return
        kv = (sum(len(r.tokens) for r in live)
              / (len(live) * max_seq)) if live else None
        tel.note_batch(len(self.pending), len(live), slots, kv)

    # -- batch assembly ----------------------------------------------------
    @staticmethod
    def _meta_from_rows(R: int, Q: int, rows) -> BatchMeta:
        """rows: list of (slot, tokens_chunk, start_pos)."""
        tokens = np.zeros((R, Q), np.int32)
        positions = np.zeros((R, Q), np.int32)
        start = np.zeros((R,), np.int32)
        num = np.zeros((R,), np.int32)
        act = np.zeros((R,), bool)
        for slot, chunk, sp in rows:
            n = len(chunk)
            tokens[slot, :n] = chunk
            positions[slot, :n] = np.arange(sp, sp + n)
            start[slot] = sp
            num[slot] = n
            act[slot] = True
        return BatchMeta(tokens=tokens, positions=positions, start_pos=start,
                         num_tokens=num, active=act)

    @staticmethod
    def _meta_from_segments(P: int, Q: int, rows) -> BatchMeta:
        """The compact prefill batch: batch row i is rows[i], a segment
        (slot, tokens_chunk, start_pos) whose cache row goes in ``slots``.
        The rows left over are inactive and point at slot 0."""
        meta = RequestManager._meta_from_rows(
            P, Q, [(i, chunk, sp) for i, (_, chunk, sp) in enumerate(rows)])
        slots = np.zeros((P,), np.int32)
        slots[:len(rows)] = [slot for slot, _, _ in rows]
        return dataclasses.replace(meta, slots=slots)

    @staticmethod
    def _prefill_shape(cfg):
        """(chunk, segments) of a prefill step: the batch's token budget
        over at most four rows, and the rows that budget allows."""
        chunk = max(1, cfg.max_tokens_per_batch
                    // max(1, min(cfg.max_requests_per_batch, 4)))
        return chunk, max(1, cfg.max_tokens_per_batch // chunk)

    @staticmethod
    def _held_back(model):
        """req -> the tokens a prefill leaves pending, the next decode
        step's input: the last token, which emits the next one; for a
        block-diffusion model what follows the last whole block (prefill
        stores whole blocks only), 0 to B-1 tokens, or all that the row's
        window holds once it has one (a whole block not stored yet is the
        next pass's to store, not a prefill step's)."""
        bd = getattr(model, "block_diffusion", None)
        if bd is None:
            return lambda req: 1
        return lambda req: (len(req.tokens) % bd.block_length
                            if req.block is None
                            else len(req.tokens) - req.cache_depth)

    @staticmethod
    def _prefill_rows(active, chunk: int, depth_of, segments: int,
                      consecutive: bool = True, hold=lambda req: 1,
                      window: Optional[int] = None):
        """At most ``segments`` segments (slot, tokens, start_pos) of at
        most ``chunk`` tokens for one prefill step. The requests with more
        pending than the decode step takes (``hold``: _held_back) get one
        each, oldest admission first; with ``consecutive`` the spare
        segments go, in the same order, to those with more still pending,
        as their next chunks. A slot's segments come in ascending order of
        start_pos, and all of a step's segments are computed in ONE forward.
        The two rules a step's segments obey for that reason:

        1. ONE WINDOW A STEP for a model of chunked attention layers
           (``window``; ops/kv_layout.py): the positions a slot is given in
           one step lie in one window of that many, since the step appends
           them all before any of them attends and the next window's would
           overwrite this one's rows: a segment is cut at the boundary, and
           what lies beyond waits for the next step.
        2. A TAIL OR A STATE FROM THE STEP OR THE STORE for a model whose
           attention layers carry one (ops/cca_attention.take_tails; a
           recurrent state: ops/inc_attention.carried_rows): a segment that
           starts where another segment of the same step, the same slot's,
           ends takes that segment's END as its tail or state, any other
           what the last step left in the slot (zeros at position 0), and
           the step writes back each slot's last segment's. For a tail that
           is a gather; for a recurrence it ORDERS the rows' work inside the
           one forward. It costs the scheduler nothing: the op reads it off
           the rows' own slots, starts and lengths, in the ascending order
           given here (``ServingTelemetry.note_prefill_tails`` counts the
           same on the host)."""
        rows, taken, first = [], {}, {}

        def pending(req):
            n = (len(req.tokens) - depth_of(req) - taken.get(req.slot, 0)
                 - hold(req))
            if (window and n > 0 and first.get(req.slot, -1) not in (
                    -1, (len(req.tokens) - hold(req) - n) // window)):
                return 0                # its next start is a window on
            return n

        filling = sorted((req for req in active if req is not None
                          and not req.finished and pending(req) > 0),
                         key=lambda req: req.prefill_start_s)
        while filling and len(rows) < segments:
            for req in filling[:segments - len(rows)]:
                d = len(req.tokens) - hold(req) - pending(req)
                take = min(pending(req), chunk)
                if window:
                    take = min(take, window - d % window)
                    first.setdefault(req.slot, d // window)
                rows.append((req.slot, req.tokens[d:d + take], d))
                taken[req.slot] = taken.get(req.slot, 0) + take
            filling = [req for req in filling
                       if consecutive and pending(req) > 0]
        return rows

    @staticmethod
    def _compact_prefill(ifm) -> bool:
        """Whether ``ifm``'s model takes the compact prefill batch. A
        pipeline stage streams microbatches of batch rows and of cache rows
        together (serve/pipeline_plan.py), so a batch row there cannot
        reach another slot's cache: a pipelined model keeps the slot grid."""
        return getattr(getattr(ifm, "model", None), "_pp_plan", None) is None

    def _prefill(self, ifm, active, shape, depth_of, tel, rnd=None,
                 model="llm"):
        """One prefill step for ``ifm``'s model: choose the segments
        among ``active`` (None: not a candidate), run them in one
        output-free step, return them (none: nothing is filling). The one
        prefill path of the Python loops; the caller moves its depth marks
        by the rows returned. The speculation loops call it once a model a
        round, the incremental loop as often as StepCosts allows the round
        (the round's first behind the block before, where it can);
        ``rnd`` and ``model``: _timed_prefill."""
        chunk, segments = shape
        compact = self._compact_prefill(ifm)
        chunked = (getattr(ifm.model, "attention_kinds", None)
                   or {}).get("chunked")
        rows = self._prefill_rows(active, chunk, depth_of, segments,
                                  consecutive=compact,
                                  hold=self._held_back(ifm.model),
                                  window=chunked and chunked["window"])
        if rows:
            meta = (self._meta_from_segments(segments, chunk, rows)
                    if compact else
                    self._meta_from_rows(len(active), chunk, rows))
            self._timed_prefill(ifm, meta, tel, rows, active, rnd, model)
        return rows

    # -- a decode block's two ends ------------------------------------------
    def _block_steps(self, ifm, live, max_seq: int, cap: int) -> int:
        """The decode block's steps for ``live``, at most ``cap``: the
        tokens the row with most to go still needs, never past the KV
        cache's end, as steps of ``ifm``'s model: a token a step, or the
        passes those tokens take at most (BlockDiffusion.passes_for: a row
        that needs fewer finishes early and the host cuts the overshoot)."""
        tokens = min(max(self._remaining_budget(req, max_seq)
                         for req in live),
                     max_seq - max(len(req.tokens) for req in live))
        bd = getattr(ifm.model, "block_diffusion", None)
        return max(1, min(tokens if bd is None else bd.passes_for(tokens),
                          cap))

    @staticmethod
    def _stage_decode(ifm, live, R: int):
        """(tok, pos, active) of a decode block over ``live``: each row's
        pending token and its position; for a block-diffusion model the
        row's window ``[R, 2B]`` (what it carried from its last call, else
        its pending tokens, then -1 for the masked rest) and the length its
        cache holds."""
        bd = getattr(ifm.model, "block_diffusion", None)
        tok = (np.zeros((R,), np.int32) if bd is None
               else np.full((R, 2 * bd.block_length), -1, np.int32))
        pos = np.zeros((R,), np.int32)
        act = np.zeros((R,), bool)
        for req in live:
            act[req.slot] = True
            if bd is None:
                tok[req.slot] = req.tokens[-1]
                pos[req.slot] = len(req.tokens) - 1
                continue
            pos[req.slot] = req.cache_depth
            if req.block is not None:
                tok[req.slot] = req.block
            else:
                known = req.tokens[req.cache_depth:]
                tok[req.slot, :len(known)] = known
        return tok, pos, act

    def _commit_decode(self, live, out, steps: int, max_seq: int):
        """Give ``live`` what their decode block yielded: ``steps`` tokens
        a row (``out`` [R, steps]), or each row's count
        (inference_manager.BlockPasses: whole blocks, the first of which
        begins with the tokens the row already had pending), up to where
        the request is done; stamp the first token; move the cache depth
        (such a row's by what its passes stored, a block behind what they
        emitted where the last one left a block whole)."""
        counted = isinstance(out, BlockPasses)
        for req in live:
            if counted:
                n = int(out.count[req.slot])
                known = (len(req.tokens) - req.cache_depth) % out.block_length
                new = out.tokens[req.slot, min(n, known):n]
                req.cache_depth += int(out.stored[req.slot])
                req.block = out.block[req.slot]
            else:
                new = out[req.slot, :steps]
            for t in new:
                req.tokens.append(int(t))
                if self._finish_if_done(req, max_seq):
                    break
            self._note_first_token(req)
            if not counted:
                req.cache_depth = len(req.tokens) - 1

    # =====================================================================
    # Incremental decoding (reference generate_incr_decoding :1810)
    # =====================================================================
    def generate_incr_decoding(self, model,
                               generation_config:
                               Optional[GenerationConfig] = None
                               ) -> List[GenerationResult]:
        ifm = self._manager_of(model)
        cfg = model.config
        self._resolve_prefix_cache(generation_config)
        if self.prefix_cache is not None:
            refuse_block_diffusion(
                model, "the shared-prefix pool (it shares positions, not "
                "whole blocks)")
        held_back = self._held_back(model)
        self.scheduler_loop = "python"
        R = cfg.max_requests_per_batch
        max_seq = cfg.max_sequence_length
        shape = chunk, _ = self._prefill_shape(cfg)
        active: List[Optional[Request]] = [None] * R
        done: List[GenerationResult] = []
        # the two programs' measured cost outlives the call: the server
        # re-enters this loop whenever its queue has emptied
        costs = getattr(ifm, "step_costs", None)
        if costs is None:
            costs = ifm.step_costs = StepCosts()

        def resident():
            return [req for req in active
                    if req is not None and not req.finished]

        def caught_up():
            return [req for req in resident()
                    if req.cache_depth == len(req.tokens) - held_back(req)]

        def block_steps(live, prefilled: bool) -> int:
            """The decode block's steps for ``live``. Dynamic trip count:
            exactly the steps still needed, one compiled program
            regardless of size (engine.py). The verify-consistent wide
            decode (decode_width > 1) appends only the real token's KV
            (kv_append_q), so no staging window needs reserving near the
            cache end. With prefill still pending the block is kept
            short, so that the next chunk isn't starved behind it."""
            return self._block_steps(
                ifm, live, max_seq,
                min(cfg.decode_block_steps, chunk) if prefilled
                else cfg.decode_block_steps)

        def prefill_step(tel, rnd) -> int:
            """One prefill step over whoever is filling, their depths
            moved on; the steps that made (0: nobody is filling)."""
            rows = self._prefill(ifm, active, shape,
                                 lambda r: r.cache_depth, tel, rnd)
            for slot, chunk_toks, sp in rows:
                active[slot].cache_depth = sp + len(chunk_toks)
            return 1 if rows else 0

        # A decode block's read-back waits one launch, as a prefill step's
        # does (ISSUE 61): while someone resident is still filling, the
        # NEXT round's first prefill step, its LEAD step, is chosen, staged
        # and launched behind the running block, before the block is read.
        # The device runs calls in launch order, so the step starts the
        # moment the block ends, and the host's learning so, the commit,
        # the admission and the next build pass while it runs. One step
        # and no more: the round's allowance is computed from the committed
        # state. ``lead``: the step the round before launched for this one
        # (0 or 1), ``led`` whom it brought to a prompt's end, ``handed``
        # its wait where telemetry is on; ``due``: StepCosts' answer for
        # the next round that prefills, asked where its lead step would be
        # launched (None: not asked yet; a round to be timed takes no lead
        # step and starts on an idle device).
        lead, led, handed, due = 0, (), None, None
        while self.pending or any(a is not None for a in active):
            tel = self._tel()
            rnd = (tel.begin_round("incr", R, handed) if tel is not None
                   else None)
            handed = None
            self._reap_expired(active, max_seq, done)
            self._fill_slots(active, max_seq, done)
            self._prefix_install(active, (("llm", ifm),))
            if rnd is not None:
                rnd.admitted(R - active.count(None), len(self.pending))
            # decode-interleaved chunked prefill (ISSUE 19, 32, 36, 48): a
            # round dispatches bounded prefill steps (separate calls of the
            # one program, outputs unused) while a request is still
            # filling, AND the decode block for the caught-up slots. The
            # round is shared by everyone resident (StepCosts: the loop's
            # own measurement of the two programs): the steps together cost
            # no more than the block that follows them, times (decoding +
            # filling) / decoding. One step is always allowed. So the
            # row-seconds the decoders are stalled never pass the
            # request-seconds the block takes from the rows that ride it
            # and the requests it holds off: at a full batch a decoding
            # row waits for prefill at most a block's time, a batch that
            # has emptied earns its refill by how empty it is, and a
            # queued short request's TTFT does not track the longest
            # resident prompt's prefill. Queued requests without a slot do
            # not count: no prefill step helps them. With nothing decoding
            # there is nobody to stall: the round prefills until a request
            # has caught up.
            # (the lead step is this round's first: whoever it brought
            # to the end of a prompt was filling when the round's steps
            # began, and counts so)
            decoding = [req for req in caught_up() if req.guid not in led]
            allowed = None
            if decoding:
                filling = len(resident()) - len(decoding)
                allowed = costs.allowance(block_steps(decoding, True),
                                          len(decoding), filling)
                if tel is not None:
                    tel.note_round_allowance(
                        allowed, costs.weight(len(decoding), filling))
            steps, timed, t0 = lead, False, time.perf_counter()
            lead, led = 0, ()
            while (steps < allowed if allowed is not None
                   else not (steps and caught_up())):
                if not prefill_step(tel, rnd):
                    break
                if not steps:
                    timed = costs.due() if due is None else due
                    due = None
                steps += 1
                if timed:
                    # a timed round waits for each step before it stages
                    # the next, telemetry or not, so both time the same
                    # thing; any other round's steps queue behind each
                    # other, telemetry or not
                    if rnd is None:
                        device_fence(ifm.model.op_state)
                    else:
                        rnd.phase(None)
                        rnd.settle()
                        rnd.phase("sched_build")
            if timed:
                costs.note_prefill(time.perf_counter() - t0, steps)
            if tel is not None:
                tel.note_round_prefill(steps)
            # decode: every caught-up slot feeds what it has pending; the
            # token-feedback loop runs fused on device (DECODE_BLOCK steps
            # per call); EOS/length overshoot is reconciled host-side.
            # Mid-prefill slots (cache_depth short of the pending tokens)
            # sit this block out.
            live = caught_up()
            if live:
                block = block_steps(live, steps > 0)
                tok, pos, act = self._stage_decode(ifm, live, R)
                self._tel_tick(tel, live, R, max_seq)
                kinds = getattr(model, "attention_kinds", None)
                reads = None
                if tel is not None and kinds:   # rings beside full; latent
                    reads = tel.note_attention_reads(kinds, pos[act] + 1,
                                                     block)
                if rnd is not None:
                    rnd.phase(None)
                t0 = time.perf_counter()
                launched = ifm.launch_decode_block(tok, pos, act, block,
                                                   tel=tel, rnd=rnd)
                if len(live) < len(resident()):     # someone is filling
                    if due is None:
                        due = costs.due()
                    if not due:
                        if rnd is not None:
                            rnd.phase("sched_build")
                        lead, due = prefill_step(tel, rnd), None
                        led = ({req.guid for req in caught_up()}
                               - {req.guid for req in live})
                        if rnd is not None:
                            rnd.phase(None)
                            handed = rnd.hand_on()
                if tel is not None:
                    tel.note_round_ahead(bool(lead))
                toks = ifm.read_decode_block(launched, tel=tel)
                dt = time.perf_counter() - t0   # the np readback = fence
                if timed or not steps:  # the device was idle at dispatch
                    costs.note_decode(dt, block)
                if tel is not None:
                    rnd.phase("sched_commit", live)
                    tel.record_decode_block(dt, block, len(live),
                                            [r.guid for r in live], t0,
                                            width=ifm.decode_width,
                                            passes=toks if isinstance(
                                                toks, BlockPasses) else None,
                                            reads=reads)
                self._commit_decode(live, toks, block, max_seq)
            for slot in range(R):
                req = active[slot]
                if req is not None and req.finished:
                    self._prefix_store(req, (("llm", ifm),))
                    done.append(self._collect(req))
                    active[slot] = None
            if rnd is not None:
                rnd.end()
        return done

    # -- adaptive speculation support (serve/spec_controller.py) ----------
    @staticmethod
    def _spec_controller(gc: Optional[GenerationConfig], llm, ssms,
                         engine_depth: int, beam_width: int = 1):
        """Build the per-request adaptive speculation controller, or None
        when the policy disables it (then every path behaves exactly like
        the pre-controller static engine)."""
        gc = gc or GenerationConfig()
        if not gc.adaptive_spec:
            return None, gc
        from flexflow_tpu.serve.spec_controller import SpecController

        return SpecController.from_generation_config(
            gc, llm, ssms, engine_depth=engine_depth,
            beam_width=beam_width), gc

    def _tick_controller(self, ctrl, tel, live):
        if ctrl is None or tel is None:
            return
        stats = ctrl.live_stats(r.guid for r in live)
        tel.note_spec_controller(stats["ewma_mean"], stats["n_fallback"],
                                 ctrl.take_new_fallbacks())

    def _partition_spec(self, ctrl, drafting, rnd, live, roomy, rounds):
        """The fused loop's controller partition: split the roomy
        requests into (draftable, parked) by ``drafting`` (the round's
        ``SpecController.drafting`` set), feed the controller telemetry
        gauges, and shrink a pure-probe tick to ONE round (one
        acceptance sample — minimal probe tax on parked traffic).
        ``rnd`` is the round's RoundTrace (None: telemetry off).
        Returns (draftable, parked, rounds)."""
        self._tick_controller(ctrl, None if rnd is None else rnd.tel, live)
        if ctrl is None:
            return roomy, [], rounds
        draftable = [req for req in roomy if req.guid in drafting]
        parked = [req for req in roomy if req.guid not in drafting]
        if draftable and all(ctrl.in_fallback(r.guid) for r in draftable):
            if rnd is not None and rounds > 1:
                rnd.note_cut("probe")
            rounds = 1
        return draftable, parked, rounds

    @staticmethod
    def _prefill_kind(active, rows) -> str:
        """What a draft model's prefill rows are: ``catch_up`` when every
        row has generated already (its drafts fell more than one accepted
        block behind: parked by the controller and probing back,
        re-queued), ``prefill`` when a prompt is still going in."""
        return ("catch_up" if all(active[slot].num_generated > 0
                                  for slot, _, _ in rows) else "prefill")

    def _fallback_decode(self, llm_ifm, reqs, R, max_seq, cfg, tel,
                         rnd) -> int:
        """Fused incremental decode for requests the adaptive speculation
        controller parked in fallback: the same decode-block program
        generate_incr_decoding drives, at the verify width of the engine
        that parked them (``llm_ifm.decode_width``), so a parked request
        emits the tokens the verify pass would have committed. Draft
        caches are left stale; the prefill cycle heals them if/when the
        request probes back into drafting."""
        if rnd is not None:
            rnd.phase("sched_build")
        block = self._block_steps(llm_ifm, reqs, max_seq,
                                  cfg.decode_block_steps)
        R_tok, pos, act = self._stage_decode(llm_ifm, reqs, R)
        self._tel_tick(tel, reqs, R, max_seq)
        if rnd is not None:
            rnd.phase(None)
        t0 = time.perf_counter()
        toks = llm_ifm.decode_block(R_tok, pos, act, block, tel=tel,
                                    rnd=rnd)
        if tel is not None:     # decode_block's np readback = fence
            dt = time.perf_counter() - t0
            rnd.phase("sched_commit", reqs)
            tel.record_decode_block(dt, block, len(reqs),
                                    [r.guid for r in reqs], t0,
                                    width=llm_ifm.decode_width)
        self._commit_decode(reqs, toks, block, max_seq)
        return block

    # =====================================================================
    # Speculative inference (reference generate_spec_infer :1867)
    # =====================================================================
    def generate_spec_infer(self, llm, ssms: List[Any],
                            spec_depth: Optional[int] = None,
                            beam_width: Optional[int] = None,
                            generation_config: Optional[GenerationConfig]
                            = None) -> List[GenerationResult]:
        """LLM verifies token trees proposed by draft SSMs.

        Each SSM proposes a depth-``spec_depth`` token tree per request:
        greedy chains at beam_width 1, or a ``beam_width``-wide beam search
        (reference BeamSearchBatchConfig, batch_config.h:125); trees are
        merged (shared prefixes dedup — the reference's merge_dfs_trees,
        request_manager.cc); the LLM scores all tree nodes in one step; the
        longest root path whose every child matches the verifier's argmax
        is accepted, plus one bonus token.

        ``generation_config`` carries the adaptive-speculation policy
        (GenerationConfig: on by default). With the controller on, the
        fused paths tune each request's draft depth from its observed
        acceptance and park requests whose estimated spec speedup drops
        below the incremental break-even on the fused incremental decode
        block (serve/spec_controller.py) — output tokens are identical
        either way (greedy acceptance commits the verifier's own argmax
        sequence); only the wall clock changes. ``spec_depth`` stays the
        compiled maximum; ``generation_config.spec_depth`` (when set)
        overrides it. The host-stepped debug/beam-merge path runs static.
        """
        if generation_config is not None and generation_config.spec_depth:
            spec_depth = generation_config.spec_depth
        self._resolve_prefix_cache(generation_config)
        loop, W = self._spec_route(llm, ssms, beam_width)
        self.scheduler_loop = "python:" + loop
        if loop == "spec_tree_host":
            return self._generate_spec_tree_host(llm, ssms,
                                                 spec_depth=spec_depth,
                                                 beam_width=W)
        return self._generate_spec_fused(
            llm, ssms, loop, W, spec_depth=spec_depth,
            generation_config=generation_config)

    def prepare_spec_infer(self, llm, ssms: List[Any],
                           spec_depth: Optional[int] = None,
                           beam_width: Optional[int] = None,
                           generation_config: Optional[GenerationConfig]
                           = None):
        """Build the engine ``generate_spec_infer`` will run with these
        arguments, ahead of the first request. A front door that is handed
        draft models calls this (serve/loadgen.EngineHandle), so the
        verifier's manager knows an engine verifies the model, and at what
        width, before the model's first decode block: served incrementally
        beside it, the model decodes at the verify width from the start."""
        if generation_config is not None and generation_config.spec_depth:
            spec_depth = generation_config.spec_depth
        loop, W = self._spec_route(llm, ssms, beam_width)
        return self._engine_of(loop, llm, ssms, self._depth_of(spec_depth), W)

    @staticmethod
    def _spec_route(llm, ssms, beam_width):
        """``(loop, beam width)``: the fused loop (``spec_tree_fused``:
        MultiSpecEngine; ``spec_beam_fused``: BeamSpecEngine) unless
        ``inference_debugging`` wants per-op dumps or several drafts'
        beams want merging, which the host-stepped loop does."""
        for m in (llm, *ssms):
            refuse_block_diffusion(m, "speculation (drafting, tree "
                                   "verification and its commit)")
            refuse_looped(m, "speculation (drafting, tree verification "
                          "and its commit)")
        widths = [s.config.max_beam_width for s in ssms]
        W = beam_width or max(widths)
        if any(w != W for w in widths):
            # a BEAM_SEARCH-mode graph's output layout is fixed by the
            # width it was COMPILED with (packed [top-k probs, top-k ids]
            # at width>1, argmax ids at width 1) — a mismatched request
            # would silently misparse the packing
            raise ValueError(
                f"beam_width={W} but the draft models were compiled with "
                f"max_beam_width={widths}; rebuild the SSMs with the "
                f"requested width (FFConfig.max_beam_width)")
        if llm.config.inference_debugging or (W > 1 and len(ssms) > 1):
            # per-op tensor dumps stay phase-ordered; several drafts' beams
            # step through each draft as STAGED TREE NODES (no per-beam
            # KV) and the surviving beam paths merge like extra chains
            return "spec_tree_host", W
        # everything else runs inside one device while_loop: one chain a
        # draft verified as one tree, or one draft's beam tree, whose NODE
        # LAYOUT is compile-time static (frontier = the newest W nodes)
        return ("spec_beam_fused" if W > 1 else "spec_tree_fused"), W

    def _depth_of(self, spec_depth: Optional[int]) -> int:
        return min(spec_depth or self.max_spec_depth, self.max_spec_depth)

    def _engine_of(self, loop: str, llm, ssms, depth: int, beam_width: int):
        """The fused engine that ``loop`` runs over the verifier ``llm``,
        kept on the model and rebuilt only when the drafts or the depth
        changed; None for the host-stepped loop, which has none. Whichever,
        the verifier's manager is told the verify width, so that the decode
        blocks of this model (``_fallback_decode``, and incremental decoding
        from here on) take the verify pass's shapes."""
        from flexflow_tpu.kernels.attention import SUBLANE, round_up
        from flexflow_tpu.serve.engine import BeamSpecEngine, MultiSpecEngine

        llm_ifm = self._manager_of(llm)
        if loop == "spec_tree_host":
            # _verify_and_commit's width: root + depth nodes a branch
            llm_ifm.verified_at(round_up(
                1 + depth * len(ssms) * beam_width, SUBLANE))
            return None
        rounds = llm.config.spec_rounds_per_call
        attr = "_beam_engine" if beam_width > 1 else "_multi_engine"
        engine = getattr(llm, attr, None)
        if (engine is None or engine.depth != depth
                or engine.ssms != list(ssms)
                or getattr(engine, "width", 1) != beam_width):
            engine = (BeamSpecEngine(llm, ssms[0], depth, beam_width,
                                     max_rounds=rounds) if beam_width > 1
                      else MultiSpecEngine(llm, ssms, depth,
                                           max_rounds=rounds))
            setattr(llm, attr, engine)
        llm_ifm.verified_at(engine.tree_width)
        return engine

    @staticmethod
    def _manager_of(model) -> InferenceManager:
        ifm = getattr(model, "_inference_manager", None)
        if ifm is None:
            ifm = model._inference_manager = InferenceManager(model)
        return ifm

    def _generate_spec_tree_host(self, llm, ssms: List[Any],
                                 spec_depth: Optional[int] = None,
                                 beam_width: int = 1
                                 ) -> List[GenerationResult]:
        """Host-stepped tree speculation: per-round draft (greedy chains or
        ``beam_width``-wide beam search), host-side tree merge, one verify
        step, KV commit. Slower than the fused engines (one dispatch per
        phase) but supports beams and inference_debugging dumps.

        This debug path intentionally keeps the historical serial
        drain-prefill-then-decode order and does not consult the
        shared-prefix pool — per-op dumps stay phase-ordered. The
        throughput loops (incremental, fused speculation) carry the
        ISSUE 19 interleaving + prefix reuse."""
        llm_ifm = self._manager_of(llm)
        ssm_ifms = [self._manager_of(ssm) for ssm in ssms]
        cfg = llm.config
        R = cfg.max_requests_per_batch
        max_seq = cfg.max_sequence_length
        depth = self._depth_of(spec_depth)
        shape = self._prefill_shape(cfg)
        # tree capacity: root + depth nodes per surviving branch
        T = 1 + depth * len(ssms) * beam_width
        self._engine_of("spec_tree_host", llm, ssms, depth, beam_width)
        active: List[Optional[Request]] = [None] * R
        done: List[GenerationResult] = []

        def ssm_depth_of(i):
            return lambda r: r.ssm_cache_depth.get(i, 0)

        while self.pending or any(a is not None for a in active):
            tel = self._tel()
            self._reap_expired(active, max_seq, done)
            self._fill_slots(active, max_seq, done)
            # ---- prompt prefill: verifier + every SSM ----
            rows = self._prefill(llm_ifm, active, shape,
                                 lambda r: r.cache_depth, tel)
            for slot, toks, sp in rows:
                active[slot].cache_depth = sp + len(toks)
            prefilled = bool(rows)
            for i, ifm in enumerate(ssm_ifms):
                rows = self._prefill(ifm, active, shape, ssm_depth_of(i),
                                     tel, model=f"ssm{i}")
                for slot, toks, sp in rows:
                    active[slot].ssm_cache_depth[i] = sp + len(toks)
                prefilled = prefilled or bool(rows)
            if prefilled:
                continue
            live = [req for req in active if req is not None and not req.finished]
            if live:
                self._tel_tick(tel, live, R, max_seq)
                # ---- draft phase: each SSM proposes chains (or beams) ----
                chains: List[Dict[int, List[int]]] = []  # per branch: slot->toks
                for i, ifm in enumerate(ssm_ifms):
                    if beam_width > 1:
                        chains.extend(self._draft_beams(
                            ifm, i, live, R, depth, beam_width))
                    else:
                        chains.append(self._draft_chains(ifm, i, live, R,
                                                         depth))
                # clamp speculation so tree positions never pass the KV cache
                # end / the request's length limit
                for req in live:
                    limit = min(req.max_sequence_length or max_seq, max_seq)
                    room = max(0, limit - len(req.tokens) - 1)
                    if room < depth:
                        for c in chains:
                            if req.slot in c:
                                c[req.slot] = c[req.slot][:room]
                # ---- merge chains into token trees ----
                trees = {}
                for req in live:
                    node_tok, node_parent = [req.tokens[-1]], [-1]
                    for c in chains:
                        cur = 0
                        for t in c.get(req.slot, []):
                            child = next((j for j in range(len(node_tok))
                                          if node_parent[j] == cur
                                          and node_tok[j] == t), None)
                            if child is None:
                                node_tok.append(t)
                                node_parent.append(cur)
                                child = len(node_tok) - 1
                            cur = child
                    # Each chain is clamped to `room`, but the MERGED tree can
                    # hold up to 1 + n_ssms*room nodes, and node j is staged at
                    # cache[start + j]: without this cap, divergent chains near
                    # the sequence limit write tree KV past max_seq (dropped by
                    # append_kv) and verify against a clipped cache. Parents
                    # always precede children, so truncating the suffix keeps
                    # a valid tree.
                    cap = max_seq - (len(req.tokens) - 1)
                    if len(node_tok) > cap:
                        node_tok = node_tok[:cap]
                        node_parent = node_parent[:cap]
                    trees[req.slot] = (node_tok, node_parent)
                # ---- verify on the LLM ----
                self._verify_and_commit(llm, llm_ifm, live, trees, R, T,
                                        max_seq, depth, tel=tel)
            for slot in range(R):
                req = active[slot]
                if req is not None and req.finished:
                    done.append(self._collect(req))
                    active[slot] = None
        return done

    def _generate_spec_fused(self, llm, ssms: List[Any], loop: str,
                             beam_width: int,
                             spec_depth: Optional[int] = None,
                             generation_config: Optional[GenerationConfig]
                             = None) -> List[GenerationResult]:
        """Speculative decoding with a fused engine (serve/engine.py:
        MultiSpecEngine at beam_width 1, BeamSpecEngine for one draft's
        beams): the one speculation loop that serves traffic.

        Host responsibilities shrink to continuous batching: slot fill,
        chunked prefill (verifier + every draft), dispatching fused round
        blocks, and EOS / length reconciliation over the returned rounds.
        Each device call runs up to SPEC_ROUNDS_PER_CALL full rounds
        (draft + verify + accept + commit); ``engine.run_block`` states
        what goes in and what comes back. With the adaptive controller on
        (GenerationConfig.adaptive_spec, the default) each request's
        depth bound comes from its acceptance EWMA, and requests whose
        estimated spec speedup falls below incremental break-even decode
        through ``_fallback_decode`` until a probe round recovers them.
        """
        llm_ifm = self._manager_of(llm)
        ssm_ifms = [self._manager_of(ssm) for ssm in ssms]
        caches = (("llm", llm_ifm),
                  *((f"ssm{i}", m) for i, m in enumerate(ssm_ifms)))
        cfg = llm.config
        R = cfg.max_requests_per_batch
        max_seq = cfg.max_sequence_length
        B = len(ssms)
        depth = self._depth_of(spec_depth)
        ctrl, gc = self._spec_controller(generation_config, llm, ssms,
                                         engine_depth=depth,
                                         beam_width=beam_width)
        engine = self._engine_of(loop, llm, ssms, depth, beam_width)
        shape = self._prefill_shape(cfg)
        round_name = loop.removesuffix("_fused")    # sched_round's ``loop``
        active: List[Optional[Request]] = [None] * R
        done: List[GenerationResult] = []

        def has_room(req):
            """A full staging window of KV room left (engine.room); a
            cramped request finishes through the single-step path."""
            return max_seq - len(req.tokens) >= engine.room

        def carries_block(req):
            """The drafts all stand at one depth and owe at most one
            accepted block (1..depth+1 tokens): what run_block takes."""
            sd = req.ssm_cache_depth.get(0, 0)
            return (1 <= len(req.tokens) - sd <= depth + 1
                    and all(req.ssm_cache_depth.get(i, 0) == sd
                            for i in range(1, B)))

        while self.pending or any(a is not None for a in active):
            tel = self._tel()
            rnd = (tel.begin_round(round_name, R) if tel is not None
                   else None)
            self._reap_expired(active, max_seq, done, ctrl)
            parked_guids = ({req.guid for req in active if req is not None
                             and ctrl.in_fallback(req.guid)}
                            if ctrl is not None else ())
            self._fill_slots(active, max_seq, done, parked_guids)
            drafting_guids = (None if ctrl is None else ctrl.drafting(
                req.guid for req in active if req is not None))
            self._prefix_install(active, caches)
            if rnd is not None:
                rnd.admitted(R - active.count(None), len(self.pending))
            # one bounded prefill chunk per model per round (same path as
            # incremental); caught-up slots spec/decode below in the SAME
            # round (decode-interleaved chunked prefill, ISSUE 19). With
            # telemetry on each step is waited for behind the round's next
            # device call (_timed_prefill), so the calls queue as they do
            # with it off
            rows = self._prefill(llm_ifm, active, shape,
                                 lambda r: r.cache_depth, tel, rnd)
            for slot, toks, sp in rows:
                active[slot].cache_depth = sp + len(toks)
            prefilled = bool(rows)
            if rows and rnd is not None:
                rnd.note_cut("prefill")
            # a row whose drafts owe no more than one accepted block goes
            # to the engine as it is (run_block's first draft step is the
            # catch-up); only a row that owes more, and can still draft
            # (room for a block, and not parked by the controller: healing
            # a parked request's draft cache would be pure waste until its
            # probe comes due), is fed in chunks here
            owing = [req if req is not None and not carries_block(req)
                     and has_room(req)
                     and (ctrl is None or req.guid in drafting_guids)
                     else None for req in active]
            for i, ifm in enumerate(ssm_ifms):
                rows = self._prefill(
                    ifm, owing, shape,
                    lambda r, i=i: r.ssm_cache_depth.get(i, 0), tel, rnd,
                    model=f"ssm{i}")
                for slot, toks, sp in rows:
                    active[slot].ssm_cache_depth[i] = sp + len(toks)
                if rows:
                    prefilled = True
                    if rnd is not None:
                        rnd.note_cut(self._prefill_kind(active, rows))
            live = [req for req in active
                    if req is not None and not req.finished]
            # decode-interleaved chunked prefill: only slots whose
            # VERIFIER cache is caught up join this round's spec/decode
            # work; mid-prefill slots wait (their next chunk dispatches
            # next round) instead of stalling everyone else.
            ready = [req for req in live
                     if req.cache_depth == len(req.tokens) - 1]
            if not ready:
                if rnd is not None:
                    rnd.end()
                continue
            roomy = [req for req in ready if has_room(req)]
            cramped = [req for req in ready if not has_room(req)]
            # controller partition: parked requests decode through the
            # fused incremental block (same cost/tokens as plain
            # incremental) until their probe round recovers them
            draftable, parked, rounds = self._partition_spec(
                ctrl, drafting_guids, rnd, live, roomy,
                min(cfg.spec_rounds_per_call, engine.max_rounds))
            if prefilled:
                # prefill still pending somewhere: one spec round, then
                # back to the next chunk
                rounds = 1
            # a draftable slot may still have lagging drafts mid-interleave
            # (their chunk dispatched above); it drafts once healed
            draftable = [req for req in draftable if carries_block(req)]
            if cramped:
                # cache nearly full: finish remaining tokens one by one
                # through the non-fused single-step decode path
                rows = [(req.slot, req.tokens[-1:], len(req.tokens) - 1)
                        for req in cramped]
                meta = self._meta_from_rows(R, 1, rows)
                if rnd is not None:
                    rnd.phase(None)
                t0 = time.perf_counter()
                out = llm_ifm.step(meta, tel=tel, rnd=rnd)
                if tel is not None:       # step's np readback = fence
                    dt = time.perf_counter() - t0
                    rnd.phase("sched_commit", cramped)
                    tel.record_decode_block(dt, 1, len(cramped),
                                            [req.guid for req in cramped],
                                            t0)
                for slot, _t, sp in rows:
                    req = active[slot]
                    req.tokens.append(int(out[slot, 0]))
                    req.cache_depth = sp + 1
                    for i in range(B):
                        req.ssm_cache_depth[i] = min(
                            req.ssm_cache_depth.get(i, 0), sp)
                    self._note_first_token(req)
                    self._finish_if_done(req, max_seq)
            if parked:
                self._fallback_decode(llm_ifm, parked, R, max_seq, cfg, tel,
                                      rnd)
                for req in parked:
                    ctrl.note_fallback_block(req.guid)
            if draftable:
                if rnd is not None:
                    rnd.phase("sched_build")
                tks = np.zeros((R, depth + 1), np.int32)
                nblk = np.ones((R,), np.int32)
                base = np.zeros((R,), np.int32)
                act = np.zeros((R,), bool)
                remaining = np.zeros((R,), np.int32)
                depth_vec = None
                if ctrl is not None:
                    depth_vec = np.full((R,), depth, np.int32)
                for req in draftable:
                    assert req.cache_depth == len(req.tokens) - 1
                    base[req.slot] = sd = req.ssm_cache_depth.get(0, 0)
                    nblk[req.slot] = len(req.tokens) - sd
                    tks[req.slot, :nblk[req.slot]] = req.tokens[sd:]
                    act[req.slot] = True
                    remaining[req.slot] = self._remaining_budget(req, max_seq)
                    if ctrl is not None:
                        depth_vec[req.slot] = ctrl.depth_for(req.guid)
                self._tel_tick(tel, draftable, R, max_seq)
                # engines are cached on the llm across managers: hand THIS
                # manager's explicit telemetry through (a None keeps the
                # engine on the process-global one)
                engine.telemetry = self.telemetry
                if rnd is not None:
                    rnd.phase(None)
                t0 = time.perf_counter()
                toks, n_acc, d_used = engine.run_block(
                    tks, nblk, base, act, rounds, remaining, depth=depth_vec,
                    min_depth=gc.min_spec_depth, trace=rnd)
                block_dt = time.perf_counter() - t0
                if rnd is not None:
                    rnd.phase("sched_commit", draftable)
                for req in draftable:
                    last_rpos = len(req.tokens) - 1
                    round_events = []
                    observed = []
                    for k in range(rounds):
                        n = int(n_acc[req.slot, k])
                        if n < 0:         # the row sat this round out
                            continue
                        observed.append((int(d_used[req.slot, k]), n))
                        last_rpos = len(req.tokens) - 1
                        new_toks = ([int(t) for t in toks[req.slot, k, :n]]
                                    + [int(toks[req.slot, k, depth])])
                        # trim the accepted chunk at the generation budget
                        # / EOS — incremental decoding would have stopped
                        # there
                        room = req.max_new_tokens - req.num_generated
                        new_toks = new_toks[:max(0, room)]
                        if (self.eos_token_id is not None
                                and self.eos_token_id in new_toks):
                            new_toks = new_toks[
                                :new_toks.index(self.eos_token_id) + 1]
                        req.tokens.extend(new_toks)
                        round_events.append((k, n, len(new_toks)))
                        if self._finish_if_done(req, max_seq):
                            break
                    if ctrl is not None:
                        ctrl.observe_block(req.guid, observed)
                    self._note_first_token(req)
                    if tel is not None and round_events:
                        tel.trace_rounds(req.guid, round_events, t0,
                                         block_dt, rounds)
                    d = len(req.tokens) - 1
                    # verifier cache: committed in-engine through the last
                    # accepted prefix (count = all but the pending token)
                    req.cache_depth = d
                    for i in range(B):
                        # draft caches are only guaranteed correct through
                        # the last round's catch-up position: a losing
                        # branch's cache holds ITS chain, a beam's its
                        # staged nodes, not the committed tokens — the next
                        # block is handed the gap as its accepted block
                        # (carries_block)
                        req.ssm_cache_depth[i] = min(last_rpos + 1, d)
            for slot in range(R):
                req = active[slot]
                if req is not None and req.finished:
                    if ctrl is not None:
                        ctrl.drop(req.guid)
                    self._prefix_store(req, caches)
                    done.append(self._collect(req))
                    active[slot] = None
            if rnd is not None:
                rnd.end()
        return done

    def _draft_chains(self, ifm, ssm_idx, live, R, depth):
        """Greedy depth-``depth`` chain per live request on one SSM.

        The whole chain runs as ONE fused device program
        (engine.make_draft_chain: a scan of width-1 decodes) — the unfused
        version paid a host round trip per token per SSM. The prefill
        loop has already caught each SSM's cache up
        to exactly one pending token (after a divergent acceptance the
        missing committed tokens go through the prefill program like any
        other prompt chunk).
        """
        from flexflow_tpu.serve.engine import make_draft_chain

        model = ifm.model
        if model.config.inference_debugging:
            # debug mode serializes into per-step step() calls so every
            # draft token's op tensors are dumped (the fused scan body
            # cannot host-dump); same numerics, slower.
            return self._draft_chains_debug(ifm, ssm_idx, live, R, depth)
        fn = getattr(model, "_draft_chain_fn", None)
        if fn is None or model._draft_chain_depth != depth:
            fn = make_draft_chain(model, ifm._compute_dtype, depth)
            model._draft_chain_fn = fn
            model._draft_chain_depth = depth
        tok = np.zeros((R,), np.int32)
        pos = np.zeros((R,), np.int32)
        act = np.zeros((R,), bool)
        for req in live:
            d = req.ssm_cache_depth.get(ssm_idx, 0)
            assert d == len(req.tokens) - 1, (d, len(req.tokens))
            tok[req.slot] = req.tokens[-1]
            pos[req.slot] = d
            act[req.slot] = True
        ifm._rng, step_rng = jax.random.split(ifm._rng)
        toks, model.op_state = fn(model.params, model.op_state,
                                  jnp.asarray(tok), jnp.asarray(pos),
                                  jnp.asarray(act), step_rng)
        toks = np.asarray(toks)
        chains = {}
        for req in live:
            chains[req.slot] = [int(t) for t in toks[req.slot]]
            # the chain commits the pending token's KV (+1); drafted tokens
            # beyond it are tentative — cache entries past the accepted
            # point are overwritten next round, so bookkeeping stays at d+1
            req.ssm_cache_depth[ssm_idx] = \
                req.ssm_cache_depth.get(ssm_idx, 0) + 1
        return chains

    def _draft_beams(self, ifm, ssm_idx, live, R, depth, width):
        """Beam-search drafting on one SSM; returns ``width`` chain dicts
        (the surviving beam paths, root excluded) ready for tree merging.

        Reference machinery: BeamSearchBatchConfig + BeamTopK parent
        tracking + per-beam KV in spec_inc_multihead_self_attention.cu.
        TPU-first: each step stages the WHOLE current beam tree as tree
        nodes on the draft model (tree attention gives each frontier node
        its ancestor-path context), so no per-beam cache duplication or
        compaction exists at all. The BEAM_SEARCH-mode graph emits packed
        [top-k probs, top-k ids] per node (models/llama.py) and the host
        keeps the classic cumulative-log-prob beam bookkeeping.

        Correctness-first host loop: each step re-verifies the full
        accumulated tree (~W x the frontier-only FLOPs at depth d) — beams
        are a drafting-quality feature; the throughput paths are the fused
        engines. generate_spec_infer validates that ``width``
        matches every draft's compiled max_beam_width before routing here
        (the packed output layout is fixed at graph-build time).
        """
        import math

        assert ifm.model.config.max_beam_width == width, \
            (ifm.model.config.max_beam_width, width)
        W = width
        nodes = {}      # slot -> [token]
        parents = {}    # slot -> [parent idx]
        ndepth = {}     # slot -> [depth in tree]
        scores = {}     # slot -> {node idx: cumulative logprob}
        frontier = {}   # slot -> [node idx]
        start = {}
        for req in live:
            s = req.slot
            d = req.ssm_cache_depth.get(ssm_idx, 0)
            assert d == len(req.tokens) - 1, (d, len(req.tokens))
            nodes[s] = [req.tokens[-1]]
            parents[s] = [-1]
            ndepth[s] = [0]
            scores[s] = {0: 0.0}
            frontier[s] = [0]
            start[s] = d
        for _t in range(depth):
            # pad the staged width to a sublane multiple so the biased
            # (tree) flash path stays engaged on TPU (pad nodes are masked
            # off via num_nodes; see MultiSpecEngine.tree_width). Staging
            # near max_seq is safe: append_kv drops out-of-range writes
            # and flash_attend clamps lengths to the cache end — garbage
            # proposals there simply fail verification.
            from flexflow_tpu.kernels.attention import SUBLANE, round_up

            T = round_up(max(len(nodes[req.slot]) for req in live), SUBLANE)
            tokens = np.zeros((R, T), np.int32)
            positions = np.zeros((R, T), np.int32)
            parent = np.full((R, T), -1, np.int32)
            sp = np.zeros((R,), np.int32)
            num = np.zeros((R,), np.int32)
            act = np.zeros((R,), bool)
            for req in live:
                s = req.slot
                n = len(nodes[s])
                tokens[s, :n] = nodes[s]
                parent[s, :n] = parents[s]
                positions[s, :n] = start[s] + np.asarray(ndepth[s])
                sp[s] = start[s]
                num[s] = n
                act[s] = True
            meta = TreeBatchMeta(
                tokens=tokens, positions=positions, parent=parent,
                ancestor=ancestor_mask_from_parents(parent), start_pos=sp,
                num_nodes=num, active=act)
            out = np.asarray(ifm.step(meta))        # [R, T, 2W] packed
            probs, ids = out[..., :W], out[..., W:].astype(np.int32)
            for req in live:
                s = req.slot
                cands = []
                for fi in frontier[s]:
                    base = scores[s][fi]
                    for j in range(W):
                        p = max(float(probs[s, fi, j]), 1e-20)
                        cands.append((base + math.log(p),
                                      int(ids[s, fi, j]), fi))
                cands.sort(key=lambda c: -c[0])
                new_frontier = []
                for sc, tok, fi in cands[:W]:
                    nodes[s].append(tok)
                    parents[s].append(fi)
                    ndepth[s].append(ndepth[s][fi] + 1)
                    idx = len(nodes[s]) - 1
                    scores[s][idx] = sc
                    new_frontier.append(idx)
                frontier[s] = new_frontier
        # surviving beam paths -> chains (best beam first; merge dedups)
        out_chains: List[Dict[int, List[int]]] = [dict() for _ in range(W)]
        for req in live:
            s = req.slot
            order = sorted(frontier[s], key=lambda i: -scores[s][i])
            for b, leaf in enumerate(order):
                path = []
                cur = leaf
                while cur != 0:
                    path.append(nodes[s][cur])
                    cur = parents[s][cur]
                out_chains[b][s] = list(reversed(path))
            # the first tree step committed the pending root's KV; drafted
            # nodes beyond are tentative (overwritten by later staging)
            req.ssm_cache_depth[ssm_idx] = start[s] + 1
        return out_chains

    def _draft_chains_debug(self, ifm, ssm_idx, live, R, depth):
        """Unfused per-token draft loop, kept for inference_debugging dumps
        (one InferenceManager.step per drafted token)."""
        rows = []
        for req in live:
            d = req.ssm_cache_depth.get(ssm_idx, 0)
            rows.append((req.slot, req.tokens[-1:], d))
        meta = self._meta_from_rows(R, 1, rows)
        out = ifm.step(meta)
        chains = {}
        last = {}
        for req, (slot, _catch, d) in zip(live, rows):
            tok = int(out[slot, 0])
            chains[slot] = [tok]
            last[slot] = tok
            req.ssm_cache_depth[ssm_idx] = d + 1
        for _ in range(depth - 1):
            rows = [(req.slot, [last[req.slot]],
                     req.ssm_cache_depth[ssm_idx]) for req in live]
            meta = self._meta_from_rows(R, 1, rows)
            out = ifm.step(meta)
            for req in live:
                req.ssm_cache_depth[ssm_idx] += 1
                tok = int(out[req.slot, 0])
                chains[req.slot].append(tok)
                last[req.slot] = tok
        for req in live:
            req.ssm_cache_depth[ssm_idx] -= (depth - 1)
        return chains

    def _verify_and_commit(self, llm, ifm, live, trees, R, T, max_seq, depth,
                           tel=None):
        from flexflow_tpu.kernels.attention import SUBLANE, round_up

        T = round_up(T, SUBLANE)  # sublane-align the verify width (flash)
        tokens = np.zeros((R, T), np.int32)
        positions = np.zeros((R, T), np.int32)
        parent = np.full((R, T), -1, np.int32)
        start = np.zeros((R,), np.int32)
        num = np.zeros((R,), np.int32)
        act = np.zeros((R,), bool)
        node_depth = np.zeros((R, T), np.int32)
        for req in live:
            ntok, npar = trees[req.slot]
            n = len(ntok)
            sp = len(req.tokens) - 1
            assert req.cache_depth == sp, (req.cache_depth, sp)
            tokens[req.slot, :n] = ntok
            parent[req.slot, :n] = npar
            for j in range(1, n):
                node_depth[req.slot, j] = node_depth[req.slot, npar[j]] + 1
            positions[req.slot, :n] = sp + node_depth[req.slot, :n]
            start[req.slot] = sp
            num[req.slot] = n
            act[req.slot] = True
        anc = ancestor_mask_from_parents(parent)
        meta = TreeBatchMeta(tokens=tokens, positions=positions,
                             parent=parent, ancestor=anc, start_pos=start,
                             num_nodes=num, active=act)
        t0 = time.perf_counter()
        out = ifm.step(meta)                               # [R, T] argmax ids
        if tel is not None:               # step's np readback = fence
            tel.spec_block_seconds.observe(time.perf_counter() - t0)
        # ---- greedy acceptance walk ----
        src_node = np.zeros((R, self.max_spec_depth + 1), np.int32)
        ncommit = np.zeros((R,), np.int32)
        needs_commit = False
        for req in live:
            ntok, npar = trees[req.slot]
            n = len(ntok)
            cur, path = 0, []
            while True:
                want = int(out[req.slot, cur])
                child = next((j for j in range(cur + 1, n)
                              if npar[j] == cur and ntok[j] == want), None)
                if child is None:
                    break
                path.append(child)
                cur = child
            bonus = int(out[req.slot, cur])
            accepted = [ntok[j] for j in path]
            # verifier cache: path nodes must land at start+1..start+k
            if path != list(range(1, len(path) + 1)):
                needs_commit = True
            src_node[req.slot, :len(path)] = [j - 1 for j in path]
            ncommit[req.slot] = len(path)
            # trim the accepted chunk at EOS / max_new_tokens before it is
            # appended — incremental decoding would have stopped there
            new_toks = accepted + [bonus]
            room = req.max_new_tokens - req.num_generated
            new_toks = new_toks[:max(0, room)]
            if self.eos_token_id is not None and self.eos_token_id in new_toks:
                new_toks = new_toks[:new_toks.index(self.eos_token_id) + 1]
            req.tokens.extend(new_toks)
            self._note_first_token(req)
            if tel is not None:
                # one host-stepped round: the per-round decode metrics the
                # fused engines record in run_block (engine.py)
                tel.spec_rounds.inc()
                tel.acceptance_length.observe(len(path))
                tel.tokens_per_round.observe(len(new_toks))
            req.cache_depth = min(start[req.slot] + 1 + len(path),
                                  len(req.tokens) - 1)
            self._finish_if_done(req, max_seq)
        if needs_commit:
            llm.op_state = self._commit(
                llm.op_state, jax.numpy.asarray(src_node),
                jax.numpy.asarray(ncommit), jax.numpy.asarray(start + 1),
                jax.numpy.asarray(act), max_seq=max_seq)


_request_manager: Optional[RequestManager] = None


def get_request_manager() -> RequestManager:
    """Singleton accessor (reference RequestManager::get_request_manager)."""
    global _request_manager
    if _request_manager is None:
        _request_manager = RequestManager()
    return _request_manager
