"""Serving telemetry subsystem: metrics registry + per-request tracing.

The instrumentation seam for the serving stack:
``serve/request_manager.py`` and ``serve/engine.py``
call the ``ServingTelemetry`` hooks below at block granularity, the
registry exports Prometheus text / JSON (``serve/api.py`` ``/metrics``,
``ffsv_metrics_dump`` in the C ABI), and the tracer writes a
Perfetto-loadable JSONL span trace per request.

Disabled by default. ``enable_telemetry()`` installs a process-global
``ServingTelemetry``; every instrumentation site resolves
``get_telemetry()`` once per host-loop iteration and skips ALL work on
None — the disabled decode round pays one attribute read, nothing else
(tests/test_telemetry.py pins zero events recorded when disabled).

Metric vocabulary (all ``ffsv_`` — the serving ABI prefix):

===============================  =========  =================================
name                             kind       meaning
===============================  =========  =================================
ffsv_requests_total              counter    requests admitted
ffsv_requests_finished_total     counter    requests completed
ffsv_requests_rejected_total     counter    submissions refused at admission
ffsv_requests_timed_out_total    counter    requests expired between rounds
ffsv_requests_cancelled_total    counter    requests cancelled by the host
ffsv_requests_preempted_total    counter    slot evictions for a deadline
ffsv_queue_depth                 gauge      submission queue depth (front door)
ffsv_tokens_generated_total      counter    output tokens committed
ffsv_prefill_tokens_total        counter    prompt tokens prefilled
ffsv_prefill_positions_total     counter    positions prefill steps computed
ffsv_prefill_attended_pairs_total counter   (query, key) pairs prefill attended
ffsv_round_prefill_steps         histogram  prefill steps a round dispatched
ffsv_round_prefill_allowance     histogram  prefill steps a round was allowed
ffsv_round_prefill_weight        histogram  its (decoding + filling) / decoding
ffsv_round_prefill_ahead         histogram  1: a step was queued behind the block
ffsv_spec_rounds_total           counter    speculation rounds executed
ffsv_decode_steps_total          counter    row-steps of decode blocks
ffsv_diffusion_row_passes_total  counter    passes block-diffusion rows ran
ffsv_diffusion_commit_passes_total counter  those that only stored a block
ffsv_diffusion_tokens_total      counter    {by} positions denoise passes unmasked
ffsv_acceptance_length           histogram  accepted draft tokens per round
ffsv_tokens_per_round            histogram  committed tokens per round (+bonus)
ffsv_batch_occupancy             histogram  live slots / max slots per tick
ffsv_kv_cache_utilization        histogram  mean seq_len / max_seq over live
ffsv_prefill_step_seconds        histogram  a prefill step's span (see below)
ffsv_decode_block_seconds        histogram  device-fenced decode block time
ffsv_decode_width                gauge      tokens a row of the last decode block
ffsv_spec_block_seconds          histogram  device-fenced speculation block
ffsv_request_latency_seconds     histogram  admission -> finish
ffsv_request_ttft_seconds        histogram  admission -> first token
ffsv_request_queue_wait_seconds  histogram  admission -> batch-slot grant
ffsv_request_prefill_seconds     histogram  slot grant -> first token
ffsv_per_token_latency_seconds   histogram  latency / output tokens
ffsv_spec_effective_depth        histogram  controller depth per spec round
ffsv_spec_fallback_total         counter    requests parked on incremental
ffsv_spec_fallback_active        gauge      requests currently parked
ffsv_spec_acceptance_ewma        gauge      mean controller acceptance EWMA
ffsv_jit_cache_misses_total      counter    engine block compiles (traces)
ffsv_engine_retraces_total       counter    compiles BEYOND each engine's 1st
ffsv_failovers_total             counter    crash re-dispatches to survivors
ffsv_prefix_cache_hits_total     counter    admission lookups matching a prefix
ffsv_prefix_cache_misses_total   counter    admission lookups with no match
ffsv_prefix_cache_evictions_total counter   pooled prefixes LRU-evicted
ffsv_prefix_shared_tokens_total  counter    prompt tokens served from the pool
ffsv_prefix_pool_tokens          gauge      tokens held by the prefix pool
ffsv_kv_cache_bytes              gauge      {kind} bytes of the caches of a kind
ffsv_attn_positions_read_total   counter    {kind} layer-positions decode read
ffsv_attn_positions_held_total   counter    layer-positions decode rows held (chunked)
ffsv_attn_prefill_entries_total  counter    layer-entries prefill segments read (chunked)
ffsv_moe_routed_pairs_total      counter    {phase} (token, expert) pairs run
ffsv_moe_tokens_total            counter    {phase} real tokens the experts saw
ffsv_moe_experts_touched         summary    {phase} distinct experts a call read
ffsv_moe_resident_calls_total    counter    {phase} calls that kept their rows in VMEM
ffsv_moe_expert_pairs_total      counter    {expert} routed pairs of one expert
ffsv_moe_zero_pairs_total        counter    {phase} picks that were no expert (w * x)
ffsv_cca_tails_total             counter    {phase,source} rows by where their tail came from
ffsv_kda_states_total            counter    {phase,source} rows by where their recurrent state came from (any recurrent op)
ffsv_kda_state_steps_total       counter    live rows x recurrent layers x steps of the decode blocks (any recurrent op)
ffsv_kda_chunk_tokens_total      counter    prefill tokens x recurrent layers through a chunked form's kernel
===============================  =========  =================================

A decode block's step is one token a row, or one pass over a row's block
where the model fills blocks by diffusion (``FFModel.block_diffusion``;
no other model has the ``ffsv_diffusion_*`` series): a row-pass either
denoises, unmasking positions ``by`` ``threshold`` (every pick more
probable than it) or by ``floor`` (the schedule's most confident, where
fewer cleared it); the pass that leaves a block whole is when its tokens
come out, and the row's next pass stores the block in front of the block
it denoises. A row-pass that did nothing but store a block is a
``commit_passes``; the decode block runs none since it folds them
(serve/engine._diffusion_block), and the series stays at 0 as the measure
of what is left of them.

``kind`` is ``window``, ``full`` or ``latent``: a model with windowed
attention layers beside full ones keeps a ring a windowed layer and every
position a full one; a model of latent layers (ops/latent_attention.py) one
shared entry a position (``FFModel.attention_kinds``; no other model has
these series). A model of CHUNKED layers (``eva_window``: an exact window
and one summary a chunk of the positions before it, two extents in one
stream) has ``ffsv_kv_cache_bytes{kind="chunked"}`` and counts what its
decode steps read by extent: ``kind="summary"`` (the summary rows of the
windows before a row's own) and ``kind="chunk_window"`` (its window's
positions up to its own), both layer-entries of the same bytes;
``ffsv_attn_positions_held_total`` is the positions those rows held (what
a full cache would have read), ``ffsv_attn_prefill_entries_total`` what the
prefill steps' segments had to read, once a segment.
A model whose attention layers CARRY A TAIL (ops/cca_attention.py: the last
positions' unmixed latents, a slot's state beside a plain k/v cache, which
every step overwrites; ``"tail_bytes"`` under ``attention_kinds["full"]``)
has ``kind="full"`` for its caches, ``ffsv_kv_cache_bytes{kind="tail"}`` for
the tails, and ``ffsv_cca_tails_total{phase,source}``: a prefill step's
segments and a decode block's row-steps by where their tail came from, on
the host from the step's own rows: ``start`` (position 0: zeros), ``step``
(another segment of the same step, the same slot's, that ends where this
one starts) or ``state`` (what an earlier step left in the slot).
A model with layers that keep a RECURRENT STATE (the contract of
ops/recurrent.py: a gated delta rule, ops/kda_attention.py, or a state-space
mixer, ops/ssd_mixer.py; a slot holds one state a layer, the sum of every
position so far, and the convolutions' tails;
``attention_kinds["recurrent"]``, which names the op under ``op``; the
``kda`` in the three series' names is historical, they count the kind
whatever the op: the benchmark's readers hold the names) has
``ffsv_kv_cache_bytes{kind="recurrent"}`` (states and tails) beside
``kind="full"`` (its plain k/v caches, the only kind whose positions are
read), ``ffsv_kda_states_total{phase,source}``, the twin of
``ffsv_cca_tails_total`` (``step``: the hand-over of a state inside one
prefill step), and ``ffsv_kda_state_steps_total``: live rows x recurrent
layers x steps of the decode blocks, each one state read and written;
and, where a prefill step's chunked form runs as a kernel (``kda_chunk``;
the mixer's is plain XLA: ``attention_kinds["recurrent"]["chunk_kernel"]``),
``ffsv_kda_chunk_tokens_total``: prefill tokens x recurrent layers.
A model with a LOOP REGION (ops/loop.py: a span of layers run several times
over one set of weights, a cache plane a pass; ``FFModel.loop_region``) has
``kind="full"`` over ALL its planes (passes x layers: what a decode step
reads) and ``ffsv_loop_layer_steps_total{phase}``: the layer-applications
the steps' real tokens went through, tokens x the span's layers added once
a pass INSIDE the device loop (``ops/loop.count_pass``, in the op state as
the routed experts' counters are, fetched with a snapshot), with
``ffsv_loop_tokens_total{phase}``, those tokens counted beside them: the
one over the span's layers and the other is the passes a token ran.
``ffsv_kv_cache_bytes`` is what compile allocated for each kind;
``ffsv_attn_positions_read_total`` is what the rows of the decode steps had
to attend, from the batch's lengths on the host: for each row of each step
its length, cut to the window in a windowed layer, times the layers of the
kind (times ``families``' bytes a position a layer, the cache bytes a step
must read).

The ``ffsv_moe_*`` series are the routed-expert op's (ops/moe.py), which
counts ON THE DEVICE, in its op state, as the steps run; ``watch_model``
makes the registry fetch those counters when a snapshot or a scrape is
taken (one small device-to-host read) and at no other time. They are sums
over the model's expert layers: a token counts once per layer, and
``ffsv_moe_experts_touched``'s count is layer-steps, of which
``ffsv_moe_resident_calls_total`` are those whose rows the kernel gathered
and whose results it weighted and added itself (a step that fits in VMEM
beside the weights' buffers, kernels/moe.rows_fit: static a program). A layer that holds a
share of its router's experts (``held``) counts its own: ``expert`` is the
held index, and a pair routed to an expert held elsewhere is no pair.
``phase`` is
``decode``, ``prefill`` or ``verify``, fixed when a program is traced.
``ffsv_moe_zero_pairs_total`` exists only for a model whose router has
indices that name no expert (``zero_experts``): the picks of those, which
add ``w * x``, are no ``routed`` pair and touch nothing.

Batch-level spans (``tracing.SpanTracer.begin``/``end``, ``tid`` 0): the
Python scheduler loops open a ``RoundTrace`` per iteration (``sched_round``
with the leaves ``sched_admit`` / ``sched_build`` / ``sched_commit``), and
every device call records ``call_stage`` / ``call_launch`` / ``call_wait``
through ``ServingTelemetry.call_phase`` (inside a ``spec_block`` span for
the fused engines), in sequence and never two open. A call's wait may come
after the next call's launch (``stage k+1, launch k+1, wait k``: a lagged
prefill step, in the incremental loop and in the fused speculation loop;
the incremental loop's decode block too, where a request is still filling:
the next round's first step, its LEAD step, is staged and launched between
the block's ``call_launch`` and its ``call_wait``, and waited for in that
next round, whose ``RoundTrace`` is handed it); README "Telemetry" has the
tables. A ``prefill`` span says whose cache the
step filled (``model``: ``llm``, ``ssm<i>``) and, on a lead step's,
``ahead``; a ``spec_block`` span what it was launched behind (``behind``:
``prefill`` or nothing).

Fleet layer (this package's distributed half): ``fleet.FleetTelemetry``
keeps one ServingTelemetry per replica (distinct Chrome-trace ``pid``
rows, merged registries via ``MetricsRegistry.merge``), ``slo`` holds
the error-budget burn-rate alerting the load harnesses report, and
``flight_recorder`` is the bounded per-replica event ring the
ReplicaPool dumps as a JSONL incident report on crash detection.

The request-level SLO histograms (latency/ttft/queue-wait/prefill/
per-token) carry a sliding window (``slo_window_s``, default 60 s):
``/metrics`` additionally exports ``<name>_window`` summaries with exact
p50/p90/p99 over the trailing window, so a scrape under load reads the
CURRENT tail, not the whole-run aggregate (serve/loadgen.py's live-SLO
contract).

Timing honesty: block/step timings are recorded by the serving loop
AROUND device calls whose results the host has waited for (``np.asarray``
of the packed block output; for an output-free prefill step a
``block_until_ready`` on the output its program hands back beside the
donated op_state, the last layer's hidden state, which stays on the
device: ``PendingPrefill``), so a recorded time never measures
the enqueue alone (utils/profiling.py protocol). Both loops that serve
traffic (incremental; fused speculation) make a prefill step's wait only
after the round's NEXT device call has been launched (the next step, the
decode block, the speculation block), so the device is never left with
nothing queued for the measurement's sake; the incremental loop's decode
block is read back only after the next round's lead step has been launched
behind it, telemetry or not; a span therefore starts where
the last recorded call's ended if that is later than its own launch
(``own_start``: the ``prefill`` and ``decode_block`` spans, the
``spec_block`` span, the ``decode_round`` spans spread over it). The spans
of calls queued behind each other do not overlap, each brackets its own
call's device time, and ``ffsv_prefill_step_seconds`` (and
``ffsv_decode_block_seconds``, ``ffsv_spec_block_seconds``) observes the
span's length: a call's service time on a busy device, staging and launch
included only for a call that found the device idle. The boundary between
two such spans is the host's return from the earlier call's wait, so it
is as late as the host is in learning that a call ended (milliseconds on
a shared host: PERF.md 7 (s)): a round's sum is exact, its split is not,
at either end of a block that has a step queued behind it.
"""

from __future__ import annotations

import time
import weakref
from typing import Optional

import jax
import numpy as np

from flexflow_tpu.telemetry.metrics import (
    COUNT_BUCKETS,
    DEFAULT_TIME_BUCKETS,
    FRACTION_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsHTTPServer,
    MetricsRegistry,
    Summary,
    percentile,
)
from flexflow_tpu.telemetry.flight_recorder import (FlightRecorder,
                                                    load_incident_report)
from flexflow_tpu.telemetry.slo import SLOMonitor, SLOPolicy, replay_records
from flexflow_tpu.telemetry.tracing import (SpanTracer, load_jsonl,
                                            mint_trace_id,
                                            stitch_chrome_trace)


class RoundTrace:
    """The phase spans of one scheduler-loop iteration (``sched_round``
    on ``tid`` 0). Exactly one leaf is open at a time: ``sched_admit``
    from the start, then whatever ``phase`` names; ``phase(None)`` closes
    the open leaf before a device call, whose own ``call_*`` leaves
    (``ServingTelemetry.call_phase``) take over until the loop names the
    next phase. ``pending`` is the round's prefill step that was launched
    and is not waited for yet (``PendingPrefill``; the incremental loop
    and the fused speculation loop lag each step's wait by one device
    call): whoever launches the round's next call settles it between that
    call's launch and its own wait. It is handed on once, by a LEAD step:
    the incremental loop's step queued behind a round's decode block is the
    next round's first, and that round's trace is given it (``hand_on``,
    ``begin_round(pending=)``) and settles it like any other; no other
    step outlives its round.
    Built only with telemetry on (``begin_round``)."""

    __slots__ = ("tel", "round", "leaf", "args", "grants0", "reqs",
                 "tokens0", "pending")

    def __init__(self, tel: "ServingTelemetry", loop: str, slots: int,
                 pending: Optional["PendingPrefill"] = None):
        self.tel = tel
        self.args = {"loop": loop, "slots": slots}
        self.grants0 = tel.n_grants
        self.reqs = None
        self.tokens0 = 0
        self.pending = pending
        self.round = tel.tracer.begin("sched_round")
        self.leaf = tel.tracer.begin("sched_admit")

    def phase(self, name: Optional[str], reqs=None):
        """Close the open leaf and open ``name`` (a leaf of that name
        already open stays open). ``reqs``, given with ``sched_commit``,
        are the requests whose tokens the phase appends: the span
        reports how many it committed."""
        tr = self.tel.tracer
        leaf = self.leaf
        if leaf is not None and leaf[0] != name:
            if self.reqs is not None:
                tr.end(leaf, committed=sum(len(r.tokens) for r in self.reqs)
                       - self.tokens0)
                self.reqs = None
            else:
                tr.end(leaf)
            leaf = None
        if reqs is not None:
            self.reqs = reqs
            self.tokens0 = sum(len(r.tokens) for r in reqs)
        if leaf is None:
            self.leaf = tr.begin(name) if name else None

    def admitted(self, live: int, pending: int):
        """Admission is over: ``sched_admit`` closes with the slots it
        granted, the round notes its live and pending requests, and
        ``sched_build`` opens."""
        self.tel.tracer.end(self.leaf,
                            granted=self.tel.n_grants - self.grants0)
        self.args["live"] = live
        self.args["pending"] = pending
        self.leaf = self.tel.tracer.begin("sched_build")

    def note_cut(self, why: str):
        """Why this round's speculation block was held to one round
        (``prefill``, ``catch_up`` or ``probe``); the first cause
        stands."""
        self.args.setdefault("cut", why)

    def settle(self) -> Optional[str]:
        """Wait for the round's pending prefill step, if it has one, and
        say what was waited for (``prefill``; None: nothing was pending).
        Its ``call_wait`` is a leaf: no other may be open."""
        if self.pending is None:
            return None
        self.pending.settle()
        self.pending = None
        return "prefill"

    def hand_on(self) -> Optional["PendingPrefill"]:
        """Take the pending step out of the round, for the next round's
        trace: a lead step, whose ``prefill`` spans say ``ahead``."""
        step, self.pending = self.pending, None
        if step is not None:
            step.ahead = True
        return step

    def end(self):
        self.phase(None)
        self.settle()       # a round that ended without a block
        self.tel.tracer.end(self.round, **self.args)


class PendingPrefill:
    """A prefill step between its launch and the wait that times it. The
    step's program hands back its last hidden state beside the op state it
    donates onward (``InferenceManager.step``): ``settle`` waits on that output,
    never reads it, and records the step, its spans and its counters
    together. WHEN is the loop's choice: at once (the host-stepped
    speculation loop, a driver without a ``RoundTrace``), or after the next
    device call has been launched so that the device has work queued
    meanwhile (the incremental loop and the fused speculation loop).
    ``leaf``: whether the wait is a ``call_wait`` leaf (a loop with a
    ``RoundTrace``). ``model``: whose cache the step fills, onto its spans
    (``llm``, ``ssm<i>``). ``ahead``: a lead step (``RoundTrace.hand_on``).
    Built only with telemetry on; the launch time is taken here, on the
    clock ``settle`` reads."""

    __slots__ = ("tel", "rows", "positions", "leaf", "model", "t0", "out",
                 "ahead")

    def __init__(self, tel: "ServingTelemetry", rows, positions: int,
                 leaf: bool, model: str = "llm"):
        self.tel = tel
        self.rows = rows            # [(guid, start_pos, n_tokens)]
        self.positions = positions
        self.leaf = leaf
        self.model = model
        self.out = None             # the launched step's output, a future
        self.ahead = False
        self.t0 = time.perf_counter()

    def settle(self):
        """Once, after ``out`` is set."""
        tel = self.tel
        wait = (tel.call_phase(None, "call_wait", "prefill")
                if self.leaf else None)
        jax.block_until_ready(self.out)
        tel.call_phase(wait, None)
        self.out = None
        tel.record_prefill(time.perf_counter() - self.t0,
                           sum(n for _, _, n in self.rows), self.rows,
                           self.t0, positions=self.positions,
                           model=self.model, ahead=self.ahead)


def _carried_series(kinds) -> Optional[str]:
    """The counter of a model whose slots carry something every step
    overwrites, by ``FFModel.attention_kinds``: a tail beside a cache
    (ops/cca_attention.py), a recurrent state (ops/kda_attention.py,
    ops/ssd_mixer.py), or None."""
    if "recurrent" in (kinds or ()):
        return "ffsv_kda_states_total"
    if "tail_bytes" in (kinds or {}).get("full", ()):
        return "ffsv_cca_tails_total"
    return None


class ServingTelemetry:
    """One registry + tracer pair with the serving hook vocabulary.

    The hook methods keep every instrumentation site in the serving
    stack to one guarded line; they are the only place metric names are
    spelled, so the table in the module docstring stays the schema."""

    SLO_WINDOW_S = 60.0
    FLIGHT_CAPACITY = 512

    def __init__(self, trace_path: Optional[str] = None,
                 slo_window_s: Optional[float] = None,
                 pid: int = 1, process_name: Optional[str] = None,
                 flight_capacity: Optional[int] = None):
        self.registry = MetricsRegistry()
        self.tracer = SpanTracer(trace_path, pid=pid,
                                 process_name=process_name)
        # crash-forensics ring: hooks below append; the ReplicaPool
        # monitor dumps it as an incident report on crash detection
        self.flight = FlightRecorder(
            self.FLIGHT_CAPACITY if flight_capacity is None
            else flight_capacity)
        # slot grants so far: a scheduler round's ``sched_admit`` span
        # reports the difference over its admission phase
        self.n_grants = 0
        # when the last recorded prefill step or decode block ended
        self._call_end = 0.0
        win = self.SLO_WINDOW_S if slo_window_s is None else slo_window_s
        r = self.registry
        self.requests_total = r.counter(
            "ffsv_requests_total", "requests admitted")
        self.requests_finished = r.counter(
            "ffsv_requests_finished_total", "requests completed")
        # overload front door (serve/admission.py + request_manager):
        # every non-success terminal disposition gets its own counter so
        # a dashboard can see WHERE load is being shed
        self.requests_rejected = r.counter(
            "ffsv_requests_rejected_total",
            "submissions refused by admission control")
        self.requests_timed_out = r.counter(
            "ffsv_requests_timed_out_total",
            "requests whose deadline expired between decode rounds")
        self.requests_cancelled = r.counter(
            "ffsv_requests_cancelled_total",
            "requests cancelled host-side (LLM.cancel / ffsv_request_cancel)")
        self.requests_preempted = r.counter(
            "ffsv_requests_preempted_total",
            "slot evictions re-queueing a best-effort request for a "
            "deadline-at-risk one")
        self.submit_queue_depth = r.gauge(
            "ffsv_queue_depth",
            "submission queue depth (registered, not yet slotted)")
        self.tokens_generated = r.counter(
            "ffsv_tokens_generated_total", "output tokens committed")
        self.prefill_tokens = r.counter(
            "ffsv_prefill_tokens_total", "prompt tokens prefilled")
        self.prefill_positions = r.counter(
            "ffsv_prefill_positions_total",
            "positions prefill steps computed (batch rows x chunk)")
        self.prefill_pairs = r.counter(
            "ffsv_prefill_attended_pairs_total",
            "(query, key) pairs the prefill steps' rows had to attend, "
            "causal, in one attention layer")
        self.round_prefill_steps = r.histogram(
            "ffsv_round_prefill_steps",
            "prefill steps one round of the incremental loop dispatched "
            "(above 1: the round was allowed more than one)",
            buckets=COUNT_BUCKETS)
        self.round_prefill_allowance = r.histogram(
            "ffsv_round_prefill_allowance",
            "prefill steps StepCosts allowed one round of the incremental "
            "loop that began with a row decoding (the block's worth, times "
            "ffsv_round_prefill_weight)",
            buckets=COUNT_BUCKETS)
        self.round_prefill_weight = r.histogram(
            "ffsv_round_prefill_weight",
            "what that round's block was weighed by: the requests in slots "
            "(decoding + filling) over the rows decoding; 1 at a full "
            "batch",
            buckets=COUNT_BUCKETS)
        self.round_prefill_ahead = r.histogram(
            "ffsv_round_prefill_ahead",
            "one observation a decode block of the incremental loop: 1 "
            "where the next round's first prefill step was launched behind "
            "the block before its read-back, else 0",
            buckets=FRACTION_BUCKETS)
        self.spec_rounds = r.counter(
            "ffsv_spec_rounds_total", "speculation rounds executed")
        self.decode_steps = r.counter(
            "ffsv_decode_steps_total",
            "row-steps of incremental decode blocks (a step: one token a "
            "row, or one pass over a block-diffusion row's block)")
        self.diffusion_row_passes = r.counter(
            "ffsv_diffusion_row_passes_total",
            "passes the rows of a block-diffusion model's decode blocks "
            "ran")
        # nothing feeds it: the decode block stores every block in the
        # row's next denoise pass, and a reader of the share that such
        # passes take (what is left of them) reads 0, not nothing
        r.counter(
            "ffsv_diffusion_commit_passes_total",
            "row-passes that did nothing but store a whole block")
        self.diffusion_tokens = {
            by: r.counter(f'ffsv_diffusion_tokens_total{{by="{by}"}}',
                          "positions denoise passes unmasked: above the "
                          "confidence threshold, or the schedule's floor")
            for by in ("threshold", "floor")}
        self.acceptance_length = r.histogram(
            "ffsv_acceptance_length",
            "accepted draft tokens per speculation round",
            buckets=COUNT_BUCKETS)
        self.tokens_per_round = r.histogram(
            "ffsv_tokens_per_round",
            "committed tokens per round (accepted + bonus)",
            buckets=COUNT_BUCKETS)
        self.batch_occupancy = r.histogram(
            "ffsv_batch_occupancy", "live slots / max slots per host tick",
            buckets=FRACTION_BUCKETS)
        self.kv_utilization = r.histogram(
            "ffsv_kv_cache_utilization",
            "mean sequence length / max_seq over live requests",
            buckets=FRACTION_BUCKETS)
        self.prefill_seconds = r.histogram(
            "ffsv_prefill_step_seconds",
            "a prefill step's span: its time on a busy device")
        self.decode_block_seconds = r.histogram(
            "ffsv_decode_block_seconds",
            "device-fenced fused decode block time")
        self.decode_width = r.gauge(
            "ffsv_decode_width",
            "tokens a row the last decode block's steps computed: 1, or "
            "the verify width where a speculation engine verifies the model")
        self.spec_block_seconds = r.histogram(
            "ffsv_spec_block_seconds",
            "device-fenced fused speculation block time")
        self.request_latency = r.histogram(
            "ffsv_request_latency_seconds", "admission -> finish",
            window_s=win)
        self.request_ttft = r.histogram(
            "ffsv_request_ttft_seconds", "admission -> first token",
            window_s=win)
        self.request_queue_wait = r.histogram(
            "ffsv_request_queue_wait_seconds",
            "admission -> batch-slot grant", window_s=win)
        self.request_prefill = r.histogram(
            "ffsv_request_prefill_seconds",
            "batch-slot grant -> first token", window_s=win)
        self.per_token_latency = r.histogram(
            "ffsv_per_token_latency_seconds",
            "request latency / output tokens", window_s=win)
        # adaptive speculation controller (serve/spec_controller.py)
        self.spec_effective_depth = r.histogram(
            "ffsv_spec_effective_depth",
            "controller-chosen draft depth per speculation round",
            buckets=COUNT_BUCKETS)
        self.spec_fallback_total = r.counter(
            "ffsv_spec_fallback_total",
            "times a request was parked on incremental decoding")
        self.spec_fallback_active = r.gauge(
            "ffsv_spec_fallback_active",
            "requests currently parked on incremental decoding")
        self.spec_acceptance_ewma = r.gauge(
            "ffsv_spec_acceptance_ewma",
            "mean per-token acceptance EWMA over live spec requests")
        # compile observability (serve/engine.py): the engines count
        # their own _block_impl traces (the python body only executes
        # while XLA traces), so these count COMPILES exactly — the PR 15
        # "adaptive mixed batches never retrace" invariant as a metric
        self.jit_cache_misses = r.counter(
            "ffsv_jit_cache_misses_total",
            "fused engine block compiles (jit cache misses)")
        self.engine_retraces = r.counter(
            "ffsv_engine_retraces_total",
            "engine block compiles beyond each engine's expected first")
        self.failovers = r.counter(
            "ffsv_failovers_total",
            "crash re-dispatches of in-flight/queued requests to "
            "surviving replicas (serve/replica.py)")
        # shared-prefix KV cache (serve/prefix_cache.py, ISSUE 19)
        self.prefix_hits = r.counter(
            "ffsv_prefix_cache_hits_total",
            "admission-time prefix lookups that matched a pooled prefix")
        self.prefix_misses = r.counter(
            "ffsv_prefix_cache_misses_total",
            "admission-time prefix lookups with no usable match")
        self.prefix_evictions = r.counter(
            "ffsv_prefix_cache_evictions_total",
            "pooled prefixes evicted (LRU, token-budget pressure)")
        self.prefix_shared_tokens = r.counter(
            "ffsv_prefix_shared_tokens_total",
            "prompt tokens served from the shared-prefix pool "
            "(prefill FLOPs skipped)")
        self.prefix_pool_tokens = r.gauge(
            "ffsv_prefix_pool_tokens",
            "tokens currently held by the shared-prefix pool")

        # models whose on-device counters a snapshot reads (watch_model):
        # id -> [weak reference, the counters' values at the last snapshot]
        self._watched = {}
        r.add_collector(self._collect_moe)
        # the served model's loop-region counter, if it has one (watch_model)
        self.loop = None
        r.add_collector(self._collect_loop)

    # -- on-device counters (ops/moe.py) ----------------------------------
    def watch_model(self, model):
        """From now on every snapshot of the registry also reads
        ``model``'s routed-expert counters off the device. Called once a
        device call (serve/inference_manager.py); a model without such
        counters costs two dict lookups."""
        from flexflow_tpu.ffconst import OpType
        from flexflow_tpu.ops.loop import LOOP_COUNTERS
        from flexflow_tpu.ops.moe import MOE_COUNTERS, counter_fields

        if (id(model) not in self._watched
                and MOE_COUNTERS in (model.op_state or {})):
            self._watched[id(model)] = [
                weakref.ref(model), 0,
                counter_fields([ly for ly in model.layers
                                if ly.op_type == OpType.MOE_EXPERTS])]
        if self.loop is None and LOOP_COUNTERS in (model.op_state or {}):
            # a loop region's counter (ops/loop.py), read off the device
            # with every snapshot: [weak reference, its values at the last]
            self.loop = [weakref.ref(model), 0]
        for kind, a in (getattr(model, "attention_kinds", None)
                        or {}).items():     # rings beside full, or latent
            self.registry.gauge(
                f'ffsv_kv_cache_bytes{{kind="{kind}"}}',
                "bytes of the KV caches of one kind of attention layer"
                ).set(a["cache_bytes"])
            if "tail_bytes" in a:       # layers that carry a row's tail
                self.registry.gauge(
                    'ffsv_kv_cache_bytes{kind="tail"}',
                    "bytes of the tails kept beside the caches"
                    ).set(a["tail_bytes"])

    def note_attention_reads(self, kinds, lengths, steps: int):
        """A decode block of ``steps`` steps over rows whose caches hold
        ``lengths`` positions after the first step's append: the
        layer-positions each kind of attention layer has to read
        (``kinds``: ``FFModel.attention_kinds``). Returns what goes on the
        block's span beside ``steps`` and ``rows``: for a model of chunked
        layers ``{"entries": the layer-entries the block's rows had to
        read}``, else nothing."""
        at = np.asarray(lengths, np.int64)[:, None] + np.arange(steps)
        if "chunked" in kinds:
            return self._note_chunked_reads(kinds["chunked"], at)
        carried = _carried_series(kinds)
        if carried:
            # a row-step's tail (or state) is the stored one, but at
            # position 0
            self._note_tails("decode", carried, start=int((at == 1).sum()),
                             state=int((at > 1).sum()))
        for kind, a in kinds.items():
            if kind == "recurrent":     # reads its state, not positions
                self.registry.counter(
                    "ffsv_kda_state_steps_total",
                    "live rows x recurrent layers x steps of the decode "
                    "blocks: one state read and written each"
                    ).inc(int(at.size) * a["layers"])
                continue
            seen = at if a["window"] is None else np.minimum(at, a["window"])
            self.registry.counter(
                f'ffsv_attn_positions_read_total{{kind="{kind}"}}',
                "layer-positions the decode steps' rows had to attend"
                ).inc(int(seen.sum()) * a["layers"])
        return {}

    def _note_tails(self, phase: str, series: str, **by_source):
        for source, n in by_source.items():
            self.registry.counter(
                f'{series}{{phase="{phase}",source="{source}"}}',
                "rows of the steps by where their tail or recurrent state "
                "came from (a model whose attention layers carry one)"
                ).inc(n)

    def note_prefill_tails(self, kinds, runs):
        """A prefill step's ``runs`` [(slot, start, tokens)] over a model
        whose attention layers carry a tail or a recurrent state (``kinds``:
        ``FFModel.attention_kinds``; ops/inc_attention.carried_rows and
        ops/cca_attention.take_tails decide the same on the device): a run
        at position 0 starts from zeros, one that another run of the
        step's, the same slot's, ends in front of takes that run's end, any
        other what an earlier step left in the slot."""
        series = _carried_series(kinds)
        if not series:
            return
        ends = {(slot, sp + n) for slot, sp, n in runs if n}
        src = ["start" if sp == 0 else
               "step" if (slot, sp) in ends else "state"
               for slot, sp, n in runs if n]
        self._note_tails("prefill", series, **{
            s: src.count(s) for s in ("start", "step", "state")})
        a = kinds.get("recurrent", {})
        if a.get("chunk_kernel"):
            self.registry.counter(
                "ffsv_kda_chunk_tokens_total",
                "prefill tokens x recurrent layers that went through the "
                "chunked form's kernel (kda_chunk)"
                ).inc(sum(n for _, _, n in runs) * a["layers"])

    def _chunked_counter(self, name, n, layers):
        helps = {
            'ffsv_attn_positions_read_total{kind="summary"}':
                "layer-entries the decode steps' rows had to attend",
            'ffsv_attn_positions_read_total{kind="chunk_window"}':
                "layer-entries the decode steps' rows had to attend",
            "ffsv_attn_positions_held_total":
                "layer-positions the decode steps' rows held",
            "ffsv_attn_prefill_entries_total":
                "layer-entries the prefill steps' segments had to attend"}
        self.registry.counter(name, helps[name]).inc(int(n) * layers)

    def _note_chunked_reads(self, a, at):
        """``at`` [rows, steps]: the positions each row's cache holds after
        each decode step's append, over chunked layers ``a``
        (ops/kv_layout.py): the summaries of the windows before the last
        position's own and that window's positions up to it are what the
        step read."""
        last, W, c = at - 1, a["window"], a["chunk"]
        L = a["layers"]
        summaries = int((last // W * (W // c)).sum())
        window = int((last % W + 1).sum())
        self._chunked_counter(
            'ffsv_attn_positions_read_total{kind="summary"}', summaries, L)
        self._chunked_counter(
            'ffsv_attn_positions_read_total{kind="chunk_window"}', window, L)
        self._chunked_counter("ffsv_attn_positions_held_total", at.sum(), L)
        return {"entries": (summaries + window) * L}

    def note_chunked_prefill(self, a, runs):
        """A prefill step's ``runs`` [(start, tokens)] over chunked layers
        ``a``: a run's queries had the summaries before its window and the
        window up to its last position to read (once a run: the kernel
        streams them for the segment, not for each query)."""
        W, c = a["window"], a["chunk"]
        self._chunked_counter(
            "ffsv_attn_prefill_entries_total",
            sum(sp // W * (W // c) + (sp + n - 1) % W + 1
                for sp, n in runs if n), a["layers"])

    @staticmethod
    def _read_counters(model, key: Optional[str] = None):
        """The counters under ``key`` (None: the routed experts') as numpy,
        or None. The serving thread donates the op state to every device
        call and puts the new one in its place: a reader on another thread
        can catch the old array deleted, and looks again."""
        from flexflow_tpu.ops.moe import MOE_COUNTERS

        for _ in range(8):
            try:
                return np.asarray(model.op_state[key or MOE_COUNTERS])
            except (RuntimeError, KeyError):
                time.sleep(0.002)
        return None

    def _collect_loop(self):
        """``ffsv_loop_layer_steps_total{phase}`` and
        ``ffsv_loop_tokens_total{phase}``: what the device counted
        (ops/loop.LOOP_COUNTERS: uint32, wraps) since the last snapshot."""
        from flexflow_tpu.ops.loop import LOOP_COUNTERS, LOOP_PHASES

        model = self.loop and self.loop[0]()
        if model is None:
            return
        raw = self._read_counters(model, LOOP_COUNTERS)
        if raw is None:
            return
        gained = (raw - np.uint32(self.loop[1])).astype(np.int64)
        self.loop[1] = raw
        for phase, (layer_steps, tokens) in zip(LOOP_PHASES, gained):
            lab = f'{{phase="{phase}"}}'
            self.registry.counter(
                "ffsv_loop_layer_steps_total" + lab,
                "real tokens x the layers of the loop region's span, added "
                "once a pass on the device: the layer-applications the "
                "steps' tokens went through").inc(int(layer_steps))
            self.registry.counter(
                "ffsv_loop_tokens_total" + lab,
                "those tokens, counted on the device with them"
                ).inc(int(tokens))

    def _collect_moe(self):
        from flexflow_tpu.ops.moe import MOE_PHASES, ZERO_FIELD

        r = self.registry
        for key, watched in list(self._watched.items()):
            model = watched[0]()
            if model is None:
                del self._watched[key]
                continue
            raw = self._read_counters(model)
            if raw is None:
                continue
            # the device counts in uint32 and wraps; what a snapshot adds
            # is the difference since the last one, modulo 2**32, summed
            # over the layers (the rows)
            gained = (raw - np.uint32(watched[1])).astype(np.int64).sum(0)
            watched[1] = raw
            n, fields = len(MOE_PHASES), watched[2]
            pairs = gained[:-len(fields) * n]
            calls, tokens, routed, touched, resident, *zero = gained[
                len(pairs):].reshape(len(fields), n)
            assert len(zero) == (ZERO_FIELD in fields)
            for i, ph in enumerate(MOE_PHASES):
                lab = f'{{phase="{ph}"}}'
                r.counter("ffsv_moe_routed_pairs_total" + lab,
                          "(token, expert) pairs the routed experts ran"
                          ).inc(int(routed[i]))
                r.counter("ffsv_moe_tokens_total" + lab,
                          "real tokens the routed experts saw, per layer"
                          ).inc(int(tokens[i]))
                r.summary("ffsv_moe_experts_touched" + lab,
                          "distinct experts one expert-layer call read"
                          ).add(int(calls[i]), float(touched[i]))
                r.counter("ffsv_moe_resident_calls_total" + lab,
                          "expert-layer calls whose rows the kernel gathered "
                          "and added itself").inc(int(resident[i]))
                if zero:
                    r.counter("ffsv_moe_zero_pairs_total" + lab,
                              "picks of a router index that names no expert"
                              ).inc(int(zero[0][i]))
            for e, n_pairs in enumerate(pairs):
                r.counter(f'ffsv_moe_expert_pairs_total{{expert="{e}"}}',
                          "routed pairs of one expert, over layers"
                          ).inc(int(n_pairs))

    # -- hooks (serve/request_manager.py, serve/engine.py) ---------------
    def note_admission(self, guid: int, prompt_tokens: int,
                       max_new_tokens: int,
                       trace_id: Optional[str] = None):
        self.requests_total.inc()
        self.tracer.admission(guid, prompt_tokens, max_new_tokens,
                              trace_id=trace_id)
        self.flight.record("admission", guid=guid, trace_id=trace_id,
                           prompt_tokens=prompt_tokens,
                           max_new_tokens=max_new_tokens)

    def note_batch(self, pending: int, live: int, slots: int,
                   kv_fraction: Optional[float]):
        """Once per host scheduling tick that dispatched device work."""
        self.submit_queue_depth.set(pending)
        self.batch_occupancy.observe(live / max(1, slots))
        if kv_fraction is not None:
            self.kv_utilization.observe(kv_fraction)
        self.flight.record("batch", pending=pending, live=live,
                           slots=slots,
                           kv_fraction=(round(kv_fraction, 4)
                                        if kv_fraction is not None
                                        else None))

    def note_rejected(self, tenant: str, reason: str, queue_depth: int):
        """One admission rejection at the front door (serve/api.py's
        submit path, before any request is registered)."""
        self.requests_rejected.inc()
        self.submit_queue_depth.set(queue_depth)
        self.flight.record("rejection", tenant=tenant, reason=reason,
                           queue_depth=queue_depth)

    def note_preempted(self, guid: int):
        """One slot eviction: a running best-effort request re-queued so
        a deadline-at-risk higher-priority one takes its slot."""
        self.requests_preempted.inc()
        self.flight.record("preemption", guid=guid)

    def note_prefix_lookup(self, shared_tokens: int, pool_tokens: int):
        """One admission-time shared-prefix lookup (request_manager.
        _prefix_match): hit/miss, tokens the slot will NOT re-prefill,
        and the pool-occupancy gauge."""
        if shared_tokens > 0:
            self.prefix_hits.inc()
            self.prefix_shared_tokens.inc(shared_tokens)
        else:
            self.prefix_misses.inc()
        self.prefix_pool_tokens.set(pool_tokens)
        self.flight.record("prefix_lookup", shared_tokens=shared_tokens)

    def note_prefix_store(self, evicted: int, pool_tokens: int):
        """One insert-on-finish into the shared-prefix pool
        (request_manager._prefix_store), with how many LRU victims the
        token budget claimed to make room."""
        if evicted > 0:
            self.prefix_evictions.inc(evicted)
        self.prefix_pool_tokens.set(pool_tokens)

    def note_slot_grant(self, guid: int, slot: int):
        """One batch-slot grant (request_manager._grant): the queue-wait
        -> service boundary, recorded for crash forensics — "what was
        scheduled right before the crash" is the first question an
        incident report answers."""
        self.n_grants += 1
        self.flight.record("slot_grant", guid=guid, slot=slot)

    def note_retrace(self, engine: str, new_traces: int,
                     total_traces: int):
        """Compile-count accounting after an engine block call that
        traced: ``new_traces`` compiles happened during the call,
        bringing the engine's lifetime count to ``total_traces``. Every
        trace is a jit cache miss; anything beyond the engine's expected
        single compile is a retrace (the PR 15 no-retrace invariant
        violation, also flight-recorded — a retrace storm right before a
        crash is a classic incident signature)."""
        self.jit_cache_misses.inc(new_traces)
        retraces = min(int(new_traces), max(0, int(total_traces) - 1))
        if retraces > 0:
            self.engine_retraces.inc(retraces)
            self.flight.record("retrace", engine=engine,
                               traces=int(total_traces))

    def note_failover(self, guid: int, replica: int, target: int,
                      trace_id: Optional[str] = None):
        """One crash re-dispatch (serve/replica.py): the request keeps
        its trace_id; only the serving replica (and per-replica guid)
        changes."""
        self.failovers.inc()
        self.flight.record("failover", guid=guid, replica=replica,
                           target=target, trace_id=trace_id)

    def own_start(self, t0: float, end: float) -> float:
        """Where the span of a device call that was launched at ``t0`` and
        waited for until ``end`` starts: no earlier than the last recorded
        prefill step or decode block ended. A call launched behind another
        (a lagged prefill step, the block after it) ran on the device from
        then on, so the spans bracket each its own call's device time and
        do not overlap."""
        return max(t0, min(self._call_end, end))

    def _own_time(self, seconds: float, t0: Optional[float]):
        """(start, seconds) of the span of a prefill step or decode block
        launched at ``t0`` (None: ``seconds`` ago) and waited for until
        ``seconds`` later (``own_start``), which the next such span then
        starts no earlier than."""
        end = time.perf_counter() if t0 is None else t0 + seconds
        t0 = self.own_start(end - seconds, end)
        self._call_end = end
        return t0, end - t0

    def record_prefill(self, seconds: float, n_tokens: int, rows=(),
                       t0: Optional[float] = None, positions: int = 0,
                       model: str = "llm", ahead: bool = False):
        """``t0``: the step's launch on ``perf_counter`` (None: it ended
        just now), ``seconds`` from there to the end of its wait; the span
        and the histogram get the step's own time (``_own_time``).
        ``positions``: the batch rows x chunk the step's program computed,
        real tokens or padding. ``model``: onto every copy of the span
        (tracing.SpanTracer.prefill), as ``ahead`` is where it is set (a
        lead step: launched behind the decode block before it). Counters
        and spans move together, so a snapshot never counts a step whose
        span is not out yet."""
        t0, seconds = self._own_time(seconds, t0)
        self.prefill_seconds.observe(seconds)
        self.prefill_tokens.inc(n_tokens)
        self.prefill_positions.inc(positions)
        # token t of a run from start_pos sees the start_pos + t + 1
        # positions up to itself
        self.prefill_pairs.inc(sum(n * sp + n * (n + 1) // 2
                                   for _, sp, n in rows))
        extra = {"ahead": True} if ahead else {}
        for guid, start_pos, n in rows:
            self.tracer.prefill(guid, start_pos, n, t0, seconds, model,
                                **extra)

    def note_round_prefill(self, steps: int):
        """Once per round of the incremental loop: the prefill steps
        dispatched for it before its decode block, none included; its lead
        step, launched behind the block before, is its first."""
        self.round_prefill_steps.observe(steps)

    def note_round_allowance(self, allowed: int, weight: float):
        """Once per round of the incremental loop that began with a row
        decoding: the prefill steps the rule allowed it, and the weight
        (everyone resident over the rows decoding) it gave the block."""
        self.round_prefill_allowance.observe(allowed)
        self.round_prefill_weight.observe(weight)

    def note_round_ahead(self, ahead: bool):
        """Once per decode block of the incremental loop: whether the next
        round's first prefill step was launched behind it."""
        self.round_prefill_ahead.observe(float(ahead))

    def record_decode_block(self, seconds: float, steps: int, n_live: int,
                            guids=(), t0: Optional[float] = None,
                            width: int = 1, passes=None, reads=None):
        """``t0``: as in ``record_prefill``; a block launched behind a
        prefill step starts where that step's wait returned, so no prefill
        time falls inside a ``decode_block`` span. Every request's copy of
        the span carries the block's live rows, ``n_live``, and the tokens
        a row each step computed, ``width`` (InferenceManager.decode_width:
        one, the verify width of the engine that verifies the model, or a
        block-diffusion model's block). ``passes`` (such a model's
        inference_manager.BlockPasses; None: a token a row a step) feeds
        the ``ffsv_diffusion_*`` counters and gives the span ``committed``
        (tokens the call emitted). ``reads``: what
        ``note_attention_reads`` handed back for the block, onto the span."""
        t0, seconds = self._own_time(seconds, t0)
        self.decode_block_seconds.observe(seconds)
        self.decode_steps.inc(steps * n_live)
        self.decode_width.set(width)
        extra = dict(reads or {})
        if passes is not None:
            ran = {k: int(v.sum()) for k, v in passes.stats.items()}
            self.diffusion_row_passes.inc(ran["passes"])
            self.diffusion_tokens["threshold"].inc(ran["by_threshold"])
            self.diffusion_tokens["floor"].inc(ran["by_floor"])
            extra["committed"] = ran["count"]
        for g in guids:
            self.tracer.decode_block(g, steps, t0, seconds, int(n_live),
                                     int(width), **extra)
        self.flight.record("decode_block", seconds=round(seconds, 6),
                           steps=int(steps), n_live=int(n_live))

    def end_spec_block(self, span, **args):
        """Close a fused block's live ``spec_block`` span over the block's
        own time (``own_start``): launched behind a prefill step, the block
        ran on the device only from that step's end, and its
        ``call_stage`` leaf then lies before the span. A block that found
        nothing pending keeps the start ``begin`` gave it."""
        self.tracer.end(span, t0=self.own_start(span[3],
                                                time.perf_counter()), **args)

    def record_spec_block(self, seconds: float, n_acc: np.ndarray,
                          depths=None, t0: Optional[float] = None):
        """After one fused speculation block (all engines): ``n_acc`` is
        the packed [R, rounds] accepted-length matrix, -1 marking idle
        rounds. Called from engine.run_block, so bench/direct engine
        drivers are instrumented too, not just the RequestManager.
        ``depths`` (same shape, optional) is the per-round EFFECTIVE
        draft depth the adaptive controller ran each row under. ``t0``:
        the block's launch, ``seconds`` before the end of its wait; the
        histogram gets the block's own time (``own_start``)."""
        if t0 is not None:
            seconds = t0 + seconds - self.own_start(t0, t0 + seconds)
        self.spec_block_seconds.observe(seconds)
        valid = np.asarray(n_acc).ravel()
        mask = valid >= 0
        valid = valid[mask]
        self.spec_rounds.inc(int(valid.size))
        self.acceptance_length.observe_many(valid.tolist())
        self.tokens_per_round.observe_many((valid + 1).tolist())
        dv = None
        if depths is not None:
            dv = np.asarray(depths).ravel()[mask]
            self.spec_effective_depth.observe_many(dv[dv > 0].tolist())
        # flight-recorder round summary + depth decision, one event per
        # fused block (same granularity as every other hook)
        self.flight.record(
            "spec_block", seconds=round(seconds, 6),
            rounds=int(valid.size), committed=int((valid + 1).sum()),
            mean_acc=(round(float(valid.mean()), 3) if valid.size else 0.0),
            depths=(sorted(set(int(d) for d in dv[dv > 0]))
                    if dv is not None else []))

    def call_phase(self, prev, name: Optional[str], program: str = ""):
        """One device call's leaf spans, in order: ``call_stage`` (build
        and transfer the inputs), ``call_launch`` (the jitted call until
        it returns its futures), ``call_wait`` (the blocking read-back,
        or the wait on a prefill step's output). Closes ``prev`` (None:
        nothing open) and opens ``name`` (None: the call is over); returns
        the new token."""
        if prev is not None:
            self.tracer.end(prev)
        return self.tracer.begin(name, program=program) if name else None

    def begin_round(self, loop: str, slots: int,
                    pending=None) -> "RoundTrace":
        """Open one scheduler-loop iteration's ``sched_round`` span and
        its first phase, ``sched_admit``. ``pending``: the lead step the
        round before handed on (``RoundTrace.hand_on``)."""
        return RoundTrace(self, loop, slots, pending)

    def note_spec_controller(self, ewma_mean, n_fallback: int,
                             new_fallbacks: int):
        """Once per scheduling tick that consulted the adaptive
        speculation controller: batch-mean acceptance EWMA, requests
        currently parked on incremental decoding, and how many parked
        since the last tick."""
        if ewma_mean is not None:
            self.spec_acceptance_ewma.set(ewma_mean)
        self.spec_fallback_active.set(n_fallback)
        if new_fallbacks > 0:
            self.spec_fallback_total.inc(new_fallbacks)

    def trace_rounds(self, guid: int, committed_per_round, block_t0: float,
                     block_dur: float, rounds_in_block: int):
        """Per-request round events reconciled from a fused block;
        ``committed_per_round`` is [(round_idx, n_accepted, committed)].
        The rounds are spread over the block's own time (``own_start``),
        so none lies over the prefill step the block was launched behind."""
        end = block_t0 + block_dur
        block_t0 = self.own_start(block_t0, end)
        block_dur = end - block_t0
        for k, n, c in committed_per_round:
            self.tracer.decode_round(guid, k, n, c, block_t0, block_dur,
                                     rounds_in_block)

    def note_finish(self, guid: int, output_tokens: int, latency_s: float,
                    ttft_s: float, queue_wait_s: float = 0.0,
                    prefill_s: float = 0.0, status: str = "ok",
                    failovers: int = 0, preemptions: int = 0):
        self.requests_finished.inc()
        if status == "timed_out":
            self.requests_timed_out.inc()
        elif status == "cancelled":
            self.requests_cancelled.inc()
        self.tokens_generated.inc(output_tokens)
        if latency_s > 0:
            self.request_latency.observe(latency_s)
            self.per_token_latency.observe(
                latency_s / max(1, output_tokens))
        if ttft_s > 0:
            self.request_ttft.observe(ttft_s)
        if queue_wait_s > 0:
            self.request_queue_wait.observe(queue_wait_s)
        if prefill_s > 0:
            self.request_prefill.observe(prefill_s)
        self.tracer.finish(guid, output_tokens, latency_s, ttft_s,
                           status=status, failovers=failovers,
                           preemptions=preemptions)
        self.flight.record("finish", guid=guid, status=status,
                           output_tokens=int(output_tokens),
                           latency_s=round(latency_s, 6))

    def close(self):
        self.tracer.close()


# ---------------------------------------------------------------------------
# process-global switch (resolved per host-loop iteration, never cached
# across loops, so enabling mid-session takes effect at the next batch)
# ---------------------------------------------------------------------------

_telemetry: Optional[ServingTelemetry] = None
_exit_hook = False      # wait_for_profiler_stop registered with atexit


def enable_telemetry(trace_path: Optional[str] = None) -> ServingTelemetry:
    """Install (or replace) the global ServingTelemetry and return it."""
    global _telemetry, _exit_hook
    if _telemetry is not None:
        _telemetry.close()
    _telemetry = ServingTelemetry(trace_path)
    if not _exit_hook:
        import atexit

        atexit.register(wait_for_profiler_stop)
        _exit_hook = True
    return _telemetry


def wait_for_profiler_stop(limit_s: float = 600.0) -> int:
    """At exit of a process that switched telemetry on: join every thread
    that is still inside ``jax.profiler.stop_trace``, for at most
    ``limit_s`` seconds; returns how many were. Collecting a session takes
    30 s and 0.1 ms a device operation (three minutes for 5 s of a step of
    1400 small operations at 4 ms); a caller that stops its session on a
    daemon thread and gives up waiting for it (benchmark/run.py joins for
    120 s) then exits with that thread in the profiler's C++, CPython's
    finalization ends the thread on its way back into Python, the forced
    unwind meets a ``catch (...)`` and the process aborts, exit 134, with
    its work done and printed (PERF.md section 6, PR 51). Costs nothing
    where no thread is there."""
    import sys
    import threading
    import time

    def inside(frame):
        while frame is not None:
            code = frame.f_code
            if (code.co_name == "stop_trace"
                    and "profiler" in code.co_filename):
                return True
            frame = frame.f_back
        return False

    deadline = time.monotonic() + limit_s
    stopping = {ident for ident, frame in sys._current_frames().items()
                if inside(frame)}
    for t in threading.enumerate():
        if t.ident in stopping and t is not threading.current_thread():
            t.join(max(0.0, deadline - time.monotonic()))
    return len(stopping)


def disable_telemetry():
    global _telemetry
    if _telemetry is not None:
        _telemetry.close()
    _telemetry = None


def get_telemetry() -> Optional[ServingTelemetry]:
    return _telemetry


_fleets = None      # weak set of live FleetTelemetry instances


def _fleet_set():
    global _fleets
    if _fleets is None:
        import weakref

        _fleets = weakref.WeakSet()
    return _fleets


def register_fleet(fleet):
    """FleetTelemetry self-registers so process-wide aggregation
    (``aggregate_registry`` -> ``ffsv_metrics_dump``) sees every live
    replica pool. Weakly held: a collected pool drops out on its own."""
    _fleet_set().add(fleet)


def aggregate_registry() -> MetricsRegistry:
    """Process-wide fleet totals: the global registry (single-engine
    traffic) merged with every live fleet's per-replica registries —
    what a C host reads through the aggregated ``ffsv_metrics_dump``.
    Exact by construction (MetricsRegistry.merge); an empty process
    yields an empty registry."""
    regs = []
    tel = get_telemetry()
    if tel is not None:
        regs.append(tel.registry)
    for fleet in list(_fleet_set()):
        regs.extend(t.registry for t in fleet.replica_telemetries())
    return MetricsRegistry.merge(regs)


def ensure_telemetry(trace_path: Optional[str] = None) -> ServingTelemetry:
    """Enable the global telemetry if absent, otherwise keep the live
    instance (its registry survives) and attach ``trace_path`` to its
    tracer — warning, not silently dropping, if the tracer is already
    writing a DIFFERENT file. The one bootstrap used by LLM.compile,
    start_metrics_server, and the C-ABI host."""
    tel = get_telemetry()
    if tel is None:
        return enable_telemetry(trace_path)
    if trace_path and not tel.tracer.attach_file(trace_path):
        import warnings

        warnings.warn(
            f"telemetry trace path {trace_path!r} ignored: telemetry is "
            f"already tracing to {tel.tracer.path!r}", stacklevel=2)
    return tel


__all__ = [
    "COUNT_BUCKETS",
    "DEFAULT_TIME_BUCKETS",
    "FRACTION_BUCKETS",
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "MetricsHTTPServer",
    "MetricsRegistry",
    "RoundTrace",
    "SLOMonitor",
    "SLOPolicy",
    "ServingTelemetry",
    "SpanTracer",
    "aggregate_registry",
    "disable_telemetry",
    "enable_telemetry",
    "ensure_telemetry",
    "get_telemetry",
    "load_incident_report",
    "load_jsonl",
    "mint_trace_id",
    "percentile",
    "register_fleet",
    "replay_records",
    "stitch_chrome_trace",
]
