"""Per-request span tracing: structured JSONL events, Perfetto-loadable.

Every serving request emits a span sequence — admission -> prefill
chunk(s) -> decode/speculation rounds -> finish — as Chrome Trace Event
Format objects, one JSON object per line (JSONL). Each event carries the
request guid as its ``tid``, so Perfetto renders one track per request;
``pid`` identifies the serving process (1 for a single engine; replica
pools assign one pid per replica and ``stitch_chrome_trace`` merges the
per-replica tracers onto one clock-corrected timeline, correlated by the
fleet-wide ``args.trace_id``). ``export_chrome_trace`` wraps the
buffered events into a ``{"traceEvents": [...]}`` file that Perfetto /
chrome://tracing load directly (the raw JSONL is for programmatic
consumption: one ``json.loads`` per line).

Batch-level spans (``begin``/``end``, ``tid`` 0): what the scheduler
loop and one device call do once per occurrence, whatever the number of
requests in the batch (``sched_round`` > ``sched_admit`` / ``sched_build``
/ ``sched_commit``; ``spec_block`` > ``call_stage`` / ``call_launch`` /
``call_wait``; README "Telemetry" has the table). Each is also entered as
a ``jax.profiler.TraceAnnotation`` under the same name, so a
``jax.profiler`` trace taken around the run shows it on the host plane,
on the profiler's own clock, beside the device plane.

Correlation with device traces: spans written after the fact (the
per-request tracks) carry ``perf_counter`` times only. ``profiler_mark``
drops a named annotation into the running profiler session and records
its ``perf_counter`` time as an instant event under the same name; the
difference between the two is the offset between the clocks
(``tools/profile_trace.mark_offset_ns``;
``utils/profiling.profiler_trace`` writes a mark at both ends of its
session when telemetry is on). The ``clock_sync`` metadata record holds
the ``perf_counter`` origin all span timestamps are relative to. Span
events also carry the guid in ``args`` so a device-trace step can be
matched to the request(s) it served.

Round-granularity caveat: speculation/decode rounds execute INSIDE one
fused device program (serve/engine.py), so the host only observes the
block's fenced wall time plus per-round acceptance counts after the
fact. Round events are therefore reconstructed with the block duration
divided evenly across its rounds — per-round ordering and counts are
exact, per-round timestamps are block-granular estimates.
"""

from __future__ import annotations

import itertools
import json
import time
from typing import IO, Iterable, List, Optional

from jax.profiler import TraceAnnotation

# Process-wide trace-id mint (serve/api.py front door + serve/replica.py
# pool). A counter, not a UUID: runs replay deterministically, and the
# id only needs to be unique within one serving process/trace file. The
# hex digits keep grep-ability ("t-0000002a") without dragging in
# entropy the tests would have to mock out.
_trace_counter = itertools.count(1)

# name prefix of the marks ``SpanTracer.profiler_mark`` writes into a
# profiler session (tools/profile_trace.py finds them by it)
MARK_PREFIX = "ffsv_mark_"
_mark_counter = itertools.count()


def mint_trace_id() -> str:
    """New distributed-trace id. Minted ONCE per request at the front
    door (submit/pool dispatch) and carried unchanged across failover
    re-dispatch, preemption re-queue, and the native shadow path — the
    correlation key that stitches a request's spans across replicas."""
    return f"t-{next(_trace_counter):08x}"


class SpanTracer:
    """Buffers trace events; optionally appends them to a JSONL file.

    The in-memory buffer is a RING of the most recent ``max_events``
    (default 64k) so a long-lived serving process cannot grow without
    bound — the JSONL file, when a path is given, still receives every
    event. The ``clock_sync`` epoch record is kept outside the ring so
    exports stay alignable however much history has rotated out.
    """

    FLUSH_EVERY = 128

    def __init__(self, path: Optional[str] = None, max_events: int = 65536,
                 pid: int = 1, process_name: Optional[str] = None):
        from collections import deque

        self.path = path
        self.pid = int(pid)
        self._ring = deque(maxlen=max(1, int(max_events)))
        self._sync: Optional[dict] = None
        self._name_ev: Optional[dict] = None
        # guid -> trace_id, registered at admission and stamped into
        # every subsequent span's args (popped at finish). Distinct from
        # tid=guid: the guid is per-replica, the trace_id is fleet-wide.
        self._ids = {}
        self._file: Optional[IO[str]] = None
        self._n_written = 0
        self._t0 = time.perf_counter()
        if path:
            self._file = open(path, "w")
        self.emit("clock_sync", "M", ts_s=self._t0,
                  perf_counter_origin=self._t0)
        if process_name:
            # Chrome-trace process_name metadata: Perfetto labels this
            # pid's row group (one group per replica in a stitched trace)
            ev = {"name": "process_name", "ph": "M", "pid": self.pid,
                  "tid": 0, "ts": 0.0, "args": {"name": process_name}}
            self._name_ev = ev
            if self._file is not None:
                self._file.write(json.dumps(ev) + "\n")
                self._n_written += 1

    @property
    def events(self) -> List[dict]:
        """clock_sync (+ process_name) + the retained event window."""
        head = [e for e in (self._sync, self._name_ev) if e]
        return head + list(self._ring)

    def attach_file(self, path: str) -> bool:
        """Start writing JSONL to ``path`` on an already-live tracer,
        seeding it with the retained event window. Re-attaching the
        SAME path is a no-op success; returns False (and does nothing)
        only if a DIFFERENT trace file is already attached."""
        if self._file is not None:
            return self.path == path
        self.path = path
        self._file = open(path, "w")
        for ev in self.events:
            self._file.write(json.dumps(ev) + "\n")
        self._file.flush()
        return True

    # -- core -------------------------------------------------------------
    def _us(self, t: Optional[float]) -> float:
        return ((time.perf_counter() if t is None else t) - self._t0) * 1e6

    def emit(self, name: str, ph: str, guid: Optional[int] = None,
             ts_s: Optional[float] = None, dur_s: Optional[float] = None,
             **args):
        """Record one Trace Event Format object. ``ph``: "X" complete
        span (needs dur_s), "i" instant, "M" metadata. ``ts_s``/``dur_s``
        are perf_counter-based seconds; ts defaults to now. A trace_id
        registered for ``guid`` (via admission) is stamped into args."""
        ev = {"name": name, "ph": ph, "pid": self.pid,
              "tid": int(guid) if guid is not None else 0,
              "ts": round(self._us(ts_s), 1)}
        if dur_s is not None:
            ev["dur"] = round(dur_s * 1e6, 1)
        if ph == "i":
            ev["s"] = "t"            # thread-scoped instant
        if guid is not None and "trace_id" not in args:
            tid = self._ids.get(int(guid))
            if tid is not None:
                args["trace_id"] = tid
        if args:
            ev["args"] = args
        if ev["name"] == "clock_sync":
            self._sync = ev
        else:
            self._ring.append(ev)
        if self._file is not None:
            # buffered write; flushed every FLUSH_EVERY events and on
            # close()/flush() — a per-event fsync-style flush would put
            # syscall pairs inside the serving host loop
            self._file.write(json.dumps(ev) + "\n")
            self._n_written += 1
            if self._n_written % self.FLUSH_EVERY == 0:
                self._file.flush()

    # -- batch-level spans, live on the profiler's clock too --------------
    def begin(self, name: str, **args):
        """Open a batch-level span (``tid`` 0): enters a profiler
        annotation under ``name`` and returns the token ``end`` closes.
        Tokens close in the reverse order they opened."""
        ann = TraceAnnotation(name, **args)
        ann.__enter__()
        return (name, ann, args, time.perf_counter())

    def end(self, span, t0: Optional[float] = None, **args):
        """Close ``span`` and record it as one complete event; ``args``
        join those given to ``begin``. ``t0``: where the recorded event
        starts, if later than ``begin`` (a device call's span that had to
        wait for the call before it: ``ServingTelemetry.own_start``); the
        profiler's annotation keeps the whole."""
        name, ann, args0, began = span
        now = time.perf_counter()
        ann.__exit__(None, None, None)
        t0 = began if t0 is None else t0
        self.emit(name, "X", ts_s=t0, dur_s=now - t0, **args0, **args)

    def profiler_mark(self) -> str:
        """Tie this tracer's clock to a running ``jax.profiler`` session:
        an empty annotation goes into the session and an instant event of
        the same name records the ``perf_counter`` time it was written
        at. Returns the mark's name."""
        name = f"{MARK_PREFIX}{next(_mark_counter)}"
        t = time.perf_counter()
        with TraceAnnotation(name):
            pass
        self.emit(name, "i", ts_s=t, perf_counter_s=t)
        return name

    # -- span vocabulary (the JSONL schema documented in README) ----------
    def admission(self, guid: int, prompt_tokens: int, max_new_tokens: int,
                  trace_id: Optional[str] = None):
        if trace_id:
            self._ids[int(guid)] = trace_id
        self.emit("admission", "i", guid, request_guid=guid,
                  prompt_tokens=prompt_tokens,
                  max_new_tokens=max_new_tokens)

    def prefill(self, guid: int, start_pos: int, n_tokens: int,
                ts_s: float, dur_s: float, model: str = "llm", **extra):
        """``model``: whose cache the step filled, ``llm`` (the model that
        is verified, or decodes alone) or ``ssm<i>`` (draft ``i``).
        ``extra``: ``ahead=True`` on a lead step's. The same on every
        request's copy of the span."""
        self.emit("prefill", "X", guid, ts_s=ts_s, dur_s=dur_s,
                  request_guid=guid,
                  start_pos=start_pos, n_tokens=n_tokens, model=model,
                  **extra)

    def decode_block(self, guid: int, steps: int, ts_s: float,
                     dur_s: float, rows: int, width: int = 1, **extra):
        """``rows``: the block's live rows; ``width``: the tokens a row
        each step computed (one of them real; all of them where the model
        fills blocks by diffusion: its block length, a pass computing two
        blocks a row; its blocks also carry ``committed``).
        The same on every request's copy of the span."""
        self.emit("decode_block", "X", guid, ts_s=ts_s, dur_s=dur_s,
                  request_guid=guid, steps=steps, rows=rows, width=width,
                  **extra)

    def decode_round(self, guid: int, round_idx: int, n_accepted: int,
                     committed: int, block_t0: float, block_dur: float,
                     rounds_in_block: int):
        """One speculation round, reconstructed from a fused block (see
        module docstring for the timestamp caveat)."""
        per = block_dur / max(1, rounds_in_block)
        self.emit("decode_round", "X", guid,
                  ts_s=block_t0 + round_idx * per, dur_s=per,
                  request_guid=guid,
                  round=round_idx, n_accepted=n_accepted,
                  committed_tokens=committed)

    def finish(self, guid: int, output_tokens: int, latency_s: float,
               ttft_s: float, status: str = "ok", failovers: int = 0,
               preemptions: int = 0):
        """Terminal span: carries the closed status taxonomy
        (ok|timed_out|cancelled|error) plus the disruption counts, so a
        trace query can partition requests by disposition without
        joining against the metrics registry."""
        self.emit("finish", "i", guid, request_guid=guid,
                  output_tokens=output_tokens,
                  latency_s=round(latency_s, 6),
                  ttft_s=round(ttft_s, 6),
                  status=status, failovers=int(failovers),
                  preemptions=int(preemptions))
        self._ids.pop(int(guid), None)

    # -- output -----------------------------------------------------------
    def export_chrome_trace(self, path: str):
        """Write the buffered events as one Perfetto-loadable JSON file."""
        with open(path, "w") as f:
            json.dump({"traceEvents": self.events,
                       "displayTimeUnit": "ms"}, f)

    def flush(self):
        if self._file is not None:
            self._file.flush()

    def close(self):
        if self._file is not None:
            self._file.close()
            self._file = None


def load_jsonl(path: str) -> List[dict]:
    """Parse a JSONL trace back into event dicts (test/analysis helper)."""
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def stitch_chrome_trace(tracers: Iterable["SpanTracer"],
                        path: Optional[str] = None) -> List[dict]:
    """Merge several tracers' buffered events into ONE Chrome trace on a
    common timeline (the fleet view: one pid row group per replica).

    Every tracer timestamps relative to its own ``perf_counter`` origin
    (its ``clock_sync`` record), so naive concatenation would overlay
    replicas spawned minutes apart at t=0. Correction: the EARLIEST
    origin becomes the fleet epoch and each tracer's events shift by
    ``(origin_i - origin_base) * 1e6`` µs — all tracers live in one
    process, so perf_counter deltas ARE the true skew.
    Per-tracer pids keep replica rows separate; a failed-over request's
    spans appear under BOTH pids sharing one ``args.trace_id``.

    Returns the merged event list; writes ``{"traceEvents": ...}`` JSON
    when ``path`` is given."""
    tracers = list(tracers)
    if not tracers:
        merged: List[dict] = []
    else:
        base = min(tr._t0 for tr in tracers)
        merged = []
        for tr in tracers:
            shift_us = (tr._t0 - base) * 1e6
            for ev in tr.events:
                ev = dict(ev)
                if ev.get("ph") != "M":
                    ev["ts"] = round(ev.get("ts", 0.0) + shift_us, 1)
                merged.append(ev)
    if path is not None:
        with open(path, "w") as f:
            json.dump({"traceEvents": merged, "displayTimeUnit": "ms"}, f)
    return merged
