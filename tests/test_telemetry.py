"""Telemetry subsystem tests (lean: one tiny spec pair shared across the
serving tests — the tier-1 budget is saturated, so geometry matches the
proven TINY config from test_serving and generation lengths stay small).

Covers: counter/histogram math + exact percentiles, Prometheus/JSON
export format, span lifecycle + JSONL/Chrome trace output, the /metrics
HTTP endpoint, a 2-round speculative decode recording the expected
acceptance-length events, the disabled path recording nothing, the
batch-level span vocabulary of the three Python scheduler loops (in the
tracer, and in a jax.profiler session with its clock marks), and the
benchmark's readers of those spans on hand-built traces (the lagged prefill
step's order of leaves itself: tests/test_telemetry_lag.py).
"""

import inspect
import json
import os
import sys
import urllib.request

import pytest

from flexflow_tpu.serve.request_manager import RequestManager
from flexflow_tpu.telemetry import (MetricsHTTPServer, MetricsRegistry,
                                    SpanTracer, disable_telemetry,
                                    enable_telemetry, get_telemetry,
                                    load_jsonl)


# ---------------------------------------------------------------------------
# instrument math + export (no models)
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_math():
    reg = MetricsRegistry()
    c = reg.counter("reqs", "requests")
    c.inc()
    c.inc(2)
    assert c.value == 3
    assert reg.counter("reqs") is c        # get-or-create returns existing
    g = reg.gauge("depth")
    g.set(7)
    assert g.value == 7
    h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count == 4 and h.sum == pytest.approx(55.55)
    snap = h.snapshot()
    # cumulative bucket counts: <=0.1:1, <=1:2, <=10:3, +Inf:4
    assert snap["buckets"] == [[0.1, 1], [1.0, 2], [10.0, 3], ["+Inf", 4]]
    # exact percentiles over retained samples (1..100 -> p50=50.5, p99=99.01)
    h2 = reg.histogram("pct", buckets=(1e9,))
    h2.observe_many(range(1, 101))
    assert h2.percentile(50) == pytest.approx(50.5)
    assert h2.percentile(99) == pytest.approx(99.01)
    with pytest.raises(TypeError):
        reg.counter("lat")                 # kind mismatch must raise


def test_prometheus_and_json_export():
    reg = MetricsRegistry()
    reg.counter("ffsv_requests_total", "requests admitted").inc(3)
    h = reg.histogram("ffsv_step_seconds", "step time", buckets=(0.01, 0.1))
    h.observe(0.005)
    h.observe(0.5)
    text = reg.to_prometheus()
    assert "# TYPE ffsv_requests_total counter" in text
    assert "ffsv_requests_total 3" in text
    assert "# TYPE ffsv_step_seconds histogram" in text
    assert 'ffsv_step_seconds_bucket{le="0.01"} 1' in text
    assert 'ffsv_step_seconds_bucket{le="+Inf"} 2' in text
    assert "ffsv_step_seconds_count 2" in text
    snap = json.loads(reg.to_json())
    assert snap["ffsv_requests_total"] == {"type": "counter", "value": 3}
    assert snap["ffsv_step_seconds"]["count"] == 2
    assert snap["ffsv_step_seconds"]["percentiles"]["p50"] > 0


def test_span_tracer_lifecycle(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    tr = SpanTracer(path)
    tr.admission(42, prompt_tokens=4, max_new_tokens=8)
    t0 = tr._t0
    tr.prefill(42, start_pos=0, n_tokens=3, ts_s=t0 + 0.001, dur_s=0.002)
    tr.decode_round(42, 0, n_accepted=2, committed=3, block_t0=t0 + 0.004,
                    block_dur=0.01, rounds_in_block=2)
    tr.finish(42, output_tokens=8, latency_s=0.02, ttft_s=0.005)
    tr.close()
    evs = load_jsonl(path)
    assert [e["name"] for e in evs] == ["clock_sync", "admission",
                                       "prefill", "decode_round", "finish"]
    assert all(e["tid"] == 42 for e in evs[1:])  # one track per request
    pre = evs[2]
    assert pre["ph"] == "X" and pre["dur"] == pytest.approx(2000, abs=1)
    assert pre["args"]["n_tokens"] == 3
    rnd = evs[3]
    assert rnd["args"]["n_accepted"] == 2
    assert rnd["dur"] == pytest.approx(5000, abs=1)   # block_dur / rounds
    # Perfetto/chrome form wraps the same events
    chrome = str(tmp_path / "trace.json")
    tr.export_chrome_trace(chrome)
    doc = json.load(open(chrome))
    assert [e["name"] for e in doc["traceEvents"]] == [e["name"] for e in evs]


def test_metrics_http_endpoint():
    reg = MetricsRegistry()
    reg.counter("ffsv_requests_total").inc(5)
    srv = MetricsHTTPServer(lambda: reg, port=0)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        text = urllib.request.urlopen(base + "/metrics").read().decode()
        assert "ffsv_requests_total 5" in text
        snap = json.loads(urllib.request.urlopen(
            base + "/metrics.json").read().decode())
        assert snap["ffsv_requests_total"]["value"] == 5
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# serving integration (tiny spec pair shared session-wide with test_loadgen
# via conftest.tiny_spec_pair — tier-1 budget: one build, many tests)
# ---------------------------------------------------------------------------

def test_spec_decode_records_expected_telemetry(tiny_spec_pair, tmp_path):
    """A 2-round speculative decode (depth 2, same-weights draft -> full
    acceptance, 3 tokens/round, 6-token budget) must produce the JSONL
    span trace plus a metrics snapshot with the exact acceptance-length
    events, per-round token counts, batch occupancy and p50/p99
    per-token latency — the subsystem's acceptance criteria."""
    from flexflow_tpu.serve.batch_config import GenerationConfig

    llm, ssm = tiny_spec_pair
    trace = str(tmp_path / "spec.jsonl")
    tel = enable_telemetry(trace_path=trace)
    try:
        rm = RequestManager()
        for p in [[5, 9, 23, 44], [7, 3, 11]]:
            rm.register_new_request(p, max_new_tokens=6)
        # static policy: the exact event counts below assume every round
        # speculates at depth 2; the adaptive controller would (rightly)
        # park this same-size draft pair on incremental decoding — its
        # own telemetry is covered in test_spec_controller.py
        results = rm.generate_spec_infer(
            llm, [ssm], spec_depth=2,
            generation_config=GenerationConfig(adaptive_spec=False))
        assert sorted(len(r.output_tokens) for r in results) == [6, 6]

        reg = tel.registry
        # full acceptance at depth 2: each request commits 3 tokens/round
        # for 2 rounds -> 4 round events, every accepted length == 2
        acc = reg.get("ffsv_acceptance_length")
        assert acc.count == 4 and acc.sum == 8
        tpr = reg.get("ffsv_tokens_per_round")
        assert tpr.count == 4 and tpr.sum == 12   # 3 committed per round
        assert reg.get("ffsv_spec_rounds_total").value == 4
        assert reg.get("ffsv_tokens_generated_total").value == 12
        assert reg.get("ffsv_batch_occupancy").count > 0
        assert reg.get("ffsv_batch_occupancy").percentile(50) == 1.0
        assert reg.get("ffsv_kv_cache_utilization").count > 0
        # the verifier's 5; the draft's 3 of the four-token prompt (the
        # three-token one rides in as its row's first accepted block)
        assert reg.get("ffsv_prefill_tokens_total").value == 8
        assert reg.get("ffsv_spec_block_seconds").count >= 1
        lat = reg.get("ffsv_per_token_latency_seconds")
        assert lat.count == 2
        assert 0 < lat.percentile(50) <= lat.percentile(99)
        assert reg.get("ffsv_request_latency_seconds").count == 2
        # queue-wait/service decomposition histograms (loadgen SLO seam)
        assert reg.get("ffsv_request_queue_wait_seconds").count == 2
        assert reg.get("ffsv_request_prefill_seconds").count == 2
        # SLO histograms carry the sliding window: fresh traffic is
        # inside it, so windowed p99 == whole-run exact p99 here
        win = reg.get("ffsv_request_latency_seconds").windowed_percentiles()
        assert win["count"] == 2
        assert win["p99"] == pytest.approx(
            reg.get("ffsv_request_latency_seconds").percentile(99))
        # exporters carry the same story
        text = reg.to_prometheus()
        assert "ffsv_acceptance_length_bucket" in text
        assert "ffsv_requests_finished_total 2" in text
    finally:
        disable_telemetry()      # closes + flushes the JSONL trace file

    # span trace: admission -> prefill -> decode rounds -> finish,
    # one track (tid) per request guid
    evs = load_jsonl(trace)
    names = [e["name"] for e in evs]
    assert names.count("admission") == 2 and names.count("finish") == 2
    rounds = [e for e in evs if e["name"] == "decode_round"]
    assert len(rounds) == 4
    assert all(e["args"]["n_accepted"] == 2 for e in rounds)
    guids = {r.guid for r in results}
    assert {e["tid"] for e in rounds} == guids
    assert any(e["name"] == "prefill" for e in evs)
    # latency fields surfaced on the results themselves (serve/api.py),
    # including the queue-wait/service decomposition: admission->slot +
    # slot->first-token exactly partition TTFT on this scheduler path
    assert all(r.latency_s > 0 and r.ttft_s > 0 for r in results)
    assert all(r.queue_wait_s >= 0 and r.prefill_s > 0 for r in results)
    assert all(r.ttft_s == pytest.approx(r.queue_wait_s + r.prefill_s)
               for r in results)


def test_disabled_path_records_no_events(tiny_spec_pair):
    """With telemetry disabled the decode round must record NOTHING — no
    global registry exists and a freshly enabled one afterwards is empty
    (the zero-overhead guard for the disabled path)."""
    llm, ssm = tiny_spec_pair
    disable_telemetry()
    assert get_telemetry() is None
    rm = RequestManager()
    rm.register_new_request([5, 9, 23, 44], max_new_tokens=6)
    (res,) = rm.generate_spec_infer(llm, [ssm], spec_depth=2)
    assert get_telemetry() is None          # nothing auto-enabled
    assert len(res.output_tokens) == 6
    assert res.latency_s > 0                # cheap always-on result fields
    tel = enable_telemetry()
    try:
        snap = tel.registry.snapshot()      # fresh registry: all zeros
        assert all(m.get("value", 0) == 0 and m.get("count", 0) == 0
                   for m in snap.values())
        assert len(tel.tracer.events) == 1  # clock_sync only
    finally:
        disable_telemetry()


# ---------------------------------------------------------------------------
# the anatomy of a scheduler round: batch-level spans on tid 0 (ISSUE 24)
# ---------------------------------------------------------------------------

LEAVES = ("sched_admit", "sched_build", "sched_commit",
          "call_stage", "call_launch", "call_wait")
VOCABULARY = LEAVES + ("sched_round", "spec_block")
LOOPS = ("incr", "spec_beam", "spec_tree")


def _serve(loop, llm, ssm, rm):
    """Three requests through two slots on one of the Python scheduler
    loops (the fused speculation loop under either engine: ``ssm`` is
    compiled at beam width 2 for ``spec_beam``): a refill mid-batch, so
    rounds with and without a prefill. ``incr_ahead``: the incremental
    loop, the first prompt 50 tokens long (four steps) and the two costs
    given, so that it is still filling when the first blocks are launched
    and no round is timed: rounds that begin with a lead step (ISSUE 61)."""
    from flexflow_tpu.serve.batch_config import GenerationConfig
    from flexflow_tpu.serve.step_costs import GivenCosts

    first = ([5, 9, 23, 44] if loop != "incr_ahead"
             else [(5 * i) % 96 + 1 for i in range(50)])
    for p in [first, [7, 3, 11], [9, 9, 4, 1, 2]]:
        rm.register_new_request(p, max_new_tokens=8)
    if loop == "incr":
        return rm.generate_incr_decoding(llm)
    if loop == "incr_ahead":
        ifm = RequestManager._manager_of(llm)
        ifm.step_costs = GivenCosts(1.0, 0.3)   # a block of 8: two steps
        try:
            return rm.generate_incr_decoding(llm)
        finally:
            del ifm.step_costs
    results = rm.generate_spec_infer(
        llm, [ssm], spec_depth=2,
        generation_config=GenerationConfig(adaptive_spec=False))
    assert rm.scheduler_loop == f"python:{loop}_fused"
    return results


@pytest.mark.parametrize("loop", LOOPS + ("incr_ahead",))
def test_scheduler_round_span_vocabulary(loop, tiny_spec_pair,
                                         tiny_beam_draft, monkeypatch):
    """A served batch emits each vocabulary span once per occurrence on
    tid 0; the leaves inside a sched_round do not overlap and cover it;
    spec_block.rounds is what the device ran (the executed columns of
    n_acc) and never more than the rounds asked for. ``incr_ahead``: a
    lead step's leaves lie between its block's launch and wait, its
    ``prefill`` span says ``ahead`` and starts where the block's ended, and
    ``ffsv_round_prefill_ahead`` has one observation a block."""
    from flexflow_tpu.serve import engine as eng

    llm, ssm = tiny_spec_pair
    if loop == "spec_beam":
        ssm = tiny_beam_draft
    blocks = []                       # (rounds asked, n_acc) per run_block
    for cls in (eng.BeamSpecEngine, eng.MultiSpecEngine):
        def spy(self, *a, _orig=cls.run_block,
                _sig=inspect.signature(cls.run_block), **kw):
            out = _orig(self, *a, **kw)
            asked = _sig.bind(self, *a, **kw).arguments["n_rounds"]
            blocks.append((int(asked), out[1].copy()))
            return out
        monkeypatch.setattr(cls, "run_block", spy)

    tel = enable_telemetry()
    try:
        results = _serve(loop, llm, ssm, RequestManager())
        events = tel.tracer.events
        reg = tel.registry
        n_decode_calls = reg.get("ffsv_decode_block_seconds").count
        n_prefill_calls = reg.get("ffsv_prefill_step_seconds").count
        n_spec_calls = reg.get("ffsv_spec_block_seconds").count
        ahead = reg.get("ffsv_round_prefill_ahead")
    finally:
        disable_telemetry()
    assert sorted(len(r.output_tokens) for r in results) == [8, 8, 8]
    ahead_loop, loop = loop == "incr_ahead", loop.split("_ahead")[0]

    batch = [e for e in events if e["ph"] == "X" and e["tid"] == 0]
    assert batch and {e["name"] for e in batch} <= set(VOCABULARY)
    # the per-request tracks are untouched: never on tid 0
    per_request = [e for e in events if e["ph"] == "X" and e["tid"] != 0]
    assert {e["name"] for e in per_request} <= {"prefill", "decode_block",
                                                "decode_round"}
    by = {n: [e for e in batch if e["name"] == n] for n in VOCABULARY}

    # once per occurrence: one sched_round an iteration, one sched_admit
    # in each, one stage/launch per device call, one wait per fenced call
    rounds = by["sched_round"]
    assert len(rounds) >= 2
    assert all(r["args"]["loop"] == loop and r["args"]["slots"] == 2
               and 0 <= r["args"]["live"] <= 2 for r in rounds)
    assert len(by["sched_admit"]) == len(rounds)
    assert sum(e["args"]["granted"] for e in by["sched_admit"]) == 3
    n_calls = n_decode_calls + n_prefill_calls + n_spec_calls
    for leaf in ("call_stage", "call_launch", "call_wait"):
        assert len(by[leaf]) == n_calls, leaf
        programs = [e["args"]["program"] for e in by[leaf]]
        assert programs.count("prefill") == n_prefill_calls
        assert programs.count("spec_block") == n_spec_calls
    committed = sum(e["args"].get("committed", 0)
                    for e in by["sched_commit"])
    assert committed == sum(len(r.output_tokens) for r in results)

    # spec_block: what the device ran, against what was asked
    assert len(by["spec_block"]) == n_spec_calls == len(blocks)
    assert (n_spec_calls > 0) == (loop != "incr")
    for ev, (asked, n_acc) in zip(by["spec_block"], blocks):
        ran = int((n_acc >= 0).any(axis=0).sum())
        assert ev["args"]["rounds"] == ran <= asked
        assert ev["args"]["rounds_asked"] == asked
        assert ev["args"]["rows"] == int((n_acc >= 0).any(axis=1).sum())
        assert ev["args"]["engine"] == ("BeamSpecEngine"
                                        if loop == "spec_beam"
                                        else "MultiSpecEngine")
    if loop != "incr":
        # the next block is handed the last block's accepted tokens: no
        # catch-up chunk, so only a prompt going in cuts a block short
        assert {r["args"].get("cut") for r in rounds} == {None, "prefill"}
        assert (max(asked for asked, _ in blocks)
                == llm.config.spec_rounds_per_call)

    # leaves: inside their round, in order, no overlap, and they cover it
    eps = 0.25                       # two roundings to 0.1 us
    leaves = sorted((e for e in batch if e["name"] in LEAVES),
                    key=lambda e: e["ts"])
    covered = 0.0
    for r in rounds:
        r0, r1 = r["ts"], r["ts"] + r["dur"]
        inside = [e for e in leaves if r0 - eps <= e["ts"] < r1]
        assert inside and inside[0]["name"] == "sched_admit"
        for a, b in zip(inside, inside[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + eps, (a, b)
        assert inside[-1]["ts"] + inside[-1]["dur"] <= r1 + eps
        covered += sum(e["dur"] for e in inside)
    assert len(leaves) == sum(
        1 for e in leaves
        if any(r["ts"] - eps <= e["ts"] < r["ts"] + r["dur"]
               for r in rounds))                 # no leaf outside a round
    assert covered >= 0.9 * sum(r["dur"] for r in rounds)

    # a lead step: one observation a decode block of the incremental loop
    assert ahead.count == (n_decode_calls if loop == "incr" else 0)
    calls = [(e["name"][5:], e["args"]["program"]) for e in leaves
             if e["name"].startswith("call_")]
    behind = [calls[i + 1:calls.index(("wait", "decode_block"), i)]
              for i, c in enumerate(calls) if c == ("launch", "decode_block")]
    step = [("stage", "prefill"), ("launch", "prefill")]
    # between a block's launch and its wait: the wait of the step before
    # it, then the lead step's stage and launch, if it has either
    assert all(b in ([], step, [("wait", "prefill")],
                     [("wait", "prefill")] + step) for b in behind)
    led = sum(b[-2:] == step for b in behind)
    assert ahead.sum == led and (led >= 2 if ahead_loop else led == 0)
    spans = {n: sorted({(e["ts"], e["ts"] + e["dur"]) for e in per_request
                        if e["name"] == n and (n == "decode_block"
                                               or e["args"].get("ahead"))})
             for n in ("decode_block", "prefill")}
    assert len(spans["prefill"]) == led
    for t0, t1 in spans["prefill"]:
        # its own time: from the end of the block it was queued behind
        assert any(abs(t0 - b1) <= eps for _, b1 in spans["decode_block"])
        assert all(t1 <= b0 + eps or b1 <= t0 + eps
                   for b0, b1 in spans["decode_block"])


def test_disabled_path_enters_no_annotation(tiny_spec_pair,
                                            tiny_beam_draft, monkeypatch):
    """Telemetry off: a batch served on each loop, under each engine,
    records no event and enters no profiler annotation."""
    from flexflow_tpu.telemetry import tracing

    llm, ssm = tiny_spec_pair
    entered = []

    class Counting(tracing.TraceAnnotation):
        def __enter__(self):
            entered.append(1)
            return super().__enter__()

    monkeypatch.setattr(tracing, "TraceAnnotation", Counting)
    disable_telemetry()
    for loop in LOOPS:
        results = _serve(loop, llm, tiny_beam_draft
                         if loop == "spec_beam" else ssm, RequestManager())
        assert len(results) == 3
    assert get_telemetry() is None and not entered
    tel = enable_telemetry()
    try:
        assert len(tel.tracer.events) == 1      # clock_sync only
        tel.tracer.end(tel.tracer.begin("sched_round"))
        assert entered                          # the spy does see one
    finally:
        disable_telemetry()


def _tools_profile_trace():
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    sys.path.insert(0, tools)
    try:
        import profile_trace
    finally:
        sys.path.pop(0)
    return profile_trace


def test_profiler_session_holds_spans_and_clock_mark(tiny_spec_pair,
                                                     tiny_beam_draft,
                                                     tmp_path):
    """A CPU jax.profiler session around a served batch holds the
    program's batch-level spans on its host plane under their names, and
    the clock marks by which the rest of the tracer's spans are put on
    the profiler's clock."""
    from flexflow_tpu.telemetry.tracing import MARK_PREFIX
    from flexflow_tpu.utils.profiling import profiler_trace

    pt = _tools_profile_trace()
    llm, ssm = tiny_spec_pair[0], tiny_beam_draft
    logdir = str(tmp_path / "prof")
    tel = enable_telemetry()
    try:
        with profiler_trace(logdir):
            _serve("spec_beam", llm, ssm, RequestManager())
        events = tel.tracer.events
    finally:
        disable_telemetry()

    marks = [e for e in events if e["name"].startswith(MARK_PREFIX)]
    assert len(marks) == 2 and all(e["ph"] == "i" for e in marks)
    on_plane = pt.host_annotations(logdir)
    names = {n for n, _, _ in on_plane}
    assert set(VOCABULARY) <= names
    assert {e["name"] for e in marks} <= names
    offset = pt.mark_offset_ns(logdir, events)
    assert offset is not None
    # aligned by marks, the tracer's sched_round spans fall on the
    # annotations of the same name (same count, starts within 0.5 ms)
    mine = sorted(s[1] for s in pt.spans_on_profiler_clock(logdir, events)
                  if s[0] == "sched_round")
    theirs = sorted(st for n, st, _ in on_plane if n == "sched_round")
    assert len(mine) == len(theirs) >= 2
    assert max(abs(a - b) for a, b in zip(mine, theirs)) < 5e5
    # the per-request tracks are written after the fact: only the marks
    # put them beside the device plane
    assert "decode_round" not in names
    assert any(s[0] == "decode_round"
               for s in pt.spans_on_profiler_clock(logdir, events))
    assert pt.mark_offset_ns(logdir, [e for e in events
                                      if e not in marks]) is None


# the benchmark's readers of the new spans, on a hand-built traced stretch
# of 100 ms (times below in microseconds after its start)
_ROUNDS = [     # (name, start, end, args)
    ("sched_round", 1000, 41000, {"loop": "spec_tree"}),
    ("sched_admit", 1000, 1500, {"granted": 1}),
    ("sched_build", 1500, 2000, {}),
    ("prefill", 2000, 12000, {"n_tokens": 8}),
    ("prefill", 2000, 12000, {"n_tokens": 8}),          # second request
    ("call_stage", 2000, 3000, {"program": "prefill"}),
    ("call_launch", 3000, 4000, {"program": "prefill"}),
    ("call_wait", 4000, 12000, {"program": "prefill"}),
    ("sched_build", 12000, 13000, {}),
    ("spec_block", 13000, 38000, {"rounds_asked": 4, "rounds": 2,
                                  "rows": 2}),
    ("call_stage", 13000, 16000, {"program": "spec_block"}),
    ("call_launch", 16000, 17000, {"program": "spec_block"}),
    ("call_wait", 17000, 38000, {"program": "spec_block"}),
    ("sched_commit", 38000, 41000, {"committed": 7}),
    ("sched_round", 50000, 90000, {"loop": "spec_tree", "cut": "catch_up"}),
    ("sched_admit", 50000, 50500, {"granted": 0}),
    ("sched_build", 50500, 52000, {}),
    ("spec_block", 52000, 84000, {"rounds_asked": 1, "rounds": 1,
                                  "rows": 2}),
    ("call_stage", 52000, 53000, {"program": "spec_block"}),
    ("call_launch", 53000, 54000, {"program": "spec_block"}),
    ("call_wait", 54000, 84000, {"program": "spec_block"}),
    ("sched_commit", 84000, 88000, {"committed": 4}),
    # a round that runs over the stretch's end: not counted
    ("sched_round", 95000, 105000, {"loop": "spec_tree"}),
    ("spec_block", 96000, 104000, {"rounds_asked": 1, "rounds": 1,
                                   "rows": 1}),
]
_BUSY = [(4500, 11500), (17500, 37500), (55000, 83000), (96500, 99500)]
_EXPECTED = {
    "spec_round_ms": (20 + 28) / 3,         # busy in the two blocks / rounds
    "spec_rounds_per_block": 3 / 2,
    "call_idle_ms": (3 + 5 + 4) / 3,        # prefill, block, block
    "call_stage_ms": (1 + 3 + 1) / 3,
    "sched_host_ms": ((40 - 35) + (40 - 32)) / 2,
    "idle_attributed": 100 * 23 / 42,       # 42 ms idle, 23 inside leaves
}


def _hand_ctx(spans, busy=_BUSY):
    from benchmark.lib import trace as TR

    off, t0_s = 1000.0, 100.0              # profiler = perf_counter + 1 us
    t0 = t0_s * 1e9 + off
    raw = {"planes": {"/device:TPU:0": [[f"fusion.{i}", t0 + a * 1e3,
                                         (b - a) * 1e3]
                                        for i, (a, b) in enumerate(busy)]},
           "marks": {"bench_mark_0": t0, "bench_mark_1": t0 + 100e6}}
    origin = 99.0
    events = [{"name": "clock_sync", "ph": "M", "ts": 0.0,
               "args": {"perf_counter_origin": origin}}]
    for name, a, b, args in spans:
        events.append({"name": name, "ph": "X", "tid": 0,
                       "ts": (t0_s - origin) * 1e6 + a,
                       "dur": float(b - a), "args": dict(args)})
    red = TR.reduce_trace(raw, {"bench_mark_0": t0_s,
                                "bench_mark_1": t0_s + 0.1}, events)
    assert red["busy_s"] == pytest.approx(sum(b - a for a, b in busy) / 1e6)
    return {"trace": red}


@pytest.mark.parametrize("metric", sorted(_EXPECTED))
def test_phase_span_readers_on_a_hand_built_trace(metric, capsys):
    """Known spans and busy intervals give known numbers; a trace without
    the spans (any program before ISSUE 24) gives None."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        from benchmark.run import load_module

        read = load_module("layer_metrics", metric).read
        assert read(_hand_ctx(_ROUNDS)) == pytest.approx(_EXPECTED[metric])
        old = [s for s in _ROUNDS if s[0] == "prefill"]   # the parent's
        assert read(_hand_ctx(old)) is None
        assert read({"trace": None}) is None
    finally:
        sys.path.remove(root)
    out = capsys.readouterr().out
    assert all(line.startswith("# ") for line in out.splitlines())
    if metric == "spec_rounds_per_block":
        assert "rounds asked 2.500" in out and "'catch_up': 1" in out


@pytest.mark.parametrize("before,after,want", [
    ((0, 0.0), (40, 34.0), 85.0),       # every block of the window but six
    ((10, 10.0), (50, 10.0), 0.0),      # blocks, and nobody filling: 0
    ((7, 3.0), (7, 3.0), None),         # no block in the window
    (None, (8, 2.0), 25.0),             # the series was born in the window
    (None, None, None),                 # the parent: no such histogram
])
def test_prefill_ahead_share_on_hand_made_snapshots(before, after, want):
    """ISSUE 61's reader: 100 x the mean of ``ffsv_round_prefill_ahead``
    over the window (one observation a decode block of the incremental
    loop, 1 where a lead step was queued behind it); None without the
    histogram, without a block, or untraced."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        from benchmark.run import load_module

        read = load_module("layer_metrics", "prefill_ahead_share").read
    finally:
        sys.path.remove(root)
    snap = lambda cs: ({} if cs is None else
                       {"ffsv_round_prefill_ahead": {"count": cs[0],
                                                     "sum": cs[1]}})
    got = read({"tel": {"before": snap(before), "after": snap(after)}})
    assert got == (want if want is None else pytest.approx(want))
    assert read({"tel": None}) is None


# the four readers of ISSUE 37, on a hand-built traced stretch of 100 ms
# (microseconds after its start): a round of two lagged prefill steps and a
# block, a round of one step whose staging found the device idle, and a
# block that runs over the stretch's end
_LAG_ROUNDS = [
    ("sched_round", 1000, 45000, {"loop": "incr"}),
    ("call_stage", 2000, 3000, {"program": "prefill"}),
    ("call_launch", 3000, 4000, {"program": "prefill"}),
    ("call_stage", 5000, 6000, {"program": "prefill"}),
    ("call_launch", 6000, 7000, {"program": "prefill"}),
    ("call_wait", 7000, 14000, {"program": "prefill"}),
    ("prefill", 2000, 14000, {"n_tokens": 8}),
    ("prefill", 2000, 14000, {"n_tokens": 8}),      # second request
    ("call_stage", 15000, 16000, {"program": "decode_block"}),
    ("call_launch", 16000, 17000, {"program": "decode_block"}),
    ("call_wait", 17000, 24000, {"program": "prefill"}),
    ("prefill", 14000, 24000, {"n_tokens": 16}),
    ("call_wait", 24000, 44000, {"program": "decode_block"}),
    ("decode_block", 24000, 44000, {"steps": 4, "rows": 3}),
    ("decode_block", 24000, 44000, {"steps": 4, "rows": 3}),
    ("sched_round", 50000, 90000, {"loop": "incr"}),
    ("call_stage", 51000, 53000, {"program": "prefill"}),
    ("call_launch", 53000, 54000, {"program": "prefill"}),
    ("call_wait", 54000, 60000, {"program": "prefill"}),
    ("prefill", 51000, 60000, {"n_tokens": 4}),
    ("decode_block", 62000, 88000, {"steps": 4, "rows": 1}),
    ("sched_round", 95000, 105000, {"loop": "incr"}),
    ("decode_block", 96000, 104000, {"steps": 4, "rows": 2}),
]
_LAG_BUSY = [(3500, 23500), (24500, 43500), (54500, 59500), (63000, 87000)]
_LAG_EXPECTED = {
    "prefill_step_ms": (10.5 + 9.5 + 5.0) / 3,
    # idle in the staging of: the first round's first step 1.5 ms, its
    # second 0, the second round's step 3 ms
    "prefill_stage_idle_ms": (1.5 + 0.0 + 3.0) / 3,
    "decode_rows_per_block": (3 + 1) / 2,
    "traced_output_tok_s": (10 * 0.5 + 20 * 0.5) / 4.0,
}
_LAG_RECORDS = [{"n_out": 10, "submit": 1.0, "finish": 3.0},
                {"n_out": 20, "submit": 4.0, "finish": 8.0}]


# the three readers of ISSUE 52, on a hand-built traced stretch of 100 ms of
# the fused speculation loop: a prompt round (the verifier's step, the
# draft's, the block launched behind it), a round whose block is launched
# alone, and a round whose prefill step runs over the stretch's end
_SPEC_ROUNDS = [
    ("sched_round", 1000, 55000, {"loop": "spec_tree", "cut": "prefill"}),
    ("call_stage", 2000, 3000, {"program": "prefill"}),
    ("call_launch", 3000, 4000, {"program": "prefill"}),
    ("call_stage", 5000, 6000, {"program": "prefill"}),
    ("call_launch", 6000, 7000, {"program": "prefill"}),
    ("call_wait", 7000, 14000, {"program": "prefill"}),
    ("prefill", 2000, 14000, {"n_tokens": 8, "model": "llm"}),
    ("prefill", 2000, 14000, {"n_tokens": 8, "model": "llm"}),  # 2nd request
    ("call_stage", 15000, 18000, {"program": "spec_block"}),
    ("call_launch", 18000, 19000, {"program": "spec_block"}),
    ("call_wait", 19000, 24000, {"program": "prefill"}),
    ("prefill", 14000, 24000, {"n_tokens": 16, "model": "ssm0"}),
    ("call_wait", 24000, 50000, {"program": "spec_block"}),
    ("spec_block", 24000, 50000, {"rounds_asked": 1, "rounds": 1, "rows": 2,
                                  "behind": "prefill"}),
    ("sched_round", 60000, 95000, {"loop": "spec_tree"}),
    ("spec_block", 61000, 92000, {"rounds_asked": 4, "rounds": 3, "rows": 2,
                                  "behind": None}),
    ("call_stage", 61000, 63000, {"program": "spec_block"}),
    ("call_launch", 63000, 64000, {"program": "spec_block"}),
    ("call_wait", 64000, 92000, {"program": "spec_block"}),
    ("sched_round", 96000, 105000, {"loop": "spec_tree", "cut": "prefill"}),
    ("prefill", 97000, 104000, {"n_tokens": 8, "model": "llm"}),
]
_SPEC_BUSY = [(3500, 23500), (24000, 49500), (64500, 91500), (98500, 99500)]
_SPEC_EXPECTED = {
    "spec_prefill_step_ms": 10.5,           # the draft's step: 9.5
    # busy inside the two steps of the stretch over the stretch's 73.5 ms
    "spec_prompt_share": 100 * (10.5 + 9.5) / 73.5,
    # staged behind the draft's step: nothing; alone: 61-64 ms of a device
    # that starts at 64.5
    "spec_stage_idle_ms": (0.0 + 3.0) / 2,
}
_SPEC_SAID = {
    "spec_prefill_step_ms": "ssm0 x1 9.500",
    "spec_prompt_share": "llm 10.5, ssm0 9.5; rounds with a prefill step 1, "
                         "without 1",
    "spec_stage_idle_ms": "behind a prefill step x1 0.000, alone x1 3.000",
}


def _lag_ctx(spans, busy=_LAG_BUSY):
    return dict(_hand_ctx(spans, busy), records=_LAG_RECORDS, w0=2.0,
                w1=6.0)


def _without(spans, *keys):
    return [(n, a, b, {k: v for k, v in args.items() if k not in keys})
            for n, a, b, args in spans]


@pytest.mark.parametrize("metric",
                         sorted(_LAG_EXPECTED) + sorted(_SPEC_EXPECTED))
def test_lag_readers_on_a_hand_built_trace(metric, capsys):
    """Known spans, busy intervals and records give known numbers; a
    program without the source (the parent: no ``rows``, or no ``model``
    and no ``behind``; one before ISSUE 24: no leaves; an untraced context)
    gives None."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        from benchmark.run import load_module

        read = load_module("layer_metrics", metric).read
        if metric in _SPEC_EXPECTED:
            assert read(_lag_ctx(_SPEC_ROUNDS, _SPEC_BUSY)) == pytest.approx(
                _SPEC_EXPECTED[metric])
            parent = _without(_SPEC_ROUNDS, "model", "behind")
            assert read(_lag_ctx(parent, _SPEC_BUSY)) is None
            # the incremental loop's spans: a ``model``, no ``spec_block``
            incr = [(n, a, b, dict(args, model="llm") if n == "prefill"
                     else args) for n, a, b, args in _LAG_ROUNDS]
            assert read(_lag_ctx(incr)) == {
                "spec_prefill_step_ms": pytest.approx(
                    _LAG_EXPECTED["prefill_step_ms"]),
                "spec_prompt_share": pytest.approx(100 * 25.0 / 68.0),
                "spec_stage_idle_ms": None}[metric]
        else:
            assert read(_lag_ctx(_LAG_ROUNDS)) == pytest.approx(
                _LAG_EXPECTED[metric])
            parent = _without(_LAG_ROUNDS, "rows")
            bare = [s for s in parent if s[0] == "decode_block"]
            missing = {"prefill_step_ms": _lag_ctx(bare),
                       "prefill_stage_idle_ms": _lag_ctx(bare),
                       "decode_rows_per_block": _lag_ctx(parent),
                       "traced_output_tok_s": {"records": [], "w0": 2.0,
                                               "w1": 6.0}}[metric]
            assert read(missing) is None
            if metric != "decode_rows_per_block":   # the parent's spans do
                assert read(_lag_ctx(parent)) == pytest.approx(
                    _LAG_EXPECTED[metric])
        assert read({"trace": None}) is None
    finally:
        sys.path.remove(root)
    out = capsys.readouterr().out
    assert all(line.startswith("# ") for line in out.splitlines())
    if metric == "prefill_stage_idle_ms":
        assert "first step x2 2.250, later steps x1 0.000" in out
    assert _SPEC_SAID.get(metric, "") in out


# ---------------------------------------------------------------------------
# a block-diffusion model's decode blocks: counters and span attributes
# against a hand-counted run (ISSUE 39; the fold of a block's store into the
# row's next pass: ISSUE 40)
# ---------------------------------------------------------------------------

def test_diffusion_counters_and_span_attributes_hand_counted():
    """One request, prompt 5 (a remainder of 1), 11 new tokens, the floor
    of one position a denoise pass (no pick of seeded weights clears 0.9),
    calls of 7 passes. Call 1: block one takes 3 passes and is emitted by
    the third (4 tokens, 3 of them new); pass 4 stores it in front of block
    two, whose fourth pass, the call's last, emits it: the request leaves
    with 7 new tokens and block two not stored. Call 2 (4 tokens to go: 4
    passes): its first stores block two in front of block three, its last
    emits that one. No pass only stores."""
    import flexflow_tpu as ff
    from flexflow_tpu.ffconst import CompMode, InferenceMode
    from flexflow_tpu.models import FAMILIES
    from flexflow_tpu.models.checkpoint_store import TINY_CONFIGS

    fam = FAMILIES["sdar_moe"]
    model = ff.FFModel(ff.FFConfig(
        max_requests_per_batch=2, max_sequence_length=64,
        max_tokens_per_batch=16, seed=0, kv_cache_dtype="float32",
        decode_block_steps=7))
    fam.build(model, fam.config_cls(**TINY_CONFIGS["sdar_moe"]),
              mode=InferenceMode.INC_DECODING_MODE)
    model.compile(comp_mode=CompMode.COMP_MODE_INFERENCE)
    tel = enable_telemetry()
    try:
        rm = RequestManager()
        rm.register_new_request([5, 9, 23, 44, 7], max_new_tokens=11)
        (res,) = rm.generate_incr_decoding(model)
        assert len(res.output_tokens) == 11
        snap = tel.registry.snapshot()
        value = lambda name: snap[name]["value"]
        assert value("ffsv_diffusion_row_passes_total") == 7 + 4
        assert value("ffsv_diffusion_commit_passes_total") == 0
        assert value('ffsv_diffusion_tokens_total{by="floor"}') == 3 + 4 + 4
        assert value('ffsv_diffusion_tokens_total{by="threshold"}') == 0
        assert value("ffsv_decode_steps_total") == 7 + 4
        assert value("ffsv_decode_width") == 4
        blocks = [e["args"] for e in tel.tracer.events
                  if e["name"] == "decode_block"]
        assert [(b["steps"], b["rows"], b["width"], b["committed"])
                for b in blocks] == [(7, 1, 4, 8), (4, 1, 4, 4)]
        assert not any("commits" in b for b in blocks)
    finally:
        disable_telemetry()


def test_diffusion_readers_on_hand_made_snapshots():
    """The three per-layer readers over known counter gains; a program
    without the series (the parent, any other model) gives None."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        from benchmark.run import load_module

        read = {n: load_module("layer_metrics", f"diffusion_{n}").read
                for n in ("tokens_per_pass", "commit_share",
                          "threshold_share")}
    finally:
        sys.path.remove(root)
    series = {"ffsv_diffusion_row_passes_total": (100.0, 600.0),
              "ffsv_diffusion_commit_passes_total": (20.0, 130.0),
              'ffsv_diffusion_tokens_total{by="threshold"}': (0.0, 90.0),
              'ffsv_diffusion_tokens_total{by="floor"}': (80.0, 350.0)}
    ctx = {"tel": {"before": {k: {"value": a} for k, (a, _) in series.items()},
                   "after": {k: {"value": b} for k, (_, b) in series.items()}}}
    assert read["tokens_per_pass"](ctx) == pytest.approx(360 / 500)
    assert read["commit_share"](ctx) == pytest.approx(22.0)
    assert read["threshold_share"](ctx) == pytest.approx(25.0)
    for bare in ({"tel": None}, {"tel": {"before": {}, "after": {}}}):
        assert all(r(bare) is None for r in read.values())


def test_exit_waits_for_a_thread_still_stopping_a_profiler_session(tmp_path):
    """``wait_for_profiler_stop`` (the exit hook ``enable_telemetry``
    registers, once): a thread whose stack is inside a profiler's
    ``stop_trace`` is joined, any other thread is left alone, and with no
    such thread it returns at once."""
    import importlib.util
    import threading
    import time

    from flexflow_tpu import telemetry as T

    assert T.wait_for_profiler_stop(limit_s=5.0) == 0
    path = tmp_path / "fake_profiler.py"
    path.write_text("def stop_trace(inside, done):\n"
                    "    inside.set()\n"
                    "    done.wait()\n")
    spec = importlib.util.spec_from_file_location("fake_profiler", path)
    fake = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fake)
    inside, done, forever = (threading.Event() for _ in range(3))
    stopper = threading.Thread(target=fake.stop_trace, args=(inside, done),
                               daemon=True)
    other = threading.Thread(target=forever.wait, daemon=True)
    stopper.start(), other.start()
    assert inside.wait(5.0)
    threading.Timer(0.3, done.set).start()
    t = time.monotonic()
    assert T.wait_for_profiler_stop(limit_s=20.0) == 1
    assert not stopper.is_alive() and other.is_alive()
    assert 0.2 < time.monotonic() - t < 10.0
    forever.set()
    # a thread that never leaves costs the limit and no more
    inside.clear(), done.clear()
    stuck = threading.Thread(target=fake.stop_trace, args=(inside, done),
                             daemon=True)
    stuck.start()
    assert inside.wait(5.0)
    t = time.monotonic()
    assert T.wait_for_profiler_stop(limit_s=0.2) == 1 and stuck.is_alive()
    assert time.monotonic() - t < 5.0
    done.set()
    T.enable_telemetry()
    T.enable_telemetry()
    T.disable_telemetry()
    assert T._exit_hook is True
