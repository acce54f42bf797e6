"""Fleet observability tests (ISSUE 18).

Gates, in dependency order: MetricsRegistry.merge is EXACT against a
single-registry ground truth (counters, gauges, histograms incl. the
sliding-window percentiles); the SLO burn-rate monitor fires and clears
deterministically on a fake clock; the flight recorder's bounded ring
dumps a parseable incident report; the two fused engines compile
exactly once under an adaptive-depth mixed batch (retrace counters stay
zero); a fleet-wide trace_id survives preemption re-queue and crash
failover token-identically; the seeded failover_run produces the
acceptance-criteria artifacts — one stitched Chrome trace with the
failed-over request's spans under BOTH replicas' pid rows, a pool
metrics.json whose merged counters equal the sum of the per-replica
registries, a burn-rate timeline with >= 1 fired alert during the
outage and zero in steady state, and a parseable flight-recorder JSONL.

Kept lean on purpose (tier-1 budget): the session ``tiny_spec_pair``,
fake clocks everywhere a clock is injectable, and the file is hoisted
to the front of the run by conftest._EARLY_FILES.
"""

import json
import os
import sys
import time

import pytest

from flexflow_tpu.serve.loadgen import EngineHandle, TenantSpec, WorkloadSpec
from flexflow_tpu.serve.request_manager import RequestManager
from flexflow_tpu.telemetry import ServingTelemetry, mint_trace_id
from flexflow_tpu.telemetry.flight_recorder import (FlightRecorder,
                                                    load_incident_report)
from flexflow_tpu.telemetry.metrics import MetricsRegistry
from flexflow_tpu.telemetry.slo import SLOMonitor, SLOPolicy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROMPT_A = [5, 9, 23, 7]
PROMPT_B = [11, 3, 19]
NEW_TOKENS = 8


def _tools():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import profile_trace
        import trace_report
    finally:
        sys.path.pop(0)
    return trace_report, profile_trace


# ---------------------------------------------------------------------------
# metrics merge: exact vs single-registry ground truth (no models)
# ---------------------------------------------------------------------------

def test_merge_exact_vs_single_registry_ground_truth():
    """merge([a, b]) must equal the registry that would exist had every
    observation landed on ONE registry — counter values, histogram
    bucket/count/sum, exact percentiles AND windowed percentiles (the
    pool-level /metrics contract)."""
    truth = MetricsRegistry()
    parts = [MetricsRegistry(), MetricsRegistry()]
    # deterministic observation stream, split round-robin across replicas
    for i in range(40):
        reg = parts[i % 2]
        for r in (reg, truth):
            r.counter("ffsv_requests_total").inc()
            r.counter("ffsv_tokens_generated_total").inc(3 * i + 1)
            r.histogram("ffsv_request_latency_seconds",
                        buckets=(0.01, 0.1, 1.0),
                        window_s=60.0).observe(0.005 * (i + 1),
                                               at=float(i))
            r.histogram("ffsv_acceptance_length",
                        buckets=(1, 2, 4)).observe(i % 5)
    # a replica-local instrument the other replica never saw
    parts[1].counter("ffsv_failovers_total").inc(2)
    truth.counter("ffsv_failovers_total").inc(2)
    # extensive gauges sum across replicas (fleet queue depth IS the sum)
    parts[0].gauge("ffsv_submit_queue_depth").set(3)
    parts[1].gauge("ffsv_submit_queue_depth").set(4)

    merged = MetricsRegistry.merge(parts)
    t_snap, m_snap = truth.snapshot(), merged.snapshot()
    assert set(m_snap) == set(t_snap) | {"ffsv_submit_queue_depth"}
    for name, want in t_snap.items():
        got = m_snap[name]
        if want["type"] == "counter":
            assert got["value"] == want["value"], name
        elif want["type"] == "histogram":
            assert got["count"] == want["count"], name
            assert got["sum"] == pytest.approx(want["sum"]), name
            assert got["buckets"] == want["buckets"], name
            assert got["percentiles"] == pytest.approx(
                want["percentiles"]), name
    assert m_snap["ffsv_submit_queue_depth"]["value"] == 7

    # windowed percentiles over the merged registry == percentiles over
    # the union of in-window samples (same now => same sample multiset)
    mh = merged.get("ffsv_request_latency_seconds")
    th = truth.get("ffsv_request_latency_seconds")
    now = 45.0        # evicts samples older than t=-15: none yet — then
    assert mh.windowed_percentiles(now=now) == pytest.approx(
        th.windowed_percentiles(now=now))
    late = 80.0       # ...a cutoff at t=20 drops the first half
    got, want = (mh.windowed_percentiles(now=late),
                 th.windowed_percentiles(now=late))
    assert got["count"] == want["count"] < 40
    assert got == pytest.approx(want)

    # schema-mismatch safety: differing window_s / buckets must raise,
    # not silently blend incompatible vocabularies
    odd = MetricsRegistry()
    odd.histogram("ffsv_request_latency_seconds", buckets=(0.01, 0.1, 1.0),
                  window_s=5.0)
    with pytest.raises(ValueError, match="window_s"):
        MetricsRegistry.merge([parts[0], odd])
    odd2 = MetricsRegistry()
    odd2.histogram("ffsv_acceptance_length", buckets=(9,))
    with pytest.raises(ValueError, match="bucket"):
        MetricsRegistry.merge([parts[0], odd2])


# ---------------------------------------------------------------------------
# SLO burn-rate alerting on a fake clock (no models)
# ---------------------------------------------------------------------------

def test_burn_rate_fires_and_clears_on_fake_clock():
    pol = SLOPolicy(name="t", availability_target=0.99,
                    fast_window_s=60.0, slow_window_s=600.0)
    mon = SLOMonitor(policy=pol, clock=lambda: 0.0)
    # steady state: 50 good requests, one per second — never fires
    for t in range(50):
        mon.observe(True, at=float(t))
        assert mon.tick(now=float(t)) is None
    assert mon.burn_rates(now=49.0)["fast_burn"] == 0.0

    # outage: 20 bad in 20 s; both windows exceed their thresholds
    events = []
    for i in range(20):
        t = 50.0 + i
        mon.observe(False, at=t)
        ev = mon.tick(now=t)
        if ev:
            events.append(ev)
    assert mon.alert_active and mon.alerts_fired == 1
    assert events[0]["type"] == "fire" and events[0]["slo"] == "t"
    # burn math is exact: bad-fraction over the window / budget
    rates = mon.burn_rates(now=69.0)
    assert rates["slow_n"] == 70 and rates["slow_bad"] == 20
    assert rates["slow_burn"] == pytest.approx((20 / 70) / 0.01, rel=1e-3)
    # still burning at the next tick: state holds, no duplicate fire
    assert mon.tick(now=70.0) is None

    # recovery: far past the slow window both windows drain -> clear
    mon.observe(True, at=700.0)
    ev = mon.tick(now=700.0)
    assert ev is not None and ev["type"] == "clear"
    assert not mon.alert_active
    rep = mon.report()
    assert rep["alerts_fired"] == 1 and rep["n_bad"] == 20
    assert [e["type"] for e in rep["timeline"]] == ["fire", "clear"]

    # multi-window anti-flap: a blip that saturates the FAST window but
    # not the slow one never pages
    mon2 = SLOMonitor(policy=pol, clock=lambda: 0.0)
    for t in range(300):
        mon2.observe(True, at=float(t))
    mon2.observe(False, at=300.0)     # 1 bad of 301 in the slow window
    assert mon2.burn_rates(now=300.0)["fast_burn"] >= pol.budget
    assert mon2.tick(now=300.0) is None
    assert mon2.alerts_fired == 0


def test_slo_policy_classifiers():
    pol = SLOPolicy(latency_slo_s=1.0, ttft_slo_s=0.5)
    assert pol.is_good(status="ok", latency_s=0.2, ttft_s=0.1)
    assert not pol.is_good(status="timed_out")
    assert not pol.is_good(status="ok", failovers=1)   # count_failovers
    assert not pol.is_good(status="ok", latency_s=2.0)
    assert not pol.is_good(status="ok", ttft_s=0.9)
    with pytest.raises(ValueError):
        SLOPolicy(availability_target=1.0)
    with pytest.raises(ValueError):
        SLOPolicy(fast_window_s=10.0, slow_window_s=5.0)


# ---------------------------------------------------------------------------
# flight recorder: bounded ring -> parseable incident report (no models)
# ---------------------------------------------------------------------------

def test_flight_recorder_dump_roundtrip(tmp_path):
    t = [0.0]
    fr = FlightRecorder(capacity=4, clock=lambda: t[0])
    for i in range(6):
        t[0] = float(i)
        fr.record("round", i=i)
    assert fr.n_recorded == 6
    evs = fr.events()
    assert [e["i"] for e in evs] == [2, 3, 4, 5]     # ring keeps newest 4
    assert [e["t_s"] for e in evs] == [2.0, 3.0, 4.0, 5.0]

    path = str(tmp_path / "incident_r3_1.jsonl")
    fr.dump(path, header={"replica": 3, "error": "RuntimeError: boom",
                          "n_waiting": 2})
    header, events = load_incident_report(path)
    assert header["kind"] == "incident" and header["replica"] == 3
    assert header["n_events"] == 4 == len(events)
    assert [e["i"] for e in events] == [2, 3, 4, 5]

    # corruption is an error, not a silently-short report
    bad = tmp_path / "truncated.jsonl"
    lines = open(path).read().splitlines()
    bad.write_text("\n".join(lines[:-1]) + "\n")
    with pytest.raises(ValueError, match="claims"):
        load_incident_report(str(bad))
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    with pytest.raises(ValueError, match="empty"):
        load_incident_report(str(empty))
    headless = tmp_path / "headless.jsonl"
    headless.write_text(json.dumps({"kind": "round"}) + "\n")
    with pytest.raises(ValueError, match="incident"):
        load_incident_report(str(headless))


# ---------------------------------------------------------------------------
# retrace accounting: adaptive mixed batch = ONE compile per engine
# ---------------------------------------------------------------------------

def test_adaptive_mixed_batch_compiles_once_per_engine(tiny_spec_pair,
                                                       tiny_beam_draft):
    """The fused engines pad their block signatures so an adaptive-depth
    MIXED batch (different prompt lengths, different budgets, per-request
    effective depths) reuses one compile; the retrace counters are how a
    violation would page. Engines cache on the llm, so the lifetime
    trace count being 1 is a session-wide invariant, not just this
    test's."""
    from flexflow_tpu.serve.batch_config import GenerationConfig

    llm, ssm = tiny_spec_pair
    beam = tiny_beam_draft                  # width 2: the beam engine
    tel = ServingTelemetry()
    prompts = [[5, 9, 23, 44], [7, 3, 11], [2, 4], [9, 1, 6, 12, 3]]

    # margin 0: the cost model would (rightly) park this same-size draft
    # pair on incremental, and a parked batch never runs the spec block
    # — depth adaptation itself stays fully active
    def gc():
        return GenerationConfig(adaptive_spec=True,
                                spec_fallback_margin=0.0,
                                spec_recover_margin=0.1)

    rm = RequestManager(telemetry=tel)
    for i, p in enumerate(prompts):
        rm.register_new_request(p, max_new_tokens=6 + 2 * i)
    rm.generate_spec_infer(llm, [beam], spec_depth=3,
                           generation_config=gc())
    assert llm._beam_engine._trace_count == 1

    rm2 = RequestManager(telemetry=tel)
    for p in prompts[:2]:
        rm2.register_new_request(p, max_new_tokens=6)
    rm2.generate_spec_infer(llm, [ssm], spec_depth=3,
                            generation_config=gc())
    assert llm._multi_engine._trace_count == 1

    # a retrace (total_traces > 1) is the violation; none happened, so
    # the counter stays zero while cache-miss accounting still moves
    assert tel.registry.get("ffsv_engine_retraces_total").value == 0
    # the delta-reporting hook never double-counts: a second mixed batch
    # through the same engines reports no new compiles
    before = tel.registry.get("ffsv_jit_cache_misses_total").value
    rm3 = RequestManager(telemetry=tel)
    for p in prompts[:3]:
        rm3.register_new_request(p, max_new_tokens=5)
    rm3.generate_spec_infer(llm, [beam], spec_depth=3,
                            generation_config=gc())
    assert llm._beam_engine._trace_count == 1
    assert tel.registry.get("ffsv_jit_cache_misses_total").value == before
    assert tel.registry.get("ffsv_engine_retraces_total").value == 0


# ---------------------------------------------------------------------------
# trace_id propagation: preemption re-queue (pool failover below)
# ---------------------------------------------------------------------------

def test_trace_id_survives_preemption_requeue(tiny_spec_pair):
    """A preempted request keeps its fleet-wide trace_id through the
    re-queue (same Request object), produces identical tokens, and its
    finish span carries preemptions + the trace_id — ISSUE 16c's
    token-identity invariant, observed through the ISSUE 18 lens."""
    llm, ssm = tiny_spec_pair
    ssms = [ssm]
    ref_rm = RequestManager()
    ref_rm.max_spec_depth = 2
    ga = ref_rm.register_new_request(PROMPT_A, max_new_tokens=24)
    gb = ref_rm.register_new_request(PROMPT_B, max_new_tokens=24)
    ref_rm.generate_spec_infer(llm, ssms)
    ref = {tuple(PROMPT_A): ref_rm.results[ga].output_tokens,
           tuple(PROMPT_B): ref_rm.results[gb].output_tokens}

    tel = ServingTelemetry()
    handle = EngineHandle(llm, ssms=ssms, spec_depth=2)
    handle.rm.telemetry = tel
    try:
        handle.start_server()
        srv, rm = handle._server, handle.rm
        gA, evA = srv.submit([PROMPT_A], 24, 0, trace_id="t-victim-a")
        gB, evB = srv.submit([PROMPT_B], 24, 0)
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            ra, rb = rm.inflight.get(gA[0]), rm.inflight.get(gB[0])
            if ra is not None and rb is not None \
                    and ra.slot >= 0 and rb.slot >= 0:
                break
            time.sleep(0.002)
        else:
            pytest.fail("A/B never took their slots")
        # high-priority arrival with its deadline budget nearly burned:
        # the at-risk predicate must evict one best-effort request
        gC, evC = srv.submit([PROMPT_B], 2, 0, priority=1, timeout_s=30.0)
        with srv._work:
            rm.inflight[gC[0]].arrival_s -= 70.0
        assert evC.wait(timeout=120.0) and evA.wait(120.0) and evB.wait(120.0)
        resA, resB = rm.results[gA[0]], rm.results[gB[0]]
        assert resA.preemptions + resB.preemptions >= 1
        # explicit trace_id round-trips; the minted one is well-formed
        assert resA.trace_id == "t-victim-a"
        assert resB.trace_id.startswith("t-")
        assert resB.trace_id != resA.trace_id
        # tokens identical through the re-queue
        assert resA.output_tokens == ref[tuple(PROMPT_A)]
        assert resB.output_tokens == ref[tuple(PROMPT_B)]
        # the finish span reports the preemption count + trace_id
        finishes = {e["tid"]: e["args"] for e in tel.tracer.events
                    if e["name"] == "finish"}
        victim = resA if resA.preemptions else resB
        assert finishes[victim.guid]["preemptions"] == victim.preemptions
        assert finishes[victim.guid]["trace_id"] == victim.trace_id
        assert finishes[victim.guid]["status"] == "ok"
    finally:
        handle.stop_server()


def test_mint_trace_id_unique():
    a, b = mint_trace_id(), mint_trace_id()
    assert a != b and a.startswith("t-") and b.startswith("t-")


# ---------------------------------------------------------------------------
# the acceptance-criteria run: seeded crash chaos with full observability
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def llama_ckpt(tmp_path_factory):
    from flexflow_tpu.models.checkpoint_store import save_tiny_checkpoint

    d = str(tmp_path_factory.mktemp("obs_ckpt"))
    save_tiny_checkpoint("llama", d, seed=0)
    return d


def test_fleet_observability_acceptance(llama_ckpt, tmp_path):
    from flexflow_tpu.serve.replica import (ReplicaPool,
                                            checkpoint_replica_factory,
                                            failover_run)
    from flexflow_tpu.telemetry.fleet import FleetTelemetry

    tr, pt = _tools()
    trace_dir = str(tmp_path / "fleet")
    fleet = FleetTelemetry(trace_dir=trace_dir)
    pool = ReplicaPool(checkpoint_replica_factory(llama_ckpt, slots=2,
                                                  max_seq=64),
                       n_replicas=2, telemetry=fleet)
    spec = WorkloadSpec(prompt_lens=(4, 8), output_lens=(16, 24),
                        vocab_size=128,
                        tenants=(TenantSpec("default", 1.0,
                                            deadline_s=2.0),))
    # harness-scaled thresholds: one failed-over request of 10 must page;
    # zero bad can never page
    policy = SLOPolicy(name="obs", fast_burn_threshold=6.0,
                       slow_burn_threshold=3.0)
    pool.start_server()
    try:
        fo = failover_run(pool, spec, rate_rps=8.0, n_requests=10, seed=0,
                          crash_after=4, timeout_s=120.0,
                          slo_policy=policy)
        assert fo["resolved_fraction"] == 1.0
        assert fo["n_failed_over"] >= 1

        # (c) burn-rate alerting: the outage fired at least once
        assert fo["alerts_fired"] >= 1
        assert fo["slo"]["timeline"][0]["type"] == "fire"
        assert fo["slo"]["n_bad"] >= 1

        # (a) one stitched Chrome trace: the failed-over request's spans
        # sit under BOTH replicas' pid rows joined by one trace_id
        arts = fo["artifacts"]
        doc = json.load(open(arts["trace"]))
        evs = doc["traceEvents"]
        meta = [e for e in evs if e.get("ph") == "M"
                and e.get("name") == "process_name"]
        assert {e["pid"] for e in meta} >= {1, 2}
        byreq = tr.request_traces(evs)
        crossed = {tid: e for tid, e in byreq.items()
                   if len({x.get("pid") for x in e}) >= 2}
        assert crossed, "no request's spans stitched across two replicas"
        summaries = [tr.summarize_request(tid, e)
                     for tid, e in crossed.items()]
        hit = [s for s in summaries
               if s["failovers"] >= 1 and s["status"] == "ok"]
        assert hit, summaries
        # the survivor RE-ADMITTED it under the same trace_id: admission
        # spans exist on both pids
        tid = hit[0]["trace_id"]
        adm = [e for e in byreq[tid] if e["name"] == "admission"]
        assert len(adm) >= 2 and len({e["pid"] for e in adm}) >= 2
        # tools/trace_report summarizes the same story
        rep = tr.trace_report(evs)
        assert rep["n_failed_over"] >= 1
        top = rep["requests"][0]
        assert top["critical_path"]
        assert top["total_us"] >= top["queue_wait_us"] >= 0.0
        assert top["other_wait_us"] >= 0.0
        assert "ms" in tr.format_report(rep)

        # (b) pooled metrics: merged counters equal the sum of the
        # per-replica registries, instrument by instrument
        snap = json.load(open(arts["metrics"]))
        assert sorted(snap["replicas"]) == ["0", "1"]
        per = snap["replicas"]
        for name, m in snap["fleet"].items():
            vals = [per[r][name] for r in per if name in per[r]]
            if m["type"] == "counter":
                assert m["value"] == pytest.approx(
                    sum(v["value"] for v in vals)), name
            elif m["type"] == "histogram":
                assert m["count"] == sum(v["count"] for v in vals), name
                assert m["sum"] == pytest.approx(
                    sum(v["sum"] for v in vals)), name
        assert snap["fleet"]["ffsv_failovers_total"]["value"] >= 1
        assert snap["fleet"]["ffsv_requests_total"]["value"] >= 10
        # the pool-level Prometheus endpoint view carries replica labels
        text = fleet.to_prometheus()
        assert 'ffsv_requests_total{replica="0"}' in text
        assert 'ffsv_requests_total{replica="1"}' in text

        # (d) flight recorder: the crash produced a parseable incident
        # report attributed to the dead replica
        assert arts["incidents"]
        for p in arts["incidents"]:
            header, events = load_incident_report(p)
            assert header["replica"] == 0
            assert header["error"]
            assert header["n_events"] == len(events) > 0
            assert all("kind" in e and "t_s" in e for e in events)
        assert pool.stats()["incident_reports"] == arts["incidents"]

        # clock marks: one per replica pid, for aligning a jax.profiler
        # device trace with the fleet span trace (each tracer ties ITS
        # perf_counter origin to the profiler session by a named mark;
        # tools/profile_trace.mark_offset_ns reads the pair back)
        tracers = [t.tracer for t in fleet.replica_telemetries()]
        names = [trc.profiler_mark() for trc in tracers]
        assert len(set(names)) == 2
        recs = [next(e for e in trc.events if e["name"] == n)
                for trc, n in zip(tracers, names)]
        assert [r["pid"] for r in recs] == [1, 2]
        assert all(r["ph"] == "i" and r["name"].startswith("ffsv_mark_")
                   and r["args"]["perf_counter_s"] > 0 for r in recs)
        assert pt.mark_offset_ns is not None    # the reader ships with it

        # steady-state control: same pool, same policy, no crash -> the
        # pager stays silent (crash_after beyond the run's engine calls)
        deadline = time.monotonic() + 120
        while pool.n_alive() < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert pool.n_alive() == 2
        steady = failover_run(pool, spec, rate_rps=8.0, n_requests=8,
                              seed=1, crash_after=10 ** 6,
                              timeout_s=120.0, slo_policy=policy)
        assert steady["n_failed_over"] == 0
        assert steady["alerts_fired"] == 0
        assert steady["slo"]["timeline"] == []
        assert steady["resolved_fraction"] == 1.0
    finally:
        pool.stop_server(flush_timeout_s=30)
        fleet.close()


# ---------------------------------------------------------------------------
# aggregated C-ABI metrics dump sees live fleets
# ---------------------------------------------------------------------------

def test_capi_metrics_dump_aggregates_fleet():
    from flexflow_tpu.serve import capi_host
    from flexflow_tpu.telemetry import disable_telemetry
    from flexflow_tpu.telemetry.fleet import FleetTelemetry

    disable_telemetry()
    fleet = FleetTelemetry()
    # unique name: other live fleets in the session must not interfere
    fleet.for_replica(0).registry.counter("test_obs_capi_total").inc(3)
    fleet.for_replica(1).registry.counter("test_obs_capi_total").inc(4)
    snap = json.loads(capi_host.metrics_dump("json"))
    assert snap["test_obs_capi_total"]["value"] == 7
    text = capi_host.metrics_dump("prometheus")
    line = next(ln for ln in text.splitlines()
                if ln.startswith("test_obs_capi_total"))
    assert float(line.split()[-1]) == 7.0
    with pytest.raises(ValueError):
        capi_host.metrics_dump("xml")
