"""Load-harness tests (lean: pure-math tests plus ONE integration pass on
the session-shared tiny spec pair — tier-1 budget).

Covers the ISSUE-14 acceptance list: seeded Poisson schedules are
reproducible, goodput/deadline accounting is exact on a hand-built
record set, sliding-window percentiles match the exact-histogram values
on retained samples, and the end-to-end runner drives the
background-server submission queue and yields the queue-wait/service
decomposition."""

import numpy as np
import pytest

from flexflow_tpu.serve.loadgen import (EngineHandle, LoadRunner,
                                        RequestRecord, TenantSpec,
                                        WorkloadSpec, build_schedule,
                                        find_knee, format_report, summarize,
                                        sweep)


# ---------------------------------------------------------------------------
# schedule synthesis (pure)
# ---------------------------------------------------------------------------

def test_poisson_schedule_seeded_reproducible():
    spec = WorkloadSpec(prompt_lens=(4, 8, 16), output_lens=(2, 4),
                        tenants=(TenantSpec("a", 3.0), TenantSpec("b", 1.0)),
                        vocab_size=128)
    s1 = build_schedule(spec, 32, rate_rps=10.0, seed=7)
    s2 = build_schedule(spec, 32, rate_rps=10.0, seed=7)
    assert [(r.arrival_s, r.tenant, r.prompt, r.max_new_tokens)
            for r in s1] == \
           [(r.arrival_s, r.tenant, r.prompt, r.max_new_tokens)
            for r in s2]
    s3 = build_schedule(spec, 32, rate_rps=10.0, seed=8)
    assert [r.prompt for r in s1] != [r.prompt for r in s3]
    # arrivals are strictly increasing with ~1/rate mean spacing
    arr = np.array([r.arrival_s for r in s1])
    assert (np.diff(arr) > 0).all()
    assert 0.02 < arr[-1] / len(arr) < 0.5       # loose: mean ~0.1 s
    # weighted tenants both appear; lengths come from the declared mix
    assert {r.tenant for r in s1} == {"a", "b"}
    assert {len(r.prompt) for r in s1} <= {4, 8, 16}
    assert {r.max_new_tokens for r in s1} <= {2, 4}
    # fixed-rate arrivals are exact
    u = build_schedule(spec, 4, rate_rps=2.0, seed=0, process="uniform")
    assert [r.arrival_s for r in u] == [0.0, 0.5, 1.0, 1.5]


def test_goodput_and_deadline_accounting_exact():
    """Hand-built records with known timings: every aggregate in the SLO
    report is checked against its closed-form value."""
    def rec(i, out, lat, ttft, qw, deadline):
        return RequestRecord(idx=i, tenant="t", scheduled_s=0.0,
                             submitted_s=float(i), prompt_tokens=4,
                             output_tokens=out, latency_s=lat, ttft_s=ttft,
                             queue_wait_s=qw, prefill_s=ttft - qw,
                             deadline_s=deadline)

    records = [
        rec(0, out=10, lat=1.0, ttft=0.25, qw=0.05, deadline=2.0),  # met
        rec(1, out=20, lat=3.0, ttft=0.50, qw=0.10, deadline=2.0),  # missed
        rec(2, out=30, lat=1.0, ttft=0.75, qw=0.15, deadline=None),  # vacuous
    ]
    # duration: first submit 0.0 -> last finish = submitted 1 + lat 3 = 4
    rep = summarize(records, offered_rps=1.5)
    assert rep["n_requests"] == 3
    assert rep["duration_s"] == pytest.approx(4.0)
    assert rep["achieved_rps"] == pytest.approx(3 / 4.0)
    assert rep["throughput_tokens_per_s"] == pytest.approx(60 / 4.0)
    # goodput drops ONLY the missed-deadline request's 20 tokens
    assert rep["goodput_tokens_per_s"] == pytest.approx(40 / 4.0)
    assert rep["deadline_met_fraction"] == pytest.approx(2 / 3, abs=1e-4)
    assert rep["offered_rps"] == 1.5
    # percentiles over [1.0, 1.0, 3.0] / [0.25, 0.5, 0.75]
    assert rep["latency_p50_s"] == pytest.approx(1.0)
    assert rep["latency_p99_s"] == pytest.approx(2.96)
    assert rep["ttft_p50_s"] == pytest.approx(0.5)
    # queue-wait vs service split: mean qw 0.1, mean latency 5/3
    assert rep["queue_wait_mean_s"] == pytest.approx(0.1)
    assert rep["service_mean_s"] == pytest.approx(5 / 3 - 0.1, abs=1e-4)
    assert rep["queue_wait_fraction"] == pytest.approx(0.1 / (5 / 3),
                                                       abs=1e-4)
    # TPOT: (lat - ttft) / (out - 1)
    assert rep["tpot_p50_ms"] == pytest.approx(
        1e3 * sorted([(1.0 - 0.25) / 9, (3.0 - 0.5) / 19,
                      (1.0 - 0.75) / 29])[1], rel=1e-3)


def test_find_knee_bound_and_sustain():
    steps = [
        {"offered_rps": 2, "achieved_rps": 2.0, "ttft_p99_s": 0.1},
        {"offered_rps": 4, "achieved_rps": 3.9, "ttft_p99_s": 0.3},
        {"offered_rps": 8, "achieved_rps": 5.0, "ttft_p99_s": 2.0},
    ]
    # rate 8 unsustained (5 < 0.9*8); rate 4 within bound
    assert find_knee(steps, p99_ttft_bound_s=0.5) == 4
    # tighter bound knocks out rate 4 too
    assert find_knee(steps, p99_ttft_bound_s=0.2) == 2
    # no TTFT bound: sustain criterion alone
    assert find_knee(steps) == 4
    assert find_knee([steps[2]], p99_ttft_bound_s=0.5) is None


def test_sliding_window_percentiles_match_exact():
    from flexflow_tpu.telemetry.metrics import Histogram, percentile

    h = Histogram("lat", buckets=(1e9,), window_s=10.0)
    vals = list(range(1, 101))
    for i, v in enumerate(vals):
        h.observe(float(v), at=float(i) * 0.05)   # all within 5 s
    # whole window retained: windowed == exact over all samples
    w = h.windowed_percentiles(now=5.0)
    assert w["count"] == 100
    assert w["p50"] == pytest.approx(h.percentile(50))
    assert w["p99"] == pytest.approx(h.percentile(99))
    # advance time: only samples newer than now-10s remain (ts > 2.5 ->
    # values 51..100), while the whole-run exact percentiles keep all
    w2 = h.windowed_percentiles(now=12.5)
    assert w2["count"] == 50
    assert w2["p50"] == pytest.approx(percentile(list(range(51, 101)), 50))
    assert h.count == 100                      # aggregate view unchanged
    # empty window: count 0, no percentile keys, no crash
    w3 = h.windowed_percentiles(now=1000.0)
    assert w3["count"] == 0 and "p50" not in w3
    # snapshot + Prometheus expositions carry the window summary
    snap = h.snapshot()
    assert snap["window"]["seconds"] == 10.0
    from flexflow_tpu.telemetry.metrics import MetricsRegistry

    reg = MetricsRegistry()
    hh = reg.histogram("ffsv_x_seconds", window_s=60.0)
    hh.observe(0.5)
    text = reg.to_prometheus()
    assert 'ffsv_x_seconds_window{quantile="0.99"} 0.5' in text
    assert "ffsv_x_seconds_window_count 1" in text


# ---------------------------------------------------------------------------
# end-to-end: drive the submission queue on the shared tiny pair
# ---------------------------------------------------------------------------

def test_load_runner_end_to_end(tiny_spec_pair):
    """Open-loop pass against the background-server path: all requests
    finish, the SLO report is self-consistent, and the queue-wait/
    prefill decomposition survives the submission queue."""
    llm, ssm = tiny_spec_pair
    spec = WorkloadSpec(prompt_lens=(3, 5), output_lens=(3, 4),
                        tenants=(TenantSpec("a", 1.0, deadline_s=60.0),
                                 TenantSpec("b", 1.0)),
                        vocab_size=128)
    handle = EngineHandle(llm, ssms=[ssm], spec_depth=2)
    try:
        schedule = build_schedule(spec, 6, rate_rps=50.0, seed=0)
        records = LoadRunner(handle).run(schedule, timeout_s=120.0)
    finally:
        handle.stop_server()
    assert len(records) == 6
    assert all(r.output_tokens in (3, 4) for r in records)
    assert all(r.latency_s > 0 for r in records)
    assert all(r.ttft_s == pytest.approx(r.queue_wait_s + r.prefill_s)
               for r in records)
    rep = summarize(records)
    assert rep["throughput_tokens_per_s"] > 0
    assert rep["goodput_tokens_per_s"] == rep["throughput_tokens_per_s"]
    assert rep["latency_p99_s"] >= rep["latency_p50_s"] > 0
    assert set(rep["per_tenant"]) == {"a", "b"}
    # only 2 batch slots for 6 near-simultaneous arrivals: someone waited
    assert rep["queue_wait_p99_s"] > 0


def test_format_report_renders():
    steps = [{"offered_rps": 2.0, "achieved_rps": 1.9,
              "throughput_tokens_per_s": 50.0,
              "goodput_tokens_per_s": 45.0, "ttft_p50_s": 0.01,
              "ttft_p99_s": 0.02, "latency_p50_s": 0.1,
              "latency_p99_s": 0.2, "queue_wait_mean_s": 0.01,
              "service_mean_s": 0.09}]
    text = format_report({"steps": steps, "knee_rps": 2.0,
                          "p99_ttft_bound_s": 1.0})
    assert "offered r/s" in text and "knee: 2.00 req/s" in text
