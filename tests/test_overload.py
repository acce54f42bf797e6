"""Overload front-door tests (ISSUE 16): admission control math on a
fake clock, end-to-end timeouts/cancellation on every scheduler path,
deadline-aware preemption with re-queue token identity, server fault
containment + restart, flush-with-timeout shutdown, the chaos harness's
every-future-resolves invariant (and tools/faulttest.py, the command
the README gives for it) and explicit rejected/timed-out accounting in
summarize().

Budget discipline: pure-math tests dominate; the integration tests share
the session tiny spec pair plus ONE module-scoped tiny incremental
model (needed because the incremental loop is a distinct scheduler path
from the speculative one)."""

import json
import os
import sys
import time

import pytest

from flexflow_tpu.serve.admission import (AdmissionController,
                                          AdmissionPolicy, RejectedError)
from flexflow_tpu.serve.faultinject import (EngineFault, FaultInjector,
                                            check_invariants, run_chaos)
from flexflow_tpu.serve.loadgen import EngineHandle, RequestRecord, summarize
from flexflow_tpu.serve.request_manager import RequestManager
from flexflow_tpu.telemetry import ServingTelemetry
from flexflow_tpu.telemetry.metrics import percentile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float):
        self.t += dt


# ---------------------------------------------------------------------------
# admission policy math (pure, fake clock)
# ---------------------------------------------------------------------------

def test_admission_queue_depth_bound_and_retry_after():
    clk = FakeClock()
    pol = AdmissionPolicy(max_queue_depth=4, min_retry_after_s=0.05)
    ctrl = AdmissionController(pol, clock=clk)
    ctrl.admit("t", 0)
    ctrl.admit("t", 3)                       # 3 + 1 == limit: still admits
    with pytest.raises(RejectedError) as ei:
        ctrl.admit("t", 4)
    e = ei.value
    assert e.reason == "queue_full"
    assert e.queue_depth == 4 and e.tenant == "t"
    assert e.retry_after_s == pytest.approx(0.05)   # cold: min retry-after
    # batch admission counts all n against the depth bound
    with pytest.raises(RejectedError):
        ctrl.admit("t", 2, n=3)
    # realized queue waits drive the retry-after hint (windowed p99)
    waits = [0.2, 0.4, 1.0]
    for w in waits:
        ctrl.observe_queue_wait(w)
    p99 = percentile(sorted(waits), 99)
    assert ctrl.queue_wait_p99() == pytest.approx(p99)
    with pytest.raises(RejectedError) as ei:
        ctrl.admit("t", 4)
    assert ei.value.retry_after_s == pytest.approx(p99)
    # samples age out of the window
    clk.advance(pol.window_s + 1.0)
    assert ctrl.queue_wait_p99() == 0.0
    st = ctrl.stats()
    assert st["n_admitted"] == 2 and st["n_rejected"] == 3
    assert st["rejects_by_reason"] == {"queue_full": 3}
    assert st["peak_queue_depth"] == 4


def test_admission_tenant_token_buckets():
    clk = FakeClock(100.0)
    pol = AdmissionPolicy(max_queue_depth=100,
                          tenant_rates={"a": (1.0, 2.0)},
                          default_rate=(10.0, 1.0))
    ctrl = AdmissionController(pol, clock=clk)
    ctrl.admit("a", 0)
    ctrl.admit("a", 0)                       # burst capacity 2
    with pytest.raises(RejectedError) as ei:
        ctrl.admit("a", 0)
    assert ei.value.reason == "tenant_rate"
    assert ei.value.retry_after_s == pytest.approx(1.0)   # 1 credit @ 1 rps
    clk.advance(0.5)                         # half a credit refilled
    with pytest.raises(RejectedError) as ei:
        ctrl.admit("a", 0)
    assert ei.value.retry_after_s == pytest.approx(0.5)
    clk.advance(0.5)
    ctrl.admit("a", 0)                       # refilled: admits again
    # unlisted tenants get default_rate (burst 1 here)
    ctrl.admit("z", 0)
    with pytest.raises(RejectedError) as ei:
        ctrl.admit("z", 0)
    assert ei.value.reason == "tenant_rate"
    # credits are only consumed when EVERY check passes: a queue_full
    # rejection must not burn the tenant's last credit
    pol2 = AdmissionPolicy(max_queue_depth=1,
                           tenant_rates={"b": (1.0, 1.0)})
    ctrl2 = AdmissionController(pol2, clock=FakeClock())
    with pytest.raises(RejectedError) as ei:
        ctrl2.admit("b", 5)
    assert ei.value.reason == "queue_full"
    ctrl2.admit("b", 0)                      # the credit survived


def test_admission_estimated_wait_bound():
    clk = FakeClock()
    pol = AdmissionPolicy(max_queue_depth=100, max_estimated_wait_s=0.5)
    ctrl = AdmissionController(pol, clock=clk)
    ctrl.admit("t", 0)                       # cold start admits
    for _ in range(3):
        ctrl.observe_queue_wait(1.0)
    with pytest.raises(RejectedError) as ei:
        ctrl.admit("t", 0)
    assert ei.value.reason == "wait_bound"
    assert ei.value.retry_after_s == pytest.approx(1.0)
    # waits aging out of the window re-open the door
    clk.advance(pol.window_s + 1.0)
    ctrl.admit("t", 0)


# ---------------------------------------------------------------------------
# summarize(): rejected/timed-out accounted explicitly (pure)
# ---------------------------------------------------------------------------

def test_summarize_accounts_rejected_and_timed_out():
    def rec(i, status, out, lat, deadline=None):
        return RequestRecord(idx=i, tenant="t", scheduled_s=0.0,
                             submitted_s=float(i), prompt_tokens=4,
                             output_tokens=out, latency_s=lat, ttft_s=0.1,
                             queue_wait_s=0.05, prefill_s=0.05,
                             deadline_s=deadline, status=status)

    records = [
        rec(0, "ok", out=10, lat=1.0, deadline=2.0),      # met
        rec(1, "timed_out", out=4, lat=2.5, deadline=2.0),  # partial, shed
        rec(2, "rejected", out=0, lat=0.0),               # never served
        rec(3, "cancelled", out=2, lat=0.5),
    ]
    rep = summarize(records, duration_s=4.0, n_scheduled=5)
    assert rep["n_requests"] == 4
    assert rep["n_ok"] == 1 and rep["n_rejected"] == 1
    assert rep["n_timed_out"] == 1 and rep["n_cancelled"] == 1
    assert rep["n_errors"] == 0
    # 4 records / 5 scheduled: one future never resolved
    assert rep["resolved_fraction"] == pytest.approx(0.8)
    # served excludes ONLY the rejection; partial timed-out tokens count
    # toward raw throughput but never toward goodput
    assert rep["achieved_rps"] == pytest.approx(3 / 4.0)
    assert rep["throughput_tokens_per_s"] == pytest.approx(16 / 4.0)
    assert rep["goodput_tokens_per_s"] == pytest.approx(10 / 4.0)
    # only the ok-and-met request counts as meeting its deadline
    assert rep["deadline_met_fraction"] == pytest.approx(0.25)
    # latency percentiles rank the served set [1.0, 2.5, 0.5]
    assert rep["latency_p50_s"] == pytest.approx(1.0)
    # all-rejected degenerates without crashing
    rep0 = summarize([rec(0, "rejected", out=0, lat=0.0)], duration_s=1.0)
    assert rep0["achieved_rps"] == 0.0
    assert rep0["latency_p50_s"] == 0.0


# ---------------------------------------------------------------------------
# telemetry counters (satellite 3)
# ---------------------------------------------------------------------------

def test_overload_telemetry_counters():
    tel = ServingTelemetry()
    tel.note_rejected("t", "queue_full", 7)
    tel.note_preempted(1)
    tel.note_finish(1, 2, 0.1, 0.05, status="timed_out")
    tel.note_finish(2, 2, 0.1, 0.05, status="cancelled")
    tel.note_finish(3, 2, 0.1, 0.05, status="ok")
    assert tel.requests_rejected.value == 1
    assert tel.requests_preempted.value == 1
    assert tel.requests_timed_out.value == 1
    assert tel.requests_cancelled.value == 1
    assert tel.requests_finished.value == 3
    assert tel.submit_queue_depth.value == 7
    text = tel.registry.to_prometheus()
    for name in ("ffsv_requests_rejected_total",
                 "ffsv_requests_timed_out_total",
                 "ffsv_requests_cancelled_total",
                 "ffsv_requests_preempted_total",
                 "ffsv_queue_depth"):
        assert name in text


# ---------------------------------------------------------------------------
# integration: the incremental and speculative loops on tiny models
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_incr_model():
    """One tiny INC_DECODING model: the incremental loop is a scheduler
    path of its own (the session spec pair only exercises
    generate_spec_infer)."""
    import flexflow_tpu as ff
    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models.llama import LLAMAConfig, create_llama_model

    tiny = LLAMAConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, max_position_embeddings=64)
    cfg = ff.FFConfig(max_requests_per_batch=2, max_sequence_length=64,
                      max_tokens_per_batch=16, seed=0,
                      kv_cache_dtype="float32")
    m = ff.FFModel(cfg)
    create_llama_model(m, tiny, mode=InferenceMode.INC_DECODING_MODE)
    m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    return m


PROMPT_A = [5, 9, 23, 7]
PROMPT_B = [11, 3, 19]


def test_cancel_before_loop_both_paths(tiny_incr_model, tiny_spec_pair):
    """A request cancelled before its generation round resolves as
    status='cancelled' with no output on the incremental and on the
    speculative path; the co-registered request is unaffected."""
    llm, ssm = tiny_spec_pair

    def run(loop):
        rm = RequestManager()
        rm.max_spec_depth = 2
        g_ok = rm.register_new_request(PROMPT_A, max_new_tokens=4)
        g_cx = rm.register_new_request(PROMPT_B, max_new_tokens=4)
        assert rm.cancel(g_cx) is True
        assert rm.cancel(424242) is False      # unknown guid
        loop(rm)
        res_ok, res_cx = rm.results[g_ok], rm.results[g_cx]
        assert res_ok.status == "ok" and len(res_ok.output_tokens) == 4
        assert res_cx.status == "cancelled" and res_cx.cancelled
        assert res_cx.output_tokens == []
        assert rm.cancel(g_cx) is False        # already finished
        assert not rm.pending and not rm.inflight

    run(lambda rm: rm.generate_incr_decoding(tiny_incr_model))
    run(lambda rm: rm.generate_spec_infer(llm, [ssm]))


def test_timeout_resolves_with_partial_result(tiny_incr_model):
    """A request whose deadline expires is reaped between rounds: the
    result exists (never hangs), carries timed_out=True, and holds only
    the prefix generated so far."""
    rm = RequestManager()
    g_ok = rm.register_new_request(PROMPT_A, max_new_tokens=3)
    g_to = rm.register_new_request(PROMPT_B, max_new_tokens=3,
                                   timeout_s=1e-6)     # expired on arrival
    rm.generate_incr_decoding(tiny_incr_model)
    res = rm.results[g_to]
    assert res.status == "timed_out" and res.timed_out
    assert res.output_tokens == []
    assert rm.results[g_ok].status == "ok"
    # expiry mid-generation keeps the partial prefix. A stall injector
    # paces each decode block to >= 80 ms, so 48 tokens (6 blocks) CANNOT
    # beat the 0.2 s deadline no matter how fast the warm model decodes —
    # the reap seam must fire mid-generation.
    inj = FaultInjector(stall_every=1, stall_s=0.08).install(tiny_incr_model)
    try:
        g_mid = rm.register_new_request(PROMPT_A, max_new_tokens=48,
                                        timeout_s=0.2)
        rm.generate_incr_decoding(tiny_incr_model)
    finally:
        inj.uninstall()
    res_mid = rm.results[g_mid]
    assert res_mid.status == "timed_out"
    assert len(res_mid.output_tokens) < 48
    assert not rm.pending and not rm.inflight


def test_midstream_cancel_server_path(tiny_spec_pair):
    llm, ssm = tiny_spec_pair
    handle = EngineHandle(llm, ssms=[ssm], spec_depth=2)
    try:
        handle.start_server()
        srv = handle._server
        guids, ev = srv.submit([PROMPT_A], 48, 0)
        assert handle.rm.cancel(guids[0]) is True
        assert ev.wait(timeout=120.0)
        res = handle.rm.results[guids[0]]
        assert res.status == "cancelled" and res.cancelled
        assert len(res.output_tokens) < 48
    finally:
        handle.stop_server()
    assert check_invariants(handle) == []


def test_preemption_requeues_with_identical_tokens(tiny_spec_pair):
    """ISSUE 16c: a deadline-at-risk high-priority arrival evicts a
    best-effort running request; the victim is RE-QUEUED (re-prefilled),
    not killed, so its final tokens match an unpreempted run exactly."""
    llm, ssm = tiny_spec_pair
    ssms = [ssm]
    # reference outputs, no contention
    ref_rm = RequestManager()
    ref_rm.max_spec_depth = 2
    ga = ref_rm.register_new_request(PROMPT_A, max_new_tokens=24)
    gb = ref_rm.register_new_request(PROMPT_B, max_new_tokens=24)
    ref_rm.generate_spec_infer(llm, ssms)
    ref = {tuple(PROMPT_A): ref_rm.results[ga].output_tokens,
           tuple(PROMPT_B): ref_rm.results[gb].output_tokens}

    handle = EngineHandle(llm, ssms=ssms, spec_depth=2)
    try:
        handle.start_server()
        srv, rm = handle._server, handle.rm
        gA, evA = srv.submit([PROMPT_A], 24, 0)
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
        # high-priority arrival with most of its deadline budget already
        # burned waiting upstream: shift arrival into the past so the
        # at-risk predicate (remaining < preempt_risk * total) holds with
        # plenty of real wall clock left
        gC, evC = srv.submit([PROMPT_B], 2, 0, priority=1, timeout_s=30.0)
        with srv._work:
            rm.inflight[gC[0]].arrival_s -= 70.0
        assert evC.wait(timeout=120.0) and evA.wait(120.0) and evB.wait(120.0)
        resA, resB = rm.results[gA[0]], rm.results[gB[0]]
        resC = rm.results[gC[0]]
        assert resC.status == "ok"
        # one best-effort request was evicted and re-queued...
        assert resA.preemptions + resB.preemptions >= 1
        # ...and BOTH still produced exactly the unpreempted tokens
        assert resA.output_tokens == ref[tuple(PROMPT_A)]
        assert resB.output_tokens == ref[tuple(PROMPT_B)]
        assert resA.status == "ok" and resB.status == "ok"
    finally:
        handle.stop_server()
    assert check_invariants(handle) == []


# ---------------------------------------------------------------------------
# satellites 1 + 2: server fault containment, restart, flush-with-timeout
# ---------------------------------------------------------------------------

def test_server_fault_fails_all_futures_and_is_restartable(tiny_incr_model):
    handle = EngineHandle(tiny_incr_model)
    inj = FaultInjector(error_every=1, max_errors=1).install(tiny_incr_model)
    try:
        handle.start_server()
        srv = handle._server
        guids, ev = srv.submit([PROMPT_A, PROMPT_B], 4, 0)
        assert ev.wait(timeout=60.0)
        assert isinstance(srv._error, EngineFault)
        # in-flight AND queued requests all resolved with the error
        for g in guids:
            res = handle.rm.results[g]
            assert res.status == "error"
            assert "EngineFault" in res.error
        # the door is closed, not hanging
        with pytest.raises(RuntimeError):
            srv.submit([PROMPT_A], 4, 0)
        handle.stop_server(flush_timeout_s=10.0)
    finally:
        inj.uninstall()
    assert check_invariants(handle) == []
    # the stack restarts clean on the same manager/model
    try:
        handle.start_server()
        guids, ev = handle._server.submit([PROMPT_A], 4, 0)
        assert ev.wait(timeout=120.0)
        assert handle.rm.results[guids[0]].status == "ok"
    finally:
        handle.stop_server()


def test_stop_server_flush_timeout_cancels_stragglers(tiny_incr_model):
    handle = EngineHandle(tiny_incr_model)
    handle.start_server()
    srv = handle._server
    guids, ev = srv.submit([[7, 3]], 56, 0)
    # wait for the slot grant: the loop has taken the request (a fixed
    # sleep here lost the race whenever the workers were busy)
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        req = handle.rm.inflight.get(guids[0])
        if req is None or req.prefill_start_s:
            break
        time.sleep(0.002)
    handle.stop_server(flush_timeout_s=0.01)   # well under 56 tokens
    # the waiter resolved (flush cancels stragglers rather than hanging)
    assert ev.is_set()
    # the cancelled round ends within a block; its result is there once
    # the loop's thread has returned, however slow the machine
    srv._thread.join(60)
    assert not srv._thread.is_alive()
    res = handle.rm.results.get(guids[0])
    assert res is not None
    assert res.status in ("cancelled", "ok")   # ok only if absurdly fast
    assert handle._server is None
    assert check_invariants(handle) == []


# ---------------------------------------------------------------------------
# the chaos harness: every submitted future resolves
# ---------------------------------------------------------------------------

def test_run_chaos_every_future_resolves(tiny_incr_model):
    inj = FaultInjector(error_every=7, max_errors=1).install(tiny_incr_model)
    report = run_chaos(
        EngineHandle(tiny_incr_model), n_requests=10, seed=0, injector=inj,
        max_new_tokens=6, timeout_s=0.05, cancel_fraction=0.3,
        timeout_fraction=0.3, admission=AdmissionPolicy(max_queue_depth=4),
        resolve_bound_s=120.0)
    assert report["problems"] == []
    assert report["resolved_fraction"] == 1.0
    assert sum(report["statuses"].values()) == 10
    assert "unresolved" not in report["statuses"]
    # the seeded plan exercises more than the happy path
    assert set(report["statuses"]) - {"ok"}


def test_faulttest_tool_holds_its_invariant(capsys):
    """tools/faulttest.py as the README gives it, on its default ``tiny``
    geometry: the model it builds, its injector, the report it prints and
    the exit status that says the invariant held."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import faulttest
    finally:
        sys.path.pop(0)
    assert faulttest.main(["--requests", "4"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["problems"] == [] and report["n_requests"] == 4
    assert report["resolved_fraction"] == 1.0
