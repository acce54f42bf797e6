"""The incremental loop under telemetry waits for a prefill step one device
call late (ISSUE 37): the order of the ``call_*`` leaves across calls, the
per-request ``prefill`` / ``decode_block`` spans that come of it and the
counters that move with them (the benchmark's readers of what this adds:
tests/test_telemetry.py, beside the other hand-built trace).
"""

import pytest

from flexflow_tpu.serve.inference_manager import InferenceManager
from flexflow_tpu.serve.request_manager import RequestManager
from flexflow_tpu.serve.step_costs import GivenCosts
from flexflow_tpu.telemetry import disable_telemetry, enable_telemetry


class _TimedCosts(GivenCosts):
    """Every prefilling round is a timed one."""

    def due(self):
        return True


_LONG = [(5 * i) % 96 + 1 for i in range(50)]
_SHORT = [7, 3, 2]


def _serve(llm, costs, telemetry: bool):
    """A long prompt filling (two segments of 8 a step) beside a short
    request decoding: round 1 takes one step (nothing decodes yet), round 2
    the three that are left, of the four its block of 4 pays for."""
    ifm = getattr(llm, "_inference_manager", None)
    if ifm is None:
        ifm = llm._inference_manager = InferenceManager(llm)
    ifm.step_costs = costs
    rm = RequestManager()
    gl = rm.register_new_request(_LONG, max_new_tokens=3)
    gs = rm.register_new_request(_SHORT, max_new_tokens=12)
    tel = enable_telemetry() if telemetry else None
    try:
        rm.generate_incr_decoding(llm)
        events = tel.tracer.events if tel else None
        prefilled = (tel.registry.get("ffsv_prefill_tokens_total").value
                     if tel else None)
    finally:
        disable_telemetry()
        del ifm.step_costs
    return ([rm.results[g].output_tokens for g in (gl, gs)], events,
            prefilled)


@pytest.fixture(scope="module")
def lagged(tiny_spec_pair):
    return _serve(tiny_spec_pair[0], GivenCosts(1.0, 1.0), True)


def _calls_by_round(events):
    """For each ``sched_round``: its ``call_*`` leaves in order, as
    (leaf, program)."""
    batch = sorted((e for e in events if e["ph"] == "X" and e["tid"] == 0),
                   key=lambda e: e["ts"])
    out = []
    for r in (e for e in batch if e["name"] == "sched_round"):
        out.append([(e["name"][5:], e["args"]["program"]) for e in batch
                    if e["name"].startswith("call_")
                    and r["ts"] <= e["ts"] < r["ts"] + r["dur"]])
    return out


def _device_calls(events, name):
    """The per-request spans ``name``, one a device call: (start, end, its
    copies' args)."""
    calls = {}
    for e in events:
        if e["ph"] == "X" and e["tid"] != 0 and e["name"] == name:
            calls.setdefault((e["ts"], e["dur"]), []).append(e["args"])
    return [(ts, ts + dur, args) for (ts, dur), args in sorted(calls.items())]


@pytest.mark.parametrize("timed", [False, True])
def test_a_prefill_step_is_waited_for_after_the_next_launch(
        timed, lagged, tiny_spec_pair):
    """With the costs given, a round that takes several prefill steps
    launches step k+1 before step k's wait, and its decode block before the
    last step's; a timed round waits for each step before it stages the
    next. Either way: one stage, one launch, one wait a device call."""
    events = (_serve(tiny_spec_pair[0], _TimedCosts(1.0, 1.0), True)[1]
              if timed else lagged[1])
    rounds = _calls_by_round(events)
    S, L, W = (("stage", "prefill"), ("launch", "prefill"),
               ("wait", "prefill"))
    block = [("stage", "decode_block"), ("launch", "decode_block")]
    done = [("wait", "decode_block")]
    if timed:
        assert rounds[0] == [S, L, W] + block + done
        assert rounds[1] == [S, L, W] * 3 + block + done
    else:
        assert rounds[0] == [S, L] + block + [W] + done
        assert rounds[1] == [S, L, S, L, W, S, L, W] + block + [W] + done
    for calls in rounds:
        for leaf in ("stage", "launch", "wait"):
            assert (sum(c[0] == leaf for c in calls)
                    == sum(c == S for c in calls) + bool(block[0] in calls))


def test_the_spans_of_a_round_bracket_each_its_own_call(lagged):
    """The ``prefill`` spans do not overlap each other nor a
    ``decode_block`` span, though the calls were launched behind each
    other; their tokens are the counter's gain, so a snapshot never counts
    a step whose span is not out."""
    _, events, prefilled = lagged
    steps = _device_calls(events, "prefill")
    blocks = _device_calls(events, "decode_block")
    assert len(steps) == 4 and len(blocks) >= 2
    spans = sorted(steps + blocks)
    eps = 0.25                      # two roundings to 0.1 us
    for a, b in zip(spans, spans[1:]):
        assert a[1] <= b[0] + eps, (a, b)
    # the round's calls follow each other without a gap: a step's span
    # starts where the one before ended, and so does the block's
    second = steps[1:] + [blocks[1]]
    for a, b in zip(second, second[1:]):
        assert b[0] == pytest.approx(a[1], abs=eps)
    assert sum(a["n_tokens"] for _, _, args in steps
               for a in args) == prefilled == len(_LONG) - 1 + len(_SHORT) - 1


def test_decode_block_spans_carry_their_rows(lagged):
    """Every request's copy of a ``decode_block`` span carries the block's
    live rows, so the copy a reader keeps has it."""
    blocks = _device_calls(lagged[1], "decode_block")
    assert blocks
    for _, _, args in blocks:
        assert {a["rows"] for a in args} == {len(args)}
    assert {len(args) for _, _, args in blocks} == {1, 2}


@pytest.mark.parametrize("costs", [GivenCosts(1.0, 1.0),
                                   _TimedCosts(1.0, 1.0)])
def test_served_tokens_are_the_same_with_telemetry_on_and_off(
        costs, lagged, tiny_spec_pair):
    plain = _serve(tiny_spec_pair[0], costs, False)
    assert plain[1] is None
    assert plain[0] == lagged[0]
    assert [len(t) for t in plain[0]] == [3, 12]


@pytest.mark.parametrize("timed", [False, True])
@pytest.mark.parametrize("telemetry", [False, True])
def test_who_waits_for_a_prefill_step(telemetry, timed, tiny_spec_pair,
                                      monkeypatch):
    """Telemetry off: a timed round fences the state after each step and
    no other round waits for anything, as before ISSUE 37. Telemetry on:
    one wait on each step's own output, never a fence of the state."""
    import flexflow_tpu.telemetry as TL
    from flexflow_tpu.serve import request_manager as RM

    fenced, waited = [], []
    monkeypatch.setattr(RM, "device_fence", fenced.append)
    monkeypatch.setattr(TL.jax, "block_until_ready", waited.append)
    costs = (_TimedCosts if timed else GivenCosts)(1.0, 1.0)
    tokens = _serve(tiny_spec_pair[0], costs, telemetry)[0]
    assert [len(t) for t in tokens] == [3, 12]
    assert len(fenced) == (4 if timed and not telemetry else 0)
    assert len(waited) == (4 if telemetry else 0)
    assert all(w is not None and not isinstance(w, dict) for w in waited)
