"""The loops that serve traffic wait, under telemetry, for a prefill step one
device call late (the incremental loop: ISSUE 37; the fused speculation
loop, both engines: ISSUE 52): the order of the ``call_*`` leaves across
calls, the per-request ``prefill`` / ``decode_block`` / ``decode_round``
spans and the ``spec_block`` span that come of it, and the counters that
move with them (the benchmark's readers of what this adds:
tests/test_telemetry.py, beside the other hand-built traces).
"""

import pytest

from flexflow_tpu.serve.batch_config import GenerationConfig
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
# ``incr``: generate_incr_decoding. ``spec_tree`` / ``spec_beam``: the fused
# speculation loop over MultiSpecEngine / BeamSpecEngine, every round
# speculating. ``spec_parked``: the same loop with its controller on, which
# parks a draft of the verifier's own size on the decode block at once
# (_fallback_decode) and leaves the long prompt, 14 positions from its
# cache's end and the engine's depth the default, to the single-step path
LOOPS = ("incr", "spec_tree", "spec_beam", "spec_parked")
_NEW = {"incr": (3, 12), "spec_parked": (3, 12),
        "spec_tree": (6, 12), "spec_beam": (6, 12)}


@pytest.fixture(scope="module")
def models(tiny_spec_pair, tiny_beam_draft):
    return {"llm": tiny_spec_pair[0], "ssm": tiny_spec_pair[1],
            "beam": tiny_beam_draft}


def _serve(models, loop, telemetry: bool, costs=None):
    """A long prompt filling (two segments of 8 a step) beside a short
    request decoding. ``incr``, with ``costs`` given: round 1 takes one
    step (nothing decodes yet), round 2 the three that are left, of the
    four its block of 4 pays for. The speculation loops take one step a
    model a round: the verifier's, then the draft's while the long prompt
    owes it more than a block, then the round's block."""
    llm = models["llm"]
    ifm = getattr(llm, "_inference_manager", None)
    if ifm is None:
        ifm = llm._inference_manager = InferenceManager(llm)
    if loop == "incr":
        ifm.step_costs = costs or GivenCosts(1.0, 1.0)
    rm = RequestManager()
    long_new, short_new = _NEW[loop]
    gl = rm.register_new_request(_LONG, max_new_tokens=long_new)
    gs = rm.register_new_request(_SHORT, max_new_tokens=short_new)
    tel = enable_telemetry() if telemetry else None
    try:
        if loop == "incr":
            rm.generate_incr_decoding(llm)
        else:
            rm.generate_spec_infer(
                llm, [models["beam" if loop == "spec_beam" else "ssm"]],
                spec_depth=None if loop == "spec_parked" else 2,
                generation_config=GenerationConfig(
                    adaptive_spec=loop == "spec_parked"))
        events = tel.tracer.events if tel else None
        prefilled = (tel.registry.get("ffsv_prefill_tokens_total").value
                     if tel else None)
    finally:
        disable_telemetry()
        if loop == "incr":
            del ifm.step_costs
    return ([rm.results[g].output_tokens for g in (gl, gs)], events,
            prefilled)


@pytest.fixture(scope="module", params=LOOPS)
def lagged(request, models):
    """(loop, served tokens, events, prefilled tokens) of one served run
    with telemetry on."""
    return (request.param,) + _serve(models, request.param, True)


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
    """The spans ``name`` (a request's copy each, or ``spec_block``'s one),
    one a device call: (start, end, its copies' args)."""
    calls = {}
    for e in events:
        if (e["ph"] == "X" and e["name"] == name
                and (e["tid"] != 0 or name == "spec_block")):
            calls.setdefault((e["ts"], e["dur"]), []).append(e["args"])
    return [(ts, ts + dur, args) for (ts, dur), args in sorted(calls.items())]


S, L, W = ("stage", "prefill"), ("launch", "prefill"), ("wait", "prefill")
# only the incremental loop times its rounds
_LOOP_TIMED = [("incr", False), ("incr", True)] + [(loop, False)
                                                   for loop in LOOPS[1:]]


def _launch_and_wait(program):
    return ([("stage", program), ("launch", program)], [("wait", program)])


@pytest.mark.parametrize("lagged,timed", _LOOP_TIMED, indirect=["lagged"])
def test_a_prefill_step_is_waited_for_after_the_next_launch(
        timed, lagged, models):
    """A round launches prefill step k+1 before step k's wait, and its
    block (decode block, speculation block, the single step of a row near
    its cache's end) before the last step's; a timed round of the
    incremental loop waits for each step before it stages the next; a round
    that launches nothing behind its step waits for it as it ends. Either
    way: one stage, one launch, one wait a device call (a lead step's wait
    in the round after its launch)."""
    loop, _, events, _ = lagged
    if timed:
        events = _serve(models, loop, True, _TimedCosts(1.0, 1.0))[1]
    rounds = _calls_by_round(events)
    block, done = _launch_and_wait("decode_block")
    if loop == "incr" and timed:
        assert rounds[0] == [S, L, W] + block + done
        assert rounds[1] == [S, L, W] * 3 + block + done
    elif loop == "incr":
        # ISSUE 61: the long prompt is still filling when a block is
        # launched, so the next round's first step, its lead step, is
        # staged and launched behind the block and before the block's
        # wait, and waited for after that round's next launch
        assert rounds[0] == [S, L] + block + [W, S, L] + done
        assert rounds[1] == [S, L, W, S, L, W] + block + [W] + done
    elif loop == "spec_parked":
        step, stepped = _launch_and_wait("step")
        assert rounds[0] == rounds[1] == [S, L] + block + [W] + done
        assert rounds[2] == [S, L, W]       # nobody ready: the round's end
        assert rounds[3] == [S, L] + step + [W] + stepped
        assert all(r == step + stepped for r in rounds[4:]) and rounds[4:]
    else:
        block, done = _launch_and_wait("spec_block")
        # the verifier's step, the draft's, the block: each waited for
        # behind the next one's launch
        assert rounds[0] == rounds[1] == rounds[2] == (
            [S, L, S, L, W] + block + [W] + done)
        assert rounds[3] == [S, L] + block + [W] + done
        assert all(r == block + done for r in rounds[4:]) and rounds[4:]
    for leaf in ("stage", "wait"):
        for calls in rounds if loop != "incr" or timed else [sum(rounds, [])]:
            assert (sum(c[0] == leaf for c in calls)
                    == sum(c[0] == "launch" for c in calls))


def test_the_spans_of_a_round_bracket_each_its_own_call(lagged):
    """The ``prefill`` spans do not overlap each other nor a
    ``decode_block``, ``spec_block`` or ``decode_round`` span, though the
    calls were launched behind each other; a block launched behind a step
    starts where the step ended, a block alone where it was staged; every
    ``prefill`` copy says whose cache it filled and every ``spec_block``
    what it was launched behind; the spans' tokens are the counter's gain,
    so a snapshot never counts a step whose span is not out."""
    loop, _, events, prefilled = lagged
    steps = _device_calls(events, "prefill")
    blocks = (_device_calls(events, "decode_block")
              + _device_calls(events, "spec_block"))
    spans = sorted(steps + blocks)
    eps = 0.25                      # two roundings to 0.1 us
    for a, b in zip(spans, spans[1:]):
        assert a[1] <= b[0] + eps, (a, b)
    own = {"incr": {"llm": 4}, "spec_parked": {"llm": 4},
           "spec_tree": {"llm": 4, "ssm0": 3},
           "spec_beam": {"llm": 4, "ssm0": 3}}[loop]
    by_model = {}
    for _, _, args in steps:
        assert len({a["model"] for a in args}) == 1
        by_model[args[0]["model"]] = by_model.get(args[0]["model"], 0) + 1
    assert by_model == own
    # the verifier's 49 + 2; a draft's 48 (the long prompt's last token and
    # the short prompt ride in as their rows' first accepted block)
    assert sum(a["n_tokens"] for _, _, args in steps
               for a in args) == prefilled == (
        len(_LONG) - 1 + len(_SHORT) - 1 + (48 if "ssm0" in own else 0))
    if loop == "incr":
        assert len(blocks) >= 2
        # the round's calls follow each other without a gap: a step's span
        # starts where the one before ended, and so does the block's
        second = steps[1:] + [blocks[1]]
        for a, b in zip(second, second[1:]):
            assert b[0] == pytest.approx(a[1], abs=eps)
    if not loop.startswith("spec_") or loop == "spec_parked":
        return
    rounds = _device_calls(events, "decode_round")
    leaves = sorted((e for e in events if e["ph"] == "X" and e["tid"] == 0
                     and e["name"] in ("sched_build", "call_stage")),
                    key=lambda e: e["ts"])
    behind = [b for b in blocks if b[2][0]["behind"] == "prefill"]
    alone = [b for b in blocks if b[2][0]["behind"] is None]
    assert len(behind) == 4 and alone and len(behind + alone) == len(blocks)
    for b in blocks:
        # the call's stage leaf, and the leaf that was open before it
        at = max(i for i, e in enumerate(leaves) if e["ts"] < b[1]
                 and e.get("args", {}).get("program") == "spec_block")
        stage, before = leaves[at], leaves[at - 1]
        assert before["name"] == "sched_build"
        first = min(r[0] for r in rounds if before["ts"] <= r[0] < b[1])
        step = max(s for s in steps if s[0] < b[1])
        if b in behind:
            # staged while the step before it ran; on the device, and in
            # its spans, from that step's end
            assert stage["ts"] + stage["dur"] <= b[0] + eps
            assert b[0] == pytest.approx(step[1], abs=eps)
            assert first == pytest.approx(step[1], abs=eps)
        else:
            assert step[1] <= before["ts"] + eps
            assert (before["ts"] + before["dur"] - eps <= first
                    <= b[0] + eps <= stage["ts"] + 2 * eps)
    for r in rounds:
        for s in steps:
            assert r[1] <= s[0] + eps or s[1] <= r[0] + eps, (r, s)


@pytest.mark.parametrize("lagged", ["incr"], indirect=True)
def test_decode_block_spans_carry_their_rows(lagged):
    """Every request's copy of a ``decode_block`` span carries the block's
    live rows, so the copy a reader keeps has it."""
    blocks = _device_calls(lagged[2], "decode_block")
    assert blocks
    for _, _, args in blocks:
        assert {a["rows"] for a in args} == {len(args)}
    assert {len(args) for _, _, args in blocks} == {1, 2}


@pytest.mark.parametrize("lagged,timed", _LOOP_TIMED, indirect=["lagged"])
def test_served_tokens_are_the_same_with_telemetry_on_and_off(
        timed, lagged, models):
    loop, tokens, _, _ = lagged
    plain = _serve(models, loop, False,
                   _TimedCosts(1.0, 1.0) if timed else None)
    assert plain[1] is None
    assert plain[0] == tokens
    assert [len(t) for t in plain[0]] == list(_NEW[loop])


@pytest.mark.parametrize("loop,timed", _LOOP_TIMED)
@pytest.mark.parametrize("telemetry", [False, True])
def test_who_waits_for_a_prefill_step(telemetry, loop, timed, models,
                                      monkeypatch):
    """Telemetry off: a timed round fences the state after each step and
    no other round waits for anything, as before ISSUE 37; the speculation
    loop never does. Telemetry on: one wait on each step's own output (the
    verifier's four, and a draft's three where one drafts), never a fence
    of the state."""
    import flexflow_tpu.telemetry as TL
    from flexflow_tpu.serve import request_manager as RM

    fenced, waited = [], []
    monkeypatch.setattr(RM, "device_fence", fenced.append)
    monkeypatch.setattr(TL.jax, "block_until_ready", waited.append)
    costs = (_TimedCosts if timed else GivenCosts)(1.0, 1.0)
    tokens = _serve(models, loop, telemetry, costs)[0]
    assert [len(t) for t in tokens] == list(_NEW[loop])
    steps = 7 if loop in ("spec_tree", "spec_beam") else 4
    assert len(fenced) == (4 if timed and not telemetry else 0)
    assert len(waited) == (steps if telemetry else 0)
    assert all(w is not None and not isinstance(w, dict) for w in waited)
