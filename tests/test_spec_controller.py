"""Adaptive speculation controller tests (spec decoding must never lose
to plain decoding).

Lean by design (tier-1 budget): the policy layer is pure functions
tested as data-in/data-out; the engine contract runs on the shared
session-scoped ``tiny_spec_pair``; one end-to-end adversarial-draft test
pins the fallback story against incremental decoding.
"""

import os
import time
import warnings

import numpy as np
import pytest

from flexflow_tpu.serve.batch_config import GenerationConfig
from flexflow_tpu.serve.request_manager import RequestManager
from flexflow_tpu.serve.spec_controller import (
    ControllerPolicy,
    SpecController,
    best_depth,
    depth_schedule,
    expected_tokens_per_round,
    initial_state,
    note_fallback_block,
    probe_due,
    round_cost,
    speedup_estimate,
)


# ---------------------------------------------------------------------------
# pure cost model
# ---------------------------------------------------------------------------

def test_cost_model_monotonicity():
    # E[tokens/round] grows with acceptance and with depth
    for d in (1, 4, 8):
        es = [expected_tokens_per_round(p, d)
              for p in (0.0, 0.25, 0.5, 0.75, 1.0)]
        assert es == sorted(es)
        assert es[0] == 1.0                    # bonus token only
        assert es[-1] == d + 1                 # full accept + bonus
    for p in (0.2, 0.6, 0.95):
        es = [expected_tokens_per_round(p, d) for d in range(1, 9)]
        assert es == sorted(es)
    # round cost grows linearly with depth
    assert round_cost(4, 0.1) > round_cost(1, 0.1)
    # the speedup estimate is monotone in acceptance at fixed depth/cost
    ss = [speedup_estimate(p, 4, 0.1) for p in (0.0, 0.3, 0.6, 0.9)]
    assert ss == sorted(ss)
    # and the best achievable estimate is monotone in acceptance too
    bs = [best_depth(p, 1, 8, 0.1)[1] for p in (0.0, 0.3, 0.6, 0.9)]
    assert bs == sorted(bs)


def test_best_depth_tracks_acceptance():
    # hopeless drafts want the shallowest chain, great drafts the deepest
    d_lo, est_lo = best_depth(0.05, 1, 8, 0.1)
    d_hi, est_hi = best_depth(0.99, 1, 8, 0.1)
    assert d_lo == 1 and d_hi == 8
    assert est_lo < 1.0 < est_hi
    # best depth never decreases as acceptance improves
    depths = [best_depth(p, 1, 8, 0.1)[0]
              for p in np.linspace(0.0, 1.0, 21)]
    assert depths == sorted(depths)
    # a draft as costly as its verifier can never beat incremental:
    # E = sum p^k <= d+1 = C at ratio 1, with equality only at p == 1
    for p in (0.3, 0.7, 1.0):
        assert best_depth(p, 1, 8, 1.0, overhead=0.0)[1] <= 1.0 + 1e-9


def test_depth_schedule_grows_and_shrinks():
    pol = ControllerPolicy(min_depth=1, max_depth=8, draft_cost_ratio=0.1,
                           ewma_alpha=0.5)
    # full accepts at the current depth -> schedule climbs to max
    sched = depth_schedule([(d, d) for d in range(1, 12)], pol)
    assert sched[-1].depth == 8
    assert not sched[-1].fallback
    # then a run of zero accepts -> depth collapses and the request parks
    sched2 = depth_schedule([(8, 8)] * 4 + [(8, 0)] * 8, pol)
    assert sched2[-1].fallback
    assert sched2[-1].depth == 1
    # the schedule is deterministic (pure function)
    assert depth_schedule([(4, 2), (4, 0)], pol) \
        == depth_schedule([(4, 2), (4, 0)], pol)


def test_fallback_hysteresis_no_flapping():
    """The park/un-park thresholds differ (0.95 / 1.05): a draft hovering
    exactly at break-even must not oscillate between modes."""
    pol = ControllerPolicy(min_depth=1, max_depth=8, draft_cost_ratio=0.3,
                           ewma_alpha=0.3, fallback_margin=0.95,
                           recover_margin=1.05)
    # drive acceptance down until parked
    sched = depth_schedule([(4, 0)] * 10, pol)
    assert sched[-1].fallback
    # break-even-ish samples (est lands between the margins): stays parked
    st = sched[-1]
    flips = 0
    prev = st.fallback
    from flexflow_tpu.serve.spec_controller import observe_round

    for _ in range(30):
        st = observe_round(st, 2, 1, pol)      # sample 0.5 each round
        flips += int(st.fallback != prev)
        prev = st.fallback
    assert flips <= 1                          # at most one transition
    # strongly recovered acceptance un-parks it
    for _ in range(10):
        st = observe_round(st, st.depth, st.depth, pol)
    assert not st.fallback
    assert st.depth == pol.max_depth


def test_same_size_draft_parks_from_the_start():
    """A draft as large as its verifier cannot win: the cost model parks
    it before a single wasted round (and counts the fallback entry)."""
    pol = ControllerPolicy(min_depth=1, max_depth=8, draft_cost_ratio=1.0)
    st = initial_state(pol)
    assert st.fallback and st.fallback_entries == 1
    # while a 2-layers-of-32 truncation draft starts speculating
    pol2 = ControllerPolicy(min_depth=1, max_depth=8,
                            draft_cost_ratio=0.08)
    assert not initial_state(pol2).fallback


def test_probe_cadence_and_recovery():
    pol = ControllerPolicy(min_depth=1, max_depth=4, draft_cost_ratio=1.0,
                           probe_every=3, recover_margin=1.05)
    ctrl = SpecController(pol)
    guid = 7

    def drafts():
        return guid in ctrl.drafting([guid])

    assert not drafts()                        # parked at admission
    assert ctrl.take_new_fallbacks() == 1
    for _ in range(pol.probe_every - 1):
        ctrl.note_fallback_block(guid)
        assert not drafts()
    ctrl.note_fallback_block(guid)
    assert drafts()                            # probe due
    # a bad probe re-parks and restarts the clock
    ctrl.observe_block(guid, [(1, 0)])
    assert not drafts()
    assert probe_due(note_fallback_block(ctrl.states[guid]), pol) is False
    # an empty probe block (engine masked every round) also restarts it
    for _ in range(pol.probe_every):
        ctrl.note_fallback_block(guid)
    assert drafts()
    ctrl.observe_block(guid, [])
    assert not drafts()
    ctrl.drop(guid)
    assert guid not in ctrl.states


def test_parked_request_rides_with_a_drafting_one():
    """Parking is per request, the incremental block per batch: beside a
    request that speculates a parked one speculates too (the block runs
    anyway), at the depth its own acceptance earns; a batch parked whole
    decodes incrementally but for the probes that are due."""
    pol = ControllerPolicy(min_depth=1, max_depth=4, draft_cost_ratio=0.1,
                           probe_every=2)
    ctrl = SpecController(pol)
    for _ in range(6):                         # zero acceptance: parks
        ctrl.observe_block(1, [(4, 0)])
        ctrl.observe_block(2, [(4, 0)])
    ctrl.observe_block(3, [(4, 4)])
    assert ctrl.in_fallback(1) and ctrl.in_fallback(2)
    assert not ctrl.in_fallback(3)
    assert ctrl.drafting([1, 2, 3]) == {1, 2, 3}
    assert ctrl.depth_for(1) == 1 and ctrl.depth_for(3) == 4
    assert ctrl.in_fallback(1)                 # riding is not recovering
    assert ctrl.drafting([1, 2]) == set()
    ctrl.note_fallback_block(1)
    ctrl.note_fallback_block(1)
    assert ctrl.drafting([1, 2]) == {1}        # its probe, not 2's
    assert ctrl.drafting([]) == set()


# ---------------------------------------------------------------------------
# engine contract: per-row depth vector, no retrace
# ---------------------------------------------------------------------------

def test_engine_depth_vector_caps_and_adapts(tiny_spec_pair):
    """One compiled block serves a mixed-depth batch: row depths bound
    acceptance per row, the device grows a fully-accepting row's depth
    between rounds, and depth_used reports what each round ran under."""
    from flexflow_tpu.serve.engine import MultiSpecEngine

    llm, ssm = tiny_spec_pair                 # same weights: full accepts
    eng = MultiSpecEngine(llm, [ssm], depth=4, max_rounds=8)
    tks = np.zeros((2, 5), np.int32)
    tks[:, 0] = 5                             # a one-token block a row
    nblk = np.ones((2,), np.int32)
    base = np.zeros((2,), np.int32)
    act = np.ones((2,), bool)
    remaining = np.full((2,), 12, np.int32)
    a, n_acc, d_used = eng.run_block(tks, nblk, base, act, 3, remaining,
                                     depth=np.array([1, 4], np.int32),
                                     min_depth=1)
    assert a.shape[2] == 5 and n_acc.shape == d_used.shape
    valid = n_acc >= 0
    assert valid[:, 0].all()
    # acceptance never exceeds the round's depth bound, per row
    assert (n_acc[valid] <= d_used[valid]).all()
    # round 0 ran each row at its requested depth
    assert d_used[0, 0] == 1 and d_used[1, 0] == 4
    # same-weights draft accepts fully -> the capped row grew next round
    assert n_acc[0, 0] == 1
    assert d_used[0, 1] == 2
    # the full-depth row is already at the compiled max and stays there
    assert n_acc[1, 0] == 4 and d_used[1, 1] == 4


def test_give_up_is_the_blocks_not_the_rows():
    """A row at the floor that accepts nothing leaves a block only when
    every live row has collapsed: beside one that still accepts, the
    block runs on anyway and the row keeps committing the verifier's
    token. Depths still shrink and grow per row."""
    import jax.numpy as jnp

    from flexflow_tpu.serve.engine import _adapt_depth_rule

    def rule(adapt, act, n_acc, depth):
        d, alive = _adapt_depth_rule(
            jnp.bool_(adapt), jnp.array(act), jnp.array(n_acc, jnp.int32),
            jnp.array(depth, jnp.int32), jnp.array(act), jnp.int32(1), 4)
        return d.tolist(), alive.tolist()

    # row 0 collapsed, row 1 accepts fully, row 2 is not in the block
    assert rule(True, [True, True, False], [0, 3, 0], [1, 3, 1]) == \
        ([1, 4, 1], [True, True, False])
    # both live rows collapsed: the block ends
    assert rule(True, [True, True, False], [0, 0, 0], [1, 1, 1]) == \
        ([1, 1, 1], [False, False, False])
    # a zero accept above the floor shrinks and stays
    assert rule(True, [True, True, False], [0, 0, 0], [1, 2, 1]) == \
        ([1, 1, 1], [True, True, False])
    # a static block adapts nothing
    assert rule(False, [True, True, False], [0, 0, 0], [1, 1, 1]) == \
        ([1, 1, 1], [True, True, False])


# ---------------------------------------------------------------------------
# end to end: a zero-acceptance draft must not lose to incremental
# ---------------------------------------------------------------------------

def _adversarial_ssm(beam=1):
    """1-layer draft with UNRELATED weights (seed 99): cheap enough that
    the cost model starts out speculating, wrong enough that acceptance
    is ~zero — the controller must detect and park within a few rounds.
    ``beam`` 2: compiled for the beam engine."""
    import flexflow_tpu as ff
    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models.llama import LLAMAConfig, create_llama_model

    cfg = ff.FFConfig(max_requests_per_batch=2, max_sequence_length=64,
                      max_tokens_per_batch=16, seed=99,
                      kv_cache_dtype="float32", max_beam_width=beam)
    m = ff.FFModel(cfg)
    create_llama_model(
        m,
        LLAMAConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                    num_hidden_layers=1, num_attention_heads=4,
                    num_key_value_heads=2, max_position_embeddings=128),
        mode=InferenceMode.BEAM_SEARCH_MODE)
    m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    return m


def test_zero_acceptance_adversarial_draft_never_loses(tiny_spec_pair):
    from flexflow_tpu.telemetry import ServingTelemetry

    llm, _good = tiny_spec_pair
    adv = _adversarial_ssm()
    prompts = [[5, 9, 23, 44], [7, 3, 11]]
    max_new = 40

    def run_incr():
        rm = RequestManager()
        for p in prompts:
            rm.register_new_request(p, max_new_tokens=max_new)
        t0 = time.perf_counter()
        res = rm.generate_incr_decoding(llm)
        return ({tuple(r.input_tokens): r.output_tokens for r in res},
                time.perf_counter() - t0)

    def run_spec(tel=None):
        rm = RequestManager(telemetry=tel)
        for p in prompts:
            rm.register_new_request(p, max_new_tokens=max_new)
        t0 = time.perf_counter()
        res = rm.generate_spec_infer(llm, [adv])
        return ({tuple(r.input_tokens): r.output_tokens for r in res},
                time.perf_counter() - t0)

    incr, _ = run_incr()                       # also compiles decode block
    tel = ServingTelemetry()
    spec, _ = run_spec(tel)
    # the controller must not change WHAT is generated, ever: greedy
    # acceptance + the incremental fallback both commit the verifier's
    # own argmax continuation
    assert spec == incr
    for p in prompts:
        assert len(spec[tuple(p)]) == max_new

    reg = tel.registry
    # the controller detected the hopeless draft and parked both requests
    assert reg.get("ffsv_spec_fallback_total").value >= 2
    # most tokens came through the fused incremental block, not rounds:
    # 2 x 40 tokens with at most the initial sizing-up + sparse probes
    # speculating (each block is <= spec_rounds_per_call = 4 rounds)
    spec_rounds = reg.get("ffsv_spec_rounds_total").value
    assert spec_rounds <= 20, spec_rounds
    assert reg.get("ffsv_decode_steps_total").value >= max_new
    # effective depth collapsed to the floor while it still speculated
    eff = reg.get("ffsv_spec_effective_depth")
    assert eff.count == spec_rounds
    if eff.count:
        assert eff.percentile(50) <= 2

    # wall clock: warm timed passes; parity (~1.05x) holds on real
    # hardware where forwards dominate — on shared CI machines the
    # dispatch-overhead-dominated TINY models jitter, so the ratio is
    # enforced strictly only under FF_TPU_STRICT_TIMING (repo idiom,
    # see test_serving.py) and is otherwise informational
    _, dt_incr = run_incr()
    _, dt_spec = run_spec()
    ratio = dt_spec / max(dt_incr, 1e-9)
    if os.environ.get("FF_TPU_STRICT_TIMING") == "1":
        assert ratio <= 1.15, (dt_spec, dt_incr)
    elif ratio > 1.5:
        warnings.warn(f"adaptive spec vs incr wall-clock ratio {ratio:.2f} "
                      f"({dt_spec:.3f}s vs {dt_incr:.3f}s, informational)")


def test_zero_acceptance_beam_engine_parks_too(tiny_spec_pair):
    """The beam engine gets the same controller through the same loop
    (the tree engine is the test above): adversarial draft -> park ->
    tokens identical to incremental."""
    from flexflow_tpu.telemetry import ServingTelemetry

    llm, _good = tiny_spec_pair
    adv = _adversarial_ssm(beam=2)
    prompts = [[5, 9, 23, 44], [7, 3, 11]]

    rm = RequestManager()
    for p in prompts:
        rm.register_new_request(p, max_new_tokens=16)
    incr = {tuple(r.input_tokens): r.output_tokens
            for r in rm.generate_incr_decoding(llm)}

    tel = ServingTelemetry()
    rm2 = RequestManager(telemetry=tel)
    for p in prompts:
        rm2.register_new_request(p, max_new_tokens=16)
    res = rm2.generate_spec_infer(llm, [adv])
    assert rm2.scheduler_loop == "python:spec_beam_fused"
    assert {tuple(r.input_tokens): r.output_tokens for r in res} == incr
    assert tel.registry.get("ffsv_spec_fallback_total").value >= 2
    assert tel.registry.get("ffsv_spec_rounds_total").value <= 12


@pytest.mark.parametrize("engine", ["beam", "tree"])
def test_parked_row_never_stalls_a_drafting_batch(tiny_spec_pair,
                                                  tiny_beam_draft, engine,
                                                  monkeypatch):
    """A request the controller holds parked from its first round, beside
    one that drafts: no fallback decode block runs while both are live
    (such a block decodes the parked row alone for decode_block_steps
    steps and every other row waits it out), the parked one is served by
    the speculation blocks, and both get the incremental tokens."""
    import dataclasses

    llm, ssm = tiny_spec_pair
    if engine == "beam":
        ssm = tiny_beam_draft
    prompts = [[5, 9, 23, 44], [7, 3, 11]]
    rm = RequestManager()
    for p in prompts:
        rm.register_new_request(p, max_new_tokens=12)
    incr = {tuple(r.input_tokens): r.output_tokens
            for r in rm.generate_incr_decoding(llm)}

    rm2 = RequestManager()
    held = rm2.register_new_request(prompts[0], max_new_tokens=12)
    rm2.register_new_request(prompts[1], max_new_tokens=20)
    state = SpecController._state

    def hold_parked(self, guid):
        st = state(self, guid)
        if guid == held and not st.fallback:
            st = self.states[guid] = dataclasses.replace(
                st, fallback=True, acceptance=0.0, depth=1)
        return st

    monkeypatch.setattr(SpecController, "_state", hold_parked)
    monkeypatch.setattr(SpecController, "observe_block",
                        lambda self, guid, rounds: None)
    fallbacks = []
    fallback = RequestManager._fallback_decode

    def counted(self, ifm, reqs, *args, **kwargs):
        fallbacks.append([r.guid for r in reqs])
        return fallback(self, ifm, reqs, *args, **kwargs)

    monkeypatch.setattr(RequestManager, "_fallback_decode", counted)
    res = rm2.generate_spec_infer(
        llm, [ssm],
        generation_config=GenerationConfig(spec_draft_cost_ratio=0.1))
    assert rm2.scheduler_loop == f"python:spec_{engine}_fused"
    out = {tuple(r.input_tokens): r.output_tokens for r in res}
    assert out[tuple(prompts[0])] == incr[tuple(prompts[0])]
    assert out[tuple(prompts[1])][:12] == incr[tuple(prompts[1])]
    assert fallbacks == []


def test_adaptive_output_matches_static(tiny_spec_pair):
    """Flipping the controller on/off must never change tokens — only
    wall clock (the acceptance-criteria spec_matches_incr invariant)."""
    llm, ssm = tiny_spec_pair
    prompts = [[5, 9, 23, 44], [7, 3, 11]]

    def run(adaptive):
        rm = RequestManager()
        for p in prompts:
            rm.register_new_request(p, max_new_tokens=10)
        res = rm.generate_spec_infer(
            llm, [ssm], spec_depth=3,
            generation_config=GenerationConfig(adaptive_spec=adaptive))
        return {tuple(r.input_tokens): r.output_tokens for r in res}

    assert run(True) == run(False)


def test_c_host_generation_config_validation():
    """The ffsv spec-JSON boundary rejects out-of-range policy values,
    not just typo'd keys — a C host cannot silently run a degenerate
    controller (probe_every=0 would re-draft every tick, alpha>1 breaks
    the EWMA, inverted margins break the hysteresis)."""
    from flexflow_tpu.serve.capi_host import _parse_generation_config

    assert _parse_generation_config({}) is None
    gc = _parse_generation_config(
        {"generation_config": {"adaptive": True, "spec_depth": 3,
                               "fallback_margin": 0.9,
                               "recover_margin": 1.1}})
    assert gc.spec_depth == 3 and gc.adaptive_spec
    for bad in ({"adaptve": True},              # typo'd key
                {"probe_every": 0},
                {"ewma_alpha": 4},
                {"ewma_alpha": 0},
                {"min_spec_depth": 0},
                {"fallback_margin": -1},
                {"recover_margin": 0.5},        # < default fallback 0.95
                {"draft_cost_ratio": -0.1},
                {"spec_depth": "deep"}):
        with pytest.raises(ValueError):
            _parse_generation_config({"generation_config": bad})


def test_generation_config_depth_override(tiny_spec_pair):
    """generation_config.spec_depth overrides the spec_depth argument
    (the ffsv C-host contract: the JSON policy wins)."""
    llm, ssm = tiny_spec_pair
    seen = {}
    from flexflow_tpu.serve import request_manager as rmod

    orig = rmod.RequestManager._generate_spec_fused

    def spy(self, llm_, ssms_, loop, beam_width, spec_depth=None,
            generation_config=None):
        seen["depth"] = spec_depth
        return orig(self, llm_, ssms_, loop, beam_width,
                    spec_depth=spec_depth,
                    generation_config=generation_config)

    rmod.RequestManager._generate_spec_fused = spy
    try:
        rm = RequestManager()
        rm.register_new_request([5, 9], max_new_tokens=4)
        rm.generate_spec_infer(
            llm, [ssm], spec_depth=4,
            generation_config=GenerationConfig(spec_depth=2))
    finally:
        rmod.RequestManager._generate_spec_fused = orig
    assert seen["depth"] == 2
