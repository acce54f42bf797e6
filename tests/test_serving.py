"""Serving-stack tests: incremental decoding, continuous batching, and
speculative inference with tree verification.

Test strategy follows the reference CI matrix (reference
tests/inference/python_inference_tests.sh): (a) incremental decoding is
deterministic, (b) spec-infer output must token-match incremental decoding
(check_partial_token_match :29), (c) batching must not change results.
"""

import os
import types
import warnings

import jax
import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.ffconst import InferenceMode
from flexflow_tpu.models.llama import LLAMAConfig, create_llama_model
from flexflow_tpu.serve.request_manager import RequestManager

TINY = LLAMAConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                   num_hidden_layers=2, num_attention_heads=4,
                   num_key_value_heads=2, max_position_embeddings=128)


def make_model(mode=InferenceMode.INC_DECODING_MODE, seed=0, max_requests=4,
               max_seq=64, tp=1, beam=1):
    cfg = ff.FFConfig(max_requests_per_batch=max_requests,
                      max_sequence_length=max_seq, max_tokens_per_batch=16,
                      seed=seed, kv_cache_dtype="float32",
                      tensor_parallelism_degree=tp, max_beam_width=beam)
    model = ff.FFModel(cfg)
    create_llama_model(model, TINY, mode=mode)
    model.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    return model


@pytest.fixture(scope="module")
def models():
    """``make_model`` memoised for the tests that only serve from a model:
    a fresh RequestManager starts every slot from position 0, so the
    scenarios below share one build (and its compiled programs) a shape."""
    built = {}

    def get(**kw):
        key = tuple(sorted(kw.items()))
        if key not in built:
            built[key] = make_model(**kw)
        return built[key]

    return get


def _draft(models, engine, **kw):
    """The draft model ``generate_spec_infer`` sends to ``engine``: one
    compiled at beam width 2 for the beam engine (the route refuses a
    width the draft was not compiled with), at width 1 for the tree's."""
    return models(mode=InferenceMode.BEAM_SEARCH_MODE,
                  beam=2 if engine == "beam" else 1, **kw)


def _spec_infer(rm, engine, llm, ssm, spec_depth, monkeypatch):
    """One draft model through ``generate_spec_infer``: to the beam engine
    at the width 2 it was compiled with, to the fused tree engine at
    width 1, both through the one fused loop. The draft is priced at a
    tenth of the verifier, as a real one is: at the tiny pair's own ratio
    of 1 the controller parks every request before either engine runs a
    block."""
    from flexflow_tpu.serve import engine as engines
    from flexflow_tpu.serve.batch_config import GenerationConfig

    cls = {"beam": engines.BeamSpecEngine,
           "tree": engines.MultiSpecEngine}[engine]
    run_block, blocks = cls.run_block, []

    def counted(self, *args, **kwargs):
        blocks.append(type(self))
        return run_block(self, *args, **kwargs)

    monkeypatch.setattr(cls, "run_block", counted)
    out = rm.generate_spec_infer(
        llm, [ssm], spec_depth=spec_depth,
        generation_config=GenerationConfig(spec_draft_cost_ratio=0.1))
    assert rm.scheduler_loop == f"python:spec_{engine}_fused"
    assert blocks and set(blocks) == {cls}
    return out


SPEC_ENGINES = pytest.mark.parametrize("engine", ["beam", "tree"])


def test_incr_decoding_deterministic():
    model = make_model()
    rm = RequestManager()
    prompts = [[5, 9, 23, 44], [7, 3]]
    for p in prompts:
        rm.register_new_request(p, max_new_tokens=8)
    results = rm.generate_incr_decoding(model)
    assert len(results) == 2
    by_input = {tuple(r.input_tokens): r for r in results}
    for p in prompts:
        r = by_input[tuple(p)]
        assert len(r.output_tokens) == 8
        assert all(0 <= t < TINY.vocab_size for t in r.output_tokens)
    # decoding again from scratch gives identical output
    rm2 = RequestManager()
    model2 = make_model()
    for p in prompts:
        rm2.register_new_request(p, max_new_tokens=8)
    results2 = rm2.generate_incr_decoding(model2)
    for r2 in results2:
        assert by_input[tuple(r2.input_tokens)].output_tokens == r2.output_tokens


def test_continuous_batching_more_requests_than_slots():
    model = make_model(max_requests=2)
    rm = RequestManager()
    prompts = [[i + 1, i + 2, i + 3] for i in range(5)]
    for p in prompts:
        rm.register_new_request(p, max_new_tokens=4)
    results = rm.generate_incr_decoding(model)
    assert len(results) == 5
    # each request's result matches a solo run
    solo_model = make_model(max_requests=2)
    for p, r in zip(prompts, sorted(results, key=lambda r: r.guid)):
        rm_solo = RequestManager()
        rm_solo.register_new_request(p, max_new_tokens=4)
        solo = rm_solo.generate_incr_decoding(solo_model)[0]
        assert solo.output_tokens == r.output_tokens, p


def test_prefill_longer_than_chunk():
    model = make_model()
    rm = RequestManager()
    prompt = list(np.random.RandomState(0).randint(1, 100, size=37))
    rm.register_new_request([int(t) for t in prompt], max_new_tokens=4)
    (res,) = rm.generate_incr_decoding(model)
    assert len(res.output_tokens) == 4


def test_max_sequence_length_respected():
    model = make_model(max_seq=16)
    rm = RequestManager()
    rm.register_new_request([1, 2, 3], max_new_tokens=100)
    (res,) = rm.generate_incr_decoding(model)
    assert len(res.input_tokens) + len(res.output_tokens) <= 16


def test_verify_consistent_decode_width_matches_width1():
    """decode_width > 1 (verify-consistent decode: the pending token staged
    as node 0 of a width-W window, same program shapes as the spec verify
    pass — see FFConfig.decode_width) must produce the same tokens as the
    width-1 path, including requests that run into the cache end (the
    cramped single-step fallback)."""

    def run(width, max_new=20, max_seq=64):
        cfg = ff.FFConfig(max_requests_per_batch=4,
                          max_sequence_length=max_seq,
                          max_tokens_per_batch=16, seed=0,
                          kv_cache_dtype="float32", decode_width=width)
        model = ff.FFModel(cfg)
        create_llama_model(model, TINY, mode=InferenceMode.INC_DECODING_MODE)
        model.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
        rm = RequestManager()
        for p in [[5, 9, 23, 44], [7, 3], [1, 2, 3]]:
            rm.register_new_request(p, max_new_tokens=max_new)
        return {tuple(r.input_tokens): r.output_tokens
                for r in rm.generate_incr_decoding(model)}

    assert run(8) == run(1)
    # cramped: generation hits the cache end; the W-window path must hand
    # the tail to the single-step fallback and still match
    assert run(8, max_new=60, max_seq=40) == run(1, max_new=60, max_seq=40)


@pytest.mark.parametrize("case", ["kernel_on_and_no_engine",
                                  "config_wins_over_an_engine"])
def test_decode_width_without_and_against_an_engine(case, monkeypatch):
    """A model that no engine verifies decodes one token a row, with the
    Pallas kernel serving it too (where the width used to be the verify
    pass's 8 unasked); ``FFConfig.decode_width``, when set, is the width
    whatever an engine says."""
    from flexflow_tpu import kernels as ffk
    from flexflow_tpu.serve.inference_manager import InferenceManager

    monkeypatch.setenv("FF_PALLAS_INTERPRET", "1")
    asked = 4 if case == "config_wins_over_an_engine" else 0
    cfg = ff.FFConfig(max_requests_per_batch=2, max_sequence_length=128,
                      max_tokens_per_batch=16, seed=0, decode_width=asked)
    model = ff.FFModel(cfg)
    create_llama_model(model, TINY, mode=InferenceMode.TREE_VERIFY_MODE)
    model.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    assert ffk.use_pallas(cfg)
    ifm = InferenceManager(model)
    assert ifm.decode_width == (asked or 1)
    ifm.verified_at(8)
    assert ifm.decode_width == (asked or 8)


@pytest.mark.parametrize("engine,depth,width", [
    ("tree", 4, 8), ("tree", 8, 16), ("beam", 4, 16)])
def test_decode_width_is_the_verifying_engines(engine, depth, width,
                                               monkeypatch):
    """Served incrementally and then speculatively, one model ends up at
    its engine's verify width (the tree's nodes padded to sublanes: 16
    past a depth of 7, and for a beam of 2 at depth 4): the loop that fetches the
    engine tells the verifier's manager, the block built at one token a row
    is dropped, ``_fallback_decode`` (every request parks at the tiny
    pair's cost ratio of 1) runs at the engine's width, the span and the
    gauge say so, and the tokens are what they were."""
    from flexflow_tpu.serve import engine as engines
    from flexflow_tpu.telemetry import ServingTelemetry

    llm = make_model(InferenceMode.TREE_VERIFY_MODE, max_requests=2)
    ssm = make_model(InferenceMode.BEAM_SEARCH_MODE, max_requests=2,
                     beam=2 if engine == "beam" else 1)
    make, built = engines.make_decode_block, []

    def counted(model, dtype, steps, width=1):
        built.append(width)
        return make(model, dtype, steps, width=width)

    monkeypatch.setattr(engines, "make_decode_block", counted)
    cls = {"beam": engines.BeamSpecEngine,
           "tree": engines.MultiSpecEngine}[engine]
    monkeypatch.setattr(cls, "run_block", lambda *a, **k: pytest.fail(
        "a request drafted: the fallback was to decode every token"))

    def serve(loop, tel=None):
        rm = RequestManager(telemetry=tel)
        for p in [[5, 9, 23, 44], [7, 3, 11]]:
            rm.register_new_request(p, max_new_tokens=10)
        return {tuple(r.input_tokens): r.output_tokens for r in loop(rm)}

    incr = serve(lambda rm: rm.generate_incr_decoding(llm))
    ifm = llm._inference_manager
    assert ifm.decode_width == 1 and built == [1]
    tel = ServingTelemetry()
    spec = serve(lambda rm: rm.generate_spec_infer(
        llm, [ssm], spec_depth=depth), tel)
    assert getattr(llm, f"_{'beam' if engine == 'beam' else 'multi'}"
                   "_engine").tree_width == width
    assert ifm.decode_width == width and built == [1, width]
    assert spec == incr
    assert tel.registry.get("ffsv_decode_width").value == width
    blocks = [e for e in tel.tracer.events
              if e.get("name") == "decode_block"]
    assert blocks and {e["args"]["width"] for e in blocks} == {width}
    # from here on incremental decoding takes the verify pass's shapes too
    assert serve(lambda rm: rm.generate_incr_decoding(llm)) == incr
    assert built == [1, width]


@pytest.mark.parametrize("pallas", ["off", "on"])
def test_one_draft_takes_the_tree_engine_on_every_platform(pallas,
                                                           monkeypatch):
    """One draft at width 1 goes to MultiSpecEngine through the fused loop
    whether the Pallas kernels serve the model (as on the chip; here
    interpreted) or not: the engine the tests run by default is the one
    the chip runs, and it returns incremental decoding's tokens."""
    from flexflow_tpu import kernels as ffk
    from flexflow_tpu.serve.engine import MultiSpecEngine

    if pallas == "on":
        monkeypatch.setenv("FF_PALLAS_INTERPRET", "1")
    llm = make_model(InferenceMode.TREE_VERIFY_MODE, max_requests=2,
                     max_seq=128)
    ssm = make_model(InferenceMode.BEAM_SEARCH_MODE, max_requests=2,
                     max_seq=128)
    assert ffk.use_pallas(llm.config) == (pallas == "on")

    def serve(loop):
        rm = RequestManager()
        for p in [[5, 9, 23, 44], [7, 3, 11]]:
            rm.register_new_request(p, max_new_tokens=6)
        return rm, {tuple(r.input_tokens): r.output_tokens
                    for r in loop(rm)}

    rm, spec = serve(lambda rm: rm.generate_spec_infer(llm, [ssm],
                                                       spec_depth=3))
    assert rm.scheduler_loop == "python:spec_tree_fused"
    assert isinstance(llm._multi_engine, MultiSpecEngine)
    assert spec == serve(lambda rm: rm.generate_incr_decoding(llm))[1]


@SPEC_ENGINES
def test_spec_infer_matches_incr_decoding(models, engine, monkeypatch):
    """With the SSM = the LLM's own weights, speculation must accept nearly
    everything and the output must be token-identical to incremental
    decoding (the reference CI gate, python_inference_tests.sh:29)."""
    prompts = [[5, 9, 23, 44], [7, 3, 11]]
    incr_model = models(seed=0)
    rm = RequestManager()
    for p in prompts:
        rm.register_new_request(p, max_new_tokens=12)
    incr = {tuple(r.input_tokens): r.output_tokens
            for r in rm.generate_incr_decoding(incr_model)}

    llm = models(mode=InferenceMode.TREE_VERIFY_MODE, seed=0)
    ssm = _draft(models, engine, seed=0)
    rm2 = RequestManager()
    for p in prompts:
        rm2.register_new_request(p, max_new_tokens=12)
    spec = _spec_infer(rm2, engine, llm, ssm, 4, monkeypatch)
    assert len(spec) == 2
    for r in spec:
        assert incr[tuple(r.input_tokens)][:12] == r.output_tokens[:12]


@SPEC_ENGINES
def test_spec_infer_divergent_ssm_still_correct(models, engine,
                                                monkeypatch):
    """A different-weight SSM proposes mostly-wrong drafts; the verifier must
    still emit exactly the incremental-decoding tokens."""
    prompts = [[5, 9, 23, 44]]
    incr_model = models(seed=0)
    rm = RequestManager()
    for p in prompts:
        rm.register_new_request(p, max_new_tokens=10)
    incr = {tuple(r.input_tokens): r.output_tokens
            for r in rm.generate_incr_decoding(incr_model)}

    llm = models(mode=InferenceMode.TREE_VERIFY_MODE, seed=0)
    ssm = _draft(models, engine, seed=123)
    rm2 = RequestManager()
    for p in prompts:
        rm2.register_new_request(p, max_new_tokens=10)
    spec = _spec_infer(rm2, engine, llm, ssm, 4, monkeypatch)
    for r in spec:
        assert incr[tuple(r.input_tokens)][:10] == r.output_tokens[:10]


@pytest.mark.parametrize("tp", [2, 4])
def test_incr_decoding_tensor_parallel_matches(tp):
    """Serving under TP must be token-identical to single-device — the
    reference inference CI's TP-config matrix
    (tests/inference/python_test_configs/generate_configs.py)."""
    import jax
    if len(jax.devices()) < tp:
        pytest.skip("not enough devices")

    def gen(degree):
        m = make_model(max_requests=2, tp=degree)
        rm = RequestManager()
        rm.register_new_request([5, 9, 23, 44], max_new_tokens=8)
        rm.register_new_request([7, 3], max_new_tokens=8)
        return {tuple(r.input_tokens): r.output_tokens
                for r in rm.generate_incr_decoding(m)}

    assert gen(1) == gen(tp)


def test_spec_infer_tensor_parallel_matches():
    """Speculative serving under TP=2 token-matches incremental (the
    reference CI runs spec_infer across its TP configs too)."""
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("not enough devices")
    prompts = [[5, 9, 23, 44]]

    rm = RequestManager()
    for p in prompts:
        rm.register_new_request(p, max_new_tokens=10)
    incr = {tuple(r.input_tokens): r.output_tokens
            for r in rm.generate_incr_decoding(
                make_model(InferenceMode.INC_DECODING_MODE, max_requests=2,
                           tp=2))}

    llm = make_model(InferenceMode.TREE_VERIFY_MODE, max_requests=2, tp=2)
    ssm = make_model(InferenceMode.BEAM_SEARCH_MODE, max_requests=2, tp=2)
    rm2 = RequestManager()
    for p in prompts:
        rm2.register_new_request(p, max_new_tokens=10)
    spec = rm2.generate_spec_infer(llm, [ssm], spec_depth=4)
    for r in spec:
        assert incr[tuple(r.input_tokens)] == r.output_tokens


@SPEC_ENGINES
def test_spec_cramped_and_roomy_requests_coexist(models, engine,
                                                 monkeypatch):
    """A request whose prompt nearly fills the KV cache (no room to draft a
    full round) must finish via the single-step path while a roomy request
    speculates — without tripping the draft-cache assertions."""
    max_seq = 32
    depth = 4
    cramped_prompt = list(range(1, 28))       # room = 32-27 = 5 < a tree's 8
    roomy_prompt = [5, 9, 23]

    incr_model = models(seed=0, max_seq=max_seq)
    rm = RequestManager()
    rm.register_new_request(cramped_prompt, max_new_tokens=8)
    rm.register_new_request(roomy_prompt, max_new_tokens=12)
    incr = {tuple(r.input_tokens): r.output_tokens
            for r in rm.generate_incr_decoding(incr_model)}
    assert len(incr[tuple(cramped_prompt)]) == max_seq - len(cramped_prompt)

    llm = models(mode=InferenceMode.TREE_VERIFY_MODE, seed=0,
                 max_seq=max_seq)
    ssm = _draft(models, engine, seed=0, max_seq=max_seq)
    rm2 = RequestManager()
    rm2.register_new_request(cramped_prompt, max_new_tokens=8)
    rm2.register_new_request(roomy_prompt, max_new_tokens=12)
    spec = _spec_infer(rm2, engine, llm, ssm, depth, monkeypatch)
    assert len(spec) == 2
    for r in spec:
        assert incr[tuple(r.input_tokens)] == r.output_tokens


@SPEC_ENGINES
def test_spec_infer_eos_and_budget_respected(models, engine, monkeypatch):
    """EOS accepted mid-chunk must stop generation exactly there, and the
    output must never exceed max_new_tokens (matching incremental)."""
    incr_model = models(seed=0)
    rm = RequestManager()
    rm.register_new_request([5, 9, 23, 44], max_new_tokens=7)
    (incr,) = rm.generate_incr_decoding(incr_model)
    # pick an EOS id that actually appears in the incremental output
    eos = incr.output_tokens[3]
    stop_at = incr.output_tokens.index(eos) + 1

    llm = models(mode=InferenceMode.TREE_VERIFY_MODE, seed=0)
    ssm = _draft(models, engine, seed=0)
    rm2 = RequestManager(eos_token_id=eos)
    rm2.register_new_request([5, 9, 23, 44], max_new_tokens=7)
    (spec,) = _spec_infer(rm2, engine, llm, ssm, 4, monkeypatch)
    assert len(spec.output_tokens) == stop_at
    assert spec.output_tokens == incr.output_tokens[:stop_at]
    assert len(spec.output_tokens) <= 7


def test_spec_infer_multi_ssm_tree():
    """Two different SSMs -> a genuine token tree (shared-root chains) and a
    commit path; output must still match incremental decoding."""
    prompts = [[5, 9, 23, 44], [2, 8]]
    incr_model = make_model(seed=0)
    rm = RequestManager()
    for p in prompts:
        rm.register_new_request(p, max_new_tokens=10)
    incr = {tuple(r.input_tokens): r.output_tokens
            for r in rm.generate_incr_decoding(incr_model)}

    llm = make_model(mode=InferenceMode.TREE_VERIFY_MODE, seed=0)
    ssm1 = make_model(mode=InferenceMode.BEAM_SEARCH_MODE, seed=0)
    ssm2 = make_model(mode=InferenceMode.BEAM_SEARCH_MODE, seed=7)
    rm2 = RequestManager()
    for p in prompts:
        rm2.register_new_request(p, max_new_tokens=10)
    spec = rm2.generate_spec_infer(llm, [ssm1, ssm2], spec_depth=3)
    for r in spec:
        assert incr[tuple(r.input_tokens)][:10] == r.output_tokens[:10]


def test_spec_infer_multi_ssm_tree_near_limit():
    """Two SSMs near the sequence limit: each chain fits `room` but the
    MERGED tree (1 + 2*depth nodes) would stage KV past max_seq without the
    tree cap (ADVICE r1). ssm1 is divergent (fills the early tree indices),
    ssm2 shares the verifier's weights — so the chain the verifier accepts
    occupies the tree's TAIL, exactly the nodes that overflow the cache —
    and the output must still match incremental decoding."""
    max_seq = 32
    prompt = list(range(1, 26))                  # len 25, sp=24, cap=8 < 9
    incr_model = make_model(seed=0, max_seq=max_seq)
    rm = RequestManager()
    rm.register_new_request(prompt, max_new_tokens=20)
    (incr,) = rm.generate_incr_decoding(incr_model)
    assert len(incr.output_tokens) == max_seq - len(prompt)

    llm = make_model(mode=InferenceMode.TREE_VERIFY_MODE, seed=0,
                     max_seq=max_seq)
    ssm1 = make_model(mode=InferenceMode.BEAM_SEARCH_MODE, seed=3,
                      max_seq=max_seq)
    ssm2 = make_model(mode=InferenceMode.BEAM_SEARCH_MODE, seed=0,
                      max_seq=max_seq)
    rm2 = RequestManager()
    rm2.register_new_request(prompt, max_new_tokens=20)
    (spec,) = rm2.generate_spec_infer(llm, [ssm1, ssm2], spec_depth=4)
    assert spec.output_tokens == incr.output_tokens


def test_multi_ssm_spec_host_calls_bounded():
    """Multi-SSM tree speculation must be FUSED: the number of host->device
    dispatches for a whole generation must not scale with drafted tokens
    (the pre-fusion path paid one InferenceManager.step per drafted token
    per SSM per round and could never beat incremental decoding; what
    the reference CI speed gate compare_speed_spec_infer_incr_decoding,
    python_inference_tests.sh:57, asks is measured on the chip by the
    benchmark's speculation cell, ``opt-6.7b-spec.decode-heavy``)."""
    from flexflow_tpu.serve.engine import MultiSpecEngine
    from flexflow_tpu.serve.inference_manager import InferenceManager

    deep = LLAMAConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                       num_hidden_layers=4, num_attention_heads=4,
                       num_key_value_heads=2, max_position_embeddings=128)

    def build(mode, layers):
        cfg = ff.FFConfig(max_requests_per_batch=4, max_sequence_length=128,
                          max_tokens_per_batch=16, seed=3,
                          kv_cache_dtype="float32")
        m = ff.FFModel(cfg)
        mc = LLAMAConfig(**{**deep.__dict__, "num_hidden_layers": layers})
        create_llama_model(m, mc, mode=mode)
        m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
        return m

    llm = build(InferenceMode.TREE_VERIFY_MODE, 4)
    ssms = [build(InferenceMode.BEAM_SEARCH_MODE, 1) for _ in range(2)]

    calls = {"step": 0, "block": 0}
    orig_step = InferenceManager.step
    orig_block = MultiSpecEngine.run_block

    def step_counted(self, *a, **k):
        calls["step"] += 1
        return orig_step(self, *a, **k)

    def block_counted(self, *a, **k):
        calls["block"] += 1
        return orig_block(self, *a, **k)

    InferenceManager.step = step_counted
    MultiSpecEngine.run_block = block_counted
    try:
        from flexflow_tpu.serve.batch_config import GenerationConfig

        rm = RequestManager()
        for p in [[5, 9, 23, 44], [7, 3], [2, 8, 9], [11]]:
            rm.register_new_request(p, max_new_tokens=40)
        # static policy: this test pins the FUSED tree path's dispatch
        # economy; the adaptive controller legitimately reshapes the
        # profile (probe cycles re-prefill draft caches) and has its own
        # dispatch-count coverage in test_spec_controller.py
        res = rm.generate_spec_infer(
            llm, ssms, spec_depth=3,
            generation_config=GenerationConfig(adaptive_spec=False))
    finally:
        InferenceManager.step = orig_step
        MultiSpecEngine.run_block = orig_block
    assert sum(len(r.output_tokens) for r in res) >= 4 * 40
    # 160 generated tokens over ~45 tree rounds; the unfused path paid
    # ~rounds*(n_ssm*depth+1) ~ 300+ host dispatches. Fused: blocks of
    # spec_rounds_per_call (default 4) rounds + the prompts' prefill steps
    # (these random drafts accept nothing; an accepting draft is
    # test_fused_tree_high_acceptance_blocks_carry).
    assert calls["block"] <= 14, calls
    assert calls["step"] <= 16, calls


def test_beam_width2_spec_matches_incr_decoding():
    """Draft beam search at width 2 (reference BeamSearchBatchConfig /
    BeamTopK machinery): speculation output must stay token-identical to
    incremental decoding — beams only change WHICH tree is proposed, never
    what gets accepted."""
    prompts = [[5, 9, 23, 44], [7, 3, 11]]
    incr_model = make_model(seed=0)
    rm = RequestManager()
    for p in prompts:
        rm.register_new_request(p, max_new_tokens=12)
    incr = {tuple(r.input_tokens): r.output_tokens
            for r in rm.generate_incr_decoding(incr_model)}

    def make_beam_model(mode, width):
        cfg = ff.FFConfig(max_requests_per_batch=4, max_sequence_length=64,
                          max_tokens_per_batch=16, seed=0,
                          kv_cache_dtype="float32", max_beam_width=width)
        m = ff.FFModel(cfg)
        create_llama_model(m, TINY, mode=mode)
        m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
        return m

    llm = make_beam_model(InferenceMode.TREE_VERIFY_MODE, 1)
    ssm = make_beam_model(InferenceMode.BEAM_SEARCH_MODE, 2)
    # beam-mode graph ends in packed top-k, not argmax
    assert ssm.layers[-1].op_type == ff.OpType.CONCAT
    rm2 = RequestManager()
    for p in prompts:
        rm2.register_new_request(p, max_new_tokens=12)
    spec = rm2.generate_spec_infer(llm, [ssm], spec_depth=3, beam_width=2)
    assert len(spec) == 2
    for r in spec:
        assert incr[tuple(r.input_tokens)][:12] == r.output_tokens[:12]


def test_beam_draft_proposes_wider_trees():
    """At width 2 the draft must actually branch: the two surviving beam
    paths differ somewhere for at least one request (random-init models
    have near-uniform next-token distributions, so beams diverge)."""
    def make_beam_model(mode, width, seed=1):
        cfg = ff.FFConfig(max_requests_per_batch=4, max_sequence_length=64,
                          max_tokens_per_batch=16, seed=seed,
                          kv_cache_dtype="float32", max_beam_width=width)
        m = ff.FFModel(cfg)
        create_llama_model(m, TINY, mode=mode)
        m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
        return m

    llm = make_beam_model(InferenceMode.TREE_VERIFY_MODE, 1)
    ssm = make_beam_model(InferenceMode.BEAM_SEARCH_MODE, 2)
    seen = []
    orig = RequestManager._draft_beams

    def spy(self, ifm, ssm_idx, live, R, depth, width):
        out = orig(self, ifm, ssm_idx, live, R, depth, width)
        seen.append([dict(c) for c in out])
        return out

    RequestManager._draft_beams = spy
    try:
        rm = RequestManager()
        rm.register_new_request([5, 9, 23, 44], max_new_tokens=10)
        # drive the HOST beam path explicitly (the single-SSM W>1 default
        # is now the fused BeamSpecEngine, which never calls _draft_beams;
        # the host path remains the multi-SSM / inference_debugging route)
        rm._generate_spec_tree_host(llm, [ssm], spec_depth=3, beam_width=2)
    finally:
        RequestManager._draft_beams = orig
    assert seen, "beam draft never ran"
    assert any(c0 != c1 for c0, c1 in
               (tuple(cs) for cs in seen)), "beams never diverged"


def test_beam_width2_fused_matches_host_and_is_faster():
    """The fused beam engine (BeamSpecEngine: static node layout, on-device
    top-W + acceptance + KV commit) must produce token-identical output to
    the host-stepped beam path, and a timed pass must not be slower
    (reference BeamSearchBatchConfig, batch_config.h:125-126)."""
    import time

    prompts = [[5, 9, 23, 44], [7, 3, 11], [2, 8]]

    def make_pair(seed=0):
        def mk(mode, width):
            cfg = ff.FFConfig(max_requests_per_batch=4,
                              max_sequence_length=64,
                              max_tokens_per_batch=16, seed=seed,
                              kv_cache_dtype="float32",
                              max_beam_width=width)
            m = ff.FFModel(cfg)
            create_llama_model(m, TINY, mode=mode)
            m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
            return m

        return (mk(InferenceMode.TREE_VERIFY_MODE, 1),
                mk(InferenceMode.BEAM_SEARCH_MODE, 2))

    def run(path_fn):
        llm, ssm = make_pair()
        rm = RequestManager()
        for p in prompts:
            rm.register_new_request(p, max_new_tokens=16)
        t0 = time.perf_counter()
        res = path_fn(rm, llm, ssm)
        dt = time.perf_counter() - t0
        # second timed pass on warm jit caches (compile time excluded)
        rm2 = RequestManager()
        for p in prompts:
            rm2.register_new_request(p, max_new_tokens=16)
        t0 = time.perf_counter()
        path_fn(rm2, llm, ssm)
        dt = time.perf_counter() - t0
        return {tuple(r.input_tokens): r.output_tokens for r in res}, dt

    fused, dt_fused = run(
        lambda rm, llm, ssm: rm.generate_spec_infer(
            llm, [ssm], spec_depth=3, beam_width=2))
    host, dt_host = run(
        lambda rm, llm, ssm: rm._generate_spec_tree_host(
            llm, [ssm], spec_depth=3, beam_width=2))
    assert fused == host                    # token-identical, every request
    # fused = one device call per block vs ~depth host dispatches per
    # round. Token identity is the hard contract; wall-clock comparison
    # is informational by default (flaky on loaded CI machines) and only
    # enforced under FF_TPU_STRICT_TIMING=1 (ADVICE r3).
    if os.environ.get("FF_TPU_STRICT_TIMING") == "1":
        assert dt_fused <= dt_host * 1.1, (dt_fused, dt_host)
    elif dt_fused > dt_host * 1.1:
        warnings.warn(f"fused beam block slower than host loop: "
                      f"{dt_fused:.3f}s vs {dt_host:.3f}s (informational)")


def test_beam_width_mismatch_rejected():
    """A draft compiled at one width cannot be driven at another: the
    packed output layout is fixed at graph-build time."""
    llm = make_model(mode=InferenceMode.TREE_VERIFY_MODE)
    cfg = ff.FFConfig(max_requests_per_batch=4, max_sequence_length=64,
                      max_tokens_per_batch=16, seed=0,
                      kv_cache_dtype="float32", max_beam_width=2)
    ssm = ff.FFModel(cfg)
    create_llama_model(ssm, TINY, mode=InferenceMode.BEAM_SEARCH_MODE)
    ssm.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    rm = RequestManager()
    rm.register_new_request([5, 9], max_new_tokens=4)
    with pytest.raises(ValueError, match="max_beam_width"):
        rm.generate_spec_infer(llm, [ssm], spec_depth=3, beam_width=1)


def test_spec_infer_multi_ssm_draftable_window_terminates():
    """Regression: the host draftable gate must be at least as strict as
    MultiSpecEngine's live_mask (which reserves the sublane-PADDED verify
    width). A prompt landing in the gap between the unpadded and padded
    windows previously made the engine mask the request dead every round
    while the host kept rescheduling it — an infinite loop."""
    prompt = list(range(1, 19))      # len 18, max_seq 32: in the gap for
    depth = 4                        # B=2, d=4 (T=9 pads to 16)
    incr_model = make_model(seed=0, max_seq=32)
    rm = RequestManager()
    rm.register_new_request(prompt, max_new_tokens=10)
    incr = rm.generate_incr_decoding(incr_model)[0].output_tokens

    llm = make_model(mode=InferenceMode.TREE_VERIFY_MODE, seed=0, max_seq=32)
    ssm1 = make_model(mode=InferenceMode.BEAM_SEARCH_MODE, seed=0, max_seq=32)
    ssm2 = make_model(mode=InferenceMode.BEAM_SEARCH_MODE, seed=7, max_seq=32)
    rm2 = RequestManager()
    rm2.register_new_request(prompt, max_new_tokens=10)
    spec = rm2.generate_spec_infer(llm, [ssm1, ssm2], spec_depth=depth)
    assert spec[0].output_tokens == incr[:len(spec[0].output_tokens)]
    assert len(spec[0].output_tokens) == 10


@pytest.mark.parametrize("adaptive", [False, True],
                         ids=["static", "adaptive"])
@pytest.mark.parametrize("n_ssm", [1, 2, "beam"])
def test_fused_high_acceptance_blocks_carry(n_ssm, adaptive, monkeypatch):
    """A draft that accepts (the verifier's own weights) must keep the fused
    loop in whole blocks: the accepted block of a call's last round is
    handed to the next call, so once a prompt is in no draft ever runs the
    prefill program again and nothing but a prompt prefill holds a block to
    one round. With two drafts the first is divergent: it loses every
    round, and the carried block is what heals its cache. ``beam``: one
    draft at width 2 through the beam engine, whose draft cache holds its
    staged tree nodes after a round, which the carried block overwrites."""
    from flexflow_tpu.serve.batch_config import GenerationConfig
    from flexflow_tpu.serve.engine import BeamSpecEngine, MultiSpecEngine
    from flexflow_tpu.serve.inference_manager import InferenceManager

    depth, plen, new = 4, 11, 96
    prompts = [[(7 * i + 3 * j) % 120 + 1 for j in range(plen)]
               for i in range(5)]                   # 5 requests, 4 slots
    rm = RequestManager()
    for p in prompts:
        rm.register_new_request(p, max_new_tokens=new)
    incr = {tuple(r.input_tokens): r.output_tokens
            for r in rm.generate_incr_decoding(make_model(seed=0, max_seq=128))}

    llm = make_model(mode=InferenceMode.TREE_VERIFY_MODE, seed=0, max_seq=128)
    ssms = [make_model(mode=InferenceMode.BEAM_SEARCH_MODE, seed=s,
                       max_seq=128, beam=2 if n_ssm == "beam" else 1)
            for s in ([7, 0] if n_ssm == 2 else [0])]
    engine = BeamSpecEngine if n_ssm == "beam" else MultiSpecEngine
    per_call = llm.config.spec_rounds_per_call
    draft_ends, llm_prefills, blocks = [], [], []
    orig_step, orig_block = InferenceManager.step, engine.run_block

    def step_spy(self, meta, *a, **k):
        ends = (meta.start_pos + meta.num_tokens)[meta.active]
        if self.model in ssms:
            draft_ends.extend(int(e) for e in ends)
        elif meta.num_tokens.max() > 1:
            llm_prefills.append(1)
        return orig_step(self, meta, *a, **k)

    def block_spy(self, tks, nblk, base, active, n_rounds, *a, **k):
        out = orig_block(self, tks, nblk, base, active, n_rounds, *a, **k)
        blocks.append((int(n_rounds), int((out[1] >= 0).any(axis=0).sum()),
                       int(nblk[active].max())))
        return out

    monkeypatch.setattr(InferenceManager, "step", step_spy)
    monkeypatch.setattr(engine, "run_block", block_spy)
    rm2 = RequestManager()
    for p in prompts:
        rm2.register_new_request(p, max_new_tokens=new)
    spec = rm2.generate_spec_infer(
        llm, ssms, spec_depth=depth,
        # (a cheap draft, as a real one is: the controller's cost model
        # would park a draft the size of its verifier from token one)
        generation_config=GenerationConfig(adaptive_spec=adaptive,
                                           spec_draft_cost_ratio=0.1))
    assert len(spec) == len(prompts)
    for r in spec:
        assert r.output_tokens == incr[tuple(r.input_tokens)]
    # a draft's prefill program only ever fed prompt tokens (a chunk
    # leaves at least one token pending, so it ends inside the prompt)
    assert draft_ends and max(draft_ends) <= plen - 1, draft_ends
    # blocks entered with what the last one accepted, not one token
    assert max(nb for _, _, nb in blocks) == depth + 1
    # only a round with a prompt prefill asks for a single round
    asked = [a for a, _, _ in blocks]
    assert set(asked) <= {1, per_call}, asked
    assert asked.count(1) <= len(llm_prefills), (asked, llm_prefills)
    rounds_run = sum(ran for _, ran, _ in blocks)
    assert 3 * len(blocks) <= rounds_run, blocks


def test_multi_engine_carried_block_equals_prefilled_gap():
    """run_block entered with a carried block (nblk 2..depth+1, beside rows
    with nblk 1) returns the tokens and leaves the caches that feeding the
    gap through the drafts' prefill program and entering with the pending
    token alone gives from the same state."""
    from flexflow_tpu.serve.engine import MultiSpecEngine
    from flexflow_tpu.serve.inference_manager import InferenceManager

    depth, R = 4, 4
    llm = make_model(mode=InferenceMode.TREE_VERIFY_MODE, seed=0)
    ssms = [make_model(mode=InferenceMode.BEAM_SEARCH_MODE, seed=s)
            for s in (7, 0)]
    models = [llm] + ssms
    ifms = [InferenceManager(m) for m in models]
    eng = MultiSpecEngine(llm, ssms, depth, max_rounds=4)
    seqs = [[(5 * r + 3 * j) % 120 + 1 for j in range(n)]
            for r, n in enumerate((9, 12, 7, 10))]
    owed = [1, depth + 1, 2, 3]          # tokens each row's drafts lack
    act = np.ones((R,), bool)
    remaining = np.full((R,), 30, np.int32)

    def prefill(ifm, upto):
        rows = [(r, seqs[r][:upto[r]], 0) for r in range(R)]
        ifm.step(RequestManager._meta_from_rows(R, 16, rows),
                 want_output=False)

    def snapshot():
        return [jax.tree.map(np.asarray, m.op_state["kv_cache"])
                for m in models]

    def run(carried):
        # verifier: all but the pending token; drafts: ``owed`` behind
        prefill(ifms[0], [len(s) - 1 for s in seqs])
        for ifm in ifms[1:]:
            prefill(ifm, [len(s) - o for s, o in zip(seqs, owed)])
        if carried:
            nblk = np.array(owed, np.int32)
        else:
            for ifm in ifms[1:]:
                rows = [(r, seqs[r][len(seqs[r]) - o:-1], len(seqs[r]) - o)
                        for r, o in enumerate(owed) if o > 1]
                ifm.step(RequestManager._meta_from_rows(R, 16, rows),
                         want_output=False)
            nblk = np.ones((R,), np.int32)
        tks = np.zeros((R, depth + 1), np.int32)
        for r, n in enumerate(nblk):
            tks[r, :n] = seqs[r][len(seqs[r]) - n:]
        base = np.array([len(s) for s in seqs], np.int32) - nblk
        toks, n_acc, _ = eng.run_block(tks, nblk, base, act, 3, remaining)
        return toks[:, :3].copy(), n_acc[:, :3].copy(), snapshot()

    toks_a, n_acc_a, caches_a = run(carried=True)
    toks_b, n_acc_b, caches_b = run(carried=False)
    np.testing.assert_array_equal(n_acc_a, n_acc_b)
    assert (n_acc_a >= 0).all() and n_acc_a.max() == depth
    keep = np.arange(depth + 1)[None, None, :] < n_acc_a[:, :, None]
    keep[:, :, depth] = True                        # the bonus token
    np.testing.assert_array_equal(toks_a[keep], toks_b[keep])
    # caches agree wherever they are committed: the verifier's through the
    # last round's root, the drafts' through the last round's catch-up
    final = np.array([len(s) for s in seqs]) + (n_acc_a + 1).sum(axis=1) - 1
    last_root = final - (n_acc_a[:, -1] + 1)
    for ca, cb, upto in zip(caches_a, caches_b,
                            [final] + [last_root + 1] * len(ssms)):
        for name in ("k", "v"):
            for r in range(R):
                np.testing.assert_allclose(
                    ca[name][:, r, :, :upto[r]], cb[name][:, r, :, :upto[r]],
                    rtol=1e-5, atol=1e-5)


def test_long_context_serving():
    """Long-context serving: a 1,500-token prompt in a 2,048-slot KV cache
    must prefill in chunks and decode correctly (long context is
    first-class — the cache/streaming design must not assume short S)."""
    cfg = ff.FFConfig(max_requests_per_batch=2, max_sequence_length=2048,
                      max_tokens_per_batch=256, seed=0,
                      kv_cache_dtype="float32")
    m = ff.FFModel(cfg)
    create_llama_model(m, TINY, mode=InferenceMode.INC_DECODING_MODE)
    m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    rng = np.random.RandomState(0)
    long_prompt = [int(t) for t in rng.randint(1, 100, size=1500)]
    short_prompt = [5, 9, 23]
    rm = RequestManager()
    rm.register_new_request(long_prompt, max_new_tokens=6)
    rm.register_new_request(short_prompt, max_new_tokens=6)
    res = {tuple(r.input_tokens): r.output_tokens
           for r in rm.generate_incr_decoding(m)}
    assert len(res[tuple(long_prompt)]) == 6
    # the short request must be unaffected by sharing a batch with the
    # long one: compare against a solo run
    rm2 = RequestManager()
    rm2.register_new_request(short_prompt, max_new_tokens=6)
    m2 = ff.FFModel(cfg)
    create_llama_model(m2, TINY, mode=InferenceMode.INC_DECODING_MODE)
    m2.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    solo = rm2.generate_incr_decoding(m2)[0].output_tokens
    assert res[tuple(short_prompt)] == solo


# ---------------------------------------------------------------------------
# The incremental loop's bookkeeping against a rule, with no model: slots,
# blocks, EOS and budgets (what every default user's generate call runs)
# ---------------------------------------------------------------------------
_RULE_EOS = 13


def _rule_next(last, pos):
    return (last + pos) % 50 + 1


def _closed_form(prompt, max_new, max_seq, eos=_RULE_EOS):
    """What one request generates alone under ``_rule_next``."""
    toks = list(prompt)
    while len(toks) - len(prompt) < max_new and len(toks) < max_seq:
        toks.append(_rule_next(toks[-1], len(toks) - 1))
        if toks[-1] == eos:
            break
    return toks[len(prompt):]


class _RuleIFM:
    """Stands in for InferenceManager: the next token is ``_rule_next`` of
    the last token and its position. Records every call, and in ``order``
    the launches and reads as they came (``("step", k)``, ``("launch", i)``,
    ``("read", i)``); ``on_decode`` runs at the start of each decode block
    with the call's index, ``on_read`` before its read-back. A prefill step
    counts in the round of the block launched after it: a lead step, sent
    between a block's launch and its read, in the next. ``costs``: the
    loop's ``step_costs``, given and not timed (None: the loop times the
    fake's calls itself, and has no estimate before its third sample)."""

    decode_width = 1                # what the loop stamps its blocks' spans

    def __init__(self, on_decode=None, costs=None, on_read=None):
        self.prefills = []          # BatchMeta of every prefill step
        self.decodes = []           # (tok, pos, act, block) of every block
        self.rounds = [0]           # prefill steps before each decode block
        self.order = []
        self.on_decode = on_decode
        self.on_read = on_read
        self.model = types.SimpleNamespace(op_state=None)   # none to fence
        if costs is not None:
            self.step_costs = costs

    def step(self, meta, want_output=True, tel=None):
        assert not want_output
        self.order.append(("step", len(self.prefills)))
        self.prefills.append(meta)
        self.rounds[-1] += 1

    def launch_decode_block(self, tok, pos, act, block, tel=None, rnd=None):
        if rnd is not None:         # telemetry on: the round's last step
            rnd.settle()
        if self.on_decode is not None:
            self.on_decode(len(self.decodes))
        self.order.append(("launch", len(self.decodes)))
        self.decodes.append((tok.copy(), pos.copy(), act.copy(), block))
        self.rounds.append(0)
        out = np.zeros((tok.shape[0], block), np.int32)
        cur, p = tok.copy(), pos.copy()
        for j in range(block):
            cur = _rule_next(cur, p)
            p = p + 1
            out[:, j] = np.where(act, cur, 0)
        return out

    def read_decode_block(self, launched, tel=None):
        if self.on_read is not None:
            self.on_read(len(self.decodes) - 1)
        self.order.append(("read", len(self.decodes) - 1))
        return launched

    def lead_steps(self):
        """The blocks (by index) that had a prefill step launched between
        their launch and their read, and never more than one."""
        ahead = []
        for i, (what, k) in enumerate(self.order):
            if what == "launch":
                behind = self.order[i + 1:self.order.index(("read", k))]
                assert all(w == "step" for w, _ in behind) and len(behind) < 2
                if behind:
                    ahead.append(k)
        return ahead


def _rule_model(cfg, ifm):
    class Model:
        config = cfg
        _inference_manager = ifm

    return Model()


_RULE_PROMPTS = [[3, 4, 5], [10], [7, 8], [1, 2, 3, 4, 5, 6], [9, 9]]
# a queue for four slots: prompts of 6-14 chunks of 4 (16 tokens a step)
_RULE_QUEUE = [[(7 * i + j) % 50 + 1 for j in range(n)]
               for i, n in enumerate([24, 56, 33, 41, 25, 50, 37, 29])]


def _allowing(steps, block):
    """Costs under which a decode block of ``block`` pays for ``steps``
    prefill steps."""
    from flexflow_tpu.serve.step_costs import GivenCosts

    return GivenCosts(1.0, (steps + 0.5) / block)


@pytest.mark.parametrize("slots,block,steps", [
    (slots, block, None) for slots in (1, 2, 3) for block in (1, 4, 8)
] + [(4, 4, 1), (4, 4, 2), (4, 4, 5), (4, 2, 3), (4, 1, 16), (6, 4, 1)])
def test_incr_loop_matches_closed_form(slots, block, steps):
    """Five requests through 1-3 slots in blocks of 1-8 steps: each gets
    exactly what it would generate alone (EOS inside a block, a budget
    that ends mid-block, rows refilled from the queue, one-token prompts).
    ``steps``: a queue of eight long prompts through four slots where a
    decode block pays for that many prefill steps a round, times everyone
    resident over the rows decoding (six slots: up to five filling beside
    one); whatever the rounds held, every request's tokens are its own."""
    queue = _RULE_PROMPTS if steps is None else _RULE_QUEUE
    max_seq = 24 if steps is None else 80
    cfg = ff.FFConfig(max_requests_per_batch=slots,
                      max_sequence_length=max_seq,
                      max_tokens_per_batch=16, decode_block_steps=block)
    rm = RequestManager(eos_token_id=_RULE_EOS)
    for i, pr in enumerate(queue):
        rm.register_new_request(pr, max_new_tokens=6 + i)
    costs, asked = None, []
    if steps is not None:
        costs = _allowing(steps, block)
        allowance = costs.allowance

        def asking(b, decoding, filling):
            asked.append(types.SimpleNamespace(
                round=len(ifm.decodes), block=b, decoding=decoding,
                filling=filling, allowed=allowance(b, decoding, filling)))
            return asked[-1].allowed

        costs.allowance = asking
    ifm = _RuleIFM(costs=costs)
    res = rm.generate_incr_decoding(_rule_model(cfg, ifm))
    assert rm.scheduler_loop == "python"
    got = {tuple(r.input_tokens): r.output_tokens for r in res}
    want = {tuple(pr): _closed_form(pr, 6 + i, max_seq)
            for i, pr in enumerate(queue)}
    assert got == want
    # the scenarios the docstring names are in this workload
    assert any(out[-1] == _RULE_EOS and len(out) < 6 + i
               for i, out in enumerate(want.values()))
    assert any(len(out) % 4 for out in want.values())
    assert all(r.status == "ok" for r in res)
    if steps is None:
        assert max(int(act.sum()) for _, _, act, _ in ifm.decodes) == slots
        return
    # the first round has nothing decoding and prefills until a request
    # has caught up; a round that begins with a row decoding asks what it
    # may take, and keeps to it
    assert ifm.rounds[0] == 6 and asked[0].round == 1
    assert all(ifm.rounds[a.round] <= a.allowed for a in asked)
    # both counts are read off the slots: the rest are empty or finished
    assert all(a.decoding >= 1 and a.filling >= 0
               and a.decoding + a.filling <= slots for a in asked)
    worth = (steps + 0.5) / block       # of one decode step, in prefill steps
    assert all(a.allowed == max(1, int(
        a.block * worth * (a.decoding + a.filling) / a.decoding))
        for a in asked)
    # a full batch: what the block pays for, to the letter
    assert max(a.allowed for a in asked if not a.filling) == steps
    # a request filling beside the rows decoding: more, and taken (with one
    # step a block too)
    most = max(asked, key=lambda a: a.filling / a.decoding)
    assert most.filling / most.decoding == (5 if slots == 6 else 3)
    assert max(a.allowed for a in asked) > steps
    assert steps == 16 or any(      # sixteen: more than the queue asks for
        ifm.rounds[a.round] > max(1, int(a.block * worth)) for a in asked)


def test_incr_loop_fills_its_batch_sooner_with_several_steps_a_round():
    """The same queue, a block that pays for one step against five: the
    four slots all decode after fewer rounds, no token differs,
    ``ffsv_round_prefill_steps`` counts the rounds that took more than one
    step, ``ffsv_round_prefill_allowance`` what each round that began
    with a row decoding was allowed and ``ffsv_round_prefill_weight`` what
    it weighed the block by. Telemetry waits for each step's output
    (the fake has none) and changes nothing of the schedule."""
    from flexflow_tpu.serve.step_costs import GivenCosts
    from flexflow_tpu.telemetry import disable_telemetry, enable_telemetry

    cfg = ff.FFConfig(max_requests_per_batch=4, max_sequence_length=80,
                      max_tokens_per_batch=16, decode_block_steps=4)

    def run(costs):
        rm = RequestManager(eos_token_id=_RULE_EOS)
        for pr in _RULE_QUEUE:
            rm.register_new_request(pr, max_new_tokens=24)
        ifm = _RuleIFM(costs=costs)
        res = rm.generate_incr_decoding(_rule_model(cfg, ifm))
        full = [int(act.sum()) for _, _, act, _ in ifm.decodes].index(4)
        return ({tuple(r.input_tokens): r.output_tokens for r in res},
                full, ifm.rounds)

    # 0.4 of a step: one, even with four resident to one row decoding
    one, full_one, rounds_one = run(GivenCosts(1.0, 0.1))
    tel = enable_telemetry()
    try:
        before = tel.registry.snapshot()
        five, full_five, rounds_five = run(_allowing(5, 4))
        hist = tel.registry.get("ffsv_round_prefill_steps")
        # the benchmark's readers; nothing from a program without them
        from benchmark.layer_metrics import (
            prefill_allowance_per_round as allowance,
            prefill_steps_per_round as metric)

        ctx = {"tel": {"before": before, "after": tel.registry.snapshot()}}
        assert metric.read(ctx) == sum(rounds_five) / (len(rounds_five) - 1)
        assert metric.read({"tel": {"before": {}, "after": {}}}) is None
        assert metric.read({"tel": None}) is None
        assert hist.count == len(rounds_five) - 1   # one a decode block
        assert hist.sum == sum(rounds_five)
        # observations above 1: the rounds the rule engaged in
        assert sum(hist._counts[2:]) == sum(n > 1 for n in rounds_five) >= 3
        # what the rule allowed: one observation for each round that began
        # with a row decoding (all but the first), and no round took more
        allowed = tel.registry.get("ffsv_round_prefill_allowance")
        assert allowed.count == len(rounds_five) - 2 == len(allowed._samples)
        assert all(took <= may for took, may
                   in zip(rounds_five[1:], allowed._samples))
        # 5.5 steps a block, three requests filling beside one row
        assert max(allowed._samples) == 22 > min(allowed._samples) == 5
        # the weight the block was given, once for each such round: four
        # residents to one row decoding at most, one at a full batch
        weight = tel.registry.get("ffsv_round_prefill_weight")
        assert weight.count == allowed.count == len(weight._samples)
        assert max(weight._samples) == 4 > min(weight._samples) == 1
        assert [int(5.5 * w) for w in weight._samples] == allowed._samples
        assert allowance.read(ctx) == allowed.sum / allowed.count
        assert allowance.read({"tel": {"before": {}, "after": {}}}) is None
        assert allowance.read({"tel": None}) is None
    finally:
        disable_telemetry()
    assert five == one
    assert full_five < full_one, (full_five, full_one)
    # three filling beside one decoding: more than the block's five, and taken
    assert max(rounds_five[1:]) == 8 and max(rounds_one[1:]) <= 2
    # telemetry off: the same
    assert run(_allowing(5, 4))[1:] == (full_five, rounds_five)


@pytest.mark.parametrize("decoding,filling,weight", [
    (1, 0, 1),                              # a full batch: PR 32's bound
    (12, 4, 4 / 3), (8, 8, 2), (18, 14, 32 / 18),   # PR 36's rule: weight 1
    (4, 12, 4), (3, 13, 16 / 3), (1, 3, 4), (2, 3, 2.5)])
def test_step_costs_are_medians_of_a_few_timed_rounds(decoding, filling,
                                                      weight):
    """No estimate, and so one step a round, until three samples of each
    program are in; then as many steps as together cost no more than the
    block, times everyone resident over the rows decoding, from the
    medians of the last five samples: a stop of the machine inside one
    sample moves nothing. Every prefilling round is timed until both
    estimates stand, then one in eight."""
    from flexflow_tpu.serve.step_costs import StepCosts

    costs = StepCosts()
    assert costs.weight(decoding, filling) == pytest.approx(weight)

    def allowance(block):
        return costs.allowance(block, decoding, filling)

    assert allowance(16) == 1
    for i in range(3):
        assert costs.due()
        costs.note_prefill(2 * 0.0226, 2)     # two steps, timed together
        assert allowance(16) == 1
        costs.note_decode(16 * 0.0106, 16)
    assert allowance(16) == int(7.5044 * weight)    # 169.6 / 22.6 ms
    assert allowance(4) == max(1, int(1.8761 * weight))
    assert allowance(1) == max(1, int(0.4690 * weight))
    assert [costs.due() for _ in range(16)].count(True) == 2
    costs.note_prefill(9.0, 1)                  # the machine stopped
    costs.note_decode(16 * 0.6, 16)
    assert allowance(16) == int(7.5044 * weight)
    for _ in range(3):                          # the model got slower
        costs.note_prefill(0.03, 1)
    assert allowance(16) == int(5.6533 * weight)


def test_step_costs_allow_no_less_than_the_larger_of_rule():
    """Over every (decoding, filling) of 32 slots and quotients on both
    sides of a step: the sum of the two terms is never below the larger of
    them (PR 36's rule), and at a full batch it is the block's worth to
    the letter (PR 32's bound)."""
    from flexflow_tpu.serve.step_costs import GivenCosts

    more = 0
    for q in (0.4, 1.0, 3.08, 3.58, 7.5):
        costs = GivenCosts(1.0, q / 16)
        for decoding in range(1, 33):
            assert costs.allowance(16, decoding, 0) == max(1, int(q))
            for filling in range(33 - decoding):
                new = costs.allowance(16, decoding, filling)
                old = max(1, int(q * max(1.0, filling / decoding)))
                assert new >= old, (q, decoding, filling)
                more += new > old
    assert more
    # K-EXAONE's queue at PR 47, 18 rows decoding and 14 residents
    # filling: six steps where the quotient alone (3.42) gave three
    assert GivenCosts(22.5, 4.81).allowance(16, 18, 14) == 6


def _serve_on_toy_device(monkeypatch, cfg, queue, new_tokens, decode_s=0.8,
                         stop_at=None, traced=False):
    """``queue`` through the incremental loop on a device that runs what
    it is sent in order: a prefill step 1.0 s, a decode step ``decode_s``,
    dispatch free; a fence, a readback or a wait on a step's output waits
    for the device. The loop reads the toy's clock and times the two
    programs itself. ``stop_at``: the machine stops for 100 s inside that
    prefill step. Returns the results, the fake manager, and the requests
    still queued at each decode block."""
    from flexflow_tpu.serve import request_manager as RM
    from flexflow_tpu.telemetry import disable_telemetry, enable_telemetry

    host, device = [0.0], [0.0]     # the clock; when the device is free

    def wait(_state=None):
        host[0] = max(host[0], device[0])

    class Device(_RuleIFM):
        def step(self, meta, want_output=True, tel=None):
            super().step(meta, want_output, tel)
            stop = 100.0 * (len(self.prefills) == stop_at)
            device[0] = max(host[0], device[0]) + 1.0 + stop
            # the step's output: ready when the device has run it
            return types.SimpleNamespace(
                block_until_ready=lambda end=device[0]: host.__setitem__(
                    0, max(host[0], end)))

        def launch_decode_block(self, tok, pos, act, block, tel=None,
                                rnd=None):
            device[0] = max(host[0], device[0]) + decode_s * block
            return (super().launch_decode_block(tok, pos, act, block, tel,
                                                rnd), device[0])

        def read_decode_block(self, launched, tel=None):
            out, end = launched
            host[0] = max(host[0], end)     # the block's end, not what
            return super().read_decode_block(out, tel)  # is queued behind

    monkeypatch.setattr(RM, "device_fence", wait)
    monkeypatch.setattr(RM, "time", types.SimpleNamespace(
        perf_counter=lambda: host[0]))
    rm = RequestManager(eos_token_id=_RULE_EOS)
    for pr in queue:
        rm.register_new_request(pr, max_new_tokens=new_tokens)
    queued = []
    ifm = Device(on_decode=lambda i: queued.append(len(rm.pending)))
    if traced:
        enable_telemetry()
    try:
        res = rm.generate_incr_decoding(_rule_model(cfg, ifm))
    finally:
        disable_telemetry()
    return res, ifm, queued


@pytest.mark.parametrize("stop_at", [None, 7])
def test_incr_loop_times_the_same_rounds_traced_and_untraced(monkeypatch,
                                                             stop_at):
    """The loop's own timing on a device that runs what it is sent in order
    (a prefill step 1.0 s, a decode step 0.8 s, dispatch free; a fence, a
    readback or a wait on a step's output waits for the device): a timed
    round waits for each of its steps before it stages the next, with a
    fence of the state when telemetry is off and a wait on the step's
    output when it is on, and every other round's steps queue behind each
    other either way, so the estimates, and with them the steps of every
    round, are the same traced and untraced. ``stop_at``: the machine stops
    for 100 s inside that prefill step; nothing changes."""
    cfg = ff.FFConfig(max_requests_per_batch=4, max_sequence_length=80,
                      max_tokens_per_batch=16, decode_block_steps=4)

    def run(traced):
        res, ifm, _ = _serve_on_toy_device(monkeypatch, cfg, _RULE_QUEUE, 24,
                                           stop_at=stop_at, traced=traced)
        return ({tuple(r.input_tokens): r.output_tokens for r in res},
                ifm.rounds, list(ifm.step_costs._prefill),
                list(ifm.step_costs._decode))

    plain, traced = run(False), run(True)
    assert plain == traced
    # the first round has nothing decoding; then one step a round until the
    # third sample of each program, then the three that a block of 4 steps
    # (3.2 s) pays for, and more where requests fill beside the rows
    # decoding (late in the run: four a round)
    assert plain[1][:4] == [6, 1, 1, 3], plain[1]
    assert max(plain[1][1:]) == 4
    assert sorted(plain[2])[:-1] == [1.0] * (len(plain[2]) - 1)
    assert max(plain[2]) == (1.0 if stop_at is None else 101.0)
    assert {round(d, 6) for d in plain[3]} == {0.8}


def _asked_costs(every, order):
    """Costs under which a block of 4 pays for one step, and a ``due``
    that says yes to every ``every``-th round that asks (0: to none) and
    keeps each answer beside the last entry of ``order`` (the fake's) when
    it was asked."""
    from flexflow_tpu.serve.step_costs import GivenCosts

    class Asked(GivenCosts):
        def due(self):
            yes = bool(every) and (len(self.asked) + 1) % every == 0
            self.asked.append((yes, order[-1] if order else None))
            return yes

    costs = Asked(1.0, 1.5 / 4)
    costs.asked = []
    return costs


def _filling(rm):
    """Whether a request in a slot is short of its prompt (the loop's own
    marks, read off the manager from a hook of the fake)."""
    return any(r.slot >= 0 and not r.finished
               and r.cache_depth < len(r.tokens) - 1
               for r in rm.inflight.values())


@pytest.mark.parametrize("every", [0, 1, 3])
def test_incr_loop_queues_a_lead_step_behind_a_block(monkeypatch, every):
    """ISSUE 61: between a decode block's launch and its read the loop
    launches the next round's first prefill step, one and no more, exactly
    when a request in a slot is still filling and the coming round is not
    one StepCosts times (a timed round fences each of its steps, and
    starts on an idle device); never when everyone resident is decoding.
    Whatever the rounds held, every request's tokens are its own."""
    from flexflow_tpu.serve import request_manager as RM

    cfg = ff.FFConfig(max_requests_per_batch=4, max_sequence_length=80,
                      max_tokens_per_batch=16, decode_block_steps=4)
    rm = RequestManager(eos_token_id=_RULE_EOS)
    queue = [[(11 * i + j) % 50 + 1 for j in range(n + i // 8)]
             for i, n in enumerate(3 * [24, 56, 33, 41, 25, 50, 37, 29])]
    for i, pr in enumerate(queue):
        rm.register_new_request(pr, max_new_tokens=6 + i % 9)
    filling = []
    ifm = _RuleIFM(on_decode=lambda i: filling.append(_filling(rm)))
    costs = ifm.step_costs = _asked_costs(every, ifm.order)
    monkeypatch.setattr(RM, "device_fence",
                        lambda state: ifm.order.append(("fence", None)))
    res = rm.generate_incr_decoding(_rule_model(cfg, ifm))
    assert ({tuple(r.input_tokens): r.output_tokens for r in res}
            == {tuple(pr): _closed_form(pr, 6 + i % 9, 80)
                for i, pr in enumerate(queue)})
    lead = ifm.lead_steps()
    assert any(filling) and not all(filling)
    assert all(filling[k] for k in lead)
    if every == 1:
        assert not lead
    # a block with someone filling: a lead step behind it, or else the
    # round after it is timed, each of its steps fenced
    for k in (k for k, f in enumerate(filling) if f):
        seg = ifm.order[ifm.order.index(("launch", k)) + 1:]
        if ("launch", k + 1) in seg:
            seg = seg[:seg.index(("launch", k + 1))]
        steps = sum(what == "step" for what, _ in seg)
        fences = sum(what == "fence" for what, _ in seg)
        assert steps >= 1
        assert fences == (0 if k in lead else steps), (k, seg)
    # one question a round that prefills, put where its lead step would be
    # launched: a yes there, and the block has none behind it
    assert len(costs.asked) == sum(n > 0 for n in ifm.rounds)
    assert ([k for k, f in enumerate(filling) if f and k not in lead]
            == [at[1] for yes, at in costs.asked
                if yes and at and at[0] == "launch"])
    assert len(lead) == {0: sum(filling), 1: 0, 3: 11}[every] < len(filling)


@pytest.mark.parametrize("how", ["cancel", "expire"])
@pytest.mark.parametrize("victim", ["filling", "decoding"])
def test_incr_loop_reaps_a_request_between_a_lead_step_and_the_read(
        victim, how):
    """A request cancelled, or out of time, after a lead step was launched
    and before its block is read, whether the step filled it or the block
    carried it: it resolves so, its slot is refilled, and every other
    request's tokens are its own."""
    cfg = ff.FFConfig(max_requests_per_batch=4, max_sequence_length=80,
                      max_tokens_per_batch=16, decode_block_steps=4)
    rm = RequestManager(eos_token_id=_RULE_EOS)
    for i, pr in enumerate(_RULE_QUEUE):
        rm.register_new_request(pr, max_new_tokens=6 + i)
    hit = []

    def reap(k):
        if hit or ifm.order[-1][0] != "step":
            return
        meta, (_, _, act, _) = ifm.prefills[-1], ifm.decodes[k]
        slots = (set(meta.slots[meta.active].tolist())
                 if victim == "filling" else set(np.flatnonzero(act)))
        req = min((r for r in rm.inflight.values() if r.slot in slots),
                  key=lambda r: r.guid)
        hit.append(req.guid)
        if how == "cancel":
            assert rm.cancel(req.guid)
        else:
            req.deadline_s = 1e-9           # long past

    ifm = _RuleIFM(costs=_allowing(2, 4), on_read=reap)
    res = {r.guid: r for r in rm.generate_incr_decoding(_rule_model(cfg, ifm))}
    assert len(hit) == 1 and len(res) == len(_RULE_QUEUE)
    gone = res.pop(hit[0])
    assert gone.status == ("cancelled" if how == "cancel" else "timed_out")
    full = _closed_form(gone.input_tokens, 13, 80)
    assert gone.output_tokens == full[:len(gone.output_tokens)] != full
    assert all(r.status == "ok" for r in res.values())
    assert ({tuple(r.input_tokens): r.output_tokens for r in res.values()}
            == {tuple(pr): _closed_form(pr, 6 + i, 80)
                for i, pr in enumerate(_RULE_QUEUE)
                if pr != gone.input_tokens})
    assert ifm.lead_steps()


@pytest.mark.parametrize("prompt,new_tokens,slots,holds", [
    (20, 12, 4, 1.0), (20, 32, 4, 1.0),
    (40, 8, 8, 0.5 ** 0.5), (48, 8, 8, 0.5 ** 0.5)])
def test_incr_loop_keeps_its_rows_under_a_cheaper_decode_step(
        monkeypatch, prompt, new_tokens, slots, holds):
    """PR 38's fault, on the toy device: a queue that keeps every slot
    resident, served at a decode step of 0.8 s and of 0.4 s. The block's
    worth in prefill steps halves (3.2 -> 1.6, floored 3 -> 1), and under
    the larger-of rule the rows decoding a round went with it wherever the
    decoders stayed the majority (3.59 -> 2.95 of 4 rows on the first
    queue, 5.87 -> 3.73 of 8 on the third). The block weighed by everyone
    resident answers with the steps the emptier batch earns. Prompts of
    two steps: a freed slot is decoding again a round later at either
    cost, and not one row is lost. Prompts that ask for more than any
    block here pays for (1.25 and 1.5 steps a row a round): the rows
    follow the square root of the cost (steps needed, k x rows = steps
    allowed, q x slots / rows) and no longer the cost."""
    cfg = ff.FFConfig(max_requests_per_batch=slots, max_sequence_length=80,
                      max_tokens_per_batch=16, decode_block_steps=4)
    queue = [[(7 * i + j) % 50 + 1 for j in range(prompt + i % 3)]
             for i in range(6 * slots)]

    def rows_decoding(decode_s):
        res, ifm, queued = _serve_on_toy_device(
            monkeypatch, cfg, queue, new_tokens, decode_s=decode_s)
        assert all(r.status == "ok" for r in res)
        # while requests wait for a slot: every slot holds a resident
        loaded = [int(act.sum()) for (_, _, act, _), n
                  in zip(ifm.decodes, queued) if n]
        assert len(loaded) >= 10
        return sum(loaded) / len(loaded)

    dear, cheap = rows_decoding(0.8), rows_decoding(0.4)
    assert cheap >= holds * dear, (dear, cheap)


def test_incr_loop_lifecycle():
    """Two slots, three requests: the third waits for a slot, a row that
    meets EOS mid-block is cut there, the freed slot refills the next
    round, and a one-token prompt causes no prefill step."""
    first = _closed_form([8], 8, 32, eos=None)
    eos = first[1]                      # [8]'s second token, mid-block
    assert eos not in _closed_form([5, 6, 7], 4, 32, eos=None)
    cfg = ff.FFConfig(max_requests_per_batch=2, max_sequence_length=32,
                      max_tokens_per_batch=16, decode_block_steps=4)
    rm = RequestManager(eos_token_id=eos)
    g1 = rm.register_new_request([5, 6, 7], max_new_tokens=4)
    g2 = rm.register_new_request([8], max_new_tokens=8)
    g3 = rm.register_new_request([9, 10], max_new_tokens=3)
    waiting = []
    ifm = _RuleIFM(on_decode=lambda i: waiting.append(len(rm.pending)))
    rm.generate_incr_decoding(_rule_model(cfg, ifm))
    # round 1: slots 0 and 1 decode, the third request waits
    tok, pos, act, block = ifm.decodes[0]
    assert waiting[0] == 1 and block == 4
    assert (tok[0], pos[0], tok[1], pos[1]) == (7, 2, 8, 0) and act.all()
    assert rm.results[g1].output_tokens == _closed_form([5, 6, 7], 4, 32)
    assert rm.results[g2].output_tokens == first[:2]     # cut at EOS
    # round 2: the freed slots refill; [9, 10] prefills 9 and decodes 10
    tok, pos, act, block = ifm.decodes[1]
    assert waiting[1] == 0 and block == 3
    assert (tok[0], pos[0]) == (10, 1) and list(act) == [True, False]
    assert len(rm.results[g3].output_tokens) == 3
    # prefill: [5, 6] in round 1, [9] in round 2; never the one-token prompt
    filled = [[int(t) for row, n in zip(m.tokens, m.num_tokens)
               for t in row[:n]] for m in ifm.prefills]
    assert filled == [[5, 6], [9]]
    assert not rm.pending and not rm.inflight


def test_incr_loop_prompt_fills_cache():
    """A prompt as long as the cache has no room for one token: it ends
    with no output and the loop terminates (and serves its neighbour)."""
    cfg = ff.FFConfig(max_requests_per_batch=1, max_sequence_length=8,
                      max_tokens_per_batch=16, decode_block_steps=4)
    rm = RequestManager()
    full = rm.register_new_request(list(range(1, 9)), max_new_tokens=4)
    ok = rm.register_new_request([7], max_new_tokens=2)
    ifm = _RuleIFM()
    res = rm.generate_incr_decoding(_rule_model(cfg, ifm))
    assert len(res) == 2
    assert rm.results[full].output_tokens == []
    assert rm.results[full].status == "rejected"
    assert rm.results[ok].output_tokens == _closed_form([7], 2, 8, eos=None)
    assert not ifm.prefills and len(ifm.decodes) == 1


def test_arrival_mid_call_is_served():
    """A request that arrives while a decode block runs is admitted in the
    next round and finishes in the same generate call."""
    cfg = ff.FFConfig(max_requests_per_batch=2, max_sequence_length=32,
                      max_tokens_per_batch=16, decode_block_steps=4)
    rm = RequestManager()
    early = rm.register_new_request([3, 4, 5], max_new_tokens=10)
    late = []

    def arrive(i):
        if i == 0:
            late.append(rm.register_new_request([6, 7], max_new_tokens=5))

    ifm = _RuleIFM(on_decode=arrive)
    res = rm.generate_incr_decoding(_rule_model(cfg, ifm))
    assert {r.guid for r in res} == {early, late[0]}
    assert rm.results[late[0]].output_tokens == \
        _closed_form([6, 7], 5, 32, eos=None)
    assert rm.results[early].output_tokens == \
        _closed_form([3, 4, 5], 10, 32, eos=None)
    # it shared the second block with the request that was running
    assert ifm.decodes[1][2].all()


def test_default_incr_path():
    """FFConfig() as it comes: the Python loop, and a prefill step that is
    the compact batch addressed by slot (what the benchmark's cells run)."""
    rm = RequestManager()
    rm.register_new_request([3, 4, 5, 6], max_new_tokens=3)
    ifm = _RuleIFM()
    rm.generate_incr_decoding(_rule_model(ff.FFConfig(), ifm))
    assert rm.scheduler_loop == "python"
    assert ifm.prefills and all(m.slots is not None for m in ifm.prefills)


# ---------------------------------------------------------------------------
# The compact prefill batch (segments x chunk, addressed by slot) against
# the slot grid it replaced
# ---------------------------------------------------------------------------
def _tiny_family(family, mode, seed, R=6, max_seq=63, batch_tokens=16,
                 beam=1):
    """MHA at D=128, multi-query at D=64 (Falcon's geometry), a tiny OLMoE.
    63 positions a slot: a multiple of no chunk, so a prompt's last chunk
    can start within a chunk of the cache's end."""
    from flexflow_tpu.models.falcon import FalconConfig, create_falcon_model
    from flexflow_tpu.models.olmoe import OLMoEConfig, create_olmoe_model

    cfg = ff.FFConfig(max_requests_per_batch=R, max_sequence_length=max_seq,
                      max_tokens_per_batch=batch_tokens, seed=seed,
                      kv_cache_dtype="float32", max_beam_width=beam)
    m = ff.FFModel(cfg)
    if family == "mha_d128":
        create_llama_model(m, LLAMAConfig(
            vocab_size=128, hidden_size=256, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=2,
            num_key_value_heads=2, max_position_embeddings=128), mode=mode)
    elif family == "mqa_d64":
        create_falcon_model(m, FalconConfig(
            vocab_size=128, hidden_size=128, num_hidden_layers=2,
            num_attention_heads=2, num_kv_heads=1), mode=mode)
    else:
        create_olmoe_model(m, OLMoEConfig.from_hf_config(dict(
            vocab_size=128, hidden_size=64, intermediate_size=32,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, num_experts=8, num_experts_per_tok=2,
            norm_topk_prob=False, max_position_embeddings=128,
            rms_norm_eps=1e-5, rope_theta=10000.0)), mode=mode)
    m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    return m


def _kv_layers(model):
    """Every K and V cache of the model (one stack) as [R, KH, S, D]."""
    st = model.op_state["kv_cache"]
    return [np.asarray(a) for kv in ("k", "v") for a in st[kv]]


# six prompts for six slots, admitted together and never replaced, so slot
# i holds prompt i to the end: more requests filling than the four segments
# of a step; then fewer, so the long ones take several segments of one
# step; the last fills to one position short of the cache's end, its final
# chunk starting at 60 of 63 with the program 4 wide
_COMPACT_PROMPT_LENS = (30, 2, 9, 13, 5, 62)


def _serve_one_way(loop, family, compact, monkeypatch,
                   lens=_COMPACT_PROMPT_LENS, **sizes):
    seen = []
    if compact:
        build = RequestManager._meta_from_segments
        monkeypatch.setattr(
            RequestManager, "_meta_from_segments", staticmethod(
                lambda *a: seen.append(build(*a)) or seen[-1]))
    else:       # the old builder, RequestManager._meta_from_rows
        monkeypatch.setattr(RequestManager, "_compact_prefill",
                            staticmethod(lambda ifm: False))
    spec = loop != "incr"
    if loop == "spec_beam_fused" and family == "mqa_d64":
        # Falcon's builder has no beam head (models/falcon.py): its one
        # draft goes to the tree engine, which "spec_tree_fused" below
        # only runs with two
        loop = "spec_tree_fused"
        beam, seeds = 1, (0,)
    else:
        beam = 2 if loop == "spec_beam_fused" else 1
        seeds = {"incr": (), "spec_tree_fused": (0, 5)}.get(loop, (0,))
    llm = _tiny_family(family, InferenceMode.TREE_VERIFY_MODE if spec
                       else InferenceMode.INC_DECODING_MODE, seed=0, **sizes)
    ssms = [_tiny_family(family, InferenceMode.BEAM_SEARCH_MODE, seed=s,
                         beam=beam, **sizes) for s in seeds]
    rng = np.random.RandomState(4)
    prompts = [[int(t) for t in rng.randint(1, 128, size=n)]
               for n in lens]
    rm = RequestManager()
    for p in prompts:
        rm.register_new_request(p, max_new_tokens=7)
    if loop == "incr":
        results = rm.generate_incr_decoding(llm)
    elif loop == "spec_tree_host":
        results = rm._generate_spec_tree_host(llm, ssms, spec_depth=3)
    else:
        results = rm.generate_spec_infer(llm, ssms, spec_depth=3)
        assert rm.scheduler_loop == "python:" + loop
    by_prompt = {tuple(r.input_tokens): r.output_tokens for r in results}
    outs = [by_prompt[tuple(p)] for p in prompts]
    # a slot's written positions: all but its pending last token for the
    # verifier; its prompt (less the pending token) for a draft, whose
    # later positions only hold what its own chain left
    verifier = [[c[i, :, :len(p) + len(o) - 1] for c in _kv_layers(llm)]
                for i, (p, o) in enumerate(zip(prompts, outs))]
    drafts = [[c[i, :, :len(p) - 1] for m in ssms for c in _kv_layers(m)]
              for i, p in enumerate(prompts)]
    return outs, verifier, drafts, seen


@pytest.mark.parametrize("family", ["mha_d128", "mqa_d64", "olmoe"])
@pytest.mark.parametrize("loop", ["incr", "spec_beam_fused",
                                  "spec_tree_fused", "spec_tree_host"])
def test_compact_prefill_matches_slot_grid(loop, family, monkeypatch):
    """The same requests served with the compact [segments x chunk]
    prefill batch and with the slot grid give the same output tokens and
    equal K/V caches over every written position, in each Python loop
    (the fused one under either engine: one draft's beams, two drafts'
    chains; Falcon has no beam head, so there one draft's chain)."""
    with monkeypatch.context() as mp:
        outs, kv, draft_kv, seen = _serve_one_way(loop, family, True, mp)
    with monkeypatch.context() as mp:
        g_outs, g_kv, g_draft_kv, _ = _serve_one_way(loop, family, False,
                                                       mp)
    assert outs == g_outs
    assert [len(o) for o in outs] == [7, 7, 7, 7, 7, 1]
    for got, want in zip(kv + draft_kv, g_kv + g_draft_kv):
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    assert any(np.abs(a).max() > 0 for a in kv[-1])     # it was written
    # the compact program is one shape, segments x chunk
    assert {m.tokens.shape for m in seen} == {(4, 4)}
    live = [m.slots[m.active] for m in seen]
    # one step held two chunks of one slot, and one all four segments
    assert any(len(set(s)) < len(s) for s in live)
    assert any(len(set(s)) == 4 for s in live)
    # the last prompt's final chunk started within a chunk of the cache end
    assert any(int(m.start_pos[m.active].max()) == 60 for m in seen)


@pytest.mark.parametrize("slots", [1, 2])
@pytest.mark.parametrize("loop", ["incr", "spec_beam_fused",
                                  "spec_tree_fused", "spec_tree_host"])
def test_compact_prefill_chunk_wider_than_cache(loop, slots, monkeypatch):
    """With one or two slots the chunk is the batch's token budget over
    them, which a short max_sequence_length falls under: the by-slot
    append then writes through the whole row, and the loops serve what
    the slot grid serves."""
    sizes = dict(R=slots, max_seq=24, batch_tokens=64)
    lens = (23, 11)[:slots]
    with monkeypatch.context() as mp:
        outs, kv, draft_kv, seen = _serve_one_way(loop, "mha_d128", True, mp,
                                                  lens, **sizes)
    with monkeypatch.context() as mp:
        g_outs, g_kv, g_draft_kv, _ = _serve_one_way(loop, "mha_d128", False,
                                                       mp, lens, **sizes)
    assert outs == g_outs
    assert [len(o) for o in outs] == [1, 7][:slots]
    for got, want in zip(kv + draft_kv, g_kv + g_draft_kv):
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    assert {m.tokens.shape for m in seen} == {(slots, 64 // slots)}


@pytest.mark.parametrize("Q", [6, 40], ids=["chunk6", "chunk_over_cache"])
@pytest.mark.parametrize("stacked", [False, True],
                         ids=["per_layer", "stacked"])
def test_segment_append_is_exact_like_the_scatter(stacked, Q):
    """append_kv_contiguous by slot (the compact prefill's append) leaves
    the cache the windowed scatter leaves, position for position: runs
    that start anywhere (a draft catching up from an odd depth), two runs
    of one slot in either order, padding that must not touch what the
    other run wrote, a run within a chunk of the cache's end and one that
    would pass it, an inactive row; and the same with a chunk wider than
    the cache is long."""
    import jax.numpy as jnp

    from flexflow_tpu.ops.inc_attention import (append_kv,
                                                append_kv_contiguous)

    R, KH, S, D, L = 5, 2, 37, 8, 3
    rng = np.random.RandomState(8)
    cache = jnp.asarray(rng.randn(L, R, KH, S, D).astype(np.float32))
    new = jnp.asarray(rng.randn(7, Q, KH, D).astype(np.float32))
    #        slot start  n   active
    segs = [(3,   17,   2,  True),      # the later chunk first ...
            (3,   11,   6,  True),      # ... its padding-free predecessor
            (0,   33,   4,  True),      # starts within Q of the end
            (1,   35,   6,  True),      # would pass the end: 2 land
            (4,   5,    0,  True),      # nothing real
            (2,   9,    6,  False),     # inactive
            (2,   20,   3,  True)]
    slots, start, num, act = (jnp.asarray(c) for c in zip(*segs))
    layer = 1
    want = cache[layer]
    for i, (slot, sp, n, on) in enumerate(segs):    # the oracle, row by row
        grid = jnp.zeros((R, Q, KH, D)).at[slot].set(new[i])
        want = append_kv(want, grid, jnp.zeros((R,), jnp.int32).at[slot].set(sp),
                         jnp.zeros((R,), jnp.int32).at[slot].set(n),
                         jnp.zeros((R,), bool).at[slot].set(on))
    if stacked:
        got = append_kv_contiguous(cache, layer, new, start, act, slots, num)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(cache[0]))
        got = got[layer]
    else:
        got = append_kv_contiguous(cache[layer], None, new, start, act, slots,
                                   num)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.array_equal(np.asarray(got), np.asarray(cache[layer]))


def test_prefill_segments_go_to_the_oldest_admission_first():
    """Eight requests filling over four segments a step, slots refilled as
    they free: no request waits more than two rounds for its first
    segment, whichever slot it sits in (the lowest slot used to win, and
    the high ones starved while low ones were refilled)."""
    from types import SimpleNamespace as NS

    # the chooser alone: slot 0 was granted last, slot 5 first
    reqs = [NS(slot=i, tokens=list(range(n)), finished=False, d=0,
               prefill_start_s=t)
            for i, (n, t) in enumerate([(30, 9.0), (3, 2.0), (10, 3.0),
                                        (1, 0.5), (6, 4.0), (20, 1.0)])]
    rows = RequestManager._prefill_rows(reqs + [None], 4, lambda r: r.d, 4)
    assert [(slot, sp, len(t)) for slot, t, sp in rows] == [
        (5, 0, 4), (1, 0, 2), (2, 0, 4), (4, 0, 4)]
    # two filling, four segments: the spare ones are their next chunks,
    # one each in the same order, and the last token stays pending
    rows = RequestManager._prefill_rows([reqs[0], reqs[2]], 4,
                                        lambda r: r.d, 4)
    assert [(slot, sp, len(t)) for slot, t, sp in rows] == [
        (2, 0, 4), (0, 0, 4), (2, 4, 4), (0, 4, 4)]
    reqs[2].d = 4
    rows = RequestManager._prefill_rows([reqs[2]], 4, lambda r: r.d, 4)
    assert [(slot, sp, len(t)) for slot, t, sp in rows] == [
        (2, 4, 4), (2, 8, 1)]
    # on the slot grid a slot has one row
    rows = RequestManager._prefill_rows([reqs[0]], 4, lambda r: r.d, 4,
                                        consecutive=False)
    assert [(slot, sp, len(t)) for slot, t, sp in rows] == [(0, 0, 4)]

    # the loop: 14 requests of 9 tokens (two chunks) and one output token
    # over 8 slots
    model = _tiny_family("mqa_d64", InferenceMode.INC_DECODING_MODE, 0, R=8)
    rm = RequestManager()
    rng = np.random.RandomState(2)
    for _ in range(14):
        rm.register_new_request([int(t) for t in rng.randint(1, 128, size=9)],
                                max_new_tokens=1)
    rounds, granted, first = [0], {}, {}
    grant, prefill = rm._grant, rm._prefill

    def note_grant(req, *a):
        granted[req.guid] = rounds[0]
        return grant(req, *a)

    def note_prefill(ifm, active, *a, **kw):
        rows = prefill(ifm, active, *a, **kw)
        for slot, _, _ in rows:
            first.setdefault(active[slot].guid, rounds[0])
        rounds[0] += 1
        return rows

    rm._grant, rm._prefill = note_grant, note_prefill
    assert len(rm.generate_incr_decoding(model)) == 14
    waits = [first[g] - granted[g] for g in granted]
    assert len(waits) == 14 and max(waits) == 2, waits


@pytest.mark.parametrize("config,rounds", [
    ("falcon-7b", False), ("olmoe-1b-7b", False),
    ("k-exaone-236b-a23b", False), ("k-exaone-236b-a23b", True),
    ("mistral-small-4-119b", False)])
def test_check_compact_prefill_tool_rehearses(config, rounds, monkeypatch,
                                              capsys):
    """tools/check_compact_prefill.py (the on-chip check of the compact
    program against the slot grid) runs at a configuration's rehearsal
    sizes: on the CPU the two programs agree to the bit, and an expert
    model's compact run, sent where the grid run went, overrides no pick
    of its own. ``rounds``: the windowed cut is then served by the
    scheduler loop, whose second round takes sixteen consecutive steps
    (a block's four, times four resident to one row decoding: 1024
    positions through a ring of 128 rows): every token is what one step a
    round gives, the first the grid run's pick."""
    import json

    monkeypatch.syspath_prepend(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    for key in ("JAX_PLATFORMS", "FF_PALLAS_INTERPRET"):
        monkeypatch.setenv(key, os.environ.get(key, ""))   # restored after
    from tools import check_compact_prefill

    assert check_compact_prefill.main(
        ["--rehearse"] + ["--rounds"] * rounds + [config]) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["ok"] and res["config"] == config
    assert res["steps_compact"] < res["steps_grid"]
    assert res["logits_max_rel_l2"] == 0.0 and res["cache_max_abs_diff"] == 0.0
    assert (res["routed_tokens"] > 0) == (config != "falcon-7b")
    assert not any(res["routes_overridden_by_layer"])
    if rounds:
        assert res["served_tokens_equal"]
        assert res["served_first_tokens_off_the_grid"] == 0
        assert res["served_steps_by_round"][:4] == [1, 16, 2, 0]
        assert res["served_positions_a_round_max"] >= 2 * res["ring_rows"][0]
