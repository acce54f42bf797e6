"""Shared-prefix KV cache + decode-interleaved chunked prefill (ISSUE 19, 32):
radix trie match/insert/evict/refcount math on a fake clock, KV segment
extract/install roundtrip on both op_state layouts, token identity cold
vs warm on the incremental loop and on the fused speculation loop under
both engines, including a preemption re-queue that crosses a pooled prefix,
eviction-under-pressure never corrupting a live slot, and the
decode-interleaves-with-prefill dispatch order (a round prefills as many
steps as its decode block pays for, by the two programs' given costs, with
telemetry on and off: ISSUE 32).

Budget discipline: pure-math tests dominate; the integration tests share
the session tiny spec pair plus ONE module-scoped tiny incremental model
and ONE extra draft (the fused multi-SSM engine needs two distinct
drafts), and one cold reference run feeds every identity comparison.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from flexflow_tpu.serve import prefix_cache as pcm
from flexflow_tpu.serve.batch_config import GenerationConfig
from flexflow_tpu.serve.prefix_cache import PrefixCache
from flexflow_tpu.serve.request_manager import RequestManager

# a 12-token "system prompt" three prompts share (vocab 128)
SHARED = [3, 14, 15, 9, 2, 6, 5, 35, 8, 97, 93, 23]
P0 = SHARED + [7, 8]           # warms the pool (full 14-token prompt)
PA = SHARED + [9, 10]          # diverges at depth 12: radix partial match
PB = P0 + [40, 41]             # extends P0's stored prompt: full match
# long enough that the preemption test can evict a mid-generation victim
REF_NEW = 24


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float):
        self.t += dt


# ---------------------------------------------------------------------------
# radix trie math (pure, fake clock, dummy segments)
# ---------------------------------------------------------------------------

def test_radix_match_insert_refcount():
    clk = FakeClock()
    pc = PrefixCache(max_tokens=1024, clock=clk)
    assert pc.match([1, 2, 3]) == (0, None) and pc.misses == 1
    assert pc.would_store([1, 2, 3, 4])
    e1, n_ev = pc.insert([1, 2, 3, 4], {"llm": object()})
    assert e1 is not None and n_ev == 0
    assert pc.pool_tokens == 4 and len(pc) == 1
    # a request EXTENDING the stored prompt matches its full length
    clk.advance(1.0)
    shared, ent = pc.match([1, 2, 3, 4, 9])
    assert shared == 4 and ent is e1 and ent.refs == 1
    assert ent.last_used == pytest.approx(1.0)      # LRU touch
    # the exact stored prompt caps at len-1 (the last token must still
    # be fed to emit the first output logits) — subtree descent finds
    # the entry below the 3-deep match
    shared, ent = pc.match([1, 2, 3, 4])
    assert shared == 3 and ent is e1 and ent.refs == 2
    # divergence mid-path is a radix PARTIAL match: only the agreeing
    # depth is shared, the entry's first `shared` positions get installed
    shared, ent = pc.match([1, 2, 99, 100, 101])
    assert shared == 2 and ent is e1
    # below min_tokens (default 2) is a miss, not a 1-token hit
    assert pc.match([1, 99, 98]) == (0, None)
    assert pc.hits == 3 and pc.shared_tokens_total == 4 + 3 + 2
    for _ in range(3):
        pc.release(e1)
    assert e1.refs == 0
    pc.release(e1)                                  # floors at zero
    assert e1.refs == 0
    # duplicate insert: no new entry, the existing one gets an LRU touch
    clk.advance(1.0)
    dup, n_ev = pc.insert([1, 2, 3, 4], {"llm": object()})
    assert dup is None and n_ev == 0 and len(pc) == 1
    assert e1.last_used == pytest.approx(2.0)
    # out-of-bounds prompts are never stored
    assert not pc.would_store([5])
    assert pc.insert([5], {"llm": object()}) == (None, 0)


def test_radix_lru_eviction_protects_live_refs():
    clk = FakeClock()
    pc = PrefixCache(max_tokens=8, clock=clk)
    seg = {"llm": object()}
    pc.insert([1, 2, 3, 4], seg)
    clk.advance(1.0)
    pc.insert([5, 6, 7, 8], seg)
    assert pc.pool_tokens == 8 and pc.evictions == 0
    # over budget: the LRU entry ([1,2,3,4]) goes, and its dead branch
    # is pruned from the trie (no stale partial matches)
    clk.advance(1.0)
    _, n_ev = pc.insert([9, 10, 11, 12], seg)
    assert n_ev == 1 and pc.evictions == 1 and pc.pool_tokens == 8
    assert pc.match([1, 2, 3, 4, 9]) == (0, None)
    # an entry with a live reference (a request between match and
    # finish) is NEVER evicted — the pool runs over budget instead
    shared, live = pc.match([5, 6, 7, 8, 99])
    assert shared == 4 and live.refs == 1
    clk.advance(1.0)
    _, n_ev = pc.insert([20, 21, 22, 23, 24, 25], seg)
    assert pc.pool_tokens > pc.max_tokens            # transiently over
    assert live in pc._entries                       # survivor
    assert pc.match([5, 6, 7, 8, 99])[1] is live     # still matchable
    # once released it becomes the next LRU victim
    pc.release(live)
    pc.release(live)
    clk.advance(1.0)
    pc.insert([30, 31, 32, 33], seg)
    assert live not in pc._entries and pc.pool_tokens <= pc.max_tokens


# ---------------------------------------------------------------------------
# KV segment extract/install (both op_state layouts)
# ---------------------------------------------------------------------------

def test_kv_segment_roundtrip_both_layouts():
    R, KH, S, D, L = 2, 2, 16, 4, 2
    rng = np.random.default_rng(0)

    def mk(shape):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32))

    src_ak = rng.normal(size=(R, KH, S, D)).astype(np.float32)
    src_sk = rng.normal(size=(L, R, KH, S, D)).astype(np.float32)
    src = {"attn0": {"k_cache": jnp.asarray(src_ak),
                     "v_cache": mk((R, KH, S, D))},
           "kv_cache": {"k": jnp.asarray(src_sk),
                        "v": mk((L, R, KH, S, D))},
           "other": 3}                              # non-KV state ignored
    segs = pcm.extract_prefix_kv(src, slot=0, length=5, max_seq=S)
    P = 8                                           # padded to _PAD bucket
    assert set(segs) == {"attn0", "kv_cache"}
    assert segs["attn0"]["k"].shape == (KH, P, D)
    assert segs["kv_cache"]["k"].shape == (L, KH, P, D)
    np.testing.assert_array_equal(segs["attn0"]["k"], src_ak[0, :, :P])
    np.testing.assert_array_equal(segs["kv_cache"]["k"],
                                  src_sk[:, 0, :, :P])
    # install into slot 1 of a fresh op_state: shared positions land
    # bit-for-bit, the other slot stays untouched
    dst = {"attn0": {"k_cache": jnp.zeros((R, KH, S, D), jnp.float32),
                     "v_cache": jnp.zeros((R, KH, S, D), jnp.float32)},
           "kv_cache": {"k": jnp.zeros((L, R, KH, S, D), jnp.float32),
                        "v": jnp.zeros((L, R, KH, S, D), jnp.float32)}}
    assert pcm.prefix_compatible(dst, segs, 5, S)
    out = pcm.install_prefix_kv(dst, 1, segs, 5, S)
    np.testing.assert_array_equal(np.asarray(out["attn0"]["k_cache"])[1, :, :P],
                                  src_ak[0, :, :P])
    np.testing.assert_array_equal(
        np.asarray(out["kv_cache"]["k"])[:, 1, :, :P], src_sk[:, 0, :, :P])
    assert not np.asarray(out["attn0"]["k_cache"])[0].any()
    assert not np.asarray(out["kv_cache"]["k"])[:, 0].any()
    # geometry mismatches refuse loudly instead of corrupting
    bad = {"attn0": {"k_cache": jnp.zeros((R, KH + 1, S, D), jnp.float32),
                     "v_cache": jnp.zeros((R, KH + 1, S, D), jnp.float32)}}
    assert not pcm.prefix_compatible(bad, segs, 5, S)
    assert not pcm.prefix_compatible(dst, segs, S, S)  # seg holds only 8 pos
    assert pcm.extract_prefix_kv(dst, 0, S + 1, S) is None


# ---------------------------------------------------------------------------
# integration: token identity cold vs warm on the scheduler loops
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_incr_model():
    """One tiny INC_DECODING model (the incremental loop is a scheduler
    path of its own; the session spec pair only covers spec paths)."""
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


@pytest.fixture(scope="module")
def tiny_ssm2():
    """A second draft (seed 7) for the fused multi-SSM engine, on the
    session tiny_spec_pair geometry."""
    import flexflow_tpu as ff
    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models.llama import LLAMAConfig, create_llama_model

    tiny = LLAMAConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, max_position_embeddings=128)
    cfg = ff.FFConfig(max_requests_per_batch=2, max_sequence_length=64,
                      max_tokens_per_batch=16, seed=7,
                      kv_cache_dtype="float32")
    m = ff.FFModel(cfg)
    create_llama_model(m, tiny, mode=InferenceMode.BEAM_SEARCH_MODE)
    m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    return m


@pytest.fixture(scope="module")
def incr_ref(tiny_incr_model):
    """Cold (no prefix cache) incremental outputs for P0/PA/PB at
    max_new_tokens=REF_NEW — the reference every warm run must reproduce."""
    rm = RequestManager()
    guids = {tuple(p): rm.register_new_request(list(p),
                                               max_new_tokens=REF_NEW)
             for p in (P0, PA, PB)}
    rm.generate_incr_decoding(tiny_incr_model)
    assert all(rm.results[g].status == "ok" for g in guids.values())
    return {p: rm.results[g].output_tokens for p, g in guids.items()}


def test_token_identity_incremental_cold_vs_warm(tiny_incr_model, incr_ref):
    gc = GenerationConfig(prefix_cache=True, prefix_cache_tokens=4096)
    rm = RequestManager()
    g0 = rm.register_new_request(P0, max_new_tokens=REF_NEW)
    rm.generate_incr_decoding(tiny_incr_model, generation_config=gc)
    pc = rm.prefix_cache
    assert pc is not None and pc.max_tokens == 4096
    # insert-on-finish pooled the full prompt; its own lookup was a miss
    assert len(pc) == 1 and pc.pool_tokens == len(P0)
    assert pc.misses == 1 and pc.hits == 0
    assert rm.results[g0].output_tokens == incr_ref[tuple(P0)]
    assert rm.results[g0].prefix_hit_tokens == 0
    # PA partial-matches 12 shared tokens, PB full-matches all 14 —
    # both skip those prefill positions and still emit EXACTLY the
    # cold-path tokens
    ga = rm.register_new_request(PA, max_new_tokens=REF_NEW)
    gb = rm.register_new_request(PB, max_new_tokens=REF_NEW)
    rm.generate_incr_decoding(tiny_incr_model, generation_config=gc)
    assert rm.results[ga].output_tokens == incr_ref[tuple(PA)]
    assert rm.results[gb].output_tokens == incr_ref[tuple(PB)]
    assert rm.results[ga].prefix_hit_tokens == len(SHARED)
    assert rm.results[gb].prefix_hit_tokens == len(P0)
    assert pc.hits == 2 and pc.shared_tokens_total == len(SHARED) + len(P0)
    # every terminal path released its pool handle
    assert all(e.refs == 0 for e in pc._entries)


def test_token_identity_spec_beam_and_tree(tiny_spec_pair, tiny_ssm2,
                                           tiny_beam_draft):
    """The fused loop installs and stores a shared prefix in the verifier
    and in every draft, under either engine."""
    llm, ssm = tiny_spec_pair
    gc = GenerationConfig(prefix_cache=True, prefix_cache_tokens=4096)

    def run_pair(ssms):
        # cold reference: both prompts, no pool
        cold = RequestManager()
        c0 = cold.register_new_request(P0, max_new_tokens=REF_NEW)
        ca = cold.register_new_request(PA, max_new_tokens=REF_NEW)
        cold.generate_spec_infer(llm, ssms, spec_depth=3)
        # warm: P0 finishes and pools; PA reuses 12 shared tokens
        warm = RequestManager()
        w0 = warm.register_new_request(P0, max_new_tokens=REF_NEW)
        warm.generate_spec_infer(llm, ssms, spec_depth=3,
                                 generation_config=gc)
        wa = warm.register_new_request(PA, max_new_tokens=REF_NEW)
        warm.generate_spec_infer(llm, ssms, spec_depth=3,
                                 generation_config=gc)
        pc = warm.prefix_cache
        assert pc is not None and len(pc) >= 1 and pc.hits >= 1
        assert warm.results[wa].prefix_hit_tokens == len(SHARED)
        assert warm.results[w0].output_tokens == cold.results[c0].output_tokens
        assert warm.results[wa].output_tokens == cold.results[ca].output_tokens
        assert warm.results[wa].status == "ok"

    run_pair([tiny_beam_draft])     # one draft's beams: the beam engine
    run_pair([ssm])                 # one draft's chain: the tree engine
    run_pair([ssm, tiny_ssm2])      # and two drafts' chains


def test_preemption_requeue_crosses_shared_prefix(tiny_incr_model, incr_ref):
    """A preempted victim is re-queued with cache_depth=0 but keeps its
    pool handle: the re-grant re-installs the shared prefix (the
    _prefix_install empty-cache guard) and the final tokens still match
    an uncontended cold run exactly. The high-priority request must
    ARRIVE while A/B hold the slots (registration order alone would just
    grant it first), so this drives the background-server front door."""
    import time

    from flexflow_tpu.serve.loadgen import EngineHandle

    gc = GenerationConfig(prefix_cache=True, prefix_cache_tokens=4096)
    handle = EngineHandle(tiny_incr_model, generation_config=gc)
    try:
        handle.start_server()
        srv, rm = handle._server, handle.rm
        g0, ev0 = srv.submit([P0], REF_NEW, 0)
        assert ev0.wait(timeout=120.0)
        assert rm.results[g0[0]].status == "ok"
        assert len(rm.prefix_cache) == 1            # pool warmed
        gA, evA = srv.submit([PA], REF_NEW, 0)
        gB, evB = srv.submit([PB], REF_NEW, 0)
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            ra, rb = rm.inflight.get(gA[0]), rm.inflight.get(gB[0])
            if ra is not None and rb is not None \
                    and ra.slot >= 0 and rb.slot >= 0:
                break
            time.sleep(0.002)
        else:
            pytest.fail("A/B never took their slots")
        # high-priority arrival with most of its deadline budget burned
        # waiting upstream: arrival shifted into the past makes the
        # at-risk predicate hold with plenty of real wall clock left
        gC, evC = srv.submit([[11, 3, 19]], 2, 0, priority=1,
                             timeout_s=30.0)
        with srv._work:
            rm.inflight[gC[0]].arrival_s -= 70.0
        assert evC.wait(timeout=120.0) and evA.wait(120.0) and evB.wait(120.0)
        res_a, res_b = rm.results[gA[0]], rm.results[gB[0]]
        res_c = rm.results[gC[0]]
        assert res_c.status == "ok" and len(res_c.output_tokens) == 2
        # one of A/B was evicted mid-flight and re-queued across its
        # pooled prefix...
        assert res_a.preemptions + res_b.preemptions >= 1
        # ...and BOTH still hit the pool and emit the cold-path tokens
        assert res_a.prefix_hit_tokens == len(SHARED)
        assert res_b.prefix_hit_tokens == len(P0)
        assert res_a.output_tokens == incr_ref[tuple(PA)]
        assert res_b.output_tokens == incr_ref[tuple(PB)]
        assert res_a.status == "ok" and res_b.status == "ok"
    finally:
        handle.stop_server()
    assert not rm.pending and not rm.inflight


def test_eviction_pressure_keeps_tokens_identical(tiny_incr_model, incr_ref):
    """A pool budget too small for every finished prompt forces
    mid-serve evictions; live requests hold references so their entries
    survive, and outputs stay bit-identical to the cold path."""
    gc = GenerationConfig(prefix_cache=True, prefix_cache_tokens=16)
    rm = RequestManager()
    g0 = rm.register_new_request(P0, max_new_tokens=REF_NEW)
    rm.generate_incr_decoding(tiny_incr_model, generation_config=gc)
    pc = rm.prefix_cache
    assert len(pc) == 1
    ga = rm.register_new_request(PA, max_new_tokens=REF_NEW)
    gb = rm.register_new_request(PB, max_new_tokens=REF_NEW)
    rm.generate_incr_decoding(tiny_incr_model, generation_config=gc)
    assert rm.results[ga].output_tokens == incr_ref[tuple(PA)]
    assert rm.results[gb].output_tokens == incr_ref[tuple(PB)]
    assert rm.results[ga].prefix_hit_tokens == len(SHARED)
    assert rm.results[gb].prefix_hit_tokens == len(P0)
    # insert-on-finish overflowed the 16-token budget and evicted, but
    # P0's entry was reference-protected while A/B were in flight
    assert pc.evictions >= 1
    assert all(e.refs == 0 for e in pc._entries)


# ---------------------------------------------------------------------------
# decode-interleaved chunked prefill: dispatch order
# ---------------------------------------------------------------------------

def _interleave_events(model, telemetry: bool):
    """A 60-token prompt (59 to fill: five steps of 2 segments x 8, the
    first shared) beside a 3-token one that decodes 20 tokens in blocks of
    8, with the two programs' costs GIVEN: a decode block of 8 steps for
    one row, beside one request filling, pays for two prefill steps (8 x
    0.15 x 2 / 1.0). Returns the loop's device calls in order and the two
    results."""
    from flexflow_tpu.serve.request_manager import InferenceManager
    from flexflow_tpu.serve.step_costs import GivenCosts
    from flexflow_tpu.telemetry import disable_telemetry, enable_telemetry

    rm = RequestManager()
    long_prompt = [(i % 96) + 1 for i in range(60)]
    gl = rm.register_new_request(long_prompt, max_new_tokens=2)
    gs = rm.register_new_request([7, 3, 2], max_new_tokens=20)
    events = []
    orig_prefill = rm._timed_prefill

    def spy_prefill(*args, **kwargs):
        events.append("prefill")
        return orig_prefill(*args, **kwargs)

    rm._timed_prefill = spy_prefill
    ifm = getattr(model, "_inference_manager", None)
    if ifm is None:
        ifm = model._inference_manager = InferenceManager(model)
    orig_decode = ifm.launch_decode_block

    def spy_decode(tok, pos, act, block, **kw):
        events.append("decode")
        return orig_decode(tok, pos, act, block, **kw)

    ifm.launch_decode_block = spy_decode
    ifm.step_costs = GivenCosts(1.0, 0.15)
    if telemetry:
        enable_telemetry()
    try:
        rm.generate_incr_decoding(model)
    finally:
        disable_telemetry()
        ifm.launch_decode_block = orig_decode
        del ifm.step_costs
    return events, rm.results[gl], rm.results[gs]


def test_decode_interleaves_with_chunked_prefill(tiny_incr_model):
    """The deterministic form of the TTFT claim: with a long prompt
    prefilling in chunks, a co-resident caught-up request's decode block
    is dispatched BEFORE the long prompt's final prefill chunk — the
    short request never waits for the full prefill as it did under the
    old drain-prefill-then-decode order. The prompt is longer than one
    round's allowance: a round prefills as many steps as its decode block
    pays for (two, by the costs given) and no more."""
    events, long_res, short_res = _interleave_events(tiny_incr_model, False)
    assert long_res.status == "ok" and short_res.status == "ok"
    assert len(short_res.output_tokens) == 20
    # the long prompt needed several bounded steps...
    assert events.count("prefill") == 5
    # ...and the short request decoded while those were still pending
    first_decode = events.index("decode")
    last_prefill = len(events) - 1 - events[::-1].index("prefill")
    assert first_decode < last_prefill, events
    # round 1 stops at the short request's catch-up; round 2 takes the two
    # steps its block of 8 pays for; round 3's block is the short request's
    # last 4 tokens, which pay for one; round 4 has nobody to stall
    assert events == ["prefill", "decode", "prefill", "prefill", "decode",
                      "prefill", "decode", "prefill", "decode"], events


def test_telemetry_leaves_the_rounds_as_they_are(tiny_incr_model):
    """Telemetry fences every prefill step and times it; the steps a round
    takes come from the two costs and the state alone, so the traced run
    measures the schedule the untraced run has, token for token."""
    plain = _interleave_events(tiny_incr_model, False)
    traced = _interleave_events(tiny_incr_model, True)
    assert traced[0] == plain[0]
    assert traced[1].output_tokens == plain[1].output_tokens
    assert traced[2].output_tokens == plain[2].output_tokens
