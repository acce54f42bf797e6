"""ZAYA1 on the serving path, at tiny sizes on the CPU: attention in a
convolved latent whose rows carry a tail from step to step beside a plain
grouped k/v cache, a top-1 router that is an MLP fed by the layer before
with an output that names no expert, a head on the embedding's own table.

The program against the plain reference (which has teeth for a conv tap, the
value's shift, ``gamma * r_prev`` and the routed term); one prompt fed three
ways (consecutive segments of one slot in one compact step, a segment a
step, a token a step): the same logits and the same tails; a slot reused; a
row preempted and prefilled again; what refuses a tail; the skip pick; a
synthetic checkpoint under the ``HF_KEYS`` names; the yardstick's arithmetic
and the cell's traffic file.
"""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import flexflow_tpu as ff
from flexflow_tpu.ffconst import InferenceMode
from flexflow_tpu.models import FAMILIES, family_for_hf_config
from flexflow_tpu.ops.inc_attention import TAIL_STACK, commit_tree_kv
from flexflow_tpu.serve.request_manager import RequestManager

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(vocab_size=256, hidden_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            moe_intermediate_size=32, num_experts=4, router_hidden_size=16)
# what the reference reads beside the sizes
REF_CFG = dict(TINY, partial_rotary_factor=0.5, rms_norm_eps=1e-5,
               rope_parameters={"hybrid": {"rope_theta": 5e6}})
# float32 program against float32 reference: rounding only
TOL = 2e-5


@pytest.fixture(scope="module")
def bench():
    """The benchmark's family and reference for ZAYA1, loaded as run.py
    loads them."""
    sys.path.insert(0, ROOT)
    try:
        from benchmark.run import load_module

        yield load_module("families", "zaya"), load_module("reference", "zaya")
    finally:
        sys.path.remove(ROOT)


def _build(mode=InferenceMode.INC_DECODING_MODE, tiny=TINY, **ffkw):
    from flexflow_tpu.models.zaya import ZayaConfig, create_zaya_model

    kw = dict(max_requests_per_batch=4, max_sequence_length=256,
              max_tokens_per_batch=64, seed=3, compute_dtype="float32",
              kv_cache_dtype="float32", num_devices=1)
    kw.update(ffkw)
    m = ff.FFModel(ff.FFConfig(**kw))
    c = ZayaConfig(**tiny, router_init_std=1.0)
    create_zaya_model(m, c, mode=mode, data_type=ff.DataType.DT_FLOAT)
    m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    return m, c


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, TINY["vocab_size"], size=n)


def _reference(bench, m, c, toks, routes=None, **kw):
    family, reference = bench
    w = family.reference_weights(m, c)
    logits, scores = reference.forward_routed(
        {**w, "layers": list(w["layers"])}, toks, REF_CFG, routes=routes,
        **kw)
    return np.asarray(logits), [np.asarray(s) for s in scores]


def _rel(a, b):
    return float((np.linalg.norm(a - b, axis=-1)
                  / np.linalg.norm(b, axis=-1)).max())


def _tails(m, slot):
    return np.asarray(m.op_state[TAIL_STACK]["t"])[:, slot]


# ---------------------------------------------------------------------------
# (i) the program against the plain reference
# ---------------------------------------------------------------------------

def test_program_matches_plain_reference_through_cache_and_tail(bench):
    """Prefill in chunks of the compact batch (two consecutive segments of
    one slot in a step, then a ragged one from the state), then eight decode
    steps: logits at every position against the full float32 forward, the
    reference choosing its own routes. And the reference has teeth: each
    term the issue names, left out, is far outside the tolerance."""
    family, _ = bench
    m, c = _build()
    assert m.attention_kinds == {"full": {
        "layers": 2, "window": None,
        "cache_bytes": 2 * 2 * 4 * 2 * 256 * 16 * 4,
        "tail_bytes": 2 * 4 * (2 * 96 + 16) * 4}}
    toks = _tokens(16 + 16 + 11 + 8)
    plan = [[16, 16], [11]] + [1] * 8
    ours, routes = family.drive(m, toks, plan, slot=2)
    ref, scores = _reference(bench, m, c, toks)
    assert [r[:, 0].tolist() for r in routes] == [
        s.argmax(-1).tolist() for s in scores]
    assert _rel(ours, ref) < TOL
    for term in ("conv_tap", "value_shift", "eda", "routed"):
        wrong, _ = _reference(bench, m, c, toks, without=(term,))
        assert _rel(wrong, ref) > 0.1, term
    # the skip output was picked somewhere, so the comparison covers it
    assert any((r == c.num_experts).any() for r in routes)


# ---------------------------------------------------------------------------
# (ii) one prompt three ways
# ---------------------------------------------------------------------------

PLANS = {
    "consecutive_segments_in_one_step": [[16, 16, 16, 5]],
    "two_steps_of_two_segments": [[16, 16], [16, 5]],
    "one_segment_a_step": [[16], [16], [16], [5]],
    "one_token_a_step": [1] * 53,
}


@pytest.fixture(scope="module")
def three_ways(bench):
    family, _ = bench
    toks = _tokens(53, seed=5)
    out = {}
    for name, plan in PLANS.items():
        m, _ = _build()
        logits, routes = family.drive(m, toks, plan, slot=1)
        out[name] = (logits, routes, _tails(m, 1), _tails(m, 0))
    return out


@pytest.mark.parametrize("way", sorted(PLANS)[1:])
def test_one_prompt_fed_three_ways_gives_the_same_logits_and_tails(
        three_ways, way):
    """A segment's tail from another segment of the step, from the state a
    step left, or a token at a time: the same logits to float32 rounding,
    the same routes, and the slot is left with the same tail (the LAST
    segment's, whichever row of the batch carried it); no other slot's tail
    is touched."""
    base = three_ways[sorted(PLANS)[0]]
    got = three_ways[way]
    assert _rel(got[0], base[0]) < TOL
    assert all((a == b).all() for a, b in zip(got[1], base[1]))
    np.testing.assert_allclose(got[2], base[2], rtol=1e-4, atol=1e-5)
    assert np.abs(base[2]).max() > 0.1 and not got[3].any()


def test_the_slot_grid_prefill_carries_the_tail_too(bench, three_ways):
    """A prefill chunk on the slot grid (``slots`` None, a row a slot: what
    a harness that feeds one slot at a time sends) takes its tail from the
    state like a decode step: the same logits as the compact batch's."""
    family, _ = bench
    sys.path.insert(0, ROOT)
    try:
        from benchmark.families._common import program_logits
    finally:
        sys.path.remove(ROOT)
    m, _ = _build()
    grid = program_logits(m, _tokens(53, seed=5), 32)   # then 21 decoded
    assert _rel(grid, three_ways[sorted(PLANS)[0]][0]) < TOL


def test_take_tails_by_source():
    """The op's own rule on a hand-made step: a row at position 0 starts
    from zeros whatever the slot held, a row that continues another row of
    its slot from that row's end (through a one-token row too), any other
    from the state; only each slot's last row is written back, and an idle
    row neither reads nor writes."""
    from flexflow_tpu.ops.cca_attention import take_tails

    C, Dv, Q = 6, 2, 3
    rng = np.random.default_rng(1)
    stored = rng.standard_normal((4, 2 * C + Dv)).astype(np.float32)
    u = rng.standard_normal((5, Q, C)).astype(np.float32)
    v2 = rng.standard_normal((5, Q, Dv)).astype(np.float32)
    #        slot start n
    rows = [(2, 0, 3),      # starts a request in a slot that held something
            (2, 3, 1),      # continues row 0 (one token)
            (2, 4, 2),      # continues row 1: u_{-2} is row 0's last
            (1, 7, 3),      # from the state
            (0, 5, 0)]      # idle
    slots, start, n = (jnp.asarray(x, jnp.int32) for x in zip(*rows))
    eu, ev, kept = take_tails(jnp.asarray(stored), slots, start, n,
                              jnp.asarray(u), jnp.asarray(v2))
    eu, ev, kept = np.asarray(eu), np.asarray(ev), np.asarray(kept)
    assert not eu[0, :2].any() and not ev[0, 0].any()
    np.testing.assert_array_equal(eu[1, :2], u[0, 1:3])
    np.testing.assert_array_equal(ev[1, 0], v2[0, 2])
    np.testing.assert_array_equal(eu[2, :2], [u[0, 2], u[1, 0]])
    np.testing.assert_array_equal(ev[2, 0], v2[1, 0])
    np.testing.assert_array_equal(eu[3, :2], [stored[1, C:2 * C],
                                              stored[1, :C]])
    np.testing.assert_array_equal(ev[3, 0], stored[1, 2 * C:])
    np.testing.assert_array_equal(
        kept[2], np.concatenate([u[2, 1], u[2, 0], v2[2, 1]]))
    np.testing.assert_array_equal(
        kept[1], np.concatenate([u[3, 2], u[3, 1], v2[3, 2]]))
    np.testing.assert_array_equal(kept[[0, 3]], stored[[0, 3]])


# ---------------------------------------------------------------------------
# (iii) a slot reused; (iv) a row preempted and prefilled again
# ---------------------------------------------------------------------------

def test_a_reused_slot_starts_from_a_cleared_tail(bench):
    """A second request in a slot whose first left its tail there: its
    logits are those of the same request alone in a fresh model."""
    family, _ = bench
    first, second = _tokens(40, seed=7), _tokens(37, seed=8)
    plan = [[16, 16], [5]]
    m, _ = _build()
    family.drive(m, first, [[16, 16], [8]], slot=3)
    assert np.abs(_tails(m, 3)).max() > 0.1
    again, _ = family.drive(m, second, plan, slot=3)
    fresh, _ = _build()
    alone, _ = family.drive(fresh, second, plan, slot=3)
    assert _rel(again, alone) < TOL
    np.testing.assert_allclose(_tails(m, 3), _tails(fresh, 3), rtol=1e-4,
                               atol=1e-5)


def test_preemption_rebuilds_the_tail_and_keeps_the_tokens():
    """Deadline-aware preemption drops a victim's cache depth and prefills
    its prompt and what it generated again from position 0, which rebuilds
    the tail with the cache: the victim's tokens are those of an
    undisturbed run."""
    import time

    from flexflow_tpu.serve.loadgen import EngineHandle

    m, _ = _build(max_requests_per_batch=2, max_tokens_per_batch=32)
    prompts = [[int(t) for t in _tokens(n, seed=i)]
               for i, n in enumerate((45, 38))]
    new = 60
    ref_rm = RequestManager()
    guids = [ref_rm.register_new_request(p, max_new_tokens=new)
             for p in prompts]
    ref_rm.generate_incr_decoding(m)
    ref = [ref_rm.results[g].output_tokens for g in guids]
    handle = EngineHandle(m)
    try:
        handle.start_server()
        srv, rm = handle._server, handle.rm
        subs = [srv.submit([p], new, 0) for p in prompts]
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            reqs = [rm.inflight.get(g[0]) for g, _ in subs]
            if all(r is not None and r.slot >= 0 and r.num_generated > 8
                   for r in reqs):
                break
            time.sleep(0.002)
        else:
            pytest.fail("the two never took their slots")
        gC, evC = srv.submit([prompts[1][:9]], 2, 0, priority=1,
                             timeout_s=30.0)
        with srv._work:
            rm.inflight[gC[0]].arrival_s -= 70.0    # its deadline at risk
        assert evC.wait(120.0) and all(ev.wait(120.0) for _, ev in subs)
        res = [rm.results[g[0]] for g, _ in subs]
        assert rm.results[gC[0]].status == "ok"
        assert sum(r.preemptions for r in res) >= 1
        assert [r.output_tokens for r in res] == ref
    finally:
        handle.stop_server()


# ---------------------------------------------------------------------------
# (v) what cannot carry a tail refuses, by its mechanism
# ---------------------------------------------------------------------------

def _refusal(name):
    from flexflow_tpu.models.zaya import ZayaConfig

    if name in ("tree_verify_mode", "beam_search_mode"):
        mode = (InferenceMode.TREE_VERIFY_MODE if name == "tree_verify_mode"
                else InferenceMode.BEAM_SEARCH_MODE)
        return lambda: _build(mode=mode)
    if name == "tensor_parallel_mesh":
        return lambda: _build(tensor_parallelism_degree=2, num_devices=2)
    if name == "pipeline_plan":
        return lambda: _build(pipeline_parallelism_degree=2, num_devices=2)
    if name in ("three_taps", "two_picks", "a_sliding_layer", "untied"):
        hf = dict(TINY, **{"three_taps": {"cca_time0": 3},
                           "two_picks": {"num_experts_per_tok": 2},
                           "a_sliding_layer": {"layer_types": [
                               "hybrid", "hybrid_sliding"]},
                           "untied": {"tie_word_embeddings": False}}[name])
        return lambda: ZayaConfig.from_hf_config(hf)
    m, _ = _build()
    if name == "commit_tree_kv":
        z = jnp.zeros((4,), jnp.int32)
        return lambda: commit_tree_kv(m.op_state, jnp.zeros((4, 3), jnp.int32),
                                      z, z, z > 0)
    if name in ("tree_engine_commit", "beam_engine_commit"):
        from flexflow_tpu.serve.engine import BeamSpecEngine, MultiSpecEngine

        eng = (MultiSpecEngine if name == "tree_engine_commit"
               else BeamSpecEngine)
        z = jnp.zeros((4,), jnp.int32)
        # (the engines' commit asks before it reads anything of itself)
        return lambda: eng._commit(type("E", (), {"depth": 2})(), m.op_state,
                                   z, z, z, z > 0)
    if name == "prefix_pool":
        from flexflow_tpu.serve import prefix_cache

        return lambda: prefix_cache.extract_prefix_kv(m.op_state, 0, 8, 256)
    if name == "tree_batch_on_the_op":
        from flexflow_tpu.ops.base import OpContext
        from flexflow_tpu.ops.cca_attention import IncMultiHeadCCAttention

        ctx = OpContext(training=False, rng=None, compute_dtype=jnp.float32,
                        batch_config=type("M", (), {"ancestor": 0})())
        layer = next(ly for ly in m.layers if "rotary_dim" in ly.attrs)
        return lambda: IncMultiHeadCCAttention.forward(
            layer.attrs, m.params[layer.name], [jnp.zeros((4, 1, 128))], ctx)
    raise KeyError(name)


@pytest.mark.parametrize("what,sentence", [
    ("tree_verify_mode", "incremental decoding only.*tree verification"),
    ("beam_search_mode", "incremental decoding only.*beam drafting"),
    ("tensor_parallel_mesh", "mesh that divides a model.*carries a tail"),
    ("pipeline_plan", "'pipe': 2.*carries a tail"),
    ("commit_tree_kv", "tree verification.*rejected draft cannot be rolled"),
    ("tree_engine_commit", "speculation commit.*carries a tail"),
    ("beam_engine_commit", "speculation commit.*carries a tail"),
    ("prefix_pool", "shared-prefix pool is not supported over an attention "
                    "layer that carries a tail"),
    ("tree_batch_on_the_op", "a token a row a step"),
    ("three_taps", "cca_time0"),
    ("two_picks", "num_experts_per_tok"),
    ("a_sliding_layer", "hybrid_sliding"),
    ("untied", "tie_word_embeddings")])
def test_what_cannot_carry_a_tail_refuses_loudly(what, sentence):
    with pytest.raises(NotImplementedError, match=sentence):
        _refusal(what)()


def test_prefix_pool_refuses_when_a_request_asks_for_it():
    from flexflow_tpu.serve.batch_config import GenerationConfig

    m, _ = _build()
    rm = RequestManager()
    rm.register_new_request(list(range(1, 20)), max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="shared-prefix pool"):
        rm.generate_incr_decoding(m, GenerationConfig(prefix_cache=True))


# ---------------------------------------------------------------------------
# (vi) the skip pick; the loop and what telemetry keeps of the tail
# ---------------------------------------------------------------------------

def test_the_skip_pick_adds_nothing_and_is_a_token_without_a_pair(bench):
    """With the selection bias pushed onto the last output every token of
    every layer skips: the logits are the reference's with the routed term
    left out, no expert runs, and the tokens are counted with no routed pair
    beside them."""
    from flexflow_tpu.telemetry import ServingTelemetry

    family, _ = bench
    m, c = _build(telemetry=True)
    for i in range(c.num_hidden_layers):
        m.set_parameter_by_key(
            (f"layers.{i}.mlp.router.balancing_bias", "weight"),
            np.eye(c.router_width, dtype=np.float32)[c.num_experts] * 10)
    toks = _tokens(30, seed=11)
    ours, routes = family.drive(m, toks, [[16, 6]] + [1] * 8, slot=0)
    assert all((r == c.num_experts).all() for r in routes)
    ref, _ = _reference(bench, m, c, toks, without=("routed",))
    assert _rel(ours, ref) < TOL
    tel = ServingTelemetry()
    tel.watch_model(m)
    snap = tel.registry.snapshot()
    assert snap['ffsv_moe_tokens_total{phase="prefill"}']["value"] == 2 * 22
    assert snap['ffsv_moe_tokens_total{phase="decode"}']["value"] == 2 * 8
    for phase in ("prefill", "decode"):
        assert snap[f'ffsv_moe_routed_pairs_total{{phase="{phase}"}}'][
            "value"] == 0


def test_the_loop_serves_it_and_counts_where_the_tails_came_from(bench):
    """Through RequestManager (compact prefill with consecutive segments,
    decode blocks): the tokens are those the program gives one request at a
    time; ``ffsv_cca_tails_total`` counts every prefill segment and decode
    row-step by where its tail came from, ``ffsv_attn_positions_read_total
    {kind="full"}`` what the decode steps' rows had to attend, the two
    gauges what compile allocated."""
    from flexflow_tpu.telemetry import ServingTelemetry

    family, _ = bench
    m, c = _build(telemetry=True)
    prompts = [[int(t) for t in _tokens(n, seed=20 + i)]
               for i, n in enumerate((70, 9, 1))]
    new = 12
    tel = ServingTelemetry()
    rm = RequestManager()
    rm.telemetry = tel
    for p in prompts:
        rm.register_new_request(p, max_new_tokens=new)
    got = rm.generate_incr_decoding(m)
    alone, _ = _build()
    for res in got:
        p = list(res.input_tokens)
        alone.op_state = jax.tree.map(jnp.zeros_like, alone.op_state)
        toks = np.asarray(p + list(res.output_tokens))[:-1]
        logits, _ = family.drive(alone, toks, [1] * len(toks))
        assert res.output_tokens == logits[len(p) - 1:].argmax(-1).tolist()
    snap = tel.registry.snapshot()

    def tails(phase, source):
        key = f'ffsv_cca_tails_total{{phase="{phase}",source="{source}"}}'
        return snap[key]["value"]

    # a prompt of n tokens is prefilled but its last, in chunks of 16: the
    # 70-token prompt is 69 tokens in five segments, the first from zeros,
    # and with four segments a step at least three continue another of
    # their step; the 9-token one is one segment; the 1-token one has none
    assert tails("prefill", "start") == 2
    assert tails("prefill", "step") + tails("prefill", "state") == 4
    assert tails("prefill", "step") >= 3
    # its one token is decoded at position 0: from zeros
    assert tails("decode", "start") == 1
    # every row-step of the decode blocks (a block's steps are its
    # longest row's: a row that is done rides along, and is counted)
    assert tails("decode", "state") + 1 == \
        snap["ffsv_decode_steps_total"]["value"] >= 3 * new
    lens = [len(p) + j for p in prompts for j in range(new)]
    assert snap['ffsv_attn_positions_read_total{kind="full"}'][
        "value"] >= 2 * sum(lens)
    kinds = m.attention_kinds["full"]
    assert snap['ffsv_kv_cache_bytes{kind="full"}']["value"] == \
        kinds["cache_bytes"]
    assert snap['ffsv_kv_cache_bytes{kind="tail"}']["value"] == \
        kinds["tail_bytes"]


# ---------------------------------------------------------------------------
# (vii) a synthetic checkpoint under the HF_KEYS names
# ---------------------------------------------------------------------------

def test_the_decode_block_streams_by_the_block_and_prefill_by_the_loop(
        bench, monkeypatch):
    """Served with the kernels interpreted, in the cell's dtype: the decode
    block's trace takes the k/v kernel's BLOCK form (2 key/value heads: a
    partition's descriptor is 64 KB, a DMA block is eight of them), the
    compact prefill step's 256 query rows a key/value head the LOOP (1 MiB
    of scores a block would be 2 MiB here), one trace a layer each, and the
    tokens are those of the jnp path."""
    import flexflow_tpu.kernels as ffk

    def serve():
        m, _ = _build(tiny=dict(TINY, num_attention_heads=8),
                      max_sequence_length=2048, max_tokens_per_batch=256,
                      compute_dtype="bfloat16", kv_cache_dtype="bfloat16")
        rm = RequestManager()
        rm.register_new_request([int(t) for t in _tokens(150, seed=51)],
                                max_new_tokens=6)
        return [r.output_tokens for r in rm.generate_incr_decoding(m)]

    plain = serve()
    monkeypatch.setenv("FF_PALLAS_INTERPRET", "1")
    ffk.reset_dispatch_stats()
    assert serve() == plain
    assert not ffk.fallback_counts
    assert ffk.stream_form_counts == {("block", "append", 1024): 2,
                                      ("loop", "rows", 128): 2}
    assert ffk.stream_summary() == (
        "k/v kernel: block form of 1024, append: 2 traces; loop form of "
        "128, rows: 2 traces")
    ffk.reset_dispatch_stats()
    assert not ffk.stream_form_counts
    assert ffk.stream_summary() == "k/v kernel: 0 traces"


def test_hf_weight_map_loads_a_synthetic_checkpoint_with_one_table(bench):
    """A state dict under the names ``models/zaya.HF_KEYS`` lists (torch
    layouts: ``[out, in]`` Linears, one a projection an expert, Conv1d
    weights ``[C, 1, 2]`` and ``[C, D, 2]``), with NO ``lm_head.weight``:
    loaded through the family, the program's logits are the reference's on
    the same checkpoint read directly, the head reading the embedding's
    array; and the family's way back from the served weights is the
    checkpoint."""
    family, reference = bench
    fam = family_for_hf_config({"model_type": "zaya"})
    assert fam is FAMILIES["zaya"] and fam.name == "zaya"
    m, c = _build()
    E, H, G, D = (c.hidden_size, c.num_attention_heads,
                  c.num_key_value_heads, c.head_dim)
    C_, Dv, R, n, I = ((H + G) * D, G * D // 2, c.router_hidden_size,
                       c.num_experts, c.moe_intermediate_size)
    rng = np.random.default_rng(4)

    def f(*s, scale=0.08):
        return (rng.standard_normal(s) * scale).astype(np.float32)

    sd = {"model.embed_tokens.weight": f(c.vocab_size, E),
          "model.norm.weight": 1 + f(E)}
    layers = []
    for i in range(c.num_hidden_layers):
        p = f"model.layers.{i}"
        lw = {"ln1": 1 + f(E), "ln2": 1 + f(E), "wq": f(E, H * D),
              "wk": f(E, G * D), "wv1": f(E, Dv), "wv2": f(E, Dv),
              "wo": f(H * D, E), "conv0_w": f(2, C_, scale=0.5),
              "conv0_b": f(C_), "conv1_w": f(H + G, 2, D, D, scale=0.2),
              "conv1_b": f(C_), "tau": 1 + f(G),
              "res_attn": np.stack([1 + f(E), f(E), 1 + f(E), f(E)]),
              "res_mlp": np.stack([1 + f(E), f(E), 1 + f(E), f(E)]),
              "wd": f(E, R), "rn": 1 + f(R), "w1": f(R, R, scale=0.3),
              "w2": f(R, R, scale=0.3), "w3": f(R, n + 1, scale=1.0),
              "bias": f(n + 1, scale=0.01), "gate": f(n, E, I),
              "up": f(n, E, I), "down": f(n, I, E)}
        if i:
            lw["gamma"] = 0.3
            sd[f"{p}.mlp.router.eda_gamma"] = np.array([0.3], np.float32)
        layers.append(lw)
        sd.update({
            f"{p}.input_layernorm.weight": lw["ln1"],
            f"{p}.post_attention_layernorm.weight": lw["ln2"],
            **{f"{p}.self_attn.{hf}.weight": lw[w].T for hf, w in (
                ("q_proj", "wq"), ("k_proj", "wk"), ("v1_proj", "wv1"),
                ("v2_proj", "wv2"), ("o_proj", "wo"))},
            # torch Conv1d: depthwise [C, 1, k]; grouped [C_out, D_in, k]
            f"{p}.self_attn.conv0.weight": lw["conv0_w"].T[:, None, :],
            f"{p}.self_attn.conv0.bias": lw["conv0_b"],
            f"{p}.self_attn.conv1.weight":
                lw["conv1_w"].transpose(0, 3, 2, 1).reshape(C_, D, 2),
            f"{p}.self_attn.conv1.bias": lw["conv1_b"],
            f"{p}.self_attn.temperature": lw["tau"],
            **{f"{p}.{sub}.res_scale.{v}": lw[short][j]
               for sub, short in (("self_attn", "res_attn"),
                                  ("mlp", "res_mlp"))
               for j, v in enumerate("abcd")},
            f"{p}.mlp.router.down_proj.weight": lw["wd"].T,
            f"{p}.mlp.router.norm.weight": lw["rn"],
            **{f"{p}.mlp.router.fc{j}.weight": lw[f"w{j}"].T
               for j in (1, 2, 3)},
            f"{p}.mlp.router.balancing_bias": lw["bias"],
            **{f"{p}.mlp.experts.{e}.{proj}_proj.weight": lw[proj][e].T
               for e in range(n) for proj in ("gate", "up", "down")}})
    assert "lm_head.weight" not in sd
    loaded = fam.load_hf(m, fam.config_cls(**TINY), sd)
    assert loaded == len(fam.hf_weight_map(c)) and not any(
        "lm_head" in k for k in fam.hf_weight_map(c))
    assert "lm_head" not in m.params or not m.params["lm_head"]
    toks = _tokens(24, seed=9)
    ours, _ = family.drive(m, toks, [[16], [4]] + [1] * 4)
    ref, _ = reference.forward_routed(
        {"emb": sd["model.embed_tokens.weight"], "layers": layers,
         "norm": sd["model.norm.weight"]}, toks, REF_CFG)
    assert _rel(ours, np.asarray(ref)) < TOL
    back = list(family.reference_weights(m, c)["layers"])[1]
    for name, want in layers[1].items():
        np.testing.assert_allclose(back[name], want, rtol=1e-6, atol=1e-6,
                                   err_msg=name)


# ---------------------------------------------------------------------------
# (viii) the yardstick's arithmetic, the traffic file, the rehearsal
# ---------------------------------------------------------------------------

def test_the_router_barrier_is_a_graph_step_of_its_own():
    """One barrier a layer, behind the router's ``fc2`` (XLA:TPU of libtpu
    0.0.34 cannot compile the prefill step without it; ROADMAP R7 (e) says
    when it goes): a step of the model's graph that lowers to an
    optimisation barrier, and no option of ``dense``."""
    import inspect

    from flexflow_tpu.models.zaya import RouterBarrier

    m, c = _build()
    steps = [ly for ly in m.layers if ly.op_type == RouterBarrier.op_type]
    assert [ly.name for ly in steps] == [
        f"layers.{i}.mlp.router.barrier" for i in range(c.num_hidden_layers)]
    for ly in steps:
        assert ly.inputs[0].owner_layer.name == ly.name.replace(
            "barrier", "fc2")
        assert not ly.weights
    x = jnp.arange(6.0).reshape(2, 3)
    (y,) = RouterBarrier.forward({}, {}, [x], None)
    assert np.array_equal(np.asarray(y), np.asarray(x))
    text = jax.jit(lambda t: RouterBarrier.forward({}, {}, [t], None)[0]
                   ).lower(x).as_text()
    assert "optimization_barrier" in text
    assert "fence" not in inspect.signature(ff.FFModel.dense).parameters


@pytest.mark.parametrize("what", ["arithmetic", "traffic_file",
                                  "selfcheck", "traced_rehearsal"])
def test_the_cell_its_traffic_and_the_arithmetic_of_its_bytes(
        bench, what, monkeypatch, capsys):
    family, _ = bench
    monkeypatch.syspath_prepend(ROOT)
    for key in ("JAX_PLATFORMS", "FF_PALLAS_INTERPRET"):
        monkeypatch.setenv(key, os.environ.get(key, ""))   # restored after
    with open(os.path.join(ROOT, "benchmark/configs/zaya1-8b.json")) as f:
        cfg = json.load(f)
    if what == "arithmetic":
        # ISSUE 50's own figures, from the configuration file's sizes
        assert family.cache_position_bytes(cfg) == 1024
        assert family.cache_bytes_per_token(cfg) == 10 * 1024
        assert abs(family.expert_bytes(cfg) - 12.58e6) < 0.03e6
        dense = sum(r * c * e for _, r, c, e in family.dense_weights(cfg))
        table = 262272 * 2048
        per_layer = (dense - table) / 10
        assert 6.0e6 < per_layer < 7.5e6        # "6M a layer", int8 + scales
        # a step that touches 9.9 experts a layer over 15 rows of 5.9k: the
        # issue's "about 5.0 GB" at 20 layers, here at the 10 that are held
        need = family.decode_step_must_read(cfg, 9.9, 15 * 5900 * 10)
        assert abs(need - (dense + 9.9 * 10 * family.expert_bytes(cfg)
                           + 15 * 5900 * 10 * 1024)) < 1
        assert 2.6e9 < need < 2.9e9
        twenty = dict(cfg, num_hidden_layers=20)
        assert 4.8e9 < family.decode_step_must_read(
            twenty, 9.9, 15 * 5900 * 20) < 5.2e9
        # and it never counts all 16 experts
        full = sum(r * c * e for _, r, c, e in family.decode_weights(cfg))
        assert abs(full - dense - 16 * 10 * family.expert_bytes(cfg)) < 1
        assert family.decode_step_must_read(cfg, 16, 0) == pytest.approx(full)
        assert family.layers_of(cfg, "sparse") == 10
        assert family.pair_flops(cfg) == 6 * 2048 * 2048
        return
    from benchmark import run, selfcheck
    from benchmark.lib import traffic as T

    if what == "traffic_file":
        t = T.load_traffic(os.path.join(
            ROOT, "benchmark/traffic/long-context-reasoning.json"))
        assert (t["loop"], t["clients"], t["warmup_s"], t["prompt_pool"],
                t["seed_step"], t["tokens_seed"]) == (
                    "closed", 20, 10, 4, "cycle", 20261003)
        assert t["cycle"] == [
            [2048, 768], [4096, 512], [8192, 1024], [3072, 768],
            [6144, 512], [12288, 1024], [2048, 512], [4096, 1024],
            [8192, 768], [3072, 1024], [6144, 768], [4096, 512]]
        assert max(p + o for p, o in t["cycle"]) == 13312 < \
            cfg["assumed"]["max_sequence_length"]
        return
    if what == "selfcheck":
        # the benchmark's own check of its files covers the new ones
        assert selfcheck.every_entry_resolves_to_its_files()
        assert selfcheck.length_cycles_do_not_depend_on_the_seed()
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            b = json.load(f)
        cell = "zaya1-8b.long-context-reasoning"
        mine = {m["name"] for m in b["per_layer"]
                if cell in m.get("workloads", ())}
        assert {"decode_cca_hbm_roofline", "attn_kv_hbm_roofline",
                "cca_tail_step_share", "moe_local_mxu_roofline",
                "experts_touched", "device_idle"} <= mine
        assert not {"decode_hbm_roofline", "moe_local_hbm_roofline"} & mine
        assert cfg["reduced"] == ["num_hidden_layers"] and \
            cfg["published"] == {"num_hidden_layers": 40}
        return
    from flexflow_tpu import kernels as ffk
    from flexflow_tpu.kernels import moe as K

    ffk.reset_dispatch_stats()      # what the tests before this one traced
    K.reset_dispatch_stats()
    rc = run.main(["--workload", "zaya1-8b.long-context-reasoning",
                   "--seed", "3000000019", "--seconds", "4", "--trace", "1",
                   "--rehearse"])
    out = capsys.readouterr().out
    last = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and last["correct"] and last["rehearsal"], out[-2000:]
    said = [ln for ln in out.splitlines() if "REHEARSAL" in ln][0]
    assert '"cca_tail_step_share"' in said
