"""EvaByte on the serving path, at tiny sizes on the CPU: chunked (EVA)
attention over a cache of two extents in one stream (a summary row a chunk,
then the window's rows, which tumble).

The owner's two-extent arithmetic; the attention kernel (interpreted)
against the jnp oracle over both extents in its decode, row-map and plain
forms; the summariser (kernel and jnp) against the reference's pools; the
program, through the compact prefill and cached decode across window
boundaries with a prompt that ends inside a chunk, against the plain
reference, and the four things the model is not each failing the same
tolerance; the scheduler's one-window-a-step rule and the builder's
refusal of sizes that could straddle; what refuses a chunked cache; the
loop, its tokens and the host's counts; the configuration file against the
catalog; the cell's rehearsal.
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
from flexflow_tpu.kernels.attention import (flash_attend, pool_chunk,
                                            reference_attend,
                                            summarise_chunks,
                                            supports_chunked)
from flexflow_tpu.models import FAMILIES
from flexflow_tpu.models.evabyte import EvaByteConfig, create_evabyte_model
from flexflow_tpu.ops import inc_attention as IA
from flexflow_tpu.ops import kv_layout as kvl
from flexflow_tpu.serve.request_manager import Request, RequestManager

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

TINY = dict(vocab_size=64, hidden_size=64, intermediate_size=96,
            num_hidden_layers=2, num_attention_heads=2,
            num_key_value_heads=2, window_size=32, chunk_size=4)
# what reference/evabyte.forward reads of a configuration file
REF_CFG = dict(TINY, rms_norm_eps=1e-5, rope_theta=100000.0)
# float32 program against the float32 reference: rounding only
TOL = 1e-4


@pytest.fixture(scope="module")
def bench():
    """The benchmark's family and reference for EvaByte, loaded as run.py
    loads them."""
    sys.path.insert(0, ROOT)
    try:
        from benchmark.run import load_module

        yield (load_module("families", "evabyte"),
               load_module("reference", "evabyte"))
    finally:
        sys.path.remove(ROOT)


def _build(mode=InferenceMode.INC_DECODING_MODE, tiny=TINY, **ffkw):
    kw = dict(max_requests_per_batch=4, max_sequence_length=128,
              max_tokens_per_batch=32, seed=3, compute_dtype="float32",
              kv_cache_dtype="float32", num_devices=1)
    kw.update(ffkw)
    m = ff.FFModel(ff.FFConfig(**kw))
    create_evabyte_model(m, EvaByteConfig(**tiny), mode=mode,
                         data_type=ff.DataType.DT_FLOAT)
    m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    # the norm offsets start at zero: drawn here, so that a fold of 1 + g
    # into the wrong place would show
    rng = np.random.default_rng(1)
    for name, lp in m.params.items():
        if name.endswith("layernorm") or name == "model.norm":
            lp["weight"] = jnp.asarray(
                rng.normal(0, 0.2, lp["weight"].shape), jnp.float32)
    return m


# ---------------------------------------------------------------------------
# ops/kv_layout.py: two extents in one stream
# ---------------------------------------------------------------------------

def test_two_extents_in_one_stream():
    S, c, W = 24576, 16, 2048
    ns = kvl.chunked_summary_rows(S, c)
    assert ns == 1536
    assert kvl.chunked_cache_shape(4, 32, S, c, W, 128) == (4, 32, 3584, 128)
    pos = np.array([0, 2047, 2048, 4095, 24575])
    np.testing.assert_array_equal(
        kvl.chunked_window_row(pos, ns, W), ns + np.array([0, 2047, 0, 2047,
                                                           2047]))
    src, dst = kvl.chunked_chunk_rows(np.array([15, 2047, 2063]), ns, W, c)
    np.testing.assert_array_equal(src, ns + np.array([0, 2032, 0]))
    np.testing.assert_array_equal(dst, [0, 127, 128])
    # a decode step at position 24575, a prefill segment of 128 from 4096,
    # a row that sits out
    lengths = jnp.array([24576, 4224, 0])
    qpos = jnp.array([[24575] * 128, list(range(4096, 4224)), [0] * 128])
    summaries, stored_len, stored_q = kvl.chunked_view(lengths, qpos, ns, W,
                                                       c)
    np.testing.assert_array_equal(summaries, [1408, 256, 0])
    np.testing.assert_array_equal(stored_len, [ns + 2048, ns + 128, 0])
    np.testing.assert_array_equal(stored_q[0], [ns + 2047] * 128)
    np.testing.assert_array_equal(stored_q[1], ns + np.arange(128))
    rows = np.asarray(kvl.chunked_key_rows(summaries, ns, ns + W))
    assert (rows[0, :1408] == np.arange(1408)).all()
    assert (rows[0, 1408:ns] < 0).all() and (rows[2, :ns] < 0).all()
    assert (rows[:, ns:] == np.arange(ns, ns + W)).all()
    # what a step reads by the visibility rule is what the family counts
    seen = ((rows >= 0) & (rows <= np.asarray(stored_q)[:, :1])
            & (rows < np.asarray(stored_len)[:, None])).sum(1)
    np.testing.assert_array_equal(seen, [1408 + 2048, 256 + 1, 0])
    cache = jnp.arange(2 * 3 * (ns + W) * 2).reshape(2, 3, ns + W, 2)
    got = kvl.read_chunked(cache, 2050, 2060, ns, W, at=(1,))
    np.testing.assert_array_equal(got, cache[1, :, ns + 2:ns + 12])
    with pytest.raises(AssertionError):
        kvl.read_chunked(cache, 2040, 2050, ns, W)      # two windows


# ---------------------------------------------------------------------------
# kernels/attention.py: the attend over both extents, the summariser
# ---------------------------------------------------------------------------

# a stream of 128 summary rows and a window of 128: chunk 16 of 2048
# positions (a supported tiling at the smallest sizes)
KS, KC, KW = 2048, 16, 128
KNS = KS // KC


def _stream(R=3, KH=2, D=128, seed=0):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(size=(R, KH, KNS + KW, D)), jnp.float32)
            for _ in range(2)]


def _oracle(q, k, v, lengths, qpos):
    summaries, ln, qp = kvl.chunked_view(lengths, qpos, KNS, KW, KC)
    return reference_attend(
        q, k, v, ln, qp, key_pos=kvl.chunked_key_rows(summaries, KNS,
                                                      KNS + KW))


@pytest.mark.parametrize("form", ["plain", "rows", "append"])
def test_chunked_flash_attend_matches_the_oracle(form):
    """Interpreted, ragged: a row in window 0 (no summary), a row deep in
    the slot (all 128 summary rows less its own window's), a row that sits
    out; the row map reads another slot's stream; the fused append lands at
    the stored row of its position, also where that is the window's first."""
    assert supports_chunked(KNS + KW, KNS, 128)
    assert not supports_chunked(KNS + KW, 64, 128)
    k, v = _stream()
    rng = np.random.default_rng(1)
    if form == "append":
        Q, pos = 1, np.array([37, 1920, 0])         # 1920 = 15 x 128
        lengths = jnp.array([38, 1921, 0])
        qpos = jnp.asarray(pos)[:, None]
    else:
        Q, lengths = 8, jnp.array([40, 2000, 0])
        qpos = jnp.array([np.arange(32, 40), np.arange(1992, 2000),
                          np.arange(8)])
    q = jnp.asarray(rng.normal(size=(3, Q, 2, 128)), jnp.float32)
    summaries, ln, qp = kvl.chunked_view(lengths, qpos, KNS, KW, KC)
    np.testing.assert_array_equal(summaries, [0, 8 * 15, 0])
    kw = dict(summaries=summaries, summary_rows=KNS, interpret=True)
    if form == "plain":
        got = flash_attend(q, k, v, ln, qp, **kw)
        want = _oracle(q, k, v, lengths, qpos)
    elif form == "rows":
        rows = jnp.array([1, 0, 2])
        got = flash_attend(q, k, v, ln, qp, None, None, None, rows, **kw)
        want = _oracle(q, k[rows], v[rows], lengths, qpos)
    else:
        new = [jnp.asarray(rng.normal(size=(3, 1, 2, 128)), jnp.float32)
               for _ in range(2)]
        appos = jnp.where(lengths > 0, kvl.chunked_window_row(
            jnp.asarray(pos), KNS, KW), -1)
        got, k2, v2 = flash_attend(q, k, v, ln, qp, None, None,
                                   (new[0], new[1], appos), **kw)
        at = np.asarray(appos)
        for r in (0, 1):
            k = k.at[r, :, at[r]].set(new[0][r, 0])
            v = v.at[r, :, at[r]].set(new[1][r, 0])
        assert at[1] == KNS                         # the window's first row
        np.testing.assert_array_equal(k2, k)
        np.testing.assert_array_equal(v2, v)
        want = _oracle(q, k, v, lengths, qpos)
    np.testing.assert_allclose(got[:2], want[:2], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("path", ["kernel", "jnp"])
def test_the_summariser_is_the_references_pool(path, bench, monkeypatch):
    """A decode step that appended a chunk's last position pools the
    chunk's rows of the window extent into the chunk's summary row; a row
    in the middle of a chunk and a row that wrote nothing touch nothing."""
    _, reference = bench
    R, KH, D = 3, 2, 128
    k, v = [x[None] for x in _stream(R, KH, D, seed=2)]     # a stack of 1
    rng = np.random.default_rng(3)
    mu, phi = [jnp.asarray(rng.normal(size=(KH, D)), jnp.float32) * 0.2
               for _ in range(2)]
    pos = jnp.array([1935, 45, 63])      # ends chunk 120; mid-chunk; idle
    wrote = jnp.array([True, True, False])
    attrs = {"max_seq_length": KS, "eva_window": KW, "chunk_size": KC}
    params = {"adaptive_mu_k": mu, "adaptive_phi": phi}
    if path == "kernel":
        monkeypatch.setenv("FF_PALLAS_INTERPRET", "1")
    k2, v2 = IA.summarise_decode(attrs, params, k, v, 0, pos, wrote, None)
    src = KNS + 1935 % KW // KC * KC
    assert (src, 1935 // KC) == (KNS + 0, 120)
    kc, vc = k[0, 0, :, src:src + KC], v[0, 0, :, src:src + KC]
    kbar, vbar = reference.summaries(
        kc.transpose(1, 0, 2), vc.transpose(1, 0, 2), mu, phi, KC)
    np.testing.assert_allclose(k2[0, 0, :, 120], kbar[0], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(v2[0, 0, :, 120], vbar[0], rtol=1e-5,
                               atol=1e-5)
    for new, old in ((k2, k), (v2, v)):
        new = np.array(new)
        new[0, 0, :, 120] = np.asarray(old)[0, 0, :, 120]
        np.testing.assert_array_equal(new, old)
    # and the prefill form pools a run's fresh keys to the same pairs
    run_k = kc.transpose(1, 0, 2)[None]                     # [1, c, KH, D]
    run_v = vc.transpose(1, 0, 2)[None]
    pk, pv, n = IA.summarise_run(attrs, params, run_k, run_v,
                                 jnp.array([KC]))
    assert int(n[0]) == 1
    np.testing.assert_allclose(pk[0, 0], kbar[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(pv[0, 0], vbar[0], rtol=1e-5, atol=1e-5)
    # the pool is a softmax, not the mean it starts from at zero vectors
    zk, _ = pool_chunk(kc, vc, jnp.zeros_like(mu), phi)
    np.testing.assert_allclose(zk, kc.mean(1), rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(zk) - np.asarray(kbar[0])).max() > 0.05


# ---------------------------------------------------------------------------
# the program against the plain reference
# ---------------------------------------------------------------------------

# a prompt that ends inside a chunk of 4 (74 = 18 x 4 + 2) in window 2, and
# decoded positions that cross into window 3 at 96: three boundaries in
# prefill and decode together
PROMPT, DECODED = 74, 30


@pytest.fixture(scope="module")
def served(bench):
    family, reference = bench
    m = _build()
    toks = np.random.default_rng(5).integers(1, 64, size=PROMPT + DECODED)
    ours = family.served_logits(m, toks, PROMPT)
    weights = family.reference_weights(m, 2)
    return ours, weights, toks


@pytest.mark.parametrize("wrong", [None, "float8", "early", "mean",
                                   "swapped"])
def test_served_logits_match_the_reference_and_nothing_else(bench, served,
                                                            wrong):
    """Through the compact prefill (four segments a step, a window a step)
    and 30 decode steps: the logits at the prompt's last position and at
    every decoded one are the reference's; the reference with float8 matmul
    inputs, with a window's summaries visible a window early, with a mean
    for the pool, with mu and phi exchanged, is each far outside the same
    tolerance."""
    family, reference = bench
    ours, weights, toks = served
    kw = ({} if wrong is None else
          {"matmul_dtype": jnp.float8_e4m3fn} if wrong == "float8" else
          {"wrong": wrong})
    ref = np.asarray(reference.forward(weights, toks, REF_CFG, **kw))
    out = family.C.compare_logits(ours, ref[PROMPT - 1:], TOL)
    assert out["positions"] == DECODED + 1
    if wrong is None:
        assert out["ok"], out
    else:
        assert out["max_rel_l2"] > 100 * TOL, out


def test_the_kernels_serve_the_program_interpreted(bench, served,
                                                   monkeypatch):
    """The same comparison with the Pallas kernels interpreted, at sizes the
    kernels tile (window 128, a slot of 2048): attend and summariser both
    take the kernel path, and none falls back."""
    from flexflow_tpu import kernels as ffk

    family, reference = bench
    monkeypatch.setenv("FF_PALLAS_INTERPRET", "1")
    tiny = dict(TINY, window_size=128, chunk_size=16, hidden_size=256,
                num_attention_heads=2, num_key_value_heads=2)
    ffk.reset_dispatch_stats()
    m = _build(tiny=tiny, max_sequence_length=2048, max_tokens_per_batch=256)
    assert m.op_state[IA.CHUNKED_STACK]["k"].shape == (2, 4, 2, 256, 128)
    toks = np.random.default_rng(6).integers(1, 64, size=250 + 10)
    ours = family.served_logits(m, toks, 250)
    ref = np.asarray(reference.forward(
        family.reference_weights(m, 2), toks,
        dict(REF_CFG, **tiny)))[249:]
    assert family.C.compare_logits(ours, ref, TOL)["ok"]
    assert ffk.fast_path_count > 0 and not ffk.fallback_counts


# ---------------------------------------------------------------------------
# a step's segments of one slot lie in one window
# ---------------------------------------------------------------------------

def _filling(slot, n_tokens, depth):
    req = Request(guid=slot, prompt_tokens=list(range(n_tokens)),
                  max_new_tokens=4)
    req.slot, req.cache_depth = slot, depth
    return req


def test_a_steps_segments_of_a_slot_lie_in_one_window():
    """Without the rule a slot three segments short of a boundary is given
    four; with it the fourth goes to the next step, the spare segment to
    another request, and a segment that begins off the grid is cut at the
    boundary."""
    a, b = _filling(0, 500, 2048 - 3 * 128), _filling(1, 3000, 2048 + 128)
    a.tokens = list(range(4000))
    a.prefill_start_s, b.prefill_start_s = 1.0, 2.0
    depth = lambda r: r.cache_depth
    plain = RequestManager._prefill_rows([a, None], 128, depth, 4)
    assert [sp for _, _, sp in plain] == [1664, 1792, 1920, 2048]
    rows = RequestManager._prefill_rows([a, None], 128, depth, 4,
                                        window=2048)
    assert [sp for _, _, sp in rows] == [1664, 1792, 1920]
    rows = RequestManager._prefill_rows([a, b], 128, depth, 4, window=2048)
    assert [(s, sp) for s, _, sp in rows] == [
        (0, 1664), (1, 2176), (0, 1792), (1, 2304)]
    a.cache_depth = 2000
    rows = RequestManager._prefill_rows([a, None], 128, depth, 4,
                                        window=2048)
    assert [(sp, len(t)) for _, t, sp in rows] == [(2000, 48)]


@pytest.mark.parametrize("sizes,ok", [
    (dict(window_size=32, chunk_size=4), True),
    (dict(window_size=32, chunk_size=3), False),    # chunks tile no window
    (dict(window_size=24, chunk_size=4), False),    # a segment of 8 tiles it
                                                    # but windows no slot
    (dict(window_size=12, chunk_size=4), False),    # a segment of 8 straddles
    (dict(window_size=32, chunk_size=16), False),   # a segment of 8 ends
                                                    # inside a chunk
])
def test_the_builder_refuses_sizes_that_could_straddle(sizes, ok):
    if ok:
        _build(tiny=dict(TINY, **sizes))
        return
    with pytest.raises(NotImplementedError, match="ONE window"):
        _build(tiny=dict(TINY, **sizes))


# ---------------------------------------------------------------------------
# what does not support a chunked cache says so
# ---------------------------------------------------------------------------

def _refusal(name):
    if name in ("tree_verify_mode", "beam_search_mode"):
        mode = (InferenceMode.TREE_VERIFY_MODE if name == "tree_verify_mode"
                else InferenceMode.BEAM_SEARCH_MODE)
        return lambda: _build(mode=mode)
    if name == "tensor_parallel_mesh":
        return lambda: _build(tensor_parallelism_degree=2, num_devices=2)
    if name == "pipeline_plan":
        return lambda: _build(pipeline_parallelism_degree=2, num_devices=2)
    if name == "grouped_heads":
        return lambda: EvaByteConfig.from_hf_config(
            dict(TINY, num_key_value_heads=1))
    if name == "another_attention_class":
        return lambda: EvaByteConfig.from_hf_config(
            dict(TINY, attention_class="mha"))
    if name == "tree_op_on_the_builder":
        m = ff.FFModel(ff.FFConfig(max_requests_per_batch=4,
                                   max_sequence_length=128,
                                   max_tokens_per_batch=32))
        x = m.create_tensor([4, 1, 64], ff.DataType.DT_FLOAT)
        return lambda: m._serving_attention(
            ff.OpType.TREE_INC_MULTIHEAD_SELF_ATTENTION, x, 64, 2, 2, 0, 0,
            0.0, False, False, False, None, None, True, False, 1.0, True,
            False, 1e5, None, eva_window=32, chunk_size=4)
    m = _build()
    if name == "commit_tree_kv":
        z = jnp.zeros((4,), jnp.int32)
        return lambda: IA.commit_tree_kv(
            m.op_state, jnp.zeros((4, 3), jnp.int32), z, z, z > 0)
    if name == "speculation_commit":
        return lambda: IA.refuse_windowed(m.op_state, "a speculation commit")
    if name == "prefix_pool":
        from flexflow_tpu.serve import prefix_cache

        return lambda: prefix_cache.extract_prefix_kv(m.op_state, 0, 8, 128)
    raise KeyError(name)


@pytest.mark.parametrize("what,sentence", [
    ("tree_verify_mode", "incremental decoding only"),
    ("beam_search_mode", "incremental decoding only"),
    ("tree_op_on_the_builder", "survives only inside its chunk's summary"),
    ("tensor_parallel_mesh", "chunked attention layer"),
    ("pipeline_plan", "chunked attention layer"),
    ("commit_tree_kv", "cannot be read back, moved or rolled back"),
    ("speculation_commit", "one summary a chunk"),
    ("prefix_pool", "shared-prefix pool is not supported over a chunked"),
    ("grouped_heads", "one pair a head"),
    ("another_attention_class", "attention_class")])
def test_what_cannot_hold_a_chunked_cache_refuses_loudly(what, sentence):
    with pytest.raises(NotImplementedError, match=sentence):
        _refusal(what)()


def test_prefix_pool_refuses_when_a_request_asks_for_it():
    from flexflow_tpu.serve.batch_config import GenerationConfig

    m = _build()
    rm = RequestManager()
    rm.register_new_request(list(range(1, 20)), max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="shared-prefix pool"):
        rm.generate_incr_decoding(m, GenerationConfig(prefix_cache=True))


def test_other_models_layers_carry_none_of_this():
    """Only a chunked layer has the keys: every other model's attention and
    norm attrs, and so its traced programs, are what they were."""
    from flexflow_tpu.models.llama import LLAMAConfig, create_llama_model

    m = ff.FFModel(ff.FFConfig(max_requests_per_batch=2,
                               max_sequence_length=64,
                               max_tokens_per_batch=16))
    create_llama_model(m, LLAMAConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2))
    m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    new = {"eva_window", "chunk_size", "unit_offset"}
    assert not any(new & set(ly.attrs) for ly in m.layers)
    assert all("data_type" not in ly.attrs for ly in m.layers
               if ly.op_type == ff.OpType.RMS_NORM)
    assert IA.CHUNKED_STACK not in m.op_state
    assert getattr(m, "attention_kinds", None) is None


# ---------------------------------------------------------------------------
# the serving loop and the host's counts
# ---------------------------------------------------------------------------

def test_the_loop_serves_it_and_counts_both_extents(bench):
    """Through RequestManager (compact prefill under the one-window rule,
    decode blocks): the tokens are those the two programs give one request
    at a time; the series are a hand count from the lengths."""
    from flexflow_tpu.telemetry import ServingTelemetry

    family, _ = bench
    m = _build(telemetry=True)
    W, c, L = 32, 4, 2
    assert m.attention_kinds == {"chunked": {
        "layers": L, "window": W, "chunk": c,
        "cache_bytes": L * 2 * 4 * 2 * (128 // c + W) * 32 * 4}}
    prompts = [list(np.random.default_rng(i).integers(1, 64, size=n))
               for i, n in enumerate((75, 9, 40))]
    new = 26
    tel = ServingTelemetry()
    calls, note = [], tel.note_attention_reads
    tel.note_attention_reads = lambda kinds, lengths, steps: (
        calls.append((np.array(lengths), steps)),
        note(kinds, lengths, steps))[1]
    rm = RequestManager()
    rm.telemetry = tel
    for p in prompts:
        rm.register_new_request([int(t) for t in p], max_new_tokens=new)
    got = rm.generate_incr_decoding(m)
    assert sorted(len(r.input_tokens) for r in got) == [9, 40, 75]
    alone = _build()
    for res in got:
        p = res.input_tokens
        alone.op_state = jax.tree.map(jnp.zeros_like, alone.op_state)
        toks = np.asarray(list(p) + list(res.output_tokens))
        # the loop prefills all but the prompt's last token
        logits = family.served_logits(alone, toks[:-1], len(p) - 1)
        assert res.output_tokens == logits[1:].argmax(-1).tolist()
    snap = tel.registry.snapshot()
    value = lambda name: snap[name]["value"]
    # the positions each row held after each step of each block (a row
    # that needs fewer steps than its block runs them all the same; the
    # host cuts what it emitted): a step at position q held q + 1
    held = np.concatenate([(ln[:, None] + np.arange(steps)).ravel()
                           for ln, steps in calls])
    assert value("ffsv_decode_steps_total") == len(held) >= 3 * new
    assert {len(p) + j for p in prompts for j in range(new)} <= set(held)
    at = held - 1
    assert value('ffsv_attn_positions_read_total{kind="summary"}') == \
        L * int((at // W * (W // c)).sum())
    assert value('ffsv_attn_positions_read_total{kind="chunk_window"}') == \
        L * int((at % W + 1).sum())
    assert value("ffsv_attn_positions_held_total") == L * int(held.sum())
    assert value('ffsv_kv_cache_bytes{kind="chunked"}') == \
        m.attention_kinds["chunked"]["cache_bytes"]
    assert 'ffsv_attn_positions_read_total{kind="full"}' not in snap
    read = (value('ffsv_attn_positions_read_total{kind="summary"}')
            + value('ffsv_attn_positions_read_total{kind="chunk_window"}'))
    assert read == L * int(family.entries_read(
        {"window_size": W, "chunk_size": c}, held).sum())
    # and every block's span carries its own share of that count
    blocks = {ev["ts"]: ev["args"]["entries"] for ev in tel.tracer.events
              if ev.get("name") == "decode_block"}
    assert len(blocks) == len(calls) and sum(blocks.values()) == read


def test_preemption_moves_nothing_and_keeps_the_tokens():
    """Deadline-aware preemption drops a victim's cache and prefills its
    prompt and what it generated again from position 0: no position is
    moved, so a chunked cache carries it, and the victim's tokens are those
    of an undisturbed run (its decoded chunks are then pooled by the
    prefill program where the decode step pooled them before)."""
    import time

    from flexflow_tpu.serve.loadgen import EngineHandle

    m = _build(max_requests_per_batch=2)
    prompts = [[int(t) for t in np.random.default_rng(i).integers(
        1, 64, size=n)] for i, n in enumerate((45, 38))]
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
# the configuration file, the weight map
# ---------------------------------------------------------------------------

def test_the_configuration_is_the_catalogs_but_for_the_heads(bench):
    family, _ = bench
    with open(os.path.join(ROOT, "benchmark/configs/evabyte-6.5b.json")) as f:
        cfg = json.load(f)
    c = family._model_cfg(cfg)
    assert (c.num_hidden_layers, c.hidden_size, c.intermediate_size,
            c.num_attention_heads, c.vocab_size, c.window_size,
            c.chunk_size) == (32, 4096, 11008, 32, 320, 2048, 16)
    assert cfg["reduced"] == ["num_pred_heads"]
    assert cfg["published"] == {"num_pred_heads": 8}
    with pytest.raises(KeyError, match="not understood"):
        family._model_cfg(dict(cfg, sliding_window=4096))
    with pytest.raises(NotImplementedError, match="fp32_skip_add"):
        family._model_cfg(dict(cfg, fp32_skip_add=False))
    # the arithmetic the file states
    weights = sum(r * k * b for _, r, k, b in family.decode_weights(cfg))
    assert abs(weights / 1e9 - 6.48) < 0.01
    assert family.cache_position_bytes(cfg) == 16384
    assert int(family.entries_read(cfg, 24576)) == 1408 + 2048
    a = cfg["assumed"]
    ns = kvl.chunked_summary_rows(a["max_sequence_length"], 16)
    slot = (ns + 2048) * 16384 * 32
    assert (ns + 2048, round(slot / 1e9, 3)) == (3584, 1.879)
    assert round(a["max_requests_per_batch"] * slot / 1e9, 2) == 7.52
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guides here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "EvaByte")
    differs = {k for k, v in row["config"].items() if cfg.get(k, "-") != v}
    assert differs == set(cfg["reduced"])
    assert cfg["source"] == row["source_url"]


def test_hf_weight_map_keeps_the_first_head_and_the_pooling_vectors():
    m = _build()
    c = EvaByteConfig(**TINY)
    rng = np.random.default_rng(0)
    wmap = FAMILIES["evabyte"].hf_weight_map(c)
    sd = {}
    for key, (layer, w, transpose) in wmap.items():
        shape = np.asarray(m.params[layer][w]).shape
        sd[key] = rng.normal(size=shape[::-1] if transpose else shape
                             ).astype(np.float32)
    head = rng.normal(size=(8 * 64, 64)).astype(np.float32)  # eight heads
    sd["lm_head.weight"] = head
    for key in [k for k in sd if k.endswith(("adaptive_mu_k",
                                             "adaptive_phi"))]:
        sd[key] = sd[key].reshape(1, 2, 1, 1, 32)       # as published
    mu = sd["model.layers.1.self_attn.adaptive_mu_k"]
    assert FAMILIES["evabyte"].load_hf(m, c, sd) == len(wmap)
    np.testing.assert_array_equal(m.params["lm_head"]["kernel"],
                                  head[:64].T)
    np.testing.assert_array_equal(
        m.params["model.layers.1.self_attn"]["adaptive_mu_k"], mu[0, :, 0, 0])


# ---------------------------------------------------------------------------
# the cell: its rehearsal, its traffic file
# ---------------------------------------------------------------------------

def test_the_cell_rehearses_and_its_traffic_is_the_issues(monkeypatch,
                                                          capsys):
    """``run.py --rehearse`` of the new cell, traced, so that the reference
    check, the kernels (interpreted) and every reader run; and the traffic
    file through the generator's own loader."""
    monkeypatch.syspath_prepend(ROOT)
    for key in ("JAX_PLATFORMS", "FF_PALLAS_INTERPRET"):
        monkeypatch.setenv(key, os.environ.get(key, ""))   # restored after
    from benchmark import run
    from benchmark.lib import traffic as T

    t = T.load_traffic(os.path.join(
        ROOT, "benchmark/traffic/long-context-bytes.json"))
    assert (t["loop"], t["clients"], t["warmup_s"], t["prompt_pool"],
            t["seed_step"]) == ("closed", 5, 10, 4, "cycle")
    assert t["cycle"] == [[13800, 768], [9500, 1024], [20000, 512],
                          [15000, 768], [16100, 512], [11000, 1024],
                          [18300, 512], [8000, 768]]
    assert sum(p // 2048 != (p + o - 1) // 2048 for p, o in t["cycle"]) == 6
    assert sum(p % 16 != 0 for p, _ in t["cycle"]) == 6
    assert T.Cycle(t, 3000000019, 320).next()[1] == t["cycle"][0][1]
    assert run.main(["--workload", "evabyte-6.5b.long-context-bytes",
                     "--seed", "3000000019", "--seconds", "4", "--trace",
                     "1", "--rehearse"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    res = json.loads(out[-1])
    assert res["correct"] and res["rehearsal"] and not res["failed"]
    said = next(ln for ln in out if ln.startswith("# REHEARSAL"))
    share = json.loads(said.split("result: ")[1])["kv_chunked_read_share"]
    assert 0 < share["value"] < 100
