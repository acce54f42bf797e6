"""SDAR-MoE on the serving path, at tiny sizes on the CPU in float32: a
decode step that fills a block of four positions by diffusion, sees the block
both ways, emits it when it is whole and stores it in the row's next pass,
in front of the next block.

The system's forward (whole blocks through the compact prefill, then passes
of the decode block through the cache) against the plain reference's full
forward, with two deliberately wrong variants that must fail; served tokens
through ``generate_incr_decoding`` against the reference's published loop,
rows out of phase, at thresholds that unmask 1, 2 and 4 positions a pass;
the fold itself: a pass of two blocks against the two passes it replaces, a
whole block carried over a call, a request that ends on the pass that
completes a block, a row at the cache's end; the renormalised expert weights; what refuses such a model; and the
one-token decode block of a dense model, which is the parent's program.
"""

import dataclasses
import functools
import hashlib
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import flexflow_tpu as ff
from flexflow_tpu.ffconst import InferenceMode
from flexflow_tpu.models.sdar_moe import SDARMoEConfig, create_sdar_moe_model
from flexflow_tpu.serve.batch_config import BlockDiffusion, GenerationConfig
from flexflow_tpu.serve.request_manager import RequestManager

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(vocab_size=256, hidden_size=128, moe_intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, num_experts=8,
            num_experts_per_tok=2, mask_token_id=255)
# (confidence threshold, denoising steps) -> positions a denoise pass
# unmasks: the floor of 1 (no pick of these seeded weights clears 0.9), the
# floor of 2, everything at once (every pick clears 0)
SCHEDULES = {1: (0.9, 4), 2: (0.9, 2), 4: (0.0, 4)}


@pytest.fixture(scope="module")
def bench():
    """The benchmark's family and reference for SDAR-MoE, loaded as run.py
    loads them."""
    sys.path.insert(0, ROOT)
    try:
        from benchmark.run import load_module

        yield (load_module("families", "sdar_moe"),
               load_module("reference", "sdar_moe"))
    finally:
        sys.path.remove(ROOT)


def _build(threshold=0.9, steps=4, mode=InferenceMode.INC_DECODING_MODE,
           **ffkw):
    kw = dict(max_requests_per_batch=4, max_sequence_length=128,
              max_tokens_per_batch=32, seed=3, compute_dtype="float32",
              kv_cache_dtype="float32", num_devices=1, decode_block_steps=5)
    kw.update(ffkw)
    m = ff.FFModel(ff.FFConfig(**kw))
    c = SDARMoEConfig(**TINY, confidence_threshold=threshold,
                      denoising_steps=steps)
    create_sdar_moe_model(m, c, mode=mode, data_type=ff.DataType.DT_FLOAT)
    m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    return m, c


def _reference_cfg(c):
    return dict(TINY, rms_norm_eps=c.rms_norm_eps, rope_theta=c.rope_theta,
                assumed=dict(block_length=c.block_length,
                             denoising_steps=c.denoising_steps,
                             confidence_threshold=c.confidence_threshold,
                             mask_token_id=c.mask_token_id))


@pytest.fixture(scope="module")
def served(bench):
    """One compiled model a schedule, with the reference's weights."""
    fam, _ = bench
    out = {}
    for per_pass, (thr, steps) in SCHEDULES.items():
        m, c = _build(thr, steps)
        out[per_pass] = (m, c, fam.reference_weights(m, c.num_hidden_layers))
    return out


# ---------------------------------------------------------------------------
# (a) logits: prefill of whole blocks, a denoise pass, the pass after a commit
# ---------------------------------------------------------------------------

def _two_blocks(bench, served, known, **kw):
    fam, ref = bench
    m, c, w = served[1]
    m.op_state = jax.tree_util.tree_map(jnp.zeros_like, m.op_state)
    toks = np.random.default_rng(known).integers(1, 255, size=24 + known)
    return fam.compare_two_blocks(m, _reference_cfg(c), ref, w,
                                  toks.tolist(), known, 1e-4, 0.0, **kw)


@pytest.mark.parametrize("known", [0, 1, 3])
def test_forward_matches_reference(bench, served, known):
    """Prompts of length 4k, 4k + 1 and 4k + 3: three chunks of whole
    blocks through the compact prefill, the remainder and masks through a
    denoise pass, then the next block's first pass, which reads what the
    commit pass stored."""
    out = _two_blocks(bench, served, known)
    assert out["ok"] and out["route_flips"] == 0, out
    assert out["positions"] == 24 + 4 + 4
    assert max(out["denoise_rel_l2"], out["after_commit_rel_l2"]) < 1e-4


@pytest.mark.parametrize("wrong", ["one_way", "stale_commit"])
def test_wrong_variants_fail(bench, served, wrong):
    """A mask that is causal inside the block (here: the reference computes
    it, so the program's two-way block is what differs), and a cache
    committed from a denoise pass's tokens, are each far outside the check."""
    kw = ({"one_way": True} if wrong == "one_way"
          else {"wrong": "stale_commit"})
    out = _two_blocks(bench, served, 1, **kw)
    assert not out["ok"] and out["max_rel_l2"] > 0.1, out
    if wrong == "stale_commit":         # only the pass that reads the cache
        assert out["denoise_rel_l2"] < 1e-4 < out["after_commit_rel_l2"]


# ---------------------------------------------------------------------------
# (b) served tokens, rows out of phase
# ---------------------------------------------------------------------------

PROMPTS = (8, 9, 11, 3, 10, 5)          # six requests over four slots
NEW = (7, 8, 9, 5, 6, 10)               # most of them no multiple of 4


def _serve(m, prompts, news, eos=None):
    rm = RequestManager(eos_token_id=eos)
    guids = [rm.register_new_request(p, max_new_tokens=n)
             for p, n in zip(prompts, news)]
    rm.generate_incr_decoding(m)
    return [list(rm.results[g].output_tokens) for g in guids]


@pytest.mark.parametrize("per_pass", sorted(SCHEDULES))
def test_served_tokens_match_reference_generate(bench, served, per_pass):
    """Ragged prompts (one shorter than a block), rows that enter at
    different passes (the two last wait for a slot), answers cut inside a
    block: token for token the published loop's."""
    _, ref = bench
    m, c, w = served[per_pass]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 255, size=n).tolist() for n in PROMPTS]
    got = _serve(m, prompts, NEW)
    took = []
    for p, n, g in zip(prompts, NEW, got):
        trace = []
        assert g == ref.generate(w, p, n, _reference_cfg(c), trace=trace)
        assert len(g) == n
        took += [t[1] for t in trace if t[0] == "denoise"]
    # the schedule is the one the case names: most passes unmask that many
    assert np.bincount(took).argmax() == per_pass, took


def test_end_of_sequence_inside_a_block(bench, served):
    """The output ends with the first end-of-sequence token of a committed
    block, though the block was generated whole."""
    _, ref = bench
    m, c, w = served[1]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 255, size=n).tolist() for n in PROMPTS]
    free = [ref.generate(w, p, 9, _reference_cfg(c)) for p in prompts]
    # an id some answer first shows inside a block, not at its end
    eos = next(o[i] for o, p in zip(free, prompts) for i in range(len(o))
               if o.index(o[i]) == i and (len(p) + i) % 4 not in (3,))
    got = _serve(m, prompts, [9] * len(prompts), eos=eos)
    want = [ref.generate(w, p, 9, _reference_cfg(c), eos=eos)
            for p in prompts]
    assert got == want
    assert any(len(g) < 9 and g[-1] == eos for g in got), (eos, got)


# ---------------------------------------------------------------------------
# (b2) the fold: a block that is whole is stored by its row's next pass
# ---------------------------------------------------------------------------

def _watched(m, prompts, news, eos=None):
    """``_serve``, and what every decode block was handed and handed back:
    (live slots, windows staged, stored lengths staged, steps asked,
    BlockPasses)."""
    ifm, calls = RequestManager._manager_of(m), []
    launch, read = ifm.launch_decode_block, ifm.read_decode_block

    def noting(tok, pos, act, n, **kw):     # the loop's two ends of a block
        calls.append((np.flatnonzero(act), tok.copy(), pos.copy(), n))
        return launch(tok, pos, act, n, **kw)

    def handed_back(launched, **kw):
        out = read(launched, **kw)
        calls[-1] += (out,)
        return out

    ifm.launch_decode_block, ifm.read_decode_block = noting, handed_back
    try:
        return _serve(m, prompts, news, eos), calls
    finally:
        del ifm.launch_decode_block, ifm.read_decode_block


def _whole(win):
    return (win[:, :4] >= 0).all(axis=1)


@pytest.mark.parametrize("per_pass", sorted(SCHEDULES))
def test_every_emitted_block_is_stored_by_a_later_pass_or_carried(
        bench, served, per_pass):
    """Over the ragged, out-of-phase run of (b): in every call and every
    live row, the blocks emitted are those a pass of the call stored in
    front of the next, less the one the row came with, plus the one it
    leaves with; the stored length a row is staged at moves by just what
    its passes stored; no pass only stores."""
    m, c, _ = served[per_pass]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 255, size=n).tolist() for n in PROMPTS]
    got, calls = _watched(m, prompts, NEW)
    assert [len(g) for g in got] == list(NEW)
    folded = 0
    for live, tok, pos, n, out in calls:
        came, left = _whole(tok), _whole(out.block)
        st = out.stats
        assert (st["count"][live] // 4
                == st["folded"][live] - came[live] + left[live]).all()
        assert (st["passes"][live] == n).all()
        assert (st["by_threshold"] + st["by_floor"]
                >= st["passes"] * (1 if per_pass == 1 else 0)).all()
        assert (out.stored == 4 * st["folded"]).all()
        folded += int(st["folded"].sum())
    assert folded > 0


def test_a_whole_block_crosses_a_call_and_is_staged_back(bench, served):
    """Two positions a pass, two passes a block, calls of five passes: the
    tenth pass completes the fifth block as the second call ends. The
    request leaves the call with the block in its tokens and not in its
    cache; the third call is handed it whole in front of four masks, at the
    stored length, and its first pass stores it; its last pass completes the
    seventh block, which nothing stores: the request is done."""
    _, ref = bench
    m, c, w = served[2]
    prompt = np.random.default_rng(7).integers(1, 255, size=8).tolist()
    (got,), calls = _watched(m, [prompt], [28])
    assert got == ref.generate(w, prompt, 28, _reference_cfg(c))
    assert [n for *_, n, _ in calls] == [5, 5, 4]
    (_, _, _, _, second), (_, tok, pos, _, third) = calls[1:]
    assert _whole(second.block)[0] and second.count[0] == 12
    assert second.stats["folded"][0] == 2           # blocks three and four
    assert pos[0] == 8 + 16 and tok[0].tolist() == got[16:20] + [-1] * 4
    assert third.stats["folded"][0] == 2 and third.count[0] == 8
    assert _whole(third.block)[0]


@pytest.mark.parametrize("how", ["max_new_tokens", "eos"])
def test_request_that_ends_on_the_completing_pass_needs_no_more(
        bench, served, how):
    """The pass that leaves the block whole emits it: a request whose last
    token (or end-of-sequence) is in that block is done when the call
    returns, four passes for four tokens at the floor of one, and nothing
    stores the block: nothing will read that row's cache."""
    _, ref = bench
    m, c, w = served[1]
    rng = np.random.default_rng(11)
    while True:     # an answer whose fourth token it has not shown before
        prompt = rng.integers(1, 255, size=8).tolist()
        free = ref.generate(w, prompt, 12, _reference_cfg(c))
        if free[3] not in free[:3]:
            break
    eos = free[3] if how == "eos" else None
    (got,), calls = _watched(m, [prompt], [4 if eos is None else 12], eos)
    assert got == free[:4] and len(calls) == 1
    (_, _, _, n, out), = calls
    if eos is None:
        assert n == 4 and out.stats["passes"][0] == 4
        assert out.stats["folded"][0] == 0 and _whole(out.block)[0]
    assert out.count[0] >= 4 and out.stats["by_floor"][0] >= 4


def test_row_one_block_short_of_the_caches_end(bench, served):
    """A prompt that leaves two blocks of the cache's 128 positions: the
    first is stored by the pass that begins the second, at [120, 128); the
    second is emitted and the row then sits out (a pass of two blocks would
    write past the end) while the other row goes on in wide passes; the
    request ends at the cache's length."""
    _, ref = bench
    m, c, w = served[1]
    rng = np.random.default_rng(13)
    prompts = [rng.integers(1, 255, size=n).tolist() for n in (120, 9)]
    got, calls = _watched(m, prompts, [20, 14])
    want = [ref.generate(w, p, n, _reference_cfg(c))
            for p, n in zip(prompts, (8, 14))]
    assert got == want
    slot = next(s for live, tok, pos, _, _ in calls
                for s in live if pos[s] == 120)
    last = [out for live, _, pos, _, out in calls if slot in live][-1]
    assert _whole(last.block)[slot]
    assert sum(out.stats["folded"][slot] for live, _, pos, _, out in calls
               if slot in live and pos[slot] >= 120) == 1


def _rel_l2(a, b):
    return float((np.linalg.norm(a - b, axis=-1)
                  / np.linalg.norm(b, axis=-1)).max())


@pytest.mark.parametrize("stale", [False, True])
def test_wide_pass_is_the_two_passes_it_replaces(bench, served, stale):
    """The decode block's own pass (engine.diffusion_pass), slot 0 with a
    whole block at [16, 20) in front of four masks, slot 1 with a ragged
    block at [8, 12) and nothing carried: ONE pass of 2B tokens against a
    B-wide pass over the whole block followed by the next block's first
    pass (the two forwards the commit and the denoise pass used to be). The new block's logits, both rows', and the keys and values of
    [16, 20) in every layer are the same; slot 1's cache past its B real
    tokens is not written. ``stale``: the cache keeps what the block's last
    denoise pass wrote (one position still masked): far outside."""
    from flexflow_tpu.serve.batch_config import BatchMeta
    from flexflow_tpu.serve.engine import diffusion_pass, forward_with_meta
    from flexflow_tpu.serve.request_manager import RequestManager as RM

    fam, _ = bench
    m, c, _ = served[1]
    m.op_state = jax.tree_util.tree_map(jnp.zeros_like, m.op_state)
    rng = np.random.default_rng(17)
    run = fam.Passes(m)
    run.prefill(rng.integers(1, 255, size=16).tolist())
    chunk, segments = RM._prefill_shape(m.config)
    run._step(RM._meta_from_segments(
        segments, chunk, [(1, rng.integers(1, 255, size=8).tolist(), 0)]),
        False, 8)
    logits_t = m.layers[-1].inputs[0]
    wide = functools.partial(jax.jit(
        lambda params, st, win, pos, act, carried: diffusion_pass(
            m, params, st, win, pos, act, carried, None, jnp.float32,
            outputs=[logits_t])), m.params)

    @jax.jit
    def b_wide(params, st, blk, pos, act):      # the pass of one block
        meta = BatchMeta(
            tokens=jnp.where(blk < 0, c.mask_token_id, blk),
            positions=pos[:, None] + jnp.arange(4), start_pos=pos,
            num_tokens=4 * act.astype(jnp.int32), active=act)
        return forward_with_meta(m, params, st, meta, None, jnp.float32,
                                 phase="decode", outputs=[logits_t])

    block = rng.integers(1, 255, size=4).tolist()
    ragged = [int(rng.integers(1, 255)), -1, -1, -1]
    masks = [-1] * 4
    rows = lambda *r: jnp.asarray(list(r) + [masks * (len(r[0]) // 4)] * 2,
                                  jnp.int32)
    only = lambda *slots: jnp.isin(jnp.arange(4), jnp.asarray(slots, int))
    start = m.op_state
    # the two passes: the whole block alone, then the next block's first
    seen = list(block)
    if stale:
        seen[2] = -1
    _, st = b_wide(m.params, start, rows(seen, masks),
                   jnp.asarray([16, 8, 0, 0]), only(0))
    (two,), st_two = b_wide(m.params, st, rows(masks, ragged),
                            jnp.asarray([20, 8, 0, 0]), only(0, 1))
    # the one pass
    (one,), st_one = wide(start, rows(block + masks, ragged + masks),
                          jnp.asarray([16, 8, 0, 0]), only(0, 1), only(0))
    assert one.shape == two.shape == (4, 4, 256)
    err = _rel_l2(np.asarray(one[:2]), np.asarray(two[:2]))
    kv = lambda st, r, a, b: np.concatenate(
        [np.asarray(st["kv_cache"][x][:, r, :, a:b]) for x in "kv"])
    stored = _rel_l2(kv(st_one, 0, 16, 20), kv(st_two, 0, 16, 20))
    if stale:
        assert err > 0.1 and stored > 0.1, (err, stored)
        return
    assert err < 1e-4 and stored < 1e-4, (err, stored)
    assert np.abs(kv(st_one, 0, 16, 20)).min(axis=-1).max() > 0
    assert not np.asarray(kv(st_one, 1, 12, 16)).any()
    assert np.asarray(kv(st_one, 1, 8, 12)).any()


def test_passes_through_the_kernels_append_are_the_scatters(monkeypatch):
    """Decode blocks of passes (rows out of phase; two answers cross
    position 128, where the kernel's stream block ends) with the attention
    kernel on (interpreted): the kernel appends each pass's run of one or
    two blocks itself, in every layer, and no pass scatters; off the kernel
    path ``append_kv_stacked`` does. The same tokens and the same cache."""
    from flexflow_tpu import kernels as ffk

    rng = np.random.default_rng(19)
    prompts = [rng.integers(1, 255, size=n).tolist() for n in (96, 9, 110, 3)]
    news = (30, 22, 18, 27)

    def served_with():
        ffk.reset_dispatch_stats()
        m, _ = _build(max_sequence_length=256)
        got = _serve(m, prompts, news)
        return (got, dict(ffk.fused_append_counts),
                dict(ffk.scatter_append_counts),
                {x: np.asarray(m.op_state["kv_cache"][x])[..., :32]
                 for x in "kv"})

    want, fused, scattered, cache = served_with()
    assert not fused and scattered == {8: 2}        # a trace a layer
    for key in ("JAX_PLATFORMS",):
        monkeypatch.setenv(key, os.environ.get(key, ""))
    monkeypatch.setenv("FF_PALLAS_INTERPRET", "1")
    got, fused, scattered, kcache = served_with()
    assert got == want and [len(g) for g in got] == list(news)
    assert fused == {8: 2} and not scattered and not ffk.fallback_counts
    for x in "kv":
        assert np.abs(cache[x]).max() > 0.1
        np.testing.assert_allclose(kcache[x], cache[x], atol=1e-4, rtol=1e-4)


def test_check_folded_commit_tool_rehearses(monkeypatch, capsys):
    """tools/check_folded_commit.py (the on-chip check of the decode
    block's wide pass against the plain reference, which the benchmark's
    reference check, a B-wide pass, does not run) at the configuration's
    rehearsal sizes."""
    monkeypatch.syspath_prepend(ROOT)
    for key in ("JAX_PLATFORMS", "FF_PALLAS_INTERPRET"):
        monkeypatch.setenv(key, os.environ.get(key, ""))   # restored after
    from tools import check_folded_commit

    assert check_folded_commit.main(["--rehearse"]) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["ok"] and res["routes_ok"] and res["wide_rel_l2"] < res["tol"]
    assert res["stored_rel_l2"] < res["tol_cache"]
    # the wide pass's run rides in the attention kernel, a trace a layer
    assert "fused appends of width 8: 2 traces" in res["appends"]
    assert "scatter appends of width 8" not in res["appends"]


# ---------------------------------------------------------------------------
# (c) the expert weights; the family's config
# ---------------------------------------------------------------------------

def test_expert_weights_are_renormalised_top_k(bench, served):
    fam, ref = bench
    m, c, w = served[1]
    m.op_state = jax.tree_util.tree_map(jnp.zeros_like, m.op_state)
    toks = np.random.default_rng(5).integers(1, 255, size=8)
    by_name = {ly.name: ly for ly in m.layers}
    run = fam.Passes(m)
    _, routes = run.prefill(toks.tolist())
    _, probs = ref.forward_routed(w, jnp.asarray(toks), _reference_cfg(c))
    for i, (chosen, p) in enumerate(zip(routes, probs)):
        p = np.asarray(p)
        assert (np.sort(chosen, -1)
                == np.sort(np.argsort(-p, -1)[:, :2], -1)).all()
    # the graph's weights sum to one over the chosen: read them at a step
    from flexflow_tpu.serve.engine import forward_with_meta
    from flexflow_tpu.serve.request_manager import RequestManager as RM

    weights_t = [by_name[f"layers.{i}.mlp.weights"].outputs[0]
                 for i in range(2)]
    meta = RM._meta_from_segments(4, 8, [(0, toks.tolist(), 0)])
    vals, _ = jax.jit(lambda p, st: forward_with_meta(
        m, p, st, meta, None, jnp.float32, outputs=weights_t))(
            m.params, m.op_state)
    for v, p in zip(vals, probs):
        top = np.sort(np.asarray(p), -1)[:, -2:][:, ::-1]
        np.testing.assert_allclose(np.asarray(v)[0], top / top.sum(-1,
                                   keepdims=True), rtol=1e-5)


def test_config_reads_the_catalog_keys_and_refuses_the_rest():
    with open(os.path.join(ROOT, "benchmark/configs/sdar-30b-a3b.json")) as f:
        hf = json.load(f)
    c = SDARMoEConfig.from_hf_config(hf)
    assert (c.num_hidden_layers, c.num_experts, c.moe_intermediate_size,
            c.num_attention_heads, c.num_key_value_heads, c.head_dim,
            c.vocab_size) == (12, 128, 768, 32, 4, 128, 151936)
    assert c.diffusion == BlockDiffusion(4, 4, 0.9, 151669)
    assert c.diffusion.floor == 1 and c.diffusion.passes_for(5) == 8
    assert c.diffusion.emitted_most(16) == 64
    for bad in ({"sliding_window": 4096}, {"use_sliding_window": True},
                {"mlp_only_layers": [0]}, {"decoder_sparse_step": 2},
                {"rope_scaling": {"type": "yarn"}},
                {"norm_topk_prob": False}):
        with pytest.raises(NotImplementedError, match="sdar_moe with"):
            SDARMoEConfig.from_hf_config({**hf, **bad})
    with pytest.raises(NotImplementedError, match="denoising steps"):
        BlockDiffusion(4, 3, 0.9, 0)


def test_olmoe_still_refuses_norm_topk_prob():
    from flexflow_tpu.models.olmoe import OLMoEConfig

    with pytest.raises(NotImplementedError, match="sdar_moe"):
        OLMoEConfig.from_hf_config({"norm_topk_prob": True})


def test_family_is_registered_with_its_weight_map():
    from flexflow_tpu.models import FAMILIES, family_for_hf_config

    fam = family_for_hf_config({"model_type": "sdar_moe"})
    assert fam is FAMILIES["sdar_moe"] and fam.config_cls is SDARMoEConfig
    c = SDARMoEConfig(**TINY)
    keys = fam.hf_weight_map(c)
    assert keys["model.layers.1.mlp.experts.down_proj.weight"] == (
        "layers.1.mlp.experts", "down", False)
    assert keys["model.layers.0.self_attn.k_norm.weight"][1] == "k_norm"
    # stacked, and unstacked again
    rng = np.random.default_rng(0)
    sd = {f"model.layers.0.mlp.experts.{e}.{p}.weight":
          rng.normal(size=(3, 5)).astype(np.float32)
          for e in range(8) for p in ("gate_proj", "up_proj", "down_proj")}
    one = dataclasses.replace(c, num_hidden_layers=1)
    before = {k: v.copy() for k, v in sd.items()}
    sd["model.embed_tokens.weight"] = np.zeros((2, 2), np.float32)
    fam.preprocess(sd, one)
    assert sd["model.layers.0.mlp.experts.up_proj.weight"].shape == (8, 5, 3)
    from flexflow_tpu.models.sdar_moe import unstack_hf_experts

    unstack_hf_experts(sd, one)
    assert all((sd[k] == v).all() for k, v in before.items())


# ---------------------------------------------------------------------------
# (d) what refuses a block-diffusion model
# ---------------------------------------------------------------------------

def test_compiled_model_carries_its_block(served):
    m, c, _ = served[2]
    assert m.block_diffusion == BlockDiffusion(4, 2, 0.9, 255)
    assert RequestManager._manager_of(m).decode_width == 4
    assert all(ly.attrs.get("block_length") == 4 for ly in m.layers
               if "self_attn" in ly.name)


@pytest.mark.parametrize("what", ["tree_verify", "beam", "speculation",
                                  "prefix_pool", "mesh", "debug", "chunk",
                                  "decode_width"])
def test_refusals_name_the_reason(served, what):
    m, c, _ = served[1]
    if what in ("tree_verify", "beam"):
        mode = (InferenceMode.TREE_VERIFY_MODE if what == "tree_verify"
                else InferenceMode.BEAM_SEARCH_MODE)
        with pytest.raises(NotImplementedError,
                           match="incremental decoding only"):
            _build(mode=mode)
    elif what == "speculation":
        for llm, ssms in ((m, [m]),):
            with pytest.raises(NotImplementedError,
                               match="speculation .* block-diffusion"):
                RequestManager().generate_spec_infer(llm, ssms)
    elif what == "prefix_pool":
        rm = RequestManager()
        rm.register_new_request([1, 2, 3, 4, 5], max_new_tokens=4)
        with pytest.raises(NotImplementedError, match="shared-prefix pool"):
            rm.generate_incr_decoding(m, GenerationConfig(prefix_cache=True))
    elif what == "mesh":
        with pytest.raises(NotImplementedError,
                           match="a mesh that divides a model"):
            _build(num_devices=2, tensor_parallelism_degree=2)
    elif what == "debug":
        with pytest.raises(NotImplementedError, match="inference_debugging"):
            _build(inference_debugging=True)
    elif what == "chunk":
        with pytest.raises(NotImplementedError, match="whole blocks of 4"):
            _build(max_tokens_per_batch=24)     # 24 / 4 rows: a chunk of 6
    else:
        with pytest.raises(NotImplementedError, match="decode_width 8"):
            _build(decode_width=8)


# ---------------------------------------------------------------------------
# (e) a dense model's decode block is the parent's program
# ---------------------------------------------------------------------------

# sha256 of the StableHLO text of a tiny LLaMA's decode block at width 1 and
# at a verify width of 8, as the commit before this family traced it (PR
# 38's method; lowering prints no locations). This PR threads a phase and a
# block length through the step without a trace of either in a program that
# has neither. A PR that changes these programs on purpose records its own.
PARENT_DECODE_BLOCK = {
    1: "d054135dba46a8f1b56ccbdcbe80bcefddaeb08b4a855a96859588f1810c6e55",
    8: "903863173ff087d48d5d814b6603ddaaa8280f1595884ab0fe5bc3ca645bfa8c"}


def decode_block_hash(width: int) -> str:
    from flexflow_tpu.models import FAMILIES
    from flexflow_tpu.models.checkpoint_store import TINY_CONFIGS
    from flexflow_tpu.serve.engine import make_decode_block

    fam = FAMILIES["llama"]
    m = ff.FFModel(ff.FFConfig(max_requests_per_batch=2,
                               max_sequence_length=64,
                               max_tokens_per_batch=16, seed=0,
                               kv_cache_dtype="float32", num_devices=1))
    fam.build(m, fam.config_cls(**TINY_CONFIGS["llama"]),
              mode=InferenceMode.INC_DECODING_MODE)
    m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    block = make_decode_block(m, jnp.dtype(m.config.compute_dtype), 8,
                              width=width)
    text = block.lower(m.params, m.op_state, jnp.zeros((2,), jnp.int32),
                       jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool),
                       jax.random.PRNGKey(0), jnp.int32(2)).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("width", [1, 8])
def test_dense_decode_block_is_the_parents_program(width):
    assert decode_block_hash(width) == PARENT_DECODE_BLOCK[width]
