"""EXAONE-MoE on the serving path, at tiny sizes on the CPU: windowed and
full attention layers in one model, a ring beside the full cache, a share of
the router's experts held here with a shared expert.

The windowed kernel (interpreted) against the jnp oracle at the ring's
boundaries; every writer of a ring against the positions it should hold; the
held-range expert op against today's and the eight shares against the whole
layer; the program, through chunked prefill and cached decode past the
ring's wrap, against the plain reference; what refuses a windowed layer; and
the series telemetry keeps of the two kinds.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import flexflow_tpu as ff
from flexflow_tpu.ffconst import InferenceMode
from flexflow_tpu.kernels import moe as K
from flexflow_tpu.kernels.attention import flash_attend, reference_attend
from flexflow_tpu.models.exaone_moe import (ExaoneMoEConfig,
                                            create_exaone_moe_model)
from flexflow_tpu.ops import kv_layout as kvl
from flexflow_tpu.ops.inc_attention import (WINDOW_STACK, append_kv,
                                            append_kv_contiguous,
                                            append_kv_stacked, commit_tree_kv)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# 4 layers: one period (3 windowed, 1 full; 1 dense, 3 sparse). A window of
# 16 and a token budget of 64 make a ring of 128 rows beside 512 positions.
TINY = dict(vocab_size=256, hidden_size=128, intermediate_size=192,
            moe_intermediate_size=64, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=32,
            num_experts=16, num_experts_per_tok=4, sliding_window=16)
HELD = (4, 4)


@pytest.fixture(scope="module")
def bench():
    """The benchmark's family and reference for EXAONE-MoE, loaded as
    run.py loads them."""
    sys.path.insert(0, ROOT)
    try:
        from benchmark.run import load_module

        yield (load_module("families", "exaone_moe"),
               load_module("reference", "exaone_moe"))
    finally:
        sys.path.remove(ROOT)


def _build(held=HELD, mode=InferenceMode.INC_DECODING_MODE, **ffkw):
    kw = dict(max_requests_per_batch=2, max_sequence_length=512,
              max_tokens_per_batch=64, seed=3, compute_dtype="float32",
              kv_cache_dtype="float32", num_devices=1)
    kw.update(ffkw)
    m = ff.FFModel(ff.FFConfig(**kw))
    c = ExaoneMoEConfig(**TINY, held_experts=held)
    create_exaone_moe_model(m, c, mode=mode, data_type=ff.DataType.DT_FLOAT)
    m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    return m, c


def _reference_cfg(c):
    return dict(TINY, rms_norm_eps=c.rms_norm_eps,
                rope_parameters={"rope_theta": c.rope_theta},
                layer_types=c.layer_types, mlp_layer_types=c.mlp_layer_types,
                routed_scaling_factor=c.routed_scaling_factor)


# ---------------------------------------------------------------------------
# the windowed kernel against the oracle, at the ring's boundaries
# ---------------------------------------------------------------------------

#  name: (Q, start positions, real tokens, row map, fused append, stacked)
KERNEL_CASES = {
    "prefill_chunks_from_0_at_and_past_the_wrap":
        (8, (0, 250, 600), (8, 8, 5), False, False, False),
    "compact_rows_on_a_stack_with_an_idle_row":
        (8, (0, 250, 600), (8, 0, 5), True, False, True),
    "decode_append_at_rows_0_and_last":
        (1, (0, 255, 767), (1, 1, 1), False, True, False),
    "decode_append_on_a_stack_with_an_idle_row":
        (1, (5, 256, 1000), (1, 0, 1), False, True, True),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_windowed_flash_attend_matches_the_oracle_on_a_ring(case):
    """A ring of 256 rows holding each slot's last positions, a window of
    16: the kernel (interpreted) streams the blocks the window touches and
    masks by absolute position; the oracle is given the position every ring
    row holds, and a second one the whole history in a cache that never
    wraps. A fused append lands in ring row ``p % 256`` and nowhere else."""
    Q, starts, nums, row_map, fused, stacked = KERNEL_CASES[case]
    R, H, KH, D, W, rows = 3, 4, 2, 128, 16, 256
    rng = np.random.default_rng(0)
    starts, nums = np.array(starts), np.array(nums)
    lengths = starts + nums
    S = int(lengths.max()) + 8
    full = rng.standard_normal((2, R, KH, S, D)).astype(np.float32)
    ring = rng.standard_normal((2, R, KH, rows, D)).astype(np.float32)
    for r in range(R):      # what the steps before this one wrote
        for p in range(max(0, lengths[r] - rows), lengths[r] - int(fused)):
            ring[:, r, :, p % rows] = full[:, r, :, p]
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)
    i32 = lambda a: jnp.asarray(a, jnp.int32)
    q = bf(rng.standard_normal((R, Q, H, D)))
    qpos = starts[:, None] + np.arange(Q)[None]
    kc, vc = bf(ring[0]), bf(ring[1])
    layer = None
    if stacked:
        kc, vc, layer = jnp.stack([kc * 0, kc]), jnp.stack([vc * 0, vc]), 1
    kw = dict(interpret=True, window=W, layer_idx=layer)
    if fused:
        new = [bf(np.stack([c[r, :, lengths[r] - 1] for r in range(R)])[:, None])
               for c in full]
        out, k2, v2 = flash_attend(
            q, kc, vc, i32(lengths), i32(qpos),
            append_kv=(*new, i32(np.where(nums > 0, lengths - 1, -1))), **kw)
        for r in np.nonzero(nums)[0]:
            ring[:, r, :, (lengths[r] - 1) % rows] = full[:, r, :,
                                                          lengths[r] - 1]
        for got, want in ((k2, ring[0]), (v2, ring[1])):
            got = got[layer] if stacked else got
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(bf(want), np.float32))
    else:
        out = flash_attend(q, kc, vc, i32(lengths), i32(qpos),
                           rows=i32(np.arange(R)) if row_map else None, **kw)
    on_ring = reference_attend(
        q, bf(ring[0]), bf(ring[1]), i32(lengths), i32(qpos), window=W,
        key_pos=kvl.ring_positions(i32(lengths), rows))
    on_history = reference_attend(q, bf(full[0]), bf(full[1]), i32(lengths),
                                  i32(qpos), window=W)
    for r in np.nonzero(nums)[0]:
        for want in (on_ring, on_history):
            np.testing.assert_allclose(
                np.asarray(out[r, :nums[r]], np.float32),
                np.asarray(want[r, :nums[r]], np.float32), atol=3e-2)


def test_a_window_sees_exactly_its_positions():
    """The oracle's mask itself: with value v_j = j the attention output is
    a mean of the visible positions, which have to be i-15 .. i."""
    R, Q, S, D, W = 1, 4, 64, 8, 16
    v = jnp.broadcast_to(jnp.arange(S, dtype=jnp.float32)[:, None], (S, D))
    out = reference_attend(
        jnp.zeros((R, Q, 1, D)), jnp.zeros((R, 1, S, D)), v[None, None],
        jnp.asarray([44]), jnp.asarray([[40, 41, 42, 43]]), window=W)
    np.testing.assert_allclose(np.asarray(out[0, :, 0]),
                               [32.5, 33.5, 34.5, 35.5], rtol=1e-6)


# ---------------------------------------------------------------------------
# every writer of a ring holds the positions it should
# ---------------------------------------------------------------------------

def _ring_writer(name, cache, new, start, num, active):
    L1 = 1      # the layer written in a stack of 3
    if name == "scatter":
        return append_kv(cache, new, start, num, active, ring=True)
    if name == "scatter_stacked":
        stack = jnp.stack([cache] * 3)
        return append_kv_stacked(stack, L1, new, start, num, active,
                                 ring=True)[L1]
    if name == "contiguous":
        return append_kv_contiguous(cache, None, new, start, active,
                                    num_tokens=num, ring=True)
    if name == "by_slot_stacked":      # batch rows 0, 1, 2 -> slots 2, 0, 1
        slots = jnp.asarray([2, 0, 1], jnp.int32)
        by_slot = cache[jnp.argsort(slots)]     # slot slots[r] holds row r's
        stack = jnp.stack([by_slot] * 3)
        out = append_kv_contiguous(stack, jnp.int32(L1), new, start, active,
                                   slots, num, ring=True)
        assert np.array_equal(np.asarray(out[0]), np.asarray(by_slot))
        return out[L1][slots]
    raise KeyError(name)


@pytest.mark.parametrize("writer", ["scatter", "scatter_stacked",
                                    "contiguous", "by_slot_stacked"])
@pytest.mark.parametrize("Q", [1, 6])
def test_ring_writers_put_a_position_in_its_row(writer, Q):
    """Runs that start at row 0, end on the ring's last row, pass its end
    and wrap, with a padded tail and an inactive row: position p lands in
    row p % 24, every other row keeps what it held."""
    R, KH, rows, D = 3, 2, 24, 8
    rng = np.random.RandomState(1)
    cache = jnp.asarray(rng.randn(R, KH, rows, D).astype(np.float32))
    new = jnp.asarray(rng.randn(R, Q, KH, D).astype(np.float32))
    for starts, nums, act in (((0, 24 - Q, 45), (Q, Q, Q), (1, 1, 1)),
                              ((70, 21, 9), (Q, max(1, Q - 2), Q), (1, 1, 0))):
        start, num = jnp.asarray(starts), jnp.asarray(nums)
        active = jnp.asarray(act, bool)
        got = np.asarray(_ring_writer(writer, cache, new, start, num, active))
        want = np.asarray(cache).copy()
        for r in range(R):
            for t in range(nums[r] if act[r] else 0):
                want[r, :, (starts[r] + t) % rows] = np.asarray(new)[r, t]
        np.testing.assert_array_equal(got, want)
    held = kvl.ring_positions(jnp.asarray([0, 5, 24, 30]), rows)
    assert (np.asarray(held[0]) < 0).all()
    assert np.asarray(held[1])[:6].tolist() == [0, 1, 2, 3, 4, -19]
    assert np.asarray(held[3])[[0, 5, 6, 23]].tolist() == [24, 29, 6, 23]
    got = kvl.read_ring(cache, 30, 40, at=(1,))
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(cache)[1][:, [p % rows
                                                  for p in range(30, 40)]])


def test_a_ring_is_the_window_plus_a_steps_tokens_in_whole_blocks():
    assert kvl.ring_rows(128, 512, 8192) == 640      # the cell's
    assert kvl.ring_rows(16, 64, 512) == 128
    assert kvl.ring_rows(128, 512, 256) == 256       # never over max_seq
    m, _ = _build()
    shapes = {k: v["k"].shape for k, v in m.op_state.items()}
    assert shapes == {"kv_cache": (1, 2, 2, 512, 32),
                      WINDOW_STACK: (3, 2, 2, 128, 32)}     # 4 leaves
    by = {ly.name: ly.attrs for ly in m.layers if "head_dim" in ly.attrs}
    assert [(a.get("cache_stack"), a["cache_layer_idx"],
             a.get("sliding_window"), a["apply_rotary_embedding"])
            for a in by.values()] == [
        (WINDOW_STACK, 0, 16, True), (WINDOW_STACK, 1, 16, True),
        (WINDOW_STACK, 2, 16, True), (None, 0, None, False)]
    assert m.attention_kinds == {
        "full": {"layers": 1, "window": None,
                 "cache_bytes": 2 * 2 * 2 * 512 * 32 * 4},
        "window": {"layers": 3, "window": 16,
                   "cache_bytes": 3 * 2 * 2 * 2 * 128 * 32 * 4}}


# ---------------------------------------------------------------------------
# the expert op told which experts it holds
# ---------------------------------------------------------------------------

def _routing(T=24, E=16, k=4, H=64, inter=32, seed=0):
    rng = np.random.default_rng(seed)
    idx = np.stack([rng.choice(E, size=k, replace=False) for _ in range(T)])
    valid = np.ones((T,), bool)
    valid[rng.choice(T, size=T // 4, replace=False)] = False
    w = rng.uniform(0.05, 0.5, size=(T, k)).astype(np.float32)
    f = lambda *s: jnp.asarray(rng.normal(0, 0.2, size=s), jnp.float32)
    return (f(T, H), jnp.asarray(idx, jnp.int32), jnp.asarray(w),
            jnp.asarray(valid), f(E, H, inter), f(E, H, inter),
            f(E, inter, H))


@pytest.mark.parametrize("pallas", [True, False],
                         ids=["pallas_interpret", "ragged_fallback"])
def test_the_eight_shares_add_up_to_the_whole_layer(pallas):
    """16 experts two a share: the shares' outputs sum to the op that holds
    all 16, a pair routed elsewhere is neither computed nor counted (an
    expert nobody here was sent to has no tile), and the counts are by held
    index."""
    x, idx, w, valid, gate, up, down = _routing()
    whole, sizes = K.moe_experts(x, idx, w, valid, gate, up, down,
                                 pallas=pallas, interpret=True)
    total, counted = 0.0, []
    for first in range(0, 16, 2):
        part = slice(first, first + 2)
        y, s = K.moe_experts(x, idx, w, valid, gate[part], up[part],
                             down[part], pallas=pallas, interpret=True,
                             held=(first, 16))
        assert s.shape == (2,)
        total, counted = total + y, counted + np.asarray(s).tolist()
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-5, atol=2e-5)
    assert counted == np.asarray(sizes).tolist()
    assert sum(counted) == int(valid.sum()) * 4
    # no tile for a pair held elsewhere: the plan of a share sees only its own
    local = idx - 6
    mine = valid[:, None] & (local >= 0) & (local < 2)
    _, pair_row, _, n_active, s = K.plan_routes(local, mine, 2, 16)
    M = pair_row.size + 2 * 15
    assert (np.asarray(pair_row)[~np.asarray(mine)] >= M - M % 16).all()
    assert int(n_active[0]) == int(np.sum(-(-np.asarray(s) // 16)))


def test_the_whole_range_is_todays_op_bit_for_bit():
    x, idx, w, valid, gate, up, down = _routing()
    run = lambda **kw: jax.jit(lambda *a: K.moe_experts(
        *a, pallas=False, **kw))
    args = (x, idx, w, valid, gate, up, down)
    a, b = run()(*args), run(held=(0, 16))(*args)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
    # and the builder records today's layer for it: no new attr, same trace
    whole, _ = _build(held=(0, 16))
    none, _ = _build(held=None)
    for m in (whole, none):
        attrs = [ly.attrs for ly in m.layers
                 if ly.name.endswith("mlp.experts")]
        assert all("router_width" not in a and a["num_experts"] == 16
                   for a in attrs)
    share, _ = _build()
    attrs = [ly.attrs for ly in share.layers
             if ly.name.endswith("mlp.experts")]
    assert [(a["num_experts"], a["router_width"], a["first_expert"])
            for a in attrs] == [(4, 16, 4)] * 3
    assert share.params["layers.1.mlp.experts"]["gate"].shape == (4, 128, 64)


def test_reference_shares_add_up_with_the_shared_expert_counted_once(bench):
    """The plain reference's sparse layer: the eight shares' outputs, less
    the shared expert that every chip computes alike in seven of them, are
    the uncut layer."""
    _, reference = bench
    rng = np.random.default_rng(2)
    f = lambda *s: jnp.asarray(rng.normal(0, 0.3, size=s), jnp.float32)
    E, H, I = 16, 32, 24
    lw = {"router": f(H, E), "bias": f(E) * 0.2, "gate": f(E, H, I),
          "up": f(E, H, I), "down": f(E, I, H), "s_gate": f(H, I),
          "s_up": f(H, I), "s_down": f(I, H)}
    cfg = {"num_experts_per_tok": 4, "routed_scaling_factor": 2.5}
    m = f(10, H)
    whole, scores = reference._sparse(m, lw, cfg, None, (0, E))
    shared = reference._swiglu(m, lw["s_gate"], lw["s_up"], lw["s_down"])
    total = 0.0
    for first in range(0, E, 2):
        part = {**lw, **{n: lw[n][first:first + 2]
                         for n in ("gate", "up", "down")}}
        y, s = reference._sparse(m, part, cfg, None, (first, 2))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(scores))
        total = total + (y - shared)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    # the bias moves the choice and never the weight
    plain = reference._sparse(m, {**lw, "bias": 0 * lw["bias"]}, cfg,
                              jax.lax.top_k(scores, 4)[1], (0, E))[0]
    np.testing.assert_allclose(np.asarray(plain), np.asarray(whole),
                               rtol=1e-6, atol=1e-6)
    assert not np.array_equal(
        np.asarray(jax.lax.top_k(scores, 4)[1]),
        np.asarray(jax.lax.top_k(scores - lw["bias"], 4)[1]))


# ---------------------------------------------------------------------------
# the program against the plain reference, through both caches
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant", [None, "int8"])
def test_program_matches_plain_reference_through_both_caches(bench, quant):
    """Nine prefill chunks of 16, then 56 tokens decoded one at a time:
    200 positions through a ring of 128 rows (12 windows: it wraps) and the
    full cache, float32 compute; the program's routes validated against the
    reference's biased scores and the logits compared on those routes
    (families/exaone_moe.reference_check)."""
    family, reference = bench
    m, c = _build(quantization_type=quant)
    if quant:
        from flexflow_tpu.quant import QuantizedWeight

        assert isinstance(m.params["layers.1.mlp.experts"]["gate"],
                          QuantizedWeight)
    bias = m.params["layers.1.mlp.gate.e_score_correction_bias"]["weight"]
    assert bias.shape == (16,) and float(jnp.abs(bias).min()) > 0
    toks = np.random.default_rng(5).integers(1, 256, size=200)
    chunks = [16] * 9 + [1] * 56
    ours, routes = family.program_logits_and_routes(m, toks, chunks)
    assert len(routes) == 3 and routes[0].shape == (200, 4)
    cfg = _reference_cfg(c)
    ref, scores = reference.forward_routed(
        family._reference_weights(m, c), jnp.asarray(toks), cfg,
        routes=routes, held=HELD)
    checked = family.check_routes(routes, scores, family.ROUTE_MARGIN)
    assert checked["routes_ok"] and checked["doubled_experts"] == 0
    assert checked["route_flips"] == 0          # float32: no near-tie flips
    np.testing.assert_allclose(ours, np.asarray(ref), rtol=3e-4, atol=3e-4)
    # the check has teeth: each convention and each mechanism moves the
    # logits by far more than the tolerance
    weights = lambda: family._reference_weights(m, c)
    got = lambda cfg_, held=HELD: reference.forward_routed(
        weights(), jnp.asarray(toks), cfg_, routes=routes, held=held)[0]
    for wrong in (dict(cfg, sliding_window=200),        # no window
                  dict(cfg, layer_types=["sliding_attention"] * 4),
                  dict(cfg, routed_scaling_factor=1.0),
                  dict(cfg, mlp_layer_types=["dense"] + ["sparse"] * 3,
                       num_experts_per_tok=4, sliding_window=15)):
        assert not family.C.compare_logits(ours, got(wrong), 0.03)["ok"]
    other = [np.roll(r, 1, axis=0) for r in routes]     # another token's
    assert not family.C.compare_logits(
        ours, reference.forward_routed(weights(), jnp.asarray(toks), cfg,
                                       routes=other, held=HELD)[0],
        0.03)["ok"]


def test_kernels_serve_both_kinds_interpreted(bench, monkeypatch):
    """The same comparison with the Pallas kernels interpreted: the caches
    are then lane-padded ([.., 128]), the windowed layers run
    ``flash_attend(window=16)`` on the ring, and nothing falls back."""
    import flexflow_tpu.kernels as ffk

    monkeypatch.setenv("FF_PALLAS_INTERPRET", "1")
    ffk.reset_dispatch_stats()
    family, reference = bench
    m, c = _build()
    assert m.op_state[WINDOW_STACK]["k"].shape == (3, 2, 2, 128, 128)
    toks = np.random.default_rng(6).integers(1, 256, size=150)
    ours, routes = family.program_logits_and_routes(
        m, toks, [16] * 8 + [1] * 22)
    ref, _ = reference.forward_routed(
        family._reference_weights(m, c), jnp.asarray(toks),
        _reference_cfg(c), routes=routes, held=HELD)
    np.testing.assert_allclose(ours, np.asarray(ref), rtol=3e-4, atol=3e-4)
    assert ffk.fast_path_count > 0 and not ffk.fallback_counts
    assert K.fast_path_count > 0 and not K.fallback_counts


def test_a_step_larger_than_the_ring_was_sized_for_says_so():
    m, _ = _build()
    sys.path.insert(0, ROOT)
    try:
        from benchmark.run import load_module

        family = load_module("families", "exaone_moe")
    finally:
        sys.path.remove(ROOT)
    with pytest.raises(ValueError, match="ring was sized for"):
        family.program_logits_and_routes(m, np.arange(1, 81), [80])


# ---------------------------------------------------------------------------
# what does not support a windowed layer says so
# ---------------------------------------------------------------------------

def _refusal(name):
    if name in ("tree_verify_mode", "beam_search_mode"):
        mode = (InferenceMode.TREE_VERIFY_MODE if name == "tree_verify_mode"
                else InferenceMode.BEAM_SEARCH_MODE)
        return lambda: _build(mode=mode)
    if name == "tree_attention_op":
        def build():
            m = ff.FFModel(ff.FFConfig(max_requests_per_batch=2))
            x = m.create_tensor([2, 1, 64], ff.DataType.DT_FLOAT)
            m._serving_attention(
                ff.OpType.TREE_INC_MULTIHEAD_SELF_ATTENTION, x, 64, 4, 4, 0,
                0, 0.0, False, False, False, None, None, True, False, 1.0,
                True, False, 1e4, "a", sliding_window=8)
        return build
    if name == "tensor_parallel_mesh":
        return lambda: _build(tensor_parallelism_degree=2, num_devices=2)
    if name == "pipeline_plan":
        return lambda: _build(pipeline_parallelism_degree=2, num_devices=2)
    m, _ = _build()
    if name == "commit_tree_kv":
        z = jnp.zeros((2,), jnp.int32)
        return lambda: commit_tree_kv(m.op_state, jnp.zeros((2, 3), jnp.int32),
                                      z, z, z > 0)
    if name == "speculation_commit":
        from flexflow_tpu.ops.inc_attention import refuse_windowed

        return lambda: refuse_windowed(m.op_state, "a speculation commit")
    if name == "prefix_pool":
        from flexflow_tpu.serve import prefix_cache

        return lambda: prefix_cache.extract_prefix_kv(m.op_state, 0, 8, 512)
    raise KeyError(name)


@pytest.mark.parametrize("what", [
    "tree_verify_mode", "beam_search_mode", "tree_attention_op",
    "tensor_parallel_mesh", "pipeline_plan", "commit_tree_kv",
    "speculation_commit", "prefix_pool"])
def test_what_cannot_hold_a_ring_refuses_loudly(what):
    with pytest.raises(NotImplementedError, match="window|ring"):
        _refusal(what)()


def test_prefix_pool_refuses_when_a_request_asks_for_it():
    from flexflow_tpu.serve.batch_config import GenerationConfig
    from flexflow_tpu.serve.request_manager import RequestManager

    m, _ = _build()
    rm = RequestManager()
    rm.register_new_request(list(range(1, 20)), max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="shared-prefix pool"):
        rm.generate_incr_decoding(m, GenerationConfig(prefix_cache=True))


# ---------------------------------------------------------------------------
# the serving loop and what telemetry keeps of the two kinds
# ---------------------------------------------------------------------------

def test_the_loop_serves_it_and_counts_by_kind(bench):
    """Through RequestManager (compact prefill, decode blocks): the tokens
    are those the slot-grid program gives one request at a time; the
    ``ffsv_attn_positions_read_total`` series are what the decode steps'
    rows had to attend, the ``ffsv_moe_*`` series count held experts by
    held index and computed pairs only."""
    from flexflow_tpu.serve.request_manager import RequestManager
    from flexflow_tpu.telemetry import ServingTelemetry

    family, _ = bench
    m, c = _build(telemetry=True)
    assert m.op_state["moe_counters"].shape == (3, 4 + 5 * 3)
    prompts = [list(np.random.default_rng(i).integers(1, 256, size=n))
               for i, n in enumerate((150, 9))]
    new = 12
    tel = ServingTelemetry()
    rm = RequestManager()
    rm.telemetry = tel
    for p in prompts:
        rm.register_new_request([int(t) for t in p], max_new_tokens=new)
    got = rm.generate_incr_decoding(m)
    alone, _ = _build()
    assert sorted(len(r.input_tokens) for r in got) == [9, 150]
    for res in got:                 # in the order they finished
        p = res.input_tokens
        alone.op_state = jax.tree.map(jnp.zeros_like, alone.op_state)
        toks = list(p) + list(res.output_tokens)
        logits, _ = family.program_logits_and_routes(
            alone, np.asarray(toks[:-1]),
            [16] * (len(p) // 16) + [1] * (len(toks) - 1 - len(p) // 16 * 16))
        assert res.output_tokens == logits[len(p) - 1:].argmax(-1).tolist()
    snap = tel.registry.snapshot()
    read = {k: snap[f'ffsv_attn_positions_read_total{{kind="{k}"}}']["value"]
            for k in ("window", "full")}
    # decode step j of a request attends len(prompt) + j positions
    lens = [len(p) + j for p in prompts for j in range(new)]
    assert read == {"full": 1 * sum(lens),
                    "window": 3 * sum(min(n, 16) for n in lens)}
    assert {k: snap[f'ffsv_kv_cache_bytes{{kind="{k}"}}']["value"]
            for k in ("window", "full")} == {
        k: a["cache_bytes"] for k, a in m.attention_kinds.items()}
    dec = snap['ffsv_moe_tokens_total{phase="decode"}']["value"]
    routed = snap['ffsv_moe_routed_pairs_total{phase="decode"}']["value"]
    assert dec == 3 * 2 * new and 0 < routed < dec * 4
    per_expert = [snap[f'ffsv_moe_expert_pairs_total{{expert="{e}"}}'][
        "value"] for e in range(4)]
    assert 'ffsv_moe_expert_pairs_total{expert="4"}' not in snap
    assert sum(per_expert) == routed + snap[
        'ffsv_moe_routed_pairs_total{phase="prefill"}']["value"]
    touched = snap['ffsv_moe_experts_touched{phase="decode"}']
    assert touched["sum"] / touched["count"] <= 4


def test_hf_weight_map_reads_only_the_held_experts():
    from flexflow_tpu.models import exaone_moe as X

    c = ExaoneMoEConfig(**TINY, held_experts=HELD)
    sd = {f"model.layers.1.mlp.experts.{e}.{p}_proj.weight":
          np.full((64, 128) if p != "down" else (128, 64), e, np.float32)
          for e in range(16) for p in ("gate", "up", "down")}
    sd["mtp.layers.0.mlp.gate.weight"] = np.zeros((2, 2))
    X.preprocess_hf_state_dict(sd, c)
    assert sorted(sd) == [X._experts_key(1, p + "_proj")
                          for p in ("down", "gate", "up")]
    stack = sd[X._experts_key(1, "gate_proj")]
    assert stack.shape == (4, 128, 64)
    assert stack[:, 0, 0].tolist() == [4, 5, 6, 7]
    m = X.hf_weight_map(c)
    assert m["model.layers.0.mlp.gate_proj.weight"] == (
        "layers.0.mlp.gate_proj", "kernel", True)
    assert m["model.layers.3.mlp.gate.e_score_correction_bias"] == (
        "layers.3.mlp.gate.e_score_correction_bias", "weight", False)
    assert "model.layers.0.mlp.gate.weight" not in m
    built, _ = _build()
    assert {v[0] for v in m.values()} == set(built.params)
    with pytest.raises(NotImplementedError, match="group"):
        ExaoneMoEConfig.from_hf_config(dict(TINY, n_group=2))
