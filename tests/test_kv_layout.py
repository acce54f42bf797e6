"""The stored layout of a D=64 KV cache (ops/kv_layout.py).

A cache that takes the packed flash path is stored as its kernel reads it,
``[.., S/2, 128]``. Every writer and reader of it is held here against the
same operation on the position-major ``[.., S, 64]`` cache, position for
position; a tiny D=64 model is served both ways; and the decode and compact
prefill steps of such a model are searched for a cache-sized relayout.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import flexflow_tpu as ff
import flexflow_tpu.kernels as ffk
from flexflow_tpu.ffconst import InferenceMode
from flexflow_tpu.ops import kv_layout as kvl
from flexflow_tpu.ops.inc_attention import (_attend, _init_kv_state,
                                            append_kv, append_kv_contiguous,
                                            append_kv_stacked,
                                            commit_tree_kv)
from flexflow_tpu.serve.request_manager import RequestManager

L, R, KH, S, D = 3, 5, 2, 64, 64
LAYER = 1


def _cache(seed=0, stacked=True):
    rng = np.random.RandomState(seed)
    shape = (L, R, KH, S, D) if stacked else (R, KH, S, D)
    return jnp.asarray(rng.randn(*shape).astype(np.float32))


def _run(seed, Q, rows=R):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(rows, Q, KH, D).astype(np.float32))


def _both_ways(op, cache):
    """``op(cache, pack)`` on the position-major cache and on its packed
    twin: the packed result, read back by position, is the other's."""
    want = np.asarray(op(cache, 1))
    got = op(kvl.to_rows(cache, 2), 2)
    assert got.shape == cache.shape[:-2] + (S // 2, 2 * D)
    np.testing.assert_array_equal(np.asarray(kvl.to_positions(got, 2)), want)
    assert not np.array_equal(want, np.asarray(cache)), "nothing was written"
    return want


def _ints(*xs):
    return jnp.asarray(xs, jnp.int32)


#  name           Q  start_pos (odd, last position, past the end ...)   num
SCATTERS = {
    "decode_q1": (1, (0, 7, S - 1, S, 12), (1, 1, 1, 1, 0)),
    "run_q6": (6, (0, 7, S - 6, S - 3, 20), (6, 4, 6, 6, 6)),
}
ACTIVE = jnp.asarray([True, True, True, True, False])


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["per_layer", "stacked"])
@pytest.mark.parametrize("case", sorted(SCATTERS))
def test_scatter_appends_on_a_packed_cache(case, stacked):
    """``append_kv`` / ``append_kv_stacked``: one token and a run, from an
    even and an odd position, ending at the cache's last position, passing
    it (the tail is dropped), padding beyond ``num_tokens`` kept, an
    inactive row."""
    Q, start, num = SCATTERS[case]
    new, start, num = _run(1, Q), _ints(*start), _ints(*num)
    if stacked:
        want = _both_ways(lambda c, pack: append_kv_stacked(
            c, LAYER, new, start, num, ACTIVE, pack), _cache())
        np.testing.assert_array_equal(want[0], np.asarray(_cache()[0]))
    else:
        _both_ways(lambda c, pack: append_kv(c, new, start, num, ACTIVE,
                                             pack), _cache(stacked=False))


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["per_layer", "stacked"])
def test_engine_append_on_a_packed_cache(stacked):
    """``append_kv_contiguous`` as the fused engines call it (no
    ``num_tokens``): every active row's run fits; an inactive row near the
    cache's end is left alone."""
    Q = 6
    new = _run(2, Q)
    start = _ints(0, 7, S - Q, 31, S - 2)
    cache = _cache(3, stacked)
    _both_ways(lambda c, pack: append_kv_contiguous(
        c, LAYER if stacked else None, new, start, ACTIVE, pack=pack), cache)


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["per_layer", "stacked"])
@pytest.mark.parametrize("Q", [6, 80], ids=["chunk6", "chunk_over_cache"])
def test_segment_append_on_a_packed_cache(Q, stacked):
    """``append_kv_contiguous`` by slot (the compact prefill's append): two
    segments of one slot in one step, the later chunk first; an odd start;
    a run that ends at the cache's last position; one within a chunk of
    the end and one that would pass it; nothing real; an inactive row."""
    #        slot start     n   active
    segs = [(3,   17,      2,  True),
            (3,   11,      6,  True),
            (0,   S - 6,   6,  True),
            (1,   S - 3,   6,  True),
            (4,   5,       0,  True),
            (2,   9,       6,  False),
            (2,   20,      3,  True)]
    slots, start, num, act = (jnp.asarray(c) for c in zip(*segs))
    new = _run(4, Q, rows=len(segs))
    cache = _cache(5, stacked)
    want = _both_ways(lambda c, pack: append_kv_contiguous(
        c, LAYER if stacked else None, new, start, act, slots, num,
        pack=pack), cache)
    layer = want[LAYER] if stacked else want
    np.testing.assert_array_equal(layer[3, :, 11:19],
                                  np.concatenate([np.swapaxes(new[1, :6], 0, 1),
                                                  np.swapaxes(new[0, :2], 0,
                                                              1)], axis=1))
    np.testing.assert_array_equal(layer[1, :, S - 3:],
                                  np.swapaxes(new[3, :3], 0, 1))


@pytest.mark.parametrize("state", ["per_layer", "stacked"])
def test_tree_commit_on_a_packed_cache(state):
    """``commit_tree_kv`` (and through ``move_kv`` the engines' commits):
    accepted tree nodes gathered from anywhere in the staged region and
    compacted behind an even or an odd committed prefix."""
    src_node = _ints([0, 2, 5, 6], [1, 3, 4, 7], [0, 1, 2, 3], [2, 4, 6, 7],
                     [0, 1, 2, 3])
    num = _ints(4, 2, 0, 3, 4)
    start = _ints(10, 21, 5, S - 9, 30)

    def state_of(c):
        if state == "stacked":
            return {"kv_cache": {"k": c, "v": c + 1.0}}
        return {f"l{i}": {"k_cache": c[i], "v_cache": c[i] + 1.0}
                for i in range(L)}

    def flat(st, pack):
        return np.stack([np.asarray(kvl.to_positions(a, pack))
                         for a in jax.tree.leaves(st)])

    c = _cache(6)
    want = flat(commit_tree_kv(state_of(c), src_node, num, start, ACTIVE), 1)
    got = flat(commit_tree_kv(state_of(kvl.to_rows(c, 2)), src_node, num,
                              start, ACTIVE, max_seq=S), 2)
    np.testing.assert_array_equal(got, want)
    assert not np.array_equal(want, flat(state_of(c), 1))


def test_positions_read_back_from_a_packed_cache():
    c = _cache(7)
    p = kvl.to_rows(c, 2)
    assert kvl.pack_of(p, S) == 2 and kvl.pack_of(c, S) == 1
    for a, b in [(0, 8), (3, 4), (5, 18), (S - 1, S), (0, S)]:
        np.testing.assert_array_equal(
            np.asarray(kvl.read_positions(p[:, 2], a, b, 2)),
            np.asarray(c[:, 2, :, a:b]))
        np.testing.assert_array_equal(
            np.asarray(kvl.read_positions(c[:, 2], a, b, 1)),
            np.asarray(c[:, 2, :, a:b]))
    pos = _ints([0, 1, 63], [7, 7, 2], [62, 3, 4], [9, 8, 7], [31, 32, 33])
    want = np.stack([np.asarray(c)[:, r][:, :, np.asarray(pos[r])]
                     for r in range(R)], axis=1)
    for cache, pack in ((c, 1), (p, 2)):
        np.testing.assert_array_equal(
            np.asarray(kvl.gather_positions(cache, pos, pack)), want)
        np.testing.assert_array_equal(
            np.asarray(kvl.gather_positions(cache[LAYER], pos, pack)),
            want[LAYER])


@pytest.mark.parametrize("state", ["per_layer", "stacked"])
def test_prefix_segment_from_a_packed_cache_into_another_slot(state):
    """extract -> install: the segment is position-major on the host
    whatever the cache's layout, and lands in another slot of a packed
    cache as it lands in a position-major one."""
    from flexflow_tpu.serve import prefix_cache as pcm

    def state_of(c, pack):
        c = kvl.to_rows(c, pack)
        if state == "stacked":
            return {"kv_cache": {"k": c, "v": c + 1.0}}
        return {"l0": {"k_cache": c[0], "v_cache": c[0] + 1.0},
                "l1": {"k_cache": c[1], "v_cache": c[1] + 1.0}}

    src, dst = _cache(8), _cache(9)
    segs = pcm.extract_prefix_kv(state_of(src, 2), 1, 13, S)
    want = pcm.extract_prefix_kv(state_of(src, 1), 1, 13, S)
    for name in want:
        assert segs[name]["k"].shape[-2:] == (16, D)    # padded to _PAD
        for c in ("k", "v"):
            np.testing.assert_array_equal(segs[name][c], want[name][c])
    assert pcm.prefix_compatible(state_of(dst, 2), segs, 13, S)
    assert not pcm.prefix_compatible(state_of(dst, 2), segs, 17, S)
    assert pcm.extract_prefix_kv(state_of(dst, 2), 0, S + 1, S) is None
    got = pcm.install_prefix_kv(state_of(dst, 2), 3, segs, 13, S)
    ref = pcm.install_prefix_kv(state_of(dst, 1), 3, want, 13, S)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(kvl.to_positions(a, 2)),
                                      np.asarray(b))
    k = jax.tree.leaves(ref)[0]
    np.testing.assert_array_equal(np.asarray(k)[..., 3, :, :13, :],
                                  np.asarray(src)[:k.shape[0], 1, :, :13]
                                  if state == "stacked"
                                  else np.asarray(src)[0, 1, :, :13])


def test_jnp_attention_unpacks_a_packed_layer_and_says_so():
    """A packed cache attended off the Pallas path (the kernel switched
    off after the cache was allocated): the jnp oracle gets the layer
    position-major, and the fallback is recorded."""
    attrs = dict(head_dim=D, num_q_heads=4, num_kv_heads=KH,
                 max_seq_length=S)
    rng = np.random.RandomState(10)
    q = jnp.asarray(rng.randn(R, 3, 4, D).astype(np.float32))
    lengths = _ints(9, 1, S, 0, 30)
    qpos = (lengths - 3).clip(0)[:, None] + jnp.arange(3)[None]
    k, v = _cache(11), _cache(12)
    want = _attend(attrs, q, k, v, lengths, qpos, jnp.float32, None,
                   layer_idx=LAYER)
    ffk.reset_dispatch_stats()
    got = _attend(attrs, q, kvl.to_rows(k, 2), kvl.to_rows(v, 2), lengths,
                  qpos, jnp.float32, None, layer_idx=LAYER)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert list(ffk.fallback_counts) == [
        "packed cache attended off the Pallas path"]


#   D   S    Pallas   stored [rows, lanes]   pack
LAYOUTS = {
    "d128": (128, 256, True, (256, 128), 1),
    "d64_packed": (64, 256, True, (128, 128), 2),
    "d64_untileable_pads": (64, 128, True, (128, 128), 1),
    "d64_no_flash_either_way": (64, 100, True, (100, 64), 1),
    "d64_off_pallas": (64, 256, False, (256, 64), 1),
    "d32_pads": (32, 256, True, (256, 128), 1),
    "d256": (256, 512, True, (512, 256), 1),
}


@pytest.mark.parametrize("case", sorted(LAYOUTS))
def test_where_the_layout_is_chosen(case, monkeypatch):
    """Packed storage where the packed flash path will serve the cache
    (head dim 64, a length its 256-position block tiles, Pallas in use);
    everywhere else the layouts of before."""
    Dh, max_seq, pallas, stored, pack = LAYOUTS[case]
    if pallas:
        monkeypatch.setenv("FF_PALLAS_INTERPRET", "1")
    st = _init_kv_state(dict(max_requests=3, max_seq_length=max_seq,
                             num_kv_heads=2, head_dim=Dh,
                             cache_dtype="float32"), None)
    assert st["k_cache"].shape == st["v_cache"].shape == (3, 2) + stored
    assert kvl.pack_of(st["k_cache"], max_seq) == pack


# ---------------------------------------------------------------------------
# A tiny D=64 model, served with the cache stored packed (the Pallas
# kernels, interpreted) and position-major (the jnp path)
# ---------------------------------------------------------------------------
def _tiny_d64(mode, seed, max_seq=256, llama=False, beam=1):
    """Multi-query at D=64 (Falcon's geometry; a LLaMA of that geometry
    where the draft runs a beam, which Falcon's builder has no head for),
    256 positions a slot: the packed path's smallest cache."""
    from flexflow_tpu.models.falcon import FalconConfig, create_falcon_model
    from flexflow_tpu.models.llama import LLAMAConfig, create_llama_model

    cfg = ff.FFConfig(max_requests_per_batch=4, max_sequence_length=max_seq,
                      max_tokens_per_batch=64, seed=seed,
                      kv_cache_dtype="float32", max_beam_width=beam)
    m = ff.FFModel(cfg)
    if llama:
        create_llama_model(m, LLAMAConfig(
            vocab_size=128, hidden_size=128, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=2,
            num_key_value_heads=1, max_position_embeddings=256), mode=mode)
    else:
        create_falcon_model(m, FalconConfig(
            vocab_size=128, hidden_size=128, num_hidden_layers=2,
            num_attention_heads=2, num_kv_heads=1), mode=mode)
    m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    return m


def _serve(loop):
    from flexflow_tpu.serve.batch_config import GenerationConfig

    spec = loop != "incr"
    beam = loop == "spec_beam_fused"
    llm = _tiny_d64(InferenceMode.TREE_VERIFY_MODE if spec
                    else InferenceMode.INC_DECODING_MODE, seed=0, llama=beam)
    ssms = [_tiny_d64(InferenceMode.BEAM_SEARCH_MODE, seed=s, llama=beam,
                      beam=2 if beam else 1)
            for s in {"incr": (), "spec_tree_fused": (0, 5)}.get(loop, (0,))]
    rng = np.random.RandomState(4)
    # several chunks of 16 from an odd depth, one chunk, a few tokens, and
    # a prompt that fills its slot to within a chunk of the cache's end
    prompts = [[int(t) for t in rng.randint(1, 128, size=n)]
               for n in (37, 16, 3, 246)]
    rm = RequestManager()
    for p in prompts:
        rm.register_new_request(p, max_new_tokens=7)
    gc = GenerationConfig(spec_draft_cost_ratio=0.1)
    if loop == "incr":
        results = rm.generate_incr_decoding(llm)
    elif loop == "spec_tree_host":
        results = rm._generate_spec_tree_host(llm, ssms, spec_depth=3)
    else:
        results = rm.generate_spec_infer(llm, ssms, spec_depth=3,
                                         generation_config=gc)
        assert rm.scheduler_loop == "python:" + loop
    by_prompt = {tuple(r.input_tokens): r.output_tokens for r in results}
    return ([by_prompt[tuple(p)] for p in prompts],
            [m.op_state["kv_cache"]["k"].shape for m in [llm] + ssms])


@pytest.mark.parametrize("loop", ["incr", "spec_tree_fused",
                                  "spec_tree_host", "spec_beam_fused"])
def test_tiny_d64_model_serves_the_same_tokens_packed(loop, monkeypatch):
    """Incremental decoding with the compact prefill, the fused tree and
    beam engines and the host-stepped tree loop (a D=64 verifier and
    drafts): the same tokens with the cache stored packed as
    position-major."""
    want, shapes = _serve(loop)
    assert set(shapes) == {(2, 4, 1, 256, 64)}
    monkeypatch.setenv("FF_PALLAS_INTERPRET", "1")
    ffk.reset_dispatch_stats()
    got, shapes = _serve(loop)
    assert set(shapes) == {(2, 4, 1, 128, 128)}
    assert ffk.fast_path_count > 0, "flash path never engaged"
    assert got == want
    assert [len(o) for o in got] == [7, 7, 7, 7]


# ---------------------------------------------------------------------------
# No cache-sized relayout in a step
# ---------------------------------------------------------------------------
def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it, but not of a
    kernel's body (its refs are blocks in VMEM, not the cache)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _relayouts(closed, elems):
    return [(e.primitive.name, v.aval.shape)
            for e in _eqns(closed.jaxpr)
            if e.primitive.name in ("reshape", "transpose", "copy", "copy_p")
            for v in e.invars
            if hasattr(v.aval, "shape") and np.prod(v.aval.shape) >= elems]


@pytest.mark.parametrize("step", ["decode_block", "compact_prefill"])
def test_no_step_relays_a_layer_of_the_cache(step, monkeypatch):
    """The gain of storing the cache packed is that no step reshapes it:
    the decode block and the compact prefill step of a tiny D=64 model
    hold no reshape, transpose or copy whose operand is as large as one
    layer's cache."""
    from flexflow_tpu.serve.engine import make_decode_block
    from flexflow_tpu.serve.inference_manager import InferenceManager

    monkeypatch.setenv("FF_PALLAS_INTERPRET", "1")
    ffk.reset_dispatch_stats()
    m = _tiny_d64(InferenceMode.INC_DECODING_MODE, seed=0)
    ifm = InferenceManager(m)
    k = m.op_state["kv_cache"]["k"]
    assert kvl.pack_of(k, 256) == 2
    layer = int(np.prod(k.shape[1:]))
    rng = jax.random.PRNGKey(0)
    Rm = m.config.max_requests_per_batch
    if step == "decode_block":
        assert ifm.decode_width == 1    # the kernel on, no engine verifies m
        block = make_decode_block(m, ifm._compute_dtype, 4,
                                  width=ifm.decode_width)
        closed = jax.make_jaxpr(block)(
            m.params, m.op_state, jnp.zeros(Rm, jnp.int32),
            jnp.zeros(Rm, jnp.int32), jnp.ones(Rm, bool), rng, jnp.int32(2))
    else:
        chunk, segments = RequestManager._prefill_shape(m.config)
        # an even and an odd start, and two segments of one slot
        rows = [(0, list(range(1, chunk + 1)), 0),
                (0, list(range(1, chunk + 1)), chunk),
                (2, list(range(1, 8)), 33), (3, [5], 200)][:segments]
        meta = RequestManager._meta_from_segments(segments, chunk, rows)
        closed = jax.make_jaxpr(ifm._step_impl)(m.params, m.op_state, meta,
                                                rng)
    assert ffk.fast_path_count > 0 and not ffk.fallback_counts
    calls = [e for e in _eqns(closed.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 2, "one kernel a layer"
    assert _relayouts(closed, layer) == []
