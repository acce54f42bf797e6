"""Mistral-4 on the serving path, at tiny sizes on the CPU: multi-head latent
attention over a latent cache (one shared entry a position), YaRN rotary with
a query scaled by its position, a share of the router's experts held here
with a shared expert.

The latent kernel (interpreted) against the jnp oracle in its decode and
row-map forms at ragged lengths; the absorbed form against the expanded one;
the owner's entry arithmetic; the program, through chunked prefill and cached
decode past ``original_max_position_embeddings``, against the plain
reference (which has teeth for YaRN's ramp and for the position scale); the
four expert shares against the whole layer; the HF weight map; what refuses
a latent layer; and the series telemetry keeps of the kind.
"""

import functools
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import flexflow_tpu as ff
from flexflow_tpu.ffconst import InferenceMode
from flexflow_tpu.kernels import moe as K
from flexflow_tpu.kernels.attention import (_pick_latent_blocks,
                                            flash_attend_latent,
                                            latent_form,
                                            reference_attend_latent,
                                            supports_latent)
from flexflow_tpu.models import FAMILIES
from flexflow_tpu.models.mistral4 import (Mistral4Config,
                                          create_mistral4_model,
                                          rope_permutation, yarn_inv_freq)
from flexflow_tpu.ops import kv_layout as kvl
from flexflow_tpu.ops import latent_attention as LA
from flexflow_tpu.ops.inc_attention import LATENT_STACK, commit_tree_kv

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# positions cross original_max_position_embeddings (32) three times in 120;
# factor 8 puts YaRN's ramp over rotary dims 2..9 of the 16
ROPE = dict(rope_type="yarn", rope_theta=10000.0, factor=8.0, beta_fast=32,
            beta_slow=1, mscale=1, mscale_all_dim=1,
            original_max_position_embeddings=32, llama_4_scaling_beta=0.1)
TINY = dict(vocab_size=256, hidden_size=128, moe_intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, q_lora_rank=64,
            kv_lora_rank=128, qk_nope_head_dim=64, qk_rope_head_dim=32,
            v_head_dim=64, n_routed_experts=16, num_experts_per_tok=4,
            rope_parameters=ROPE)
HELD = (4, 4)


@pytest.fixture(scope="module")
def bench():
    """The benchmark's family and reference for Mistral-4, loaded as run.py
    loads them."""
    sys.path.insert(0, ROOT)
    try:
        from benchmark.run import load_module

        yield (load_module("families", "mistral4"),
               load_module("reference", "mistral4"))
    finally:
        sys.path.remove(ROOT)


def _build(held=HELD, mode=InferenceMode.INC_DECODING_MODE, tiny=TINY, **ffkw):
    kw = dict(max_requests_per_batch=2, max_sequence_length=512,
              max_tokens_per_batch=64, seed=3, compute_dtype="float32",
              kv_cache_dtype="float32", num_devices=1)
    kw.update(ffkw)
    m = ff.FFModel(ff.FFConfig(**kw))
    c = Mistral4Config(**tiny, held_experts=held)
    create_mistral4_model(m, c, mode=mode, data_type=ff.DataType.DT_FLOAT)
    m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    return m, c


def _reference_cfg(c, **rope):
    return dict(TINY, rms_norm_eps=c.rms_norm_eps,
                routed_scaling_factor=c.routed_scaling_factor,
                rope_parameters=dict(ROPE, **rope))


# ---------------------------------------------------------------------------
# the latent kernel against the oracle
# ---------------------------------------------------------------------------

#  name: (Q, start positions, real tokens, row map or "append", stacked)
KERNEL_CASES = {
    "decode_at_position_0_a_block_edge_and_the_last":
        (1, (0, 1024, 2047), (1, 1, 1), False, False),
    "decode_on_a_stack_with_an_idle_row":
        (1, (5, 300, 1500), (1, 0, 1), False, True),
    "decode_append_at_position_0_a_block_edge_and_the_last":
        (1, (0, 1024, 2047), (1, 1, 1), "append", False),
    "decode_append_on_a_stack_with_an_idle_row":
        (1, (15, 1039, 1500), (1, 0, 1), "append", True),
    # one token a row at the published 32 heads: 32 query rows a program
    "decode_append_of_32_heads_on_a_stack":
        (1, (0, 1023, 2047), (1, 1, 1), "append", True),
    "row_map_chunks_ragged_on_a_stack_with_an_idle_row":
        (8, (0, 250, 1300), (8, 0, 5), True, True),
    "row_map_two_segments_of_one_slot":
        (16, (1000, 1016, 40), (16, 16, 3), "twice", False),
    "grid_chunks_across_a_dma_block_edge":
        (16, (1016, 5, 2030), (16, 16, 16), False, False),
    # the decode step's block form scores a whole DMA block (1024 positions,
    # four softmax partitions) and masks the partitions past the row's end
    "decode_append_last_block_of_1_2_and_3_live_partitions":
        (1, (1124, 1324, 1624), (1, 1, 1), "append", False),
    "decode_append_last_block_of_4_live_partitions_and_partition_edges":
        (1, (1900, 255, 256), (1, 1, 1), "append", False),
    "decode_append_at_positions_1023_and_1024":
        (1, (1023, 1024, 1022), (1, 1, 1), "append", True),
    "decode_over_a_row_of_exactly_one_dma_block":
        (1, (1023, 2047, 511), (1, 1, 1), False, False),
    "decode_append_hand_off_over_an_idle_row_between_two_live_rows":
        (1, (1500, 700, 1100), (1, 0, 1), "append", False),
    "decode_append_after_an_idle_first_row":
        (1, (5, 1500, 2040), (0, 1, 1), "append", True),
    # 128 tokens a row of 4 heads are 512 query rows: a block's scores (2
    # MiB) do not fit beside the stream, so the partition loop serves them,
    # as it serves a cell's prefill segment
    "row_map_chunks_ragged_in_the_partition_form":
        (128, (0, 250, 1300), (128, 0, 77), True, True),
    "grid_chunks_across_a_dma_block_edge_in_the_partition_form":
        (128, (960, 5, 1900), (128, 128, 100), False, False),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_latent_flash_attend_matches_the_oracle(case):
    """2048 positions of 256 stored values, the first 128 the latent: the
    kernel (interpreted) fetches a block once, scores against all of it and
    takes its first 128 lanes as values; DMA blocks of 1024 positions,
    softmax sub-blocks of 256, only the sub-blocks that hold a valid
    position. ``rows``: batch row r reads cache row rows[r]. ``append``: a
    decode step's entry lands at its position in place, before the row is
    attended, and an idle row's cache is left as it was."""
    Q, starts, nums, row_map, stacked = KERNEL_CASES[case]
    R, H, W, rank, S = 3, 32 if "32_heads" in case else 4, 256, 128, 2048
    assert latent_form(H * Q, S) == (
        "partition" if "partition_form" in case else "block")
    rng = np.random.default_rng(0)
    starts, nums = np.array(starts), np.array(nums)
    lengths = np.where(nums > 0, starts + nums, 0)
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)
    i32 = lambda a: jnp.asarray(a, jnp.int32)
    cache = bf(rng.standard_normal((R, 1, S, W)) * 0.5)
    q = bf(rng.standard_normal((R, Q, H, W)))
    qpos = starts[:, None] + np.arange(Q)[None]
    rows = {True: i32([2, 0, 1]), "twice": i32([1, 1, 0])}.get(row_map)
    c, layer = cache, None
    append = None
    if row_map == "append":
        # the cache as before the step: the new position holds something
        # else, and the kernel writes the entry there and nowhere else
        at = (np.arange(R), 0, np.maximum(lengths - 1, 0))
        append = (cache[at][:, None, None], i32(np.where(nums > 0, starts,
                                                         -1)))
        c = cache.at[at].set(7.0)
        for r in np.nonzero(nums == 0)[0]:      # an idle row's stays
            cache = cache.at[r, 0, 0].set(7.0)
    if stacked:
        c, layer = jnp.stack([c * 0, c]), 1
    out = flash_attend_latent(q, c, i32(lengths), i32(qpos), rows, append,
                              rank=rank, qk_scale=0.1, layer_idx=layer,
                              interpret=True)
    if append is not None:
        out, c2 = out
        np.testing.assert_array_equal(
            np.asarray(c2[layer] if stacked else c2, np.float32),
            np.asarray(cache, np.float32))
        assert not stacked or not np.asarray(c2[0]).any()
    want = reference_attend_latent(
        q, cache if rows is None else cache[rows], i32(lengths), i32(qpos),
        rank=rank, qk_scale=0.1)
    assert out.shape == (R, Q, H, rank)
    for r in np.nonzero(nums)[0]:
        np.testing.assert_allclose(
            np.asarray(out[r, :nums[r]], np.float32),
            np.asarray(want[r, :nums[r]], np.float32), atol=3e-2)


def test_block_form_and_partition_form_agree_bit_for_bit():
    """The same queries over the same cache through both forms of the
    kernel: one token a row of 4 heads (a block's scores fit beside the
    stream: the block form) against the same rows as token 0 of a call of
    128 tokens a row (512 query rows do not: the partition loop). Each
    score is the same dot product and the softmax advances through the same
    partitions, so the outputs are equal bit for bit."""
    R, H, W, rank, S = 3, 4, 256, 128, 2048
    rng = np.random.default_rng(1)
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)
    i32 = lambda a: jnp.asarray(a, jnp.int32)
    cache = bf(rng.standard_normal((R, 1, S, W)) * 0.5)
    q = bf(rng.standard_normal((R, 128, H, W)))
    starts = np.array([1023, 300, 1700])
    lengths = i32(starts + 1)
    qpos = starts[:, None] + np.arange(128)[None]
    assert latent_form(H, S) == "block"
    assert latent_form(H * 128, S) == "partition"
    attend = functools.partial(flash_attend_latent, rank=rank, qk_scale=0.1,
                               interpret=True)
    narrow = attend(q[:, :1], cache, lengths, i32(qpos[:, :1]))
    wide = attend(q, cache, lengths, i32(qpos))
    np.testing.assert_array_equal(np.asarray(narrow, np.float32),
                                  np.asarray(wide[:, :1], np.float32))
    # and the decode step's own call, the append fused: the same bits
    at = (np.arange(R), 0, starts)
    fused, c2 = attend(q[:, :1], cache.at[at].set(7.0), lengths,
                       i32(qpos[:, :1]), None,
                       (cache[at][:, None, None], i32(starts)))
    np.testing.assert_array_equal(np.asarray(fused, np.float32),
                                  np.asarray(narrow, np.float32))
    np.testing.assert_array_equal(np.asarray(c2, np.float32),
                                  np.asarray(cache, np.float32))


def test_the_form_is_decided_from_the_shapes_of_the_call():
    """Both cells' decode steps take the block form and their prefill
    segments the partition loop; the rule turns at 256 query rows of a
    1024-position block (1 MiB of float32 scores)."""
    assert latent_form(32, 32768) == "block"            # Mistral's decode
    assert latent_form(32 * 128, 32768) == "partition"  # and its segment
    assert latent_form(64, 8192) == "block"             # LongCat's decode
    assert latent_form(32 * 128, 8192) == "partition"   # a group of 32 heads
    assert latent_form(256, 8192) == "block"
    assert latent_form(257, 8192) == "partition"
    assert latent_form(512, 512) == "block"             # a block of 512


def test_latent_blocks_depend_on_the_cache_alone():
    """The softmax partition is the same for every query width (the rule
    ``_pick_block_s`` states): it is picked from S, and from nothing else."""
    assert _pick_latent_blocks(32768) == (1024, 256)    # the cell's
    assert _pick_latent_blocks(512) == (512, 256)
    assert _pick_latent_blocks(384) == (128, 128)
    assert _pick_latent_blocks(200) == (0, 0)
    assert supports_latent(32768, 384, 256)
    assert not supports_latent(32768, 320, 256)         # lanes not full
    assert not supports_latent(32768, 384, 192)         # values not whole tiles
    v = jnp.broadcast_to(jnp.arange(64, dtype=jnp.float32)[:, None], (64, 8))
    out = reference_attend_latent(
        jnp.zeros((1, 3, 2, 8)), v[None, None], jnp.asarray([44]),
        jnp.asarray([[41, 42, 43]]), rank=4, qk_scale=1.0)
    np.testing.assert_allclose(np.asarray(out[0, :, 0, 0]),
                               [20.5, 21.0, 21.5], rtol=1e-6)   # causal means


def test_the_owner_lays_out_an_entry_and_a_query_alike():
    """ops/kv_layout: an entry is [latent | rope | zeros] in whole lane
    tiles where the kernel serves it, the exact entry elsewhere; a query
    carried into the stored space has the same layout; positions read
    back are what was appended."""
    assert kvl.latent_width(256, 64, True) == 384       # 768 B in bf16
    assert kvl.latent_width(256, 64, False) == 320      # the published 640
    assert kvl.latent_cache_shape(16, 32768, 384) == (16, 1, 32768, 384)
    rng = np.random.default_rng(1)
    lat = jnp.asarray(rng.standard_normal((2, 3, 8)), jnp.float32)
    rope = jnp.asarray(rng.standard_normal((2, 3, 4)), jnp.float32)
    entry = kvl.latent_entry(lat, rope, 16)
    assert entry.shape == (2, 3, 1, 16)
    np.testing.assert_array_equal(np.asarray(entry[:, :, 0, :8]), lat)
    np.testing.assert_array_equal(np.asarray(entry[:, :, 0, 8:12]), rope)
    assert not np.asarray(entry[..., 12:]).any()
    q = kvl.latent_query(jnp.ones((2, 3, 5, 8)), jnp.ones((2, 3, 5, 4)), 16)
    assert q.shape == (2, 3, 5, 16) and float(q.sum()) == 2 * 3 * 5 * 12
    cache = jnp.zeros((2, 2, 1, 32, 16))                # a stack of two
    cache = LA.append_latent(cache, 1, entry, jnp.asarray([4, 29]),
                             jnp.asarray([3, 2]), jnp.asarray([True, True]),
                             None)
    got_l, got_r = kvl.read_latent(cache, 4, 7, 8, 4, at=(1, 0))
    np.testing.assert_array_equal(np.asarray(got_l), lat[0])
    np.testing.assert_array_equal(np.asarray(got_r), rope[0])
    np.testing.assert_array_equal(                      # a padded tail drops
        np.asarray(kvl.read_latent(cache, 29, 32, 8, 4, at=(1, 1))[0]),
        np.concatenate([lat[1, :2], np.zeros((1, 8))]))
    assert not np.asarray(cache[0]).any()


def test_absorbed_equals_expanded():
    """Queries carried into the latent space through the key half and the
    output carried out through the value half give what attending the
    expanded per-head keys and values gives; a quantised half keeps one
    scale per (head, column), as ``kv_b_proj``'s columns have."""
    from flexflow_tpu.quant import quantize_array

    rng = np.random.default_rng(2)
    T, H, rank, dn, dr, dv = 9, 4, 128, 64, 16, 64
    f = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)
    wk, wv = f(H, rank, dn), f(H, rank, dv)
    q_nope, q_rope = f(1, T, H, dn), f(1, T, H, dr)
    c_kv, k_rope = f(1, T, rank), f(1, T, dr)
    for quant in (False, True):
        wk_, wv_ = ((quantize_array(wk, "int8"), quantize_array(wv, "int8"))
                    if quant else (wk, wv))
        if quant:
            assert wk_.scale.shape == (H, dn) and wv_.scale.shape == (H, dv)
            from flexflow_tpu.quant import dequantize_array
            wk, wv = dequantize_array(wk_), dequantize_array(wv_)
        width = rank + dr
        qf = kvl.latent_query(LA.absorb_queries(q_nope, wk_), q_rope, width)
        cache = kvl.latent_entry(c_kv, k_rope, width).transpose(0, 2, 1, 3)
        lengths, qpos = jnp.asarray([T]), jnp.arange(T)[None]
        o_lat = reference_attend_latent(qf, cache, lengths, qpos, rank=rank,
                                        qk_scale=0.2)
        got = LA.carry_out(o_lat, wv_, jnp.float32)
        # expanded: per-head keys [c W_k | k_rope] and values c W_v
        k = jnp.concatenate([jnp.einsum("tc,hcd->thd", c_kv[0], wk),
                             jnp.broadcast_to(k_rope[0][:, None],
                                              (T, H, dr))], -1)
        v = jnp.einsum("tc,hcd->thd", c_kv[0], wv)
        s = jnp.einsum("qhd,khd->hqk",
                       jnp.concatenate([q_nope[0], q_rope[0]], -1), k) * 0.2
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
        want = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        np.testing.assert_allclose(np.asarray(got[0]),
                                   np.asarray(want.reshape(T, H * dv)),
                                   rtol=2e-4, atol=2e-4)


def test_yarn_is_a_frequency_table_and_the_pairing_a_permutation():
    """The published configuration's numbers: the ramp runs over rotary dims
    12..25 of 32, the softmax scale is 0.19497, cos and sin keep factor 1,
    s(p) steps at multiples of 8192; ``rotary_cos_sin`` given the table."""
    from flexflow_tpu.ops.inc_attention import rotary_cos_sin

    rope = dict(ROPE, factor=128, original_max_position_embeddings=8192)
    f = yarn_inv_freq(64, rope)
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(f[:13], base[:13], rtol=1e-12)
    np.testing.assert_allclose(f[25:], base[25:] / 128, rtol=1e-12)
    assert (f[13:25] < base[13:25]).all() and (f[13:25] > base[13:25] / 128
                                               ).all()
    np.testing.assert_allclose(yarn_inv_freq(64, {"rope_theta": 10000.0}),
                               base, rtol=1e-12)
    c = Mistral4Config(rope_parameters=rope)
    r = c.rotary()
    assert abs(r["softmax_scale"] - 0.19497) < 1e-5 and r["rope_factor"] == 1
    assert (r["pos_scale_beta"], r["pos_scale_period"]) == (0.1, 8192)
    s_p = LA.position_scale(jnp.asarray([[0, 8191, 8192, 16384, 32767]]),
                            0.1, 8192)
    np.testing.assert_allclose(np.asarray(s_p[0]),
                               [1, 1, 1.0693, 1.1099, 1.1386], atol=1e-4)
    pos = jnp.asarray([[0, 7, 100]])
    cos, sin = rotary_cos_sin(pos, 64, 10000.0, jnp.float32, inv_freq=f)
    np.testing.assert_allclose(np.asarray(cos[0, 2, :32]),
                               np.cos(100 * f), rtol=1e-4, atol=1e-5)
    plain = rotary_cos_sin(pos, 64, 10000.0, jnp.float32)     # as it was
    np.testing.assert_allclose(np.asarray(plain[1][0, 1, 32:]),
                               np.sin(7 * base), rtol=1e-5, atol=1e-6)
    assert rope_permutation(8).tolist() == [0, 2, 4, 6, 1, 3, 5, 7]


# ---------------------------------------------------------------------------
# the program against the plain reference, through the latent cache
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant", [None, "int8"])
def test_program_matches_plain_reference_through_the_latent_cache(bench,
                                                                  quant):
    """Six prefill chunks of 16, then 24 tokens decoded one at a time: 120
    positions (``original_max_position_embeddings`` is 32, so YaRN's ramp
    and the position scale both act) through the latent cache, float32
    compute; the program's routes validated against the reference's biased
    scores and the logits compared on those routes. The reference is the
    EXPANDED form with the published adjacent pairing; the program is the
    absorbed form with rotate-half on permuted columns."""
    family, reference = bench
    m, c = _build(quantization_type=quant)
    if quant:
        from flexflow_tpu.quant import QuantizedWeight

        a = m.params["layers.0.self_attn"]
        assert all(isinstance(a[n], QuantizedWeight) for n in
                   ("wq_a", "wq_b", "wkv_a", "wk_b", "wv_b", "wo"))
        assert a["wk_b"].scale.shape == (4, 64)     # a head's own columns
    assert m.op_state[LATENT_STACK]["c"].shape == (2, 2, 1, 512, 160)
    toks = np.random.default_rng(5).integers(1, 256, size=120)
    chunks = [16] * 6 + [1] * 24
    ours, routes = family.program_logits_and_routes(m, toks, chunks)
    cfg = _reference_cfg(c)
    weights = lambda: family._reference_weights(m, c)
    ref, scores = reference.forward_routed(weights(), jnp.asarray(toks), cfg,
                                           routes=routes, held=HELD)
    checked = family.check_routes(routes, scores, family.ROUTE_MARGIN)
    assert checked["routes_ok"] and checked["route_flips"] == 0
    np.testing.assert_allclose(ours, np.asarray(ref), rtol=3e-4, atol=3e-4)
    # the check has teeth: each convention moves the logits by far more
    # than the cell's tolerance
    got = lambda cfg_: reference.forward_routed(
        weights(), jnp.asarray(toks), cfg_, routes=routes, held=HELD)[0]
    wrong = {"no_yarn_ramp": _reference_cfg(c, rope_type="default"),
             "no_position_scale": _reference_cfg(c, llama_4_scaling_beta=0.0),
             "no_mscale_in_the_softmax_scale": _reference_cfg(
                 c, mscale_all_dim=0.0),
             "another_original_context": _reference_cfg(
                 c, original_max_position_embeddings=64)}
    for name, cfg_ in wrong.items():
        assert not family.C.compare_logits(ours, got(cfg_), 0.03)["ok"], name
    # and the pairing: the served columns handed over unpermuted are another
    # model
    raw = weights()
    raw["layers"] = [dict(lw, wkv_a=np.asarray(
        family.C.dense(m.params[f"layers.{i}.self_attn"]["wkv_a"])))
        for i, lw in enumerate(raw["layers"])]
    assert not family.C.compare_logits(
        ours, reference.forward_routed(raw, jnp.asarray(toks), cfg,
                                       routes=routes, held=HELD)[0],
        0.03)["ok"]


def test_the_kernel_serves_the_program_interpreted(bench, monkeypatch):
    """The same comparison with the Pallas kernels interpreted: the cache is
    then in whole lane tiles ([.., 256]), prefill and decode run
    ``flash_attend_latent``, and nothing falls back."""
    import flexflow_tpu.kernels as ffk

    monkeypatch.setenv("FF_PALLAS_INTERPRET", "1")
    ffk.reset_dispatch_stats()
    family, reference = bench
    m, c = _build()
    assert m.op_state[LATENT_STACK]["c"].shape == (2, 2, 1, 512, 256)
    toks = np.random.default_rng(6).integers(1, 256, size=100)
    ours, routes = family.program_logits_and_routes(
        m, toks, [16] * 5 + [1] * 20)
    ref, _ = reference.forward_routed(
        family._reference_weights(m, c), jnp.asarray(toks),
        _reference_cfg(c), routes=routes, held=HELD)
    np.testing.assert_allclose(ours, np.asarray(ref), rtol=3e-4, atol=3e-4)
    assert ffk.fast_path_count > 0 and not ffk.fallback_counts
    assert K.fast_path_count > 0 and not K.fallback_counts
    # the decode step's 4 query rows take the block form, the append fused;
    # a prefill chunk's 64 rows over a block of 512 positions (128 KB of
    # scores) fit too at this size, where a cell's 4096 rows do not
    # (test_the_form_is_decided_from_the_shapes_of_the_call); one trace a
    # layer each
    assert ffk.latent_form_counts == {("block", "append"): 2,
                                      ("block", "grid"): 2}
    assert ffk.latent_summary() == ("latent kernel: block form, append: 2 "
                                    "traces; block form, grid: 2 traces")


def test_the_blocked_reference_is_the_whole_one_on_its_tail(bench):
    """One layer is a whole period, so the positions before the compared
    tail need only their keys: ``last`` computes queries, experts and
    logits of the tail alone and gives the whole forward's numbers there."""
    family, reference = bench
    m, c = _build(tiny=dict(TINY, num_hidden_layers=1))
    toks = jnp.asarray(np.random.default_rng(8).integers(1, 256, size=90))
    cfg = _reference_cfg(c)
    whole, _ = reference.forward_routed(family._reference_weights(m, c), toks,
                                        cfg, held=HELD)
    tail, scores = reference.forward_routed(
        family._reference_weights(m, c), toks, cfg, held=HELD, last=20)
    assert tail.shape == (20, 256) and scores[0].shape == (20, 16)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(whole[-20:]),
                               rtol=1e-5, atol=1e-5)
    two, c2 = _build()
    with pytest.raises(AssertionError, match="one layer"):
        reference.forward_routed(family._reference_weights(two, c2), toks,
                                 cfg, held=HELD, last=20)


def test_reference_shares_add_up_with_the_shared_expert_counted_once(bench):
    """The plain reference's sparse layer: the four shares' outputs, less
    the shared expert that every chip computes alike in three of them, are
    the uncut layer."""
    _, reference = bench
    rng = np.random.default_rng(2)
    f = lambda *s: jnp.asarray(rng.normal(0, 0.3, size=s), jnp.float32)
    E, H, I = 16, 32, 24
    lw = {"router": f(H, E), "bias": f(E) * 0.2, "gate": f(E, H, I),
          "up": f(E, H, I), "down": f(E, I, H), "s_gate": f(H, I),
          "s_up": f(H, I), "s_down": f(I, H)}
    lw = {k: np.asarray(v) for k, v in lw.items()}
    cfg = {"num_experts_per_tok": 4, "routed_scaling_factor": 1.0}
    mm = reference._matmul(None)[0]
    m = np.asarray(f(10, H))
    whole, scores = reference._sparse(mm, m, lw, cfg, None, (0, E))
    shared = reference._swiglu(mm, m, lw["s_gate"], lw["s_up"], lw["s_down"])
    total = 0.0
    for first in range(0, E, 4):
        part = {**lw, **{n: lw[n][first:first + 4]
                         for n in ("gate", "up", "down")}}
        y, s = reference._sparse(mm, m, part, cfg, None, (first, 4))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(scores))
        total = total + (y - shared)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# the HF weight map
# ---------------------------------------------------------------------------

def test_hf_weight_map_reads_the_held_experts_and_keeps_the_pairing(bench):
    """A checkpoint in the published layout (``kv_b_proj`` whole, rope
    columns in adjacent pairs, one Linear an expert, a vision tower beside
    the text model) loaded through the family: only the held experts are
    read, the vision keys are dropped unread, and the program's logits are
    the reference's on the SAME checkpoint read directly (so the load-time
    permutation and the reference's adjacent pairing agree)."""
    family, reference = bench
    m, c = _build()
    H, dn, dr, dv, rank, qr, E = 4, 64, 32, 64, 128, 64, 128
    rng = np.random.default_rng(4)
    f = lambda *s: (rng.standard_normal(s) * 0.08).astype(np.float32)
    sd = {"model.embed_tokens.weight": f(256, E), "model.norm.weight":
          1 + f(E), "lm_head.weight": f(256, E),
          "vision_tower.patch_conv.weight": np.zeros((2, 2)),
          "multi_modal_projector.linear_1.weight": np.zeros((2, 2))}
    for i in range(2):
        a, p = f"model.layers.{i}.self_attn", f"model.layers.{i}.mlp"
        sd.update({
            f"{a}.q_a_proj.weight": f(qr, E),
            f"{a}.q_a_layernorm.weight": 1 + f(qr),
            f"{a}.q_b_proj.weight": f(H * (dn + dr), qr),
            f"{a}.kv_a_proj_with_mqa.weight": f(rank + dr, E),
            f"{a}.kv_a_layernorm.weight": 1 + f(rank),
            f"{a}.kv_b_proj.weight": f(H * (dn + dv), rank),
            f"{a}.o_proj.weight": f(E, H * dv),
            f"model.layers.{i}.input_layernorm.weight": 1 + f(E),
            f"model.layers.{i}.post_attention_layernorm.weight": 1 + f(E),
            f"{p}.gate.weight": f(16, E),
            f"{p}.gate.e_score_correction_bias": f(16),
            f"{p}.shared_experts.gate_proj.weight": f(64, E),
            f"{p}.shared_experts.up_proj.weight": f(64, E),
            f"{p}.shared_experts.down_proj.weight": f(E, 64)})
        for e in range(16):
            for proj, shape in (("gate_proj", (64, E)), ("up_proj", (64, E)),
                                ("down_proj", (E, 64))):
                sd[f"{p}.experts.{e}.{proj}.weight"] = (
                    f(*shape) if HELD[0] <= e < sum(HELD)
                    else np.full(shape, np.nan, np.float32))    # never read
    fam = FAMILIES["mistral4"]
    n = fam.load_hf(m, c, sd)
    assert n == len(fam.hf_weight_map(c)) and {
        v[0] for v in fam.hf_weight_map(c).values()} == set(m.params)
    assert not any(np.isnan(np.asarray(leaf)).any()
                   for leaf in jax.tree.leaves(m.params))
    # the reference's weights straight from the checkpoint, published order
    g = lambda k: jnp.asarray(sd[k])
    layers = []
    for i in range(2):
        a, p = f"model.layers.{i}.self_attn", f"model.layers.{i}.mlp"
        kvb = np.asarray(sd[f"{a}.kv_b_proj.weight"]).reshape(H, dn + dv,
                                                               rank)
        held = range(HELD[0], sum(HELD))
        layers.append({
            "ln1": g(f"model.layers.{i}.input_layernorm.weight"),
            "wq_a": g(f"{a}.q_a_proj.weight").T,
            "q_norm": g(f"{a}.q_a_layernorm.weight"),
            "wq_b": g(f"{a}.q_b_proj.weight").T,
            "wkv_a": g(f"{a}.kv_a_proj_with_mqa.weight").T,
            "kv_norm": g(f"{a}.kv_a_layernorm.weight"),
            "wk_b": jnp.asarray(kvb[:, :dn].transpose(0, 2, 1)),
            "wv_b": jnp.asarray(kvb[:, dn:].transpose(0, 2, 1)),
            "wo": g(f"{a}.o_proj.weight").T,
            "ln2": g(f"model.layers.{i}.post_attention_layernorm.weight"),
            "router": g(f"{p}.gate.weight").T,
            "bias": g(f"{p}.gate.e_score_correction_bias"),
            **{n_: jnp.stack([g(f"{p}.experts.{e}.{n_}_proj.weight").T
                              for e in held]) for n_ in ("gate", "up", "down")},
            **{f"s_{n_}": g(f"{p}.shared_experts.{n_}_proj.weight").T
               for n_ in ("gate", "up", "down")}})
    weights = {"emb": g("model.embed_tokens.weight"), "layers": layers,
               "norm": g("model.norm.weight"), "head": g("lm_head.weight").T}
    toks = np.random.default_rng(9).integers(1, 256, size=70)
    ours, routes = family.program_logits_and_routes(m, toks,
                                                    [16] * 4 + [1] * 6)
    ref, _ = reference.forward_routed(weights, jnp.asarray(toks),
                                      _reference_cfg(c), routes=routes,
                                      held=HELD)
    np.testing.assert_allclose(ours, np.asarray(ref), rtol=3e-4, atol=3e-4)
    # and the family's way back from the served weights is the checkpoint
    back = list(family._reference_weights(m, c)["layers"])
    for name in ("wq_b", "wkv_a", "wk_b", "wv_b"):
        np.testing.assert_allclose(back[1][name], np.asarray(layers[1][name]),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# what does not support a latent layer says so
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
    if name == "grouped_routing":
        return lambda: Mistral4Config.from_hf_config(dict(TINY, n_group=2))
    if name == "another_rope_type":
        return lambda: Mistral4Config.from_hf_config(
            dict(TINY, rope_parameters=dict(ROPE, rope_type="longrope")))
    if name == "a_full_rank_query":
        return lambda: Mistral4Config.from_hf_config(
            dict(TINY, q_lora_rank=None))
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
    if name == "tree_batch_on_the_op":
        from flexflow_tpu.ops.base import OpContext

        ctx = OpContext(training=False, rng=None,
                        compute_dtype=jnp.float32,
                        batch_config=type("M", (), {"ancestor": 0})())
        layer = next(ly for ly in m.layers if "kv_lora_rank" in ly.attrs)
        return lambda: LA.IncMultiHeadLatentAttention.forward(
            layer.attrs, m.params[layer.name], [jnp.zeros((2, 1, 128))], ctx)
    raise KeyError(name)


@pytest.mark.parametrize("what,sentence", [
    ("tree_verify_mode", "incremental decoding only"),
    ("beam_search_mode", "incremental decoding only"),
    ("tensor_parallel_mesh", "latent attention layer"),
    ("pipeline_plan", "latent attention layer"),
    ("commit_tree_kv", "one shared entry a position"),
    ("speculation_commit", "one shared entry a position"),
    ("prefix_pool", "shared-prefix pool is not supported over a latent"),
    ("tree_batch_on_the_op", "incremental decoding on one chip"),
    ("grouped_routing", "group"),
    ("another_rope_type", "longrope"),
    ("a_full_rank_query", "q_lora_rank")])
def test_what_cannot_hold_a_latent_entry_refuses_loudly(what, sentence):
    with pytest.raises(NotImplementedError, match=sentence):
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
# the serving loop and what telemetry keeps of the kind
# ---------------------------------------------------------------------------

def test_the_loop_serves_it_and_counts_the_latent_kind(bench):
    """Through RequestManager (compact prefill, decode blocks): the tokens
    are those the slot-grid program gives one request at a time; the
    ``ffsv_attn_positions_read_total{kind="latent"}`` series is what the
    decode steps' rows had to attend in both layers, the gauge what compile
    allocated, the prefill pairs what a causal prefill attends."""
    from flexflow_tpu.serve.request_manager import RequestManager
    from flexflow_tpu.telemetry import ServingTelemetry

    family, _ = bench
    m, c = _build(telemetry=True)
    assert m.attention_kinds == {"latent": {
        "layers": 2, "window": None, "cache_bytes": 2 * 2 * 512 * 160 * 4}}
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
    for res in got:
        p = res.input_tokens
        alone.op_state = jax.tree.map(jnp.zeros_like, alone.op_state)
        toks = list(p) + list(res.output_tokens)
        logits, _ = family.program_logits_and_routes(
            alone, np.asarray(toks[:-1]),
            [16] * (len(p) // 16) + [1] * (len(toks) - 1 - len(p) // 16 * 16))
        assert res.output_tokens == logits[len(p) - 1:].argmax(-1).tolist()
    snap = tel.registry.snapshot()
    lens = [len(p) + j for p in prompts for j in range(new)]
    assert snap['ffsv_attn_positions_read_total{kind="latent"}'][
        "value"] == 2 * sum(lens)
    assert snap['ffsv_kv_cache_bytes{kind="latent"}']["value"] == \
        m.attention_kinds["latent"]["cache_bytes"]
    assert 'ffsv_kv_cache_bytes{kind="full"}' not in snap
    # a prompt of n tokens is prefilled but its last: n-1 tokens, token t
    # sees t+1 positions
    assert snap["ffsv_prefill_attended_pairs_total"]["value"] == sum(
        (len(p) - 1) * len(p) // 2 for p in prompts)
    assert snap['ffsv_moe_tokens_total{phase="decode"}']["value"] == \
        2 * 2 * new
