"""Pallas serving-attention kernel vs jnp oracle.

Runs the actual Pallas kernel in interpreter mode on CPU (the TPU compiles
the same code natively), mirroring the reference's per-op GPU test harness
idea (reference tests/ops/ + tests/align/) for our hot serving kernel.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from flexflow_tpu.kernels import attention as fa
from flexflow_tpu.kernels.attention import NEG_INF, reference_attend
from flexflow_tpu.ops import kv_layout as kvl


def flash_attend(q, k, v, *args, **kw):
    """``kernels.attention.flash_attend`` on position-major test caches
    ``[.., S, D]``: handed over in the stored layout (packed at D=64,
    ops/kv_layout.py), and a fused append's caches unpacked again."""
    pack = fa._pack_factor(q.shape[-1])
    out = fa.flash_attend(q, kvl.to_rows(k, pack), kvl.to_rows(v, pack),
                          *args, **kw)
    if kw.get("append_kv") is None:
        return out
    return (out[0],) + tuple(kvl.to_positions(c, pack) for c in out[1:])


def _mk(R, Q, H, KH, D, S, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(R, Q, H, D).astype(np.float32), dtype)
    k = jnp.asarray(rng.randn(R, KH, S, D).astype(np.float32), dtype)
    v = jnp.asarray(rng.randn(R, KH, S, D).astype(np.float32), dtype)
    return q, k, v


def _cmp(ref, out, lengths, tol):
    act = np.asarray(lengths) > 0
    r = np.asarray(ref, np.float32)[act]
    o = np.asarray(out, np.float32)[act]
    np.testing.assert_allclose(r, o, atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_flash_decode_matches_reference(dtype, tol):
    R, Q, H, KH, D, S = 4, 1, 8, 4, 128, 256
    q, k, v = _mk(R, Q, H, KH, D, S, dtype)
    lengths = jnp.asarray([37, 1, 256, 0], jnp.int32)
    qpos = (lengths - 1).clip(0)[:, None]
    ref = reference_attend(q, k, v, lengths, qpos)
    out = flash_attend(q, k, v, lengths, qpos, interpret=True)
    _cmp(ref, out, lengths, tol)


def test_flash_prefill_causal():
    R, Q, H, KH, D, S = 3, 32, 8, 8, 64, 256
    q, k, v = _mk(R, Q, H, KH, D, S)
    lengths = jnp.asarray([32, 7, 20], jnp.int32)
    qpos = jnp.tile(jnp.arange(Q, dtype=jnp.int32)[None], (R, 1))
    ref = reference_attend(q, k, v, lengths, qpos)
    out = flash_attend(q, k, v, lengths, qpos, interpret=True)
    _cmp(ref, out, lengths, 2e-5)


def test_flash_tree_bias_and_alibi():
    R, Q, H, KH, D, S = 2, 16, 8, 4, 128, 256
    q, k, v = _mk(R, Q, H, KH, D, S, seed=3)
    lengths = jnp.asarray([100, 60], jnp.int32)
    qpos = jnp.asarray([[i + 40 for i in range(Q)],
                        [i + 20 for i in range(Q)]], jnp.int32)
    rng = np.random.RandomState(7)
    bias = np.where(rng.rand(R, Q, S) < 0.4, NEG_INF, 0.0).astype(np.float32)
    bias[:, :, 0] = 0.0  # at least one visible key per row
    alibi = jnp.asarray((rng.rand(H) * 0.2).astype(np.float32))
    ref = reference_attend(q, k, v, lengths, qpos, bias=jnp.asarray(bias),
                           alibi=alibi, causal=False)
    out = flash_attend(q, k, v, lengths, qpos, bias=jnp.asarray(bias),
                       alibi=alibi, causal=False, interpret=True)
    _cmp(ref, out, lengths, 2e-5)


def test_flash_gqa_groups():
    R, Q, H, KH, D, S = 2, 4, 16, 2, 128, 128
    q, k, v = _mk(R, Q, H, KH, D, S, seed=5)
    lengths = jnp.asarray([128, 50], jnp.int32)
    qpos = jnp.asarray([[124 + i for i in range(Q)],
                        [46 + i for i in range(Q)]], jnp.int32)
    ref = reference_attend(q, k, v, lengths, qpos)
    out = flash_attend(q, k, v, lengths, qpos, interpret=True)
    _cmp(ref, out, lengths, 2e-5)


def test_flash_lengths_clamped_to_cache():
    R, Q, H, KH, D, S = 2, 1, 4, 4, 64, 256
    q, k, v = _mk(R, Q, H, KH, D, S, seed=9)
    lengths = jnp.asarray([S + 64, S], jnp.int32)   # overshoot clamps to S
    qpos = jnp.asarray([[S - 1], [S - 1]], jnp.int32)
    ref = reference_attend(q, k, v, jnp.minimum(lengths, S), qpos)
    out = flash_attend(q, k, v, lengths, qpos, interpret=True)
    _cmp(ref, out, lengths, 2e-5)


def test_serving_attention_op_uses_same_semantics():
    """End-to-end: IncMultiHeadSelfAttention forward on CPU (jnp path) equals
    the Pallas kernel in interpret mode on the same cache/meta."""
    import math

    from flexflow_tpu.ops.inc_attention import append_kv

    R, Q, H, KH, D, S = 2, 1, 8, 4, 64, 256
    rng = np.random.RandomState(11)
    k_cache = jnp.zeros((R, KH, S, D), jnp.float32)
    v_cache = jnp.zeros((R, KH, S, D), jnp.float32)
    # pre-fill 10 positions
    pre_k = jnp.asarray(rng.randn(R, 10, KH, D).astype(np.float32))
    pre_v = jnp.asarray(rng.randn(R, 10, KH, D).astype(np.float32))
    zero = jnp.zeros((R,), jnp.int32)
    act = jnp.ones((R,), bool)
    k_cache = append_kv(k_cache, pre_k, zero, zero + 10, act)
    v_cache = append_kv(v_cache, pre_v, zero, zero + 10, act)
    q = jnp.asarray(rng.randn(R, Q, H, D).astype(np.float32))
    lengths = jnp.asarray([10, 10], jnp.int32)
    qpos = jnp.asarray([[9], [9]], jnp.int32)
    ref = reference_attend(q, k_cache, v_cache, lengths, qpos,
                           qk_scale=1.0 / math.sqrt(D))
    out = flash_attend(q, k_cache, v_cache, lengths, qpos,
                       qk_scale=1.0 / math.sqrt(D), interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               atol=2e-5, rtol=2e-5)


def test_head_dim_64_takes_flash_path_and_matches_jnp(monkeypatch):
    """D=64-class models (GPT-2/StarCoder geometry) must keep the flash
    path WITHOUT cache padding (r2 VERDICT: the former pad-to-128 cost 2x
    KV memory and bandwidth forever) — the kernel packs two positions per
    128-lane cache row instead. Numerics must match the jnp path
    token-for-token."""
    import flexflow_tpu as ff
    import flexflow_tpu.kernels as ffk
    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models.llama import LLAMAConfig, create_llama_model
    from flexflow_tpu.serve.request_manager import RequestManager

    tiny = LLAMAConfig(vocab_size=128, hidden_size=256, intermediate_size=256,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, max_position_embeddings=256)

    def gen():
        cfg = ff.FFConfig(max_requests_per_batch=2, max_sequence_length=256,
                          max_tokens_per_batch=16, seed=0,
                          kv_cache_dtype="float32")
        m = ff.FFModel(cfg)
        create_llama_model(m, tiny, mode=InferenceMode.INC_DECODING_MODE)
        m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
        # the packed flash path needs NO head-dim padding, and the cache
        # is stored as the kernel reads it: two positions to a 128-lane row
        want = (256 // 2, 128) if ffk.use_pallas() else (256, 64)
        assert m.op_state["kv_cache"]["k"].shape[-2:] == want
        rm = RequestManager()
        rm.register_new_request([5, 9, 23], max_new_tokens=6)
        return [r.output_tokens for r in rm.generate_incr_decoding(m)]

    base = gen()                                   # jnp path (CPU)
    monkeypatch.setenv("FF_PALLAS_INTERPRET", "1")  # force Pallas kernels
    ffk.reset_dispatch_stats()
    flash = gen()
    assert ffk.fast_path_count > 0, "flash path never engaged"
    assert not ffk.fallback_counts, ffk.fallback_counts
    assert base == flash


def test_flash_packed_d64_matches_reference():
    """The packed D=64 kernel (two positions per 128-lane row, even/odd
    half sub-blocks) must match the jnp oracle for decode, prefill, bias,
    GQA, and the fused append."""
    R, H, KH, D, S = 4, 8, 4, 64, 512
    for Q, seed in [(1, 0), (8, 1), (16, 2)]:
        q, k, v = _mk(R, Q, H, KH, D, S, seed=seed)
        lengths = jnp.asarray([37, 1, 512, 255], jnp.int32)
        qpos = ((lengths - Q).clip(0)[:, None]
                + jnp.arange(Q, dtype=jnp.int32)[None])
        ref = reference_attend(q, k, v, lengths, qpos)
        out = flash_attend(q, k, v, lengths, qpos, interpret=True)
        _cmp(ref, out, lengths, 2e-5)
    # tree bias path
    Q = 8
    q, k, v = _mk(R, Q, H, KH, D, S, seed=5)
    rng = np.random.RandomState(9)
    bias = jnp.asarray(
        np.where(rng.rand(R, Q, S) < 0.3, NEG_INF, 0.0).astype(np.float32))
    lengths = jnp.asarray([100, 60, 512, 8], jnp.int32)
    qpos = (lengths - 1).clip(0)[:, None] + jnp.arange(Q, dtype=jnp.int32)[None]
    ref = reference_attend(q, k, v, lengths, qpos, bias=bias, causal=False)
    out = flash_attend(q, k, v, lengths, qpos, bias=bias, causal=False,
                       interpret=True)
    _cmp(ref, out, lengths, 2e-5)
    # fused append at D=64 (packed row merge + window write-back)
    k_new = jnp.asarray(rng.randn(R, 1, KH, D).astype(np.float32))
    v_new = jnp.asarray(rng.randn(R, 1, KH, D).astype(np.float32))
    appos = jnp.asarray([36, 0, 511, -1], jnp.int32)
    lengths = jnp.asarray([37, 1, 512, 0], jnp.int32)
    qpos = (appos.clip(0)[:, None] + jnp.arange(Q, dtype=jnp.int32)[None])
    rows = jnp.arange(R)
    valid = appos >= 0
    cols = jnp.where(valid, appos, S)
    k_ref = k.at[rows, :, cols.clip(0, S)].set(
        jnp.where(valid[:, None, None], k_new[:, 0], k[rows, :, cols % S]),
        mode="drop")
    v_ref = v.at[rows, :, cols.clip(0, S)].set(
        jnp.where(valid[:, None, None], v_new[:, 0], v[rows, :, cols % S]),
        mode="drop")
    ref = reference_attend(q, k_ref, v_ref, lengths, qpos)
    out, k_out, v_out = flash_attend(
        q, k, v, lengths, qpos, append_kv=(k_new, v_new, appos),
        interpret=True)
    _cmp(ref, out, lengths, 2e-5)
    k_out = np.asarray(k_out)
    assert k_out.shape == (R, KH, S, D)
    for r in range(R):
        p = int(appos[r])
        if p >= 0:
            np.testing.assert_array_equal(k_out[r, :, p], k_new[r, 0])
            # outside the 8-packed-row (16-position) aligned window the
            # cache is bitwise preserved
            pb = (p // 2 // 8) * 8 * 2
            keep = np.ones(S, bool)
            keep[pb:pb + 16] = False
            np.testing.assert_array_equal(k_out[r][:, keep],
                                          np.asarray(k)[r][:, keep])
        else:
            np.testing.assert_array_equal(k_out[r], np.asarray(k)[r])


def test_fallback_is_recorded_and_warned(monkeypatch):
    import warnings

    import flexflow_tpu.kernels as ffk
    from flexflow_tpu.ops.inc_attention import _attend

    monkeypatch.setenv("FF_PALLAS_INTERPRET", "1")
    ffk.reset_dispatch_stats()
    attrs = dict(head_dim=16, num_q_heads=2, num_kv_heads=2,
                 max_seq_length=100)
    q = jnp.zeros((2, 1, 2, 16))
    k = jnp.zeros((2, 2, 100, 16))   # S=100: not tileable
    lengths = jnp.asarray([1, 1], jnp.int32)
    qpos = jnp.zeros((2, 1), jnp.int32)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        _attend(attrs, q, k, k, lengths, qpos, jnp.float32, None)
        _attend(attrs, q, k, k, lengths, qpos, jnp.float32, None)
    assert sum(ffk.fallback_counts.values()) == 2
    assert len([x for x in w if "jnp path" in str(x.message)]) == 1  # once


def test_flash_fused_append_matches_scatter_oracle():
    """The fused in-place KV append (flash_attend append_kv: in-stream
    VMEM merge + aligned 8-row write-back + cache aliasing) must equal
    scatter-append-then-attend, preserve every cache row outside the
    aligned window, and skip appos<0 rows."""
    R, Q, H, KH, D, S = 4, 8, 8, 4, 128, 256
    q, k, v = _mk(R, Q, H, KH, D, S, seed=11)
    rng = np.random.RandomState(13)
    k_new = jnp.asarray(rng.randn(R, 1, KH, D).astype(np.float32))
    v_new = jnp.asarray(rng.randn(R, 1, KH, D).astype(np.float32))
    # row 3 inactive (appos=-1): nothing appended, nothing attended
    appos = jnp.asarray([37, 0, 255, -1], jnp.int32)
    lengths = jnp.asarray([38, 1, 256, 0], jnp.int32)
    qpos = (appos.clip(0)[:, None] + jnp.arange(Q, dtype=jnp.int32)[None])

    # oracle: scatter-append first, then plain attention
    rows = jnp.arange(R)
    valid = appos >= 0
    cols = jnp.where(valid, appos, S)
    k_ref = k.at[rows, :, cols.clip(0, S)].set(
        jnp.where(valid[:, None, None], k_new[:, 0], k[rows, :, cols % S]),
        mode="drop")
    v_ref = v.at[rows, :, cols.clip(0, S)].set(
        jnp.where(valid[:, None, None], v_new[:, 0], v[rows, :, cols % S]),
        mode="drop")
    ref = reference_attend(q, k_ref, v_ref, lengths, qpos)

    out, k_out, v_out = flash_attend(
        q, k, v, lengths, qpos, append_kv=(k_new, v_new, appos),
        interpret=True)
    _cmp(ref, out, lengths, 2e-5)
    # appended rows landed; everything outside each row's aligned window
    # is bitwise-preserved (the write-back may rewrite up to 8 rows)
    k_out, v_out = np.asarray(k_out), np.asarray(v_out)
    for r in range(R):
        p = int(appos[r])
        if p >= 0:
            np.testing.assert_array_equal(k_out[r, :, p], k_new[r, 0])
            np.testing.assert_array_equal(v_out[r, :, p], v_new[r, 0])
            pb = (p // 8) * 8
            keep = np.ones(S, bool)
            keep[pb:pb + 8] = False
            np.testing.assert_array_equal(k_out[r][:, keep],
                                          np.asarray(k)[r][:, keep])
            # committed rows inside the window below p are re-landed
            # bitwise-identical
            np.testing.assert_array_equal(k_out[r][:, pb:p],
                                          np.asarray(k)[r][:, pb:p])
        else:
            np.testing.assert_array_equal(k_out[r], np.asarray(k)[r])
            np.testing.assert_array_equal(v_out[r], np.asarray(v)[r])


def test_flash_fused_append_stacked_layer():
    """append_kv with the stacked [L, R, KH, S, D] cache + layer_idx:
    only the selected layer's cache changes."""
    L, R, Q, H, KH, D, S = 3, 2, 8, 4, 4, 128, 256
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(R, Q, H, D).astype(np.float32))
    ks = jnp.asarray(rng.randn(L, R, KH, S, D).astype(np.float32))
    vs = jnp.asarray(rng.randn(L, R, KH, S, D).astype(np.float32))
    k_new = jnp.asarray(rng.randn(R, 1, KH, D).astype(np.float32))
    v_new = jnp.asarray(rng.randn(R, 1, KH, D).astype(np.float32))
    appos = jnp.asarray([10, 130], jnp.int32)
    lengths = appos + 1
    qpos = appos[:, None] + jnp.arange(Q, dtype=jnp.int32)[None]
    out, k_out, v_out = flash_attend(
        q, ks, vs, lengths, qpos, append_kv=(k_new, v_new, appos),
        layer_idx=1, interpret=True)
    k1 = jnp.asarray(ks[1]).at[jnp.arange(R), :, appos].set(k_new[:, 0])
    v1 = jnp.asarray(vs[1]).at[jnp.arange(R), :, appos].set(v_new[:, 0])
    ref = reference_attend(q, k1, v1, lengths, qpos)
    _cmp(ref, out, lengths, 2e-5)
    k_out = np.asarray(k_out)
    np.testing.assert_array_equal(k_out[0], np.asarray(ks)[0])
    np.testing.assert_array_equal(k_out[2], np.asarray(ks)[2])
    for r in range(R):
        np.testing.assert_array_equal(k_out[1, r, :, int(appos[r])],
                                      k_new[r, 0])


# A run of positions a row (a block-diffusion pass: two blocks of four, of
# which ``n`` are real), S = 256 so a stream block is 128 positions.
#  name: per row (appos, n): n = None: the whole width A; appos < 0: idle
RUN_CASES = {
    "starts": [(0, None), (4, None), (8, None), (12, None)],
    "two_windows": [(12, None), (28, None), (-1, None), (44, None)],
    "crosses_block": [(124, None), (120, None), (124, 4), (116, None)],
    "caches_end": [(256 - 8, None), (256 - 4, 4), (256 - 8, 4), (256 - 4,
                                                                 None)],
    "real_half": [(0, 4), (4, 4), (8, 4), (124, 4)],
    "rows_sit_out": [(36, None), (-1, None), (52, 0), (60, 4)],
}


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["layer", "stack"])
@pytest.mark.parametrize("A", [4, 8])
@pytest.mark.parametrize("case", sorted(RUN_CASES))
def test_flash_fused_append_of_a_run(case, A, stacked):
    """The fused append of a RUN of up to A positions a row
    (``append_kv`` with ``k_new [R, A, KH, D]`` and a count): the output is
    scatter-then-attend's under the block mask, and the returned caches
    EQUAL ``append_kv_stacked``'s bit for bit: positions of the run past the
    count, past the cache's end, a row that sits out and every row outside
    the run keep what they held."""
    from flexflow_tpu.ops.inc_attention import append_kv_stacked

    R, Q, KH, G, D, S, L, B = 4, 8, 2, 2, 128, 256, 3, 4
    rng = np.random.RandomState(23)
    q = jnp.asarray(rng.randn(R, Q, KH * G, D).astype(np.float32))
    ks = jnp.asarray(rng.randn(L, R, KH, S, D).astype(np.float32))
    vs = jnp.asarray(rng.randn(L, R, KH, S, D).astype(np.float32))
    k_new = jnp.asarray(rng.randn(R, A, KH, D).astype(np.float32))
    v_new = jnp.asarray(rng.randn(R, A, KH, D).astype(np.float32))
    appos = jnp.asarray([p for p, _ in RUN_CASES[case]], jnp.int32)
    n = jnp.asarray([A if c is None else min(c, A)
                     for _, c in RUN_CASES[case]], jnp.int32)
    live = appos >= 0
    start = jnp.maximum(appos, 0)
    # what lands: the scatter drops what lies past the cache's end
    landed = jnp.where(live, jnp.minimum(n, S - start), 0)
    lengths = start * live + landed
    qpos = (start[:, None] + jnp.arange(Q)[None]) // B * B + B - 1
    k_ref = append_kv_stacked(ks, 1, k_new, start, n, live)
    v_ref = append_kv_stacked(vs, 1, v_new, start, n, live)
    ref = reference_attend(q, k_ref[1], v_ref[1], lengths, qpos)
    if stacked:
        out, k_out, v_out = fa.flash_attend(
            q, ks, vs, lengths, qpos, append_kv=(k_new, v_new, appos, n),
            layer_idx=1, interpret=True)
    else:
        out, k_out, v_out = fa.flash_attend(
            q, ks[1], vs[1], lengths, qpos,
            append_kv=(k_new, v_new, appos, n), interpret=True)
        k_ref, v_ref = k_ref[1], v_ref[1]
    _cmp(ref, out, lengths, 2e-5)
    np.testing.assert_array_equal(np.asarray(k_out), np.asarray(k_ref))
    np.testing.assert_array_equal(np.asarray(v_out), np.asarray(v_ref))
    assert (np.asarray(k_out) != np.asarray(ks if stacked else ks[1])).any() \
        == bool(landed.any())


def test_flash_fused_append_of_a_run_without_a_count_in_bfloat16():
    """No count: the whole width lands (up to the cache's end). In the
    cell's dtype, where a write-back window is one packed tile."""
    from flexflow_tpu.ops.inc_attention import append_kv_stacked

    R, Q, A, KH, D, S = 3, 8, 8, 2, 128, 256
    q, k, v = _mk(R, Q, 2 * KH, KH, D, S, dtype=jnp.bfloat16, seed=29)
    rng = np.random.RandomState(31)
    k_new = jnp.asarray(rng.randn(R, A, KH, D), jnp.bfloat16)
    v_new = jnp.asarray(rng.randn(R, A, KH, D), jnp.bfloat16)
    appos = jnp.asarray([124, S - 4, 60], jnp.int32)
    lengths = jnp.minimum(appos + A, S)
    qpos = (appos[:, None] + jnp.arange(Q)[None]) // 4 * 4 + 3
    full = jnp.full((R,), A, jnp.int32)
    k_ref = append_kv_stacked(k[None], 0, k_new, appos, full, appos >= 0)[0]
    v_ref = append_kv_stacked(v[None], 0, v_new, appos, full, appos >= 0)[0]
    out, k_out, v_out = fa.flash_attend(
        q, k, v, lengths, qpos, append_kv=(k_new, v_new, appos),
        interpret=True)
    _cmp(reference_attend(q, k_ref, v_ref, lengths, qpos), out, lengths, 3e-2)
    np.testing.assert_array_equal(np.asarray(k_out, np.float32),
                                  np.asarray(k_ref, np.float32))
    np.testing.assert_array_equal(np.asarray(v_out, np.float32),
                                  np.asarray(v_ref, np.float32))


@pytest.mark.parametrize("layout", ["packed_d64", "ring", "chunked", "wide"])
def test_flash_fused_append_of_a_run_is_a_plain_caches(layout):
    """A packed D=64 cache, a ring and a chunked stream keep the appends
    they have, and a run is at most APPEND_RUN_MOST positions: the kernel
    says so in one assertion."""
    D = 64 if layout == "packed_d64" else 128
    A = fa.APPEND_RUN_MOST + 1 if layout == "wide" else 4
    q, k, v = _mk(2, 8, 4, 2, D, 256)
    new = jnp.zeros((2, A, 2, D), jnp.float32)
    at = jnp.zeros((2,), jnp.int32)
    kw = {"ring": dict(window=16),
          "chunked": dict(summaries=at, summary_rows=128)}.get(layout, {})
    with pytest.raises(AssertionError, match="plain position-major"):
        flash_attend(q, k, v, at + 4, jnp.zeros((2, 8), jnp.int32),
                     append_kv=(new, new, at), interpret=True, **kw)


#  name: (query heads a key/value head, key/value heads, head dim, ring rows)
ONE_TOKEN_CASES = {
    "mha_d128": (1, 4, 128, None),          # OLMoE, OPT's verifier
    "gqa8_d128": (8, 2, 128, None),         # K-EXAONE's full layers
    "gqa8_d128_ring": (8, 2, 128, 256),     # its windowed layers
    "mqa71_d64_packed": (71, 1, 64, None),  # Falcon
    "mha_d64_packed": (1, 4, 64, None),     # a D=64 draft
}


@pytest.mark.parametrize("case", sorted(ONE_TOKEN_CASES))
def test_flash_fused_append_at_one_token_a_row(case):
    """A decode step of a model that no engine verifies: ONE query token a
    row, so ``G x Q`` = 1, 8 or 71 query rows a key/value head (no sublane
    of them at 1, no whole one at 71), with the fused append, over the full
    cache, a ring and the packed D=64 layout; an idle row is left alone."""
    G, KH, D, ring = ONE_TOKEN_CASES[case]
    R, S, W = 4, 512, 16
    rows = ring or S
    q, k, v = _mk(R, 1, G * KH, KH, D, rows, seed=3)
    rng = np.random.RandomState(17)
    k_new = jnp.asarray(rng.randn(R, 1, KH, D).astype(np.float32))
    v_new = jnp.asarray(rng.randn(R, 1, KH, D).astype(np.float32))
    # position 0, inside a block, the last (past a ring's wrap), idle
    appos = np.array([0, 300, (1023 if ring else S - 1), -1])
    lengths = jnp.asarray(np.where(appos >= 0, appos + 1, 0), jnp.int32)
    qpos = jnp.asarray(np.maximum(appos, 0)[:, None], jnp.int32)
    live = np.nonzero(appos >= 0)[0]
    at = (live, slice(None), appos[live] % rows)
    k_ref, v_ref = k.at[at].set(k_new[live, 0]), v.at[at].set(v_new[live, 0])
    kw = {} if ring is None else dict(
        window=W, key_pos=kvl.ring_positions(lengths, rows))
    ref = reference_attend(q, k_ref, v_ref, lengths, qpos, **kw)
    out, k_out, v_out = flash_attend(
        q, k, v, lengths, qpos,
        append_kv=(k_new, v_new, jnp.asarray(appos, jnp.int32)),
        interpret=True, **({} if ring is None else {"window": W}))
    assert out.shape == (R, 1, G * KH * D)
    _cmp(ref, out, lengths, 2e-5)
    for r in live:
        np.testing.assert_array_equal(k_out[r, :, appos[r] % rows],
                                      k_new[r, 0])
        np.testing.assert_array_equal(v_out[r, :, appos[r] % rows],
                                      v_new[r, 0])
    np.testing.assert_array_equal(k_out[3], k[3])
    # what a row held below its new position is what it holds now
    np.testing.assert_array_equal(k_out[1, :, :300 % rows],
                                  k[1, :, :300 % rows])


#  name: (head dim, attrs beside the plain layer's, positions)
APPEND_LAYOUTS = {
    "plain": (128, {}, 256),
    "packed_d64": (64, {}, 256),
    "ring": (128, {"sliding_window": 16, "max_step_tokens": 16}, 256),
    "chunked": (128, {"eva_window": 128, "chunk_size": 16}, 2048),
}


@pytest.mark.parametrize("Q", [1, 4])
@pytest.mark.parametrize("layout", sorted(APPEND_LAYOUTS))
def test_forward_fuses_the_appends_the_kernel_takes(layout, Q, monkeypatch):
    """``IncMultiHeadSelfAttention.forward`` on the kernel path
    (interpreted): one new position a row is appended by the attention
    kernel on every layout, as ever; a run of four by the kernel on a plain
    cache and by ``append_and_ref`` (the appends they had) on a packed
    cache, a ring and a chunked stream; and a fused engine's wide step
    (``kv_contiguous``) keeps its own append on a plain cache too."""
    import flexflow_tpu as ff
    import flexflow_tpu.kernels as ffk
    from flexflow_tpu.ops import inc_attention as ia
    from flexflow_tpu.ops.base import OpContext
    from flexflow_tpu.serve.batch_config import BatchMeta

    monkeypatch.setenv("FF_PALLAS_INTERPRET", "1")
    D, more, S = APPEND_LAYOUTS[layout]
    R, H, KH, E = 2, 4, 2, 64
    attrs = dict(num_q_heads=H, num_kv_heads=KH, head_dim=D, embed_dim=E,
                 max_requests=R, max_seq_length=S, cache_dtype="float32",
                 **more)
    rng = np.random.RandomState(41)
    params = {w: jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.1)
              for w, shape in (("wq", (E, H * D)), ("wk", (E, KH * D)),
                               ("wv", (E, KH * D)), ("wo", (H * D, E)),
                               ("adaptive_mu_k", (KH, D)),
                               ("adaptive_phi", (KH, D)))}
    x = jnp.asarray(rng.randn(R, Q, E).astype(np.float32))
    start = jnp.asarray([16, 48], jnp.int32)
    meta = BatchMeta(tokens=jnp.zeros((R, Q), jnp.int32),
                     positions=start[:, None] + jnp.arange(Q)[None],
                     start_pos=start, num_tokens=jnp.full((R,), Q, jnp.int32),
                     active=jnp.ones((R,), bool))
    sent = []
    real = ia.append_and_ref
    monkeypatch.setattr(ia, "append_and_ref",
                        lambda *a, **k: sent.append(a[2].shape[1])
                        or real(*a, **k))

    def forward(**flags):
        ctx = OpContext(layer_name="attn", compute_dtype=jnp.float32,
                        batch_config=meta, config=ff.FFConfig(num_devices=1),
                        state_in={"attn": ia._init_kv_state(attrs, None)})
        for flag, value in flags.items():
            setattr(ctx, flag, value)
        del sent[:]
        ffk.reset_dispatch_stats()
        (out,) = ia.IncMultiHeadSelfAttention.forward(attrs, params, [x], ctx)
        assert out.shape == (R, Q, E) and np.isfinite(np.asarray(out)).all()
        assert not ffk.fallback_counts, ffk.fallback_counts
        return ctx.state_out["attn"]["k_cache"]

    cache = forward()
    assert np.asarray(cache).any()
    if Q == 1 or layout == "plain":
        assert not sent and ffk.fused_append_counts == {Q: 1}
        assert not ffk.scatter_append_counts
    else:   # (a chunked step of 4 < 16 positions pools no whole chunk)
        assert sent == [Q] and not ffk.fused_append_counts
        assert ffk.scatter_append_counts == (
            {} if layout == "packed_d64" else {Q: 1})
    if Q > 1 and layout == "plain":
        np.testing.assert_array_equal(
            np.asarray(forward(kv_contiguous=True)), np.asarray(cache))
        assert sent == [Q] and not ffk.fused_append_counts


@pytest.mark.parametrize("config", [
    "falcon-7b", "olmoe-1b-7b", "k-exaone-236b-a23b", "mistral-small-4-119b",
    "opt-6.7b-spec", "evabyte-6.5b"])
def test_decode_block_at_one_token_a_row_yields_the_wide_blocks_tokens(
        config, monkeypatch, capsys):
    """tools/profile_decode.py --config (the A/B of the decode block's two
    widths, by hand on the chip) at a configuration's rehearsal sizes, in
    float32: with no engine over the model the manager resolves one token a
    row, the kernels (interpreted) serve both blocks, and the block at
    width 1 decodes the tokens of the block at the verify width 8."""
    import json
    import os

    monkeypatch.syspath_prepend(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    for key in ("JAX_PLATFORMS", "FF_PALLAS_INTERPRET"):
        monkeypatch.setenv(key, os.environ.get(key, ""))   # restored after
    from flexflow_tpu import kernels as ffk
    from tools import profile_decode

    ffk.reset_dispatch_stats()
    assert profile_decode.main_configs([config, "--rehearse"]) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["config"] == config and res["width_resolved"] == 1
    assert res["widths"] == [1, 8] and res["rows"] >= 2
    assert res["tokens_equal"] == 1.0
    assert res["attention"]["fast_path_traces"] > 0
    assert not res["attention"]["fallback_traces"]
    assert "ms_per_step" not in res         # no device time from a CPU


def test_head_dim_64_short_cache_pads_to_keep_flash(monkeypatch):
    """D=64 with a cache length the packed 256-position block can't tile
    (S=128) must fall back to the pad-to-128 cache layout — NOT off the
    flash path entirely (BS=128 tiles the padded cache)."""
    import flexflow_tpu as ff
    import flexflow_tpu.kernels as ffk
    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models.llama import LLAMAConfig, create_llama_model
    from flexflow_tpu.serve.request_manager import RequestManager

    tiny = LLAMAConfig(vocab_size=128, hidden_size=256, intermediate_size=256,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, max_position_embeddings=128)
    monkeypatch.setenv("FF_PALLAS_INTERPRET", "1")
    ffk.reset_dispatch_stats()
    cfg = ff.FFConfig(max_requests_per_batch=2, max_sequence_length=128,
                      max_tokens_per_batch=16, seed=0,
                      kv_cache_dtype="float32")
    m = ff.FFModel(cfg)
    create_llama_model(m, tiny, mode=InferenceMode.INC_DECODING_MODE)
    m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    assert m.op_state["kv_cache"]["k"].shape[-1] == 128   # padded layout
    rm = RequestManager()
    rm.register_new_request([5, 9, 23], max_new_tokens=6)
    (r,) = rm.generate_incr_decoding(m)
    assert len(r.output_tokens) == 6
    assert ffk.fast_path_count > 0, "flash path never engaged"
    assert not ffk.fallback_counts, ffk.fallback_counts


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["per_layer", "stacked"])
@pytest.mark.parametrize("D,KH,H", [(128, 2, 4), (64, 1, 4)],
                         ids=["pack1_d128", "pack2_mqa_d64"])
def test_flash_row_map_matches_reference_on_gathered_rows(D, KH, H, stacked):
    """The compact prefill batch's row map: program p streams cache row
    rows[p]. Two programs name slot 5 (consecutive chunks of one prompt),
    one program is inactive, and the cache has more rows than the batch."""
    P, Q, S, R, L = 4, 16, 512, 6, 3
    rng = np.random.RandomState(21)
    q = jnp.asarray(rng.randn(P, Q, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(L, R, KH, S, D).astype(np.float32))
    v = jnp.asarray(rng.randn(L, R, KH, S, D).astype(np.float32))
    rows = jnp.asarray([5, 2, 5, 0], jnp.int32)
    start = np.array([250, 3, 250 + Q, 0], np.int32)
    lengths = jnp.asarray([250 + Q, 3 + 9, 250 + 2 * Q, 0], jnp.int32)
    qpos = jnp.asarray(start[:, None] + np.arange(Q)[None, :], jnp.int32)
    layer = 1
    ref = reference_attend(q, k[layer][rows], v[layer][rows], lengths, qpos)
    if stacked:
        out = flash_attend(q, k, v, lengths, qpos, rows=rows,
                           layer_idx=layer, interpret=True)
    else:
        out = flash_attend(q, k[layer], v[layer], lengths, qpos, rows=rows,
                           interpret=True)
    _cmp(ref, out, lengths, 2e-5)


@pytest.mark.parametrize("KH,more", [(16, 0), (2, 1)],
                         ids=["loop_form", "block_form"])
def test_flash_without_row_map_keeps_its_kernel_arguments(KH, more):
    """Decode blocks, tree verify and the speculation block call
    flash_attend without a map and must get the kernel they always got:
    one scalar-prefetch vector (the lengths) and seven operands. With a
    map the call carries one more of each. So it is wherever the stream's
    DMA block is its partition (16 key/value heads: 512 KB a descriptor of
    128 positions); the block form (2 heads) carries one vector more, its
    place in the DMA pipeline."""
    R, Q, H, D, S = 2, 8, 16, 128, 256
    q, k, v = _mk(R, Q, H, KH, D, S)
    lengths = jnp.asarray([20, 9], jnp.int32)
    qpos = jnp.tile(jnp.arange(Q, dtype=jnp.int32)[None], (R, 1))

    def call(rows):
        jaxpr = jax.make_jaxpr(
            lambda *a: fa.flash_attend.__wrapped__(*a, rows=rows))(
                q, k, v, lengths, qpos)
        (eqn,) = [e for e in jaxpr.jaxpr.eqns
                  if e.primitive.name == "pallas_call"]
        return (eqn.params["grid_mapping"].num_index_operands,
                len(eqn.invars))

    assert fa.stream_block(KH, D, 4, H // KH * Q, S) == (128, 256)[more]
    assert call(None) == (1 + more, 7 + more)
    assert call(jnp.asarray([1, 0], jnp.int32)) == (2 + more, 8 + more)


# ---------------------------------------------------------------------------
# the plain stream's two forms: a DMA block of several softmax partitions,
# its scores in one pass and its partitions unrolled, against a partition a
# block (``stream_block`` decides, of the call's shapes alone)
# ---------------------------------------------------------------------------

# 2 key/value heads of 128 in bfloat16 over 1024 positions, the target
# brought down to 256 KB: a DMA block of 512 positions, four partitions
FORMS_TARGET = 2 * 512 * 128 * 2
#  name: (G, Q, lengths AFTER the step, A (0: no append), counts, mode)
FORM_CASES = {
    # ragged rows, one idle, one full
    "decode_g1": (1, 1, [700, 0, 129, 1024], 1, None, "append"),
    "decode_g4": (4, 1, [700, 0, 129, 1024], 1, None, "append"),
    "decode_g8": (8, 1, [700, 0, 129, 1024], 1, None, "append"),
    # rows that end inside a block's first partition (513, 1) and its last
    # (500, 1000), on a partition's edge (128, 640) and on a block's (512):
    # the new position lands in a block's last window (512, 1024) and its
    # first (513, 1)
    "decode_edges": (4, 1, [513, 1, 500, 1000, 128, 640, 512, 1024], 1, None,
                     "append"),
    "decode_float32": (4, 1, [700, 0, 129, 1024], 1, None, "append32"),
    "decode_stack": (4, 1, [300, 1000, 5, 0], 1, None, "stack"),
    # runs that cross a partition (132), a DMA block (516), end the cache
    "run4": (4, 4, [700, 0, 132, 1024, 516], 4, None, "append"),
    "run4_counted": (4, 4, [700, 0, 131, 1022, 514], 4, [4, 0, 3, 2, 2],
                     "append"),
    "run8": (8, 8, [520, 0, 136, 1024, 8], 8, None, "append"),
    "run8_counted": (8, 8, [517, 0, 131, 1021, 0], 8, [5, 0, 3, 5, 0],
                     "append"),
    "run8_stack": (2, 8, [520, 0, 136, 1024], 8, None, "stack"),
    # no append: the compact prefill batch's row map, and a slot grid
    "rows": (4, 8, [700, 100, 129, 1024], 0, None, "rows"),
    "grid": (4, 8, [700, 0, 129, 1024], 0, None, "grid"),
}


@pytest.mark.parametrize("case", sorted(FORM_CASES))
def test_the_streams_block_form_equals_its_loop_form_bit_for_bit(
        case, monkeypatch):
    """The same arithmetic delivered in larger pieces: the output and both
    caches after the append, bit for bit, whichever form streams them."""
    G, Q, lengths, A, counts, mode = FORM_CASES[case]
    R, KH, D, S = len(lengths), 2, 128, 1024
    dt = jnp.float32 if mode == "append32" else jnp.bfloat16
    rng = np.random.RandomState(51)
    q = jnp.asarray(rng.randn(R, Q, KH * G, D), dt)
    shape = ((3,) if mode == "stack" else ()) + (R, KH, S, D)
    k, v = jnp.asarray(rng.randn(*shape), dt), jnp.asarray(rng.randn(*shape),
                                                           dt)
    lengths = jnp.asarray(lengths, jnp.int32)
    kw = {"layer_idx": 1} if mode == "stack" else {}
    start = lengths - Q
    if A:
        n = jnp.asarray([A] * R if counts is None else counts, jnp.int32)
        start = jnp.where(lengths > 0, lengths - n, -1)
        kw["append_kv"] = (jnp.asarray(rng.randn(R, A, KH, D), dt),
                           jnp.asarray(rng.randn(R, A, KH, D), dt), start,
                           *([] if counts is None else [n]))
    if mode == "rows":
        kw["rows"] = jnp.asarray(rng.permutation(R), jnp.int32)
    qpos = jnp.maximum(start, 0)[:, None] + jnp.arange(Q)[None]
    target = FORMS_TARGET * (2 if dt == jnp.float32 else 1)

    def run(block_target):
        monkeypatch.setattr(fa, "STREAM_BLOCK_TARGET", block_target)
        DB = fa.stream_block(KH, D, jnp.dtype(dt).itemsize, G * Q, S)
        return DB, jax.tree.leaves(fa.flash_attend.__wrapped__(
            q, k, v, lengths, qpos.astype(jnp.int32), interpret=True, **kw))

    (DB, block), (BS, loop) = run(target), run(1)
    assert (DB, BS) == (512, 128)
    assert len(block) == len(loop) == (3 if A else 1)
    for a, b in zip(block, loop):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    if A:       # and something landed
        assert (np.asarray(block[1], np.float32)
                != np.asarray(k, np.float32)).any()


#  configuration: (key/value heads, query heads, head dim, positions a slot
#  or rows of its stream, tokens a row of a decode step, the decode step's
#  DMA block); a latent cache, another kernel: (query heads, positions)
CELL_STREAMS = {
    "zaya1-8b": (2, 8, 128, 16384, 1, 1024),
    "sdar-30b-a3b": (4, 32, 128, 1024, 8, 256),          # by its scores
    "k-exaone-236b-a23b": (8, 64, 128, 8192, 1, 256),   # its full layers
    "olmoe-1b-7b": (16, 16, 128, 1024, 1, 128),
    "opt-6.7b-spec": (32, 32, 128, 1024, 8, 128),
    "falcon-7b": (1, 71, 64, 2048, 1, 256),             # packed: outside
    "evabyte-6.5b": (32, 32, 128, 24576 // 16 + 2048, 1, 128),
    "mistral-small-4-119b": (32, 32768),
    "longcat-flash-omni": (64, 8192),
    "solar-open2-250b": (8, 64, 128, 16384, 1, 256),    # its GQA layers
    "granite-4.0-h-micro": (8, 32, 64, 8192, 1, 256),   # packed: outside
    "ouro-2.6b": (16, 16, 128, 1024, 1, 128),           # OLMoE's shape
}


def _cells():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    return [(w["name"], w["config"]) for w in cells]


@pytest.mark.parametrize("cell,config", _cells())
def test_the_stream_block_is_decided_from_the_shapes_of_the_call(cell,
                                                                  config):
    """``stream_block`` at each cell's decode and prefill shapes: a DMA block
    of several partitions where a partition's K descriptor is under the
    target, doubled until it reaches it (ZAYA1's 2 key/value heads,
    K-EXAONE's 8) or the block's scores would pass their limit (SDAR's 4
    heads of 64 query rows); the partition itself at 16 heads and more, on
    a packed cache and for every prefill segment (128 tokens a row)."""
    if len(CELL_STREAMS[config]) == 2:      # ``latent_form``'s to decide
        H, S = CELL_STREAMS[config]
        assert fa.latent_form(H, S) == "block"
        assert fa.latent_form(32 * 128, S) == "partition"
        return
    KH, H, D, S, Q, want = CELL_STREAMS[config]
    BS = fa._pick_block_s(S, D)
    assert BS == (256 if D == 64 else 128)
    assert fa.stream_block(KH, D, 2, H // KH * Q, S) == want
    assert (want == BS or KH * want * D * 2 >= fa.STREAM_BLOCK_TARGET
            or KH * H // KH * Q * 2 * want * 4 > fa.STREAM_BLOCK_SCORES_LIMIT)
    assert fa.stream_block(KH, D, 2, H // KH, S) >= want     # one token
    assert fa.stream_block(KH, D, 2, H // KH * 128, S) == BS  # a segment
    # not from anything else: the same shapes, the same block
    assert fa.stream_block.__code__.co_varnames[:5] == (
        "KH", "D", "itemsize", "GQ", "S")


# ---------------------------------------------------------------------------
# the latent kernel at 64 heads of a 640-lane entry (LongCat-Flash's shape)
# ---------------------------------------------------------------------------

def _latent_case(R, Q, H, W, rank, S, starts, nums, seed=0):
    rng = np.random.default_rng(seed)
    starts, nums = np.array(starts), np.array(nums)
    lengths = np.where(nums > 0, starts + nums, 0)
    cache = jnp.asarray(rng.standard_normal((R, 1, S, W)) * 0.5,
                        jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((R, Q, H, W)), jnp.bfloat16)
    qpos = starts[:, None] + np.arange(Q)[None]
    return (q, cache, jnp.asarray(lengths, jnp.int32),
            jnp.asarray(qpos, jnp.int32), starts, nums, lengths)


#  form: (S, start positions, real tokens) of a decode step's three rows
LATENT_64_DECODES = {
    "decode_append": (1024, (0, 511, 1023), (1, 0, 1)),
    # the block form scores all 1024 positions of a DMA block and masks the
    # partitions (256 positions) past the row's end
    "decode_append_last_block_of_1_2_and_3_live_partitions":
        (2048, (1100, 1400, 1700), (1, 1, 1)),
    "decode_append_last_block_of_4_live_partitions":
        (2048, (2000, 767, 768), (1, 1, 1)),
    "decode_append_at_positions_1023_and_1024":
        (2048, (1023, 1024, 2047), (1, 1, 1)),
    "decode_append_hand_off_over_an_idle_row":
        (2048, (1500, 900, 1030), (1, 0, 1)),
}


@pytest.mark.parametrize("form",
                         sorted(LATENT_64_DECODES) + ["prefill_row_map"])
def test_latent_kernel_at_64_heads_of_640_lanes(form):
    """64 heads, rank 512, 640 stored lanes (512 + 64 in whole lane tiles):
    the decode form, one token a row with the fused append, is ONE call of
    64 query rows in the block form; the compact prefill's row map at 128
    tokens a segment is 8192 query rows, which do not fit the kernel's VMEM
    beside the stream (108.8 MB of scoped VMEM against 100, by the chip's
    compiler), so the heads go in two calls of 32 over the same cache, in
    the partition form. Both against the jnp oracle, on a stack, the cache
    after the append compared exactly."""
    H, W, rank = 64, 640, 512
    if form in LATENT_64_DECODES:
        R, Q = 3, 1
        S, starts, nums = LATENT_64_DECODES[form]
        q, cache, lengths, qpos, starts, nums, ln = _latent_case(
            R, Q, H, W, rank, S, starts, nums)
        assert fa.latent_head_groups(H, Q, W, rank, S) == 1
        assert fa.latent_form(H * Q, S) == "block"
        at = (np.arange(R), 0, np.maximum(ln - 1, 0))
        append = (cache[at][:, None, None],
                  jnp.asarray(np.where(nums > 0, starts, -1), jnp.int32))
        before = cache.at[at].set(7.0)
        for r in np.nonzero(nums == 0)[0]:          # an idle row's stays
            before = before.at[r, 0, 0].set(cache[r, 0, 0])
        out, c2 = fa.flash_attend_latent(
            q, jnp.stack([before * 0, before]), lengths, qpos, None, append,
            rank=rank, qk_scale=0.1, layer_idx=1, interpret=True)
        np.testing.assert_array_equal(np.asarray(c2[1], np.float32),
                                      np.asarray(cache, np.float32))
        assert not np.asarray(c2[0]).any()
        want = fa.reference_attend_latent(q, cache, lengths, qpos, rank=rank,
                                          qk_scale=0.1)
    else:
        R, Q, S = 2, 128, 1024
        q, cache, lengths, qpos, starts, nums, ln = _latent_case(
            R, Q, H, W, rank, S, (896, 100), (128, 37))
        assert fa.latent_head_groups(H, Q, W, rank, S) == 2
        assert fa.latent_head_groups(H, Q, W, rank, 8192) == 2
        assert fa.latent_form(H // 2 * Q, S) == "partition"
        rows = jnp.asarray([1, 0], jnp.int32)
        out = fa.flash_attend_latent(
            q, jnp.stack([cache * 0, cache]), lengths, qpos, rows,
            rank=rank, qk_scale=0.1, layer_idx=1, interpret=True)
        want = fa.reference_attend_latent(q, cache[rows], lengths, qpos,
                                          rank=rank, qk_scale=0.1)
    assert out.shape == (R, Q, H, rank)
    for r in np.nonzero(nums)[0]:
        np.testing.assert_allclose(
            np.asarray(out[r, :nums[r]], np.float32),
            np.asarray(want[r, :nums[r]], np.float32), atol=3e-2)


@pytest.mark.parametrize("shape,groups", [
    # (H, Q, W, rank, S): Mistral-Small-4's prefill segment and decode step
    ((32, 128, 384, 256, 32768), 1), ((32, 1, 384, 256, 32768), 1),
    # LongCat-Flash's: the prefill segment in halves, the decode step whole
    ((64, 128, 640, 512, 8192), 2), ((64, 1, 640, 512, 8192), 1),
    # a segment no head of which fits: the op falls back and says so
    ((64, 16384, 640, 512, 8192), 0)])
def test_latent_head_groups_are_decided_from_the_shapes(shape, groups):
    """Mistral's shapes take the one call they took (its traced program is
    the parent's); the groups divide the heads and each fits the budget."""
    assert fa.latent_head_groups(*shape) == groups
    H, Q, W, rank, S = shape
    if groups:
        DB, SB = fa._pick_latent_blocks(S)
        assert H % groups == 0 and fa._latent_vmem_bytes(
            H // groups * Q, W, rank, DB, SB, 2, 2) <= fa.LATENT_VMEM_LIMIT


@pytest.mark.parametrize("shape,block", [("zaya1", 1024), ("sdar", 256)])
def test_time_stream_attend_tool_rehearses(shape, block, monkeypatch,
                                           capsys):
    """tools/time_stream_attend.py (the kernel alone at a cell's decode
    shape, by hand on the chip) at its rehearsal sizes, interpreted: every
    variant runs, the rule's form is the block form there, and one step's
    outputs and caches are bit-equal between the two forms."""
    import json
    import os

    monkeypatch.syspath_prepend(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    monkeypatch.setenv("JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS", ""))
    from tools import time_stream_attend

    assert time_stream_attend.main(["--rehearse", "--shapes", shape]) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["ok"] and res["device"] == "cpu"
    cell = res["shapes"][shape]
    assert (cell["form"], cell["rule_block"]) == ("block", block)
    assert cell["forms_bit_equal"] is True
    assert sorted(cell["us_a_call"]) == [
        "loop", "loop.arith", "loop.stream", "tree", "tree.arith",
        "tree.stream"]
    assert fa.ABLATE is None and fa.stream_block.__name__ == "stream_block"


@pytest.fixture(scope="module")
def one_v5e(request):
    """A described (not attached) v5e chip to compile for, or a skip."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    mp = pytest.MonkeyPatch()
    request.addfinalizer(mp.undo)
    mp.setenv("TPU_LOG_DIR", "disabled")
    mp.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    mp.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


#  a cell's decode call: rows, key/value heads, group, tokens a row (its run
#  of new positions), positions a slot, the DMA block
V5E_DECODES = {
    "zaya1": (16, 2, 4, 1, 16384, 1024),
    "sdar": (32, 4, 8, 8, 1024, 256),
    "k-exaone": (32, 8, 8, 1, 8192, 256),
    "olmoe": (32, 16, 1, 1, 1024, 128),
}


@pytest.mark.parametrize("cell", sorted(V5E_DECODES))
def test_the_decode_forms_compile_for_a_v5e_at_the_cells_shapes(cell,
                                                                 one_v5e):
    """What interpret mode cannot show: Mosaic takes the block form (and the
    loop) at the published widths, the append fused, on a stack. Compiled
    for a described chip; nothing runs."""
    R, KH, G, Q, S, DB = V5E_DECODES[cell]
    D = 128
    assert fa.stream_block(KH, D, 2, G * Q, S) == DB

    def aval(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_v5e)

    def step(q, k, v, lengths, qpos, new, at):
        return fa.flash_attend.__wrapped__(
            q, k, v, lengths, qpos, append_kv=(new, new, at), layer_idx=1)

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        aval((R, Q, KH * G, D)), aval((2, R, KH, S, D)),
        aval((2, R, KH, S, D)), aval((R,), jnp.int32),
        aval((R, Q), jnp.int32), aval((R, Q, KH, D)),
        aval((R,), jnp.int32)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1


# ---------------------------------------------------------------------------
# the plane of a stacked cache as an OPERAND (a loop region's pass picks it)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["append", "run", "rows", "grid"])
@pytest.mark.parametrize("KH", [16, 2], ids=["loop_form", "block_form"])
def test_flash_at_a_traced_plane_is_the_static_one_bit_for_bit(mode, KH):
    """``flash_attend(plane=)``: the index of the stacked cache as a traced
    int32 scalar (one more scalar-prefetched operand, read where the static
    ``layer_idx`` is baked in), at a stack of 2 passes x 3 layers = 6
    planes, under ``jit`` with the plane an argument: the output and, with
    ``append_kv``, both caches are those of the static call bit for bit, at
    every plane; no other plane is written."""
    R, Q, D, S, L = 3, {"append": 1, "run": 4, "rows": 16, "grid": 8}[mode], \
        128, 256, 6
    H = 16
    rng = np.random.RandomState(60)
    q = jnp.asarray(rng.randn(R, Q, H, D), jnp.bfloat16)
    ks = jnp.asarray(rng.randn(L, R, KH, S, D), jnp.bfloat16)
    vs = jnp.asarray(rng.randn(L, R, KH, S, D), jnp.bfloat16)
    start = jnp.asarray([130, 0, 7], jnp.int32)
    live = jnp.asarray([True, False, True])
    lengths = jnp.where(live, start + Q, 0)
    qpos = start[:, None] + jnp.arange(Q)[None]
    kw = {}
    if mode in ("append", "run"):
        k_new = jnp.asarray(rng.randn(R, Q, KH, D), jnp.bfloat16)
        v_new = jnp.asarray(rng.randn(R, Q, KH, D), jnp.bfloat16)
        at = jnp.where(live, start, -1)
        kw["append_kv"] = ((k_new, v_new, at) if mode == "append" else
                           (k_new, v_new, at, jnp.full((R,), Q, jnp.int32)))
    elif mode == "rows":
        kw["rows"] = jnp.asarray([2, 0, 2], jnp.int32)

    traced = jax.jit(lambda plane, k, v: fa.flash_attend(
        q, k, v, lengths, qpos, plane=plane, interpret=True, **kw))
    for plane in (0, 4, 5):
        want = fa.flash_attend(q, ks, vs, lengths, qpos, layer_idx=plane,
                               interpret=True, **kw)
        got = traced(jnp.int32(plane), ks, vs)
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        if "append_kv" in kw:
            others = np.arange(L) != plane
            assert (np.asarray(got[1], np.float32)[others]
                    == np.asarray(ks, np.float32)[others]).all()
            assert (np.asarray(got[1], np.float32)[plane]
                    != np.asarray(ks, np.float32)[plane]).any()
    with pytest.raises(AssertionError, match="operand or static"):
        fa.flash_attend(q, ks, vs, lengths, qpos, plane=jnp.int32(1),
                        layer_idx=1, interpret=True)
