"""Granite-4.0-H on the serving path, at tiny sizes on the CPU: three layers
in four are state-space mixers that keep a recurrent state a row (Mamba-2:
one scalar decay a head, ``B`` and ``C`` shared by the heads, a skip term, a
gated norm over the whole inner width) beside one no-position GQA layer's
plain k/v cache; a dense SwiGLU in every layer; a tied head; four
multipliers.

(a) the recurrence's forms against each other and the literal float64
recurrence, with ragged, padded and idle rows, and the kernel interpreted
and compiled for a described v5e; (b) the program against the plain
reference through the hand-over, a ragged segment and decode steps; every
knock-out and every multiplier seen; one prompt fed four ways; int8; the
packed 64-wide heads; (c) a slot reused, a row preempted and prefilled
again; (d) what cannot carry a recurrent state refuses, by its reason; (e) a
synthetic checkpoint under the ``HF_KEYS`` names; (f) the counters and
``attention_kinds``; the yardstick's arithmetic, the cell's files and its
rehearsal.
"""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import flexflow_tpu as ff
from flexflow_tpu.ffconst import InferenceMode, OpType
from flexflow_tpu.models import FAMILIES, family_for_hf_config
from flexflow_tpu.ops.inc_attention import (FULL_STACK, RECURRENT_STACK,
                                            commit_tree_kv)
from flexflow_tpu.serve.request_manager import RequestManager

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "granite-4.0-h-micro.context-reasoning"
CONFIG = os.path.join(ROOT, "benchmark/configs/granite-4.0-h-micro.json")

# the published key names, at the rehearsal size: one period of the check's
# layer pattern
TINY = dict(vocab_size=256, hidden_size=128, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2,
            shared_intermediate_size=256,
            layer_types=["mamba", "mamba", "attention", "mamba"],
            mamba_n_heads=8, mamba_d_head=32, mamba_d_state=16,
            mamba_d_conv=4, mamba_n_groups=1, mamba_expand=2,
            embedding_multiplier=12, residual_multiplier=0.22,
            attention_multiplier=0.015625, logits_scaling=8,
            rms_norm_eps=1e-5)
# float32 program against float32 reference: rounding only
TOL = 3e-5


@pytest.fixture(scope="module")
def bench():
    """The benchmark's family and reference for Granite-4.0-H, loaded as
    run.py loads them."""
    sys.path.insert(0, ROOT)
    try:
        from benchmark.run import load_module

        yield (load_module("families", "granite_hybrid"),
               load_module("reference", "granite_hybrid"))
    finally:
        sys.path.remove(ROOT)


def _build(mode=InferenceMode.INC_DECODING_MODE, tiny=TINY, **ffkw):
    from flexflow_tpu.models.granite_hybrid import (
        GraniteHybridConfig, create_granite_hybrid_model)

    kw = dict(max_requests_per_batch=4, max_sequence_length=256,
              max_tokens_per_batch=64, seed=3, compute_dtype="float32",
              kv_cache_dtype="float32", num_devices=1)
    kw.update(ffkw)
    m = ff.FFModel(ff.FFConfig(**kw))
    c = GraniteHybridConfig.from_hf_config(tiny)
    create_granite_hybrid_model(m, c, mode=mode,
                                data_type=ff.DataType.DT_FLOAT)
    m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    return m, c


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, TINY["vocab_size"], size=n)


def _reference(bench, m, c, toks, cfg=TINY, **kw):
    return np.asarray(bench[1].forward(bench[0].reference_weights(m, c),
                                       toks, cfg, **kw))


def _rel(a, b):
    return float((np.linalg.norm(a - b, axis=-1)
                  / np.linalg.norm(b, axis=-1)).max())


def _state(m, slot):
    st = m.op_state[RECURRENT_STACK]
    return np.asarray(st["s"])[:, slot], np.asarray(st["u"])[:, slot]


# ---------------------------------------------------------------------------
# (a) one recurrence, its forms
# ---------------------------------------------------------------------------

def _literal(S0, dx, g, B, C):
    """Token by token, in float64 numpy: ``(y [R, T, H, P], S_T)``."""
    S = np.asarray(S0, np.float64).copy()
    out = np.zeros(dx.shape, np.float64)
    for t in range(dx.shape[1]):
        S = (np.exp(g[:, t])[..., None, None] * S
             + dx[:, t][..., None] * B[:, t][:, None, None, :])
        out[:, t] = np.einsum("rhpn,rn->rhp", S, C[:, t])
    return out, S


def _draw(decay: str, R=3, T=150, H=4, P=8, N=16, seed=0):
    """A step's dx, g, B, C as the op makes them; ``decay``: "mild" or
    "strongest" (every head at the seeded initialisation's strongest: A =
    16, dt above 0.1)."""
    rng = np.random.default_rng(seed)
    dt = (np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (R, T, H)))
          if decay == "mild" else rng.uniform(0.1, 0.7, (R, T, H)))
    A = rng.uniform(1, 16, H) if decay == "mild" else np.full(H, 16.0)
    n = np.asarray([T, T - 37, 0][:R])          # whole, ragged, idle
    real = np.arange(T)[None, :] < n[:, None]
    dt = np.where(real[..., None], dt, 0.0)
    x = rng.standard_normal((R, T, H, P))
    B, C = rng.standard_normal((2, R, T, N))
    S0 = rng.standard_normal((R, H, P, N))
    return S0, dt[..., None] * x, -A * dt, B, C, n


@pytest.mark.parametrize("decay", ["mild", "strongest"])
def test_chunked_recurrent_and_literal_forms_agree(decay):
    """The chunked form (one chunk of 128 and a ragged second, and chunks of
    16 through the scan) and the recurrent form a token at a time against
    the literal float64 recurrence: outputs at every real position and the
    end state, with a ragged row whose padding leaves the state as it is and
    an idle row that keeps its own. At the strongest seeded decay a chunk's
    cumulative log decay passes -100, where ``exp(-G)`` overflows float32:
    nothing here forms it."""
    from flexflow_tpu.ops.ssd_mixer import chunked, recurrent_step

    S0, dx, g, B, C, n = _draw(decay)
    want_y, want_S = _literal(S0, dx, g, B, C)
    args = [jnp.asarray(a, jnp.float32) for a in (S0, dx, g, B, C)]
    real = np.arange(dx.shape[1])[None, :] < n[:, None]
    for chunk in (128, 16):
        y, S = chunked(*args, chunk=chunk)
        assert np.isfinite(np.asarray(y)).all()
        np.testing.assert_allclose(np.asarray(y)[real], want_y[real],
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(S), want_S, rtol=2e-4,
                                   atol=2e-5)
    np.testing.assert_array_equal(np.asarray(S)[2], np.asarray(args[0])[2])
    S = args[0]
    for t in range(24):
        y, S = recurrent_step(S, args[1][:, t], jnp.exp(args[2][:, t]),
                              args[3][:, t], args[4][:, t])
        np.testing.assert_allclose(np.asarray(y)[:2], want_y[:2, t],
                                   rtol=2e-4, atol=2e-4)


# the toy shape, and one with what the cell's has and the toy has not: two
# head blocks a row, ``P = 64`` and ``N = 128``, so the merge of 64 heads'
# sums, its rotations and the placing of ``y`` run at their real widths;
# and one whose 24 heads merge in three groups of 8 over 48 lanes (the
# windows double once and are then added up)
STEP_SHAPES = {"toy": (2, 5, 8, 16, 32), "two_blocks": (2, 5, 128, 64, 128),
               "uneven": (2, 5, 24, 8, 48)}


def _step_inputs(shape, seed=0):
    L, R, H, P, N = shape
    rng = np.random.default_rng(seed)
    stack = jnp.asarray(rng.standard_normal((L, R, H, P, N)), jnp.float32)
    dx = jnp.asarray(rng.standard_normal((R, H, P)), jnp.float32)
    a = jnp.asarray(rng.uniform(0.05, 1, (R, H)), jnp.float32)
    B, C = (jnp.asarray(rng.standard_normal((R, N)), jnp.float32)
            for _ in range(2))
    return stack, dx, a, B, C


@pytest.mark.parametrize("shape,live", [
    ("toy", (1, 0, 1, 1, 0)), ("toy", (0, 0, 0, 0, 0)),
    ("toy", (1, 1, 1, 1, 1)), ("toy", (0, 0, 0, 0, 1)),
    ("two_blocks", (1, 0, 1, 1, 0)), ("two_blocks", (0, 0, 0, 0, 0)),
    ("uneven", (1, 0, 1, 1, 0))])
def test_the_recurrent_kernel_interpreted(shape, live):
    """``ssd_state_step`` in interpret mode against ``recurrent_step``, on a
    stack of two layers: a live row's state is updated in place, a fresh
    row's starts from zeros whatever the slot held, an idle row's state is
    untouched and its output zeros, the other layer is untouched; with
    nobody live nothing changes."""
    from flexflow_tpu.kernels import linear_attention as LA
    from flexflow_tpu.ops.ssd_mixer import recurrent_step

    L, R, H, P, N = STEP_SHAPES[shape]
    hb = LA.ssd_heads_per_block(H, P, N)
    assert (H // hb, LA.ssd_merged_heads(hb, N)) == {
        "toy": (1, 8), "two_blocks": (2, 64), "uneven": (1, 8)}[shape]
    stack, dx, a, B, C = _step_inputs(STEP_SHAPES[shape])
    live = jnp.asarray(live, bool)
    fresh = jnp.asarray([0, 1, 1, 0, 0], bool)
    y, new = LA.ssd_state_step(stack, 1, dx, a, B, C, live, fresh,
                               interpret=True)
    S0 = jnp.where(fresh[:, None, None, None], 0, stack[1])
    want_y, want_S = recurrent_step(S0, dx, a, B, C)
    lv = np.asarray(live)
    np.testing.assert_allclose(np.asarray(y)[lv], np.asarray(want_y)[lv],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new)[1][lv],
                               np.asarray(want_S)[lv], rtol=1e-5, atol=1e-5)
    assert not np.asarray(y)[~lv].any()
    np.testing.assert_array_equal(np.asarray(new)[1][~lv],
                                  np.asarray(stack)[1][~lv])
    np.testing.assert_array_equal(np.asarray(new)[0], np.asarray(stack)[0])


@pytest.mark.parametrize("shape", list(STEP_SHAPES))
def test_the_recurrent_kernel_passes_a_state_through_bit_for_bit(shape):
    """A decay of 1 and ``dx = 0`` (what a padding position gives): the
    state goes back as it came, every bit of it, and ``y = S C``."""
    from flexflow_tpu.kernels.linear_attention import ssd_state_step

    stack, dx, a, B, C = _step_inputs(STEP_SHAPES[shape], seed=1)
    R = stack.shape[1]
    live = jnp.ones((R,), bool)
    y, new = ssd_state_step(stack, 0, jnp.zeros_like(dx), jnp.ones_like(a),
                            B, C, live, jnp.zeros((R,), bool),
                            interpret=True)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(stack))
    want = np.einsum("rhpn,rn->rhp", np.asarray(stack[0], np.float64),
                     np.asarray(C, np.float64))
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5, atol=1e-5)


def test_the_step_timing_tool_rehearses(capsys):
    """tools/time_ssd_step.py (the kernel alone at the cell's shape, and the
    tool's own copies of its body with a part left out), tiny and
    interpreted: no time is taken here, and the tool's whole copy gives the
    module's results bit for bit."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import time_ssd_step
    finally:
        sys.path.remove(os.path.join(ROOT, "tools"))
    assert time_ssd_step.main(["--rehearse"]) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["device"] == "cpu" and res["whole_is_module"] is True
    assert not any(k.endswith("_live4") for k in res)


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip to compile for; nothing runs on it."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")
    mp.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    mp.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here, or its lock is held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        mp.undo()


def test_the_recurrent_kernel_compiles_for_a_v5e_at_the_cells_shape(one_chip):
    """What interpret mode cannot show: Mosaic takes ``ssd_state_step`` at
    the published widths (the whole model's 36 layers x 32 slots x 64 heads
    of 64 x 128 float32; a row's 64 heads one program, the 2.1 MB
    descriptor that ``ssd_heads_per_block`` gives, and its six levels of
    merges) on a donated stack, under a device name that the gated delta
    rule's readers do not match, nor its theirs. Compiled for a described
    chip; nothing runs."""
    from flexflow_tpu.kernels import linear_attention as LA

    L, S, H, P, N = 36, 32, 64, 64, 128
    assert LA.supports_ssd(H, P, N)
    assert LA.ssd_heads_per_block(H, P, N) == 64 == LA.ssd_merged_heads(64, N)
    # the rule is the descriptor's bytes: twice the tile, half the heads
    assert LA.ssd_heads_per_block(H, 2 * P, N) == 32
    assert LA.ssd_heads_per_block(48, P, N) == 48
    assert LA.SSD_NAME == "ssd_state_step"
    for other in (LA.NAME, LA.CHUNK_NAME):
        assert other not in LA.SSD_NAME and LA.SSD_NAME not in other

    def aval(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(stack, dx, a, B, C, live, fresh):
        return LA.ssd_state_step.__wrapped__(stack, 7, dx, a, B, C, live,
                                             fresh)

    text = jax.jit(step, donate_argnums=(0,)).lower(
        aval((L, S, H, P, N)), aval((S, H, P)), aval((S, H)), aval((S, N)),
        aval((S, N)), aval((S,), bool), aval((S,), bool)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "ssd_state_step" in text and "kda_state_step" not in text
    # the stack goes through in place: no copy of it anywhere
    assert "f32[36,32,64,64,128]{4,3,2,1,0} copy(" not in text


# ---------------------------------------------------------------------------
# (b) the program against the plain reference; one prompt four ways
# ---------------------------------------------------------------------------

PLAN = [[16, 16], [11]] + [1] * 8


@pytest.fixture(scope="module")
def driven(bench):
    """The check's plan on slot 2 of a fresh model: (model, config, tokens,
    logits, the first mixer's inputs, its state behind the last prefill step
    and at the end, the reference's logits)."""
    family, _ = bench
    m, c = _build()
    toks = _tokens(16 + 16 + 11 + 8)
    mid = []
    ours, inputs = family.drive(
        m, toks, PLAN, slot=2,
        after_prefill=lambda: mid.append(family.held_state(m, 2)))
    return (m, c, toks, ours, inputs, mid[0], family.held_state(m, 2),
            _reference(bench, m, c, toks))


def test_program_matches_plain_reference_through_hand_over_and_decode(
        bench, driven):
    """Prefill in chunks of the compact batch (two consecutive segments of
    one slot in ONE step: the hand-over; then a ragged one from the store),
    then eight decode steps through the state and the cache: logits at
    every position against the full float32 forward; the state the first
    mixer holds behind the prefill steps and at the end against the literal
    recurrence on that layer's own inputs; a bfloat16 state is outside the
    state's tolerance."""
    family, reference = bench
    m, c, toks, ours, inputs, mid, held, ref = driven
    assert m.attention_kinds == {
        "full": {"layers": 1, "window": None,
                 "cache_bytes": 2 * 4 * 2 * 256 * 32 * 4},
        "recurrent": {"layers": 3, "window": None,
                      "cache_bytes": 3 * 4 * (8 * 32 * 16 + 3 * 288) * 4,
                      "state_bytes": 3 * 4 * 8 * 32 * 16 * 4,
                      "conv_bytes": 3 * 4 * 3 * 288 * 4,
                      "op": "INC_SSD_MIXER", "chunk_kernel": False}}
    assert m.op_state[RECURRENT_STACK]["s"].dtype == jnp.float32
    assert m.op_state[RECURRENT_STACK]["u"].dtype == jnp.float32
    assert m.op_state[FULL_STACK]["k"].shape[0] == 1
    assert _rel(ours, ref) < TOL
    lw = family.reference_weights(m, c)["layers"][0]
    assert family.state_error(TINY, reference, lw, inputs, held) < 1e-5
    assert family.state_error(TINY, reference, lw, inputs[:43], mid) < 1e-5
    assert family.state_error(TINY, reference, lw, inputs, held,
                              state_dtype=jnp.bfloat16) > 1e-3


@pytest.mark.parametrize("term", [
    "D", "conv_bias", "conv_tap", "dt_bias", "z_gate", "norm_order",
    "residual_multiplier", "attention_multiplier", "embedding_multiplier"])
def test_the_reference_has_teeth(bench, driven, term):
    """Each term the issue names, left out of the reference, and each
    multiplier at the value a model without it would have, is far outside
    the tolerance the program is held to."""
    m, c, toks, _, _, _, _, ref = driven
    assert _rel(_reference(bench, m, c, toks, without=(term,)), ref) > 0.05


def test_the_logits_are_scaled(bench, driven):
    """``logits_scaling``: the program's logits are the reference's at
    scaling 1, over 8."""
    m, c, toks, ours, _, _, _, _ = driven
    unscaled = _reference(bench, m, c, toks, cfg=dict(TINY, logits_scaling=1))
    assert _rel(8 * ours, unscaled) < TOL and _rel(ours, unscaled) > 0.8


PLANS = {
    "consecutive_segments_in_one_step": [[16, 16, 16, 5]],
    "two_steps_of_two_segments": [[16, 16], [16, 5]],
    "one_segment_a_step": [[16], [16], [16], [5]],
    "one_token_a_step": [1] * 53,
}


@pytest.fixture(scope="module", params=["jnp", "kernels"])
def four_ways(bench, request):
    """The prompt through each plan, on the jnp path and with the kernels
    interpreted (``pallas_interpret_forced``: every one-token step through
    ``ssd_state_step``)."""
    family, _ = bench
    toks = _tokens(53, seed=5)
    out = {"path": request.param}
    with pytest.MonkeyPatch.context() as mp:
        if request.param == "kernels":
            mp.setenv("FF_PALLAS_INTERPRET", "1")
        for name, plan in PLANS.items():
            m, _ = _build()
            logits, _ = family.drive(m, toks, plan, slot=1)
            out[name] = (logits, _state(m, 1), _state(m, 0))
    return out


@pytest.mark.parametrize("way", sorted(PLANS)[1:])
def test_one_prompt_fed_four_ways_gives_the_same_logits_and_states(
        four_ways, way):
    """A segment's state from another segment of the step, from the store a
    step left, or a token at a time (the recurrent form all the way): the
    same logits to float32 rounding, and the slot is left with the same
    state and tail (the LAST segment's, whichever row of the batch carried
    it); no other slot's is touched."""
    base = four_ways[sorted(PLANS)[0]]
    got = four_ways[way]
    assert _rel(got[0], base[0]) < TOL
    for a, b in zip(got[1], base[1]):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
    assert np.abs(base[1][0]).max() > 0.05 and np.abs(base[1][1]).max() > 0.1
    assert not got[2][0].any() and not got[2][1].any()


def test_the_slot_grid_prefill_carries_the_state_too(four_ways):
    """A prefill chunk on the slot grid (``slots`` None, a row a slot) takes
    its state from the store like a decode step: the same logits as the
    compact batch's."""
    sys.path.insert(0, ROOT)
    try:
        from benchmark.families._common import program_logits
    finally:
        sys.path.remove(ROOT)
    with pytest.MonkeyPatch.context() as mp:
        if four_ways["path"] == "kernels":
            mp.setenv("FF_PALLAS_INTERPRET", "1")
        m, _ = _build()
        grid = program_logits(m, _tokens(53, seed=5), 32)   # then 21 decoded
    assert _rel(grid, four_ways[sorted(PLANS)[0]][0]) < TOL


def _serve(m, lens=(70, 9), new=6, tel=None, seed=50):
    rm = RequestManager()
    rm.telemetry = tel
    for i, n in enumerate(lens):
        rm.register_new_request([int(t) for t in _tokens(n, seed=seed + i)],
                                max_new_tokens=new)
    return rm.generate_incr_decoding(m)


def test_the_kernel_path_serves_the_same_tokens(monkeypatch):
    """Served through RequestManager with the kernels interpreted: the
    decode block goes through ``ssd_state_step`` and the flash kernel, and
    in float32 the tokens are those of the jnp path; the cell's dtypes
    (bfloat16 compute and cache beside a float32 state and tail) are
    served."""
    import flexflow_tpu.kernels as ffk

    def serve(dtype="float32"):
        m, _ = _build(max_sequence_length=512, compute_dtype=dtype,
                      kv_cache_dtype=dtype)
        assert m.op_state[RECURRENT_STACK]["s"].dtype == jnp.float32
        assert m.op_state[RECURRENT_STACK]["u"].dtype == jnp.float32
        return [r.output_tokens for r in _serve(m)]

    plain = serve()
    monkeypatch.setenv("FF_PALLAS_INTERPRET", "1")
    ffk.reset_dispatch_stats()
    assert serve() == plain
    assert not ffk.fallback_counts and ffk.fast_path_count > 0
    assert [len(t) for t in serve("bfloat16")] == [6, 6]


def test_int8_through_quantize_params(bench):
    """``quantization_type="int8"``: the mixer's two matrices, the
    attention layer's four, the shared_mlp's two and the tied table are
    int8 with a scale a column, the small vectors are not; the program on
    them is the reference on the same weights dequantised."""
    from flexflow_tpu.quant import is_quantized

    family, _ = bench
    m, c = _build(quantization_type="int8")
    p = m.params
    for layer, names in (("layers.0.mamba", ("win", "wout")),
                         ("layers.2.self_attn", ("wq", "wk", "wv", "wo")),
                         ("layers.1.shared_mlp.input_linear", ("kernel",)),
                         ("layers.1.shared_mlp.output_linear", ("kernel",)),
                         ("embed_tokens", ("weight",))):
        assert all(is_quantized(p[layer][n]) for n in names), layer
    assert not any(is_quantized(p["layers.0.mamba"][n]) for n in (
        "conv", "conv_bias", "A_log", "dt_bias", "D", "norm"))
    assert "lm_head" not in p or not p["lm_head"]      # the table, tied
    toks = _tokens(40, seed=11)
    ours, _ = family.drive(m, toks, [[16, 16], [4]] + [1] * 4, slot=3)
    assert _rel(ours, _reference(bench, m, c, toks)) < TOL


def test_packed_64_wide_heads_at_a_scale_of_their_own(bench, monkeypatch):
    """The published attention layer's shape on the kernel path: 4 query
    heads a key/value head of 64, a cache packed two positions a 128-lane
    row, no rotary table and the softmax scale 1/64 (not 1/8): prefill
    segments and decode steps through the interpreted flash kernel against
    the reference; at 1/8 the reference is far away."""
    import flexflow_tpu.kernels as ffk

    monkeypatch.setenv("FF_PALLAS_INTERPRET", "1")
    ffk.reset_dispatch_stats()
    tiny = dict(TINY, hidden_size=256, num_attention_heads=4,
                num_key_value_heads=1, mamba_n_heads=8, mamba_d_head=64,
                shared_intermediate_size=128, num_hidden_layers=2,
                layer_types=["mamba", "attention"])
    family, _ = bench
    m, c = _build(tiny=tiny)
    assert m.op_state[FULL_STACK]["k"].shape == (1, 4, 1, 128, 128)
    toks = _tokens(16 + 16 + 7 + 4, seed=13)
    ours, _ = family.drive(m, toks, [[16, 16], [7]] + [1] * 4, slot=1)
    assert not ffk.fallback_counts and ffk.fast_path_count > 0
    ref = _reference(bench, m, c, toks, cfg=tiny)
    assert _rel(ours, ref) < TOL
    assert _rel(_reference(bench, m, c, toks, cfg=tiny,
                           without=("attention_multiplier",)), ref) > 0.01


# ---------------------------------------------------------------------------
# (c) a slot reused; a row preempted and prefilled again
# ---------------------------------------------------------------------------

def test_a_reused_slot_starts_from_a_cleared_state(bench):
    """A second request in a slot whose first left its state there (a fresh
    row over a dirty slot): its logits are those of the same request alone
    in a fresh model."""
    family, _ = bench
    first, second = _tokens(40, seed=7), _tokens(37, seed=8)
    plan = [[16, 16], [5]]
    m, _ = _build()
    family.drive(m, first, [[16, 16], [8]], slot=3)
    assert np.abs(_state(m, 3)[0]).max() > 0.05
    again = family.drive(m, second, plan, slot=3)[0]
    fresh, _ = _build()
    alone = family.drive(fresh, second, plan, slot=3)[0]
    assert _rel(again, alone) < TOL
    for a, b in zip(_state(m, 3), _state(fresh, 3)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_preemption_rebuilds_the_state_and_keeps_the_tokens():
    """Deadline-aware preemption drops a victim's cache depth and prefills
    its prompt and what it generated again from position 0, which rebuilds
    the state with the cache; a freed slot is refilled: the victims' tokens
    are those of an undisturbed run."""
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
# (d) the refusals
# ---------------------------------------------------------------------------

def _refusal(name):
    from flexflow_tpu.models.granite_hybrid import GraniteHybridConfig

    if name in ("tree_verify_mode", "beam_search_mode"):
        mode = (InferenceMode.TREE_VERIFY_MODE if name == "tree_verify_mode"
                else InferenceMode.BEAM_SEARCH_MODE)
        return lambda: _build(mode=mode)
    if name == "tensor_parallel_mesh":
        return lambda: _build(tensor_parallelism_degree=2, num_devices=2)
    if name == "pipeline_plan":
        return lambda: _build(pipeline_parallelism_degree=2, num_devices=2)
    hf = {"routed_experts": {"num_local_experts": 62},
          "with_rope": {"position_embedding_type": "rope"},
          "two_groups": {"mamba_n_groups": 2},
          "proj_bias": {"mamba_proj_bias": True},
          "untied": {"tie_word_embeddings": False},
          "inner_width": {"mamba_n_heads": 48}}
    if name in hf:
        return lambda: GraniteHybridConfig.from_hf_config({**TINY,
                                                           **hf[name]})
    if name == "two_state_shapes":
        def build():
            m = ff.FFModel(ff.FFConfig(max_requests_per_batch=4,
                                       max_sequence_length=256, seed=3))
            t = m.create_tensor([4, 1, 128], ff.DataType.DT_FLOAT)
            t = m.inc_ssd_mixer(t, 128, 8, 32, 16, name="a")
            m.inc_ssd_mixer(t, 128, 8, 32, 32, name="b")
            m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
        return build
    m, _ = _build()
    if name == "commit_tree_kv":
        z = jnp.zeros((4,), jnp.int32)
        return lambda: commit_tree_kv(m.op_state, jnp.zeros((4, 3), jnp.int32),
                                      z, z, z > 0)
    if name == "tree_engine_commit":
        from flexflow_tpu.serve.engine import MultiSpecEngine

        z = jnp.zeros((4,), jnp.int32)
        return lambda: MultiSpecEngine._commit(
            type("E", (), {"depth": 2})(), m.op_state, z, z, z, z > 0)
    if name == "prefix_pool":
        from flexflow_tpu.serve import prefix_cache

        return lambda: prefix_cache.extract_prefix_kv(m.op_state, 0, 8, 256)
    if name == "tree_batch_on_the_op":
        from flexflow_tpu.ops.base import OpContext
        from flexflow_tpu.ops.ssd_mixer import IncSSDMixer

        ctx = OpContext(training=False, rng=None, compute_dtype=jnp.float32,
                        batch_config=type("M", (), {"ancestor": 0})())
        layer = next(ly for ly in m.layers
                     if ly.op_type == OpType.INC_SSD_MIXER)
        return lambda: IncSSDMixer.forward(
            layer.attrs, m.params[layer.name], [jnp.zeros((4, 1, 128))], ctx)
    raise KeyError(name)


@pytest.mark.parametrize("what,sentence", [
    ("tree_verify_mode", "incremental decoding only.*tree verification"),
    ("beam_search_mode", "incremental decoding only.*beam drafting"),
    ("tensor_parallel_mesh",
     "mesh that divides a model.*keeps a recurrent state.*dividing mesh"),
    ("pipeline_plan", "'pipe': 2.*recurrent state.*pipeline stage no "
                      "hand-over"),
    ("commit_tree_kv", "tree verification.*rejected draft cannot be rolled"),
    ("tree_engine_commit", "speculation commit.*recurrent state"),
    ("prefix_pool", "shared-prefix pool is not supported.*state-space mixer"
                    ".*ssd_mixer.*no snapshot"),
    ("tree_batch_on_the_op", "a token a row a step"),
    ("routed_experts", "num_local_experts = 62.*only the dense sibling"),
    ("with_rope", "position_embedding_type"),
    ("two_groups", "mamba_n_groups"),
    ("proj_bias", "mamba_proj_bias"),
    ("untied", "tie_word_embeddings"),
    ("inner_width", "mamba_expand x hidden_size"),
    ("two_state_shapes", "recurrent states of different shapes in one "
                         "model")])
def test_what_cannot_carry_a_recurrent_state_refuses_loudly(what, sentence):
    with pytest.raises(NotImplementedError, match=sentence):
        _refusal(what)()


def test_prefix_pool_refuses_when_a_request_asks_for_it():
    from flexflow_tpu.serve.batch_config import GenerationConfig

    m, _ = _build()
    rm = RequestManager()
    rm.register_new_request(list(range(1, 20)), max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="shared-prefix pool"):
        rm.generate_incr_decoding(m, GenerationConfig(prefix_cache=True))


def test_the_cache_manager_names_no_recurrent_op():
    """``core/model.py`` finds recurrent layers by the contract of
    ops/recurrent.py: no line of it imports or names either op's module, and
    both ops' states are found by the same two members."""
    import inspect

    from flexflow_tpu.core import model as core
    from flexflow_tpu.ops import kda_attention, recurrent, ssd_mixer

    consolidate = inspect.getsource(core.FFModel._consolidate_kv_caches)
    assert "kda" not in consolidate.replace("ffsv_kda_", "") \
        and "ssd" not in consolidate
    for op, attrs in ((kda_attention.IncKDAttention, dict(
            num_heads=2, head_dim=8, conv_kernel=4, gate_rank=8,
            max_requests=3)), (ssd_mixer.IncSSDMixer, dict(
                num_heads=2, head_dim=8, state_dim=16, conv_kernel=4,
                max_requests=3))):
        state = op.init_state(attrs, [((3, 1, 32), None)])
        assert sorted(state) == [recurrent.STATE, recurrent.TAIL]
        assert all(v.dtype == jnp.float32 and v.shape[0] == 3
                   for v in state.values())
        assert op.takes_chunk_kernel(dict(attrs, use_pallas=False),
                                     None) is False


# ---------------------------------------------------------------------------
# (f) the loop and what telemetry keeps of the state
# ---------------------------------------------------------------------------

def test_the_loop_serves_it_and_counts_where_the_states_came_from(bench):
    """Through RequestManager (compact prefill with consecutive segments,
    decode blocks): the tokens are those the program gives one request at a
    time; the three ``ffsv_kda_*`` series (the name is historical) count the
    mixers' states as they count a gated delta rule's; the attention layer's
    positions alone are counted as read; the two gauges say what compile
    allocated."""
    from flexflow_tpu.telemetry import ServingTelemetry

    family, _ = bench
    m, c = _build(telemetry=True)
    tel = ServingTelemetry()
    new = 12
    got = _serve(m, lens=(70, 9, 1), new=new, tel=tel, seed=20)
    alone, _ = _build()
    for res in got:
        p = list(res.input_tokens)
        alone.op_state = jax.tree.map(jnp.zeros_like, alone.op_state)
        toks = np.asarray(p + list(res.output_tokens))[:-1]
        logits = family.drive(alone, toks, [1] * len(toks))[0]
        assert res.output_tokens == logits[len(p) - 1:].argmax(-1).tolist()
    snap = tel.registry.snapshot()

    def states(phase, source):
        key = f'ffsv_kda_states_total{{phase="{phase}",source="{source}"}}'
        return snap[key]["value"]

    assert states("prefill", "start") == 2
    assert states("prefill", "step") + states("prefill", "state") == 4
    assert states("prefill", "step") >= 3
    assert states("decode", "start") == 1
    row_steps = snap["ffsv_decode_steps_total"]["value"]
    assert states("decode", "state") + 1 == row_steps >= 3 * new
    assert snap["ffsv_kda_state_steps_total"]["value"] == 3 * row_steps
    assert "ffsv_kda_chunk_tokens_total" not in snap    # no chunk kernel
    lens = [n + j for n in (70, 9, 1) for j in range(new)]
    assert snap['ffsv_attn_positions_read_total{kind="full"}'][
        "value"] >= 1 * sum(lens)
    assert 'ffsv_attn_positions_read_total{kind="recurrent"}' not in snap
    for kind in ("full", "recurrent"):
        assert snap[f'ffsv_kv_cache_bytes{{kind="{kind}"}}']["value"] == \
            m.attention_kinds[kind]["cache_bytes"]


# ---------------------------------------------------------------------------
# (e) a synthetic checkpoint under the HF_KEYS names
# ---------------------------------------------------------------------------

def test_hf_weight_map_loads_a_synthetic_checkpoint(bench):
    """A state dict under the names ``models/granite_hybrid.HF_KEYS`` stands
    for (torch layouts: ``[out, in]`` Linears, a depthwise Conv1d weight
    ``[C, 1, 4]``, no key for the tied head): loaded through the family, the
    program's logits are the reference's on the same checkpoint read
    directly; and the family's way back from the served weights is the
    checkpoint."""
    family, reference = bench
    fam = family_for_hf_config({"model_type": "granitemoehybrid"})
    assert fam is FAMILIES["granitemoehybrid"]
    m, c = _build()
    E, V, I = c.hidden_size, c.vocab_size, c.shared_intermediate_size
    nh, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    H, P, N = c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state
    conv = H * P + 2 * N
    rng = np.random.default_rng(4)

    def f(*s, scale=0.08):
        return (rng.standard_normal(s) * scale).astype(np.float32)

    sd = {"model.embed_tokens.weight": f(V, E, scale=0.02),
          "model.norm.weight": 1 + f(E)}
    layers = []
    for i in range(c.num_hidden_layers):
        p = f"model.layers.{i}"
        lw = {"ln1": 1 + f(E), "ln2": 1 + f(E), "w_in": f(E, 2 * I),
              "w_out": f(I, E)}
        if c.kind(i) == "attention":
            lw.update(wq=f(E, nh * hd), wk=f(E, nkv * hd), wv=f(E, nkv * hd),
                      wo=f(nh * hd, E))
            mix = {f"{p}.self_attn.{hf}_proj.weight": lw[w].T for hf, w in (
                ("q", "wq"), ("k", "wk"), ("v", "wv"), ("o", "wo"))}
        else:
            lw.update(win=f(E, 2 * H * P + 2 * N + H),
                      conv=f(4, conv, scale=0.5), conv_bias=f(conv),
                      A_log=np.log(rng.uniform(1, 16, H)).astype(np.float32),
                      dt_bias=f(H) - 3, D=1 + f(H), norm=1 + f(H * P),
                      wout=f(H * P, E))
            mix = {f"{p}.mamba.in_proj.weight": lw["win"].T,
                   f"{p}.mamba.out_proj.weight": lw["wout"].T,
                   # torch Conv1d, depthwise: [C, 1, taps]
                   f"{p}.mamba.conv1d.weight": lw["conv"].T[:, None, :],
                   f"{p}.mamba.conv1d.bias": lw["conv_bias"],
                   f"{p}.mamba.A_log": lw["A_log"],
                   f"{p}.mamba.dt_bias": lw["dt_bias"],
                   f"{p}.mamba.D": lw["D"],
                   f"{p}.mamba.norm.weight": lw["norm"]}
        layers.append(lw)
        sd.update({**mix,
                   f"{p}.input_layernorm.weight": lw["ln1"],
                   f"{p}.post_attention_layernorm.weight": lw["ln2"],
                   f"{p}.shared_mlp.input_linear.weight": lw["w_in"].T,
                   f"{p}.shared_mlp.output_linear.weight": lw["w_out"].T})
    loaded = fam.load_hf(m, fam.config_cls.from_hf_config(TINY), sd)
    assert loaded == len(fam.hf_weight_map(c))
    toks = _tokens(24, seed=9)
    ours = family.drive(m, toks, [[16], [4]] + [1] * 4)[0]
    ref = reference.forward(
        {"emb": sd["model.embed_tokens.weight"], "layers": layers,
         "norm": sd["model.norm.weight"]}, toks, TINY)
    assert _rel(ours, np.asarray(ref)) < TOL
    back = family.reference_weights(m, c)["layers"]
    for i in (0, 2):
        for name, want in layers[i].items():
            np.testing.assert_allclose(back[i][name], want, rtol=1e-6,
                                       atol=1e-6, err_msg=f"{i}.{name}")


# ---------------------------------------------------------------------------
# the yardstick's arithmetic, the cell's files, the rehearsals
# ---------------------------------------------------------------------------

def _printable(s, most=200):
    return isinstance(s, str) and 1 <= len(s) <= most and s.isprintable()


@pytest.mark.parametrize("what", ["arithmetic", "files", "traced_rehearsal",
                                  "variants_tool"])
def test_the_cell_its_files_and_the_arithmetic_of_its_bytes(
        bench, what, monkeypatch, capsys):
    family, _ = bench
    monkeypatch.syspath_prepend(ROOT)
    for key in ("JAX_PLATFORMS", "FF_PALLAS_INTERPRET"):
        monkeypatch.setenv(key, os.environ.get(key, ""))   # restored after
    with open(CONFIG) as f:
        cfg = json.load(f)
    if what == "arithmetic":
        # ISSUE 56's own figures, from the configuration file's sizes
        assert family.cache_position_bytes(cfg) == 2048
        assert family.state_bytes(cfg) == 64 * 64 * 128 * 4 == 2097152
        assert family.conv_tail_bytes(cfg) == 3 * 4352 * 4 == 52224
        # the whole model, ISSUE 56's table: the published depth
        whole = {**cfg, **cfg.get("published", {})}
        assert family.layers_of(whole, "full") == 4
        assert family.layers_of(whole, "recurrent") == 36
        assert family.cache_bytes_per_token(whole) == 4 * 2048
        from flexflow_tpu.kernels.linear_attention import ssd_step_bytes

        assert family.state_step_bytes(cfg, 7.0) == ssd_step_bytes(
            7.0, 64, 64, 128)
        # 36 x 76.18M + 4 x 60.82M + 205.5M parameters, int8 with scales:
        # "3.19 GB"
        weights = sum(r * c * e for _, r, c, e in family.decode_weights(whole))
        assert 3.19e9 < weights < 3.22e9
        # the issue's step: 30 rows of ~3.2k positions: 3.19 + 4.5 + 0.8 GB
        need = family.decode_step_must_read(whole, 30 * 3200 * 4, 30)
        assert abs(need - (weights + 30 * 3200 * 4 * 2048
                           + 30 * 36 * 2 * (2097152 + 52224))) < 1
        assert 8.4e9 < need < 8.8e9
        held = 32 * (36 * (2097152 + 52224) + 4 * 8192 * 2048)
        assert abs(held - 4.62e9) < 0.02e9      # 2.48 + 2.15 GB
        # what the file serves: the depth that stands (PERF.md section 6,
        # PR 56: stage 0 of two, 20 layers)
        assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 20
        assert cfg["layer_types"] == cfg["published"]["layer_types"][:20]
        assert family.layers_of(cfg, "full") == 2
        assert family.layers_of(cfg, "recurrent") == 18
        stage = sum(r * c * e for _, r, c, e in family.decode_weights(cfg))
        assert 1.69e9 < stage < 1.72e9          # "1.70 GB"
        assert abs(stage - (weights + 205.5e6 + 100352 * 4 + 4096) / 2
                   ) < 1e6                      # half the layers, the head whole
        return
    from benchmark import run, selfcheck

    if what == "files":
        assert selfcheck.every_entry_resolves_to_its_files()
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            b = json.load(f)
        entry = {w["name"]: w for w in b["workloads"]}[CELL]
        assert (entry["config"], entry["traffic"], entry["chips"]) == (
            "granite-4.0-h-micro", "context-reasoning", 1)
        conf = {c["name"]: c for c in b["configs"]}["granite-4.0-h-micro"]
        # (PR 54's first check was refused for a `why` of 201 characters)
        for e in b["configs"] + b["workloads"]:
            assert _printable(e["why"]), e
        assert _printable(conf["source"]) and _printable(conf["file"])
        for m in b["per_layer"]:
            assert _printable(m["layer"]), m
        assert CELL in {m["name"]: m for m in b["end_to_end"]}[
            "output_tok_s"]["workloads"]
        mine = {m["name"] for m in b["per_layer"]
                if CELL in m.get("workloads", ())}
        assert {"ssd_state_hbm_roofline", "decode_ssd_hbm_roofline",
                "ssd_state_share", "kv_recurrent_share",
                "attn_kv_hbm_roofline", "decode_step_ms", "prefill_step_ms",
                "device_idle", "peak_hbm_gb"} <= mine
        assert not {"kda_state_hbm_roofline", "decode_kda_hbm_roofline",
                    "linear_attn_share", "kda_chunk_share",
                    "decode_hbm_roofline", "experts_touched"} & mine
        assert len(mine) == 26      # PR 61: prefill_ahead_share
        # the configuration file against the catalog, key by key
        with open("/opt/skills/guides/model-configs/architectures.jsonl"
                  ) as f:
            catalog = {e["name"]: e for e in map(json.loads, f)}
        published = catalog["granite-4.0-h-micro"]
        assert conf["source"] == cfg["source"] == published["source_url"]
        differs = sorted(k for k, v in published["config"].items()
                         if cfg.get(k, "absent") != v)
        assert differs == sorted(cfg["reduced"]) == sorted(conf["reduced"])
        assert cfg["reduced"] == [] or cfg["reduced"] == [
            "num_hidden_layers", "layer_types"]
        a = cfg["assumed"]
        assert (a["max_requests_per_batch"], a["max_sequence_length"],
                a["max_tokens_per_batch"], a["decode_block_steps"],
                a["recurrent_state_dtype"], a["conv_tail_dtype"],
                a["kv_cache_dtype"], a["quantization"]) == (
                    32, 8192, 512, 16, "float32", "float32", "bfloat16",
                    "int8")
        assert all("as ISSUE 56 states it; not checked against the published"
                   " code" in a[k] for k in ("block", "mamba", "mamba_init",
                                             "attention", "hf_keys"))
        return
    if what == "variants_tool":
        sys.path.insert(0, os.path.join(ROOT, "tools"))
        try:
            import check_reference_variants
        finally:
            sys.path.remove(os.path.join(ROOT, "tools"))
        assert check_reference_variants.main(
            ["--config", "granite-4.0-h-micro", "--rehearse"]) == 0
        res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert res["ok"] and res["device"] == "cpu"
        assert res["state_rel_err"] < 1e-5 < res["state_tol"] < res[
            "wrong_bfloat16_state_state"]
        assert sum(k.startswith("wrong_") for k in res) == len(
            family.VARIANTS) + 1
        return
    from flexflow_tpu import kernels as ffk

    ffk.reset_dispatch_stats()      # what the tests before this one traced
    rc = run.main(["--workload", CELL, "--seed", "3000000019", "--seconds",
                   "2", "--trace", "1", "--rehearse"])
    out = capsys.readouterr().out
    last = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and last["correct"] and last["rehearsal"], out[-2000:]
    said = [ln for ln in out.splitlines() if "REHEARSAL" in ln][0]
    assert '"kv_recurrent_share"' in said
