"""Solar-Open2 on the serving path, at tiny sizes on the CPU: three layers in
four keep a recurrent state a row (a gated delta rule with one decay a key
channel) beside a gated GQA layer's plain k/v cache, over a held range of
routed experts.

(a) the recurrence's three forms against each other, at mild and at the
strongest seeded decay, with padding and idle rows, and the kernel
interpreted; (b) the program against the plain reference through the
hand-over, a ragged segment and decode steps; one prompt fed four ways; (c)
a slot reused, a row preempted and prefilled again; (d) the eight shares add
up to the uncut layer and the eight vocabulary slices to the head; (e) what
cannot carry a recurrent state refuses, by its reason; (f) a synthetic
checkpoint under the ``HF_KEYS`` names; (g) the reference has teeth; (h) the
counters and ``attention_kinds``; the yardstick's arithmetic, the cell's
files and its rehearsal.
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
from flexflow_tpu.ops.inc_attention import (FULL_STACK, RECURRENT_STACK,
                                            carried_rows, commit_tree_kv)
from flexflow_tpu.serve.request_manager import RequestManager

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "solar-open2-250b.long-context-reasoning"

TINY = dict(vocab_size=256, hidden_size=64, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            linear_num_heads=4, linear_head_dim=16, moe_intermediate_size=32,
            n_routed_experts=8, num_experts_per_tok=2, gqa_layers=(0,))
# what the reference reads: the published key names
REF_CFG = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               n_routed_experts=8, num_experts_per_tok=2,
               routed_scaling_factor=1.0, gqa_layers=[0], rms_norm_eps=1e-5,
               linear_attn_config=dict(num_heads=4, head_dim=16,
                                       short_conv_kernel_size=4,
                                       num_kv_heads=None))
# float32 program against float32 reference: rounding only
TOL = 3e-5


@pytest.fixture(scope="module")
def bench():
    """The benchmark's family and reference for Solar-Open2, loaded as
    run.py loads them."""
    sys.path.insert(0, ROOT)
    try:
        from benchmark.run import load_module

        yield (load_module("families", "solar_open2"),
               load_module("reference", "solar_open2"))
    finally:
        sys.path.remove(ROOT)


def _build(mode=InferenceMode.INC_DECODING_MODE, tiny=TINY, **ffkw):
    from flexflow_tpu.models.solar_open2 import (SolarOpen2Config,
                                                 create_solar_open2_model)

    kw = dict(max_requests_per_batch=4, max_sequence_length=256,
              max_tokens_per_batch=64, seed=3, compute_dtype="float32",
              kv_cache_dtype="float32", num_devices=1)
    kw.update(ffkw)
    m = ff.FFModel(ff.FFConfig(**kw))
    c = SolarOpen2Config(**tiny)
    create_solar_open2_model(m, c, mode=mode, data_type=ff.DataType.DT_FLOAT)
    m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    return m, c


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, TINY["vocab_size"], size=n)


def _weights(bench, m, c):
    w = bench[0].reference_weights(m, c)
    return {**w, "layers": list(w["layers"])}


def _reference(bench, m, c, toks, cfg=REF_CFG, **kw):
    logits, scores = bench[1].forward_routed(_weights(bench, m, c), toks,
                                             cfg, **kw)
    return np.asarray(logits), [np.asarray(s) for s in scores]


def _rel(a, b):
    return float((np.linalg.norm(a - b, axis=-1)
                  / np.linalg.norm(b, axis=-1)).max())


def _state(m, slot):
    st = m.op_state[RECURRENT_STACK]
    return np.asarray(st["s"])[:, slot], np.asarray(st["u"])[:, slot]


# ---------------------------------------------------------------------------
# (a) one recurrence, three forms
# ---------------------------------------------------------------------------

def _literal(S0, q, k, g, v, beta):
    """Token by token, in float64 numpy: ``(o [B, T, H, V], S_T)``."""
    S = np.asarray(S0, np.float64).copy()
    out = np.zeros(v.shape, np.float64)
    for t in range(q.shape[1]):
        S = S * np.exp(g[:, t])[..., None]
        d = beta[:, t][..., None] * (
            v[:, t] - np.einsum("bhkv,bhk->bhv", S, k[:, t]))
        S = S + k[:, t][..., None] * d[..., None, :]
        out[:, t] = np.einsum("bhkv,bhk->bhv", S, q[:, t])
    return out, S


def _draw(decay: str, B=3, T=150, H=2, K=16, seed=0, n=None):
    """A step's q, k, g, v, beta as the op makes them; ``decay``: "mild" or
    "strongest" (every channel at the seeded initialisation's strongest: A
    = 16, dt = 0.1, and a decay input a unit above its bias)."""
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.standard_normal((B, T, H, K))) / np.sqrt(K)
    k = unit(rng.standard_normal((B, T, H, K)))
    v = rng.standard_normal((B, T, H, K))
    beta = 2 / (1 + np.exp(-rng.standard_normal((B, T, H))))
    if decay == "mild":
        g = -np.exp(rng.uniform(np.log(1e-3), np.log(0.2), (B, T, H, K)))
    else:
        g = -16.0 * np.log1p(np.exp(np.log(np.expm1(0.1))
                                    + rng.uniform(0, 1, (B, T, H, K))))
    S0 = rng.standard_normal((B, H, K, K))
    if n is None:
        n = [T, T - 37, 0][:B]                  # whole, ragged, idle
    n = np.asarray(n)
    real = np.arange(T)[None, :] < n[:, None]
    g = np.where(real[..., None, None], g, 0.0)
    beta = np.where(real[..., None], beta, 0.0)
    return S0, q, k, g, v, beta, n


@pytest.mark.parametrize("decay", ["mild", "strongest"])
def test_chunked_recurrent_and_literal_forms_agree(decay):
    """The chunked form (chunks of 64 in sub-chunks of 16, and of one
    sub-chunk) and the recurrent form a token at a time against the literal
    float64 recurrence: outputs at every real position and the end state,
    with a ragged row whose padding leaves the state as it is and an idle
    row that keeps its own. At the strongest seeded decay a chunk's
    cumulative log decay passes -100 a channel, where ``exp(-G)`` overflows
    float32: nothing here forms it."""
    from flexflow_tpu.ops.kda_attention import chunked, recurrent_step

    S0, q, k, g, v, beta, n = _draw(decay)
    if decay == "strongest":
        assert np.cumsum(g[0, :64], axis=0).min() < -100
    want_o, want_S = _literal(S0, q, k, g, v, beta)
    f32 = [jnp.asarray(x, jnp.float32) for x in (S0, q, k, g, v, beta)]
    for kw in ({}, {"chunk": 16}):
        o, S = chunked(*f32, **kw)
        assert np.isfinite(np.asarray(o)).all()
        for b, n_b in enumerate(n):
            np.testing.assert_allclose(np.asarray(o)[b, :n_b],
                                       want_o[b, :n_b], rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(S), want_S, rtol=2e-4,
                                   atol=2e-5)
    np.testing.assert_array_equal(np.asarray(S)[2], np.float32(S0)[2])
    S = f32[0]
    for t in range(q.shape[1]):
        o_t, S = recurrent_step(S, *(x[:, t] for x in f32[1:]))
        real = t < n
        np.testing.assert_allclose(np.asarray(o_t)[real], want_o[real, t],
                                   rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(S), want_S, rtol=2e-4, atol=2e-5)


def _through_kernel(S0, q, k, g, v, beta, n, slots=None, start=None,
                    stack=None, layer=1):
    """``kda_chunk`` interpreted on a stack of two layers whose layer
    ``layer`` holds ``S0`` in the rows' slots (distinct slots, each row from
    the store, unless given): ``(o, the new stack, the old stack)``."""
    from flexflow_tpu.kernels.linear_attention import kda_chunk

    B = q.shape[0]
    rng = np.random.default_rng(9)
    if slots is None:
        slots, start = np.arange(B)[::-1] + 1, np.arange(B) + 7
    if stack is None:
        stack = rng.standard_normal((2, B + 2) + S0.shape[1:]).astype(
            np.float32)
        stack[layer, slots] = np.float32(S0)
    f32 = [jnp.asarray(x, jnp.float32) for x in (q, k, g, v, beta)]
    o, new = kda_chunk(jnp.asarray(stack), layer, *f32,
                       *(jnp.asarray(x, jnp.int32) for x in (slots, start, n)),
                       interpret=True)
    return np.asarray(o), np.asarray(new), stack


@pytest.mark.parametrize("decay", ["mild", "strongest"])
def test_the_chunked_kernel_interpreted(decay):
    """``kda_chunk`` in interpret mode against the jnp ``chunked`` and the
    literal float64 recurrence, for the decays the forms' test draws (at
    the strongest nothing overflows and nothing is NaN): rows of 0, 1, 63,
    64, 65 and 128 real tokens of 130 (a padding tail in every row, a chunk
    of nothing but padding in most), each from the store into its own slot;
    an idle row's output is zeros and its slot's state untouched bit for
    bit, as every slot no row names and the other layer."""
    from flexflow_tpu.ops.kda_attention import chunked

    S0, q, k, g, v, beta, n = _draw(decay, B=6, T=130,
                                    n=[0, 1, 63, 64, 65, 128])
    if decay == "strongest":
        assert np.cumsum(g[5, :64], axis=0).min() < -100
    want_o, want_S = _literal(S0, q, k, g, v, beta)
    o, new, stack = _through_kernel(S0, q, k, g, v, beta, n)
    assert np.isfinite(o).all() and np.isfinite(new).all()
    jo, jS = chunked(*(jnp.asarray(x, jnp.float32)
                       for x in (S0, q, k, g, v, beta)))
    slots = np.arange(6)[::-1] + 1
    for b, n_b in enumerate(n):
        for want in (want_o, np.asarray(jo)):
            np.testing.assert_allclose(o[b, :n_b], want[b, :n_b], rtol=2e-4,
                                       atol=2e-5)
    for want in (want_S, np.asarray(jS)):
        np.testing.assert_allclose(new[1, slots[1:]], want[1:], rtol=2e-4,
                                   atol=2e-5)
    assert not o[0].any()
    np.testing.assert_array_equal(new[1, [0, 6, 7]], stack[1, [0, 6, 7]])
    np.testing.assert_array_equal(new[0], stack[0])


@pytest.mark.parametrize("live", [(1, 0, 1, 1, 0), (0, 0, 0, 0, 0),
                                  (1, 1, 1, 1, 1), (0, 0, 0, 0, 1)])
def test_the_recurrent_kernel_interpreted(live):
    """``kda_state_step`` in interpret mode against ``recurrent_step``, on a
    stack of two layers: a live row's state is updated in place, a fresh
    row's starts from zeros whatever the slot held, an idle row's state is
    untouched and its output zeros, the other layer is untouched; with
    nobody live nothing changes."""
    from flexflow_tpu.kernels.linear_attention import kda_state_step
    from flexflow_tpu.ops.kda_attention import recurrent_step

    L, R, H, K = 2, 5, 4, 16
    rng = np.random.default_rng(0)
    stack = jnp.asarray(rng.standard_normal((L, R, H, K, K)), jnp.float32)
    q, k, g, v = (jnp.asarray(rng.standard_normal((R, H, K)), jnp.float32)
                  for _ in range(4))
    g = -jnp.abs(g)
    beta = jnp.asarray(rng.uniform(0, 2, (R, H)), jnp.float32)
    live = jnp.asarray(live, bool)
    fresh = jnp.asarray([0, 1, 1, 0, 0], bool)
    o, new = kda_state_step(stack, 1, q, k, g, v, beta, live, fresh,
                            interpret=True)
    S0 = jnp.where(fresh[:, None, None, None], 0, stack[1])
    want_o, want_S = recurrent_step(S0, q, k, g, v, beta)
    lv = np.asarray(live)
    np.testing.assert_allclose(np.asarray(o)[lv], np.asarray(want_o)[lv],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new)[1][lv],
                               np.asarray(want_S)[lv], rtol=1e-5, atol=1e-5)
    assert not np.asarray(o)[~lv].any()
    np.testing.assert_array_equal(np.asarray(new)[1][~lv],
                                  np.asarray(stack)[1][~lv])
    np.testing.assert_array_equal(np.asarray(new)[0], np.asarray(stack)[0])


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


def _compiled_for(one_chip, form: str) -> str:
    """The optimised HLO of one layer's call at the cell's shape (the stack
    of 6 layers x 16 slots x 64 heads of 128 x 128 float32, donated):
    "recurrent": a decode step's 16 rows; "chunked": a prefill step's 4
    rows of 128 tokens."""
    from flexflow_tpu.kernels import linear_attention as LA

    L, S, H, K = 6, 16, 64, 128
    assert LA.supports(H, K, K) and LA.heads_per_block(H) == 16
    assert LA.supports_chunk(H, K, K) and LA.chunk_size(128) == 64
    from flexflow_tpu.ops import kda_attention

    assert (LA.CHUNK, LA.SUB) == (kda_attention.CHUNK, kda_attention.SUB)

    def aval(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    if form == "recurrent":
        def step(stack, q, k, g, v, beta, live, fresh):
            return LA.kda_state_step.__wrapped__(stack, 3, q, k, g, v, beta,
                                                 live, fresh)

        args = [*[aval((S, H, K))] * 4, aval((S, H)), aval((S,), bool),
                aval((S,), bool)]
    else:
        def step(stack, q, k, g, v, beta, slots, start, n):
            return LA.kda_chunk.__wrapped__(stack, 3, q, k, g, v, beta,
                                            slots, start, n)

        args = [*[aval((4, 128, H, K))] * 4, aval((4, 128, H)),
                *[aval((4,), jnp.int32)] * 3]
    return jax.jit(step, donate_argnums=(0,)).lower(
        aval((L, S, H, K, K)), *args).compile().as_text()


@pytest.mark.parametrize("form", ["recurrent", "chunked"])
def test_the_recurrent_kernel_compiles_for_a_v5e_at_the_cells_shape(
        one_chip, form):
    """What interpret mode cannot show: Mosaic takes the kernels at the
    published widths (6 layers x 16 slots x 64 heads of 128 x 128 float32;
    the recurrent form 16 heads a program, the chunked form a prefill
    step's 4 rows of 128 tokens) on a donated stack. Compiled for a
    described chip; nothing runs."""
    text = _compiled_for(one_chip, form)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # the stack goes through in place: no copy of it anywhere
    assert "f32[6,16,64,128,128]{4,3,2,1,0} copy(" not in text


def test_the_two_kernels_device_operations_keep_their_names_apart(one_chip):
    """The chunked form's device operation is ``kda_chunk`` and nothing in
    its program is named ``kda_state_step``, the substring by which the
    benchmark's readers find the RECURRENT kernel
    (``linear_attn_share``, ``kda_state_hbm_roofline``); and the other way
    round."""
    import re

    from flexflow_tpu.kernels import linear_attention as LA

    assert (LA.NAME, LA.CHUNK_NAME) == ("kda_state_step", "kda_chunk")
    assert LA.NAME not in LA.CHUNK_NAME and LA.CHUNK_NAME not in LA.NAME
    for form, mine, other in (("chunked", LA.CHUNK_NAME, LA.NAME),
                              ("recurrent", LA.NAME, LA.CHUNK_NAME)):
        text = _compiled_for(one_chip, form)
        (call,) = [line for line in text.splitlines()
                   if 'custom_call_target="tpu_custom_call"' in line]
        assert re.match(rf"\s*(ROOT )?%{mine}(\.\d+)? = ", call), call[:80]
        assert other not in text


def test_carried_rows_by_source():
    """The rule on a hand-made step, for a state a row: a row at position 0
    starts from zeros whatever the slot held, a row that continues another
    row of its slot from that row's END (through a one-token row too), any
    other from the store; only each slot's last row is written back, into
    the named layer alone, and an idle row neither reads nor writes."""
    rng = np.random.default_rng(1)
    stored = rng.standard_normal((2, 4, 3)).astype(np.float32)
    #        slot start n
    rows = [(2, 0, 3),      # starts a request in a slot that held something
            (2, 3, 1),      # continues row 0 (one token)
            (2, 4, 2),      # continues row 1
            (1, 7, 3),      # from the store
            (0, 5, 0)]      # idle
    slots, start, n = (jnp.asarray(x, jnp.int32) for x in zip(*rows))
    seen = []

    def run(i, state):      # a row's end: its start plus (i + 1) a token
        seen.append(state)
        return state, state + (i + 1.0) * n[i]

    outs, kept = carried_rows(jnp.asarray(stored), slots, start, n, run,
                              layer=1)
    outs, kept = np.asarray(jnp.stack(outs)), np.asarray(kept)
    assert not outs[0].any()
    np.testing.assert_allclose(outs[1], 3.0)            # row 0's end
    np.testing.assert_allclose(outs[2], 3.0 + 2.0)      # row 1's end
    np.testing.assert_array_equal(outs[3], stored[1, 1])
    np.testing.assert_allclose(kept[1, 2], 5.0 + 6.0)   # row 2's end
    np.testing.assert_allclose(kept[1, 1], stored[1, 1] + 12.0)
    np.testing.assert_array_equal(kept[1, [0, 3]], stored[1, [0, 3]])
    np.testing.assert_array_equal(kept[0], stored[0])


def test_carried_rows_by_source_through_the_chunked_kernel():
    """The same hand-made step through ``kda_chunk``, whose scalars
    (``chunk_sources``) stand where ``carried_rows``' selects stood: the
    row at position 0 starts from zeros over a dirty slot, each
    continuation from the END of the row before it (kept in VMEM), the
    fourth from the store; each slot's LAST row's end alone is written
    back, into the named layer alone, and the idle row's slot and the
    slot nobody names keep their states bit for bit. Against the jnp path
    (``carried_rows`` over ``chunked``) and the literal recurrence run
    segment after segment."""
    from flexflow_tpu.kernels.linear_attention import (FROM_STEP, FROM_STORE,
                                                       FROM_ZEROS,
                                                       chunk_sources)
    from flexflow_tpu.ops.kda_attention import chunked

    rows = [(2, 0, 3), (2, 3, 1), (2, 4, 2), (1, 7, 3), (0, 5, 0)]
    # the step's rows in ascending order of start, as _prefill_rows gives
    # them (the slots' rows interleaved)
    rows = sorted(rows, key=lambda r: r[1])
    slots, start, n = (np.asarray(x) for x in zip(*rows))
    walk, slot_of, src, nl = (np.asarray(x) for x in chunk_sources(
        *(jnp.asarray(x, jnp.int32) for x in (slots, start, n))))
    assert nl == 4 and slot_of[:4].tolist() == [1, 2, 2, 2]
    assert [rows[i][1] for i in walk[:4]] == [7, 0, 3, 4]
    assert src[:4].tolist() == [FROM_STORE, FROM_ZEROS, FROM_STEP, FROM_STEP]
    # the idle row comes last and names the last live row's slot: no move
    assert rows[walk[4]][2] == 0 and slot_of[4] == 2
    S0, q, k, g, v, beta, n = _draw("mild", B=5, T=3, n=n)
    rng = np.random.default_rng(4)
    stack = rng.standard_normal((2, 4) + S0.shape[1:]).astype(np.float32)
    o, new, _ = _through_kernel(S0, q, k, g, v, beta, n, slots, start,
                                stack=stack.copy())
    f32 = [jnp.asarray(x, jnp.float32) for x in (q, k, g, v, beta)]

    def run(i, state):
        o_i, S = chunked(state[None], *(x[i:i + 1] for x in f32))
        return o_i[0], S[0]

    outs, kept = carried_rows(jnp.asarray(stack), *(
        jnp.asarray(x, jnp.int32) for x in (slots, start, n)), run, layer=1)
    for i, n_i in enumerate(n):
        np.testing.assert_allclose(o[i, :n_i], np.asarray(outs[i])[:n_i],
                                   rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(new, np.asarray(kept), rtol=2e-4, atol=2e-5)
    # slot 2: its three rows are one run of six tokens from zeros
    by_start = {sp: i for i, (_, sp, _) in enumerate(rows)}
    run2 = [by_start[0], by_start[3], by_start[4]]
    cat = [np.concatenate([x[i, :n[i]] for i in run2])[None]
           for x in (q, k, g, v, beta)]
    want_o, want_S = _literal(np.zeros_like(S0[:1]), *cat)
    np.testing.assert_allclose(new[1, 2], want_S[0], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        np.concatenate([o[i, :n[i]] for i in run2]), want_o[0], rtol=2e-4,
        atol=2e-5)
    _, want_S = _literal(stack[1, 1][None], *(
        x[by_start[7]][None] for x in (q, k, g, v, beta)))
    np.testing.assert_allclose(new[1, 1], want_S[0], rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(new[1, [0, 3]], stack[1, [0, 3]])
    np.testing.assert_array_equal(new[0], stack[0])


# ---------------------------------------------------------------------------
# (b) the program against the plain reference; one prompt four ways
# ---------------------------------------------------------------------------

def test_program_matches_plain_reference_through_hand_over_and_decode(bench):
    """Prefill in chunks of the compact batch (two consecutive segments of
    one slot in ONE step: the hand-over; then a ragged one from the store),
    then eight decode steps through the state and the cache: logits at
    every position against the full float32 forward, the reference choosing
    its own routes; the state the first KDA layer holds at the end against
    the literal recurrence on that layer's own inputs. (g) And the
    reference has teeth: each term the issue names, left out, is far
    outside the tolerance; a bfloat16 state is outside the state's."""
    family, reference = bench
    m, c = _build()
    assert m.attention_kinds == {
        "full": {"layers": 1, "window": None,
                 "cache_bytes": 2 * 4 * 2 * 256 * 16 * 4},
        "recurrent": {"layers": 3, "window": None,
                      "cache_bytes": 3 * 4 * (4 * 16 * 16 + 3 * 192) * 4,
                      "state_bytes": 3 * 4 * 4 * 16 * 16 * 4,
                      "conv_bytes": 3 * 4 * 3 * 192 * 4,
                      "op": "INC_KDA_ATTENTION", "chunk_kernel": False}}
    assert m.op_state[RECURRENT_STACK]["s"].dtype == jnp.float32
    assert m.op_state[FULL_STACK]["k"].shape[0] == 1
    toks = _tokens(16 + 16 + 11 + 8)
    plan = [[16, 16], [11]] + [1] * 8
    mid = []
    ours, routes, inputs = family.drive(
        m, toks, plan, slot=2,
        after_prefill=lambda: mid.append(family.held_state(m, 2)))
    ref, scores = _reference(bench, m, c, toks)
    assert [np.sort(r, -1).tolist() for r in routes] == [
        np.sort(np.argsort(-s, -1)[:, :2], -1).tolist() for s in scores]
    assert _rel(ours, ref) < TOL
    lw = _weights(bench, m, c)["layers"][1]
    held = family.held_state(m, 2)
    assert family.state_error(REF_CFG, reference, lw, inputs, held) < 1e-5
    assert family.state_error(REF_CFG, reference, lw, inputs[:43],
                              mid[0]) < 1e-5
    assert family.state_error(REF_CFG, reference, lw, inputs, held,
                              state_dtype=jnp.bfloat16) > 2e-3
    for term in ("beta2", "decay", "conv_tap", "o_gate", "gqa_gate"):
        wrong, _ = _reference(bench, m, c, toks, without=(term,))
        assert _rel(wrong, ref) > 0.1, term


PLANS = {
    "consecutive_segments_in_one_step": [[16, 16, 16, 5]],
    "two_steps_of_two_segments": [[16, 16], [16, 5]],
    "one_segment_a_step": [[16], [16], [16], [5]],
    "one_token_a_step": [1] * 53,
}


@pytest.fixture(scope="module", params=["jnp", "kernels"])
def four_ways(bench, request):
    """The prompt through each plan, on the jnp path and with the kernels
    interpreted (``pallas_interpret_forced``: every prefill step through
    ``kda_chunk``, every one-token step through ``kda_state_step``)."""
    from flexflow_tpu.kernels import linear_attention as LA

    family, _ = bench
    toks = _tokens(53, seed=5)
    out = {"path": request.param}
    with pytest.MonkeyPatch.context() as mp:
        if request.param == "kernels":
            mp.setenv("FF_PALLAS_INTERPRET", "1")
        LA.chunk_form_counts.clear()
        for name, plan in PLANS.items():
            m, _ = _build()
            assert m.attention_kinds["recurrent"]["chunk_kernel"] == (
                request.param == "kernels")
            logits, routes, _ = family.drive(m, toks, plan, slot=1)
            out[name] = (logits, routes, _state(m, 1), _state(m, 0))
        forms = {form for form, _, _ in LA.chunk_form_counts}
        assert forms == {"kernel" if request.param == "kernels" else "jnp"}
        assert LA.chunk_summary().startswith(f"chunked form: {forms.pop()}, ")
    return out


@pytest.mark.parametrize("way", sorted(PLANS)[1:])
def test_one_prompt_fed_four_ways_gives_the_same_logits_and_states(
        four_ways, way):
    """A segment's state from another segment of the step, from the store a
    step left, or a token at a time (the recurrent form all the way): the
    same logits to float32 rounding, the same routes, and the slot is left
    with the same state and tails (the LAST segment's, whichever row of the
    batch carried it); no other slot's is touched."""
    base = four_ways[sorted(PLANS)[0]]
    got = four_ways[way]
    assert _rel(got[0], base[0]) < TOL
    assert all((a == b).all() for a, b in zip(got[1], base[1]))
    for a, b in zip(got[2], base[2]):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
    assert np.abs(base[2][0]).max() > 0.05 and np.abs(base[2][1]).max() > 0.1
    assert not got[3][0].any() and not got[3][1].any()


def test_the_slot_grid_prefill_carries_the_state_too(bench, four_ways):
    """A prefill chunk on the slot grid (``slots`` None, a row a slot) takes
    its state from the store like a decode step: the same logits as the
    compact batch's (on the kernel path: ``kda_chunk`` over a row a slot)."""
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


def test_the_kernel_path_serves_the_same_tokens(monkeypatch):
    """Served with the kernels interpreted: a prefill step goes through
    ``kda_chunk`` (``ffsv_kda_chunk_tokens_total`` counts its tokens, and
    does not exist on the jnp path), the decode block through
    ``kda_state_step`` and the flash kernel, and in float32 the tokens are
    those of the jnp path (in bfloat16 the two paths round at different
    points and a tiny model's largest logit changes hands, so there the
    cell's dtypes are only served: bfloat16 compute, cache and tails beside
    a float32 state)."""
    import flexflow_tpu.kernels as ffk

    def serve(dtype="float32", tel=None):
        m, _ = _build(max_sequence_length=512, compute_dtype=dtype,
                      kv_cache_dtype=dtype, telemetry=tel is not None)
        assert m.op_state[RECURRENT_STACK]["s"].dtype == jnp.float32
        assert m.op_state[RECURRENT_STACK]["u"].dtype == jnp.float32
        rm = RequestManager()
        rm.telemetry = tel
        for i, n in enumerate((70, 9)):
            rm.register_new_request([int(t) for t in _tokens(n, seed=50 + i)],
                                    max_new_tokens=6)
        return [r.output_tokens for r in rm.generate_incr_decoding(m)]

    from flexflow_tpu.telemetry import ServingTelemetry

    chunk_tokens = "ffsv_kda_chunk_tokens_total"
    tel = ServingTelemetry()
    plain = serve(tel=tel)
    assert chunk_tokens not in tel.registry.snapshot()
    monkeypatch.setenv("FF_PALLAS_INTERPRET", "1")
    ffk.reset_dispatch_stats()
    tel = ServingTelemetry()
    assert serve(tel=tel) == plain
    assert not ffk.fallback_counts and ffk.fast_path_count > 0
    # every prefilled token (a prompt's last goes with the decode block)
    # through ``kda_chunk`` in each of the three layers
    snap = tel.registry.snapshot()
    assert snap[chunk_tokens]["value"] == 3 * (69 + 8) == 3 * snap[
        "ffsv_prefill_tokens_total"]["value"]
    assert [len(t) for t in serve("bfloat16")] == [6, 6]


# ---------------------------------------------------------------------------
# (c) a slot reused; a row preempted and prefilled again
# ---------------------------------------------------------------------------

def test_a_reused_slot_starts_from_a_cleared_state(bench):
    """A second request in a slot whose first left its state there: its
    logits are those of the same request alone in a fresh model."""
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
    the state with the cache: the victim's tokens are those of an
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
# (d) the share ties to the model
# ---------------------------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer(bench):
    """One KDA layer, a router of 16 over 8 chips of 2 experts: the routed
    parts the eight shares compute (each the reference with its own held
    range; the mixer, the residual stream and the shared expert, which
    every chip computes alike, counted once) add up to what the uncut
    reference gives for the whole layer; the PROGRAM's share is the
    reference's share; and the eight vocabulary slices of the head
    concatenate to the whole head's logits."""
    _, reference = bench
    one = dict(TINY, num_hidden_layers=1, gqa_layers=(), n_routed_experts=16)
    whole, c = _build(tiny=one)
    wl = _weights(bench, whole, c)
    cfg = dict(REF_CFG, gqa_layers=[], n_routed_experts=16)
    toks = _tokens(24, seed=11)
    lw = wl["layers"][0]
    x = jnp.asarray(wl["emb"])[jnp.asarray(toks)]

    def layer(first, count):
        """The layer's output (before the final norm, which is not linear)
        from the share that holds experts [first, first + count)."""
        held = {k: lw[k][first:first + count] for k in ("gate", "up", "down")}
        out, _ = reference._layer(
            x, {**lw, **held}, None, kind="kda", dims=(4, 16), eps=1e-5,
            top_k=2, scaling=1.0, held=(first, count), without=(), dt=None,
            state_dtype=None)
        return np.asarray(out)

    alike = layer(0, 0)             # what every chip computes alike
    routed = sum(layer(2 * r, 2) - alike for r in range(8))
    np.testing.assert_allclose(alike + routed, layer(0, 16), rtol=1e-4,
                               atol=1e-5)
    assert np.abs(routed).max() > 0.01
    # the program's share is the reference's share
    m0, c0 = _build(tiny=dict(one, held_experts=(4, 2)))
    assert c0.held == (4, 2) and c.held == (0, 16)
    ours = bench[0].drive(m0, toks, [[16], [8]])[0]
    want = reference.forward_routed(_weights(bench, m0, c0), toks, cfg,
                                    held=(4, 2))[0]
    assert _rel(ours, np.asarray(want)) < TOL
    # the vocabulary, eight ways
    ref = np.asarray(reference.forward_routed(wl, toks, cfg)[0])
    slices = [np.asarray(reference.forward_routed(
        {**wl, "head": wl["head"][:, 32 * r:32 * (r + 1)]}, toks, cfg)[0])
        for r in range(8)]
    np.testing.assert_allclose(np.concatenate(slices, -1), ref, rtol=1e-5,
                               atol=1e-6)


# ---------------------------------------------------------------------------
# (e) what cannot carry a recurrent state refuses, by its mechanism
# ---------------------------------------------------------------------------

def _refusal(name):
    from flexflow_tpu.models.solar_open2 import SolarOpen2Config

    if name in ("tree_verify_mode", "beam_search_mode"):
        mode = (InferenceMode.TREE_VERIFY_MODE if name == "tree_verify_mode"
                else InferenceMode.BEAM_SEARCH_MODE)
        return lambda: _build(mode=mode)
    if name == "tensor_parallel_mesh":
        return lambda: _build(tensor_parallelism_degree=2, num_devices=2)
    if name == "pipeline_plan":
        return lambda: _build(pipeline_parallelism_degree=2, num_devices=2)
    hf = {"with_rope": {"use_rope": True},
          "full_proj": {"kda_use_full_proj": True},
          "no_neg_eigval": {"kda_allow_neg_eigval": False},
          "a_dense_layer": {"first_k_dense_replace": 1},
          "kv_heads": {"linear_attn_config": {"num_kv_heads": 2}}}
    if name in hf:
        return lambda: SolarOpen2Config.from_hf_config(hf[name])
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
        return lambda: eng._commit(type("E", (), {"depth": 2})(), m.op_state,
                                   z, z, z, z > 0)
    if name == "prefix_pool":
        from flexflow_tpu.serve import prefix_cache

        return lambda: prefix_cache.extract_prefix_kv(m.op_state, 0, 8, 256)
    if name == "tree_batch_on_the_op":
        from flexflow_tpu.ffconst import OpType
        from flexflow_tpu.ops.base import OpContext
        from flexflow_tpu.ops.kda_attention import IncKDAttention

        ctx = OpContext(training=False, rng=None, compute_dtype=jnp.float32,
                        batch_config=type("M", (), {"ancestor": 0})())
        layer = next(ly for ly in m.layers
                     if ly.op_type == OpType.INC_KDA_ATTENTION)
        return lambda: IncKDAttention.forward(
            layer.attrs, m.params[layer.name], [jnp.zeros((4, 1, 64))], ctx)
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
    ("beam_engine_commit", "speculation commit.*recurrent state"),
    ("prefix_pool", "shared-prefix pool is not supported over an attention "
                    "layer that keeps a recurrent state.*no snapshot"),
    ("tree_batch_on_the_op", "a token a row a step"),
    ("with_rope", "use_rope"),
    ("full_proj", "kda_use_full_proj"),
    ("no_neg_eigval", "kda_allow_neg_eigval"),
    ("a_dense_layer", "first_k_dense_replace"),
    ("kv_heads", "num_kv_heads")])
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


# ---------------------------------------------------------------------------
# (h) the loop and what telemetry keeps of the state
# ---------------------------------------------------------------------------

def test_the_loop_serves_it_and_counts_where_the_states_came_from(bench):
    """Through RequestManager (compact prefill with consecutive segments,
    decode blocks): the tokens are those the program gives one request at a
    time; ``ffsv_kda_states_total`` counts every prefill segment and decode
    row-step by where its state came from, ``ffsv_kda_state_steps_total``
    the live rows x recurrent layers x steps of the decode blocks,
    ``ffsv_attn_positions_read_total`` the GQA layer's positions alone, the
    two gauges what compile allocated; no tail series of another model."""
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
        logits = family.drive(alone, toks, [1] * len(toks))[0]
        assert res.output_tokens == logits[len(p) - 1:].argmax(-1).tolist()
    snap = tel.registry.snapshot()

    def states(phase, source):
        key = f'ffsv_kda_states_total{{phase="{phase}",source="{source}"}}'
        return snap[key]["value"]

    # as tests/test_zaya.py counts its tails: five segments of the 70-token
    # prompt (the first from zeros, at least three handed over inside their
    # step), one of the 9-token one, none of the 1-token one
    assert states("prefill", "start") == 2
    assert states("prefill", "step") + states("prefill", "state") == 4
    assert states("prefill", "step") >= 3
    assert states("decode", "start") == 1
    row_steps = snap["ffsv_decode_steps_total"]["value"]
    assert states("decode", "state") + 1 == row_steps >= 3 * new
    assert snap["ffsv_kda_state_steps_total"]["value"] == 3 * row_steps
    lens = [len(p) + j for p in prompts for j in range(new)]
    assert snap['ffsv_attn_positions_read_total{kind="full"}'][
        "value"] >= 1 * sum(lens)
    assert 'ffsv_attn_positions_read_total{kind="recurrent"}' not in snap
    assert not any("cca_tails" in k for k in snap)
    for kind in ("full", "recurrent"):
        assert snap[f'ffsv_kv_cache_bytes{{kind="{kind}"}}']["value"] == \
            m.attention_kinds[kind]["cache_bytes"]


# ---------------------------------------------------------------------------
# (f) a synthetic checkpoint under the HF_KEYS names
# ---------------------------------------------------------------------------

def test_hf_weight_map_loads_a_synthetic_checkpoint(bench):
    """A state dict under the names ``models/solar_open2.HF_KEYS`` stands
    for (torch layouts: ``[out, in]`` Linears, one a projection an expert,
    depthwise Conv1d weights ``[C, 1, 4]``): loaded through the family, the
    program's logits are the reference's on the same checkpoint read
    directly; and the family's way back from the served weights is the
    checkpoint."""
    family, reference = bench
    fam = family_for_hf_config({"model_type": "solar_open2"})
    assert fam is FAMILIES["solar_open2"] and fam.name == "solar_open2"
    m, c = _build()
    E, V = c.hidden_size, c.vocab_size
    nh, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    H, K = c.linear_num_heads, c.linear_head_dim
    n, I, r = c.n_routed_experts, c.moe_intermediate_size, c.linear_head_dim
    rng = np.random.default_rng(4)

    def f(*s, scale=0.08):
        return (rng.standard_normal(s) * scale).astype(np.float32)

    sd = {"model.embed_tokens.weight": f(V, E),
          "model.norm.weight": 1 + f(E), "lm_head.weight": f(V, E)}
    layers = []
    for i in range(c.num_hidden_layers):
        p = f"model.layers.{i}"
        lw = {"ln1": 1 + f(E), "ln2": 1 + f(E), "router": f(E, n, scale=0.5),
              "bias": f(n, scale=0.02), "gate": f(n, E, I), "up": f(n, E, I),
              "down": f(n, I, E), "s_gate": f(E, I), "s_up": f(E, I),
              "s_down": f(I, E)}
        if c.kind(i) == "gqa":
            lw.update(wq=f(E, nh * hd), wk=f(E, nkv * hd), wv=f(E, nkv * hd),
                      wg=f(E, nh * hd), wo=f(nh * hd, E))
            attn = {f"{p}.self_attn.{hf}_proj.weight": lw[w].T for hf, w in (
                ("q", "wq"), ("k", "wk"), ("v", "wv"), ("g", "wg"),
                ("o", "wo"))}
        else:
            lw.update(wq=f(E, H * K), wk=f(E, H * K), wv=f(E, H * K),
                      conv=f(4, 3 * H * K, scale=0.5), wfa=f(E, r),
                      wfb=f(r, H * K), wga=f(E, r), wgb=f(r, H * K),
                      wb=f(E, H), A_log=np.log(rng.uniform(1, 16, H)).astype(
                          np.float32), dt_bias=f(H * K) - 3,
                      o_norm=1 + f(K), wo=f(H * K, E))
            cq, ck, cv = np.split(lw["conv"], 3, axis=1)
            attn = {
                **{f"{p}.self_attn.{hf}_proj.weight": lw[w].T for hf, w in (
                    ("q", "wq"), ("k", "wk"), ("v", "wv"), ("f_a", "wfa"),
                    ("f_b", "wfb"), ("g_a", "wga"), ("g_b", "wgb"),
                    ("b", "wb"), ("o", "wo"))},
                # torch Conv1d, depthwise: [C, 1, taps]
                **{f"{p}.self_attn.{s}_conv1d.weight": w.T[:, None, :]
                   for s, w in (("q", cq), ("k", ck), ("v", cv))},
                f"{p}.self_attn.A_log": lw["A_log"],
                f"{p}.self_attn.dt_bias": lw["dt_bias"],
                f"{p}.self_attn.o_norm.weight": lw["o_norm"]}
        layers.append(lw)
        sd.update({
            **attn,
            f"{p}.input_layernorm.weight": lw["ln1"],
            f"{p}.post_attention_layernorm.weight": lw["ln2"],
            f"{p}.mlp.gate.weight": lw["router"].T,
            f"{p}.mlp.gate.e_score_correction_bias": lw["bias"],
            **{f"{p}.mlp.shared_experts.{proj}_proj.weight":
               lw[f"s_{proj}"].T for proj in ("gate", "up", "down")},
            **{f"{p}.mlp.experts.{e}.{proj}_proj.weight": lw[proj][e].T
               for e in range(n) for proj in ("gate", "up", "down")}})
    loaded = fam.load_hf(m, fam.config_cls(**TINY), sd)
    assert loaded == len(fam.hf_weight_map(c))
    toks = _tokens(24, seed=9)
    ours = family.drive(m, toks, [[16], [4]] + [1] * 4)[0]
    ref, _ = reference.forward_routed(
        {"emb": sd["model.embed_tokens.weight"], "layers": layers,
         "norm": sd["model.norm.weight"], "head": sd["lm_head.weight"].T},
        toks, REF_CFG)
    assert _rel(ours, np.asarray(ref)) < TOL
    back = list(family.reference_weights(m, c)["layers"])
    for i in (0, 1):
        for name, want in layers[i].items():
            np.testing.assert_allclose(back[i][name], want, rtol=1e-6,
                                       atol=1e-6, err_msg=f"{i}.{name}")


# ---------------------------------------------------------------------------
# the yardstick's arithmetic, the cell's files, the rehearsals
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("what", ["arithmetic", "files", "traced_rehearsal",
                                  "variants_tool", "chunk_tool"])
def test_the_cell_its_files_and_the_arithmetic_of_its_bytes(
        bench, what, monkeypatch, capsys):
    family, _ = bench
    monkeypatch.syspath_prepend(ROOT)
    for key in ("JAX_PLATFORMS", "FF_PALLAS_INTERPRET"):
        monkeypatch.setenv(key, os.environ.get(key, ""))   # restored after
    with open(os.path.join(ROOT,
                           "benchmark/configs/solar-open2-250b.json")) as f:
        cfg = json.load(f)
    if what == "arithmetic":
        # ISSUE 54's own figures, from the configuration file's sizes
        assert family.cache_position_bytes(cfg) == 4096
        assert family.state_bytes(cfg) == 64 * 128 * 128 * 4
        assert family.conv_tail_bytes(cfg) == 3 * 3 * 8192 * 4
        assert family.layers_of(cfg, "full") == 2
        assert family.layers_of(cfg, "recurrent") == 6
        assert family.layers_of(cfg, "sparse") == 8
        assert family.cache_bytes_per_token(cfg) == 2 * 4096
        assert abs(family.expert_bytes(cfg) - 15.73e6) < 0.04e6
        assert family.pair_flops(cfg) == 6 * 4096 * 1280
        dense = sum(r * c * e for _, r, c, e in family.dense_weights(cfg))
        # 2 x (109.1M + 17.0M) + 6 x (137.7M + 17.0M) + the head's slice
        # 100.7M, int8 with scales and the bf16 conv taps: "1.2 GB outside
        # the experts + head 0.1 GB"
        assert 1.27e9 < dense < 1.31e9
        from flexflow_tpu.kernels.linear_attention import state_step_bytes

        assert family.state_step_bytes(cfg, 7.0) == state_step_bytes(
            7.0, 64, 128, 128)
        # the issue's step: 16 rows of ~6k positions, 13 of 40 experts a
        # layer: 1.3 + 1.6 + 0.8 + 0.8 GB
        need = family.decode_step_must_read(cfg, 13, 16 * 6000 * 2, 16)
        assert abs(need - (dense + 13 * 8 * family.expert_bytes(cfg)
                           + 16 * 6000 * 2 * 4096
                           + 16 * 6 * 2 * (family.state_bytes(cfg)
                                           + family.conv_tail_bytes(cfg)))
                   ) < 1
        assert 4.4e9 < need < 4.9e9
        full = sum(r * c * e for _, r, c, e in family.decode_weights(cfg))
        assert abs(full - dense - 40 * 8 * family.expert_bytes(cfg)) < 1
        assert 6.2e9 < full < 6.5e9             # "6.4 GB of int8 weights"
        return
    from benchmark import run, selfcheck

    if what == "files":
        assert selfcheck.every_entry_resolves_to_its_files()
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            b = json.load(f)
        entry = {w["name"]: w for w in b["workloads"]}[CELL]
        assert (entry["config"], entry["traffic"], entry["chips"]) == (
            "solar-open2-250b", "long-context-reasoning", 1)
        # a `why` of 201 characters refused this PR's first check
        for e in b["configs"] + b["workloads"]:
            assert 1 <= len(e["why"]) <= 200 and e["why"].isprintable(), e
        mine = {m["name"] for m in b["per_layer"]
                if CELL in m.get("workloads", ())}
        assert {"kda_state_hbm_roofline", "decode_kda_hbm_roofline",
                "linear_attn_share", "kv_recurrent_share", "kda_chunk_share",
                "attn_kv_hbm_roofline", "experts_touched",
                "device_idle"} <= mine
        assert not {"decode_hbm_roofline", "decode_cca_hbm_roofline",
                    "cca_tail_step_share"} & mine
        assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                  "vocab_size", "gqa_layers"]
        assert cfg["published"] == {
            "num_hidden_layers": 48, "n_routed_experts": 320,
            "vocab_size": 196608, "gqa_layers": list(range(0, 48, 4))}
        # every width as published
        lin = cfg["linear_attn_config"]
        assert (cfg["hidden_size"], lin["num_heads"], lin["head_dim"],
                lin["short_conv_kernel_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"], cfg["head_dim"],
                cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
                cfg["intermediate_size"]) == (
                    4096, 64, 128, 4, 64, 8, 128, 1280, 8, 10240)
        assert family._held(cfg) == (0, 40, 320)
        a = cfg["assumed"]
        assert (a["max_requests_per_batch"], a["max_sequence_length"],
                a["max_tokens_per_batch"], a["decode_block_steps"],
                a["recurrent_state_dtype"], a["conv_tail_dtype"],
                a["kv_cache_dtype"]) == (
                    16, 16384, 512, 16, "float32", "float32", "bfloat16")
        assert all("as ISSUE 54 states it; not checked against the published"
                   " code" in a[k] for k in ("block", "kda", "kda_init",
                                             "gqa", "experts"))
        return
    if what == "variants_tool":
        sys.path.insert(0, os.path.join(ROOT, "tools"))
        try:
            import check_solar_variants
        finally:
            sys.path.remove(os.path.join(ROOT, "tools"))
        assert check_solar_variants.main(["--rehearse", "--layers", "2"]) == 0
        res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert res["ok"] and res["device"] == "cpu"
        assert res["state_rel_err"] < 1e-5 < res["state_tol"] < res[
            "wrong_bfloat16_state_state"]
        return
    if what == "chunk_tool":
        # tools/time_kda_chunk.py: both forms against the literal recurrence
        # at both decays, tiny and interpreted (no time is taken here)
        sys.path.insert(0, os.path.join(ROOT, "tools"))
        try:
            import time_kda_chunk
        finally:
            sys.path.remove(os.path.join(ROOT, "tools"))
        assert time_kda_chunk.main(["--rehearse", "--tokens", "70"]) == 0
        res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert res["device"] == "cpu" and "kernel" not in res
        for form in ("jnp", "kernel"):
            for decay in ("seeded", "strong"):
                r = res[f"{form}_{decay}_decay"]
                assert r["finite"] and r["state_max_abs"] < 5e-6, (form, r)
        return
    from flexflow_tpu import kernels as ffk
    from flexflow_tpu.kernels import linear_attention as LA
    from flexflow_tpu.kernels import moe as K

    LA.chunk_form_counts.clear()
    ffk.reset_dispatch_stats()      # what the tests before this one traced
    K.reset_dispatch_stats()
    rc = run.main(["--workload", CELL, "--seed", "3000000019", "--seconds",
                   "4", "--trace", "1", "--rehearse"])
    out = capsys.readouterr().out
    last = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and last["correct"] and last["rehearsal"], out[-2000:]
    said = [ln for ln in out.splitlines() if "REHEARSAL" in ln][0]
    assert '"kv_recurrent_share"' in said
    # the rehearsal interprets the kernels: its prefill steps' chunked form
    # is ``kda_chunk`` (the compact batch's rows and the reference check's
    # slot grid), and the program counted their tokens
    assert LA.chunk_form_counts and all(
        form == "kernel" for form, _, _ in LA.chunk_form_counts)
