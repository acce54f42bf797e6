"""Sparse experts on the serving path (OLMoE), at tiny sizes on the CPU.

The routed-expert kernel against a dense einsum on drawn routings; the plain
reference against HuggingFace's ``OlmoeForCausalLM``; the program against the
plain reference through prefill and cached decode, with its routing checked
the way the benchmark checks it on the chip; the op's on-device counters and
their way into the metrics registry; stacked int8 weights; speculation over
an expert verifier. ``tests/test_model_zoo.py`` holds the program to HF
itself, ``tests/test_fleet.py`` the checkpoint store.
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
from flexflow_tpu.models.olmoe import OLMoEConfig, create_olmoe_model
from flexflow_tpu.quant import (QuantizedWeight, dequantize_array,
                                quantize_array, quantize_params,
                                quantized_nbytes)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def bench():
    """The benchmark's family and reference for OLMoE, loaded as run.py
    loads them."""
    sys.path.insert(0, ROOT)
    try:
        from benchmark.run import load_module

        yield (load_module("families", "olmoe"),
               load_module("reference", "olmoe"))
    finally:
        sys.path.remove(ROOT)


# ---------------------------------------------------------------------------
# the kernel: Pallas (interpreted) and the ragged fallback against a dense
# einsum over all experts
# ---------------------------------------------------------------------------

def _dense_experts(x, idx, w, valid, gate, up, down):
    E = gate.shape[0]
    cw = jnp.einsum("tke,tk->te", jax.nn.one_hot(idx, E), w)
    a = jax.nn.silu(jnp.einsum("th,ehi->tei", x, gate)) \
        * jnp.einsum("th,ehi->tei", x, up)
    y = jnp.einsum("tei,eih,te->th", a, down, cw)
    return jnp.where(valid[:, None], y, 0.0)


def _drawn(case, T=24, E=8, k=2, H=64, inter=32, seed=0):
    rng = np.random.default_rng(seed)
    idx = np.stack([rng.choice(E, size=k, replace=False) for _ in range(T)])
    valid = np.ones((T,), bool)
    if case == "empty_expert":
        idx = np.where(idx == 3, 4 + (idx.sum(-1, keepdims=True) % 2) * 3,
                       idx)
        idx[:, 1] = np.where(idx[:, 1] == idx[:, 0], 0, idx[:, 1])
    elif case == "one_expert_takes_all":
        idx[:, 0] = 5
        idx[:, 1] = np.where(idx[:, 1] == 5, 1, idx[:, 1])
    elif case == "inactive_and_padded":
        valid[rng.choice(T, size=T // 2, replace=False)] = False
    elif case == "nothing_real":
        valid[:] = False
    w = rng.uniform(0.05, 0.5, size=(T, k)).astype(np.float32)
    f = lambda *s: jnp.asarray(rng.normal(0, 0.2, size=s), jnp.float32)
    return (f(T, H), jnp.asarray(idx, jnp.int32), jnp.asarray(w),
            jnp.asarray(valid), f(E, H, inter), f(E, H, inter),
            f(E, inter, H))


CASES = ("uniform", "empty_expert", "one_expert_takes_all",
         "inactive_and_padded", "nothing_real")


# the Pallas call with its rows resident in VMEM (gathered and added in the
# kernel), the same call with its tiles staged through HBM, and the ragged
# fallback
FORMS = {"pallas_interpret": dict(pallas=True, resident=True),
         "pallas_staged": dict(pallas=True, resident=False),
         "ragged_fallback": dict(pallas=False)}


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("case", CASES)
def test_grouped_kernel_matches_dense_einsum(case, form):
    x, idx, w, valid, gate, up, down = _drawn(case)
    # a row that is not a token must not reach an expert even if it holds
    # something that would blow up
    x = jnp.where(valid[:, None], x, 1e30)
    y, sizes = K.moe_experts(x, idx, w, valid, gate, up, down,
                             interpret=True, **FORMS[form])
    want = _dense_experts(jnp.where(valid[:, None], x, 0.0), idx, w, valid,
                          gate, up, down)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    real = np.asarray(idx)[np.asarray(valid)]
    np.testing.assert_array_equal(
        np.asarray(sizes), np.bincount(real.ravel(), minlength=8))
    assert np.isfinite(np.asarray(y)).all()


@pytest.mark.parametrize("resident", [True, False],
                         ids=["resident", "staged"])
def test_grouped_kernel_reads_int8_experts_with_their_scales(resident):
    x, idx, w, valid, gate, up, down = _drawn("uniform", H=64, inter=64)
    q = [quantize_array(a, "int8") for a in (gate, up, down)]
    assert q[0].scale.shape == (8, 64) and q[0].q.dtype == jnp.int8
    y, _ = K.moe_experts(x, idx, w, valid, *q, pallas=True, interpret=True,
                         resident=resident)
    want = _dense_experts(x, idx, w, valid,
                          *[dequantize_array(a, jnp.float32) for a in q])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("first", [0, 2, 6])
def test_resident_kernel_over_a_held_range_of_int8_experts(first):
    """A chip's share of a wider router through the form that gathers and
    adds in the kernel: the pairs routed elsewhere reach no tile and add
    nothing, a row that is no token may hold anything, and the two Pallas
    forms agree to the last bit of the float32 sums' rounding."""
    x, idx, w, valid, gate, up, down = _drawn("inactive_and_padded", H=64,
                                              inter=64, k=3)
    x = jnp.where(valid[:, None], x, 1e30)
    q = [quantize_array(a[first:first + 2], "int8")
         for a in (gate, up, down)]
    run = lambda resident: K.moe_experts(
        x, idx, w, valid, *q, pallas=True, interpret=True,
        held=(first, 8), resident=resident)
    (y, sizes), (staged, _) = run(True), run(False)
    here = (idx >= first) & (idx < first + 2) & valid[:, None]
    deq = [jnp.zeros_like(a).at[first:first + 2].set(
        dequantize_array(b, jnp.float32)) for a, b in zip((gate, up, down), q)]
    want = _dense_experts(jnp.where(valid[:, None], x, 0.0), idx,
                          jnp.where(here, w, 0.0), valid, *deq)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(staged),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(sizes),
        [int(((idx == first + e) & here).sum()) for e in range(2)])
    assert np.isfinite(np.asarray(y)).all()


# (T, H, expert width) of a decode and a prefill step of the benchmark's four
# expert configurations (32 or 16 slots, a block-diffusion pass of 32 rows x
# 8, a token budget of 512), int8 experts, bfloat16 rows
STEP_SHAPES = {
    "olmoe.decode": (32, 2048, 1024, True),
    "olmoe.prefill": (512, 2048, 1024, True),
    "sdar.decode": (256, 2048, 768, True),
    "sdar.prefill": (512, 2048, 768, True),
    "mistral.decode": (16, 4096, 2048, True),
    "mistral.prefill": (512, 4096, 2048, True),
    "k-exaone.decode": (32, 6144, 2048, True),
    "k-exaone.prefill": (512, 6144, 2048, False),
}


@pytest.mark.parametrize("step", STEP_SHAPES)
def test_rows_stay_in_vmem_where_the_step_fits(step):
    T, H, inter, fits = STEP_SHAPES[step]
    assert K.rows_fit(T, H, inter, 1, 2) is fits
    # the rule is of the shapes alone: more rows or wider weights never fit
    # where fewer did not
    if not fits:
        assert not K.rows_fit(2 * T, H, inter, 1, 2)
        assert not K.rows_fit(T, H, inter, 2, 2)


@pytest.mark.parametrize("step", ["olmoe.decode", "sdar.decode",
                                  "k-exaone.prefill"])
def test_no_tiled_buffer_around_the_resident_kernel(step):
    """At a decode step of the two models that touch nearly every expert,
    nothing of [M, H] (the tiles' rows, M = tiles x rows a tile) exists
    outside the Pallas call: no gather of M rows before it, no buffer of
    results after it. The step that does not fit keeps both."""
    T, H, inter, fits = STEP_SHAPES[step]
    E, k = {"olmoe.decode": (64, 8), "sdar.decode": (128, 8),
            "k-exaone.prefill": (16, 8)}[step]
    held = (0, 128) if E == 16 else None
    sds = jax.ShapeDtypeStruct
    q = lambda *s: QuantizedWeight("int8", sds(s, jnp.int8),
                                   sds((s[0], s[2]), jnp.float32), s[1],
                                   "bfloat16")
    jaxpr = jax.make_jaxpr(lambda *a: K.moe_experts(*a, pallas=True,
                                                    held=held))(
        sds((T, H), jnp.bfloat16), sds((T, k), jnp.int32),
        sds((T, k), jnp.float32), sds((T,), jnp.bool_),
        q(E, H, inter), q(E, H, inter), q(E, inter, H))
    pairs = T * k if held is None else T * k * E // 128
    tm = K.pick_tile(pairs, E)
    M = (T * k + min(E, T * k) * (tm - 1)) // tm * tm

    def shapes(jp):
        for eqn in jp.eqns:
            yield from (v.aval.shape for v in eqn.outvars)
            if eqn.primitive.name != "pallas_call":   # the kernel's own body
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from shapes(sub)

    seen = set(shapes(jaxpr.jaxpr))
    assert ((M, H) in seen) is (not fits), sorted(seen)
    assert (T, H) in seen


def test_a_step_over_the_token_budget_runs_in_passes():
    """More real tokens than the scheduler's budget (tree verification can
    fill every row): the op takes them ``cap`` at a time, none dropped."""
    import functools

    from flexflow_tpu.ops.moe import _in_chunks

    x, idx, w, valid, gate, up, down = _drawn("inactive_and_padded", T=40)
    run = functools.partial(K.moe_experts, gate=gate, up=up, down=down,
                            pallas=False)
    want, sizes = run(x, idx, w, valid)
    for cap in (8, 16, 64):                 # 20 real tokens: 3, 2, 1 passes
        y, s = jax.jit(lambda *a, cap=cap: _in_chunks(*a, cap, run, 8))(
            x, idx, w, valid)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(s), np.asarray(sizes))


def test_tile_plan_puts_every_pair_in_its_experts_tile():
    _, idx, _, valid, *_ = _drawn("inactive_and_padded", T=40, E=8, k=2)
    tm = K.pick_tile(40 * 2, 8)
    row_token, pair_row, tile_expert, n_active, sizes = map(
        np.asarray, K.plan_routes(idx, valid, 8, tm))
    M = row_token.shape[0]
    assert n_active[0] == sum(-(-s // tm) for s in sizes)
    idx, valid = np.asarray(idx), np.asarray(valid)
    for t in range(40):
        for j in range(2):
            r = pair_row[t, j]
            if not valid[t]:
                assert r == M                       # routes nowhere
                continue
            assert r < n_active[0] * tm
            assert tile_expert[r // tm] == idx[t, j] and row_token[r] == t
    # the resident form's plan of the same tiles: a tile's pairs are
    # consecutive places of the sorted order, and every real pair is in one
    order, start, count, te, na, sz = map(
        np.asarray, K.plan_tiles(jnp.asarray(idx), jnp.asarray(valid), 8, tm))
    assert (te == tile_expert).all() and na == n_active and (sz == sizes).all()
    held = []
    for i in range(len(te)):
        assert 0 <= count[i] <= tm and (count[i] > 0) == (i < na[0])
        for p in order[start[i]:start[i] + count[i]]:
            assert idx[p // 2, p % 2] == te[i] and valid[p // 2]
            assert pair_row[p // 2, p % 2] == i * tm + len(held) - sum(
                count[:i])
            held.append(p)
    assert sorted(held) == [t * 2 + j for t in range(40) if valid[t]
                            for j in range(2)]
    assert K.pick_tile(256, 64) == 16 and K.pick_tile(4096, 64) == 64
    assert K.pick_tile(1 << 20, 64) == 128


def test_tile_plan_computes_each_destination_once():
    """A prefill chunk's plan (512 tokens x 8 at the cell's widths): a
    pair's destination row is two table lookups, and fused into both of the
    scatters that use it they cost the TPU compiler's fusion pass seconds a
    layer (most of a 16-layer prefill program's compile). They stay behind
    a barrier, computed once."""
    sds = jax.ShapeDtypeStruct
    lowered = jax.jit(lambda i, v: K.plan_routes(i, v, 64, 64)).lower(
        sds((512, 8), jnp.int32), sds((512,), jnp.bool_))
    assert "optimization_barrier" in lowered.as_text()


# ---------------------------------------------------------------------------
# stacked int8 weights
# ---------------------------------------------------------------------------

def test_int8_stack_round_trip_and_bytes():
    w = jnp.asarray(np.random.default_rng(1).normal(0, 0.1, (4, 64, 96)),
                    jnp.float32)
    q = quantize_array(w, "int8")
    assert isinstance(q, QuantizedWeight) and q.shape == (4, 64, 96)
    assert q.q.shape == (4, 64, 96) and q.scale.shape == (4, 96)
    back = dequantize_array(q, jnp.float32)
    # per (expert, column): half a step of that column's own scale
    assert (np.abs(np.asarray(back - w))
            <= 0.5 * np.asarray(q.scale)[:, None, :] + 1e-7).all()
    each = jnp.stack([dequantize_array(quantize_array(w[e], "int8"),
                                       jnp.float32) for e in range(4)])
    np.testing.assert_array_equal(np.asarray(back), np.asarray(each))
    params = quantize_params({"experts": {"gate": w, "bias": w[0, 0]},
                              "small": {"gate": w[:, :32, :32]}}, "int8")
    assert isinstance(params["experts"]["gate"], QuantizedWeight)
    assert not isinstance(params["small"]["gate"], QuantizedWeight)
    assert quantized_nbytes({"e": {"gate": q}}) == 4 * 64 * 96 + 4 * 96 * 4
    with pytest.raises(NotImplementedError, match="int8"):
        quantize_array(w, "int4")


# ---------------------------------------------------------------------------
# the model: reference == HF, program == reference
# ---------------------------------------------------------------------------

TINY = dict(vocab_size=256, hidden_size=64, intermediate_size=32,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, num_experts=8, num_experts_per_tok=2,
            norm_topk_prob=False, max_position_embeddings=128,
            rms_norm_eps=1e-5, rope_theta=10000.0)


def _build(cfg, mode=InferenceMode.INC_DECODING_MODE, **ffkw):
    kw = dict(max_requests_per_batch=2, max_sequence_length=64,
              max_tokens_per_batch=16, kv_cache_dtype="float32", seed=3)
    kw.update(ffkw)
    m = ff.FFModel(ff.FFConfig(**kw))
    create_olmoe_model(m, OLMoEConfig.from_hf_config(cfg), mode=mode)
    m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    return m


def test_plain_reference_matches_hf(bench):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    _, reference = bench
    torch.manual_seed(0)
    hf = transformers.OlmoeForCausalLM(transformers.OlmoeConfig(
        **TINY, tie_word_embeddings=False)).eval()
    sd = {k: v.detach().numpy() for k, v in hf.state_dict().items()}
    f = lambda a: jnp.asarray(a, jnp.float32)
    layers = []
    for i in range(TINY["num_hidden_layers"]):
        p = f"model.layers.{i}."
        ex = lambda proj: jnp.stack(
            [f(sd[f"{p}mlp.experts.{e}.{proj}.weight"].T)
             for e in range(TINY["num_experts"])])
        layers.append({
            "ln1": f(sd[p + "input_layernorm.weight"]),
            "ln2": f(sd[p + "post_attention_layernorm.weight"]),
            **{w: f(sd[f"{p}self_attn.{w[1]}_proj.weight"].T)
               for w in ("wq", "wk", "wv", "wo")},
            "q_norm": f(sd[p + "self_attn.q_norm.weight"]),
            "k_norm": f(sd[p + "self_attn.k_norm.weight"]),
            "router": f(sd[p + "mlp.gate.weight"].T),
            "gate": ex("gate_proj"), "up": ex("up_proj"),
            "down": ex("down_proj")})
    weights = {"emb": f(sd["model.embed_tokens.weight"]), "layers": layers,
               "norm": f(sd["model.norm.weight"]),
               "head": f(sd["lm_head.weight"].T)}
    toks = np.random.default_rng(0).integers(1, 256, size=12)
    with torch.no_grad():
        want = hf(torch.tensor([toks.tolist()])).logits[0].numpy()
    got, probs = reference.forward_routed(weights, jnp.asarray(toks), TINY)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    assert len(probs) == 2 and probs[0].shape == (12, 8)
    # told the routes it would take itself, it gives the same answer
    own = [np.asarray(jax.lax.top_k(p, 2)[1]) for p in probs]
    again = reference.forward_routed(weights, jnp.asarray(toks), TINY,
                                     routes=own)[0]
    np.testing.assert_allclose(np.asarray(again), np.asarray(got),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("quant", [None, "int8"])
def test_program_matches_plain_reference_through_the_cache(bench, quant):
    """Prefill one chunk, then decode through the cache, float32 compute;
    the program's routes validated against the reference's probabilities,
    the logits compared on those routes (families/olmoe.reference_check)."""
    family, reference = bench
    cfg = dict(TINY, intermediate_size=64)      # int8 needs 64 x 64
    m = _build(cfg, quantization_type=quant)
    if quant:
        assert isinstance(m.params["layers.0.mlp.experts"]["gate"],
                          QuantizedWeight)
    toks = np.random.default_rng(5).integers(1, 256, size=13)
    ours, routes = family.program_logits_and_routes(m, toks, 9)
    assert len(routes) == 2 and routes[0].shape == (13, 2)
    ref, probs = reference.forward_routed(
        family._reference_weights(m, 2), jnp.asarray(toks), cfg,
        routes=routes)
    checked = family.check_routes(routes, probs, family.ROUTE_MARGIN)
    assert checked["routes_ok"] and checked["doubled_experts"] == 0
    assert checked["route_flips"] == 0          # float32: no near-tie flips
    np.testing.assert_allclose(ours, np.asarray(ref), rtol=2e-4, atol=2e-4)
    # the check has teeth: a dropped expert, a doubled one, a wrong router
    wrong = [r.copy() for r in routes]
    wrong[0][:, 1] = wrong[0][:, 0]
    assert not family.check_routes(wrong, probs, 0.03)["routes_ok"]
    far = [np.argsort(np.asarray(p), axis=-1)[:, :2] for p in probs]
    assert not family.check_routes(far, probs, 0.03)["routes_ok"]
    renorm = reference.forward_routed(
        family._reference_weights(m, 2), jnp.asarray(toks),
        dict(cfg, norm_topk_prob=True), routes=routes)[0]
    assert not family.C.compare_logits(ours, renorm, 0.03)["ok"]


# ---------------------------------------------------------------------------
# counters: on the device, read at a snapshot, absent with telemetry off
# ---------------------------------------------------------------------------

def _serve(model, prompts, new_tokens, tel=None):
    from flexflow_tpu.serve.request_manager import RequestManager

    rm = RequestManager()
    rm.telemetry = tel
    for p in prompts:
        rm.register_new_request(p, max_new_tokens=new_tokens)
    return rm.generate_incr_decoding(model)


def test_counters_count_real_tokens_times_k_and_only_with_telemetry():
    from flexflow_tpu.ops.moe import MOE_FIELDS
    from flexflow_tpu.telemetry import ServingTelemetry

    prompts = [[3, 17, 42, 99, 7, 21, 5], [9, 8, 7]]
    off = _build(TINY)
    assert set(off.op_state) == {"kv_cache"}
    plain = _serve(off, prompts, 6)

    on = _build(TINY, telemetry=True)
    assert on.op_state["moe_counters"].shape == (2, 8 + 5 * 3)  # one leaf
    tel = ServingTelemetry()
    got = _serve(on, prompts, 6, tel=tel)
    assert [r.output_tokens for r in got] == [r.output_tokens for r in plain]
    snap = tel.registry.snapshot()
    L, k = 2, 2
    pre, dec = (snap[f'ffsv_moe_tokens_total{{phase="{p}"}}']["value"]
                for p in ("prefill", "decode"))
    # every prompt token but the last is prefilled once; the last one and
    # every generated token but the last are decode steps; in every layer,
    # and never a padded position or an idle slot
    assert pre == L * sum(len(p) - 1 for p in prompts)
    assert dec == L * 2 * 6
    for ph, n in (("prefill", pre), ("decode", dec)):
        assert snap[f'ffsv_moe_routed_pairs_total{{phase="{ph}"}}'][
            "value"] == n * k
    per_expert = [snap[f'ffsv_moe_expert_pairs_total{{expert="{e}"}}'][
        "value"] for e in range(8)]
    assert sum(per_expert) == (pre + dec) * k
    touched = snap['ffsv_moe_experts_touched{phase="decode"}']
    assert touched["type"] == "summary" and touched["count"] == L * 6
    assert 2 <= touched["sum"] / touched["count"] <= 4   # 2 rows x top-2
    # the CPU's ragged fallback stages its tiles: no call kept its rows in
    # the kernel, and the field is there to say so
    raw = np.asarray(on.op_state["moe_counters"])[:, 8:].reshape(2, 5, 3)
    assert MOE_FIELDS.index("resident") == 4 and not raw[:, 4].any()
    assert raw[:, 0, 0].sum() == touched["count"]
    for ph in ("prefill", "decode"):
        assert snap[f'ffsv_moe_resident_calls_total{{phase="{ph}"}}'][
            "value"] == 0
    # a second snapshot with no step in between adds nothing
    assert tel.registry.snapshot() == snap
    text = tel.registry.to_prometheus()
    assert 'ffsv_moe_experts_touched_count{phase="decode"} 12' in text
    assert text.count("# TYPE ffsv_moe_expert_pairs_total counter") == 1


# ---------------------------------------------------------------------------
# the expert op under tree-verify metas: SpecInfer == incremental decoding
# ---------------------------------------------------------------------------

def test_specinfer_over_an_expert_verifier_is_token_identical():
    from flexflow_tpu.serve.request_manager import RequestManager

    prompts = [[3, 17, 42, 99, 7], [11, 12, 13, 14, 15, 16, 17, 18]]
    want = [r.output_tokens for r in
            _serve(_build(TINY, max_sequence_length=96), prompts, 12)]

    llm = _build(TINY, InferenceMode.TREE_VERIFY_MODE, max_sequence_length=96)
    # the verifier's own first layer as its draft
    ssm = _build(dict(TINY, num_hidden_layers=1),
                 InferenceMode.BEAM_SEARCH_MODE, max_sequence_length=96)
    for name, ws in ssm.params.items():
        for w in ws:
            ssm.set_parameter_by_key(
                (name, w), llm.get_parameter_by_key((name, w)))
    rm = RequestManager()
    for p in prompts:
        rm.register_new_request(p, max_new_tokens=12)
    got = rm.generate_spec_infer(llm, [ssm], spec_depth=3)
    assert [r.output_tokens[:12] for r in got] == want


# ---------------------------------------------------------------------------
# LLM.from_checkpoint on a tiny saved checkpoint: the normal path end to end
# ---------------------------------------------------------------------------

def test_olmoe_serves_from_a_saved_checkpoint(tmp_path):
    from flexflow_tpu.models.checkpoint_store import save_tiny_checkpoint
    from flexflow_tpu.serve.api import LLM

    save_tiny_checkpoint("olmoe", str(tmp_path), seed=4)
    llm = LLM.from_checkpoint(str(tmp_path))
    llm.compile(max_requests_per_batch=2, max_seq_length=64,
                max_tokens_per_batch=16)
    out = llm.generate([[5, 6, 7, 8]], max_new_tokens=5)
    assert len(out[0].output_tokens) == 5
