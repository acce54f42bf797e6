"""LongCat-Flash on the serving path, at the tiny sizes of its configuration
file's ``rehearsal`` block on the CPU, float32, seeded weights: two latent
attention sublayers a layer (four latent caches in two layers), the routed
experts on a shortcut beside them, a softmax router whose outputs past the
experts cost nothing.

The program, through chunked prefill and cached decode, against the plain
reference (logits and routes); the expert shares against the whole layer
with the zero experts counted once; a token routed to zero experts only;
the folded ``mla_scale_*`` factors against the published form; the selection
bias; the HF weight map; the cell's rehearsal and traffic; the refusals;
and the counter that only this kind of model has.
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
from flexflow_tpu.kernels import moe as K
from flexflow_tpu.models import FAMILIES
from flexflow_tpu.models.longcat_flash import (LongcatFlashConfig,
                                               create_longcat_flash_model)
from flexflow_tpu.ops.base import OpContext
from flexflow_tpu.ops.inc_attention import LATENT_STACK
from flexflow_tpu.ops.moe import (MOE_COUNTERS, MOE_FIELDS, MOE_PHASES,
                                  MoeExperts, counter_fields)
from flexflow_tpu.serve.batch_config import make_batch_meta

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "longcat-flash-omni.context-reasoning"


@pytest.fixture(scope="module")
def bench():
    """The benchmark's family and reference for LongCat-Flash, loaded as
    run.py loads them, and the configuration file at its rehearsal sizes."""
    sys.path.insert(0, ROOT)
    try:
        from benchmark.run import apply_rehearsal, load_module

        with open(os.path.join(
                ROOT, "benchmark/configs/longcat-flash-omni.json")) as f:
            cfg = json.load(f)
        apply_rehearsal(cfg, {"cycle": []})
        yield (load_module("families", "longcat_flash"),
               load_module("reference", "longcat_flash"), cfg)
    finally:
        sys.path.remove(ROOT)


def _build(family, cfg, mode=InferenceMode.INC_DECODING_MODE, model_cfg=None,
           **ffkw):
    kw = dict(max_requests_per_batch=2, max_sequence_length=512,
              max_tokens_per_batch=64, seed=3, compute_dtype="float32",
              kv_cache_dtype="float32", num_devices=1)
    kw.update(ffkw)
    m = ff.FFModel(ff.FFConfig(**kw))
    c = model_cfg or family._model_cfg(cfg)
    create_longcat_flash_model(m, c, mode=mode,
                               data_type=ff.DataType.DT_FLOAT)
    m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    return m, c


def _held(family, cfg):
    first, count, _ = family._held(cfg)
    return first, count


# ---------------------------------------------------------------------------
# (i) the program against the plain reference, through the latent caches
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("how", ["float32", "int8", "interpreted_kernels"])
def test_program_matches_plain_reference_through_the_latent_caches(
        bench, how, monkeypatch):
    """Four prefill chunks of 16, then 20 tokens decoded one at a time,
    through the four latent caches of two layers: the routes the program
    took are the reference's own, and the logits the reference's on those
    routes. The reference is the published form (expanded keys and values,
    adjacent rotary pairs, the ``mla_scale_*`` multiplications, a loop over
    the chosen, the identity past the experts); the program the absorbed
    form with both factors folded."""
    import flexflow_tpu.kernels as ffk

    family, reference, cfg = bench
    if how == "interpreted_kernels":
        monkeypatch.setenv("FF_PALLAS_INTERPRET", "1")
        ffk.reset_dispatch_stats()
        K.reset_dispatch_stats()
    m, c = _build(family, cfg,
                  quantization_type="int8" if how == "int8" else None)
    width = 256 if how == "interpreted_kernels" else 160
    assert m.op_state[LATENT_STACK]["c"].shape == (4, 2, 1, 512, width)
    assert [ly.attrs["cache_layer_idx"] for ly in m.layers
            if ly.op_type == OpType.INC_MULTIHEAD_LATENT_ATTENTION] == [
                0, 1, 2, 3]
    toks = np.random.default_rng(5).integers(1, cfg["vocab_size"], size=84)
    ours, routes = family.program_logits_and_routes(
        m, toks, [16] * 4 + [1] * 20)
    assert len(routes) == 2 and routes[0].shape == (84, cfg["moe_topk"])
    rcfg = family._reference_cfg(cfg)
    ref, scores = reference.forward_routed(
        family._reference_weights(m, c), toks, rcfg, routes=routes,
        held=_held(family, cfg))
    checked = family.check_routes(routes, [np.asarray(s) for s in scores],
                                  family.ROUTE_MARGIN)
    assert checked["routes_ok"] and checked["route_flips"] == 0
    np.testing.assert_allclose(ours, np.asarray(ref), rtol=3e-4, atol=3e-4)
    # some picks are experts held here, some held elsewhere, some no expert
    first, count = _held(family, cfg)
    picks = np.concatenate(routes)
    E = rcfg["n_routed_experts"]
    assert ((picks >= first) & (picks < first + count)).any()
    assert ((picks >= first + count) & (picks < E)).any()
    assert (picks >= E).any()
    if how == "interpreted_kernels":
        assert ffk.fast_path_count > 0 and not ffk.fallback_counts
        assert K.fast_path_count > 0 and not K.fallback_counts
        # the decode step's few query rows take the block form of the
        # latent kernel, the append fused; at this size a prefill chunk's
        # rows fit beside the stream too (128 KB of scores a block of 512
        # positions), where the cell's 4096 rows a group of 32 heads take
        # the partition loop; one trace a latent cache each
        from flexflow_tpu.kernels.attention import latent_form
        assert ffk.latent_form_counts == {("block", "append"): 4,
                                          ("block", "grid"): 4}
        assert latent_form(64, 8192) == "block"
        assert latent_form(64 // 2 * 128, 8192) == "partition"


# ---------------------------------------------------------------------------
# (ii) the shares add up, the zero experts counted once
# ---------------------------------------------------------------------------

def _expert_layer(rng, H, I, E, Z, k, T):
    f = lambda *s: jnp.asarray(rng.normal(0, 0.3, size=s), jnp.float32)
    lw = {"router": f(H, E + Z) * 3, "bias": f(E + Z) * 0.01,
          "gate": f(E, H, I), "up": f(E, H, I), "down": f(E, I, H)}
    cfg = {"n_routed_experts": E, "moe_topk": k,
           "routed_scaling_factor": 6.0}
    return lw, cfg, f(T, H)


def _run_op(x, idx, w, params, attrs, counters=None, num_tokens=None):
    """``MoeExperts.forward`` on one prefill step of one slot: x [T, H]."""
    T = x.shape[0]
    n = T if num_tokens is None else num_tokens
    meta = make_batch_meta(
        1, T, tokens=np.zeros((1, T), np.int32),
        positions=np.arange(T, dtype=np.int32)[None],
        start_pos=np.zeros(1, np.int32), num_tokens=np.array([n], np.int32),
        active=np.array([True]))
    ctx = OpContext(training=False, rng=None, compute_dtype=jnp.float32,
                    batch_config=meta, config=ff.FFConfig(
                        max_requests_per_batch=1, max_tokens_per_batch=64,
                        num_devices=1))
    if counters is not None:
        ctx.state_in[MOE_COUNTERS] = counters
    (y,) = MoeExperts.forward(attrs, params, [x[None], idx[None], w[None]],
                              ctx)
    return y[0], ctx.state_out.get(MOE_COUNTERS)


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_shares_add_up_with_the_zero_experts_counted_once(bench, side):
    """4 ranks x 2 held experts of 8, beside 4 zero experts: every rank's
    output holds its own experts' part AND the zero experts' term (each chip
    adds it for its own tokens); the four, less three copies of that term,
    are the uncut reference's layer."""
    _, reference, _ = bench
    H, I, E, Z, k, T = 32, 24, 8, 4, 4, 10
    lw, cfg, m = _expert_layer(np.random.default_rng(2), H, I, E, Z, k, T)
    mm = reference._matmul(None)
    whole, scores = reference._routed(mm, m, lw, cfg, None, (0, E))
    zero_only, _ = reference._routed(mm, m, lw, cfg, None, (0, E),
                                     without=("routed",))
    chosen = jax.lax.top_k(scores, k)[1]
    assert (np.asarray(chosen) >= E).any() and (np.asarray(chosen) < E).any()
    s = jax.nn.softmax(m @ lw["router"], axis=-1)
    w = 6.0 * jnp.take_along_axis(s, chosen, -1)
    total = 0.0
    for first in range(0, E, 2):
        part = {n: lw[n][first:first + 2] for n in ("gate", "up", "down")}
        if side == "reference":
            y, sc = reference._routed(mm, m, {**lw, **part}, cfg, None,
                                      (first, 2))
            np.testing.assert_array_equal(np.asarray(sc), np.asarray(scores))
        else:
            y, _ = _run_op(m, chosen, w, part, dict(
                num_experts=2, expert_width=I, router_width=E + Z,
                first_expert=first, zero_experts=(E, Z)))
        total = total + (y - zero_only)
    np.testing.assert_allclose(np.asarray(total + zero_only),
                               np.asarray(whole), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# (iii) a token whose picks are all zero experts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("held", [(0, 8), (2, 2)])
def test_a_token_of_zero_picks_gets_x_times_its_weights_and_no_row(held):
    """Token 0 picks zero experts only, token 1 experts only, token 2 both,
    token 3 is padding: the first gets exactly ``x * sum w``, is no row of
    the kernel and moves ``zero`` and never ``routed``; padding moves
    nothing."""
    H, I, E, Z, k = 32, 24, 8, 4, 3
    first, count = held
    rng = np.random.default_rng(3)
    f = lambda *s: jnp.asarray(rng.normal(0, 0.3, size=s), jnp.float32)
    params = {"gate": f(count, H, I), "up": f(count, H, I),
              "down": f(count, I, H)}
    x = f(4, H)
    idx = jnp.asarray([[8, 11, 9], [2, 3, 0], [3, 10, 1], [8, 2, 9]],
                      jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 0.4, size=(4, k)), jnp.float32)
    attrs = dict(num_experts=count, expert_width=I, router_width=E + Z,
                 first_expert=first, zero_experts=(E, Z), counter_row=0)
    fields = counter_fields([attrs])
    assert fields == MOE_FIELDS + ("zero",)
    n = len(MOE_PHASES)
    counters = jnp.zeros((1, count + len(fields) * n), jnp.uint32)
    y, after = _run_op(x, idx, w, params, attrs, counters, num_tokens=3)
    np.testing.assert_array_equal(np.asarray(y[0]),
                                  np.asarray(x[0] * jnp.sum(w[0])))
    assert not np.asarray(y[3]).any()
    got = dict(zip(fields, np.asarray(after)[0, count:].reshape(-1, n)[
        :, MOE_PHASES.index("prefill")]))
    here = lambda e: first <= e < first + count
    assert got["tokens"] == 3 and got["zero"] == 4
    assert got["routed"] == sum(here(e) for e in (2, 3, 0, 3, 1))
    assert np.asarray(after)[0, :count].sum() == got["routed"]
    # token 2: its expert picks held here, plus x times its zero pick
    plain, _ = _run_op(x, idx, w, params,
                       {k_: v for k_, v in attrs.items()
                        if k_ not in ("zero_experts", "counter_row")},
                       num_tokens=3)
    np.testing.assert_allclose(
        np.asarray(y[2]), np.asarray(plain[2] + x[2] * w[2, 1]), rtol=1e-5,
        atol=1e-6)


# ---------------------------------------------------------------------------
# (iv) the folded mla_scale_* form is the published one
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("flags", [(True, True), (True, False),
                                   (False, True), (False, False)])
def test_the_folded_mla_scales_are_the_published_form(bench, flags):
    """The program folds ``mla_scale_q_lora`` into the softmax scale and
    ``mla_scale_kv_lora`` into the latent norm's weight; the reference
    multiplies where the published code does. Equal for every setting of
    the two booleans, and a reference told the other setting of either is
    another model."""
    family, reference, cfg = bench
    q_on, kv_on = flags
    cfg = dict(cfg, mla_scale_q_lora=q_on, mla_scale_kv_lora=kv_on)
    m, c = _build(family, cfg)
    H = cfg["hidden_size"]
    assert c.q_scale == (np.sqrt(H / cfg["q_lora_rank"]) if q_on else 1.0)
    assert c.latent_scale == (np.sqrt(H / cfg["kv_lora_rank"]) if kv_on
                              else 1.0)
    a = next(ly for ly in m.layers
             if ly.op_type == OpType.INC_MULTIHEAD_LATENT_ATTENTION)
    dims = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    assert a.attrs["softmax_scale"] == pytest.approx(
        dims ** -0.5 * c.q_scale)
    # a seeded latent norm starts at 1 in the folded form: a published
    # weight of 1 / latent_scale, which is what the reference is handed
    np.testing.assert_allclose(np.asarray(m.params[a.name]["kv_norm"]), 1.0)
    handed = list(family._reference_weights(m, c)["layers"])[0]["attn"][0]
    np.testing.assert_allclose(handed["kv_norm"], 1.0 / c.latent_scale,
                               rtol=1e-6)
    toks = np.random.default_rng(7).integers(1, cfg["vocab_size"], size=40)
    ours, routes = family.program_logits_and_routes(m, toks,
                                                    [16] * 2 + [1] * 8)
    got = lambda cfg_: np.asarray(reference.forward_routed(
        family._reference_weights(m, c), toks, family._reference_cfg(cfg_),
        routes=routes, held=_held(family, cfg))[0])
    np.testing.assert_allclose(ours, got(cfg), rtol=3e-4, atol=3e-4)
    for key in ("mla_scale_q_lora", "mla_scale_kv_lora"):
        other = dict(cfg, **{key: not cfg[key]})
        assert not family.C.compare_logits(ours, got(other), 0.03)["ok"], key


# ---------------------------------------------------------------------------
# (v) the bias moves a pick and never a weight
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bias", ["seeded", "zeroed"])
def test_the_bias_moves_a_pick_and_never_a_weight(bench, bias):
    """The seeded ``e_score_correction_bias`` (a quarter of a mean softmax
    score) changes some of the program's picks and not most; the weight of a
    pick is its softmax score times ``routed_scaling_factor`` whatever the
    bias says, not renormalised."""
    family, reference, cfg = bench
    m, c = _build(family, cfg)
    names = [ly.name for ly in m.layers
             if ly.name.endswith("e_score_correction_bias")]
    assert len(names) == cfg["num_layers"]
    seeded = np.asarray(m.params[names[0]]["weight"])
    assert seeded.shape == (c.router_width,) and 0.5 < seeded.std() / (
        0.25 / c.router_width) < 2
    if bias == "zeroed":
        for n in names:
            m.params[n]["weight"] = jnp.zeros_like(m.params[n]["weight"])
    toks = np.random.default_rng(11).integers(1, cfg["vocab_size"], size=64)
    _, routes = family.program_logits_and_routes(m, toks, [16] * 4)
    weights = family._reference_weights(m, c)
    layer0 = list(weights["layers"])[0]
    # the first layer's router sees the same input whatever the bias: its
    # unbiased choice and its scores, from the reference
    mm = reference._matmul(None)
    rcfg = family._reference_cfg(cfg)
    emb = jnp.asarray(weights["emb"])[jnp.asarray(toks)]
    a0 = layer0["attn"][0]
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(len(toks))
        h1 = emb + reference._attention(
            mm, reference._rms(emb, a0["ln"], c.rms_norm_eps), a0, rcfg, pos,
            pos[:, None] >= pos[None, :], ())
        x = reference._rms(h1, layer0["ffn"][0]["ln"], c.rms_norm_eps)
        s = np.asarray(jax.nn.softmax(mm(x, layer0["router"]), axis=-1))
    k = cfg["moe_topk"]
    unbiased = np.sort(np.argsort(-s, axis=-1, kind="stable")[:, :k], -1)
    moved = (np.sort(routes[0], -1) != unbiased).any(-1).mean()
    if bias == "zeroed":
        assert moved == 0
    else:
        assert 0 < moved < 0.5
    # the weights the expert op was given: the scores of its picks times 6
    w_t = next(ly.inputs[2] for ly in m.layers
               if ly.op_type == OpType.MOE_EXPERTS)
    got = _graph_value(m, toks[:16], w_t)
    want = c.routed_scaling_factor * np.take_along_axis(
        s[:16], routes[0][:16], -1)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-7)
    assert not np.allclose(got.sum(-1), c.routed_scaling_factor)


def _graph_value(m, toks, tensor):
    """The value of ``tensor`` in a prefill step of ``toks`` on slot 0 of a
    fresh cache."""
    from flexflow_tpu.serve.engine import build_feeds

    R, Q = m.config.max_requests_per_batch, len(toks)
    t = np.zeros((R, Q), np.int32)
    t[0] = toks
    pos = np.zeros((R, Q), np.int32)
    pos[0] = np.arange(Q)
    meta = make_batch_meta(
        R, Q, tokens=t, positions=pos, start_pos=np.zeros(R, np.int32),
        num_tokens=np.array([Q] + [0] * (R - 1), np.int32),
        active=np.array([True] + [False] * (R - 1)))
    ctx = OpContext(training=False, rng=None, compute_dtype=jnp.float32,
                    batch_config=meta, mesh=m.mesh, config=m.config)
    state = jax.tree.map(jnp.zeros_like, m.op_state)
    values, _ = m._run_graph(m.params, build_feeds(m, meta), ctx, state)
    return np.asarray(values[tensor.tensor_id][0])


# ---------------------------------------------------------------------------
# (vi) the HF weight map
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("held_rank", [0, 3])
def test_hf_weight_map_loads_the_published_names(bench, held_rank):
    """A synthetic checkpoint under the published key names (``self_attn.
    {0,1}``, ``mlps.{0,1}``, two of each norm, ``mlp.router.classifier``,
    ``kv_b_proj`` whole, rope columns in adjacent pairs, one Linear an
    expert, an audio encoder beside the text model) loaded through the
    family: only the held experts are read, the encoder's keys dropped
    unread, and the program's logits are the reference's on the SAME
    checkpoint read directly, so the load-time permutation, the folded
    ``mla_scale_kv_lora`` and the reference's published form agree."""
    family, reference, cfg = bench
    cfg = dict(cfg, assumed=dict(cfg["assumed"], expert_rank=held_rank))
    m, c = _build(family, cfg)
    first, count = c.held
    nh, dn, dr, dv = (c.num_attention_heads, c.qk_nope_head_dim,
                      c.qk_rope_head_dim, c.v_head_dim)
    rank, qr, H, I, Ie = (c.kv_lora_rank, c.q_lora_rank, c.hidden_size,
                          c.ffn_hidden_size, c.expert_ffn_hidden_size)
    V, E, W = c.vocab_size, c.n_routed_experts, c.router_width
    rng = np.random.default_rng(4)
    f = lambda *s: (rng.standard_normal(s) * 0.08).astype(np.float32)
    sd = {"model.embed_tokens.weight": f(V, H), "model.norm.weight":
          1 + f(H), "lm_head.weight": f(V, H),
          "audio_encoder.conv1.weight": np.zeros((2, 2)),
          "visual.patch_embed.weight": np.zeros((2, 2))}
    for i in range(c.num_layers):
        ly = f"model.layers.{i}"
        for s in (0, 1):
            a = f"{ly}.self_attn.{s}"
            sd.update({
                f"{a}.q_a_proj.weight": f(qr, H),
                f"{a}.q_a_layernorm.weight": 1 + f(qr),
                f"{a}.q_b_proj.weight": f(nh * (dn + dr), qr),
                f"{a}.kv_a_proj_with_mqa.weight": f(rank + dr, H),
                f"{a}.kv_a_layernorm.weight": 1 + f(rank),
                f"{a}.kv_b_proj.weight": f(nh * (dn + dv), rank),
                f"{a}.o_proj.weight": f(H, nh * dv),
                f"{ly}.input_layernorm.{s}.weight": 1 + f(H),
                f"{ly}.post_attention_layernorm.{s}.weight": 1 + f(H),
                f"{ly}.mlps.{s}.gate_proj.weight": f(I, H),
                f"{ly}.mlps.{s}.up_proj.weight": f(I, H),
                f"{ly}.mlps.{s}.down_proj.weight": f(H, I)})
        sd[f"{ly}.mlp.router.classifier.weight"] = f(W, H) * 8
        sd[f"{ly}.mlp.router.e_score_correction_bias"] = f(W) * 0.01
        for e in range(E):
            for proj, shape in (("gate_proj", (Ie, H)), ("up_proj", (Ie, H)),
                                ("down_proj", (H, Ie))):
                sd[f"{ly}.mlp.experts.{e}.{proj}.weight"] = (
                    f(*shape) if first <= e < first + count
                    else np.full(shape, np.nan, np.float32))    # never read
    g = lambda k_: jnp.asarray(sd[k_])      # before load_hf consumes them
    layers = []
    for i in range(c.num_layers):
        ly = f"model.layers.{i}"

        def attn(s):
            a = f"{ly}.self_attn.{s}"
            kvb = np.asarray(sd[f"{a}.kv_b_proj.weight"]).reshape(
                nh, dn + dv, rank)
            return {"ln": g(f"{ly}.input_layernorm.{s}.weight"),
                    "wq_a": g(f"{a}.q_a_proj.weight").T,
                    "q_norm": g(f"{a}.q_a_layernorm.weight"),
                    "wq_b": g(f"{a}.q_b_proj.weight").T,
                    "wkv_a": g(f"{a}.kv_a_proj_with_mqa.weight").T,
                    "kv_norm": g(f"{a}.kv_a_layernorm.weight"),
                    "wk_b": jnp.asarray(kvb[:, :dn].transpose(0, 2, 1)),
                    "wv_b": jnp.asarray(kvb[:, dn:].transpose(0, 2, 1)),
                    "wo": g(f"{a}.o_proj.weight").T}

        def ffn(s):
            return {"ln": g(f"{ly}.post_attention_layernorm.{s}.weight"),
                    **{n: g(f"{ly}.mlps.{s}.{n}_proj.weight").T
                       for n in ("gate", "up", "down")}}

        layers.append({
            "attn": [attn(0), attn(1)], "ffn": [ffn(0), ffn(1)],
            "router": g(f"{ly}.mlp.router.classifier.weight").T,
            "bias": g(f"{ly}.mlp.router.e_score_correction_bias"),
            **{n: jnp.stack([g(f"{ly}.mlp.experts.{e}.{n}_proj.weight").T
                             for e in range(first, first + count)])
               for n in ("gate", "up", "down")}})
    weights = {"emb": g("model.embed_tokens.weight"), "layers": layers,
               "norm": g("model.norm.weight"), "head": g("lm_head.weight").T}
    fam = FAMILIES["longcat_flash"]
    n = fam.load_hf(m, c, sd)
    assert n == len(fam.hf_weight_map(c)) and {
        v[0] for v in fam.hf_weight_map(c).values()} == set(m.params)
    assert not any(np.isnan(np.asarray(leaf)).any()
                   for leaf in jax.tree.leaves(m.params))
    toks = np.random.default_rng(9).integers(1, V, size=70)
    ours, routes = family.program_logits_and_routes(m, toks,
                                                    [16] * 4 + [1] * 6)
    ref, _ = reference.forward_routed(weights, toks,
                                      family._reference_cfg(cfg),
                                      routes=routes, held=(first, count))
    np.testing.assert_allclose(ours, np.asarray(ref), rtol=3e-4, atol=3e-4)
    # and the family's way back from the served weights is the checkpoint
    back = list(family._reference_weights(m, c)["layers"])[1]["attn"][1]
    for name in ("wq_b", "wkv_a", "kv_norm", "wk_b", "wv_b"):
        np.testing.assert_allclose(
            back[name], np.asarray(layers[1]["attn"][1][name]), rtol=1e-6,
            atol=1e-6)


# ---------------------------------------------------------------------------
# (vii) the cell: its rehearsal, its traffic file
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("what", ["traffic_file", "traced_rehearsal"])
def test_the_cell_rehearses_and_its_traffic_is_the_issues(what, monkeypatch,
                                                          capsys):
    """The traffic file through the generator's own loader; and ``run.py
    --rehearse`` of the new cell, traced, so that the reference check, the
    kernels (interpreted) and every reader run."""
    monkeypatch.syspath_prepend(ROOT)
    for key in ("JAX_PLATFORMS", "FF_PALLAS_INTERPRET"):
        monkeypatch.setenv(key, os.environ.get(key, ""))   # restored after
    from benchmark import run
    from benchmark.lib import traffic as T

    if what == "traffic_file":
        t = T.load_traffic(os.path.join(
            ROOT, "benchmark/traffic/context-reasoning.json"))
        assert (t["loop"], t["clients"], t["warmup_s"], t["prompt_pool"],
                t["seed_step"]) == ("closed", 40, 10, 4, "cycle")
        assert t["cycle"] == [
            [1024, 1024], [2048, 768], [4096, 1024], [1536, 1536],
            [3072, 768], [6144, 1024], [1024, 768], [2048, 1536],
            [4096, 768], [1536, 1024], [3072, 1536], [2048, 1024]]
        assert sum(p for p, _ in t["cycle"]) == 31744
        assert sum(o for _, o in t["cycle"]) == 12800
        assert max(p + o for p, o in t["cycle"]) == 7168
        assert T.Cycle(t, 3000000019, 16384).next()[1] == t["cycle"][0][1]
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench_json = json.load(f)
        (cell,) = [w for w in bench_json["workloads"] if w["name"] == CELL]
        assert cell["chips"] == 1 and cell["traffic"] == "context-reasoning"
        mine = {m["name"] for m in bench_json["per_layer"]
                if CELL in m.get("workloads", ())}
        assert len(mine) == 33 and {"zero_expert_share",
                                    "decode_scmoe_hbm_roofline"} <= mine
        return
    # what the tests before this one traced off the kernels' path is theirs
    import flexflow_tpu.kernels as ffk

    ffk.reset_dispatch_stats()
    K.reset_dispatch_stats()
    assert run.main(["--workload", CELL, "--seed", "3000000019", "--seconds",
                     "4", "--trace", "1", "--rehearse"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    res = json.loads(out[-1])
    assert res["correct"] and res["rehearsal"] and not res["failed"]
    said = next(ln for ln in out if ln.startswith("# REHEARSAL"))
    values = json.loads(said.split("result: ")[1])
    assert 5 < values["zero_expert_share"]["value"] < 95
    # as stored at the rehearsal's widths: 128 + 32 values in 256 lanes
    assert values["kv_latent_bytes_per_pos"]["value"] == 512


# ---------------------------------------------------------------------------
# (viii) the refusals
# ---------------------------------------------------------------------------

def _refusal(name, family, cfg):
    hf = family._reference_cfg(cfg)
    if name in ("tree_verify_mode", "beam_search_mode"):
        mode = (InferenceMode.TREE_VERIFY_MODE if name == "tree_verify_mode"
                else InferenceMode.BEAM_SEARCH_MODE)
        return lambda: _build(family, cfg, mode=mode)
    if name == "tensor_parallel_mesh":
        return lambda: _build(family, cfg, tensor_parallelism_degree=2,
                              num_devices=2)
    wrong = {"another_zero_expert": {"zero_expert_type": "copy"},
             "another_attention": {"attention_method": "GQA"},
             "a_bias_on_the_logits": {"router_bias": True},
             "a_full_rank_query": {"q_lora_rank": None}}
    if name in wrong:
        return lambda: LongcatFlashConfig.from_hf_config(
            dict(hf, **wrong[name]))
    m, _ = _build(family, cfg)
    if name == "speculation_commit":
        from flexflow_tpu.ops.inc_attention import refuse_windowed

        return lambda: refuse_windowed(m.op_state, "a speculation commit")
    if name == "tree_batch_on_the_op":
        from flexflow_tpu.ops.latent_attention import \
            IncMultiHeadLatentAttention

        ctx = OpContext(training=False, rng=None, compute_dtype=jnp.float32,
                        batch_config=type("M", (), {"ancestor": 0})())
        layer = next(ly for ly in m.layers if "kv_lora_rank" in ly.attrs)
        return lambda: IncMultiHeadLatentAttention.forward(
            layer.attrs, m.params[layer.name],
            [jnp.zeros((2, 1, cfg["hidden_size"]))], ctx)
    raise KeyError(name)


@pytest.mark.parametrize("what,sentence", [
    ("tree_verify_mode", "incremental decoding only"),
    ("beam_search_mode", "incremental decoding only"),
    ("tensor_parallel_mesh", "latent attention layer"),
    ("speculation_commit", "one shared entry a position"),
    ("tree_batch_on_the_op", "incremental decoding on one chip"),
    ("another_zero_expert", "only the identity"),
    ("another_attention", "latent attention"),
    ("a_bias_on_the_logits", "router_bias"),
    ("a_full_rank_query", "q_lora_rank")])
def test_what_it_does_not_build_refuses_loudly(bench, what, sentence):
    family, _, cfg = bench
    with pytest.raises(NotImplementedError, match=sentence):
        _refusal(what, family, cfg)()


# ---------------------------------------------------------------------------
# (ix) the counter only this kind of model has
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model", ["longcat_flash", "olmoe_shaped"])
def test_zero_pairs_are_counted_only_where_a_router_has_them(bench, model):
    """Through RequestManager with telemetry: a model with a zero range has
    ``ffsv_moe_zero_pairs_total{phase}``, its ``zero`` and ``routed`` picks
    are those its router made, and the latent series count FOUR caches; an
    OLMoE-shaped model's counter row has the parent's width and no such
    series."""
    from flexflow_tpu.serve.request_manager import RequestManager
    from flexflow_tpu.telemetry import ServingTelemetry

    family, _, cfg = bench
    prompts = [[int(t) for t in np.random.default_rng(i).integers(
        1, 256, size=n)] for i, n in enumerate((40, 9))]
    new, n = 6, len(MOE_PHASES)
    tel = ServingTelemetry()
    rm = RequestManager()
    rm.telemetry = tel
    if model == "olmoe_shaped":
        from flexflow_tpu.models.olmoe import OLMoEConfig, create_olmoe_model

        m = ff.FFModel(ff.FFConfig(
            max_requests_per_batch=2, max_sequence_length=128,
            max_tokens_per_batch=64, seed=3, compute_dtype="float32",
            kv_cache_dtype="float32", num_devices=1, telemetry=True))
        create_olmoe_model(m, OLMoEConfig(
            vocab_size=256, hidden_size=64, intermediate_size=32,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, num_experts=8, num_experts_per_tok=2),
            data_type=ff.DataType.DT_FLOAT)
        m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
        assert m.op_state[MOE_COUNTERS].shape == (2, 8 + len(MOE_FIELDS) * n)
    else:
        m, c = _build(family, cfg, telemetry=True)
        assert m.op_state[MOE_COUNTERS].shape == (
            2, c.held[1] + (len(MOE_FIELDS) + 1) * n)
        assert m.attention_kinds["latent"]["layers"] == 4
    for p in prompts:
        rm.register_new_request(p, max_new_tokens=new)
    got = rm.generate_incr_decoding(m)
    assert all(len(r.output_tokens) == new for r in got)
    snap = tel.registry.snapshot()
    zero = [k for k in snap if k.startswith("ffsv_moe_zero_pairs_total")]
    if model == "olmoe_shaped":
        assert not zero
        return
    assert sorted(zero) == sorted(
        f'ffsv_moe_zero_pairs_total{{phase="{ph}"}}' for ph in MOE_PHASES)
    k = cfg["moe_topk"]
    for ph in ("prefill", "decode"):
        tokens = snap[f'ffsv_moe_tokens_total{{phase="{ph}"}}']["value"]
        z = snap[f'ffsv_moe_zero_pairs_total{{phase="{ph}"}}']["value"]
        routed = snap[f'ffsv_moe_routed_pairs_total{{phase="{ph}"}}']["value"]
        # a zero pick is never routed; picks of experts held elsewhere are
        # neither
        assert 0 < z < tokens * k and 0 <= routed and z + routed < tokens * k
    assert snap['ffsv_moe_tokens_total{phase="decode"}']["value"] == \
        2 * 2 * new
    lens = [len(p) + j for p in prompts for j in range(new)]
    assert snap['ffsv_attn_positions_read_total{kind="latent"}'][
        "value"] == 4 * sum(lens)
    assert snap['ffsv_kv_cache_bytes{kind="latent"}']["value"] == \
        m.attention_kinds["latent"]["cache_bytes"] == 4 * 2 * 512 * 160 * 4
