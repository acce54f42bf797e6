"""Family "longcat_flash": builds a serving handle for a LongCat-Flash
configuration file (incremental decoding over latent caches, two a layer;
one chip's share of an expert-parallel deployment; a router some of whose
outputs are experts that cost nothing), and holds what the yardstick needs
to know about the family's shapes: the bytes a cache position costs a latent
layer, the arithmetic of one query-key pair of its attention, the bytes of
one held expert, the arithmetic of one computed (token, expert) pair, and
the bytes a whole decode step must read."""

from __future__ import annotations

import time

import numpy as np

from . import _common as C
# what the expert families do alike: the drive of the program through
# chunks and decode steps that also returns its routes, the warm-up that
# holds the expert kernel to its compiled path, and the check of the routes
from .exaone_moe import _host, program_logits_and_routes  # noqa: F401
from .olmoe import check_routes, warm_and_check  # noqa: F401

# Reference check, logits: relative L2 error, worst position (bfloat16
# compute and cache against float32 on the same dequantised int8 weights).
# The readings at the published widths, two layers (four latent caches), 388
# positions, on the chip (my chip run, PR 47; PERF.md section 6): the program
# 0.0171; the reference itself, on the same routes, with bfloat16 matmul
# inputs 0.0228 (what the served precision costs this model: no fault), and
# wrong on purpose: (a) the zero experts' term left out 1.186, (b) the routed
# SwiGLU experts left out 1.075, (c) ``mla_scale_q_lora`` left out 1.175,
# ``mla_scale_kv_lora`` left out 1.302, (d) float8 (e4m3) matmul inputs, the
# nearest precision below, 0.489. The limit is 2.9 times the program's
# reading, 2.2 times what bfloat16 itself reads, and 9.8 times under the
# smallest of the wrong ones. A softmax over 768 gives small weights: with
# the program's default initialiser for the router (logits of standard
# deviation 1.33) and a seeded latent of 3.46 times unit RMS, (b) read 0.068
# beside a program at 0.064, so the seeded router and latent norm are what
# the configuration's ``assumed`` says (``router_init``, ``latent_norm_init``)
# and the limit was not widened to fit.
REFERENCE_TOL = 0.05
# Every layer is alike, so a period is one; two, so that a latent cache
# written from another layer's output, and a routed branch that rejoined,
# are read too.
REFERENCE_LAYERS = 2
# Prefilled in three chunks, so that the second and third attend entries an
# earlier step appended, then decoded one token at a time through the cache.
REFERENCE_CHUNKS = 3
REFERENCE_DECODED = 4
# Reference check, routing: families/olmoe.py has the reasoning. Here the
# choice is made on float32 softmax scores over 768 plus a bias, from a
# bfloat16 hidden state: a logit off by 0.05 is a score off by 5%. Every pick
# of the program outside the reference's own top-12 has to be an index whose
# biased reference score is within this relative margin of the reference's
# 12th largest. Both readings on the chip (9312 picks; my chip run, PR 47):
# the program takes 101 picks outside, the worst 8.3% short; the reference
# with float8 matmul inputs takes 1712, the worst 90.6% short. Three times
# the one, under a third of the other.
ROUTE_MARGIN = 0.25


def _held(cfg: dict):
    """(first, count, the router's SwiGLU experts) of this chip's share."""
    a = cfg["assumed"]
    count = cfg["n_routed_experts"]
    return a["expert_rank"] * count, count, a["expert_parallel"] * count


def _reference_cfg(cfg: dict) -> dict:
    """The configuration as the reference and the builder read it: all the
    router's SwiGLU experts under ``n_routed_experts``, the held range and
    the router's seeded initialiser beside it."""
    first, count, experts = _held(cfg)
    return {**cfg, "n_routed_experts": experts,
            "held_experts": (first, count),
            "router_init_std": cfg["assumed"].get("router_init_std")}


def _model_cfg(cfg: dict, layers=None):
    from flexflow_tpu.models.longcat_flash import LongcatFlashConfig

    hf = _reference_cfg(cfg)
    if layers is not None:
        hf["num_layers"] = layers
    return LongcatFlashConfig.from_hf_config(hf)


def build(cfg: dict, telemetry: bool) -> dict:
    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models.longcat_flash import create_longcat_flash_model
    from flexflow_tpu.serve.loadgen import EngineHandle

    llm = C.build_model(C.ffconfig(cfg, telemetry), create_longcat_flash_model,
                        _model_cfg(cfg), InferenceMode.INC_DECODING_MODE)
    return {"handle": EngineHandle(llm), "llm": llm, "models": [llm]}


# ---- the family's shapes, for the per-layer readers -----------------------

def layers_of(cfg: dict, kind: str) -> int:
    """How many layers of ``kind`` the configuration has: "latent"
    (attention sublayers and their caches: two a layer), "dense" (dense FFN
    sublayers: two a layer), "sparse" (routed layers: one a layer)."""
    L = cfg["num_layers"]
    return {"latent": 2 * L, "dense": 2 * L, "sparse": L}.get(kind, 0)


def cache_position_bytes(cfg: dict) -> float:
    """Bytes of one cache position of ONE latent layer that MUST be read:
    the latent and the rotated key part, bf16 (1152 B published; 1280 are
    stored, to fill the lanes, and are no work)."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * 2.0


def latent_pair_flops(cfg: dict) -> float:
    """Arithmetic of one (query, key) pair of ONE latent layer, all heads,
    by the PUBLISHED description: a head's score over ``qk_nope_head_dim +
    qk_rope_head_dim`` values and its value sum over ``v_head_dim``, a
    multiply and an add each (the absorbed form does more for the same
    result)."""
    return 2.0 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])


def expert_bytes(cfg: dict) -> float:
    """Bytes of one held expert: gate, up and down with their scales."""
    H, I = cfg["hidden_size"], cfg["expert_ffn_hidden_size"]
    return 3 * H * I * C.weight_element_bytes(cfg) + (2 * I + H) * 4.0


def pair_flops(cfg: dict) -> float:
    """Arithmetic of one computed (token, expert) pair: three H x I gemvs.
    A pick of a zero expert is no pair and costs a multiply-add a value."""
    return 6.0 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def _mlp(name, H, width, b):
    return [(f"{name}.gate", H, width, b), (f"{name}.up", H, width, b),
            (f"{name}.down", width, H, b),
            (f"{name}.scales", 1, 2 * width + H, 4.0)]


def dense_weights(cfg: dict):
    """(name, rows, cols, bytes per element) of every matrix one decode
    step multiplies by OUTSIDE the routed experts: what every step reads
    whatever its router does. int8 payload plus the float32 scale per
    column."""
    H, V, I = cfg["hidden_size"], cfg["vocab_size"], cfg["ffn_hidden_size"]
    nh, qr, rank = (cfg["num_attention_heads"], cfg["q_lora_rank"],
                    cfg["kv_lora_rank"])
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    width = _held(cfg)[2] + cfg["zero_expert_num"]
    b = C.weight_element_bytes(cfg)
    attn = [("wq_a", H, qr, b), ("wq_b", qr, nh * (dn + dr), b),
            ("wkv_a", H, rank + dr, b), ("wk_b", rank, nh * dn, b),
            ("wv_b", rank, nh * dv, b), ("wo", nh * dv, H, b),
            ("scales", 1, qr + nh * (2 * dn + dr + dv) + rank + dr + H, 4.0),
            ("norms", 1, 2 * H + qr + rank, 2.0)]
    out = []
    for i in range(cfg["num_layers"]):
        per_layer = [("router", H, width, b), ("router.scale", 1, width, 4.0),
                     ("router.bias", 1, width, 4.0)]
        for s in (0, 1):
            per_layer += [(f"self_attn.{s}.{n}", r, c, e)
                          for n, r, c, e in attn]
            per_layer += _mlp(f"mlps.{s}", H, I, b)
        out += [(f"layers.{i}.{n}", r, c, e) for n, r, c, e in per_layer]
    return out + [("lm_head", H, V, b), ("lm_head.scale", 1, V, 4.0),
                  ("norm", 1, H, 2.0)]


def decode_weights(cfg: dict):
    """``dense_weights`` and ALL the held experts: an upper count of what a
    decode step multiplies by (a step reads the experts its tokens chose:
    ``decode_step_must_read``)."""
    H, Ie = cfg["hidden_size"], cfg["expert_ffn_hidden_size"]
    b = C.weight_element_bytes(cfg)
    out = dense_weights(cfg)
    for i in range(cfg["num_layers"]):
        for e in range(cfg["n_routed_experts"]):
            out += _mlp(f"layers.{i}.experts.{e}", H, Ie, b)
    return out


def cache_bytes_per_token(cfg: dict) -> float:
    """Cache bytes a live position adds to a decode step's reads."""
    return cache_position_bytes(cfg) * layers_of(cfg, "latent")


def decode_step_must_read(cfg: dict, experts_touched: float,
                          layer_positions: float) -> float:
    """Bytes ONE decode step must read: every matrix outside the experts
    once, ``experts_touched`` held experts in each sparse layer, and
    ``layer_positions`` cache positions (summed over the step's rows and the
    latent layers) at the bytes a position must cost."""
    dense = sum(r * c * e for _, r, c, e in dense_weights(cfg))
    return (dense
            + experts_touched * layers_of(cfg, "sparse") * expert_bytes(cfg)
            + layer_positions * cache_position_bytes(cfg))


# ---- the reference check --------------------------------------------------

def _reference_weights(m, mc):
    """The served weights, dequantised to float32 on the device and brought
    to the host, the layers one at a time (a generator), in the PUBLISHED
    form: the rope columns of ``wq_b`` and ``wkv_a``, which the program
    keeps permuted for its rotate-half, go back to adjacent pairs, and the
    latent norm's weight gives back the ``mla_scale_kv_lora`` factor the
    program keeps folded into it."""
    from flexflow_tpu.models.longcat_flash import rope_permutation

    p = m.params
    nh, dn, dr = mc.num_attention_heads, mc.qk_nope_head_dim, \
        mc.qk_rope_head_dim
    rank = mc.kv_lora_rank
    back = np.argsort(rope_permutation(dr))

    def dense(leaf):
        return np.asarray(C.dense(leaf))

    def norm(name):
        return dense(p[name]["weight"])

    def attention(name, ln):
        a = p[name]
        wq_b = dense(a["wq_b"]).reshape(-1, nh, dn + dr)
        wq_b = np.concatenate([wq_b[..., :dn], wq_b[..., dn:][..., back]],
                              axis=-1).reshape(-1, nh * (dn + dr))
        wkv_a = dense(a["wkv_a"])
        wkv_a = np.concatenate([wkv_a[:, :rank], wkv_a[:, rank:][:, back]],
                               axis=-1)
        return {"ln": norm(ln), "wq_a": dense(a["wq_a"]),
                "q_norm": dense(a["q_norm"]), "wq_b": wq_b, "wkv_a": wkv_a,
                "kv_norm": dense(a["kv_norm"]) / np.float32(mc.latent_scale),
                "wk_b": dense(a["wk_b"]), "wv_b": dense(a["wv_b"]),
                "wo": dense(a["wo"])}

    def ffn(name, ln):
        return {"ln": norm(ln), **{
            n: dense(p[f"{name}.{n}_proj"]["kernel"])
            for n in ("gate", "up", "down")}}

    def layers():
        for i in range(mc.num_layers):
            ly = f"layers.{i}"
            x = p[f"{ly}.mlp.experts"]
            yield {
                "attn": [attention(f"{ly}.self_attn.{s}",
                                   f"{ly}.input_layernorm.{s}")
                         for s in (0, 1)],
                "ffn": [ffn(f"{ly}.mlps.{s}",
                            f"{ly}.post_attention_layernorm.{s}")
                        for s in (0, 1)],
                "router": dense(p[f"{ly}.mlp.router.classifier"]["kernel"]),
                "bias": norm(f"{ly}.mlp.router.e_score_correction_bias"),
                "gate": dense(x["gate"]), "up": dense(x["up"]),
                "down": dense(x["down"])}

    return {"emb": norm("embed_tokens"), "layers": layers(),
            "norm": norm("norm"), "head": dense(p["lm_head"]["kernel"])}


def reference_run(cfg: dict):
    """Drive the program at the published widths on the reference check's
    cut: ``(tokens, the program's logits, its routes, the weights for the
    reference, seconds)``. Three prefill chunks, then four tokens decoded,
    through the latent caches."""
    from concurrent.futures import ThreadPoolExecutor

    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models.longcat_flash import create_longcat_flash_model

    t_build = time.perf_counter()
    chunk = C.prefill_chunk(cfg)
    mc = _model_cfg(cfg, min(REFERENCE_LAYERS, cfg["num_layers"]))
    m = C.build_model(C.ffconfig(cfg, False, max_requests_per_batch=2),
                      create_longcat_flash_model, mc,
                      InferenceMode.INC_DECODING_MODE)
    chunks = [chunk] * REFERENCE_CHUNKS + [1] * REFERENCE_DECODED
    toks = np.random.default_rng(cfg["weights_seed"]).integers(
        1, cfg["vocab_size"], size=sum(chunks))
    # the weights come over to the host while the program compiles and runs
    t0 = time.perf_counter()
    with ThreadPoolExecutor(1) as pool:
        coming = pool.submit(
            lambda w: {**w, "layers": list(w["layers"])},
            _reference_weights(m, mc))
        ours, routes = program_logits_and_routes(m, toks, chunks)
        t1 = time.perf_counter()
        weights = coming.result()
    t2 = time.perf_counter()
    return toks, ours, routes, weights, [t0 - t_build, t1 - t0, t2 - t1]


def reference_logits(cfg: dict, reference, weights, toks, routes, **kw):
    """The reference on the host's CPU (``families/exaone_moe._host``):
    (logits, [biased scores] per layer), numpy."""
    import jax

    first, count, _ = _held(cfg)
    with jax.default_device(_host()):
        ref, scores = reference.forward_routed(
            weights, toks, _reference_cfg(cfg), routes=routes,
            held=(first, count), **kw)
        return np.asarray(ref), [np.asarray(s) for s in scores]


# what the reference computes when it is asked to be wrong on purpose
# (``reference_check(variants=)``): keyword arguments of ``forward_routed``
VARIANTS = {
    "zero_out": {"without": ("zero",)},
    "routed_out": {"without": ("routed",)},
    "q_scale_out": {"without": ("q_scale",)},
    "kv_scale_out": {"without": ("kv_scale",)},
    "float8": {"matmul_dtype": "float8_e4m3fn"},
    # what the served precision itself costs this model: no fault
    "bfloat16": {"matmul_dtype": "bfloat16"},
}


def reference_check(cfg: dict, reference, variants=()) -> dict:
    """Two layers (four latent caches) at the published widths with the
    held range, the same seeded weights as served. The routes the program
    took are checked against the reference's biased scores, and the logits,
    at all positions, against the reference run on those routes
    (ROUTE_MARGIN). ``variants`` (names of ``VARIANTS``; by hand,
    tools/check_longcat_variants.py): beside the program's reading, what the
    reference reads against ITSELF, on the same routes, with the zero
    experts' term, the routed experts or an ``mla_scale_*`` factor left
    out, or float8 matmul inputs, as ``wrong_<name>``."""
    import jax.numpy as jnp

    toks, ours, routes, weights, seconds = reference_run(cfg)
    t = time.perf_counter()
    ref, scores = reference_logits(cfg, reference, weights, toks, routes)
    out = C.compare_logits(ours, ref, REFERENCE_TOL)
    out.update(check_routes(routes, scores, ROUTE_MARGIN))
    out["ok"] = out["ok"] and out["routes_ok"]
    # where a cold run's minute goes: the cut's build, its two programs
    # (compiled, then run), the wait for the weights, the reference
    out["seconds"] = [round(x, 1) for x in
                      seconds + [time.perf_counter() - t]]
    for name in variants:
        kw = dict(VARIANTS[name])
        if "matmul_dtype" in kw:
            kw["matmul_dtype"] = getattr(jnp, kw["matmul_dtype"])
        wrong, _ = reference_logits(cfg, reference, weights, toks, routes,
                                    **kw)
        out[f"wrong_{name}"] = C.compare_logits(wrong, ref, REFERENCE_TOL)[
            "max_rel_l2"]
    if "float8" in variants:    # and the routes float8 would take
        _, s8 = reference_logits(cfg, reference, weights, toks, None,
                                 matmul_dtype=jnp.float8_e4m3fn)
        k = cfg["moe_topk"]
        took = [np.argsort(-s, axis=-1, kind="stable")[:, :k] for s in s8]
        f8 = check_routes(took, scores, ROUTE_MARGIN)
        out["float8_routes"] = [f8["route_flips"], f8["worst_flip"]]
    return out
