"""Family "mistral4": builds a serving handle for a Mistral-4 configuration
file (incremental decoding over a latent cache; one chip's share of an
expert-parallel deployment), and holds what the yardstick needs to know
about the family's shapes: the bytes a cache position costs a layer, the
arithmetic of one query-key pair of its attention, the bytes of one held
expert, the arithmetic of one computed (token, expert) pair."""

from __future__ import annotations

import time

import numpy as np

from . import _common as C
# what the expert families do alike: the shapes of a SwiGLU expert, the
# drive of the program through chunks and decode steps that also returns
# its routes, the warm-up that holds the expert kernel to its compiled path,
# and the check of the routes
from .exaone_moe import (expert_bytes, pair_flops,  # noqa: F401
                         program_logits_and_routes)
from .olmoe import (check_routes, expert_kernel_paths,  # noqa: F401
                    warm_and_check)

# Reference check, logits: relative L2 error, worst position (bfloat16
# compute and cache against float32 on the same dequantised int8 weights).
# Both readings at the published widths, two layers, 388 positions (PERF.md
# section 6, PR 35): the program on the chip 0.0248; the reference itself
# with float8 (e4m3) matmul inputs, the nearest precision below, 0.389 (the
# reference with bfloat16 matmul inputs reads 0.0255: what the program reads
# is what bfloat16 costs this model, whose scores sum 320 products of a
# query that was itself rounded after 64, where the other families' sum 128).
# The other families' 0.03 would leave the program a fifth of room; this is
# 2.4 times the one reading and under a sixth of the other.
REFERENCE_TOL = 0.06
# Every layer is alike, so a period is one; two, so that a latent cache
# written from another layer's output is read too.
REFERENCE_LAYERS = 2
# Prefilled in three chunks, so that the second and third attend entries an
# earlier step appended, then decoded one token at a time through the cache.
REFERENCE_CHUNKS = 3
REFERENCE_DECODED = 4
# Reference check, routing: families/exaone_moe.py has the reasoning (the
# same router: biased sigmoid scores, chosen from a bfloat16 hidden state).
# Both readings (3104 picks): the program on the chip takes 39 picks outside
# the reference's top-4, the worst 0.45% short; the reference with float8
# matmul inputs takes 590, the worst 19.9% short.
ROUTE_MARGIN = 0.02


def _held(cfg: dict):
    """(first, count, router width) of this chip's routed experts."""
    a = cfg["assumed"]
    count = cfg["n_routed_experts"]
    return a["expert_rank"] * count, count, a["expert_parallel"] * count


def _reference_cfg(cfg: dict) -> dict:
    """The configuration as the reference and the builder read it: the
    router's width under ``n_routed_experts``, the held range beside it."""
    first, count, width = _held(cfg)
    return {**cfg, "n_routed_experts": width, "held_experts": (first, count)}


def _model_cfg(cfg: dict, layers=None):
    from flexflow_tpu.models.mistral4 import Mistral4Config

    hf = _reference_cfg(cfg)
    if layers is not None:
        hf["num_hidden_layers"] = layers
    return Mistral4Config.from_hf_config(hf)


def build(cfg: dict, telemetry: bool) -> dict:
    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models.mistral4 import create_mistral4_model
    from flexflow_tpu.serve.loadgen import EngineHandle

    llm = C.build_model(C.ffconfig(cfg, telemetry), create_mistral4_model,
                        _model_cfg(cfg), InferenceMode.INC_DECODING_MODE)
    return {"handle": EngineHandle(llm), "llm": llm, "models": [llm]}


# ---- the family's shapes, for the per-layer readers -----------------------

def layers_of(cfg: dict, kind: str) -> int:
    """How many of the configuration's layers are ``kind``: "latent"
    (attention: all of them), "sparse" or "dense" (MLP)."""
    L = cfg["num_hidden_layers"]
    dense = min(L, cfg.get("first_k_dense_replace", 0))
    return {"latent": L, "sparse": L - dense, "dense": dense}.get(kind, 0)


def cache_position_bytes(cfg: dict) -> float:
    """Bytes of one cache position of ONE layer that MUST be read: the
    latent and the rotated key part, bf16 (640 B published). What is
    stored beside them to fill the lanes is no work."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * 2.0


def latent_pair_flops(cfg: dict) -> float:
    """Arithmetic of one (query, key) pair of ONE layer, all heads, by the
    PUBLISHED description: a head's score over ``qk_head_dim`` values and
    its value sum over ``v_head_dim``, a multiply and an add each. The
    absorbed form does more operations for the same result (1152 a head
    against these 512); the share reads the same work whatever form the
    program takes."""
    return 2.0 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])


def decode_weights(cfg: dict):
    """(name, rows, cols, bytes per element) of every matrix one decode
    step multiplies by if it touches ALL the held experts (an upper count),
    int8 payload plus the float32 scale per column."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    I, Ie = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    _, count, width = _held(cfg)
    nh, qr, rank = (cfg["num_attention_heads"], cfg["q_lora_rank"],
                    cfg["kv_lora_rank"])
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    b = C.weight_element_bytes(cfg)
    attn = [("wq_a", H, qr, b), ("wq_b", qr, nh * (dn + dr), b),
            ("wkv_a", H, rank + dr, b), ("wk_b", rank, nh * dn, b),
            ("wv_b", rank, nh * dv, b), ("wo", nh * dv, H, b),
            ("scales", 1, qr + nh * (2 * dn + dr + dv) + rank + dr + H, 4.0),
            ("norms", 1, 2 * H + qr + rank, 2.0)]

    def mlp(name, width_):
        return [(f"{name}.gate", H, width_, b), (f"{name}.up", H, width_, b),
                (f"{name}.down", width_, H, b),
                (f"{name}.scales", 1, 2 * width_ + H, 4.0)]

    out = []
    for i in range(cfg["num_hidden_layers"]):
        per_layer = list(attn)
        if i < cfg.get("first_k_dense_replace", 0):
            per_layer += mlp("mlp", I)
        else:
            per_layer += [("router", H, width, b),
                          ("router.scale", 1, width, 4.0),
                          ("router.bias", 1, width, 4.0)]
            per_layer += mlp("shared", cfg["n_shared_experts"] * Ie)
            for e in range(count):
                per_layer += mlp(f"experts.{e}", Ie)
        out += [(f"layers.{i}.{n}", r, c, e) for n, r, c, e in per_layer]
    return out + [("lm_head", H, V, b), ("lm_head.scale", 1, V, 4.0),
                  ("norm", 1, H, 2.0)]


def cache_bytes_per_token(cfg: dict) -> float:
    """Cache bytes a live position adds to a decode step's reads."""
    return cache_position_bytes(cfg) * cfg["num_hidden_layers"]


# ---- the reference check --------------------------------------------------

def _reference_weights(m, mc):
    """The served weights, dequantised to float32 on the device and brought
    to the host, the layers one at a time (a generator), in the PUBLISHED
    column order: the rope columns of ``wq_b`` and ``wkv_a``, which the
    program keeps permuted for its rotate-half, go back to adjacent pairs."""
    from flexflow_tpu.models.mistral4 import rope_permutation

    p = m.params
    nh, dn, dr = mc.num_attention_heads, mc.qk_nope_head_dim, \
        mc.qk_rope_head_dim
    rank = mc.kv_lora_rank
    back = np.argsort(rope_permutation(dr)) if mc.rope_interleave \
        else np.arange(dr)

    def dense(leaf):
        return np.asarray(C.dense(leaf))

    def kernel(name):
        return dense(p[name]["kernel"])

    def layers():
        for i in range(mc.num_hidden_layers):
            a, pre = p[f"layers.{i}.self_attn"], f"layers.{i}.mlp"
            wq_b = dense(a["wq_b"]).reshape(-1, nh, dn + dr)
            wq_b = np.concatenate([wq_b[..., :dn], wq_b[..., dn:][..., back]],
                                  axis=-1).reshape(-1, nh * (dn + dr))
            wkv_a = dense(a["wkv_a"])
            wkv_a = np.concatenate([wkv_a[:, :rank], wkv_a[:, rank:][:, back]],
                                   axis=-1)
            x = p[f"{pre}.experts"]
            yield {
                "ln1": dense(p[f"layers.{i}.input_layernorm"]["weight"]),
                "wq_a": dense(a["wq_a"]), "q_norm": dense(a["q_norm"]),
                "wq_b": wq_b, "wkv_a": wkv_a,
                "kv_norm": dense(a["kv_norm"]), "wk_b": dense(a["wk_b"]),
                "wv_b": dense(a["wv_b"]), "wo": dense(a["wo"]),
                "ln2": dense(
                    p[f"layers.{i}.post_attention_layernorm"]["weight"]),
                "router": kernel(f"{pre}.gate"),
                "bias": dense(
                    p[f"{pre}.gate.e_score_correction_bias"]["weight"]),
                "gate": dense(x["gate"]), "up": dense(x["up"]),
                "down": dense(x["down"]),
                "s_gate": kernel(f"{pre}.shared_experts.gate_proj"),
                "s_up": kernel(f"{pre}.shared_experts.up_proj"),
                "s_down": kernel(f"{pre}.shared_experts.down_proj")}

    return {"emb": dense(p["embed_tokens"]["weight"]), "layers": layers(),
            "norm": dense(p["norm"]["weight"]),
            "head": dense(p["lm_head"]["kernel"])}


def reference_check(cfg: dict, reference) -> dict:
    """Two layers at the published widths with the held range, the same
    seeded weights as served: three prefill chunks, then four tokens
    decoded, through the latent cache. The routes the program took are
    checked against the reference's biased scores, and the logits, at all
    positions, against the reference run on those routes (ROUTE_MARGIN).
    The reference is numpy on the host."""
    from concurrent.futures import ThreadPoolExecutor

    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models.mistral4 import create_mistral4_model

    t_build = time.perf_counter()
    chunk = C.prefill_chunk(cfg)
    mc = _model_cfg(cfg, min(REFERENCE_LAYERS, cfg["num_hidden_layers"]))
    m = C.build_model(C.ffconfig(cfg, False, max_requests_per_batch=2),
                      create_mistral4_model, mc,
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
    first, count, _ = _held(cfg)
    ref, scores = reference.forward_routed(
        weights, toks, _reference_cfg(cfg), routes=routes,
        held=(first, count))
    t3 = time.perf_counter()
    out = C.compare_logits(ours, ref, REFERENCE_TOL)
    out.update(check_routes(routes, scores, ROUTE_MARGIN))
    out["ok"] = out["ok"] and out["routes_ok"]
    # where a cold run's minute goes: the cut's build, its two programs
    # (compiled, then run), the wait for the weights, the reference
    out["seconds"] = [round(x, 1) for x in (t0 - t_build, t1 - t0, t2 - t1,
                                            t3 - t2)]
    return out


def crossing_check(cfg: dict, reference, prefilled: int, compared: int = 256,
                   decoded: int = REFERENCE_DECODED) -> dict:
    """ONE layer (a whole period) at the published widths over a sequence
    as long as the configuration's positions allow: ``prefilled`` tokens in
    chunks through the latent cache, then ``decoded`` one at a time; the
    last ``compared`` prefilled and the decoded positions' logits against
    the BLOCKED reference (the earlier positions need only their keys),
    routes checked apart as in ``reference_check``. By hand
    (tools/check_latent_crossing.py): with ``prefilled`` a little past
    ``original_max_position_embeddings`` the compared positions lie on both
    sides of it, where the position scale steps."""
    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models.mistral4 import create_mistral4_model

    chunk = C.prefill_chunk(cfg)
    assert prefilled % chunk == 0, (prefilled, chunk)
    mc = _model_cfg(cfg, 1)
    m = C.build_model(C.ffconfig(cfg, False, max_requests_per_batch=2),
                      create_mistral4_model, mc,
                      InferenceMode.INC_DECODING_MODE)
    chunks = [chunk] * (prefilled // chunk) + [1] * decoded
    toks = np.random.default_rng(cfg["weights_seed"]).integers(
        1, cfg["vocab_size"], size=sum(chunks))
    last = compared + decoded
    ours, routes = program_logits_and_routes(m, toks, chunks)
    ours, routes = ours[-last:], [r[-last:] for r in routes]
    weights = _reference_weights(m, mc)
    weights = {**weights, "layers": list(weights["layers"])}
    first, count, _ = _held(cfg)
    ref, scores = reference.forward_routed(
        weights, toks, _reference_cfg(cfg), routes=routes,
        held=(first, count), last=last)
    out = C.compare_logits(ours, ref, REFERENCE_TOL)
    out.update(check_routes(routes, scores, ROUTE_MARGIN))
    out.update(ok=out["ok"] and out["routes_ok"],
               positions_compared=[len(toks) - last, len(toks)])
    return out
