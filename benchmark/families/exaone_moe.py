"""Family "exaone_moe": builds a serving handle for an EXAONE-MoE
configuration file (incremental decoding; one chip's share of an
expert-parallel deployment), and holds what the yardstick needs to know
about the family's shapes: which layers are windowed and which sparse, the
bytes of one held expert, the arithmetic of one computed (token, expert)
pair, the bytes a cache position costs a layer."""

from __future__ import annotations

import numpy as np

from . import _common as C
# what the two expert families do alike: the warm-up that holds the expert
# kernel to its compiled path, and the check of the program's routes against
# the reference's scores (probabilities there, biased sigmoid scores here)
from .olmoe import (check_routes, expert_kernel_paths,  # noqa: F401
                    warm_and_check)

# Reference check, logits: relative L2 error, worst position, as the other
# families (families/falcon.py has the reasoning: bfloat16 compute against
# float32 on the same dequantised int8 weights). Both readings at the
# published widths, layers 0-3, 388 positions (PERF.md section 6, PR 31): the
# program on the chip 0.0088; the reference itself with float8 (e4m3) matmul
# inputs, the nearest precision below, 0.173. The limit is 3.4 times the
# one and under a fifth of the other.
REFERENCE_TOL = 0.03
# One whole period of the layer pattern: the dense layer and three sparse
# ones, three windowed layers and the full one.
REFERENCE_LAYERS = 4
# Prefilled in three chunks, so that the second and third read a ring that
# earlier steps wrote (a chunk's window reaches 127 positions back into the
# one before), then decoded one token at a time through both caches.
REFERENCE_CHUNKS = 3
REFERENCE_DECODED = 4
# Reference check, routing: families/olmoe.py has the reasoning, which
# holds here with the family's own numbers. The program chooses experts on
# biased sigmoid scores computed from a bfloat16 hidden state; where the
# 8th and 9th largest of 128 are closer than that rounding the program
# takes the 9th, and one such pick moves a position's logits by far more
# than REFERENCE_TOL. So the logits are compared with the reference run on
# the PROGRAM's routes and the routes are checked apart: every pick
# outside the reference's own top-8 has to be an expert whose reference
# biased score is within this relative margin of the reference's 8th
# largest. The chosen scores lie near 0.9, where the sigmoid is flat (slope
# 0.09): a rounding of 0.01 in a logit moves a score by 0.1% of itself, so
# the margin is a quarter of OLMoE's. Both readings (PERF.md section 6, PR
# 31; 9312 picks): the program on the chip takes 53 picks outside the
# reference's top-8, the worst 0.26% short; the reference with float8 matmul
# inputs takes 1012, the worst 8.2% short. The margin is eight times the one
# and a quarter of the other; a pick made for any other reason (a missing
# bias, a permuted index, stale scores) is typically 10-50% short.
ROUTE_MARGIN = 0.02


def _held(cfg: dict):
    """(first, count, router width) of this chip's routed experts."""
    a = cfg["assumed"]
    count = cfg["num_experts"]
    return a["expert_rank"] * count, count, a["expert_parallel"] * count


def _reference_cfg(cfg: dict) -> dict:
    """The configuration as the reference and the builder read it: the
    router's width under ``num_experts``, the held range beside it."""
    first, count, width = _held(cfg)
    return {**cfg, "num_experts": width, "held_experts": (first, count)}


def _model_cfg(cfg: dict, layers=None):
    from flexflow_tpu.models.exaone_moe import ExaoneMoEConfig

    hf = _reference_cfg(cfg)
    if layers is not None:
        hf["num_hidden_layers"] = layers
    return ExaoneMoEConfig.from_hf_config(hf)


def build(cfg: dict, telemetry: bool) -> dict:
    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models.exaone_moe import create_exaone_moe_model
    from flexflow_tpu.serve.loadgen import EngineHandle

    llm = C.build_model(C.ffconfig(cfg, telemetry), create_exaone_moe_model,
                        _model_cfg(cfg), InferenceMode.INC_DECODING_MODE)
    return {"handle": EngineHandle(llm), "llm": llm, "models": [llm]}


# ---- the family's shapes, for the per-layer readers -----------------------

def layers_of(cfg: dict, kind: str) -> int:
    """How many of the configuration's layers are ``kind``: "window" or
    "full" (attention), "sparse" or "dense" (MLP)."""
    L = cfg["num_hidden_layers"]
    if kind in ("window", "full"):
        want = "sliding_attention" if kind == "window" else "full_attention"
        return cfg["layer_types"][:L].count(want)
    return cfg["mlp_layer_types"][:L].count(kind)


def expert_bytes(cfg: dict) -> float:
    """Bytes of one routed expert: gate, up and down with their scales."""
    H, I = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return 3 * H * I * C.weight_element_bytes(cfg) + (2 * I + H) * 4.0


def pair_flops(cfg: dict) -> float:
    """Arithmetic of one computed (token, expert) pair: three H x I gemvs."""
    return 6.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def cache_position_bytes(cfg: dict) -> float:
    """Bytes one cache position costs ONE layer: k and v, bf16."""
    return 2.0 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2


def decode_weights(cfg: dict):
    """(name, rows, cols, bytes per element) of every matrix one decode
    step multiplies by if it touches ALL the held experts (an upper count:
    a step reads the experts its tokens chose, about 14 of 16 at a full
    batch), int8 payload plus the float32 scale per column."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    I, Ie = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    _, count, width = _held(cfg)
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    b = C.weight_element_bytes(cfg)
    attn = [("wq", H, q, b), ("wk", H, kv, b), ("wv", H, kv, b),
            ("wo", q, H, b), ("scales", 1, q + 2 * kv + H, 4.0),
            ("norms", 1, 2 * H + 2 * cfg["head_dim"], 2.0)]

    def mlp(name, width_):
        return [(f"{name}.gate", H, width_, b), (f"{name}.up", H, width_, b),
                (f"{name}.down", width_, H, b),
                (f"{name}.scales", 1, 2 * width_ + H, 4.0)]

    out = []
    for i, kind in enumerate(
            cfg["mlp_layer_types"][:cfg["num_hidden_layers"]]):
        per_layer = list(attn)
        if kind == "dense":
            per_layer += mlp("mlp", I)
        else:
            per_layer += [("router", H, width, b), ("router.scale", 1, width,
                                                    4.0),
                          ("router.bias", 1, width, 4.0)]
            per_layer += mlp("shared", cfg["num_shared_experts"] * Ie)
            for e in range(count):
                per_layer += mlp(f"experts.{e}", Ie)
        out += [(f"layers.{i}.{n}", r, c, e) for n, r, c, e in per_layer]
    return out + [("lm_head", H, V, b), ("lm_head.scale", 1, V, 4.0),
                  ("norm", 1, H, 2.0)]


def cache_bytes_per_token(cfg: dict) -> float:
    """Cache bytes a live position adds to a decode step's reads: the full
    layers', which read every position (a windowed layer reads its window
    whatever the length)."""
    return cache_position_bytes(cfg) * layers_of(cfg, "full")


# ---- the reference check --------------------------------------------------

def _reference_weights(m, mc):
    """The served weights, dequantised to float32 on the device and brought
    to the host, the layers one at a time (a generator:
    reference/exaone_moe.py holds one layer's copy at once)."""
    p = m.params

    def dense(leaf):
        return np.asarray(C.dense(leaf))

    def kernel(name):
        return dense(p[name]["kernel"])

    def layers():
        for i in range(mc.num_hidden_layers):
            a, pre = p[f"layers.{i}.self_attn"], f"layers.{i}.mlp"
            lw = {"ln1": dense(p[f"layers.{i}.input_layernorm"]["weight"]),
                  "wq": dense(a["wq"]), "wk": dense(a["wk"]),
                  "wv": dense(a["wv"]), "wo": dense(a["wo"]),
                  "q_norm": dense(a["q_norm"]), "k_norm": dense(a["k_norm"]),
                  "ln2": dense(
                      p[f"layers.{i}.post_attention_layernorm"]["weight"])}
            if mc.mlp_layer_types[i] == "dense":
                lw.update(gate=kernel(f"{pre}.gate_proj"),
                          up=kernel(f"{pre}.up_proj"),
                          down=kernel(f"{pre}.down_proj"))
            else:
                x = p[f"{pre}.experts"]
                lw.update(
                    router=kernel(f"{pre}.gate"),
                    bias=dense(
                        p[f"{pre}.gate.e_score_correction_bias"]["weight"]),
                    gate=dense(x["gate"]), up=dense(x["up"]),
                    down=dense(x["down"]),
                    s_gate=kernel(f"{pre}.shared_experts.gate_proj"),
                    s_up=kernel(f"{pre}.shared_experts.up_proj"),
                    s_down=kernel(f"{pre}.shared_experts.down_proj"))
            yield lw

    return {"emb": dense(p["embed_tokens"]["weight"]), "layers": layers(),
            "norm": dense(p["norm"]["weight"]),
            "head": dense(p["lm_head"]["kernel"])}


def _host():
    """The process's CPU device, where the reference runs: true float32
    matmuls, and none of the minute that compiling a dozen
    highest-precision matmul shapes for the chip costs a cold run (PERF.md
    section 6, PR 31). None where the process has no CPU backend."""
    import jax

    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


def program_logits_and_routes(model, tokens, chunks):
    """The program's float32 logits [T, V] and, per sparse layer, the
    experts it chose [T, k] (indices over the router's whole width: the
    expert op's input, a graph value), for ``tokens`` on one slot fed in
    steps of ``chunks`` tokens (a chunk of several is a prefill step on the
    slot grid, a chunk of one a decode step) through the caches."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ffconst import OpType
    from flexflow_tpu.ops.base import OpContext
    from flexflow_tpu.serve.batch_config import make_batch_meta
    from flexflow_tpu.serve.engine import build_feeds

    R = model.config.max_requests_per_batch
    logits_t = model.layers[-1].inputs[0]
    chosen_t = [ly.inputs[1] for ly in model.layers
                if ly.op_type == OpType.MOE_EXPERTS]
    cdt = jnp.dtype(model.config.compute_dtype)

    def step(params, state, meta):
        ctx = OpContext(training=False, rng=None, compute_dtype=cdt,
                        batch_config=meta, mesh=model.mesh,
                        config=model.config)
        values, new_state = model._run_graph(params, build_feeds(model, meta),
                                             ctx, state)
        return (values[logits_t.tensor_id][0].astype(jnp.float32),
                [values[t.tensor_id][0] for t in chosen_t], new_state)

    step = jax.jit(step, donate_argnums=(1,))
    parts, start = [], 0
    for Q in chunks:
        toks = np.zeros((R, Q), np.int32)
        toks[0] = tokens[start:start + Q]
        pos = np.zeros((R, Q), np.int32)
        pos[0] = np.arange(start, start + Q)
        meta = make_batch_meta(
            R, Q, tokens=toks, positions=pos,
            start_pos=np.array([start] + [0] * (R - 1), np.int32),
            num_tokens=np.array([Q] + [0] * (R - 1), np.int32),
            active=np.array([True] + [False] * (R - 1)))
        out, chosen, model.op_state = step(model.params, model.op_state, meta)
        parts.append((np.asarray(out), [np.asarray(c) for c in chosen]))
        start += Q
    logits = np.concatenate([p[0] for p in parts], axis=0)
    routes = [np.concatenate([p[1][j] for p in parts], axis=0)
              for j in range(len(chosen_t))]
    return logits, routes


def reference_check(cfg: dict, reference) -> dict:
    """Layers 0-3 (one period) at the published widths with the held range,
    the same seeded weights as served: three prefill chunks, then four
    tokens decoded, through the ring and the full cache. The routes the
    program took are checked against the reference's biased scores, and
    the logits, at all positions, against the reference run on those routes
    (ROUTE_MARGIN above has the reasoning). The reference runs on the host's
    CPU (``_host``)."""
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models.exaone_moe import create_exaone_moe_model

    chunk = C.prefill_chunk(cfg)
    mc = _model_cfg(cfg, min(REFERENCE_LAYERS, cfg["num_hidden_layers"]))
    m = C.build_model(C.ffconfig(cfg, False, max_requests_per_batch=2),
                      create_exaone_moe_model, mc,
                      InferenceMode.INC_DECODING_MODE)
    chunks = [chunk] * REFERENCE_CHUNKS + [1] * REFERENCE_DECODED
    toks = np.random.default_rng(cfg["weights_seed"]).integers(
        1, cfg["vocab_size"], size=sum(chunks))
    # the weights come over to the host while the program compiles and runs
    # (it donates its caches only): 11 GB of float32 for the four layers,
    # in the host's memory, never on the device at once
    with ThreadPoolExecutor(1) as pool:
        coming = pool.submit(
            lambda w: {**w, "layers": list(w["layers"])},
            _reference_weights(m, mc))
        ours, routes = program_logits_and_routes(m, toks, chunks)
        weights = coming.result()
    first, count, _ = _held(cfg)
    with jax.default_device(_host()):
        ref, scores = reference.forward_routed(
            weights, jnp.asarray(toks), _reference_cfg(cfg), routes=routes,
            held=(first, count))
        ref, scores = np.asarray(ref), [np.asarray(s) for s in scores]
    out = C.compare_logits(ours, ref, REFERENCE_TOL)
    out.update(check_routes(routes, scores, ROUTE_MARGIN))
    out["ok"] = out["ok"] and out["routes_ok"]
    return out
