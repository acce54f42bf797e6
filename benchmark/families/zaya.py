"""Family "zaya": builds a serving handle for a ZAYA1 configuration file
(incremental decoding; attention in a convolved latent whose rows carry a
tail from step to step beside a plain grouped k/v cache; a top-1 router that
is an MLP fed by the layer before, with an output that names no expert), and
holds what the yardstick needs to know about the family's shapes: the bytes
a cache position costs a layer, one expert's bytes, the arithmetic of one
routed (token, expert) pair, and the bytes a whole decode step must read."""

from __future__ import annotations

import time

import numpy as np

from . import _common as C
# what the expert families do alike: the warm-up that holds the expert
# kernel to its compiled path, the check of the routes
from .olmoe import check_routes, warm_and_check  # noqa: F401

# Reference check, logits: relative L2 error, worst position (bfloat16
# compute, cache and tail against float32 on the same dequantised int8
# weights, on the program's routes). The readings at the published widths,
# two layers, 469 positions through the compact prefill and the decode step,
# on the chip are in PERF.md section 6 (PR 50) beside each knock-out's; the
# limit lies between the program's reading and the smallest wrong one's.
REFERENCE_TOL = 0.05
# Every layer is alike, so a period is one; two, so that a cache and a tail
# written from another layer's output, and a router fed by the layer
# before (``gamma * r_prev``), are read too.
REFERENCE_LAYERS = 2
# The prompt of the check: THREE whole chunks that one compact step carries
# as consecutive segments of one slot (the first from zeros, two from the
# step), then a ragged segment in a step of its own (its tail from the
# state), then tokens decoded one a step through the cache and the tail.
REFERENCE_CHUNKS = 3
REFERENCE_RAGGED = 77
REFERENCE_DECODED = 8
# the slot of the cut's two that the check's request lives in (not row 0 of
# the compact batch: the row map is read)
REFERENCE_SLOT = 1
# Reference check, routing: families/olmoe.py has the reasoning. Here ONE
# pick a token is made on float32 softmax scores over 17 plus a bias, from a
# bfloat16 router state. A pick of the program that is not the reference's
# own has to be an output whose biased reference score is within this
# relative margin of the reference's largest.
ROUTE_MARGIN = 0.25


def _hf(cfg: dict) -> dict:
    """The configuration as the builder reads it: the router's seeded
    initialiser beside the published keys."""
    return {**cfg, "router_init_std": cfg["assumed"].get("router_init_std")}


def _model_cfg(cfg: dict, layers=None):
    from flexflow_tpu.models.zaya import ZayaConfig

    hf = _hf(cfg)
    if layers is not None:
        hf["num_hidden_layers"] = layers
    return ZayaConfig.from_hf_config(hf)


def build(cfg: dict, telemetry: bool) -> dict:
    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models.zaya import create_zaya_model
    from flexflow_tpu.serve.loadgen import EngineHandle

    llm = C.build_model(C.ffconfig(cfg, telemetry), create_zaya_model,
                        _model_cfg(cfg), InferenceMode.INC_DECODING_MODE)
    return {"handle": EngineHandle(llm), "llm": llm, "models": [llm]}


# ---- the family's shapes, for the per-layer readers -----------------------

def layers_of(cfg: dict, kind: str) -> int:
    """How many layers of ``kind`` the configuration has: every layer is an
    attention sublayer over a "full" cache and a "sparse" (routed) one."""
    return cfg["num_hidden_layers"] if kind in ("full", "sparse") else 0


def cache_position_bytes(cfg: dict) -> float:
    """Bytes of one cache position of ONE layer: the rotated keys and the
    values of its key/value heads, bf16 (1024 B at the published widths)."""
    return 2.0 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2


def expert_bytes(cfg: dict) -> float:
    """Bytes of one expert: gate, up and down with their scales."""
    H, I = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return 3 * H * I * C.weight_element_bytes(cfg) + (2 * I + H) * 4.0


def pair_flops(cfg: dict) -> float:
    """Arithmetic of one routed (token, expert) pair: three H x I gemvs. A
    pick of the skip output is no pair and costs nothing."""
    return 6.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_weights(cfg: dict):
    """(name, rows, cols, bytes per element) of everything one decode step
    multiplies by OUTSIDE the routed experts: what every step reads whatever
    its router does. int8 payload plus the float32 scale per column where
    the program's rule quantises (configs/zaya1-8b.json ``assumed.int8``),
    bf16 elsewhere. The table is counted ONCE: the head reads all of it,
    the embedding a row a token of the same array."""
    E, V = cfg["hidden_size"], cfg["vocab_size"]
    H, G, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    Rw, n = cfg["router_hidden_size"], cfg["num_experts"] + 1
    Lq, Lk, Dv, Ch = H * D, G * D, G * D // 2, (H + G) * D
    b = C.weight_element_bytes(cfg)
    per_layer = [
        ("wq", E, Lq, b), ("wk", E, Lk, b), ("wv1", E, Dv, b),
        ("wv2", E, Dv, b), ("wo", Lq, E, b),
        ("attn.scales", 1, Lq + Lk + 2 * Dv + E, 4.0),
        ("conv0", 3, Ch, 2.0), ("conv1", (H + G) * 2 * D, D, 2.0),
        ("conv1.bias", 1, Ch, 2.0), ("tau", 1, G, 2.0),
        ("norms", 1, 2 * E + Rw, 2.0), ("res_scale", 8, E, 2.0),
        ("router.wd", E, Rw, b), ("router.w1", Rw, Rw, b),
        ("router.w2", Rw, Rw, b), ("router.scales", 1, 3 * Rw, 4.0),
        ("router.w3", Rw, n, 2.0), ("router.bias", 1, n, 4.0),
        ("router.gamma", 1, 1, 2.0)]
    out = [(f"layers.{i}.{name}", r, c, e)
           for i in range(cfg["num_hidden_layers"])
           for name, r, c, e in per_layer]
    return out + [("table", V, E, b), ("table.scale", 1, E, 4.0),
                  ("norm", 1, E, 2.0)]


def decode_weights(cfg: dict):
    """``dense_weights`` and ALL the experts: an upper count of what a decode
    step multiplies by (a step reads the experts its tokens chose:
    ``decode_step_must_read``)."""
    E, I = cfg["hidden_size"], cfg["moe_intermediate_size"]
    b = C.weight_element_bytes(cfg)
    out = dense_weights(cfg)
    for i in range(cfg["num_hidden_layers"]):
        for e in range(cfg["num_experts"]):
            p = f"layers.{i}.experts.{e}"
            out += [(f"{p}.gate", E, I, b), (f"{p}.up", E, I, b),
                    (f"{p}.down", I, E, b),
                    (f"{p}.scales", 1, 2 * I + E, 4.0)]
    return out


def cache_bytes_per_token(cfg: dict) -> float:
    """Cache bytes a live position adds to a decode step's reads."""
    return cache_position_bytes(cfg) * layers_of(cfg, "full")


def decode_step_must_read(cfg: dict, experts_touched: float,
                          layer_positions: float) -> float:
    """Bytes ONE decode step must read: everything outside the experts
    once (the table once), ``experts_touched`` experts in each layer, and
    ``layer_positions`` cache positions (summed over the step's rows and the
    layers) at a position's bytes. Not all 16 experts: a step reads the
    experts its rows chose."""
    dense = sum(r * c * e for _, r, c, e in dense_weights(cfg))
    return (dense
            + experts_touched * layers_of(cfg, "sparse") * expert_bytes(cfg)
            + layer_positions * cache_position_bytes(cfg))


# ---- the reference check --------------------------------------------------

def reference_weights(m, mc):
    """The served weights, dequantised to float32 on the device and brought
    to the host, the layers one at a time (a generator), under the names
    benchmark/reference/zaya.py reads."""
    p = m.params

    def dense(leaf):
        return np.asarray(C.dense(leaf))

    def one(name):
        return dense(p[name]["weight"])

    def layers():
        for i in range(mc.num_hidden_layers):
            ly = f"layers.{i}"
            a, x = p[f"{ly}.self_attn"], p[f"{ly}.mlp.experts"]
            # the program holds [Wq | Wk | Wv1 | Wv2] and the convolutions'
            # vectors (two taps, two biases) as one array each
            lq, lk = (mc.num_attention_heads * mc.head_dim,
                      mc.num_key_value_heads * mc.head_dim)
            wq, wk, wv1, wv2 = np.split(
                dense(a["wqkv"]), [lq, lq + lk, lq + lk + lk // 2], axis=1)
            vec = dense(a["conv_vec"])
            lw = {"ln1": one(f"{ly}.input_layernorm"),
                  "ln2": one(f"{ly}.post_attention_layernorm"),
                  "wq": wq, "wk": wk, "wv1": wv1, "wv2": wv2,
                  "conv0_w": vec[:2], "conv0_b": vec[2], "conv1_b": vec[3],
                  **{k: dense(a[k]) for k in ("wo", "conv1_w", "tau")},
                  "res_attn": one(f"{ly}.self_attn.res_scale"),
                  "res_mlp": one(f"{ly}.mlp.res_scale"),
                  "wd": dense(p[f"{ly}.mlp.router.down_proj"]["kernel"]),
                  "rn": one(f"{ly}.mlp.router.norm"),
                  **{f"w{j}": dense(p[f"{ly}.mlp.router.fc{j}"]["kernel"])
                     for j in (1, 2, 3)},
                  "bias": one(f"{ly}.mlp.router.balancing_bias"),
                  "gate": dense(x["gate"]), "up": dense(x["up"]),
                  "down": dense(x["down"])}
            if i:
                lw["gamma"] = float(one(f"{ly}.mlp.router.eda_gamma")[0])
            yield lw

    return {"emb": one("embed_tokens"), "layers": layers(),
            "norm": one("norm")}


class Steps:
    """The timed path's own programs on ONE slot of ``model``, a step at a
    time, with the logits and the routes read as values of the graph: the
    compact prefill (serve/request_manager._meta_from_segments: several
    segments a step, of one slot where the caller says so) and the decode
    block's body (serve/engine.forward_with_meta as make_decode_block calls
    it: one token a row on the slot grid, ``kv_contiguous``)."""

    def __init__(self, model, slot: int = 0):
        import jax
        import jax.numpy as jnp

        from flexflow_tpu.ffconst import OpType
        from flexflow_tpu.serve.engine import forward_with_meta

        self.model, self.slot = model, slot
        logits_t = model.layers[-1].inputs[0]
        chosen_t = [ly.inputs[1] for ly in model.layers
                    if ly.op_type == OpType.MOE_EXPERTS]
        cdt = jnp.dtype(model.config.compute_dtype)

        def run(params, state, meta, decode):
            (logits, *chosen), state = forward_with_meta(
                model, params, state, meta, None, cdt, kv_contiguous=decode,
                outputs=[logits_t] + chosen_t)
            return logits.astype(jnp.float32), chosen, state

        self._run = jax.jit(run, donate_argnums=(1,), static_argnums=(3,))

    def _step(self, meta, decode):
        m = self.model
        logits, chosen, m.op_state = self._run(m.params, m.op_state, meta,
                                               decode)
        return np.asarray(logits), [np.asarray(c) for c in chosen]

    def prefill(self, tokens, at: int, lengths):
        """ONE compact step: ``tokens`` from position ``at`` as consecutive
        segments of ``lengths`` tokens, all the slot's. Returns (logits,
        routes) of them, in order."""
        from flexflow_tpu.serve.request_manager import RequestManager as RM

        chunk, segments = RM._prefill_shape(self.model.config)
        assert len(lengths) <= segments and max(lengths) <= chunk
        rows, start = [], 0
        for n in lengths:
            rows.append((self.slot, list(tokens[start:start + n]),
                         at + start))
            start += n
        logits, chosen = self._step(
            RM._meta_from_segments(segments, chunk, rows), False)
        return _joined([(logits[i, :n], [c[i, :n] for c in chosen])
                        for i, n in enumerate(lengths)])

    def decode(self, token: int, at: int):
        """One decode step of the slot's row: ``token`` at position ``at``."""
        from flexflow_tpu.serve.batch_config import BatchMeta

        R = self.model.config.max_requests_per_batch
        act = np.arange(R) == self.slot
        pos = np.where(act, at, 0).astype(np.int32)
        meta = BatchMeta(
            tokens=np.where(act, token, 0).astype(np.int32)[:, None],
            positions=pos[:, None], start_pos=pos,
            num_tokens=act.astype(np.int32), active=act)
        logits, chosen = self._step(meta, True)
        return logits[self.slot], [c[self.slot] for c in chosen]


def _joined(parts):
    return (np.concatenate([p[0] for p in parts], axis=0),
            [np.concatenate([p[1][j] for p in parts], axis=0)
             for j in range(len(parts[0][1]))])


def drive(model, toks, plan, slot: int = 0):
    """``toks`` through ``Steps`` on ``slot``: ``plan`` lists the steps, a
    list of segment lengths for a compact prefill step or 1 for a decode
    step. Returns (logits [T, V], routes)."""
    run, parts, at = Steps(model, slot), [], 0
    for step in plan:
        if step == 1:
            parts.append(run.decode(int(toks[at]), at))
            at += 1
        else:
            parts.append(run.prefill(toks[at:at + sum(step)], at, step))
            at += sum(step)
    assert at == len(toks), (at, len(toks))
    return _joined(parts)


def reference_run(cfg: dict):
    """Drive the program at the published widths on the reference check's
    cut: ``(tokens, the program's logits, its routes, the weights for the
    reference, seconds)``."""
    from concurrent.futures import ThreadPoolExecutor

    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models.zaya import create_zaya_model

    t_build = time.perf_counter()
    chunk = C.prefill_chunk(cfg)
    mc = _model_cfg(cfg, min(REFERENCE_LAYERS, cfg["num_hidden_layers"]))
    # four slots, so that the cut's compact batch is the cell's own shape
    # (four segments of a chunk: RequestManager._prefill_shape)
    m = C.build_model(C.ffconfig(cfg, False, max_requests_per_batch=4),
                      create_zaya_model, mc,
                      InferenceMode.INC_DECODING_MODE)
    ragged = min(REFERENCE_RAGGED, chunk - 1)
    plan = ([[chunk] * REFERENCE_CHUNKS, [ragged]]
            + [1] * REFERENCE_DECODED)
    toks = np.random.default_rng(cfg["weights_seed"]).integers(
        1, cfg["vocab_size"],
        size=chunk * REFERENCE_CHUNKS + ragged + REFERENCE_DECODED)
    # the weights come over to the host while the program compiles and runs
    t0 = time.perf_counter()
    with ThreadPoolExecutor(1) as pool:
        coming = pool.submit(
            lambda w: {**w, "layers": list(w["layers"])},
            reference_weights(m, mc))
        ours, routes = drive(m, toks, plan, REFERENCE_SLOT)
        t1 = time.perf_counter()
        weights = coming.result()
    t2 = time.perf_counter()
    return toks, ours, routes, weights, [t0 - t_build, t1 - t0, t2 - t1]


def reference_logits(cfg: dict, reference, weights, toks, routes, **kw):
    """The reference (numpy, on the host): (logits, [biased scores] per
    layer)."""
    return reference.forward_routed(weights, toks, cfg, routes=routes, **kw)


# what the reference computes when it is asked to be wrong on purpose
# (``reference_check(variants=)``): keyword arguments of ``forward_routed``
VARIANTS = {
    "conv_tap_out": {"without": ("conv_tap",)},
    "value_shift_out": {"without": ("value_shift",)},
    "eda_out": {"without": ("eda",)},
    "routed_out": {"without": ("routed",)},
    "float8": {"matmul_dtype": "float8_e4m3fn"},
    # what the served precision itself costs this model: no fault
    "bfloat16": {"matmul_dtype": "bfloat16"},
}


def reference_check(cfg: dict, reference, variants=()) -> dict:
    """Two layers at the published widths, the same seeded weights as
    served, the timed path's own programs: three consecutive segments of one
    slot in ONE compact prefill step, a ragged segment in the next (its tail
    from the state), then eight tokens decoded through the cache and the
    tail. The routes the program took are checked against the reference's
    biased scores, and the logits, at all positions, against the reference
    run on those routes (ROUTE_MARGIN). ``variants`` (names of ``VARIANTS``;
    by hand, tools/check_zaya_variants.py): beside the program's reading,
    what the reference reads against ITSELF, on the same routes, with a conv
    tap, the value's shift, ``gamma * r_prev`` or the routed term left out,
    or float8 matmul inputs, as ``wrong_<name>``."""
    import jax.numpy as jnp

    toks, ours, routes, weights, seconds = reference_run(cfg)
    t = time.perf_counter()
    ref, scores = reference_logits(cfg, reference, weights, toks, routes)
    out = C.compare_logits(ours, ref, REFERENCE_TOL)
    out.update(check_routes(routes, scores, ROUTE_MARGIN))
    out["ok"] = out["ok"] and out["routes_ok"]
    out["skipped"] = int(sum((r == cfg["num_experts"]).sum()
                             for r in routes))
    # where a cold run's minute goes: the cut's build, its programs
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
        took = [np.argmax(s, axis=-1)[:, None] for s in s8]
        f8 = check_routes(took, scores, ROUTE_MARGIN)
        out["float8_routes"] = [f8["route_flips"], f8["worst_flip"]]
    return out
