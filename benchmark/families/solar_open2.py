"""Family "solar_open2": builds a serving handle for a Solar-Open2
configuration file (incremental decoding; one chip's share of an
expert-parallel deployment; three layers in four keep a recurrent state a
row beside one gated GQA layer's plain k/v cache), and holds what the
yardstick needs to know about the family's shapes: which layers are of which
kind, the bytes of a cache position, of a row's recurrent state and of one
held expert, the arithmetic of one routed pair, and the bytes a whole decode
step must read."""

from __future__ import annotations

import time

import numpy as np

from . import _common as C
# what the expert families do alike: the warm-up that holds the expert
# kernel to its compiled path, the check of the routes
from .olmoe import check_routes, warm_and_check  # noqa: F401
# the process's CPU device, where the float32 reference runs
from .exaone_moe import _host
# the timed path's own programs a step at a time on one slot, with the
# logits and other values of the graph read off it
from .zaya import Steps as _Steps
from .zaya import _joined

# Reference check, logits: relative L2 error, worst position (bfloat16
# compute and k/v cache and a FLOAT32 recurrent state and tails against
# float32 on the same dequantised int8 weights, on the program's routes).
# The readings at the published widths on the chip are in PERF.md section 6
# (PR 54): the program 0.0252 (the reference with bfloat16 matmul inputs
# reads 0.0241 against itself: the model's own sensitivity), the smallest
# knock-out 0.410 (the factor 2 on beta), float8 matmul inputs 0.441. The
# limit is 2.4 times the one and a seventh of the other. A bfloat16 STATE
# reads 0.0105, under the program's own reading: STATE_TOL below holds it.
REFERENCE_TOL = 0.06
# One whole period of the layer pattern: the gated GQA layer, then three KDA
# layers (a state written from another KDA layer's output is read too).
REFERENCE_LAYERS = 4
# The prompt of the check: TWO whole chunks that one compact step carries as
# consecutive segments of one slot (the first from zeros, the second from
# the step: the hand-over), then a ragged segment in a step of its own (its
# state from the store), then tokens decoded one a step through the state
# and the cache.
REFERENCE_CHUNKS = 2
REFERENCE_RAGGED = 45
REFERENCE_DECODED = 6
# Reference check, the state's precision: relative Frobenius error of the
# recurrent state the FIRST KDA layer holds after the check's last PREFILL
# step (the hand-over step and the ragged one), against the literal float32
# recurrence fed the PROGRAM'S OWN normed inputs of that layer (a graph
# value, read like the routes). The logits cannot tell a bfloat16 state
# from bfloat16 activations (the state's rounding reads BELOW the program's
# own error there); on given inputs everything before the layer cancels and
# what is left is the op's own arithmetic: the chunked form's products at
# highest precision and the dtype of what is carried (state and tails).
# Read after the prefill steps and not after the decode steps because a
# decode step's inputs cannot be given exactly: XLA:TPU drops the bfloat16
# rounding between the norm and the small-M projections of a decode step,
# so the program computes from MORE precise inputs than the graph value it
# hands out, and the state read 0.0007 further from the reference a decode
# step, kernel or jnp path alike (PERF.md section 6, PR 54: my chip runs;
# the kernel itself read 1.5e-6 against float64 on given inputs). The
# readings on the chip are in PERF.md section 6 (PR 54); the limit lies
# between the program's and a bfloat16 state's.
STATE_TOL = 5e-4
# the slot of the cut's four that the check's request lives in (not row 0 of
# the compact batch: the row map is read)
REFERENCE_SLOT = 1
# Reference check, routing: families/exaone_moe.py has the reasoning and
# the router is the same (sigmoid scores plus a bias, the 8 largest, here
# of 320): a pick outside the reference's own top-8 has to be an expert
# whose biased reference score is within this relative margin of the
# reference's 8th largest.
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
    from flexflow_tpu.models.solar_open2 import SolarOpen2Config

    hf = _reference_cfg(cfg)
    if layers is not None:
        hf["num_hidden_layers"] = layers
    return SolarOpen2Config.from_hf_config(hf)


def _build_model(cfg: dict, telemetry: bool, layers=None, **overrides):
    """``_common.build_model`` with the weights' dtype from the file
    (``assumed.weights_dtype``, bfloat16 unless the rehearsal says float32:
    at its tiny widths bfloat16 activations alone read 0.05 through three
    KDA layers, and in float32 the dry run checks the hand-over, the ragged
    segment and the decode steps to 1e-5)."""
    import flexflow_tpu as ff
    from flexflow_tpu.models.solar_open2 import create_solar_open2_model

    dt = {"bfloat16": ff.DataType.DT_BFLOAT16, "float32": ff.DataType.DT_FLOAT
          }[cfg["assumed"].get("weights_dtype", "bfloat16")]
    m = ff.FFModel(C.ffconfig(cfg, telemetry, **overrides))
    create_solar_open2_model(m, _model_cfg(cfg, layers),
                             mode=ff.InferenceMode.INC_DECODING_MODE,
                             data_type=dt)
    m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    return m


def build(cfg: dict, telemetry: bool) -> dict:
    from flexflow_tpu.serve.loadgen import EngineHandle

    llm = _build_model(cfg, telemetry)
    return {"handle": EngineHandle(llm), "llm": llm, "models": [llm]}


# ---- the family's shapes, for the per-layer readers -----------------------

def layers_of(cfg: dict, kind: str) -> int:
    """How many of the configuration's layers are ``kind``: "full" (the
    gated GQA layers, a plain k/v cache), "recurrent" (the KDA layers) or
    "sparse" (every layer)."""
    L = cfg["num_hidden_layers"]
    full = sum(1 for i in cfg["gqa_layers"] if i < L)
    return {"full": full, "recurrent": L - full, "sparse": L}.get(kind, 0)


def cache_position_bytes(cfg: dict) -> float:
    """Bytes one cache position costs ONE GQA layer: k and v, bf16 (4096 B
    at the published widths)."""
    return 2.0 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2


def state_bytes(cfg: dict) -> float:
    """Bytes of ONE row's recurrent state in ONE KDA layer: heads x K x V
    float32 (4.19 MB at the published widths)."""
    lin = cfg["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"] * lin["head_dim"] * 4.0


def conv_tail_bytes(cfg: dict) -> float:
    """Bytes of ONE row's convolution tails in ONE KDA layer: the last
    ``taps - 1`` unmixed q, k, v, float32."""
    lin = cfg["linear_attn_config"]
    return ((lin["short_conv_kernel_size"] - 1) * 3 * lin["num_heads"]
            * lin["head_dim"] * 4.0)


def state_step_bytes(cfg: dict, row_steps: float) -> float:
    """Bytes the recurrent kernel must move for ``row_steps`` live rows of
    one layer-step each: the state in and out, q, k, g, v, beta in and o
    out, float32 (kernels/linear_attention.state_step_bytes, counted
    here)."""
    lin = cfg["linear_attn_config"]
    H, K = lin["num_heads"], lin["head_dim"]
    return row_steps * H * (2.0 * K * K + 3 * K + 2 * K + 1) * 4


def expert_bytes(cfg: dict) -> float:
    """Bytes of one routed expert: gate, up and down with their scales."""
    H, I = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return 3 * H * I * C.weight_element_bytes(cfg) + (2 * I + H) * 4.0


def pair_flops(cfg: dict) -> float:
    """Arithmetic of one computed (token, expert) pair: three H x I gemvs."""
    return 6.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_weights(cfg: dict):
    """(name, rows, cols, bytes per element) of everything one decode step
    multiplies by OUTSIDE the routed experts: what every step reads whatever
    its router does. int8 payload plus the float32 scale per column where
    the program's rule quantises (configs/solar-open2-250b.json
    ``assumed.int8``), bf16 or float32 elsewhere. The embedding is a row a
    token and is not counted; the head's slice is."""
    E, V = cfg["hidden_size"], cfg["vocab_size"]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    lin = cfg["linear_attn_config"]
    H, K, taps = (lin["num_heads"], lin["head_dim"],
                  lin["short_conv_kernel_size"])
    _, _, width = _held(cfg)
    Is = cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    b, r = C.weight_element_bytes(cfg), lin["head_dim"]
    gqa = [("wq", E, nh * hd, b), ("wk", E, nkv * hd, b),
           ("wv", E, nkv * hd, b), ("wg", E, nh * hd, b),
           ("wo", nh * hd, E, b),
           ("attn.scales", 1, 2 * nh * hd + 2 * nkv * hd + E, 4.0)]
    kda = [("wqkv", E, 3 * H * K, b), ("wlow", E, 2 * r + H, b),
           ("wfb", r, H * K, b), ("wgb", r, H * K, b), ("wo", H * K, E, b),
           ("attn.scales", 1, 5 * H * K + 2 * r + H + E, 4.0),
           ("conv", taps, 3 * H * K, 2.0), ("dt_bias", 1, H * K, 4.0),
           ("A_log", 1, H, 4.0), ("o_norm", 1, K, 2.0)]
    every = [("norms", 1, 2 * E, 2.0), ("router", E, width, b),
             ("router.scale", 1, width, 4.0), ("router.bias", 1, width, 4.0),
             ("shared.gate", E, Is, b), ("shared.up", E, Is, b),
             ("shared.down", Is, E, b), ("shared.scales", 1, 2 * Is + E, 4.0)]
    out = []
    for i in range(cfg["num_hidden_layers"]):
        mix = gqa if i in cfg["gqa_layers"] else kda
        out += [(f"layers.{i}.{n}", r_, c, e) for n, r_, c, e in mix + every]
    return out + [("lm_head", E, V, b), ("lm_head.scale", 1, V, 4.0),
                  ("norm", 1, E, 2.0)]


def decode_weights(cfg: dict):
    """``dense_weights`` and ALL the held experts: an upper count of what a
    decode step multiplies by (a step reads the experts its tokens chose:
    ``decode_step_must_read``)."""
    E, I = cfg["hidden_size"], cfg["moe_intermediate_size"]
    b = C.weight_element_bytes(cfg)
    out = dense_weights(cfg)
    for i in range(cfg["num_hidden_layers"]):
        for e in range(cfg["n_routed_experts"]):
            p = f"layers.{i}.experts.{e}"
            out += [(f"{p}.gate", E, I, b), (f"{p}.up", E, I, b),
                    (f"{p}.down", I, E, b),
                    (f"{p}.scales", 1, 2 * I + E, 4.0)]
    return out


def cache_bytes_per_token(cfg: dict) -> float:
    """Cache bytes a live position adds to a decode step's reads: the GQA
    layers' (a KDA layer reads its state whatever the length)."""
    return cache_position_bytes(cfg) * layers_of(cfg, "full")


def decode_step_must_read(cfg: dict, experts_touched: float,
                          layer_positions: float, rows: float) -> float:
    """Bytes ONE decode step must move: everything outside the experts
    once, ``experts_touched`` experts in each layer, ``layer_positions``
    cache positions (summed over the step's rows and the GQA layers) at a
    position's bytes, and the recurrent state of ``rows`` live rows read AND
    written once in each KDA layer, with the tails. Not all 40 held experts:
    a step reads the experts its rows chose."""
    dense = sum(r * c * e for _, r, c, e in dense_weights(cfg))
    return (dense
            + experts_touched * layers_of(cfg, "sparse") * expert_bytes(cfg)
            + layer_positions * cache_position_bytes(cfg)
            + rows * layers_of(cfg, "recurrent")
            * 2 * (state_bytes(cfg) + conv_tail_bytes(cfg)))


# ---- the reference check --------------------------------------------------

def reference_weights(m, mc):
    """The served weights, dequantised to float32 on the device and brought
    to the host, the layers one at a time (a generator), under the names
    benchmark/reference/solar_open2.py reads."""
    p = m.params

    def dense(leaf):
        return np.asarray(C.dense(leaf))

    def one(name, weight="weight"):
        return dense(p[name][weight])

    def layers():
        r = mc.linear_head_dim
        hk = mc.linear_num_heads * mc.linear_head_dim
        for i in range(mc.num_hidden_layers):
            ly = f"layers.{i}"
            a, x = p[f"{ly}.self_attn"], p[f"{ly}.mlp.experts"]
            lw = {"ln1": one(f"{ly}.input_layernorm"),
                  "ln2": one(f"{ly}.post_attention_layernorm"),
                  "router": one(f"{ly}.mlp.gate", "kernel"),
                  "bias": one(f"{ly}.mlp.gate.e_score_correction_bias"),
                  "gate": dense(x["gate"]), "up": dense(x["up"]),
                  "down": dense(x["down"]),
                  **{f"s_{n}": one(f"{ly}.mlp.shared_experts.{n}_proj",
                                   "kernel") for n in ("gate", "up", "down")}}
            if mc.kind(i) == "gqa":
                lw.update({k: dense(a[k]) for k in a})
            else:
                # the program holds [Wq | Wk | Wv] and [W_fa | W_ga | w_b] as
                # one array each
                wq, wk, wv = np.split(dense(a["wqkv"]), [hk, 2 * hk], axis=1)
                wfa, wga, wb = np.split(dense(a["wlow"]), [r, 2 * r], axis=1)
                lw.update(wq=wq, wk=wk, wv=wv, wfa=wfa, wga=wga, wb=wb,
                          wfb=dense(a["wfb"]), wgb=dense(a["wgb"]),
                          **{k: dense(a[k]) for k in (
                              "conv", "A_log", "dt_bias", "o_norm", "wo")})
            yield lw

    return {"emb": one("embed_tokens"), "layers": layers(),
            "norm": one("norm"), "head": one("lm_head", "kernel")}


class Steps(_Steps):
    """families/zaya.Steps with one value more read off the graph: the
    normed input of the model's first KDA layer, last in the list of the
    routes."""

    def __init__(self, model, slot: int = 0):
        import jax
        import jax.numpy as jnp

        from flexflow_tpu.ffconst import OpType
        from flexflow_tpu.serve.engine import forward_with_meta

        self.model, self.slot = model, slot
        logits_t = model.layers[-1].inputs[0]
        more = [ly.inputs[1] for ly in model.layers
                if ly.op_type == OpType.MOE_EXPERTS]
        more.append(next(ly.inputs[0] for ly in model.layers
                         if ly.op_type == OpType.INC_KDA_ATTENTION))
        cdt = jnp.dtype(model.config.compute_dtype)

        def run(params, state, meta, decode):
            (logits, *got), state = forward_with_meta(
                model, params, state, meta, None, cdt, kv_contiguous=decode,
                outputs=[logits_t] + more)
            return logits.astype(jnp.float32), got, state

        self._run = jax.jit(run, donate_argnums=(1,), static_argnums=(3,))


def drive(model, toks, plan, slot: int = 0, after_prefill=None):
    """``toks`` through ``Steps`` on ``slot``: ``plan`` lists the steps, a
    list of segment lengths for a compact prefill step or 1 for a decode
    step; ``after_prefill()`` is called behind the last prefill step.
    Returns (logits [T, V], routes per layer, the first KDA layer's normed
    inputs [T, E])."""
    run, parts, at = Steps(model, slot), [], 0
    last_prefill = max((i for i, s in enumerate(plan) if s != 1), default=-1)
    for i, step in enumerate(plan):
        if step == 1:
            parts.append(run.decode(int(toks[at]), at))
            at += 1
        else:
            parts.append(run.prefill(toks[at:at + sum(step)], at, step))
            at += sum(step)
        if i == last_prefill and after_prefill is not None:
            after_prefill()
    assert at == len(toks), (at, len(toks))
    logits, got = _joined(parts)
    return logits, got[:-1], got[-1]


def held_state(model, slot: int, layer: int = 0):
    """The recurrent state ``[H, K, V]`` slot ``slot`` holds in the model's
    ``layer``-th KDA layer, as float32 on the host."""
    from flexflow_tpu.ops.inc_attention import RECURRENT_STACK

    return np.asarray(model.op_state[RECURRENT_STACK]["s"][layer, slot],
                      np.float32)


def state_error(cfg: dict, reference, lw, inputs, held, **kw) -> float:
    """Relative Frobenius error of ``held [H, K, V]`` against the literal
    recurrence of KDA layer ``lw`` on the normed ``inputs [T, E]``."""
    import jax

    lin = cfg["linear_attn_config"]
    with jax.default_device(_host()):
        want = np.asarray(reference.kda_state(
            lw, np.asarray(inputs, np.float32), H=lin["num_heads"],
            K=lin["head_dim"], eps=cfg.get("rms_norm_eps", 1e-5), **kw),
            np.float64)
    return float(np.linalg.norm(held - want) / np.linalg.norm(want))


def reference_run(cfg: dict):
    """Drive the program at the published widths on the reference check's
    cut: ``(tokens, the program's logits, its routes, the first KDA layer's
    inputs and the state it holds at the end, the weights for the
    reference, seconds)``; the state is the one held behind the last
    prefill step, the inputs those up to it."""
    from concurrent.futures import ThreadPoolExecutor

    t_build = time.perf_counter()
    chunk = C.prefill_chunk(cfg)
    layers = min(REFERENCE_LAYERS, cfg["num_hidden_layers"])
    mc = _model_cfg(cfg, layers)
    # four slots, so that the cut's compact batch is the cell's own shape
    # (four segments of a chunk: RequestManager._prefill_shape)
    m = _build_model(cfg, False, layers, max_requests_per_batch=4)
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
        held = []
        ours, routes, inputs = drive(
            m, toks, plan, REFERENCE_SLOT,
            after_prefill=lambda: held.append(held_state(m, REFERENCE_SLOT)))
        inputs = inputs[:len(toks) - REFERENCE_DECODED]
        t1 = time.perf_counter()
        weights = coming.result()
    t2 = time.perf_counter()
    return (toks, ours, routes, (inputs, held[0]), weights,
            [t0 - t_build, t1 - t0, t2 - t1])


def reference_logits(cfg: dict, reference, weights, toks, routes, **kw):
    """The reference, on the host's CPU: (logits, [biased scores] per
    layer) as numpy."""
    import jax

    first, count, _ = _held(cfg)
    with jax.default_device(_host()):
        ref, scores = reference.forward_routed(
            weights, np.asarray(toks), _reference_cfg(cfg), routes=routes,
            held=(first, count), **kw)
        return np.asarray(ref), [np.asarray(s) for s in scores]


# what the reference computes when it is asked to be wrong on purpose
# (``reference_check(variants=)``): keyword arguments of ``forward_routed``
VARIANTS = {
    "beta2_out": {"without": ("beta2",)},
    "decay_out": {"without": ("decay",)},
    "conv_tap_out": {"without": ("conv_tap",)},
    "o_gate_out": {"without": ("o_gate",)},
    "gqa_gate_out": {"without": ("gqa_gate",)},
    # the nearest precision below the configuration's: the state in bfloat16
    "bfloat16_state": {"state_dtype": "bfloat16"},
    "float8": {"matmul_dtype": "float8_e4m3fn"},
    # what the served precision itself costs this model: no fault
    "bfloat16": {"matmul_dtype": "bfloat16"},
}


def reference_check(cfg: dict, reference, variants=()) -> dict:
    """One period (layers 0-3) at the published widths with the held range,
    the same seeded weights as served, the timed path's own programs: two
    consecutive segments of one slot in ONE compact prefill step (the
    hand-over), a ragged segment in the next (its state from the store),
    then six tokens decoded through the state and the cache. The routes the
    program took are checked against the reference's biased scores, the
    logits, at all positions, against the reference run on those routes
    (ROUTE_MARGIN), and the state the first KDA layer holds behind the
    last prefill step against the literal recurrence on that layer's own
    inputs (STATE_TOL).
    ``variants`` (names of ``VARIANTS``; by hand,
    tools/check_solar_variants.py): beside the program's reading, what the
    reference reads against ITSELF, on the same routes, with a term left
    out or a precision lowered, as ``wrong_<name>``."""
    import jax.numpy as jnp

    toks, ours, routes, (inputs, held), weights, seconds = reference_run(cfg)
    t = time.perf_counter()
    ref, scores = reference_logits(cfg, reference, weights, toks, routes)
    out = C.compare_logits(ours, ref, REFERENCE_TOL)
    out.update(check_routes(routes, scores, ROUTE_MARGIN))
    first_kda = next(lw for lw in weights["layers"] if "A_log" in lw)
    out["state_rel_err"] = state_error(cfg, reference, first_kda, inputs,
                                       held)
    out["state_tol"] = STATE_TOL
    out["ok"] = (out["ok"] and out["routes_ok"]
                 and out["state_rel_err"] < STATE_TOL)
    # where a cold run's minute goes: the cut's build, its programs
    # (compiled, then run), the wait for the weights, the reference
    out["seconds"] = [round(x, 1) for x in
                      seconds + [time.perf_counter() - t]]
    for name in variants:
        kw = {k: getattr(jnp, v) if k.endswith("dtype") else v
              for k, v in VARIANTS[name].items()}
        wrong, _ = reference_logits(cfg, reference, weights, toks, routes,
                                    **kw)
        out[f"wrong_{name}"] = C.compare_logits(wrong, ref, REFERENCE_TOL)[
            "max_rel_l2"]
    if "bfloat16_state" in variants:    # and what the state's check reads
        exact = np.asarray(reference.kda_state(
            first_kda, np.asarray(inputs, np.float32),
            H=held.shape[0], K=held.shape[1]), np.float64)
        out["wrong_bfloat16_state_state"] = state_error(
            cfg, reference, first_kda, inputs, exact,
            state_dtype=jnp.bfloat16)
    return out
