"""Family "granite_hybrid": builds a serving handle for a Granite-4.0-H
configuration file (incremental decoding; as many leading layers as the file
holds, with the embedding, the final norm and the tied head; nine layers in
ten are state-space mixers that keep a recurrent state a row beside a few
plain GQA layers' k/v caches; every feed-forward dense), and
holds what the yardstick needs to know about the family's shapes: which
layers are of which kind, the bytes of a cache position and of a row's
recurrent state, and the bytes a whole decode step must move."""

from __future__ import annotations

import time

import numpy as np

from . import _common as C
# the process's CPU device, where the float32 reference runs
from .exaone_moe import _host
# the serving loop's warm-up (no experts to hold to a path)
from .falcon import warm_and_check  # noqa: F401
# the state a slot holds in a recurrent layer, off the one stack
from .solar_open2 import held_state
# the timed path's own programs a step at a time on one slot, with the
# logits and other values of the graph read off it
from .zaya import Steps as _Steps

# Reference check, logits: relative L2 error, worst position (bfloat16
# compute and k/v cache and a FLOAT32 recurrent state and tail against
# float32 on the same dequantised int8 weights). The readings at the
# published widths on the chip are in PERF.md section 6 (PR 56; the check's
# weights and tokens come from ``weights_seed``, so every run reads the
# same): the program 0.00746 (the reference with bfloat16 matmul inputs reads
# 0.00643 against itself: the model's own sensitivity), the smallest
# knock-out 0.262 (``attention_multiplier`` at 1/8), float8 matmul inputs
# 0.126. The limit is 5.4 times the one and under a third of the other. A
# bfloat16 STATE reads 0.00071, under the program's own reading: STATE_TOL
# below holds it.
REFERENCE_TOL = 0.04
# A mixer whose state is written from another mixer's output, the attention
# layer between, a mixer behind it.
REFERENCE_LAYERS = 4
REFERENCE_LAYER_TYPES = ("mamba", "mamba", "attention", "mamba")
# The prompt of the check: TWO whole chunks that one compact step carries as
# consecutive segments of one slot (the first from zeros, the second from
# the step: the hand-over), then a ragged segment in a step of its own (its
# state from the store), then tokens decoded one a step through the state
# and the cache.
REFERENCE_CHUNKS = 2
REFERENCE_RAGGED = 45
REFERENCE_DECODED = 6
# Reference check, the state's precision: relative Frobenius error of the
# recurrent state the FIRST mixer holds after the check's last PREFILL step,
# against the literal float32 recurrence fed the PROGRAM'S OWN normed inputs
# of that layer (a graph value). families/solar_open2.py has the reasoning
# (the logits cannot tell a bfloat16 state from bfloat16 activations; a
# decode step's inputs cannot be given exactly). The readings on the chip
# are in PERF.md section 6 (PR 56): the program 5.5e-5 (float32's own: a
# chunk's cumulative log decay can pass -100, where a float32 has five
# digits behind the point), a bfloat16 state 5.9e-3; the limit lies between,
# nine times the one and a twelfth of the other.
STATE_TOL = 5e-4
# the slot of the cut's four that the check's request lives in (not row 0 of
# the compact batch: the row map is read)
REFERENCE_SLOT = 1


def _model_cfg(cfg: dict, layer_types=None):
    from flexflow_tpu.models.granite_hybrid import GraniteHybridConfig

    hf = dict(cfg)
    if layer_types is not None:
        hf.update(layer_types=list(layer_types),
                  num_hidden_layers=len(layer_types))
    return GraniteHybridConfig.from_hf_config(hf)


def _build_model(cfg: dict, telemetry: bool, layer_types=None, **overrides):
    """``_common.build_model`` with the weights' dtype from the file
    (``assumed.weights_dtype``, bfloat16 unless the rehearsal says
    float32)."""
    import flexflow_tpu as ff
    from flexflow_tpu.models.granite_hybrid import create_granite_hybrid_model

    dt = {"bfloat16": ff.DataType.DT_BFLOAT16, "float32": ff.DataType.DT_FLOAT
          }[cfg["assumed"].get("weights_dtype", "bfloat16")]
    m = ff.FFModel(C.ffconfig(cfg, telemetry, **overrides))
    create_granite_hybrid_model(m, _model_cfg(cfg, layer_types),
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
    attention layers, a plain k/v cache) or "recurrent" (the mixers)."""
    types = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return {"full": types.count("attention"),
            "recurrent": types.count("mamba")}.get(kind, 0)


def cache_position_bytes(cfg: dict) -> float:
    """Bytes one cache position costs ONE attention layer: k and v, bf16
    (2048 B at the published widths)."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2.0 * cfg["num_key_value_heads"] * hd * 2


def _mixer(cfg: dict):
    """(H, P, N, conv channels) of a mixer layer."""
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    return H, P, N, H * P + 2 * cfg["mamba_n_groups"] * N


def state_bytes(cfg: dict) -> float:
    """Bytes of ONE row's recurrent state in ONE mixer: heads x P x N
    float32 (2.10 MB at the published widths)."""
    H, P, N, _ = _mixer(cfg)
    return H * P * N * 4.0


def conv_tail_bytes(cfg: dict) -> float:
    """Bytes of ONE row's convolution tail in ONE mixer: the last ``taps -
    1`` unmixed xBC, float32."""
    return (cfg["mamba_d_conv"] - 1) * _mixer(cfg)[3] * 4.0


def state_step_bytes(cfg: dict, row_steps: float) -> float:
    """Bytes the recurrent kernel must move for ``row_steps`` live rows of
    one layer-step each: the state in and out, dt * x in and y out, a decay
    a head, B and C, float32 (kernels/linear_attention.ssd_step_bytes,
    counted here)."""
    H, P, N, _ = _mixer(cfg)
    return row_steps * (H * (2.0 * P * N + 2 * P + 1) + 2 * N) * 4


def decode_weights(cfg: dict):
    """(name, rows, cols, bytes per element) of everything one decode step
    multiplies by: int8 payload plus the float32 scale per column where the
    program's rule quantises (configs/granite-4.0-h-micro.json
    ``assumed.int8``), bf16 or float32 elsewhere. The embedding's table is
    counted once, as the head (it is the same array; a step's rows of it as
    an embedding are a row a token)."""
    E, V, I = (cfg["hidden_size"], cfg["vocab_size"],
               cfg["shared_intermediate_size"])
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = E // nh
    H, P, N, conv = _mixer(cfg)
    taps = cfg["mamba_d_conv"]
    b = C.weight_element_bytes(cfg)
    win = 2 * H * P + 2 * N + H
    attention = [("wq", E, nh * hd, b), ("wk", E, nkv * hd, b),
                 ("wv", E, nkv * hd, b), ("wo", nh * hd, E, b),
                 ("attn.scales", 1, nh * hd + 2 * nkv * hd + E, 4.0)]
    mamba = [("win", E, win, b), ("wout", H * P, E, b),
             ("mamba.scales", 1, win + E, 4.0),
             ("conv", taps, conv, 2.0), ("conv_bias", 1, conv, 2.0),
             ("A_log", 1, H, 4.0), ("dt_bias", 1, H, 4.0), ("D", 1, H, 4.0),
             ("mamba.norm", 1, H * P, 2.0)]
    every = [("norms", 1, 2 * E, 2.0), ("mlp.in", E, 2 * I, b),
             ("mlp.out", I, E, b), ("mlp.scales", 1, 2 * I + E, 4.0)]
    out = []
    for i, kind in enumerate(cfg["layer_types"][:cfg["num_hidden_layers"]]):
        mix = attention if kind == "attention" else mamba
        out += [(f"layers.{i}.{n}", r, c, e) for n, r, c, e in mix + every]
    return out + [("lm_head", E, V, b), ("lm_head.scale", 1, V, 4.0),
                  ("norm", 1, E, 2.0)]


def cache_bytes_per_token(cfg: dict) -> float:
    """Cache bytes a live position adds to a decode step's reads: the
    attention layers' (a mixer reads its state whatever the length)."""
    return cache_position_bytes(cfg) * layers_of(cfg, "full")


def decode_step_must_read(cfg: dict, layer_positions: float,
                          rows: float) -> float:
    """Bytes ONE decode step must move: every weight once,
    ``layer_positions`` cache positions (summed over the step's rows and the
    attention layers) at a position's bytes, and the recurrent state of
    ``rows`` live rows read AND written once in each mixer, with the
    tails."""
    dense = sum(r * c * e for _, r, c, e in decode_weights(cfg))
    return (dense + layer_positions * cache_position_bytes(cfg)
            + rows * layers_of(cfg, "recurrent")
            * 2 * (state_bytes(cfg) + conv_tail_bytes(cfg)))


# ---- the reference check --------------------------------------------------

def reference_weights(m, mc):
    """The served weights, dequantised to float32 on the device and brought
    to the host, under the names benchmark/reference/granite_hybrid.py
    reads."""
    p = m.params

    def one(name, weight="weight"):
        return np.asarray(C.dense(p[name][weight]))

    layers = []
    for i in range(mc.num_hidden_layers):
        ly = f"layers.{i}"
        mix = p[f"{ly}.mamba" if mc.kind(i) == "mamba" else f"{ly}.self_attn"]
        layers.append({
            "ln1": one(f"{ly}.input_layernorm"),
            "ln2": one(f"{ly}.post_attention_layernorm"),
            "w_in": one(f"{ly}.shared_mlp.input_linear", "kernel"),
            "w_out": one(f"{ly}.shared_mlp.output_linear", "kernel"),
            **{k: np.asarray(C.dense(mix[k])) for k in mix}})
    return {"emb": one("embed_tokens"), "layers": layers, "norm": one("norm")}


class Steps(_Steps):
    """families/zaya.Steps with one value read off the graph beside the
    logits: the normed input of the model's first mixer."""

    def __init__(self, model, slot: int = 0):
        import jax
        import jax.numpy as jnp

        from flexflow_tpu.ffconst import OpType
        from flexflow_tpu.serve.engine import forward_with_meta

        self.model, self.slot = model, slot
        logits_t = model.layers[-1].inputs[0]
        first = next(ly.inputs[0] for ly in model.layers
                     if ly.op_type == OpType.INC_SSD_MIXER)
        cdt = jnp.dtype(model.config.compute_dtype)

        def run(params, state, meta, decode):
            (logits, inputs), state = forward_with_meta(
                model, params, state, meta, None, cdt, kv_contiguous=decode,
                outputs=[logits_t, first])
            return logits.astype(jnp.float32), [inputs], state

        self._run = jax.jit(run, donate_argnums=(1,), static_argnums=(3,))


def drive(model, toks, plan, slot: int = 0, after_prefill=None):
    """``toks`` through ``Steps`` on ``slot``: ``plan`` lists the steps, a
    list of segment lengths for a compact prefill step or 1 for a decode
    step; ``after_prefill()`` is called behind the last prefill step.
    Returns (logits [T, V], the first mixer's normed inputs [T, E])."""
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
    return (np.concatenate([p[0] for p in parts], axis=0),
            np.concatenate([p[1][0] for p in parts], axis=0))


def state_error(cfg: dict, reference, lw, inputs, held, **kw) -> float:
    """Relative Frobenius error of ``held [H, P, N]`` against the literal
    recurrence of mixer ``lw`` on the normed ``inputs [T, E]``."""
    import jax

    H, P, N, _ = _mixer(cfg)
    with jax.default_device(_host()):
        want = np.asarray(reference.ssd_state(
            lw, np.asarray(inputs, np.float32), H=H, P=P, N=N,
            eps=cfg.get("rms_norm_eps", 1e-5), **kw), np.float64)
    return float(np.linalg.norm(held - want) / np.linalg.norm(want))


def reference_cfg(cfg: dict) -> dict:
    """The configuration of the check's cut, as the reference reads it."""
    types = list(REFERENCE_LAYER_TYPES)[:min(REFERENCE_LAYERS,
                                             cfg["num_hidden_layers"])]
    return {**cfg, "layer_types": types, "num_hidden_layers": len(types)}


def reference_run(cfg: dict):
    """Drive the program at the published widths on the reference check's
    cut: ``(tokens, the program's logits, the first mixer's inputs and the
    state it holds behind the last prefill step, the weights for the
    reference, seconds)``."""
    t_build = time.perf_counter()
    chunk = C.prefill_chunk(cfg)
    cut = reference_cfg(cfg)
    mc = _model_cfg(cut)
    # four slots, so that the cut's compact batch is the cell's own shape
    # (four segments of a chunk: RequestManager._prefill_shape)
    m = _build_model(cut, False, max_requests_per_batch=4)
    ragged = min(REFERENCE_RAGGED, chunk - 1)
    plan = ([[chunk] * REFERENCE_CHUNKS, [ragged]]
            + [1] * REFERENCE_DECODED)
    toks = np.random.default_rng(cfg["weights_seed"]).integers(
        1, cfg["vocab_size"],
        size=chunk * REFERENCE_CHUNKS + ragged + REFERENCE_DECODED)
    t0 = time.perf_counter()
    held = []
    ours, inputs = drive(
        m, toks, plan, REFERENCE_SLOT,
        after_prefill=lambda: held.append(held_state(m, REFERENCE_SLOT)))
    inputs = inputs[:len(toks) - REFERENCE_DECODED]
    t1 = time.perf_counter()
    weights = reference_weights(m, mc)
    t2 = time.perf_counter()
    return (toks, ours, (inputs, held[0]), weights,
            [t0 - t_build, t1 - t0, t2 - t1])


def reference_logits(cfg: dict, reference, weights, toks, **kw):
    """The reference, on the host's CPU: logits as numpy."""
    import jax

    with jax.default_device(_host()):
        return np.asarray(reference.forward(
            weights, np.asarray(toks), reference_cfg(cfg), **kw))


# what the reference computes when it is asked to be wrong on purpose
# (``reference_check(variants=)``): keyword arguments of ``forward``
VARIANTS = {
    "D_out": {"without": ("D",)},
    "conv_bias_out": {"without": ("conv_bias",)},
    "conv_tap_out": {"without": ("conv_tap",)},
    "dt_bias_out": {"without": ("dt_bias",)},
    "z_gate_out": {"without": ("z_gate",)},
    "norm_before_gate": {"without": ("norm_order",)},
    "residual_multiplier_1": {"without": ("residual_multiplier",)},
    "attention_multiplier_0.125": {"without": ("attention_multiplier",)},
    "embedding_multiplier_1": {"without": ("embedding_multiplier",)},
    # the nearest precision below the configuration's: the state in bfloat16
    "bfloat16_state": {"state_dtype": "bfloat16"},
    "float8": {"matmul_dtype": "float8_e4m3fn"},
    # what the served precision itself costs this model: no fault
    "bfloat16": {"matmul_dtype": "bfloat16"},
}


def reference_check(cfg: dict, reference, variants=()) -> dict:
    """Four layers (mixer, mixer, attention, mixer) at the published widths
    and the whole vocabulary, the same seeded weights as served, the timed
    path's own programs: two consecutive segments of one slot in ONE compact
    prefill step (the hand-over), a ragged segment in the next (its state
    from the store), then six tokens decoded through the state and the
    cache. The logits, at all positions, against the reference
    (REFERENCE_TOL), and the state the first mixer holds behind the last
    prefill step against the literal recurrence on that layer's own inputs
    (STATE_TOL).
    ``variants`` (names of ``VARIANTS``; by hand,
    tools/check_reference_variants.py): beside the program's reading, what
    the reference reads against ITSELF with a term left out or a precision
    lowered, as ``wrong_<name>``."""
    import jax.numpy as jnp

    toks, ours, (inputs, held), weights, seconds = reference_run(cfg)
    t = time.perf_counter()
    ref = reference_logits(cfg, reference, weights, toks)
    out = C.compare_logits(ours, ref, REFERENCE_TOL)
    first = next(lw for lw in weights["layers"] if "A_log" in lw)
    out["state_rel_err"] = state_error(cfg, reference, first, inputs, held)
    out["state_tol"] = STATE_TOL
    out["ok"] = out["ok"] and out["state_rel_err"] < STATE_TOL
    # where a cold run's minute goes: the cut's build, its programs
    # (compiled, then run), the weights' way to the host, the reference
    out["seconds"] = [round(x, 1) for x in
                      seconds + [time.perf_counter() - t]]
    for name in variants:
        kw = {k: getattr(jnp, v) if k.endswith("dtype") else v
              for k, v in VARIANTS[name].items()}
        wrong = reference_logits(cfg, reference, weights, toks, **kw)
        out[f"wrong_{name}"] = C.compare_logits(wrong, ref, REFERENCE_TOL)[
            "max_rel_l2"]
    if "bfloat16_state" in variants:    # and what the state's check reads
        H, P, N, _ = _mixer(cfg)
        exact = np.asarray(reference.ssd_state(
            first, np.asarray(inputs, np.float32), H=H, P=P, N=N),
            np.float64)
        out["wrong_bfloat16_state_state"] = state_error(
            cfg, reference, first, inputs, exact, state_dtype=jnp.bfloat16)
    return out
