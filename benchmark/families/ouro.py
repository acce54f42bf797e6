"""Family "ouro": builds a serving handle for an Ouro (LoopLM) configuration
file (incremental decoding; ``num_hidden_layers`` blocks applied
``total_ut_steps`` times to every token with the SAME weights, the final
norm and the exit gate between passes, an untied head; every pass keeps a
k/v cache plane of its own), and holds what the yardstick needs to know
about the family's shapes: a decode step multiplies by every looped matrix
ONCE A PASS, and a cache position costs ``passes x layers`` planes."""

from __future__ import annotations

import time

import numpy as np

from . import _common as C
# the process's CPU device, where the float32 reference runs
from .exaone_moe import _host
# the serving loop's warm-up (no experts to hold to a path)
from .falcon import warm_and_check as _warm
# the timed path's own programs a step at a time on one slot, with the
# logits read off the graph (no expert layer: its routes are empty)
from .zaya import drive

# The reference check has two halves, both on logits, relative L2 error,
# worst position, against benchmark/reference/ouro.py in float32.
#
# THE WHOLE DEPTH, on the handle the window drives (``whole_reference``
# before the build, ``whole_check`` in the warm-up: every layer, every pass,
# every plane, the cell's slots, int8 as served), three slots live at once:
# four segments of three slots in ONE compact prefill step (two of them
# consecutive segments of one slot, a whole chunk and a ragged one), then
# four decode steps with the three rows live at different depths. The
# reference's weights are NOT read off the served model: the program's
# initialisers at ``weights_seed`` in a build of their own, unquantised,
# brought to the host and quantised there by ``plain_int8``, this file's
# own plain code, so a scale that is wrong or a matrix that was never
# quantised past its rounding shows. The readings at the published widths on
# the chip (my chip run, PR 60, call 5, tools/check_reference_variants.py
# --config ouro-2.6b and a traced run; the weights and tokens come from
# ``weights_seed``, so every run reads the same): the program 0.041876 over
# 232 positions of slots 1, 3 and 5 (192 layer-applications of bfloat16
# where the cut's 8 read 0.01326); the reference against itself: float8
# matmul inputs 0.287, three passes for four 1.238, one cache for all
# passes 1.669. The limit is 2.4 times the one and a third of the nearest
# precision below. What it costs a traced run's set-up: the unquantised
# build and the host's quantiser 10.7-17.3 s, the two logits programs on the
# built model 40.2-45.9 s (two compiles of the 48-layer body); the
# reference's own passes (10.1 s warm, 28.6 s in a cold process, 71 s in the
# tool's) run on a thread beside the build and cost the set-up nothing
# where they end before those two programs do.
WHOLE_TOL = 0.1
WHOLE_DECODED = 4
#
# A CUT, second: 2 layers x 4 passes = 8 layer-applications and 8 cache
# planes on a model of its own with the served model's dequantised weights:
# what the knock-outs are read on, each beside its name in VARIANTS (the
# program 0.01326; the reference with bfloat16 matmul inputs reads 0.01301
# against itself: the model's own sensitivity; float8 matmul inputs 0.248,
# the smallest other knock-out 0.511). The limit is three times the one and
# under a sixth of the nearest precision below.
REFERENCE_TOL = 0.04
REFERENCE_LAYERS = 2
# The prompt of the cut's check: TWO whole chunks that one compact step
# carries as consecutive segments of one slot, then a ragged segment in a
# step of its own, then tokens decoded one a step through the planes.
REFERENCE_CHUNKS = 2
REFERENCE_RAGGED = 45
REFERENCE_DECODED = 6
# the slot of the cut's four that the check's request lives in (not row 0 of
# the compact batch: the row map is read)
REFERENCE_SLOT = 1


def _model_cfg(cfg: dict, layers=None):
    from flexflow_tpu.models.ouro import OuroConfig

    hf = dict(cfg)
    if layers is not None:
        hf.update(num_hidden_layers=layers,
                  layer_types=list(cfg["layer_types"][:layers]))
    return OuroConfig.from_hf_config(hf)


def _build_model(cfg: dict, telemetry: bool, layers=None, **overrides):
    """``_common.build_model`` with the weights' dtype from the file
    (``assumed.weights_dtype``, bfloat16 unless the rehearsal says
    float32) and its XLA options (``assumed.compiler_options``)."""
    import flexflow_tpu as ff
    from flexflow_tpu.models.ouro import create_ouro_model

    dt = {"bfloat16": ff.DataType.DT_BFLOAT16, "float32": ff.DataType.DT_FLOAT
          }[cfg["assumed"].get("weights_dtype", "bfloat16")]
    # the deployment's XLA options for its serving programs, where the
    # file states any (``assumed.compiler_options``: PERF.md section 6)
    overrides.setdefault("compiler_options",
                         cfg["assumed"].get("compiler_options"))
    m = ff.FFModel(C.ffconfig(cfg, telemetry, **overrides))
    create_ouro_model(m, _model_cfg(cfg, layers),
                      mode=ff.InferenceMode.INC_DECODING_MODE, data_type=dt)
    m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    return m


def build(cfg: dict, telemetry: bool) -> dict:
    from flexflow_tpu.serve.loadgen import EngineHandle

    llm = _build_model(cfg, telemetry)
    return {"handle": EngineHandle(llm), "llm": llm, "models": [llm]}


# ---- the family's shapes, for the per-layer readers -----------------------

def layers_of(cfg: dict, kind: str) -> int:
    """How many cache planes of ``kind`` the configuration keeps: "full":
    a plane a layer A PASS."""
    return {"full": cfg["total_ut_steps"] * cfg["num_hidden_layers"]
            }.get(kind, 0)


def cache_position_bytes(cfg: dict) -> float:
    """Bytes one cache position costs ONE plane: k and v, bf16 (8192 B at
    the published widths)."""
    return 2.0 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2


def cache_bytes_per_token(cfg: dict) -> float:
    """Cache bytes a live position adds to a decode step's reads: every
    pass's plane of every layer (passes x layers x 8192 B = 1.57 MB)."""
    return cache_position_bytes(cfg) * layers_of(cfg, "full")


def _layer_weights(cfg: dict):
    """(name, rows, cols, bytes per element) of what ONE application of one
    block multiplies by: int8 payload plus the float32 scale per column,
    the four norms bf16."""
    E, I = cfg["hidden_size"], cfg["intermediate_size"]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    b = C.weight_element_bytes(cfg)
    return [("wq", E, nh * hd, b), ("wk", E, nkv * hd, b),
            ("wv", E, nkv * hd, b), ("wo", nh * hd, E, b),
            ("gate", E, I, b), ("up", E, I, b), ("down", I, E, b),
            ("scales", 1, nh * hd + 2 * nkv * hd + E + 2 * I + E, 4.0),
            ("norms", 1, 4 * E, 2.0)]


def decode_weights(cfg: dict):
    """(name, rows, cols, bytes per element) of every matrix one decode
    step multiplies by: each looped matrix ONCE A PASS (the loop streams
    the same bytes again: nothing of 2.5 GB stays on the chip between
    passes), the final norm and the gate's vector once a pass, the head
    once."""
    E, V = cfg["hidden_size"], cfg["vocab_size"]
    b = C.weight_element_bytes(cfg)
    out = []
    for t in range(cfg["total_ut_steps"]):
        out += [(f"pass{t}.layers.{i}.{n}", r, c, e)
                for i in range(cfg["num_hidden_layers"])
                for n, r, c, e in _layer_weights(cfg)]
        out += [(f"pass{t}.norm", 1, E, 2.0),
                (f"pass{t}.early_exit_gate", E + 1, 1, 2.0)]
    return out + [("lm_head", E, V, b), ("lm_head.scale", 1, V, 4.0)]


# ---- the reference check --------------------------------------------------

# the matrices the int8 deployment quantises (``assumed.int8``), under the
# names benchmark/reference/ouro.py reads
QUANTISED = ("emb", "head", "wq", "wk", "wv", "wo", "gate", "up", "down")


def plain_int8(w, dtype: str = "bfloat16"):
    """``w`` [in, out] float32 -> what the int8 deployment multiplies by,
    dequantised, by plain code of this file's own (``assumed.int8``): a
    scale a column, ``max|w| / 127`` rounded to the weights' own type
    (``dtype``), symmetric, round to nearest, clipped to +-127."""
    import jax.numpy as jnp

    scale = (jnp.abs(w).max(axis=0) / 127.0).astype(dtype).astype(
        jnp.float32)
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(w / scale), -127.0, 127.0) * scale


def reference_weights(m, L: int, dense=None):
    """``m``'s weights in float32 under the names
    benchmark/reference/ouro.py reads. ``dense`` (name, leaf) -> array: by
    default the served leaf dequantised on the device and brought to the
    host."""
    from flexflow_tpu.models.ouro import NORMS

    p = m.params
    if dense is None:
        def dense(name, leaf):
            return np.asarray(C.dense(leaf))

    def one(key, name, weight="weight"):
        return dense(key, p[name][weight])

    layers = []
    for i in range(L):
        ly = f"layers.{i}"
        a = p[f"{ly}.self_attn"]
        layers.append({
            **{k: one(k, f"{ly}.{n}") for k, n in zip(
                ("n_in", "n_in2", "n_post", "n_post2"), NORMS)},
            **{k: dense(k, a[k]) for k in ("wq", "wk", "wv", "wo")},
            **{k: one(k, f"{ly}.mlp.{k}_proj", "kernel")
               for k in ("gate", "up", "down")}})
    return {"emb": one("emb", "embed_tokens"), "layers": layers,
            "norm": one("norm", "norm"),
            "gate_w": one("gate_w", "early_exit_gate", "kernel"),
            "gate_b": one("gate_b", "early_exit_gate", "bias"),
            "head": one("head", "lm_head", "kernel")}


def reference_cfg(cfg: dict) -> dict:
    """The configuration of the check's cut, as the reference reads it."""
    L = min(REFERENCE_LAYERS, cfg["num_hidden_layers"])
    return {**cfg, "num_hidden_layers": L,
            "layer_types": list(cfg["layer_types"][:L])}


def reference_run(cfg: dict):
    """Drive the program at the published widths on the reference check's
    cut: ``(tokens, the program's logits, the weights for the reference,
    seconds)``."""
    t_build = time.perf_counter()
    chunk = C.prefill_chunk(cfg)
    cut = reference_cfg(cfg)
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
    ours, _ = drive(m, toks, plan, REFERENCE_SLOT)
    t1 = time.perf_counter()
    weights = reference_weights(m, cut["num_hidden_layers"])
    t2 = time.perf_counter()
    return toks, ours, weights, [t0 - t_build, t1 - t0, t2 - t1]


def reference_logits(cfg: dict, reference, weights, toks, **kw):
    """The reference, on the host's CPU: logits as numpy (its depth is that
    of ``weights``)."""
    import jax

    with jax.default_device(_host()):
        return np.asarray(reference.forward(
            weights, np.asarray(toks), cfg, **kw))


# What the reference computes when it is asked to be wrong on purpose
# (``reference_check(variants=)``): keyword arguments of ``forward``. Beside
# each, what it reads against the reference itself at the published widths
# on the chip on the CUT (tools/check_reference_variants.py --config
# ouro-2.6b, my chip run, PR 60; the program reads 0.01326).
VARIANTS = {
    "three_passes": {"passes": 3},                          # 0.608
    # every pass on pass 0's planes: one cache for all passes, a quarter of
    # the bytes and a DIFFERENT result
    "shared_planes": {"without": ("own_planes",)},          # 1.385
    "norm_between_out": {"without": ("norm_between",)},     # 0.962
    "post_norm_out": {"without": ("post_norm",)},           # 0.875
    # (at the published threshold, 1, the same wrong logits as three passes)
    "exit_state_before": {"without": ("exit_state",)},      # 0.608
    "rope_theta_1e4": {"rope_theta": 1e4},                  # 0.511
    # the nearest precision below the configuration's
    "float8": {"matmul_dtype": "float8_e4m3fn"},            # 0.248
    # what the served precision itself costs this model: no fault
    "bfloat16": {"matmul_dtype": "bfloat16"},               # 0.0130
}
# ... and which of them the WHOLE depth is also read with (by hand; a
# reference pass over 192 layer-applications on the host each)
WHOLE_VARIANTS = ("float8", "shared_planes", "three_passes")


def _wrong(name):
    import jax.numpy as jnp

    return {k: getattr(jnp, v) if k.endswith("dtype") else v
            for k, v in VARIANTS[name].items()}


# ---- ... its first half: the whole depth, on the built handle -------------

# what ``whole_reference`` leaves for ``whole_check``: the requests and the
# reference's logits of them, as a future (a traced run; None in a run that
# checks nothing against the reference)
_WHOLE = None


def whole_requests(cfg: dict, slots: int):
    """{slot: (tokens, the lengths of its prefill segments)}: three slots
    of the handle's, the first not slot 0 and the last the last; one of them
    two consecutive segments (a whole chunk, then a ragged one); every
    request ``WHOLE_DECODED`` tokens longer than its segments."""
    chunk = C.prefill_chunk(cfg)

    def part(n):
        return max(1, chunk * n // 128)

    plan = {1: [part(45)], slots // 2: [chunk, part(17)],
            slots - 1: [part(30)]}
    assert len(plan) == 3, slots
    rng = np.random.default_rng(cfg["weights_seed"] + 1)
    return {slot: (rng.integers(1, cfg["vocab_size"],
                                size=sum(lengths) + WHOLE_DECODED), lengths)
            for slot, lengths in plan.items()}


def whole_weights(cfg: dict):
    """The deployment's weights for the reference, made WITHOUT the served
    model: the program's initialisers at ``weights_seed`` in a build of
    their own at the weights' own type, unquantised, one slot (freed
    here); every leaf to the host as float32, and there the matrices the
    deployment quantises through ``plain_int8``."""
    import gc

    import jax
    import jax.numpy as jnp

    m = _build_model(cfg, False, quantization_type=None,
                     max_requests_per_batch=1)
    int8 = cfg["assumed"]["quantization"] == "int8"
    assert int8 or cfg["assumed"]["quantization"] is None
    host = _host()
    quantise = jax.jit(lambda w: plain_int8(
        w, cfg["assumed"].get("weights_dtype", "bfloat16")))

    def dense(name, leaf):
        w = jnp.asarray(jax.device_put(np.asarray(leaf), host), jnp.float32)
        return quantise(w) if int8 and name in QUANTISED else w

    with jax.default_device(host):
        weights = jax.block_until_ready(
            reference_weights(m, cfg["num_hidden_layers"], dense))
    del m
    gc.collect()
    return weights


def whole_reference(cfg: dict, reference, variants=()) -> dict:
    """Before the build: the reference's weights, and its logits of
    ``whole_requests`` STARTED: 192 layer-applications of float32 over 232
    positions are half a minute of the host's CPU and of nothing else, so
    they run on a thread of their own beside the served model's build and
    compiles, and ``whole_check`` waits for them (a cold traced run has 360
    s; PERF.md section 6, PR 60). ``variants`` (by hand): beside them, what
    the reference reads against ITSELF when wrong on purpose, as
    ``wrong_<name>``, the worst of the three requests."""
    import concurrent.futures

    global _WHOLE
    t0 = time.perf_counter()
    weights = whole_weights(cfg)
    t1 = time.perf_counter()
    requests = whole_requests(cfg, cfg["assumed"]["max_requests_per_batch"])

    def logits():
        t = time.perf_counter()
        ref = {slot: reference_logits(cfg, reference, weights, toks)
               for slot, (toks, _) in requests.items()}
        return ref, round(time.perf_counter() - t, 1)

    pool = concurrent.futures.ThreadPoolExecutor(1, "ouro-reference")
    pending = pool.submit(logits)
    pool.shutdown(wait=False)
    _WHOLE = (requests, pending)
    out = {"seconds": [round(t1 - t0, 1)]}
    if variants:
        ref, _ = pending.result()
    for name in variants:
        out[f"wrong_{name}"] = max(
            C.compare_logits(
                reference_logits(cfg, reference, weights, toks,
                                 **_wrong(name)), ref[slot], WHOLE_TOL
            )["max_rel_l2"] for slot, (toks, _) in requests.items())
    return out


def drive_slots(model, requests, decoded: int):
    """The timed path's own programs on several slots of ``model`` at once,
    with the logits read off the graph: every segment of ``requests``
    ({slot: (tokens, segment lengths)}) in ONE compact prefill step
    (serve/request_manager._meta_from_segments), then ``decoded`` steps of
    the decode block's body (serve/engine.forward_with_meta as
    make_decode_block calls it: one token a row on the slot grid,
    ``kv_contiguous``) with every request's row live. Returns {slot:
    logits [its tokens, V] float32}."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.serve.batch_config import BatchMeta
    from flexflow_tpu.serve.engine import forward_with_meta
    from flexflow_tpu.serve.request_manager import RequestManager as RM

    logits_t = model.layers[-1].inputs[0]
    cdt = jnp.dtype(model.config.compute_dtype)

    def run(params, state, meta, decode):
        (logits,), state = forward_with_meta(
            model, params, state, meta, None, cdt, kv_contiguous=decode,
            outputs=[logits_t])
        return logits.astype(jnp.float32), state

    run = jax.jit(run, donate_argnums=(1,), static_argnums=(3,))

    def step(meta, decode):
        logits, model.op_state = run(model.params, model.op_state, meta,
                                     decode)
        return np.asarray(logits)

    chunk, segments = RM._prefill_shape(model.config)
    rows = []
    for slot, (toks, lengths) in requests.items():
        for i, n in enumerate(lengths):
            at = sum(lengths[:i])
            rows.append((slot, list(toks[at:at + n]), at))
    assert len(rows) <= segments and max(len(r[1]) for r in rows) <= chunk
    logits = step(RM._meta_from_segments(segments, chunk, rows), False)
    out = {slot: [] for slot in requests}
    for i, (slot, seg, _) in enumerate(rows):
        out[slot].append(logits[i, :len(seg)])
    R = model.config.max_requests_per_batch
    for j in range(decoded):
        tok, pos = np.zeros((R,), np.int32), np.zeros((R,), np.int32)
        act = np.zeros((R,), bool)
        for slot, (toks, lengths) in requests.items():
            pos[slot] = sum(lengths) + j
            tok[slot], act[slot] = toks[pos[slot]], True
        logits = step(BatchMeta(
            tokens=tok[:, None], positions=pos[:, None], start_pos=pos,
            num_tokens=act.astype(np.int32), active=act), True)
        for slot in requests:
            out[slot].append(logits[slot])
    return {slot: np.concatenate(parts, axis=0)
            for slot, parts in out.items()}


def whole_check(model) -> dict:
    """In the warm-up, on the model the window drives: ``drive_slots`` over
    the requests ``whole_reference`` left, against its logits once they are
    there (WHOLE_TOL), all positions of all three slots."""
    requests, pending = _WHOLE
    t = time.perf_counter()
    ours = drive_slots(model, requests, WHOLE_DECODED)
    t1 = time.perf_counter()
    ref, reference_s = pending.result()
    out = C.compare_logits(np.concatenate([ours[s] for s in requests]),
                           np.concatenate([ref[s] for s in requests]),
                           WHOLE_TOL)
    out["slots"] = sorted(requests)
    # the two logits programs on the built model; the reference's own
    # seconds on its thread, and how long this waited for it after them
    out["program_s"] = round(t1 - t, 1)
    out["reference_s"] = reference_s
    out["waited_s"] = round(time.perf_counter() - t1, 1)
    return out


def warm_and_check(built: dict, cfg: dict) -> dict:
    """The serving loop's warm-up; before it, in a run that checks against
    the reference, the whole-depth half of that check (``ok`` holds
    both)."""
    whole = whole_check(built["llm"]) if _WHOLE is not None else None
    out = _warm(built, cfg)
    if whole is not None:
        out["whole"] = whole
        out["ok"] = out["ok"] and whole["ok"]
    return out


# ---- ... and its second: the cut -------------------------------------------

def reference_check(cfg: dict, reference, variants=()) -> dict:
    """The CUT: two layers x four passes (8 cache planes) at the published
    widths and the whole vocabulary, the served weights dequantised, the
    timed path's own programs on one slot that is not slot 0: two whole
    chunks as consecutive segments of ONE compact prefill step, a ragged
    segment in the next, then six tokens decoded one a step through the
    planes. The logits, at all positions, against the reference
    (REFERENCE_TOL). Then the reference's half of the WHOLE-depth check
    (``whole_reference``, under ``whole``), whose other half the warm-up
    runs on the built handle (``warm_and_check``). ``variants`` (names of
    ``VARIANTS``; by hand, tools/check_reference_variants.py): beside the
    program's reading, what the reference reads against ITSELF with a term
    left out or a precision lowered, as ``wrong_<name>``."""
    toks, ours, weights, seconds = reference_run(cfg)
    t = time.perf_counter()
    cut = reference_cfg(cfg)
    ref = reference_logits(cut, reference, weights, toks)
    out = C.compare_logits(ours, ref, REFERENCE_TOL)
    # where a cold run's minute goes: the cut's build, its programs
    # (compiled, then run), the weights' way to the host, the reference
    out["seconds"] = [round(x, 1) for x in
                      seconds + [time.perf_counter() - t]]
    for name in variants:
        wrong = reference_logits(cut, reference, weights, toks,
                                 **_wrong(name))
        out[f"wrong_{name}"] = C.compare_logits(wrong, ref, REFERENCE_TOL)[
            "max_rel_l2"]
    del weights
    out["whole"] = whole_reference(
        cfg, reference, [v for v in WHOLE_VARIANTS if v in variants])
    return out
