"""Family "evabyte": builds a serving handle for an EvaByte configuration file
(incremental decoding over a chunked cache: an exact window of positions and
one learned summary pair for every chunk before it), and holds what the
yardstick needs to know about the family's shapes: the entries a decode step
of a row has to read, the bytes an entry costs a layer, the matrices a
decode step multiplies by."""

from __future__ import annotations

import time

import numpy as np

from . import _common as C

# Reference check, logits: relative L2 error, worst position, as the other
# families (families/falcon.py has the reasoning: bfloat16 compute and cache
# against float32 on the same dequantised int8 weights), at the published
# widths, two layers, 4090 prompt positions and 40 decoded (PERF.md section
# 6, PR 42, has every reading). The limit lies between the program's reading
# on the chip and the least of what the reference reads when it computes one
# of the things the model is not: float8 (e4m3) matmul inputs, the nearest
# precision below; a window's summaries visible one window early; a chunk
# pooled by its mean; mu and phi exchanged.
REFERENCE_TOL = 0.03
REFERENCE_LAYERS = 2
# The check's row: a prompt that ends inside a chunk of 16 (4090 = 255 x 16
# + 10) six positions short of the end of window 1, and enough decoded
# positions to complete that chunk, leave window 1 at position 4096 and read
# all 256 summaries from there on.
REFERENCE_DECODED = 40
# The check holds the cell's slots, so that its prefill step is the timed
# compact [4, 128] batch and not the [2, 256] of a two-slot cut.
REFERENCE_SLOTS = 4


def reference_prompt(cfg: dict) -> int:
    """4090 at the published window of 2048 (a rehearsal's is smaller)."""
    return 2 * cfg["window_size"] - 6


def _model_cfg(cfg: dict, layers=None):
    from flexflow_tpu.models.evabyte import EvaByteConfig

    known = {k: v for k, v in cfg.items() if k not in (
        "source", "family", "deployment", "weights_seed", "published",
        "reduced", "assumed", "rehearsal")}
    c = EvaByteConfig.from_hf_config(known, strict=True)
    if layers is not None:
        c.num_hidden_layers = layers
    return c


def _build(cfg: dict, telemetry: bool, layers=None, **overrides):
    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models.evabyte import create_evabyte_model

    return C.build_model(
        C.ffconfig(cfg, telemetry, **overrides), create_evabyte_model,
        _model_cfg(cfg, layers), InferenceMode.INC_DECODING_MODE)


def build(cfg: dict, telemetry: bool) -> dict:
    from flexflow_tpu.serve.loadgen import EngineHandle

    llm = _build(cfg, telemetry)
    return {"handle": EngineHandle(llm), "llm": llm, "models": [llm]}


def warm_and_check(built: dict, cfg: dict) -> dict:
    """Reach every program the loop uses, before the clock of the window."""
    new = 24
    res = C.serve_pass(built["handle"],
                       C.warm_prompts(cfg, cfg["vocab_size"]), new)
    return {"ok": C.all_ok(res, new),
            "ttft_attributed": all(r.ttft_s > 0 for r in res),
            "scheduler_loop": built["handle"].rm.scheduler_loop}


# ---- the family's shapes, for the per-layer readers -----------------------

def entries_read(cfg: dict, length) -> np.ndarray:
    """Cache entries of ONE layer that a decode step has to read for a row
    whose cache holds ``length`` positions after the step's append (an
    array of lengths gives an array): the summaries of every chunk of every
    window before the last position's own, and that window's positions up
    to it. At 24576 positions: 1408 + 2048."""
    last = np.asarray(length, np.int64) - 1
    W, c = cfg["window_size"], cfg["chunk_size"]
    return last // W * (W // c) + last % W + 1


def cache_position_bytes(cfg: dict) -> float:
    """Bytes of one cache entry of ONE layer, an exact position's pair or a
    chunk's summary pair alike: keys and values of every head, bf16."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2.0 * cfg["num_key_value_heads"] * hd * 2


def cache_bytes_per_token(cfg: dict) -> float:
    """Bytes a position of a FULL cache would cost over all layers (what
    ``decode_hbm_roofline`` multiplies the positions a row holds by; this
    cell does not report it: a step reads ``entries_read``, an eighth)."""
    return cache_position_bytes(cfg) * cfg["num_hidden_layers"]


def decode_weights(cfg: dict):
    """(name, rows, cols, bytes per element) of every matrix one decode
    step multiplies by, int8 payload plus the float32 scale per column; the
    float32 norm offsets and pooling vectors beside them."""
    H, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    I = cfg["intermediate_size"]
    nh = cfg["num_attention_heads"]
    hd = H // nh
    b = C.weight_element_bytes(cfg)
    per_layer = [("wq", H, H, b), ("wk", H, H, b), ("wv", H, H, b),
                 ("wo", H, H, b), ("gate", H, I, b), ("up", H, I, b),
                 ("down", I, H, b), ("scales", 1, 5 * H + 2 * I, 4.0),
                 ("norms", 1, 2 * H, 4.0), ("pools", 1, 2 * nh * hd, 4.0)]
    out = [(f"layers.{i}.{n}", r, c, e)
           for i in range(L) for n, r, c, e in per_layer]
    return out + [("lm_head", H, V, b), ("lm_head.scale", 1, V, 4.0),
                  ("norm", 1, H, 4.0)]


# ---- the reference check --------------------------------------------------

def reference_weights(m, L: int):
    p = m.params
    layers = []
    for i in range(L):
        a, q = p[f"model.layers.{i}.self_attn"], f"model.layers.{i}"
        layers.append({
            "ln1": C.dense(p[f"{q}.input_layernorm"]["weight"]),
            "wq": C.dense(a["wq"]), "wk": C.dense(a["wk"]),
            "wv": C.dense(a["wv"]), "wo": C.dense(a["wo"]),
            "mu": C.dense(a["adaptive_mu_k"]),
            "phi": C.dense(a["adaptive_phi"]),
            "ln2": C.dense(p[f"{q}.post_attention_layernorm"]["weight"]),
            "gate": C.dense(p[f"{q}.mlp.gate_proj"]["kernel"]),
            "up": C.dense(p[f"{q}.mlp.up_proj"]["kernel"]),
            "down": C.dense(p[f"{q}.mlp.down_proj"]["kernel"])})
    return {"emb": C.dense(p["model.embed_tokens"]["weight"]),
            "layers": layers, "norm": C.dense(p["model.norm"]["weight"]),
            "head": C.dense(p["lm_head"]["kernel"])}


class Steps:
    """The serving loop's two programs over ``model``'s caches, a step at a
    time with the logits read off the graph: the compact prefill batch
    (RequestManager._meta_from_segments, as many consecutive segments of the
    one row as a step holds) and the decode block's step (one token a row
    at its position, what engine.make_decode_block's body builds)."""

    def __init__(self, model):
        import jax
        import jax.numpy as jnp

        from flexflow_tpu.serve.engine import forward_with_meta

        self.model = model
        logits_t = model.layers[-1].inputs[0]
        cdt = jnp.dtype(model.config.compute_dtype)

        def run(params, state, meta):
            (logits,), state = forward_with_meta(
                model, params, state, meta, None, cdt, outputs=[logits_t])
            return logits.astype(jnp.float32), state

        self._run = jax.jit(run, donate_argnums=(1,))

    def _step(self, meta):
        m = self.model
        logits, m.op_state = self._run(m.params, m.op_state, meta)
        return logits

    def prefill(self, tokens, slot: int = 0):
        """``tokens`` from position 0 into ``slot``, a step's segments at a
        time; the logits [V] at the last position."""
        from flexflow_tpu.serve.request_manager import RequestManager as RM

        chunk, segments = RM._prefill_shape(self.model.config)
        window = self.model.attention_kinds["chunked"]["window"]
        at = 0
        while at < len(tokens):
            # as the scheduler gives them: a step's segments of a slot lie
            # in one window (RequestManager._prefill_rows)
            end = min(at + chunk * segments, len(tokens),
                      (at // window + 1) * window)
            rows = [(slot, list(tokens[a:min(a + chunk, end)]), a)
                    for a in range(at, end, chunk)]
            logits = self._step(RM._meta_from_segments(segments, chunk, rows))
            at = end
        return np.asarray(logits[len(rows) - 1, len(rows[-1][1]) - 1])

    def decode(self, token: int, at: int, slot: int = 0):
        """One decode step of ``slot``: ``token`` at position ``at``; the
        logits [V] there."""
        from flexflow_tpu.serve.batch_config import BatchMeta

        R = self.model.config.max_requests_per_batch
        act = np.arange(R) == slot
        pos = np.where(act, at, 0).astype(np.int32)
        meta = BatchMeta(tokens=np.where(act, token, 0).astype(
            np.int32)[:, None], positions=pos[:, None], start_pos=pos,
            num_tokens=act.astype(np.int32), active=act)
        return np.asarray(self._step(meta)[slot, 0])


def served_logits(model, tokens, n_prompt: int):
    """[1 + len(tokens) - n_prompt, V]: the logits at the prompt's last
    position (``tokens[:n_prompt]`` through the compact prefill) and at
    every later one (a decode step each)."""
    run = Steps(model)
    rows = [run.prefill(tokens[:n_prompt])]
    for at in range(n_prompt, len(tokens)):
        rows.append(run.decode(int(tokens[at]), at))
    return np.stack(rows)


def reference_check(cfg: dict, reference, n_prompt=None,
                    decoded: int = REFERENCE_DECODED,
                    variants=()) -> dict:
    """A 2-layer cut at the published widths, the same seeded weights as
    served, the timed path's own programs: one row of ``n_prompt`` positions
    through the compact prefill, then ``decoded`` decode steps; the logits
    of the next-byte head at the prompt's last position and at every decoded
    one against the reference's full forward on the same tokens.
    ``variants`` (by hand, tools/check_evabyte_variants.py): also what the
    reference reads against ITSELF when it computes each named wrong thing
    (reference/evabyte.py ``wrong``; "float8": float8 matmul inputs), which
    the limit has to lie under."""
    import jax.numpy as jnp

    t0 = time.perf_counter()
    n_prompt = n_prompt or reference_prompt(cfg)
    m = _build(cfg, False, REFERENCE_LAYERS,
               max_requests_per_batch=REFERENCE_SLOTS)
    toks = np.random.default_rng(cfg["weights_seed"]).integers(
        1, cfg["vocab_size"], size=n_prompt + decoded)
    t1 = time.perf_counter()
    ours = served_logits(m, toks, n_prompt)
    t2 = time.perf_counter()
    weights = reference_weights(m, REFERENCE_LAYERS)
    at = np.arange(n_prompt - 1, len(toks))

    def ref(**kw):
        return np.asarray(reference.forward(weights, toks, cfg, **kw))[at]

    exact = ref()
    out = C.compare_logits(ours, exact, REFERENCE_TOL)
    out["seconds"] = [round(x, 1) for x in (
        t1 - t0, t2 - t1, time.perf_counter() - t2)]
    for name in variants:
        kw = ({"matmul_dtype": jnp.float8_e4m3fn} if name == "float8"
              else {"wrong": name})
        out[f"wrong_{name}"] = C.compare_logits(
            ref(**kw), exact, REFERENCE_TOL)["max_rel_l2"]
    return out
