"""Family "sdar_moe": builds a serving handle for an SDAR-MoE configuration
file (incremental decoding; a decode step is a pass over a block of
``block_length`` positions), and holds what the yardstick needs to know about
the family's shapes: the bytes a pass reads, one expert's bytes, the
arithmetic of one routed (token, expert) pair, the bytes of a cache
position."""

from __future__ import annotations

import numpy as np

from . import _common as C
# what the expert families do alike: the kernel's trace counts, the check of
# the program's routes against the reference's probabilities, the reference's
# weights (OLMoE's layers carry the same names), and an expert's bytes and a
# pair's arithmetic over ``moe_intermediate_size`` (EXAONE-MoE's)
from .exaone_moe import expert_bytes, pair_flops  # noqa: F401
from .olmoe import _reference_weights as reference_weights  # noqa: F401
from .olmoe import check_routes, expert_kernel_paths  # noqa: F401

# Reference check, logits: relative L2 error, worst position, as the other
# families (families/falcon.py has the reasoning: bfloat16 compute against
# float32 on the same dequantised int8 weights). The readings at the
# published widths, layers 0-1, 136 positions (PERF.md section 6, PR 39):
# the program on the chip 0.0093 (the reference itself with bfloat16 matmul
# inputs: 0.0094); the reference with float8 (e4m3) matmul inputs, the
# nearest precision below, 0.218; the reference with a one-way (causal) mask
# inside the block, what a program that ignored the block would compute,
# 1.14 (0.30 at the denoise pass's positions, 0.29 after the commit). The
# limit is 3.2 times the first and a seventh of the second.
REFERENCE_TOL = 0.03
REFERENCE_LAYERS = 2
# Reference check, routing: families/olmoe.py has the reasoning, which
# holds here with twice the experts: where the 8th and 9th largest of 128
# probabilities are closer than bfloat16's rounding of the hidden state
# moves them, the program takes the 9th, and one such pick moves a
# position's logits by far more than REFERENCE_TOL. So the logits are
# compared with the reference run on the PROGRAM's routes and the routes
# are checked apart: every pick outside the reference's own top-8 has to be
# an expert whose reference probability is within this relative margin of
# the reference's 8th largest. The readings (PERF.md section 6, PR 39; 2176
# picks): the program on the chip takes 11 picks outside the reference's
# top-8, the worst 1.9% short (bfloat16 inputs on the CPU: 16, 1.9%); the
# reference with float8 matmul inputs 195, the worst 35% short; a one-way
# mask 397, 90%. The margin is four times the one and under a quarter of
# the other.
ROUTE_MARGIN = 0.08
# The check's tokens: one prefill chunk of whole blocks, then a remainder of
# REFERENCE_KNOWN tokens that begins the first decoded block.
REFERENCE_KNOWN = 1


def _model_cfg(cfg: dict, layers=None):
    from flexflow_tpu.models.sdar_moe import SDARMoEConfig

    a = cfg["assumed"]
    c = SDARMoEConfig.from_hf_config({**cfg, **{
        k: a[k] for k in ("block_length", "denoising_steps",
                          "confidence_threshold", "mask_token_id")}})
    if layers is not None:
        c.num_hidden_layers = layers
    return c


def build(cfg: dict, telemetry: bool) -> dict:
    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models.sdar_moe import create_sdar_moe_model
    from flexflow_tpu.serve.loadgen import EngineHandle

    llm = C.build_model(C.ffconfig(cfg, telemetry), create_sdar_moe_model,
                        _model_cfg(cfg), InferenceMode.INC_DECODING_MODE)
    return {"handle": EngineHandle(llm), "llm": llm, "models": [llm]}


def warm_and_check(built: dict, cfg: dict) -> dict:
    """Reach every program the loop uses, before the clock of the window:
    the compact prefill and the decode block's pass. Two of the shared warm
    prompts have lengths that are no multiple of the block, so their first
    block begins with a remainder, and the asked length is no multiple of it
    either, so the last block is cut. The routed-expert kernel has to have
    been traced compiled, never through its fallback."""
    B = cfg["assumed"]["block_length"]
    new = 6 * B + 1
    prompts = C.warm_prompts(cfg, cfg["vocab_size"])
    ragged = sum(len(p) % B != 0 for p in prompts)
    res = C.serve_pass(built["handle"], prompts, new)
    moe = expert_kernel_paths()
    return {"ok": (C.all_ok(res, new) and ragged > 0
                   and moe["fast_path_traces"] > 0
                   and not moe["fallback_traces"]),
            "ttft_attributed": all(r.ttft_s > 0 for r in res),
            "scheduler_loop": built["handle"].rm.scheduler_loop,
            "ragged_prompts": ragged, "moe_experts": moe}


# ---- the family's shapes, for the per-layer readers -----------------------

def decode_weights(cfg: dict):
    """(name, rows, cols, bytes per element) of every matrix one pass
    multiplies by if it touches ALL experts (an upper count: a pass of 32
    rows x 4 tokens routes 1024 pairs over 128 experts and reads nearly
    all), int8 payload plus the float32 scale per column. A pass reads the
    weights once whatever its width."""
    H, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    E, I = cfg["num_experts"], cfg["moe_intermediate_size"]
    hd = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    b = C.weight_element_bytes(cfg)
    per_layer = [("wq", H, q, b), ("wk", H, kv, b), ("wv", H, kv, b),
                 ("wo", q, H, b), ("router", H, E, b),
                 ("scales", 1, q + 2 * kv + H + E, 4.0),
                 ("norms", 1, 2 * H + 2 * hd, 2.0)]
    for e in range(E):
        per_layer += [(f"experts.{e}.gate", H, I, b),
                      (f"experts.{e}.up", H, I, b),
                      (f"experts.{e}.down", I, H, b),
                      (f"experts.{e}.scales", 1, 2 * I + H, 4.0)]
    out = [(f"layers.{i}.{n}", r, c, e)
           for i in range(L) for n, r, c, e in per_layer]
    return out + [("lm_head", H, V, b), ("lm_head.scale", 1, V, 4.0),
                  ("norm", 1, H, 2.0)]


def cache_bytes_per_token(cfg: dict) -> float:
    return (2.0 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
            * cfg["num_hidden_layers"])                  # k and v, bf16


# ---- the reference check --------------------------------------------------

class Passes:
    """The timed path's own programs on slot 0 of ``model``, a step at a
    time, with the logits and the routes read as values of the graph: the
    compact prefill (serve/request_manager._meta_from_segments, one segment
    a step) and ONE pass of the decode block's body (serve/engine.
    forward_with_meta as engine._diffusion_block calls it: B tokens at the
    row's committed length, phase decode).
    ``wrong`` (tests): "stale_commit" keeps a denoise pass's keys and values
    as the committed ones, skipping the commit pass."""

    def __init__(self, model):
        import jax
        import jax.numpy as jnp

        from flexflow_tpu.ffconst import OpType
        from flexflow_tpu.serve.engine import forward_with_meta

        self.model = model
        self.bd = model.block_diffusion
        logits_t = model.layers[-1].inputs[0]
        chosen_t = [ly.inputs[1] for ly in model.layers
                    if ly.op_type == OpType.MOE_EXPERTS]
        cdt = jnp.dtype(model.config.compute_dtype)

        def run(params, state, meta, decode):
            (logits, *chosen), state = forward_with_meta(
                model, params, state, meta, None, cdt,
                phase="decode" if decode else None,
                outputs=[logits_t] + chosen_t)
            return (logits[0].astype(jnp.float32),
                    [c[0] for c in chosen], state)

        self._run = jax.jit(run, donate_argnums=(1,), static_argnums=(3,))

    def _step(self, meta, decode, n):
        m = self.model
        logits, chosen, m.op_state = self._run(m.params, m.op_state, meta,
                                               decode)
        return (np.asarray(logits)[:n], [np.asarray(c)[:n] for c in chosen])

    def prefill(self, tokens):
        """Whole blocks of ``tokens`` from position 0, a chunk a step,
        through the compact batch. Returns (logits, routes) of them."""
        from flexflow_tpu.serve.request_manager import RequestManager as RM

        chunk, segments = RM._prefill_shape(self.model.config)
        parts = []
        for at in range(0, len(tokens), chunk):
            seg = tokens[at:at + chunk]
            parts.append(self._step(
                RM._meta_from_segments(segments, chunk,
                                       [(0, list(seg), at)]),
                False, len(seg)))
        return _joined(parts)

    def one_pass(self, block, at):
        """One pass of the decode block over ``block`` (B ids, the mask id
        where masked) at committed length ``at``."""
        from flexflow_tpu.serve.batch_config import BatchMeta

        R, B = self.model.config.max_requests_per_batch, self.bd.block_length
        toks = np.zeros((R, B), np.int32)
        toks[0] = block
        pos = np.zeros((R,), np.int32)
        pos[0] = at
        act = np.arange(R) == 0
        meta = BatchMeta(tokens=toks, positions=pos[:, None] + np.arange(B),
                         start_pos=pos, num_tokens=B * act.astype(np.int32),
                         active=act)
        return self._step(meta, True, B)


def _joined(parts):
    return (np.concatenate([p[0] for p in parts], axis=0),
            [np.concatenate([p[1][j] for p in parts], axis=0)
             for j in range(len(parts[0][1]))])


def two_blocks(model, cfg: dict, reference, tokens, known: int, wrong=None):
    """``tokens``: whole blocks, then ``known`` tokens of the next. Through
    the timed path's programs: the whole blocks prefilled; the first block
    (the known tokens, then masks) denoised pass by pass by the reference's
    own rule on the PROGRAM's logits, then committed; then the next block's
    first pass (all masks), which reads what the commit pass stored.
    Returns the (logits, routes) of the prefill, of the first block's FIRST
    denoise pass and of the second block's first pass, the routes of the
    commit pass, the first block as its first pass saw it and as it was
    committed."""
    bd = model.block_diffusion
    B, mask_id = bd.block_length, bd.mask_token_id
    whole = len(tokens) - known
    assert whole % B == 0 and 0 <= known < B
    run = Passes(model)
    out = {"prefill": run.prefill(tokens[:whole])}
    block = np.array(list(tokens[whole:]) + [0] * (B - known), np.int64)
    masked = np.arange(B) >= known
    while masked.any():
        seen = np.where(masked, mask_id, block)
        last = run.one_pass(seen, whole)
        if "denoise" not in out:
            out["denoise"], out["first_seen"] = last, seen.tolist()
        reference.unmask(block, masked, last[0], cfg)
    # "stale_commit": the cache keeps what the last denoise pass wrote
    out["commit_routes"] = (last if wrong == "stale_commit"
                            else run.one_pass(block, whole))[1]
    out["next"] = run.one_pass([mask_id] * B, whole + B)
    out["committed"] = block.tolist()
    return out


def compare_two_blocks(model, cfg, reference, weights, tokens, known,
                       tol, margin, wrong=None, **ref_kw) -> dict:
    """``two_blocks`` against the reference's full forward on the same
    tokens and on the program's routes: (a) the prefix and the first
    block's first denoise pass, (b) the pass after the commit, whose
    context is the prefix and the committed block."""
    import jax.numpy as jnp

    got = two_blocks(model, cfg, reference, tokens, known, wrong)
    B = model.block_diffusion.block_length
    prefix = list(tokens[:len(tokens) - known])
    mask_id = model.block_diffusion.mask_token_id
    ours_a, routes_a = _joined([got["prefill"], got["denoise"]])
    # (a block of masks after it changes nothing before it, and gives both
    # of the reference's forwards one length: one set of compiled shapes)
    ref_a, probs_a = reference.forward_routed(
        weights, jnp.asarray(prefix + got["first_seen"] + [mask_id] * B),
        cfg, routes=[np.concatenate([r, r[-B:]], axis=0) for r in routes_a],
        **ref_kw)
    ref_a, probs_a = ref_a[:-B], [p[:-B] for p in probs_a]
    ours_b, routes_b = got["next"]
    ref_b, probs_b = reference.forward_routed(
        weights, jnp.asarray(prefix + got["committed"] + [mask_id] * B), cfg,
        routes=[np.concatenate([p, c, n], axis=0) for p, c, n in zip(
            got["prefill"][1], got["commit_routes"], routes_b)], **ref_kw)
    ref_a, ref_b = np.asarray(ref_a), np.asarray(ref_b)[-B:]
    out = C.compare_logits(np.concatenate([ours_a, ours_b], axis=0),
                           np.concatenate([ref_a, ref_b], axis=0), tol)
    out["denoise_rel_l2"] = C.compare_logits(
        got["denoise"][0], ref_a[-B:], tol)["max_rel_l2"]
    out["after_commit_rel_l2"] = C.compare_logits(
        ours_b, ref_b, tol)["max_rel_l2"]
    out.update(check_routes(
        [np.concatenate([a, b], axis=0) for a, b in zip(routes_a, routes_b)],
        [np.concatenate([np.asarray(a), np.asarray(b)[-B:]], axis=0)
         for a, b in zip(probs_a, probs_b)], margin))
    out["ok"] = out["ok"] and out["routes_ok"]
    return out


def reference_check(cfg: dict, reference) -> dict:
    """A 2-layer cut at the published widths, the same seeded weights as
    served, the timed path's own programs: one chunk of whole blocks through
    the compact prefill, then TWO blocks through the decode block's pass.
    Compared, at the worst position, on the program's routes with the routes
    checked apart (ROUTE_MARGIN above): the logits of every prefilled
    position, of the first block's first denoise pass (remainder + masks)
    and of the next block's first pass, which reads what the commit pass
    stored."""
    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models.sdar_moe import create_sdar_moe_model

    chunk = C.prefill_chunk(cfg)
    m = C.build_model(C.ffconfig(cfg, False, max_requests_per_batch=2),
                      create_sdar_moe_model,
                      _model_cfg(cfg, REFERENCE_LAYERS),
                      InferenceMode.INC_DECODING_MODE)
    toks = np.random.default_rng(cfg["weights_seed"]).integers(
        1, cfg["assumed"]["mask_token_id"], size=chunk + REFERENCE_KNOWN)
    return compare_two_blocks(
        m, cfg, reference, reference_weights(m, REFERENCE_LAYERS),
        toks.tolist(), REFERENCE_KNOWN, REFERENCE_TOL, ROUTE_MARGIN)
