"""What the families share: the calls into the program.

This is the only place (with the family files) where the benchmark imports
``flexflow_tpu``. It takes the system under test and nothing that measures.
"""

from __future__ import annotations

import time

import numpy as np


def ffconfig(cfg: dict, telemetry: bool, **overrides):
    """The program's FFConfig from a configuration file's ``assumed`` knobs:
    one chip, bf16 compute and cache, weights from ``weights_seed``."""
    import flexflow_tpu as ff

    a = cfg["assumed"]
    kw = dict(max_requests_per_batch=a["max_requests_per_batch"],
              max_sequence_length=a["max_sequence_length"],
              max_tokens_per_batch=a["max_tokens_per_batch"],
              decode_block_steps=a["decode_block_steps"],
              spec_rounds_per_call=a["spec_rounds_per_call"],
              use_native_scheduler=a["use_native_scheduler"],
              quantization_type=a["quantization"],
              kv_cache_dtype=a["kv_cache_dtype"],
              compute_dtype=a["compute_dtype"],
              seed=int(cfg["weights_seed"]), telemetry=telemetry,
              tensor_parallelism_degree=1, num_devices=1)
    kw.update(overrides)
    return ff.FFConfig(**kw)


def build_model(ffc, create, model_cfg, mode):
    import flexflow_tpu as ff

    m = ff.FFModel(ffc)
    create(m, model_cfg, mode=mode, data_type=ff.DataType.DT_BFLOAT16)
    m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    return m


def scale_leaf(leaf, eps: float):
    from flexflow_tpu.quant import QuantizedWeight, is_quantized

    if is_quantized(leaf):
        return QuantizedWeight(leaf.qtype, leaf.q, leaf.scale * eps,
                               leaf.rows, leaf.dtype)
    return leaf * eps


def dense(leaf):
    """A weight as float32, dequantised: what the reference is given."""
    import jax.numpy as jnp

    from flexflow_tpu.quant import dequantize_array, is_quantized

    if is_quantized(leaf):
        return dequantize_array(leaf, jnp.float32)
    return jnp.asarray(leaf, jnp.float32)


def serve_pass(handle, prompts, new_tokens: int, timeout_s: float = 900.0):
    """Send ``prompts`` at once through the handle's front door and wait
    for all: the shape warm-up, and the material of the correctness checks.
    Returns the GenerationResults in prompt order."""
    handle.start_server()
    srv, rm = handle._server, handle.rm
    guids = [srv.submit([p], new_tokens, 0)[0][0] for p in prompts]
    deadline = time.perf_counter() + timeout_s
    while any(g not in rm.results for g in guids):
        if srv._error is not None:
            raise RuntimeError("serving loop died") from srv._error
        if time.perf_counter() > deadline:
            raise TimeoutError("warm-up pass did not finish")
        time.sleep(0.005)
    return [rm.results[g] for g in guids]


def prefill_chunk(cfg: dict) -> int:
    """Tokens a row is prefilled by in one scheduler round (the program's
    rule: the batch's token budget over at most four rows)."""
    a = cfg["assumed"]
    return max(1, a["max_tokens_per_batch"]
               // max(1, min(a["max_requests_per_batch"], 4)))


def weight_element_bytes(cfg: dict) -> float:
    return {"int8": 1.0, "int4": 0.5, None: 2.0}[cfg["assumed"]["quantization"]]


def warm_prompts(cfg: dict, vocab: int, seed: int = 0):
    """A few prompts that reach every program the serving loop uses: one
    longer than a prefill chunk, one shorter, one of a single chunk."""
    chunk = prefill_chunk(cfg)
    rng = np.random.default_rng(seed)
    lens = [chunk + 7, 24,
            min(2 * chunk, cfg["assumed"]["max_sequence_length"] // 2)]
    return [rng.integers(1, vocab, size=n).tolist() for n in lens]


def all_ok(results, new_tokens: int) -> bool:
    return all(r.status == "ok" and len(r.output_tokens) == new_tokens
               for r in results)


def attention_paths():
    from flexflow_tpu import kernels as ffk

    return {"fast_path_traces": ffk.fast_path_count,
            "fallback_traces": dict(ffk.fallback_counts),
            "interpreted": ffk.pallas_interpret_forced()}


def program_logits(model, tokens, n_prefill: int):
    """Logits of the program for ``tokens`` on one slot: the first
    ``n_prefill`` positions in one prefill step, the rest one token at a
    time through the cache. Returns float32 [len(tokens), V]."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops.base import OpContext
    from flexflow_tpu.serve.batch_config import make_batch_meta
    from flexflow_tpu.serve.engine import build_feeds

    R = model.config.max_requests_per_batch
    logits_t = model.layers[-1].inputs[0]
    cdt = jnp.dtype(model.config.compute_dtype)

    def step(params, state, meta):
        ctx = OpContext(training=False, rng=None, compute_dtype=cdt,
                        batch_config=meta, mesh=model.mesh,
                        config=model.config)
        values, new_state = model._run_graph(params, build_feeds(model, meta),
                                             ctx, state)
        return values[logits_t.tensor_id][0].astype(jnp.float32), new_state

    step = jax.jit(step, donate_argnums=(1,))

    def run(chunk, start):
        Q = len(chunk)
        toks = np.zeros((R, Q), np.int32)
        toks[0] = chunk
        pos = np.zeros((R, Q), np.int32)
        pos[0] = np.arange(start, start + Q)
        meta = make_batch_meta(
            R, Q, tokens=toks, positions=pos,
            start_pos=np.array([start] + [0] * (R - 1), np.int32),
            num_tokens=np.array([Q] + [0] * (R - 1), np.int32),
            active=np.array([True] + [False] * (R - 1)))
        out, model.op_state = step(model.params, model.op_state, meta)
        return np.asarray(out)

    rows = [run(tokens[:n_prefill], 0)]
    for i in range(n_prefill, len(tokens)):
        rows.append(run(tokens[i:i + 1], i))
    return np.concatenate(rows, axis=0)


def reference_check(cfg: dict, create, model_cfg, weights_of, reference,
                    tol: float) -> dict:
    """A cut of the configuration at its published widths (``model_cfg``
    has the cut's depth), the same seeded weights as served: prefill one
    chunk, then decode through the cache, against the plain float32 full
    forward of ``reference``."""
    import jax.numpy as jnp

    from flexflow_tpu.ffconst import InferenceMode

    chunk = prefill_chunk(cfg)
    m = build_model(ffconfig(cfg, False, max_requests_per_batch=2), create,
                    model_cfg, InferenceMode.INC_DECODING_MODE)
    toks = np.random.default_rng(cfg["weights_seed"]).integers(
        1, cfg["vocab_size"], size=chunk + 4)
    ours = program_logits(m, toks, chunk)
    ref = reference.forward(weights_of(m, model_cfg.num_hidden_layers),
                            jnp.asarray(toks), cfg)
    return compare_logits(ours, ref, tol)


def compare_logits(ours, ref, tol: float) -> dict:
    """Worst position's relative L2 error of the logits. Logits and not
    tokens: with random weights the largest logit changes on rounding."""
    ours, ref = np.asarray(ours, np.float64), np.asarray(ref, np.float64)
    err = np.linalg.norm(ours - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    return {"ok": bool(np.isfinite(err).all() and err.max() < tol),
            "max_rel_l2": float(err.max()), "tol": tol,
            "positions": int(err.shape[0])}
