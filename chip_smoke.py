#!/usr/bin/env python3
"""chip_smoke.py: does the system still start on the chip?

One process, one chip. Drives both products through the entry points a
user calls, once, at the full width of one supported model, and checks
what comes out by the repo's own means:

* kernels  — ``flash_attend`` (compiled by Mosaic) against the float32
             ``reference_attend`` at the serving shapes: width-8 decode
             with the fused in-place append on the stacked cache,
             width-8 tree verify with a bias, a prefill chunk, and the
             packed head_dim-64 decode (Falcon's multi-query and
             Granite-4.0-H's grouped-query layers are served through it;
             the recurrent kernels ``kda_state_step``, ``kda_chunk`` and
             ``ssd_state_step`` are not driven here: their checks are
             ``tools/check_reference_variants.py --config <name>``).
* serving  — LLaMA-2-7B widths (4096 / 11008 / 32 heads / 128 / 32000),
             int8 weights, bf16 cache of 8 slots x 1024 positions, built
             by FFModel + create_llama_model + compile(INFERENCE) and
             served through EngineHandle -> _BackgroundServer ->
             RequestManager: incremental decoding, then SpecInfer with the
             2-layer truncation (deep layers damped) as the draft. Every
             request resolves ok, SpecInfer equals incremental on the
             first 30 tokens of every request, every attention call took
             the compiled Pallas path.
* training — BERT-large (24 x 1024, seq 128, batch 64, bf16) through
             compile(auto_parallel=True) and train_one_batch: loss finite
             and lower after a few steps.

Weights are random from a seed; ``--layers`` cuts serving depth only,
never width. Exit code 0 and ``"ok": true`` only if every phase passed.
Without a TPU the script fails before it builds anything; the CPU
rehearsal (tiny geometry, interpreted kernels) runs only behind
``--rehearse-cpu`` and says so in its result. No throughput is claimed:
the times printed are compile and run seconds of a smoke, not a benchmark.

Standard output ends with two JSON lines: the summary (phases, times,
counts, ``"claim": null``), then the result and nothing else,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.

    python3 chip_smoke.py                 # one chip, all 32 layers
    python3 chip_smoke.py --tp 4          # four-chip host: TP=4 serving,
                                          # searched training on 4 chips
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

FIRST_N_MATCH = 30      # the reference CI gate: python_inference_tests.sh:29


@dataclasses.dataclass(frozen=True)
class Geometry:
    # serving (LLaMA)
    vocab: int = 32000
    hidden: int = 4096
    inter: int = 11008
    heads: int = 32
    kv_heads: int = 32
    layers: int = 32
    draft_layers: int = 2
    quant: str = "int8"
    slots: int = 8
    max_seq: int = 1024
    max_tokens_per_batch: int = 512      # prefill chunk = this // 4
    prompt_lens: tuple = (24, 96, 200, 330, 600, 48)
    new_tokens: int = 64
    spec_depth: int = 7                  # 1 + 7 = the width-8 verify
    # training (BERT-large, bench_train geometry)
    t_vocab: int = 30522
    t_hidden: int = 1024
    t_layers: int = 24
    t_heads: int = 16
    t_seq: int = 128
    t_batch: int = 64
    t_steps: int = 4


REHEARSAL = Geometry(
    vocab=512, hidden=256, inter=512, heads=2, kv_heads=2, layers=4,
    slots=4, max_seq=256, max_tokens_per_batch=128,
    prompt_lens=(8, 20, 70, 150), new_tokens=32,
    t_vocab=512, t_hidden=128, t_layers=2, t_heads=2, t_seq=32, t_batch=8)

EPS = 0.01      # damping of the verifier's layers the draft does not share


class CompileMeter:
    """Compile seconds and persistent-cache traffic from JAX's own
    monitoring events, so a phase's wall time splits into compile and run.
    Compile time is the union of the tracing, lowering and backend-compile
    intervals (they nest, so their durations cannot be summed)."""

    def __init__(self):
        import jax.monitoring as mon

        self.spans = []         # (start, end) of every compile-stage event
        self.hits = 0
        self.writes = 0
        self.programs = []      # (seconds, name) of backend compiles >= 1 s
        mon.register_event_listener(self._on_event)
        mon.register_event_time_span_listener(self._on_span)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1    # recorded when the new entry is written

    def _on_span(self, event, start, end, **kw):
        if event.startswith("/jax/core/compile/"):
            self.spans.append((start, end))
            if event.endswith("backend_compile_duration") and end - start >= 1:
                self.programs.append((round(end - start, 1),
                                      kw.get("fun_name", "?")))

    def snapshot(self):
        return (len(self.spans), self.hits, self.writes, len(self.programs))

    def since(self, snap, wall_s):
        compile_s, edge = 0.0, 0.0
        for start, end in sorted(self.spans[snap[0]:]):
            if end > edge:
                compile_s += end - max(start, edge)
                edge = end
        return {"wall_s": round(wall_s, 1), "compile_s": round(compile_s, 1),
                "run_s": round(wall_s - compile_s, 1),
                "cache_hits": self.hits - snap[1],
                "cache_writes": self.writes - snap[2],
                "programs_compiled": self.programs[snap[3]:]}


def log(msg):
    print(msg, flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def result_line(ok, device):
    """The last line of standard output: exactly these keys, nothing else.
    Whoever runs the smoke reads this line alone; the summary with the
    phases, times and counts is the line before it."""
    return json.dumps({"ok": bool(ok),
                       "device": {"platform": str(device["platform"]),
                                  "kind": str(device["kind"]),
                                  "count": int(device["count"])}})


# ----------------------------------------------------------------------
# placement: where each large array lives, so "everything on the first
# chip" or "everything replicated" is seen
# ----------------------------------------------------------------------
def placement_table(title, named_arrays, top=8):
    import numpy as np

    rows = []
    for name, a in named_arrays:
        sh = a.sharding
        per_dev = {}
        for s in a.addressable_shards:
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) + int(
                np.prod(s.data.shape)) * a.dtype.itemsize
        rows.append({"name": name, "shape": list(a.shape),
                     "dtype": str(a.dtype),
                     "spec": str(getattr(sh, "spec", sh)),
                     "bytes_per_device": per_dev})
    rows.sort(key=lambda r: -max(r["bytes_per_device"].values()))
    log(f"  placement [{title}] (largest {min(top, len(rows))} of "
        f"{len(rows)}):")
    for r in rows[:top]:
        mib = {d: round(b / 2**20, 1)
               for d, b in sorted(r["bytes_per_device"].items())}
        log(f"    {r['name']:<34} {str(r['shape']):<22} {r['dtype']:<9} "
            f"{r['spec']:<34} MiB/device {mib}")
    return rows[:top]


def named_leaves(model):
    from flexflow_tpu.quant import is_quantized

    for lname, lp in model.params.items():
        for w, leaf in lp.items():
            if is_quantized(leaf):
                yield f"{lname}.{w}.q", leaf.q
                yield f"{lname}.{w}.scale", leaf.scale
            else:
                yield f"{lname}.{w}", leaf


def device_memory(devices):
    out = {}
    for d in devices:
        st = d.memory_stats() or {}
        out[d.id] = {"bytes_in_use": st.get("bytes_in_use"),
                     "peak_bytes_in_use": st.get("peak_bytes_in_use")}
    return out


# ----------------------------------------------------------------------
# phase: kernels against the float32 reference
# ----------------------------------------------------------------------
def phase_kernels(g: Geometry):
    import numpy as np
    import jax
    import jax.numpy as jnp

    from flexflow_tpu import kernels as ffk
    from flexflow_tpu.kernels.attention import (NEG_INF, flash_attend,
                                                reference_attend)
    from flexflow_tpu.ops import kv_layout as kvl

    interp = ffk.pallas_interpret_forced()
    R, L, W = 2, 2, 8
    H, KH, S = g.heads, g.kv_heads, g.max_seq
    D = g.hidden // g.heads
    chunk = g.max_tokens_per_batch // 4
    rng = np.random.default_rng(0)

    def rnd(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    k, v = rnd(L, R, KH, S, D), rnd(L, R, KH, S, D)
    out = {}

    def f32(*arrays):
        return [a.astype(jnp.float32) for a in arrays]

    def close(name, got, want, tol=5e-2):
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        out[name] = round(err, 4)
        check(np.isfinite(err) and err < tol,
              f"kernel {name}: max |pallas - reference| = {err}")

    # width-8 decode, one real token per row, fused append on the stack
    pos = jnp.asarray([S // 2 + 3, 5], jnp.int32)       # mid-block, block 0
    q = rnd(R, W, H, D)
    kn, vn = rnd(R, 1, KH, D), rnd(R, 1, KH, D)
    qpos = pos[:, None] + jnp.arange(W)[None, :]
    lengths = pos + 1
    got, k2, v2 = flash_attend(q, k, v, lengths, qpos,
                               append_kv=(kn, vn, pos), causal=True,
                               layer_idx=1, interpret=interp)
    rows = jnp.arange(R)
    k_ref = k[1].at[rows, :, pos].set(kn[:, 0])
    v_ref = v[1].at[rows, :, pos].set(vn[:, 0])
    close("decode_append", got,
          reference_attend(*f32(q, k_ref, v_ref), lengths, qpos,
                           causal=True))
    check(bool(jnp.array_equal(k2[1], k_ref) & jnp.array_equal(v2[1], v_ref)
               & jnp.array_equal(k2[0], k[0])),
          "kernel decode_append: in-place cache write differs from scatter")
    # width-8 tree verify: committed prefix + a chain's ancestor mask
    key = jnp.arange(S)[None, None, :]
    node = key - pos[:, None, None]
    open_ = (node < 0) | ((node >= 0) & (node <= jnp.arange(W)[None, :, None]))
    bias = jnp.where(open_, 0.0, NEG_INF).astype(jnp.float32)
    lengths = pos + W
    got = flash_attend(q, k2, v2, lengths, qpos, bias=bias, causal=False,
                       layer_idx=1, interpret=interp)
    close("tree_verify", got,
          reference_attend(*f32(q, k2[1], v2[1]), lengths, qpos, bias=bias,
                           causal=False))
    # a prefill chunk deep enough to stream several cache blocks
    start = jnp.asarray([S - 2 * chunk, 0], jnp.int32)
    q = rnd(R, chunk, H, D)
    qpos = start[:, None] + jnp.arange(chunk)[None, :]
    lengths = start + chunk
    got = flash_attend(q, k2, v2, lengths, qpos, causal=True, layer_idx=0,
                       interpret=interp)
    close("prefill_chunk", got,
          reference_attend(*f32(q, k2[0], v2[0]), lengths, qpos,
                           causal=True))
    # the packed head_dim-64 path (two positions per 128-lane row, the
    # cache handed over as it is stored: ops/kv_layout.py), at Falcon-7B's
    # multi-query heads: no model on the smoke's serving path reaches it,
    # the benchmark's Falcon cells do
    H6, D6 = 71, 64
    k6, v6 = rnd(R, 1, S, D6), rnd(R, 1, S, D6)
    q6, kn6, vn6 = rnd(R, W, H6, D6), rnd(R, 1, 1, D6), rnd(R, 1, 1, D6)
    qpos = pos[:, None] + jnp.arange(W)[None, :]
    got, k6n, _ = flash_attend(q6, kvl.to_rows(k6, 2), kvl.to_rows(v6, 2),
                               pos + 1, qpos, append_kv=(kn6, vn6, pos),
                               causal=True, interpret=interp)
    k6_ref = k6.at[rows, :, pos].set(kn6[:, 0])
    v6_ref = v6.at[rows, :, pos].set(vn6[:, 0])
    close("packed_d64_decode_append", got,
          reference_attend(*f32(q6, k6_ref, v6_ref), pos + 1, qpos,
                           causal=True))
    check(bool(jnp.array_equal(kvl.to_positions(k6n, 2), k6_ref)),
          "kernel packed_d64: in-place cache write differs from scatter")
    jax.block_until_ready(got)
    return {"max_abs_err": out, "shapes": {
        "heads": H, "kv_heads": KH, "head_dim": D, "cache_len": S,
        "decode_width": W, "prefill_chunk": chunk}}


# ----------------------------------------------------------------------
# phase: serving
# ----------------------------------------------------------------------
def build_serving(g: Geometry, tp: int):
    import flexflow_tpu as ff
    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models.llama import LLAMAConfig, create_llama_model
    from flexflow_tpu.quant import QuantizedWeight, is_quantized

    vcfg = LLAMAConfig(vocab_size=g.vocab, hidden_size=g.hidden,
                       intermediate_size=g.inter,
                       num_hidden_layers=g.layers,
                       num_attention_heads=g.heads,
                       num_key_value_heads=g.kv_heads,
                       max_position_embeddings=g.max_seq)
    ffc = ff.FFConfig(max_requests_per_batch=g.slots,
                      max_sequence_length=g.max_seq,
                      max_tokens_per_batch=g.max_tokens_per_batch,
                      kv_cache_dtype="bfloat16", compute_dtype="bfloat16",
                      seed=7, quantization_type=g.quant,
                      decode_block_steps=16, spec_rounds_per_call=4,
                      tensor_parallelism_degree=tp, num_devices=tp)

    def build(cfg, mode):
        m = ff.FFModel(ffc)
        create_llama_model(m, cfg, mode=mode,
                           data_type=ff.DataType.DT_BFLOAT16)
        m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
        return m

    llm = build(vcfg, InferenceMode.TREE_VERIFY_MODE)
    # damp the residual writes of the layers the draft does not have, so a
    # truncated random draft predicts a random verifier at a realistic rate
    for i in range(g.draft_layers, g.layers):
        for lname, w in ((f"layers.{i}.self_attn", "wo"),
                         (f"layers.{i}.mlp.down_proj", "kernel")):
            leaf = llm.params[lname][w]
            llm.params[lname][w] = (
                QuantizedWeight(leaf.qtype, leaf.q, leaf.scale * EPS,
                                leaf.rows, leaf.dtype)
                if is_quantized(leaf) else leaf * EPS)
    dcfg = dataclasses.replace(vcfg, num_hidden_layers=g.draft_layers)
    ssm = build(dcfg, InferenceMode.BEAM_SEARCH_MODE)
    for lname, lp in ssm.params.items():
        for w in lp:
            lp[w] = llm.params[lname][w]       # the draft IS the truncation
    return llm, ssm


def serve_pass(handle, prompts, new_tokens, timeout_s=900.0):
    """Replay the prompts through the background server's submission queue
    (serve/loadgen.LoadRunner, all arriving at once) and return the
    GenerationResults in prompt order."""
    from flexflow_tpu.serve.loadgen import LoadRequest, LoadRunner

    schedule = [LoadRequest(idx=i, arrival_s=0.0, tenant="default", prompt=p,
                            max_new_tokens=new_tokens)
                for i, p in enumerate(prompts)]
    try:
        records = LoadRunner(handle).run(schedule, timeout_s=timeout_s)
    finally:
        handle.stop_server()
    for rec in records:
        check(rec.status == "ok", f"request {rec.idx} resolved {rec.status!r}")
        check(rec.output_tokens == new_tokens,
              f"request {rec.idx}: {rec.output_tokens} tokens, "
              f"wanted {new_tokens}")
    by_prompt = {tuple(r.input_tokens): r for r in handle.rm.results.values()}
    return [by_prompt[tuple(p)] for p in prompts]


def phase_serving(g: Geometry, tp: int, meter: CompileMeter):
    import numpy as np
    import jax

    from flexflow_tpu import kernels as ffk
    from flexflow_tpu.serve.loadgen import EngineHandle

    ffk.reset_dispatch_stats()
    t0, snap = time.perf_counter(), meter.snapshot()
    llm, ssm = build_serving(g, tp)
    jax.block_until_ready((llm.params, llm.op_state, ssm.op_state))
    out = {"layers": g.layers, "tp": tp,
           "mesh": {k: int(v) for k, v in llm.mesh.shape.items()},
           "mesh_devices": [d.id for d in llm.mesh.devices.flat],
           "build": meter.since(snap, time.perf_counter() - t0)}
    log(f"  built {g.layers}-layer verifier + {g.draft_layers}-layer draft "
        f"on devices {out['mesh_devices']}: {out['build']}")
    def kv_placement(when):
        return placement_table(
            f"serving KV cache, {when}",
            [(f"{who}.kv_cache.{n}", a)
             for who, m in (("llm", llm), ("ssm", ssm))
             for n, a in m.op_state["kv_cache"].items()])

    out["placement"] = {
        "weights": placement_table("serving weights", named_leaves(llm)),
        "kv_cache": kv_placement("as built")}
    out["memory_after_build"] = device_memory(llm.mesh.devices.flat)

    rng = np.random.RandomState(0)
    prompts = [[int(t) for t in rng.randint(1, g.vocab, size=n)]
               for n in g.prompt_lens]

    # One model is served both ways here and the tokens compared. The
    # speculative handle comes first: a front door that is handed draft
    # models builds the engine that will verify the model, so the model's
    # manager decodes at the engine's verify width from its first block on
    # (a model that no engine verifies decodes one token a row), and the
    # incremental pass's decode block serves the speculative pass's parked
    # requests too.
    spec_handle = EngineHandle(llm, ssms=[ssm], spec_depth=g.spec_depth)
    out["decode_width"] = llm._inference_manager.decode_width

    t0, snap = time.perf_counter(), meter.snapshot()
    incr_handle = EngineHandle(llm)
    incr = serve_pass(incr_handle, prompts, g.new_tokens)
    out["incremental"] = {
        **meter.since(snap, time.perf_counter() - t0),
        "tokens": sum(len(r.output_tokens) for r in incr),
        "scheduler_loop": incr_handle.rm.scheduler_loop}
    log(f"  incremental: {out['incremental']}")

    t0, snap = time.perf_counter(), meter.snapshot()
    spec = serve_pass(spec_handle, prompts, g.new_tokens)
    n_match = sum(a.output_tokens[:FIRST_N_MATCH]
                  == b.output_tokens[:FIRST_N_MATCH]
                  for a, b in zip(incr, spec))
    n_full = sum(a.output_tokens == b.output_tokens
                 for a, b in zip(incr, spec))
    out["specinfer"] = {
        **meter.since(snap, time.perf_counter() - t0),
        "tokens": sum(len(r.output_tokens) for r in spec),
        "scheduler_loop": spec_handle.rm.scheduler_loop,
        f"match_first{FIRST_N_MATCH}": f"{n_match}/{len(spec)}",
        "match_full": f"{n_full}/{len(spec)}"}
    # the verifier's prefill step and decode block compiled in the
    # incremental pass serve this pass too: of those two names, only the
    # draft's own prefill step may compile here
    names = [n for _, n in out["specinfer"]["programs_compiled"]]
    out["specinfer"]["verifier_programs_reused"] = (
        names.count("jit(_prefill_impl)") <= 1 and "jit(block)" not in names)
    log(f"  specinfer: {out['specinfer']}")
    out["attention"] = {"fast_path_traces": ffk.fast_path_count,
                        "fallback_traces": dict(ffk.fallback_counts),
                        "interpreted": ffk.pallas_interpret_forced()}
    # the caches are donated through every program: a placement that
    # drifted from "as built" means the programs recompiled for it
    out["placement"]["kv_cache_after"] = kv_placement("after both passes")
    out["memory_after_serving"] = device_memory(llm.mesh.devices.flat)
    check(ffk.fast_path_count > 0, "Pallas serving attention never engaged")
    check(not ffk.fallback_counts,
          f"attention fell back to jnp: {ffk.fallback_counts}")
    check(n_match == len(spec),
          f"SpecInfer != incremental on the first {FIRST_N_MATCH} tokens: "
          f"{n_match}/{len(spec)} requests match")
    return out


# ----------------------------------------------------------------------
# phase: training
# ----------------------------------------------------------------------
def phase_training(g: Geometry, n_dev: int, meter: CompileMeter):
    import numpy as np
    import jax

    import flexflow_tpu as ff
    from flexflow_tpu.search.machine_model import chip_for_device

    B, S, H, V = g.t_batch, g.t_seq, g.t_hidden, g.t_vocab
    # auto_parallel searches per-op shardings inside the given mesh: on
    # one chip it degenerates; on N chips it gets a data x model mesh
    tp = 2 if n_dev % 2 == 0 else 1
    config = ff.FFConfig(batch_size=B, compute_dtype="bfloat16",
                         auto_parallel=True, num_devices=n_dev,
                         data_parallelism_degree=n_dev // tp,
                         tensor_parallelism_degree=tp)
    model = ff.FFModel(config)
    tokens = model.create_tensor([B, S], ff.DataType.DT_INT32)
    x = model.embedding(tokens, V, H, name="embed")
    for i in range(g.t_layers):
        attn = model.multihead_attention(x, x, x, embed_dim=H,
                                         num_heads=g.t_heads,
                                         name=f"enc.{i}.attn")
        x = model.layer_norm(model.add(attn, x), axes=[-1],
                             name=f"enc.{i}.ln1")
        h = model.dense(x, 4 * H, ff.ActiMode.AC_MODE_GELU,
                        name=f"enc.{i}.fc1")
        h = model.dense(h, H, name=f"enc.{i}.fc2")
        x = model.layer_norm(model.add(h, x), axes=[-1], name=f"enc.{i}.ln2")
    logits = model.dense(x, V, name="mlm_head")
    model.softmax(model.reshape(logits, [B * S, V]))

    t0, snap = time.perf_counter(), meter.snapshot()
    model.compile(optimizer=ff.SGDOptimizer(model, lr=0.01),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    jax.block_until_ready(model.params)
    out = {"devices": n_dev,
           "mesh": {k: int(v) for k, v in model.mesh.shape.items()},
           "tpu_chip_resolved": chip_for_device(),
           # search + profiled re-rank + parameter init, before any step
           "search_and_init": meter.since(snap, time.perf_counter() - t0)}
    check(model.strategy is not None, "auto_parallel produced no strategy")
    check(model.mesh.devices.size == n_dev,
          f"training mesh has {model.mesh.devices.size} devices, "
          f"wanted {n_dev}")
    log(f"  searched + initialised: {out}")
    out["placement"] = placement_table("training weights",
                                       named_leaves(model))

    rng = np.random.RandomState(0)
    xs = rng.randint(0, V, size=(B, S)).astype(np.int32)
    ys = rng.randint(0, V, size=(B * S, 1)).astype(np.int32)
    t0, snap = time.perf_counter(), meter.snapshot()
    losses = [model.train_one_batch([xs], ys) for _ in range(g.t_steps)]
    out["steps"] = {**meter.since(snap, time.perf_counter() - t0),
                    "losses": [round(x, 4) for x in losses]}
    out["memory_after_training"] = device_memory(model.mesh.devices.flat)
    log(f"  steps: {out['steps']}")
    check(all(np.isfinite(x) for x in losses), f"loss not finite: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall over {g.t_steps} steps: {losses}")
    return out


# ----------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--layers", type=int, default=None,
                    help="serving depth (cut depth, never width)")
    ap.add_argument("--tp", type=int, default=1,
                    help="chips: serving runs tensor_parallelism_degree=N, "
                         "training searches over N (default: one chip)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny geometry on the CPU with interpreted "
                         "kernels; a rehearsal, not a chip result")
    args = ap.parse_args(argv)

    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["FF_PALLAS_INTERPRET"] = "1"
        if args.tp > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.tp}")
    import jax

    dev = jax.devices()[0]
    if not args.rehearse_cpu and dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found platform {dev.platform!r}); "
              "nothing was run. `--rehearse-cpu` runs the tiny CPU "
              "rehearsal.", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.tp:
        print(f"chip_smoke: --tp {args.tp} needs {args.tp} devices, jax "
              f"sees {len(jax.devices())}", file=sys.stderr)
        return 2

    import jaxlib

    try:
        from flexflow_tpu import kernels as ffk
        from flexflow_tpu.native import native_available
        from flexflow_tpu.search.machine_model import chip_for_device
        from flexflow_tpu.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        # the script alone, without the program beside it, proves nothing
        print(f"chip_smoke: the flexflow_tpu package is not beside this "
              f"script ({e}); nothing was run.", file=sys.stderr)
        return 2

    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    g = REHEARSAL if args.rehearse_cpu else Geometry()
    if args.layers is not None:
        # a verifier no deeper than its draft makes the speculation
        # controller park every request: the tree engine would never run
        if args.layers <= g.draft_layers:
            ap.error(f"--layers must exceed the draft's {g.draft_layers}")
        g = dataclasses.replace(g, layers=args.layers)
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"chip_smoke: {'CPU REHEARSAL (not a chip result)' if args.rehearse_cpu else 'chip run'}")
    log(f"  device {device}; using {args.tp} of {device['count']} "
        f"(ids {[d.id for d in jax.devices()[:args.tp]]})")
    log(f"  jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {libtpu_version} python {sys.version.split()[0]}")
    log(f"  compile cache: {cache_dir} "
        f"({'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'fixed in-checkout path'})")
    log(f"  serving depth {g.layers} of 32 layers"
        + ("" if g.layers == 32 or args.rehearse_cpu else " (DEPTH CUT)"))

    summary = {"ok": False, "device": device, "rehearsal": args.rehearse_cpu,
               "versions": {"jax": jax.__version__,
                            "jaxlib": jaxlib.__version__,
                            "libtpu": libtpu_version},
               "compile_cache_dir": cache_dir, "phases": {}}

    def phase_environment():
        # use_pallas raises when FF_PALLAS_INTERPRET is set on a TPU, and
        # an unknown TPU kind raises, before anything is built
        check(ffk.use_pallas(), "Pallas kernels are not enabled")
        check(args.rehearse_cpu or not ffk.pallas_interpret_forced(),
              "Pallas interpret mode is forced")
        return {"chip": chip_for_device(dev),
                "native_library_built": native_available()}

    failed = []
    t_all = time.perf_counter()
    for name, fn in (("environment", phase_environment),
                     ("kernels", lambda: phase_kernels(g)),
                     ("serving", lambda: phase_serving(g, args.tp, meter)),
                     ("training", lambda: phase_training(g, args.tp, meter))):
        log(f"[{name}]")
        t0, snap = time.perf_counter(), meter.snapshot()
        try:
            res = fn()
            res["ok"] = True
        except Exception as e:
            traceback.print_exc()
            res = {"ok": False, "error": f"{type(e).__name__}: {e}"[:500]}
            failed.append(name)
        res["total"] = meter.since(snap, time.perf_counter() - t0)
        summary["phases"][name] = res
        log(f"[{name}] {'ok' if res['ok'] else 'FAILED'} {res['total']}")
        if failed == ["environment"]:
            break                   # nothing built on a wrong footing
    summary["wall_s"] = round(time.perf_counter() - t_all, 1)
    summary["memory"] = device_memory(jax.devices()[:args.tp])
    summary["failed_phases"] = failed
    summary["ok"] = not failed
    summary["claim"] = None
    if not args.rehearse_cpu:
        # the placement tables are too long for the result line
        out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "chiprun_out")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, f"chip_smoke_tp{args.tp}.json"),
                  "w") as f:
            json.dump(summary, f, indent=1)
    for res in summary["phases"].values():
        res.pop("placement", None)
    print(json.dumps(summary), flush=True)
    print(result_line(summary["ok"], device), flush=True)
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
