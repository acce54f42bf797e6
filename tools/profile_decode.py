"""Decode-step profiler: attribute fused-decode time on the real chip.

Modes (combine freely; each is one model build + timed decode blocks,
fenced by host readback):

  --layers     layer-count scaling (32/16/8): splits ms/step into a
               per-layer slope (vs the weight-stream bound) and a fixed
               per-step intercept (embed + final norm + lm_head + argmax
               + loop machinery).
  --width      decode_block at the width the manager resolved (one token
               a row unless an engine verifies the model) and, beside it,
               at the other of 1 and the sublane-padded verify width 8.
  --config NAME [NAME ...] [--rehearse] [--cut N] [--positions N]
               the same A/B on a benchmark configuration
               (benchmark/configs/NAME.json, built as its cell builds it; N
               layers instead of its depth): ragged prompts are prefilled
               into every slot but the last, then the two blocks decode the
               same rows. One JSON line a configuration: the width resolved,
               ms a step at each width, and how many of the tokens agree
               (a near-tie may fall either way in bfloat16; float32 on the
               CPU, where they must all agree). --rehearse: the CPU, the
               configuration's rehearsal sizes, interpreted kernels, no
               times.
  --jnp-attn   use_pallas=False variant: XLA jnp attention vs the Pallas
               kernel path.
  --head       head-only fused loop (embed -> final norm -> lm_head ->
               argmax) isolating the fixed per-step overhead.

Findings that shaped the shipped code (7B-geometry int8, one v5e):
  * per-layer slope 0.325 ms vs 0.247 ms stream bound;
  * verify-consistent width-8 decode costs only +4.6% over width-1 at 8
    slots (64 rows: under the ridge of 240 flops a byte of int8 weight);
    at 16 and 32 slots it is over the ridge, so since PR 38 a model takes
    the verify width only when an engine verifies it (PERF.md section 6);
  * native int8xint8 MXU gemms are NOT faster than the shipped
    dequant-into-bf16 gemm at M=64, so dequant-on-read stays;
  * jnp whole-cache attention at S=256 is slower than the Pallas block
    kernel (12.0 vs 11.2 ms/step), so the kernel dispatch stays.

Usage: python tools/profile_decode.py [--layers] [--width] [--jnp-attn]
                                      [--head]
       python tools/profile_decode.py --config falcon-7b [--rehearse]
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, ".")


def build(layers, bench, use_pallas=True):
    import flexflow_tpu as ff
    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models.llama import LLAMAConfig, create_llama_model
    from flexflow_tpu.serve.inference_manager import InferenceManager

    vcfg = LLAMAConfig(
        vocab_size=bench.VOCAB, hidden_size=bench.HIDDEN,
        intermediate_size=bench.INTER, num_hidden_layers=layers,
        num_attention_heads=bench.HEADS,
        num_key_value_heads=bench.KV_HEADS,
        max_position_embeddings=bench.MAX_SEQ)
    ffc = ff.FFConfig(max_requests_per_batch=bench.NUM_REQUESTS,
                      max_sequence_length=bench.MAX_SEQ,
                      max_tokens_per_batch=bench.NUM_REQUESTS
                      * bench.PROMPT_LEN,
                      kv_cache_dtype="bfloat16", compute_dtype="bfloat16",
                      seed=7, quantization_type=bench.QUANT,
                      decode_block_steps=128, use_pallas=use_pallas)
    m = ff.FFModel(ffc)
    create_llama_model(m, vcfg, mode=InferenceMode.TREE_VERIFY_MODE,
                       data_type=ff.DataType.DT_BFLOAT16)
    m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    return m, InferenceManager(m)


def time_block(ifm, R, prompt_len, n=96):
    """Seconds a step of the manager's own decode block."""
    return best_ms(ifm.decode_block, np.ones((R,), np.int32),
                   np.full((R,), prompt_len, np.int32), np.ones((R,), bool),
                   n) / 1e3


def run_layer_scaling(bench):
    import gc

    from flexflow_tpu.search.machine_model import TPU_CHIPS

    bw = TPU_CHIPS["v5e"].hbm_bandwidth
    R, P = bench.NUM_REQUESTS, bench.PROMPT_LEN
    results = {}
    lm_head = 0
    for L in (32, 16, 8):
        m, ifm = build(L, bench)
        wbytes = sum(int(w.nbytes) for ln, lp in m.params.items()
                     if "embed" not in ln for w in lp.values())
        lm_head = sum(int(w.nbytes) for w in m.params["lm_head"].values())
        t = time_block(ifm, R, P)
        results[L] = (t, wbytes)
        print(f"L={L:2d}: {t * 1e3:7.3f} ms/step  weights="
              f"{wbytes / 1e9:.2f} GB  stream_bound={wbytes / bw * 1e3:.3f}"
              " ms")
        del m, ifm
        gc.collect()
    (tA, _), (tB, _) = results[32], results[8]
    slope = (tA - tB) / (32 - 8)
    fixed = tA - slope * 32
    per_layer_bytes = (results[32][1] - results[8][1]) / (32 - 8)
    print(f"slope   = {slope * 1e3:.3f} ms/layer "
          f"(stream bound {per_layer_bytes / bw * 1e3:.3f} ms/layer, "
          f"ratio {slope / (per_layer_bytes / bw):.2f})")
    print(f"fixed   = {fixed * 1e3:.3f} ms/step "
          f"(lm_head stream alone {lm_head / bw * 1e3:.3f} ms)")


def other_width(width: int) -> int:
    from flexflow_tpu.kernels.attention import SUBLANE

    return SUBLANE if width == 1 else 1


def block_at(m, width: int, steps: int):
    """``(tok, pos, act, n) -> tokens [R, n]`` through the decode block of
    ``m`` at ``width``, the state threaded through the model."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.serve.engine import make_decode_block

    blk = make_decode_block(m, jnp.dtype(m.config.compute_dtype), steps,
                            width=width)
    rng = jax.random.PRNGKey(0)

    def run(tok, pos, act, n):
        toks, m.op_state, _ = blk(m.params, m.op_state, jnp.asarray(tok),
                                  jnp.asarray(pos), jnp.asarray(act), rng,
                                  jnp.int32(n))
        return np.asarray(toks)[:, :n]

    return run


def best_ms(run, tok, pos, act, n):
    run(tok, pos, act, 4)                         # compile
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        run(tok, pos, act, n)                     # its read-back = fence
        best = min(best, (time.perf_counter() - t0) / n)
    return best * 1e3


def run_width(bench):
    R, P = bench.NUM_REQUESTS, bench.PROMPT_LEN
    m, ifm = build(bench.LAYERS, bench)
    tok = np.ones((R,), np.int32)
    pos = np.full((R,), P, np.int32)
    act = np.ones((R,), bool)
    ms = {w: best_ms(block_at(m, w, 128), tok, pos, act, 96)
          for w in (ifm.decode_width, other_width(ifm.decode_width))}
    (w0, t0), (w1, t1) = ms.items()
    print(f"decode_block(width={w0}, resolved): {t0:.3f} ms/step")
    print(f"decode_block(width={w1}): {t1:.3f} ms/step "
          f"({(t1 / t0 - 1) * 100:+.1f}% against width {w0})")


def run_config(name: str, rehearse: bool, cut=None, positions=None) -> dict:
    """The two widths of the decode block on one benchmark configuration.
    ``positions``: the shortest prompt's length (default: two prefill chunks
    and one; a configuration whose layers read more the more a row holds,
    such as evabyte-6.5b's summaries beyond 2048, is profiled at its
    cell's lengths: ``--positions 14000``)."""
    import jax

    from benchmark import run as bench_run
    from benchmark.families import _common as C
    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models import FAMILIES
    from flexflow_tpu.serve.inference_manager import InferenceManager
    from flexflow_tpu.serve.request_manager import RequestManager as RM

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    f32 = {}
    if rehearse:
        bench_run.apply_rehearsal(cfg, {"cycle": []})
        f32 = dict(compute_dtype="float32", kv_cache_dtype="float32",
                   quantization_type=None)
    family = bench_run.load_module("families", cfg["family"])
    mc = family._model_cfg(cfg, cut)
    m = C.build_model(C.ffconfig(cfg, False, **f32),
                      FAMILIES[cfg["family"]].build, mc,
                      InferenceMode.INC_DECODING_MODE)
    ifm = InferenceManager(m)
    R, steps = m.config.max_requests_per_batch, m.config.decode_block_steps
    chunk = C.prefill_chunk(cfg)
    rng = np.random.default_rng(cfg["weights_seed"])
    prompts = [rng.integers(1, cfg["vocab_size"],
                            size=(positions or 2 * chunk + 1) + 3 * r).tolist()
               for r in range(R - 1)]             # the last slot stays idle
    for at in range(0, max(map(len, prompts)) - 1, chunk):
        rows = [(r, p[at:min(at + chunk, len(p) - 1)], at)
                for r, p in enumerate(prompts) if at < len(p) - 1]
        ifm.step(RM._meta_from_rows(R, chunk, rows), want_output=False)
    tok = np.array([p[-1] for p in prompts] + [0], np.int32)
    pos = np.array([len(p) - 1 for p in prompts] + [0], np.int32)
    act = np.arange(R) < R - 1
    widths = (ifm.decode_width, other_width(ifm.decode_width))
    blocks = {w: block_at(m, w, steps) for w in widths}
    # the second block rewrites the positions the first wrote: a row only
    # ever attends up to its own position
    toks = {w: blocks[w](tok, pos, act, steps)[act] for w in widths}
    same = toks[widths[0]] == toks[widths[1]]
    out = {"config": name, "layers": mc.num_hidden_layers,
           "rows": int(act.sum()), "positions": [int(pos[act].min()),
                                                 int(pos[act].max())],
           "steps": steps, "width_resolved": widths[0],
           "widths": list(widths),
           "tokens_equal": float(same.mean()),
           "first_tokens_equal": float(same[:, 0].mean()),
           "attention": C.attention_paths(),
           "device": jax.devices()[0].device_kind}
    if not rehearse:
        out["ms_per_step"] = {str(w): round(best_ms(blocks[w], tok, pos, act,
                                                    steps), 4)
                              for w in widths}
    return out


def run_jnp_attention(bench):
    m, ifm = build(bench.LAYERS, bench, use_pallas=False)
    t = time_block(ifm, bench.NUM_REQUESTS, bench.PROMPT_LEN)
    print(f"decode_block(jnp attention, width={ifm.decode_width}): "
          f"{t * 1e3:.3f} ms/step")
    return m


def run_head_only(bench, model):
    """Head-only loop on the REAL params of an already-built model."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.quant import qmatmul, qtake
    from flexflow_tpu.search.machine_model import TPU_CHIPS

    bw = TPU_CHIPS["v5e"].hbm_bandwidth
    R = bench.NUM_REQUESTS
    params = model.params
    emb = params["embed_tokens"]["weight"]
    head = params["lm_head"]["kernel"]
    fn_w = params["norm"]["weight"]

    def head_loop(params_tuple, tok0, n):
        emb, fn_w, head = params_tuple

        def body(carry):
            i, tok, acc = carry
            x = qtake(emb, tok).astype(jnp.bfloat16)          # [R, H]
            xf = x.astype(jnp.float32)
            x = (xf * jax.lax.rsqrt(
                jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6)
                * fn_w.astype(jnp.float32)).astype(jnp.bfloat16)
            logits = qmatmul(x, head, jnp.bfloat16, out_dtype=jnp.float32)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return i + 1, nxt, acc + jnp.sum(nxt)

        _, tok, acc = jax.lax.while_loop(
            lambda c: c[0] < n, body, (jnp.int32(0), tok0, jnp.int32(0)))
        return tok, acc

    jfn = jax.jit(head_loop)
    tok0 = jnp.ones((R,), jnp.int32)
    np.asarray(jfn((emb, fn_w, head), tok0, jnp.int32(96))[0])
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(jfn((emb, fn_w, head), tok0, jnp.int32(96))[0])
        best = min(best, (time.perf_counter() - t0) / 96)
    print(f"head_only loop: {best * 1e3:.3f} ms/step "
          f"(lm_head stream bound "
          f"{getattr(head, 'nbytes', 0) / bw * 1e3:.3f} ms)")


def main_configs(argv) -> int:
    rehearse = "--rehearse" in argv
    cut, positions = (int(argv[argv.index(o) + 1]) if o in argv else None
                      for o in ("--cut", "--positions"))
    names = [a for i, a in enumerate(argv) if not a.startswith("--")
             and argv[i - 1] not in ("--cut", "--positions")]
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["FF_PALLAS_INTERPRET"] = "1"
    import jax

    if not rehearse and jax.devices()[0].platform != "tpu":
        print("no TPU; nothing was run", file=sys.stderr)
        return 2
    for name in names:
        print(json.dumps(run_config(name, rehearse, cut, positions)),
              flush=True)
    return 0


def main():
    from flexflow_tpu.utils.compile_cache import enable_compile_cache

    if "--config" in sys.argv:
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        if "--rehearse" not in sys.argv:
            enable_compile_cache()
        sys.exit(main_configs(sys.argv[sys.argv.index("--config") + 1:]))
    enable_compile_cache()
    args = set(sys.argv[1:])
    sys.argv = [sys.argv[0]]       # bench.py parses argv at import time
    import bench

    if "--layers" in args or not args:
        run_layer_scaling(bench)
    if "--width" in args:
        run_width(bench)
    m = None
    if "--jnp-attn" in args:
        m = run_jnp_attention(bench)
    if "--head" in args:
        if m is None:
            m, _ = build(bench.LAYERS, bench)
        run_head_only(bench, m)


if __name__ == "__main__":
    main()
