"""Decode-step profiler: attribute fused-decode time on the real chip.

Modes (combine freely; each is one model build + timed decode blocks,
fenced by host readback):

  --layers     layer-count scaling (32/16/8): splits ms/step into a
               per-layer slope (vs the weight-stream bound) and a fixed
               per-step intercept (embed + final norm + lm_head + argmax
               + loop machinery).
  --width      decode_block at the verify-consistent width vs width=1.
  --jnp-attn   use_pallas=False variant: XLA jnp attention vs the Pallas
               kernel path.
  --head       head-only fused loop (embed -> final norm -> lm_head ->
               argmax) isolating the fixed per-step overhead.

Findings that shaped the shipped code (7B-geometry int8, one v5e):
  * per-layer slope 0.325 ms vs 0.247 ms stream bound;
  * verify-consistent width-8 decode costs only +4.6% over width-1;
  * native int8xint8 MXU gemms are NOT faster than the shipped
    dequant-into-bf16 gemm at M=64, so dequant-on-read stays;
  * jnp whole-cache attention at S=256 is slower than the Pallas block
    kernel (12.0 vs 11.2 ms/step), so the kernel dispatch stays.

Usage: python tools/profile_decode.py [--layers] [--width] [--jnp-attn]
                                      [--head]
"""

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
    tok = np.ones((R,), np.int32)
    pos = np.full((R,), prompt_len, np.int32)
    act = np.ones((R,), bool)
    ifm.decode_block(tok, pos, act, 4)            # compile
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = ifm.decode_block(tok, pos, act, n)  # one device call
        np.asarray(out)
        best = min(best, (time.perf_counter() - t0) / n)
    return best


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


def run_width(bench):
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.serve.engine import make_decode_block

    R, P = bench.NUM_REQUESTS, bench.PROMPT_LEN
    m, ifm = build(bench.LAYERS, bench)
    t = time_block(ifm, R, P)
    print(f"decode_block(width={ifm.decode_width}): {t * 1e3:.3f} ms/step")
    blk1 = make_decode_block(m, jnp.bfloat16, 128, width=1)
    rng = jax.random.PRNGKey(0)
    tok = jnp.ones((R,), jnp.int32)
    pos = jnp.full((R,), P, jnp.int32)
    act = jnp.ones((R,), bool)

    def run1(n):
        toks, st, _ = blk1(m.params, m.op_state, tok, pos, act, rng,
                           jnp.int32(n))
        m.op_state = st
        return np.asarray(toks)

    run1(4)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        run1(96)
        best = min(best, (time.perf_counter() - t0) / 96)
    print(f"decode_block(width=1): {best * 1e3:.3f} ms/step "
          f"(width-{ifm.decode_width} costs "
          f"{(t / best - 1) * 100:+.1f}%)")


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


def main():
    from flexflow_tpu.utils.compile_cache import enable_compile_cache

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
