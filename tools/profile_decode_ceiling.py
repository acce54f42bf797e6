"""Pure-JAX decode-block ceiling for the 7B int8 serving geometry.

VERDICT r4 item 8: the shipped decode block reaches ~0.82 of its HBM
weight-stream bound, with the residual attributed to XLA's zero-overlap
weight-staging DMAs (PARITY.md r4 record). This script asks the ResNet
question (tools/profile_resnet.py): is that a FRAMEWORK overhead or the
XLA ceiling on this chip? It hand-writes the minimal decode step —
embed gather, rmsnorm, dequant-into-bf16 int8 gemms, rotary, the Pallas
flash_attend kernel with fused KV append, SwiGLU, lm_head, argmax —
with no framework graph walk, engine, or BatchMeta machinery, fuses T
steps into one while_loop, and times it against the same stream bound
bench.decode_roofline uses.

Variants:
  unrolled — 32 traced layer bodies (the framework's structure)
  scanned  — lax.scan over stacked per-layer weights (uniform staging)

If the hand-rolled variants land at the same fraction of the bound as
the framework's decode block, the residual is XLA's lowering, not the
framework — and the roofline target is formally re-baselined to that
measured ceiling.

Usage: python tools/profile_decode_ceiling.py [--layers N] [--steps T]
"""

import math
import sys
import time

import numpy as np

sys.path.insert(0, ".")

# 7B int8 geometry (bench.py)
VOCAB, HIDDEN, INTER = 32000, 4096, 11008
HEADS = KV_HEADS = 32
D = HIDDEN // HEADS
R, W, S = 8, 8, 256
PROMPT = 32


def arg_int(flag, default):
    if flag in sys.argv:
        return int(sys.argv[sys.argv.index(flag) + 1])
    return default


LAYERS = arg_int("--layers", 32)
STEPS = arg_int("--steps", 96)
INTERPRET = "--interpret" in sys.argv    # CPU syntax-check mode


def build_params():
    import jax.numpy as jnp

    def q8(shape):
        # int8 payload + per-column bf16 scale (the framework's scheme)
        return {"q": jnp.zeros(shape, jnp.int8),
                "s": jnp.full((shape[1],), 0.01, jnp.bfloat16)}

    layer = {
        "in_norm": jnp.ones((HIDDEN,), jnp.bfloat16),
        "post_norm": jnp.ones((HIDDEN,), jnp.bfloat16),
        "wq": q8((HIDDEN, HIDDEN)), "wk": q8((HIDDEN, HIDDEN)),
        "wv": q8((HIDDEN, HIDDEN)), "wo": q8((HIDDEN, HIDDEN)),
        "gate": q8((HIDDEN, INTER)), "up": q8((HIDDEN, INTER)),
        "down": q8((INTER, HIDDEN)),
    }
    import jax

    stacked = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (LAYERS,) + a.shape), layer)
    globals_ = {
        "embed": jnp.zeros((VOCAB, HIDDEN), jnp.bfloat16),
        "final_norm": jnp.ones((HIDDEN,), jnp.bfloat16),
        "lm_head": q8((HIDDEN, VOCAB)),
    }
    return stacked, globals_


def weight_bytes():
    per_layer = (4 * HIDDEN * HIDDEN + 2 * HIDDEN * INTER + INTER * HIDDEN)
    scales = 2 * (4 * HIDDEN + 2 * INTER + HIDDEN)
    norms = 2 * 2 * HIDDEN
    head = HIDDEN * VOCAB + 2 * VOCAB
    return LAYERS * (per_layer + scales + norms) + head + 2 * HIDDEN


def make_block(scanned: bool):
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.kernels.attention import flash_attend

    inv = jnp.arange(0, D, 2, dtype=jnp.float32)
    freqs = 1.0 / (10000.0 ** (inv / D))

    def rotary(x, pos):
        # x [R, W, H, D], pos [R, W]
        ang = pos[..., None].astype(jnp.float32) * freqs       # [R,W,D/2]
        cos = jnp.cos(ang)[:, :, None, :]
        sin = jnp.sin(ang)[:, :, None, :]
        x1, x2 = x[..., ::2], x[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                        axis=-1)
        return out.reshape(x.shape).astype(x.dtype)

    def gemm(x, w):
        return x @ (w["q"].astype(jnp.bfloat16) * w["s"])

    def rms(x, g):
        v = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
        return (x * jax.lax.rsqrt(v + 1e-5).astype(x.dtype)) * g

    def layer_body(x, lp, k_cache, v_cache, pos, lengths, layer_idx):
        # x [R, W, HIDDEN]
        h = rms(x, lp["in_norm"])
        m = h.reshape(R * W, HIDDEN)
        q = gemm(m, lp["wq"]).reshape(R, W, HEADS, D)
        k = gemm(m, lp["wk"]).reshape(R, W, KV_HEADS, D)
        v = gemm(m, lp["wv"]).reshape(R, W, KV_HEADS, D)
        qpos = pos[:, None] + jnp.zeros((R, W), jnp.int32)
        q = rotary(q, qpos)
        k = rotary(k, qpos)
        out, k_cache, v_cache = flash_attend(
            q, k_cache, v_cache, lengths, qpos,
            append_kv=(k[:, :1], v[:, :1], pos), layer_idx=layer_idx,
            interpret=INTERPRET)
        x = x + gemm(out.reshape(R * W, HIDDEN),
                     lp["wo"]).reshape(R, W, HIDDEN)
        h = rms(x, lp["post_norm"]).reshape(R * W, HIDDEN)
        act = jax.nn.silu(gemm(h, lp["gate"])) * gemm(h, lp["up"])
        x = x + gemm(act, lp["down"]).reshape(R, W, HIDDEN)
        return x, k_cache, v_cache

    def step(carry):
        tok, pos, k_cache, v_cache, stacked, globs, t, acc = carry
        x = globs["embed"][tok][:, None, :] + jnp.zeros(
            (R, W, HIDDEN), jnp.bfloat16)
        lengths = pos + 1
        if scanned:
            # scan the caches through xs/ys (flash_attend's layer_idx is
            # static-only): each iteration attends its own [R,KH,S,D]
            # slice and the stacked updates come back as ys
            def body(xc, xs):
                lp, kc, vc = xs
                x2, kc2, vc2 = layer_body(xc, lp, kc, vc, pos, lengths,
                                          None)
                return x2, (kc2, vc2)

            x, (k_cache, v_cache) = jax.lax.scan(
                body, x, (stacked, k_cache, v_cache))
        else:
            for li in range(LAYERS):
                lp = jax.tree.map(lambda a: a[li], stacked)
                x, k_cache, v_cache = layer_body(x, lp, k_cache, v_cache,
                                                 pos, lengths, li)
        h = rms(x[:, 0], globs["final_norm"])
        logits = gemm(h, globs["lm_head"])
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        return (tok, pos + 1, k_cache, v_cache, stacked, globs, t + 1,
                acc + tok)

    def block(stacked, globs, k_cache, v_cache, tok, pos, n):
        def cond(c):
            return c[6] < n

        c0 = (tok, pos, k_cache, v_cache, stacked, globs, jnp.int32(0),
              jnp.zeros((R,), jnp.int32))
        c = jax.lax.while_loop(cond, step, c0)
        return c[7], c[2], c[3]

    # scanned variant: caches must be scan-compatible ([L, ...] leading)
    return jax.jit(block, donate_argnums=(2, 3))


def run(name, scanned):
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.search.machine_model import TPU_CHIPS

    stacked, globs = build_params()
    k_cache = jnp.zeros((LAYERS, R, KV_HEADS, S, D), jnp.bfloat16)
    v_cache = jnp.zeros((LAYERS, R, KV_HEADS, S, D), jnp.bfloat16)
    tok = jnp.ones((R,), jnp.int32)
    pos = jnp.full((R,), PROMPT, jnp.int32)
    blk = make_block(scanned)
    t0 = time.perf_counter()
    acc, k_cache, v_cache = blk(stacked, globs, k_cache, v_cache, tok,
                                pos, jnp.int32(1))
    np.asarray(acc)
    print(f"{name}: compile+first {time.perf_counter() - t0:.1f}s")
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        acc, k_cache, v_cache = blk(stacked, globs, k_cache, v_cache,
                                    tok, pos, jnp.int32(STEPS))
        np.asarray(acc)                 # readback = the honest fence
        best = min(best, (time.perf_counter() - t0) / STEPS)
    bw = TPU_CHIPS["v5e"].hbm_bandwidth
    wb = weight_bytes()
    from flexflow_tpu.kernels.attention import _pick_block_s

    BS = _pick_block_s(S, D)
    kv_rows = LAYERS * R * KV_HEADS * math.ceil(
        (PROMPT + STEPS // 2) / BS) * BS * D * 2 * 2
    bound = (wb + kv_rows) / bw
    print(f"{name}: {best * 1e3:.2f} ms/step  "
          f"({1 / best:.1f} steps/s; stream bound {bound * 1e3:.2f} ms "
          f"-> {bound / best:.3f} of bound)")
    return best


if __name__ == "__main__":
    from flexflow_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    print(f"geometry: {LAYERS}L x {HIDDEN} int8, R={R} W={W} S={S}, "
          f"T={STEPS}")
    run("unrolled", scanned=False)
    run("scanned ", scanned=True)
