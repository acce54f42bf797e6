#!/usr/bin/env python3
"""The chunked form of the gated delta rule alone, at a cell's shape: the
jnp form (``ops/kda_attention.chunked``, plain XLA) beside the kernel
(``kernels/linear_attention.kda_chunk``), both against the literal
recurrence in float64, in milliseconds and device operations a layer-step.

    chiprun --timeout 900 -- python3 tools/time_kda_chunk.py
    python3 tools/time_kda_chunk.py --rehearse        # CPU, tiny, no times

Prints one JSON object; ``chiprun_out/time_kda_chunk.json`` keeps it.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402


def draw(rng, R, T, H, K, strong: bool):
    """A step's q, k, g, v, beta as the op makes them (float64)."""
    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.standard_normal((R, T, H, K))) / np.sqrt(K)
    k = unit(rng.standard_normal((R, T, H, K)))
    v = rng.standard_normal((R, T, H, K))
    beta = 2 / (1 + np.exp(-rng.standard_normal((R, T, H))))
    if strong:      # the seeded initialisation's strongest decay
        g = -16.0 * np.log1p(np.exp(np.log(np.expm1(0.1))
                                    + rng.uniform(0, 1, (R, T, H, K))))
    else:           # A ~ U(1, 16) a head, dt log-uniform in [1e-3, 1e-1]
        A = rng.uniform(1, 16, (H, 1))
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (R, T, H, K)))
        g = -A * dt
    return q, k, g, v, beta


def literal(S0, q, k, g, v, beta):
    """Token by token in float64: ``(o [R, T, H, V], S_T)``."""
    S = np.asarray(S0, np.float64).copy()
    out = np.zeros(v.shape, np.float64)
    for t in range(q.shape[1]):
        S *= np.exp(g[:, t])[..., None]
        d = beta[:, t][..., None] * (v[:, t] - np.einsum(
            "bhkv,bhk->bhv", S, k[:, t]))
        S += k[:, t][..., None] * d[..., None, :]
        out[:, t] = np.einsum("bhkv,bhk->bhv", S, q[:, t])
    return out, S


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=128)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--heads-per-block", type=int, default=None,
                    help="the kernel's heads a program, instead of its own")
    ap.add_argument("--forms", default="jnp,kernel")
    ap.add_argument("--ablate", default=None,
                    help="kernels/linear_attention.ABLATE: a part left out")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        args.heads, args.dim, args.layers, args.slots, args.iters = (
            4, 16, 2, 5, 1)
    import jax
    import jax.numpy as jnp

    from benchmark.lib import trace as TR
    from flexflow_tpu.kernels import linear_attention as LA
    from flexflow_tpu.ops.kda_attention import chunked

    LA.ABLATE = args.ablate
    if args.heads_per_block:
        LA.CHUNK_HEADS_PER_BLOCK = args.heads_per_block
    R, T, H, K, L = args.rows, args.tokens, args.heads, args.dim, args.layers
    rng = np.random.default_rng(args.seed)
    out = {"shape": [R, T, H, K], "layers": L,
           "device": jax.devices()[0].device_kind}
    slots = jnp.arange(R, dtype=jnp.int32) + 1
    start = jnp.full((R,), 5, jnp.int32)
    n = jnp.full((R,), T, jnp.int32)
    S0 = rng.standard_normal((R, H, K, K)) * 0.1

    def layers_of(xs):
        """Each layer its own inputs (the rows rolled), or XLA computes what
        does not depend on the state once for all the layers; the last
        layer's are the drawn ones."""
        return [[jnp.roll(x, L - 1 - layer, axis=0) for x in xs]
                for layer in range(L)]

    def kernel(stack, xs):
        o = None
        for layer in range(L):
            o, stack = LA.kda_chunk.__wrapped__(
                stack, layer, *xs[layer], slots, start, n,
                interpret=args.rehearse)
        return o, stack

    def plain(stack, xs):
        o = None
        for layer in range(L):
            o, S = chunked(stack[layer, slots], *xs[layer])
            stack = stack.at[layer, slots].set(S)
        return o, stack

    forms = {"jnp": jax.jit(plain, donate_argnums=(0,)),
             "kernel": jax.jit(kernel, donate_argnums=(0,))}
    forms = {k: f for k, f in forms.items() if k in args.forms.split(",")}
    out["heads_per_block"] = LA.chunk_heads_per_block(H)
    out["ablate"] = args.ablate
    for strong in (False, True):
        drawn = draw(rng, R, T, H, K, strong)
        want_o, want_S = literal(S0, *drawn)
        f32 = layers_of([jnp.asarray(x, jnp.float32) for x in drawn])
        for name, f in forms.items():
            stack = jnp.zeros((L, args.slots, H, K, K), jnp.float32)
            stack = stack.at[:, slots].set(jnp.asarray(S0, jnp.float32))
            o, stack = f(stack, f32)
            got_S = np.asarray(stack[L - 1, slots], np.float64)
            out[f"{name}_{'strong' if strong else 'seeded'}_decay"] = {
                "o_max_abs": float(np.abs(np.asarray(o) - want_o).max()),
                "state_max_abs": float(np.abs(got_S - want_S).max()),
                "state_rel": float(np.linalg.norm(got_S - want_S)
                                   / np.linalg.norm(want_S)),
                "finite": bool(np.isfinite(np.asarray(o)).all())}
    if not args.rehearse:
        trace_dir = os.path.join(ROOT, ".bench_trace", "kda_chunk")
        for name, f in forms.items():
            stack = jnp.zeros((L, args.slots, H, K, K), jnp.float32)
            o, stack = f(stack, f32)
            jax.block_until_ready(stack)
            t0 = time.perf_counter()
            for _ in range(args.iters):
                o, stack = f(stack, f32)
            jax.block_until_ready(stack)
            ms = 1e3 * (time.perf_counter() - t0) / (args.iters * L)
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            for _ in range(5):
                o, stack = f(stack, f32)
            jax.block_until_ready(stack)
            jax.profiler.stop_trace()
            ops = next(iter(TR.read_xplane(trace_dir)["planes"].values()))
            busy = TR.total(TR.merge([(s, s + d) for _, s, d in ops]))
            out[name] = {
                "wall_ms_a_layer_step": ms,
                "device_ms_a_layer_step": busy / 1e6 / (5 * L),
                "device_ops_a_layer_step": len(ops) / (5 * L),
                "top_ops": TR.rank_ops(ops, 8)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    out["chunk_forms"] = LA.chunk_summary()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "time_kda_chunk.json"),
              "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
