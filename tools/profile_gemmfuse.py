"""Does fusing qkv (and gate|up) into single gemms speed a decode layer?

The decode layer-scaling slope (profile_decode.py --layers) is 0.325 ms/layer vs a
0.247 ms weight-stream bound. A 7B layer runs SEVEN skinny (M=64) gemms:
wq wk wv wo gate up down. Each carries per-gemm fixed cost (tile setup,
f32 accum readout, scale epilogue); fusing wq|wk|wv -> one [H, 3H] gemm
and gate|up -> one [H, 2I] gemm cuts that to four.

Timing is T-slope based so the per-call dispatch overhead cancels:
run the fused loop at T1 and T2 trips in the SAME compiled program and use
(t(T2) - t(T1)) / (T2 - T1). Each trip runs NL layer bodies back-to-back
with a serial activation dependency (like the real model); weights are jit
arguments.

Usage: python tools/profile_gemmfuse.py
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")

H, I = 4096, 11008     # 7B geometry
KV = 4096              # kv proj width (7B MHA: = H)
M = 64                 # R * decode_width
NL = 8                 # distinct layers per trip (fresh weights each)
T1, T2 = 8, 32


def main():
    from flexflow_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.search.machine_model import TPU_CHIPS

    rng = np.random.default_rng(0)

    def qw(k, n):
        return (jnp.asarray(rng.integers(-127, 127, (k, n)), jnp.int8),
                jnp.asarray(rng.standard_normal((n,)) * 0.01 + 1,
                            jnp.float32))

    sep = [{n: qw(H, w) for n, w in
            (("wq", H), ("wk", KV), ("wv", KV), ("wo", H),
             ("gate", I), ("up", I), ("down_t", H))} for _ in range(NL)]
    # down is [I, H]; build it with the right shape
    for lw in sep:
        lw["down"] = qw(I, H)
        del lw["down_t"]
    fused = []
    for lw in sep:
        qkv_q = jnp.concatenate([lw["wq"][0], lw["wk"][0], lw["wv"][0]], 1)
        qkv_s = jnp.concatenate([lw["wq"][1], lw["wk"][1], lw["wv"][1]])
        gu_q = jnp.concatenate([lw["gate"][0], lw["up"][0]], 1)
        gu_s = jnp.concatenate([lw["gate"][1], lw["up"][1]])
        fused.append({"wqkv": (qkv_q, qkv_s), "wo": lw["wo"],
                      "gateup": (gu_q, gu_s), "down": lw["down"]})

    def mm(x, w):
        q, s = w
        y = jax.lax.dot_general(
            x, q.astype(jnp.bfloat16),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return y * s

    def layer7(x, lw):
        q = mm(x, lw["wq"])
        k = mm(x, lw["wk"])
        v = mm(x, lw["wv"])
        a = (q * 0.1 + k * 0.1 + v * 0.1).astype(jnp.bfloat16)
        x = x + mm(a, lw["wo"]).astype(jnp.bfloat16)
        g = mm(x, lw["gate"])
        u = mm(x, lw["up"])
        h = (jax.nn.silu(g) * u).astype(jnp.bfloat16)
        return x + mm(h, lw["down"]).astype(jnp.bfloat16)

    def layer4(x, lw):
        qkv = mm(x, lw["wqkv"])
        q, k, v = qkv[:, :H], qkv[:, H:H + KV], qkv[:, H + KV:]
        a = (q * 0.1 + k * 0.1 + v * 0.1).astype(jnp.bfloat16)
        x = x + mm(a, lw["wo"]).astype(jnp.bfloat16)
        gu = mm(x, lw["gateup"])
        h = (jax.nn.silu(gu[:, :I]) * gu[:, I:]).astype(jnp.bfloat16)
        return x + mm(h, lw["down"]).astype(jnp.bfloat16)

    def make(layer_fn):
        def outer(x0, ws, T):
            def trip(i, x):
                for lw in ws:
                    x = layer_fn(x, lw)
                # renormalize so values stay finite over many trips
                x = (x / (1e-6 + jnp.max(jnp.abs(x)))).astype(jnp.bfloat16)
                return x
            return jax.lax.fori_loop(0, T, trip, x0)
        return jax.jit(outer, static_argnums=(2,))

    x0 = jnp.asarray(rng.standard_normal((M, H)), jnp.bfloat16)
    layer_bytes = (2 * H * H + 2 * KV * H + 3 * H * I) + (3 * H + 2 * KV
                                                          + 2 * I) * 4
    bw = TPU_CHIPS["v5e"].hbm_bandwidth

    for name, fn, ws in (("7-gemm", make(layer7), sep),
                         ("4-gemm", make(layer4), fused)):
        ts = {}
        for T in (T1, T2):
            out = fn(x0, ws, T)
            np.asarray(out)                       # compile + settle
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                out = fn(x0, ws, T)
                np.asarray(out)
                best = min(best, time.perf_counter() - t0)
            ts[T] = best
        per_layer = (ts[T2] - ts[T1]) / (T2 - T1) / NL
        print(f"{name}: {per_layer * 1e6:7.1f} us/layer "
              f"(stream bound {layer_bytes / bw * 1e6:.1f} us, "
              f"eff {layer_bytes / per_layer / 1e9:.0f} GB/s)")


if __name__ == "__main__":
    main()
