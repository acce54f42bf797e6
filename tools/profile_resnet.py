"""ResNet-50 MFU attribution (VERDICT r3 item 1 follow-up).

Times pure-JAX ResNet-50 train-step variants on the real chip to locate
where the shipped 0.28 MFU goes and what the chip's ceiling is:

1. nchw      — same structure as the framework build (NCHW, bf16 convs,
               folded one-pass BN in f32, SGD).
2. nhwc      — identical math, NHWC activations + HWIO kernels end-to-end.
3. nhwc_nobn — NHWC with BN replaced by per-channel affine (no batch
               statistics): isolates the BN reduction cost.
4. fwd_only  — NHWC forward pass alone.

Usage: python tools/profile_resnet.py
"""

import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, ".")

BATCH = 256
IMG = 224

BLOCKS = [(64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2)]


def init_params(rng, nhwc, mm1x1=False):
    params = []
    flops = [0.0]

    def conv_w(c_in, c_out, k):
        nonlocal rng
        rng, sub = rng.spawn(1)[0], rng
        w = sub.standard_normal((k, k, c_in, c_out)).astype(np.float32)
        w *= np.sqrt(2.0 / (k * k * c_in))
        if mm1x1 and k == 1:
            return w.reshape(c_in, c_out)        # clean 2-D matmul weight
        if not nhwc:
            w = w.transpose(3, 2, 0, 1)          # OIHW
        return w

    def add_conv(c_in, c_out, k, s, hw):
        out_hw = hw // s
        flops[0] += 2.0 * k * k * c_in * c_out * out_hw * out_hw * BATCH
        params.append({"w": conv_w(c_in, c_out, k),
                       "g": np.ones((c_out,), np.float32),
                       "b": np.zeros((c_out,), np.float32)})
        return out_hw

    hw = IMG
    hw = add_conv(3, 64, 7, 2, hw)
    hw //= 2                                      # maxpool
    c_in = 64
    for c_mid, blocks, stride in BLOCKS:
        for b in range(blocks):
            s = stride if b == 0 else 1
            add_conv(c_in, c_mid, 1, 1, hw)
            hw2 = add_conv(c_mid, c_mid, 3, s, hw)
            add_conv(c_mid, 4 * c_mid, 1, 1, hw2)
            if s != 1 or c_in != 4 * c_mid:
                add_conv(c_in, 4 * c_mid, 1, s, hw)
            hw = hw2
            c_in = 4 * c_mid
    params.append({"w": (rng.standard_normal((2048, 1000)) * 0.01)
                   .astype(np.float32),
                   "b": np.zeros((1000,), np.float32)})
    flops[0] += 2.0 * 2048 * 1000 * BATCH
    return params, 3.0 * flops[0]


def make_step(nhwc, use_bn, fwd_only, mm1x1=False, bn_bf16acc=False):
    import jax
    import jax.numpy as jnp

    dn = ("NHWC", "HWIO", "NHWC") if nhwc else ("NCHW", "OIHW", "NCHW")
    caxis = 3 if nhwc else 1

    def conv(x, p, s, k, relu=True, bn=use_bn):
        pad = (k - 1) // 2
        w = p["w"].astype(jnp.bfloat16)
        if mm1x1 and k == 1:
            # 1x1 conv as a matmul over the channel dim: 2-D weights have
            # clean layouts (the 4-D [O,I,1,1] update path pays ms-scale
            # transpose fusions per weight per step in a per-op trace)
            if s != 1:
                x = (x[:, :, ::s, ::s] if not nhwc else x[:, ::s, ::s, :])
            y = jnp.einsum("nchw,cd->ndhw", x, w) if not nhwc \
                else jnp.einsum("nhwc,cd->nhwd", x, w)
        else:
            y = jax.lax.conv_general_dilated(
                x, w, (s, s), [(pad, pad), (pad, pad)],
                dimension_numbers=dn)
        red = tuple(i for i in range(4) if i != caxis)
        bshape = [1] * 4
        bshape[caxis] = -1
        if bn:
            if bn_bf16acc:
                # read bf16, ACCUMULATE f32: no f32 materialization of y
                cnt = 1.0
                for i in red:
                    cnt *= y.shape[i]
                mean = jnp.sum(y, axis=red, dtype=jnp.float32) / cnt
                var = jnp.maximum(
                    jnp.sum(jnp.square(y), axis=red, dtype=jnp.float32)
                    / cnt - jnp.square(mean), 0.0)
            else:
                xf = y.astype(jnp.float32)
                mean = jnp.mean(xf, axis=red)
                var = jnp.maximum(jnp.mean(jnp.square(xf), axis=red)
                                  - jnp.square(mean), 0.0)
            rstd = jax.lax.rsqrt(var + 1e-5)
            scale = (rstd * p["g"]).astype(y.dtype).reshape(bshape)
            shift = ((p["b"] - mean * rstd * p["g"])
                     .astype(y.dtype).reshape(bshape))
        else:
            scale = p["g"].astype(y.dtype).reshape(bshape)
            shift = p["b"].astype(y.dtype).reshape(bshape)
        y = y * scale + shift
        return jax.nn.relu(y) if relu else y

    def forward(params, x):
        it = iter(params)
        x = conv(x, next(it), 2, 7)
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max,
            (1, 3, 3, 1) if nhwc else (1, 1, 3, 3),
            (1, 2, 2, 1) if nhwc else (1, 1, 2, 2),
            ((0, 0), (1, 1), (1, 1), (0, 0)) if nhwc
            else ((0, 0), (0, 0), (1, 1), (1, 1)))
        c_in = 64
        for c_mid, blocks, stride in BLOCKS:
            for b in range(blocks):
                s = stride if b == 0 else 1
                y = conv(x, next(it), 1, 1)
                y = conv(y, next(it), s, 3)
                y = conv(y, next(it), 1, 1, relu=False)
                if s != 1 or c_in != 4 * c_mid:
                    sc = conv(x, next(it), s, 1, relu=False)
                else:
                    sc = x
                x = jax.nn.relu(y + sc)
                c_in = 4 * c_mid
        x = jnp.mean(x.astype(jnp.float32), axis=(1, 2) if nhwc else (2, 3))
        head = next(it)
        return x @ head["w"] + head["b"]

    def loss_fn(params, x, y):
        logits = forward(params, x)
        lp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(lp, y, axis=1))

    if fwd_only:
        def step(params, x, y):
            return loss_fn(params, x, y), params
        return step

    def step(params, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        params = jax.tree.map(lambda p, g: p - 1e-3 * g, params, grads)
        return loss, params

    return step


def run(name, nhwc, use_bn, fwd_only, flops_scale=1.0, mm1x1=False,
        bn_bf16acc=False, donate=False, reps=5):
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.search.machine_model import TPU_CHIPS
    from flexflow_tpu.telemetry.metrics import Histogram

    rng = np.random.default_rng(0)
    params, flops = init_params(rng, nhwc, mm1x1)
    params = jax.tree.map(jnp.asarray, params)
    x = jnp.asarray(rng.standard_normal(
        (BATCH, IMG, IMG, 3) if nhwc else (BATCH, 3, IMG, IMG)),
        jnp.bfloat16)
    y = jnp.asarray(rng.integers(0, 1000, (BATCH, 1)), jnp.int32)
    step = jax.jit(make_step(nhwc, use_bn, fwd_only, mm1x1, bn_bf16acc),
                   donate_argnums=(0,) if donate else ())
    loss, params = step(params, x, y)
    loss, params = step(params, x, y)
    float(loss)            # host readback fences the warm-up
    # Per-rep spread, not just best-of (the driver's resnet MFU gate
    # reads a MEDIAN over timing blocks — bench_train._mfu_report — so a
    # wide rep distribution moves the gate without any code change;
    # r5 record: driver median 0.251 vs the >= 0.27 target while the
    # same build's best blocks sit at ~0.28). The telemetry histogram
    # gives exact percentiles over the reps.
    hist = Histogram(f"resnet_step_seconds[{name.strip()}]",
                     "per-rep step wall time")
    reps_s = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(4):
            loss, params = step(params, x, y)
        float(loss)
        reps_s.append((time.perf_counter() - t0) / 4)
        hist.observe(reps_s[-1])
    flops *= flops_scale
    peak = TPU_CHIPS["v5e"].bf16_flops
    reps_s.sort()
    best, med, worst = reps_s[0], hist.percentile(50), reps_s[-1]
    spread = (worst - best) / best if best > 0 else 0.0
    print(f"{name}: {best * 1e3:.2f} ms/step  "
          f"{flops / best / 1e12:.1f} TFLOP/s  MFU={flops / best / peak:.3f}")
    print(f"{name}: rep spread {spread:.1%}  "
          f"reps_ms={[round(t * 1e3, 2) for t in reps_s]}  "
          f"MFU best/median/worst = {flops / best / peak:.3f}/"
          f"{flops / med / peak:.3f}/{flops / worst / peak:.3f}")


if __name__ == "__main__":
    from flexflow_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if "--bn" in sys.argv:
        run("bn_bf16acc", nhwc=False, use_bn=True, fwd_only=False,
            bn_bf16acc=True)
        run("bn+donate ", nhwc=False, use_bn=True, fwd_only=False,
            bn_bf16acc=True, donate=True)
        run("nchw_base ", nhwc=False, use_bn=True, fwd_only=False)
    elif "--mm1x1" in sys.argv:
        run("nchw_mm1x1", nhwc=False, use_bn=True, fwd_only=False,
            mm1x1=True)
        run("nchw      ", nhwc=False, use_bn=True, fwd_only=False)
    else:
        run("nchw      ", nhwc=False, use_bn=True, fwd_only=False)
        run("nhwc      ", nhwc=True, use_bn=True, fwd_only=False)
        run("nhwc_nobn ", nhwc=True, use_bn=False, fwd_only=False)
        run("fwd_only  ", nhwc=True, use_bn=True, fwd_only=True,
            flops_scale=1.0 / 3.0)
