#!/usr/bin/env python3
"""The state-space mixer's recurrent kernel alone, at a cell's shape:
``kernels/linear_attention.ssd_state_step`` on a donated stack of ``layers x
slots x [H, P, N]`` float32, every layer of a decode step one call, with all
the rows live and with a quarter of them, in microseconds a call and GB/s
against ``ssd_step_bytes``; and beside it the same body with a part left out
(``y`` not computed; the decay not applied; ``dx B^T`` not added; all three:
the stream alone), so that a timing says where the body's time is. The
bodies with a part left out are THIS TOOL'S (``_body`` below, the module's
body with three switches): the module has no hook for them. ``whole`` is
the tool's copy with nothing left out and must give the module's results
bit for bit.

    chiprun --timeout 900 -- python3 tools/time_ssd_step.py
    python3 tools/time_ssd_step.py --rehearse        # CPU, tiny, no times

Prints one JSON object; ``chiprun_out/time_ssd_step.json`` keeps it.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

# what a body may leave out
PARTS = ("y", "decay", "dx")
BODIES = {"whole": (), "no_y": ("y",), "no_decay": ("decay",),
          "no_dx": ("dx",), "stream": PARTS}


def _body(without=()):
    """``linear_attention._ssd_kernel`` with the parts in ``without`` left
    out (the results are then wrong; the stream is the same)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from flexflow_tpu.kernels import linear_attention as LA

    def kernel(lidx_ref, rows_ref, nl_ref, fresh_ref, s_ref, dx_ref, a_ref,
               b_ref, c_ref, y0_ref, so_ref, y_ref, *, hb: int):
        del lidx_ref, y0_ref
        i = pl.program_id(0)
        first_head = pl.program_id(1) * hb
        nl = nl_ref[0]
        f32 = jnp.float32

        @pl.when(i < nl)
        def _live():
            P, N = s_ref.shape[1:]
            r = rows_ref[i]
            keep = jnp.where(fresh_ref[r] != 0, 0.0, 1.0).astype(f32)
            if "dx" not in without:
                dxT = LA._columns(LA._identity(P), dx_ref[...])
            B, C = b_ref[...], c_ref[...]
            m = LA.ssd_merged_heads(hb, N)
            lane = jax.lax.broadcasted_iota(jnp.int32, (P, N), 1)
            first = {sh: (lane & sh) == 0 for sh in
                     (1 << k for k in range(m.bit_length() - 1))}

            def summed(h, count):
                if count > 1:
                    sh = count // 2
                    return LA._merge(summed(h, sh), summed(h + sh, sh), sh,
                                     first[sh])
                S = s_ref[h]
                if "decay" not in without:
                    S = S * (a_ref[r, first_head + h] * keep)
                if "dx" not in without:
                    S = S + dxT[:, h:h + 1] * B
                so_ref[h] = S
                return S * C

            if "y" in without:
                for h in range(hb):
                    summed(h, 1)
                y_ref[...] = dx_ref[...]
                return
            for g in range(0, hb, m):
                yT = LA._fold_lanes(summed(g, m), m)
                y_ref[g:g + m, :] = LA._columns(LA._head_lanes(m, N), yT)

        @pl.when((i == 0) & (nl == 0))
        def _nobody():
            so_ref[...] = s_ref[...]
            y_ref[...] = jnp.zeros_like(y_ref)

    return kernel


def step_with(kernel, hb=None, interpret=False):
    """``linear_attention.ssd_state_step`` around another body, ``hb``
    heads a program instead of the module's."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from flexflow_tpu.kernels import linear_attention as LA

    def step(state, layer_idx, dx, a, B, C, live, fresh):
        L, R, H, P, N = state.shape
        heads = hb or LA.ssd_heads_per_block(H, P, N)
        nhb = H // heads
        rows, nl = LA.live_rows_first(live)
        scalars = (jnp.asarray(layer_idx, jnp.int32).reshape(1), rows,
                   nl.reshape(1), fresh.astype(jnp.int32))
        maps = LA.StepMaps(nhb)
        f32 = jnp.float32
        block = pl.BlockSpec((None, None, heads, P, N), maps.state)
        vec = pl.BlockSpec((None, heads, P), maps.row)
        shared = pl.BlockSpec((None, 1, N), maps.shared)
        new, y = pl.pallas_call(
            functools.partial(kernel, hb=heads),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(scalars), grid=(R, nhb),
                in_specs=[block, vec,
                          pl.BlockSpec(memory_space=pltpu.SMEM),
                          shared, shared, pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=[block, vec]),
            out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                       jax.ShapeDtypeStruct((R, H, P), f32)],
            input_output_aliases={len(scalars): 0, len(scalars) + 5: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret, name=LA.SSD_NAME,
        )(*scalars, state, dx.astype(f32), a.astype(f32),
          B.astype(f32).reshape(R, 1, N), C.astype(f32).reshape(R, 1, N),
          jnp.zeros((R, H, P), f32))
        return y, new

    return step


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--layers", type=int, default=18)
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--p", type=int, default=64)
    ap.add_argument("--n", type=int, default=128)
    ap.add_argument("--live", default="32,8",
                    help="live rows of the slots, a timing each")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--bodies", default="module," + ",".join(BODIES))
    ap.add_argument("--heads-per-block", type=int, default=None,
                    help="the tool's bodies' heads a program")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        args.layers, args.slots, args.heads, args.p, args.n = 2, 4, 8, 16, 32
        args.live, args.iters = "4,1", 1
    import jax
    import jax.numpy as jnp

    from benchmark.lib import trace as TR
    from flexflow_tpu.kernels import linear_attention as LA

    L, R, H, P, N = args.layers, args.slots, args.heads, args.p, args.n
    rng = np.random.default_rng(args.seed)
    out = {"shape": [L, R, H, P, N],
           "device": jax.devices()[0].device_kind,
           "heads_per_block": {"module": LA.ssd_heads_per_block(H, P, N),
                               "tool": args.heads_per_block
                               or LA.ssd_heads_per_block(H, P, N)}}
    f32 = jnp.float32
    # each layer its own inputs (the rows rolled)
    dx, B, C = (jnp.asarray(rng.standard_normal(s), f32)
                for s in ((R, H, P), (R, N), (R, N)))
    a = jnp.asarray(rng.uniform(0.05, 1, (R, H)), f32)
    xs = [[jnp.roll(x, layer, axis=0) for x in (dx, a, B, C)]
          for layer in range(L)]
    fresh = jnp.zeros((R,), bool).at[R - 1].set(True)

    def decode_step(call, stack, xs, live):
        ys = []
        for layer in range(L):
            y, stack = call(stack, layer, *xs[layer], live, fresh)
            ys.append(y)
        return jnp.stack(ys), stack

    calls = {"module": functools.partial(LA.ssd_state_step.__wrapped__,
                                         interpret=args.rehearse)}
    for name, without in BODIES.items():
        calls[name] = step_with(_body(without), args.heads_per_block,
                                args.rehearse)
    calls = {k: jax.jit(functools.partial(decode_step, f),
                        donate_argnums=(0,))
             for k, f in calls.items() if k in args.bodies.split(",")}
    S0 = rng.standard_normal((L, R, H, P, N)).astype(np.float32)
    lives = [int(x) for x in args.live.split(",")]
    # the tool's whole body gives the module's results, bit for bit
    if "module" in calls and "whole" in calls:
        live = jnp.arange(R) < lives[0]
        got = [calls[k](jnp.asarray(S0), xs, live)
               for k in ("module", "whole")]
        out["whole_is_module"] = bool(all(
            np.array_equal(np.asarray(m), np.asarray(w))
            for m, w in zip(*got)))
    if not args.rehearse:
        trace_dir = os.path.join(ROOT, ".bench_trace", "ssd_step")
        for nlive in lives:
            live = jnp.arange(R) < nlive
            want = LA.ssd_step_bytes(nlive, H, P, N)
            for name, f in calls.items():
                stack = jnp.asarray(S0)
                _, stack = f(stack, xs, live)
                jax.block_until_ready(stack)
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    _, stack = f(stack, xs, live)
                jax.block_until_ready(stack)
                wall = (time.perf_counter() - t0) / (args.iters * L)
                shutil.rmtree(trace_dir, ignore_errors=True)
                jax.profiler.start_trace(trace_dir)
                for _ in range(5):
                    _, stack = f(stack, xs, live)
                jax.block_until_ready(stack)
                jax.profiler.stop_trace()
                ops = next(iter(
                    TR.read_xplane(trace_dir)["planes"].values()))
                mine = [d for n, _, d in ops if LA.SSD_NAME in n]
                us = sum(mine) / 1e3 / max(len(mine), 1)
                out[f"{name}_live{nlive}"] = {
                    "calls": len(mine), "us_a_call": us,
                    "gb_s": want / us / 1e3 if us else None,
                    "share_of_819": want / us / 1e3 / 819 if us else None,
                    "wall_us_a_call": wall * 1e6}
                del stack
        shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "time_ssd_step.json"),
              "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
