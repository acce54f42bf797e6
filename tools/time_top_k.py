#!/usr/bin/env python3
"""The router's ``top_k`` alone on the chip: ``jax.lax.top_k`` (XLA:TPU's
full sort) on the scores as they come, as ``[rows, E]`` and in chunks of
128 rows, and the form the graph's ``TopK`` op takes, in microseconds a
call and device operations a call, float32: a table over rows {8, 32, 128,
256, 512} x experts {64, 128, 320} x k {1, 4, 8, 12} of ``[rows, E]``
scores, and the cells' own calls (``CALLS``: a decode step's ``[slots,
tokens a row, E]``, a prefill step's ``[4, 128, E]``), because XLA:TPU
sorts ``[32, 8, 128]`` in 75 us and the same scores as ``[256, 128]`` in 5
(PERF.md section 6, PR 59, which also holds what a Pallas selection and
its plain ``jax.numpy`` rounds read here before they were taken out). Any
other ``lax.top_k`` of a program can be put through it by its shape.

* ``sort``: ``jax.lax.top_k`` on the scores as the op gets them.
* ``flat``: ``lax.top_k`` on the scores as ``[rows, E]``.
* ``chunks``: ``lax.top_k`` over chunks of 128 of the rows (the same as
  ``flat`` up to 128 rows).
* ``op``: ``ops/reduction_ops.TopK.forward`` (``flat`` since PR 59).

A timing is one jitted program of ``--layers`` calls, each on its own
input (a router's layers in a step), run ``--iters`` times inside one
profiler session; a call's time is the program's stretch on the device,
first operation's start to last operation's end, over the calls (so the
gaps between small operations count), the median of the iterations. Every
form's results must be ``lax.top_k``'s bit for bit, on rows that hold ties,
``-inf``, ``nan`` and both zeros too.

    chiprun --timeout 1200 -- python3 tools/time_top_k.py
    python3 tools/time_top_k.py --rehearse        # CPU, tiny, no times

Prints one JSON object; a timed run keeps it in
``chiprun_out/time_top_k.json``. Refuses to time anything but a TPU.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

FORMS = ("sort", "flat", "chunks", "op")
OUT = os.path.join(ROOT, "chiprun_out", "time_top_k.json")
CHUNK = 128
# the cells' router calls, "leading dims x E : k": a decode step's (OLMoE,
# K-EXAONE, Mistral, SDAR's pass, LongCat, ZAYA1, Solar-Open2) and the
# compact prefill step's at each width
CALLS = ("32x1x64:8,32x1x128:8,16x1x128:4,32x8x128:8,32x1x768:12,"
         "16x1x17:1,16x1x320:8,4x128x64:8,4x128x128:8,4x128x768:12,"
         "4x128x17:1,4x128x320:8")


def forms(k: int):
    """name -> f(x [..., E]) -> (values, indices)."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops.base import OpContext
    from flexflow_tpu.ops.reduction_ops import TopK

    def flat(x, rows=None):
        x2 = x.reshape(-1, x.shape[-1])
        parts = [jax.lax.top_k(x2[r:r + (rows or len(x2))], k)
                 for r in range(0, len(x2), rows or len(x2))]
        return tuple(jnp.concatenate(p).reshape(*x.shape[:-1], k)
                     for p in zip(*parts))

    return {
        "sort": lambda x: jax.lax.top_k(x, k),
        "flat": flat,
        "chunks": lambda x: flat(x, CHUNK),
        "op": lambda x: tuple(TopK.forward({"k": k}, {}, [x], OpContext())),
    }


def scores(rng, layers: int, lead: tuple, E: int):
    """Softmax scores a layer, ``[layers, *lead, E]``, with rows of ties,
    of ``-inf`` (more than ``E - k`` of them), of ``nan`` and of both zeros
    among them."""
    rows = int(np.prod(lead))
    x = rng.standard_normal((layers, rows, E)).astype(np.float32)
    x = np.exp(x) / np.exp(x).sum(-1, keepdims=True)
    x[:, 0, :] = 0.25
    x[:, 1 % rows, 1:] = -np.inf
    x[:, 2 % rows, E // 2] = np.nan
    x[:, 3 % rows, ::2] = 0.0
    x[:, 3 % rows, 1::2] = -0.0
    return x.reshape(layers, *lead, E)


def same_bits(got, want) -> bool:
    return all(np.asarray(g).tobytes() == np.asarray(w).tobytes()
               for g, w in zip(got, want))


def time_on_device(runs, layers: int, iters: int):
    """Runs every program of ``runs`` (row, form, program, inputs)
    ``iters`` times inside one profiler session and writes each form's
    times into its row."""
    import jax

    from benchmark.lib import trace as TR

    trace_dir = os.path.join(ROOT, ".bench_trace", "top_k")
    shutil.rmtree(trace_dir, ignore_errors=True)
    windows, marks = [], {}
    try:
        jax.profiler.start_trace(trace_dir)
        for i, (_, _, prog, xs) in enumerate(runs):
            marks[f"{TR.MARK}{i}"] = time.perf_counter()
            with jax.profiler.TraceAnnotation(f"{TR.MARK}{i}"):
                pass
            t0 = time.perf_counter()
            for _ in range(iters):
                jax.block_until_ready(prog(xs))
            windows.append((t0, time.perf_counter()))
            time.sleep(0.02)    # the windows below stay apart
        jax.profiler.stop_trace()
        raw = TR.read_xplane(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    off = TR.clock_offset_ns(raw["marks"], marks)
    ops = sorted(next(iter(raw["planes"].values())), key=lambda o: o[1])
    starts = np.array([o[1] for o in ops])
    for (row, name, _, _), (t0, t1) in zip(runs, windows):
        lo, hi = (np.searchsorted(starts, t * 1e9 + off + d)
                  for t, d in ((t0, -8e6), (t1, 8e6)))
        mine = ops[lo:hi]
        n = len(mine) // iters
        if not n or len(mine) % iters:
            row[name]["us_a_call"] = None
            continue
        spans = [mine[j + n - 1][1] + mine[j + n - 1][2] - mine[j][1]
                 for j in range(0, len(mine), n)]
        row[name].update(
            us_a_call=statistics.median(spans) / 1e3 / layers,
            busy_us_a_call=sum(o[2] for o in mine) / 1e3 / (iters * layers),
            ops_a_call=n / layers)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--rows", default="8,32,128,256,512")
    ap.add_argument("--experts", default="64,128,320")
    ap.add_argument("--k", default="1,4,8,12")
    ap.add_argument("--calls", default=CALLS,
                    help="calls beside the table, LEADxE:k each")
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        args.rows, args.experts, args.k = "8,160", "17,128", "1,4"
        args.calls = "4x1x16:1,2x8x128:4"
        args.layers, args.iters = 2, 1
    import jax
    import jax.numpy as jnp

    if not args.rehearse and jax.default_backend() != "tpu":
        raise SystemExit("time_top_k: times are of a TPU; this backend is "
                         f"{jax.default_backend()} (--rehearse checks the "
                         "forms' results without one)")
    rng = np.random.default_rng(args.seed)
    ints = lambda s: [int(v) for v in s.split(",")]
    names = args.forms.split(",")
    out = {"device": jax.devices()[0].device_kind, "layers": args.layers,
           "iters": args.iters, "all_equal_lax_top_k": True, "table": []}
    runs = []       # (row of the table, form, jitted program, its inputs)
    calls = [((rows,), E, k) for rows, E, k in itertools.product(
        *(ints(a) if a else [] for a in (args.rows, args.experts, args.k)))]
    for call in args.calls.split(",") if args.calls else ():
        *lead, E = call.split(":")[0].split("x")
        calls.append((tuple(map(int, lead)), int(E), int(call.split(":")[1])))
    for lead, E, k in calls:
        xs = jnp.asarray(scores(rng, args.layers, lead, E))
        fs = forms(k)
        want = [jax.lax.top_k(x, k) for x in xs]
        row = {"scores": [*lead, E], "k": k}
        out["table"].append(row)
        for name in names:
            prog = jax.jit(lambda xs, f=fs[name]: [f(x) for x in xs])
            got = jax.block_until_ready(prog(xs))     # compiles
            if not all(same_bits(g, w) for g, w in zip(got, want)):
                out["all_equal_lax_top_k"] = False
                row[name] = {"equal": False}
                continue
            row[name] = {"equal": True}
            runs.append((row, name, prog, xs))
    if not args.rehearse:
        time_on_device(runs, args.layers, args.iters)
        os.makedirs(os.path.dirname(OUT), exist_ok=True)
        with open(OUT, "w") as fh:
            json.dump(out, fh, indent=1)
        for row in out["table"]:
            print("#", "x".join(map(str, row["scores"])), row["k"], *(
                "%s %s/%s" % (f, *(
                    v if v is None else round(v, 1) for v in (
                        row[f].get("us_a_call"), row[f].get("ops_a_call"))))
                for f in row if isinstance(row[f], dict)))
    print(json.dumps(out))
    return 0 if out["all_equal_lax_top_k"] else 1


if __name__ == "__main__":
    sys.exit(main())
