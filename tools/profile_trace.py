"""Op-level TPU trace profile via jax.profiler.ProfileData.

Captures a few training steps (the profile_resnet.py NCHW variant — the
shipped bench_train configuration's math) under jax.profiler.trace and
aggregates per-op device time from the xplane, printing the top ops by
total duration. Answers "where do the ms go" without guessing from
ablations.

Usage: python tools/profile_trace.py [resnet|decode]

Serving: a profiler session taken with telemetry on
(``utils/profiling.profiler_trace``) holds the program's batch-level spans
on its host plane (``host_annotations``); ``spans_on_profiler_clock`` puts
the tracer's other spans on the same clock by the marks both hold.
"""

import glob
import os
import sys
import time
from collections import defaultdict

import numpy as np

sys.path.insert(0, ".")
sys.path.insert(0, "tools")


def _xplane(trace_dir):
    import jax.profiler as jp

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert files, f"no xplane under {trace_dir}"
    return jp.ProfileData.from_file(max(files, key=os.path.getmtime))


def host_annotations(trace_dir, prefix=""):
    """[(name, start_ns, duration_ns)] of the host planes' events whose
    name starts with ``prefix``: the program's batch-level spans
    (``sched_round``, ``call_stage``, ...) and its clock marks are
    there under their own names, on the profiler's clock."""
    return [(ev.name, float(ev.start_ns), float(ev.duration_ns))
            for plane in _xplane(trace_dir).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(prefix)]


def mark_offset_ns(trace_dir, events):
    """profiler_ns - perf_counter_ns, the mean over the marks that
    ``SpanTracer.profiler_mark`` wrote both into the profiler session
    under ``trace_dir`` and into ``events`` (the tracer's events, or its
    JSONL loaded back). None when the two share no mark."""
    from flexflow_tpu.telemetry.tracing import MARK_PREFIX

    at = {name: start for name, start, _ in
          host_annotations(trace_dir, MARK_PREFIX)}
    diffs = [at[ev["name"]] - ev["args"]["perf_counter_s"] * 1e9
             for ev in events
             if ev.get("name", "").startswith(MARK_PREFIX)
             and ev["name"] in at]
    return sum(diffs) / len(diffs) if diffs else None


def spans_on_profiler_clock(trace_dir, events):
    """The tracer's complete spans as (name, start_ns, end_ns, args) on
    the clock of the profiler session under ``trace_dir``, aligned by
    marks: what puts the per-request tracks (written after the fact, so
    absent from the profiler's host plane) beside the device plane."""
    offset = mark_offset_ns(trace_dir, events)
    origin = next((ev["args"]["perf_counter_origin"] for ev in events
                   if ev.get("name") == "clock_sync"), None)
    if offset is None or origin is None:
        return []
    out = []
    for ev in events:
        if ev.get("ph") == "X":
            start = (origin + ev["ts"] / 1e6) * 1e9 + offset
            out.append((ev["name"], start, start + ev["dur"] * 1e3,
                        ev.get("args", {})))
    return out


def aggregate(trace_dir, steps=3, min_pct=0.5):
    """Aggregate the device plane's "XLA Ops" line: per-op kind totals
    (fusion-name prefixes) + top individual ops, per step."""
    import re

    pd = _xplane(trace_dir)
    totals = defaultdict(float)
    counts = defaultdict(int)
    kinds = defaultdict(float)
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                ms = ev.duration_ns / 1e6
                totals[ev.name] += ms
                counts[ev.name] += 1
                kinds[re.sub(r"[.\d]+$", "", ev.name)
                      .split("(")[0].split(" = ")[0]] += ms
    if not totals:
        print("no device XLA Ops captured (tracing unsupported here?)")
        return
    grand = sum(totals.values())
    print(f"device op total {grand:.1f} ms over {steps} steps -> "
          f"{grand / steps:.1f} ms/step")
    print("== by kind ==")
    for k, ms in sorted(kinds.items(), key=lambda kv: -kv[1])[:15]:
        if 100 * ms / grand < min_pct:
            break
        print(f"{ms / steps:9.2f} ms/step {100 * ms / grand:5.1f}%  {k}")
    print("== top individual ops ==")
    for n, ms in sorted(totals.items(), key=lambda kv: -kv[1])[:20]:
        if 100 * ms / grand < min_pct:
            break
        print(f"{ms / steps:8.2f} ms/step {100 * ms / grand:5.1f}% "
              f"x{counts[n] // steps:3d}  {n[:100]}")


def run_resnet(trace_dir):
    import jax

    from profile_resnet import BATCH, IMG, init_params, make_step
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    params, _ = init_params(rng, nhwc=False)
    params = jax.tree.map(jnp.asarray, params)
    x = jnp.asarray(rng.standard_normal((BATCH, 3, IMG, IMG)), jnp.bfloat16)
    y = jnp.asarray(rng.integers(0, 1000, (BATCH, 1)), jnp.int32)
    step = make_step(False, True, False)
    loss, params = step(params, x, y)
    loss, params = step(params, x, y)
    float(loss)
    with jax.profiler.trace(trace_dir):
        for _ in range(3):
            loss, params = step(params, x, y)
        float(loss)


def run_decode(trace_dir, fusion=True):
    import jax

    import bench
    from profile_decode import build

    m, ifm = build(bench.LAYERS, bench, fusion=fusion)
    R, P = bench.NUM_REQUESTS, bench.PROMPT_LEN
    tok = np.ones((R,), np.int32)
    pos = np.full((R,), P, np.int32)
    act = np.ones((R,), bool)
    np.asarray(ifm.decode_block(tok, pos, act, 4))
    with jax.profiler.trace(trace_dir):
        np.asarray(ifm.decode_block(tok, pos, act, 32))


if __name__ == "__main__":
    from flexflow_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    what = sys.argv[1] if len(sys.argv) > 1 else "resnet"
    modes = ("resnet", "decode", "decode-nofuse")
    if what not in modes:
        raise SystemExit(f"unknown mode {what!r}; pick one of {modes}")
    trace_dir = f"/tmp/fftrace_{what.replace('-', '_')}_{int(time.time())}"
    if what.startswith("decode"):
        run_decode(trace_dir, fusion=(what != "decode-nofuse"))
    else:
        run_resnet(trace_dir)
    aggregate(trace_dir, steps=32 if what.startswith("decode") else 3)
