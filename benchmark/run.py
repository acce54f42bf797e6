#!/usr/bin/env python3
"""The benchmark's command.

    python3 benchmark/run.py --workload <config>.<traffic> --seed N \
        --seconds S --trace 0|1

One process, one chip. Builds the cell's configuration through its family,
warms every program the cell's traffic uses (set-up), then drives the cell's
traffic mix through the program's front door and measures one window of
``--seconds``. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and, traced,
``breakdown``). With ``--trace 0`` the metrics are the cell's end-to-end
metrics; with ``--trace 1`` the program's telemetry is on, a few seconds of
the window are profiled, and the metrics are the cell's per-layer metrics.

Everything that belongs to one configuration, traffic mix, family or
per-layer metric is a file found by its name in ``BENCHMARK.json``
(benchmark/README.md). Without a TPU nothing runs and nothing is printed to
standard output; ``--rehearse`` (CPU, tiny sizes, interpreted kernels) is a
builder's dry run and reports no metric.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

TRACE_AFTER_S = 3.0     # the profiled stretch starts this long into the window
TRACE_S = 5.0           # and lasts this long
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def say(msg):
    print(f"# {msg}", flush=True)


def die(msg, code=2):
    print(f"benchmark/run.py: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def resolve(bench: dict, workload: str):
    """The cell's entry, configuration file, traffic file and metric lists."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    traffic_file = os.path.join(HERE, "traffic", f"{cell['traffic']}.json")

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]

    return (cell, os.path.join(ROOT, conf["file"]), traffic_file,
            mine(bench["end_to_end"]), mine(bench["per_layer"]))


def apply_rehearsal(cfg: dict, traffic: dict):
    """Tiny sizes for the CPU dry run: the configuration's own ``rehearsal``
    group, and every length of the mix divided by eight."""
    r = dict(cfg.get("rehearsal", {}))
    cfg["assumed"] = {**cfg["assumed"], **r.pop("assumed", {})}
    cfg.update(r)
    traffic["cycle"] = [[max(4, p // 8), max(4, o // 8)]
                        for p, o in traffic["cycle"]]
    for k in ("warmup_s", "pre_s", "post_s"):
        if k in traffic:
            traffic[k] = 1.0


def open_cell(workload: str, rehearse: bool):
    """Everything a process needs before it builds: the cell's entry, its
    configuration and traffic, its metric lists, the device (a TPU, or the
    process ends here) and the compile cache. Shared with sweep.py."""
    from benchmark.lib import peaks, traffic as T

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, cfg_file, traffic_file, e2e, per_layer = resolve(bench, workload)
    with open(cfg_file) as f:
        cfg = json.load(f)
    traffic = T.load_traffic(traffic_file)
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["FF_PALLAS_INTERPRET"] = "1"
        apply_rehearsal(cfg, traffic)
    import jax

    devices = jax.devices()
    dev = devices[0]
    if not rehearse and dev.platform != "tpu":
        die(f"no TPU (jax found platform {dev.platform!r}); nothing was run")
    if len(devices) < cell["chips"]:
        die(f"{workload} needs {cell['chips']} chips, jax sees {len(devices)}")
    try:
        from flexflow_tpu.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        die(f"the flexflow_tpu package is not beside benchmark/ ({e}); "
            "nothing was run")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    chip_peaks = None if rehearse else peaks.peaks_for(dev.device_kind)
    return (cell, cfg, traffic, e2e, per_layer, device, chip_peaks,
            enable_compile_cache())


class Tracer:
    """The traced run's helper thread: snapshots the program's metrics at
    the window's edges and profiles a few seconds inside it."""

    def __init__(self, tel, seconds: float):
        self.tel = tel
        self.seconds = seconds
        self.before = self.after = None
        self.marks = {}
        self.error = None
        self.thread = None

    def start(self, w0, w1):
        self.thread = threading.Thread(target=self._run, args=(w0, w1),
                                       name="bench-tracer", daemon=True)
        self.thread.start()

    @staticmethod
    def _sleep_until(t):
        d = t - time.perf_counter()
        if d > 0:
            time.sleep(d)

    def _mark(self, i):
        import jax

        name = f"bench_mark_{i}"
        self.marks[name] = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            pass

    def _run(self, w0, w1):
        import jax

        try:
            self._sleep_until(w0)
            self.before = self.tel.registry.snapshot()
            after = min(TRACE_AFTER_S, 0.2 * self.seconds)
            length = min(TRACE_S, 0.5 * self.seconds)
            self._sleep_until(w0 + after)
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            try:
                self._mark(0)
                self._sleep_until(w0 + after + length)
                self._mark(1)
            finally:
                jax.profiler.stop_trace()
            self._sleep_until(w1)
            self.after = self.tel.registry.snapshot()
        except Exception as e:      # reported by the main thread
            self.error = e

    def finish(self):
        if self.thread is not None:
            self.thread.join(120.0)
        if self.error is not None:
            raise self.error
        if self.after is None:
            self.after = self.tel.registry.snapshot()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU dry run at tiny sizes; reports no metric")
    args = ap.parse_args(argv)

    from benchmark.lib import loadgen, trace as TR, window as W
    from benchmark.lib.compile_meter import CompileMeter

    cell, cfg, traffic, e2e, per_layer, device, chip_peaks, cache_dir = \
        open_cell(args.workload, args.rehearse)
    import jax

    devices = jax.devices()
    meter = CompileMeter()
    family = load_module("families", cfg["family"])
    say(f"{args.workload} seed {args.seed} seconds {args.seconds} trace "
        f"{args.trace} on {device}; compile cache {cache_dir}")

    checks = {}
    timing = {"imports_s": time.perf_counter() - T_PROCESS}
    tel = None
    if args.trace:
        # the plain reference first, on a 2-layer cut, and freed before the
        # real model takes the memory (PERF.md: traced run only)
        t = time.perf_counter()
        reference = load_module("reference", cfg["family"])
        checks["reference"] = family.reference_check(cfg, reference)
        jax.clear_caches()
        gc.collect()        # the cut's arrays, before the peak is read
        timing["reference_s"] = time.perf_counter() - t
        say(f"reference check: {checks['reference']}")
        from flexflow_tpu.telemetry import enable_telemetry

        tel = enable_telemetry()

    t, mark = time.perf_counter(), meter.mark()
    built = family.build(cfg, telemetry=bool(args.trace))
    jax.block_until_ready([m.params for m in built["models"]])
    timing["build_s"] = time.perf_counter() - t
    timing["build_compile"] = meter.since(mark)
    t, mark = time.perf_counter(), meter.mark()
    checks["warm"] = family.warm_and_check(built, cfg)
    timing["warm_s"] = time.perf_counter() - t
    timing["warm_compile"] = meter.since(mark)
    setup_s = time.perf_counter() - T_PROCESS
    say(f"set-up {setup_s:.1f}s: {json.dumps(timing)}")
    say(f"warm-up checks: {checks['warm']}")

    tracer = Tracer(tel, args.seconds) if args.trace else None
    on_window = tracer.start if tracer else None
    vocab = cfg["vocab_size"]
    handle = built["handle"]
    slots = cfg["assumed"]["max_requests_per_batch"]
    try:
        if traffic["loop"] == "closed":
            records, w0, w1, info = loadgen.run_closed(
                handle, traffic, slots, args.seed, args.seconds, vocab,
                on_window=on_window)
            counted = [r for r in records if W.touches(r, w0, w1)]
        else:
            records, w0, w1, info = loadgen.run_open(
                handle, traffic, args.seed, args.seconds, vocab,
                on_window=on_window)
            counted = [r for r in records if r["in_window"]]
        if tracer:
            tracer.finish()
    finally:
        handle.stop_server()
    say(f"load: {json.dumps(info)}")

    # ---- correctness, outside the window -------------------------------
    from benchmark.families import _common as FC

    att = FC.attention_paths()
    in_window = meter.inside(w0, w1)
    checks["all_requests_ok"] = all(W.ok(r) for r in records)
    checks["ttft_attributed"] = all(r["ttft_attributed"] for r in records)
    checks["compiled_in_window"] = in_window
    checks["attention"] = att
    correct = (checks["warm"]["ok"] and checks["warm"]["ttft_attributed"]
               and checks["all_requests_ok"] and checks["ttft_attributed"]
               and not in_window and att["fast_path_traces"] > 0
               and not att["fallback_traces"]
               and (args.rehearse or not att["interpreted"])
               and checks.get("reference", {"ok": True})["ok"])
    say(f"checks: {json.dumps(checks)}")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell["chips"]])
    device["memory_peak_bytes"] = int(peak)

    # ---- metrics --------------------------------------------------------
    seconds = w1 - w0
    values = {}
    tok_s = W.window_tokens(records, w0, w1) / seconds
    by_first = W.window_tokens(records, w0, w1, start="first") / seconds
    tpot, n_tpot = W.tail(counted, W.tpot_ms, 90.0)
    ttft, n_ttft = W.tail(counted, W.ttft_due_ms, 90.0)
    lag = W.percentile([1e3 * (r["submit"] - r["due"]) for r in counted], 90.0)
    say(("traced run, for the overhead only; " if args.trace else "")
        + f"samples: requests {len(counted)}, tpot {n_tpot}, ttft {n_ttft}; "
        f"output_tok_s {tok_s:.3f} (from first token: {by_first:.3f}) "
        f"tpot_p90_ms {tpot} ttft_p90_ms {ttft} generator_lag_p90_ms {lag}")
    if not args.trace:
        have = {"output_tok_s": tok_s, "setup_s": setup_s}
        for m in e2e:
            if have.get(m["name"]) is not None:
                values[m["name"]] = {"value": have[m["name"]],
                                     "unit": m["unit"]}
    else:
        reduced = None
        try:
            reduced = TR.reduce_trace(TR.read_xplane(TRACE_DIR), tracer.marks,
                                      tel.tracer.events)
        except FileNotFoundError as e:
            say(f"no trace: {e}")
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = {"records": counted, "w0": w0, "w1": w1,
               "tel": {"before": tracer.before, "after": tracer.after},
               "trace": reduced, "cfg": cfg, "traffic": traffic,
               "family": family, "peaks": chip_peaks,
               "memory_peak_bytes": peak}
        for m in per_layer:
            v = load_module("layer_metrics", m["name"]).read(ctx)
            if v is not None:
                values[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            say(f"traced {reduced['window_s']:.2f}s: busy "
                f"{reduced['busy_s']:.3f}s, {len(reduced['ops'])} device "
                f"operations, {len(reduced['spans'])} program spans")

    if args.rehearse:
        say(f"REHEARSAL on the CPU, not a result: {json.dumps(values)}")
        values = {}
    out = {"correct": bool(correct), "attempted": len(counted),
           "failed": sum(not W.ok(r) for r in counted),
           "metrics": values, "device": device}
    if args.trace and reduced:
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    if args.rehearse:
        out["rehearsal"] = True
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
