#!/usr/bin/env python3
"""Find a cell's knee: one stepped sweep of the offered rate, one process.

    python3 benchmark/sweep.py --workload falcon-7b.chat-steady \
        --rates 1,1.5,2,2.5,3,3.5,4 --seconds 20

Run once when an open-loop cell is defined (and again by a later benchmark
PR after an optimisation has moved the knee); the check itself never runs
it. The cell's traffic file then holds ``rate_rps`` = 0.8 of the knee, and
PERF.md keeps the table this prints. A rate is sustained when completions
keep up with arrivals: the queue does not grow through the step (time to
first token in the step's second half is not far above its first half, and
the step drains within one request's lifetime).
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import run as RUN  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    from benchmark.lib import loadgen, window as W

    _cell, cfg, traffic, _, _, device, _, _ = RUN.open_cell(args.workload,
                                                           args.rehearse)
    if traffic["loop"] != "open":
        RUN.die("a knee is a property of an open-loop cell")
    family = RUN.load_module("families", cfg["family"])
    built = family.build(cfg, telemetry=False)
    print("# warm-up:", family.warm_and_check(built, cfg), flush=True)
    print(f"# device {device['kind']}; each step {args.seconds}s after "
          f"{traffic['pre_s']}s of the same arrivals", flush=True)
    print("| offered r/s | requests | completed r/s | ttft p50 ms 1st half | "
          "2nd half | ttft p90 ms | tpot p90 ms | drain s | failed |")
    print("| --- | --- | --- | --- | --- | --- | --- | --- | --- |")
    handle = built["handle"]
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            step = dict(traffic, rate_rps=rate, post_s=0.0)
            recs, w0, w1, info = loadgen.run_open(
                handle, step, args.seed + i, args.seconds, cfg["vocab_size"])
            win = [r for r in recs if r["in_window"]]
            half = w0 + 0.5 * (w1 - w0)
            a = W.median([W.ttft_due_ms(r) for r in win if r["due"] < half])
            b = W.median([W.ttft_due_ms(r) for r in win if r["due"] >= half])
            done = sum(1 for r in recs if w0 <= r["finish"] < w1) / (w1 - w0)
            print(f"| {rate:.2f} | {len(win)} | {done:.2f} | {a:.0f} | {b:.0f} "
                  f"| {W.tail(win, W.ttft_due_ms, 90)[0]:.0f} "
                  f"| {W.tail(win, W.tpot_ms, 90)[0]:.1f} "
                  f"| {info['drain_s']:.1f} "
                  f"| {sum(not W.ok(r) for r in recs)} |", flush=True)
    finally:
        handle.stop_server()
    return 0


if __name__ == "__main__":
    sys.exit(main())
