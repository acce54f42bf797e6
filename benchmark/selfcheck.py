#!/usr/bin/env python3
"""CPU self-check of the benchmark's own arithmetic. Seconds, no chip, no
program: ``python3 benchmark/selfcheck.py``.

It checks the yardstick, not the system: the overlap accounting on synthetic
records, percentiles, the even-paced schedule's fixed request count, that the
length cycles do not depend on the seed, the trace reduction on a hand-made
trace and on a small piece of a real one (benchmark/testdata), and that every
entry of BENCHMARK.json resolves to its files.
"""

from __future__ import annotations

import collections
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark.lib import peaks, trace as TR, traffic as T, window as W  # noqa: E402

CHECKS = []


def check(fn):
    CHECKS.append(fn)
    return fn


def near(a, b, rel):
    return abs(a - b) <= rel * abs(b)


@check
def overlap_accounting_is_placement_free():
    """A constant-rate stream gives its rate for any window placement;
    counting completions does not."""
    import random

    rnd = random.Random(0)
    clients, rate = 16, 50.0                    # tokens/s each
    recs = []
    for _ in range(clients):
        t = -rnd.uniform(0, 10)
        while t < 120:
            dur = rnd.choice([5.12, 7.68, 10.24])
            recs.append({"submit": t, "first": t, "finish": t + dur,
                         "n_out": round(rate * dur)})
            t += dur
    worst, worst_count = 0.0, 0.0
    for _ in range(50):
        w0 = rnd.uniform(20, 60)
        w1 = w0 + 45
        got = W.window_tokens(recs, w0, w1) / 45
        by_completion = sum(r["n_out"] for r in recs
                            if w0 <= r["finish"] < w1) / 45
        worst = max(worst, abs(got - clients * rate) / (clients * rate))
        worst_count = max(worst_count, abs(by_completion - clients * rate)
                          / (clients * rate))
    assert worst < 1e-9, worst
    assert worst_count > 0.01, "completion counting should show its edges"
    # prefill-bound requests: 4 s of prefill, then a burst of 48 tokens in
    # 1 s, four clients back to back: the sojourn attribution is flat
    burst = [{"submit": c * 1.25 + 5.0 * k, "first": c * 1.25 + 5.0 * k + 4.0,
              "finish": c * 1.25 + 5.0 * k + 5.0, "n_out": 48}
             for c in range(4) for k in range(-2, 40)]
    rates = [W.window_tokens(burst, w, w + 43) / 43
             for w in (10.0, 10.3, 11.1, 12.6, 13.9)]
    firsts = [W.window_tokens(burst, w, w + 43, start="first") / 43
              for w in (10.0, 10.3, 11.1, 12.6, 13.9)]
    assert max(rates) - min(rates) < 1e-9 and near(rates[0], 38.4, 1e-9)
    assert max(firsts) - min(firsts) > 0.2
    # a request with no interval is credited whole, once
    one = [{"submit": 5.0, "first": 5.0, "finish": 5.0, "n_out": 3}]
    assert W.window_tokens(one, 0, 5) == 0 and W.window_tokens(one, 5, 9) == 3
    return f"overlap error {worst:.1e}; completion counting up to {worst_count:.1%}"


@check
def percentiles_and_sample_counts():
    vals = list(range(1, 102))                 # 1..101
    assert W.percentile(vals, 50) == 51 and W.percentile(vals, 90) == 91
    assert W.percentile([], 90) is None and W.percentile([7.0], 90) == 7.0
    assert near(W.percentile([1, 2, 3, 4], 90), 3.7, 1e-12)
    recs = [{"status": "ok", "n_out": 11, "want_out": 11, "first": 1.0,
             "finish": 2.0, "due": 0.5},
            {"status": "timed_out", "n_out": 3, "want_out": 11, "first": 1.0,
             "finish": 9.0, "due": 0.5},
            {"status": "ok", "n_out": 1, "want_out": 1, "first": 1.0,
             "finish": 1.0, "due": 0.5}]
    v, n = W.tail(recs, W.tpot_ms, 90)
    assert n == 1 and near(v, 100.0, 1e-12), (v, n)   # failed: no sample
    v, n = W.tail(recs, W.ttft_due_ms, 90)
    assert n == 2 and near(v, 500.0, 1e-12)
    return "tails skip failed requests and say how many samples they hold"


@check
def even_paced_schedule_has_a_fixed_count():
    tr = {"rate_rps": 2.4, "jitter": 0.25, "pre_s": 10, "post_s": 6}
    counts = set()
    for seed in (0, 1, 17, 2 ** 31 + 5, 3000000019):
        off, n_pre, n_win = T.open_schedule(tr, seed, 45.0)
        inside = [o for o in off if 0 <= o < 45.0]
        assert len(inside) == n_win == round(2.4 * 45), (len(inside), n_win)
        assert off == sorted(off) and off[0] < 0 and off[-1] > 45.0
        assert all(0 <= o < 45.0 for o in off[n_pre:n_pre + n_win])
        counts.add((len(off), n_pre, n_win))
    assert len(counts) == 1, counts
    assert T.open_schedule(tr, 5, 45.0)[0] != T.open_schedule(tr, 6, 45.0)[0]
    return f"{counts.pop()} (total, before, inside) for every seed"


@check
def length_cycles_do_not_depend_on_the_seed():
    out = []
    for name in sorted(os.listdir(os.path.join(HERE, "traffic"))):
        tr = T.load_traffic(os.path.join(HERE, "traffic", name))
        n = len(tr["cycle"])
        period = n * tr["prompt_pool"]
        want = collections.Counter(map(tuple, tr["cycle"]))
        streams = []
        for seed in (0, 1, 7, 2 ** 31 + 11):
            cyc = T.Cycle(tr, seed, 1000)
            seq = [cyc.next() for _ in range(period + n + 5)]
            assert all(1 <= t < 1000 for p, _ in seq for t in p)
            lens = [(len(p), o) for p, o in seq]
            for start in (0, 3, n - 1):        # any stretch of one cycle
                assert collections.Counter(lens[start:start + n]) == want
            assert seq[:n + 5] == seq[period:]             # periodic
            streams.append(collections.Counter(
                (tuple(p), o) for p, o in seq[:period]))
        # every seed sends the same requests, token for token, rotated
        assert all(s == streams[0] for s in streams)
        assert T.Cycle(tr, 3, 1000).next() != T.Cycle(tr, 4, 1000).next()
        if tr.get("seed_step") == "cycle":
            # the same lengths in the same order, whatever the seed
            heads = {tuple((len(p), o) for p, o in
                           [c.next() for _ in range(n + 3)])
                     for c in (T.Cycle(tr, s, 1000)
                               for s in (0, 1, 7, 2 ** 31 + 11, 3000000019))}
            assert heads == {tuple(map(tuple, (tr["cycle"] * 2)[:n + 3]))}
        out.append(f"{name}: {n} x {tr['prompt_pool']}"
                   f" by {tr.get('seed_step', 'entry')}")
    return "cycle x pool " + ", ".join(out)


def _tracer_events(origin, spans):
    ev = [{"name": "clock_sync", "ph": "M", "ts": 0.0,
           "args": {"perf_counter_origin": origin}}]
    for name, ts_us, dur_us, args in spans:
        ev.append({"name": name, "ph": "X", "ts": ts_us, "dur": dur_us,
                   "args": args})
    return ev


@check
def trace_reduction_on_a_hand_made_trace():
    # profiler clock = perf_counter * 1e9 + 1000 ns; window 100.0 .. 100.001 s
    off = 1000.0
    t0 = 100.0 * 1e9 + off
    ops = [["while.3", t0 + 100_000, 600_000],          # parent of the next two
           ["fusion.1", t0 + 100_000, 200_000],
           ["custom-call_flash_attend.7", t0 + 300_000, 100_000],
           ["fusion.2", t0 + 800_000, 100_000],
           ["fusion.9", t0 - 500_000, 100_000]]         # before the window
    raw = {"planes": {"/device:TPU:0": ops},
           "marks": {"bench_mark_0": t0, "bench_mark_1": t0 + 1_000_000}}
    marks = {"bench_mark_0": 100.0, "bench_mark_1": 100.001}
    # program spans, microseconds after origin 99.0 s: one decode block that
    # covers the while, twice (two requests in it), and a prefill
    events = _tracer_events(99.0, [
        ("decode_block", 1_000_060.0, 700.0, {"steps": 4}),
        ("decode_block", 1_000_060.0, 700.0, {"steps": 4}),
        ("prefill", 1_000_790.0, 200.0, {"n_tokens": 64}),
        ("prefill", 1_000_790.0, 200.0, {"n_tokens": 32})])
    red = TR.reduce_trace(raw, marks, events)
    assert near(red["window_s"], 1e-3, 1e-9)
    assert near(red["busy_s"], 700e-6, 1e-9), red["busy_s"]   # union, not sum
    kinds = dict((k, s) for k, s in red["device_ops"])
    assert near(kinds["while_x1"], 300e-6, 1e-9)     # self time only
    assert near(kinds["fusion_x2"], 300e-6, 1e-9)
    assert near(kinds["custom-call_flash_attend_x1"], 100e-6, 1e-9)
    assert near(sum(kinds.values()), red["busy_s"], 1e-9)
    gaps = red["idle_gaps"]
    assert [round(g[1] * 1e6) for g in gaps] == [100, 100, 100]
    assert sorted(g[0] for g in gaps) == sorted(
        [TR.BETWEEN, TR.HOST_LABEL["decode_block"], TR.HOST_LABEL["prefill"]])
    from benchmark.lib import readers as R

    ctx = {"trace": red}
    assert near(R.decode_step_ms(ctx), 0.6 / 4, 1e-9)     # one block, 4 steps
    assert near(R.prefill_tok_s(ctx), 96 / 100e-6, 1e-9)  # both rows' tokens
    assert near(R.attn_share(ctx), 100 * 100 / 700, 1e-9)
    assert near(R.device_idle(ctx), 30.0, 1e-9)
    assert TR.reduce_trace({"planes": {}, "marks": raw["marks"]}, marks,
                           events) is None
    return "union, self time, gap naming and span intersection exact"


@check
def trace_reduction_on_a_recorded_trace():
    path = os.path.join(HERE, "testdata", "trace_sample.json")
    with open(path) as f:
        s = json.load(f)
    red = TR.reduce_trace({"planes": s["planes"], "marks": s["marks"]},
                          s["mark_times_s"], s["tracer_events"])
    assert red is not None and red["n_chips"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    ranked = red["device_ops"]
    assert ranked == sorted(ranked, key=lambda r: -r[1]) and len(ranked) <= 10
    selfsum = sum(ns for _, ns in TR.self_times(red["ops"])) / 1e9
    assert near(selfsum, TR.total(red["merged"]) / 1e9, 0.02), \
        "operations on one device line overlap beyond nesting"
    assert all(g[0] == TR.BETWEEN or g[0] in TR.HOST_LABEL.values()
               for g in red["idle_gaps"])
    assert TR.time_of(red["ops"], "flash_attend") > 0
    assert any(sp[0] in TR.HOST_LABEL for sp in red["spans"]), \
        "no program span lands in the traced stretch: clocks not aligned"
    return (f"{len(red['ops'])} operations, busy {red['busy_s'] * 1e3:.1f} of "
            f"{red['window_s'] * 1e3:.1f} ms, {len(red['spans'])} spans")


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@check
def every_entry_resolves_to_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    cells = [w["name"] for w in b["workloads"]]
    confs = {c["name"]: c for c in b["configs"]}

    def where(m):
        return set(m.get("workloads", cells))

    for c in confs.values():
        assert NAME.match(c["name"]) and os.path.exists(
            os.path.join(ROOT, c["file"])), c
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        for kind in ("families", "reference"):
            assert os.path.exists(os.path.join(HERE, kind,
                                               cfg["family"] + ".py")), kind
    for w in b["workloads"]:
        assert w["name"] == f"{w['config']}.{w['traffic']}" and w["chips"] == 1
        assert w["config"] in confs and len(w["why"]) <= 200
        T.load_traffic(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
        mine = [m for m in b["end_to_end"] if w["name"] in where(m)]
        assert len(mine) >= 2, f"{w['name']}: setup_s and one more"
        assert any(w["name"] in where(m) for m in b["per_layer"])
    for m in b["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e, m
        assert os.path.exists(os.path.join(HERE, "layer_metrics",
                                           m["name"] + ".py")), m["name"]
        assert where(m) <= where(e2e[m["moves"]]), \
            f"{m['name']} moves {m['moves']}, not reported in all its cells"
    for m in b["end_to_end"]:
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock",
                                                         "device_trace")
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    try:
        peaks.peaks_for("TPU v9")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device kind must be an error")
    return f"{len(cells)} cells, {len(b['per_layer'])} per-layer metrics"


def main() -> int:
    failed = 0
    for fn in CHECKS:
        try:
            print(f"ok   {fn.__name__}: {fn()}")
        except Exception as e:
            failed += 1
            print(f"FAIL {fn.__name__}: {type(e).__name__}: {e}")
    print(f"{len(CHECKS) - failed}/{len(CHECKS)} checks passed")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
