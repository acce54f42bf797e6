"""Reduction from a profiler trace and the program's spans to numbers.

The profiler's ``.xplane.pb`` is read once (``read_xplane``) into a plain
dict of lists; everything after that is arithmetic on that dict, so the
self-check runs it on a small recorded sample kept in
``benchmark/testdata/``:

    {"planes": {"/device:TPU:0": [[name, start_ns, dur_ns], ...]},
     "marks":  {"bench_mark_0": start_ns, ...}}

Host spans come from the program's telemetry tracer (``SpanTracer.events``:
Chrome trace events, ``ts``/``dur`` in microseconds after the tracer's
``perf_counter_origin``). They are put on the profiler's clock through marks
the benchmark writes into the trace itself (``jax.profiler.TraceAnnotation``)
at known ``perf_counter`` times.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

MARK = "bench_mark_"
Interval = Tuple[float, float]


def read_xplane(trace_dir: str) -> dict:
    import jax

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(max(files, key=os.path.getmtime))
    planes: Dict[str, list] = {}
    marks: Dict[str, float] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    # the event's name is the whole HLO instruction; its
                    # own name (before " = ", without the "%") is enough
                    planes[plane.name] = [
                        [ev.name.split(" = ")[0].lstrip("%"),
                         float(ev.start_ns), float(ev.duration_ns)]
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(MARK):
                        marks[ev.name] = float(ev.start_ns)
    return {"planes": planes, "marks": marks}


def clock_offset_ns(marks: Dict[str, float],
                    mark_times_s: Dict[str, float]) -> Optional[float]:
    """profiler_ns - perf_counter_ns, the mean over the marks found."""
    diffs = [marks[k] - mark_times_s[k] * 1e9
             for k in mark_times_s if k in marks]
    return sum(diffs) / len(diffs) if diffs else None


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(merged: Sequence[Interval], t0: float, t1: float) -> List[Interval]:
    return [(max(a, t0), min(b, t1)) for a, b in merged
            if b > t0 and a < t1]


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def busy_in(merged: Sequence[Interval], spans: Sequence[Interval]) -> float:
    """Device-busy time inside the union of ``spans`` (both merged)."""
    spans = merge(spans)
    i = j = 0
    acc = 0.0
    while i < len(merged) and j < len(spans):
        a = max(merged[i][0], spans[j][0])
        b = min(merged[i][1], spans[j][1])
        if b > a:
            acc += b - a
        if merged[i][1] < spans[j][1]:
            i += 1
        else:
            j += 1
    return acc


def op_key(name: str) -> str:
    """'fusion.123' and 'fusion.7' are one kind of operation."""
    return re.sub(r"[.\d]+$", "", name) or name


def self_times(ops: Sequence[Sequence]) -> List[Tuple[str, float]]:
    """(name, self nanoseconds) per event: an operation that encloses
    others on the same line (a ``while`` around its body) keeps only the
    time its children do not cover, so nothing is counted twice."""
    evs = sorted(ops, key=lambda e: (e[1], -e[2]))
    out: List[List] = []
    stack: List[int] = []
    for name, start, dur in evs:
        end = start + dur
        while stack and out[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= min(end, out[stack[-1]][2]) - start
        out.append([name, dur, end])
        stack.append(len(out) - 1)
    return [(n, max(0.0, d)) for n, d, _ in out]


def rank_ops(ops: Sequence[Sequence], top: int = 10) -> List[List]:
    """[[kind_xCOUNT, seconds], ...] by self time, most first."""
    acc: Dict[str, List[float]] = {}
    for name, ns in self_times(ops):
        k = op_key(name)
        a = acc.setdefault(k, [0.0, 0])
        a[0] += ns
        a[1] += 1
    ranked = sorted(acc.items(), key=lambda kv: -kv[1][0])[:top]
    return [[f"{k}_x{int(n)}", ns / 1e9] for k, (ns, n) in ranked]


def time_of(ops: Sequence[Sequence], needle: str) -> float:
    """Self nanoseconds of the operations whose name contains ``needle``."""
    return sum(ns for name, ns in self_times(ops) if needle in name)


def host_spans_ns(events: Sequence[dict], offset_ns: float
                  ) -> List[Tuple[str, float, float, dict]]:
    """The tracer's complete spans on the profiler's clock:
    (name, start_ns, end_ns, args). Spans of one scheduler call repeat once
    per request with the same start and length; they are kept once."""
    origin = None
    for ev in events:
        if ev.get("name") == "clock_sync":
            origin = ev["args"]["perf_counter_origin"]
            break
    if origin is None:
        return []
    by_call: Dict[tuple, tuple] = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        key = (ev["name"], ev["ts"], ev.get("dur"))
        args = ev.get("args", {})
        if key in by_call:
            # the same call, another request: its tokens join the first's
            first = by_call[key][3]
            first["n_tokens"] = (first.get("n_tokens", 0)
                                 + args.get("n_tokens", 0))
            continue
        start = (origin + ev["ts"] / 1e6) * 1e9 + offset_ns
        by_call[key] = (ev["name"], start, start + ev["dur"] * 1e3,
                        dict(args))
    return list(by_call.values())


HOST_LABEL = {"prefill": "host:_in_prefill_step",
              "decode_block": "host:_in_decode_block",
              "decode_round": "host:_in_spec_block"}
BETWEEN = "host:_between_device_calls"


def idle_gaps(merged: Sequence[Interval], t0: float, t1: float,
              spans: Sequence[Tuple[str, float, float, dict]],
              top: int = 10) -> List[List]:
    """The longest stretches with nothing on the device, each named by the
    program span that covers its middle (or 'between device calls')."""
    edges = [t0] + [x for iv in clip(merged, t0, t1) for x in iv] + [t1]
    gaps = [(edges[i + 1] - edges[i], 0.5 * (edges[i] + edges[i + 1]))
            for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    gaps.sort(reverse=True)
    out = []
    for length, mid in gaps[:top]:
        label = BETWEEN
        for name, a, b, _ in spans:
            if a <= mid < b and name in HOST_LABEL:
                label = HOST_LABEL[name]
                break
        out.append([label, length / 1e9])
    return out


def reduce_trace(raw: dict, mark_times_s: Dict[str, float],
                 tracer_events: Sequence[dict]) -> Optional[dict]:
    """Everything the per-layer readers need from one traced stretch, or
    None when the trace holds no device operation or no marks."""
    planes = raw["planes"]
    names = sorted(mark_times_s)
    if not planes or len([n for n in names if n in raw["marks"]]) < 2:
        return None
    offset = clock_offset_ns(raw["marks"], mark_times_s)
    t0 = mark_times_s[names[0]] * 1e9 + offset
    t1 = mark_times_s[names[-1]] * 1e9 + offset
    per_chip = [clip(merge([(s, s + d) for _, s, d in planes[k]]), t0, t1)
                for k in sorted(planes)]
    busy = [total(m) for m in per_chip]
    # operations, gaps and spans are read on the first chip
    merged = per_chip[0]
    in_win = [e for e in planes[sorted(planes)[0]]
              if e[1] + e[2] > t0 and e[1] < t1]
    if not in_win:
        return None
    spans = [s for s in host_spans_ns(tracer_events, offset)
             if s[2] > t0 and s[1] < t1]
    return {"t0_ns": t0, "t1_ns": t1, "window_s": (t1 - t0) / 1e9,
            "busy_s": sum(busy) / len(busy) / 1e9, "n_chips": len(planes),
            "ops": in_win, "merged": merged, "spans": spans,
            "device_ops": rank_ops(in_win),
            "idle_gaps": idle_gaps(merged, t0, t1, spans)}
