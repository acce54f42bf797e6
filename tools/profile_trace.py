"""A serving profiler session's host plane, and the tracer's spans on its clock.

A ``jax.profiler`` session taken with telemetry on
(``utils/profiling.profiler_trace``) holds the program's batch-level spans
on its host plane (``host_annotations``); ``spans_on_profiler_clock`` puts
the tracer's other spans on the same clock by the marks both hold
(``mark_offset_ns``). The benchmark reduces its own traces with
``benchmark/lib/trace.py``, which keeps of the host plane only the marks
``benchmark/run.py`` writes; these three read every event of it by name.
"""

import glob
import os


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
