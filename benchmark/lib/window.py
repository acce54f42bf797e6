"""Window accounting and percentiles: pure arithmetic on request records.

A record is a dict on the benchmark's own clock (``time.perf_counter``):

    submit   when the generator handed the request to the server
    due      when it was due to be sent (open loop; == submit in a closed loop)
    first    submit + GenerationResult.ttft_s
    finish   submit + GenerationResult.latency_s
    n_in, n_out, want_out, status, queue_wait_s, prefill_s

Nothing here reads a clock or the program.
"""

from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile (numpy's default rule), None when
    there are no samples. The caller prints the sample count beside it."""
    vals = sorted(values)
    if not vals:
        return None
    if len(vals) == 1:
        return float(vals[0])
    k = (len(vals) - 1) * q / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(vals) - 1)
    return float(vals[lo] + (vals[hi] - vals[lo]) * (k - lo))


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


def overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def window_tokens(records: Iterable[dict], w0: float, w1: float,
                  start: str = "submit") -> float:
    """Output tokens attributed to [w0, w1) by overlap, not by completion.

    A request's tokens are spread evenly over an interval of its life that
    ends at its finish, and the window is credited with the share of that
    interval inside it: every token of every request is counted once, over
    all the time of the window. The interval starts at ``start``: "submit"
    (the whole sojourn; what ``output_tok_s`` uses) or "first" (first token
    to finish, printed beside it). Both estimate the same steady rate; the
    sojourn gives each client a constant contribution, where first-token
    attribution leaves whole bursts of a prefill-bound request (32-64
    tokens after seconds of prefill) on one side of a window's edge or the
    other. A request whose interval has no length is credited whole to the
    window its finish falls in."""
    total = 0.0
    for r in records:
        n = r["n_out"]
        if n <= 0:
            continue
        begin, finish = r[start], r["finish"]
        if finish - begin <= 1e-9:
            if w0 <= finish < w1:
                total += n
            continue
        total += n * overlap(begin, finish, w0, w1) / (finish - begin)
    return total


def touches(r: dict, w0: float, w1: float) -> bool:
    """Did the request's life (submit to finish) overlap the window?"""
    return r["submit"] < w1 and r["finish"] > w0


def tpot_ms(r: dict) -> Optional[float]:
    """Time per output token after the first, in ms; None for a request
    with fewer than two tokens."""
    if r["n_out"] < 2:
        return None
    return 1e3 * (r["finish"] - r["first"]) / (r["n_out"] - 1)


def ttft_due_ms(r: dict) -> float:
    """Time from when the request was due to be sent to its first token."""
    return 1e3 * (r["first"] - r["due"])


def ok(r: dict) -> bool:
    return r["status"] == "ok" and r["n_out"] == r["want_out"]


def tail(records: Sequence[dict], fn, q: float) -> Tuple[Optional[float], int]:
    """Percentile of ``fn`` over the requests that resolved ok; a failed
    request has no sample (it is counted in ``failed`` instead)."""
    vals: List[float] = []
    for r in records:
        if not ok(r):
            continue
        v = fn(r)
        if v is not None:
            vals.append(v)
    return percentile(vals, q), len(vals)
