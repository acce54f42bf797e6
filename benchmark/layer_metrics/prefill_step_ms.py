"""prefill_step_ms - layer: fused engines.

Device-busy time inside the program's prefill spans wholly inside the traced stretch over their count (one a prefill call: the per-request copies of a span are merged): what a prefill step costs the device, the `p` of serve/step_costs.py read where decode_step_ms reads its `d` (profiler trace x telemetry spans).
Returns None when its source is not there (no trace, or no prefill step in
the stretch); the harness then leaves the metric out of the line.
"""

from benchmark.lib import phase_readers as P
from benchmark.lib import trace as TR


def read(ctx):
    steps = P.spans_inside(ctx, ("prefill",))
    if not steps:
        return None
    busy_ns = TR.busy_in(ctx["trace"]["merged"],
                         [(s[1], s[2]) for s in steps])
    return busy_ns / 1e6 / len(steps)
