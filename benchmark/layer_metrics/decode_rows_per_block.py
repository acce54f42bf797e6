"""decode_rows_per_block - layer: scheduler loop.

Mean `rows` of the program's decode_block spans wholly inside the traced stretch: the live rows of the blocks the device-trace readers see, the stretch's own occupancy (batch_occupancy is the whole window's mean) (telemetry spans on the profiler's clock).
Returns None when its source is not there (no trace, no decode block in the
stretch, or a program whose decode_block spans carry no `rows`: any commit
before PR 37); the harness then leaves the metric out of the line.
"""

from benchmark.lib import phase_readers as P


def read(ctx):
    rows = [s[3]["rows"] for s in P.spans_inside(ctx, ("decode_block",))
            if "rows" in s[3]]
    return sum(rows) / len(rows) if rows else None
