"""batch_occupancy - layer: scheduler loop.

Ffsv_batch_occupancy: mean live slots over slots per decode tick in the window.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.lib import readers as R


def read(ctx):
    return R.batch_occupancy(ctx)
