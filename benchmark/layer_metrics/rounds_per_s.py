"""rounds_per_s - layer: scheduler loop.

Device blocks the scheduler dispatched in the window (decode blocks plus speculation blocks) per second.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.lib import readers as R


def read(ctx):
    return R.rounds_per_s(ctx)
