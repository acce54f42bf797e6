"""sched_host_ms - layer: scheduler loop.

Per sched_round span: its length less the device call spans inside it, the
mean: the scheduler's own Python a round.
Returns None when its source is not there (a program that emits no such
span); the harness then leaves the metric out of the line.
"""

from benchmark.lib import phase_readers as P


def read(ctx):
    return P.sched_host_ms(ctx)
