"""idle_attributed - layer: device.

Share of the traced stretch's device-idle time that lies inside a leaf span
of the scheduler round (sched_admit/build/commit, call_stage/launch/wait);
the idle time by leaf is printed on a `# ` line.
Returns None when its source is not there (a program that emits no such
span); the harness then leaves the metric out of the line.
"""

from benchmark.lib import phase_readers as P


def read(ctx):
    return P.idle_attributed(ctx)
