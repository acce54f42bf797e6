"""call_stage_ms - layer: fused engines.

Mean length of the program's call_stage spans: building and transferring one
device call's inputs.
Returns None when its source is not there (a program that emits no such
span); the harness then leaves the metric out of the line.
"""

from benchmark.lib import phase_readers as P


def read(ctx):
    return P.call_stage_ms(ctx)
