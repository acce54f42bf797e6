"""spec_round_ms - layer: fused engines.

Device-busy time inside the program's spec_block spans over the rounds the
device ran in them: the speculative twin of decode_step_ms (profiler trace x
telemetry spans).
Returns None when its source is not there (a program that emits no such
span); the harness then leaves the metric out of the line.
"""

from benchmark.lib import phase_readers as P


def read(ctx):
    return P.spec_round_ms(ctx)
