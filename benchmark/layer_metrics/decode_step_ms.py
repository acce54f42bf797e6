"""decode_step_ms - layer: fused engines.

Device-busy time inside the program's decode-block spans over the steps they ran (profiler trace x telemetry spans).
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.lib import readers as R


def read(ctx):
    return R.decode_step_ms(ctx)
