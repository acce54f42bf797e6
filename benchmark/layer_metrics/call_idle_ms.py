"""call_idle_ms - layer: fused engines.

Device-idle time inside device call spans (prefill, decode_block,
spec_block), per call: staging, launch latency and read-back as the chip
feels them (profiler trace x telemetry spans).
Returns None when its source is not there (a program that emits no such
span); the harness then leaves the metric out of the line.
"""

from benchmark.lib import phase_readers as P


def read(ctx):
    return P.call_idle_ms(ctx)
