"""decode_hbm_roofline - layer: gemms (XLA).

Bytes one decode step must read (weights + live cache, lib/peaks.decode_step_bytes) over the chip's HBM bandwidth, divided by decode_step_ms.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.lib import readers as R


def read(ctx):
    return R.decode_hbm_roofline(ctx)
