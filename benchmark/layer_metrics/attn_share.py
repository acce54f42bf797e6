"""attn_share - layer: Pallas kernel: kernels/attention.py flash_attend.

Device time of flash_attend custom calls over device-busy time in the traced stretch.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.lib import readers as R


def read(ctx):
    return R.attn_share(ctx)
