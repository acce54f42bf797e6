"""device_idle - layer: device.

1 - union of device-operation intervals over the traced stretch.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.lib import readers as R


def read(ctx):
    return R.device_idle(ctx)
