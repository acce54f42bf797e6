"""peak_hbm_gb - layer: device.

memory_stats()['peak_bytes_in_use'] after the window.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""


def read(ctx):
    return ctx['memory_peak_bytes'] / 1e9 if ctx['memory_peak_bytes'] else None
