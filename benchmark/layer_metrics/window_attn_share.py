"""window_attn_share - layer: Pallas kernel (kernels/attention.py flash_attend).

Device time of flash_attend_window custom calls (the windowed layers' attention) over device-busy time in the traced stretch.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import window_readers as W


def read(ctx):
    return W.window_attn_share(ctx)
