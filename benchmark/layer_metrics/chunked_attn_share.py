"""chunked_attn_share - layer: Pallas kernel (kernels/attention.py flash_attend).

Device time of flash_attend_chunked custom calls (a chunked layer's attention, prefill and decode forms) over device-busy time in the traced stretch.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import chunked_readers as C


def read(ctx):
    return C.chunked_attn_share(ctx)
