"""decode_chunked_hbm_roofline - layer: gemms (XLA).

The whole decode step: bytes it must read (families/evabyte.decode_weights once, plus the entries its rows had to read x cache_position_bytes: what decode_hbm_roofline would count at eight times that, from the positions a row HOLDS) over the chip's HBM bandwidth, divided by decode_step_ms.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import chunked_readers as C


def read(ctx):
    return C.decode_chunked_hbm_roofline(ctx)
