"""decode_cca_hbm_roofline - layer: gemms (XLA).

The whole decode step: bytes it must read (families/zaya.decode_step_must_read: everything outside the experts once, the tied table once, experts_touched x the layers x expert_bytes, the cache positions the traced blocks' rows read x cache_position_bytes) over the chip's HBM bandwidth, divided by decode_step_ms. It does not count all 16 experts of a layer.
Returns None when its source is not there (a program without the counters:
any commit before PR 50; any other model); the harness then leaves the metric
out of the line.
"""

from benchmark.layer_metrics import cca_readers as S


def read(ctx):
    return S.decode_cca_hbm_roofline(ctx)
