"""decode_kda_hbm_roofline - layer: gemms (XLA).

The whole decode step: bytes it must move (families/solar_open2.decode_step_must_read: everything outside the experts once, experts_touched x the layers x expert_bytes, the cache positions the traced rows read in the GQA layers x cache_position_bytes, the recurrent state of the traced rows read and written once a KDA layer, the head's slice) over the chip's HBM bandwidth, divided by decode_step_ms. It does not count all 40 held experts of a layer.
Returns None when its source is not there (a program without the counters: any commit before PR 54; any other model); the harness then
leaves the metric out of the line.
"""

from benchmark.layer_metrics import kda_readers as S


def read(ctx):
    return S.decode_kda_hbm_roofline(ctx)
