"""decode_ssd_hbm_roofline - layer: gemms (XLA).

The whole decode step: bytes it must move (families/granite_hybrid.decode_step_must_read: every weight once with the tied table once, the cache positions the traced rows read in the attention layers x cache_position_bytes, the recurrent state and tail of the traced rows read and written once a mixer) over the chip's HBM bandwidth, divided by decode_step_ms.
Returns None when its source is not there (a program without the ssd_state_step kernel or the counters: any commit
before PR 56; any other model); the harness then
leaves the metric out of the line.
"""

from benchmark.layer_metrics import ssd_readers as S


def read(ctx):
    return S.decode_ssd_hbm_roofline(ctx)
