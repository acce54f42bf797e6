"""decode_scmoe_hbm_roofline - layer: gemms (XLA).

The whole decode step: bytes it must read (families/longcat_flash.decode_step_must_read: every matrix outside the experts once, experts_touched x the sparse layers x expert_bytes, the latent positions the traced blocks' rows read x cache_position_bytes) over the chip's HBM bandwidth, divided by decode_step_ms.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import scmoe_readers as S


def read(ctx):
    return S.decode_scmoe_hbm_roofline(ctx)
