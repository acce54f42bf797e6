"""moe_hbm_roofline - layer: routed experts (ops/moe.py, kernels/moe.py).

Bytes of the experts the kernel had to read in the traced decode blocks (experts_touched x layer-steps x families/olmoe.expert_bytes) over the chip's HBM bandwidth, divided by the kernel's self time inside those blocks.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import moe_readers as M


def read(ctx):
    return M.moe_hbm_roofline(ctx)
