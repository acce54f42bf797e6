"""moe_local_hbm_roofline - layer: routed experts (ops/moe.py, kernels/moe.py).

Bytes of the HELD experts the kernel had to read in the traced decode blocks (experts_touched x steps x the family's sparse layers x families/exaone_moe.expert_bytes) over the chip's HBM bandwidth, divided by the kernel's self time inside those blocks.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import window_readers as W


def read(ctx):
    return W.moe_local_hbm_roofline(ctx)
