"""moe_share - layer: routed experts (ops/moe.py, kernels/moe.py).

Self time of the device operations named moe_experts (the routed-expert Pallas kernel) over device-busy time in the traced stretch.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import moe_readers as M


def read(ctx):
    return M.moe_share(ctx)
