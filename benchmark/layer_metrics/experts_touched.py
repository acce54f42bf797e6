"""experts_touched - layer: routed experts (ops/moe.py, kernels/moe.py).

Ffsv_moe_experts_touched{phase=decode}: distinct experts one expert-layer call of a decode step read, mean over the window (of num_experts).
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import moe_readers as M


def read(ctx):
    return M.experts_touched(ctx)
