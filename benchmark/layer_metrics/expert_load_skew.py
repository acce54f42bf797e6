"""expert_load_skew - layer: routed experts (ops/moe.py, kernels/moe.py).

Ffsv_moe_expert_pairs_total{expert}: routed pairs of the busiest expert over the mean expert's, in the window; 1.0 is an even load.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import moe_readers as M


def read(ctx):
    return M.expert_load_skew(ctx)
