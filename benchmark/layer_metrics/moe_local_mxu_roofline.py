"""moe_local_mxu_roofline - layer: routed experts (ops/moe.py, kernels/moe.py).

Arithmetic of the pairs the held experts COMPUTED in the traced prefill steps (real tokens x sparse layers x ffsv_moe_routed_pairs_total over ffsv_moe_tokens_total x families/exaone_moe.pair_flops) over the chip's bf16 peak, divided by the kernel's self time inside those steps.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import window_readers as W


def read(ctx):
    return W.moe_local_mxu_roofline(ctx)
