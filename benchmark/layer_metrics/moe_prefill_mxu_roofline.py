"""moe_prefill_mxu_roofline - layer: routed experts (ops/moe.py, kernels/moe.py).

Arithmetic of the routed pairs of the traced prefill steps (real tokens x experts per token x layers x families/olmoe.pair_flops) over the chip's bf16 peak, divided by the kernel's self time inside those steps.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import moe_readers as M


def read(ctx):
    return M.moe_prefill_mxu_roofline(ctx)
