"""diffusion_tokens_per_pass - layer: fused engines.

Positions block-diffusion rows unmasked a row-pass in the window, commit passes counted: the gain of ffsv_diffusion_tokens_total (both labels) over that of ffsv_diffusion_row_passes_total (0.8 at the schedule's floor).
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import diffusion_readers as D


def read(ctx):
    return D.tokens_per_pass(ctx)
