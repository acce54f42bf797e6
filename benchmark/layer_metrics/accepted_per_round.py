"""accepted_per_round - layer: fused engines: MultiSpecEngine.

Ffsv_acceptance_length: mean accepted draft tokens per speculation round in the window.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.lib import readers as R


def read(ctx):
    return R.hist_mean(ctx, 'ffsv_acceptance_length')
