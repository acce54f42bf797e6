"""prefill_steps_per_round - layer: scheduler loop.

Ffsv_round_prefill_steps: mean prefill steps a round of the incremental loop dispatched before its decode block, over the window's rounds (a round with nothing to prefill counts 0).
Returns None when its source is not there (a program from before PR 32 has
no such histogram); the harness then leaves the metric out of the line.
"""

from benchmark.lib import readers as R


def read(ctx):
    return R.hist_mean(ctx, "ffsv_round_prefill_steps")
