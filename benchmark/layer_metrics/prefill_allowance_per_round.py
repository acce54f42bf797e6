"""prefill_allowance_per_round - layer: scheduler loop.

Ffsv_round_prefill_allowance: mean prefill steps the loop's rule allowed a round of the incremental loop that began with a row decoding, over the window's such rounds (beside prefill_steps_per_round, what the rounds took: the rule binds where the two meet).
Returns None when its source is not there (a program from before PR 36 has
no such histogram); the harness then leaves the metric out of the line.
"""

from benchmark.lib import readers as R


def read(ctx):
    return R.hist_mean(ctx, "ffsv_round_prefill_allowance")
