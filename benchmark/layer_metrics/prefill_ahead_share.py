"""prefill_ahead_share - layer: scheduler loop.

Ffsv_round_prefill_ahead: share (%) of the window's decode blocks of the incremental loop behind which the next round's first prefill step was launched before the block's read-back (one observation a block, 1 or 0), so that the chip goes from the block into that step while the host reads, commits and admits (beside device_idle and call_idle_ms, which it should lower).
Returns None when its source is not there (a program from before PR 61 has
no such histogram); the harness then leaves the metric out of the line.
"""

from benchmark.lib import readers as R


def read(ctx):
    mean = R.hist_mean(ctx, "ffsv_round_prefill_ahead")
    return None if mean is None else 100.0 * mean
