"""ttft_med_ms - layer: scheduler loop.

Time from due to first token, median over the window's requests.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.lib import readers as R
from benchmark.lib import window as W


def read(ctx):
    return R.record_percentile(ctx, W.ttft_due_ms)
