"""tpot_med_ms - layer: fused engines: serve/engine.py, serve/inference_manager.py.

Per-request time per output token after the first, median.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.lib import readers as R
from benchmark.lib import window as W


def read(ctx):
    return R.record_percentile(ctx, W.tpot_ms)
