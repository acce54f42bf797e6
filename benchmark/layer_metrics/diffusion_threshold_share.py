"""diffusion_threshold_share - layer: fused engines.

Of the positions unmasked in the window, the percentage that cleared the confidence threshold: ffsv_diffusion_tokens_total{by=threshold} over both labels (0 on seeded weights: it names the regime).
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import diffusion_readers as D


def read(ctx):
    return D.threshold_share(ctx)
