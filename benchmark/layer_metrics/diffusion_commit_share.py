"""diffusion_commit_share - layer: fused engines.

Commit passes as a percentage of the window's row-passes: ffsv_diffusion_commit_passes_total over ffsv_diffusion_row_passes_total (20 at the schedule's floor; what folding a commit into the next block's first pass would remove).
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import diffusion_readers as D


def read(ctx):
    return D.commit_share(ctx)
