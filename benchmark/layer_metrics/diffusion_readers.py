"""Arithmetic shared by the per-layer readers of block-diffusion decoding
(serve/engine._diffusion_block): ``diffusion_tokens_per_pass``,
``diffusion_commit_share``, ``diffusion_threshold_share``.

``ctx`` is what ``lib/readers.py`` documents. The counters are the program's
``ffsv_diffusion_*`` series, which it feeds after every decode block of a
model that fills blocks by diffusion: a row-pass either denoises, unmasking
positions because they cleared the confidence threshold or because the
schedule's floor took the most confident, or commits a whole block. A
program without the series (any commit before PR 39, any model that yields a
token a row a step) gives every reader here None.
"""

from __future__ import annotations

from typing import Optional

from benchmark.layer_metrics.window_readers import _gained as gained

ROW_PASSES = "ffsv_diffusion_row_passes_total"
COMMITS = "ffsv_diffusion_commit_passes_total"
BY = 'ffsv_diffusion_tokens_total{{by="{}"}}'


def _unmasked(ctx):
    by = [gained(ctx, BY.format(b)) for b in ("threshold", "floor")]
    return None if None in by else by


def tokens_per_pass(ctx) -> Optional[float]:
    """Positions unmasked a row-pass, commit passes included: what a row
    gains a pass (0.8 where every block takes the floor of one position a
    denoise pass and a commit pass)."""
    by, passes = _unmasked(ctx), gained(ctx, ROW_PASSES)
    if by is None or not passes:
        return None
    return sum(by) / passes


def commit_share(ctx) -> Optional[float]:
    """Of the row-passes, the percentage that were commit passes."""
    commits, passes = gained(ctx, COMMITS), gained(ctx, ROW_PASSES)
    if commits is None or not passes:
        return None
    return 100.0 * commits / passes


def threshold_share(ctx) -> Optional[float]:
    """Of the positions unmasked, the percentage that cleared the threshold
    (the rest fell to the schedule's floor)."""
    by = _unmasked(ctx)
    if by is None or not sum(by):
        return None
    return 100.0 * by[0] / sum(by)
