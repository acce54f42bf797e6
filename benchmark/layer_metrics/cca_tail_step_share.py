"""cca_tail_step_share - layer: scheduler loop (serve/request_manager.py).

Of the window's prefill segments, the % whose tail came from another segment of the same step (ffsv_cca_tails_total{phase=prefill,source=step} over step + state + start): what the compact prefill's consecutive segments carry for a model whose attention layers keep a row's tail.
Returns None when its source is not there (a program without the counter:
any commit before PR 50; a model without a tail); the harness then leaves
the metric out of the line.
"""

from benchmark.layer_metrics import cca_readers as S


def read(ctx):
    return S.cca_tail_step_share(ctx)
