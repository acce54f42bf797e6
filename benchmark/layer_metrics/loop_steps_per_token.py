"""loop_steps_per_token - layer: loop region (core/model.py _run_loop, ops/loop.py).

Passes of the looped span a decoded token ran: ffsv_loop_layer_steps_total{phase=decode} (real tokens x the span's layers, added once a pass inside the device loop) over the span's layers x ffsv_loop_tokens_total{phase=decode} (those tokens, counted with them); 4.0 while every token runs every pass.
Returns None when its source is not there (a program without the series:
any commit before PR 60; any other model); the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import loop_readers as S


def read(ctx):
    return S.loop_steps_per_token(ctx)
