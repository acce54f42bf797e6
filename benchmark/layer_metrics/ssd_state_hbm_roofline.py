"""ssd_state_hbm_roofline - layer: Pallas kernel (kernels/linear_attention.py ssd_state_step).

The bytes the state-space mixers' recurrent kernel had to move in the traced decode blocks (the spans' own rows x steps x the recurrent layers x families/granite_hybrid.state_step_bytes: a live row's state in and out, its dt * x in and y out, a decay a head, B and C) over the chip's HBM bandwidth, divided by ssd_state_step's self time inside those blocks.
Returns None when its source is not there (a program without the kernel's name or without ffsv_kda_state_steps_total:
any commit before PR 56; any other model); the harness then
leaves the metric out of the line.
"""

from benchmark.layer_metrics import ssd_readers as S


def read(ctx):
    return S.ssd_state_hbm_roofline(ctx)
