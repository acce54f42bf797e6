"""kda_state_hbm_roofline - layer: Pallas kernel (kernels/linear_attention.py kda_state_step).

The bytes the recurrent kernel had to move in the traced decode blocks (the spans' own rows x steps x the recurrent layers x families/solar_open2.state_step_bytes: a live row's state in and out, its q, k, g, v, beta in and o out) over the chip's HBM bandwidth, divided by kda_state_step's self time inside those blocks.
Returns None when its source is not there (a program without ffsv_kda_state_steps_total or the kernel's name: any
commit before PR 54; any other model); the harness then
leaves the metric out of the line.
"""

from benchmark.layer_metrics import kda_readers as S


def read(ctx):
    return S.kda_state_hbm_roofline(ctx)
