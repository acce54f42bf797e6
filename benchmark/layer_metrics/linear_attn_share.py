"""linear_attn_share - layer: Pallas kernel (kernels/linear_attention.py kda_state_step).

The recurrent kernel's (kda_state_step) share of the traced device time. It reads the RECURRENT form only: the chunked form of a prefill step is XLA fusions with no name of their own (lib/trace.py keeps an operation's own name), so its time is not in this share.
Returns None when its source is not there (a program without the kernel: any commit before PR 54; any other model); the harness then
leaves the metric out of the line.
"""

from benchmark.layer_metrics import kda_readers as S


def read(ctx):
    return S.linear_attn_share(ctx)
