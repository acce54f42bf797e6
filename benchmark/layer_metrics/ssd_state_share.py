"""ssd_state_share - layer: Pallas kernel (kernels/linear_attention.py ssd_state_step).

The state-space mixers' recurrent kernel's (ssd_state_step) share of the traced device time. It reads the RECURRENT form only: the chunked form of a prefill step is XLA fusions with no name of their own (lib/trace.py keeps an operation's own name), so its time is not in this share.
Returns None when its source is not there (a program without the kernel: any commit before PR 56; any other model);
the harness then leaves the metric out of the line.
"""

from benchmark.layer_metrics import ssd_readers as S


def read(ctx):
    return S.ssd_state_share(ctx)
