"""latent_attn_share - layer: Pallas kernel (kernels/attention.py flash_attend).

Self time of the device operations whose name contains flash_attend_latent (the latent attention kernel: one stored stream read as keys and as values) over device-busy time.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import latent_readers as L


def read(ctx):
    return L.latent_attn_share(ctx)
