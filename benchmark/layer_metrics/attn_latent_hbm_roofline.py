"""attn_latent_hbm_roofline - layer: Pallas kernel (kernels/attention.py flash_attend).

Cache bytes the latent layers had to read in the traced decode blocks (ffsv_attn_positions_read_total{kind=latent} a row-step x steps x live rows x families/mistral4.cache_position_bytes: the 640 B that must be read, not the stored padding) over the chip's HBM bandwidth, divided by flash_attend_latent's self time inside those blocks.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import latent_readers as L


def read(ctx):
    return L.attn_latent_hbm_roofline(ctx)
