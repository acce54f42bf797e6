"""attn_window_hbm_roofline - layer: Pallas kernel (kernels/attention.py flash_attend).

Cache bytes the windowed layers had to read in the traced decode blocks (ffsv_attn_positions_read_total{kind=window} a row-step x steps x live rows x families/exaone_moe.cache_position_bytes) over the chip's HBM bandwidth, divided by flash_attend_window's self time inside those blocks. The kernel streams whole 128-position blocks, one or two a row, where the window needs 128 positions or fewer.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import window_readers as W


def read(ctx):
    return W.attn_window_hbm_roofline(ctx)
