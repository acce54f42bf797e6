"""attn_kv_hbm_roofline - layer: Pallas kernel (kernels/attention.py flash_attend).

The k/v bytes the plain attention kernel had to read in the traced decode blocks (ffsv_attn_positions_read_total{kind=full} a row-step x the spans' own rows x steps x families/zaya.cache_position_bytes) over the chip's HBM bandwidth, divided by flash_attend's self time inside those blocks.
Returns None when its source is not there (a program without the kind="full"
series: any commit before PR 50; a model that counts no full kind); the
harness then leaves the metric out of the line.
"""

from benchmark.layer_metrics import cca_readers as S


def read(ctx):
    return S.attn_kv_hbm_roofline(ctx)
