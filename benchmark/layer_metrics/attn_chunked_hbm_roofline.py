"""attn_chunked_hbm_roofline - layer: Pallas kernel (kernels/attention.py flash_attend).

Decode: cache bytes the traced decode blocks' rows had to read (the decode_block spans' own `entries`: the program's host count, from each block's rows' lengths, of the summary rows and window rows its steps had to read, all layers; x families/evabyte.cache_position_bytes) over the chip's HBM bandwidth, divided by flash_attend_chunked's self time inside those blocks. The kernel streams whole 128-row blocks of both extents and one query row a head. On a `# ` line: the prefill form's share of its own bytes.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import chunked_readers as C


def read(ctx):
    return C.attn_chunked_hbm_roofline(ctx)
