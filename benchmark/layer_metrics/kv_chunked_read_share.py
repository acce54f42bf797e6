"""kv_chunked_read_share - layer: KV cache layout (ops/kv_layout.py).

Host counts from the batch's lengths: the entries the window's decode steps had to read (summaries + window) over the positions their rows held (ffsv_attn_positions_held_total), %. A full cache reads 100.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import chunked_readers as C


def read(ctx):
    return C.kv_chunked_read_share(ctx)
