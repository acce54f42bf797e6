"""kv_window_share - layer: KV cache layout (ops/kv_layout.py).

Ffsv_attn_positions_read_total{kind}: of the cache layer-positions the window's decode steps had to read, the share the windowed layers read (a uniform cache would make every layer read every position).
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import window_readers as W


def read(ctx):
    return W.kv_window_share(ctx)
