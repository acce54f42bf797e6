"""kv_latent_bytes_per_pos - layer: KV cache layout (ops/kv_layout.py).

ffsv_kv_cache_bytes{kind=latent} over slots x positions x latent layers: what a cache position costs a latent layer as stored (640 B published: the 256-wide latent and the 64-wide rotated key part, bf16).
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import latent_readers as L


def read(ctx):
    return L.kv_latent_bytes_per_pos(ctx)
