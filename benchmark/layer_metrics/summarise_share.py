"""summarise_share - layer: KV cache layout (ops/kv_layout.py).

Device time of eva_summarise custom calls (the decode step's summariser: pool a completed chunk's 16 window rows, write its summary row in place) over device-busy time in the traced stretch. A prefill step pools its fresh keys in unnamed XLA fusions, which are not in it.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import chunked_readers as C


def read(ctx):
    return C.summarise_share(ctx)
