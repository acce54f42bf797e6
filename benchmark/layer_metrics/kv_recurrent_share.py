"""kv_recurrent_share - layer: KV cache layout (ops/kv_layout.py).

Of the cache bytes the program holds (the gauges ffsv_kv_cache_bytes{kind}, from FFModel.attention_kinds), the % that is recurrent state and convolution tails (kind=recurrent) over recurrent + full (the GQA layers' k/v caches).
Returns None when its source is not there (a program without the kind="recurrent" gauge: any commit before PR 54;
any other model); the harness then
leaves the metric out of the line.
"""

from benchmark.layer_metrics import kda_readers as S


def read(ctx):
    return S.kv_recurrent_share(ctx)
