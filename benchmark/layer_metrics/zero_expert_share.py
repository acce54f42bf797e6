"""zero_expert_share - layer: routed experts (ops/moe.py, kernels/moe.py).

The window's gain of ffsv_moe_zero_pairs_total, all phases, over that of ffsv_moe_tokens_total x moe_topk: the picks that cost no expert's arithmetic (a third if the router were even over its 768 outputs; the published model steers it with its selection bias).
Returns None when its source is not there (a program without the counter:
any commit before PR 47; a model without such picks); the harness then leaves
the metric out of the line.
"""

from benchmark.layer_metrics import scmoe_readers as S


def read(ctx):
    return S.zero_expert_share(ctx)
