"""prefill_tok_s - layer: fused engines.

Prompt tokens in the program's prefill spans over device-busy time inside them.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.lib import readers as R


def read(ctx):
    return R.prefill_tok_s(ctx)
