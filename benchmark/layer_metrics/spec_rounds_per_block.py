"""spec_rounds_per_block - layer: scheduler loop.

Rounds the device ran per speculation block (spec_block spans' `rounds`);
the rounds asked for and the scheduler's cuts are printed beside it on a `#
` line.
Returns None when its source is not there (a program that emits no such
span); the harness then leaves the metric out of the line.
"""

from benchmark.lib import phase_readers as P


def read(ctx):
    return P.spec_rounds_per_block(ctx)
