"""prefill_fill - layer: scheduler loop.

Real prompt tokens over the positions the prefill steps computed in the window (batch rows x chunk a step): 100 x the gain of ffsv_prefill_tokens_total over that of ffsv_prefill_positions_total.
Returns None when its source is not there (a program from before PR 27 has
no positions counter); the harness then leaves the metric out of the line.
"""


def read(ctx):
    tel = ctx.get("tel")
    if not tel:
        return None

    def gained(name):
        if name not in tel["after"]:
            return None
        return (tel["after"][name]["value"]
                - tel["before"].get(name, {}).get("value", 0.0))

    tokens = gained("ffsv_prefill_tokens_total")
    positions = gained("ffsv_prefill_positions_total")
    if tokens is None or not positions:
        return None
    return 100.0 * tokens / positions
