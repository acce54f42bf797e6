"""traced_output_tok_s - layer: whole program, telemetry on.

Output tokens attributed to the window by overlap, over the window, of the TRACED run (telemetry on, five seconds profiled): lib/window.window_tokens, the arithmetic of the end-to-end output_tok_s. Beside the untraced output_tok_s of the same commit it is what the measurement costs the program it measures (host clock, request records).
Returns None when its source is not there (no record, or a window without
length); the harness then leaves the metric out of the line.
"""

from benchmark.lib import window as W


def read(ctx):
    records, w0, w1 = ctx.get("records"), ctx.get("w0"), ctx.get("w1")
    if not records or w0 is None or w1 is None or w1 <= w0:
        return None
    return W.window_tokens(records, w0, w1) / (w1 - w0)
