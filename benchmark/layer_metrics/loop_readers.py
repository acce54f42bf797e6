"""The per-layer reader of a model with a LOOP REGION (a span of layers run
several times over one set of weights, a cache plane a pass: Ouro):
``loop_steps_per_token``.

``ctx`` is what ``lib/readers.py`` documents. The counters are the
program's: ``ffsv_loop_layer_steps_total{phase="decode"}`` (the
layer-applications the decode steps' real tokens went through: tokens x the
span's layers, added once a pass INSIDE the device loop, so a pass that did
not run for a row would not count) and ``ffsv_loop_tokens_total{phase=
"decode"}`` (those tokens, counted on the device in the same array, so the
two are of the same steps at any snapshot). A program without the series
(any commit before PR 60, any other model) gives the reader None.
"""

from __future__ import annotations

from typing import Optional

from benchmark.layer_metrics.window_readers import _gained

LAYER_STEPS = 'ffsv_loop_layer_steps_total{phase="decode"}'
TOKENS = 'ffsv_loop_tokens_total{phase="decode"}'


def loop_steps_per_token(ctx) -> Optional[float]:
    """Passes of the span a decoded token ran: what the device counted over
    the span's layers times the tokens it counted (what skipping passes for
    rows whose gate has crossed would lower)."""
    steps = _gained(ctx, LAYER_STEPS)
    tokens = _gained(ctx, TOKENS)
    if not steps or not tokens:
        return None
    return steps / (ctx["cfg"]["num_hidden_layers"] * tokens)
