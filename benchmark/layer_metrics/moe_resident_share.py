"""moe_resident_share - layer: routed experts (ops/moe.py, kernels/moe.py).

Of the window's expert-layer calls (the count of ffsv_moe_experts_touched, all phases), the percentage whose rows the kernel gathered and whose results it weighted and added itself, in VMEM (ffsv_moe_resident_calls_total): a program is wholly one form or the other, by its step's shape (kernels/moe.rows_fit), so this is the share of the calls made by the programs that fit. Beside it, on a `# ` line, the split by phase.
Returns None when its source is not there (a program without the counter:
any commit before PR 43; a model without experts); the harness then leaves
the metric out of the line.
"""

from benchmark.lib import phase_readers as P
from benchmark.lib import readers as R
from benchmark.layer_metrics.window_readers import _gained as gained

PHASES = ("decode", "prefill", "verify")


def read(ctx):
    calls, resident = {}, {}
    for ph in PHASES:
        lab = f'{{phase="{ph}"}}'
        resident[ph] = gained(ctx, "ffsv_moe_resident_calls_total" + lab)
        if resident[ph] is None:
            return None
        # (count, sum) the summary gained, None where the phase ran no call
        calls[ph] = (R.hist_delta(ctx, "ffsv_moe_experts_touched" + lab)
                     or (0, 0.0))[0]
    if sum(calls.values()) <= 0:
        return None
    P.say("expert-layer calls with their rows resident in VMEM, of all: "
          + ", ".join("%s %d of %d" % (ph, resident[ph], calls[ph])
                      for ph in PHASES))
    return 100.0 * sum(resident.values()) / sum(calls.values())
