"""spec_prompt_share - layer: scheduler loop.

Device-busy time inside all the program's prefill spans that name their model (the verifier's and the drafts') over the traced stretch's device-busy time: the share of the chip that a cell which speculates spends not speculating. Beside it, on a `# ` line: the busy time by model, and the scheduler rounds of the stretch with and without a prefill step (profiler trace x telemetry spans).
Returns None when its source is not there (no trace, a program whose prefill
spans do not say whose cache they filled, or no such step in the stretch); the
harness then leaves the metric out of the line.
"""

from benchmark.layer_metrics import spec_prefill_step_ms as S
from benchmark.lib import phase_readers as P
from benchmark.lib import trace as TR


def read(ctx):
    steps = S.by_model(ctx)
    if not steps:
        return None
    every = [s for v in steps.values() for s in v]
    rounds = P.spans_inside(ctx, ("sched_round",))
    prompt = sum(any(r[1] <= s[1] < r[2] for s in every) for r in rounds)
    P.say("prefill steps, device-busy ms by model: " + ", ".join(
        f"{k} {S.busy_ms(ctx, v):.1f}" for k, v in sorted(steps.items()))
        + f"; rounds with a prefill step {prompt}, without "
        f"{len(rounds) - prompt}")
    return 100.0 * S.busy_ms(ctx, every) * 1e6 / TR.total(
        ctx["trace"]["merged"])
