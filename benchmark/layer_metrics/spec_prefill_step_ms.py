"""spec_prefill_step_ms - layer: fused engines.

Device-busy time inside the program's prefill spans that say model "llm" and lie wholly inside the traced stretch, over their count (one a prefill call: the per-request copies of a span are merged): what the verifier's prompt step costs the device in a cell that speculates, the `p` of a prompt round. Beside it, on a `# ` line: the same for each draft (model "ssm0", ...: the steps that feed a draft's cache a prompt, or what it fell behind by) with their counts (profiler trace x telemetry spans).
Returns None when its source is not there (no trace, a program whose prefill
spans do not say whose cache they filled, or no such step in the stretch); the
harness then leaves the metric out of the line.
"""

from benchmark.lib import phase_readers as P
from benchmark.lib import trace as TR


def by_model(ctx):
    """The prefill calls wholly inside the stretch that name their model,
    by model."""
    out = {}
    for s in P.spans_inside(ctx, ("prefill",)):
        if "model" in s[3]:
            out.setdefault(s[3]["model"], []).append(s)
    return out


def busy_ms(ctx, spans):
    return TR.busy_in(ctx["trace"]["merged"],
                      [(s[1], s[2]) for s in spans]) / 1e6


def read(ctx):
    steps = by_model(ctx)
    if "llm" not in steps:
        return None
    drafts = sorted(k for k in steps if k != "llm")
    if drafts:
        P.say("draft prefill steps, device-busy ms a step: " + ", ".join(
            f"{k} x{len(steps[k])} {busy_ms(ctx, steps[k]) / len(steps[k]):.3f}"
            for k in drafts))
    return busy_ms(ctx, steps["llm"]) / len(steps["llm"])
