"""prefill_stage_idle_ms - layer: fused engines.

Device-idle time inside the call_stage and call_launch leaves whose program is prefill, per prefill call: what the chip stands idle for while the host stages and launches a prefill step. A step staged behind one that is still running costs nothing here; a step staged only after the one before it was waited for costs its whole staging. So this is what waiting for prefill steps (the telemetry's timing of them) costs the device, as a number. Beside it, on a `# ` line: the same for the first prefill call of a scheduler round and for the later ones (profiler trace x telemetry spans).
Returns None when its source is not there (no trace, a program that emits
no call_* leaves, or no prefill call in the stretch); the harness then
leaves the metric out of the line.
"""

from benchmark.lib import phase_readers as P


def _per_call(ctx, leaves):
    """(prefill calls among ``leaves``, device-idle ms a call in them)."""
    calls = sum(s[0] == "call_launch" for s in leaves)
    return calls, P.idle_in(ctx, leaves) / 1e6 / max(1, calls)


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    spans = sorted((s for s in tr["spans"] if s[0] == "sched_round"
                    or (s[0] in ("call_stage", "call_launch")
                        and s[3].get("program") == "prefill")),
                   key=lambda s: (s[1], -s[2]))
    leaves = []                 # wholly inside the traced stretch
    split = {True: [], False: []}   # of a round's first prefill call; later
    fresh = first = whole = False
    for s in spans:
        if s[0] == "sched_round":
            # a round that began before the stretch: which of its calls
            # came first is not known, so they stay out of the split
            fresh, whole = True, s[1] >= tr["t0_ns"]
            continue
        if s[0] == "call_stage":
            first, fresh = fresh, False
        if s[1] >= tr["t0_ns"] and s[2] <= tr["t1_ns"]:
            leaves.append(s)
            if whole:
                split[first].append(s)
    calls, idle_ms = _per_call(ctx, leaves)
    if not calls:
        return None
    P.say("prefill staging, device-idle ms a call: "
          "a round's first step x%d %.3f, later steps x%d %.3f"
          % (_per_call(ctx, split[True]) + _per_call(ctx, split[False])))
    return idle_ms
