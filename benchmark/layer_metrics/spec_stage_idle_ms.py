"""spec_stage_idle_ms - layer: fused engines.

Device-idle time inside the call_stage and call_launch leaves whose program is spec_block, per speculation block: what the chip stands idle for while the host stages a block (its host-to-device transfers, the walk over its arguments) and launches it. A block staged behind a prefill step that is still running costs nothing here; a block staged on an idle device costs its whole staging. Beside it, on a `# ` line: the same for the blocks whose span says behind "prefill" and for the blocks launched alone, apart (profiler trace x telemetry spans).
Returns None when its source is not there (no trace, a program whose
spec_block spans do not say what they were launched behind, or no block
staged in the stretch); the harness then leaves the metric out of the line.
"""

from benchmark.lib import phase_readers as P


def _per_block(ctx, leaves):
    """(blocks staged among ``leaves``, device-idle ms a block in them)."""
    blocks = sum(s[0] == "call_launch" for s in leaves)
    return blocks, P.idle_in(ctx, leaves) / 1e6 / max(1, blocks)


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    # a block's span ends after its own leaves and before the next block's
    blocks = sorted((s for s in tr["spans"] if s[0] == "spec_block"
                     and "behind" in s[3]), key=lambda s: s[2])
    split = {True: [], False: []}       # behind a prefill step; alone
    for s in P.spans_inside(ctx, ("call_stage", "call_launch")):
        if s[3].get("program") != "spec_block":
            continue
        block = next((b for b in blocks if b[2] >= s[2]), None)
        if block is not None:
            split[block[3]["behind"] == "prefill"].append(s)
    n, idle_ms = _per_block(ctx, split[True] + split[False])
    if not n:
        return None
    P.say("spec block staging, device-idle ms a block: "
          "behind a prefill step x%d %.3f, alone x%d %.3f"
          % (_per_block(ctx, split[True]) + _per_block(ctx, split[False])))
    return idle_ms
