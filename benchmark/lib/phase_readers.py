"""Arithmetic of the per-layer readers that read the scheduler's phase spans
(PR 24): ``sched_round`` and its leaves, ``spec_block``, and the ``call_*``
leaves of a device call (README "Telemetry" has the vocabulary).

``ctx`` is what ``lib/readers.py`` documents. Spans count when they lie wholly
inside the traced stretch. A program that emits no such span (any commit
before PR 24) gives every reader here None.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from . import trace as TR

LEAVES = ("sched_admit", "sched_build", "sched_commit",
          "call_stage", "call_launch", "call_wait")
DEVICE_CALLS = ("prefill", "decode_block", "spec_block")
Span = Tuple[str, float, float, dict]


def say(msg: str):
    print(f"# {msg}", flush=True)


def spans_inside(ctx, names: Sequence[str]) -> List[Span]:
    """The program spans called one of ``names`` that lie wholly inside the
    traced stretch, by start time."""
    tr = ctx.get("trace")
    if not tr:
        return []
    return sorted((s for s in tr["spans"] if s[0] in names
                   and s[1] >= tr["t0_ns"] and s[2] <= tr["t1_ns"]),
                  key=lambda s: s[1])


def idle_in(ctx, spans: Sequence[Span]) -> float:
    """Device-idle nanoseconds inside the union of ``spans``."""
    ivs = [(s[1], s[2]) for s in spans]
    return TR.total(TR.merge(ivs)) - TR.busy_in(ctx["trace"]["merged"], ivs)


def spec_round_ms(ctx) -> Optional[float]:
    """Device time of one speculation round: device-busy time inside the
    ``spec_block`` spans over the rounds the device ran in them."""
    blocks = spans_inside(ctx, ("spec_block",))
    rounds = sum(s[3].get("rounds", 0) for s in blocks)
    if not rounds:
        return None
    busy = TR.busy_in(ctx["trace"]["merged"], [(s[1], s[2]) for s in blocks])
    return busy / 1e6 / rounds


def _round_of(rounds: Sequence[Span], span: Span) -> Optional[Span]:
    for r in rounds:
        if r[1] <= span[1] and span[2] <= r[2]:
            return r
    return None


def spec_rounds_per_block(ctx) -> Optional[float]:
    """Rounds the device ran per speculation block. Beside it, on ``# ``
    lines: the rounds the scheduler asked for, and how many blocks each of
    its cuts (``RoundTrace.note_cut``) held to one round."""
    blocks = spans_inside(ctx, ("spec_block",))
    if not blocks:
        return None
    ran = sum(s[3].get("rounds", 0) for s in blocks)
    asked = sum(s[3].get("rounds_asked", 0) for s in blocks)
    rounds = spans_inside(ctx, ("sched_round",))
    cuts: Dict[str, int] = {}
    for b in blocks:
        r = _round_of(rounds, b)
        why = r[3].get("cut", "none") if r else "no_round"
        cuts[why] = cuts.get(why, 0) + 1
    say(f"spec blocks {len(blocks)}: rounds asked {asked / len(blocks):.3f} "
        f"a block, ran {ran / len(blocks):.3f}; rows a block "
        f"{sum(s[3].get('rows', 0) for s in blocks) / len(blocks):.2f}; "
        f"blocks by their round's cut {dict(sorted(cuts.items()))}")
    return ran / len(blocks)


def call_idle_ms(ctx) -> Optional[float]:
    """Device-idle time inside device call spans, per call. Beside it, on
    a ``# `` line: calls, busy and idle time per call by the kind of call
    and its round's cut (a ``catch_up`` round's prefill steps only feed a
    draft's cache what the last speculation block committed)."""
    calls = spans_inside(ctx, DEVICE_CALLS)
    if not calls or not spans_inside(ctx, ("call_wait",)):
        return None         # a program without the call_* leaves: not read
    rounds = spans_inside(ctx, ("sched_round",))
    kinds: Dict[str, List[Span]] = {}
    for c in calls:
        r = _round_of(rounds, c)
        cut = r[3].get("cut") if r else None
        kinds.setdefault(c[0] + (f"[{cut}]" if cut else ""), []).append(c)
    merged = ctx["trace"]["merged"]
    say("device calls, ms a call: " + ", ".join(
        f"{k} x{len(v)} busy "
        f"{TR.busy_in(merged, [(s[1], s[2]) for s in v]) / 1e6 / len(v):.2f}"
        f" idle {idle_in(ctx, v) / 1e6 / len(v):.2f}"
        for k, v in sorted(kinds.items())))
    return idle_in(ctx, calls) / 1e6 / len(calls)


def call_stage_ms(ctx) -> Optional[float]:
    stages = spans_inside(ctx, ("call_stage",))
    if not stages:
        return None
    by_program: Dict[str, List[float]] = {}
    for s in stages:
        by_program.setdefault(s[3].get("program", "?"), []).append(
            (s[2] - s[1]) / 1e6)
    say("call_stage ms by program: " + ", ".join(
        f"{k} {sum(v) / len(v):.3f} x{len(v)}"
        for k, v in sorted(by_program.items())))
    return sum((s[2] - s[1]) for s in stages) / 1e6 / len(stages)


def sched_host_ms(ctx) -> Optional[float]:
    """Per scheduler round: its length less the device call spans inside
    it, the mean over the rounds."""
    rounds = spans_inside(ctx, ("sched_round",))
    if not rounds:
        return None
    calls = TR.merge([(s[1], s[2])
                      for s in spans_inside(ctx, DEVICE_CALLS)])
    own = [(r[2] - r[1]) - TR.busy_in(calls, [(r[1], r[2])])
           for r in rounds]
    return sum(own) / 1e6 / len(own)


def idle_attributed(ctx) -> Optional[float]:
    """Share of the stretch's device-idle time whose instants lie inside a
    leaf span. Beside it, on a ``# `` line: the idle time by leaf."""
    leaves = spans_inside(ctx, LEAVES)
    if not leaves:
        return None
    tr = ctx["trace"]
    idle = tr["t1_ns"] - tr["t0_ns"] - TR.total(tr["merged"])
    if idle <= 0:
        return None
    by_leaf = {}
    for s in leaves:
        key = s[0] + (f"[{s[3]['program']}]" if "program" in s[3] else "")
        by_leaf.setdefault(key, []).append(s)
    window_ms = tr["window_s"] * 1e3
    parts = sorted(((idle_in(ctx, v) / 1e6, k) for k, v in by_leaf.items()),
                   reverse=True)
    say(f"device idle {idle / 1e6:.1f} ms of {window_ms:.0f}; by leaf, ms: "
        + ", ".join(f"{k} {ms:.1f}" for ms, k in parts if ms >= 0.05))
    return 100.0 * idle_in(ctx, leaves) / idle
