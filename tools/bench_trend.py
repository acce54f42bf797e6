"""Bench-trajectory regression gate over the BENCH_r*.json history.

The driver appends one ``BENCH_rNN.json`` per round ({"n", "rc", "parsed":
<bench.py JSON line>}); until now that trajectory was a pile of files a
human eyeballed. This tool turns it into an enforced gate:

* default mode prints the per-metric trend table (round by round, grouped
  by bench config so the r01 1.3B-class line is never compared against
  the 7B int8 rounds);
* ``--check`` compares the LATEST successful round's headline metrics
  against the best prior value in the same config group and exits 1 with
  a readable diff when any drops beyond its tolerance.

Headline metrics and tolerances live in :data:`HEADLINES` — dotted paths
reach into nested sections (``serving_load.peak_tokens_per_s`` is the
closed-loop load line bench.py emits). All gated metrics are
higher-is-better; rounds with ``rc != 0`` or no parsed payload are
skipped, not failed — the gate polices regressions, not infrastructure
weather.

Usage::

    python tools/bench_trend.py                 # trend table
    python tools/bench_trend.py --check         # CI gate (exit 1 on regression)
    python tools/bench_trend.py --check --dir . --tolerance value=0.05
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

# metric dotted-path -> relative drop tolerance (fraction; fail when the
# latest round is more than this far below the best prior same-config
# value). Calibrated against the committed r01-r05 history: the largest
# benign drop is resnet_train_mfu r05 0.251 vs r04 0.274 (-8.4%, a known
# rep-spread artifact — ROADMAP housekeeping), hence its looser bound.
HEADLINES: Dict[str, float] = {
    "value": 0.08,                       # specinfer tokens/s
    "vs_baseline": 0.05,
    "incr_tokens_per_s": 0.08,
    "roofline_pct": 0.05,
    "tokens_per_round": 0.10,
    "bf16_vs_baseline": 0.05,
    "train_mfu": 0.10,
    "resnet_train_mfu": 0.15,
    "serving_load.peak_tokens_per_s": 0.10,
    "serving_load.peak_goodput_tokens_per_s": 0.10,
    "serving_load.knee_rps": 0.34,       # knee is step-quantized: only a
                                         # lost step (/step-mult) is real
    # acceptance-realism sweep: spec speedup vs incremental per damping
    # regime (bf16 child line). With the adaptive speculation controller
    # these must hold >= ~1.0 at EVERY eps (ROADMAP item 1: spec never
    # loses to incremental) — a controller regression re-collapsing a
    # regime toward the static engine's 0.48-0.80x shows up as a large
    # relative drop here and fails the gate.
    "bf16_acceptance_sweep[eps=0.05].speedup_vs_incr": 0.07,
    "bf16_acceptance_sweep[eps=0.2].speedup_vs_incr": 0.07,
    "bf16_acceptance_sweep[eps=1.0].speedup_vs_incr": 0.07,
    # overload-shedding line (ISSUE 16): at 2x the measured knee the
    # high-priority tenant's goodput and the every-future-resolves
    # fraction must hold; both also carry absolute floors below.
    "serving_overload.priority_goodput": 0.05,
    "serving_overload.resolved_fraction": 0.01,
    # fleet line (ISSUE 17): crash chaos must keep resolving everything
    "serving_fleet.resolved_fraction": 0.01,
    # prefix-caching line (ISSUE 19): fraction of prefill tokens the
    # shared-prefix pool saved — a token COUNT ratio, so it's stable
    # round over round (unlike knee_ratio, which quantizes to the sweep's
    # 2x rate steps and is gated only by its absolute floor below).
    "serving_prefix.prefix_saved_frac": 0.15,
}

# Lower-is-better headlines: metric -> relative RISE tolerance (fail when
# the latest round exceeds the best — i.e. LOWEST — prior same-config
# value by more than this fraction). Cold start is a wall-clock
# build+load+jit measurement on shared CPU hosts, hence the wide band —
# the gate is for a structural regression (e.g. the weight loader going
# quadratic), not scheduler jitter.
LOWER_IS_BETTER: Dict[str, float] = {
    "serving_fleet.cold_start_s": 0.60,
    # observability tax (ISSUE 18): fraction of tiny-pair throughput lost
    # to live telemetry; bench floors it at 0.02 so the MIN prior can't
    # collapse to ~0 and arm a hair-trigger — the gate then fires when a
    # round doubles the best prior tax (e.g. an unguarded hook landing on
    # the decode hot path).
    "telemetry_overhead.overhead_frac": 1.00,
}

# Absolute floors, enforced on the LATEST round only when its bench line
# carries the marker key guarding each group — relative-to-prior gating
# alone cannot express an absolute contract (a first-ever or slowly-
# eroding sub-break-even value would pass). Grouped as
# marker-path -> {metric -> floor}: the acceptance-sweep never-lose
# floors apply to adaptive-controller rounds (parsed["adaptive_spec"]
# true; pre-controller r01-r05 lack the marker), the overload floors to
# any round that ran the serving_overload section (ISSUE 16 gate:
# priority goodput >= 0.95 at 2x knee, every future resolves).
FLOOR_GROUPS: Dict[str, Dict[str, float]] = {
    "adaptive_spec": {
        "bf16_acceptance_sweep[eps=0.05].speedup_vs_incr": 0.95,
        "bf16_acceptance_sweep[eps=0.2].speedup_vs_incr": 0.95,
        "bf16_acceptance_sweep[eps=1.0].speedup_vs_incr": 0.95,
    },
    "serving_overload": {
        "serving_overload.priority_goodput": 0.95,
        "serving_overload.resolved_fraction": 1.0,
    },
    # ISSUE 17: under seeded replica-crash chaos every submitted future
    # must still resolve (failover re-dispatch, token-identical).
    # ISSUE 18 alert sanity: the injected crash must fire >= 1 burn-rate
    # alert, and the steady-state control phase must fire none
    # (alerts_steady_ok is the run's 0/1 encoding of the latter).
    "serving_fleet": {
        "serving_fleet.resolved_fraction": 1.0,
        "serving_fleet.alerts_fired_overload": 1.0,
        "serving_fleet.alerts_steady_ok": 1.0,
    },
    # ISSUE 19: with prefix reuse on, the saturation knee of the
    # shared-prefix mix must sit strictly RIGHT of the no-reuse knee
    # (the sweep's steps are 2x apart, so any real shift reads >= 2.0;
    # 1.05 tolerates a future finer-grained sweep) and shared-prefix KV
    # reuse must save at least a quarter of the prefilled tokens.
    "serving_prefix": {
        "serving_prefix.knee_ratio": 1.05,
        "serving_prefix.prefix_saved_frac": 0.25,
    },
    # ISSUE 20: on the 32k-token batch-1 PCG the mesh-factorization search
    # must SELECT a sequence-sharded plan (seq_degree >= 2 — DP cannot
    # split one request) and its analytic cost must beat the DP-degenerate
    # replicated placement (speedup >= 1.0; both deterministic cost-model
    # quantities, so the floors are tight).
    "long_context": {
        "long_context.seq_vs_dp_speedup": 1.0,
        "long_context.seq_degree": 2.0,
    },
}

# flattened legacy view (kept: external callers/tests address it)
FLOORS: Dict[str, float] = {
    m: f for grp in FLOOR_GROUPS.values() for m, f in grp.items()}


def _get_path(d: dict, path: str):
    """Walk a dotted path; a segment like ``name[key=value]`` selects the
    element of a list-of-dicts whose ``key`` equals ``value`` (numeric
    compare when both parse) — how the acceptance-sweep entries are
    addressed."""
    cur = d
    # segment on dots OUTSIDE brackets ("[eps=0.2]" keeps its dot)
    for part in re.findall(r"[^.\[\]]+(?:\[[^\]]*\])?", path):
        m = re.fullmatch(r"([^\[]+)\[([^=\]]+)=([^\]]+)\]", part)
        if m:
            name, key, want = m.groups()
            if not isinstance(cur, dict) or name not in cur \
                    or not isinstance(cur[name], list):
                return None
            sel = None
            for item in cur[name]:
                if not isinstance(item, dict):
                    continue
                have = item.get(key)
                try:
                    if float(have) == float(want):
                        sel = item
                        break
                except (TypeError, ValueError):
                    if str(have) == want:
                        sel = item
                        break
            if sel is None:
                return None
            cur = sel
            continue
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur if isinstance(cur, (int, float)) and not isinstance(
        cur, bool) else None


def load_rounds(bench_dir: str, pattern: str = "BENCH_r*.json"
                ) -> List[dict]:
    """Parse the trajectory, ordered by round number. Each entry:
    {"round", "file", "ok", "config", "parsed"} — ``ok`` False for
    failed/empty rounds (kept for the table, skipped by the gate)."""
    rounds = []
    for path in sorted(glob.glob(os.path.join(bench_dir, pattern))):
        try:
            doc = json.load(open(path))
        except (OSError, json.JSONDecodeError) as e:
            rounds.append({"round": -1, "file": os.path.basename(path),
                           "ok": False, "config": None, "parsed": {},
                           "error": str(e)})
            continue
        parsed = doc.get("parsed") or {}
        m = re.search(r"r(\d+)", os.path.basename(path))
        n = doc.get("n", int(m.group(1)) if m else -1)
        ok = doc.get("rc", 1) == 0 and bool(parsed) \
            and parsed.get("value") is not None
        rounds.append({"round": n, "file": os.path.basename(path),
                       "ok": ok, "config": parsed.get("config"),
                       "parsed": parsed})
    rounds.sort(key=lambda r: r["round"])
    return rounds


def check_trajectory(rounds: Sequence[dict],
                     tolerances: Optional[Dict[str, float]] = None
                     ) -> Tuple[List[str], List[str]]:
    """Gate the LATEST successful round against the best prior value per
    headline metric within the same config group. Returns (regressions,
    report_lines); empty regressions == gate passes. Metrics absent from
    either side are skipped (sections appear over time — the gate only
    ever compares like with like)."""
    tol = dict(HEADLINES)
    low_tol = dict(LOWER_IS_BETTER)
    for k, v in (tolerances or {}).items():
        (low_tol if k in low_tol else tol)[k] = v
    ok_rounds = [r for r in rounds if r["ok"]]
    lines = []
    if not ok_rounds:
        return [], ["no successful rounds — nothing to gate"]
    latest = ok_rounds[-1]
    prior = [r for r in ok_rounds[:-1] if r["config"] == latest["config"]]
    lines.append(
        f"gating r{latest['round']:02d} (config {latest['config']!r}) "
        f"against {len(prior)} prior same-config round(s)")
    regressions = []
    # absolute floors apply even to a FIRST-of-its-config round (a fresh
    # sub-break-even sweep has no prior to regress from but still fails
    # the never-lose contract)
    for marker, floors in sorted(FLOOR_GROUPS.items()):
        if not latest["parsed"].get(marker):
            continue
        for metric, floor in sorted(floors.items()):
            cur = _get_path(latest["parsed"], metric)
            if cur is None:
                continue
            tag = "FLOOR-FAIL" if cur < floor else "ok"
            lines.append(f"  {tag:>10}  {metric:<40} {cur:>10.4g}  "
                         f"(absolute floor {floor:.2f})")
            if cur < floor:
                regressions.append(
                    f"{metric}: r{latest['round']:02d} {cur:.4g} below "
                    f"absolute floor {floor:.2f}")
    if not prior:
        lines.append("no prior same-config rounds — relative gate "
                     "passes vacuously")
        return regressions, lines
    for metric, t in sorted(tol.items()):
        cur = _get_path(latest["parsed"], metric)
        if cur is None:
            continue
        best, best_round = None, None
        for r in prior:
            v = _get_path(r["parsed"], metric)
            if v is not None and (best is None or v > best):
                best, best_round = v, r["round"]
        if best is None or best <= 0:
            continue
        drop = (best - cur) / best
        tag = "REGRESSION" if drop > t else "ok"
        lines.append(
            f"  {tag:>10}  {metric:<40} {cur:>10.4g}  vs best "
            f"r{best_round:02d} {best:.4g}  ({-drop * 100:+.1f}%, "
            f"tol -{t * 100:.0f}%)")
        if drop > t:
            regressions.append(
                f"{metric}: r{latest['round']:02d} {cur:.4g} vs best "
                f"r{best_round:02d} {best:.4g} "
                f"({-drop * 100:+.1f}% > -{t * 100:.0f}% tolerance)")
    # lower-is-better metrics (cold start): best prior = MINIMUM, fail
    # when the latest round RISES beyond its tolerance
    for metric, t in sorted(low_tol.items()):
        cur = _get_path(latest["parsed"], metric)
        if cur is None:
            continue
        best, best_round = None, None
        for r in prior:
            v = _get_path(r["parsed"], metric)
            if v is not None and (best is None or v < best):
                best, best_round = v, r["round"]
        if best is None or best <= 0:
            continue
        rise = (cur - best) / best
        tag = "REGRESSION" if rise > t else "ok"
        lines.append(
            f"  {tag:>10}  {metric:<40} {cur:>10.4g}  vs best "
            f"r{best_round:02d} {best:.4g}  ({rise * 100:+.1f}%, "
            f"tol +{t * 100:.0f}%, lower is better)")
        if rise > t:
            regressions.append(
                f"{metric}: r{latest['round']:02d} {cur:.4g} vs best "
                f"r{best_round:02d} {best:.4g} "
                f"({rise * 100:+.1f}% > +{t * 100:.0f}% tolerance, "
                f"lower is better)")
    return regressions, lines


def trend_table(rounds: Sequence[dict]) -> str:
    """Round-by-round values of every headline metric present anywhere."""
    metrics = [m for m in (*HEADLINES, *LOWER_IS_BETTER)
               if any(_get_path(r["parsed"], m) is not None for r in rounds)]
    w = max((len(m) for m in metrics), default=6)
    head = "metric".ljust(w) + "".join(
        f"  r{r['round']:02d}{'' if r['ok'] else '!'}".rjust(10)
        for r in rounds)
    lines = [head]
    for m in metrics:
        row = m.ljust(w)
        for r in rounds:
            v = _get_path(r["parsed"], m)
            row += (f"{v:>10.4g}" if v is not None else f"{'-':>10}")
        lines.append(row)
    lines.append("(! = failed round, excluded from the gate)")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="bench-trajectory trend viewer / regression gate")
    ap.add_argument("--dir", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        help="directory holding BENCH_r*.json (default: repo root)")
    ap.add_argument("--glob", default="BENCH_r*.json")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 when the latest round regressed")
    ap.add_argument("--tolerance", action="append", default=[],
                    metavar="METRIC=FRAC",
                    help="override a tolerance, e.g. value=0.05 "
                         "(repeatable)")
    args = ap.parse_args(argv)
    overrides = {}
    for spec in args.tolerance:
        k, _, v = spec.partition("=")
        overrides[k] = float(v)
    rounds = load_rounds(args.dir, args.glob)
    if not rounds:
        print(f"no {args.glob} files under {args.dir}", file=sys.stderr)
        return 2
    print(trend_table(rounds))
    regressions, lines = check_trajectory(rounds, overrides)
    print()
    print("\n".join(lines))
    if args.check:
        if regressions:
            print("\nBENCH TREND GATE FAILED:", file=sys.stderr)
            for r in regressions:
                print(f"  {r}", file=sys.stderr)
            return 1
        print("\nbench trend gate: PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
