#!/usr/bin/env python3
"""What fails a configuration's reference check, on the device.

    python3 tools/check_reference_variants.py --config <name> [--rehearse] [--layers N]

The benchmark's reference check of ``--config`` (``solar-open2-250b``:
``benchmark/families/solar_open2.py``; ``granite-4.0-h-micro``:
``benchmark/families/granite_hybrid.py``; ``ouro-2.6b``:
``benchmark/families/ouro.py``, which has no recurrent state and so no
second limit: two layers x four passes, eight cache planes: a few layers at the published
widths, two consecutive segments of one slot in one compact prefill step
(the state's hand-over), a ragged segment whose state comes from the store,
six decode steps through the state and the cache, against the plain
reference) with, beside the program's readings, what the reference reads
against ITSELF when it is wrong on purpose: every entry of the family's
``VARIANTS`` (a term left out, a multiplier changed, a bfloat16 recurrent
state (the logits' reading AND the state's own), float8 (e4m3) matmul inputs
and, as no fault, bfloat16 matmul inputs: what the served precision costs the
model). A family whose check
has a half on the built handle (``ouro-2.6b``: the whole depth) has it run
here too, its readings under ``whole``. One JSON line; exit 1 unless the program is inside the family's
limits, every knock-out at least 2.5 times outside the logits' limit, and a
bfloat16 state outside the state's. ``--rehearse``: CPU, the configuration's
rehearsal sizes, interpreted kernels. ``--layers``: the cut's depth (default
the family's ``REFERENCE_LAYERS``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

ROOM = 2.5
# read for what they cost, not as faults of the logits: the served
# precision; a bfloat16 state, which the STATE's limit catches
NO_FAULT = ("bfloat16", "bfloat16_state")


def main(argv=None, config=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    if config is None:
        ap.add_argument("--config", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--layers", type=int, default=None)
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["FF_PALLAS_INTERPRET"] = "1"
    import jax

    from benchmark import run as bench_run

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("no TPU; nothing was run", file=sys.stderr)
        return 2
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{config or args.config}.json")) as f:
        cfg = json.load(f)
    if args.rehearse:
        bench_run.apply_rehearsal(cfg, {"cycle": []})
    else:
        from flexflow_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
    family = bench_run.load_module("families", cfg["family"])
    reference = bench_run.load_module("reference", cfg["family"])
    if args.layers is not None:
        family.REFERENCE_LAYERS = args.layers
    res = family.reference_check(cfg, reference, variants=family.VARIANTS)
    whole = res.get("whole")
    if whole is not None:
        # a family whose check has a half on the built handle (ouro: the
        # whole depth, several slots live): build it as the cell does, run
        # that half, and hold its knock-outs to its own limit
        built = family.build(cfg, telemetry=False)
        whole.update(family.whole_check(built["llm"]))
        res["ok"] = bool(res["ok"] and whole["ok"] and all(
            v >= ROOM * whole["tol"] for k, v in whole.items()
            if k.startswith("wrong_")))
    res["device"] = jax.devices()[0].device_kind
    res["ok"] = bool(
        res["ok"]
        and all(res[f"wrong_{v}"] >= ROOM * res["tol"]
                for v in family.VARIANTS if v not in NO_FAULT)
        and ("state_tol" not in res      # a family with no state to hold
             or res["wrong_bfloat16_state_state"] > res["state_tol"]))
    print(json.dumps(res), flush=True)
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
