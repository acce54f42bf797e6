#!/usr/bin/env python3
"""What fails the ZAYA1 reference check, on the device.

    python3 tools/check_zaya_variants.py [--rehearse]

The benchmark's reference check (``benchmark/families/zaya.py``: a 2-layer
cut of ``zaya1-8b`` at the published widths, three consecutive segments of
one slot in one compact prefill step, a ragged segment whose tail comes from
the state, eight decode steps, against the plain reference) with, beside the
program's reading, what the reference reads against ITSELF when it is wrong
on purpose: a conv tap left out, the value's second half unshifted, ``gamma
* r_prev`` left out, the routed experts left out, float8 (e4m3) matmul
inputs (and, as no fault, with bfloat16 matmul inputs: what the served
precision costs this model). One JSON line; exit 1 unless the program is inside the family's
limit and every variant at least 2.5 times outside it. ``--rehearse``: CPU,
the configuration's rehearsal sizes, interpreted kernels.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

ROOM = 2.5
NO_FAULT = ("bfloat16",)    # read for what the served precision costs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rehearse", action="store_true")
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
                           "zaya1-8b.json")) as f:
        cfg = json.load(f)
    if args.rehearse:
        bench_run.apply_rehearsal(cfg, {"cycle": []})
    else:
        from flexflow_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
    family = bench_run.load_module("families", cfg["family"])
    reference = bench_run.load_module("reference", cfg["family"])
    res = family.reference_check(cfg, reference, variants=family.VARIANTS)
    res["device"] = jax.devices()[0].device_kind
    res["ok"] = bool(res["ok"] and all(
        res[f"wrong_{v}"] >= ROOM * res["tol"] for v in family.VARIANTS
        if v not in NO_FAULT))
    print(json.dumps(res), flush=True)
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
