#!/usr/bin/env python3
"""What fails the EvaByte reference check, on the device.

    python3 tools/check_evabyte_variants.py [--rehearse]

The benchmark's reference check (``benchmark/families/evabyte.py``: a
2-layer cut of ``evabyte-6.5b`` at the published widths, 4090 prompt
positions through the compact prefill and 40 decode steps, against the plain
reference) with, beside the program's reading, what the reference reads
against ITSELF when it computes each of the four things the model is not:
float8 (e4m3) matmul inputs, a window's summaries visible one window early,
a chunk pooled by its mean, ``mu`` and ``phi`` exchanged. One JSON line;
exit 1 unless the program is inside the family's limit and every variant
outside it. ``--rehearse``: CPU, the configuration's rehearsal sizes,
interpreted kernels.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

VARIANTS = ("float8", "early", "mean", "swapped")


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
                           "evabyte-6.5b.json")) as f:
        cfg = json.load(f)
    if args.rehearse:
        bench_run.apply_rehearsal(cfg, {"cycle": []})
    else:
        from flexflow_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
    family = bench_run.load_module("families", cfg["family"])
    reference = bench_run.load_module("reference", cfg["family"])
    res = family.reference_check(cfg, reference, variants=VARIANTS)
    res["device"] = jax.devices()[0].device_kind
    res["ok"] = bool(res["ok"] and all(
        res[f"wrong_{v}"] > res["tol"] for v in VARIANTS))
    print(json.dumps(res), flush=True)
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
