#!/usr/bin/env python3
"""A latent-attention configuration past its original context, on the device.

    python3 tools/check_latent_crossing.py [--rehearse] [--prefilled N]

The benchmark's reference check runs 388 positions; YaRN's ramp acts from
position 0 but the position scale ``s(p)`` is 1 below
``original_max_position_embeddings``. This runs ONE layer of
``mistral-small-4-119b`` (a whole period) at the published widths over a
sequence that crosses that position (8320 tokens prefilled in chunks through
the latent cache, then 4 decoded) and compares the last 256 prefilled and
the decoded positions' logits with the plain reference in its blocked form
(``benchmark/families/mistral4.crossing_check``). One JSON line (its
``latent_forms``: which form of the latent kernel each traced program took,
``flexflow_tpu.kernels.latent_summary()``); exit 1 if the logits or the
routes are outside the family's limits. ``--rehearse``:
CPU, the configuration's rehearsal sizes, interpreted kernels.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--prefilled", type=int, default=None,
                    help="tokens prefilled (default: the original context "
                         "plus one chunk)")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["FF_PALLAS_INTERPRET"] = "1"
    import jax

    import flexflow_tpu.kernels as ffk
    from benchmark import run as bench_run
    from benchmark.families import _common as C

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("no TPU; nothing was run", file=sys.stderr)
        return 2
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mistral-small-4-119b.json")) as f:
        cfg = json.load(f)
    if args.rehearse:
        bench_run.apply_rehearsal(cfg, {"cycle": []})
    family = bench_run.load_module("families", cfg["family"])
    reference = bench_run.load_module("reference", cfg["family"])
    prefilled = args.prefilled or (
        cfg["rope_parameters"]["original_max_position_embeddings"]
        + C.prefill_chunk(cfg))
    res = family.crossing_check(cfg, reference, prefilled)
    res["device"] = jax.devices()[0].device_kind
    res["latent_forms"] = ffk.latent_summary()
    print(json.dumps(res), flush=True)
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
