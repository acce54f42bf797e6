#!/usr/bin/env python3
"""The decode block's pass of two blocks against the plain reference, on the
device.

    python3 tools/check_folded_commit.py [--rehearse]

A block-diffusion row stores a block that is whole in the pass that begins
to denoise its next block, so every pass of the decode block is 2B tokens a
row (``serve/engine.diffusion_pass``). The benchmark's reference check
drives B-wide passes (``benchmark/families/sdar_moe.Passes``) and so never
runs that program.
This does, on SDAR's 2-layer cut at the published widths, the same seeded
weights: a chunk of whole blocks is prefilled through the compact batch on
two slots; then ONE wide pass runs slot 0 with a whole block in front of
four masks and slot 1 with a ragged block (one known token) and nothing
carried, its B real tokens first. Compared:

* the new block's logits of both rows against
  ``benchmark/reference/sdar_moe.forward_routed`` on the same tokens and the
  program's routes, relative L2 at the worst position (the family's
  ``REFERENCE_TOL``), the routes checked apart (``ROUTE_MARGIN``);
* the same logits against the two B-wide passes the wide one replaces (the
  whole block alone, then the next block's first pass: the reference
  check's program), and the keys and
  values the two ways leave at the whole block's positions in every layer
  (two programs that tile their gemms by their own batch: bfloat16 rounding,
  ``TOL_CACHE`` as tools/check_compact_prefill.py's);
* slot 1's cache past its B real tokens: not written;
* how the passes' keys and values reached the cache
  (``flexflow_tpu.kernels.append_summary``): the wide pass's run of 2B
  positions a row inside the attention kernel, a trace a layer, and no
  row-granular scatter of that width.

Prints one JSON line; exit 1 if any of it fails. ``--rehearse``: CPU, the
configuration's tiny rehearsal sizes, interpreted kernels (tier-1 runs it).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONFIG = "sdar-30b-a3b"
TOL_CACHE = 0.02            # a cache row's relative L2 between two programs


def rel_l2(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float((np.linalg.norm(a - b, axis=-1)
                  / np.maximum(np.linalg.norm(b, axis=-1), 1e-30)).max())


def check(rehearse: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmark import run as bench_run
    from benchmark.families import _common as C
    from flexflow_tpu import kernels as ffk
    from flexflow_tpu.ffconst import InferenceMode, OpType
    from flexflow_tpu.models.sdar_moe import create_sdar_moe_model
    from flexflow_tpu.serve.batch_config import BatchMeta
    from flexflow_tpu.serve.engine import diffusion_pass, forward_with_meta
    from flexflow_tpu.serve.request_manager import RequestManager as RM

    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{CONFIG}.json")) as f:
        cfg = json.load(f)
    if rehearse:
        bench_run.apply_rehearsal(cfg, {"cycle": []})
    family = bench_run.load_module("families", cfg["family"])
    reference = bench_run.load_module("reference", cfg["family"])
    layers = family.REFERENCE_LAYERS
    ffk.reset_dispatch_stats()
    m = C.build_model(C.ffconfig(cfg, False, max_requests_per_batch=2),
                      create_sdar_moe_model, family._model_cfg(cfg, layers),
                      InferenceMode.INC_DECODING_MODE)
    weights = family.reference_weights(m, layers)
    bd = m.block_diffusion
    B, mask_id = bd.block_length, bd.mask_token_id
    chunk, segments = RM._prefill_shape(m.config)
    rng = np.random.default_rng(cfg["weights_seed"])
    prefix = [rng.integers(1, mask_id, size=chunk).tolist() for _ in (0, 1)]
    whole = rng.integers(1, mask_id, size=B).tolist()
    ragged = [int(rng.integers(1, mask_id))] + [-1] * (B - 1)
    masks = [-1] * B

    # the prefix of both slots, through the compact prefill; its routes
    run = family.Passes(m)
    filled = [run._step(RM._meta_from_segments(segments, chunk,
                                               [(slot, prefix[slot], 0)]),
                        False, chunk)[1] for slot in (0, 1)]
    # (Passes reads row 0 of a step: the one segment, whichever its slot)
    logits_t = m.layers[-1].inputs[0]
    chosen_t = [ly.inputs[1] for ly in m.layers
                if ly.op_type == OpType.MOE_EXPERTS]
    cdt = jnp.dtype(m.config.compute_dtype)

    def wide(params, state, win, pos, act, carried):
        (logits, *chosen), state = diffusion_pass(
            m, params, state, win, pos, act, carried, None, cdt,
            outputs=[logits_t] + chosen_t)
        return logits.astype(jnp.float32), chosen, state

    def b_wide(params, state, blk, pos, act):   # the pass of one block
        meta = BatchMeta(
            tokens=jnp.where(blk < 0, mask_id, blk),
            positions=pos[:, None] + jnp.arange(B), start_pos=pos,
            num_tokens=B * act.astype(jnp.int32), active=act)
        (logits,), state = forward_with_meta(
            m, params, state, meta, None, cdt, phase="decode",
            outputs=[logits_t])
        return logits.astype(jnp.float32), state

    wide = functools.partial(jax.jit(wide), m.params)
    b_wide = functools.partial(jax.jit(b_wide), m.params)
    both, first = np.array([True, True]), np.array([True, False])
    at = np.array([chunk, chunk], np.int32)
    start = m.op_state
    # one pass of two blocks: slot 0 carries ``whole``, slot 1 nothing
    one, routes, st_one = wide(
        start, np.array([whole + masks, ragged + masks], np.int32), at, both,
        first)
    # the two passes it replaces
    _, st = b_wide(start, np.array([whole, masks], np.int32), at, first)
    two, st_two = b_wide(st, np.array([masks, ragged], np.int32),
                         at + np.array([B, 0], np.int32), both)
    one, two = np.asarray(one), np.asarray(two)
    routes = [np.asarray(r) for r in routes]

    # the reference on the same tokens and the program's routes; both rows
    # are chunk + 2B long (a block of masks after slot 1's changes nothing
    # before it): one set of compiled shapes
    seen = lambda block: [mask_id if t < 0 else t for t in block]
    ours, refs, probs_all, routed = [], [], [], []
    for slot, tail, take in ((0, whole + seen(masks), slice(B, 2 * B)),
                             (1, seen(ragged) + seen(masks), slice(0, B))):
        mine = [r[slot, :2 * B] if slot == 0 else
                np.concatenate([r[slot, :B], r[slot, :B]], axis=0)
                for r in routes]
        ref, probs = reference.forward_routed(
            weights, jnp.asarray(prefix[slot] + tail), cfg,
            routes=[np.concatenate([f, w], axis=0)
                    for f, w in zip(filled[slot], mine)])
        ours.append(one[slot])
        refs.append(np.asarray(ref)[chunk:][take])
        # the program's routes of the pass's REAL tokens, against the
        # reference's probabilities at those positions
        real = slice(0, 2 * B) if slot == 0 else slice(0, B)
        routed.append([r[real] for r in mine])
        probs_all.append([np.asarray(p)[chunk:][real] for p in probs])
    out = family.check_routes(
        [np.concatenate(rs, axis=0) for rs in zip(*routed)],
        [np.concatenate(ps, axis=0) for ps in zip(*probs_all)],
        family.ROUTE_MARGIN)

    def rows(state, slot, a, b):        # [pos, layers x streams x heads x D]
        kv = state["kv_cache"]
        x = np.stack([np.asarray(kv[c][:, slot, :, a:b], np.float32)
                      for c in ("k", "v")])
        return np.moveaxis(x, 3, 0).reshape(b - a, -1)

    stored = rel_l2(rows(st_one, 0, chunk, chunk + B),
                    rows(st_two, 0, chunk, chunk + B))
    beyond = float(np.abs(rows(st_one, 1, chunk + B, chunk + 2 * B)).max())
    tol = family.REFERENCE_TOL
    out.update({
        "config": CONFIG, "layers": layers, "rows": 2, "block": B,
        "prefix": chunk, "tol": tol, "tol_cache": TOL_CACHE,
        "wide_rel_l2": rel_l2(ours[0], refs[0]),
        "wide_uncarried_rel_l2": rel_l2(ours[1], refs[1]),
        "two_passes_rel_l2": max(rel_l2(two[0], refs[0]),
                                 rel_l2(two[1], refs[1])),
        "wide_against_two_passes_rel_l2": rel_l2(one, two),
        "stored_rel_l2": stored,
        "stored_max_abs": float(np.abs(
            rows(st_one, 0, chunk, chunk + B)).max()),
        "written_past_real_tokens": beyond,
        "attention": {"fast_path_traces": ffk.fast_path_count,
                      "fallback_traces": dict(ffk.fallback_counts)},
        "appends": ffk.append_summary(),
        "device": jax.devices()[0].device_kind})
    out["ok"] = bool(
        out["routes_ok"] and out["wide_rel_l2"] < tol
        and out["wide_uncarried_rel_l2"] < tol and stored < TOL_CACHE
        and out["stored_max_abs"] > 0 and beyond == 0.0
        and not ffk.fallback_counts
        and ffk.fused_append_counts.get(2 * B) == layers
        and 2 * B not in ffk.scatter_append_counts)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["FF_PALLAS_INTERPRET"] = "1"
    import jax

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("no TPU; nothing was run", file=sys.stderr)
        return 2
    res = check(args.rehearse)
    print(json.dumps(res), flush=True)
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
