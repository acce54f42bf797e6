#!/usr/bin/env python3
"""The plain k/v attention kernel ALONE at the cells' decode shapes, on the device.

    python3 tools/time_stream_attend.py [--rehearse] [--parent CHECKOUT]
        [--shapes zaya1,sdar,k-exaone,olmoe] [--blocks 128,256,...]
        [--groups 4,16,...] [--steps 8]
        [--out chiprun_out/time_stream_attend.json]

For each shape: a stack ``[layers, R, KH, S, 128]`` of keys and of values made
on the device, rows' lengths drawn from the cell's cycle (a prompt and part
of its answer), and ONE jitted loop of ``--steps`` decode steps over all the
layers' calls of ``kernels.attention.flash_attend``, the step's new
positions appended by the kernel itself and the caches aliased through
(donated). The best of five, in microseconds a call, for:

- ``tree``: the kernel as the rule decides (``stream_block``, of the call's
  shapes alone), with its form and DMA block;
- ``loop``: the same tree's loop form (the rule overridden to the partition);
- ``<form>.stream`` / ``<form>.arith``: the stream alone (no scores, no
  softmax) and the arithmetic alone (on resident buffers, nothing fetched),
  ``kernels.attention.ABLATE``;
- ``block=N``: the block form at a DMA block of N positions (``--blocks``),
  and ``--groups``: each shape again at those query heads a key/value head:
  where the rule turns;
- ``parent``: ``--parent``'s kernel (a checkout of the parent commit, e.g.
  ``.scratch/parent``), and ``bit_equal_to_parent``: whether one step's
  output and both caches after the append equal the tree's bit for bit
  (without ``--parent``: the tree's two forms against each other).

One JSON line (and ``--out``); exit 1 if a comparison is not bit-equal.
``--rehearse``: CPU, tiny shapes, interpreted kernels, one repeat: what runs
is checked, no time means anything.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# a cell's decode call: rows, key/value heads, group, tokens a row a step
# (its run of new positions), positions a slot, layers that take this kernel,
# live rows, and the traffic whose cycle the lengths are drawn from
SHAPES = {
    "zaya1": dict(R=16, KH=2, G=4, Q=1, S=16384, layers=10, live=15,
                  traffic="long-context-reasoning"),
    "sdar": dict(R=32, KH=4, G=8, Q=8, S=1024, layers=12, live=32,
                 traffic="decode-steady"),
    "k-exaone": dict(R=32, KH=8, G=8, Q=1, S=8192, layers=2, live=32,
                     traffic="long-mixed-queue"),
    "olmoe": dict(R=32, KH=16, G=1, Q=1, S=1024, layers=16, live=32,
                  traffic="decode-steady"),
}
REHEARSAL = dict(R=4, S=1024, layers=2, live=3)
D = 128


def lengths_of(shape, rng):
    """Each live row's positions before the timed steps: a request of the
    cell's cycle, its prompt and a drawn part of its answer."""
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           shape["traffic"] + ".json")) as f:
        cycle = json.load(f)["cycle"]
    out = []
    for r in range(shape["R"]):
        prompt, answer = cycle[r % len(cycle)]
        n = prompt + int(rng.integers(0, answer))
        out.append(min(n, shape["S"] - 64) if r < shape["live"] else 0)
    return out


def load_parent(checkout):
    path = os.path.join(checkout, "flexflow_tpu", "kernels", "attention.py")
    spec = importlib.util.spec_from_file_location("parent_attention", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--parent", default=None)
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--blocks", default="")
    ap.add_argument("--groups", default="",
                    help="query heads a key/value head, in place of each "
                         "shape's own: where the rule turns in the rows")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=51)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.kernels import attention as fa

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("no TPU; nothing was run", file=sys.stderr)
        return 2
    parent = load_parent(args.parent) if args.parent else None
    repeats, steps = (1, 2) if args.rehearse else (5, args.steps)
    rule = fa.stream_block

    def step_of(mod, shape, lengths):
        """One decode step of all the layers' calls: (k, v, t) -> the
        outputs' sum (what keeps every call alive), the caches."""
        R, KH, G, Q = (shape[x] for x in ("R", "KH", "G", "Q"))
        live = lengths > 0

        def step(k, v, q, new, t):
            at = jnp.where(live, lengths + t * Q, -1)
            qpos = jnp.maximum(at, 0)[:, None] + jnp.arange(Q)[None]
            total = jnp.zeros((R, Q, KH * G * D), jnp.float32)
            outs = []
            for layer in range(shape["layers"]):
                out, k, v = mod.flash_attend.__wrapped__(
                    q, k, v, jnp.where(live, at + Q, 0), qpos,
                    append_kv=(new, new * 0.5, at), layer_idx=layer,
                    interpret=args.rehearse)
                outs.append(out)
                total = total + out
            return total, outs, k, v
        return step

    def timed(mod, shape, lengths, stack):
        step = step_of(mod, shape, lengths)

        def loop(k, v, q, new):
            def body(t, c):
                total, _, k, v = step(c[1], c[2], q, new, t)
                return c[0] + total, k, v
            total = jnp.zeros(
                (shape["R"], shape["Q"], shape["KH"] * shape["G"] * D),
                jnp.float32)
            return jax.lax.fori_loop(0, steps, body, (total, k, v))
        fn = jax.jit(loop, donate_argnums=(0, 1))
        k, v, q, new = stack()
        best = None
        _, k, v = jax.block_until_ready(fn(k, v, q, new))      # compile
        for _ in range(repeats):
            t0 = time.perf_counter()
            _, k, v = jax.block_until_ready(fn(k, v, q, new))
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best / (steps * shape["layers"]) * 1e6

    def one_step(mod, shape, lengths, stack):
        k, v, q, new = stack()
        fn = jax.jit(lambda k, v, q, new: step_of(mod, shape, lengths)(
            k, v, q, new, 0)[1:], donate_argnums=(0, 1))
        return [np.asarray(x.astype(jnp.float32))
                for x in jax.tree.leaves(fn(k, v, q, new))]

    results, ok = {}, True
    for name, group in [(s, g) for s in args.shapes.split(",") if s
                        for g in (args.groups.split(",") or [""])]:
        shape = dict(SHAPES[name])
        if group:
            shape["G"], name = int(group), f"{name}.g{group}"
        if args.rehearse:
            shape.update(REHEARSAL)
        R, KH, G, Q, S, L = (shape[x] for x in
                             ("R", "KH", "G", "Q", "S", "layers"))
        rng = np.random.default_rng(args.seed)
        lengths = jnp.asarray(lengths_of(shape, rng), jnp.int32)
        if args.rehearse:
            lengths = jnp.minimum(lengths // 7, S - 64)

        def stack():
            ks = jax.random.split(jax.random.key(args.seed), 4)
            mk = jax.jit(lambda key, shp: jax.random.normal(
                key, shp, jnp.bfloat16), static_argnums=1)
            return (mk(ks[0], (L, R, KH, S, D)), mk(ks[1], (L, R, KH, S, D)),
                    mk(ks[2], (R, Q, KH * G, D)), mk(ks[3], (R, Q, KH, D)))

        DB = rule(KH, D, 2, G * Q, S)
        BS = fa._pick_block_s(S, D)
        positions = int(jnp.sum(jnp.where(lengths > 0, lengths + Q, 0)))
        res = {"shape": shape, "partition": BS, "rule_block": DB,
               "form": "block" if DB > BS else "loop",
               "positions_a_call": positions,
               "bytes_a_call": positions * KH * D * 2 * 2, "us_a_call": {}}

        def forced(db):
            fa.stream_block = (lambda *a: db) if db else rule

        variants = [("tree", None, None)]
        if DB > BS:
            variants.append(("loop", BS, None))
        for form, db in (("tree", None), ("loop", BS)):
            if form == "tree" or DB > BS:
                variants += [(f"{form}.stream", db, "stream"),
                             (f"{form}.arith", db, "arith")]
        variants += [(f"block={b}", int(b), None)
                     for b in args.blocks.split(",")
                     if b and S % int(b) == 0 and int(b) >= BS
                     and KH * G * Q * int(b) * 4 <= 4 * 1024 * 1024]
        for label, db, ablate in variants:
            forced(db)
            fa.ABLATE = ablate
            try:
                res["us_a_call"][label] = round(
                    timed(fa, shape, lengths, stack), 2)
            finally:
                forced(None)
                fa.ABLATE = None
            print(f"# {name} {label}: {res['us_a_call'].get(label)}",
                  file=sys.stderr, flush=True)
        if parent is not None:
            res["us_a_call"]["parent"] = round(
                timed(parent, shape, lengths, stack), 2)
        # bit for bit: one step's outputs and the caches after the append
        mine = one_step(fa, shape, lengths, stack)
        if parent is not None:
            theirs = one_step(parent, shape, lengths, stack)
            res["bit_equal_to_parent"] = all(
                np.array_equal(a, b) for a, b in zip(mine, theirs))
            ok = ok and res["bit_equal_to_parent"]
        if DB > BS:
            forced(BS)
            try:
                theirs = one_step(fa, shape, lengths, stack)
            finally:
                forced(None)
            res["forms_bit_equal"] = all(
                np.array_equal(a, b) for a, b in zip(mine, theirs))
            ok = ok and res["forms_bit_equal"]
        t = res["us_a_call"]["tree"]
        res["gb_s"] = round(res["bytes_a_call"] / t / 1e3, 1)
        results[name] = res
        print(f"# {name}: {json.dumps(res['us_a_call'])}", file=sys.stderr,
              flush=True)
    line = {"ok": ok, "device": jax.devices()[0].device_kind,
            "steps": steps, "repeats": repeats,
            "block_target": fa.STREAM_BLOCK_TARGET, "shapes": results}
    text = json.dumps(line)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text, flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
