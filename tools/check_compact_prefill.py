#!/usr/bin/env python3
"""The compact prefill batch against the slot grid, on the device.

    python3 tools/check_compact_prefill.py [--rehearse] [--rounds] [config ...]

The benchmark's reference check prefills through the slot grid
(``benchmark/families/_common.program_logits``) and cannot see the compact
``[segments x chunk]`` program the serving loops run. This does: for each
benchmark configuration, on a 2-layer cut at its published widths (the cut
the reference check uses, same seeded weights; a configuration whose layers
are not all alike takes one whole period of them: ``CONFIGS``), the same
prompts are
prefilled by ``RequestManager``'s own chooser and builders once through the
compact program and once through the slot-grid program. It compares the K/V
caches over every written position and the logits of the next decode step,
and prints one JSON line a configuration. Exit 1 if a cache row (a position
of a layer) differs by more than bfloat16 rounding or any prompt's logits by
more than the cell's reference tolerance (relative L2 0.03). An expert
model's router is discontinuous, so its compact run is sent, token by token,
to the experts the grid run chose (at its own probabilities for them): a
token whose own top-k this overrode is counted and its tie measured, and
more than a tie, or more than a tenth of the tokens, fails the check too.
A windowed layer's ring (ops/kv_layout.py) is compared over the positions it
still holds, a prompt's last ``ring rows``; a latent layer's one stream over
the entry's own values (the latent and the rotated key part).
``--rehearse``: CPU, the configuration's tiny rehearsal sizes, interpreted
kernels. ``--rounds``: the prompts are then also served by the scheduler
loop, with one prefill step a round and with as many as a decode block has
steps, times everyone resident over the rows decoding (consecutive compact
steps, a ring wrapping inside a round): the
tokens must be the same, and how many first tokens differ from the grid
run's pick is reported (none on the CPU).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from types import SimpleNamespace

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# configuration -> layers of the cut (K-EXAONE: one period, so that a full
# layer and the windowed ones, the dense layer and the sparse ones are there)
CONFIGS = {"falcon-7b": 2, "opt-6.7b-spec": 2, "olmoe-1b-7b": 2,
           "k-exaone-236b-a23b": 4, "mistral-small-4-119b": 2}
TOL_LOGITS = 0.03           # the families' REFERENCE_TOL
# A cache row's relative L2. The first layer's rows are a projection of the
# embedding: one bfloat16 rounding (2**-8) either way. A later layer's come
# from activations that the two programs (which tile their gemms by their
# own batch) already rounded apart in each of some ten operations.
TOL_CACHE_FIRST = 2.0 ** -7
TOL_CACHE = 0.02
TOL_TIE = 0.08              # the families' ROUTE_MARGIN: what counts as a tie


def prompts_for(cfg: dict, chunk: int):
    """(slot, tokens): more prompts filling than segments at first, one of
    several chunks, one of an odd length, one ending within a chunk of the
    cache's end, short ones (an expert model flips a route in most long
    ones). Slots in a shuffled order: a batch row is rarely its slot."""
    a = cfg["assumed"]
    R, S = a["max_requests_per_batch"], a["max_sequence_length"]
    lens = [2 * chunk + 44, S - 3, chunk + 1, 40, 17, 3 * chunk, 9, 6]
    rng = np.random.default_rng(cfg["weights_seed"])
    slots = rng.permutation(R)[:len(lens)].tolist()
    return [(s, rng.integers(1, cfg["vocab_size"], size=n).tolist())
            for s, n in zip(slots, lens)]


def graph_step(model, logits: bool):
    """The jitted serving forward over a BatchMeta (the layer walk of
    ``FFModel._run_graph``, which ``engine.forward_with_meta`` traces),
    giving besides the new cache state the routing of every expert layer
    and, if asked, float32 logits of each row's first position.

    ``forced`` is None or, for each expert layer, ``(experts [rows, Q, k],
    mask [rows, Q])``: where the mask is set the token is sent to those
    experts, at the probabilities this run's own router gave them, in place
    of its own top-k. A routing entry is ``(experts used, their
    probabilities, the run's own top-k, its probabilities)``."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ffconst import OpType
    from flexflow_tpu.ops.base import OpContext
    from flexflow_tpu.serve.engine import build_feeds

    logits_t = model.layers[-1].inputs[0]
    # the top_k layer that feeds each expert layer: (weights, chosen) out
    picks_of = {ly.inputs[1].tensor_id for ly in model.layers
                if ly.op_type == OpType.MOE_EXPERTS}
    routers = [ly for ly in model.layers
               if len(ly.outputs) == 2
               and ly.outputs[1].tensor_id in picks_of]
    cdt = jnp.dtype(model.config.compute_dtype)

    def step(params, state, meta, forced):
        ctx = OpContext(training=False, rng=None, compute_dtype=cdt,
                        batch_config=meta, mesh=model.mesh,
                        config=model.config)
        values = dict(build_feeds(model, meta))
        ctx.state_in, ctx.state_out = state, {}
        routed = []
        for layer in model.layers:
            model._apply_layer(layer, params, values, ctx)
            if layer in routers:
                w_t, idx_t = (t.tensor_id for t in layer.outputs)
                own_w, own = values[w_t], values[idx_t]
                if forced is not None:
                    idx, mask = forced[len(routed)]
                    idx = jnp.where(mask[..., None], idx, own)
                    probs = values[layer.inputs[0].tensor_id]
                    values[idx_t] = idx.astype(own.dtype)
                    values[w_t] = jnp.take_along_axis(
                        probs, idx, axis=-1).astype(own_w.dtype)
                routed.append((values[idx_t], values[w_t], own, own_w))
        out = (values[logits_t.tensor_id][:, 0].astype(jnp.float32)
               if logits else None)
        return {**ctx.state_in, **ctx.state_out}, out, routed

    return jax.jit(step, donate_argnums=(1,))


class Routes:
    """The grid run's expert picks by (layer, slot, position), and what
    became of them in the compact run, which is made to take them."""

    def __init__(self, model):
        from flexflow_tpu.ffconst import OpType

        experts = [ly for ly in model.layers
                   if ly.op_type == OpType.MOE_EXPERTS]
        self.layers = len(experts)
        self.k = experts[0].inputs[1].shape[-1] if experts else 0
        self.picks, self.overridden, self.tie = {}, [], 0.0

    def forced(self, shape, tokens):
        """The ``forced`` argument of a step of ``shape`` [rows, Q] whose
        ``tokens`` are (batch row, column, slot, position), each of which
        the grid run has recorded."""
        out = []
        for layer in range(self.layers):
            idx = np.zeros(tuple(shape) + (self.k,), np.int32)
            mask = np.zeros(shape, bool)
            for r, q, slot, position in tokens:
                idx[r, q], mask[r, q] = self.picks[layer, slot, position], True
            out.append((idx, mask))
        return out

    def note(self, routed, tokens, record: bool):
        """After a step: record the picks (the grid run), or count the
        tokens whose own top-k was overridden and measure the tie: how far
        under its own lowest pick the lowest forced expert stood."""
        for layer, entry in enumerate(routed):
            idx, w, own, own_w = (np.asarray(a) for a in entry)
            for r, q, slot, position in tokens:
                if record:
                    self.picks[layer, slot, position] = idx[r, q]
                elif set(idx[r, q].tolist()) != set(own[r, q].tolist()):
                    self.overridden.append(layer)
                    low = float(own_w[r, q].min())
                    self.tie = max(self.tie,
                                   (low - float(w[r, q].min())) / low)


def prefill(model, step, prompts, compact: bool, routes: Routes):
    """Every prompt but its last token into the cache, by the loops' own
    chooser and builders; returns the steps taken. The grid run records
    its routes, the compact run takes them."""
    from flexflow_tpu.serve.request_manager import RequestManager as RM

    R = model.config.max_requests_per_batch
    chunk, segments = RM._prefill_shape(model.config)
    active = [None] * R
    for i, (slot, toks) in enumerate(prompts):
        active[slot] = SimpleNamespace(slot=slot, tokens=toks, depth=0,
                                       finished=False, prefill_start_s=i)
    steps = 0
    while True:
        rows = RM._prefill_rows(active, chunk, lambda r: r.depth, segments,
                                consecutive=compact)
        if not rows:
            return steps
        meta = (RM._meta_from_segments(segments, chunk, rows) if compact
                else RM._meta_from_rows(R, chunk, rows))
        tokens = [(i if compact else slot, q, slot, sp + q)
                  for i, (slot, toks, sp) in enumerate(rows)
                  for q in range(len(toks))]
        model.op_state, _, routed = step(
            model.params, model.op_state, meta,
            routes.forced(meta.tokens.shape, tokens) if compact else None)
        routes.note(routed, tokens, record=not compact)
        for slot, toks, sp in rows:
            active[slot].depth = sp + len(toks)
        steps += 1


def decode(model, step, prompts, routes: Routes, record: bool):
    """One decode step on the slot grid that feeds every prompt's last
    token: float32 logits by prompt."""
    from flexflow_tpu.serve.batch_config import make_batch_meta

    R = model.config.max_requests_per_batch
    tok, pos = np.zeros((R, 1), np.int32), np.zeros((R, 1), np.int32)
    act = np.zeros((R,), bool)
    for slot, toks in prompts:
        tok[slot, 0], pos[slot, 0], act[slot] = toks[-1], len(toks) - 1, True
    meta = make_batch_meta(R, 1, tokens=tok, positions=pos,
                           start_pos=pos[:, 0],
                           num_tokens=act.astype(np.int32), active=act)
    tokens = [(slot, 0, slot, len(toks) - 1) for slot, toks in prompts]
    model.op_state, out, routed = step(
        model.params, model.op_state, meta,
        None if record else routes.forced((R, 1), tokens))
    routes.note(routed, tokens, record)
    return np.asarray(out)[[slot for slot, _ in prompts]]


def served(model, prompts, new_tokens: int = 24):
    """The prompts through ``RequestManager.generate_incr_decoding`` itself,
    once with one prefill step a scheduler round and once with as many as a
    decode block's steps, times everyone resident over the rows decoding
    (the two programs' costs given, not timed: a prefill step costs one
    decode step). Returns for each the tokens
    generated, by prompt, and the prefill steps of every round."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.serve.request_manager import (InferenceManager,
                                                    RequestManager)
    from flexflow_tpu.serve.step_costs import GivenCosts

    ifm = model._inference_manager = InferenceManager(model)
    step, block = ifm.step, ifm.launch_decode_block
    out = []
    for decode_step_s in (0.0, 1.0):
        model.op_state = jax.tree.map(jnp.zeros_like, model.op_state)
        ifm.step_costs = GivenCosts(1.0, decode_step_s)
        rounds = [0]

        def counted_step(*a, **kw):
            rounds[-1] += 1
            return step(*a, **kw)

        def counted_block(*a, **kw):
            rounds.append(0)
            return block(*a, **kw)

        ifm.step, ifm.launch_decode_block = counted_step, counted_block
        rm = RequestManager()
        guids = [rm.register_new_request(list(toks), max_new_tokens=new_tokens)
                 for _, toks in prompts]
        rm.generate_incr_decoding(model)
        out.append(([rm.results[g].output_tokens for g in guids], rounds))
    ifm.step, ifm.launch_decode_block = step, block
    return out


def written(model, c: str, slot: int, n: int):
    """What ``slot``'s cache ``c`` ("k" or "v"; "c": a latent layer's one
    stream) holds of its first ``n`` positions, one float32 ``[KH,
    positions, D]`` per attention layer in the model's order, read as stored
    through the layout's owner (packed at D=64; a windowed layer's ring: the
    last positions it keeps; a latent entry: its own values)."""
    from flexflow_tpu.ops import kv_layout as kvl
    from flexflow_tpu.ops.inc_attention import FULL_STACK, LATENT_STACK

    S = model.config.max_sequence_length
    for ly in model.layers:
        if "cache_layer_idx" not in ly.attrs:
            continue
        if c == "c":
            got = kvl.read_latent(
                model.op_state[LATENT_STACK][c], 0, n,
                ly.attrs["kv_lora_rank"], ly.attrs["qk_rope_head_dim"],
                (ly.attrs["cache_layer_idx"], slot))
            yield np.concatenate([np.asarray(a, np.float32)
                                  for a in got], axis=-1)[None]
            continue
        stack = model.op_state[ly.attrs.get("cache_stack", FULL_STACK)][c]
        at = (ly.attrs["cache_layer_idx"], slot)
        if ly.attrs.get("sliding_window") is None:
            rows = kvl.read_positions(stack, 0, n, kvl.pack_of(stack, S), at)
        else:
            rows = kvl.read_ring(stack, max(0, n - stack.shape[-2]), n, at)
        yield np.asarray(rows, np.float32)


def check(name: str, rehearse: bool, rounds: bool = False) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmark import run as bench_run
    from benchmark.families import _common as C
    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models import FAMILIES
    from flexflow_tpu.serve.request_manager import RequestManager as RM

    with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    if rehearse:
        bench_run.apply_rehearsal(cfg, {"cycle": []})
    family = bench_run.load_module("families", cfg["family"])
    layers = CONFIGS.get(name, 2)
    model = C.build_model(C.ffconfig(cfg, False),
                          FAMILIES[cfg["family"]].build,
                          family._model_cfg(cfg, layers),
                          InferenceMode.INC_DECODING_MODE)
    chunk, segments = RM._prefill_shape(model.config)
    prompts = prompts_for(cfg, chunk)
    fill, one = graph_step(model, False), graph_step(model, True)
    # An expert model's router is discontinuous: a near-tie for the last
    # place among a token's experts falls either way on a bfloat16 rounding
    # (the two programs tile their gemms differently), and everything
    # after it then differs by an expert. So the grid run goes first and
    # the compact run is sent the way it went, each token at the compact
    # run's own probabilities. A token whose own top-k was overridden is
    # counted and its tie measured; caches and logits are then compared
    # over every position of every prompt.
    routes, got = Routes(model), {}
    streams = ("c",) if "kv_cache_latent" in model.op_state else ("k", "v")
    for compact in (False, True):
        model.op_state = jax.tree.map(jnp.zeros_like, model.op_state)
        steps = prefill(model, fill, prompts, compact, routes)
        kv = {(c, slot, layer): rows
              for c in streams for slot, toks in prompts
              for layer, rows in enumerate(written(model, c, slot,
                                                   len(toks) - 1))}
        got[compact] = steps, kv, decode(model, one, prompts, routes,
                                         record=not compact)
    (steps_c, kv_c, lg_c), (steps_g, kv_g, lg_g) = got[True], got[False]
    scale = max(float(np.abs(a).max()) for a in kv_g.values())

    def rows_of(a):         # [KH, pos, D] -> [pos, KH x D]
        return np.moveaxis(a, 1, 0).reshape(a.shape[1], -1)

    # a cache row (one position of one layer, all heads) against its twin:
    # relative L2 and largest element
    err = {key: np.linalg.norm(rows_of(kv_c[key] - kv_g[key]), axis=-1)
           / np.maximum(np.linalg.norm(rows_of(kv_g[key]), axis=-1), 1e-30)
           for key in kv_g}
    by_layer = [max(float(e.max()) for key, e in err.items()
                    if key[2] == layer and e.size)
                for layer in range(layers)]
    rel = (np.linalg.norm(lg_c - lg_g, axis=-1)
           / np.linalg.norm(lg_g, axis=-1))
    overridden = [routes.overridden.count(layer)
                  for layer in range(routes.layers)]
    through_loop = {}
    if rounds:
        # the scheduler's rounds: several consecutive compact steps before
        # a decode block (a windowed layer's ring wraps inside a round)
        # give every request the tokens one step a round gives it, and the
        # first of them is the grid run's pick
        (one, rounds_one), (many, rounds_many) = served(model, prompts)
        through_loop = {
            "served_steps_a_round_max": [max(rounds_one), max(rounds_many)],
            "served_rounds": [len(rounds_one), len(rounds_many)],
            "served_steps_by_round": rounds_many,
            "served_positions_a_round_max": max(rounds_many) * segments * chunk,
            "ring_rows": sorted({int(leaf.shape[-2]) for leaf in
                                 model.op_state.get("kv_cache_window",
                                                    {}).values()}),
            "served_tokens_equal": one == many,
            "served_first_tokens_off_the_grid": sum(
                int(toks[0] != int(np.argmax(lg_g[i])))
                for i, toks in enumerate(many)),
        }
    return {"config": name, **through_loop, "layers": layers,
            "prompts": len(prompts),
            "program": [segments, chunk], "steps_compact": steps_c,
            "steps_grid": steps_g,
            "positions_compared": sum(len(t) - 1 for _, t in prompts),
            "cache_max_abs": scale, "cache_row_max_rel_l2_by_layer": by_layer,
            "cache_max_abs_diff": max(float(np.abs(kv_c[key] - kv_g[key]).max())
                                      for key in kv_g),
            "logits_max_rel_l2": float(rel.max()),
            "logits_rel_l2_by_prompt": [float(x) for x in rel],
            "routed_tokens": len(routes.picks),
            "routes_overridden_by_layer": overridden,
            "route_tie_max_rel": routes.tie,
            "ok": bool(scale > 0 and by_layer[0] <= TOL_CACHE_FIRST
                       and max(by_layer) <= TOL_CACHE
                       and float(rel.max()) < TOL_LOGITS
                       and routes.tie < getattr(family, "ROUTE_MARGIN",
                                                TOL_TIE)
                       and sum(overridden) <= 0.1 * max(1, len(routes.picks))
                       and through_loop.get("served_tokens_equal", True)),
            "device": jax.devices()[0].device_kind}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("configs", nargs="*", default=list(CONFIGS))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--rounds", action="store_true",
                    help="also serve the prompts through the scheduler loop, "
                         "with one prefill step a round and with several")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["FF_PALLAS_INTERPRET"] = "1"
    import jax

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("no TPU; nothing was run", file=sys.stderr)
        return 2
    ok = True
    for name in args.configs:
        res = check(name, args.rehearse, args.rounds)
        print(json.dumps(res), flush=True)
        ok = ok and res["ok"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
