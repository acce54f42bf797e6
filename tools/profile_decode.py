"""The decode block's two widths on a benchmark configuration's own model.

  python tools/profile_decode.py --config NAME [NAME ...] [--rehearse]
                                 [--cut N] [--positions N]

The configuration is benchmark/configs/NAME.json, built as its cell builds
it (N layers instead of its depth with --cut): ragged prompts are prefilled
into every slot but the last, then the decode block at the width the
manager resolved (one token a row unless an engine verifies the model) and
at the other of 1 and the sublane-padded verify width 8 decode the same
rows. One JSON line a configuration: the width resolved, ms a step at each
width, and how many of the tokens agree (a near-tie may fall either way in
bfloat16; float32 on the CPU, where they must all agree). --rehearse: the
CPU, the configuration's rehearsal sizes, interpreted kernels, no times.

At 16 and 32 slots the verify width puts a step's int8 gemms over the
chip's ridge, so since PR 38 a model takes it only when an engine verifies
it (PERF.md section 6).
"""

import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def other_width(width: int) -> int:
    from flexflow_tpu.kernels.attention import SUBLANE

    return SUBLANE if width == 1 else 1


def block_at(m, width: int, steps: int):
    """``(tok, pos, act, n) -> tokens [R, n]`` through the decode block of
    ``m`` at ``width``, the state threaded through the model."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.serve.engine import make_decode_block

    blk = make_decode_block(m, jnp.dtype(m.config.compute_dtype), steps,
                            width=width)
    rng = jax.random.PRNGKey(0)

    def run(tok, pos, act, n):
        toks, m.op_state, _ = blk(m.params, m.op_state, jnp.asarray(tok),
                                  jnp.asarray(pos), jnp.asarray(act), rng,
                                  jnp.int32(n))
        return np.asarray(toks)[:, :n]

    return run


def best_ms(run, tok, pos, act, n):
    run(tok, pos, act, 4)                         # compile
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        run(tok, pos, act, n)                     # its read-back = fence
        best = min(best, (time.perf_counter() - t0) / n)
    return best * 1e3


def run_config(name: str, rehearse: bool, cut=None, positions=None) -> dict:
    """The two widths of the decode block on one benchmark configuration.
    ``positions``: the shortest prompt's length (default: two prefill chunks
    and one; a configuration whose layers read more the more a row holds,
    such as evabyte-6.5b's summaries beyond 2048, is profiled at its
    cell's lengths: ``--positions 14000``)."""
    import jax

    from benchmark import run as bench_run
    from benchmark.families import _common as C
    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models import FAMILIES
    from flexflow_tpu.serve.inference_manager import InferenceManager
    from flexflow_tpu.serve.request_manager import RequestManager as RM

    with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    f32 = {}
    if rehearse:
        bench_run.apply_rehearsal(cfg, {"cycle": []})
        f32 = dict(compute_dtype="float32", kv_cache_dtype="float32",
                   quantization_type=None)
    family = bench_run.load_module("families", cfg["family"])
    mc = family._model_cfg(cfg, cut)
    m = C.build_model(C.ffconfig(cfg, False, **f32),
                      FAMILIES[cfg["family"]].build, mc,
                      InferenceMode.INC_DECODING_MODE)
    ifm = InferenceManager(m)
    R, steps = m.config.max_requests_per_batch, m.config.decode_block_steps
    chunk = C.prefill_chunk(cfg)
    rng = np.random.default_rng(cfg["weights_seed"])
    prompts = [rng.integers(1, cfg["vocab_size"],
                            size=(positions or 2 * chunk + 1) + 3 * r).tolist()
               for r in range(R - 1)]             # the last slot stays idle
    for at in range(0, max(map(len, prompts)) - 1, chunk):
        rows = [(r, p[at:min(at + chunk, len(p) - 1)], at)
                for r, p in enumerate(prompts) if at < len(p) - 1]
        ifm.step(RM._meta_from_rows(R, chunk, rows), want_output=False)
    tok = np.array([p[-1] for p in prompts] + [0], np.int32)
    pos = np.array([len(p) - 1 for p in prompts] + [0], np.int32)
    act = np.arange(R) < R - 1
    widths = (ifm.decode_width, other_width(ifm.decode_width))
    blocks = {w: block_at(m, w, steps) for w in widths}
    # the second block rewrites the positions the first wrote: a row only
    # ever attends up to its own position
    toks = {w: blocks[w](tok, pos, act, steps)[act] for w in widths}
    same = toks[widths[0]] == toks[widths[1]]
    out = {"config": name, "layers": mc.num_hidden_layers,
           "rows": int(act.sum()), "positions": [int(pos[act].min()),
                                                 int(pos[act].max())],
           "steps": steps, "width_resolved": widths[0],
           "widths": list(widths),
           "tokens_equal": float(same.mean()),
           "first_tokens_equal": float(same[:, 0].mean()),
           "attention": C.attention_paths(),
           "device": jax.devices()[0].device_kind}
    if not rehearse:
        out["ms_per_step"] = {str(w): round(best_ms(blocks[w], tok, pos, act,
                                                    steps), 4)
                              for w in widths}
    return out


def main_configs(argv) -> int:
    rehearse = "--rehearse" in argv
    cut, positions = (int(argv[argv.index(o) + 1]) if o in argv else None
                      for o in ("--cut", "--positions"))
    names = [a for i, a in enumerate(argv) if not a.startswith("--")
             and argv[i - 1] not in ("--cut", "--positions")]
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["FF_PALLAS_INTERPRET"] = "1"
    import jax

    if not rehearse:
        if jax.devices()[0].platform != "tpu":
            print("no TPU; nothing was run", file=sys.stderr)
            return 2
        from flexflow_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
    for name in names:
        print(json.dumps(run_config(name, rehearse, cut, positions)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main_configs(sys.argv[1:]))
