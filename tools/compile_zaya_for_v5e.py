#!/usr/bin/env python3
"""Compile a cut of ``zaya1-8b`` at the published widths for a DESCRIBED v5e,
without the chip: the compact prefill step, as the loops run it (output-free:
``InferenceManager._prefill_impl``, which ends at the last layer's hidden
state) and whole (``_step_impl``: the final norm, the head and the pick
behind it), and the decode block.

    python3 tools/compile_zaya_for_v5e.py [layers, default 2] [--unfenced]

The cut is built on the CPU (its weights are real; a minute and about 3 GB
at two layers), then its two serving programs are lowered with shapes placed
on a described ``v5e:2x2`` device and compiled by XLA:TPU and Mosaic
(on-chip-measurement guide, section 2). It proves nothing about results or
speed: it says whether the chip's compiler takes the programs (PR 50:
libtpu 0.0.34 segfaulted on the router's four fused gemms until
``models/zaya.RouterBarrier`` stood in the middle of them; ``--unfenced``
compiles the programs WITHOUT that barrier, and a compiler that takes them
so no longer needs it: ROADMAP R7 (e)), what they keep in memory, how many
Mosaic calls and operations they hold (the entry computation's
instructions and XLA's own count of their arithmetic: what the head costs a
prefill step is the difference of the two prefill programs, PERF.md section
6, PR 53) and whether a large array is copied or transposed
(the tied head reads the embedding's table with no relayout; each layer's
``gate`` stack is kept in VMEM, fetched in four async slices: PERF.md
section 6, PR 50). The optimised HLO lands under ``chiprun_out/``.
"""
from __future__ import annotations

import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

BIG = 4e6       # elements: a copy or transpose of more is worth a line


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    unfenced = "--unfenced" in argv
    layers = int(([a for a in argv if a != "--unfenced"] or ["2"])[0])
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # built as the chip builds it: caches laid out for the kernels
    os.environ["FF_PALLAS_INTERPRET"] = "1"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import flexflow_tpu.kernels as ffk
    from benchmark.families import _common as C
    from benchmark.run import load_module
    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models.zaya import create_zaya_model
    from flexflow_tpu.serve.engine import make_decode_block
    from flexflow_tpu.serve.inference_manager import InferenceManager
    from flexflow_tpu.serve.request_manager import RequestManager as RM

    if unfenced:
        from flexflow_tpu.models.zaya import RouterBarrier

        RouterBarrier.forward = staticmethod(
            lambda attrs, params, inputs, ctx: [inputs[0]])
        print("WITHOUT the router's barrier: a segfault below means the "
              "compiler still needs it")
    family = load_module("families", "zaya")
    with open(os.path.join(ROOT, "benchmark/configs/zaya1-8b.json")) as f:
        cfg = json.load(f)
    t = time.time()
    m = C.build_model(C.ffconfig(cfg, False), create_zaya_model,
                      family._model_cfg(cfg, layers),
                      InferenceMode.INC_DECODING_MODE)
    print(f"built {layers} layers on the CPU in {time.time() - t:.0f} s")
    # ... and lowered as the chip lowers it: compiled kernels
    del os.environ["FF_PALLAS_INTERPRET"]
    ffk.use_pallas = lambda config=None: True
    ffk.pallas_interpret_forced = lambda: False
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def aval(x):
        x = np.asarray(x) if not hasattr(x, "dtype") else x
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    params, state = jax.tree.map(aval, (m.params, m.op_state))
    cdt = jnp.dtype(m.config.compute_dtype)
    R = m.config.max_requests_per_batch
    chunk, segments = RM._prefill_shape(m.config)
    # two consecutive segments of one slot and a ragged one of another
    meta = jax.tree.map(aval, RM._meta_from_segments(segments, chunk, [
        (1, [1] * chunk, 0), (1, [1] * chunk, chunk), (2, [1] * 5, 640)]))
    i32 = np.zeros(R, np.int32)
    rng = aval(np.zeros(2, np.uint32))
    ifm = InferenceManager(m)
    programs = (
        ("prefill", ifm._prefill, (params, state, meta, rng)),
        ("prefill_whole", ifm._step, (params, state, meta, rng)),
        ("decode", make_decode_block(m, cdt, m.config.decode_block_steps),
         (params, state, aval(i32), aval(i32), aval(i32 > 0), rng,
          aval(np.int32(0)))))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    for name, fn, args in programs:
        t = time.time()
        compiled = fn.lower(*args).compile()
        text, mem = compiled.as_text(), compiled.memory_analysis()
        print(f"{name}: compiled in {time.time() - t:.1f} s; temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.3f} GB, arguments "
              f"{mem.argument_size_in_bytes / 1e9:.3f} GB")
        with open(os.path.join(ROOT, "chiprun_out",
                               f"zaya_{name}_{layers}.hlo"), "w") as f:
            f.write(text)
        for line in text.splitlines():
            hit = re.search(r"= \w+\[([\d,]+)\]\S* (copy|transpose)\(", line)
            if hit and np.prod([int(x) for x in
                                hit.group(1).split(",")]) > BIG:
                print("   large", line.strip()[:200])
        print("   Mosaic calls:", text.count('custom_call_target="tpu_custom_call"'))
        entry = text[text.index("\nENTRY "):]
        entry = entry[:entry.index("\n}")].splitlines()[2:]
        cost = compiled.cost_analysis()
        print(f"   operations: {len(entry)} in the entry computation, "
              f"{sum(' fusion(' in line for line in entry)} of them fusions; "
              f"{cost['flops'] / 1e12:.3f} TFLOP and "
              f"{cost['bytes accessed'] / 1e9:.2f} GB by XLA's count")
    return 0


if __name__ == "__main__":
    sys.exit(main())
