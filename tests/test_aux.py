"""Aux subsystem tests: dot export, profiling, inference-debug dumps,
RecompileState, network simulator (SURVEY §5 parity)."""

import functools
import os
import re

import numpy as np
import pytest

import flexflow_tpu as ff


def _small_model(batch=16):
    model = ff.FFModel(ff.FFConfig(batch_size=batch))
    t = model.create_tensor([batch, 32], ff.DataType.DT_FLOAT)
    x = model.dense(t, 32, ff.ActiMode.AC_MODE_RELU, name="fc1")
    x = model.dense(x, 8, name="fc2")
    model.softmax(x, name="sm")
    return model


def test_dot_export(tmp_path):
    model = _small_model()
    model.compile()
    path = str(tmp_path / "graph.dot")
    model.export_dot(path, include_costs=True, costs={"fc1": 1.5e-4})
    text = open(path).read()
    assert text.startswith("digraph")
    assert "fc1" in text and "fc2" in text and "sm" in text
    assert '"fc1" -> "fc2"' in text
    assert "cost: 1.500e-04s" in text


def test_export_strategy_file_on_compile(tmp_path):
    path = str(tmp_path / "strategy.dot")
    model = ff.FFModel(ff.FFConfig(batch_size=16,
                                   export_strategy_file=path))
    t = model.create_tensor([16, 32], ff.DataType.DT_FLOAT)
    model.softmax(model.dense(t, 8))
    model.compile()
    assert os.path.exists(path)


def test_include_costs_dot_graph_emits_costs(tmp_path):
    path = str(tmp_path / "costs.dot")
    model = ff.FFModel(ff.FFConfig(batch_size=16,
                                   export_strategy_file=path,
                                   include_costs_dot_graph=True))
    t = model.create_tensor([16, 32], ff.DataType.DT_FLOAT)
    model.softmax(model.dense(t, 8, name="head"))
    model.compile()
    text = open(path).read()
    assert "cost:" in text


def test_pcg_dot():
    from flexflow_tpu.search.pcg import PCG
    from flexflow_tpu.utils.dot import pcg_to_dot

    model = _small_model()
    pcg = PCG.from_model(model)
    text = pcg_to_dot(pcg)
    assert "digraph pcg" in text and "fc1" in text


def test_profiling_step_timer():
    model = _small_model()
    model.config.profiling = True
    model.compile(optimizer=ff.SGDOptimizer(model, lr=0.1),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  metrics=[ff.MetricsType.METRICS_ACCURACY])
    rng = np.random.RandomState(0)
    x = rng.randn(16, 32).astype(np.float32)
    y = rng.randint(0, 8, (16, 1)).astype(np.int32)
    model.train_one_batch([x], y)
    model.train_one_batch([x], y)
    s = model._step_timer.summary()
    assert s["train_step"]["count"] == 2
    assert s["train_step"]["mean_ms"] > 0


def test_inference_debug_dumps(tmp_path, monkeypatch):
    from flexflow_tpu.utils.debugging import compare_dumps, dump_forward

    model = _small_model()
    model.compile()
    rng = np.random.RandomState(0)
    x = rng.randn(16, 32).astype(np.float32)
    feeds = {model.input_tensors[0].tensor_id: x}
    d1 = str(tmp_path / "a")
    d2 = str(tmp_path / "b")
    vals = dump_forward(model, feeds, d1, step=0)
    dump_forward(model, feeds, d2, step=0)
    files = sorted(os.listdir(os.path.join(d1, "step_0")))
    assert len(files) == 3  # fc1, fc2, sm
    with np.load(os.path.join(d1, "step_0", files[0])) as blob:
        assert "input_0" in blob and "weight_kernel" in blob \
            and "output_0" in blob
    assert compare_dumps(os.path.join(d1, "step_0"),
                         os.path.join(d2, "step_0")) == []
    # eager values match the jitted predict path
    np.testing.assert_allclose(
        np.asarray(vals[model._final_tensor.tensor_id]),
        model.predict(x), rtol=1e-5, atol=1e-6)


def test_serving_debug_dumps(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models.llama import LLAMAConfig, create_llama_model
    from flexflow_tpu.serve.request_manager import RequestManager

    cfg = ff.FFConfig(max_requests_per_batch=2, max_tokens_per_batch=16,
                      max_sequence_length=32, inference_debugging=True)
    mcfg = LLAMAConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                       num_hidden_layers=1, num_attention_heads=2,
                       num_key_value_heads=2, max_position_embeddings=32)
    model = ff.FFModel(cfg)
    create_llama_model(model, mcfg, InferenceMode.INC_DECODING_MODE)
    model.compile()
    rm = RequestManager(eos_token_id=None)
    rm.register_new_request([3, 5, 7], max_new_tokens=2)
    rm.generate_incr_decoding(model)
    assert os.path.isdir("inference_tensors")
    steps = os.listdir("inference_tensors")
    assert steps, "no steps dumped"


def test_recompile_state():
    from flexflow_tpu.core.recompile import RecompileState

    model = ff.FFModel(ff.FFConfig(batch_size=16))
    t = model.create_tensor([16, 32], ff.DataType.DT_FLOAT)
    x = model.dense(t, 32, ff.ActiMode.AC_MODE_RELU, name="fc1")
    model.softmax(model.dense(x, 8, name="fc2"))
    model.compile(optimizer=ff.SGDOptimizer(model, lr=0.1),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  metrics=[])

    rng = np.random.RandomState(0)
    x_np = rng.randn(16, 32).astype(np.float32)
    y_np = rng.randint(0, 8, (16, 1)).astype(np.int32)
    model.train_one_batch([x_np], y_np)
    kernel_before = model.get_parameter_by_key(("fc1", "kernel"))

    fired = {"n": 0}

    def alter(rs):
        fired["n"] += 1

    rs = RecompileState(lambda: True, alter, model)
    assert model.recompile_on_condition(rs)
    assert fired["n"] == 1 and rs.recompilations == 1
    # trained parameters survive the recompile
    np.testing.assert_allclose(model.get_parameter_by_key(("fc1", "kernel")),
                               kernel_before)
    # model still trains after recompile
    model.train_one_batch([x_np], y_np)

    rs2 = RecompileState(lambda: False, alter, model)
    assert not model.recompile_on_condition(rs2)
    assert fired["n"] == 1


def test_cache_op_score_feeds_recompile_trigger():
    """Cache op (reference src/ops/cache.cc): staleness score over cached
    activations drives a RecompileState trigger, as in the MoE example."""
    from flexflow_tpu.core.recompile import RecompileState

    model = ff.FFModel(ff.FFConfig(batch_size=8))
    t = model.create_tensor([8, 16], ff.DataType.DT_FLOAT)
    x = model.dense(t, 16, ff.ActiMode.AC_MODE_RELU, name="gate")
    x = model.cache(x, num_batches=1, name="gate_cache")
    model.softmax(model.dense(x, 4, name="head"))
    model.compile(optimizer=ff.SGDOptimizer(model, lr=0.0),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  metrics=[])

    rng = np.random.RandomState(0)
    a = rng.randn(8, 16).astype(np.float32)
    y = rng.randint(0, 4, (8, 1)).astype(np.int32)
    model.train_one_batch([a], y)
    model.train_one_batch([a], y)           # identical batch: score ~ 0
    low = model.get_cache_score("gate_cache")
    assert low < 0.05, low
    b = rng.randn(8, 16).astype(np.float32) * 3
    model.train_one_batch([b], y)           # shifted batch: score jumps
    high = model.get_cache_score("gate_cache")
    assert high > low

    fired = []
    rs = RecompileState(
        lambda: model.get_cache_score("gate_cache") > max(low, 0.05),
        lambda _rs: fired.append(True), model)
    assert model.recompile_on_condition(rs)
    assert fired == [True]


def test_network_topologies_and_routing():
    from flexflow_tpu.search.network import (
        NetworkedMachineModel,
        ShortestPathRouting,
        big_switch_topology,
        flat_degree_constrained_topology,
        torus_topology,
    )

    # 2-D 4x4 torus: every node has 4 links, diameter 4
    topo = torus_topology([4, 4], link_bandwidth=1e11)
    assert topo.num_nodes == 16
    assert all(topo.degree(i) == 4 for i in range(16))
    routing = ShortestPathRouting(topo)
    path = routing.route(0, 15)
    assert path is not None and path[0] == 0 and path[-1] == 15
    assert len(path) - 1 <= 4

    # wrap-around makes 0 -> 12 one hop in a 4x4 torus (column wrap)
    assert len(routing.route(0, 12)) == 2

    # big switch: always 2 hops via the crossbar
    bs = big_switch_topology(8, 1e10)
    r2 = ShortestPathRouting(bs)
    assert len(r2.route(0, 7)) == 3

    # flat degree-constrained: connected, degree bounded
    fd = flat_degree_constrained_topology(16, degree=4, link_bandwidth=1e10)
    r3 = ShortestPathRouting(fd)
    assert all(r3.route(0, i) is not None for i in range(16))

    mm = NetworkedMachineModel(topo, hop_latency_s=1e-6)
    t_near = mm.transfer_time(0, 1, 1e9)
    t_far = mm.transfer_time(0, 10, 1e9)
    assert 0 < t_near <= t_far
    assert mm.transfer_time(3, 3, 1e9) == 0.0
    ar = mm.allreduce_time(list(range(4)), 1e9)
    assert ar > 0


def test_device_fence_and_slope_time():
    """The measurement primitives behind measure_node (PARITY r4
    protocol): device_fence reads back every leaf; slope_time recovers a
    per-iteration cost with fixed per-call overhead cancelled."""
    import time

    import jax.numpy as jnp

    from flexflow_tpu.utils.profiling import device_fence, slope_time

    out = {"a": jnp.arange(4.0), "b": (jnp.ones((2, 2)),)}
    assert device_fence(out) is out

    per_iter = 2e-3
    def run(trips):
        time.sleep(5e-3 + per_iter * trips)   # fixed cost + linear part
    t = slope_time(run, t1=1, t2=5, reps=2)
    # sleep jitter only ever ADDS time; bound loosely for loaded CI hosts
    assert 0 < t < 3 * per_iter               # fixed 5 ms cancelled

    from flexflow_tpu.utils.profiling import adaptive_slope_time
    t = adaptive_slope_time(run, reps=1)
    assert 0 < t < 3 * per_iter
    # a zero-cost workload must report "unresolvable" (0.0), not noise
    assert adaptive_slope_time(lambda trips: None, cap=8, reps=1,
                               min_resolve_s=10.0) == 0.0


def test_measure_node_slope_protocol_cpu():
    """measure_node must time via the fori_loop slope program (not
    per-call dispatch), produce a positive cached time for a real op,
    and fall back to the analytic roofline on un-runnable nodes."""
    from flexflow_tpu.search.cost_model import CostModel
    from flexflow_tpu.search.machine_model import MachineModel
    from flexflow_tpu.search.pcg import PCG

    model = _small_model()
    model.compile()
    pcg = PCG.from_model(model)
    axes = {"data": 2, "model": 4}
    cm = CostModel(MachineModel.from_name("v5e", 8), axes, training=False)
    node = next(n for n in pcg.nodes if n.weight_shapes)
    st = node.candidates(axes)[0]
    t = cm.measure_node(node, st)
    assert t > 0.0
    assert cm._profile_cache            # cached under (op, shapes, sharding)
    # cache hit: identical value, no re-measure
    assert cm.measure_node(node, st) == t


def test_profiler_trace(tmp_path):
    from flexflow_tpu.utils.profiling import profiler_trace

    model = _small_model()
    model.compile()
    x = np.random.RandomState(0).randn(16, 32).astype(np.float32)
    logdir = str(tmp_path / "trace")
    with profiler_trace(logdir):
        model.predict(x)
    assert os.path.isdir(logdir) and os.listdir(logdir)


def test_substitutions_to_dot_tool(tmp_path):
    """tools/substitutions_to_dot renders the rule set (reference
    tools/substitutions_to_dot visualizer)."""
    import runpy
    import sys

    out = tmp_path / "rules.dot"
    argv = sys.argv
    sys.argv = ["substitutions_to_dot.py", "-o", str(out)]
    try:
        with pytest.raises(SystemExit) as e:
            runpy.run_path(os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "tools", "substitutions_to_dot.py"), run_name="__main__")
        assert e.value.code == 0
    finally:
        sys.argv = argv
    text = out.read_text()
    assert "digraph substitutions" in text
    assert "fuse_linear_relu" in text


# ---------------------------------------------------------------------------
# documents and sources name only files that exist
# ---------------------------------------------------------------------------

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PY = re.compile(r"[\w./*-]+\.py\b")
_ROOTS = ("tools/", "benchmark/", "tests/", "flexflow_tpu/")
_HISTORY = ("deleted", "removed", "gone", "went")   # a line may tell it


@functools.lru_cache(maxsize=None)
def _tree():
    """Relative paths of the checkout's files (hidden and generated
    directories left out, the two tracked hidden ones kept)."""
    out = []
    for top in sorted(os.listdir(_REPO)):
        if top == "chiprun_out" or (top.startswith(".")
                                    and top not in (".github", ".claude")):
            continue
        if os.path.isfile(os.path.join(_REPO, top)):
            out.append(top)
            continue
        for d, dirs, files in os.walk(os.path.join(_REPO, top)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            out += [os.path.relpath(os.path.join(d, f), _REPO)
                    for f in files]
    return tuple(out)


def _document_names(line):
    """What a line of a document names of this repo's Python files: words
    under one of ``_ROOTS``, and bare file names."""
    return [w for w in _PY.findall(line)
            if w.startswith(_ROOTS) or "/" not in w]


def _source_names(line):
    """What a line of a source file names of the tools and of the
    top-level benchmark scripts."""
    return [w for w in _PY.findall(line)
            if re.fullmatch(r"tools/[\w*-]+\.py|bench\w*\.py", w)]


_WHERE = {
    "README.md": (["README.md"], _document_names),
    "PAPERS.md": (["PAPERS.md"], _document_names),
    "tpu-ci.yml": ([".github/workflows/tpu-ci.yml"], _document_names),
    "SKILL.md": ([".claude/skills/verify/SKILL.md"], _document_names),
    "flexflow_tpu": (["flexflow_tpu/**/*.py"], _source_names),
    "tools-tests-inference-top": (["tools/*.py", "tests/*.py",
                                   "inference/**/*.py", "*.py"],
                                  _source_names),
}


@pytest.mark.parametrize("where", list(_WHERE))
def test_names_only_files_that_exist(where):
    """A command copied from a document, or a tool a comment sends its
    reader to, is there: every Python file the README, PAPERS.md, the CI
    workflow and the verify notes name exists, and every ``tools/`` script
    and top-level ``bench*.py`` the sources name. A bare file name may lie
    anywhere in the tree; a line that says a file is gone may name it."""
    import fnmatch
    import glob

    patterns, names = _WHERE[where]
    tree = _tree()
    bare = {os.path.basename(p) for p in tree}
    files = [p for pat in patterns
             for p in glob.glob(os.path.join(_REPO, pat), recursive=True)]
    assert files, patterns
    stale = []
    for path in files:
        with open(path) as f:
            for n, line in enumerate(f, 1):
                if any(word in line for word in _HISTORY):
                    continue
                stale += [f"{os.path.relpath(path, _REPO)}:{n}: {w}"
                          for w in names(line)
                          if not fnmatch.filter(tree if "/" in w else bare, w)]
    assert not stale, "\n".join(stale)
