"""Measured searched-vs-DP A/B (VERDICT r4 item 1).

The Unity search's advantage numbers were analytic only — the cost model
grading its own homework. These tests wall-clock real train steps on the
virtual 8-device mesh under (a) the searched strategy, (b) forced pure
DP, (c) the sequence-only search, through the SAME runtime
(search/measure.py), so at least one searched win is measured, not
simulated — the reference bar is Unity's measured speedup (OSDI'22,
README.md:68).

Wall-clock thresholds are deliberately loose (the virtual CPU mesh is a
structural check, not TPU physics); the variants' timed blocks take turns
and each variant takes the median of its own.
"""

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.search import (
    data_parallel_model_strategy, searched_vs_dp_wallclock, format_ab)


def _fat_mlp():
    """Small batch + fat dense layers: DP allreduces ~MB-scale weight
    grads every step while the hybrid shards them — the regime where
    Unity's hybrid parallelism honestly beats DP (OSDI'22 eval)."""
    cfg = ff.FFConfig(batch_size=16, data_parallelism_degree=4,
                      tensor_parallelism_degree=2, tpu_chip="v5e", seed=3)
    m = ff.FFModel(cfg)
    t = m.create_tensor([16, 256], ff.DataType.DT_FLOAT)
    h = m.dense(t, 2048, ff.ActiMode.AC_MODE_RELU)
    h = m.dense(h, 2048, ff.ActiMode.AC_MODE_RELU)
    h = m.dense(h, 256, ff.ActiMode.AC_MODE_RELU)
    m.softmax(m.dense(h, 10))
    return m


def _inception():
    cfg = ff.FFConfig(batch_size=16, data_parallelism_degree=8,
                      tpu_chip="v5e", seed=7)
    m = ff.FFModel(cfg)
    t = m.create_tensor([16, 32, 8, 8], ff.DataType.DT_FLOAT)
    x = m.conv2d(t, 32, 3, 3, 1, 1, 1, 1, ff.ActiMode.AC_MODE_RELU)
    b1 = m.conv2d(x, 16, 1, 1, 1, 1, 0, 0, ff.ActiMode.AC_MODE_RELU)
    b2 = m.conv2d(m.conv2d(x, 24, 1, 1, 1, 1, 0, 0), 32, 3, 3, 1, 1,
                  1, 1, ff.ActiMode.AC_MODE_RELU)
    b3 = m.conv2d(m.conv2d(x, 8, 1, 1, 1, 1, 0, 0), 16, 5, 5, 1, 1,
                  2, 2, ff.ActiMode.AC_MODE_RELU)
    b4 = m.conv2d(x, 16, 1, 1, 1, 1, 0, 0, ff.ActiMode.AC_MODE_RELU)
    m.softmax(m.dense(m.flat(m.concat([b1, b2, b3, b4], axis=1)), 10))
    return m


def test_searched_beats_dp_wallclock_fat_mlp():
    """The Unity pillar's measured win: the searched hybrid strategy is
    faster than forced pure DP by WALL CLOCK, and the analytic advantage
    points the same way."""
    rng = np.random.RandomState(0)
    xs = [rng.randn(16, 256).astype(np.float32)]
    ys = rng.randint(0, 10, size=(16, 1)).astype(np.int32)
    res = searched_vs_dp_wallclock(_fat_mlp, xs, ys, chip="v5e",
                                   num_devices=8, steps=4, reps=2,
                                   variants=("searched", "dp"))
    print(format_ab("fat-mlp", res))
    assert res["searched"]["analytic"] < res["dp"]["analytic"]
    assert res["searched"]["wallclock"] < res["dp"]["wallclock"], res


def test_branchy_searched_not_worse_than_dp_wallclock():
    """The VERDICT gate on the branchy PCG: searched <= DP by wall
    clock. Under executable costing the search keeps DP for this
    compute-dense fork-join (the SPMD switch lowering runs every branch
    everywhere — PARITY r5), so the searched strategy must never run
    SLOWER than forced DP; tolerance covers CI jitter only."""
    rng = np.random.RandomState(1)
    xs = [rng.randn(16, 32, 8, 8).astype(np.float32)]
    ys = rng.randint(0, 10, size=(16, 1)).astype(np.int32)
    # the two strategies are the same program here, so the bound has to
    # hold on 9 ms steps beside the suite's other workers: the variants
    # take turns step by step, 32 steps each, and the medians are compared
    res = searched_vs_dp_wallclock(_inception, xs, ys, chip="v5e",
                                   num_devices=8, steps=1, reps=32,
                                   variants=("searched", "dp", "seq_only"))
    print(format_ab("inception", res))
    assert res["searched"]["wallclock"] <= 1.25 * res["dp"]["wallclock"], res
    assert res["searched"]["analytic"] <= res["dp"]["analytic"] * 1.0001


def test_branch_executor_numerics_match_plain():
    """The branch-region executor (core/branch_exec.py over
    parallel.ops.branch_data_parallel_apply) is numerically faithful:
    with an explicitly CONSTRUCTED branch strategy (the search declines
    one under honest costing) train losses match plain execution."""
    import dataclasses

    from flexflow_tpu.search import (CostModel, MachineModel, PCG,
                                     UnitySearch)
    from flexflow_tpu.search.graph_search import expand_strategy

    def searched_branch_strategy(m):
        pcg = PCG.from_model(m)
        axes = {"data": 4, "model": 1}
        cm = CostModel(MachineModel.from_name("v5e", 4), axes,
                       training=True, branch_concurrency=True)
        s = UnitySearch(pcg, cm, axes,
                        enable_substitutions=False).optimize_graph(pcg)
        assert any(st.branch for st in s.ops.values())
        return expand_strategy(pcg, s)

    rng = np.random.RandomState(2)
    xs = rng.randn(16, 32, 8, 8).astype(np.float32)
    ys = rng.randint(0, 10, size=(16, 1)).astype(np.int32)

    m = _inception()
    m.strategy = searched_branch_strategy(m)
    m.compile(optimizer=ff.SGDOptimizer(m, 0.01),
              loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    assert m._branch_plan is not None and m._branch_plan.regions
    losses = [m.train_one_batch([xs], ys) for _ in range(3)]

    m2 = _inception()
    m2.compile(optimizer=ff.SGDOptimizer(m2, 0.01),
               loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    assert m2._branch_plan is None
    losses2 = [m2.train_one_batch([xs], ys) for _ in range(3)]
    assert all(abs(a - b) < 1e-4 for a, b in zip(losses, losses2)), (
        losses, losses2)


def test_branch_plan_rejects_escaping_intermediate():
    """A branch intermediate that ALSO feeds a layer outside the region
    (auxiliary head) must disqualify the region — executing it would
    drop that tensor from the value map (r5 review finding)."""
    import dataclasses

    from flexflow_tpu.core.branch_exec import build_branch_plan
    from flexflow_tpu.search.strategy import OpStrategy, replicated

    cfg = ff.FFConfig(batch_size=16, data_parallelism_degree=8, seed=9)
    m = ff.FFModel(cfg)
    t = m.create_tensor([16, 32, 8, 8], ff.DataType.DT_FLOAT)
    x = m.conv2d(t, 32, 3, 3, 1, 1, 1, 1, ff.ActiMode.AC_MODE_RELU)
    b1 = m.conv2d(x, 16, 1, 1, 1, 1, 0, 0, ff.ActiMode.AC_MODE_RELU)
    b2 = m.conv2d(x, 16, 3, 3, 1, 1, 1, 1, ff.ActiMode.AC_MODE_RELU)
    cat = m.concat([b1, b2], axis=1)
    # auxiliary head reads b1 OUTSIDE the fork-join region
    aux = m.dense(m.flat(b1), 4)
    m.softmax(m.add(m.dense(m.flat(cat), 4), aux))

    from flexflow_tpu.search.strategy import Strategy

    def tag(name, bi):
        ly = next(l for l in m.layers if l.name == name)
        nd = len(ly.outputs[0].dims)
        return OpStrategy(input_specs=(replicated(nd),),
                          output_spec=replicated(nd),
                          weight_specs={w.name: replicated(len(w.shape))
                                        for w in ly.weights},
                          branch=(bi, 2))

    m.strategy = Strategy(ops={"conv2d_1": tag("conv2d_1", 0),
                               "conv2d_2": tag("conv2d_2", 1)})
    m.compile(optimizer=ff.SGDOptimizer(m, 0.01),
              loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    assert m._branch_plan is None   # escaped intermediate -> no region
    # and the model still trains through the sequential path
    rng = np.random.RandomState(3)
    m.train_one_batch([rng.randn(16, 32, 8, 8).astype(np.float32)],
                      rng.randint(0, 4, size=(16, 1)).astype(np.int32))


def test_data_parallel_model_strategy_covers_all_layers():
    m = _fat_mlp()
    dp = data_parallel_model_strategy(m, chip="v5e", num_devices=8)
    assert dp is not None
    weighted = [ly.name for ly in m.layers if ly.weights]
    assert all(n in dp.ops for n in weighted)
    assert all(st.branch is None for st in dp.ops.values())


def _dlrm(tables=4, vocab=50000):
    """DLRM/XDL-style PCG: big embedding tables + bottom/top MLPs
    (reference examples/cpp/DLRM; src/ops/embedding.cc vocab/replica
    sharding). DP must replicate and allreduce every table's grads; the
    searched strategy shards the tables over 'model'."""
    cfg = ff.FFConfig(batch_size=32, data_parallelism_degree=2,
                      tensor_parallelism_degree=4, tpu_chip="v5e", seed=0)
    m = ff.FFModel(cfg)
    dense_in = m.create_tensor([32, 16], ff.DataType.DT_FLOAT)
    parts = [m.dense(m.dense(dense_in, 64, ff.ActiMode.AC_MODE_RELU), 64)]
    for _ in range(tables):
        ids = m.create_tensor([32, 2], ff.DataType.DT_INT32)
        parts.append(m.flat(m.embedding(ids, vocab, 64)))
    x = m.concat(parts, axis=1)
    m.softmax(m.dense(m.dense(x, 64, ff.ActiMode.AC_MODE_RELU), 2))
    return m


def test_dlrm_searched_shards_embeddings_and_beats_dp():
    """VERDICT r4 item 5: on a DLRM-style PCG the searched strategy
    shards the embedding tables over 'model' and beats DP — analytically
    AND by wall clock (the tables' grad allreduce dominates DP)."""
    from flexflow_tpu.search import (CostModel, MachineModel, PCG,
                                     UnitySearch)

    m = _dlrm()
    pcg = PCG.from_model(m)
    axes = {"data": 2, "model": 4}
    cm = CostModel(MachineModel.from_name("v5e", 8), axes, training=True)
    search = UnitySearch(pcg, cm, axes, enable_substitutions=False)
    s = search.optimize_graph(pcg)
    dp = search._dp_baseline(pcg)
    emb = {n: st for n, st in s.ops.items() if n.startswith("embedding")}
    assert emb and all(
        "model" in tuple(st.weight_specs.get("weight", ()))
        for st in emb.values()), {n: st.weight_specs for n, st in emb.items()}
    assert s.cost < dp.cost

    # wall-clock A/B through the runtime
    rng = np.random.RandomState(0)
    xs = [rng.randn(32, 16).astype(np.float32)] + \
        [rng.randint(0, 50000, size=(32, 2)).astype(np.int32)
         for _ in range(4)]
    ys = rng.randint(0, 2, size=(32, 1)).astype(np.int32)
    res = searched_vs_dp_wallclock(_dlrm, xs, ys, chip="v5e",
                                   num_devices=8, steps=2, reps=2,
                                   variants=("searched", "dp"))
    print(format_ab("dlrm", res))
    assert res["searched"]["wallclock"] < res["dp"]["wallclock"], res
