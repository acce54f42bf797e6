"""Multi-device SPMD tests on the virtual 8-device CPU mesh.

What the reference can only test on a real 2-node cluster
(tests/multinode_helpers/mpi_wrapper*.sh) we test here: DP/TP sharded
training/inference must match single-device results bit-for-bit (CPU f32).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import flexflow_tpu as ff
from flexflow_tpu.parallel.collectives import (
    all_gather,
    ppermute_shift,
    psum,
    reduce_scatter,
)


def make_data(n=256, d=32, classes=10, seed=0):
    rng = np.random.RandomState(seed)
    centers = rng.randn(classes, d).astype(np.float32) * 2.0
    y = rng.randint(0, classes, size=n)
    x = centers[y] + rng.randn(n, d).astype(np.float32)
    return x.astype(np.float32), y.reshape(-1, 1).astype(np.int32)


def build_and_train(config, x, y, steps=4):
    model = ff.FFModel(config)
    t = model.create_tensor([config.batch_size, x.shape[1]], ff.DataType.DT_FLOAT)
    h = model.dense(t, 64, ff.ActiMode.AC_MODE_RELU)
    h = model.dense(h, 64, ff.ActiMode.AC_MODE_RELU)
    h = model.dense(h, 10)
    model.softmax(h)
    model.compile(optimizer=ff.SGDOptimizer(model, lr=0.05),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  metrics=[ff.MetricsType.METRICS_ACCURACY])
    losses = []
    bs = config.batch_size
    for i in range(steps):
        lo = (i * bs) % (x.shape[0] - bs + 1)
        losses.append(model.train_one_batch([x[lo:lo + bs]], y[lo:lo + bs]))
    return model, losses


def test_dp_matches_single_device():
    x, y = make_data()
    _, losses_1 = build_and_train(
        ff.FFConfig(batch_size=64, num_devices=1), x, y)
    model_8, losses_8 = build_and_train(
        ff.FFConfig(batch_size=64, data_parallelism_degree=8), x, y)
    assert model_8.mesh.shape["data"] == 8
    np.testing.assert_allclose(losses_1, losses_8, rtol=1e-5, atol=1e-6)


def test_tp_matches_single_device():
    x, y = make_data()
    _, losses_1 = build_and_train(
        ff.FFConfig(batch_size=64, num_devices=1), x, y)
    model_tp, losses_tp = build_and_train(
        ff.FFConfig(batch_size=64, tensor_parallelism_degree=4,
                    data_parallelism_degree=2), x, y)
    assert model_tp.mesh.shape["model"] == 4
    assert model_tp.mesh.shape["data"] == 2
    # TP kernel is sharded on the out dim
    k = model_tp.params["linear"]["kernel"]
    assert k.sharding.spec == P(None, "model")
    np.testing.assert_allclose(losses_1, losses_tp, rtol=1e-5, atol=1e-6)


def test_mesh_shape_override():
    config = ff.FFConfig(batch_size=8, mesh_shape=(2, 4),
                         mesh_axis_names=("data", "model"))
    model = ff.FFModel(config)
    t = model.create_tensor([8, 16], ff.DataType.DT_FLOAT)
    model.dense(t, 8)
    model.compile()
    assert dict(model.mesh.shape) == {"data": 2, "model": 4}


def test_parallel_ops_roundtrip():
    """repartition -> combine -> replicate chain is value-preserving."""
    config = ff.FFConfig(batch_size=8, data_parallelism_degree=8)
    model = ff.FFModel(config)
    t = model.create_tensor([8, 16], ff.DataType.DT_FLOAT)
    p = model.repartition(t, 0, 8)
    c = model.combine(p)
    r = model.replicate(c)
    a = model.allreduce(r)
    model.compile()
    x = np.random.RandomState(0).randn(8, 16).astype(np.float32)
    np.testing.assert_allclose(model.predict(x), x, rtol=1e-6)


def test_collectives_shard_map():
    mesh = jax.make_mesh((8,), ("x",))

    @jax.jit
    def run(v):
        def body(v):
            s = psum(v, "x")
            g = all_gather(v, "x")
            rs = reduce_scatter(g, "x")
            shifted = ppermute_shift(v, "x", 1)
            return s, g, rs, shifted

        # all_gather output is vma-varying under shard_map, so emit it with
        # P("x") (each shard's identical copy concatenated) rather than P().
        return jax.shard_map(body, mesh=mesh, in_specs=P("x"),
                             out_specs=(P(), P("x"), P("x"), P("x")))(v)

    v = jnp.arange(8.0)
    s, g, rs, shifted = run(v)
    # psum: replicated scalar-per-shard -> global shape (1,)
    np.testing.assert_allclose(s, [28.0])
    # all_gather: every shard holds the full arange, concatenated by P("x")
    np.testing.assert_allclose(g, np.tile(np.arange(8.0), 8))
    # reduce_scatter over 8 identical copies of arange(8): shard i gets 8*i
    np.testing.assert_allclose(rs, 8.0 * np.arange(8.0))
    np.testing.assert_allclose(shifted, np.roll(np.arange(8.0), 1))


def test_embedding_tp_sharded():
    config = ff.FFConfig(batch_size=8, tensor_parallelism_degree=8)
    model = ff.FFModel(config)
    t = model.create_tensor([8, 4], ff.DataType.DT_INT32)
    e = model.embedding(t, num_entries=100, out_dim=64)
    model.compile()
    w = model.params["embedding"]["weight"]
    assert w.sharding.spec == P(None, "model")
    ids = np.random.RandomState(0).randint(0, 100, (8, 4)).astype(np.int32)
    got = model.predict([ids])
    np.testing.assert_allclose(got, np.asarray(w)[ids], rtol=1e-6)
