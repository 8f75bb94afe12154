"""Parallelism tests on the 8-device CPU mesh.

The key pattern is the reference's own distributed-correctness test
(`TestCompareParameterAveragingSparkVsSingleMachine.java:44`): multi-device
training must match single-device training at the parameter level.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import (Adam, ArrayDataSetIterator, DataSet,
                                DenseLayer, InputType, MultiLayerNetwork,
                                NeuralNetConfiguration, OutputLayer, Sgd)
from deeplearning4j_tpu.parallel import (MeshAxes, ParallelTrainer,
                                         ParallelWrapper, ShardingStrategy,
                                         TrainingMode, blockwise_attention,
                                         local_attention_reference, make_mesh,
                                         param_specs, ring_attention_sharded,
                                         PipelinedDenseStack,
                                         ShardedCheckpoint, save_sharded,
                                         restore_sharded, global_mesh)

from conftest import make_classification

# ROADMAP guardrail (ISSUE 13): the mesh/trainer suites are concurrency-
# heavy (prefetch threads, checkpoint writers) — run every test under the
# graftlint runtime sanitizer's thread-leak watchdog + lock-order shims.
pytestmark = pytest.mark.sanitize()


def _model(seed=7, updater=None):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(updater or Sgd(0.1))
            .list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=4, loss="mcxent"))
            .set_input_type(InputType.feed_forward(8))
            .build())
    return MultiLayerNetwork(conf).init()


def _data(n=64, seed=0):
    r = np.random.default_rng(seed)
    x = r.normal(size=(n, 8)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[r.integers(0, 4, n)]
    return x, y


def test_mesh_construction():
    m = make_mesh({"data": 4, "model": 2})
    assert m.shape["data"] == 4 and m.shape["model"] == 2
    m2 = make_mesh({"data": -1})
    assert m2.shape["data"] == 8
    with pytest.raises(ValueError):
        make_mesh({"data": 3})
    g = global_mesh(model_parallel=2)
    assert g.shape["model"] == 2 and g.shape["data"] == 4


def test_sync_dp_matches_single_device():
    """8-way data-parallel SGD must equal single-device SGD on the same global
    batch (gradient allreduce == full-batch gradient)."""
    x, y = _data(64)
    single = _model(seed=3)
    multi = _model(seed=3)
    ds = DataSet(x, y)
    trainer = ParallelTrainer(multi, mesh=make_mesh({"data": 8}),
                              mode=TrainingMode.SYNC)
    for _ in range(5):
        single.fit(ds)
    for _ in range(5):
        trainer.fit(ds)
    np.testing.assert_allclose(multi.params_flat(), single.params_flat(),
                               rtol=2e-5, atol=1e-6)


def test_sync_tp_matches_single_device():
    """Tensor-parallel sharded params: same math, different layout."""
    x, y = _data(64)
    single = _model(seed=5, updater=Adam(1e-2))
    multi = _model(seed=5, updater=Adam(1e-2))
    ds = DataSet(x, y)
    trainer = ParallelTrainer(multi, mesh=make_mesh({"data": 2, "model": 4}),
                              mode=TrainingMode.SYNC,
                              strategy=ShardingStrategy.TENSOR_PARALLEL)
    for _ in range(5):
        single.fit(ds)
        trainer.fit(ds)
    np.testing.assert_allclose(multi.params_flat(), single.params_flat(),
                               rtol=2e-4, atol=1e-5)


def test_sync_fsdp_matches_single_device():
    x, y = _data(64)
    single = _model(seed=11)
    multi = _model(seed=11)
    ds = DataSet(x, y)
    trainer = ParallelTrainer(multi, mesh=make_mesh({"data": 8}),
                              mode=TrainingMode.SYNC,
                              strategy=ShardingStrategy.FSDP)
    for _ in range(4):
        single.fit(ds)
        trainer.fit(ds)
    np.testing.assert_allclose(multi.params_flat(), single.params_flat(),
                               rtol=2e-5, atol=1e-6)


def test_averaging_mode_parameter_averaging():
    """Local-SGD averaging every N (ParallelWrapper averagingFrequency
    parity): replicas diverge on different shards, then average."""
    x, y = _data(64, seed=2)
    model = _model(seed=13)
    before = model.params_flat().copy()
    trainer = ParallelWrapper(model,
                              mesh=make_mesh({"data": 4},
                                             devices=jax.devices()[:4]),
                              mode=TrainingMode.AVERAGING,
                              averaging_frequency=2, average_updaters=True)
    it = ArrayDataSetIterator(x, y, batch_size=32)
    trainer.fit(it, epochs=4)
    after = model.params_flat()
    assert not np.allclose(after, before)
    assert np.isfinite(trainer.score())
    # all replicas equal after sync_back (averaged)
    assert model.iteration_count == trainer.iteration_count


def test_averaging_single_device_equals_serial():
    """With 1 device and avg freq 1, averaging mode == serial training."""
    x, y = _data(32, seed=4)
    ds = DataSet(x, y)
    serial = _model(seed=17)
    avg = _model(seed=17)
    mesh1 = make_mesh({"data": 1}, devices=jax.devices()[:1])
    trainer = ParallelTrainer(avg, mesh=mesh1, mode=TrainingMode.AVERAGING,
                              averaging_frequency=1)
    for _ in range(3):
        serial.fit(ds)
        trainer.fit(ds)
    trainer._sync_back()
    np.testing.assert_allclose(avg.params_flat(), serial.params_flat(),
                               rtol=1e-5, atol=1e-7)


def test_parallel_trainer_learns(classification_data):
    xs, ys = classification_data
    xs = xs.astype(np.float32)[:192]
    ys = ys.astype(np.float32)[:192]
    conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-2))
            .list()
            .layer(DenseLayer(n_out=32, activation="relu"))
            .layer(OutputLayer(n_out=3, loss="mcxent"))
            .set_input_type(InputType.feed_forward(10))
            .build())
    model = MultiLayerNetwork(conf).init()
    trainer = ParallelTrainer(model, mesh=make_mesh({"data": 8}))
    trainer.fit(ArrayDataSetIterator(xs, ys, batch_size=64), epochs=20)
    ev = model.evaluate(ArrayDataSetIterator(xs, ys, batch_size=64))
    assert ev.accuracy() > 0.9


# --------------------------- ring attention --------------------------------

def test_blockwise_attention_matches_reference():
    r = np.random.default_rng(0)
    q = jnp.asarray(r.normal(size=(2, 16, 8)))
    k = jnp.asarray(r.normal(size=(2, 16, 8)))
    v = jnp.asarray(r.normal(size=(2, 16, 8)))
    ref = local_attention_reference(q, k, v)
    blk = blockwise_attention(q, k, v, block_size=5)
    np.testing.assert_allclose(np.asarray(blk), np.asarray(ref), rtol=1e-5,
                               atol=1e-6)


def test_ring_attention_matches_reference():
    r = np.random.default_rng(1)
    B, T, H = 2, 32, 8   # T sharded over 8 devices -> 4 per device
    q = jnp.asarray(r.normal(size=(B, T, H)))
    k = jnp.asarray(r.normal(size=(B, T, H)))
    v = jnp.asarray(r.normal(size=(B, T, H)))
    mesh = make_mesh({"seq": 8})
    out = ring_attention_sharded(q, k, v, mesh, axis="seq")
    ref = local_attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-6)


def test_ring_attention_differentiable():
    r = np.random.default_rng(2)
    B, T, H = 1, 16, 4
    mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
    q = jnp.asarray(r.normal(size=(B, T, H)))
    k = jnp.asarray(r.normal(size=(B, T, H)))
    v = jnp.asarray(r.normal(size=(B, T, H)))

    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    import functools
    from deeplearning4j_tpu.parallel import ring_self_attention

    spec = P(None, "seq", None)
    fn = shard_map(functools.partial(ring_self_attention, axis_name="seq"),
                   mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                   check_vma=False)

    def loss_ring(q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(local_attention_reference(q, k, v) ** 2)

    g_ring = jax.grad(loss_ring)(q, k, v)
    g_ref = jax.grad(loss_ref)(q, k, v)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-6)


# --------------------------- pipeline --------------------------------------

def test_pipeline_matches_sequential():
    mesh = make_mesh({"pipe": 4}, devices=jax.devices()[:4])
    stack = PipelinedDenseStack(features=16, n_stages=4, mesh=mesh)
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(8, 16)))
    ref = stack.reference_forward(stack.params, x)
    out = stack.pipelined_forward(stack.params, x, n_microbatches=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-6)


def test_pipeline_more_microbatches_than_stages():
    mesh = make_mesh({"pipe": 2}, devices=jax.devices()[:2])
    stack = PipelinedDenseStack(features=8, n_stages=2, mesh=mesh, seed=3)
    r = np.random.default_rng(1)
    x = jnp.asarray(r.normal(size=(12, 8)))
    ref = stack.reference_forward(stack.params, x)
    out = stack.pipelined_forward(stack.params, x, n_microbatches=6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-6)


# --------------------------- sharded checkpoint ----------------------------

def test_sharded_checkpoint_roundtrip(tmp_path):
    model = _model(seed=23)
    x, y = _data(32)
    model.fit(DataSet(x, y))
    out_before = np.asarray(model.output(x[:4]))
    save_sharded(str(tmp_path / "ckpt"), model)

    model2 = _model(seed=99)
    restore_sharded(str(tmp_path / "ckpt"), model2)
    np.testing.assert_allclose(np.asarray(model2.output(x[:4])), out_before,
                               rtol=1e-6)
    assert model2.iteration_count == model.iteration_count
    # resume equivalence
    model.fit(DataSet(x, y))
    model2.fit(DataSet(x, y))
    np.testing.assert_allclose(model2.params_flat(), model.params_flat(),
                               rtol=1e-5)


def test_sharded_checkpoint_manager(tmp_path):
    model = _model(seed=29)
    mgr = ShardedCheckpoint(str(tmp_path / "ckpts"), keep=2)
    x, y = _data(16)
    for step in range(3):
        model.fit(DataSet(x, y))
        mgr.save(model, step)
    assert mgr.latest_step() == 2
    model2 = _model(seed=1)
    assert mgr.restore_latest(model2) == 2
    np.testing.assert_allclose(model2.params_flat(), model.params_flat(),
                               rtol=1e-6)


def _cnn_model(seed=21):
    from deeplearning4j_tpu.nn.layers import (BatchNormalization,
                                              ConvolutionLayer,
                                              ConvolutionMode, PoolingType,
                                              SubsamplingLayer)
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.05))
            .list()
            .layer(ConvolutionLayer(n_out=8, kernel_size=(3, 3),
                                    stride=(1, 1), activation="relu",
                                    convolution_mode=ConvolutionMode.SAME))
            .layer(BatchNormalization())
            .layer(SubsamplingLayer(pooling_type=PoolingType.MAX,
                                    kernel_size=(2, 2), stride=(2, 2)))
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=4, loss="mcxent"))
            .set_input_type(InputType.convolutional(8, 8, 3))
            .build())
    return MultiLayerNetwork(conf).init()


def test_sync_tp_conv_model_matches_single_device():
    """Tensor-parallel CNN (conv kernels sharded on the output-channel
    axis, BN params sharded to match): same math as single-device."""
    r = np.random.default_rng(2)
    x = r.normal(size=(32, 8, 8, 3)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[r.integers(0, 4, 32)]
    ds = DataSet(x, y)
    single = _cnn_model(seed=21)
    multi = _cnn_model(seed=21)
    trainer = ParallelTrainer(multi, mesh=make_mesh({"data": 2, "model": 4}),
                              mode=TrainingMode.SYNC,
                              strategy=ShardingStrategy.TENSOR_PARALLEL)
    for _ in range(3):
        single.fit(ds)
        trainer.fit(ds)
    np.testing.assert_allclose(multi.params_flat(), single.params_flat(),
                               rtol=5e-4, atol=1e-5)


def _lstm_model(seed=23):
    from deeplearning4j_tpu.nn.layers import GravesLSTM, RnnOutputLayer
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.05))
            .list()
            .layer(GravesLSTM(n_out=8, activation="tanh"))
            .layer(RnnOutputLayer(n_out=5, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(5, 12))
            .build())
    return MultiLayerNetwork(conf).init()


def test_sync_tp_lstm_model_matches_single_device():
    """Tensor-parallel LSTM (gate-block weights sharded on the output
    axis): same math as single-device."""
    r = np.random.default_rng(3)
    idx = r.integers(0, 5, (16, 12))
    x = np.eye(5, dtype=np.float32)[idx]
    y = np.eye(5, dtype=np.float32)[np.roll(idx, -1, 1)]
    ds = DataSet(x, y)
    single = _lstm_model(seed=23)
    multi = _lstm_model(seed=23)
    trainer = ParallelTrainer(multi, mesh=make_mesh({"data": 2, "model": 4}),
                              mode=TrainingMode.SYNC,
                              strategy=ShardingStrategy.TENSOR_PARALLEL)
    for _ in range(3):
        single.fit(ds)
        trainer.fit(ds)
    np.testing.assert_allclose(multi.params_flat(), single.params_flat(),
                               rtol=5e-4, atol=1e-5)


def test_tp_specs_cover_conv_and_lstm_params():
    """The sharding rules must actually shard conv/LSTM tensors (not fall
    back to replicated) when the axis divides."""
    mesh = make_mesh({"data": 2, "model": 4})
    cnn = _cnn_model()
    specs = param_specs(cnn.params, ShardingStrategy.TENSOR_PARALLEL, mesh)
    flat = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    sharded = [s for s in flat if any(a is not None for a in s)]
    assert len(sharded) >= 4, f"conv model barely sharded: {flat}"
    lstm = _lstm_model()
    specs = param_specs(lstm.params, ShardingStrategy.TENSOR_PARALLEL, mesh)
    flat = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    sharded = [s for s in flat if any(a is not None for a in s)]
    assert len(sharded) >= 2, f"lstm model barely sharded: {flat}"


def test_causal_ring_attention_matches_reference():
    """Causal ring attention (global-position masks across devices) ==
    causal reference — the long-context decoder-training path."""
    mesh = make_mesh({"seq": 8})
    r = np.random.default_rng(7)
    B, T, H = 2, 8 * 6, 16
    q = jnp.asarray(r.normal(size=(B, T, H)).astype(np.float32))
    k = jnp.asarray(r.normal(size=(B, T, H)).astype(np.float32))
    v = jnp.asarray(r.normal(size=(B, T, H)).astype(np.float32))
    out = ring_attention_sharded(q, k, v, mesh, axis="seq", causal=True)
    ref = local_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_causal_blockwise_attention_matches_reference():
    r = np.random.default_rng(8)
    B, T, H = 2, 70, 16   # ragged vs block size
    q = jnp.asarray(r.normal(size=(B, T, H)).astype(np.float32))
    k = jnp.asarray(r.normal(size=(B, T, H)).astype(np.float32))
    v = jnp.asarray(r.normal(size=(B, T, H)).astype(np.float32))
    out = blockwise_attention(q, k, v, block_size=16, causal=True)
    ref = local_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_causal_ring_attention_differentiable():
    mesh = make_mesh({"seq": 8})
    r = np.random.default_rng(9)
    B, T, H = 1, 8 * 4, 8
    q = jnp.asarray(r.normal(size=(B, T, H)).astype(np.float32))

    def loss_ring(q_):
        return jnp.sum(ring_attention_sharded(q_, q_, q_, mesh, axis="seq",
                                              causal=True) ** 2)

    def loss_ref(q_):
        return jnp.sum(local_attention_reference(q_, q_, q_,
                                                 causal=True) ** 2)

    g1 = jax.grad(loss_ring)(q)
    g2 = jax.grad(loss_ref)(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=5e-4,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# ComputationGraph in the parallel stack (SparkComputationGraph.java +
# ParallelWrapper.java:48 take any Model — graphs must parallelize too)
# ---------------------------------------------------------------------------

def _graph_resnet(seed=13):
    """Tiny ResNet graph (DAG with ElementWiseVertex residuals), f32 for
    exact multi==single comparison."""
    from deeplearning4j_tpu.models.zoo import resnet50
    return resnet50(n_classes=4, image=16, seed=seed, blocks=(1, 1),
                    width=8, compute_dtype=None, updater=Sgd(0.05)).init()


def _graph_data(n=32, image=16, classes=4, seed=0):
    r = np.random.default_rng(seed)
    x = r.normal(size=(n, image, image, 3)).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[r.integers(0, classes, n)]
    return x, y


def _graph_params_flat(g):
    leaves = [np.asarray(l).ravel() for l in jax.tree_util.tree_leaves(
        {k: g.params[k] for k in sorted(g.params)})]
    return np.concatenate(leaves) if leaves else np.zeros(0)


def test_graph_sync_dp_matches_single_device():
    x, y = _graph_data()
    single = _graph_resnet(seed=13)
    multi = _graph_resnet(seed=13)
    ds = DataSet(x, y)
    trainer = ParallelTrainer(multi, mesh=make_mesh({"data": 8}),
                              mode=TrainingMode.SYNC)
    for _ in range(3):
        single.fit(ds)
    for _ in range(3):
        trainer.fit(ds)
    np.testing.assert_allclose(_graph_params_flat(multi),
                               _graph_params_flat(single),
                               rtol=5e-5, atol=1e-5)


def test_graph_sync_tp_matches_single_device():
    x, y = _graph_data()
    single = _graph_resnet(seed=17)
    multi = _graph_resnet(seed=17)
    ds = DataSet(x, y)
    trainer = ParallelTrainer(multi, mesh=make_mesh({"data": 2, "model": 4}),
                              mode=TrainingMode.SYNC,
                              strategy=ShardingStrategy.TENSOR_PARALLEL)
    for _ in range(3):
        single.fit(ds)
        trainer.fit(ds)
    np.testing.assert_allclose(_graph_params_flat(multi),
                               _graph_params_flat(single),
                               rtol=5e-4, atol=2e-5)


def test_graph_averaging_mode():
    x, y = _graph_data()
    single = _graph_resnet(seed=19)
    multi = _graph_resnet(seed=19)
    ds = DataSet(x, y)
    trainer = ParallelTrainer(multi, mesh=make_mesh({"data": 4},
                                                    devices=jax.devices()[:4]),
                              mode=TrainingMode.AVERAGING,
                              averaging_frequency=2)
    for _ in range(4):
        single.fit(ds)
        trainer.fit(ds)
    # averaging mode is local SGD — not bit-identical to full-batch, but it
    # must train (score finite + decreasing) and keep replicas averaged
    assert np.isfinite(trainer.score())


def test_graph_multidataset_parallel():
    """Multi-input graph (MergeVertex) trained through the trainer on
    MultiDataSet batches — dp == single-device."""
    from deeplearning4j_tpu.datasets.iterators import MultiDataSet
    from deeplearning4j_tpu.nn.conf.graph import MergeVertex
    from deeplearning4j_tpu.nn.conf.input_type import InputType as IT
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    def build():
        b = (NeuralNetConfiguration.builder().seed(23).updater(Sgd(0.1))
             .graph_builder())
        b.add_inputs("a", "b")
        b.add_layer("ha", DenseLayer(n_out=8, activation="tanh"), "a")
        b.add_layer("hb", DenseLayer(n_out=8, activation="tanh"), "b")
        b.add_vertex("m", MergeVertex(), "ha", "hb")
        b.add_layer("out", OutputLayer(n_out=3, loss="mcxent"), "m")
        b.set_outputs("out")
        b.set_input_types(IT.feed_forward(5), IT.feed_forward(7))
        return ComputationGraph(b.build()).init()

    r = np.random.default_rng(4)
    xa = r.normal(size=(32, 5)).astype(np.float32)
    xb = r.normal(size=(32, 7)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[r.integers(0, 3, 32)]
    mds = MultiDataSet(features=[xa, xb], labels=[y])
    single, multi = build(), build()
    trainer = ParallelTrainer(multi, mesh=make_mesh({"data": 8}),
                              mode=TrainingMode.SYNC)
    for _ in range(3):
        single.fit(mds)
        trainer.fit(mds)
    np.testing.assert_allclose(_graph_params_flat(multi),
                               _graph_params_flat(single),
                               rtol=2e-5, atol=1e-6)


def test_sync_dp_masked_data_matches_single_device():
    """Masked batches (padded RNN sequences) must thread through the
    trainer identically to single-device fit (round-3 review regression:
    masks were silently dropped)."""
    from deeplearning4j_tpu.nn.layers import GravesLSTM, RnnOutputLayer
    from deeplearning4j_tpu.nn.conf.input_type import InputType as IT

    def build():
        conf = (NeuralNetConfiguration.builder().seed(31).updater(Sgd(0.1))
                .list()
                .layer(GravesLSTM(n_out=8, activation="tanh"))
                .layer(RnnOutputLayer(n_out=3, loss="mcxent"))
                .set_input_type(IT.recurrent(5, 6))
                .build())
        return MultiLayerNetwork(conf).init()

    r = np.random.default_rng(9)
    x = r.normal(size=(16, 6, 5)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[r.integers(0, 3, (16, 6))]
    fmask = np.ones((16, 6), np.float32)
    fmask[:, 4:] = 0.0           # variable-length sequences
    ds = DataSet(x, y, features_mask=fmask, labels_mask=fmask)
    single, multi = build(), build()
    trainer = ParallelTrainer(multi, mesh=make_mesh({"data": 8}),
                              mode=TrainingMode.SYNC)
    for _ in range(3):
        single.fit(ds)
    for _ in range(3):
        trainer.fit(ds)
    np.testing.assert_allclose(multi.params_flat(), single.params_flat(),
                               rtol=2e-5, atol=1e-6)


def test_googlenet_merge_dag_sync_dp():
    """Inception-style multi-branch DAG (MergeVertex) through the trainer:
    dp == single-device — breadth beyond the ElementWiseVertex ResNet."""
    from deeplearning4j_tpu.models.zoo import googlenet

    def build():
        g = googlenet(n_classes=3, image=32, seed=29, updater=Sgd(0.05))
        return g.init()

    r = np.random.default_rng(2)
    x = r.normal(size=(16, 32, 32, 3)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[r.integers(0, 3, 16)]
    ds = DataSet(x, y)
    single, multi = build(), build()
    trainer = ParallelTrainer(multi, mesh=make_mesh({"data": 8}),
                              mode=TrainingMode.SYNC)
    for _ in range(2):
        single.fit(ds)
        trainer.fit(ds)
    np.testing.assert_allclose(_graph_params_flat(multi),
                               _graph_params_flat(single),
                               rtol=5e-5, atol=1e-5)
