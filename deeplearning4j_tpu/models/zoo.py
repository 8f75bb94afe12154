"""Model zoo — the BASELINE.md configs.

LeNet-MNIST mirrors the reference's canonical MNIST CNN example topology
(Conv 5x5x20 → maxpool → Conv 5x5x50 → maxpool → Dense 500 → softmax 10),
the config DL4J ships in its examples and the first BASELINE config.
"""
from __future__ import annotations

import time

import numpy as np

from ..nn.conf import InputType, NeuralNetConfiguration
from ..nn.layers import (ConvolutionLayer, ConvolutionMode, DenseLayer,
                         OutputLayer, PoolingType, SubsamplingLayer)
from ..nn.multilayer import MultiLayerNetwork
from ..nn.updaters import Adam, Nesterovs

__all__ = ["lenet_mnist", "bench_lenet", "bench_lenet_ragged",
           "bench_lenet_superstep", "mlp_mnist",
           "char_rnn", "bench_char_rnn", "resnet50", "bench_resnet50",
           "vgg16", "vgg19", "alexnet", "googlenet", "sample_characters"]


def lenet_mnist(seed: int = 42, updater=None) -> MultiLayerNetwork:
    conf = (NeuralNetConfiguration.builder()
            .seed(seed)
            .updater(updater or Nesterovs(learning_rate=0.01, momentum=0.9))
            .l2(5e-4)
            .list()
            .layer(ConvolutionLayer(n_out=20, kernel_size=(5, 5),
                                    stride=(1, 1), activation="identity",
                                    convolution_mode=ConvolutionMode.TRUNCATE))
            .layer(SubsamplingLayer(pooling_type=PoolingType.MAX,
                                    kernel_size=(2, 2), stride=(2, 2)))
            .layer(ConvolutionLayer(n_out=50, kernel_size=(5, 5),
                                    stride=(1, 1), activation="identity"))
            .layer(SubsamplingLayer(pooling_type=PoolingType.MAX,
                                    kernel_size=(2, 2), stride=(2, 2)))
            .layer(DenseLayer(n_out=500, activation="relu"))
            .layer(OutputLayer(n_out=10, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.convolutional_flat(28, 28, 1))
            .build())
    return MultiLayerNetwork(conf)


def mlp_mnist(seed: int = 42) -> MultiLayerNetwork:
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Adam(1e-3))
            .list()
            .layer(DenseLayer(n_out=1024, activation="relu"))
            .layer(DenseLayer(n_out=1024, activation="relu"))
            .layer(OutputLayer(n_out=10, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(784))
            .build())
    return MultiLayerNetwork(conf)


def char_rnn(vocab_size: int = 77, lstm_size: int = 200, seq_len: int = 64,
             seed: int = 42, tbptt: int = 50) -> MultiLayerNetwork:
    """GravesLSTM char-RNN (BASELINE config #3) — the reference's
    char-modelling example topology: 2xLSTM + RnnOutputLayer, TBPTT."""
    from ..nn.conf import BackpropType
    from ..nn.layers import GravesLSTM, RnnOutputLayer

    conf = (NeuralNetConfiguration.builder()
            .seed(seed)
            .updater(Adam(2e-3))
            .list()
            .layer(GravesLSTM(n_out=lstm_size, activation="tanh"))
            .layer(GravesLSTM(n_out=lstm_size, activation="tanh"))
            .layer(RnnOutputLayer(n_out=vocab_size, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(vocab_size, seq_len))
            .backprop_type(BackpropType.TRUNCATED_BPTT)
            .t_bptt_forward_length(tbptt)
            .t_bptt_backward_length(tbptt)
            .build())
    return MultiLayerNetwork(conf)


def bench_char_rnn(batch: int = 64, seq_len: int = 128, steps: int = 240,
                   warmup: int = 3, vocab: int = 77):
    """tokens/sec for char-RNN training (BASELINE config #3): one
    `fit_scan_arrays` window of `steps` batches, timed from dispatch to a
    host read of the score, so the fixed per-call dispatch+sync cost is
    spread over the window."""
    from ..datasets.iterators import DataSet

    model = char_rnn(vocab_size=vocab, seq_len=seq_len, tbptt=64).init()
    r = np.random.default_rng(0)
    idx = r.integers(0, vocab, (batch, seq_len))
    x = np.eye(vocab, dtype=np.float32)[idx]
    y = np.eye(vocab, dtype=np.float32)[np.roll(idx, -1, axis=1)]
    import jax
    import jax.numpy as jnp

    # device-resident [T,...] batches: put ONE batch on the device and
    # broadcast it there, so the window measures the steps, not the upload;
    # warmup with the SAME scan length (the epoch fn specializes on T)
    xs = jnp.broadcast_to(jax.device_put(x), (steps,) + x.shape)
    ys = jnp.broadcast_to(jax.device_put(y), (steps,) + y.shape)
    model.fit_scan_arrays(xs, ys)
    float(model.score())  # host read of the last score: waits for the window
    t0 = time.perf_counter()
    model.fit_scan_arrays(xs, ys)
    float(model.score())
    dt = time.perf_counter() - t0
    return batch * seq_len * steps / dt, "charRNN-tokens"


def resnet50(n_classes: int = 1000, image: int = 224, seed: int = 42,
             updater=None, blocks=(3, 4, 6, 3), width: int = 64,
             compute_dtype: str | None = "bfloat16",
             remat: str | None = None,
             activation_store_dtype: str | None = None):
    """ResNet-50 as a ComputationGraph (BASELINE config #2): bottleneck
    residual blocks via ElementWiseVertex(add) — the reference expresses
    ResNet the same way with its vertex API. NHWC, bottleneck 1-3-1 convs,
    BN+ReLU. Default policy: bf16 compute on the MXU, f32 master weights."""
    from ..nn.conf import InputType
    from ..nn.conf.graph import ElementWiseVertex
    from ..nn.graph import ComputationGraph
    from ..nn.layers import (ActivationLayer, BatchNormalization,
                             GlobalPoolingLayer)

    b = (NeuralNetConfiguration.builder()
         .seed(seed)
         .updater(updater or Adam(1e-3))
         .weight_init("relu")
         .compute_dtype(compute_dtype)
         .remat(remat)
         .activation_store_dtype(activation_store_dtype)
         .graph_builder()
         .add_inputs("input")
         .set_input_types(InputType.convolutional(image, image, 3)))

    def conv_bn_relu(name, inp, n_out, k, s, relu=True):
        b.add_layer(f"{name}_conv",
                    ConvolutionLayer(n_out=n_out, kernel_size=(k, k),
                                     stride=(s, s), activation="identity",
                                     convolution_mode=ConvolutionMode.SAME,
                                     has_bias=False), inp)
        b.add_layer(f"{name}_bn",
                    BatchNormalization(activation="relu" if relu else "identity"),
                    f"{name}_conv")
        return f"{name}_bn"

    top = conv_bn_relu("stem", "input", width, 7, 2)
    b.add_layer("stem_pool",
                SubsamplingLayer(pooling_type=PoolingType.MAX,
                                 kernel_size=(3, 3), stride=(2, 2),
                                 convolution_mode=ConvolutionMode.SAME),
                top)
    top = "stem_pool"

    ch = width
    for stage, n_blocks in enumerate(blocks):
        out_ch = ch * 4
        for blk in range(n_blocks):
            name = f"s{stage}b{blk}"
            stride = 2 if (blk == 0 and stage > 0) else 1
            t1 = conv_bn_relu(f"{name}_1", top, ch, 1, stride)
            t2 = conv_bn_relu(f"{name}_2", t1, ch, 3, 1)
            t3 = conv_bn_relu(f"{name}_3", t2, out_ch, 1, 1, relu=False)
            if blk == 0:
                sc = conv_bn_relu(f"{name}_sc", top, out_ch, 1, stride,
                                  relu=False)
            else:
                sc = top
            b.add_vertex(f"{name}_add", ElementWiseVertex(op="add"), t3, sc)
            b.add_layer(f"{name}_relu", ActivationLayer(activation="relu"),
                        f"{name}_add")
            top = f"{name}_relu"
        ch *= 2

    b.add_layer("avgpool", GlobalPoolingLayer(pooling_type=PoolingType.AVG),
                top)
    b.add_layer("fc", OutputLayer(n_out=n_classes, activation="softmax",
                                  loss="mcxent"), "avgpool")
    b.set_outputs("fc")
    return ComputationGraph(b.build())


def bench_resnet50(batch: int = 256, steps: int = 30,
                   image: int = 224, n_classes: int = 1000,
                   compute_dtype: str | None = "bfloat16"):
    """samples/sec for ResNet-50 ImageNet-shaped training (BASELINE #2):
    the [steps]-pass runs as one device-resident `fit_scan_arrays`
    dispatch, so the number measures the training step, not the upload
    or per-step dispatch. Warmup = one full same-length scan (the epoch fn
    specializes on T). Round-4 ablation winners applied (see BASELINE.md
    ablation table): Adam m/v stored bf16, bf16 input window (the model
    casts inputs to the compute dtype at entry anyway — pre-casting halves
    the scanned window's HBM read), 30-step window."""
    import jax
    import jax.numpy as jnp

    model = resnet50(image=image, n_classes=n_classes,
                     compute_dtype=compute_dtype,
                     updater=Adam(1e-3, state_dtype="bfloat16")).init()
    r = np.random.default_rng(0)
    x = r.normal(size=(batch, image, image, 3)).astype(np.float32)
    y = np.eye(n_classes, dtype=np.float32)[r.integers(0, n_classes, batch)]
    if compute_dtype is not None:
        x = x.astype(jnp.dtype(compute_dtype))
    # device-resident [T,...] batches: put ONE batch on the device and
    # broadcast it there; the whole [steps]-pass runs as one scan dispatch
    # (same device-resident policy as the LeNet/charRNN benches)
    xs = jnp.broadcast_to(jax.device_put(x), (steps,) + x.shape)
    ys = jnp.broadcast_to(jax.device_put(y), (steps,) + y.shape)
    model.fit_scan_arrays(xs, ys)
    float(model.score())  # host read of the last score: waits for the window
    t0 = time.perf_counter()
    model.fit_scan_arrays(xs, ys)
    float(model.score())
    dt = time.perf_counter() - t0
    return batch * steps / dt, "ResNet50-ImageNet"


def _vgg(cfg, n_classes, image, seed, updater) -> MultiLayerNetwork:
    b = (NeuralNetConfiguration.builder()
         .seed(seed)
         .updater(updater or Nesterovs(learning_rate=0.01, momentum=0.9))
         .weight_init("relu")
         .list())
    for v in cfg:
        if v == "M":
            b.layer(SubsamplingLayer(pooling_type=PoolingType.MAX,
                                     kernel_size=(2, 2), stride=(2, 2)))
        else:
            b.layer(ConvolutionLayer(n_out=v, kernel_size=(3, 3),
                                     stride=(1, 1), activation="relu",
                                     convolution_mode=ConvolutionMode.SAME))
    b.layer(DenseLayer(n_out=4096, activation="relu"))
    b.layer(DenseLayer(n_out=4096, activation="relu"))
    b.layer(OutputLayer(n_out=n_classes, activation="softmax", loss="mcxent"))
    conf = b.set_input_type(InputType.convolutional(image, image, 3)).build()
    return MultiLayerNetwork(conf)


def vgg16(n_classes: int = 1000, image: int = 224, seed: int = 42,
          updater=None) -> MultiLayerNetwork:
    """VGG-16 (BASELINE config #5 uses this for multi-host data parallel).
    Mirrors the reference's TrainedModels.VGG16 topology."""
    cfg = [64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
           512, 512, 512, "M", 512, 512, 512, "M"]
    return _vgg(cfg, n_classes, image, seed, updater)


def bench_lenet(batch: int = 512, steps: int = 800, warmup: int = 5):
    """samples/sec for LeNet-MNIST training steps (BASELINE config #1):
    one `fit_scan_arrays` window of `steps` batches — see bench_char_rnn."""
    from ..datasets.iterators import DataSet

    model = lenet_mnist().init()
    r = np.random.default_rng(0)
    x = r.normal(size=(batch, 784)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[r.integers(0, 10, batch)]
    import jax
    import jax.numpy as jnp

    # device-resident [T,...] batches: put ONE batch on the device and
    # broadcast it there, so the window measures the steps, not the upload;
    # warmup with the SAME scan length (the epoch fn specializes on T)
    xs = jnp.broadcast_to(jax.device_put(x), (steps,) + x.shape)
    ys = jnp.broadcast_to(jax.device_put(y), (steps,) + y.shape)
    model.fit_scan_arrays(xs, ys)
    float(model.score())  # host read of the last score: waits for the window
    t0 = time.perf_counter()
    model.fit_scan_arrays(xs, ys)
    float(model.score())
    dt = time.perf_counter() - t0
    return batch * steps / dt, "LeNet-MNIST"


def bench_lenet_dispatch(batch: int = 512, steps: int = 300, warmup: int = 20):
    """samples/sec for LeNet through the PER-BATCH fit() path (one jitted
    step dispatch per batch — the reference's actual usage pattern,
    `MultiLayerNetwork.fit(DataSetIterator)`). Complements the
    device-resident fit_scan number: together they track both the
    dispatch path and the scan fast path (BASELINE row 1)."""
    from ..datasets.iterators import DataSet

    model = lenet_mnist().init()
    r = np.random.default_rng(0)
    x = r.normal(size=(batch, 784)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[r.integers(0, 10, batch)]
    ds = DataSet(x, y)   # device_tuple cache: transfer paid once
    for _ in range(warmup):
        model.fit(ds)
    float(model.score())
    t0 = time.perf_counter()
    for _ in range(steps):
        model.fit(ds)
    float(model.score())
    dt = time.perf_counter() - t0
    return batch * steps / dt, "LeNet-MNIST-dispatch"


def bench_char_rnn_dispatch(batch: int = 64, seq_len: int = 128,
                            steps: int = 150, warmup: int = 10,
                            vocab: int = 77):
    """tokens/sec for char-RNN through the per-batch fit() path (TBPTT
    chunking included) — the dispatch-path complement of bench_char_rnn."""
    from ..datasets.iterators import DataSet

    model = char_rnn(vocab_size=vocab, seq_len=seq_len, tbptt=64).init()
    r = np.random.default_rng(0)
    idx = r.integers(0, vocab, (batch, seq_len))
    x = np.eye(vocab, dtype=np.float32)[idx]
    y = np.eye(vocab, dtype=np.float32)[np.roll(idx, -1, axis=1)]
    ds = DataSet(x, y)
    for _ in range(warmup):
        model.fit(ds)
    float(model.score())
    t0 = time.perf_counter()
    for _ in range(steps):
        model.fit(ds)
    float(model.score())
    dt = time.perf_counter() - t0
    return batch * seq_len * steps / dt, "charRNN-tokens-dispatch"


def bench_lenet_ragged(batch: int = 256, full_batches: int = 5,
                       ragged: int = 255, epochs: int = 4, warmup: int = 1):
    """Ragged-final-batch LeNet through the per-batch fit() path, three
    ways — the input-pipeline before/after artifact (ISSUE 3):

      serial           plain iterator: the ragged tail costs a SECOND
                       nn/train_step compile (the HEAD pathology)
      padded           fit(pad_ragged=True): weight-zero padding, ONE
                       compile, pad_fraction reported
      padded_prefetch  + fit(prefetch=True): device_tuple() staged one
                       batch ahead on a background thread

    Each variant runs under its OWN telemetry session on a FRESH model so
    compile counts attribute cleanly. Timing excludes the warmup epoch
    (compiles); samples/sec counts REAL rows only, so serial and padded
    are directly comparable."""
    from ..datasets.iterators import ArrayDataSetIterator
    from ..telemetry import runtime as telemetry_runtime
    from ..telemetry.runtime import TelemetrySession

    n = batch * full_batches + ragged
    r = np.random.default_rng(0)
    x = r.normal(size=(n, 784)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[r.integers(0, 10, n)]
    variants = (("serial", {}),
                ("padded", dict(pad_ragged=True)),
                ("padded_prefetch", dict(pad_ragged=True, prefetch=True)))
    state = {}
    for name, kw in variants:   # per-variant session + model: compile
        sess = TelemetrySession()   # counts attribute cleanly
        model = lenet_mnist().init()
        it = ArrayDataSetIterator(x, y, batch_size=batch)
        with telemetry_runtime.enabled(sess):
            model.fit(it, epochs=warmup, **kw)   # pays the compiles
            float(model.score())
        state[name] = (sess, model, it, kw, [])
    rounds = []
    for _ in range(3):   # ALTERNATING reps: clock/thermal drift hits every
        times = {}       # variant equally, not just the last one
        for name, kw in variants:
            sess, model, it, kw, reps = state[name]
            with telemetry_runtime.enabled(sess):
                t0 = time.perf_counter()
                model.fit(it, epochs=epochs, **kw)
                float(model.score())
                times[name] = time.perf_counter() - t0
                reps.append(times[name])
        rounds.append(times)
    out = {}
    steps = (full_batches + 1) * epochs
    for name, _ in variants:
        sess, model, it, kw, reps = state[name]
        reps.sort()
        dt = reps[len(reps) // 2]
        rec = {"samples_per_s": round(n * epochs / dt, 1),
               "steps_per_s": round(steps / dt, 2),
               "steps_per_s-spread": [round(steps / reps[-1], 2),
                                      round(steps / reps[0], 2)],
               "train_step_compiles": sess.compiles.count("nn/train_step")}
        pipe = sess.pipeline_summary()
        if pipe:
            rec["pipeline"] = pipe
        out[name] = rec
    # paired per-round comparison: each round's variants run back-to-back,
    # so the host's load/thermal drift (which swamps a sub-1% effect across
    # minutes) cancels; ratio > 1 means prefetch was faster that round
    ratios = sorted(r["serial"] / r["padded_prefetch"] for r in rounds)
    out["prefetch_vs_serial_paired_ratio"] = round(
        ratios[len(ratios) // 2], 4)
    out["prefetch_ge_serial"] = ratios[len(ratios) // 2] >= 1.0
    return out


def _paired_superstep(model_fn, x, y, batch, epochs, warmup, superstep):
    """Alternating paired reps of fit(superstep=K) vs fit(superstep=1) —
    the SAME `fit(iterator)` call, only the knob differs, so the paired
    ratio isolates exactly the host-dispatch floor the superstep removes.
    Per-variant telemetry session + fresh model (compile counts attribute
    cleanly, same protocol as bench_lenet_ragged)."""
    from ..datasets.iterators import ArrayDataSetIterator
    from ..nn.superstep import auto_superstep_k
    from ..telemetry import runtime as telemetry_runtime
    from ..telemetry.runtime import TelemetrySession

    n = x.shape[0]
    variants = (("perbatch", 1), ("superstep", superstep))
    state = {}
    for name, k in variants:
        sess = TelemetrySession()
        model = model_fn()
        it = ArrayDataSetIterator(x, y, batch_size=batch)
        with telemetry_runtime.enabled(sess):
            model.fit(it, epochs=warmup, superstep=k)   # pays the compiles
            float(model.score())
        state[name] = (sess, model, it, k, [], [])
    rounds = []
    for _ in range(3):   # ALTERNATING reps: drift hits every variant
        times = {}
        for name, _k in variants:
            sess, model, it, k, reps, disp = state[name]
            with telemetry_runtime.enabled(sess):
                d0 = sess.span_totals().get("device/dispatch", 0.0)
                t0 = time.perf_counter()
                model.fit(it, epochs=epochs, superstep=k)
                float(model.score())
                dt = time.perf_counter() - t0
                disp.append(sess.span_totals().get("device/dispatch", 0.0)
                            - d0)
            times[name] = dt
            reps.append(dt)
        rounds.append(times)
    out = {}
    for name, _k in variants:
        sess, model, it, k, reps, disp = state[name]
        order = sorted(range(len(reps)), key=lambda i: reps[i])
        mid = order[len(order) // 2]
        dt = reps[mid]
        out[name] = {
            "samples_per_s": round(n * epochs / dt, 1),
            "samples_per_s-spread": [round(n * epochs / max(reps), 1),
                                     round(n * epochs / min(reps), 1)],
            # host seconds inside dispatch calls / wall — the r05
            # device/dispatch attribution, expected to collapse under
            # the superstep (one dispatch per window, not per batch)
            "dispatch_share": round(disp[mid] / dt, 4),
            "superstep_compiles": sess.compiles.count("nn/superstep"),
            "train_step_compiles": sess.compiles.count("nn/train_step"),
        }
    out["superstep_k"] = (auto_superstep_k(x[:batch].nbytes + y[:batch].nbytes)
                          if superstep == "auto" else superstep)
    ratios = sorted(r["perbatch"] / r["superstep"] for r in rounds)
    out["superstep_vs_perbatch_paired_ratio"] = round(
        ratios[len(ratios) // 2], 4)
    out["paired_ratios"] = [round(v, 4) for v in ratios]
    return out


def bench_lenet_superstep(batch: int = 512, n_batches: int = 24,
                          epochs: int = 3, warmup: int = 1,
                          superstep="auto"):
    """Per-batch-API training through the device-resident superstep loop
    vs the K=1 per-batch dispatch loop (ISSUE 11), alternating paired
    reps: the headline LeNet config (the r05 per-batch-vs-fit_scan gap)
    plus a dispatch-bound mlp128 config.

    CPU-sandbox caveat (same class of artifact the serving bench
    documents): XLA:CPU executes convolutions inside a `lax.scan` body
    markedly slower than standalone, so on a CPU host the LeNet pairing
    can INVERT — the seed's `fit_scan_arrays` shows the identical
    inversion, while on the accelerator r05 measured that same scan at
    ~6.7x the per-batch path. The mlp128 pairing is dispatch-bound and
    shows the superstep win on any host; on accelerator hardware both do."""
    r = np.random.default_rng(0)
    n = batch * n_batches
    x = r.normal(size=(n, 784)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[r.integers(0, 10, n)]
    out = _paired_superstep(lambda: lenet_mnist().init(), x, y, batch,
                            epochs, warmup, superstep)

    def mlp128():
        from ..nn.conf import NeuralNetConfiguration
        conf = (NeuralNetConfiguration.builder()
                .seed(7).updater(Adam(1e-3)).list()
                .layer(DenseLayer(n_out=128, activation="relu"))
                .layer(OutputLayer(n_out=10, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(64))
                .build())
        from ..nn.multilayer import MultiLayerNetwork
        return MultiLayerNetwork(conf).init()

    b2 = 64
    x2 = r.normal(size=(b2 * 64, 64)).astype(np.float32)
    y2 = np.eye(10, dtype=np.float32)[r.integers(0, 10, b2 * 64)]
    out["mlp128"] = _paired_superstep(mlp128, x2, y2, b2, epochs, warmup,
                                      superstep)
    return out


def alexnet(n_classes: int = 1000, image: int = 224, seed: int = 42,
            updater=None) -> MultiLayerNetwork:
    """AlexNet (Krizhevsky 2012, single-tower variant — the topology the
    reference era's model zoo shipped). NHWC; LRN after the first two conv
    blocks as in the paper."""
    from ..nn.conf import InputType
    from ..nn.layers import LocalResponseNormalization

    b = (NeuralNetConfiguration.builder()
         .seed(seed)
         .updater(updater or Nesterovs(learning_rate=0.01, momentum=0.9))
         .weight_init("relu")
         .list()
         .layer(ConvolutionLayer(n_out=96, kernel_size=(11, 11),
                                 stride=(4, 4), activation="relu",
                                 convolution_mode=ConvolutionMode.SAME))
         .layer(LocalResponseNormalization())
         .layer(SubsamplingLayer(pooling_type=PoolingType.MAX,
                                 kernel_size=(3, 3), stride=(2, 2)))
         .layer(ConvolutionLayer(n_out=256, kernel_size=(5, 5),
                                 stride=(1, 1), activation="relu",
                                 convolution_mode=ConvolutionMode.SAME))
         .layer(LocalResponseNormalization())
         .layer(SubsamplingLayer(pooling_type=PoolingType.MAX,
                                 kernel_size=(3, 3), stride=(2, 2)))
         .layer(ConvolutionLayer(n_out=384, kernel_size=(3, 3),
                                 stride=(1, 1), activation="relu",
                                 convolution_mode=ConvolutionMode.SAME))
         .layer(ConvolutionLayer(n_out=384, kernel_size=(3, 3),
                                 stride=(1, 1), activation="relu",
                                 convolution_mode=ConvolutionMode.SAME))
         .layer(ConvolutionLayer(n_out=256, kernel_size=(3, 3),
                                 stride=(1, 1), activation="relu",
                                 convolution_mode=ConvolutionMode.SAME))
         .layer(SubsamplingLayer(pooling_type=PoolingType.MAX,
                                 kernel_size=(3, 3), stride=(2, 2)))
         .layer(DenseLayer(n_out=4096, activation="relu", dropout=0.5))
         .layer(DenseLayer(n_out=4096, activation="relu", dropout=0.5))
         .layer(OutputLayer(n_out=n_classes, activation="softmax",
                            loss="mcxent")))
    conf = b.set_input_type(InputType.convolutional(image, image, 3)).build()
    return MultiLayerNetwork(conf)


def vgg19(n_classes: int = 1000, image: int = 224, seed: int = 42,
          updater=None) -> MultiLayerNetwork:
    """VGG-19 (TrainedModels.VGG19 topology analog): VGG-16 with the extra
    conv in blocks 3-5."""
    cfg = [64, 64, "M", 128, 128, "M", 256, 256, 256, 256, "M",
           512, 512, 512, 512, "M", 512, 512, 512, 512, "M"]
    return _vgg(cfg, n_classes, image, seed, updater)


def googlenet(n_classes: int = 1000, image: int = 224, seed: int = 42,
              updater=None):
    """GoogLeNet / Inception-v1 (Szegedy 2014) as a ComputationGraph:
    inception modules = four parallel branches concatenated with
    MergeVertex — the multi-branch DAG workload the vertex API exists for
    (reference expresses it identically with its graph API)."""
    from ..nn.conf import InputType
    from ..nn.conf.graph import MergeVertex
    from ..nn.graph import ComputationGraph
    from ..nn.layers import GlobalPoolingLayer

    b = (NeuralNetConfiguration.builder()
         .seed(seed)
         .updater(updater or Adam(1e-3))
         .weight_init("relu")
         .graph_builder()
         .add_inputs("input")
         .set_input_types(InputType.convolutional(image, image, 3)))

    def conv(name, inp, n_out, k, s=1):
        b.add_layer(name, ConvolutionLayer(
            n_out=n_out, kernel_size=(k, k), stride=(s, s),
            activation="relu", convolution_mode=ConvolutionMode.SAME), inp)
        return name

    def pool(name, inp, k=3, s=2):
        b.add_layer(name, SubsamplingLayer(
            pooling_type=PoolingType.MAX, kernel_size=(k, k), stride=(s, s),
            convolution_mode=ConvolutionMode.SAME), inp)
        return name

    def inception(name, inp, c1, c3r, c3, c5r, c5, pp):
        b1 = conv(f"{name}_1x1", inp, c1, 1)
        b3 = conv(f"{name}_3x3", conv(f"{name}_3x3r", inp, c3r, 1), c3, 3)
        b5 = conv(f"{name}_5x5", conv(f"{name}_5x5r", inp, c5r, 1), c5, 5)
        bp = conv(f"{name}_poolproj",
                  pool(f"{name}_pool", inp, 3, 1), pp, 1)
        b.add_vertex(f"{name}_concat", MergeVertex(), b1, b3, b5, bp)
        return f"{name}_concat"

    top = conv("stem1", "input", 64, 7, 2)
    top = pool("stem1_pool", top)
    top = conv("stem2a", top, 64, 1)
    top = conv("stem2b", top, 192, 3)
    top = pool("stem2_pool", top)
    top = inception("i3a", top, 64, 96, 128, 16, 32, 32)
    top = inception("i3b", top, 128, 128, 192, 32, 96, 64)
    top = pool("pool3", top)
    top = inception("i4a", top, 192, 96, 208, 16, 48, 64)
    top = inception("i4b", top, 160, 112, 224, 24, 64, 64)
    top = inception("i4c", top, 128, 128, 256, 24, 64, 64)
    top = inception("i4d", top, 112, 144, 288, 32, 64, 64)
    top = inception("i4e", top, 256, 160, 320, 32, 128, 128)
    top = pool("pool4", top)
    top = inception("i5a", top, 256, 160, 320, 32, 128, 128)
    top = inception("i5b", top, 384, 192, 384, 48, 128, 128)
    b.add_layer("gap", GlobalPoolingLayer(pooling_type=PoolingType.AVG),
                top)
    b.add_layer("out", OutputLayer(n_out=n_classes, activation="softmax",
                                   loss="mcxent", dropout=0.6), "gap")
    conf = b.set_outputs("out").build()
    return ComputationGraph(conf)


def sample_characters(net, char_to_idx: dict, seed_text: str, n_chars: int,
                      temperature: float = 1.0, rng_seed: int = 0):
    """Generate text with a trained char-RNN via stateful rnn_time_step
    (the reference's GravesLSTMCharModellingExample sampling loop)."""
    if not seed_text:
        raise ValueError("seed_text must contain at least one character")
    idx_to_char = {i: c for c, i in char_to_idx.items()}
    vocab = len(char_to_idx)
    net.rnn_clear_previous_state()
    out = None
    for ch in seed_text:
        x = np.zeros((1, vocab), np.float32)
        x[0, char_to_idx[ch]] = 1.0
        out = net.rnn_time_step(x)
    rng = np.random.default_rng(rng_seed)
    generated = []
    for _ in range(n_chars):
        p = np.asarray(out, np.float64).reshape(-1)
        if temperature != 1.0:
            logp = np.log(np.maximum(p, 1e-12)) / temperature
            p = np.exp(logp - logp.max())
        p = p / p.sum()
        nxt = int(rng.choice(vocab, p=p))
        generated.append(idx_to_char[nxt])
        x = np.zeros((1, vocab), np.float32)
        x[0, nxt] = 1.0
        out = net.rnn_time_step(x)
    net.rnn_clear_previous_state()
    return "".join(generated)
