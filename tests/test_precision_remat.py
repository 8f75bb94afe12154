"""Mixed precision + selective rematerialization (ISSUE 18, parts 2/3).

Two unified contracts across every fit path (per-batch `fit`,
`fit(superstep=K)`, `fit(grad_accumulation=M)`, and the 1F1B
ParallelTrainer strategies):

  * selective remat (`remat_policy`) is a NUMERICS NO-OP — it moves the
    checkpoint-boundary save set (activation memory vs recompute), never
    the math: every policy trains to f32-ulp-identical parameters as the
    un-rematerialized run on the same stream;
  * bf16-compute / fp32-master (`compute_dtype="bfloat16"`) is one
    precision semantics everywhere: floating inputs cast once to the
    compute dtype, non-output layers compute on bf16-cast params with
    the cotangent landing back in the fp32 master tree, the output
    layer/loss stays fp32 — so regrouping-equivalent paths stay
    BIT-identical, and the old pipeline.py compute_dtype rejection is
    gone (1F1B runs bf16 and composes with checkpoint-resume);
  * the static activation-byte accounting (`pp_stage_saved_bytes`)
    orders the policies: `nothing`/None save 0, `dots` saves strictly
    less than `everything` (the un-checkpointed stage residual set);
  * `FitCheckpointer` records compute_dtype/remat/remat_policy in the
    checkpoint context and resume warns on mismatch (math warning for
    compute_dtype, no-op warning for remat knobs).
"""
import logging

import numpy as np
import pytest

from deeplearning4j_tpu import (Adam, DataSet, DenseLayer,
                                EmbeddingSequenceLayer, InputType,
                                MultiLayerNetwork, NeuralNetConfiguration,
                                OutputLayer, RnnOutputLayer,
                                TransformerBlock)
from deeplearning4j_tpu.datasets import ArrayDataSetIterator
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.parallel import ParallelTrainer, ShardingStrategy

pytestmark = pytest.mark.sanitize


def _mlp(seed=7, h=16, depth=2, **conf_kw):
    b = NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-2))
    for k, v in conf_kw.items():
        b = getattr(b, k)(v)
    b = b.list()
    for _ in range(depth):
        b = b.layer(DenseLayer(n_out=h, activation="tanh"))
    conf = (b.layer(OutputLayer(n_out=4, loss="mcxent"))
            .set_input_type(InputType.feed_forward(8)).build())
    return MultiLayerNetwork(conf).init()


def _pp_mlp(seed=7, h=16, depth=4, **conf_kw):
    b = NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-2))
    for k, v in conf_kw.items():
        b = getattr(b, k)(v)
    b = b.list()
    for _ in range(depth):
        b = b.layer(DenseLayer(n_out=h, activation="tanh"))
    conf = (b.layer(OutputLayer(n_out=4, loss="mcxent"))
            .set_input_type(InputType.feed_forward(h)).build())
    return MultiLayerNetwork(conf).init()


def _pp_lm(seed=0, vocab=32, width=16, t=8, depth=2, **conf_kw):
    b = NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
    for k, v in conf_kw.items():
        b = getattr(b, k)(v)
    b = (b.list()
         .layer(EmbeddingSequenceLayer(n_in=vocab, n_out=width)))
    for _ in range(depth):
        b = b.layer(TransformerBlock(n_heads=4))
    conf = (b.layer(RnnOutputLayer(n_out=vocab, activation="softmax",
                                   loss="mcxent"))
            .set_input_type(InputType.recurrent(1, t)).build())
    return MultiLayerNetwork(conf).init()


def _iter(n=32, batch=8, seed=0):
    r = np.random.default_rng(seed)
    x = r.normal(size=(n, 8)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[r.integers(0, 4, n)]
    return ArrayDataSetIterator(x, y, batch_size=batch, shuffle=False)


def _micros(n, mb=8, h=16, seed=0):
    r = np.random.default_rng(seed)
    return [DataSet(r.normal(size=(mb, h)).astype(np.float32),
                    np.eye(4, dtype=np.float32)[r.integers(0, 4, mb)])
            for _ in range(n)]


def _flat(model):
    return np.asarray(model.params_flat())


FIT_PATHS = [{}, {"superstep": 2}, {"grad_accumulation": 2}]


# ======================================================================
# selective remat: every policy is a numerics no-op on every fit path
# ======================================================================

def test_remat_policy_numerics_noop_across_fit_paths():
    # one un-rematerialized baseline per fit path, shared across every
    # policy variant (keeps the tier-1 wall: 3 baselines + 12 variants)
    baselines = []
    for kwargs in FIT_PATHS:
        base = _mlp()
        base.fit(_iter(), epochs=1, **kwargs)
        baselines.append(_flat(base))
    for policy in (None, "nothing", "dots", "everything"):
        kw = {"remat": "full"}
        if policy is not None:
            kw["remat_policy"] = policy
        for kwargs, want in zip(FIT_PATHS, baselines):
            m = _mlp(**kw)
            m.fit(_iter(), epochs=1, **kwargs)
            np.testing.assert_allclose(
                _flat(m), want, rtol=2e-6, atol=2e-7,
                err_msg=f"policy={policy} kwargs={kwargs}")


def test_remat_policy_per_layer_mode_noop():
    base = _mlp()
    base.fit(_iter(), epochs=1)
    m = _mlp(remat="layer", remat_policy="dots")
    m.fit(_iter(), epochs=1)
    np.testing.assert_allclose(_flat(m), _flat(base), rtol=2e-6, atol=2e-7)


def test_remat_policy_numerics_noop_1f1b():
    micros = _micros(8)
    base = ParallelTrainer(_pp_mlp(), mesh_shape=(2, 2, 2),
                           strategy=ShardingStrategy.ZERO1_TP_PP)
    base.fit(ListDataSetIterator(list(micros)), grad_accumulation=4)
    for policy in ("dots", "everything"):
        tr = ParallelTrainer(_pp_mlp(remat_policy=policy),
                             mesh_shape=(2, 2, 2),
                             strategy=ShardingStrategy.ZERO1_TP_PP)
        tr.fit(ListDataSetIterator(list(micros)), grad_accumulation=4)
        assert tr._pp_info["remat"]["policy"] == policy
        np.testing.assert_allclose(_flat(tr.model), _flat(base.model),
                                   rtol=2e-6, atol=2e-7)


def test_remat_policy_typo_fails_fast():
    with pytest.raises(ValueError, match="bogus"):
        NeuralNetConfiguration.builder().remat_policy("bogus")


# ======================================================================
# bf16-compute / fp32-master: one semantics across fit paths
# ======================================================================

def test_bf16_master_params_stay_fp32():
    m = _mlp(compute_dtype="bfloat16")
    m.fit(_iter(), epochs=1)
    flat = _flat(m)
    assert flat.dtype == np.float32
    assert np.isfinite(flat).all()


def test_bf16_bitexact_across_grouping_equivalent_paths():
    a = _mlp(compute_dtype="bfloat16")
    a.fit(_iter(), epochs=1)
    b = _mlp(compute_dtype="bfloat16")
    b.fit(_iter(), epochs=1, superstep=2)
    # superstep is a pure regrouping — bf16 compute must not break the
    # bit-identity the fp32 paths already guarantee
    np.testing.assert_array_equal(_flat(a), _flat(b))


def test_bf16_accum_bitexact_across_window_grouping():
    a = _mlp(compute_dtype="bfloat16")
    a.fit(_iter(), epochs=1, grad_accumulation=2)
    b = _mlp(compute_dtype="bfloat16")
    b.fit(_iter(), epochs=1, grad_accumulation=2, superstep=2)
    np.testing.assert_array_equal(_flat(a), _flat(b))


# ======================================================================
# 1F1B compute_dtype lift: bf16 pipeline runs and composes with resume
# ======================================================================

def test_pp_bf16_runs_and_composes_with_checkpoint_resume(tmp_path):
    micros = _micros(8)
    full = ParallelTrainer(_pp_mlp(compute_dtype="bfloat16"),
                           mesh_shape=(2, 2, 2),
                           strategy=ShardingStrategy.ZERO1_TP_PP)
    assert full._pp_info["remat"]["compute_dtype"] == "bfloat16"
    full.fit(ListDataSetIterator(list(micros)), epochs=2,
             grad_accumulation=4)
    assert np.isfinite(_flat(full.model)).all()

    # interrupted-and-resumed run: epoch 1 saved, epoch 2 after resume
    ck = str(tmp_path / "pp_bf16")
    a = ParallelTrainer(_pp_mlp(compute_dtype="bfloat16"),
                        mesh_shape=(2, 2, 2),
                        strategy=ShardingStrategy.ZERO1_TP_PP)
    a.fit(ListDataSetIterator(list(micros)), epochs=1, grad_accumulation=4,
          checkpoint_dir=ck, checkpoint_every=1)
    b = ParallelTrainer(_pp_mlp(compute_dtype="bfloat16"),
                        mesh_shape=(2, 2, 2),
                        strategy=ShardingStrategy.ZERO1_TP_PP)
    b.fit(ListDataSetIterator(list(micros)), epochs=2, grad_accumulation=4,
          checkpoint_dir=ck, resume=True)
    np.testing.assert_array_equal(_flat(b.model), _flat(full.model))


# ======================================================================
# static activation-byte accounting: the policies are ordered
# ======================================================================

def test_pp_stage_saved_bytes_policy_ordering():
    from deeplearning4j_tpu.parallel.mesh import MeshAxes, make_mesh
    from deeplearning4j_tpu.parallel.pipeline import (PipelinePlan,
                                                      pp_stage_saved_bytes)

    mesh = make_mesh({MeshAxes.DATA: 2, MeshAxes.MODEL: 2,
                      MeshAxes.PIPE: 2})
    plan = PipelinePlan(_pp_lm(), mesh, pipe_axis=MeshAxes.PIPE,
                        model_axis=MeshAxes.MODEL,
                        data_axis=MeshAxes.DATA, tp=True)
    micro = (4, 8, 16)
    col = {p: pp_stage_saved_bytes(plan, micro, policy=p)
           for p in (None, "nothing", "dots", "everything")}
    # None == jax's save-nothing default == the "nothing" policy
    assert col[None] == 0 and col["nothing"] == 0
    # the selective policy must cut the blanket (un-checkpointed)
    # residual set
    assert 0 < col["dots"] < col["everything"]


def test_saved_bytes_boundary_inputs_excluded():
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.remat import saved_bytes

    def f(a, b):
        return jnp.tanh(a @ b).sum()

    a = np.zeros((4, 8), np.float32)
    b = np.zeros((8, 8), np.float32)
    # save-nothing: boundary args are alive anyway and must not count
    assert saved_bytes(f, a, b, policy="nothing") == 0
    assert saved_bytes(f, a, b, policy="dots") > 0


# ======================================================================
# checkpoint context: resume warns on precision/remat mismatch
# ======================================================================

def test_resume_warns_on_precision_and_remat_mismatch(tmp_path, caplog):
    ck = str(tmp_path / "ctx")
    a = _mlp()
    a.fit(_iter(), epochs=1, checkpoint_dir=ck, checkpoint_every=1)

    b = _mlp(compute_dtype="bfloat16", remat="full", remat_policy="dots")
    with caplog.at_level(logging.WARNING, logger="deeplearning4j_tpu"):
        b.fit(_iter(), epochs=1, checkpoint_dir=ck, resume=True)
    msgs = [r.message for r in caplog.records]
    assert any("compute_dtype" in m and "MATH" in m for m in msgs)
    assert any("remat_policy" in m and "no-op" in m for m in msgs)


def test_resume_same_policy_no_warning(tmp_path, caplog):
    ck = str(tmp_path / "ctx_same")
    a = _mlp(remat="full", remat_policy="dots")
    a.fit(_iter(), epochs=1, checkpoint_dir=ck, checkpoint_every=1)

    b = _mlp(remat="full", remat_policy="dots")
    with caplog.at_level(logging.WARNING, logger="deeplearning4j_tpu"):
        b.fit(_iter(), epochs=1, checkpoint_dir=ck, resume=True)
    assert not any("remat" in r.message or "compute_dtype" in r.message
                   for r in caplog.records)
