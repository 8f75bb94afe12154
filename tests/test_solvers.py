"""Line-search optimizer tests (CG / LBFGS / line gradient descent).

The reference validates these on small convex problems
(`org.deeplearning4j.optimize.solver.BackTrackLineSearchTest`,
`TestOptimizers` in deeplearning4j-core): here a linear least-squares model
has a known optimum, so the solvers must drive the loss to it.
"""
import numpy as np
import pytest

from deeplearning4j_tpu import (DataSet, DenseLayer, InputType,
                                MultiLayerNetwork, NeuralNetConfiguration,
                                OutputLayer)
from deeplearning4j_tpu.nn.conf import OptimizationAlgorithm
from deeplearning4j_tpu.optimize.solvers import BackTrackLineSearch


def _lstsq_problem(seed=0, n=64, d=8):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float64)
    w_true = rng.normal(size=(d, 1)).astype(np.float64)
    y = x @ w_true + 0.01 * rng.normal(size=(n, 1))
    # optimal mean squared residual (per DL4J mse convention: mean over
    # examples of sum over outputs, halved? our "mse" loss is mean sq err)
    w_opt, *_ = np.linalg.lstsq(x, y, rcond=None)
    resid = y - x @ w_opt
    return (x.astype(np.float32), y.astype(np.float32),
            float(np.mean(resid ** 2)))


def _linear_model(algo, seed=1):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed)
            .optimization_algo(algo)
            .max_num_line_search_iterations(8)
            .list()
            .layer(OutputLayer(n_out=1, activation="identity", loss="mse",
                               has_bias=False))
            .set_input_type(InputType.feed_forward(8))
            .build())
    return MultiLayerNetwork(conf).init()


@pytest.mark.parametrize("algo", [
    OptimizationAlgorithm.CONJUGATE_GRADIENT,
    OptimizationAlgorithm.LBFGS,
    OptimizationAlgorithm.LINE_GRADIENT_DESCENT,
])
def test_line_search_solvers_reach_lstsq_optimum(algo):
    x, y, opt_loss = _lstsq_problem()
    model = _linear_model(algo)
    ds = DataSet(x, y)
    for _ in range(60):
        model.fit(ds)
    final = model.score()
    # within 5% of the least-squares optimum (scale-free convex gate)
    assert final <= opt_loss * 1.05 + 1e-6, (algo, final, opt_loss)


def test_cg_converges_faster_than_plain_line_search():
    """At a fixed budget of 15 iterations CG sits on the least-squares
    optimum and is no worse than plain line-search gradient descent.

    Every draw is seeded (problem from default_rng, model init from
    .seed()), so the scores are a fixed function of the code and of the
    installed jax. What is asserted is what the problem's structure
    gives, not an absolute gap, which moves with the jax installed (a
    gap of 1e-4 calibrated on another one failed on every tree here).
    Measured on jax 0.9.0 (2026-10-03): optimum 5.18e-5, CG 5.20e-5
    (1.005x, there since iteration 10), LGD 7.35e-5 (1.42x, still
    descending)."""
    x, y, opt_loss = _lstsq_problem(seed=0)
    ds = DataSet(x, y)
    scores = {}
    for algo in (OptimizationAlgorithm.CONJUGATE_GRADIENT,
                 OptimizationAlgorithm.LINE_GRADIENT_DESCENT):
        m = _linear_model(algo)
        for _ in range(15):
            m.fit(ds)
        scores[algo] = m.score()
    cg = scores[OptimizationAlgorithm.CONJUGATE_GRADIENT]
    lgd = scores[OptimizationAlgorithm.LINE_GRADIENT_DESCENT]
    # CG has converged: within 5% of the lstsq optimum
    assert cg <= opt_loss * 1.05 + 1e-6, (cg, opt_loss)
    assert cg <= lgd, (cg, lgd)


def test_lbfgs_trains_classifier():
    rng = np.random.default_rng(5)
    x = np.concatenate([rng.normal(-1.5, 1, (60, 6)),
                        rng.normal(1.5, 1, (60, 6))]).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[np.array([0] * 60 + [1] * 60)]
    conf = (NeuralNetConfiguration.builder()
            .seed(2)
            .optimization_algo(OptimizationAlgorithm.LBFGS)
            .list()
            .layer(DenseLayer(n_out=12, activation="tanh"))
            .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(6))
            .build())
    model = MultiLayerNetwork(conf).init()
    ds = DataSet(x, y)
    for _ in range(40):
        model.fit(ds)
    from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator
    acc = model.evaluate(ArrayDataSetIterator(x, y, batch_size=60)).accuracy()
    assert acc >= 0.95, acc


def test_backtrack_line_search_armijo():
    # f(alpha) = (alpha - 0.6)^2 along the direction; f0 = f(0) = 0.36,
    # slope at 0 is -1.2 (descent). Armijo accepts alpha=1 (f=0.16).
    ls = BackTrackLineSearch(max_iterations=8)
    alpha, fa = ls.optimize(lambda a: (a - 0.6) ** 2, 0.36, -1.2)
    assert alpha > 0
    assert fa < 0.36
    assert fa <= 0.36 + 1e-4 * alpha * (-1.2)


def test_backtrack_line_search_rejects_ascent():
    # loss increases for every trial step: no alpha accepted
    ls = BackTrackLineSearch(max_iterations=5)
    alpha, fa = ls.optimize(lambda a: 1.0 + a, 1.0, -0.1)
    assert alpha == 0.0 and fa == 1.0


def test_graph_line_search_solver():
    from deeplearning4j_tpu.nn.conf.input_type import InputType as IT
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    x, y, opt_loss = _lstsq_problem(seed=7)
    b = (NeuralNetConfiguration.builder()
         .seed(4)
         .optimization_algo(OptimizationAlgorithm.CONJUGATE_GRADIENT)
         .graph_builder()
         .add_inputs("in"))
    b.add_layer("out", OutputLayer(n_out=1, activation="identity",
                                   loss="mse", has_bias=False), "in")
    b.set_outputs("out")
    b.set_input_types(IT.feed_forward(8))
    g = ComputationGraph(b.build()).init()
    ds = DataSet(x, y)
    for _ in range(40):
        g.fit(ds)
    assert g.score() <= opt_loss * 1.05 + 1e-6


def test_sgd_path_unchanged():
    """Default algo still routes through the jitted updater step."""
    x, y, _ = _lstsq_problem(seed=9)
    conf = (NeuralNetConfiguration.builder()
            .seed(1).list()
            .layer(OutputLayer(n_out=1, activation="identity", loss="mse"))
            .set_input_type(InputType.feed_forward(8))
            .build())
    m = MultiLayerNetwork(conf).init()
    s0 = None
    ds = DataSet(x, y)
    for _ in range(20):
        m.fit(ds)
        if s0 is None:
            s0 = m.score()
    assert m.score() < s0
