"""Model-zoo topology tests (AlexNet / VGG-19 / GoogLeNet Inception-v1 +
char sampling). Small image sizes keep the CPU mesh fast; no test builds the
full-size variants.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from deeplearning4j_tpu import DataSet
from deeplearning4j_tpu.models.zoo import (alexnet, char_rnn, googlenet,
                                           sample_characters, vgg19)


def test_alexnet_forward_and_train_step():
    net = alexnet(n_classes=5, image=64).init()
    r = np.random.default_rng(0)
    x = r.normal(size=(4, 64, 64, 3)).astype(np.float32)
    o = np.asarray(net.output(x))
    assert o.shape == (4, 5)
    np.testing.assert_allclose(o.sum(1), 1.0, rtol=1e-4)
    y = np.eye(5, dtype=np.float32)[r.integers(0, 5, 4)]
    net.fit(DataSet(x, y))
    assert np.isfinite(float(net.score()))


def test_vgg19_topology():
    net = vgg19(n_classes=3, image=32).init()
    # 16 convs + 5 pools + 2 dense + output = 24 layers
    assert len(net.layers) == 24
    x = np.random.default_rng(1).normal(size=(2, 32, 32, 3)) \
        .astype(np.float32)
    o = np.asarray(net.output(x))
    assert o.shape == (2, 3)


def test_googlenet_inception_merge():
    g = googlenet(n_classes=4, image=64).init()
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 64, 64, 3))
                    .astype(np.float32))
    out = g.output(x)
    o = np.asarray(out[0] if isinstance(out, (list, tuple)) else out)
    assert o.shape == (2, 4)
    np.testing.assert_allclose(o.sum(1), 1.0, rtol=1e-4)
    # inception concat must feed all four branches into the merge
    assert g.conf.vertex_inputs["i3a_concat"] == [
        "i3a_1x1", "i3a_3x3", "i3a_5x5", "i3a_poolproj"]


def test_char_sampling_stateful():
    """sample_characters drives rnn_time_step with carried state and
    returns n characters from the vocab (reference char-modelling example
    sampling loop)."""
    chars = "ab c"
    c2i = {c: i for i, c in enumerate(chars)}
    net = char_rnn(vocab_size=len(chars), seq_len=8, lstm_size=12).init()
    out = sample_characters(net, c2i, "ab", 20, temperature=0.8, rng_seed=1)
    assert len(out) == 20
    assert set(out) <= set(chars)
    # deterministic given the same rng seed
    out2 = sample_characters(net, c2i, "ab", 20, temperature=0.8, rng_seed=1)
    assert out == out2
