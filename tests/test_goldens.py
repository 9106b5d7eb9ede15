"""Loss-curve regression goldens (BASELINE.md measurement plan item 2).

Deterministic seeded training runs whose per-step losses were recorded on
CPU and committed as fixtures. Any change to initialization draws, updater
math, loss conventions, RNG threading, or layer numerics shows up here as a
diff — the role the reference's loss-parity configs play (BASELINE configs
#1/#3/#4). Tolerances allow for XLA-version fusion drift, not semantic
change.
"""

import numpy as np

from deeplearning4j_tpu.models import MultiLayerNetwork
from deeplearning4j_tpu.nn import (ConvolutionLayer, DenseLayer, GravesLSTM,
                                   InputType, NeuralNetConfiguration,
                                   OutputLayer, RnnOutputLayer,
                                   SubsamplingLayer)
from deeplearning4j_tpu.train import Adam, CollectScoresListener, Sgd

# Recorded at commit 2305a6a (the parent of PR 33) on jax / jaxlib 0.9.0,
# CPU backend, under the suite's own conftest (8 virtual devices, x64 off);
# two fresh processes gave the same digits. The values before these were
# from another jax and initialiser stream and missed from step 1, so the
# curves guarded nothing; these hold `fit` to the trajectory it had before
# the train step and the loop moved into `train/fit_engine.py`. Goldens are
# fixtures of one toolchain: record them again (twice, and compare) when
# the jax pin moves, never to make a change of the engine pass.
LENET_GOLDEN = [2.247756, 2.208591, 2.171265, 2.144371, 2.125517,
                2.076218, 2.015083, 1.953701, 1.946526, 1.947022]
LSTM_GOLDEN = [2.502773, 2.483892, 2.466112, 2.449220, 2.433141,
               2.417893, 2.403498, 2.389911]
BERT_GOLDEN = [1.090776, 1.286131, 1.276235, 0.919525, 1.136208, 1.115440]

_TOL = dict(rtol=2e-3, atol=2e-3)


def test_lenet_loss_curve_golden():
    from deeplearning4j_tpu.data import MnistDataSetIterator
    conf = (NeuralNetConfiguration.builder().seed(123).updater(Adam(1e-3)).list()
            .layer(ConvolutionLayer(n_out=4, kernel_size=(5, 5), activation="relu"))
            .layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
            .layer(DenseLayer(n_out=32, activation="relu"))
            .layer(OutputLayer(n_out=10, activation="softmax"))
            .set_input_type(InputType.convolutional_flat(28, 28, 1)).build())
    net = MultiLayerNetwork(conf).init()
    it = MnistDataSetIterator(batch_size=32, train=True, num_examples=160,
                              shuffle=False)
    if not it.synthetic:
        import pytest
        pytest.skip("real MNIST cache present; golden recorded on the "
                    "deterministic synthetic set")
    c = CollectScoresListener()
    net.set_listeners(c)
    net.fit(it, epochs=2)
    np.testing.assert_allclose([s for _, s in c.scores], LENET_GOLDEN, **_TOL)


def test_graves_lstm_loss_curve_golden():
    B, T, V = 8, 16, 12
    seq = np.tile(np.arange(V), (B, T // V + 2))[:, :T + 1]
    x = np.eye(V, dtype=np.float32)[seq[:, :-1]]
    y = np.eye(V, dtype=np.float32)[seq[:, 1:]]
    conf = (NeuralNetConfiguration.builder().seed(99).updater(Sgd(0.5)).list()
            .layer(GravesLSTM(n_out=16))
            .layer(RnnOutputLayer(n_out=V, activation="softmax"))
            .set_input_type(InputType.recurrent(V, T)).build())
    net = MultiLayerNetwork(conf).init()
    losses = []
    for _ in range(8):
        net.fit(x, y, epochs=1)
        losses.append(float(net.score()))
    np.testing.assert_allclose(losses, LSTM_GOLDEN, **_TOL)


def test_bert_loss_curve_golden():
    from deeplearning4j_tpu.zoo import Bert
    model = Bert(vocab_size=64, d_model=32, n_layers=2, n_heads=2, ffn_size=64,
                 max_len=16, num_classes=2, seed=5)
    net = model.init()
    rng = np.random.default_rng(3)
    toks = rng.integers(0, 64, (8, 16)).astype(np.int32)
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 8)]
    losses = []
    for _ in range(6):
        net.fit(toks, y, epochs=1)
        losses.append(float(net.score()))
    np.testing.assert_allclose(losses, BERT_GOLDEN, **_TOL)
