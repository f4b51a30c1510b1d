"""MNIST 3-layer MLP — the reference MultiLayerTest end-to-end slice.

Run: python examples/mnist_mlp.py  (set JAX_PLATFORMS=cpu to force CPU;
DL4J_TPU_EXAMPLE_FAST=1 shrinks the run for CI smoke)
"""
import os

import numpy as np

FAST = os.environ.get("DL4J_TPU_EXAMPLE_FAST") == "1"

from deeplearning4j_tpu.config import NeuralNetConfiguration
from deeplearning4j_tpu.datasets.mnist import synthetic_mnist
from deeplearning4j_tpu.eval import Evaluation
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize import ScoreIterationListener
from deeplearning4j_tpu.utils import jaxenv

jaxenv.configure()  # compile cache + platform pin, before JAX starts

conf = (NeuralNetConfiguration.builder()
        .lr(1.0)  # adagrad master step size (reference masterStepSize)
        .n_in(784).activation_function("relu")
        .optimization_algo("iteration_gradient_descent")
        .num_iterations(1).batch_size(512)
        .compute_dtype("bfloat16")
        .list(3).hidden_layer_sizes([256, 128])
        .override(2, layer="output", loss_function="mcxent",
                  activation_function="softmax", n_out=10)
        .pretrain(False).build())

net = MultiLayerNetwork(conf)
net.set_listeners([ScoreIterationListener(10)])

x, y = synthetic_mnist(2048 if FAST else 8192)  # or load_mnist(...) for real IDX
from deeplearning4j_tpu.datasets import ListDataSetIterator
from deeplearning4j_tpu.datasets.api import DataSet

net.fit(ListDataSetIterator(DataSet(np.asarray(x), np.asarray(y)),
                            batch_size=512), epochs=1 if FAST else 3)

ev = Evaluation()
ev.eval(np.asarray(y), np.asarray(net.output(x)))
print(ev.stats())
