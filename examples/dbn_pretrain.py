"""DBN: layer-wise RBM pretraining + supervised finetune (reference
MultiLayerNetwork.pretrain + finetune over CD-1 RBMs).

DL4J_TPU_EXAMPLE_FAST=1 shrinks the run (CI smoke, tests/test_examples.py)."""
import os

import numpy as np

from deeplearning4j_tpu.config import NeuralNetConfiguration
from deeplearning4j_tpu.datasets.mnist import synthetic_mnist
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.utils import jaxenv

jaxenv.configure()  # compile cache + platform pin, before JAX starts

FAST = os.environ.get("DL4J_TPU_EXAMPLE_FAST") == "1"

conf = (NeuralNetConfiguration.builder()
        .lr(2.0)  # adagrad master step; update is lr/batch-scaled (reference semantics)
        .n_in(784).activation_function("sigmoid")
        .optimization_algo("iteration_gradient_descent")
        .num_iterations(8 if FAST else 40).batch_size(512)
        .list(3).hidden_layer_sizes([256, 128])
        .override(0, layer="rbm", k=1)
        .override(1, layer="rbm", k=1)
        .override(2, layer="output", loss_function="mcxent",
                  activation_function="softmax", n_out=10)
        .pretrain(True)  # unsupervised CD-1 pass before finetune
        .build())

net = MultiLayerNetwork(conf)
x, y = synthetic_mnist(4096)
before = net.score(x, y)
net.fit(x, y)
print(f"score: {before:.4f} -> {net.score(x, y):.4f}")
print("accuracy:", float((net.predict(x) == np.argmax(np.asarray(y), 1)).mean()))
