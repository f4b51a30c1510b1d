"""Data-parallel training two ways (reference iterative reduce):
1. On-mesh per-step gradient averaging (shard_map + pmean over ICI) —
   the TPU-native path; runs on however many devices exist.
2. The coarse epoch-wave parameter-averaging runtime (master/worker
   choreography with heartbeats/eviction) embedded in one process.
"""
import jax
import numpy as np

from deeplearning4j_tpu.config import NeuralNetConfiguration
from deeplearning4j_tpu.datasets import ListDataSetIterator
from deeplearning4j_tpu.datasets.api import DataSet
from deeplearning4j_tpu.datasets.iris import load_iris
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel import DataParallelTrainer
from deeplearning4j_tpu.scaleout import (CollectionJobIterator,
                                         DistributedRuntime,
                                         NeuralNetWorkPerformer)
from deeplearning4j_tpu.utils import jaxenv

jaxenv.configure()  # compile cache + platform pin, before JAX starts

conf = (NeuralNetConfiguration.builder()
        .lr(0.1).n_in(4).activation_function("tanh")
        .optimization_algo("iteration_gradient_descent")
        .num_iterations(5).use_adagrad(False)
        .list(2).hidden_layer_sizes([8])
        .override(1, layer="output", loss_function="mcxent",
                  activation_function="softmax", n_out=3)
        .pretrain(False).build())

x, y = load_iris()
x, y = np.asarray(x), np.asarray(y)

# -- 1: on-mesh DP (per-step pmean all-reduce) ---------------------------
n_dev = len(jax.devices())
net = MultiLayerNetwork(conf)
trainer = DataParallelTrainer(net)  # mesh defaults to all local devices
usable = len(x) // (n_dev * 2) * (n_dev * 2)
it = ListDataSetIterator(DataSet(x[:usable], y[:usable]),
                         batch_size=usable // 2)
trainer.fit(it, epochs=20)
print(f"on-mesh DP over {n_dev} device(s): score {net.score(x, y):.4f}")

# -- 2: epoch-wave parameter averaging (scaleout runtime) ----------------
rng = np.random.RandomState(0)
batches = [DataSet(x[i], y[i]) for i in
           (rng.choice(len(x), 32) for _ in range(8))]
rt = DistributedRuntime(
    CollectionJobIterator(batches),
    lambda: NeuralNetWorkPerformer(conf.to_json(), epochs=1),
    n_workers=2)
final = rt.run(timeout=120)
print(f"epoch-wave averaging: {rt.waves} waves, params {final.shape}")
