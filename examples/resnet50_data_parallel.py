"""ResNet-50 data-parallel training over a device mesh (BASELINE.md configs
#3/#5: the ParallelWrapper path). On one chip this is plain jitted training;
on a pod slice the SAME code shards the batch over all devices with gradient
all-reduce riding ICI.

Run (single chip):      python examples/resnet50_data_parallel.py
Run (8 virtual devs):   XLA_FLAGS=--xla_force_host_platform_device_count=8 \
                        JAX_PLATFORMS=cpu python examples/resnet50_data_parallel.py --tiny
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import time

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.models import resnet50_conf, resnet_tiny_conf
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.ops.dataset import DataSet
from deeplearning4j_tpu.parallel.mesh import make_mesh
from deeplearning4j_tpu.parallel.graph_wrapper import GraphDataParallelTrainer


def main():
    tiny = "--tiny" in sys.argv
    ndev = len(jax.devices())
    if tiny:
        conf = resnet_tiny_conf(num_classes=10, height=32, width=32)
        batch, img, classes = 8 * ndev, 32, 10
    else:
        conf = resnet50_conf(num_classes=1000)
        batch, img, classes = 128 * ndev, 224, 1000
    # init() keeps master params in f32; the bf16 cast happens inside the
    # jitted step
    net = ComputationGraph(conf, compute_dtype=jnp.bfloat16).init()
    trainer = GraphDataParallelTrainer(net, make_mesh(ndev))

    rng = np.random.default_rng(0)
    X = rng.normal(size=(batch, img, img, 3)).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rng.integers(0, classes, batch)]
    ds = DataSet(X, y)
    for step in range(5):
        t0 = time.perf_counter()
        trainer.fit_batch(ds)
        jax.block_until_ready(net.params)
        dt = time.perf_counter() - t0
        print(f"step {step}: {batch / dt:8.1f} img/s over {ndev} device(s)"
              f"  ({dt * 1e3:.0f} ms)")


if __name__ == "__main__":
    from deeplearning4j_tpu.ops.platform import configure_compilation_cache
    configure_compilation_cache()
    main()
