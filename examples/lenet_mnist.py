"""LeNet on MNIST — the canonical first example (dl4j-examples
LenetMnistExample; BASELINE.md config #1).

Run: python examples/lenet_mnist.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deeplearning4j_tpu.datasets import MnistDataSetIterator
from deeplearning4j_tpu.models import lenet_conf
from deeplearning4j_tpu.nn import MultiLayerNetwork
from deeplearning4j_tpu.optimize.listeners import (PerformanceListener,
                                                   ScoreIterationListener)


def main():
    net = MultiLayerNetwork(lenet_conf(learning_rate=0.02)).init()
    net.set_listeners(ScoreIterationListener(50), PerformanceListener(50))
    net.fit(MnistDataSetIterator(128, 8000), num_epochs=2)
    ev = net.evaluate(MnistDataSetIterator(256, 1000, train=False))
    print(ev.stats())


if __name__ == "__main__":
    from deeplearning4j_tpu.ops.platform import configure_compilation_cache
    configure_compilation_cache()
    main()
