"""Training with the browser UI attached (reference dl4j-ui examples):
StatsListener -> InMemoryStatsStorage -> UIServer at http://localhost:9000.

Run: python examples/training_ui.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from deeplearning4j_tpu.models import lenet_conf
from deeplearning4j_tpu.nn import MultiLayerNetwork
from deeplearning4j_tpu.datasets import MnistDataSetIterator
from deeplearning4j_tpu.ui import InMemoryStatsStorage, StatsListener
from deeplearning4j_tpu.ui.server import UIServer


def main():
    storage = InMemoryStatsStorage()
    UIServer.get_instance().attach(storage)
    print("UI at http://localhost:9000")

    net = MultiLayerNetwork(lenet_conf(learning_rate=0.02)).init()
    net.set_listeners(StatsListener(storage, update_frequency=10))
    net.fit(MnistDataSetIterator(128, 8000), num_epochs=5)
    print("done; UI stays up (ctrl-c to exit)")
    import time
    while True:
        time.sleep(60)


if __name__ == "__main__":
    from deeplearning4j_tpu.ops.platform import configure_compilation_cache
    configure_compilation_cache()
    main()
