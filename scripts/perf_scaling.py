"""Weak-scaling harness on the virtual N-device CPU mesh (BASELINE config
#5 stand-in until multi-chip hardware exists): fixed per-device batch,
devices 1 -> 2 -> 4 -> 8, parallel efficiency of the sync-DP (GSPMD grad
all-reduce) and local-steps (shard_map + pmean averaging round) programs.

Weak scaling: ideal is CONSTANT wall time per step as devices grow (work
grows with the mesh); efficiency(n) = t(1) / t(n). This bounds the
collective + program overhead of the DP programs — the same programs the
driver dry-runs and that ride ICI on real hardware.

Run: python scripts/perf_scaling.py   (forces an 8-device CPU platform)
"""
import os
import sys
import time

flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
         if "host_platform_device_count" not in f]
flags.append("--xla_force_host_platform_device_count=8")
os.environ["XLA_FLAGS"] = " ".join(flags)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                              # noqa: E402
jax.config.update("jax_platforms", "cpu")

import numpy as np                                      # noqa: E402

from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,  # noqa
                                   MultiLayerNetwork)
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer  # noqa
from deeplearning4j_tpu.ops.dataset import DataSet      # noqa: E402
from deeplearning4j_tpu.parallel.mesh import make_mesh  # noqa: E402
from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper  # noqa

PER_DEV_BATCH = 64
HIDDEN = 512
N_IN, N_OUT = 256, 16
STEPS = 30


def _net(seed=7):
    conf = (NeuralNetConfiguration.Builder().seed(seed).learning_rate(0.05)
            .updater("adam").weight_init("xavier").activation("relu").list()
            .layer(DenseLayer(n_out=HIDDEN))
            .layer(DenseLayer(n_out=HIDDEN))
            .layer(OutputLayer(n_out=N_OUT, loss="mcxent",
                               activation="softmax"))
            .set_input_type(InputType.feed_forward(N_IN)).build())
    return MultiLayerNetwork(conf).init()


def _batches(n_dev, k=1):
    rng = np.random.default_rng(3)
    out = []
    for _ in range(k):
        X = rng.normal(size=(PER_DEV_BATCH * n_dev, N_IN)).astype(np.float32)
        y = np.eye(N_OUT, dtype=np.float32)[
            rng.integers(0, N_OUT, PER_DEV_BATCH * n_dev)]
        out.append(DataSet(X, y))
    return out


def measure(mode: str, n_dev: int) -> float:
    net = _net()
    freq = 1 if mode == "sync" else 2
    pw = (ParallelWrapper.Builder(net).mesh(make_mesh(n_dev))
          .averaging_frequency(freq).build())
    data = _batches(n_dev, k=freq)
    pw.fit(data)                       # compile
    float(net.score_value)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        pw.fit(data)
    float(net.score_value)
    return (time.perf_counter() - t0) / (STEPS * freq)


def main():
    print(f"weak scaling, per-device batch {PER_DEV_BATCH}, "
          f"MLP {N_IN}-{HIDDEN}-{HIDDEN}-{N_OUT}, {STEPS} rounds")
    for mode in ("sync", "local-steps"):
        t1 = None
        for n in (1, 2, 4, 8):
            t = measure(mode, n)
            t1 = t1 or t
            print(f"  {mode:11s} n={n}: {t*1000:7.2f} ms/step  "
                  f"efficiency {t1/t:5.1%}  "
                  f"({PER_DEV_BATCH*n/t:,.0f} ex/s)")


if __name__ == "__main__":
    from deeplearning4j_tpu.ops.platform import configure_compilation_cache
    configure_compilation_cache()
    main()
