"""Gradient-compression steady-state + round-dispatch overhead measurement
(VERDICT r2 item #7; SURVEY.md §5.8 DCN compression).

Runs local-steps DP with threshold-encoded delta sharing on the virtual
8-device CPU mesh and reports (a) the steady-state transmitted-element
fraction as a function of threshold — the sparse-regime claim of
parallel/compression.py holds when the threshold is chosen near the
per-round delta magnitude, exactly as its docstring instructs — and (b)
the host-side cost per round (python prep: stacking/padding/transfer)
on top of the compiled round program, the dispatch-overhead datum this
single-host environment can honestly produce.

Run: python scripts/perf_compression.py
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
from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper  # noqa


def _task(rng):
    conf = (NeuralNetConfiguration.Builder().seed(5).learning_rate(0.1)
            .updater("sgd").weight_init("xavier").activation("tanh").list()
            .layer(DenseLayer(n_out=64))
            .layer(DenseLayer(n_out=64))
            .layer(OutputLayer(n_out=3, loss="mcxent", activation="softmax"))
            .set_input_type(InputType.feed_forward(16)).build())
    net = MultiLayerNetwork(conf).init()
    X = rng.normal(size=(256, 16)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[(np.abs(X).sum(1) * 3).astype(int) % 3]
    batches = [DataSet(X[i * 32:(i + 1) * 32], y[i * 32:(i + 1) * 32])
               for i in range(8)]
    return net, batches


def main():
    k = 4
    print("threshold sweep (steady-state sent fraction, 60 epochs each):")
    for thr in (1e-3, 3e-3, 1e-2, 3e-2, 1e-1):
        net, batches = _task(np.random.default_rng(5))
        pw = (ParallelWrapper.Builder(net).workers(8)
              .averaging_frequency(k).gradient_compression(thr).build())
        fracs = []
        for _ in range(60):
            pw.fit(batches)
            fracs.append(float(pw.last_sent_fraction))
        print(f"  t={thr:7.0e}: steady sent fraction "
              f"{np.mean(fracs[-10:]):.4f}   final score "
              f"{float(net.score_value):.4f}")

    # host-side per-round overhead: pw._run_round (prep+stack+pad+dispatch)
    # vs the raw compiled round on pre-staged arrays
    net, batches = _task(np.random.default_rng(5))
    pw = (ParallelWrapper.Builder(net).workers(8).averaging_frequency(k)
          .gradient_compression(3e-2).build())
    pw.fit(batches)                      # build + warm the program
    rounds = 40
    t0 = time.perf_counter()
    for _ in range(rounds):
        pw._run_round(batches[:k])
    float(net.score_value)
    full = (time.perf_counter() - t0) / rounds

    import jax.numpy as jnp
    feats = np.stack([b.features for b in batches[:k]])
    labels = np.stack([b.labels for b in batches[:k]])
    feats = jnp.asarray(feats.reshape((k, 8, -1) + feats.shape[2:]))
    labels = jnp.asarray(labels.reshape((k, 8, -1) + labels.shape[2:]))
    sp, su, ss, sr = pw._stacked
    t0 = time.perf_counter()
    for _ in range(rounds):
        sp, su, ss, sr, score, sent = pw._jit_round(
            sp, su, ss, sr, feats, labels, None, None, net.iteration)
    float(score)
    prog = (time.perf_counter() - t0) / rounds
    print(f"\nround wall {full * 1e3:.1f} ms vs compiled program "
          f"{prog * 1e3:.1f} ms -> host prep/dispatch overhead "
          f"{(full - prog) * 1e3:.1f} ms/round "
          f"({(full - prog) / full * 100:.0f}% of the round)")


if __name__ == "__main__":
    from deeplearning4j_tpu.ops.platform import configure_compilation_cache
    configure_compilation_cache()
    main()
