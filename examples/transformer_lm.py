"""Decoder-only transformer language model on character data — the TPU-era
long-context flagship (models/transformer.py). Trains a small causal LM on
a repetitive corpus and samples from it; --sp runs the same model
sequence-parallel over a virtual 8-device mesh (ring attention over the
sp axis; run with JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8).

Run: python examples/transformer_lm.py [--sp]
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deeplearning4j_tpu.models import generate, lm_batch, transformer_lm_conf
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.ops.dataset import DataSet

CORPUS = ("the quick brown fox jumps over the lazy dog. " * 40)


def main():
    chars = sorted(set(CORPUS))
    stoi = {c: i for i, c in enumerate(chars)}
    ids = np.asarray([stoi[c] for c in CORPUS], np.int32)
    V, T, B = len(chars), 64, 16

    net = ComputationGraph(transformer_lm_conf(
        vocab_size=V, d_model=64, num_heads=4, num_layers=2,
        max_length=T, learning_rate=3e-3, seed=7)).init()
    print(f"vocab {V}, params {net.num_params():,}")

    rng = np.random.default_rng(0)
    trainer = None
    if "--sp" in sys.argv:
        from deeplearning4j_tpu.parallel.mesh import make_mesh
        from deeplearning4j_tpu.parallel.sequence import \
            GraphSequenceParallelTrainer
        trainer = GraphSequenceParallelTrainer(
            net, make_mesh(axis_names=("sp",)))
        fit = trainer.fit_batch
        print(f"sequence-parallel over {trainer.mesh.shape}")
    else:
        fit = net.fit_batch

    for step in range(200):
        starts = rng.integers(0, len(ids) - T - 1, B)
        seq = np.stack([ids[s:s + T + 1] for s in starts])
        x, y = lm_batch(seq, V)
        fit(DataSet(x, y))
        if step % 50 == 0:
            print(f"step {step:3d} loss {float(net.score_value):.3f}")

    if trainer is not None:
        # sampling feeds ragged contexts; close() hands the attention slot
        # back to whatever was registered before (the flash default)
        trainer.close()

    prompt = [stoi[c] for c in "the quick "]
    out = generate(net, prompt, 40, temperature=0)
    print("sample (no-cache):", "".join(chars[i] for i in out))

    # the serving path: KV-cache decode — same greedy continuation, O(T)
    # per emitted token instead of a full O(T^2) forward
    from deeplearning4j_tpu.models import TransformerDecoder
    dec = TransformerDecoder(net)
    cached = dec.generate([prompt], 40, temperature=0.0)[0]
    print("sample (kv-cache):", "".join(chars[i] for i in cached))
    assert list(cached) == list(out), "cache/no-cache divergence"


if __name__ == "__main__":
    from deeplearning4j_tpu.ops.platform import configure_compilation_cache
    configure_compilation_cache()
    main()
