"""GravesLSTM character model (dl4j-examples GravesLSTMCharModellingExample;
BASELINE.md config #2): TBPTT training + temperature sampling with
rnnTimeStep-style stateful inference.

Run: python examples/char_rnn.py [path/to/corpus.txt]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from deeplearning4j_tpu.models import char_rnn_conf, CharacterIterator
from deeplearning4j_tpu.nn import MultiLayerNetwork

FALLBACK = ("the quick brown fox jumps over the lazy dog. "
            "pack my box with five dozen liquor jugs. ") * 200


def sample(net, it, seed_text="the ", n=120, temperature=0.8):
    rng = np.random.default_rng(0)
    net.rnn_clear_previous_state()
    # keep only seed characters the corpus vocabulary knows
    seed_text = "".join(ch for ch in seed_text if ch in it.char_to_idx) \
        or it.chars[0]
    out = list(seed_text)
    for ch in seed_text:
        x = np.zeros((1, len(it.chars)), np.float32)
        x[0, it.char_to_idx[ch]] = 1
        probs = net.rnn_time_step(x)[0]
    for _ in range(n):
        p = np.asarray(probs, np.float64) ** (1.0 / temperature)
        p /= p.sum()
        idx = rng.choice(len(p), p=p)
        out.append(it.chars[idx])
        x = np.zeros((1, len(it.chars)), np.float32)
        x[0, idx] = 1
        probs = net.rnn_time_step(x)[0]
    return "".join(out)


def main():
    text = open(sys.argv[1]).read() if len(sys.argv) > 1 else FALLBACK
    it = CharacterIterator(text, seq_length=50, batch_size=32)
    net = MultiLayerNetwork(
        char_rnn_conf(vocab_size=len(it.chars), hidden=200,
                      learning_rate=0.05)).init()
    for epoch in range(8):
        net.fit(it)
        print(f"epoch {epoch}: score={float(net.score_value):.4f}")
        print("  sample:", sample(net, it)[:100])


if __name__ == "__main__":
    from deeplearning4j_tpu.ops.platform import configure_compilation_cache
    configure_compilation_cache()
    main()
