"""In-graph A/B: flagship LM (B=32, T=512) with materialized attention vs
the short-T Pallas kernel forced through the helper seam (r5, VERDICT r4
item #1). Standalone op chains can mislead (fusion boundaries differ
in-graph); tokens/sec through the real fit path is the decision metric.

Usage: python scripts/perf_lm_attention_ab.py [g_heads q_split]
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402

from deeplearning4j_tpu.kernels.pallas_shortseq import short_attention  # noqa: E402
from deeplearning4j_tpu.models import (lm_batch_sparse,      # noqa: E402
                                       transformer_lm_conf)
from deeplearning4j_tpu.nn.graph import ComputationGraph     # noqa: E402
from deeplearning4j_tpu.nn import helpers                    # noqa: E402

V, B, T = 32_000, 32, 512
WARMUP, STEPS, RUNS = 5, 30, 3
G = int(sys.argv[1]) if len(sys.argv) > 1 else 16
QS = int(sys.argv[2]) if len(sys.argv) > 2 else 1


def measure_lm():
    conf = transformer_lm_conf(vocab_size=V, d_model=768, num_heads=12,
                               num_layers=12, max_length=T,
                               learning_rate=3e-4)
    net = ComputationGraph(conf, compute_dtype=jnp.bfloat16).init()
    rng = np.random.default_rng(0)
    x, y = lm_batch_sparse(rng.integers(0, V, (B, T + 1)))
    from deeplearning4j_tpu.ops.dataset import DataSet
    ds = DataSet(jax.device_put(jnp.asarray(x)),
                 jax.device_put(jnp.asarray(y)))
    for _ in range(WARMUP):
        net.fit_batch(ds)
    float(net.score_value)
    vals = []
    for _ in range(RUNS):
        t0 = time.perf_counter()
        for _ in range(STEPS):
            net.fit_batch(ds)
        float(net.score_value)
        vals.append(B * T * STEPS / (time.perf_counter() - t0))
    return float(np.median(vals)), vals


def main():
    print(f"device={jax.devices()[0].device_kind}  G={G} qs={QS}")
    # the lazy DEFAULT helper now routes T=512 to the short kernel (r5) —
    # the baseline leg must pin a short_t=False helper or it would measure
    # the kernel against itself
    from deeplearning4j_tpu.kernels.pallas_attention import \
        make_pallas_flash_helper
    snap0 = helpers.snapshot_helper("attention")
    helpers.register_helper(
        "attention", make_pallas_flash_helper(short_t=False),
        ("tpu",))
    helpers.enable_helper("attention")
    try:
        base, bvals = measure_lm()
    finally:
        helpers.restore_helper("attention", snap0)
    print(f"materialized attention: {base:,.0f} tokens/s  "
          f"({[f'{v:,.0f}' for v in bvals]})")

    def short_helper(conf, q, k, v, mask):
        if q.shape[1] > 512:
            return None
        return short_attention(q, k, v, causal=conf.causal, key_mask=mask,
                               g_heads=G, q_split=QS, interpret=False)

    snap = helpers.snapshot_helper("attention")
    helpers.register_helper("attention", short_helper, ("tpu",))
    helpers.enable_helper("attention")
    try:
        kern, kvals = measure_lm()
    finally:
        helpers.restore_helper("attention", snap)
    print(f"short-T Pallas kernel:  {kern:,.0f} tokens/s  "
          f"({[f'{v:,.0f}' for v in kvals]})")
    print(f"delta: {100.0 * (kern - base) / base:+.1f}%")


if __name__ == "__main__":
    from deeplearning4j_tpu.ops.platform import configure_compilation_cache
    configure_compilation_cache()
    main()
