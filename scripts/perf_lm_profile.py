"""Capture an XLA profile of the transformer-LM train step (bench.py
``BENCH_MODE=transformer`` program: GPT-2-small-ish 12x768, vocab 32k) and
dump the xplane for scripts/perf_opbreakdown.py.

Usage: python scripts/perf_lm_profile.py [T] [BATCH]
"""
import glob
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.models import lm_batch, lm_batch_sparse, transformer_lm_conf
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.ops.dataset import DataSet
from deeplearning4j_tpu.ops.platform import configure_compilation_cache

configure_compilation_cache()

if os.environ.get("LM_PROFILE_PALLAS"):
    from deeplearning4j_tpu.kernels.pallas_attention import \
        register_pallas_flash_attention
    register_pallas_flash_attention(min_seq_len=256)

T = int(sys.argv[1]) if len(sys.argv) > 1 else 512
BATCH = int(sys.argv[2]) if len(sys.argv) > 2 else 32
V = 32_000
LOGDIR = "/tmp/jaxprof"

conf = transformer_lm_conf(vocab_size=V, d_model=768, num_heads=12,
                           num_layers=12, max_length=T, learning_rate=3e-4)
net = ComputationGraph(conf, compute_dtype=jnp.bfloat16).init()
rng = np.random.default_rng(0)
if os.environ.get("LM_PROFILE_ONEHOT"):
    x, y = lm_batch(rng.integers(0, V, (BATCH, T + 1)), V)
    ds = DataSet(jax.device_put(jnp.asarray(x)),
                 jax.device_put(jnp.asarray(y, jnp.bfloat16)))
else:
    x, y = lm_batch_sparse(rng.integers(0, V, (BATCH, T + 1)))
    ds = DataSet(jax.device_put(jnp.asarray(x)),
                 jax.device_put(jnp.asarray(y)))

for _ in range(3):
    net.fit_batch(ds)
float(net.score_value)

jax.profiler.start_trace(LOGDIR)
for _ in range(5):
    net.fit_batch(ds)
float(net.score_value)
jax.profiler.stop_trace()

print("xplane files:",
      glob.glob(LOGDIR + "/**/*.xplane.pb", recursive=True)[-3:])
