"""Decompose ResNet-50 step time on the live chip: forward only,
forward+backward, full train step (fwd+bwd+updater). Also prints XLA
cost-analysis FLOPs -> measured MFU."""
import time, json, sys
import numpy as np
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax, jax.numpy as jnp

from deeplearning4j_tpu.models import resnet50_conf
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.ops.dataset import DataSet
from deeplearning4j_tpu.ops.platform import configure_compilation_cache

configure_compilation_cache()

BATCH = int(sys.argv[1]) if len(sys.argv) > 1 else 128
IMG = 224

conf = resnet50_conf(num_classes=1000, height=IMG, width=IMG, channels=3)
net = ComputationGraph(conf, compute_dtype=jnp.bfloat16).init()
net.params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), net.params)

rng = np.random.default_rng(0)
X = jnp.asarray(rng.normal(size=(BATCH, IMG, IMG, 3)), jnp.bfloat16)
y = jnp.asarray(np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, BATCH)], jnp.bfloat16)
inputs = {"input": X}
labels = {"fc": y}


def timeit(fn, *args, n=15, warmup=3):
    for _ in range(warmup):
        r = fn(*args)
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / n


# forward only
fwd = jax.jit(lambda p, s, x: net._forward(net._cast_params(p), s, x,
                                           train=True, rng=jax.random.PRNGKey(0))[0]["fc"])
t_fwd = timeit(fwd, net.params, net.state, inputs)

# fwd+bwd
def lossfn(p, s):
    return net._loss(p, s, inputs, labels, jax.random.PRNGKey(0))
grad = jax.jit(lambda p, s: jax.value_and_grad(lossfn, has_aux=True)(p, s))
t_bwd = timeit(grad, net.params, net.state)

# full step (non-donating copy so we can re-run on same buffers)
step = jax.jit(net._make_train_step())
t_full = timeit(step, net.params, net.updater_state, net.state, inputs, labels,
                None, None, 0, {})

# cost analysis of the full step
try:
    lowered = jax.jit(net._make_train_step()).lower(
        net.params, net.updater_state, net.state, inputs, labels, None, None, 0, {})
    ca = lowered.compile().cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops = ca.get("flops", float("nan"))
except Exception as e:
    flops = float("nan")

print(json.dumps({
    "batch": BATCH,
    "t_fwd_ms": round(t_fwd * 1e3, 2),
    "t_fwdbwd_ms": round(t_bwd * 1e3, 2),
    "t_full_ms": round(t_full * 1e3, 2),
    "img_per_s_full": round(BATCH / t_full, 1),
    "xla_flops_per_step": None if np.isnan(flops) else flops,
    "tflops_per_s": None if np.isnan(flops) else round(flops / t_full / 1e12, 1),
}))
