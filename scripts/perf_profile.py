"""Capture an XLA profile of the ResNet-50 train step and print the op-type
time breakdown (uses tensorboard_plugin_profile's converters, no UI)."""
import glob, json, os, sys
import numpy as np
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax, jax.numpy as jnp

from deeplearning4j_tpu.models import resnet50_conf
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.ops.platform import configure_compilation_cache

configure_compilation_cache()

BATCH = int(sys.argv[1]) if len(sys.argv) > 1 else 128
LOGDIR = "/tmp/jaxprof"

conf = resnet50_conf(num_classes=1000, height=224, width=224, channels=3)
net = ComputationGraph(conf, compute_dtype=jnp.bfloat16).init()
net.params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), net.params)
rng = np.random.default_rng(0)
X = jnp.asarray(rng.normal(size=(BATCH, 224, 224, 3)), jnp.bfloat16)
y = jnp.asarray(np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, BATCH)], jnp.bfloat16)
inputs, labels = {"input": X}, {"fc": y}

step = jax.jit(net._make_train_step())
args = (net.params, net.updater_state, net.state, inputs, labels, None, None, 0, {})
r = step(*args)
jax.block_until_ready(r[3])

jax.profiler.start_trace(LOGDIR)
for _ in range(5):
    r = step(*args)
jax.block_until_ready(r[3])
jax.profiler.stop_trace()

xspaces = glob.glob(LOGDIR + "/**/*.xplane.pb", recursive=True)
print("xplane files:", xspaces)
try:
    from tensorboard_plugin_profile.convert import raw_to_tool_data as rtd
    for tool in ("op_profile", "overview_page^"):
        try:
            data, _ = rtd.xspace_to_tool_data(xspaces, tool, {})
            out = f"/tmp/prof_{tool.strip('^')}.json"
            with open(out, "w") as f:
                f.write(data if isinstance(data, str) else data.decode())
            print("wrote", out)
        except Exception as e:
            print(tool, "failed:", e)
except Exception as e:
    print("converter import failed:", e)
