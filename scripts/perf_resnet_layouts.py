"""VERDICT r4 item: the two untouched ResNet non-conv buckets, measured.

(a) Weight-staging copies: masters live in default layouts; conv fusions
want others, so each step pays relayout copies (copy_subtract_fusion etc.
in the xplane trace). The suggested fix — store masters in the compiled
executable's preferred layouts via jax.experimental.layout AUTO and
restage once at init — is implemented here AOT and measured end-to-end.

(b) BN/elementwise floor: chained microbenches of the residual add and
BN stat reductions at the hot [128,56,56,256] bf16 shape establish the
ACHIEVABLE bandwidth for 4-D tiled layouts (the r3 "4-5 ms floor" used
the 781 GB/s 1-D streaming anchor, which these shapes do not reach).

Run on the TPU backend: python scripts/perf_resnet_layouts.py
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout

from deeplearning4j_tpu.models import resnet50_conf
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.ops.platform import configure_compilation_cache

configure_compilation_cache()

BATCH = int(sys.argv[1]) if len(sys.argv) > 1 else 128

conf = resnet50_conf(num_classes=1000, height=224, width=224, channels=3)
net = ComputationGraph(conf, compute_dtype=jnp.bfloat16).init()
rng = np.random.default_rng(0)
X = jnp.asarray(rng.normal(size=(BATCH, 224, 224, 3)), jnp.bfloat16)
y = jnp.asarray(np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, BATCH)],
                jnp.bfloat16)
args = (net.params, net.updater_state, net.state, {"input": X}, {"fc": y},
        None, None, 0, {})
fn = net._make_train_step()


def run(step, p, u, n=20):
    r = step(p, u, *args[2:])
    float(r[3])
    t0 = time.perf_counter()
    for _ in range(n):
        p, u, s, sc = step(p, u, *args[2:])
    float(sc)
    return BATCH * n / (time.perf_counter() - t0)


print(f"baseline jit: {run(jax.jit(fn), net.params, net.updater_state):.0f} "
      "img/s")

FA = Format(Layout.AUTO)
compiled = jax.jit(
    fn, in_shardings=(FA, FA, None, None, None, None, None, None, None),
    out_shardings=(FA, FA, None, None)).lower(*args).compile()
inf = compiled.input_formats
outf = compiled.output_formats
flat_in = jax.tree_util.tree_leaves(inf[0][0])
flat_out = jax.tree_util.tree_leaves(outf[0])
mism = sum(a.layout != b.layout for a, b in zip(flat_in, flat_out))
print(f"param in/out layout mismatches: {mism} of {len(flat_in)} "
      "(0 = stable across steps without donation)")
pA = jax.device_put(net.params, inf[0][0])
uA = jax.device_put(net.updater_state, inf[0][1])
print(f"AUTO master layouts (restaged once): {run(compiled, pA, uA):.0f} "
      "img/s")

# (b) achievable-bandwidth anchors at the hot shape
a = jnp.asarray(rng.normal(size=(128, 56, 56, 256)), jnp.bfloat16)
b = jnp.asarray(rng.normal(size=(128, 56, 56, 256)), jnp.bfloat16)


def chain_add(a, b):
    out, _ = jax.lax.scan(lambda c, _: (c + b, None), a, None, length=50)
    return jnp.sum(out.astype(jnp.float32))


f = jax.jit(chain_add)
float(f(a, b))
t0 = time.perf_counter()
float(f(a, b))
dt = (time.perf_counter() - t0) / 50
gb = a.size * 2 * 3 / 1e9
print(f"residual add anchor: {dt*1000:.3f} ms ({gb/dt:.0f} GB/s effective)")


def chain_red(a):
    def body(c, _):
        s = jnp.sum(a.astype(jnp.float32), axis=(0, 1, 2))
        s2 = jnp.sum(jnp.square(a.astype(jnp.float32)), axis=(0, 1, 2))
        return c + s[0] + s2[0], None
    out, _ = jax.lax.scan(body, jnp.float32(0), None, length=50)
    return out


g = jax.jit(chain_red)
float(g(a))
t0 = time.perf_counter()
float(g(a))
dt = (time.perf_counter() - t0) / 50
gb = a.size * 2 / 1e9
print(f"BN stat reduce anchor (sum+sumsq): {dt*1000:.3f} ms "
      f"({gb/dt:.0f} GB/s read)")
