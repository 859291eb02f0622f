"""Conv roofline microbenchmark (VERDICT r2 item #1).

Separates the CHIP's realizable ceiling from the PROGRAM's realized
throughput: every distinct ResNet-50 conv shape is timed standalone (fwd and
fwd+bwd), best-of over layout/dtype variants, against a plain big-matmul
anchor on the same chip — the number XLA can demonstrably reach when nothing
but one MXU op is in flight.

Honest sync protocol (BASELINE.md r2): a host transfer of a device scalar
is the execution barrier, so every timed program reduces to a scalar that
is float()-ed.

Usage:  python scripts/perf_conv_roofline.py [--quick]
Writes: prints a per-shape table and a JSON summary line.
"""

from __future__ import annotations

import json
import sys
import time

import os

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _sync(x):
    return float(x)


def time_fn(fn, *args, iters=20, warmup=3):
    """Pipelined timing: queue `iters` async dispatches, sync ONCE on the
    last scalar. Device programs on one stream run in order, so the final
    host transfer bounds them all; the host round-trip (measured by
    rtt_floor()) is amortized to RTT/iters instead of dominating every
    sample the way per-call float() syncing does."""
    for _ in range(warmup):
        r = fn(*args)
    _sync(r)
    t0 = time.perf_counter()
    rs = [fn(*args) for _ in range(iters)]
    s = _sync(rs[-1])
    dt = (time.perf_counter() - t0) / iters
    return dt, s


def rtt_floor(iters=20):
    """Per-call host<->device round-trip: a no-op program float()-ed every
    call — the latency every UNpipelined measurement pays."""
    x = jnp.zeros(())

    @jax.jit
    def nop(x):
        return x + 1.0

    _sync(nop(x))
    t0 = time.perf_counter()
    for _ in range(iters):
        _sync(nop(x))
    return (time.perf_counter() - t0) / iters


# ---------------------------------------------------------------- shapes
def resnet50_conv_shapes(batch=128, hw=224):
    """Distinct (name, H, W, Cin, Cout, k, stride) convs of ResNet-50 at
    the bench config (models/resnet.py; reference ConvolutionLayer.java:172
    hot loop). H/W are INPUT spatial dims."""
    shapes = [("stem7x7/2", 224, 3, 64, 7, 2, 1)]
    stages = [  # (out_hw, mid, out, n_blocks)
        (56, 64, 256, 3), (28, 128, 512, 4),
        (14, 256, 1024, 6), (7, 512, 2048, 3)]
    prev_out = 64   # after stem pool
    for i, (hw_s, mid, out, nb) in enumerate(stages):
        in_hw = hw_s * 2 if i > 0 else hw_s
        stride = 2 if i > 0 else 1
        # first block: reduce (maybe strided), projection; every block:
        # 3x3 + expand; later blocks: reduce from `out`. count = per-step
        # occurrences, so occurrence-weighted sums compare against the
        # profiled conv bucket of the full training step
        shapes.append((f"s{i}_reduce1x1/{stride}", in_hw, prev_out, mid, 1,
                       stride, 1))
        shapes.append((f"s{i}_proj1x1/{stride}", in_hw, prev_out, out, 1,
                       stride, 1))
        shapes.append((f"s{i}_3x3", hw_s, mid, mid, 3, 1, nb))
        shapes.append((f"s{i}_expand1x1", hw_s, mid, out, 1, 1, nb))
        if nb > 1:
            shapes.append((f"s{i}_reduce1x1", hw_s, out, mid, 1, 1, nb - 1))
        prev_out = out
    return [(n, h, h, ci, co, k, st, c)
            for (n, h, ci, co, k, st, c) in shapes]


def conv_flops(batch, h, w, cin, cout, k, stride):
    oh, ow = (h + stride - 1) // stride, (w + stride - 1) // stride
    return 2.0 * batch * oh * ow * cin * cout * k * k


# ---------------------------------------------------------------- programs
# Per-program launch overhead was ~4-6 ms on the r2 installation even when
# dispatches are pipelined (measured: every single-op program costs >=4 ms
# wall regardless of FLOPs, while 8 chained 4096^3 matmuls in ONE program
# run at 123 TF/s). So each shape is measured as a CHAIN of convs inside one
# jit — the within-program number is what the fused training step actually
# sees. A scalar carry multiplies the input each round to defeat hoisting.
CHAIN = 10


def make_conv_fwd(k, stride, dtype):
    @jax.jit
    def fwd(x, w):
        acc = jnp.asarray(1.0, jnp.float32)
        for _ in range(CHAIN):
            xe = x * (acc * 1e-24 + 1.0).astype(x.dtype)
            y = jax.lax.conv_general_dilated(
                xe, w, (stride, stride), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            acc = acc + jnp.sum(y.astype(jnp.float32))
        return acc
    return fwd


def make_conv_fwdbwd(k, stride, dtype):
    def loss(x, w):
        acc = jnp.asarray(1.0, jnp.float32)
        for _ in range(CHAIN):
            xe = x * (acc * 1e-24 + 1.0).astype(x.dtype)
            y = jax.lax.conv_general_dilated(
                xe, w, (stride, stride), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            acc = acc + jnp.sum(y.astype(jnp.float32))
        return acc

    @jax.jit
    def both(x, w):
        l, (gx, gw) = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
        return l + jnp.sum(gx.astype(jnp.float32)[0, 0, 0]) + \
            jnp.sum(gw.astype(jnp.float32)[0, 0])
    return both


def matmul_anchor(n=8192, dtype=jnp.bfloat16, iters=20):
    """Plain [n,n]@[n,n] — the chip's demonstrable MXU ceiling."""
    a = jnp.asarray(np.random.default_rng(0).normal(0, 1, (n, n)), dtype)
    b = jnp.asarray(np.random.default_rng(1).normal(0, 1, (n, n)), dtype)

    @jax.jit
    def mm(a, b):
        return jnp.sum((a @ b).astype(jnp.float32)[0])

    dt, _ = time_fn(mm, a, b, iters=iters)
    return 2.0 * n ** 3 / dt / 1e12, dt


def chained_matmul_anchor(n=4096, chain=8, dtype=jnp.bfloat16, iters=20):
    """Dispatch-amortized anchor: `chain` dependent matmuls per program —
    isolates per-program dispatch/sync overhead from MXU throughput."""
    a = jnp.asarray(np.random.default_rng(0).normal(0, 0.01, (n, n)), dtype)

    @jax.jit
    def mm(a):
        x = a
        for _ in range(chain):
            x = (x @ a).astype(dtype) * jnp.asarray(1e-2, dtype)
        return jnp.sum(x.astype(jnp.float32)[0])

    dt, _ = time_fn(mm, a, iters=iters)
    return 2.0 * n ** 3 * chain / dt / 1e12, dt


def main():
    quick = "--quick" in sys.argv
    batch = 64 if quick else 128
    rng = np.random.default_rng(7)
    print(f"devices: {jax.devices()}  batch={batch}")

    rtt = rtt_floor()
    print(f"host round-trip floor (noop + float()): {rtt*1e3:.1f} ms")

    anchors = {"rtt_ms": rtt * 1e3}
    for n in ([4096] if quick else [4096, 8192]):
        tf, dt = matmul_anchor(n)
        anchors[f"matmul{n}_bf16"] = tf
        print(f"anchor matmul {n}^3 bf16: {tf:8.1f} TFLOP/s ({dt*1e3:.2f} ms)")
    tf, dt = chained_matmul_anchor()
    anchors["matmul4096x8_bf16"] = tf
    print(f"anchor chained 8x4096^3 bf16: {tf:8.1f} TFLOP/s ({dt*1e3:.2f} ms)")
    tf, dt = matmul_anchor(4096, jnp.float32)
    anchors["matmul4096_f32"] = tf
    print(f"anchor matmul 4096^3 f32: {tf:8.1f} TFLOP/s ({dt*1e3:.2f} ms)")

    rows = []
    total_fwd_ms = total_bwd_ms = total_tflop = 0.0
    for (name, h, w, cin, cout, k, stride, count) in \
            resnet50_conv_shapes(batch):
        x = jnp.asarray(rng.normal(0, 1, (batch, h, w, cin)), jnp.bfloat16)
        wgt = jnp.asarray(rng.normal(0, 0.05, (k, k, cin, cout)),
                          jnp.bfloat16)
        fl = conv_flops(batch, h, w, cin, cout, k, stride)
        dt_f, _ = time_fn(make_conv_fwd(k, stride, jnp.bfloat16), x, wgt,
                          iters=5 if quick else 10)
        dt_b, _ = time_fn(make_conv_fwdbwd(k, stride, jnp.bfloat16), x, wgt,
                          iters=5 if quick else 10)
        dt_f /= CHAIN                   # per-conv, launch amortized away
        dt_b /= CHAIN
        tf_f = fl / dt_f / 1e12
        tf_b = 3 * fl / dt_b / 1e12     # bwd = 2x fwd FLOPs
        rows.append({"shape": name, "h": h, "cin": cin, "cout": cout,
                     "k": k, "stride": stride, "count": count,
                     "gflop": fl / 1e9,
                     "fwd_ms": dt_f * 1e3, "fwd_tflops": tf_f,
                     "fwdbwd_ms": dt_b * 1e3, "fwdbwd_tflops": tf_b})
        total_fwd_ms += count * dt_f * 1e3
        total_bwd_ms += count * dt_b * 1e3
        total_tflop += count * 3 * fl / 1e12
        print(f"{name:20s} x{count} {h:4d}x{h:<4d} {cin:4d}->{cout:<4d}"
              f" k{k} s{stride}"
              f"  fwd {dt_f*1e3:7.2f} ms {tf_f:7.1f} TF/s"
              f"  fwd+bwd {dt_b*1e3:7.2f} ms {tf_b:7.1f} TF/s")

    print(f"\noccurrence-weighted: sum fwd {total_fwd_ms:.1f} ms   "
          f"sum fwd+bwd {total_bwd_ms:.1f} ms   "
          f"({total_tflop:.2f} TFLOP total fwd+bwd)")
    print(json.dumps({"anchors": anchors, "convs": rows,
                      "sum_fwdbwd_ms": total_bwd_ms}))


if __name__ == "__main__":
    from deeplearning4j_tpu.ops.platform import configure_compilation_cache
    configure_compilation_cache()
    main()
