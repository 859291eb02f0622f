"""Short-T attention kernel shootout at the flagship LM shape (r5,
VERDICT r4 item #1).

Measures the standalone attention op — forward and forward+backward — at
B=32, H=12, T=512, D=64 bf16 causal (the B=32/T=512 fit-path shape whose
materialized bucket is 20.2 ms/step over 12 layers, BASELINE.md r4):

- materialized: the SelfAttentionLayer built-in path (einsum + where +
  softmax + einsum), exactly as the layer traces it
- general: kernels/pallas_attention.py (streaming flash pair; one k block
  at this shape)
- short/G=n: kernels/pallas_shortseq.py whole-block kernel, G heads per
  grid step

Protocol (BASELINE.md r3 measurement rules): N_CHAIN dependent iterations
inside ONE jitted program (per-dispatch timing measures the dispatch, not
the kernel), honest sync via a float() host transfer, median of repeats.

Usage: python scripts/perf_attention_short.py [fwd|bwd|all]
"""

import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deeplearning4j_tpu.kernels.pallas_attention import pallas_flash_attention
from deeplearning4j_tpu.kernels.pallas_shortseq import short_attention

B, T, H, D = 32, 512, 12, 64
# slope protocol: per-op time = (wall(N_LONG) - wall(N_SHORT)) / (diff) —
# the fixed dispatch+sync floor cancels out (BASELINE.md r3
# measurement rule; a single 24-op chain buried every variant under
# ~4 ms/op of dispatch artifact)
N_SHORT = 6
N_LONG = 54
REPEATS = 5
CAUSAL = True


def materialized(q, k, v):
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, q.dtype))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    neg = jnp.asarray(-1e30, q.dtype)
    cmask = jnp.tril(jnp.ones((T, T), bool))
    logits = jnp.where(cmask[None, None], logits, neg)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def chain_fwd(fn, n):
    @jax.jit
    def run(q, k, v):
        for _ in range(n):
            o = fn(q, k, v)
            q = q + jnp.asarray(0.001, q.dtype) * o
        return jnp.sum(q[0, 0, 0].astype(jnp.float32))
    return run


def chain_bwd(fn, n):
    def loss(q, k, v):
        o = fn(q, k, v)
        return jnp.sum((o.astype(jnp.float32)) ** 2) * 1e-6

    grad = jax.grad(loss, argnums=(0, 1, 2))

    @jax.jit
    def run(q, k, v):
        for _ in range(n):
            gq, gk, gv = grad(q, k, v)
            eps = jnp.asarray(1e-4, q.dtype)
            q = q - eps * gq.astype(q.dtype)
            k = k - eps * gk.astype(q.dtype)
            v = v - eps * gv.astype(q.dtype)
        return jnp.sum(q[0, 0, 0].astype(jnp.float32))
    return run


def _walls(run, q, k, v):
    float(run(q, k, v))                          # compile + warm
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        float(run(q, k, v))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def bench(name, chain, fn, q, k, v):
    try:
        w_short = _walls(chain(fn, N_SHORT), q, k, v)
        w_long = _walls(chain(fn, N_LONG), q, k, v)
        per_op = (w_long - w_short) / (N_LONG - N_SHORT)
        print(f"{name:28s} {per_op * 1e6:9.1f} us/op   "
              f"(walls {w_short * 1e3:7.1f} / {w_long * 1e3:7.1f} ms)",
              flush=True)
        return per_op
    except Exception as e:  # noqa: BLE001 — shootout must report all rows
        print(f"{name:28s} FAILED: {type(e).__name__}: {e}", flush=True)
        return None


def main():
    mode = sys.argv[1] if len(sys.argv) > 1 else "all"
    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(rng.normal(size=(B, T, H, D)) * 0.3,
                             jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    print(f"shape B={B} T={T} H={H} D={D} bf16 causal={CAUSAL} "
          f"chains={N_SHORT}/{N_LONG} device={jax.devices()[0].device_kind}")

    variants = [("materialized", materialized),
                ("general-pallas", functools.partial(
                    pallas_flash_attention, causal=CAUSAL,
                    q_block=512, k_block=512, interpret=False))]
    for g in (2, 4, 16):
        for qs in (-1, 1, 4, 8):
            if (B * H) % g == 0:
                variants.append((f"short/G={g}/qs={qs}", functools.partial(
                    short_attention, causal=CAUSAL, g_heads=g, q_split=qs,
                    interpret=False)))
    only = os.environ.get("VARIANTS")
    if only:
        keep = only.split(",")
        variants = [(n, f) for n, f in variants
                    if any(pat in n for pat in keep)]

    results = {}
    if mode in ("fwd", "all"):
        print("--- forward ---")
        for name, fn in variants:
            results[("fwd", name)] = bench(name, chain_fwd, fn, q, k, v)
    if mode in ("bwd", "all"):
        print("--- forward+backward ---")
        for name, fn in variants:
            results[("bwd", name)] = bench(name, chain_bwd, fn, q, k, v)

    flops_fwd = 2 * 2 * B * H * T * T * D
    for (m, name), sec in results.items():
        if sec:
            f = flops_fwd * (3.5 if m == "bwd" else 1)
            print(f"{m} {name:24s} ~{f / sec / 1e12:6.1f} TF/s")


if __name__ == "__main__":
    from deeplearning4j_tpu.ops.platform import configure_compilation_cache
    configure_compilation_cache()
    main()
