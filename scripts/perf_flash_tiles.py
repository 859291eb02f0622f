"""Tile sweep of the training flash-attention kernels on the real backend
(PR 30): device time of a forward and of a forward + backward attention
layer at one shape, for grid tiles [QB, KB] and row blocks of SUB rows, so
that ``kernels/pallas_attention.py``'s ``BLOCK`` / ``SUB`` rest on a reading.

Each variant runs a chain of ``--layers`` attention layers on [B, T, H·Dh]
activations (the layer's own layout; o feeds the next q) inside ONE jitted
call, timed on the host clock around ``block_until_ready`` — one call is
many milliseconds of device work, so dispatch does not show. Where
``_export/parent`` holds a checkout of the parent commit (``git archive
<parent> | tar -x -C _export/parent``) its kernels run the same chain as
the baseline, with the [BH, T, Dh] transposes they need.

    chiprun -- python scripts/perf_flash_tiles.py
    chiprun -- python scripts/perf_flash_tiles.py --heads 20 --batch 16 --masked

Prints one JSON line per variant and writes them to
``chiprun_out/flash_tiles.json``. Fails without a TPU.
"""

import argparse
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from deeplearning4j_tpu.kernels import pallas_attention as pa  # noqa: E402

VARIANTS = [(1024, 1024, 256), (1024, 1024, 128), (1024, 1024, 512),
            (1024, 1024, 1024), (512, 512, 256), (512, 512, 128),
            (512, 512, 512), (1024, 512, 256), (256, 256, 256)]


def parent_module():
    path = os.path.join(HERE, "_export", "parent", "deeplearning4j_tpu",
                        "kernels", "pallas_attention.py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        "deeplearning4j_tpu.kernels.parent_pallas_attention", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def chain(attend, layers):
    def fwd(q, k, v, km):
        for _ in range(layers):
            q = attend(q, k, v, km)
        return q

    def loss(q, k, v, km):
        return jnp.sum(fwd(q, k, v, km).astype(jnp.float32) ** 2)
    return jax.jit(fwd), jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


def timed(fn, args, reps):
    out = fn(*args)
    jax.block_until_ready(out)
    best = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best.append(time.perf_counter() - t0)
    return float(np.median(best)), out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--masked", action="store_true")
    ap.add_argument("--not-causal", action="store_true")
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("perf_flash_tiles: no TPU; a CPU timing says nothing here")
    b, t, h, d = a.batch, a.seq, a.heads, a.head_dim
    causal = not a.not_causal
    g = pa.heads_per_tile(h, d)
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(b, t, h * d)) * 0.5,
                           jnp.bfloat16) for _ in range(3))
    km = None
    if a.masked:
        m = np.ones((b, t), np.float32)
        m[::2, t - t // 4:] = 0.0
        km = jnp.asarray(m)
    rows, first = [], None

    def report(name, attend):
        nonlocal first
        fwd, grad = chain(attend, a.layers)
        try:
            tf, out = timed(fwd, (q, k, v, km), a.reps)
            tg, _ = timed(grad, (q, k, v, km), a.reps)
        except Exception as e:   # noqa: BLE001 — a variant may not lower
            row = {"variant": name, "error": f"{type(e).__name__}: "
                   f"{str(e)[:300]}"}
        else:
            if first is None:
                first = out
            row = {"variant": name,
                   "fwd_ms_a_layer": 1e3 * tf / a.layers,
                   "fwd_bwd_ms_a_layer": 1e3 * tg / a.layers,
                   "max_abs_diff_to_first": float(jnp.max(jnp.abs(
                       out.astype(jnp.float32)
                       - first.astype(jnp.float32))))}
        rows.append(row)
        print(json.dumps(row), flush=True)

    old = parent_module()
    if old is not None:
        def old_layer(q, k, v, km):
            return old.pallas_flash_attention(
                q.reshape(b, t, h, d), k.reshape(b, t, h, d),
                v.reshape(b, t, h, d), causal=causal, interpret=False,
                key_mask=km).reshape(b, t, h * d)
        report("parent, the layer's layout in and out", old_layer)

    for qb, kb, sub in VARIANTS:
        if t % qb or t % kb:
            continue
        if g:
            tile = pa.Tile(g, d, 1, causal, qb, kb, sub, False)
            report(f"packed g={g} {qb}x{kb} sub {sub}",
                   lambda q, k, v, km, tile=tile: pa._flash(q, k, v, km,
                                                            tile))
        else:
            tile = pa.Tile(1, d, h, causal, qb, kb, sub, False)

            def folded(q, k, v, km, tile=tile):
                fold = lambda x: x.reshape(b, t, h, d).transpose(
                    0, 2, 1, 3).reshape(b * h, t, d)
                o = pa._flash(fold(q), fold(k), fold(v), km, tile)
                return o.reshape(b, h, t, d).transpose(
                    0, 2, 1, 3).reshape(b, t, h * d)
            report(f"folded {qb}x{kb} sub {sub}", folded)

    report("as shipped (pallas_flash_attention)",
           lambda q, k, v, km: pa.pallas_flash_attention(
               q.reshape(b, t, h, d), k.reshape(b, t, h, d),
               v.reshape(b, t, h, d), causal=causal, interpret=False,
               key_mask=km).reshape(b, t, h * d))
    os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
    with open(os.path.join(HERE, "chiprun_out", "flash_tiles.json"),
              "w") as f:
        json.dump({"shape": [b, t, h, d], "causal": causal,
                   "masked": a.masked, "device":
                   jax.devices()[0].device_kind, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    from deeplearning4j_tpu.ops.platform import configure_compilation_cache
    configure_compilation_cache()
    main()
