"""Real-backend kernel regression gate (r5, VERDICT r4 item #4 — the
CuDNNGradientChecks role: accelerator kernels vs built-in reference on
the ACTUAL device, not interpret mode).

The CPU interpret-mode tests keep CI green but cannot catch Mosaic
lowering/layout bugs; this script runs every custom kernel against its
materialized/jnp reference ON the real TPU at bench-relevant shapes,
forward AND gradients, and prints one table + one JSON line for
BASELINE.md. Run each round: `python scripts/perf_kernel_checks.py`.

Checks:
  short-T attention  (pallas_shortseq, T=512 flagship shape, causal,
                      unmasked + ragged key mask)
  general flash pair (pallas_attention, T=4096 long-context shape,
                      causal, unmasked + ragged in-kernel key mask; the
                      packed tile at gpt2-medium.train-t1024's shape and at
                      gpt2-large.chat-open's masked 1024 bucket; the folded
                      operands at Dh 192)
  fused sparse CE    (fused_ce vs one-hot mcxent, LM head shape)
  analytic LayerNorm (layernorm custom VJP vs naive autodiff)
  decode block layout (decode_block4_impl at gpt2-large shapes, 16 slots:
                      compiled only — no relayout copy of a layer's K or
                      V in the optimized HLO, memory_analysis peak under
                      8 GB; the 32-slot peak is printed, not gated)
  slab-stream         (kernels/slab_attention.py against the einsum body of
                      ``_slab_attend`` at gpt2-large.chat-open's shape,
                      [16, 10, 1024, 128] bf16, 1 / 4 / 8 queries a slot:
                      largest absolute difference of the outputs; printed,
                      not gated: the isolated time of each over 100 calls,
                      the plan counts of one traced decode_block4_impl,
                      and the block timed at empty slabs, 3 and 8 of 16
                      lanes alive (the rest stopped) and full slabs, with
                      the kernel at each tile that divides T and with the
                      einsum body — the readings MIN_TILE / MIN_BLOCK_BYTES
                      of kernels/slab_attention.py were set from)
  slab-stream-live    (the same kernel at each serving cell's slab shape,
                      16 x [10, 1024, 128] for gpt2-large.chat-open and
                      32 x [4, 2048, 128] for granite-4.0-h-micro.chat-short:
                      one layer's call with 3 of 16 (8 of 32) lanes alive
                      at positions drawn from the cell's traffic mix, the
                      rest stopped — largest absolute difference from the
                      einsum body on the alive lanes; printed, not gated:
                      microseconds a call at each tile that divides T, the
                      plan's own marked, beside the full-slab call (every
                      lane alive at T - 1), and the positions each reads
                      of those held)
  expert-mask         (RoutedExpertsLayer.forward's decode call, 16 lanes
                      with 16 / 6 / 1 marked, at joyai-llm-flash's shape —
                      top-8 of 256 at d 2048 / h 768 — and at
                      longcat-flash-chat's — top-12 of 768 with 16 held at
                      d 6144 / h 2048: kernel against the dense path;
                      printed, not gated: microseconds a call, the experts
                      it read, and what their bytes take at 819 GB/s — the
                      baseline of ROADMAP S12 (c))
  ssm-update          (kernels/ssm_update.py against ``ssm_step`` at
                      granite-4.0-h-micro.chat-short's [32, 64, 64, 128]
                      bf16 state: errors in units of the tests' tolerances;
                      printed, not gated: microseconds a call over 48 calls
                      in one program, eight states in turn, beside the
                      head-loop body it replaced, and what the call's bytes
                      take at 819 GB/s)

Error metric: max|a−b| / (max|b| + 1e-30) over fwd outputs and each
gradient; thresholds sized for bf16 matmul noise (attention) and f32
(CE/LN).
"""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402


def rel(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-30))


def ref_attention(q, k, v, causal, key_mask=None):
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(d)
    if key_mask is not None:
        s = jnp.where(key_mask[:, None, None, :] > 0, s, -1e30)
    if causal:
        t = q.shape[1]
        i = jnp.arange(t)
        s = jnp.where(i[:, None] >= i[None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


def check_attention(rows, kernel_fn, name, b, t, h, d, key_mask_tail):
    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(rng.normal(size=(b, t, h, d)) * 0.3,
                             jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    masks = [None]
    if key_mask_tail:
        km = np.ones((b, t), np.float32)
        km[:, t - key_mask_tail:] = 0.0      # ragged; key 0 visible
        masks.append(jnp.asarray(km))
    for km in masks:
        tag = f"{name}{'/masked' if km is not None else ''}"

        def f(q, k, v):
            return jnp.sum(kernel_fn(q, k, v, km).astype(jnp.float32) ** 2)

        def fr(q, k, v):
            return jnp.sum(ref_attention(q, k, v, True, km) ** 2)

        got = jax.jit(kernel_fn)(q, k, v, km)
        want = ref_attention(q, k, v, True, km)
        errs = {"fwd": rel(got, want)}
        g = jax.jit(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
        for nm, a, b_ in zip(("dq", "dk", "dv"), g, gr):
            errs[nm] = rel(a, b_)
        # bf16 dots + f32 reference: ~0.5% matmul noise is expected
        # (BASELINE.md r3); 5e-2 catches real lowering bugs with margin
        rows.append((tag, errs, 5e-2))
        print(f"  {tag}: " + " ".join(f"{k}={v:.2e}"
                                      for k, v in errs.items()), flush=True)


def check_fused_ce(rows):
    from deeplearning4j_tpu.kernels.fused_ce import fused_sparse_ce_score
    from deeplearning4j_tpu.ops.losses import compute_loss
    rng = np.random.default_rng(0)
    n, t, dmodel, v = 8, 512, 768, 32_000
    x = jnp.asarray(rng.normal(size=(n, t, dmodel)) * 0.1, jnp.float32)
    W = jnp.asarray(rng.normal(size=(dmodel, v)) * 0.02, jnp.float32)
    b = jnp.zeros((v,), jnp.float32)
    ids = jnp.asarray(rng.integers(0, v, (n, t)), jnp.int32)
    onehot = jax.nn.one_hot(ids, v, dtype=jnp.float32)

    # ids/onehot ride as ARGUMENTS — a closed-over [N,T,V] constant gets
    # inlined into the HLO and blows the remote-compile request limit
    def f(x, W, b, ids):
        return fused_sparse_ce_score({"W": W, "b": b}, x, ids, None, True)

    def fr(x, W, b, onehot):
        return compute_loss("mcxent", onehot, x @ W + b, "softmax", None,
                            True)

    errs = {"fwd": rel(jax.jit(f)(x, W, b, ids),
                       jax.jit(fr)(x, W, b, onehot))}
    g = jax.jit(jax.grad(f, argnums=(0, 1, 2)))(x, W, b, ids)
    gr = jax.jit(jax.grad(fr, argnums=(0, 1, 2)))(x, W, b, onehot)
    for nm, a, b_ in zip(("dx", "dW", "db"), g, gr):
        errs[nm] = rel(a, b_)
    rows.append(("fused-CE", errs, 1e-4))
    print("  fused-CE: " + " ".join(f"{k}={v:.2e}"
                                    for k, v in errs.items()), flush=True)


def check_layernorm(rows):
    from deeplearning4j_tpu.kernels.layernorm import layernorm
    rng = np.random.default_rng(0)
    n, t, c = 32, 512, 768
    x = jnp.asarray(rng.normal(size=(n, t, c)), jnp.float32)
    gamma = jnp.asarray(rng.normal(size=(c,)) * 0.1 + 1.0, jnp.float32)
    beta = jnp.asarray(rng.normal(size=(c,)) * 0.1, jnp.float32)

    def naive(x, gamma, beta):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * gamma + beta

    def f(x, gamma, beta):
        return jnp.sum(layernorm(x, gamma, beta, 1e-5) ** 2)

    def fr(x, gamma, beta):
        return jnp.sum(naive(x, gamma, beta) ** 2)

    # eps stays a python float: jit would trace it into the custom_vjp's
    # nondiff position
    ln = jax.jit(lambda x, g, b: layernorm(x, g, b, 1e-5))
    errs = {"fwd": rel(ln(x, gamma, beta), jax.jit(naive)(x, gamma, beta))}
    g = jax.jit(jax.grad(f, argnums=(0, 1, 2)))(x, gamma, beta)
    gr = jax.jit(jax.grad(fr, argnums=(0, 1, 2)))(x, gamma, beta)
    for nm, a, b_ in zip(("dx", "dgamma", "dbeta"), g, gr):
        errs[nm] = rel(a, b_)
    rows.append(("analytic-LN", errs, 1e-4))
    print("  analytic-LN: " + " ".join(f"{k}={v:.2e}"
                                       for k, v in errs.items()), flush=True)


SLAB_PEAK_LIMIT = 8e9      # bytes: decode_block4_impl, gpt2-large, 16 slots


def _decode_block_program(slots, k=4):
    """decode_block{k}_impl at the gpt2-large cell's shapes, from shapes
    alone (no weights are made): (the jitted program, its arguments'
    shapes, one layer's cache shape)."""
    from jax.sharding import SingleDeviceSharding
    from benchmark.harness import program
    from deeplearning4j_tpu.models import TransformerDecoder
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "gpt2-large.json")) as f:
        config = json.load(f)
    dev = SingleDeviceSharding(jax.devices()[0])

    def on(tree, dtype=None):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, dtype if dtype is not None
                and a.dtype == jnp.float32 else a.dtype, sharding=dev),
            tree)
    net, _, (params, state, _) = program.make_net(config)
    params = on(params, jnp.bfloat16)
    net.params = params
    dec = TransformerDecoder(net, t_max=config["run"]["engine"]["t_max"])
    caches = on(jax.eval_shape(lambda: dec.init_cache(slots)))
    dec._fn(("block", k))
    jitted = dec._cost_seam[f"decode_block{k}_impl"][0]
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    vec = lambda dt: jax.ShapeDtypeStruct((slots,), dt, sharding=dev)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=dev)
    args = (params, on(state), caches, vec(jnp.int32), vec(jnp.int32),
            vec(jnp.bool_), vec(jnp.float32), vec(jnp.int32),
            jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=dev),
            scalar, scalar)
    return jitted, args, caches[dec.attn_names[0]]["k"].shape


def _compile_decode_block(slots, k=4):
    """The same, compiled for this backend: (compiled, one layer's cache
    shape)."""
    jitted, args, shape = _decode_block_program(slots, k)
    return jitted.lower(*args).compile(), shape


def slab_relayout_copies(hlo_text, cache_shape):
    """The optimized HLO's copies of a whole layer's K or V that are not
    the read itself: every synchronous ``copy`` of that many elements,
    and every ``copy-start`` whose two sides differ in more than their
    memory space (a prefetch of the slab into fast memory, same layout
    on both sides, IS the one read the roofline counts)."""
    import re
    size = int(np.prod(cache_shape))
    shape_re = re.compile(r"\w+\[([\d,]+)\](\{[^}]*\})?")
    found = []
    for line in hlo_text.splitlines():
        m = re.search(r" = (.*?) copy(-start)?\(", line)
        if not m:
            continue
        shapes = [(int(np.prod([int(d) for d in dims.split(",")])),
                   re.sub(r"S\(\d+\)", "", layout or ""))
                  for dims, layout in shape_re.findall(m.group(1))]
        if not shapes or shapes[0][0] != size:
            continue
        if m.group(2) is None or shapes[0][1] != shapes[1][1]:
            found.append(line.strip()[:200])
    return found


def check_decode_block_layout(rows):
    from deeplearning4j_tpu.models.generation import compiled_peak_bytes
    compiled, shape = _compile_decode_block(16)
    peak = compiled_peak_bytes(compiled)
    copies = slab_relayout_copies(compiled.as_text(), shape)
    for line in copies[:4]:
        print("  slab copy: " + line, flush=True)
    print(f"  decode_block4_impl, 16 slots, slab {list(shape)}: peak "
          f"{peak / 1e9:.2f} GB, {len(copies)} relayout copies of a "
          "layer's K or V", flush=True)
    rows.append(("decode-block-layout",
                 {"slab_copies": float(len(copies)),
                  "peak_over_limit": max(0.0, (peak - SLAB_PEAK_LIMIT)
                                         / SLAB_PEAK_LIMIT)}, 0.0))
    try:
        peak32 = compiled_peak_bytes(_compile_decode_block(32)[0])
        print(f"  decode_block4_impl, 32 slots: peak {peak32 / 1e9:.2f} "
              "GB (printed, not gated)", flush=True)
    except Exception as e:   # noqa: BLE001 — printed, not gated
        print("  decode_block4_impl, 32 slots: does not compile: "
              f"{type(e).__name__}: {str(e)[:160]}", flush=True)


SLAB_SHAPE = (16, 10, 1024, 128)     # gpt2-large.chat-open: one layer's K


def _timed(fn, *args, calls=10):
    """Seconds a call of ``fn(*args)``, after one warm call."""
    import time
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls


def slab_attend_times(window=1, loops=100, layers=5):
    """One attention layer's ``_slab_attend`` at the cell's shape, ``window``
    queries a slot: (largest absolute difference between the kernel's output
    and the einsum body's, {"einsum" / "kernel": microseconds a call}). The
    ``loops`` calls run inside one program, each on the queries the call
    before left, over ``layers`` slabs in turn, each carried and written a
    row a call as a decode step does — one loop-invariant slab the compiler
    reads once and keeps in fast memory."""
    from deeplearning4j_tpu.nn import helpers
    from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
    b, hg, t, lanes = SLAB_SHAPE
    dh = 64
    h = hg * lanes // dh
    layer = SelfAttentionLayer(n_in=h * dh, n_out=h * dh, num_heads=h,
                               causal=True)
    rng = np.random.default_rng(0)
    mk = lambda shape: jnp.asarray(rng.normal(size=shape) * 0.5,
                                   jnp.bfloat16)
    q = mk((b, window, h, dh))
    slabs = [(mk(SLAB_SHAPE), mk(SLAB_SHAPE)) for _ in range(layers)]
    qpos = jnp.asarray(rng.integers(0, t - window, (b, 1)), jnp.int32) \
        + jnp.arange(window, dtype=jnp.int32)[None, :]
    qpos = qpos.at[0].set(jnp.arange(window)).at[1].set(
        t - window + jnp.arange(window))

    def chained_fn():     # a new function a path: the path is picked in trace
        def chained(q, slabs, qpos):
            def body(i, carry):
                q, slabs = carry
                at = (0, 0, i % t, 0)
                out = []
                for ck, cv in slabs:
                    row = q[:1, :1, :lanes // dh].reshape(1, 1, 1, lanes)
                    ck = jax.lax.dynamic_update_slice(ck, row, at)
                    cv = jax.lax.dynamic_update_slice(cv, row, at)
                    q = q + (layer._slab_attend(q, ck, cv, qpos)
                             * 1e-3).astype(q.dtype)
                    out.append((ck, cv))
                return q, out
            return jax.lax.fori_loop(0, loops // layers, body,
                                     (q, slabs))[0]
        return jax.jit(chained)

    outs, times = {}, {}
    try:
        for name, switch in (("einsum", helpers.disable_helper),
                             ("kernel", helpers.enable_helper)):
            switch("slab_attention")
            outs[name] = np.asarray(
                jax.jit(lambda *a: layer._slab_attend(*a))(
                    q, *slabs[0], qpos), np.float32)
            times[name] = _timed(chained_fn(), q, slabs, qpos, calls=3) \
                / (loops // layers * layers) * 1e6
    finally:
        helpers.enable_helper("slab_attention")
    return float(np.max(np.abs(outs["kernel"] - outs["einsum"]))), times


#: lanes of gpt2-large.chat-open's 16 at which a decode block is timed:
#: (positions, stopped) — empty slabs, three lanes alive mid-answer (the
#: cell's mean load) beside thirteen stopped ones, eight alive, full slabs
BLOCK_LOADS = {
    "empty": ([0] * 16, [False] * 16),
    "3 alive": ([250, 700, 700, 400] + [700] * 3 + [150] + [700] * 8,
                [i not in (0, 3, 7) for i in range(16)]),
    "8 alive": ([250, 700, 520, 400, 700, 700, 880, 150, 700, 300, 700,
                 700, 610, 700, 230, 700],
                [i not in (0, 2, 3, 6, 7, 9, 12, 14) for i in range(16)]),
    "full": ([1000] * 16, [False] * 16),
}


def decode_block_times(slots=16, loads=BLOCK_LOADS, calls=10):
    """decode_block4_impl at gpt2-large's shapes with zero weights (its
    time does not depend on them), its lanes at each of ``loads``' positions
    and stops: (plans of the traced block, {load: milliseconds a block})."""
    import time
    from deeplearning4j_tpu.analysis import AttentionPlanAudit
    with AttentionPlanAudit() as audit:
        jitted, args, _ = _decode_block_program(slots)
        compiled = jitted.lower(*args).compile()
    params, state, caches = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype), args[:3])

    def spin(caches, pos, stopped):
        rest = [jnp.zeros(slots, jnp.int32), jnp.asarray(pos, jnp.int32),
                jnp.asarray(stopped), jnp.zeros(slots, jnp.float32),
                jnp.full(slots, -1, jnp.int32), jax.random.PRNGKey(0),
                jnp.int32(0), jnp.int32(0)]
        t0 = time.perf_counter()
        for _ in range(calls):
            caches = compiled(params, state, caches, *rest)[-1]
        jax.block_until_ready(caches)
        return caches, (time.perf_counter() - t0) / calls * 1e3
    times = {}
    for name, (pos, stopped) in loads.items():
        # a block's first calls after another load's (or the compile) run
        # a per cent or two slower: warm each load, then time it
        caches, _ = spin(caches, pos, stopped)
        caches, times[name] = spin(caches, pos, stopped)
    return audit.plans(), times


def check_slab_stream(rows):
    from deeplearning4j_tpu.kernels import slab_attention as sa
    from deeplearning4j_tpu.nn import helpers
    errs = {}
    # a decode step, and the verify windows no cell runs
    for window in (1, 4, 8):
        errs[f"c{window}"], times = slab_attend_times(window)
        print(f"  slab-stream: {window} queries a slot over "
              f"{list(SLAB_SHAPE)} bf16: max|kernel-einsum|="
              f"{errs[f'c{window}']:.2e}; a call: " + ", ".join(
                  f"{k} {v:.1f} us" for k, v in times.items()), flush=True)
    # bf16 outputs of O(1): a rounding step or two of reassociation
    rows.append(("slab-stream", errs, 3e-2))
    # the block with the kernel at each tile that divides T (the plan's
    # marked), and with the einsum body
    tb_plan = sa.plan(1, SLAB_SHAPE[1], SLAB_SHAPE[2], SLAB_SHAPE[3],
                      jnp.bfloat16)[1]
    saved = sa.TILES
    runs = [(f"kernel, tb {tb}{' (plan)' if tb == tb_plan else ''}", tb)
            for tb in saved if SLAB_SHAPE[2] % tb == 0] + [("einsum", None)]
    for label, tb in runs:
        sa.TILES = saved if tb is None else (tb,)
        if tb is None:
            helpers.disable_helper("slab_attention")
        try:
            plans, times = decode_block_times()
        finally:
            sa.TILES = saved
            helpers.enable_helper("slab_attention")
        print(f"  decode_block4_impl, {label}: plans {plans}; ms a block: "
              + ", ".join(f"{k} {v:.3f}" for k, v in times.items()),
              flush=True)


#: the serving cells' slab attention layers: slab shape [slots, H_kv/g, T,
#: g·Dh], query and KV heads, lanes alive of the slots, traffic mix
LIVE_CELLS = {
    "gpt2-large.chat-open": dict(shape=(16, 10, 1024, 128), heads=20,
                                 kv_heads=20, alive=3, traffic="chat-open"),
    "granite-4.0-h-micro.chat-short": dict(
        shape=(32, 4, 2048, 128), heads=32, kv_heads=8, alive=8,
        traffic="chat-short"),
}


def _mix_positions(traffic, n, rng):
    """``n`` positions of lanes mid-answer under a traffic mix: a prompt
    and an answer drawn from its log-normals (clipped as the load
    generator clips them), the lane a uniform share of the way through
    the answer."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "traffic",
        traffic + ".json")
    with open(path, encoding="utf-8") as f:
        mix = json.load(f)

    def draw(d):
        v = d["median"] * np.exp(d["sigma"] * rng.standard_normal(n))
        return np.clip(np.rint(v), d["min"], d["max"]).astype(np.int64)
    prompt, answer = draw(mix["prompt_tokens"]), draw(mix["new_tokens"])
    return prompt + (rng.random(n) * answer).astype(np.int64)


def slab_live_times(cell, loops=100, layers=5):
    """One slab attention layer of ``cell`` (LIVE_CELLS) with the kernel:
    (the plan's tile, largest absolute difference from the einsum body on
    the alive lanes, {tile: {"live" / "full": (microseconds a call,
    positions read)}}, positions held). The ``loops`` calls run inside one
    program over ``layers`` slabs in turn, as slab_attend_times does."""
    from deeplearning4j_tpu.kernels import slab_attention as sa
    from deeplearning4j_tpu.nn import helpers
    from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
    c = LIVE_CELLS[cell]
    b, hg, t, lanes = c["shape"]
    h, dh = c["heads"], 64
    layer = SelfAttentionLayer(n_in=h * dh, n_out=h * dh, num_heads=h,
                               num_kv_heads=c["kv_heads"], causal=True)
    rng = np.random.default_rng(0)
    mk = lambda shape: jnp.asarray(rng.normal(size=shape) * 0.5,
                                   jnp.bfloat16)
    q = mk((b, 1, h, dh))
    slabs = [(mk(c["shape"]), mk(c["shape"])) for _ in range(layers)]
    pos = np.minimum(_mix_positions(c["traffic"], b, rng), t - 1)
    alive = np.zeros(b, bool)
    alive[rng.choice(b, c["alive"], replace=False)] = True
    cases = {"live": (jnp.asarray(pos[:, None], jnp.int32),
                      jnp.asarray(alive)),
             "full": (jnp.full((b, 1), t - 1, jnp.int32),
                      jnp.ones(b, bool))}

    def chained(q, slabs, qpos, live):
        def body(i, q):
            for ck, cv in slabs:
                q = q + (layer._slab_attend(q, ck, cv, qpos, live)
                         * 1e-3).astype(q.dtype)
            return q
        return jax.lax.fori_loop(0, loops // layers, body, q)

    saved = sa.TILES
    tb_plan = sa.plan(1, hg, t, lanes, jnp.bfloat16)[1]
    helpers.enable_helper("slab_attention")
    times = {}
    try:
        for tb in [n for n in saved if t % n == 0]:
            sa.TILES = (tb,)
            times[tb] = {}
            for name, (qpos, live) in cases.items():
                last = sa.live_tiles(qpos, live, tb, t // tb)
                us = _timed(jax.jit(chained), q, slabs, qpos, live,
                            calls=3) / (loops // layers * layers) * 1e6
                times[tb][name] = (us, int(jnp.sum(last + 1)) * tb)
    finally:
        sa.TILES = saved
    qpos, live = cases["live"]
    got = jax.jit(lambda *a: layer._slab_attend(*a))(q, *slabs[0], qpos,
                                                      live)
    helpers.disable_helper("slab_attention")
    try:
        want = jax.jit(lambda *a: layer._slab_attend(*a))(q, *slabs[0],
                                                          qpos)
    finally:
        helpers.enable_helper("slab_attention")
    err = float(np.max(np.abs(np.asarray(got, np.float32)[alive]
                              - np.asarray(want, np.float32)[alive])))
    return tb_plan, err, times, b * t


def check_slab_live(rows):
    errs = {}
    for cell in LIVE_CELLS:
        tb_plan, errs[cell], times, held = slab_live_times(cell)
        print(f"  slab-stream, {cell}: {LIVE_CELLS[cell]['alive']} of "
              f"{LIVE_CELLS[cell]['shape'][0]} lanes alive; "
              f"max|kernel-einsum| on them={errs[cell]:.2e}; a call by "
              f"tile (positions read of {held}):", flush=True)
        for tb, got in times.items():
            print(f"    tb {tb:5d}{' (plan)' if tb == tb_plan else '':7s}"
                  + "; ".join(f" {k} {us:.1f} us, {n} read"
                              for k, (us, n) in got.items()), flush=True)
    rows.append(("slab-stream-live", errs, 3e-2))


#: the two drawn configurations' expert layers as their cells serve them
EXPERT_LAYERS = {
    "joyai-llm-flash": dict(
        n_in=2048, expert_hidden=768, num_experts=256, top_k=8,
        routed_scaling=2.5, shared_experts=1),
    "longcat-flash-chat": dict(
        n_in=6144, expert_hidden=2048, num_experts=512, zero_experts=256,
        experts_held=16, top_k=12, score_function="softmax",
        renormalize=False, routed_scaling=6.0, shared_experts=0),
}


def expert_mask_call(name, marked, lanes=16, loops=50):
    """The expert layer of ``name`` on ``lanes`` rows, the first ``marked``
    of them marked, bf16: (largest error of the kernel's output against the
    dense path's relative to its largest value, microseconds a call, experts
    read a call, microseconds their weights' bytes take). The ``loops``
    calls run inside one program, each on rows of its own (so every call
    routes afresh) plus a thousandth of what the call before gave (so they
    run in turn)."""
    from benchmark.harness.manifest import peaks
    from deeplearning4j_tpu.nn import helpers
    from deeplearning4j_tpu.nn.conf.layers import RoutedExpertsLayer
    hbm = peaks(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    kw = EXPERT_LAYERS[name]
    layer = RoutedExpertsLayer(n_out=kw["n_in"], **kw)
    params = layer.init_params(jax.random.PRNGKey(0), jnp.bfloat16)
    xs = jax.random.normal(jax.random.PRNGKey(1),
                           (loops, lanes, 1, layer.n_in), jnp.bfloat16)
    x = xs[0]
    mask = (jnp.arange(lanes) < marked)[:, None]
    held = slice(layer.first_expert, layer.first_expert + layer._held())

    def once():           # a new function a path: the path is picked in trace
        return jax.jit(lambda p, x: layer.forward(p, None, x, mask=mask)[0])
    got = once()(params, x)
    helpers.disable_helper("routed_experts")
    try:
        want = once()(params, x)
    finally:
        helpers.enable_helper("routed_experts")

    @jax.jit
    def chained(p, xs):
        def body(carry, x):
            before, read = carry
            y, counts = layer.forward(p, None, x + 1e-3 * before, mask=mask)
            return (y, read + jnp.sum(counts["expert_rows"][held] > 0)), None
        return jax.lax.scan(body, (jnp.zeros_like(xs[0]), jnp.int32(0)),
                            xs)[0]
    seconds = _timed(chained, params, xs, calls=3) / loops
    read = float(chained(params, xs)[1]) / loops
    expert_bytes = 3 * layer.n_in * layer.expert_hidden * 2
    return (rel(got, want), seconds * 1e6, read,
            read * expert_bytes / hbm * 1e6)


def check_expert_mask(rows):
    for name in EXPERT_LAYERS:
        errs = {}
        for marked in (16, 6, 1):
            errs[f"m{marked}"], us, read, bytes_us = expert_mask_call(
                name, marked)
            print(f"  expert-mask, {name}: {marked} of 16 lanes marked: "
                  f"{us:.1f} us a call, {read:.2f} experts read a call, "
                  f"their bytes {bytes_us:.1f} us at the memory's rate; "
                  f"max|kernel-dense|={errs[f'm{marked}']:.2e}", flush=True)
        # bf16 rows of O(1) summed over top-k experts in two orders
        rows.append((f"expert-mask@{name}", errs, 3e-2))


#: granite-4.0-h-micro.chat-short: one state-space layer's state, 32 slots
SSM_SHAPE = (32, 64, 64, 128)


def _head_loop_kernel(s_ref, xt_ref, dt_ref, bc_ref, ad_ref, so_ref, yt_ref):
    """The state-update body before the row layout, kept to be timed beside
    it: one slot a grid step, a static loop over its heads, each a [P, N]
    tile decayed by a lane of dt_ref [1, H], given a column of x [P, H]
    broadcast along the lanes, read out by a sum across the lanes and
    selected into lane h of yt [P, H]."""
    heads = s_ref.shape[0]
    xt = xt_ref[...]
    dt = dt_ref[...]
    decay = jnp.exp(dt * ad_ref[0:1, :])
    u = xt * dt
    bvec, cvec = bc_ref[0:1, :], bc_ref[1:2, :]
    lane = jax.lax.broadcasted_iota(jnp.int32, xt.shape, 1)
    y = xt * ad_ref[1:2, :]
    for h in range(heads):
        s = s_ref[h].astype(jnp.float32) * decay[:, h:h + 1] \
            + u[:, h:h + 1] * bvec
        so_ref[h] = s.astype(so_ref.dtype)
        col = jnp.sum(s * cvec, axis=1, keepdims=True)
        y = y + jnp.where(lane == h, col, 0.0)
    yt_ref[...] = y


def head_loop_update(state, x, dt, a, b, c, d):
    """``ssm_step``'s contract through :func:`_head_loop_kernel`, its
    operands laid out as its helper laid them out."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    s, h, p, n = state.shape
    f32 = jnp.float32
    slot = lambda i: (i, 0, 0)
    new, yt = pl.pallas_call(
        _head_loop_kernel,
        name="ssm_head_loop",
        grid=(s,),
        in_specs=[pl.BlockSpec((None, h, p, n), lambda i: (i, 0, 0, 0)),
                  pl.BlockSpec((None, p, h), slot),
                  pl.BlockSpec((None, 1, h), slot),
                  pl.BlockSpec((None, 2, n), slot),
                  pl.BlockSpec((2, h), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((None, h, p, n), lambda i: (i, 0, 0, 0)),
                   pl.BlockSpec((None, p, h), slot)],
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((s, p, h), f32)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(state, jnp.swapaxes(x.astype(f32), 1, 2), dt.astype(f32)[:, None, :],
      jnp.stack([b.astype(f32), c.astype(f32)], axis=1),
      jnp.stack([a.astype(f32), d.astype(f32)]))
    return new, jnp.swapaxes(yt, 1, 2)


def _ssm_operands(loops, layers=1, shape=SSM_SHAPE, seed=0):
    """``layers`` bfloat16 states of ``shape``, A and D, and ``loops``
    tokens' x, dt, B and C drawn as the layer makes them (dt a softplus, A
    negative)."""
    s, h, p, n = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    states = [jax.random.normal(k, shape).astype(jnp.bfloat16)
              for k in jax.random.split(ks[0], layers)]
    xs = jax.random.normal(ks[1], (loops, s, h, p))
    dts = jax.nn.softplus(jax.random.normal(ks[2], (loops, s, h)) - 2.0)
    a = -jnp.exp(jax.random.normal(ks[3], (h,)))
    bs, cs = (jax.random.normal(k, (loops, s, n)) for k in ks[4:6])
    d = jax.random.normal(ks[6], (h,))
    return states, xs, dts, a, bs, cs, d


def ssm_update_call(update, loops=48, layers=8):
    """``update(state, x, dt, a, b, c, d) -> (state, y)`` at SSM_SHAPE:
    (its error against ``ssm_step`` in units of the test's tolerances — a
    bfloat16 state within one rounding, y within rtol 1e-5 / atol 1e-4 —
    and microseconds a call). The ``loops`` calls run inside one program,
    each on a token of its own and on the state the call ``layers`` calls
    before left: ``layers`` states in turn, as the layers of a decode step
    take theirs, 268 MB that fast memory cannot keep between calls as it
    could keep one."""
    from deeplearning4j_tpu.nn.conf.layers.state_space import ssm_step
    states, xs, dts, a, bs, cs, d = _ssm_operands(loops, layers)
    one = lambda f: jax.jit(lambda st, i: f(st, xs[i], dts[i], a, bs[i],
                                           cs[i], d))
    got_s, got_y = one(update)(states[0], 0)
    ref_s, ref_y = one(ssm_step)(states[0], 0)
    got_s, ref_s = (np.asarray(v, np.float32) for v in (got_s, ref_s))
    got_y, ref_y = np.asarray(got_y), np.asarray(ref_y)
    errs = {"state": float(np.max(np.abs(got_s - ref_s)
                                  / (2 ** -7 * (1 + np.abs(ref_s))))),
            "y": float(np.max(np.abs(got_y - ref_y)
                              / (1e-4 + 1e-5 * np.abs(ref_y))))}
    by_layer = lambda v: v.reshape((loops // layers, layers) + v.shape[1:])

    @jax.jit
    def chained(states, xs, dts, bs, cs):
        def body(carry, toks):
            states, acc = carry
            out = []
            for st, x, dt, b, c in zip(states, *toks):
                st, y = update(st, x, dt, a, b, c, d)
                out.append(st)
                acc = acc + y[0, 0, 0]
            return (out, acc), None
        return jax.lax.scan(body, (states, jnp.float32(0)),
                            tuple(map(by_layer, (xs, dts, bs, cs))))[0]
    seconds = _timed(chained, states, xs, dts, bs, cs, calls=3) / loops
    return errs, seconds * 1e6


def check_ssm_update(rows):
    from benchmark.families.mamba2_hybrid.flops import ssm_decode_need
    from benchmark.harness.manifest import peaks
    from deeplearning4j_tpu.kernels.ssm_update import make_ssm_update_helper
    s, h, p, n = SSM_SHAPE
    need = ssm_decode_need({"ssm_heads": h, "ssm_head_dim": p,
                            "ssm_state": n}, s)
    bytes_us = need["bytes"] / peaks(
        jax.devices()[0].device_kind)["hbm_bytes_per_s"] * 1e6
    helper = make_ssm_update_helper(interpret=False)
    kernel = lambda *args: helper(None, *args)
    errs, us = ssm_update_call(kernel)
    head_errs, head_us = ssm_update_call(head_loop_update)
    print(f"  ssm-update {list(SSM_SHAPE)} bf16: {us:.1f} us a call (the "
          f"head loop before it {head_us:.1f} us); its bytes "
          f"{bytes_us:.1f} us at the memory's rate; errors in tolerances: "
          + ", ".join(f"{k} {v:.2f}" for k, v in errs.items())
          + " (head loop " + ", ".join(f"{k} {v:.2f}"
                                       for k, v in head_errs.items())
          + ")", flush=True)
    rows.append(("ssm-update", errs, 1.0))


def main():
    from deeplearning4j_tpu.kernels.pallas_attention import \
        pallas_flash_attention
    from deeplearning4j_tpu.kernels.pallas_shortseq import short_attention

    print(f"device={jax.devices()[0].device_kind}  "
          f"backend={jax.default_backend()}")
    rows = []

    check_attention(
        rows,
        lambda q, k, v, km: short_attention(q, k, v, causal=True,
                                            key_mask=km, interpret=False),
        "short-T@512", b=32, t=512, h=12, d=64, key_mask_tail=128)
    # smaller B/H than the bench shape: the f32 materialized REFERENCE
    # must also fit/compile quickly ([B,H,T,T] logits are 3.2 GB at the
    # full bench shape); the kernel path itself is shape-generic
    flash = lambda q, k, v, km: pallas_flash_attention(
        q, k, v, causal=True, interpret=False, key_mask=km)
    check_attention(rows, flash, "flash@4096", b=2, t=4096, h=4, d=64,
                    key_mask_tail=2048)
    # Dh 192 does not pack: the folded [BH, T, Dh] operands, g = 1
    check_attention(rows, flash, "flash@2048xDh192", b=2, t=2048, h=4,
                    d=192, key_mask_tail=512)
    # the packed tile (two 64-wide heads to a 128-lane row) at the shapes
    # the cells run it: gpt2-medium.train-t1024's step, and the masked
    # 1024 bucket of gpt2-large.chat-open's admissions (H 20; 4 of its 16
    # rows, for the float32 reference's sake)
    check_attention(rows, flash, "flash@train-t1024", b=8, t=1024, h=16,
                    d=64, key_mask_tail=0)
    check_attention(rows, flash, "flash@chat-open-1024", b=4, t=1024, h=20,
                    d=64, key_mask_tail=256)
    check_fused_ce(rows)
    check_layernorm(rows)
    check_decode_block_layout(rows)
    check_slab_stream(rows)
    check_slab_live(rows)
    check_expert_mask(rows)
    check_ssm_update(rows)

    ok_all = True
    print(f"{'check':22s} {'threshold':>9s}  errors")
    for tag, errs, thresh in rows:
        ok = all(e <= thresh for e in errs.values())
        ok_all &= ok
        detail = " ".join(f"{k}={v:.2e}" for k, v in errs.items())
        print(f"{tag:22s} {thresh:9.0e}  {detail}  "
              f"{'PASS' if ok else 'FAIL'}")
    print(json.dumps({
        "metric": "kernel_checks_real_backend",
        "pass": ok_all,
        "max_err": max(max(e.values()) for _, e, _ in rows),
        "checks": {tag: {k: round(v, 8) for k, v in errs.items()}
                   for tag, errs, _ in rows},
    }))
    return 0 if ok_all else 1


if __name__ == "__main__":
    from deeplearning4j_tpu.ops.platform import configure_compilation_cache
    configure_compilation_cache()
    sys.exit(main())
