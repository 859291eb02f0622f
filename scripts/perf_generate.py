#!/usr/bin/env python
"""Generation serving-path sweep: (batch, prompt-T, gen-T) grid over the
KV-cache decode loop (models/generation.py), one JSON line per point —
prefill tok/s, steady decode tok/s (emitted tokens), per-token p50/p99
latency, and the no-cache recompute baseline with its speedup ratio —
plus one continuous-batching A/B line (mixed-length stream, slot refill
on vs off). BENCH_MODE=generate in bench.py is the single-point
headline protocol; this is the full grid behind it.

Model knobs (defaults: the flagship 12x768/12-head/32k-vocab LM):
  GEN_VOCAB, GEN_DMODEL, GEN_HEADS, GEN_LAYERS
Sweep knobs (comma-separated):
  GEN_BATCHES   (default "8,32")
  GEN_PROMPTS   (default "128,512")
  GEN_TOKENS    (default "32,64")
Protocol: GEN_RUNS median-of-N (default 3) after one warmup per compile.

--block-sweep runs the decode-pipeline A/B instead: for each fused-block
size K in GEN_BLOCKS (default "1,4,8"), the serving-pattern loop (K
steps per device program, ONE [B, K] readback per block, K>1
double-buffered) at the default serving shape (the largest
batch/prompt/gen-T of the grid knobs; GEN_SWEEP_BATCH/PROMPT/TOKENS
override) — one JSON object with per-K steady decode tok/s, p50/p99
per-token latency, and readbacks/step. Exits NON-ZERO if no K>1 beats
the K=1 baseline: the pipelined path must never ship slower than the
loop it replaces.

--mesh-sweep (r12) runs the mesh-sharded serving A/B instead: for each
named (data, tp) mesh shape in GEN_MESH_SHAPES (default
"1x1,2x1,1x2,4x1"), the serving-pattern loop at the best fused-block
size (best of GEN_BLOCKS measured on the unsharded decoder;
GEN_MESH_BLOCK overrides) — one JSON object with per-shape steady
decode tok/s, p50/p99 per-token latency, readbacks/block, and the
token-parity verdict vs the 1x1 run (greedy AND fixed-seed sampled).
Exits NON-ZERO if any sharded shape breaks token parity: sharding may
move compute, never tokens. Shapes that don't fit jax.device_count()
(or fail the heads/batch divisibility contract) are reported skipped.
On CPU the script forces XLA_FLAGS=--xla_force_host_platform_device_
count=8 (GEN_MESH_DEVICES overrides) so the sweep runs without TPU
hardware.

--shared-prefix (ISSUE 12) runs the paged-vs-slab A/B on N streams ×
one common system prompt: slab prompt-prefill tok/s vs paged-with-
prefix-cache-hits, max concurrent sequences at byte-identical KV pool
budgets (devstats-verified), and the prefix hit rate. ``--gate [X]``
enforces the acceptance bars (paged prefill speedup >= X, default 5.0;
concurrency ratio >= 3x; hit rate >= 0.9) with a non-zero exit.

Run: [JAX_PLATFORMS=...] python scripts/perf_generate.py \
         [--block-sweep | --mesh-sweep | --shared-prefix [--gate [X]]]
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "--mesh-sweep" in sys.argv[1:]:
    # must land BEFORE jax initializes; a no-op on real TPU/GPU backends
    # (the flag only affects the host cpu platform)
    _flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
              if "xla_force_host_platform_device_count" not in f]
    _flags.append("--xla_force_host_platform_device_count=" +
                  os.environ.get("GEN_MESH_DEVICES", "8"))
    os.environ["XLA_FLAGS"] = " ".join(_flags)

VOCAB = int(os.environ.get("GEN_VOCAB", "32000"))
DMODEL = int(os.environ.get("GEN_DMODEL", "768"))
HEADS = int(os.environ.get("GEN_HEADS", "12"))
LAYERS = int(os.environ.get("GEN_LAYERS", "12"))
BATCHES = [int(x) for x in os.environ.get("GEN_BATCHES", "8,32").split(",")]
PROMPTS = [int(x) for x in os.environ.get("GEN_PROMPTS", "128,512").split(",")]
TOKENS = [int(x) for x in os.environ.get("GEN_TOKENS", "32,64").split(",")]
RUNS = int(os.environ.get("GEN_RUNS", "3"))
NOCACHE_STEPS = int(os.environ.get("GEN_NOCACHE_STEPS", "8"))


def _median(fn, runs=RUNS):
    vals = [fn() for _ in range(runs)]
    med = float(np.median(vals))
    spread = 100.0 * (max(vals) - min(vals)) / med if med else 0.0
    return med, round(spread, 2)


def _serving_run(dec, k, b, tokens, lengths, gen_t):
    """The canonical serving-pattern timing loop, shared with the bench
    driver (ONE definition repo-wide: a timing fix cannot land in one
    table and miss another). Returns (tok/s, per-token latencies,
    decode blocks, readbacks)."""
    from bench import serving_run    # repo root is on sys.path (above)
    return serving_run(dec, k, b, tokens, lengths, gen_t,
                       tag="perf.decode")


def block_sweep() -> int:
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import (TransformerDecoder,
                                           transformer_lm_conf)
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    from deeplearning4j_tpu.observability.metrics import percentiles

    ks = []
    for tok in os.environ.get("GEN_BLOCKS", "1,4,8").split(","):
        k = int(tok)
        if k >= 1 and k not in ks:
            ks.append(k)
    b = int(os.environ.get("GEN_SWEEP_BATCH", str(max(BATCHES))))
    tp = int(os.environ.get("GEN_SWEEP_PROMPT", str(max(PROMPTS))))
    gen_t = int(os.environ.get("GEN_SWEEP_TOKENS", str(max(TOKENS))))
    conf = transformer_lm_conf(vocab_size=VOCAB, d_model=DMODEL,
                               num_heads=HEADS, num_layers=LAYERS,
                               max_length=tp + gen_t + 1)
    net = ComputationGraph(conf, compute_dtype=jnp.bfloat16).init()
    dec = TransformerDecoder(net)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, VOCAB, (b, tp)).astype(np.int32)
    lengths = np.full(b, tp, np.int32)

    def run_once(k):
        """(tok/s, per-token latencies, readbacks per STEP) at block k."""
        tps, lats, nb, reads = _serving_run(dec, k, b, tokens, lengths,
                                            gen_t)
        return tps, lats, reads / (nb * k)

    table = {}
    for k in ks:
        run_once(k)                          # warm the K-block program
        vals, lats, rps = [], [], []
        for _ in range(RUNS):
            tps, ls, rp = run_once(k)
            vals.append(tps)
            lats.extend(ls)
            rps.append(rp)
        med = float(np.median(vals))
        # p50/p99 via the shared Histogram implementation
        # (observability/metrics.py) — not a private np.percentile copy
        pct = percentiles(lats, (50, 99))
        table[str(k)] = {
            "decode_tok_s": round(med, 1),
            "spread_pct": round(
                100.0 * (max(vals) - min(vals)) / med, 2) if med else 0.0,
            "p50_ms": round(pct["p50"] * 1e3, 3),
            "p99_ms": round(pct["p99"] * 1e3, 3),
            "readbacks_per_step": round(float(np.mean(rps)), 4),
        }
    k1 = table.get("1", {}).get("decode_tok_s", 0.0)
    best_gt1 = max((t["decode_tok_s"] for kk, t in table.items()
                    if int(kk) > 1), default=None)
    ok = best_gt1 is None or k1 == 0 or best_gt1 >= k1
    print(json.dumps({
        "block_sweep": table,
        "shape": {"batch": b, "prompt_t": tp, "gen_t": gen_t,
                  "vocab": VOCAB, "d_model": DMODEL, "layers": LAYERS},
        "best_gt1_vs_k1": round(best_gt1 / k1, 3)
        if best_gt1 and k1 else None,
        "ok": ok,
    }, indent=1), flush=True)
    return 0 if ok else 1


def mesh_sweep() -> int:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import (TransformerDecoder,
                                           transformer_lm_conf)
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.observability.metrics import percentiles
    from deeplearning4j_tpu.parallel.mesh import (generation_mesh,
                                                  parse_mesh_shape)

    b = int(os.environ.get("GEN_SWEEP_BATCH", str(max(BATCHES))))
    tp = int(os.environ.get("GEN_SWEEP_PROMPT", str(max(PROMPTS))))
    gen_t = int(os.environ.get("GEN_SWEEP_TOKENS", str(max(TOKENS))))
    conf = transformer_lm_conf(vocab_size=VOCAB, d_model=DMODEL,
                               num_heads=HEADS, num_layers=LAYERS,
                               max_length=tp + gen_t + 1)
    net = ComputationGraph(conf, compute_dtype=jnp.bfloat16).init()
    # parity twin at f32: cross-mesh token identity is a property of the
    # PARTITIONING discipline, and it is gated where reduction-order
    # noise sits far below any decision threshold. At bf16 compute the
    # GSPMD reduction reorder lands AT the quantum, so an untrained
    # flat-logit model can drift tokens across meshes — a dtype
    # property, not a sharding bug; the bf16 net above still carries
    # every timed number. Same conf + seed → identical master params.
    net_parity = ComputationGraph(conf).init()
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, VOCAB, (b, tp)).astype(np.int32)
    lengths = np.full(b, tp, np.int32)
    parity_prompts = [tokens[i, :tp] for i in range(min(b, 4))]

    def run_once(dec, k):
        """(tok/s, per-token latencies, readbacks per BLOCK) at block
        k — the shared --block-sweep timing loop on ``dec``."""
        tps, lats, nb, reads = _serving_run(dec, k, b, tokens, lengths,
                                            gen_t)
        return tps, lats, reads / nb

    # best K measured on the unsharded decoder (GEN_MESH_BLOCK pins it)
    dec0 = TransformerDecoder(net)
    blk_env = os.environ.get("GEN_MESH_BLOCK", "")
    if blk_env:
        best_k = int(blk_env)
    else:
        ks = sorted({int(t) for t in
                     os.environ.get("GEN_BLOCKS", "1,4,8").split(",")
                     if int(t) >= 1})
        by_k = {}
        for k in ks:
            run_once(dec0, k)                    # warm
            by_k[k] = float(np.median(
                [run_once(dec0, k)[0] for _ in range(RUNS)]))
        best_k = max(by_k, key=by_k.get)

    # parity references off the unsharded f32 twin
    pdec0 = TransformerDecoder(net_parity)
    ref_greedy = pdec0.generate(parity_prompts, 12, temperature=0.0,
                                block_size=best_k)
    ref_sampled = pdec0.generate(parity_prompts, 12, temperature=1.0,
                                 seed=11, block_size=best_k)

    shapes = [s.strip() for s in
              os.environ.get("GEN_MESH_SHAPES",
                             "1x1,2x1,1x2,4x1").split(",") if s.strip()]
    table = {}
    parity_ok = True
    for shp in shapes:
        try:
            data, tpx = parse_mesh_shape(shp)
        except ValueError as e:
            table[shp] = {"skipped": str(e)[:160]}
            continue
        if data * tpx > jax.device_count():
            table[shp] = {"skipped": f"needs {data * tpx} devices, "
                                     f"jax.device_count()="
                                     f"{jax.device_count()}"}
            continue
        try:
            mesh = generation_mesh(data, tpx)
            dec = TransformerDecoder(net, mesh=mesh)
            pdec = TransformerDecoder(net_parity, mesh=mesh)
            got_g = pdec.generate(parity_prompts, 12, temperature=0.0,
                                  block_size=best_k)
            got_s = pdec.generate(parity_prompts, 12, temperature=1.0,
                                  seed=11, block_size=best_k)
        except ValueError as e:
            table[shp] = {"skipped": str(e)[:160]}
            continue
        parity = (all(np.array_equal(a, g)
                      for a, g in zip(ref_greedy, got_g)) and
                  all(np.array_equal(a, g)
                      for a, g in zip(ref_sampled, got_s)))
        parity_ok = parity_ok and parity
        run_once(dec, best_k)                    # warm this mesh
        vals, lats, rpb = [], [], []
        for _ in range(RUNS):
            tps, ls, rp = run_once(dec, best_k)
            vals.append(tps)
            lats.extend(ls)
            rpb.append(rp)
        med = float(np.median(vals))
        pct = percentiles(lats, (50, 99))
        table[shp] = {
            "decode_tok_s": round(med, 1),
            "spread_pct": round(
                100.0 * (max(vals) - min(vals)) / med, 2) if med else 0.0,
            "p50_ms": round(pct["p50"] * 1e3, 3),
            "p99_ms": round(pct["p99"] * 1e3, 3),
            "readbacks_per_block": round(float(np.mean(rpb)), 4),
            "token_parity_vs_1x1": parity,
        }
    print(json.dumps({
        "mesh_sweep": table,
        "block_size": best_k,
        "shape": {"batch": b, "prompt_t": tp, "gen_t": gen_t,
                  "vocab": VOCAB, "d_model": DMODEL, "heads": HEADS,
                  "layers": LAYERS},
        "devices": jax.device_count(),
        "ok": parity_ok,
    }, indent=1), flush=True)
    return 0 if parity_ok else 1


def shared_prefix_sweep(gate: float = None) -> int:
    """--shared-prefix (ISSUE 12): N streams × ONE common system prompt
    — the paged-vs-slab A/B on the workload prefix caching exists for.
    Reports (a) prompt-prefill tok/s slab vs paged-with-prefix-hits and
    the speedup, (b) max CONCURRENT sequences at byte-identical KV pool
    budgets (devstats-verified), and (c) the prefix hit rate. With
    ``--gate X`` (default 5.0) exits non-zero unless the paged prefill
    speedup >= X, the concurrency ratio >= 3x, and the steady hit rate
    >= 0.9 — the ISSUE 12 acceptance bars.

    Knobs: GEN_PREFIX_LEN (default 192), GEN_PREFIX_TAIL (16),
    GEN_PREFIX_REQUESTS (16), GEN_PREFIX_GEN (4), GEN_SLOTS (4),
    GEN_PAGE_SIZE (16) — plus the model knobs above."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import (SlotGenerationEngine,
                                           TransformerDecoder,
                                           transformer_lm_conf)
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.observability.devstats import kv_cache_stats

    pfx = int(os.environ.get("GEN_PREFIX_LEN", "192"))
    tail = int(os.environ.get("GEN_PREFIX_TAIL", "16"))
    gen_t = int(os.environ.get("GEN_PREFIX_GEN", "4"))
    n_req = int(os.environ.get("GEN_PREFIX_REQUESTS", "16"))
    slots = int(os.environ.get("GEN_SLOTS", "4"))
    ps = int(os.environ.get("GEN_PAGE_SIZE", "16"))
    t_max = ((pfx + tail + gen_t) // ps + 2) * ps    # ps | t_max
    conf = transformer_lm_conf(vocab_size=VOCAB, d_model=DMODEL,
                               num_heads=HEADS, num_layers=LAYERS,
                               max_length=t_max)
    net = ComputationGraph(conf, compute_dtype=jnp.bfloat16).init()
    dec = TransformerDecoder(net)
    rng = np.random.default_rng(0)
    sys_p = rng.integers(0, VOCAB, pfx).astype(np.int32)
    prompts = [np.concatenate(
        [sys_p, rng.integers(0, VOCAB, tail).astype(np.int32)])
        for _ in range(n_req)]
    prompt_tokens = sum(len(p) for p in prompts)

    def stream_run(paged: bool):
        eng = SlotGenerationEngine(net, num_slots=slots, decoder=dec,
                                   paged=paged, page_size=ps)
        if paged:
            # prime: one request registers the prefix chain — the
            # measured stream is the steady (all-hit) serving state
            eng.submit(prompts[0], 1)
            eng.run_until_drained()
        for p in prompts:
            eng.submit(p, gen_t)
        t0 = time.perf_counter()
        eng.run_until_drained()
        wall = time.perf_counter() - t0
        st = eng.stats()
        return (prompt_tokens / wall, st["prefix_cache_hits"],
                st["prefix_cache_misses"])

    stream_run(False)                        # warm both compile paths
    stream_run(True)
    slab_med, slab_spread = _median(lambda: stream_run(False)[0])
    on_runs = [stream_run(True) for _ in range(RUNS)]
    paged_med = float(np.median([r[0] for r in on_runs]))
    hits, misses = on_runs[-1][1], on_runs[-1][2]
    hit_rate = hits / max(1, hits + misses)
    speedup = paged_med / slab_med if slab_med else 0.0

    # ---- max concurrent sequences at byte-identical pool budgets ----
    # the slab reserves t_max per slot; at the SAME devstats-verified
    # KV bytes the paged pool admits every short sequence its pages
    # actually fit — count live slots after ONE admission wave
    short = [rng.integers(0, VOCAB, max(2, ps // 2)).astype(np.int32)
             for _ in range(8 * slots)]
    slab_eng = SlotGenerationEngine(net, num_slots=slots, decoder=dec)
    paged_eng = SlotGenerationEngine(
        net, num_slots=8 * slots, decoder=dec, paged=True, page_size=ps,
        num_pages=slots * (t_max // ps) + 1)
    slab_bytes = kv_cache_stats(slab_eng)["bytes"]
    paged_bytes = kv_cache_stats(paged_eng)["bytes"]
    for eng in (slab_eng, paged_eng):
        for p in short:
            eng.submit(p, 2)
        eng._sweep_pending()
        eng._admit()
    slab_live = sum(r is not None for r in slab_eng._slots)
    paged_live = sum(r is not None for r in paged_eng._slots)
    slab_eng.run_until_drained()
    paged_eng.run_until_drained()
    ratio = paged_live / max(1, slab_live)

    out = {
        "shared_prefix": {
            "prefix_len": pfx, "tail_len": tail, "requests": n_req,
            "gen_tokens": gen_t, "slots": slots, "page_size": ps,
            "slab_prompt_tok_s": round(slab_med, 1),
            "slab_spread_pct": slab_spread,
            "paged_prompt_tok_s": round(paged_med, 1),
            "paged_prefill_speedup": round(speedup, 2),
            "prefix_hit_rate": round(hit_rate, 4),
            "prefix_hit_tokens": int(hits) * (pfx // ps) * ps,
        },
        "concurrency_at_fixed_bytes": {
            "kv_pool_bytes": {"slab": slab_bytes,
                              "paged": paged_bytes},
            "slab_concurrent": int(slab_live),
            "paged_concurrent": int(paged_live),
            "ratio": round(ratio, 2),
        },
    }
    ok = True
    if gate is not None:
        out["gate"] = {"min_prefill_speedup": gate,
                       "min_concurrency_ratio": 3.0,
                       "min_hit_rate": 0.9}
        ok = (speedup >= gate and ratio >= 3.0 and hit_rate >= 0.9)
        out["ok"] = ok
    print(json.dumps(out, indent=1), flush=True)
    return 0 if ok else 1


def integrity_ab(gate: float = None) -> int:
    """Sentinel + sampled-verification overhead A/B (ISSUE 15): the
    SDC defense on vs off at the K=4 soak shape (the chaos_soak model:
    tiny LM, paged ps=8, 2 slots, fused K=4 blocks, a mixed stream
    with a shared system prompt so prefix-cache hits — and therefore
    sampled content verification — land inside the timed region).
    Interleaved best-of reps, same noise policy as the journal A/B.
    ``--gate [PCT]`` (default 2.0) exits non-zero when the measured
    overhead exceeds PCT, or when the timed region compiled anything
    new on either arm (the sentinel must ride the EXISTING programs:
    its verdict column changes shapes at construction, never at
    steady state)."""
    from deeplearning4j_tpu.analysis.compile_audit import CompileAudit
    from deeplearning4j_tpu.models import (SlotGenerationEngine,
                                           TransformerDecoder,
                                           transformer_lm_conf)
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.observability.integrity import IntegrityConfig

    vocab, slots, k, ps = 12, 2, 4, 8
    net = ComputationGraph(transformer_lm_conf(
        vocab, d_model=32, num_heads=2, num_layers=2, max_length=32,
        learning_rate=1e-2, seed=5)).init()
    cfg = IntegrityConfig(kv_verify_rate=0.25)
    dec_on = TransformerDecoder(net, sentinel=True,
                                logit_bound=cfg.logit_bound)
    dec_off = TransformerDecoder(net)
    rng = np.random.default_rng(0)
    sys_prompt = rng.integers(0, vocab, 2 * ps + 1)
    reqs = []
    for i in range(48):
        if i % 2 == 0:      # half the stream shares the system prompt:
            p = np.concatenate(      # hits drive sampled verification
                [sys_prompt, rng.integers(0, vocab, 2)])
        else:
            p = rng.integers(0, vocab, int(rng.integers(2, 5)))
        reqs.append((p, int(rng.integers(4, 10))))

    def drain(on: bool) -> float:
        eng = SlotGenerationEngine(
            net, num_slots=slots, decoder=dec_on if on else dec_off,
            block_size=k, paged=True, page_size=ps, num_pages=96,
            tracing=False, max_pending=len(reqs) + 1,
            integrity=cfg if on else None)
        for p, g in reqs:
            eng.submit(p, g)
        t0 = time.perf_counter()
        eng.run_until_drained()
        return eng.emitted_tokens / (time.perf_counter() - t0)

    drain(True)                              # warm both arms' compiles
    drain(False)
    reps = int(os.environ.get("GEN_RUNS", "3"))
    on, off = [], []
    with CompileAudit() as audit:
        snap = audit.snapshot()
        for r in range(reps):
            # alternate the pair order (drift must not masquerade as
            # defense overhead — same policy as the journal A/B)
            if r % 2 == 0:
                on.append(drain(True))
                off.append(drain(False))
            else:
                off.append(drain(False))
                on.append(drain(True))
        steady_delta = audit.delta(snap)
    on_best, off_best = float(max(on)), float(max(off))
    overhead = round(100.0 * (1.0 - on_best / off_best), 2) \
        if off_best else None
    doc = {
        "integrity_ab": {
            "shape": {"slots": slots, "block": k, "page_size": ps,
                      "requests": len(reqs),
                      "verify_rate": cfg.kv_verify_rate},
            "integrity_on_tok_s": round(on_best, 1),
            "integrity_off_tok_s": round(off_best, 1),
            "integrity_on_tok_s_median": round(float(np.median(on)), 1),
            "integrity_off_tok_s_median": round(float(np.median(off)),
                                                1),
            "integrity_overhead_pct": overhead,
            "steady_new_compiles": steady_delta,
        }}
    ok = True
    if gate is not None:
        gate_ok = overhead is not None and overhead <= gate
        doc["integrity_ab"]["gate_pct"] = gate
        doc["integrity_ab"]["gate_ok"] = bool(gate_ok and
                                              not steady_delta)
        ok = bool(gate_ok and not steady_delta)
    print(json.dumps(doc), flush=True)
    return 0 if ok else 1


def spec_ab(gate: float = None) -> int:
    """Speculative decoding on/off A/B (ISSUE 16) at the block-sweep
    fallback shapes. Two workloads over ONE cyclic-trained tiny LM and
    ONE shared decoder (so both arms run the same compiled programs and
    the spec arm's fallback rungs are the off arm's own blocks):

    - high-acceptance: cyclic prompts the prompt-lookup drafter
      predicts near-perfectly — the verify forward scores the whole
      draft window (spec_k=16, decoupled from the fallback block) in
      ONE dispatch for roughly one block's bytes, so steady tok/s must
      clear ``gate``x (default 2x) the non-speculative arm;
    - adversarial: the drafter is patched to propose out-of-vocab
      candidates (guaranteed 0% acceptance), arming the adaptive
      fallback — tok/s must stay >= 0.95x of the off arm (the probe
      cadence is the only residual overhead).

    Exits non-zero when either bound fails at any swept shape, or when
    the timed region compiled anything (the spec<->fallback switch must
    ride already-compiled programs)."""
    from deeplearning4j_tpu.analysis.compile_audit import CompileAudit
    from deeplearning4j_tpu.models import (SlotGenerationEngine,
                                           TransformerDecoder,
                                           lm_batch, transformer_lm_conf)
    from deeplearning4j_tpu.models.speculative import NGramDrafter
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.observability.metrics import MetricsRegistry
    from deeplearning4j_tpu.observability.profiler import PhaseProfiler
    from deeplearning4j_tpu.ops.dataset import DataSet

    vocab, slots, ps, sk = 12, 4, 8, 16
    net = ComputationGraph(transformer_lm_conf(
        vocab, d_model=32, num_heads=2, num_layers=2, max_length=128,
        learning_rate=1e-2, seed=5)).init()
    rng = np.random.default_rng(3)
    # cyclic training data -> the model's greedy continuation IS the
    # cycle, which the suffix index predicts exactly: the honest
    # high-acceptance regime (prompt-echo), not a rigged drafter
    starts = rng.integers(0, vocab, (16, 1))
    seq = (starts + np.arange(17)[None, :]) % vocab
    x, y = lm_batch(seq, vocab)
    ds = DataSet(x, y)
    for _ in range(150):
        net.fit_batch(ds)
    dec = TransformerDecoder(net)
    prompts = [(int(rng.integers(0, vocab)) + np.arange(16)) % vocab
               for _ in range(24)]
    prompts = [p.astype(np.int32) for p in prompts]
    gens = [int(rng.integers(56, 65)) for _ in prompts]

    reg = MetricsRegistry()
    prof = PhaseProfiler(registry=reg)

    def drain(k: int, spec: bool) -> tuple:
        eng = SlotGenerationEngine(
            net, num_slots=slots, decoder=dec, block_size=k,
            paged=True, page_size=ps, num_pages=320, tracing=False,
            max_pending=len(prompts) + 1, registry=reg, profiler=prof,
            profiling=True, speculative=spec, spec_k=sk,
            spec_probe_every=64)
        outs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
        t0 = time.perf_counter()
        eng.run_until_drained()
        dt = time.perf_counter() - t0
        st = eng.stats()
        acc = st["spec_accepted_tokens"] / st["spec_drafted"] \
            if st["spec_drafted"] else None
        return (eng.emitted_tokens / dt, acc,
                [np.asarray(r.result(0)) for r in outs])

    reps = int(os.environ.get("GEN_RUNS", "4"))
    doc, ok = {"spec_ab": {}}, True
    gate = 2.0 if gate is None else float(gate)
    for k in (1, 2, 4):
        drain(k, True)                       # warm both arms' compiles
        drain(k, False)
        on, off = [], []
        with CompileAudit() as audit:
            snap = audit.snapshot()
            for r in range(reps):            # interleaved best-of, same
                if r % 2 == 0:               # drift policy as the other
                    on.append(drain(k, True))   # A/Bs in this file
                    off.append(drain(k, False))
                else:
                    off.append(drain(k, False))
                    on.append(drain(k, True))
            steady_delta = audit.delta(snap)
        # greedy parity IS part of the perf claim: a fast wrong stream
        # is not a speedup
        for a, b in zip(on[0][2], off[0][2]):
            np.testing.assert_array_equal(a, b)
        # adversarial arm: guaranteed-infeasible drafts (out-of-vocab
        # never equals a selection) -> 0% acceptance, fallback armed
        orig_draft = NGramDrafter.draft
        NGramDrafter.draft = lambda self, kk: np.full(kk, -1, np.int32)
        try:
            drain(k, True)                   # re-arm EWMA on bad drafts
            adv = [drain(k, True) for _ in range(reps)]
        finally:
            NGramDrafter.draft = orig_draft
        for a, b in zip(adv[0][2], off[0][2]):
            np.testing.assert_array_equal(a, b)   # fallback parity too
        on_best = float(max(v for v, _, _ in on))
        off_best = float(max(v for v, _, _ in off))
        adv_best = float(max(v for v, _, _ in adv))
        speedup = on_best / off_best if off_best else None
        adv_ratio = adv_best / off_best if off_best else None
        row = {
            "shape": {"slots": slots, "k": k, "spec_k": sk,
                      "page_size": ps, "requests": len(prompts)},
            "spec_tok_s": round(on_best, 1),
            "nonspec_tok_s": round(off_best, 1),
            "adversarial_tok_s": round(adv_best, 1),
            "speedup": round(speedup, 3) if speedup else None,
            "adversarial_ratio": round(adv_ratio, 3)
            if adv_ratio else None,
            "acceptance_rate": round(on[0][1], 4)
            if on[0][1] is not None else None,
            "adversarial_acceptance": round(adv[0][1], 4)
            if adv[0][1] is not None else None,
            "steady_new_compiles": steady_delta,
        }
        shape_ok = bool(speedup and speedup >= gate and
                        adv_ratio and adv_ratio >= 0.95 and
                        not steady_delta)
        row["ok"] = shape_ok
        ok = ok and shape_ok
        doc["spec_ab"][f"k{k}"] = row
    doc["spec_ab"]["gate"] = {"min_speedup": gate,
                              "min_adversarial_ratio": 0.95}
    doc["spec_ab"]["ok"] = ok
    print(json.dumps(doc, indent=1), flush=True)
    return 0 if ok else 1


def main() -> int:
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import (SlotGenerationEngine,
                                           TransformerDecoder,
                                           transformer_lm_conf)
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.observability.metrics import percentiles

    t_max = max(PROMPTS) + max(TOKENS) + 1
    conf = transformer_lm_conf(vocab_size=VOCAB, d_model=DMODEL,
                               num_heads=HEADS, num_layers=LAYERS,
                               max_length=t_max)
    net = ComputationGraph(conf, compute_dtype=jnp.bfloat16).init()
    dec = TransformerDecoder(net)
    rng = np.random.default_rng(0)

    for b in BATCHES:
        for tp in PROMPTS:
            tokens = rng.integers(0, VOCAB, (b, tp)).astype(np.int32)
            lengths = np.full(b, tp, np.int32)

            def prefill_once():
                caches = dec.init_cache(b)
                t0 = time.perf_counter()
                nxt, _, caches = dec.prefill(caches, tokens, lengths)
                np.asarray(nxt)
                return b * tp / (time.perf_counter() - t0), caches, nxt

            prefill_once()                       # warm the compile
            pre_med, pre_spread = _median(lambda: prefill_once()[0])

            dec.recompute_logits(tokens, lengths)     # warm baseline

            def nocache_once():
                t0 = time.perf_counter()
                for _ in range(NOCACHE_STEPS):
                    ids, _ = dec.recompute_logits(tokens, lengths)
                np.asarray(ids)
                return b * NOCACHE_STEPS / (time.perf_counter() - t0)

            nc_med, nc_spread = _median(nocache_once)

            for gen_t in TOKENS:
                def decode_once():
                    _, caches, nxt = prefill_once()
                    ids = np.asarray(nxt)
                    pos = lengths.copy()
                    lat = []
                    t0 = time.perf_counter()
                    for _ in range(gen_t):
                        s0 = time.perf_counter()
                        nx, _, caches = dec.decode_step(caches, ids, pos)
                        ids = np.asarray(nx)     # serving-pattern sync
                        lat.append(time.perf_counter() - s0)
                        pos = pos + 1
                    return b * gen_t / (time.perf_counter() - t0), lat

                decode_once()                    # warm the decode compile
                vals, lats = [], []
                for _ in range(RUNS):
                    v, lat = decode_once()
                    vals.append(v)
                    lats.extend(lat)
                med = float(np.median(vals))
                spread = 100.0 * (max(vals) - min(vals)) / med if med else 0
                pct = percentiles(lats, (50, 99))   # shared Histogram math
                print(json.dumps({
                    "point": {"batch": b, "prompt_t": tp, "gen_t": gen_t},
                    "prefill_tok_s": round(pre_med, 1),
                    "prefill_spread_pct": pre_spread,
                    "decode_tok_s": round(med, 1),
                    "decode_spread_pct": round(spread, 2),
                    "decode_p50_ms": round(pct["p50"] * 1e3, 3),
                    "decode_p99_ms": round(pct["p99"] * 1e3, 3),
                    "nocache_tok_s": round(nc_med, 1),
                    "nocache_spread_pct": nc_spread,
                    "decode_vs_recompute": round(med / nc_med, 2)
                    if nc_med else None,
                }), flush=True)

    # ---- continuous-batching A/B: mixed-length stream ----
    slots = int(os.environ.get("GEN_SLOTS", "8"))
    n_req = int(os.environ.get("GEN_REQUESTS", str(4 * slots)))
    req_rng = np.random.default_rng(7)
    tp, gen_t = max(PROMPTS), max(TOKENS)
    plens = req_rng.integers(max(8, tp // 8), max(16, tp // 2), n_req)
    gens = req_rng.integers(max(4, gen_t // 4), gen_t + 1, n_req)
    prompts = [req_rng.integers(0, VOCAB, n).astype(np.int32)
               for n in plens]

    def batching_run(refill):
        eng = SlotGenerationEngine(net, num_slots=slots, refill=refill,
                                   decoder=dec)
        for p, g in zip(prompts, gens):
            eng.submit(p, int(g))
        t0 = time.perf_counter()
        eng.run_until_drained()
        return (eng.emitted_tokens / (time.perf_counter() - t0),
                eng.decode_steps)

    batching_run(True)                           # warm slot-prefill buckets
    on = [batching_run(True) for _ in range(RUNS)]
    off = [batching_run(False) for _ in range(RUNS)]
    on_med = float(np.median([x[0] for x in on]))
    off_med = float(np.median([x[0] for x in off]))
    print(json.dumps({
        "continuous_batching": {
            "slots": slots, "requests": n_req,
            "refill_on_tok_s": round(on_med, 1),
            "refill_off_tok_s": round(off_med, 1),
            "refill_speedup": round(on_med / off_med, 3) if off_med else None,
            "decode_steps_on": on[0][1], "decode_steps_off": off[0][1],
        }}), flush=True)
    return 0


if __name__ == "__main__":
    from deeplearning4j_tpu.ops.platform import configure_compilation_cache
    configure_compilation_cache()
    if "--block-sweep" in sys.argv[1:]:
        sys.exit(block_sweep())
    if "--mesh-sweep" in sys.argv[1:]:
        sys.exit(mesh_sweep())
    if "--shared-prefix" in sys.argv[1:]:
        _gate = None
        if "--gate" in sys.argv[1:]:
            _i = sys.argv.index("--gate")
            _nxt = sys.argv[_i + 1] if _i + 1 < len(sys.argv) else ""
            _gate = float(_nxt) if _nxt.replace(
                ".", "", 1).isdigit() else 5.0
        sys.exit(shared_prefix_sweep(gate=_gate))
    if "--spec-ab" in sys.argv[1:]:
        _gate = None
        if "--gate" in sys.argv[1:]:
            _i = sys.argv.index("--gate")
            _nxt = sys.argv[_i + 1] if _i + 1 < len(sys.argv) else ""
            _gate = float(_nxt) if _nxt.replace(
                ".", "", 1).isdigit() else 2.0
        sys.exit(spec_ab(gate=_gate))
    if "--integrity-ab" in sys.argv[1:]:
        _gate = None
        if "--gate" in sys.argv[1:]:
            _i = sys.argv.index("--gate")
            _nxt = sys.argv[_i + 1] if _i + 1 < len(sys.argv) else ""
            _gate = float(_nxt) if _nxt.replace(
                ".", "", 1).isdigit() else 2.0
        sys.exit(integrity_ab(gate=_gate))
    sys.exit(main())
