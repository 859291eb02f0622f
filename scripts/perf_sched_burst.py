#!/usr/bin/env python
"""Long-prompt-burst scheduling A/B (ISSUE 11): FIFO whole-prompt
admission vs the scheduling tier (EDF + chunked prefill + adaptive
decode block size), under the workload the tier exists for — steady
short interactive streams with a burst of long prompts dropped on top.

Both arms run the SAME submission schedule against the SAME shared
decoder (compiles warm before timing):

- **fifo** — the legacy engine: FIFO queue order, whole-prompt batched
  prefill, fixed block size. A long prefill monopolizes the device for
  its full duration, so every in-flight short stream's inter-token
  latency spikes while it runs.
- **sched** — ``scheduling="edf"``, ``prefill_chunk=C`` (long prompts
  fill their cache window by window, interleaved with decode blocks),
  ``adaptive_block=True`` (K follows queue depth, capped by the
  measured block latency).

Reported per arm, from a per-arm SLOTracker over the SHORT streams
only: per-token p50/p99 (steady decode: (finish − first token) /
(tokens − 1)), TTFT p99, plus aggregate decode tok/s and — under
``--audit-compiles`` — the CompileAudit delta across the measured
phase (adaptive-K switching must lower NOTHING once warm).

    JAX_PLATFORMS=cpu python scripts/perf_sched_burst.py
    python scripts/perf_sched_burst.py --gate     # exit 1 unless p99
                                                  # improves >= 2x at
                                                  # tok/s within 5%

Shrink with BURST_DMODEL/LAYERS/VOCAB/SHORTS/LONGS/PROMPT for smoke.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


def run_arm(net, dec, *, sched: bool, n_short: int, n_long: int,
            short_prompt: int, long_prompt: int, short_gen: int,
            long_gen: int, num_slots: int, chunk: int, seed: int,
            slo_cls, registry_cls) -> dict:
    """One arm: identical schedule, per-arm registry + SLO tracker."""
    import numpy as np

    from deeplearning4j_tpu.models.generation import SlotGenerationEngine

    rng = np.random.default_rng(seed)
    v = dec.vocab_size
    shorts = [rng.integers(0, v, short_prompt).astype(np.int32)
              for _ in range(n_short)]
    longs = [rng.integers(0, v, long_prompt).astype(np.int32)
             for _ in range(n_long)]
    reg = registry_cls()
    slo = slo_cls(registry=reg)
    kw = dict(scheduling="edf", prefill_chunk=chunk, adaptive_block=True,
              block_ladder=(1, 2, 4, 8)) if sched else \
        dict(block_size=4)
    eng = SlotGenerationEngine(net, num_slots=num_slots, decoder=dec,
                               registry=reg, slo=slo, tracing=True,
                               max_pending=4 * (n_short + n_long),
                               **kw).start()
    t0 = time.perf_counter()
    handles = []
    # steady short streams, burst of longs dropped at ~1/4 through
    burst_at = max(1, n_short // 4)
    for i, p in enumerate(shorts):
        handles.append(eng.submit(p, short_gen, route="short"))
        if i == burst_at:
            for q in longs:
                handles.append(eng.submit(q, long_gen, route="burst"))
        time.sleep(0.01)
    for h in handles:
        h.result(600)
    wall = time.perf_counter() - t0
    stats = eng.stats()
    eng.shutdown()
    snap = slo.snapshot()
    short_agg = (snap.get("routes") or {}).get("short") or {}
    return {"mode": "sched" if sched else "fifo",
            "wall_s": round(wall, 3),
            "decode_tok_s": round(stats["emitted_tokens"] / wall, 1),
            "short_per_token_p50_ms": _ms(short_agg, "per_token_s",
                                          "p50"),
            "short_per_token_p99_ms": _ms(short_agg, "per_token_s",
                                          "p99"),
            "short_ttft_p99_ms": _ms(short_agg, "ttft_s", "p99"),
            "prefill_chunks": int(stats["prefill_chunks"]),
            "requests": len(handles)}


def _ms(agg: dict, key: str, q: str):
    val = (agg.get(key) or {}).get(q)
    return None if val is None else round(val * 1e3, 3)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--gate", action="store_true",
                    help="exit 1 unless short-stream per-token p99 "
                         "improves >= 2x with decode tok/s within 5%%")
    ap.add_argument("--audit-compiles", action="store_true",
                    help="assert {} compile delta across the measured "
                         "sched arm (adaptive-K switching lowers "
                         "nothing once warm)")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from deeplearning4j_tpu.analysis.compile_audit import CompileAudit
    from deeplearning4j_tpu.models import transformer_lm_conf
    from deeplearning4j_tpu.models.generation import TransformerDecoder
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.observability.metrics import MetricsRegistry
    from deeplearning4j_tpu.observability.slo import SLOTracker

    d_model = _env_int("BURST_DMODEL", 128)
    layers = _env_int("BURST_LAYERS", 2)
    vocab = _env_int("BURST_VOCAB", 256)
    n_short = _env_int("BURST_SHORTS", 24)
    n_long = _env_int("BURST_LONGS", 6)
    short_prompt = _env_int("BURST_SHORT_PROMPT", 8)
    long_prompt = _env_int("BURST_PROMPT", 384)
    short_gen = _env_int("BURST_SHORT_GEN", 32)
    long_gen = _env_int("BURST_LONG_GEN", 8)
    num_slots = _env_int("BURST_SLOTS", 4)
    chunk = _env_int("BURST_CHUNK", 32)
    t_max = _env_int("BURST_TMAX", max(512, long_prompt + long_gen + 8))

    net = ComputationGraph(transformer_lm_conf(
        vocab, d_model=d_model, num_heads=4, num_layers=layers,
        max_length=t_max, learning_rate=1e-2, seed=5)).init()
    dec = TransformerDecoder(net)

    common = dict(n_short=n_short, n_long=n_long,
                  short_prompt=short_prompt, long_prompt=long_prompt,
                  short_gen=short_gen, long_gen=long_gen,
                  num_slots=num_slots, chunk=chunk, seed=args.seed,
                  slo_cls=SLOTracker, registry_cls=MetricsRegistry)

    with CompileAudit() as audit:
        # warmup: one small pass per arm compiles every program the
        # measured phase uses (incl. every adaptive rung + the chunk)
        warm = dict(common, n_short=max(4, num_slots),
                    n_long=2, short_gen=8, long_gen=4)
        run_arm(net, dec, sched=False, **warm)
        run_arm(net, dec, sched=True, **warm)
        # the warm arms' queue depths need not visit every adaptive
        # rung — lower each one explicitly (caches are donated per
        # dispatch: thread the returned ones)
        import numpy as np
        caches = dec.init_cache(num_slots)
        ids = np.zeros(num_slots, np.int32)
        pos = np.full(num_slots, short_prompt, np.int32)
        for k in (1, 2, 4, 8):
            _, _, _, _, caches = dec.decode_block(caches, ids, pos,
                                                  block_size=k)
        del caches

        fifo = run_arm(net, dec, sched=False, **common)
        snap = audit.snapshot()
        sched = run_arm(net, dec, sched=True, **common)
        sched_delta = audit.delta(snap)

    p99_f = fifo["short_per_token_p99_ms"]
    p99_s = sched["short_per_token_p99_ms"]
    speedup = None if not p99_f or not p99_s else round(p99_f / p99_s, 2)
    tok_ratio = round(sched["decode_tok_s"] / fifo["decode_tok_s"], 4) \
        if fifo["decode_tok_s"] else None
    out = {"fifo": fifo, "sched": sched,
           "short_p99_improvement_x": speedup,
           "decode_tok_s_ratio": tok_ratio,
           "sched_steady_new_compiles": sched_delta,
           "shape": {"d_model": d_model, "layers": layers,
                     "vocab": vocab, "t_max": t_max,
                     "long_prompt": long_prompt, "chunk": chunk,
                     "slots": num_slots}}
    print(json.dumps(out, indent=None if args.json else 1,
                     default=str))
    if args.audit_compiles and sched_delta:
        print(f"FAIL: adaptive switching compiled: {sched_delta}",
              file=sys.stderr)
        return 1
    if args.gate:
        if speedup is None or speedup < 2.0:
            print(f"FAIL: p99 improvement {speedup}x < 2x",
                  file=sys.stderr)
            return 1
        if tok_ratio is None or tok_ratio < 0.95:
            print(f"FAIL: decode tok/s ratio {tok_ratio} < 0.95",
                  file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    from deeplearning4j_tpu.ops.platform import configure_compilation_cache
    configure_compilation_cache()
    sys.exit(main())
