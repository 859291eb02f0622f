#!/usr/bin/env python
"""Symmetric-vs-disaggregated serving A/B at FIXED total worker count
(ISSUE 14): does splitting the fleet into prefill and decode workers
keep prefill bursts from moving decode p99 — without giving up
aggregate throughput?

Both arms run the SAME submission schedule against the SAME shared
decoder (compiles warm before timing), two workers each:

- **symmetric** — an ``EngineFleetRouter`` with 2 both-phase paged
  replicas (the r13/r17 fleet): every worker prefills AND decodes, so
  a burst of long prompts stalls each worker's decode streams for the
  duration of its prefill dispatches.
- **disagg** — a ``PhaseRouter`` with 1 prefill + 1 decode worker:
  bursts land on the prefill worker only; the active streams keep
  decoding on the decode worker, reached through the measured KV-page
  handoff.

The workload is steady short-prompt decode streams with a burst of
long prompts dropped partway through. Reported per arm, from a per-arm
SLOTracker over the STEADY streams only: per-token p50/p99 (whole-life
(finish − first token)/(tokens − 1) — burst-induced stalls land here),
TTFT p99, aggregate decode tok/s, and — for the disagg arm — the
EXACT transfer account: every shipped byte must equal pages x the
pool's per-page bytes + token payload ("Densifying Assumed-sparse
Tensors": transfer cost is measured, never assumed).

    JAX_PLATFORMS=cpu python scripts/perf_disagg.py
    python scripts/perf_disagg.py --gate   # exit 1 unless steady p99
                                           # improves >= 2x at >= 0.95x
                                           # aggregate tok/s, transfer
                                           # account exact, {} steady
                                           # compiles on the disagg arm

Emits a bench-style ``history_record`` (scripts/perf_regress.py
normalization) so the perf-regression sentinel tracks the improvement
across rounds. Shrink with DISAGG_STEADY/BURST/PROMPT/... for smoke.
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


def _ms(agg: dict, key: str, q: str):
    val = (agg.get(key) or {}).get(q)
    return None if val is None else round(val * 1e3, 3)


def run_arm(net, dec, *, disagg: bool, n_steady: int, n_burst: int,
            steady_prompt: int, burst_prompt: int, steady_gen: int,
            burst_gen: int, num_slots: int, page_size: int,
            block_size: int, seed: int, slo_cls, registry_cls) -> dict:
    """One arm: identical schedule, 2 workers, per-arm registry + SLO
    tracker. Slot budget is FIXED fleet-wide (slots are KV memory, the
    per-chip budget): symmetric = 2 workers x ``num_slots`` decode
    slots; disagg = ONE decode worker holding all ``2 x num_slots``
    (its whole memory is KV — that is the point of the split) and a
    prefill worker whose slots are admission parallelism only. The
    disagg arm records every ship for the exact transfer cross-check."""
    import numpy as np

    from deeplearning4j_tpu.streaming.disagg import (PhaseRouter,
                                                     SerializedKVTransport)
    from deeplearning4j_tpu.streaming.fleet import EngineFleetRouter

    rng = np.random.default_rng(seed)
    v = dec.vocab_size
    steady = [rng.integers(0, v, steady_prompt).astype(np.int32)
              for _ in range(n_steady)]
    burst = [rng.integers(0, v, burst_prompt).astype(np.int32)
             for _ in range(n_burst)]
    reg = registry_cls()
    slo = slo_cls(registry=reg)
    common = dict(decoder=dec, page_size=page_size,
                  block_size=block_size, registry=reg, slo_tracker=slo,
                  max_pending=4 * (n_steady + n_burst),
                  heartbeat_interval=0.05, monitor_interval=0.05,
                  suspect_after=0.5, dead_after=2.0)
    transport = None
    if disagg:
        transport = SerializedKVTransport(record_ships=True)
        router = PhaseRouter(net, prefill_replicas=1, decode_replicas=1,
                             transport=transport,
                             prefill_slots=num_slots,
                             decode_slots=2 * num_slots,
                             **common).start()
    else:
        router = EngineFleetRouter(net, num_replicas=2, paged=True,
                                   num_slots=num_slots,
                                   **common).start()

    t0 = time.perf_counter()
    handles = []
    burst_at = max(1, n_steady // 4)
    for i, p in enumerate(steady):
        handles.append(router.submit(p, steady_gen, route="steady"))
        if i == burst_at:
            for q in burst:
                handles.append(router.submit(q, burst_gen,
                                             route="burst"))
        time.sleep(0.01)
    for h in handles:
        h.result(600)
    wall = time.perf_counter() - t0
    stats = router.stats()
    out = {"mode": "disagg" if disagg else "symmetric",
           "wall_s": round(wall, 3),
           "decode_tok_s": round(stats["emitted_tokens"] / wall, 1),
           "requests": len(handles)}
    if disagg:
        d = router.disagg_stats()
        # per-page pool bytes from the decode worker's live pool —
        # the devstats-side number the measured bytes must match
        rep = router._replicas[router.role_ids("decode")[0]]
        eng = rep.engine.engine if rep.supervised else rep.engine
        page_bytes = eng._pool_bytes() // eng.num_pages
        ship_pages = sum(p for p, _, _ in transport.ships)
        ship_bytes = sum(b for _, b, _ in transport.ships)
        ship_tok = sum(t for _, _, t in transport.ships)
        out["handoffs"] = d["handoffs"]
        out["transfer"] = {
            "pages": ship_pages, "bytes": ship_bytes,
            "token_bytes": ship_tok, "page_bytes": page_bytes,
            "kb_per_handoff": round(ship_bytes / 1024 /
                                    max(1, len(transport.ships)), 2),
            "exact": bool(
                d["handoffs"]["bytes"] == ship_bytes and
                d["handoffs"]["pages"] == ship_pages and
                ship_bytes == ship_pages * page_bytes + ship_tok)}
    router.shutdown()
    snap = slo.snapshot()
    agg = (snap.get("routes") or {}).get("steady") or {}
    out.update({
        "steady_per_token_p50_ms": _ms(agg, "per_token_s", "p50"),
        "steady_per_token_p99_ms": _ms(agg, "per_token_s", "p99"),
        "steady_ttft_p99_ms": _ms(agg, "ttft_s", "p99")})
    return out


def run_ab(seed: int = 0, audit=None, shape=None) -> dict:
    """The full A/B (reusable by bench.py's ``disagg`` side metric):
    warm both arms, time symmetric, snapshot compiles, time disagg,
    and return the joined document. ``shape`` overrides the env-driven
    dimensions (bench passes a smoke shape)."""
    from deeplearning4j_tpu.models import transformer_lm_conf
    from deeplearning4j_tpu.models.generation import TransformerDecoder
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.observability.metrics import MetricsRegistry
    from deeplearning4j_tpu.observability.slo import SLOTracker

    sh = {
        "d_model": _env_int("DISAGG_DMODEL", 128),
        "layers": _env_int("DISAGG_LAYERS", 2),
        "heads": _env_int("DISAGG_HEADS", 4),
        "vocab": _env_int("DISAGG_VOCAB", 256),
        "n_steady": _env_int("DISAGG_STEADY", 12),
        "n_burst": _env_int("DISAGG_BURST", 6),
        "steady_prompt": _env_int("DISAGG_STEADY_PROMPT", 8),
        "burst_prompt": _env_int("DISAGG_PROMPT", 384),
        "steady_gen": _env_int("DISAGG_STEADY_GEN", 48),
        "burst_gen": _env_int("DISAGG_BURST_GEN", 4),
        "num_slots": _env_int("DISAGG_SLOTS", 4),
        "page_size": _env_int("DISAGG_PAGE", 16),
        "block_size": _env_int("DISAGG_BLOCK", 4),
    }
    if shape:
        sh.update(shape)
    t_max = _env_int("DISAGG_TMAX", max(
        512, sh["burst_prompt"] + sh["burst_gen"] + 16))

    net = ComputationGraph(transformer_lm_conf(
        sh["vocab"], d_model=sh["d_model"], num_heads=sh["heads"],
        num_layers=sh["layers"], max_length=t_max,
        learning_rate=1e-2, seed=5)).init()
    dec = TransformerDecoder(net)
    common = dict(n_steady=sh["n_steady"], n_burst=sh["n_burst"],
                  steady_prompt=sh["steady_prompt"],
                  burst_prompt=sh["burst_prompt"],
                  steady_gen=sh["steady_gen"],
                  burst_gen=sh["burst_gen"],
                  num_slots=sh["num_slots"], page_size=sh["page_size"],
                  block_size=sh["block_size"], seed=seed,
                  slo_cls=SLOTracker, registry_cls=MetricsRegistry)

    # warmup: the FULL prompt mix at tiny generation budgets — the
    # measured phase's admission buckets (count x tail-length, both
    # pow2) and the export/import page-count buckets only cover when
    # the warm arm coalesces the same batches the measured arm will
    warm = dict(common, steady_gen=4, burst_gen=2)
    run_arm(net, dec, disagg=False, **warm)
    run_arm(net, dec, disagg=True, **warm)

    symmetric = run_arm(net, dec, disagg=False, **common)
    snap = audit.snapshot() if audit is not None else None
    disagg = run_arm(net, dec, disagg=True, **common)
    steady_delta = audit.delta(snap) if audit is not None else None

    p99_s, p99_d = (symmetric["steady_per_token_p99_ms"],
                    disagg["steady_per_token_p99_ms"])
    speedup = None if not p99_s or not p99_d \
        else round(p99_s / p99_d, 2)
    tok_ratio = round(disagg["decode_tok_s"] /
                      symmetric["decode_tok_s"], 4) \
        if symmetric["decode_tok_s"] else None
    return {"symmetric": symmetric, "disagg": disagg,
            "steady_p99_improvement_x": speedup,
            "decode_tok_s_ratio": tok_ratio,
            "disagg_steady_new_compiles": steady_delta,
            "shape": dict(sh, t_max=t_max)}


def _attach_history(out: dict) -> None:
    """Bench-style flat record: perf_regress.normalize_record over a
    synthetic doc whose side metrics carry the A/B headline numbers —
    archived rounds then gate drift in the improvement factor, the
    throughput ratio, and the per-handoff wire cost."""
    try:
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "_disagg_perf_regress",
            os.path.join(REPO_ROOT, "scripts", "perf_regress.py"))
        pr = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(pr)
        doc = {"metric": "disagg_burst_steady_p99_improvement_x",
               "value": out.get("steady_p99_improvement_x"),
               "side_metrics": {
                   "disagg_decode_tok_s_ratio":
                       {"value": out.get("decode_tok_s_ratio")},
                   "disagg_transfer_kb_per_handoff":
                       {"value": (out["disagg"].get("transfer") or
                                  {}).get("kb_per_handoff")}}}
        out["history_record"] = pr.normalize_record(doc)
    except Exception as e:   # noqa: BLE001 — trajectory must not kill
        out["history_record"] = {"error": str(e)[:200]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--gate", action="store_true",
                    help="exit 1 unless steady per-token p99 improves "
                         ">= --min-p99-x with aggregate tok/s >= "
                         "--min-tok-ratio, the transfer byte account "
                         "exact, and {} compiles across the measured "
                         "disagg arm")
    ap.add_argument("--min-p99-x", type=float, default=2.0)
    ap.add_argument("--min-tok-ratio", type=float, default=0.95)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from deeplearning4j_tpu.analysis.compile_audit import CompileAudit

    with CompileAudit() as audit:
        out = run_ab(seed=args.seed, audit=audit)
    _attach_history(out)
    print(json.dumps(out, indent=None if args.json else 1, default=str))

    if args.gate:
        rc = 0
        sp = out["steady_p99_improvement_x"]
        tr = out["decode_tok_s_ratio"]
        tx = (out["disagg"].get("transfer") or {})
        if sp is None or sp < args.min_p99_x:
            print(f"FAIL: steady p99 improvement {sp}x < "
                  f"{args.min_p99_x}x", file=sys.stderr)
            rc = 1
        if tr is None or tr < args.min_tok_ratio:
            print(f"FAIL: aggregate tok/s ratio {tr} < "
                  f"{args.min_tok_ratio}", file=sys.stderr)
            rc = 1
        if not tx.get("exact"):
            print(f"FAIL: transfer account not exact: {tx}",
                  file=sys.stderr)
            rc = 1
        if out["disagg_steady_new_compiles"]:
            print(f"FAIL: disagg arm compiled in steady state: "
                  f"{out['disagg_steady_new_compiles']}",
                  file=sys.stderr)
            rc = 1
        return rc
    return 0


if __name__ == "__main__":
    from deeplearning4j_tpu.ops.platform import configure_compilation_cache
    configure_compilation_cache()
    sys.exit(main())
