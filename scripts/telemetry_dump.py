#!/usr/bin/env python
"""Fetch a running engine's telemetry snapshot and pretty-print it.

Talks to an observability TelemetryServer (``/snapshot`` by default;
``--metrics`` for the raw Prometheus text, ``--traces [N]`` for recent
request timelines, ``--slo`` for the SLO tracker document, ``--fleet``
for an EngineFleetRouter's replica table, ``--scrape`` to merge N
replicas' snapshots into one fleet summary, ``--watch`` to re-scrape
periodically and print deltas) over plain HTTP — no in-process
imports, so it works against any serving process on any host:

    python scripts/telemetry_dump.py http://127.0.0.1:9100
    python scripts/telemetry_dump.py http://127.0.0.1:9100 --json
    python scripts/telemetry_dump.py http://host:9100 --traces 5
    python scripts/telemetry_dump.py http://host:9100 --metrics
    python scripts/telemetry_dump.py http://host:9100 --slo
    python scripts/telemetry_dump.py http://host:9100 --fleet
    python scripts/telemetry_dump.py --scrape http://h1:9100,http://h2:9100,http://h3:9100
    python scripts/telemetry_dump.py http://host:9100 --watch 5

``--fleet`` expects the serving process to have registered the
router's ``fleet_stats`` as a snapshot source
(``TelemetryServer.add_source("fleet", router.fleet_stats)``); it
pretty-prints every fleet-shaped source it finds — per-replica health
state, heartbeat age, live load vs capacity, plus the exactly-once
ledger and fleet counters.

``--scrape URL,URL,...`` (ISSUE 9) is the fleet-wide view for
SEPARATE-PROCESS replicas, each running its own TelemetryServer: it
fetches every replica's ``/snapshot`` and merges them into one
document — aggregate SLO attainment/burn (windows pooled by summing
met/n across replicas), a per-replica health table (reachability,
uptime, attainment, deadline-headroom quantiles, KV-cache bytes,
durable-journal backlog/degraded state), and fleet-wide summed
counters. An unreachable replica degrades to a ``down`` row; the
merge never fails the scrape. ``--fleet`` likewise prints each fleet
source's journal health line when the router carries a RequestJournal.

``--watch SECS`` re-samples the target (single URL or ``--scrape``
set) every SECS seconds and prints DELTAS between samples — counter
rates (/s), gauge changes, replica up/down transitions and attainment
moves — the live view for babysitting a soak. ``--count N`` bounds the
number of samples (default: until interrupted).

The pretty printer groups the nested registry snapshot by family:
counters/gauges one line per labeled child, histograms as
count/sum/p50/p99, then the transfer deltas, compile audit (when the
server runs one), and every registered stats source.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.error
import urllib.request


def fetch(url: str, timeout: float = 5.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        body = resp.read().decode()
        if resp.headers.get_content_type() == "application/json":
            return json.loads(body)
        return body


def _fmt_hist(h: dict) -> str:
    p50 = h.get("p50")
    p99 = h.get("p99")
    ms = (lambda v: "-" if v is None else f"{v * 1e3:.3f}ms")
    return (f"count={h.get('count')} sum={h.get('sum'):.6g}s "
            f"p50={ms(p50)} p99={ms(p99)}")


def pretty(snapshot: dict, out=sys.stdout) -> None:
    w = out.write
    w(f"uptime: {snapshot.get('uptime_s', '?')}s\n")
    metrics = snapshot.get("metrics", {})
    for name in sorted(metrics):
        fam = metrics[name]
        w(f"\n{name}  [{fam.get('type')}]")
        if fam.get("help"):
            w(f"  — {fam['help']}")
        w("\n")
        for label, value in fam.get("values", {}).items():
            tag = f"{{{label}}}" if label else ""
            if isinstance(value, dict):          # histogram child
                w(f"  {tag:<40} {_fmt_hist(value)}\n")
            else:
                w(f"  {tag:<40} {value}\n")
    transfers = snapshot.get("transfers")
    if transfers:
        w("\ndevice→host readbacks since server start:\n")
        for tag, n in transfers.items():
            w(f"  {tag:<40} {n}\n")
    audit = snapshot.get("compile_audit")
    if audit:
        w(f"\ncompile audit: total={audit.get('total_compiles')} "
          f"duplicate_signature="
          f"{audit.get('duplicate_signature_compiles')}\n")
        new = audit.get("new_since_start")
        w(f"  new since server start: {new if new else '{} (steady)'}\n")
    traces = snapshot.get("traces")
    if traces:
        w(f"\ntraces: {traces.get('completed')} completed "
          f"({traces.get('ring')} in ring)\n")
    for name, src in (snapshot.get("sources") or {}).items():
        w(f"\nsource {name}:\n")
        if isinstance(src, dict):
            for k in sorted(src):
                w(f"  {k:<40} {src[k]}\n")
        else:
            w(f"  {src}\n")


def _fleet_sources(snapshot: dict) -> dict:
    """Every snapshot source with the ``fleet_stats()`` shape (a
    ``replicas`` table plus a ``ledger``) — the router's registration
    name is the caller's choice, so match on shape, not name."""
    return {name: src
            for name, src in (snapshot.get("sources") or {}).items()
            if isinstance(src, dict)
            and isinstance(src.get("replicas"), dict)
            and isinstance(src.get("ledger"), dict)}


def pretty_fleet(snapshot: dict, out=sys.stdout) -> int:
    w = out.write
    fleets = _fleet_sources(snapshot)
    if not fleets:
        w("no fleet sources in /snapshot (register one with "
          "TelemetryServer.add_source('fleet', router.fleet_stats))\n")
        return 2
    for name, src in sorted(fleets.items()):
        w(f"fleet {src.get('fleet', '?')}  (source '{name}')\n")
        hdr = (f"  {'replica':<9} {'role':<8} {'state':<8} {'hb-age':>7} "
               f"{'load':>5} {'cap':>4} {'queue':>6} {'active':>7} "
               f"{'sup':>4} {'reach':>6}\n")
        w(hdr)
        for rid, row in sorted(src["replicas"].items()):
            age = row.get("heartbeat_age_s")
            fmt = (lambda v: "-" if v is None else str(v))
            w(f"  {rid:<9} {fmt(row.get('role')):<8} "
              f"{row.get('state', '?'):<8} "
              f"{'-' if age is None else f'{age:.3f}s':>7} "
              f"{fmt(row.get('load')):>5} {fmt(row.get('capacity')):>4} "
              f"{fmt(row.get('queue_depth')):>6} "
              f"{fmt(row.get('active_slots')):>7} "
              f"{'y' if row.get('supervised') else 'n':>4} "
              f"{'y' if row.get('reachable') else 'n':>6}\n")
        led = src["ledger"]
        w("  ledger: " + " ".join(f"{k}={led[k]}" for k in sorted(led))
          + "\n")
        dg = src.get("disagg")
        if isinstance(dg, dict):
            ho = dg.get("handoffs") or {}
            w(f"  disagg: handoffs={ho.get('completed')} "
              f"fenced={ho.get('fenced')} failed={ho.get('failed')} "
              f"pages={ho.get('pages')} bytes={ho.get('bytes')} "
              f"transport="
              f"{(dg.get('transport') or {}).get('transport')}\n")
        jr = src.get("journal")
        if isinstance(jr, dict):
            w(f"  journal: pending={jr.get('pending')} "
              f"degraded={'Y' if jr.get('degraded') else 'n'} "
              f"bytes={jr.get('bytes')} "
              f"segments={jr.get('segments')} "
              f"fsync={jr.get('fsync_policy')} "
              f"dropped={jr.get('dropped_records')} "
              f"recovered={jr.get('recovered_requests')}\n")
        counters = src.get("counters") or {}
        if counters:
            w("  counters: " + " ".join(f"{k}={counters[k]}"
                                        for k in sorted(counters)) + "\n")
        w("\n")
    return 0


def pretty_traces(doc: dict, out=sys.stdout) -> None:
    w = out.write
    w(f"{doc.get('count', 0)} trace(s) "
      f"(of {doc.get('total_completed', '?')} completed)\n")
    for t in doc.get("traces", []):
        w(f"\n{t['request_id']}  status={t.get('status')} "
          f"duration={t.get('duration_ms')}ms"
          f"{'  dropped=' + str(t['dropped_spans']) if t.get('dropped_spans') else ''}\n")
        for s in t.get("spans", []):
            attrs = "" if not s.get("attrs") else \
                "  " + json.dumps(s["attrs"], default=str)
            w(f"  {s['t0']:>10.4f}s  {s['name']:<14} "
              f"{s['duration_ms']:>9.3f}ms{attrs}\n")


def scrape_fleet(urls, timeout: float = 5.0) -> dict:
    """Fetch every replica's ``/snapshot`` and merge (ISSUE 9): one
    fleet document with aggregate SLO attainment (windows pooled by
    summing met/n — exact, unlike averaging ratios), a per-replica
    health/headroom table, and fleet-wide summed counters. Unreachable
    replicas degrade to ``up: False`` rows."""
    per_url = {}
    for url in urls:
        base = url.rstrip("/")
        try:
            per_url[base] = fetch(f"{base}/snapshot", timeout)
        except (urllib.error.URLError, OSError, ValueError,
                TimeoutError) as e:
            per_url[base] = {"__error__": f"{type(e).__name__}: {e}"}
    return merge_snapshots(per_url)


def _kv_bytes(snap: dict):
    kv = ((snap.get("devstats") or {}).get("kv_cache") or {})
    vals = [v.get("bytes") for v in kv.values()
            if isinstance(v, dict) and isinstance(v.get("bytes"), int)]
    return sum(vals) if vals else None


def _bubble_col(snap: dict):
    """Decode pipeline bubble-% of one replica, from the /snapshot
    profiler headline (None when the replica predates the profiler)."""
    return ((snap.get("profiler") or {}).get("headline") or {}) \
        .get("bubble_pct")


def _counter_sum(snap: dict, family: str):
    """Sum a counter family's children from a snapshot's metrics (e.g.
    ``kv_transfer_bytes_total`` across a replica's fleets); None when
    the family is absent."""
    doc = (snap.get("metrics") or {}).get(family) or {}
    if doc.get("type") != "counter":
        return None
    vals = [v for v in (doc.get("values") or {}).values()
            if isinstance(v, (int, float))]
    return sum(vals) if vals else None


def _role_col(snap: dict):
    """P / D / P+D from the ``generation_engine_role`` gauge family
    (disagg tier): which phase roles this replica's engines serve;
    None for a classic both-phase replica (prints '-')."""
    doc = (snap.get("metrics") or {}).get("generation_engine_role") or {}
    if doc.get("type") != "gauge":
        return None
    roles = set()
    for key, v in (doc.get("values") or {}).items():
        if not v:
            continue
        for part in str(key).split(","):
            if part.startswith("role="):
                roles.add(part[5:])
    if not roles:
        return None
    short = {"prefill": "P", "decode": "D"}
    return "+".join(short.get(r, r[:1].upper()) for r in sorted(roles))


def _gauge_sum(snap: dict, family: str, label: str = None):
    """Sum a gauge family's children from a snapshot's metrics (e.g.
    ``journal_pending`` across a replica's journals); None when the
    family is absent. ``label`` restricts to children carrying that
    exact ``name=value`` pair (e.g. ``state=free`` of
    ``generation_kv_pages`` across a replica's engines)."""
    doc = (snap.get("metrics") or {}).get(family) or {}
    if doc.get("type") != "gauge":
        return None
    vals = [v for k, v in (doc.get("values") or {}).items()
            if isinstance(v, (int, float)) and
            (label is None or label in str(k).split(","))]
    return sum(vals) if vals else None


def _gauge_max(snap: dict, family: str):
    """Max across a gauge family's children (e.g. the STALEST canary
    age across a replica's fleets); None when absent."""
    doc = (snap.get("metrics") or {}).get(family) or {}
    if doc.get("type") != "gauge":
        return None
    vals = [v for v in (doc.get("values") or {}).values()
            if isinstance(v, (int, float))]
    return max(vals) if vals else None


def merge_snapshots(per_url: dict) -> dict:
    """Merge N ``/snapshot`` documents (keyed by replica URL) into the
    fleet summary — pure dict math, reused by the one-shot scrape, the
    watch loop, and the tests."""
    replicas = {}
    win_pool = {"short": {"n": 0, "met": 0}, "long": {"n": 0, "met": 0}}
    counters: dict = {}
    requests = missed = 0
    target = None
    for base, snap in sorted(per_url.items()):
        err = snap.get("__error__")
        if err:
            replicas[base] = {"up": False, "error": err}
            continue
        slo = snap.get("slo") or {}
        row = {"up": True,
               "uptime_s": snap.get("uptime_s"),
               "requests": slo.get("requests"),
               "missed": slo.get("missed"),
               "kv_cache_bytes": _kv_bytes(snap)}
        for win, agg in (slo.get("windows") or {}).items():
            if win in win_pool:
                win_pool[win]["n"] += int(agg.get("n") or 0)
                win_pool[win]["met"] += int(agg.get("met") or 0)
                row[f"attainment_{win}"] = agg.get("attainment")
                # per-replica burn rate (ISSUE 11): the autoscaler's
                # input signal, visible per replica in the fleet table
                row[f"burn_{win}"] = agg.get("burn_rate")
        overall = slo.get("overall") or {}
        head = overall.get("headroom_s") or {}
        row["headroom_p50_s"] = head.get("p50")
        row["headroom_min_s"] = head.get("min")
        row["ttft_p99_s"] = (overall.get("ttft_s") or {}).get("p99")
        # paged-KV health (ISSUE 12): pool pages free / prefix-shared
        # per replica (gauge sums across its engines) plus the fleet's
        # prefix hit rate from the summed counters below — the scrape
        # view of the concurrency-at-fixed-memory claim
        row["kv_pages_free"] = _gauge_sum(
            snap, "generation_kv_pages", label="state=free")
        row["kv_pages_shared"] = _gauge_sum(
            snap, "generation_kv_pages", label="state=shared")
        # journal health (ISSUE 10): durable-WAL backlog + degraded flag
        # per replica — a degraded journal means the replica serves with
        # no durability and deserves the same attention as a missed SLO
        row["journal_pending"] = _gauge_sum(snap, "journal_pending")
        deg = _gauge_sum(snap, "journal_degraded")
        row["journal_degraded"] = None if deg is None else bool(deg)
        # hot-loop profiler (ISSUE 13): decode pipeline bubble-%
        row["bubble_pct"] = _bubble_col(snap)
        # disagg tier (ISSUE 14): phase role (P = prefill worker, D =
        # decode worker, '-' = classic both-phase) and the measured
        # KV-handoff transfer account
        row["role"] = _role_col(snap)
        xb = _counter_sum(snap, "kv_transfer_bytes_total")
        row["kv_transfer_mb"] = None if xb is None \
            else round(xb / 1e6, 2)
        row["kv_handoffs"] = _counter_sum(snap, "fleet_kv_handoffs_total")
        # SDC defense (ISSUE 15): sentinel trips + detected page
        # corruptions per replica, and the golden canary's staleness
        # (max across the replica's fleets = its stalest canary — a
        # growing age means the prober can no longer get a clean probe
        # through, which deserves the same attention as a missed SLO)
        # speculative decoding (ISSUE 16): rolling acceptance rate per
        # replica — accepted drafted tokens over proposed, summed
        # across the replica's engines; None (prints '-') when the
        # replica never speculated
        acc = _counter_sum(snap, "generation_spec_accepted_tokens_total")
        drafted = _counter_sum(snap, "generation_spec_drafted_total")
        row["spec_acc"] = None if not drafted \
            else round((acc or 0) / drafted, 3)
        row["numerical_faults"] = _counter_sum(snap,
                                               "numerical_fault_total")
        row["kv_corruptions"] = _counter_sum(snap,
                                             "kv_page_corruption_total")
        row["canary_age_s"] = _gauge_max(snap,
                                         "integrity_canary_age_seconds")
        if target is None and slo.get("target") is not None:
            target = float(slo["target"])
        requests += int(slo.get("requests") or 0)
        missed += int(slo.get("missed") or 0)
        for fam, doc in (snap.get("metrics") or {}).items():
            if doc.get("type") != "counter":
                continue
            vals = [v for v in (doc.get("values") or {}).values()
                    if isinstance(v, (int, float))]
            if vals:
                counters[fam] = counters.get(fam, 0) + sum(vals)
        replicas[base] = row
    target = 0.99 if target is None else target
    slo_agg = {"target": target, "requests": requests, "missed": missed}
    for win, pool in win_pool.items():
        att = 1.0 if not pool["n"] else pool["met"] / pool["n"]
        slo_agg[f"attainment_{win}"] = round(att, 6)
        slo_agg[f"burn_rate_{win}"] = round(
            (1.0 - att) / (1.0 - target), 6)
    up = [b for b, r in replicas.items() if r.get("up")]
    return {"replicas": replicas,
            "up": len(up), "scraped": len(replicas),
            "slo": slo_agg,
            "counters": {k: counters[k] for k in sorted(counters)}}


def pretty_scrape(doc: dict, out=sys.stdout) -> None:
    w = out.write
    w(f"fleet scrape: {doc['up']}/{doc['scraped']} replicas up\n")
    w(f"  {'replica':<36} {'up':>2} {'role':>4} {'uptime':>8} "
      f"{'att-short':>9} "
      f"{'att-long':>8} {'burn-sh':>8} {'reqs':>6} {'miss':>5} "
      f"{'hd-p50':>8} {'hd-min':>8} {'kv-bytes':>10} {'pg-free':>7} "
      f"{'pg-shr':>6} {'xfer-MB':>8} {'j-pend':>6} {'j-deg':>5} "
      f"{'bub%':>6} {'spec-acc':>8} {'numflt':>6} "
      f"{'kv-cor':>6} {'canary':>7}\n")
    fmt = (lambda v, spec="": "-" if v is None else format(v, spec))
    for base, row in sorted(doc["replicas"].items()):
        if not row.get("up"):
            w(f"  {base:<36}  n  DOWN ({row.get('error', '?')})\n")
            continue
        jd = row.get("journal_degraded")
        w(f"  {base:<36} {'y':>2} {fmt(row.get('role')):>4} "
          f"{fmt(row.get('uptime_s')):>8} "
          f"{fmt(row.get('attainment_short')):>9} "
          f"{fmt(row.get('attainment_long')):>8} "
          f"{fmt(row.get('burn_short')):>8} "
          f"{fmt(row.get('requests')):>6} {fmt(row.get('missed')):>5} "
          f"{fmt(row.get('headroom_p50_s')):>8} "
          f"{fmt(row.get('headroom_min_s')):>8} "
          f"{fmt(row.get('kv_cache_bytes')):>10} "
          f"{fmt(row.get('kv_pages_free')):>7} "
          f"{fmt(row.get('kv_pages_shared')):>6} "
          f"{fmt(row.get('kv_transfer_mb')):>8} "
          f"{fmt(row.get('journal_pending')):>6} "
          f"{'-' if jd is None else ('Y' if jd else 'n'):>5} "
          f"{fmt(row.get('bubble_pct')):>6} "
          f"{fmt(row.get('spec_acc')):>8} "
          f"{fmt(row.get('numerical_faults')):>6} "
          f"{fmt(row.get('kv_corruptions')):>6} "
          f"{fmt(row.get('canary_age_s')):>7}\n")
    hits = doc["counters"].get("prefix_cache_hit_total")
    misses = doc["counters"].get("prefix_cache_miss_total")
    if hits is not None or misses is not None:
        total = (hits or 0) + (misses or 0)
        rate = "-" if not total else f"{(hits or 0) / total:.3f}"
        w(f"  prefix cache: {hits or 0} hits / {misses or 0} misses "
          f"(hit rate {rate})\n")
    agg = doc["slo"]
    w(f"  fleet SLO (target {agg['target']}): "
      f"attainment short={agg['attainment_short']} "
      f"long={agg['attainment_long']} "
      f"burn short={agg['burn_rate_short']} "
      f"long={agg['burn_rate_long']} "
      f"requests={agg['requests']} missed={agg['missed']}\n")
    if doc["counters"]:
        w("  summed counters:\n")
        for fam, v in doc["counters"].items():
            w(f"    {fam:<44} {v}\n")


def _flat_sample(snap: dict) -> dict:
    """One watch sample: monotonically increasing series (counters +
    histogram counts) and instantaneous series (gauges) flattened to
    ``name{labels}`` keys."""
    rates, gauges = {}, {}
    for fam, doc in (snap.get("metrics") or {}).items():
        typ = doc.get("type")
        for label, value in (doc.get("values") or {}).items():
            key = f"{fam}{{{label}}}" if label else fam
            if typ == "counter" and isinstance(value, (int, float)):
                rates[key] = value
            elif typ == "histogram" and isinstance(value, dict):
                rates[key + ":count"] = value.get("count") or 0
            elif typ == "gauge" and isinstance(value, (int, float)):
                gauges[key] = value
    return {"rates": rates, "gauges": gauges}


def _fleet_sample(doc: dict) -> dict:
    """Watch sample over a merged scrape: summed counters are the rate
    series; per-replica attainment/up are the gauge series."""
    gauges = {}
    for base, row in doc["replicas"].items():
        gauges[f"up{{{base}}}"] = 1.0 if row.get("up") else 0.0
        if row.get("attainment_short") is not None:
            gauges[f"attainment_short{{{base}}}"] = \
                row["attainment_short"]
    gauges["fleet_attainment_short"] = doc["slo"]["attainment_short"]
    return {"rates": dict(doc["counters"]), "gauges": gauges}


def print_deltas(prev: dict, cur: dict, dt: float,
                 out=sys.stdout) -> None:
    """Counter rates and gauge changes between two watch samples; flat
    lines (no per-sample headers) so a terminal tail stays greppable."""
    w = out.write
    for key in sorted(cur["rates"]):
        d = cur["rates"][key] - prev["rates"].get(key, 0)
        if d:
            w(f"  {key:<56} +{d:g}  ({d / dt:.2f}/s)\n")
    for key in sorted(cur["gauges"]):
        old = prev["gauges"].get(key)
        new = cur["gauges"][key]
        if old is None or new != old:
            w(f"  {key:<56} "
              f"{'-' if old is None else f'{old:g}'} -> {new:g}\n")


def watch(sample_fn, period: float, count=None, out=sys.stdout,
          clock=time.monotonic, sleep=time.sleep) -> int:
    """The ``--watch`` loop: sample, sleep, re-sample, print deltas.
    ``count`` bounds the number of RE-samples (None: until ^C);
    ``clock``/``sleep`` are injectable for deterministic tests."""
    prev = sample_fn()
    prev_t = clock()
    done = 0
    try:
        while count is None or done < count:
            sleep(period)
            cur = sample_fn()
            t = clock()
            out.write(f"-- watch sample +{t - prev_t:.2f}s --\n")
            print_deltas(prev, cur, max(t - prev_t, 1e-9), out)
            prev, prev_t = cur, t
            done += 1
    except KeyboardInterrupt:
        pass
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("url", nargs="?", default="http://127.0.0.1:9100",
                    help="TelemetryServer base URL "
                         "(default http://127.0.0.1:9100)")
    ap.add_argument("--json", action="store_true",
                    help="print the raw /snapshot JSON")
    ap.add_argument("--metrics", action="store_true",
                    help="print the raw Prometheus /metrics text")
    ap.add_argument("--traces", type=int, nargs="?", const=10, default=None,
                    metavar="N", help="print the last N request traces")
    ap.add_argument("--fleet", action="store_true",
                    help="print fleet router replica tables (state, "
                         "heartbeat age, load/capacity, exactly-once "
                         "ledger) from the snapshot's fleet sources")
    ap.add_argument("--slo", action="store_true",
                    help="print the /slo document (rolling-window "
                         "attainment + burn rate, headroom/TTFT/queue "
                         "quantiles, per-route and per-replica splits)")
    ap.add_argument("--scrape", default=None, metavar="URL,URL,...",
                    help="fleet-wide scrape: fetch every listed "
                         "replica's /snapshot and merge into one "
                         "summary (aggregate SLO attainment, "
                         "per-replica health/headroom, summed "
                         "counters); exit 2 if NO replica answered")
    ap.add_argument("--watch", type=float, default=None, metavar="SECS",
                    help="re-sample every SECS seconds and print "
                         "deltas (counter rates, gauge changes) "
                         "between samples; combine with --scrape for "
                         "the fleet-wide live view")
    ap.add_argument("--count", type=int, default=None, metavar="N",
                    help="with --watch: stop after N delta samples "
                         "(default: run until interrupted)")
    ap.add_argument("--timeout", type=float, default=5.0)
    args = ap.parse_args(argv)
    base = args.url.rstrip("/")

    if args.scrape:
        urls = [u for u in args.scrape.split(",") if u.strip()]
        if args.watch is not None:
            return watch(lambda: _fleet_sample(
                scrape_fleet(urls, args.timeout)),
                args.watch, args.count)
        doc = scrape_fleet(urls, args.timeout)
        if args.json:
            print(json.dumps(doc, indent=1, default=str))
        else:
            pretty_scrape(doc)
        return 0 if doc["up"] else 2

    if args.watch is not None:
        def sample():
            return _flat_sample(fetch(f"{base}/snapshot", args.timeout))
        try:
            return watch(sample, args.watch, args.count)
        except (urllib.error.URLError, OSError, TimeoutError) as e:
            print(f"error: cannot reach {base}: {e}", file=sys.stderr)
            return 2

    try:
        if args.slo:
            doc = fetch(f"{base}/slo", args.timeout)
            print(json.dumps(doc, indent=1, default=str))
            return 0
        if args.metrics:
            sys.stdout.write(fetch(f"{base}/metrics", args.timeout))
            return 0
        if args.traces is not None:
            doc = fetch(f"{base}/traces/recent?n={args.traces}",
                        args.timeout)
            if args.json:
                print(json.dumps(doc, indent=1, default=str))
            else:
                pretty_traces(doc)
            return 0
        snap = fetch(f"{base}/snapshot", args.timeout)
    except (urllib.error.URLError, OSError, TimeoutError) as e:
        print(f"error: cannot reach {base}: {e}", file=sys.stderr)
        return 2
    if args.fleet:
        if args.json:
            fleets = _fleet_sources(snap)
            print(json.dumps(fleets, indent=1, default=str))
            # an absent fleet source is a misconfiguration either way:
            # match the pretty path's exit code so automation keyed on
            # it doesn't read '{}' as healthy
            return 0 if fleets else 2
        return pretty_fleet(snap)
    if args.json:
        print(json.dumps(snap, indent=1, default=str))
    else:
        pretty(snap)
    return 0


if __name__ == "__main__":
    sys.exit(main())
