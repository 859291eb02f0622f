"""Hot-loop phase profiler: pipeline phase/bubble accounting for the
decode serving path — the in-process sink of the engine loop's seams
(``observability.tracing.Seam``), beside their mirror onto the device
trace's clock. An operator reads it at ``GET /profile`` in production,
where a profiler trace cannot be taken (stopping one holds the
interpreter for some 50 s a traced second, PERF.md); the benchmark reads
a window of it through :meth:`PhaseProfiler.between`.

- **Phase decomposition** — the engine hands the seams' stamps of each
  decode-block retire cycle (dispatch → ``device_fetch`` returns → host
  bookkeeping done → journal append done → completion publishes done) to
  :meth:`EngineChannel.record_block`, which turns them into a
  telescoping decomposition: ``device`` (dispatch → data ready — the
  block_until_ready delta on the retired carry), ``host``, ``journal``,
  ``publish``. The four phases sum EXACTLY to the block's wall time
  (t_publish − t_dispatch) by construction — the exactness tests pin
  that. Batched/paged admission and chunked-prefill windows get the same
  treatment (``kind="admission"`` / ``"chunk"``).

- **Pipeline bubble** — time the device CERTAINLY sat idle before a
  dispatch, waiting on the host: ``max(0, t_dispatch − t_done)`` where
  ``t_done`` is the readback of the work dispatched LAST before it, and
  0 where other work was still in flight at the dispatch
  (``overlapped``: the double buffer dispatches block t+1 before block
  t's readback; an admission's prefill queues behind the block in
  flight). A serve loop that slept for lack of work re-anchors at its
  wake-up (:meth:`EngineChannel.mark_idle`), so an empty queue is not a
  bubble. Each record also says what the device had done last
  (``after``: ``block`` / ``admission`` / ``chunk`` / ``idle``), so the
  idle stretch that follows an admission — prefill read back, its
  bookkeeping, the stale block's retire, the next dispatch — is told
  apart from the one after a freed lane. The dispatch call's own
  duration rides alongside (``dispatch_ms``): with nothing in flight the
  device cannot start before the call returns, and a dispatch from host
  state spends 2–3 ms converting its arguments (PERF.md), so a reader
  adds it to the bubble for the idle stretch as the host saw it. K>1
  steady decode shows ~0 bubble, the K=1 legacy loop one
  host-bookkeeping bubble per step. ``bubble_pct = bubble / (bubble +
  device)``.

- **Lane bubble** — idle cache slots × block device time while work was
  QUEUED, over total slot-time: the continuous-batching waste measure
  (``refill=False`` static waves strand finished lanes until the wave
  drains, so their lane-bubble is strictly higher — gated in tests).

- **PhaseTimeline** — a bounded ring of per-block phase records (newest
  last; 8192 entries, 13 minutes of 95 ms blocks): the forensic view
  ``GET /profile?timeline=N`` serves, and what
  :meth:`PhaseProfiler.between` sums over a window, saying ``truncated``
  when the ring no longer reaches back to the window's start. Every
  entry carries the ``block`` id of its dispatch
  (``tracing.next_block_id``), which the requests' spans name too. The
  ring lives on the PROFILER, not the engine, so it survives a
  supervisor engine rebuild (the supervisor passes the profiler
  through, exactly like the SLO tracker) — chaos_soak ``--profile``
  asserts that.

Overhead contract (exact-count tests): recording is the seams' stamps
plus O(#phases) histogram observes per BLOCK (not per token), the ring
is bounded, and nothing here touches the device or runs under jit —
graftlint GL016 statically rejects profiler/phase-stamp recording calls
inside jit-traced or shard_map code, the same gate GL008/GL015 give the
other sinks.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Dict, List, Optional

from .metrics import MetricsRegistry, default_registry

#: phase names of the telescoping per-block decomposition (these sum to
#: the block's wall time); ``bubble`` rides alongside, not inside
PHASES = ("device", "host", "journal", "publish")

#: fine-grained phase buckets (seconds): decode phases live in the
#: 10µs..1s decade; the registry default ladder starts at 100µs
PHASE_BUCKETS = (1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3,
                 5e-3, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                 10.0, 30.0)


def _ms(t0: float, t1: Optional[float]) -> float:
    return 0.0 if t1 is None else (t1 - t0) * 1e3


class PhaseTimeline:
    """Fixed-capacity ring of per-block phase records (newest last).
    Memory is O(capacity) forever; ``total_added`` counts everything
    ever recorded, so a ring that survived an engine rebuild shows
    continuity even after old entries rotate out."""

    def __init__(self, capacity: int = 8192):
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=self.capacity)
        self._added = 0
        self._lost_until: Optional[float] = None   # newest evicted "t"

    def add(self, entry: dict) -> None:
        with self._lock:
            if len(self._ring) == self.capacity:
                lost = self._ring[0]["t"]
                if self._lost_until is None or lost > self._lost_until:
                    self._lost_until = lost
            self._ring.append(entry)
            self._added += 1

    def between(self, t0: float, t1: float,
                engine: Optional[str] = None) -> dict:
        """Sums over the records whose dispatch fell in ``[t0, t1)`` on
        the interval clock (one engine's, or every engine's): per record
        kind the count, each phase, the bubble and the dispatch calls'
        time; and the last two again by what the device had done last
        (``bubble_after``: only dispatches with nothing in flight, so
        bubble + dispatch is there the idle stretch as the host saw it).
        ``truncated`` is true when a record dispatched at or after ``t0``
        has rotated out: the sums are then of a part of the window."""
        with self._lock:
            items = list(self._ring)
            truncated = self._lost_until is not None and \
                self._lost_until >= t0
        kinds: Dict[str, dict] = {}
        after: Dict[str, dict] = {}
        for e in items:
            if not t0 <= e["t"] < t1 or \
                    (engine is not None and e["engine"] != engine):
                continue
            acc = kinds.setdefault(e["kind"], {
                "n": 0, "bubble_seconds": 0.0, "dispatch_seconds": 0.0,
                "phase_seconds": {}})
            for p, v in e["phases_ms"].items():
                acc["phase_seconds"][p] = \
                    acc["phase_seconds"].get(p, 0.0) + v / 1e3
            accs = [acc]
            if e.get("after") is not None:
                accs.append(after.setdefault(e["after"], {
                    "n": 0, "bubble_seconds": 0.0,
                    "dispatch_seconds": 0.0}))
            for acc in accs:
                acc["n"] += 1
                acc["bubble_seconds"] += e["bubble_ms"] / 1e3
                acc["dispatch_seconds"] += e.get("dispatch_ms", 0.0) / 1e3
        return {"t0": t0, "t1": t1, "truncated": truncated,
                "kinds": kinds, "bubble_after": after}

    def recent(self, n: Optional[int] = None) -> List[dict]:
        """Last ``n`` entries (all when None; empty for n <= 0 — a
        zero-entry round must read back zero entries, not the whole
        ring, and a negative query is a caller bug, not a slice)."""
        with self._lock:
            items = list(self._ring)
        if n is None:
            return items
        n = int(n)
        return items[-n:] if n > 0 else []

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    @property
    def total_added(self) -> int:
        with self._lock:
            return self._added


class EngineChannel:
    """One engine's phase account inside a :class:`PhaseProfiler`.

    Keyed by the engine's STABLE ``slo_label`` (not the per-instance
    engine id), so a supervisor-rebuilt engine continues the same
    channel — phase history, bubble anchors, and per-impl steady
    durations all survive the takeover, like the SLO clocks do.

    All ``record_*`` methods are called from the engine's serve/readback
    thread with host interval-clock stamps; ``summary()`` may race them
    from the telemetry thread, hence the lock. Nothing here dispatches
    device work (GL016 statically enforces call-site discipline)."""

    def __init__(self, profiler: "PhaseProfiler", name: str,
                 num_slots: int):
        self._profiler = profiler
        self.name = str(name)
        self.num_slots = int(num_slots)
        self._lock = threading.Lock()
        # bubble anchor: the work dispatched LAST, as (dispatch stamp,
        # stamp at which it was known done — block retire / prefill
        # readback / chunk dispatch / the serve loop's wake-up —, kind),
        # and the one before it. Records arrive in readback order, which
        # an admission behind an in-flight block reverses: only a later
        # dispatch moves the anchor, and the overtaken block is judged
        # against the anchor before
        self._anchor: Optional[tuple] = None
        self._anchor_prev: Optional[tuple] = None
        # last block retire per impl, for steady pipelined spacing
        self._last_retire: Dict[str, float] = {}
        # plain accumulators (summary() reads these; the registry
        # histograms carry the same observations for /metrics)
        self._phase_s = {p: 0.0 for p in PHASES}
        self._bubble_s = 0.0
        self._blocks = 0
        self._admissions = 0
        self._chunks = 0
        # lane occupancy: slot-seconds busy vs idle-while-work-queued,
        # integrated over block device spans
        self._lane_busy_s = 0.0
        self._lane_idle_queued_s = 0.0
        self._lane_total_s = 0.0
        # per-impl measured steady durations:
        # impl -> [n, total_s, min_s, steps_per_dispatch]
        self._impl: Dict[str, List[float]] = {}
        # speculative-decode account (ISSUE 16): verify blocks, token
        # outcomes, and the draft/verify/rewind sub-phase sums
        self._spec = {"blocks": 0, "accepted": 0, "drafted": 0,
                      "draft_s": 0.0, "verify_s": 0.0, "rewind_s": 0.0}
        reg = profiler.registry
        self._h_phase = {
            p: reg.histogram(
                "profiler_phase_seconds",
                "decode-cycle phase decomposition (device/host/journal/"
                "publish sum to block wall time; bubble = device idle "
                "gap before dispatch; draft/verify/rewind ride "
                "alongside, attributing speculative blocks)",
                ("engine", "phase"),
                buckets=PHASE_BUCKETS).labels(self.name, p)
            for p in PHASES + ("bubble", "draft", "verify", "rewind")}
        m_blocks = reg.counter(
            "profiler_records_total", "phase-profiled cycles, by kind",
            ("engine", "kind"))
        self._m_kind = {kind: m_blocks.labels(self.name, kind)
                        for kind in ("block", "admission", "chunk",
                                     "spec")}

    def _bubble_locked(self, kind: str, t_dispatch: float, t_done: float,
                       overlapped: bool):
        """(bubble seconds before this dispatch, kind of the work the
        device had done last — None where other work was in flight);
        moves the anchor. Caller holds the lock."""
        cur = self._anchor
        newest = cur is None or t_dispatch >= cur[0]
        ref = cur if newest else self._anchor_prev
        if overlapped or ref is None:
            bubble, after = 0.0, None
        else:
            bubble, after = max(0.0, t_dispatch - ref[1]), ref[2]
        if newest:
            self._anchor_prev = cur
            self._anchor = (t_dispatch, t_done, kind)
        return bubble, after

    def mark_idle(self, t: float) -> None:
        """The serve loop woke at ``t`` from a wait for work: the device
        was idle for lack of work, not for the host — the next dispatch's
        bubble counts from here."""
        with self._lock:
            self._anchor_prev, self._anchor = self._anchor, (t, t, "idle")

    # ---------------------------------------------------------- recording
    def record_block(self, *, impl: str, k: int, lanes: int, queued: int,
                     t_dispatch: float, t_fetched: float, t_host: float,
                     t_journal: float, t_publish: float,
                     block: Optional[int] = None,
                     overlapped: bool = False,
                     t_dispatched: Optional[float] = None) -> None:
        """One retired decode block. The five stamps are interval-clock
        times at the retire cycle's seams; phases telescope so they sum
        to ``t_publish - t_dispatch`` exactly. ``overlapped``: another
        block was in flight at the dispatch (the double buffer);
        ``t_dispatched``: the dispatch call's return (``dispatch_ms`` of
        the timeline entry)."""
        phases = {"device": t_fetched - t_dispatch,
                  "host": t_host - t_fetched,
                  "journal": t_journal - t_host,
                  "publish": t_publish - t_journal}
        with self._lock:
            bubble, after = self._bubble_locked("block", t_dispatch,
                                                t_fetched, overlapped)
            for p, v in phases.items():
                self._phase_s[p] += v
            self._bubble_s += bubble
            self._blocks += 1
            # lane occupancy over this block's device span: idle lanes
            # only count as waste while there was queued work they
            # could have served (continuous batching's whole claim)
            span = max(0.0, phases["device"])
            lanes = min(int(lanes), self.num_slots)
            self._lane_total_s += self.num_slots * span
            self._lane_busy_s += lanes * span
            if queued > 0:
                self._lane_idle_queued_s += (self.num_slots - lanes) * span
            # steady duration per impl (impl_measured): in pipelined steady
            # state (zero bubble) consecutive retirements are spaced by
            # the true per-block device time, which the dispatch→ready
            # delta OVERSTATES (it spans the overlapped host work);
            # serialized blocks use the direct delta
            last = self._last_retire.get(impl)
            if bubble == 0.0 and last is not None and \
                    0.0 < t_fetched - last < phases["device"]:
                steady = t_fetched - last
            else:
                steady = max(phases["device"], 1e-9)
            self._last_retire[impl] = t_fetched
            ent = self._impl.get(impl)
            if ent is None:
                # the FIRST observation of an impl absorbs its jit
                # compile/lowering — mark it seen but keep it out of
                # the steady aggregate (n stays 0 until the 2nd block)
                self._impl[impl] = [0, 0.0, steady, max(1, int(k))]
            else:
                ent[0] += 1
                ent[1] += steady
                ent[2] = min(ent[2], steady)
        for p, v in phases.items():
            self._h_phase[p].observe(max(0.0, v))
        self._h_phase["bubble"].observe(bubble)
        self._m_kind["block"].inc()
        # raw floats on purpose: rounding 6 values per block is real
        # cost on the readback thread; JSON renders them fine
        self._profiler.timeline.add({
            "engine": self.name, "kind": "block", "impl": impl,
            "block": block, "k": k, "lanes": lanes, "queued": queued,
            "t": t_dispatch, "bubble_ms": bubble * 1e3, "after": after,
            "dispatch_ms": _ms(t_dispatch, t_dispatched),
            "phases_ms": {p: v * 1e3 for p, v in phases.items()},
        })

    def record_spec(self, *, impl: str, k: int, lanes: int, queued: int,
                    accepted: int, drafted: int, t_draft: float,
                    t_dispatch: float, t_fetched: float, t_rewind: float,
                    t_host: float, t_journal: float,
                    t_publish: float,
                    block: Optional[int] = None) -> None:
        """One retired speculative verify block (ISSUE 16). The generic
        telescoping account is unchanged — device/host/journal/publish
        still sum to ``t_publish - t_dispatch`` exactly, so every
        consumer of the classic decomposition reads spec blocks like any
        other block. The spec-specific attribution rides alongside
        (like ``bubble``): ``draft`` is the host-side drafting span
        BEFORE dispatch (``t_dispatch - t_draft``), ``verify`` the
        device span of the fused K+1-position forward, ``rewind`` the
        page-table/position rollback sub-span of host (``t_rewind -
        t_fetched``). Drafting is real work, not device idle: the
        bubble anchor compares against ``t_draft``."""
        phases = {"device": t_fetched - t_dispatch,
                  "host": t_host - t_fetched,
                  "journal": t_journal - t_host,
                  "publish": t_publish - t_journal}
        draft_s = max(0.0, t_dispatch - t_draft)
        rewind_s = max(0.0, t_rewind - t_fetched)
        with self._lock:
            bubble, after = self._bubble_locked("block", t_draft,
                                                t_fetched, False)
            for p, v in phases.items():
                self._phase_s[p] += v
            self._bubble_s += bubble
            self._blocks += 1
            self._spec["blocks"] += 1
            self._spec["accepted"] += int(accepted)
            self._spec["drafted"] += int(drafted)
            self._spec["draft_s"] += draft_s
            self._spec["verify_s"] += max(0.0, phases["device"])
            self._spec["rewind_s"] += rewind_s
            span = max(0.0, phases["device"])
            lanes = min(int(lanes), self.num_slots)
            self._lane_total_s += self.num_slots * span
            self._lane_busy_s += lanes * span
            if queued > 0:
                self._lane_idle_queued_s += (self.num_slots - lanes) * span
            # the spec path never pipelines (the drafter needs the
            # retired suffix), so the dispatch→ready delta IS the steady
            # device duration — no retire-spacing correction needed
            steady = max(span, 1e-9)
            self._last_retire[impl] = t_fetched
            ent = self._impl.get(impl)
            if ent is None:
                # first observation absorbs the verify jit compile —
                # excluded from the steady aggregate like record_block
                self._impl[impl] = [0, 0.0, steady, max(1, int(k) + 1)]
            else:
                ent[0] += 1
                ent[1] += steady
                ent[2] = min(ent[2], steady)
        for p, v in phases.items():
            self._h_phase[p].observe(max(0.0, v))
        self._h_phase["bubble"].observe(bubble)
        self._h_phase["draft"].observe(draft_s)
        self._h_phase["verify"].observe(max(0.0, phases["device"]))
        self._h_phase["rewind"].observe(rewind_s)
        self._m_kind["spec"].inc()
        self._profiler.timeline.add({
            "engine": self.name, "kind": "spec", "impl": impl,
            "block": block, "k": k, "lanes": lanes, "queued": queued,
            "accepted": int(accepted), "drafted": int(drafted),
            "t": t_dispatch, "bubble_ms": bubble * 1e3, "after": after,
            "draft_ms": draft_s * 1e3, "rewind_ms": rewind_s * 1e3,
            "phases_ms": {p: v * 1e3 for p, v in phases.items()},
        })

    def record_admission(self, *, impl: str, count: int,
                         t_dispatch: float, t_fetched: float,
                         t_host: float, t_journal: float,
                         t_publish: float, block: Optional[int] = None,
                         overlapped: bool = False,
                         t_dispatched: Optional[float] = None) -> None:
        """One batched admission wave (slab or paged): same telescoping
        decomposition; the prefill readback becomes the new bubble
        anchor (prefill IS device work — a decode block dispatched
        right after it shows only the host gap as bubble, marked
        ``after: "admission"``). ``overlapped``: a decode block was in
        flight at the dispatch, so the prefill queued behind it and the
        device was not idle."""
        phases = {"device": t_fetched - t_dispatch,
                  "host": t_host - t_fetched,
                  "journal": t_journal - t_host,
                  "publish": t_publish - t_journal}
        with self._lock:
            bubble, after = self._bubble_locked("admission", t_dispatch,
                                                t_fetched, overlapped)
            for p, v in phases.items():
                self._phase_s[p] += v
            self._bubble_s += bubble
            self._admissions += 1
            ent = self._impl.get(impl)
            d = max(phases["device"], 1e-9)
            if ent is None:
                # same warmup exclusion as record_block: the first
                # admission wave pays the prefill compile
                self._impl[impl] = [0, 0.0, d, 1]
            else:
                ent[0] += 1
                ent[1] += d
                ent[2] = min(ent[2], d)
        for p, v in phases.items():
            self._h_phase[p].observe(max(0.0, v))
        self._h_phase["bubble"].observe(bubble)
        self._m_kind["admission"].inc()
        self._profiler.timeline.add({
            "engine": self.name, "kind": "admission", "impl": impl,
            "block": block, "count": count, "t": t_dispatch,
            "bubble_ms": bubble * 1e3, "after": after,
            "dispatch_ms": _ms(t_dispatch, t_dispatched),
            "phases_ms": {p: v * 1e3 for p, v in phases.items()},
        })

    def record_chunk(self, *, t_dispatch: float, t_done: float,
                     final: bool, block: Optional[int] = None,
                     overlapped: bool = False) -> None:
        """One chunked-prefill window. Non-final windows never sync
        (t_done is dispatch-return), so only the device phase is
        attributed; the window still moves the bubble anchor — the
        device is busy with it either way."""
        d = t_done - t_dispatch
        with self._lock:
            bubble, after = self._bubble_locked("chunk", t_dispatch, t_done,
                                                overlapped)
            self._phase_s["device"] += d
            self._bubble_s += bubble
            self._chunks += 1
        self._h_phase["device"].observe(max(0.0, d))
        self._h_phase["bubble"].observe(bubble)
        self._m_kind["chunk"].inc()
        self._profiler.timeline.add({
            "engine": self.name, "kind": "chunk", "final": bool(final),
            "block": block, "t": t_dispatch, "bubble_ms": bubble * 1e3,
            "after": after,
            "phases_ms": {"device": d * 1e3},
        })

    # ------------------------------------------------------------- views
    def summary(self) -> dict:
        with self._lock:
            phase_s = dict(self._phase_s)
            bubble_s = self._bubble_s
            blocks, adm, chunks = self._blocks, self._admissions, \
                self._chunks
            lane_busy = self._lane_busy_s
            lane_idle_q = self._lane_idle_queued_s
            lane_total = self._lane_total_s
            impl = {k: list(v) for k, v in self._impl.items()}
            spec = dict(self._spec)
        device_s = phase_s["device"]
        total_s = sum(phase_s.values())
        out = {
            "blocks": blocks,
            "admissions": adm,
            "chunks": chunks,
            "phase_seconds": {p: round(v, 6) for p, v in phase_s.items()},
            "phase_pct": {p: round(100.0 * v / total_s, 2)
                          for p, v in phase_s.items()} if total_s else {},
            "bubble_seconds": round(bubble_s, 6),
            "bubble_pct": round(100.0 * bubble_s / (bubble_s + device_s),
                                2) if bubble_s + device_s > 0 else 0.0,
            "lane_bubble_pct": round(100.0 * lane_idle_q / lane_total, 2)
            if lane_total > 0 else 0.0,
            "lane_busy_pct": round(100.0 * lane_busy / lane_total, 2)
            if lane_total > 0 else 0.0,
            "impl_measured": {
                name: {"n": int(n),
                       "mean_s": round(tot / n if n else mn, 6),
                       "min_s": round(mn, 6),
                       "steps_per_dispatch": int(k)}
                for name, (n, tot, mn, k) in sorted(impl.items())},
        }
        if spec["blocks"]:
            # speculative-decode headline (ISSUE 16): acceptance rate is
            # THE observable — the fleet scrape's spec-acc column
            out["spec"] = {
                "blocks": spec["blocks"],
                "accepted": spec["accepted"],
                "drafted": spec["drafted"],
                "acceptance_rate": round(
                    spec["accepted"] / spec["drafted"], 4)
                if spec["drafted"] else 0.0,
                "draft_seconds": round(spec["draft_s"], 6),
                "verify_seconds": round(spec["verify_s"], 6),
                "rewind_seconds": round(spec["rewind_s"], 6),
            }
        return out


class PhaseProfiler:
    """Process-wide phase/bubble account over N engines.

    Engines call :meth:`channel` once at construction (keyed by their
    stable ``slo_label``); the telemetry server serves
    :meth:`snapshot` at ``GET /profile`` and embeds :meth:`summary`
    into ``/snapshot`` for the fleet scrape. Default-plus-injectable
    like every other observability sink."""

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 timeline_capacity: int = 8192):
        self.registry = registry if registry is not None \
            else default_registry()
        self.timeline = PhaseTimeline(timeline_capacity)
        self._lock = threading.Lock()
        self._channels: Dict[str, EngineChannel] = {}

    def channel(self, name: str, num_slots: int = 0) -> EngineChannel:
        """Get-or-create the channel for one engine label. Idempotent:
        a supervisor-rebuilt engine re-enters ITS channel (same
        ``slo_label``) and keeps accumulating — the timeline ring and
        phase history survive the rebuild."""
        with self._lock:
            ch = self._channels.get(str(name))
            if ch is None:
                ch = EngineChannel(self, str(name), num_slots)
                self._channels[str(name)] = ch
            elif num_slots:
                ch.num_slots = int(num_slots)
        return ch

    def channels(self) -> Dict[str, EngineChannel]:
        with self._lock:
            return dict(self._channels)

    def between(self, t0: float, t1: float,
                engine: Optional[str] = None) -> dict:
        """The timeline's sums over ``[t0, t1)`` on the interval clock
        (:meth:`PhaseTimeline.between`): what a reader of one window — the
        benchmark after its run, an operator at ``/profile?since=`` —
        gets instead of the process-lifetime totals of :meth:`summary`."""
        return self.timeline.between(t0, t1, engine)

    # -------------------------------------------------------------- views
    def summary(self) -> dict:
        """The lightweight per-engine summary ``/snapshot`` embeds:
        phase/bubble/lane accounting plus a headline the fleet scrape's
        bubble-% column reads."""
        engines = {name: ch.summary()
                   for name, ch in sorted(self.channels().items())}
        headline = {}
        if engines:
            dev = sum(e["phase_seconds"]["device"]
                      for e in engines.values())
            bub = sum(e["bubble_seconds"] for e in engines.values())
            headline = {
                "blocks": sum(e["blocks"] for e in engines.values()),
                "bubble_pct": round(100.0 * bub / (bub + dev), 2)
                if bub + dev > 0 else 0.0,
            }
        return {"engines": engines, "headline": headline,
                "timeline": {"len": len(self.timeline),
                             "total_recorded":
                                 self.timeline.total_added}}

    def snapshot(self, timeline_n: Optional[int] = None,
                 since_s: Optional[float] = None) -> dict:
        """The full ``GET /profile`` document: per-engine phase
        decomposition + bubble accounting, optionally the last N
        timeline entries, and optionally (``since_s``) the sums over the
        last that many seconds (:meth:`between`)."""
        out = self.summary()
        if timeline_n:
            out["timeline"]["recent"] = self.timeline.recent(timeline_n)
        if since_s is not None:
            from .tracing import interval_now
            now = interval_now()
            out["window"] = self.between(now - float(since_s), now)
        return out


_DEFAULT_LOCK = threading.Lock()
_DEFAULT: Optional[PhaseProfiler] = None


def default_profiler() -> PhaseProfiler:
    """Process-default profiler (bound to the default registry) every
    engine falls back to when none is injected — the same
    default-plus-injectable discipline as the registry, trace ring, and
    SLO tracker."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = PhaseProfiler()
        return _DEFAULT
