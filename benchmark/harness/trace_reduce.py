"""From a profiler trace (``.xplane.pb``) to numbers: device busy and idle
time, time per compiled program, time per device operation, and the longest
idle gaps with what the benchmark's own host spans say the host was doing.

What a TPU trace holds (looked at by hand, PR 25): one plane per chip,
``/device:TPU:<n>``, with a line ``XLA Modules`` (one event per execution of
a compiled program, named ``jit_<function>(<fingerprint>)``) and a line
``XLA Ops`` (one event per HLO operation, named by its HLO text, operand
shapes included; a loop's event spans its body's events). Host threads are
lines of the plane ``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans
appear there under their own names. All on one clock, in nanoseconds.

The benchmark brackets what it measures in a span named ``bench.window``;
everything is reduced inside that span.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_MODULE_RE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")

Event = Tuple[float, float, str]          # start_ns, duration_ns, name


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]                     # start_ns, end_ns
    ops: Dict[str, List[Event]]                     # per device plane
    modules: Dict[str, List[Event]]                 # per device plane
    spans: List[Event]                              # host bench.* spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return parse(ProfileData.from_file(path))


def parse(profile) -> Trace:
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [(float(e.start_ns),
                                        float(e.duration_ns), e.name)
                                       for e in line.events]
                elif line.name == "XLA Modules":
                    modules[plane.name] = [(float(e.start_ns),
                                            float(e.duration_ns), e.name)
                                           for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((float(e.start_ns),
                                      float(e.duration_ns), e.name))
    win = [s for s in spans if s[2] == WINDOW_SPAN]
    if win:
        start, dur, _ = max(win, key=lambda s: s[1])
        window = (start, start + dur)
    else:
        every = [e for evs in ops.values() for e in evs]
        if not every:
            raise ValueError("trace holds no device operation and no "
                             f"{WINDOW_SPAN} span")
        window = (min(e[0] for e in every),
                  max(e[0] + e[1] for e in every))
    return Trace(window, ops, modules,
                 [s for s in spans if s[2] != WINDOW_SPAN])


def _clip(events: Sequence[Event], window: Tuple[float, float]
          ) -> List[Tuple[float, float]]:
    lo, hi = window
    out = []
    for start, dur, _ in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((a, b))
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_intervals(trace: Trace, plane: str) -> List[Tuple[float, float]]:
    return _union(_clip(trace.ops.get(plane, ()), trace.window))


def busy_seconds(trace: Trace) -> float:
    """Seconds in which an operation ran on the device, inside the window,
    averaged over the chips that ran anything."""
    per = [sum(b - a for a, b in busy_intervals(trace, p)) / 1e9
           for p in trace.ops]
    per = [x for x in per if x > 0]
    return sum(per) / len(per) if per else 0.0


def idle_share(trace: Trace) -> Optional[float]:
    """1 - busy / window, in percent; None where nothing ran."""
    busy = busy_seconds(trace)
    if busy <= 0 or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy / trace.window_s)


def program_name(module_event_name: str) -> str:
    """``jit_train_step(5948129834251684432)`` -> ``train_step``."""
    return _MODULE_RE.match(module_event_name).group(1)


def program_times(trace: Trace) -> Dict[str, List[float]]:
    """Device seconds of every execution inside the window, by program."""
    lo, hi = trace.window
    out: Dict[str, List[float]] = {}
    for evs in trace.modules.values():
        for start, dur, name in evs:
            if start >= lo and start + dur <= hi:
                out.setdefault(program_name(name), []).append(dur / 1e9)
    return out


def program_starts(trace: Trace, program: str) -> List[float]:
    """Start times (seconds on the trace's clock) of a program's executions
    inside the window, on the first chip that ran it."""
    lo, hi = trace.window
    for evs in trace.modules.values():
        got = sorted(start / 1e9 for start, dur, name in evs
                     if program_name(name) == program and start >= lo
                     and start + dur <= hi)
        if got:
            return got
    return []


def _self_times(events: Sequence[Event]) -> List[Tuple[float, str]]:
    """(self seconds, name) per event: its duration minus the part its
    nested events cover (a loop's event spans its body's)."""
    order = sorted(events, key=lambda e: (e[0], -e[1]))
    out: List[List] = []
    stack: List[int] = []
    for start, dur, name in order:
        while stack and start >= out[stack[-1]][2]:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[0] -= min(dur, parent[2] - start)
        out.append([dur, name, start + dur])
        stack.append(len(out) - 1)
    return [(max(d, 0.0) / 1e9, n) for d, n, _ in out]


def op_label(hlo_text: str) -> str:
    """A short stable label for an HLO operation's event: its name without
    the instance number (``%fusion.162 = ...`` -> ``fusion``), with the
    custom-call target where it is one."""
    head = hlo_text.split(" = ", 1)[0].lstrip("%")
    base = re.sub(r"[.\d]+$", "", head) or head
    m = re.search(r'custom_call_target="([^"]+)"', hlo_text)
    return f"{base}[{m.group(1)}]" if m else base


def top_ops(trace: Trace, top: int = 10) -> List[List]:
    """The device operations that took most (self) time in the window."""
    lo, hi = trace.window
    total: Dict[str, float] = {}
    for evs in trace.ops.values():
        inside = [e for e in evs if e[0] >= lo and e[0] + e[1] <= hi]
        for secs, name in _self_times(inside):
            label = op_label(name)
            total[label] = total.get(label, 0.0) + secs
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v] for k, v in ranked]


def ops_matching(trace: Trace, needle: str) -> List[Event]:
    """Device operations inside the window whose HLO text holds ``needle``."""
    lo, hi = trace.window
    return [e for evs in trace.ops.values() for e in evs
            if needle in e[2] and e[0] >= lo and e[0] + e[1] <= hi]


def idle_gaps(trace: Trace, top: int = 10) -> List[List]:
    """The longest stretches in which no operation ran on the first chip,
    each named by the benchmark's host span that covers most of it
    (``no_span`` where none does), longest first."""
    if not trace.ops:
        return []
    plane = sorted(trace.ops)[0]
    busy = busy_intervals(trace, plane)
    lo, hi = trace.window
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:top]:
        best, best_cover = "no_span", 0.0
        for start, dur, name in trace.spans:
            cover = min(b, start + dur) - max(a, start)
            if cover > best_cover:
                best, best_cover = name, cover
        out.append([best, (b - a) / 1e9])
    return out


_SHAPE = r"[a-z]+\d*\[([\d,]*)\](?:\{[^}]*\})?"


def _dims(match) -> Tuple[int, ...]:
    return tuple(int(x) for x in match.group(1).split(",") if x)


def operand_shapes(hlo_text: str) -> List[Tuple[int, ...]]:
    """Shapes of an operation's operands, read from its HLO text: every
    shape that is followed by an operand's name (``bf16[8,64]{1,0} %x``)."""
    return [_dims(m) for m in re.finditer(_SHAPE + r" %", hlo_text)]


def result_shapes(hlo_text: str) -> List[Tuple[int, ...]]:
    """Shapes of what an operation produces: the type (one shape, or a
    tuple of them) between `` = `` and the operation's name."""
    _, _, rest = hlo_text.partition(" = ")
    if rest.startswith("("):
        head = rest[:rest.index(") ") + 1]
    else:
        head = rest.split(" ", 1)[0]
    return [_dims(m) for m in re.finditer(_SHAPE, head)]
