"""Training-stats collection (reference ui-model
stats/BaseStatsListener.java:43,287-539 — per-iteration score, timing, memory,
param/gradient/update histograms + ratios, encoded and routed into a
StatsStorage; SURVEY.md §2.8, §5.5).

The SBE binary encoding is replaced with plain dict records (JSON-friendly);
the storage router contract is preserved. Histogram collection is periodic
(``update_frequency``) so the jitted train step isn't forced to sync every
iteration — the 'don't destroy jit performance' answer from SURVEY.md §7
hard-parts #2."""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional

import numpy as np

from ..optimize.listeners import IterationListener


def _histogram(arr: np.ndarray, bins: int = 20) -> Dict:
    arr = np.asarray(arr, np.float64).reshape(-1)
    if arr.size == 0:
        return {"bins": [], "counts": []}
    counts, edges = np.histogram(arr, bins=bins)
    return {"bins": edges.tolist(), "counts": counts.tolist()}


class StatsListener(IterationListener):
    """Collect per-iteration stats into a StatsStorage router."""

    def __init__(self, storage, session_id: Optional[str] = None,
                 update_frequency: int = 1, histograms_frequency: int = 10,
                 collect_histograms: bool = True):
        self.storage = storage
        self.session_id = session_id or f"session_{int(time.time())}"
        self.update_frequency = max(1, int(update_frequency))
        self.histograms_frequency = max(1, int(histograms_frequency))
        self.collect_histograms = collect_histograms
        self._last_time = None
        self._init_reported = False

    def iteration_done(self, model, iteration: int):
        if iteration % self.update_frequency:
            return
        now = time.time()
        record: Dict = {
            "session": self.session_id,
            "type": "update",
            "iteration": iteration,
            "epoch": getattr(model, "epoch", 0),
            "timestamp": now,
            "score": float(model.score_value),
        }
        if self._last_time is not None:
            dt = now - self._last_time
            record["iterations_per_sec"] = self.update_frequency / max(dt, 1e-9)
        self._last_time = now
        if not self._init_reported:
            self._init_reported = True
            self.storage.put_static_info({
                "session": self.session_id,
                "type": "init",
                "timestamp": now,
                "model_class": type(model).__name__,
                "num_params": model.num_params(),
                "num_layers": len(getattr(model, "layers", [])) or
                len(getattr(model.conf, "vertices", {})),
                "config_json": model.conf.to_json(indent=None),
            })
        if self.collect_histograms and \
                iteration % self.histograms_frequency == 0:
            params = model.param_table() if hasattr(model, "param_table") \
                else {}
            record["param_histograms"] = {k: _histogram(v)
                                          for k, v in params.items()}
            record["param_mean_magnitudes"] = {
                k: float(np.mean(np.abs(v))) for k, v in params.items()}
        try:
            import resource
            record["max_rss_mb"] = resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss / 1024.0
        except Exception:
            pass
        self.storage.put_update(record)


class SparkStyntheticPhaseTimer:
    """Per-phase timing (reference spark StatsCalculationHelper /
    SparkTrainingStats; SURVEY.md §5.1): time named phases of a distributed
    run, export a timeline."""

    def __init__(self):
        self.events: List[Dict] = []
        self._open: Dict[str, float] = {}

    def start(self, phase: str):
        self._open[phase] = time.time()

    def end(self, phase: str):
        t0 = self._open.pop(phase, None)
        if t0 is not None:
            self.events.append({"phase": phase, "start": t0,
                                "duration": time.time() - t0})

    def timeline(self) -> List[Dict]:
        return list(self.events)

    def export_html(self, path):
        rows = "".join(
            f"<tr><td>{e['phase']}</td><td>{e['start']:.3f}</td>"
            f"<td>{e['duration'] * 1000:.1f} ms</td></tr>"
            for e in self.events)
        with open(path, "w") as f:
            f.write("<html><body><h2>Phase timeline</h2><table border=1>"
                    "<tr><th>phase</th><th>start</th><th>duration</th></tr>"
                    f"{rows}</table></body></html>")


def profiler_trace(log_dir: str):
    """Context manager around jax.profiler (SURVEY.md §5.1 parity — the
    jax-native replacement for the reference's listener-based profiling):
    how an operator takes an xplane. The trace it writes holds the
    program's own spans beside the device's events, on one clock: the
    engine loop's and ``fit_batch``'s seams (``dl4j.engine.*``,
    ``dl4j.train.*`` on ``/host:CPU``; observability.tracing.Seam), every
    operation's layer in its ``tf_op`` (the named scopes), and the
    attention kernels under their own names. Keep it to a second or two
    outside a benchmark: the stop holds the interpreter for some 50 s a
    traced second (PERF.md)."""
    import contextlib
    import jax

    @contextlib.contextmanager
    def _ctx():
        jax.profiler.start_trace(log_dir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
    return _ctx()
