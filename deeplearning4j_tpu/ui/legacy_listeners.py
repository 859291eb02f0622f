"""Legacy visualization listeners (reference deeplearning4j-ui, 1,461 LoC:
HistogramIterationListener, FlowIterationListener,
ConvolutionalIterationListener + their Remote* variants posting via
WebReporter; SURVEY.md §2.8).

Each listener hooks the IterationListener bus and routes a typed record into
a StatsStorage backend (their Play-era counterparts rendered to the browser;
here the web UI in ui/server.py and any storage backend consume the same
records; Remote* = same listener pointed at a RemoteStatsRouter)."""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ..optimize.listeners import IterationListener
from .storage import StatsStorage


def _histogram(arr: np.ndarray, bins: int = 20):
    counts, edges = np.histogram(np.asarray(arr, np.float64).ravel(),
                                 bins=bins)
    return {"counts": counts.tolist(),
            "edges": np.round(edges, 6).tolist()}


class HistogramIterationListener(IterationListener):
    """Per-iteration parameter + gradient-proxy histograms and score
    (reference HistogramIterationListener)."""

    def __init__(self, storage: StatsStorage, frequency: int = 1,
                 session_id: str = "histogram"):
        self.storage = storage
        self.frequency = max(1, int(frequency))
        self.session_id = session_id
        self._prev_flat: Optional[np.ndarray] = None

    def iteration_done(self, model, iteration: int):
        if iteration % self.frequency:
            return
        flat = model.params_flat()
        record = {"session": self.session_id, "type": "histogram",
                  "iteration": int(iteration),
                  "score": float(model.score_value)
                  if model.score_value is not None else None,
                  "params": _histogram(flat)}
        # update magnitudes stand in for the gradient histogram, matching
        # what the reference displays between iterations
        if self._prev_flat is not None and self._prev_flat.shape == flat.shape:
            record["updates"] = _histogram(flat - self._prev_flat)
        self._prev_flat = flat
        self.storage.put_update(record)


class FlowIterationListener(IterationListener):
    """Network-structure + per-layer activation summary snapshot (reference
    FlowIterationListener's flow view)."""

    def __init__(self, storage: StatsStorage, frequency: int = 1,
                 session_id: str = "flow",
                 timing_frequency: Optional[int] = None):
        self.storage = storage
        self.frequency = max(1, int(frequency))
        self.session_id = session_id
        self._static_sent = False
        # the per-layer timing probe is EAGER (one dispatch + blocking read
        # per layer, each a full host round-trip): by default it
        # runs on the first record and then every 10th reported iteration;
        # records in between reuse the last measured timings. Pass
        # timing_frequency=0 to disable the probe entirely (the flow tab
        # then shows structure + param counts without timings).
        if timing_frequency is None:
            self.timing_frequency = self.frequency * 10
        elif int(timing_frequency) <= 0:
            self.timing_frequency = 0
        else:
            self.timing_frequency = int(timing_frequency)
        self._last_timings = None

    @staticmethod
    def _structure(model):
        """(layer/vertex display names, ordered param dicts) for both model
        families: MLN keeps a layer list; ComputationGraph keeps
        name-keyed vertices in topological order."""
        params = getattr(model, "params", None)
        if isinstance(params, dict):               # ComputationGraph
            order = model.conf.topological_order
            return list(order), [params[n] for n in order]
        layers = [type(l).__name__ for l in getattr(model, "layers", [])]
        return layers, list(params or [])

    def iteration_done(self, model, iteration: int):
        if iteration % self.frequency:
            return
        names, param_dicts = self._structure(model)
        if not self._static_sent:
            self.storage.put_static_info(
                {"session": self.session_id, "type": "flow_static",
                 "layers": names})
            self._static_sent = True
        sizes = [sum(int(np.prod(v.shape)) for v in p.values())
                 for p in param_dicts]
        if self.timing_frequency and (
                self._last_timings is None
                or iteration % self.timing_frequency == 0):
            timed = self._time_layers(model)
            if timed is not None:
                self._last_timings = timed
        record = {"session": self.session_id, "type": "flow",
                  "iteration": int(iteration),
                  "score": float(model.score_value)
                  if model.score_value is not None else None,
                  "param_counts": sizes,
                  "layer_timings_ms": self._last_timings}
        self.storage.put_update(record)

    @staticmethod
    def _time_layers(model, probe_examples: int = 4):
        """Per-layer/vertex forward timing on a probe slice of the last
        training batch (the reference FlowIterationListener's per-layer
        boxes carry timing). Eager execution with a blocking read each step
        — run at a coarse ``timing_frequency``; None when the model exposes
        no last batch."""
        import time
        ds = getattr(model, "last_input_batch", None)
        params = getattr(model, "params", None)
        if ds is None or not params:
            return None
        timings = []
        try:
            import jax
            import jax.numpy as jnp
            if isinstance(params, dict):           # ComputationGraph
                feats = ds.features
                probe = [np.asarray(f)[:probe_examples] for f in feats] \
                    if isinstance(feats, (list, tuple)) \
                    else np.asarray(feats)[:probe_examples]
                acts = dict(model._inputs_dict(probe))
                state = model._inference_state()
                for name in model.conf.topological_order:
                    v = model.conf.vertices[name]
                    xs = [acts[i] for i in model.conf.vertex_inputs[name]]
                    t0 = time.perf_counter()
                    y, _ = v.forward(params[name], state[name], xs,
                                     train=False, rng=None, masks=None)
                    jax.block_until_ready(y)
                    acts[name] = y
                    timings.append(
                        round((time.perf_counter() - t0) * 1e3, 3))
                return timings
            layers = getattr(model, "layers", None)
            if not layers:
                return None
            x = np.asarray(ds.features)[:probe_examples]
            act = jnp.asarray(x, model.compute_dtype)
            mask = None
            inf_state = model._inference_state()
            for i, layer in enumerate(layers):
                pp = model.conf.preprocessor_for(i)
                t0 = time.perf_counter()
                if pp is not None:
                    act = pp.pre_process(act, mask)
                    mask = pp.feed_forward_mask(mask)
                act, _ = layer.forward(model.params[i], inf_state[i], act,
                                       train=False, rng=None, mask=mask)
                np.asarray(act[:1])          # block: honest per-layer time
                timings.append(round((time.perf_counter() - t0) * 1e3, 3))
        except Exception:                    # pragma: no cover - best effort
            return None
        return timings


class ConvolutionalIterationListener(IterationListener):
    """Activation grids for conv layers (reference
    ConvolutionalIterationListener renders PNG grids; here the grid tensor
    summary goes to storage and optionally to disk as .npy)."""

    def __init__(self, storage: StatsStorage, sample_input,
                 frequency: int = 10, session_id: str = "conv",
                 output_dir=None, max_channels: int = 16,
                 max_layers: int = 4):
        self.storage = storage
        self.sample = np.asarray(sample_input)
        self.frequency = max(1, int(frequency))
        self.session_id = session_id
        self.output_dir = output_dir
        self.max_channels = max_channels
        # cap layers carrying pixel grids: each grid is tens of KB per
        # record, and storage backends are append-only
        self.max_layers = max_layers

    def iteration_done(self, model, iteration: int):
        if iteration % self.frequency:
            return
        import base64

        from .png import activation_grid, to_uint8

        acts: List[np.ndarray] = model.feed_forward(self.sample)
        conv_layers = []
        for i, a in enumerate(acts[1:]):
            if a.ndim == 4 and len(conv_layers) < self.max_layers:
                grid = a[0, :, :, :self.max_channels]
                # normalized uint8 strip travels in the record so the web
                # UI can render the grid as a PNG (the reference drew AWT
                # image grids server-side)
                u8 = to_uint8(activation_grid(grid, self.max_channels))
                conv_layers.append({
                    "layer": i,
                    "shape": list(a.shape),
                    "mean": float(a.mean()),
                    "std": float(a.std()),
                    "grid_shape": list(u8.shape),
                    "grid_b64": base64.b64encode(u8.tobytes()).decode(),
                })
                if self.output_dir is not None:
                    from pathlib import Path
                    d = Path(self.output_dir)
                    d.mkdir(parents=True, exist_ok=True)
                    np.save(d / f"iter{iteration:06d}_layer{i}.npy",
                            np.transpose(grid, (2, 0, 1)))
        self.storage.put_update(
            {"session": self.session_id, "type": "convolutional",
             "iteration": int(iteration), "layers": conv_layers})
