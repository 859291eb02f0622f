"""ComputationGraph: the DAG model (reference nn/graph/ComputationGraph.java,
2,782 LoC — feedForward in topo order :1147, calcBackpropGradients reverse
topo :1062, multi-input/multi-output, rnn state; SURVEY.md §2.1, §3.2).

Functional executor: the stored topological order is walked inside one jitted
train step; autodiff differentiates through the whole DAG, so there is no
reverse-topo pass to write. Multi-output losses sum over all output layer
vertices (reference behaviour)."""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...observability import tracing
from ...observability.tracing import Seam
from ...ops import rng as rngmod
from ..helpers import get_helper
from ..multilayer import _nz
from ...ops.dataset import DataSet, MultiDataSet
from ...ops.updaters import make_updater, normalize_gradient, schedule_lr
from .fusion import build_fusion_plan
from .graph_config import ComputationGraphConfiguration
from ..conf.layers.feedforward import head_params
from .vertices import LayerVertex


def scoped(names):
    """Each vertex name in turn, inside ``jax.named_scope(name)`` while the
    caller's loop body runs: a walk over the graph then stamps every
    operation with its vertex (``embed``, ``attn3``, ``ffn3``, ``lnf``,
    ``out``) in the HLO metadata — op_name, which the device trace carries
    as each operation's tf_op — forward and backward. Metadata only: the
    compiled instructions are the same."""
    for name in names:
        with jax.named_scope(name):
            yield name


class ComputationGraph:
    def __init__(self, conf: ComputationGraphConfiguration,
                 compute_dtype=None):
        self.conf = conf
        self.compute_dtype = compute_dtype or jnp.float32
        self.params: Dict[str, Dict] = {}
        self.state: Dict[str, Dict] = {}
        self.updaters: Dict[str, object] = {}
        self.updater_state: Dict[str, Dict] = {}
        self.iteration = 0
        self.epoch = 0
        self.listeners: List = []
        self.score_value = float("nan")
        self._jit_cache: Dict = {}
        self._initialized = False
        self._rnn_state: Optional[Dict[str, Dict]] = None

    # ------------------------------------------------------------------ init
    def init(self) -> "ComputationGraph":
        key = rngmod.root_key(self.conf.seed)
        self.params, self.state = {}, {}
        self.updaters, self.updater_state = {}, {}
        storage_dtype = jnp.float64 if self.compute_dtype == jnp.float64 \
            else jnp.float32   # f32 masters; bf16 cast happens in-step
        for idx, name in enumerate(self.conf.topological_order):
            v = self.conf.vertices[name]
            vkey = rngmod.for_layer(rngmod.for_purpose(key, "init"), idx)
            p = v.init_params(vkey, storage_dtype)
            self.params[name] = p
            self.state[name] = v.init_state()
            layer = v.layer if isinstance(v, LayerVertex) else None
            upd = make_updater(
                (layer.updater if layer else None) or "sgd",
                momentum=_nz(layer.momentum if layer else None, 0.9),
                adam_mean_decay=_nz(
                    layer.adam_mean_decay if layer else None, 0.9),
                adam_var_decay=_nz(
                    layer.adam_var_decay if layer else None, 0.999),
                rho=_nz(layer.rho if layer else None, 0.95),
                rms_decay=_nz(layer.rms_decay if layer else None, 0.95),
                epsilon=_nz(layer.epsilon if layer else None, 1e-8))
            self.updaters[name] = upd
            self.updater_state[name] = {k: upd.init(val)
                                        for k, val in p.items()}
        self._initialized = True
        return self

    def _ensure_init(self):
        if not self._initialized:
            self.init()

    # ---------------------------------------------------------------- fusion
    def _get_fusion_plan(self):
        """Cached cross-vertex fusion plan (nn/graph/fusion.py); training
        path only."""
        cached = self._jit_cache.get("fusion")
        if cached is None:
            cached = build_fusion_plan(self.conf)
            self._jit_cache["fusion"] = cached
        return cached

    def _forward_fused(self, fu, params, state, acts, masks, new_state):
        """Execute one BN->add->act pattern. Falls back to the sequential
        vertex math when runtime masks are present or the helper was
        disabled after the plan was cached."""
        x = acts[fu.bn_input]
        res = acts[fu.res_input]
        bn = self.conf.vertices[fu.bn_name].layer
        helper = get_helper("batchnorm_add_act_train")
        if helper is not None and masks.get(fu.bn_input) is None and \
                masks.get(fu.res_input) is None:
            y, mean, var = helper(x, params[fu.bn_name]["gamma"],
                                  params[fu.bn_name]["beta"],
                                  state[fu.bn_name]["mean"], res, bn.eps,
                                  fu.activation)
            d = bn.decay
            new_state[fu.bn_name] = {
                "mean": d * state[fu.bn_name]["mean"] + (1 - d) * mean,
                "var": d * state[fu.bn_name]["var"] + (1 - d) * var}
            masks[fu.act_name] = None
        else:
            y, nstate = bn.forward(params[fu.bn_name], state[fu.bn_name], x,
                                   train=True, mask=masks.get(fu.bn_input))
            y = y + res
            if fu.activation == "relu":
                y = jnp.maximum(y, 0)
            new_state[fu.bn_name] = nstate
            # plain-walk parity: the add vertex propagates its FIRST input's
            # mask, and the activation vertex inherits it. The skipped BN
            # vertex never wrote masks[bn_name], so when it IS the first
            # input, substitute what the walk would have assigned there
            # (its own input's mask)
            first_in = self.conf.vertex_inputs[fu.add_name][0]
            masks[fu.act_name] = masks.get(fu.bn_input) \
                if first_in == fu.bn_name else masks.get(first_in)
        acts[fu.act_name] = y
        new_state[fu.act_name] = state[fu.act_name]

    # --------------------------------------------------------------- forward
    def _forward(self, params, state, inputs: Dict[str, jnp.ndarray], *,
                 train, rng, input_masks: Optional[Dict] = None,
                 output_preout: bool = False,
                 initial_rnn: Optional[Dict] = None,
                 skip_preoutput=()):
        """Walk topo order. Returns (activations dict, new_state dict, reg).
        With ``output_preout``, output layer vertices contribute their
        PRE-activation (for fused losses) in a separate dict.
        ``initial_rnn``: per-vertex rnn carries (graph TBPTT / rnnTimeStep —
        reference ComputationGraph.java:2010, :1194-analog); a non-empty
        entry replaces that vertex's state, like the MLN path.
        ``skip_preoutput``: terminal output vertices whose projection is
        computed INSIDE the loss (kernels/fused_ce.py) — only their input is
        recorded; the [.., n_out] pre-activation is never built."""
        acts: Dict[str, jnp.ndarray] = dict(inputs)
        masks: Dict[str, Optional[jnp.ndarray]] = dict(input_masks or {})
        new_state: Dict[str, Dict] = {}
        preouts: Dict[str, jnp.ndarray] = {}
        last_inputs: Dict[str, jnp.ndarray] = {}
        reg = jnp.asarray(0.0, jnp.float32)
        out_set = set(self.conf.network_outputs) if output_preout else set()
        fusion_plan, fusion_skip = self._get_fusion_plan() if train \
            else ({}, set())
        for idx, name in enumerate(scoped(self.conf.topological_order)):
            if name in fusion_skip:
                # computed by a fused pattern at its activation vertex
                new_state.setdefault(name, state[name])
                continue
            if name in fusion_plan:
                self._forward_fused(fusion_plan[name], params, state, acts,
                                    masks, new_state)
                continue
            v = self.conf.vertices[name]
            in_names = self.conf.vertex_inputs[name]
            xs = [acts[i] for i in in_names]
            ms = [masks.get(i) for i in in_names]
            vrng = rngmod.for_layer(rng, idx) if rng is not None else None
            vstate = state[name]
            if initial_rnn is not None and initial_rnn.get(name):
                vstate = initial_rnn[name]
            if isinstance(v, LayerVertex):
                reg = reg + v.layer.reg_penalty(params[name])
            if name in out_set and isinstance(v, LayerVertex) and \
                    hasattr(v.layer, "preoutput"):
                x = xs[0]
                m = ms[0]
                if v.preprocessor is not None:
                    x = v.preprocessor.pre_process(x, m)
                    m = v.preprocessor.feed_forward_mask(m)
                if v.layer.drop_out and train:
                    x = v.layer.maybe_dropout(x, train=train, rng=vrng)
                last_inputs[name] = x
                masks[name] = m
                new_state[name] = vstate
                if name in skip_preoutput:
                    continue            # projection fused into the loss
                pre = v.layer.preoutput(head_params(self.conf, params, name),
                                        x)
                preouts[name] = pre
                acts[name] = v.layer.activation_fn()(pre)
            else:
                y, nstate = v.forward(head_params(self.conf, params, name),
                                      vstate, xs,
                                      train=train, rng=vrng, masks=ms)
                acts[name] = y
                new_state[name] = nstate
                masks[name] = ms[0] if ms else None
        return acts, new_state, reg, preouts, masks, last_inputs

    def _to_device_dtype(self, a):
        """compute_dtype for floats; integer inputs (token ids for
        embedding gathers) KEEP their dtype — casting ids through bf16
        (7-bit mantissa) silently corrupts every id >= 257."""
        a = jnp.asarray(a)
        if jnp.issubdtype(a.dtype, jnp.integer) or \
                jnp.issubdtype(a.dtype, jnp.bool_):
            return a
        return a.astype(self.compute_dtype)

    def _inputs_dict(self, features) -> Dict[str, jnp.ndarray]:
        names = self.conf.network_inputs
        if isinstance(features, dict):
            return {k: self._to_device_dtype(v)
                    for k, v in features.items()}
        if isinstance(features, (list, tuple)):
            return {n: self._to_device_dtype(f)
                    for n, f in zip(names, features)}
        return {names[0]: self._to_device_dtype(features)}

    @staticmethod
    def _strip_rnn_carry(states):
        """Drop transient rnn h/c before storing: each minibatch starts from
        zero rnn state (see MultiLayerNetwork._strip_rnn_carry)."""
        return {name: ({k: v for k, v in s.items() if k not in ("h", "c")}
                       if isinstance(s, dict) else s)
                for name, s in states.items()}

    def _inference_state(self):
        """State minus the transient rnn carry ('h'/'c'): output/score are
        stateless like the reference; only rnnTimeStep continues from stored
        state (see MultiLayerNetwork._inference_state)."""
        return self._strip_rnn_carry(self.state)

    def output(self, *features, train: bool = False):
        """Forward pass → list of output activations (reference
        ComputationGraph.output)."""
        self._ensure_init()
        if len(features) == 1:
            inputs = self._inputs_dict(features[0])
        else:
            inputs = self._inputs_dict(list(features))
        fn = self._jit_cache.get("output")
        if fn is None:
            def _out(params, state, inputs):
                acts, *_ = self._forward(params, state, inputs, train=False,
                                         rng=None)
                return [acts[o] for o in self.conf.network_outputs]
            # inference seam: donating would free params/state the next
            # call still needs (GL005 siblings donate TRAIN-step buffers)
            fn = jax.jit(_out)   # graftlint: disable=GL005
            self._jit_cache["output"] = fn
        outs = fn(self.params, self._inference_state(), inputs)
        return [np.asarray(o) for o in outs]

    # -------------------------------------------------------------- training
    def _cast_params(self, params):
        """Mixed precision: bf16 compute against f32 master params (see
        MultiLayerNetwork._cast_params)."""
        cd = self.compute_dtype
        if cd == jnp.float32 or cd == jnp.float64:
            return params
        return jax.tree_util.tree_map(
            lambda a: a.astype(cd) if a.dtype == jnp.float32 else a, params)

    def _fused_ce_outputs(self, labels: Dict):
        """Terminal softmax+mcxent output layers whose labels arrived as
        integer class ids: their [.., n_out] projection + loss run as ONE
        fused sparse cross-entropy (kernels/fused_ce.py) — at a 32k LM
        vocab the one-hot labels alone are 2·V bytes/token and the
        materialized loss reads them twice. Only outputs no other vertex
        consumes are eligible (their activation is never built)."""
        eligible = set()
        for out_name in self.conf.network_outputs:
            v = self.conf.vertices[out_name]
            if not isinstance(v, LayerVertex):
                continue
            layer = v.layer
            if str(getattr(layer, "loss", "")).lower() not in (
                    "mcxent", "negativeloglikelihood",
                    "categorical_crossentropy"):
                continue
            if str(getattr(layer, "activation", "")).lower() != "softmax":
                continue
            from ..conf.layers import OutputLayer
            if not isinstance(layer, OutputLayer) or not layer.has_bias:
                continue                 # needs a W/b projection to fuse
            y = labels.get(out_name)
            if y is None or not jnp.issubdtype(jnp.asarray(y).dtype,
                                               jnp.integer):
                continue
            # shape gate: sparse ids are [N, T] for rnn heads, [N] for ff
            # heads — with an optional trailing singleton ([N, 1] /
            # [N, T, 1], the classic DL4J column-vector label format).
            # Integer-dtype ONE-HOT labels ([N, V] / [N, T, V]) keep the
            # materialized path (compute_loss promotes them) — dtype alone
            # must not reroute previously-working inputs.
            expected = 2 if layer.input_kind() == "rnn" else 1
            nd = jnp.ndim(y)
            if nd != expected and not (nd == expected + 1 and
                                       jnp.shape(y)[-1] == 1):
                continue
            if any(out_name in ins
                   for n, ins in self.conf.vertex_inputs.items()):
                continue                         # someone consumes this act
            eligible.add(out_name)
        return eligible

    def _loss(self, params, state, inputs, labels: Dict, rng,
              label_masks: Optional[Dict] = None, input_masks=None,
              initial_rnn=None):
        from ...kernels.fused_ce import fused_sparse_ce_score
        params = self._cast_params(params)
        fused_outs = self._fused_ce_outputs(labels)
        acts, new_state, reg, preouts, masks, last_in = self._forward(
            params, state, inputs, train=True, rng=rng,
            input_masks=input_masks, output_preout=True,
            initial_rnn=initial_rnn, skip_preoutput=fused_outs)
        score = reg
        for out_name in self.conf.network_outputs:
            v = self.conf.vertices[out_name]
            if not isinstance(v, LayerVertex) or \
                    not hasattr(v.layer, "compute_score"):
                continue
            y = labels[out_name]
            lmask = (label_masks or {}).get(out_name)
            if out_name in fused_outs:
                x = last_in[out_name]
                if lmask is None and x.ndim == 3:
                    lmask = masks.get(out_name)
                with jax.named_scope("loss"):
                    score = score + fused_sparse_ce_score(
                        params[out_name], x, y, lmask)
                continue
            from ...kernels.fused_ce import (_MCXENT_LOSSES,
                                             sparse_shaped)
            if sparse_shaped(v.layer, y) and \
                    str(getattr(v.layer, "loss", "")).lower() in \
                    _MCXENT_LOSSES:
                raise ValueError(
                    f"output '{out_name}' got integer class-id labels but "
                    "is not fused-CE eligible (sparse labels need a "
                    "TERMINAL OutputLayer with softmax activation whose "
                    "activation no other vertex consumes). Pass one-hot "
                    "labels here, or restructure the graph so the softmax "
                    "head is terminal.")
            pre = preouts[out_name]
            if lmask is None and pre.ndim == 3:
                lmask = masks.get(out_name)
            with jax.named_scope("loss"):
                score = score + v.layer.compute_score(params[out_name], y,
                                                      pre, lmask)
        return score, new_state

    def _make_train_step(self, with_rnn_carry: bool = False):
        conf = self.conf

        def train_step(params, upd_state, state, inputs, labels, input_masks,
                       label_masks, iteration, initial_rnn):
            rng = rngmod.for_iteration(
                rngmod.for_purpose(rngmod.root_key(conf.seed), "dropout"),
                iteration)

            def lf(p):
                return self._loss(p, state, inputs, labels, rng, label_masks,
                                  input_masks,
                                  initial_rnn if with_rnn_carry else None)

            (score, new_state), grads = jax.value_and_grad(
                lf, has_aux=True)(params)
            it_f = jnp.asarray(iteration, jnp.float32)
            new_params, new_upd = {}, {}
            for name in conf.topological_order:
                g = grads.get(name, {})
                if not g:
                    new_params[name] = params[name]
                    new_upd[name] = upd_state[name]
                    continue
                v = conf.vertices[name]
                layer = v.layer if isinstance(v, LayerVertex) else None
                with jax.named_scope("updater"), jax.named_scope(name):
                    if layer is not None:
                        g = normalize_gradient(
                            g, layer.gradient_normalization,
                            _nz(layer.gradient_normalization_threshold,
                                1.0))
                    lr = schedule_lr(
                        _nz(layer.learning_rate if layer else None, 0.1),
                        conf.lr_policy, it_f,
                        decay_rate=conf.lr_policy_decay_rate,
                        steps=conf.lr_policy_steps,
                        power=conf.lr_policy_power,
                        max_iterations=float(conf.max_iterations or 1),
                        schedule=conf.learning_rate_schedule)
                    upd = self.updaters[name]
                    np_, nu = {}, {}
                    for pname, grad in g.items():
                        step, nstate = upd.update(
                            grad, upd_state[name][pname], lr, it_f)
                        np_[pname] = params[name][pname] - step
                        nu[pname] = nstate
                new_params[name] = np_
                new_upd[name] = nu
            return new_params, new_upd, new_state, score

        return train_step

    def _labels_dict(self, labels) -> Dict:
        names = self.conf.network_outputs
        if isinstance(labels, dict):
            return {k: self._to_device_dtype(v)
                    for k, v in labels.items()}
        if isinstance(labels, (list, tuple)):
            return {n: self._to_device_dtype(l)
                    for n, l in zip(names, labels)}
        return {names[0]: self._to_device_dtype(labels)}

    def fit(self, data, num_epochs: int = 1):
        """Train on DataSet / MultiDataSet / iterator thereof (reference
        ComputationGraph.fit)."""
        self._ensure_init()
        if isinstance(data, (DataSet, MultiDataSet)):
            data = [data]
        elif not isinstance(data, (list, tuple)) and \
                not hasattr(data, "reset"):
            # plain generator/iterator: materialize once so every epoch
            # actually trains (an exhausted generator would silently no-op)
            data = list(data)
        for _ in range(num_epochs):
            for ds in data:
                self.fit_batch(ds)
            if hasattr(data, "reset"):
                data.reset()
            self.epoch += 1
        return self

    def _get_train_step(self, with_rnn_carry: bool = False):
        key = ("train", with_rnn_carry)
        if key not in self._jit_cache:
            from ...ops.platform import train_donate_argnums
            self._jit_cache[key] = jax.jit(
                self._make_train_step(with_rnn_carry),
                donate_argnums=train_donate_argnums())
        return self._jit_cache[key]

    def fit_batch(self, ds):
        """One training step. Its seams (observability.tracing.Seam) land
        on a profiler session's trace: ``dl4j.train.stage`` (the batch to
        device arrays), ``dl4j.train.step`` (a StepTraceAnnotation around
        the step's dispatch, numbered by the iteration) and
        ``dl4j.train.readback`` (listeners, which read the loss)."""
        self._ensure_init()
        self.last_input_batch = ds    # probe data for flow/debug listeners
        with Seam(tracing.TRAIN_STAGE, self.iteration):
            inputs = self._inputs_dict(ds.features)
            tbptt = self.conf.backprop_type == "truncated_bptt" and \
                (self.conf.tbptt_fwd_length or 0) > 0 and \
                any(v.ndim == 3 for v in inputs.values())
            if not tbptt:
                labels = self._labels_dict(ds.labels)
                imasks, lmasks = self._masks_of(ds)
        if tbptt:
            self._fit_tbptt(ds)
            return
        with Seam(tracing.TRAIN_STEP, self.iteration, step=True):
            step = self._get_train_step(False)
            self.params, self.updater_state, new_states, score = step(
                self.params, self.updater_state, self.state, inputs, labels,
                imasks, lmasks, self.iteration, {})
            self.state = self._strip_rnn_carry(new_states)
            self.score_value = score  # device scalar; sync deferred
            self.iteration += 1
        if self.listeners:
            with Seam(tracing.TRAIN_READBACK, self.iteration):
                for lst in self.listeners:
                    lst.iteration_done(self, self.iteration)

    @staticmethod
    def _slice_time(d: Optional[Dict], start: int, end: int,
                    min_ndim: int = 3) -> Optional[Dict]:
        """Slice every time-distributed array in a name→array dict along
        axis 1. Masks are [N, T] (min_ndim=2); features/labels [N, T, C]."""
        if d is None:
            return None
        return {k: (v if v is None or v.ndim < min_ndim else v[:, start:end])
                for k, v in d.items()}

    def _fit_tbptt(self, ds):
        """Graph truncated BPTT (reference ComputationGraph TBPTT path,
        the doTruncatedBPTT analog of MultiLayerNetwork.java:1194): slide a
        tbptt_fwd_length window over time, carrying per-vertex RNN state
        across windows within the minibatch."""
        inputs = self._inputs_dict(ds.features)
        labels = self._labels_dict(ds.labels)
        imasks, lmasks = self._masks_of(ds)
        t_total = max(v.shape[1] for v in inputs.values() if v.ndim == 3)
        window = self.conf.tbptt_fwd_length
        step = self._get_train_step(True)
        carry: Dict[str, Dict] = {}
        for start in range(0, t_total, window):
            end = min(start + window, t_total)
            # 2D integer labels (sparse class ids, [N, T]) are
            # time-distributed too — slice them like masks, not like
            # [N, T, C] one-hot (min_ndim=3 would pass them whole and the
            # fused CE would see T_total ids against a window of inputs)
            # ... but only when dim 1 actually spans time: a [N, 1] integer
            # column label on a feedforward head in a mixed graph must pass
            # through whole, not be sliced along its singleton class axis
            sliced_labels = {
                k: (v if v is None else
                    (v[:, start:end]
                     if v.ndim >= 3 or (v.ndim == 2 and
                                        jnp.issubdtype(v.dtype, jnp.integer)
                                        and v.shape[1] == t_total)
                     else v))
                for k, v in labels.items()}
            self.params, self.updater_state, new_states, score = step(
                self.params, self.updater_state, self.state,
                self._slice_time(inputs, start, end),
                sliced_labels,
                self._slice_time(imasks, start, end, min_ndim=2),
                self._slice_time(lmasks, start, end, min_ndim=2),
                self.iteration, carry)
            # carry only RNN h/c into the next window (detached by design)
            carry = {name: {k: v for k, v in st.items() if k in ("h", "c")}
                     for name, st in new_states.items()
                     if isinstance(st, dict) and ("h" in st or "c" in st)}
            self.state = self._strip_rnn_carry(new_states)
            self.score_value = score   # device scalar; sync deferred
            self.iteration += 1
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration)

    # --------------------------------------------------------------- scoring
    def _masks_of(self, ds):
        """(input_masks, label_masks) dicts from a DataSet/MultiDataSet."""
        if isinstance(ds, MultiDataSet):
            imasks = None
            if ds.features_masks:
                imasks = {n: None if m is None else
                          jnp.asarray(m, self.compute_dtype)
                          for n, m in zip(self.conf.network_inputs,
                                          ds.features_masks)}
            lmasks = None
            if ds.labels_masks:
                lmasks = {n: None if m is None else
                          jnp.asarray(m, self.compute_dtype)
                          for n, m in zip(self.conf.network_outputs,
                                          ds.labels_masks)}
            return imasks, lmasks
        imasks = None if ds.features_mask is None else \
            {self.conf.network_inputs[0]:
             jnp.asarray(ds.features_mask, self.compute_dtype)}
        lmasks = None if ds.labels_mask is None else \
            {self.conf.network_outputs[0]:
             jnp.asarray(ds.labels_mask, self.compute_dtype)}
        return imasks, lmasks

    def score(self, ds) -> float:
        self._ensure_init()
        inputs = self._inputs_dict(ds.features)
        labels = self._labels_dict(ds.labels)
        imasks, lmasks = self._masks_of(ds)
        loss, _ = self._loss(self.params, self._inference_state(), inputs,
                             labels, None, label_masks=lmasks,
                             input_masks=imasks)
        return float(loss)

    def compute_gradient_and_score(self, ds):
        self._ensure_init()
        inputs = self._inputs_dict(ds.features)
        labels = self._labels_dict(ds.labels)
        imasks, lmasks = self._masks_of(ds)

        def lf(p):
            return self._loss(p, self._inference_state(), inputs, labels,
                              None, label_masks=lmasks, input_masks=imasks)
        (score, _), grads = jax.value_and_grad(lf, has_aux=True)(self.params)
        return grads, float(score)

    # ------------------------------------------------------------- pretrain
    def pretrain(self, data, num_epochs: int = 1):
        """Greedy layerwise unsupervised pretraining over every pretrainable
        layer vertex (AutoEncoder/RBM/VAE) in topological order (reference
        ComputationGraph.pretrain, ComputationGraph.java:540)."""
        self._ensure_init()
        for name in self.conf.topological_order:
            v = self.conf.vertices[name]
            if isinstance(v, LayerVertex) and \
                    hasattr(v.layer, "pretrain_loss"):
                self.pretrain_layer(name, data, num_epochs)
        return self

    def pretrain_layer(self, layer_name: str, data, num_epochs: int = 1):
        """Unsupervised pretraining of one named layer vertex (reference
        ComputationGraph.pretrainLayer, ComputationGraph.java:577): featurize
        the vertex's input through the graph (upstream vertices already
        pretrained, inference mode — XLA prunes every vertex the input does
        not depend on), then fit the layer's reconstruction/ELBO loss."""
        self._ensure_init()
        v = self.conf.vertices.get(layer_name)
        if v is None:
            raise ValueError(f"Unknown vertex '{layer_name}'")
        if not (isinstance(v, LayerVertex) and
                hasattr(v.layer, "pretrain_loss")):
            raise ValueError(
                f"Vertex '{layer_name}' is not pretrainable (needs an "
                "AutoEncoder/RBM/VariationalAutoencoder layer)")
        from ...datasets.iterators import as_iterator
        in_name = self.conf.vertex_inputs[layer_name][0]
        layer = v.layer
        upd = self.updaters[layer_name]
        lr = _nz(layer.learning_rate, 0.1)
        key = ("pretrain", layer_name)
        fn = self._jit_cache.get(key)
        if fn is None:
            def _ptrain(p, ustate, all_params, state, inputs, it):
                acts, *_ = self._forward(self._cast_params(all_params),
                                         state, inputs, train=False,
                                         rng=None)
                act = acts[in_name]
                if v.preprocessor is not None:
                    act = v.preprocessor.pre_process(act, None)
                rng = rngmod.for_iteration(
                    rngmod.for_purpose(rngmod.root_key(self.conf.seed),
                                       f"pretrain-{layer_name}"), it)
                loss, grads = jax.value_and_grad(
                    lambda q: layer.pretrain_loss(q, act, rng))(p)
                it_f = jnp.asarray(it, jnp.float32)
                newp, newu = {}, {}
                for pname, g in grads.items():
                    s, ns = upd.update(g, ustate[pname], lr, it_f)
                    newp[pname] = p[pname] - s
                    newu[pname] = ns
                return newp, newu, loss

            # layerwise pretrain is cold-path; donation unmeasured here
            fn = jax.jit(_ptrain)   # graftlint: disable=GL005
            self._jit_cache[key] = fn
        for _ in range(num_epochs):
            for ds in as_iterator(data):
                inputs = self._inputs_dict(ds.features)
                self.params[layer_name], self.updater_state[layer_name], \
                    loss = fn(self.params[layer_name],
                              self.updater_state[layer_name], self.params,
                              self._inference_state(), inputs,
                              self.iteration)
                self.score_value = float(loss)
                self.iteration += 1
        return self

    # ------------------------------------------------------ rnn / stateful
    def rnn_time_step(self, *features):
        """Stateful streaming inference (reference
        ComputationGraph.rnnTimeStep, ComputationGraph.java:2010): each
        input may be [N, nIn] (single step) or [N, T, nIn]; per-vertex
        hidden state persists between calls until
        rnn_clear_previous_state(). Returns a list of output arrays (one
        per network output), time-squeezed when inputs were single-step."""
        self._ensure_init()
        if len(features) == 1:
            inputs = self._inputs_dict(features[0])
        else:
            inputs = self._inputs_dict(list(features))
        # Only RECURRENT inputs get the single-step [N, nIn] -> [N, 1, nIn]
        # expansion; a genuinely-2D static input (e.g. feeding a
        # DuplicateToTimeSeriesVertex) stays 2D, and outputs are
        # time-squeezed only when a recurrent input was actually expanded.
        rec_names = set(self.conf.network_inputs)
        if self.conf.input_types is not None:
            rec_names = {n for n, t in zip(self.conf.network_inputs,
                                           self.conf.input_types)
                         if getattr(t, "kind", None) == "rnn"}
        squeeze = any(v.ndim == 2 for k, v in inputs.items()
                      if k in rec_names)
        inputs = {k: (v[:, None, :] if v.ndim == 2 and k in rec_names else v)
                  for k, v in inputs.items()}
        if self._rnn_state is None:
            self._rnn_state = {}
        state = {}
        for name in self.conf.topological_order:
            carry = self._rnn_state.get(name)
            if carry:
                state[name] = {**self.state[name], **carry}
            else:
                state[name] = {k: v for k, v in self.state[name].items()
                               if k not in ("h", "c")} \
                    if isinstance(self.state[name], dict) \
                    else self.state[name]
        # one jitted program — eager per-vertex dispatch pays one dispatch
        # per vertex per step; jax.jit keys on the state pytree
        # structure, so no-carry and carrying calls each get their trace
        fn = self._jit_cache.get("rnn_step")
        if fn is None:
            def _step(params, state, inputs):
                acts, new_state, *_ = self._forward(params, state, inputs,
                                                    train=False, rng=None)
                carries = {n: {k: v for k, v in ns.items()
                               if k in ("h", "c")}
                           for n, ns in new_state.items()
                           if isinstance(ns, dict)
                           and ("h" in ns or "c" in ns)}
                return [acts[o] for o in self.conf.network_outputs], carries

            # inference seam: params/state must survive the call
            fn = jax.jit(_step)   # graftlint: disable=GL005
            self._jit_cache["rnn_step"] = fn
        outs_dev, carries = fn(self.params, state, inputs)
        self._rnn_state.update(carries)
        outs = [np.asarray(o) for o in outs_dev]
        if squeeze:
            outs = [o[:, 0] if o.ndim == 3 else o for o in outs]
        return outs

    def rnn_clear_previous_state(self):
        """Reset streaming rnn state (reference rnnClearPreviousState,
        ComputationGraph.java:1999)."""
        self._rnn_state = None

    def _eval_batch_parts(self, ds):
        """(labels list, label-mask list) aligned with network_outputs, from
        a DataSet or MultiDataSet."""
        n_out = len(self.conf.network_outputs)
        if isinstance(ds, MultiDataSet):
            labels = list(ds.labels)
            lmasks = list(ds.labels_masks) if ds.labels_masks \
                else [None] * n_out
        else:
            labels = [ds.labels]
            lmasks = [ds.labels_mask]
        labels += [None] * (n_out - len(labels))
        lmasks += [None] * (n_out - len(lmasks))
        # materialize host-side HERE so the eval loop hands evaluators
        # plain numpy without any per-element sync of its own
        labels = [None if l is None else np.asarray(l) for l in labels]
        lmasks = [None if m is None else np.asarray(m) for m in lmasks]
        return labels, lmasks

    def do_evaluation(self, data, evaluations: Dict):
        """Accumulate per-output IEvaluation objects (Evaluation /
        RegressionEvaluation / ROC family) over a dataset iterator —
        ``{output_name: evaluation}``. One forward pass per batch feeds
        every output's evaluator. Reference ComputationGraph.doEvaluation
        (ComputationGraph.java:2531) throws for graphs with more than one
        output array; evaluating every head per pass is the TPU-era
        extension the multi-output vertex set deserves."""
        self._ensure_init()
        from ...datasets.iterators import as_iterator
        out_names = self.conf.network_outputs
        from ...ops.transfer import device_fetch
        for ds in as_iterator(data):
            outs = self.output(ds.features)
            labels, lmasks = self._eval_batch_parts(ds)
            # one audited fused readback per output head — the whole
            # [B, ...] array at once, never per-element syncs inside
            # the evaluator loop
            outs = [device_fetch(o, tag="graph.eval") for o in outs]
            for i, name in enumerate(out_names):
                ev = evaluations.get(name)
                if ev is None or labels[i] is None:
                    continue
                ev.eval(labels[i], outs[i], mask=lmasks[i])
        return evaluations

    def evaluate_outputs(self, data) -> Dict[str, object]:
        """Classification evaluation of EVERY output head →
        {output_name: Evaluation} (the multi-output path reference
        ComputationGraph.evaluate(MultiDataSetIterator) lacks)."""
        from ...eval.evaluation import Evaluation
        evs = {name: Evaluation() for name in self.conf.network_outputs}
        return self.do_evaluation(data, evs)

    def evaluate(self, data, labels_list=None, top_n: int = 1):
        """Single-head classification evaluation (reference
        ComputationGraph.evaluate(DataSetIterator/MultiDataSetIterator),
        ComputationGraph.java:2468-2529). Multi-output graphs evaluate
        output 0 against the first labels array; use evaluate_outputs()/
        do_evaluation() for every head."""
        from ...eval.evaluation import Evaluation
        first = self.conf.network_outputs[0]
        evs = self.do_evaluation(
            data, {first: Evaluation(labels=labels_list, top_n=top_n)})
        return evs[first]

    def evaluate_regression(self, data):
        """reference ComputationGraph.evaluateRegression (first head; use
        do_evaluation with a per-output dict for more)."""
        from ...eval.regression import RegressionEvaluation
        first = self.conf.network_outputs[0]
        return self.do_evaluation(
            data, {first: RegressionEvaluation()})[first]

    def evaluate_roc(self, data, threshold_steps: int = 0):
        """reference ComputationGraph.evaluateROC."""
        from ...eval.roc import ROC
        first = self.conf.network_outputs[0]
        return self.do_evaluation(data, {first: ROC(threshold_steps)})[first]

    def evaluate_roc_multi_class(self, data, threshold_steps: int = 0):
        """reference ComputationGraph.evaluateROCMultiClass."""
        from ...eval.roc import ROCMultiClass
        first = self.conf.network_outputs[0]
        return self.do_evaluation(
            data, {first: ROCMultiClass(threshold_steps)})[first]

    def summary(self) -> str:
        """Printable vertex table (reference ComputationGraph.summary())."""
        self._ensure_init()
        rows = [("vertex", "type", "inputs", "params")]
        total = 0
        for name in self.conf.topological_order:
            v = self.conf.vertices[name]
            n = sum(int(np.prod(p.shape))
                    for p in self.params[name].values())
            total += n
            vtype = type(v.layer).__name__ if isinstance(v, LayerVertex) \
                else type(v).__name__
            rows.append((name, vtype,
                         ",".join(self.conf.vertex_inputs[name]), f"{n:,}"))
        from ..multilayer import format_summary_table
        return format_summary_table(rows, total)

    # ----------------------------------------------------------- param utils
    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def num_params(self) -> int:
        self._ensure_init()
        return sum(int(np.prod(v.shape)) for p in self.params.values()
                   for v in p.values())

    def params_flat(self) -> np.ndarray:
        self._ensure_init()
        parts = []
        for name in self.conf.topological_order:
            p = self.params[name]
            for k in sorted(p.keys()):
                parts.append(np.asarray(p[k]).reshape(-1))
        return np.concatenate(parts) if parts else np.zeros(0, np.float32)

    def set_params_flat(self, flat: np.ndarray):
        self._ensure_init()
        offset = 0
        for name in self.conf.topological_order:
            p = self.params[name]
            for k in sorted(p.keys()):
                size = int(np.prod(p[k].shape))
                self.params[name][k] = jnp.asarray(
                    flat[offset:offset + size].reshape(p[k].shape), p[k].dtype)
                offset += size

    def clone(self) -> "ComputationGraph":
        import copy as _copy
        net = ComputationGraph(_copy.deepcopy(self.conf), self.compute_dtype)
        net.init()
        # fresh buffers: the jitted train step donates params/updater/state,
        # so sharing arrays would let a fit() on either net delete the
        # other's (see MultiLayerNetwork.clone)
        net.params = jax.tree_util.tree_map(jnp.copy, self.params)
        net.state = jax.tree_util.tree_map(jnp.copy, self.state)
        net.updater_state = jax.tree_util.tree_map(jnp.copy,
                                                   self.updater_state)
        net.iteration = self.iteration
        return net
