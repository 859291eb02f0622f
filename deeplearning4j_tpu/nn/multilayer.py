"""MultiLayerNetwork: the sequential-stack model (reference
nn/multilayer/MultiLayerNetwork.java, 2,715 LoC; fit loop :982, backprop
:1072, TBPTT :1194, rnnTimeStep stateful inference; SURVEY.md §2.1, §3.1).

TPU-first inversion of the reference architecture (SURVEY.md §7):

- the flattened-params buffer with per-layer views (MultiLayerNetwork.java:447)
  becomes a pytree ``[ {param_name: jnp.ndarray}, ... ]`` with
  ``params_flat()`` providing the flattened view for serializer parity;
- the mutable solver/updater/step (StochasticGradientDescent.java:53-75)
  becomes one pure jitted ``train_step``: value_and_grad over the whole stack
  → per-layer gradient normalization → per-layer updater → params - step.
  XLA fuses the lot; buffer donation replaces ND4J workspaces;
- per-iteration dropout keys are folded from (seed, iteration, layer) — no
  global RNG;
- BN running stats / RNN carry live in an explicit ``state`` pytree threaded
  through the step (TBPTT carries it across time windows, rnnTimeStep across
  calls).

The train step is compiled once per (batch-shape, dtype); AsyncDataSetIterator
(datasets/iterators.py) overlaps host→device transfer with compute.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.fused_ce import (fused_sparse_ce_score,
                                sparse_labels_eligible)
from ..ops import rng as rngmod
from ..ops.dataset import DataSet
from ..ops.updaters import make_updater, normalize_gradient, schedule_lr
from .conf.config import MultiLayerConfiguration
from .conf.layers.feedforward import (OutputLayer, LossLayer,
                                      CenterLossOutputLayer)
from .conf.layers.recurrent import BaseRecurrentLayerConf


def _nz(value, default):
    """None-aware default (0.0 is a real value — e.g. frozen-layer lr)."""
    return default if value is None else value


def format_summary_table(rows, total: int) -> str:
    """Shared summary() renderer: header+rows -> aligned table + footer."""
    widths = [max(len(r[c]) for r in rows) for c in range(len(rows[0]))]
    lines = ["  ".join(v.ljust(w) for v, w in zip(r, widths))
             for r in rows]
    lines.append(f"Total params: {total:,}")
    return "\n".join(lines)


def _as_device_dtype(a, dtype):
    """dtype for floats; integer arrays (embedding token ids) keep their
    dtype — a bf16 round-trip corrupts ids >= 257."""
    a = jnp.asarray(a)
    if jnp.issubdtype(a.dtype, jnp.integer) or \
            jnp.issubdtype(a.dtype, jnp.bool_):
        return a
    return a.astype(dtype)


def _as_jnp_batch(ds: DataSet, dtype):
    feats = _as_device_dtype(ds.features, dtype)
    labels = _as_device_dtype(ds.labels, dtype) \
        if ds.labels is not None else None
    fmask = jnp.asarray(ds.features_mask, dtype) \
        if ds.features_mask is not None else None
    lmask = jnp.asarray(ds.labels_mask, dtype) \
        if ds.labels_mask is not None else None
    return feats, labels, fmask, lmask


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration, compute_dtype=None):
        self.conf = conf
        self.layers = conf.layers
        self.compute_dtype = compute_dtype or jnp.float32
        self.params: List[Dict] = []
        self.state: List[Dict] = []
        self.updaters = []
        self.updater_state: List[Dict] = []
        self.iteration = 0
        self.epoch = 0
        self.listeners: List = []
        self.score_value = float("nan")
        self._rnn_state: Optional[List[Dict]] = None
        self._jit_cache: Dict = {}
        self._initialized = False

    # ------------------------------------------------------------------ init
    def init(self, params: Optional[List[Dict]] = None) -> "MultiLayerNetwork":
        key = rngmod.root_key(self.conf.seed)
        self.params = []
        self.state = []
        self.updaters = []
        self.updater_state = []
        # master params live in f32 (f64 only for gradient checks):
        # under bf16 compute, _cast_params casts INSIDE the step and the
        # update applies to the full-precision master copy
        storage_dtype = jnp.float64 if self.compute_dtype == jnp.float64 \
            else jnp.float32
        for i, layer in enumerate(self.layers):
            lkey = rngmod.for_layer(rngmod.for_purpose(key, "init"), i)
            p = layer.init_params(lkey, storage_dtype) \
                if params is None else params[i]
            self.params.append(p)
            self.state.append(layer.init_state())
            upd = make_updater(
                layer.updater or "sgd",
                momentum=_nz(layer.momentum, 0.9),
                adam_mean_decay=_nz(layer.adam_mean_decay, 0.9),
                adam_var_decay=_nz(layer.adam_var_decay, 0.999),
                rho=_nz(layer.rho, 0.95),
                rms_decay=_nz(layer.rms_decay, 0.95),
                epsilon=_nz(layer.epsilon, 1e-8))
            self.updaters.append(upd)
            self.updater_state.append({k: upd.init(v) for k, v in p.items()})
        self._initialized = True
        return self

    def _ensure_init(self):
        if not self._initialized:
            self.init()

    # ------------------------------------------------------- forward passes
    def _forward(self, params, state, x, *, train, rng, fmask=None,
                 to_layer=None, initial_rnn=None, last_preoutput=False,
                 skip_last_preoutput=False):
        """Run the stack. Returns (activation, new_state_list, reg_penalty).
        ``initial_rnn``: optional list of per-layer rnn carries (TBPTT).
        ``last_preoutput``: stop before the output layer's activation/loss so
        the caller can apply the fused loss (stable log-softmax path).
        ``skip_last_preoutput``: additionally skip the output projection
        itself — it runs INSIDE the fused sparse-CE loss
        (kernels/fused_ce.py), so the [.., n_out] pre-activation is never
        built."""
        new_states = []
        reg = jnp.asarray(0.0, jnp.float32)
        act = x
        mask = fmask
        n_layers = len(self.layers) if to_layer is None else to_layer
        for i in range(n_layers):
            layer = self.layers[i]
            pp = self.conf.preprocessor_for(i)
            if pp is not None:
                act = pp.pre_process(act, mask)
                mask = pp.feed_forward_mask(mask)
            lrng = None
            if rng is not None:
                lrng = rngmod.for_layer(rng, i)
            lstate = state[i]
            if initial_rnn is not None and initial_rnn[i]:
                lstate = initial_rnn[i]
            is_last = (i == n_layers - 1)
            if last_preoutput and is_last and hasattr(layer, "preoutput"):
                if layer.drop_out and train:
                    act = layer.maybe_dropout(act, train=train, rng=lrng)
                new_states.append(lstate)
                reg = reg + layer.reg_penalty(params[i])
                if skip_last_preoutput:
                    return None, new_states, reg, act, mask
                pre = layer.preoutput(params[i], act)
                return pre, new_states, reg, act, mask
            act, nstate = layer.forward(params[i], lstate, act, train=train,
                                        rng=lrng, mask=mask)
            new_states.append(nstate)
            reg = reg + layer.reg_penalty(params[i])
        if last_preoutput:
            # no preoutput-capable head (e.g. ends mid-stack)
            return act, new_states, reg, act, mask
        return act, new_states, reg

    def _inference_state(self):
        """State with the transient rnn carry ('h'/'c') removed: like the
        reference, output/score/evaluate are STATELESS — only rnnTimeStep
        continues from stored state. BatchNorm running stats etc. remain."""
        return [{k: v for k, v in s.items() if k not in ("h", "c")}
                if isinstance(s, dict) else s for s in self.state]

    def output(self, x, train: bool = False) -> np.ndarray:
        """Full forward pass (reference MultiLayerNetwork.output)."""
        self._ensure_init()
        x = _as_device_dtype(x, self.compute_dtype)
        fn = self._jit_cache.get("output")
        if fn is None:
            def _out(params, state, x):
                y, _, _ = self._forward(params, state, x, train=False, rng=None)
                return y
            # inference seam: donating would free params/state the next
            # call still needs (GL005 siblings donate TRAIN-step buffers)
            fn = jax.jit(_out)   # graftlint: disable=GL005
            self._jit_cache["output"] = fn
        return np.asarray(fn(self.params, self._inference_state(), x))

    def feed_forward(self, x, train: bool = False) -> List[np.ndarray]:
        """Per-layer activations (reference feedForward)."""
        self._ensure_init()
        act = _as_device_dtype(x, self.compute_dtype)
        outs = [np.asarray(act)]
        mask = None
        inf_state = self._inference_state()
        for i, layer in enumerate(self.layers):
            pp = self.conf.preprocessor_for(i)
            if pp is not None:
                act = pp.pre_process(act, mask)
            act, _ = layer.forward(self.params[i], inf_state[i], act,
                                   train=train, rng=None, mask=mask)
            # per-layer host materialization IS the contract here: the
            # reference feedForward returns host activations per layer
            outs.append(np.asarray(act))   # graftlint: disable=GL007
        return outs

    # ------------------------------------------------------------- training
    def _output_layer(self):
        last = self.layers[-1]
        if not hasattr(last, "compute_score"):
            raise ValueError("Last layer has no loss (need Output/Loss layer)")
        return last

    def _cast_params(self, params):
        """Mixed precision: when compute_dtype is low-precision (bf16), cast
        f32 master params to it for the forward/backward; autodiff through the
        cast delivers f32 gradients to the f32 master copy — the TPU-idiomatic
        replacement for the reference's fp16 HalfIndexer path
        (CudnnConvolutionHelper fp16, SURVEY.md §2.2)."""
        cd = self.compute_dtype
        if cd == jnp.float32 or cd == jnp.float64:
            return params
        return jax.tree_util.tree_map(
            lambda a: a.astype(cd) if a.dtype == jnp.float32 else a, params)

    def _loss_fn(self, params, state, feats, labels, fmask, lmask, rng,
                 initial_rnn=None):
        params = self._cast_params(params)
        out_layer = self._output_layer()
        fused = sparse_labels_eligible(out_layer, labels, params[-1])
        pre, new_states, reg, last_in, out_mask = self._forward(
            params, state, feats, train=True, rng=rng, fmask=fmask,
            initial_rnn=initial_rnn, last_preoutput=True,
            skip_last_preoutput=fused)
        if fused:
            mask = lmask if lmask is not None else \
                (out_mask if last_in.ndim == 3 else None)
            score = fused_sparse_ce_score(params[-1], last_in, labels, mask)
        else:
            from ..kernels.fused_ce import _MCXENT_LOSSES, sparse_shaped
            if sparse_shaped(out_layer, labels) and \
                    str(getattr(out_layer, "loss", "")).lower() in \
                    _MCXENT_LOSSES:
                raise ValueError(
                    "the output layer got integer class-id labels but is "
                    "not fused-CE eligible (sparse labels need a plain "
                    "softmax Output/RnnOutput head; center-loss heads "
                    "need one-hot labels). Pass one-hot labels here.")
            mask = lmask if lmask is not None else \
                (out_mask if pre.ndim == 3 else None)
            score = out_layer.compute_score(params[-1], labels, pre, mask)
        aux_state = new_states
        if isinstance(out_layer, CenterLossOutputLayer):
            closs, new_center_state = out_layer.center_loss_and_update(
                state[-1], last_in, labels)
            score = score + closs
            aux_state = new_states[:-1] + [new_center_state]
        return score + reg, aux_state

    def _make_train_step(self, with_rnn_carry: bool):
        conf = self.conf

        def train_step(params, upd_state, state, feats, labels, fmask, lmask,
                       iteration, initial_rnn):
            rng = rngmod.for_iteration(
                rngmod.for_purpose(rngmod.root_key(conf.seed), "dropout"),
                iteration)

            def lf(p):
                return self._loss_fn(p, state, feats, labels, fmask, lmask,
                                     rng, initial_rnn if with_rnn_carry else None)

            (score, new_states), grads = jax.value_and_grad(
                lf, has_aux=True)(params)

            new_params = []
            new_upd_states = []
            it_f = jnp.asarray(iteration, jnp.float32)
            for i, layer in enumerate(self.layers):
                g = grads[i]
                if not g:
                    new_params.append(params[i])
                    new_upd_states.append(upd_state[i])
                    continue
                g = normalize_gradient(
                    g, layer.gradient_normalization,
                    _nz(layer.gradient_normalization_threshold, 1.0))
                lr = schedule_lr(
                    _nz(layer.learning_rate, 0.1), conf.lr_policy, it_f,
                    decay_rate=conf.lr_policy_decay_rate,
                    steps=conf.lr_policy_steps, power=conf.lr_policy_power,
                    max_iterations=float(conf.max_iterations or 1),
                    schedule=conf.learning_rate_schedule)
                upd = self.updaters[i]
                np_, nu = {}, {}
                for name, grad in g.items():
                    use_lr = lr
                    if name in ("b", "vb", "mub", "ob") and \
                            layer.bias_learning_rate is not None:
                        use_lr = schedule_lr(
                            layer.bias_learning_rate, conf.lr_policy, it_f,
                            decay_rate=conf.lr_policy_decay_rate,
                            steps=conf.lr_policy_steps,
                            power=conf.lr_policy_power,
                            max_iterations=float(conf.max_iterations or 1),
                            schedule=conf.learning_rate_schedule)
                    step, nstate = upd.update(grad, upd_state[i][name],
                                              use_lr, it_f)
                    np_[name] = params[i][name] - step
                    nu[name] = nstate
                new_params.append(np_)
                new_upd_states.append(nu)
            return new_params, new_upd_states, new_states, score

        return train_step

    def _get_train_step(self, with_rnn_carry: bool = False):
        key = ("train", with_rnn_carry)
        if key not in self._jit_cache:
            from ..ops.platform import train_donate_argnums
            self._jit_cache[key] = jax.jit(
                self._make_train_step(with_rnn_carry),
                donate_argnums=train_donate_argnums())
        return self._jit_cache[key]

    def fit(self, data, labels=None, num_epochs: int = 1):
        """Train (reference MultiLayerNetwork.fit(DataSetIterator) and
        fit(INDArray, INDArray), MultiLayerNetwork.java:1474).
        ``data``: DataSet, DataSetIterator, list of DataSets — or a
        features array with ``labels`` supplied separately."""
        self._ensure_init()
        if isinstance(labels, (int, np.integer)):
            # old positional form fit(data, num_epochs)
            num_epochs, labels = int(labels), None
        if labels is not None:
            data = DataSet(np.asarray(data), np.asarray(labels))
        from ..datasets.iterators import as_iterator, AsyncDataSetIterator
        for epoch in range(num_epochs):
            for lst in self.listeners:
                if hasattr(lst, "on_epoch_start"):
                    lst.on_epoch_start(self)
            it = as_iterator(data)
            if getattr(it, "async_supported", True):
                it = AsyncDataSetIterator(it)
            for ds in it:
                if self.conf.pretrain:
                    raise ValueError("conf.pretrain=True: call pretrain(data)")
                if self.conf.backprop_type == "truncated_bptt" and \
                        ds.features.ndim == 3 and \
                        (self.conf.tbptt_fwd_length or 0) > 0:
                    self._fit_tbptt(ds)
                else:
                    self._fit_batch(ds)
            self.epoch += 1
            for lst in self.listeners:
                if hasattr(lst, "on_epoch_end"):
                    lst.on_epoch_end(self)
        return self

    @staticmethod
    def _strip_rnn_carry(states):
        """Drop transient rnn h/c from a state list before storing: each
        minibatch starts from zero rnn state (reference fit semantics; the
        carry would also break retrace on a batch-size change). BatchNorm
        running stats etc. are kept. TBPTT threads its carry explicitly."""
        return [{k: v for k, v in s.items() if k not in ("h", "c")}
                if isinstance(s, dict) else s for s in states]

    def _fit_batch(self, ds: DataSet):
        self.last_input_batch = ds    # probe data for flow/debug listeners
        feats, labels, fmask, lmask = _as_jnp_batch(ds, self.compute_dtype)
        step = self._get_train_step(False)
        empty_rnn = [{} for _ in self.layers]
        self.params, self.updater_state, new_states, score = step(
            self.params, self.updater_state, self.state, feats, labels,
            fmask, lmask, self.iteration, empty_rnn)
        self.state = self._strip_rnn_carry(new_states)
        self.score_value = score  # device scalar; sync deferred to reader
        self.iteration += 1
        for lst in self.listeners:
            lst.iteration_done(self, self.iteration)

    def _fit_tbptt(self, ds: DataSet):
        """Truncated BPTT (reference doTruncatedBPTT,
        MultiLayerNetwork.java:1194): slide a window of tbptt_fwd_length over
        time, carrying RNN state across windows within the minibatch."""
        t_total = ds.features.shape[1]
        window = self.conf.tbptt_fwd_length
        step = self._get_train_step(True)
        carry = [dict() for _ in self.layers]
        for start in range(0, t_total, window):
            end = min(start + window, t_total)
            feats = jnp.asarray(ds.features[:, start:end], self.compute_dtype)
            # _as_device_dtype: integer (sparse-CE) labels keep their dtype
            labels = _as_device_dtype(ds.labels[:, start:end],
                                      self.compute_dtype)
            fmask = None if ds.features_mask is None else \
                jnp.asarray(ds.features_mask[:, start:end], self.compute_dtype)
            lmask = None if ds.labels_mask is None else \
                jnp.asarray(ds.labels_mask[:, start:end], self.compute_dtype)
            self.params, self.updater_state, new_states, score = step(
                self.params, self.updater_state, self.state, feats, labels,
                fmask, lmask, self.iteration, carry)
            # carry only RNN h/c into the next window (detached by design)
            carry = [
                {k: v for k, v in st.items() if k in ("h", "c")}
                if isinstance(self.layers[i], BaseRecurrentLayerConf) else {}
                for i, st in enumerate(new_states)]
            self.state = self._strip_rnn_carry(new_states)
            self.score_value = score  # device scalar; sync deferred to reader
            self.iteration += 1
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration)

    # ------------------------------------------------------------- pretrain
    def pretrain(self, data, num_epochs: int = 1):
        """Greedy layerwise unsupervised pretraining (reference
        MultiLayerNetwork.pretrain: AutoEncoder/RBM/VAE layers)."""
        self._ensure_init()
        from ..datasets.iterators import as_iterator
        for li, layer in enumerate(self.layers):
            if not hasattr(layer, "pretrain_loss"):
                continue
            lr = _nz(layer.learning_rate, 0.1)
            upd = self.updaters[li]

            @jax.jit
            def ptrain(p, ustate, feats, it, _li=li, _layer=layer, _upd=upd):
                # featurize through the already-pretrained sub-stack
                act = feats
                for j in range(_li):
                    pp = self.conf.preprocessor_for(j)
                    if pp is not None:
                        act = pp.pre_process(act)
                    act, _ = self.layers[j].forward(self.params[j],
                                                    self.state[j], act,
                                                    train=False, rng=None)
                rng = rngmod.for_iteration(
                    rngmod.for_purpose(rngmod.root_key(self.conf.seed),
                                       f"pretrain{_li}"), it)
                loss, grads = jax.value_and_grad(
                    lambda pp_: _layer.pretrain_loss(pp_, act, rng))(p)
                newp, newu = {}, {}
                for name, g in grads.items():
                    s, ns = _upd.update(g, ustate[name], lr,
                                        jnp.asarray(it, jnp.float32))
                    newp[name] = p[name] - s
                    newu[name] = ns
                return newp, newu, loss

            for epoch in range(num_epochs):
                it = as_iterator(data)
                for ds in it:
                    feats = jnp.asarray(ds.features, self.compute_dtype)
                    self.params[li], self.updater_state[li], loss = ptrain(
                        self.params[li], self.updater_state[li], feats,
                        self.iteration)
                    self.score_value = float(loss)
                    self.iteration += 1
        return self

    # ------------------------------------------------------------ scoring
    def score(self, ds: DataSet, training: bool = False) -> float:
        """Loss on a dataset (reference MultiLayerNetwork.score(DataSet))."""
        self._ensure_init()
        feats, labels, fmask, lmask = _as_jnp_batch(ds, self.compute_dtype)
        loss, _ = self._loss_fn(self.params, self._inference_state(), feats,
                                labels, fmask, lmask, None)
        return float(loss)

    def compute_gradient_and_score(self, ds: DataSet):
        """(gradients, score) without updating — GradientCheckUtil's entry."""
        self._ensure_init()
        feats, labels, fmask, lmask = _as_jnp_batch(ds, self.compute_dtype)

        def lf(p):
            return self._loss_fn(p, self._inference_state(), feats, labels,
                                 fmask, lmask, None)
        (score, _), grads = jax.value_and_grad(lf, has_aux=True)(self.params)
        return grads, float(score)

    def predict(self, x) -> np.ndarray:
        """Argmax class per example (reference MultiLayerNetwork.predict,
        MultiLayerNetwork.java:1423); time-series outputs predict per
        step."""
        return np.argmax(self.output(x), axis=-1)

    def evaluate(self, data, batch_size: int = 0):
        from ..eval.evaluation import Evaluation
        return self.do_evaluation(data, Evaluation())

    def do_evaluation(self, data, evaluation):
        """Accumulate any IEvaluation (Evaluation / RegressionEvaluation /
        ROC family) over the data (reference doEvaluation)."""
        from ..datasets.iterators import as_iterator
        for ds in as_iterator(data):
            out = self.output(ds.features)
            # eval accumulators are host-side numpy by design; one sync
            # per dataset batch, not per step — not a decode-loop hazard
            # graftlint: disable=GL007
            evaluation.eval(np.asarray(ds.labels), np.asarray(out),
                            mask=None if ds.labels_mask is None
                            else np.asarray(ds.labels_mask))
        return evaluation

    def evaluate_regression(self, data):
        """reference MultiLayerNetwork.evaluateRegression."""
        from ..eval.regression import RegressionEvaluation
        return self.do_evaluation(data, RegressionEvaluation())

    def evaluate_roc(self, data, threshold_steps: int = 0):
        """reference evaluateROC (binary ROC on a 2-class/1-unit output)."""
        from ..eval.roc import ROC
        return self.do_evaluation(data, ROC(threshold_steps))

    def evaluate_roc_multi_class(self, data, threshold_steps: int = 0):
        """reference evaluateROCMultiClass (one-vs-all per class)."""
        from ..eval.roc import ROCMultiClass
        return self.do_evaluation(data, ROCMultiClass(threshold_steps))

    def score_examples(self, ds: DataSet,
                       add_regularization: bool = False) -> np.ndarray:
        """Per-example loss [N] (reference scoreExamples: the score each
        example contributes, optionally with the l1/l2 penalty added)."""
        self._ensure_init()
        from ..ops.losses import get_loss
        feats, labels, fmask, lmask = _as_jnp_batch(ds, self.compute_dtype)
        out_layer = self._output_layer()
        fn = self._jit_cache.get("score_examples")
        if fn is None:
            def _scores(params, state, feats, labels, fmask, lmask):
                params = self._cast_params(params)
                pre, _, reg, _, out_mask = self._forward(
                    params, state, feats, train=False, rng=None,
                    fmask=fmask, last_preoutput=True)
                mask = lmask if lmask is not None else \
                    (out_mask if pre.ndim == 3 else None)
                per = get_loss(out_layer.loss)(
                    labels, pre, out_layer.activation or "identity", mask)
                return per, reg
            # inference seam: params/state must survive the call
            fn = jax.jit(_scores)   # graftlint: disable=GL005
            self._jit_cache["score_examples"] = fn
        per, reg = fn(self.params, self._inference_state(), feats, labels,
                      fmask, lmask)
        per = np.asarray(per, np.float64)
        if add_regularization:
            per = per + float(reg)
        return per

    def summary(self) -> str:
        """Printable layer table (reference MultiLayerNetwork.summary())."""
        self._ensure_init()
        rows = [("idx", "layer", "nIn", "nOut", "params")]
        total = 0
        for i, layer in enumerate(self.layers):
            n = sum(int(np.prod(v.shape)) for v in self.params[i].values())
            total += n
            rows.append((str(i), type(layer).__name__,
                         str(getattr(layer, "n_in", "") or ""),
                         str(getattr(layer, "n_out", "") or ""), f"{n:,}"))
        return format_summary_table(rows, total)

    # ------------------------------------------------------ rnn / stateful
    def rnn_time_step(self, x) -> np.ndarray:
        """Stateful streaming inference (reference rnnTimeStep): x may be
        [N, nIn] (single step) or [N, T, nIn]; hidden state persists between
        calls until rnn_clear_previous_state(). The whole stack runs as ONE
        jitted program per call — eager per-op dispatch pays one dispatch
        per op per step (measured r2: 2.36 s/step unjitted vs one dispatch
        jitted; serving loops live on this)."""
        self._ensure_init()
        x = _as_device_dtype(x, self.compute_dtype)
        squeeze = x.ndim == 2
        if squeeze:
            x = x[:, None, :]
        if self._rnn_state is None:
            self._rnn_state = [dict() for _ in self.layers]
        # jax.jit keys on the argument pytree structure itself, so the
        # first (no-carry) call and later (h/c-carrying) calls each get
        # their own trace from ONE cached jit
        fn = self._jit_cache.get("rnn_step")
        if fn is None:
            def _step(params, states, rnn_states, act):
                new_rnn = []
                for i, layer in enumerate(self.layers):
                    pp = self.conf.preprocessor_for(i)
                    if pp is not None:
                        act = pp.pre_process(act)
                    lstate = rnn_states[i] if rnn_states[i] else states[i]
                    act, nstate = layer.forward(params[i], lstate, act,
                                                train=False, rng=None)
                    new_rnn.append(
                        {k: v for k, v in nstate.items() if k in ("h", "c")}
                        if isinstance(layer, BaseRecurrentLayerConf) else {})
                return act, new_rnn

            # inference seam: params/state must survive the call
            fn = jax.jit(_step)   # graftlint: disable=GL005
            self._jit_cache["rnn_step"] = fn
        act, self._rnn_state = fn(self.params, self._inference_state(),
                                  self._rnn_state, x)
        out = np.asarray(act)
        return out[:, 0] if squeeze and out.ndim == 3 else out

    def rnn_clear_previous_state(self):
        self._rnn_state = None

    # --------------------------------------------------------- param access
    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def num_params(self) -> int:
        self._ensure_init()
        return sum(int(np.prod(v.shape)) for p in self.params
                   for v in p.values())

    def param_table(self) -> Dict[str, np.ndarray]:
        """Flat name → array view, names like ``0_W`` (reference paramTable)."""
        self._ensure_init()
        return {f"{i}_{k}": np.asarray(v) for i, p in enumerate(self.params)
                for k, v in sorted(p.items())}

    def params_flat(self) -> np.ndarray:
        """Single flattened parameter vector in deterministic order
        (layer asc, param name asc) — the ``coefficients.bin`` analog."""
        self._ensure_init()
        parts = [np.asarray(v).reshape(-1) for i, p in enumerate(self.params)
                 for k, v in sorted(p.items())]
        if not parts:
            return np.zeros((0,), np.float32)
        return np.concatenate(parts)

    def set_params_flat(self, flat: np.ndarray):
        self._ensure_init()
        offset = 0
        for i, p in enumerate(self.params):
            for k in sorted(p.keys()):
                size = int(np.prod(p[k].shape))
                self.params[i][k] = jnp.asarray(
                    flat[offset:offset + size].reshape(p[k].shape),
                    p[k].dtype)
                offset += size

    def clone(self) -> "MultiLayerNetwork":
        import copy as _copy
        net = MultiLayerNetwork(_copy.deepcopy(self.conf), self.compute_dtype)
        net.init()
        # materialize fresh device buffers: the jitted train step DONATES
        # params/updater/state, so sharing buffers with the clone would let
        # a fit() on either net delete the other's arrays
        net.params = jax.tree_util.tree_map(jnp.copy, self.params)
        net.state = jax.tree_util.tree_map(jnp.copy, self.state)
        net.updater_state = jax.tree_util.tree_map(jnp.copy,
                                                   self.updater_state)
        net.iteration = self.iteration
        return net
