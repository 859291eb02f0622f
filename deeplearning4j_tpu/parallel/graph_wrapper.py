"""Synchronous data-parallel trainer for ComputationGraph (the CG face of
ParallelWrapper; reference ParallelWrapper accepts Model = MLN or CG).

Batch sharded over the mesh ``data`` axis, params replicated; XLA/GSPMD
inserts the gradient all-reduce over ICI."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..nn.helpers import attention_spmd
from ..ops.dataset import DataSet, MultiDataSet
from .mesh import make_mesh


class GraphDataParallelTrainer:
    def __init__(self, net, mesh: Optional[Mesh] = None):
        self.net = net
        self.mesh = mesh if mesh is not None else make_mesh()
        self._jit_step = None

    @property
    def num_workers(self) -> int:
        return int(np.prod(list(self.mesh.shape.values())))

    def _build(self):
        net = self.net
        mesh = self.mesh
        step = net._make_train_step()
        rep = NamedSharding(mesh, P())
        data = NamedSharding(mesh, P("data"))

        def wrapped(params, upd, state, inputs, labels, imasks, lmasks,
                    iteration):
            # tracers carry no sharding: tell the attention kernels which
            # mesh this jit partitions over (Mosaic needs a shard_map)
            with attention_spmd(mesh, "data"):
                return step(params, upd, state, inputs, labels, imasks,
                            lmasks, iteration, {})

        self._jit_step = jax.jit(
            wrapped,
            in_shardings=(rep, rep, rep, data, data, data, data, None),
            out_shardings=(rep, rep, rep, rep),
            donate_argnums=(0, 1, 2))

    def fit_batch(self, ds: DataSet):
        net = self.net
        net._ensure_init()
        if self._jit_step is None:
            self._build()
        n = ds.num_examples()
        n_dev = self.num_workers
        multi = isinstance(ds, MultiDataSet)
        feats = list(ds.features) if multi else [ds.features]
        labels = list(ds.labels) if multi else [ds.labels]
        fmasks = list(ds.features_masks or [None] * len(feats)) if multi \
            else [ds.features_mask]
        lmasks = list(ds.labels_masks or [None] * len(labels)) if multi \
            else [ds.labels_mask]
        if n % n_dev:
            # pad to an even device split with repeated rows that carry ZERO
            # loss weight (labels mask) — repeating without the mask would
            # double-weight those examples (see ParallelWrapper
            # ._pad_to_devices; reference round-robins real examples,
            # ParallelWrapper.java:333)
            pad = n_dev - n % n_dev
            idx = np.concatenate([np.arange(n), np.arange(pad) % n])
            take = lambda a: None if a is None else np.asarray(a)[idx]
            feats = [take(f) for f in feats]
            fmasks = [take(m) for m in fmasks]
            padded_l, padded_m = [], []
            for lab, m in zip(labels, lmasks):
                if m is None and lab is not None:
                    m = np.ones(np.shape(lab)[:2] if np.ndim(lab) == 3
                                else (n,), np.float32)
                lab, m = take(lab), take(m)
                if m is not None:
                    m = np.asarray(m, np.float32).copy()
                    m[n:] = 0.0
                padded_l.append(lab)
                padded_m.append(m)
            labels, lmasks = padded_l, padded_m
        inputs = net._inputs_dict(feats)
        label_d = net._labels_dict(labels)
        imask_d = None
        if any(m is not None for m in fmasks):
            imask_d = {nm: None if m is None else jnp.asarray(m, jnp.float32)
                       for nm, m in zip(net.conf.network_inputs, fmasks)}
        lmask_d = None
        if any(m is not None for m in lmasks):
            lmask_d = {nm: None if m is None else jnp.asarray(m, jnp.float32)
                       for nm, m in zip(net.conf.network_outputs, lmasks)}
        net.params, net.updater_state, new_states, score = self._jit_step(
            net.params, net.updater_state, net.state, inputs, label_d,
            imask_d, lmask_d, net.iteration)
        net.state = net._strip_rnn_carry(new_states)
        net.score_value = float(score)
        net.iteration += 1
        for lst in net.listeners:
            lst.iteration_done(net, net.iteration)

    def fit(self, data, num_epochs: int = 1):
        from ..datasets.iterators import as_iterator, AsyncDataSetIterator
        for _ in range(num_epochs):
            it = as_iterator(data)
            if getattr(it, "async_supported", True):
                it = AsyncDataSetIterator(it)
            for ds in it:
                self.fit_batch(ds)
            self.net.epoch += 1
        return self
