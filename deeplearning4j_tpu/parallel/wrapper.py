"""ParallelWrapper: data-parallel training over a device mesh (reference
parallelism/ParallelWrapper.java, 662 LoC; SURVEY.md §2.4, §3.3).

The reference spawns one trainer thread + model replica per device,
round-robins DataSets into per-worker queues, and every
``averaging_frequency`` iterations averages parameters across replicas with
``Nd4j.averageAndPropagate`` (and optionally updater state, ``averageUpdaters``).

TPU-first redesign (SURVEY.md §7): one SPMD program instead of threads.

- ``averaging_frequency == 1`` (synchronous DP): the global batch is sharded
  over the mesh's ``data`` axis and params are replicated; XLA/GSPMD inserts
  the gradient all-reduce over ICI — the collective the reference stages
  through host memory.
- ``averaging_frequency == k > 1`` (the reference's actual semantics): each
  device keeps its OWN diverged replica (params stacked on a leading device
  axis) and runs k local steps via ``lax.scan``; then params (+ updater state,
  matching ``averageUpdaters(true)``) are ``pmean``-ed across the mesh inside
  ``shard_map`` — local-steps/periodic-averaging DP, one compiled program per
  round, no host round-trips.

Multi-host: the same program runs under ``jax.distributed`` initialization
(see multihost.py); the mesh then spans hosts and XLA routes the same
collectives over ICI within a slice and DCN across slices.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.dataset import DataSet
from .mesh import make_mesh


class ParallelWrapper:
    """Builder-style API mirroring the reference:

        ParallelWrapper.Builder(net).workers(8).averaging_frequency(5)
            .average_updaters(True).build().fit(iterator)
    """

    _ns_counter = 0      # cross-process-consistent KV namespace source

    def __init__(self, net, mesh: Optional[Mesh] = None,
                 averaging_frequency: int = 1, average_updaters: bool = True,
                 prefetch_buffer: int = 2, report_score: bool = True,
                 gradient_compression: Optional[float] = None):
        self.net = net
        self.mesh = mesh if mesh is not None else make_mesh()
        # XLA's CPU backend cannot execute multi-process computations: a
        # mesh spanning other processes' CPU devices would die inside the
        # first jitted step with XlaRuntimeError. Fall back to an
        # EMULATED collective: each process computes over the full global
        # batch on a mesh of its LOCAL devices (replicated compute — the
        # result every process holds is exactly what the all-reduce would
        # have produced), and _host_sync() then pins the replicas
        # together with a gloo-style host-side parameter mean through the
        # jax.distributed coordinator's KV store (multihost.py). The
        # multi-host checkpoint/resume contract stays fully exercised.
        self._emulated_hosts = 1
        self._sync_no = 0
        # KV-store keys are write-once and must MATCH across processes:
        # namespace them by construction order (identical on every
        # process — same program), never by id()
        self._sync_ns = ParallelWrapper._ns_counter
        ParallelWrapper._ns_counter += 1
        if self._needs_cpu_emulation(self.mesh):
            import jax
            local = [d for d in self.mesh.devices.flat
                     if d.process_index == jax.process_index()]
            self._emulated_hosts = jax.process_count()
            self.mesh = Mesh(np.array(local).reshape(-1), ("data",))
        self.averaging_frequency = max(1, int(averaging_frequency))
        self.average_updaters = average_updaters
        self.prefetch_buffer = prefetch_buffer
        self.report_score = report_score
        # threshold for encoded delta sharing (EncodedGradientsAccumulator
        # role — parallel/compression.py); None = dense averaging
        self.gradient_compression = gradient_compression
        if gradient_compression is not None and \
                self.averaging_frequency == 1:
            raise ValueError(
                "gradient_compression requires local-steps mode "
                "(averaging_frequency > 1); synchronous DP all-reduces "
                "gradients inside GSPMD where threshold encoding does not "
                "apply")
        self._jit_sync = None
        self._jit_round = None
        self.last_sent_fraction: Optional[float] = None
        self.listeners: List = []

    class Builder:
        def __init__(self, net):
            self._net = net
            self._mesh = None
            self._freq = 1
            self._avg_upd = True
            self._prefetch = 2
            self._compression = None

        def workers(self, n: int):
            self._mesh = make_mesh(n)
            return self

        def mesh(self, mesh: Mesh):
            self._mesh = mesh
            return self

        def averaging_frequency(self, k: int):
            self._freq = int(k)
            return self

        def gradient_compression(self, threshold: float):
            """Threshold-encoded delta sharing with error feedback (the
            EncodedGradientsAccumulator role); local-steps mode only."""
            self._compression = float(threshold)
            return self

        def average_updaters(self, flag: bool):
            self._avg_upd = bool(flag)
            return self

        def prefetch_buffer(self, n: int):
            self._prefetch = int(n)
            return self

        def build(self) -> "ParallelWrapper":
            return ParallelWrapper(self._net, self._mesh, self._freq,
                                   self._avg_upd, self._prefetch,
                                   gradient_compression=self._compression)

    # ------------------------------------------------------------------ fit
    @staticmethod
    def _needs_cpu_emulation(mesh: Mesh) -> bool:
        import jax
        try:
            if jax.process_count() <= 1:
                return False
        except RuntimeError:
            return False
        if jax.default_backend() != "cpu":
            return False
        pid = jax.process_index()
        return any(d.process_index != pid for d in mesh.devices.flat)

    def _host_sync(self):
        """Emulated-collective mode only: average params (+ updater state,
        matching averageUpdaters) across processes on the HOST, at the
        same cadence the real collective would run (per sync step / per
        averaging round — NOT once at fit() exit, which would leave
        params divergent mid-fit under per-process data and break
        mid-fit checkpoints). With the full global batch replicated per
        process the mean is a bitwise no-op that still proves every
        process agrees; with per-process data it IS the parameter
        averaging the reference TrainingMaster performs."""
        from .multihost import host_allreduce_mean
        net = self.net
        self._sync_no += 1
        tag = f"n{self._sync_ns}-s{self._sync_no}"
        net.params = host_allreduce_mean(net.params, tag + "/p")
        if self.average_updaters:
            net.updater_state = host_allreduce_mean(net.updater_state,
                                                    tag + "/u")

    def _host_sync_stacked(self):
        """Local-steps emulation: complete the round's pmean across
        processes by host-averaging the stacked replica trees (every
        local device already holds the local mean, so the cross-process
        mean of equal-sized hosts IS the global mean)."""
        from .multihost import host_allreduce_mean
        sp, su, ss, sr = self._stacked
        self._sync_no += 1
        tag = f"n{self._sync_ns}-r{self._sync_no}"
        sp = host_allreduce_mean(sp, tag + "/p")
        if self.average_updaters:
            su = host_allreduce_mean(su, tag + "/u")
        ss = host_allreduce_mean(ss, tag + "/s")
        # the residual (error-feedback carry) is per-replica by design
        self._stacked = (sp, su, ss, sr)

    @property
    def num_workers(self) -> int:
        return int(np.prod(list(self.mesh.shape.values())))

    def fit(self, data, num_epochs: int = 1):
        net = self.net
        net._ensure_init()
        from ..datasets.iterators import as_iterator, AsyncDataSetIterator
        for _ in range(num_epochs):
            it = as_iterator(data)
            if getattr(it, "async_supported", True):
                it = AsyncDataSetIterator(it, self.prefetch_buffer)
            if self.averaging_frequency == 1:
                self._fit_sync(it)
            else:
                self._fit_local_steps(it)
            net.epoch += 1
        return self

    # --- mode 1: synchronous DP, grads all-reduced by GSPMD ---
    def _fit_sync(self, iterator):
        net = self.net
        mesh = self.mesh
        if self._jit_sync is None:
            step = net._make_train_step(False)
            rep = NamedSharding(mesh, P())

            data = NamedSharding(mesh, P("data"))

            def sharded_step(params, upd, state, feats, labels, fmask, lmask,
                             iteration, empty_rnn):
                return step(params, upd, state, feats, labels, fmask, lmask,
                            iteration, empty_rnn)

            self._jit_sync = jax.jit(
                sharded_step,
                in_shardings=(rep, rep, rep, data, data, data, data, None,
                              rep),
                out_shardings=(rep, rep, rep, rep),
                donate_argnums=(0, 1, 2))
        empty_rnn = [{} for _ in getattr(net, "layers", [])]
        for ds in iterator:
            feats, labels, fmask, lmask = self._pad_to_devices(ds)
            cd = net.compute_dtype
            # masks stay f32 (stage_dtype policy, datasets/iterators.py):
            # a bf16 mask makes the masked-loss count drift above 256
            net.params, net.updater_state, new_states, score = self._jit_sync(
                net.params, net.updater_state, net.state,
                jnp.asarray(feats, cd), jnp.asarray(labels, cd),
                None if fmask is None else jnp.asarray(fmask, jnp.float32),
                None if lmask is None else jnp.asarray(lmask, jnp.float32),
                net.iteration, empty_rnn)
            net.state = net._strip_rnn_carry(new_states) \
                if hasattr(net, "_strip_rnn_carry") else new_states
            net.score_value = score   # device scalar; sync deferred to reader
            net.iteration += 1
            if self._emulated_hosts > 1:
                self._host_sync()     # the grad all-reduce this step's
                # local-mesh GSPMD could not span is completed on the host
            for lst in net.listeners:
                lst.iteration_done(net, net.iteration)

    # --- mode k: local steps + periodic parameter averaging ---
    def _fit_local_steps(self, iterator):
        net = self.net
        mesh = self.mesh
        n_dev = self.num_workers
        k = self.averaging_frequency
        if self._jit_round is None:
            step = net._make_train_step(False)
            avg_upd = self.average_updaters
            compress = self.gradient_compression

            def round_fn(stacked_params, stacked_upd, stacked_state,
                         stacked_residual,
                         feats, labels, fmask, lmask, iteration):
                # per-device view: strip the leading device axis
                params = jax.tree_util.tree_map(lambda a: a[0], stacked_params)
                upd = jax.tree_util.tree_map(lambda a: a[0], stacked_upd)
                state = jax.tree_util.tree_map(lambda a: a[0], stacked_state)
                feats = feats[:, 0]       # [k, 1, b, ...] -> [k, b, ...]
                labels = labels[:, 0]
                # masks ride the scan exactly like feats/labels (None stays
                # None: it is an empty pytree, so scan/shard_map pass it
                # through) — ParallelWrapper.java:333 accepts any DataSet,
                # including padded variable-length RNN batches
                fmask = None if fmask is None else fmask[:, 0]
                lmask = None if lmask is None else lmask[:, 0]
                empty_rnn = [{} for _ in getattr(net, "layers", [])]

                strip = getattr(net, "_strip_rnn_carry", lambda s: s)

                def body(carry, batch):
                    p, u, s, it = carry
                    f, l, fm, lm = batch
                    p, u, s, score = step(p, u, s, f, l, fm, lm, it,
                                          empty_rnn)
                    # each minibatch starts from zero rnn state (fit
                    # semantics); also keeps the scan carry structure fixed
                    return (p, u, strip(s), it + 1.0), score

                base = params       # identical across replicas at round
                # start (every round ends replica-synchronized)
                (params, upd, state, _), scores = lax.scan(
                    body, (params, upd, state,
                           jnp.asarray(iteration, jnp.float32)),
                    (feats, labels, fmask, lmask))
                residual = jax.tree_util.tree_map(lambda a: a[0],
                                                  stacked_residual)
                if compress is not None:
                    # EncodedGradientsAccumulator role: share the round's
                    # parameter DELTA threshold-quantized to {-t, 0, +t},
                    # carry the un-sent remainder per replica, apply the
                    # replica-mean of the encodings to the shared base
                    from .compression import sent_fraction, threshold_encode
                    deltas = jax.tree_util.tree_map(
                        lambda p, b: p - b, params, base)
                    enc_res = jax.tree_util.tree_map(
                        lambda d, r: threshold_encode(d, r, compress),
                        deltas, residual,
                        is_leaf=lambda x: isinstance(x, jnp.ndarray))
                    encoded = jax.tree_util.tree_map(
                        lambda er: er[0], enc_res,
                        is_leaf=lambda x: isinstance(x, tuple))
                    residual = jax.tree_util.tree_map(
                        lambda er: er[1], enc_res,
                        is_leaf=lambda x: isinstance(x, tuple))
                    mean_enc = lax.pmean(encoded, "data")
                    params = jax.tree_util.tree_map(
                        lambda b, e: b + e, base, mean_enc)
                    leaves = jax.tree_util.tree_leaves(encoded)
                    sent = sum(sent_fraction(l) * l.size for l in leaves) \
                        / max(sum(l.size for l in leaves), 1)
                else:
                    # Nd4j.averageAndPropagate analog over ICI:
                    params = lax.pmean(params, "data")
                    sent = jnp.asarray(1.0, jnp.float32)
                # each replica encoded its own shard: report the mean
                sent = lax.pmean(sent, "data")
                if avg_upd:
                    upd = lax.pmean(upd, "data")
                state = lax.pmean(state, "data")
                score = lax.pmean(jnp.mean(scores), "data")
                restack = lambda t: jax.tree_util.tree_map(
                    lambda a: a[None], t)
                return (restack(params), restack(upd), restack(state),
                        restack(residual), score, sent)

            self._jit_round = jax.jit(shard_map(
                round_fn, mesh=mesh,
                in_specs=(P("data"), P("data"), P("data"), P("data"),
                          P(None, "data"), P(None, "data"),
                          P(None, "data"), P(None, "data"), P()),
                out_specs=(P("data"), P("data"), P("data"), P("data"),
                           P(), P()),
                check_vma=False))
            # stack replicas once: [n_dev, ...] per leaf; the residual
            # (error-feedback carry for compressed sharing) starts at zero
            self._stacked = (
                jax.tree_util.tree_map(
                    lambda a: jnp.broadcast_to(a, (n_dev,) + a.shape),
                    net.params),
                jax.tree_util.tree_map(
                    lambda a: jnp.broadcast_to(a, (n_dev,) + a.shape),
                    net.updater_state),
                jax.tree_util.tree_map(
                    lambda a: jnp.broadcast_to(a, (n_dev,) + a.shape),
                    net.state),
                # dense mode never touches the residual: an empty pytree
                # avoids allocating an extra params-sized buffer per device
                (jax.tree_util.tree_map(
                    lambda a: jnp.zeros((n_dev,) + a.shape, a.dtype),
                    net.params) if compress is not None else {}))

        buf = []
        for ds in iterator:
            buf.append(ds)
            if len(buf) == k:
                self._run_round(buf)
                buf = []
        if buf:
            self._run_round(buf)
        # unstack back into the wrapped net
        sp, su, ss, _sr = self._stacked
        net.params = jax.tree_util.tree_map(lambda a: a[0], sp)
        net.updater_state = jax.tree_util.tree_map(lambda a: a[0], su)
        unstacked = jax.tree_util.tree_map(lambda a: a[0], ss)
        net.state = net._strip_rnn_carry(unstacked) \
            if hasattr(net, "_strip_rnn_carry") else unstacked

    @staticmethod
    def _stack_masks(masks, ref_arrays):
        """Stack per-batch masks into [k, global_b, T...]; batches without a
        mask get all-ones (identical semantics to no mask)."""
        if all(m is None for m in masks):
            return None
        shape_tail = next(m.shape[1:] for m in masks if m is not None)
        return np.stack([
            m if m is not None
            else np.ones((len(ref),) + shape_tail, np.float32)
            for m, ref in zip(masks, ref_arrays)])

    def _run_round(self, batches: List[DataSet]):
        net = self.net
        k = len(batches)
        n_dev = self.num_workers
        padded = [self._pad_to_devices(b) for b in batches]
        feats = np.stack([p[0] for p in padded])
        labels = np.stack([p[1] for p in padded])
        fmask = self._stack_masks([p[2] for p in padded],
                                  [p[0] for p in padded])
        lmask = self._stack_masks([p[3] for p in padded],
                                  [p[1] for p in padded])
        # [k, global_b, ...] -> [k, n_dev, b, ...]
        feats = feats.reshape((k, n_dev, -1) + feats.shape[2:])
        labels = labels.reshape((k, n_dev, -1) + labels.shape[2:])
        # masks transfer as f32 regardless of compute dtype (stage_dtype
        # policy, datasets/iterators.py): summing a bf16 mask for the loss
        # normalization cannot represent counts above 256 exactly
        if fmask is not None:
            fmask = jnp.asarray(
                fmask.reshape((k, n_dev, -1) + fmask.shape[2:]), jnp.float32)
        if lmask is not None:
            lmask = jnp.asarray(
                lmask.reshape((k, n_dev, -1) + lmask.shape[2:]), jnp.float32)
        sp, su, ss, sr = self._stacked
        sp, su, ss, sr, score, sent = self._jit_round(
            sp, su, ss, sr, jnp.asarray(feats, net.compute_dtype),
            jnp.asarray(labels, net.compute_dtype), fmask, lmask,
            net.iteration)
        self._stacked = (sp, su, ss, sr)
        if self._emulated_hosts > 1:
            self._host_sync_stacked()    # per averaging round, the same
            # cadence the cross-host pmean would have run at
        self.last_sent_fraction = sent    # device scalar (1.0 when dense)
        net.score_value = score   # device scalar; sync deferred to reader
        net.iteration += k
        for lst in net.listeners:
            lst.iteration_done(net, net.iteration)

    def _pad_to_devices(self, ds: DataSet):
        """Pad the batch so it divides evenly across devices (SPMD shapes
        must be static; the reference round-robins leftovers,
        ParallelWrapper.java:333). Padded rows repeat real examples for
        finite arithmetic but carry ZERO loss weight via the labels mask, so
        score and gradient match the unpadded batch exactly — repeating rows
        without the mask would silently double-weight them on every final
        partial batch of every epoch.
        Returns (features, labels, features_mask, labels_mask)."""
        n = ds.num_examples()
        n_dev = self.num_workers
        rem = n % n_dev
        if rem == 0:
            return ds.features, ds.labels, ds.features_mask, ds.labels_mask
        pad = n_dev - rem
        idx = np.concatenate([np.arange(n), np.arange(pad) % n])
        take = lambda a: None if a is None else a[idx]
        lmask = ds.labels_mask
        if lmask is None and ds.labels is not None:
            # synthesize: [N, T] ones for time-series labels (masked-RNN
            # count semantics), else per-example [N]
            if np.ndim(ds.labels) == 3:
                lmask = np.ones(np.shape(ds.labels)[:2], np.float32)
            else:
                lmask = np.ones((n,), np.float32)
        lmask = take(lmask)
        if lmask is not None:
            lmask = np.asarray(lmask, np.float32).copy()
            lmask[n:] = 0.0
        return (ds.features[idx], take(ds.labels), take(ds.features_mask),
                lmask)
