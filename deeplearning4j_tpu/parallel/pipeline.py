"""Pipeline parallelism: GPipe-style microbatch schedule over an ICI ring.

The reference has no pipeline parallelism (SURVEY.md §2.4 taxonomy note);
this is the TPU-era extension, built the idiomatic way (scaling-book
pipelining recipe): a stack of L identical blocks has its parameters stacked
on a leading axis sharded over the ``pipe`` mesh axis — each of the S stages
holds L/S consecutive blocks. Microbatches stream through the stages:

    tick t: every stage receives its neighbour's activation via
    ``lax.ppermute`` (neighbour-to-neighbour ICI traffic), stage 0 injects
    microbatch t, each stage applies its local blocks (``lax.scan`` over its
    slice of the stacked params), and the last stage emits a finished
    microbatch. M microbatches drain in M + S - 1 ticks (the pipeline bubble).

The whole schedule is ordinary traced JAX (scan + ppermute), so
``jax.grad`` differentiates through it — the backward pass is the reverse
pipeline with transposed ppermutes, generated by autodiff instead of a
hand-written 1F1B schedule.

``pipeline_apply`` is the functional core; ``PipelineParallelTrainer`` wraps
a (block conf × L, head conf) net into a full sharded training step.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.dataset import DataSet
from ..ops.updaters import make_updater
from .mesh import make_mesh


def pipeline_apply(block_fn: Callable, stacked_params, x_micro, mesh: Mesh,
                   axis: str = "pipe"):
    """Run microbatches through a pipelined stack of identical blocks.

    block_fn(params_one_block, x) -> y with y.shape == x.shape.
    stacked_params: pytree with leading axis L (total blocks), sharded over
    ``axis`` (L % n_stages == 0). x_micro: [M, mb, ...] microbatches.
    Returns [M, mb, ...] outputs. Differentiable w.r.t. params and inputs.
    """
    n_stages = mesh.shape[axis]
    m_micro = x_micro.shape[0]
    ticks = m_micro + n_stages - 1

    def staged(local_params, xs):
        # local_params: [L/S, ...]; xs: full [M, mb, ...] (replicated)
        stage = lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        act = jnp.zeros(mb_shape, xs.dtype)
        outs = jnp.zeros((m_micro,) + mb_shape, xs.dtype)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def apply_local(a):
            def body(carry, p):
                return block_fn(p, carry), None
            out, _ = lax.scan(body, a, local_params)
            return out

        def tick(carry, t):
            act, outs = carry
            # stage 0 injects microbatch t; others take the ring hand-off
            incoming = lax.ppermute(act, axis, perm)
            inject = lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, m_micro - 1), axis=0, keepdims=False)
            act_in = jnp.where(stage == 0, inject, incoming)
            act_out = apply_local(act_in)
            # last stage writes finished microbatch t-(S-1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, m_micro - 1)
            is_emit = jnp.logical_and(stage == n_stages - 1,
                                      t >= n_stages - 1)
            cur = lax.dynamic_index_in_dim(outs, out_idx, 0, keepdims=False)
            outs = lax.dynamic_update_index_in_dim(
                outs, jnp.where(is_emit, act_out, cur), out_idx, 0)
            return (act_out, outs), None

        (act, outs), _ = lax.scan(tick, (act, outs), jnp.arange(ticks))
        # emit from the last stage to every device (cheap at mb scale; the
        # loss is computed replicated so autodiff stays simple)
        outs = lax.psum(jnp.where(stage == n_stages - 1, outs, 0.0), axis)
        return outs

    pspec = jax.tree_util.tree_map(
        lambda a: P(axis, *([None] * (a.ndim - 1))), stacked_params)
    return jax.shard_map(
        staged, mesh=mesh, in_specs=(pspec, P()), out_specs=P(),
        check_vma=False)(stacked_params, x_micro)


class PipelineParallelTrainer:
    """Train an L-deep stack of identical blocks + replicated head with the
    pipeline schedule. ``block_conf`` must map n_in → n_in (residual-friendly
    square blocks, the transformer/LSTM-stack shape PP is used for)."""

    def __init__(self, block_conf, depth: int, head_conf,
                 mesh: Optional[Mesh] = None, axis: str = "pipe",
                 num_microbatches: int = 4, learning_rate: float = 0.1,
                 updater: str = "sgd", seed: int = 12345):
        from ..ops import rng as rngmod
        self.mesh = mesh if mesh is not None else \
            make_mesh(axis_names=("pipe",))
        self.axis = axis
        n_stages = self.mesh.shape[axis]
        if depth % n_stages:
            raise ValueError(f"depth {depth} not divisible by {n_stages} stages")
        self.block_conf = block_conf
        self.head_conf = head_conf
        self.depth = depth
        self.num_microbatches = int(num_microbatches)
        self.learning_rate = float(learning_rate)
        self.iteration = 0
        self.score_value = float("nan")

        key = rngmod.root_key(seed)
        block_params = [
            block_conf.init_params(rngmod.for_layer(key, i), jnp.float32)
            for i in range(depth)]
        # stack [L, ...] then shard the leading axis over the pipe stages
        self.block_params = jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls), *block_params)
        self.head_params = head_conf.init_params(
            rngmod.for_layer(key, depth), jnp.float32)
        self._place()
        self.updater = make_updater(updater)
        self.upd_state = {
            "block": jax.tree_util.tree_map(self.updater.init,
                                            self.block_params),
            "head": jax.tree_util.tree_map(self.updater.init,
                                           self.head_params)}
        self._jit_step = None

    def _place(self):
        mesh, axis = self.mesh, self.axis
        self.block_params = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, NamedSharding(
                mesh, P(axis, *([None] * (a.ndim - 1))))), self.block_params)
        self.head_params = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, NamedSharding(mesh, P())),
            self.head_params)

    def _loss(self, bp, hp, x_micro, y_micro):
        block = self.block_conf
        head = self.head_conf

        def block_fn(p, a):
            out, _ = block.forward(p, {}, a, train=False, rng=None)
            return out

        feats = pipeline_apply(block_fn, bp, x_micro, self.mesh, self.axis)
        m, mb = feats.shape[0], feats.shape[1]
        flat = feats.reshape((m * mb,) + feats.shape[2:])
        labels = y_micro.reshape((m * mb,) + y_micro.shape[2:])
        pre = head.preoutput(hp, flat)
        return head.compute_score(hp, labels, pre, None)

    def fit_batch(self, ds: DataSet):
        m = self.num_microbatches
        n = ds.num_examples()
        if n % m:
            raise ValueError(f"batch {n} not divisible by {m} microbatches")
        x = np.asarray(ds.features, np.float32).reshape(
            (m, n // m) + ds.features.shape[1:])
        y = np.asarray(ds.labels, np.float32).reshape(
            (m, n // m) + ds.labels.shape[1:])
        if self._jit_step is None:
            lr = self.learning_rate
            upd = self.updater

            def step(bp, hp, us, x_micro, y_micro, it):
                (score), grads = jax.value_and_grad(
                    lambda ps: self._loss(ps[0], ps[1], x_micro, y_micro))(
                        (bp, hp))
                gb, gh = grads
                it_f = jnp.asarray(it, jnp.float32)

                def apply(p, g, s):
                    st, ns = upd.update(g, s, lr, it_f)
                    return p - st, ns

                nb = jax.tree_util.tree_map(
                    lambda p, g, s: apply(p, g, s)[0], bp, gb, us["block"])
                nbs = jax.tree_util.tree_map(
                    lambda p, g, s: apply(p, g, s)[1], bp, gb, us["block"])
                nh = jax.tree_util.tree_map(
                    lambda p, g, s: apply(p, g, s)[0], hp, gh, us["head"])
                nhs = jax.tree_util.tree_map(
                    lambda p, g, s: apply(p, g, s)[1], hp, gh, us["head"])
                return nb, nh, {"block": nbs, "head": nhs}, score

            self._jit_step = jax.jit(step, donate_argnums=(0, 1, 2))
        self.block_params, self.head_params, self.upd_state, score = \
            self._jit_step(self.block_params, self.head_params,
                           self.upd_state, jnp.asarray(x), jnp.asarray(y),
                           self.iteration)
        self.score_value = score
        self.iteration += 1

    def output(self, x) -> np.ndarray:
        m = self.num_microbatches
        n = x.shape[0]
        pad = (-n) % m
        if pad:
            x = np.concatenate([x, x[:pad]], 0)
        xm = np.asarray(x, np.float32).reshape(
            (m, x.shape[0] // m) + x.shape[1:])
        block = self.block_conf

        def block_fn(p, a):
            out, _ = block.forward(p, {}, a, train=False, rng=None)
            return out

        feats = pipeline_apply(block_fn, self.block_params, jnp.asarray(xm),
                               self.mesh, self.axis)
        flat = feats.reshape((x.shape[0],) + feats.shape[2:])
        out, _ = self.head_conf.forward(self.head_params, {}, flat,
                                        train=False, rng=None)
        return np.asarray(out)[:n]
