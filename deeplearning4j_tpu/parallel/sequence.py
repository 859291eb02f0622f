"""Sequence/context parallelism: ring attention over the ICI ring.

The reference's only long-sequence mechanism is truncated BPTT (SURVEY.md
§5.7); ring attention is the TPU-era extension the survey prescribes
("designed fresh over ICI collective-permute"). Implementation:

- sequences are sharded over the mesh's ``sp`` axis (each device holds a
  [B, T/n, H, D] chunk of q/k/v);
- each device computes blockwise attention of its q chunk against the
  currently-held k/v chunk with a streaming (flash-style) softmax — running
  max ``m``, running denominator ``l``, running numerator ``o``;
- k/v chunks rotate around the ring with ``lax.ppermute`` (ICI
  neighbour-to-neighbour traffic, overlapping compute with transfer), n steps
  until every q block has seen every k/v block;
- causal masking uses the global position offsets implied by each chunk's
  ring position.

``ring_self_attention`` is the public entry; on a 1-device mesh it reduces to
ordinary attention, and the CPU-mesh test asserts exact equivalence against
the single-device reference implementation."""

from __future__ import annotations

import functools
import weakref
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def attention_reference(q, k, v, causal: bool = False):
    """Plain single-device attention: q/k/v [B, T, H, D] → [B, T, H, D]."""
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((tq, tk), bool))
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _block_attend(q, k, v, m, l, o, q_offset, k_offset, causal,
                  k_keep=None):
    """One streaming-softmax block update. q [B,Tq,H,D], k/v [B,Tk,H,D];
    m/l [B,H,Tq], o [B,Tq,H,D] are the running max/denominator/numerator.
    ``k_keep`` [B,Tk]: masked keys (0) have their logits REPLACED by −1e30
    — replacement, not an additive bias, so a fully-masked row degrades to
    the same uniform average the materialized softmax path produces."""
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale    # [B,H,Tq,Tk]
    if k_keep is not None:
        logits = jnp.where(k_keep[:, None, None, :] > 0, logits,
                           jnp.asarray(-1e30, logits.dtype))
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        qpos = q_offset + jnp.arange(tq)
        kpos = k_offset + jnp.arange(tk)
        mask = qpos[:, None] >= kpos[None, :]
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    block_max = jnp.max(logits, axis=-1)                    # [B,H,Tq]
    new_m = jnp.maximum(m, block_max)
    # guard fully-masked blocks (all -inf)
    new_m_safe = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
    p = jnp.exp(logits - new_m_safe[..., None])
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    correction = jnp.where(jnp.isfinite(m), jnp.exp(m - new_m_safe), 0.0)
    new_l = l * correction + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    new_o = o * jnp.transpose(correction, (0, 2, 1))[..., None] + pv
    return new_m, new_l, new_o


def _merge_partials(o, lse, o_p, lse_p):
    """Merge two normalized attention partials (o_i, lse_i) — the standard
    flash combination: weights exp(lse_i − logaddexp) are ≤ 1, so the merge
    is stable even though each o_i is already normalized."""
    new = jnp.logaddexp(lse, lse_p)
    new_safe = jnp.where(jnp.isfinite(new), new, 0.0)
    w = jnp.where(jnp.isfinite(lse), jnp.exp(lse - new_safe), 0.0)
    wp = jnp.where(jnp.isfinite(lse_p), jnp.exp(lse_p - new_safe), 0.0)
    return o * w[..., None] + o_p * wp[..., None], new


def _ring_perm(n_dev):
    return [(i, (i + 1) % n_dev) for i in range(n_dev)]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring_flash(ql3, kl3, vl3, axis, n_dev, causal, qb, kb, interpret):
    """Ring attention with the Pallas flash kernels as the per-chunk-pair
    compute (VERDICT r3 item #3 — the r3 ring ran jnp `_block_attend` math
    per shard, so sequence-parallel long-context lost the kernel win).

    Shard-local [BH, T_local, D] q/k/v; k/v chunks rotate over ``axis``.
    Under causal masking every pair is one of three STATIC cases — src <
    my: fully visible (non-causal kernel), src == my: diagonal (causal
    kernel at zero offset), src > my: strictly future (skip) — selected by
    ``lax.switch`` on the traced ring position, so the kernels never need
    dynamic position offsets. Per-pair (o, lse) partials merge via
    :func:`_merge_partials`.

    Backward is the FlashAttention-2 factorization ring-composed: because
    per-pair probabilities recompute as exp(s − lse_global), calling the
    pair backward kernels with the GLOBAL lse/o/do yields exact global
    gradient contributions; dq accumulates locally while dk/dv accumulators
    rotate home along with their k/v chunks (one ring, both grads)."""
    o, _ = _ring_flash_fwd_impl(ql3, kl3, vl3, axis, n_dev, causal, qb, kb,
                                interpret)
    return o


def _ring_flash_fwd_impl(ql3, kl3, vl3, axis, n_dev, causal, qb, kb,
                         interpret):
    from ..kernels.pallas_attention import _flash_fwd_impl
    bh, t, d = ql3.shape
    my = lax.axis_index(axis) if n_dev > 1 else jnp.int32(0)
    o0 = jnp.zeros((bh, t, d), jnp.float32)
    lse0 = jnp.full((bh, t), -jnp.inf, jnp.float32)

    def pair_fn(diag):
        def fn(kv):
            kc, vc = kv
            op, lsep = _flash_fwd_impl(ql3, kc, vc, None, 1, diag, qb, kb,
                                       interpret)
            return op.astype(jnp.float32), lsep[:, 0, 0].astype(jnp.float32)
        return fn

    def skip_fn(kv):
        return o0, lse0

    def body(step, carry):
        o, lse, kc, vc = carry
        src = (my - step) % n_dev
        if causal:
            idx = jnp.where(src == my, 2, jnp.where(src < my, 1, 0))
            op, lsep = lax.switch(idx, [skip_fn, pair_fn(False),
                                        pair_fn(True)], (kc, vc))
        else:
            op, lsep = pair_fn(False)((kc, vc))
        o, lse = _merge_partials(o, lse, op, lsep)
        if n_dev > 1:
            perm = _ring_perm(n_dev)
            kc = lax.ppermute(kc, axis, perm)
            vc = lax.ppermute(vc, axis, perm)
        return o, lse, kc, vc

    if n_dev > 1:
        o, lse, _, _ = lax.fori_loop(0, n_dev, body, (o0, lse0, kl3, vl3))
    else:
        o, lse, _, _ = body(0, (o0, lse0, kl3, vl3))
    return o.astype(ql3.dtype), lse


def _ring_flash_fwd(ql3, kl3, vl3, axis, n_dev, causal, qb, kb, interpret):
    o, lse = _ring_flash_fwd_impl(ql3, kl3, vl3, axis, n_dev, causal, qb,
                                  kb, interpret)
    return o, (ql3, kl3, vl3, o, lse)


def _ring_flash_bwd(axis, n_dev, causal, qb, kb, interpret, res, do):
    from ..kernels.pallas_attention import _flash_bwd_impl
    ql3, kl3, vl3, o, lse = res
    bh, t, d = ql3.shape
    my = lax.axis_index(axis) if n_dev > 1 else jnp.int32(0)
    # the kernels' row carrier: [BH, 1, 1, T]; the row term rowsum(dO·O) is
    # made inside the dq kernel from its dO and O tiles
    lse3 = lse[:, None, None, :]

    def pair_fn(diag):
        def fn(kv):
            kc, vc = kv
            dqp, dkp, dvp = _flash_bwd_impl(ql3, kc, vc, None, 1, o, lse3,
                                            do, diag, qb, kb, interpret)
            return (dqp.astype(jnp.float32), dkp.astype(jnp.float32),
                    dvp.astype(jnp.float32))
        return fn

    def skip_fn(kv):
        z = jnp.zeros((bh, t, d), jnp.float32)
        return z, z, z

    def body(step, carry):
        dq, kc, vc, dkc, dvc = carry
        src = (my - step) % n_dev
        if causal:
            idx = jnp.where(src == my, 2, jnp.where(src < my, 1, 0))
            dqp, dkp, dvp = lax.switch(idx, [skip_fn, pair_fn(False),
                                             pair_fn(True)], (kc, vc))
        else:
            dqp, dkp, dvp = pair_fn(False)((kc, vc))
        dq = dq + dqp
        dkc = dkc + dkp
        dvc = dvc + dvp
        if n_dev > 1:
            perm = _ring_perm(n_dev)
            kc, vc, dkc, dvc = (lax.ppermute(x, axis, perm)
                                for x in (kc, vc, dkc, dvc))
        return dq, kc, vc, dkc, dvc

    z = jnp.zeros((bh, t, d), jnp.float32)
    if n_dev > 1:
        # n_dev rotations bring each dk/dv accumulator home with its chunk
        dq, _, _, dk, dv = lax.fori_loop(
            0, n_dev, body, (z, kl3, vl3, z, z))
    else:
        dq, _, _, dk, dv = body(0, (z, kl3, vl3, z, z))
    return (dq.astype(ql3.dtype), dk.astype(kl3.dtype),
            dv.astype(vl3.dtype))


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def _ring_block(t_local: int):
    """Largest kernel block that tiles the shard length (None → the jnp
    path; block == t_local is always legal since a full-dim block is exempt
    from the TPU divisibility rule)."""
    if t_local <= 512:
        return t_local
    for blk in (512, 256, 128):
        if t_local % blk == 0:
            return blk
    return None


def ring_self_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                        causal: bool = False, impl: Optional[str] = None,
                        batch_axis: Optional[str] = None):
    """Ring attention: q/k/v [B, T, H, D] sharded over ``axis`` on dim 1.
    Returns [B, T, H, D] with the same sharding.

    ``impl``: None picks the Pallas pair-kernel ring when the shard length
    tiles a kernel block (the fast path; see :func:`_ring_flash`), else the
    jnp streaming-softmax ring; "jnp"/"pallas" force a path (the parity
    test runs both).

    ``batch_axis``: on a composed (data, sp) mesh, the mesh axis the BATCH
    dim is sharded over — devices along it run independent rings
    (``ppermute`` over ``axis`` only rotates within one batch shard)."""
    from ..kernels.pallas_attention import _interpret_default
    n_dev = mesh.shape[axis]
    t_local = q.shape[1] // n_dev
    blk = _ring_block(t_local)
    # auto mode requires a real kernel backend: in Pallas INTERPRET mode
    # (CPU) the kernels are orders of magnitude slower than the XLA jnp
    # ring, so interpret backends keep the jnp path unless impl="pallas"
    # forces the kernels (parity tests and the driver dryrun do)
    use_kernel = (impl == "pallas") or (
        impl is None and blk is not None and not _interpret_default())
    if use_kernel and blk is None:
        raise ValueError(f"no kernel block tiles shard length {t_local}")
    spec = P(batch_axis, axis, None, None)
    if use_kernel:
        interpret = _interpret_default()

        def ring_kernel(ql, kl, vl):
            bl, tl, hl, dl = ql.shape
            fold = lambda x: x.transpose(0, 2, 1, 3).reshape(bl * hl, tl, dl)
            o3 = _ring_flash(fold(ql), fold(kl), fold(vl), axis, n_dev,
                             causal, blk, blk, interpret)
            return o3.reshape(bl, hl, tl, dl).transpose(0, 2, 1, 3)

        return jax.shard_map(ring_kernel, mesh=mesh,
                             in_specs=(spec, spec, spec), out_specs=spec,
                             check_vma=False)(q, k, v)

    def ring(ql, kl, vl):
        b, t_local, h, d = ql.shape
        my_idx = lax.axis_index(axis)
        m = jnp.full((b, h, t_local), -jnp.inf, ql.dtype)
        l = jnp.zeros((b, h, t_local), ql.dtype)
        o = jnp.zeros_like(ql)
        q_offset = my_idx * t_local

        def body(step, carry):
            m, l, o, k_cur, v_cur = carry
            # chunk currently held originated from device (my_idx - step)
            src = (my_idx - step) % n_dev
            k_offset = src * t_local
            m, l, o = _block_attend(ql, k_cur, v_cur, m, l, o,
                                    q_offset, k_offset, causal)
            # rotate: receive the next chunk from the ring neighbour
            perm = _ring_perm(n_dev)
            k_next = lax.ppermute(k_cur, axis, perm)
            v_next = lax.ppermute(v_cur, axis, perm)
            return m, l, o, k_next, v_next

        m, l, o, _, _ = lax.fori_loop(
            0, n_dev, body, (m, l, o, kl, vl)) if n_dev > 1 else \
            body(0, (m, l, o, kl, vl))
        denom = jnp.transpose(jnp.maximum(l, 1e-20), (0, 2, 1))[..., None]
        return o / denom

    return jax.shard_map(ring, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def sequence_sharded(mesh: Mesh, x, axis: str = "sp"):
    """Place [B, T, ...] with T sharded over the mesh axis."""
    from jax.sharding import NamedSharding
    spec = P(*([None, axis] + [None] * (x.ndim - 2)))
    return jax.device_put(x, NamedSharding(mesh, spec))


class SequenceParallelTrainer:
    """Sequence-parallel training of a self-attention block: activations are
    sharded over the ``sp`` axis on the TIME dimension end-to-end — the QKV
    projections and loss are local to each device's sequence chunk, and the
    attention itself runs through ``ring_self_attention`` (k/v rotating over
    the ICI ring via ppermute). The whole step — ring forward, reverse-ring
    backward (autodiff through ppermute), updater — is one jitted program.

    This trains the same math as SelfAttentionLayer
    (nn/conf/layers/attention.py) with per-token MSE/softmax heads; the
    CPU-mesh test asserts one SP step == one single-device step.
    """

    def __init__(self, attn_conf, mesh: Optional[Mesh] = None,
                 axis: str = "sp", learning_rate: float = 0.1,
                 seed: int = 12345):
        from ..ops import rng as rngmod
        from .mesh import make_mesh
        self.conf = attn_conf
        self.mesh = mesh if mesh is not None else make_mesh(axis_names=("sp",))
        self.axis = axis
        self.learning_rate = float(learning_rate)
        self.params = attn_conf.init_params(rngmod.root_key(seed))
        self.iteration = 0
        self.score_value = float("nan")
        self._jit_step = None

    def _loss(self, params, x, y):
        """Per-token regression loss on the attention output; x/y [B, T, d]
        sequence-sharded. All ops except the ring are T-local."""
        conf = self.conf
        n, t, _ = x.shape
        hcount, hs = conf.num_heads, conf._head_size()
        q = (x @ params["Wq"]).reshape(n, t, hcount, hs)
        k = (x @ params["Wk"]).reshape(n, t, hcount, hs)
        v = (x @ params["Wv"]).reshape(n, t, hcount, hs)
        out = ring_self_attention(q, k, v, self.mesh, self.axis,
                                  causal=conf.causal)
        out = out.reshape(n, t, hcount * hs)
        if conf.project_out:
            out = out @ params["Wo"] + params["bo"][None, None, :]
        out = conf.activation_fn()(out)
        return jnp.mean((out - y) ** 2)

    def fit_batch(self, x, y):
        from jax.sharding import NamedSharding
        mesh, axis = self.mesh, self.axis
        n_sp = mesh.shape[axis]
        if x.shape[1] % n_sp:
            raise ValueError(
                f"sequence length {x.shape[1]} not divisible by sp axis size "
                f"{n_sp}; pad the sequence to a multiple of {n_sp}")
        x = sequence_sharded(mesh, jnp.asarray(x, jnp.float32), axis)
        y = sequence_sharded(mesh, jnp.asarray(y, jnp.float32), axis)
        if self._jit_step is None:
            lr = self.learning_rate
            rep = NamedSharding(mesh, P())
            seq = NamedSharding(mesh, P(None, axis, None))

            def step(params, xb, yb):
                score, grads = jax.value_and_grad(self._loss)(params, xb, yb)
                new = jax.tree_util.tree_map(
                    lambda p, g: p - lr * g, params, grads)
                return new, score

            self._jit_step = jax.jit(
                step, in_shardings=(rep, seq, seq),
                out_shardings=(rep, rep), donate_argnums=(0,))
        self.params, score = self._jit_step(self.params, x, y)
        self.score_value = score
        self.iteration += 1
        return float(score)


def enable_ring_attention(mesh: Mesh, axis: str = "sp",
                          platforms=("tpu", "cpu"),
                          batch_axis: Optional[str] = None,
                          impl: Optional[str] = None,
                          _scoped: bool = False):
    """Route every SelfAttentionLayer through ring attention over ``mesh``
    via the helper seam (nn/helpers kind="attention" — the same registry the
    cuDNN-style kernels use): with activations sequence-sharded on T, the
    whole transformer trains sequence-parallel without touching the model.
    Masked attention is not ring-supported — the helper refuses so the
    layer's error surfaces instead of silently attending across padding."""
    from ..nn.helpers import register_helper

    def ring_helper(conf, q, k, v, mask):
        if mask is not None:
            raise ValueError("ring attention does not support key masks; "
                             "train unmasked (LM) sequences or disable the "
                             "ring helper")
        return ring_self_attention(q, k, v, mesh, axis, causal=conf.causal,
                                   batch_axis=batch_axis, impl=impl)

    register_helper("attention", ring_helper, platforms, _scoped=_scoped)
    # a prior disable_ring_attention() leaves the kind in the disabled set;
    # re-enabling must clear it or every later trainer silently falls back
    # to the all-gather path
    from ..nn.helpers import enable_helper
    enable_helper("attention")
    return ring_helper


def disable_ring_attention():
    from ..nn.helpers import disable_helper
    disable_helper("attention")


# ring helpers of trainers that have been close()d, mapped to the snapshot
# each trainer displaced: restoring a closed ring from a snapshot would
# resurrect a ring bound to a dead mesh, so restores walk this chain to the
# most recent still-live registration instead (weak keys: entries vanish
# once nothing else can resurrect the helper)
_CLOSED_RING_SNAPSHOTS: "weakref.WeakKeyDictionary" = \
    weakref.WeakKeyDictionary()


class GraphSequenceParallelTrainer:
    """Sequence-parallel training of a whole ComputationGraph (the
    transformer LM flagship, models/transformer.py): token ids and labels
    are sharded over the mesh ``sp`` axis on the TIME dimension; LN / FFN /
    embedding / output-loss are token-local so GSPMD partitions them
    trivially, and attention runs through ``ring_self_attention`` via the
    helper seam (``enable_ring_attention``). One jitted program per step —
    the standard graph train step, resharded.

    The CPU-mesh test asserts one SP step == one single-device step
    (ring attention is exact, not an approximation)."""

    def __init__(self, net, mesh: Optional[Mesh] = None, axis: str = "sp",
                 data_axis: Optional[str] = None,
                 ring_impl: Optional[str] = None):
        """``data_axis``: on a composed 2-D mesh (e.g. make_mesh(
        axis_names=("data", "sp"), shape=(2, 4))), the axis the BATCH dim
        shards over — DP×SP: independent rings per batch shard, gradients
        all-reduced over ``data`` by GSPMD. ``ring_impl``: forwarded to
        :func:`ring_self_attention` ("pallas" forces the kernel ring even
        on interpret backends — the parity tests and driver dryrun do)."""
        from .mesh import make_mesh
        from ..nn.helpers import snapshot_helper
        self.net = net
        self.mesh = mesh if mesh is not None else \
            make_mesh(axis_names=("sp",))
        self.axis = axis
        if data_axis is not None and data_axis == axis:
            raise ValueError(
                f"data_axis {data_axis!r} must differ from the sequence "
                f"axis {axis!r} (use a 2-D mesh like axis_names="
                f"('data', 'sp'))")
        self.data_axis = data_axis if data_axis in self.mesh.shape else None
        # The ring helper claims the process-global "attention" slot; without
        # restoration, every later SelfAttentionLayer in the process (other
        # nets, net.output() sampling) would silently route through ring
        # attention bound to THIS trainer's mesh. Snapshot what was there and
        # put it back in close() / on context exit.
        self._prev_attention = snapshot_helper("attention")
        self._ring_helper = enable_ring_attention(
            self.mesh, axis, batch_axis=self.data_axis, impl=ring_impl,
            _scoped=True)
        self._closed = False
        self._jit_step = None

    def close(self):
        """Restore whatever attention helper was registered before this
        trainer claimed the slot (the lazy flash default, usually). Safe to
        call more than once. Restores only while THIS trainer's helper still
        holds the slot — under non-LIFO closes (or a helper registered after
        this trainer) restoring would reinstall a stale ring bound to this
        trainer's mesh over whoever registered since, so close() warns and
        leaves the current registration alone instead."""
        if self._closed:
            return
        self._closed = True
        _CLOSED_RING_SNAPSHOTS[self._ring_helper] = self._prev_attention
        from ..nn import helpers
        current = helpers._HELPERS.get("attention")
        if current is not None and current[0] is not self._ring_helper:
            import warnings
            warnings.warn(
                "GraphSequenceParallelTrainer.close(): the 'attention' "
                "helper slot was re-registered after this trainer claimed "
                "it; leaving the current registration in place (close "
                "trainers LIFO to restore cleanly)", stacklevel=2)
            return
        snap = self._prev_attention
        while snap[0] is not None and snap[0][0] in _CLOSED_RING_SNAPSHOTS:
            # the displaced helper belongs to an already-closed trainer
            # (non-LIFO close order): restoring it would resurrect a ring
            # bound to a dead mesh — walk to what THAT trainer displaced,
            # until a still-live registration (or the empty slot) surfaces
            snap = _CLOSED_RING_SNAPSHOTS[snap[0][0]]
        helpers.restore_helper("attention", snap)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def _build(self):
        net = self.net
        mesh, axis = self.mesh, self.axis
        step = net._make_train_step()
        from jax.sharding import NamedSharding
        rep = NamedSharding(mesh, P())
        da = self.data_axis
        seq2 = NamedSharding(mesh, P(da, axis))
        seq3 = NamedSharding(mesh, P(da, axis, None))

        def wrapped(params, upd, state, inputs, labels, imasks, lmasks,
                    iteration):
            return step(params, upd, state, inputs, labels, imasks, lmasks,
                        iteration, {})

        self._jit_step = jax.jit(
            wrapped,
            in_shardings=(rep, rep, rep, seq2, seq3, seq2, seq2, None),
            out_shardings=(rep, rep, rep, rep),
            donate_argnums=(0, 1, 2))

    def fit_batch(self, ds):
        if self._closed:
            raise RuntimeError(
                "GraphSequenceParallelTrainer is closed: its ring-attention "
                "registration has been restored away, so training would "
                "silently lose sequence parallelism; create a new trainer")
        from ..nn import helpers
        current = helpers._HELPERS.get("attention")
        if current is None or current[0] is not self._ring_helper:
            raise RuntimeError(
                "this trainer's ring-attention helper no longer holds the "
                "'attention' slot (another trainer or helper registration "
                "displaced it); training would route attention through the "
                "wrong mesh — close the other registration first or use "
                "one trainer at a time")
        net = self.net
        net._ensure_init()
        n_sp = self.mesh.shape[self.axis]
        t = np.asarray(ds.features).shape[1]
        if t % n_sp:
            raise ValueError(f"sequence length {t} not divisible by sp "
                             f"axis size {n_sp}")
        if self.data_axis:
            n_dp = self.mesh.shape[self.data_axis]
            n = np.asarray(ds.features).shape[0]
            if n % n_dp:
                raise ValueError(f"batch size {n} not divisible by data "
                                 f"axis size {n_dp}")
        if self._jit_step is None:
            self._build()
        net.last_input_batch = ds    # probe data for flow/debug listeners
        inputs = net._inputs_dict(ds.features)
        labels = net._labels_dict(ds.labels)
        # label masks ([N, T]) shard over T like the labels; attention KEY
        # masks are rejected inside the ring helper, but the per-token LOSS
        # mask is T-local and correct under SP
        imasks, lmasks = net._masks_of(ds)
        net.params, net.updater_state, new_states, score = self._jit_step(
            net.params, net.updater_state, net.state, inputs, labels,
            imasks, lmasks, net.iteration)
        net.state = net._strip_rnn_carry(new_states)
        net.score_value = score
        net.iteration += 1
        for lst in net.listeners:
            lst.iteration_done(net, net.iteration)

    def fit(self, data, num_epochs: int = 1):
        from ..datasets.iterators import as_iterator
        for _ in range(num_epochs):
            for ds in as_iterator(data):
                self.fit_batch(ds)
            self.net.epoch += 1
        return self
