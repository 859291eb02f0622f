"""Accelerated-implementation registry — the TPU analog of the reference's
Helper SPI (ConvolutionHelper/SubsamplingHelper/BatchNormalizationHelper/
LocalResponseNormalizationHelper + the LSTMHelpers seam; reference
nn/layers/convolution/ConvolutionLayer.java:69-76 reflective cuDNN loading,
SURVEY.md §2.2).

Instead of reflective class loading, layers consult this registry by op kind;
a registered override (typically a Pallas kernel or custom lowering) is used
when its platform matches, with the pure-jnp implementation as the
always-available reference path — which is exactly what the reference's
"silent fallback to built-in" does, and what its CuDNN-vs-builtin equivalence
tests rely on (SURVEY.md §4).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Callable, Dict, List, Optional, Tuple

import jax

_HELPERS: Dict[str, Tuple[Callable, Tuple[str, ...]]] = {}
_DISABLED: set = set()
_SPMD = threading.local()      # per thread: engines trace on their own


@contextlib.contextmanager
def attention_spmd(mesh, batch_axis: Optional[str] = None,
                   head_axis: Optional[str] = None):
    """Declare, to the code TRACED inside, that the enclosing jit is
    partitioned over ``mesh`` with attention's batch rows sharded over
    ``batch_axis`` and its heads over ``head_axis``. Whoever owns the mesh
    (a sharded TransformerDecoder, a data-parallel trainer) enters this
    inside the function it jits; kernel helpers read it through
    :func:`attention_spmd_context` — tracers carry no sharding, and Mosaic
    kernels cannot lower under a multi-device jit unless wrapped in a
    ``shard_map`` over that mesh."""
    prev = attention_spmd_context()
    _SPMD.ctx = (mesh, batch_axis, head_axis)
    try:
        yield
    finally:
        _SPMD.ctx = prev


def attention_spmd_context():
    """(mesh, batch_axis, head_axis) declared by :func:`attention_spmd` for
    the current trace, or None under a single-device jit."""
    return getattr(_SPMD, "ctx", None)


_READS = threading.local()     # per thread, like _SPMD


@contextlib.contextmanager
def slab_read_tally():
    """Collect, for the code TRACED inside on this thread, what each read
    of a slab cache in attention reads: yields the list that
    :func:`note_slab_reads` appends ``(positions read, positions held)``
    to — traced values of the enclosing trace, which its owner sums (the
    decode block's ``SLAB_COUNTERS``). Closed, a note goes nowhere."""
    prev = getattr(_READS, "tally", None)
    tally: List = []
    _READS.tally = tally
    try:
        yield tally
    finally:
        _READS.tally = prev


def note_slab_reads(read, held) -> None:
    """Called while a program is traced, once per attention call over a
    slab cache: the positions the call reads, summed over its slots, and
    the positions the slab holds (slots × T) — scalars, traced or not."""
    tally = getattr(_READS, "tally", None)
    if tally is not None:
        tally.append((read, held))


_PLAN_LOCK = threading.Lock()
_PLANS: Dict[str, int] = {}


def note_attention_plan(kind: str, **detail) -> None:
    """Called while a program is TRACED, once per attention call: which
    path the call took — ``packed`` (heads side by side in 128-lane tiles,
    with its ``g`` and tile sizes), ``folded`` ([BH, T, Dh] through the same
    flash kernels), ``short`` (the whole-block short-T kernels) or
    ``materialized`` (the built-in softmax); a read of the slab cache
    ``slab_stream`` (the streaming decode kernel, with its ``g`` and block)
    or ``slab_einsum`` (the built-in body). The count is process-wide and
    only grows; readers snapshot and subtract
    (:func:`attention_plan_counts`, ``analysis.AttentionPlanAudit``,
    ``devstats``' ``attention_plans``)."""
    key = kind + "".join(f",{k}={v}" for k, v in sorted(detail.items()))
    with _PLAN_LOCK:
        _PLANS[key] = _PLANS.get(key, 0) + 1


def attention_plan_counts() -> Dict[str, int]:
    """Attention calls traced so far in this process, by plan
    (``"packed,g=2,kb=512,qb=512"``, ``"folded,..."``, ``"short"``,
    ``"materialized"``)."""
    with _PLAN_LOCK:
        return dict(_PLANS)


# Lazy default discovery — the analog of the reference's reflective
# Class.forName("...CudnnConvolutionHelper") at ConvolutionLayer.java:69-76:
# the kernel module providing this kind self-registers on first use. The
# providers ship with the package, so a provider that fails to import is an
# error that propagates — never a silent switch to the built-in path.
_DEFAULT_PROVIDERS: Dict[str, str] = {
    "batchnorm_train": "deeplearning4j_tpu.kernels.batchnorm",
    "batchnorm_add_act_train": "deeplearning4j_tpu.kernels.batchnorm",
    "lrn": "deeplearning4j_tpu.kernels.lrn",
    # long-context attention: Pallas flash kernels above min_seq_len=1024
    # (2-2.8x measured, BASELINE.md r3), jnp blockwise for masked long
    # sequences, decline below — the materialized path stays the default
    # where it wins. Ring attention (enable_ring_attention) replaces this
    # slot explicitly for sequence-parallel training.
    "attention": "deeplearning4j_tpu.kernels.pallas_attention",
    # drop-free routed experts: the grouped FFN kernel reads only the
    # experts hit (TPU only; the layer's dense jnp path elsewhere)
    "routed_experts": "deeplearning4j_tpu.kernels.expert_ffn",
    # decode attention over the lane-dense slab: K and V streamed in
    # position tiles (TPU only; the layer's einsum body elsewhere and for
    # the shapes the kernel declines)
    "slab_attention": "deeplearning4j_tpu.kernels.slab_attention",
    # a Mamba-2 decode step's state update, each slot's state read once and
    # written in place (TPU only; the layer's jnp body elsewhere)
    "ssm_update": "deeplearning4j_tpu.kernels.ssm_update",
    # "lstm" is deliberately NOT a default provider: honest r2 measurements
    # (BASELINE.md) show XLA's scan lowering beats the Pallas kernel at
    # char-RNN shapes in both f32 (11.5 vs 12.5 ms/step) and bf16 (8.0 vs
    # 10.6) — kernels/lstm.py stays opt-in via register_lstm_helper()
}


# kinds whose current registration came from lazy default discovery:
# replacing those is routine (e.g. ring attention taking the slot from the
# default flash kernel), so no warning fires for them
_DEFAULT_REGISTERED: set = set()


def register_helper(kind: str, fn: Callable,
                    platforms: Tuple[str, ...] = ("tpu",),
                    _default: bool = False, _scoped: bool = False) -> None:
    """``_scoped``: the caller snapshotted the slot and will restore it
    (e.g. GraphSequenceParallelTrainer) — deliberate, reversible
    replacement, so the one-slot-per-kind warning is skipped."""
    prev = _HELPERS.get(kind)
    prev_was_default = kind in _DEFAULT_REGISTERED
    if prev is not None and prev[0] is not fn and not prev_was_default \
            and not _scoped:
        # one slot per kind: e.g. flash attention and ring attention both
        # claim "attention" — silent replacement has bitten before
        # (registering flash mid-SP-training defeats sequence sharding).
        # Replacing a lazily-discovered DEFAULT is routine and silent.
        import warnings
        warnings.warn(
            f"helper kind '{kind}' already registered "
            f"({getattr(prev[0], '__name__', prev[0])}); replacing with "
            f"{getattr(fn, '__name__', fn)}", stacklevel=2)
    if _default:
        _DEFAULT_REGISTERED.add(kind)
    else:
        _DEFAULT_REGISTERED.discard(kind)
    _HELPERS[kind] = (fn, tuple(p.lower() for p in platforms))


def get_helper(kind: str) -> Optional[Callable]:
    """Return the accelerated impl for ``kind`` if one is registered for the
    default backend platform, else None (caller falls back to pure jnp)."""
    if kind in _DISABLED:
        return None
    if kind not in _HELPERS and kind in _DEFAULT_PROVIDERS:
        import importlib
        importlib.import_module(_DEFAULT_PROVIDERS[kind]).register_default()
    if kind not in _HELPERS:
        return None
    fn, platforms = _HELPERS[kind]
    return fn if jax.default_backend().lower() in platforms else None


def disable_helper(kind: str) -> None:
    """Force the built-in path (used by helper-vs-builtin equivalence tests)."""
    _DISABLED.add(kind)


def enable_helper(kind: str) -> None:
    _DISABLED.discard(kind)


def snapshot_helper(kind: str):
    """Capture the full registration state of ``kind`` (entry, default flag,
    disabled flag) so a scoped registration — e.g. a sequence-parallel
    trainer claiming the "attention" slot — can put back EXACTLY what it
    displaced via :func:`restore_helper` when it is done."""
    return (_HELPERS.get(kind), kind in _DEFAULT_REGISTERED,
            kind in _DISABLED)


def restore_helper(kind: str, snapshot) -> None:
    """Restore state captured by :func:`snapshot_helper`. An empty snapshot
    (nothing was registered) removes the kind entirely, which re-arms lazy
    default discovery rather than leaving a stale override behind."""
    entry, was_default, was_disabled = snapshot
    if entry is None:
        _HELPERS.pop(kind, None)
        _DEFAULT_REGISTERED.discard(kind)
    else:
        _HELPERS[kind] = entry
        if was_default:
            _DEFAULT_REGISTERED.add(kind)
        else:
            _DEFAULT_REGISTERED.discard(kind)
    if was_disabled:
        _DISABLED.add(kind)
    else:
        _DISABLED.discard(kind)
