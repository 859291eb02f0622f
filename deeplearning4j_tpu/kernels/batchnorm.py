"""Fused batch-norm training helper — the TPU analog of the reference's
CudnnBatchNormalizationHelper (deeplearning4j-cuda nn/layers/normalization/
CudnnBatchNormalizationHelper.java; helper seam SURVEY.md §2.2).

Why it exists: profiling the ResNet-50 train step shows batch-norm dominates
the HBM-bound elementwise/reduction time (the convs themselves run near MXU
peak). The pure-jnp path costs extra memory passes: two-pass mean/var via
``jnp.var``, a saved ``x - mean`` residual, and an autodiff-generated backward
with several reduction sweeps. This helper reduces traffic to the minimum:

  forward:  ONE multi-output reduction pass for the statistics, then one FMA
            pass ``y = x * scale + shift`` with the per-channel scale/shift
            folded to the input dtype and no extra saved residual. The
            statistics use a shifted one-pass form: moments of ``x - s``,
            where the shift ``s`` is the layer's RUNNING mean (a loop
            constant, so it costs nothing and breaks no fusion). The raw
            one-pass ``E[x^2]-E[x]^2`` (stock flax BN) cancels
            catastrophically for large-mean low-variance channels; once the
            running mean has warmed up (a few iterations at decay 0.9), the
            shifted subtraction is well-conditioned for any input scale. A
            data-dependent shift (e.g. sampling x itself) was measured to
            break XLA's reduction fusion and cost ~15% step time.
  backward: one pass for the two reductions (dbeta, dgamma), one pass for dx
            via the analytic formula — recomputing xhat from x instead of
            storing it (x is already resident for the conv weight gradient).

Statistics always accumulate in f32 regardless of bf16 compute (matching the
built-in path's policy). Equivalence against the built-in path is tested the
same way the reference tests cuDNN-vs-builtin (SURVEY.md §4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.shapes import chan


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def bn_train_fused(x, gamma, beta, shift_hint, eps):
    """Batch-norm training forward: normalize over all axes but the last.

    ``shift_hint`` is a per-channel f32 estimate of the mean used only to
    condition the one-pass variance (pass the running mean; zeros degrade to
    flax-BN-level conditioning, never worse). Returns ``(y, mean, var)`` with
    mean/var in f32 (biased var, matching ``jnp.var``'s default used by the
    built-in path).

    VJP contract: only the cotangent of ``y`` propagates. The returned
    ``mean``/``var`` exist for running-statistics updates, which are never
    differentiated — their incoming cotangents are DISCARDED by the custom
    backward rule (same for :func:`bn_add_act_train_fused`). Do not
    differentiate through the statistics outputs; gradients would be
    silently wrong."""
    out, _res = _bn_fwd_impl(x, gamma, beta, shift_hint, eps)
    return out


def _bn_fwd_impl(x, gamma, beta, shift_hint, eps):
    axes = tuple(range(x.ndim - 1))
    n = 1
    for a in axes:
        n *= x.shape[a]
    xf = x.astype(jnp.float32)
    s = lax.stop_gradient(shift_hint.astype(jnp.float32))
    # one fused sweep of x: sibling reductions of (x-s) and (x-s)^2
    d = xf - chan(s, xf.ndim)
    m1 = jnp.sum(d, axis=axes) / n
    m2 = jnp.sum(d * d, axis=axes) / n
    mean = s + m1
    var = jnp.maximum(m2 - m1 * m1, 0.0)
    rstd = lax.rsqrt(var + eps)
    scale = gamma.astype(jnp.float32) * rstd
    shift = beta.astype(jnp.float32) - mean * scale
    # single FMA pass in the compute dtype
    y = x * chan(scale.astype(x.dtype), x.ndim) + \
        chan(shift.astype(x.dtype), x.ndim)
    return (y, mean, var), (x, gamma, mean, rstd)


def _bn_bwd(eps, res, cots):
    # _dmean/_dvar deliberately discarded — see the VJP contract in the
    # bn_train_fused docstring (statistics outputs are non-differentiable).
    dy, _dmean, _dvar = cots
    x, gamma, mean, rstd = res
    axes = tuple(range(x.ndim - 1))
    n = 1
    for a in axes:
        n *= x.shape[a]
    dyf = dy.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    xhat = (xf - chan(mean, xf.ndim)) * chan(rstd, xf.ndim)
    # pass 1: both reductions share the same inputs -> one HBM sweep
    dbeta = jnp.sum(dyf, axis=axes)
    dgamma = jnp.sum(dyf * xhat, axis=axes)
    # pass 2: dx by the analytic formula
    g32 = gamma.astype(jnp.float32)
    k = chan((g32 * rstd).astype(x.dtype), x.ndim)
    dx = k * (dy
              - chan((dbeta / n).astype(x.dtype), x.ndim)
              - (xhat * chan((dgamma / n).astype(x.dtype), x.ndim)))
    return (dx, dgamma.astype(gamma.dtype), dbeta.astype(gamma.dtype),
            jnp.zeros_like(mean))
    # zero cotangent for shift_hint: it only conditions the arithmetic


def _bn_train_fused_fwd(x, gamma, beta, shift_hint, eps):
    (y, mean, var), res = _bn_fwd_impl(x, gamma, beta, shift_hint, eps)
    return (y, mean, var), res


bn_train_fused.defvjp(_bn_train_fused_fwd, _bn_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def bn_add_act_train_fused(x, gamma, beta, shift_hint, res, eps, act):
    """Fused ``act(batchnorm(x) + res)`` training op — the residual-block
    tail (BN → ElementWise add → ReLU) executed as one HBM pass instead of
    three, used by the ComputationGraph fusion pass (nn/graph/fusion.py).

    ``act`` is 'relu' or 'identity' (static). Returns ``(y, mean, var)``."""
    out, _res = _bn_add_act_fwd_impl(x, gamma, beta, shift_hint, res, eps,
                                     act)
    return out


def _bn_add_act_fwd_impl(x, gamma, beta, shift_hint, res, eps, act):
    axes = tuple(range(x.ndim - 1))
    n = 1
    for a in axes:
        n *= x.shape[a]
    xf = x.astype(jnp.float32)
    s = lax.stop_gradient(shift_hint.astype(jnp.float32))
    d = xf - chan(s, xf.ndim)
    m1 = jnp.sum(d, axis=axes) / n
    m2 = jnp.sum(d * d, axis=axes) / n
    mean = s + m1
    var = jnp.maximum(m2 - m1 * m1, 0.0)
    rstd = lax.rsqrt(var + eps)
    scale = gamma.astype(jnp.float32) * rstd
    shift = beta.astype(jnp.float32) - mean * scale
    y = x * chan(scale.astype(x.dtype), x.ndim) + \
        chan(shift.astype(x.dtype), x.ndim) + res
    if act == "relu":
        y = jnp.maximum(y, 0)
    return (y, mean, var), (x, gamma, mean, rstd, y)


def _bn_add_act_bwd(eps, act, resids, cots):
    dy, _dmean, _dvar = cots
    x, gamma, mean, rstd, y = resids
    if act == "relu":
        dy = jnp.where(y > 0, dy, jnp.zeros_like(dy))
    dres = dy
    axes = tuple(range(x.ndim - 1))
    n = 1
    for a in axes:
        n *= x.shape[a]
    dyf = dy.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    xhat = (xf - chan(mean, xf.ndim)) * chan(rstd, xf.ndim)
    dbeta = jnp.sum(dyf, axis=axes)
    dgamma = jnp.sum(dyf * xhat, axis=axes)
    g32 = gamma.astype(jnp.float32)
    k = chan((g32 * rstd).astype(x.dtype), x.ndim)
    dx = k * (dy
              - chan((dbeta / n).astype(x.dtype), x.ndim)
              - (xhat * chan((dgamma / n).astype(x.dtype), x.ndim)))
    return (dx, dgamma.astype(gamma.dtype), dbeta.astype(gamma.dtype),
            jnp.zeros_like(mean), dres)


def _bn_add_act_fused_fwd(x, gamma, beta, shift_hint, res, eps, act):
    out, resids = _bn_add_act_fwd_impl(x, gamma, beta, shift_hint, res, eps,
                                       act)
    return out, resids


bn_add_act_train_fused.defvjp(_bn_add_act_fused_fwd, _bn_add_act_bwd)


def register_default(platforms=("tpu",)) -> None:
    """Install behind the helper seam (auto-called by the registry's lazy
    discovery on TPU backends; the built-in path stays the default on CPU so
    helper-vs-builtin tests compare against it)."""
    from ..nn.helpers import register_helper
    register_helper("batchnorm_train", bn_train_fused, platforms)
    register_helper("batchnorm_add_act_train", bn_add_act_train_fused,
                    platforms)
