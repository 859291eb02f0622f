"""Fused LSTM recurrence as a Pallas TPU kernel — the framework's analog of
the cuDNN LSTM helper the reference's north star asks for (SURVEY.md §2.2
note 2: no CudnnLSTMHelper exists at the reference snapshot; LSTMHelpers.java
:57/:271 is the seam to accelerate).

The input projection ``x @ W + b`` is one large MXU matmul done OUTSIDE the
kernel (XLA already tiles it optimally). The kernel fuses the sequential
part: per-timestep ``h @ R``, gate math, and state update, with ``h``/``c``
held in VMEM scratch across the whole sequence — the HBM round-trips of the
carry that a ``lax.scan`` pays every step are what this removes.

Grid = (T,); TPU grid execution is sequential, so VMEM scratch legally
carries state between steps. Supported fast path: sigmoid gates + tanh cell
(the Graves/cuDNN configuration), with or without peepholes. The layer-level
helper falls back to the reference ``_lstm_scan`` for masks or exotic
activations.

Training: ``jax.custom_vjp`` — forward runs the kernel; backward re-derives
the VJP through the pure-jnp recurrence (rematerialized), so gradients are
EXACTLY those of the reference path the equivalence tests check against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import _interpret_default


def _recurrence_jnp(xw_t, R, h0, c0, peep):
    """Reference recurrence (delegates to the single shared implementation
    in nn/conf/layers/recurrent.py so kernel gradients can never drift from
    the built-in path)."""
    from ..nn.conf.layers.recurrent import _lstm_recurrence
    return _lstm_recurrence(xw_t, R, peep, h0, c0, None,
                            jax.nn.sigmoid, jnp.tanh)


def _make_kernel(peephole: bool):
    def kernel(xw_ref, r_ref, h0_ref, c0_ref, *refs):
        if peephole:
            pi_ref, pf_ref, po_ref = refs[:3]
            refs = refs[3:]
        y_ref, hT_ref, cT_ref, h_scr, c_scr = refs
        t = pl.program_id(0)

        @pl.when(t == 0)
        def _():
            h_scr[:] = h0_ref[:]
            c_scr[:] = c0_ref[:]

        h_prev = h_scr[:]
        c_prev = c_scr[:]
        pre = xw_ref[0] + jnp.dot(h_prev, r_ref[:],
                                  preferred_element_type=jnp.float32)
        H = h_prev.shape[-1]
        pre_i = pre[:, :H]
        pre_f = pre[:, H:2 * H]
        pre_g = pre[:, 2 * H:3 * H]
        pre_o = pre[:, 3 * H:]
        if peephole:
            pre_i = pre_i + c_prev * pi_ref[:][None, :]
            pre_f = pre_f + c_prev * pf_ref[:][None, :]
        i = jax.nn.sigmoid(pre_i)
        f = jax.nn.sigmoid(pre_f)
        g = jnp.tanh(pre_g)
        c = f * c_prev + i * g
        if peephole:
            pre_o = pre_o + c * po_ref[:][None, :]
        o = jax.nn.sigmoid(pre_o)
        h = (o * jnp.tanh(c)).astype(h_scr.dtype)
        c = c.astype(c_scr.dtype)
        h_scr[:] = h
        c_scr[:] = c
        y_ref[0] = h

        @pl.when(t == pl.num_programs(0) - 1)
        def _():
            hT_ref[:] = h
            cT_ref[:] = c

    return kernel


def _pallas_forward(xw_t, R, h0, c0, peep):
    T, N, H4 = xw_t.shape
    H = H4 // 4
    dtype = xw_t.dtype
    peephole = peep is not None
    vec = pl.BlockSpec((H,), lambda t: (0,), memory_space=pltpu.VMEM)
    in_specs = [
        pl.BlockSpec((1, N, H4), lambda t: (t, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((H, H4), lambda t: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((N, H), lambda t: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((N, H), lambda t: (0, 0), memory_space=pltpu.VMEM),
    ]
    args = [xw_t, R, h0, c0]
    if peephole:
        in_specs += [vec, vec, vec]
        args += list(peep)
    interpret = _interpret_default()
    out = pl.pallas_call(
        _make_kernel(peephole),
        name="lstm_cell",
        grid=(T,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, N, H), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((N, H), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((N, H), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, N, H), dtype),
            jax.ShapeDtypeStruct((N, H), dtype),
            jax.ShapeDtypeStruct((N, H), dtype),
        ],
        scratch_shapes=[pltpu.VMEM((N, H), dtype),
                        pltpu.VMEM((N, H), dtype)],
        interpret=interpret,
    )(*args)
    return tuple(out)   # match the reference recurrence's (y, hT, cT) pytree


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def _fused(xw_t, R, h0, c0, pi, pf, po):
    peep = None if pi is None else (pi, pf, po)
    return _pallas_forward(xw_t, R, h0, c0, peep)


def _fused_fwd(xw_t, R, h0, c0, pi, pf, po):
    return _fused(xw_t, R, h0, c0, pi, pf, po), (xw_t, R, h0, c0, pi, pf, po)


def _fused_bwd(res, grads):
    xw_t, R, h0, c0, pi, pf, po = res

    def ref(xw_t, R, h0, c0, pi, pf, po):
        peep = None if pi is None else (pi, pf, po)
        return _recurrence_jnp(xw_t, R, h0, c0, peep)

    _, vjp_fn = jax.vjp(ref, xw_t, R, h0, c0, pi, pf, po)
    return vjp_fn(grads)


_fused.defvjp(_fused_fwd, _fused_bwd)


def lstm_helper(conf, params, x, h0, c0, mask):
    """Registered ``lstm`` helper: (layer conf, params, x [N,T,nIn], h0, c0,
    mask) → (y [N,T,H], hT, cT). Falls back to the pure scan for configs the
    kernel doesn't cover — mirroring the reference helpers' silent fallback
    (ConvolutionLayer.java:69-76)."""
    from ..nn.conf.layers.recurrent import _lstm_scan
    gate = getattr(conf, "gate_activation", "sigmoid")
    cell = conf.activation or "tanh"
    peep = (params["pi"], params["pf"], params["po"]) \
        if getattr(conf, "peephole", False) and "pi" in params else None
    # Auto-select (r2 honest measurements, char-RNN 2x512 B64 T128): the
    # fused kernel wins by ~5% in f32 (12.5 vs 13.1 ms/step) but loses by
    # ~6% in bf16 (8.6 vs 8.1) — XLA's scan lowering already keeps h/c
    # resident and fuses the gate math, and in bf16 its layout choices for
    # the small per-step [B,4H] recurrent matmul beat the kernel's. So:
    # low-precision inputs take the scan, f32 takes the kernel.
    # (f64 — gradient-check precision — also takes the scan)
    if mask is not None or gate != "sigmoid" or cell != "tanh" \
            or x.dtype != jnp.float32:
        gate_act, cell_act = conf._acts()
        return _lstm_scan(conf, params["W"], params["R"], params["b"], peep,
                          x, h0, c0, mask, gate_act, cell_act)
    n, t, _ = x.shape
    H = conf.n_out
    xw = (x.reshape(n * t, -1) @ params["W"]).reshape(n, t, 4 * H) \
        + params["b"][None, None, :]
    xw_t = jnp.transpose(xw, (1, 0, 2))
    pi, pf, po = peep if peep is not None else (None, None, None)
    y_t, hT, cT = _fused(xw_t, params["R"], h0, c0, pi, pf, po)
    return jnp.transpose(y_t, (1, 0, 2)), hT, cT


def register_lstm_helper(platforms=("tpu", "cpu")) -> None:
    """Install the fused kernel behind the layer helper seam (the analog of
    dropping deeplearning4j-cuda on the classpath). OPT-IN only: honest r2
    measurements showed XLA's scan beats this kernel at char-RNN shapes
    (BASELINE.md), so it is deliberately absent from the lazy default
    providers in nn/helpers."""
    from ..nn.helpers import register_helper
    register_helper("lstm", lstm_helper, platforms)
