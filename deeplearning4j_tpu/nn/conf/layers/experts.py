"""Routed experts without drops: scores, a selection bias, top-k, scaled
weights, and what every token gets besides (a shared expert; zero-compute
experts).

    s = sigmoid(x Wr)  or  softmax(x Wr)     float32, over the router's width
    chosen = top_k(s + b)                    b: for choosing only
    g_i = scaling * s_i / sum_{j chosen} s_j     (``renormalize``; else
    g_i = scaling * s_i)
    y = sum_{i chosen, i < E} g_i E_i(x) + (sum_{i chosen, i >= E} g_i) x
        + E_shared(x),                       E = (silu(x Wg) * x Wu) Wd

The router is ``num_experts + zero_experts`` wide: a choice past the experts
that have weights is a ZERO-COMPUTE expert, the identity, and adds ``g x``
(float32, scope ``zero``). It has no weights and no home: every chip
computes it whole for its own tokens. ``shared_experts`` 0 leaves the shared
expert out. The defaults (sigmoid, renormalised, no zero-compute expert) are
the DeepSeek-V3 layer.

No capacity and no dropped token: every token is computed by every expert it
chose, whatever the load. The layer is told which experts it HOLDS
(``first_expert`` .. ``first_expert + experts_held``): it routes over all of
them, and returns the part its own experts give plus the shared expert's —
what one chip of an expert-parallel layer computes before the exchange (all
of them by default).

The second return (the layer's state) is an [E] int32 count of this call's
tokens by the expert they chose, under two names: ``expert_tokens``, of the
tokens a ``mask`` [N, T] marks (a decode block's alive lanes; every token
without one), and ``expert_rows``, of the choices that REACHED the experts
(the rows they computed, the weights that were read) — one count, since a
mask keeps the other tokens out; with zero-compute experts also
``zero_tokens``, one count of the marked tokens' choices among them (they
are no experts, and stay out of the [E] counts). Integer, not
differentiated; a decode block sums them into the engine's counters.

A mask keeps unmarked tokens OUT of the routed experts: after ``route`` and
before either way through the experts, an unmarked token's choices are cast
to an index past the router's width, which no chip holds — the kernel's
layout gives it no row and no tile, so an expert only unmarked tokens chose
is not read, and the dense path weighs it by zero. A decode step therefore
reads the experts its alive lanes chose, not those of all its lanes (the
engine's ``moe_experts_read`` equals its ``moe_experts_hit``). A marked
token's output is bit for bit what it is without
the mask: rows of a tile are independent, so who shares a tile changes
nothing. What stays dense over every token: the router product and the
shared expert (their weights are read once a call whatever the tokens) and
the zero-compute term (no weights), so an unmarked token's output is its
shared expert plus its identity share and nothing else. Nothing reads it —
a stopped lane re-emits its last id and the sentinel's verdict exempts it —
and nothing after ``route`` works across tokens, so whatever a stopped
lane's stale rows hold (a NaN too) stays in that lane. The same holds for a
training batch's padding mask: a padded token's output reaches no loss and
no other token, so a step's numbers do not move.

Two ways through the experts, one result: the built-in path is dense over
the experts held (every expert on every token, weighted by a gate that is
zero where it was not chosen) — the plain definition, right for a CPU at a
test's size; the ``routed_experts`` helper (kernels/expert_ffn.py, the TPU's
default) computes each token-expert pair once and reads only the experts
hit."""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp

from ...helpers import get_helper
from ..input_type import InputType
from ..serde import register_config
from .attention import gated_ffn, in_token_blocks, token_block
from .base import BaseRecurrentLayerConf

@register_config
@dataclasses.dataclass
class RoutedExpertsLayer(BaseRecurrentLayerConf):
    """Input [N, T, n_in] → [N, T, n_in]."""
    num_experts: int = 8
    top_k: int = 2
    expert_hidden: int = 0
    shared_experts: int = 1          # the shared expert is this many wide
    routed_scaling: float = 1.0
    first_expert: int = 0
    experts_held: int = 0            # 0: all of them
    score_function: str = "sigmoid"  # or "softmax", over the router's width
    renormalize: bool = True         # gates over the chosen sum to scaling
    zero_experts: int = 0            # identity experts past the weighted ones
    #: what the decode walk reads off the class: where a pass counts, it
    #: hands ``forward`` the alive lanes as ``mask`` (the others stay out of
    #: the experts) and keeps the counts
    counts_tokens = True

    def set_n_in(self, it: InputType) -> None:
        if not self.n_in:
            self.n_in = it.size
        if not self.n_out:
            self.n_out = self.n_in

    def _held(self) -> int:
        return self.experts_held or self.num_experts

    def _routed_over(self) -> int:
        """The router's width: every expert a token may choose."""
        return self.num_experts + self.zero_experts

    def init_params(self, key, dtype=jnp.float32) -> Dict:
        d, h, e = self.n_in, self.expert_hidden, self._held()
        ks = jax.random.split(key, 8)
        w = lambda k, shape: self._winit(k, shape, shape[-2], shape[-1],
                                         dtype)
        p = {"Wr": w(ks[0], (d, self._routed_over())),
             "b": jnp.zeros((self._routed_over(),), dtype),
             "Wg": w(ks[1], (e, d, h)), "Wu": w(ks[2], (e, d, h)),
             "Wd": w(ks[3], (e, h, d))}
        if self.shared_experts:
            hs = self.shared_experts * h
            p.update(Sg=w(ks[4], (d, hs)), Su=w(ks[5], (d, hs)),
                     Sd=w(ks[6], (hs, d)))
        return p

    def init_state(self) -> Dict:
        zero = jnp.zeros((self.num_experts,), jnp.int32)
        state = {"expert_tokens": zero, "expert_rows": zero}
        if self.zero_experts:
            state["zero_tokens"] = jnp.zeros((), jnp.int32)
        return state

    def regularizable(self):
        return ("Wg", "Wu", "Wd", "Sg", "Su", "Sd")

    # graftlint: traced
    def route(self, params, x):
        """x [N, d] → (chosen [N, k] int32, gates [N, k] f32)."""
        score = {"sigmoid": jax.nn.sigmoid,
                 "softmax": lambda z: jax.nn.softmax(z, axis=-1)}[
                     self.score_function]
        s = score(jnp.einsum(
            "nd,de->ne", x, params["Wr"],
            preferred_element_type=jnp.float32).astype(jnp.float32))
        _, chosen = jax.lax.top_k(s + params["b"].astype(jnp.float32)[None],
                                  self.top_k)
        picked = jnp.take_along_axis(s, chosen, axis=-1)
        if self.renormalize:
            gates = self.routed_scaling * picked / jnp.sum(picked, axis=-1,
                                                           keepdims=True)
        else:
            gates = self.routed_scaling * picked
        return chosen.astype(jnp.int32), gates

    # graftlint: traced
    def _dense(self, params, x, chosen, gates):
        """The experts held, every one on every token; [N, d] f32."""
        local = chosen - self.first_expert
        weight = jnp.sum(
            jax.nn.one_hot(local, self._held(), dtype=jnp.float32)
            * gates[..., None], axis=1)                       # [N, E]
        h = jax.nn.silu(jnp.einsum("nd,edh->neh", x, params["Wg"])) \
            * jnp.einsum("nd,edh->neh", x, params["Wu"])
        y = jnp.einsum("neh,ehd->ned", h, params["Wd"])
        return jnp.einsum("ned,ne->nd", y.astype(jnp.float32), weight)

    # graftlint: traced
    def _reached(self, chosen, marked):
        """``chosen`` [N, k] as it reaches the experts: an unmarked token's
        choices (``marked`` [N] bool; None marks all) cast past the router's
        width — an expert held nowhere, so they get no row and no weight."""
        return chosen if marked is None else jnp.where(
            marked[:, None], chosen, self._routed_over())

    # graftlint: traced
    def _block(self, params, x, marked=None):
        """x [N, d], marked [N] bool or None → (y [N, d], chosen [N, k])."""
        with jax.named_scope("route"):
            chosen, gates = self.route(params, x)
        with jax.named_scope("experts"):
            reached = self._reached(chosen, marked)
            helper = get_helper("routed_experts")
            if helper is not None:
                y = helper(x, reached, gates, params["Wg"], params["Wu"],
                           params["Wd"], self.first_expert,
                           self._routed_over())
            else:
                y = self._dense(params, x, reached, gates)
        if self.zero_experts:
            with jax.named_scope("zero"):
                identity = jnp.sum(jnp.where(chosen >= self.num_experts,
                                             gates, 0.0), axis=-1)
                y = y + identity[:, None] * x.astype(jnp.float32)
        if self.shared_experts:
            with jax.named_scope("shared"):
                y = y + gated_ffn(x, params["Sg"], params["Su"],
                                  params["Sd"]).astype(jnp.float32)
        return y.astype(x.dtype), chosen

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        shape = x.shape
        flat = x.reshape(-1, shape[-1])
        marked = None if mask is None else mask.reshape(-1) > 0
        arrays = (flat,) if marked is None else (flat, marked)
        # a long input in blocks: bounds the token-expert rows laid out
        y, chosen = in_token_blocks(lambda *blk: self._block(params, *blk),
                                    *arrays, block=token_block(self.top_k))
        # by expert, the choices that reached one: a cast choice, like a
        # zero-compute one, lies past the [E] counts and is dropped
        load = jnp.zeros((self.num_experts,), jnp.int32).at[
            self._reached(chosen, marked).reshape(-1)].add(
                jnp.repeat(jnp.ones((flat.shape[0],), jnp.int32),
                           self.top_k))
        counts = {"expert_tokens": load, "expert_rows": load}
        if self.zero_experts:
            zero = chosen >= self.num_experts
            counts["zero_tokens"] = jnp.sum(
                zero if marked is None else zero & marked[:, None],
                dtype=jnp.int32)
        return y.reshape(shape), counts
