"""Feed-forward layer family: Dense, Output(+Rnn/CenterLoss variants),
LossLayer, ActivationLayer, DropoutLayer, Embedding, AutoEncoder, RBM
(reference nn/conf/layers/* + nn/layers/{feedforward,training}/*;
SURVEY.md §2.1).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ....ops.losses import get_loss, compute_loss
from ....ops.shapes import chan
from ..input_type import InputType
from ..serde import register_config
from .base import FeedForwardLayerConf, LayerConf


@register_config
@dataclasses.dataclass
class DenseLayer(FeedForwardLayerConf):
    """Fully connected layer: act(x·W + b) (reference DenseLayer/BaseLayer
    preOutput gemm). The hot matmul maps straight onto the MXU."""

    def init_params(self, key, dtype=jnp.float32) -> Dict:
        kw, _ = jax.random.split(key)
        return {"W": self._winit(kw, (self.n_in, self.n_out), self.n_in,
                                 self.n_out, dtype),
                "b": self._binit((self.n_out,), dtype)}

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        pre = x @ params["W"] + chan(params["b"], x.ndim)
        return self.activation_fn()(pre), state


@register_config
@dataclasses.dataclass
class OutputLayer(DenseLayer):
    """Dense + loss head (reference OutputLayer/BaseOutputLayer). The loss is
    computed from the *pre-output* with the fused stable form (losses.py)."""
    loss: str = "mcxent"
    #: False: a projection with no bias (the head of an RMSNorm decoder);
    #: such a head takes the materialized loss, not the fused sparse CE
    has_bias: bool = True
    #: a vertex name: the head holds no weights and reads that vertex's
    #: table ``W`` [n_out, n_in] transposed (a head tied to the embedding);
    #: the graph hands it over as ``params["W_tied"]`` (:func:`head_params`)
    tied_to: str = ""
    #: logits divided by this (a tied head's ``logits_scaling``)
    logit_divisor: float = 1.0

    def init_params(self, key, dtype=jnp.float32) -> Dict:
        if self.tied_to:
            return {}
        p = super().init_params(key, dtype)
        if not self.has_bias:
            del p["b"]
        return p

    def compute_score(self, params, labels, preoutput, mask=None,
                      average: bool = True):
        return compute_loss(self.loss, labels, preoutput,
                            self.activation or "identity", mask, average)

    def preoutput(self, params, x):
        if self.tied_to:
            out = jnp.einsum("...d,vd->...v", x, params["W_tied"])
            if self.logit_divisor != 1.0:
                out = out / jnp.asarray(self.logit_divisor, out.dtype)
            return out
        if not self.has_bias:
            return x @ params["W"]
        return x @ params["W"] + chan(params["b"], x.ndim)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        return self.activation_fn()(self.preoutput(params, x)), state


@register_config
@dataclasses.dataclass
class RnnOutputLayer(OutputLayer):
    """Output layer applied per timestep to [N, T, F] input (reference
    RnnOutputLayer). Loss respects the label mask for variable length."""

    def input_kind(self) -> str:
        return "rnn"

    def set_n_in(self, it: InputType) -> None:
        if not self.n_in:
            self.n_in = it.size

    def get_output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.n_out, it.timesteps)


@register_config
@dataclasses.dataclass
class LossLayer(LayerConf):
    """Loss without params: applies activation + loss to its input directly
    (reference LossLayer)."""
    loss: str = "mse"

    def input_kind(self) -> str:
        return "any"

    def compute_score(self, params, labels, preoutput, mask=None,
                      average: bool = True):
        return compute_loss(self.loss, labels, preoutput,
                            self.activation or "identity", mask, average)

    def preoutput(self, params, x):
        return x

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        return self.activation_fn()(x), state


@register_config
@dataclasses.dataclass
class ActivationLayer(LayerConf):
    """Parameterless activation (reference ActivationLayer)."""

    def input_kind(self) -> str:
        return "any"

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        return self.activation_fn()(x), state


@register_config
@dataclasses.dataclass
class DropoutLayer(LayerConf):
    """Explicit dropout layer (reference DropoutLayer); drop_out is the
    retention probability."""

    def input_kind(self) -> str:
        return "any"

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        return self.maybe_dropout(x, train=train, rng=rng), state


@register_config
@dataclasses.dataclass
class EmbeddingLayer(FeedForwardLayerConf):
    """Index → vector lookup (reference EmbeddingLayer): input is int ids
    [N] or one-hot [N, nIn]; a gather, not a matmul — the TPU-native way."""

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        W = params["W"]
        if x.ndim >= 2 and x.shape[-1] == self.n_in:
            ids = jnp.argmax(x, axis=-1)        # one-hot input
        else:
            ids = x.astype(jnp.int32).reshape(x.shape[0])
        out = W[ids] + chan(params["b"], 2)
        return self.activation_fn()(out), state

    def init_params(self, key, dtype=jnp.float32) -> Dict:
        kw, _ = jax.random.split(key)
        return {"W": self._winit(kw, (self.n_in, self.n_out), self.n_in,
                                 self.n_out, dtype),
                "b": self._binit((self.n_out,), dtype)}


@register_config
@dataclasses.dataclass
class AutoEncoder(FeedForwardLayerConf):
    """Denoising autoencoder (reference nn/layers/feedforward/autoencoder/
    AutoEncoder.java): encode/decode with tied-ish params; pretrain minimizes
    reconstruction loss with input corruption."""
    corruption_level: float = 0.3
    sparsity: float = 0.0
    loss: str = "mse"

    def init_params(self, key, dtype=jnp.float32) -> Dict:
        kw, kv = jax.random.split(key)
        return {"W": self._winit(kw, (self.n_in, self.n_out), self.n_in,
                                 self.n_out, dtype),
                "b": self._binit((self.n_out,), dtype),
                "vb": jnp.zeros((self.n_in,), dtype)}

    def encode(self, params, x):
        return self.activation_fn()(x @ params["W"] + chan(params["b"], x.ndim))

    def decode(self, params, h):
        return self.activation_fn()(h @ params["W"].T + chan(params["vb"], h.ndim))

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        return self.encode(params, x), state

    def pretrain_loss(self, params, x, rng):
        corrupted = x
        if self.corruption_level > 0 and rng is not None:
            keep = jax.random.bernoulli(rng, 1.0 - self.corruption_level, x.shape)
            corrupted = x * keep
        h = self.encode(params, corrupted)
        recon_pre = h @ params["W"].T + chan(params["vb"], h.ndim)
        per = get_loss(self.loss)(x, recon_pre, self.activation or "sigmoid")
        return jnp.mean(per)


@register_config
@dataclasses.dataclass
class RBM(FeedForwardLayerConf):
    """Restricted Boltzmann machine (reference nn/layers/feedforward/rbm/RBM.java):
    forward = propup; pretrain = CD-1 contrastive divergence."""
    visible_unit: str = "binary"    # binary | gaussian
    hidden_unit: str = "binary"
    k: int = 1

    def init_params(self, key, dtype=jnp.float32) -> Dict:
        kw, _ = jax.random.split(key)
        return {"W": self._winit(kw, (self.n_in, self.n_out), self.n_in,
                                 self.n_out, dtype),
                "b": self._binit((self.n_out,), dtype),   # hidden bias
                "vb": jnp.zeros((self.n_in,), dtype)}     # visible bias

    def propup(self, params, v):
        return jax.nn.sigmoid(v @ params["W"] + chan(params["b"], v.ndim))

    def propdown(self, params, h):
        pre = h @ params["W"].T + chan(params["vb"], h.ndim)
        return pre if self.visible_unit == "gaussian" else jax.nn.sigmoid(pre)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        return self.activation_fn()(x @ params["W"] + chan(params["b"], x.ndim)), state

    def cd_gradient(self, params, v0, rng):
        """One CD-k step → param gradients (to be fed to the updater)."""
        h0 = self.propup(params, v0)
        hs = h0
        vk = v0
        for i in range(self.k):
            rng, k1 = jax.random.split(rng)
            hs = jax.random.bernoulli(k1, hs).astype(v0.dtype) \
                if self.hidden_unit == "binary" else hs
            vk = self.propdown(params, hs)
            hs = self.propup(params, vk)
        n = v0.shape[0]
        gw = -(v0.T @ h0 - vk.T @ hs) / n
        gb = -jnp.mean(h0 - hs, axis=0)
        gvb = -jnp.mean(v0 - vk, axis=0)
        return {"W": gw, "b": gb, "vb": gvb}

    def pretrain_loss(self, params, x, rng):
        # Reconstruction cross-entropy as the monitored pretrain score.
        h = self.propup(params, x)
        recon = self.propdown(params, h)
        eps = 1e-7
        if self.visible_unit == "gaussian":
            return jnp.mean((x - recon) ** 2)
        r = jnp.clip(recon, eps, 1 - eps)
        return -jnp.mean(x * jnp.log(r) + (1 - x) * jnp.log(1 - r))


@register_config
@dataclasses.dataclass
class CenterLossOutputLayer(OutputLayer):
    """Output layer with center loss (reference nn/layers/training/
    CenterLossOutputLayer.java): total = primary loss + (lambda/2)·||f - c_y||²;
    class centers live in layer *state* and move by ``alpha`` toward the batch
    class means — they are not gradient-trained, matching the reference."""
    alpha: float = 0.05
    lambda_: float = 2e-4

    def init_state(self) -> Dict:
        return {"centers": jnp.zeros((self.n_out, self.n_in), jnp.float32)}

    def center_loss_and_update(self, state, features, labels):
        centers = state["centers"]
        y = jnp.argmax(labels, axis=-1)
        c_y = centers[y]                                    # [N, nIn]
        diff = features - c_y
        loss = 0.5 * self.lambda_ * jnp.mean(jnp.sum(diff * diff, axis=-1))
        # centers_j += alpha * mean_{i: y_i=j}(f_i - c_j)
        counts = jnp.maximum(jnp.sum(labels, axis=0), 1.0)  # [nOut]
        sums = labels.T @ diff                               # [nOut, nIn]
        new_centers = centers + self.alpha * sums / counts[:, None]
        return loss, {"centers": new_centers}


def head_params(conf, params: Dict, name: str) -> Dict:
    """What vertex ``name``'s layer reads as its parameters: its own, or —
    for a head tied to another vertex's table (``OutputLayer.tied_to``) —
    that table under ``W_tied`` (one array, held once, trained by both)."""
    tied = getattr(getattr(conf.vertices[name], "layer", None), "tied_to", "")
    if not tied:
        return params[name]
    return {"W_tied": params[tied]["W"]}
