"""What ``TransformerDecoder`` asks of the layers it walks, and what the slab
and the paged programs share: a model of plain attention with one expert
layer reads back the same columns from both kinds of block (tokens and
expert counters), and layers that are no subclass of the stock embedding
and attention classes decode through the same walk by offering ``embed`` /
``advance``."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import (SlotGenerationEngine,
                                       TransformerDecoder, generate,
                                       transformer_lm_conf)
from deeplearning4j_tpu.models.generation import MOE_COUNTERS
from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import (LayerNormalization,
                                               RnnOutputLayer,
                                               RoutedExpertsLayer,
                                               SelfAttentionLayer)
from deeplearning4j_tpu.nn.conf.layers.base import BaseRecurrentLayerConf
from deeplearning4j_tpu.nn.graph import ComputationGraph

VOCAB, D, T_MAX = 61, 32, 64
PROMPTS = [np.arange(5) % VOCAB, (np.arange(9) * 7) % VOCAB,
           (np.arange(13) * 11 + 3) % VOCAB]


def _lm_conf(swap=None):
    """``transformer_lm_conf`` at a tiny size; ``swap(name, layer)`` may
    hand back another layer for a vertex."""
    conf = transformer_lm_conf(VOCAB, D, 4, 2, max_length=T_MAX)
    for name, v in conf.vertices.items():
        layer = swap(name, v.layer) if swap and hasattr(v, "layer") else None
        if layer is not None:
            v.layer = layer
    return conf


@pytest.fixture(scope="module")
def expert_net():
    """Plain ``SelfAttentionLayer`` blocks, the second FFN a
    ``RoutedExpertsLayer``: a paged pool AND expert counters."""
    experts = RoutedExpertsLayer(n_in=D, n_out=D, num_experts=4, top_k=2,
                                 expert_hidden=16, activation="identity")
    return ComputationGraph(_lm_conf(
        lambda name, layer: experts if name == "ffn1" else None)).init()


@pytest.mark.parametrize("block,sentinel", [(1, True), (4, False)])
def test_paged_and_slab_blocks_of_an_expert_model_read_back_the_same(
        expert_net, block, sentinel):
    """Same requests, same greedy tokens, so the same assignments: ALL the
    ``moe_*`` counters of the two engines are equal, with every lane held to
    the end and with a lane idle at times — an idle lane still routes, over
    what its cache holds (stale rows on the slab, the null page in a pool),
    but its choices reach no expert, so what is read is what was hit.
    A plain slab engine at K = 1 takes ``decode_step_impl``, which counts
    nothing (ROADMAP Design 4); with the sentinel on, both engines take a
    block of one, and the verdict column sits between tokens and counters."""
    dec = TransformerDecoder(expert_net, t_max=T_MAX, sentinel=sentinel)
    assert dec.moe_names == ["ffn1"]
    want = [generate(expert_net, p, 9, temperature=0, bucket=T_MAX)
            for p in PROMPTS]
    for slots in (3, 2):
        stats = {}
        for paged in (False, True):
            eng = SlotGenerationEngine(
                expert_net, decoder=dec, num_slots=slots, block_size=block,
                seed=0, paged=paged, page_size=8, integrity=sentinel or None)
            reqs = [eng.submit(p, 9) for p in PROMPTS]
            eng.run_until_drained()
            for r, w in zip(reqs, want):
                np.testing.assert_array_equal(r.result(0), w)
            stats[paged] = eng.stats()
        assert stats[True]["moe_assignments"] > 0
        for k in MOE_COUNTERS:
            assert stats[True][k] == stats[False][k], (slots, k)
        assert stats[True]["moe_experts_read"] == \
            stats[True]["moe_experts_hit"]


# ---- layers that are what they offer, not what they inherit ----
@dataclasses.dataclass
class LookupWithPositions(BaseRecurrentLayerConf):
    """An embedding of its own: no ``TokenAndPositionEmbedding`` above it."""
    max_length: int = T_MAX

    def get_output_type(self, it):
        return InputType.recurrent(self.n_out, it.timesteps)

    def init_params(self, key, dtype=jnp.float32):
        return {"W": jnp.zeros((self.n_in, self.n_out), dtype),
                "P": jnp.zeros((self.max_length, self.n_out), dtype)}

    def _rows(self, params, ids, pos):
        pos = jnp.minimum(pos, self.max_length - 1)
        return params["W"][ids.astype(jnp.int32)] + params["P"][pos]

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        return self._rows(params, x, jnp.arange(x.shape[1])[None]), state

    def embed(self, params, ids, window):
        if window.start is None:
            return self.forward(params, None, ids)[0]
        if window.valid is None:                       # one token a row
            return self._rows(params, ids, window.start)[:, None]
        return self._rows(params, ids, window.start[:, None]
                          + jnp.arange(ids.shape[1])[None])


@dataclasses.dataclass
class Delegating(BaseRecurrentLayerConf):
    """Keeps sequence state by handing every question to a
    ``SelfAttentionLayer`` it holds; it is no subclass of one."""
    inner: SelfAttentionLayer = None

    def get_output_type(self, it):
        return self.inner.get_output_type(it)

    def init_params(self, key, dtype=jnp.float32):
        return self.inner.init_params(key, dtype)

    def forward(self, params, state, x, **kw):
        return self.inner.forward(params, state, x, **kw)

    def __getattr__(self, name):   # advance, init_cache, causal, ...
        if name == "inner":
            raise AttributeError(name)
        return getattr(self.inner, name)


@pytest.fixture(scope="module")
def stock_and_standin():
    stock = ComputationGraph(_lm_conf()).init()

    def swap(name, layer):
        if name == "embed":
            return LookupWithPositions(n_in=VOCAB, n_out=D)
        if isinstance(layer, SelfAttentionLayer):
            return Delegating(n_in=D, n_out=D, inner=layer)
    standin = ComputationGraph(_lm_conf(swap)).init()
    standin.params = stock.params            # same names, same shapes
    return stock, standin


def test_standin_layers_decode_as_the_stock_classes_do(stock_and_standin):
    stock, standin = stock_and_standin
    dec = TransformerDecoder(standin, t_max=T_MAX)
    assert dec.attn_names == ["attn0", "attn1"] and dec.moe_names == []
    assert type(dec.embed) is LookupWithPositions
    assert dec.kv_heads_per_row == TransformerDecoder(stock).kv_heads_per_row
    want = [generate(stock, p, 9, temperature=0, bucket=T_MAX)
            for p in PROMPTS]
    for got in (dec.generate(PROMPTS, 9, temperature=0.0, block_size=1),
                dec.generate(PROMPTS, 9, temperature=0.0, block_size=4)):
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
    for kw in ({}, {"paged": True, "page_size": 8},
               {"prefill_chunk": 4, "speculative": True, "spec_k": 4}):
        eng = SlotGenerationEngine(standin, decoder=dec, num_slots=2,
                                   block_size=4, seed=0, **kw)
        reqs = [eng.submit(p, 9) for p in PROMPTS]
        eng.run_until_drained()
        for r, w in zip(reqs, want):
            np.testing.assert_array_equal(r.result(0), w)


def _graph(*layers):
    g = (NeuralNetConfiguration.Builder().seed(1).learning_rate(0.1)
         .updater("sgd").weight_init("xavier").graph_builder()
         .add_inputs("in"))
    prev = "in"
    for name, layer in layers:
        g.add_layer(name, layer, prev)
        prev = name
    g.set_outputs(prev)
    return ComputationGraph(g.build()).init()


HEAD = ("out", RnnOutputLayer(n_in=D, n_out=VOCAB, loss="mcxent",
                              activation="softmax"))
ATTN = SelfAttentionLayer(n_in=D, n_out=D, num_heads=4, causal=True)


@pytest.mark.parametrize("layers,message", [
    ([("ln", LayerNormalization(n_in=D, n_out=D)), ("attn", ATTN), HEAD],
     "not a decoder LM"),                              # no embedding
    ([("embed", LookupWithPositions(n_in=VOCAB, n_out=D)),
      ("ln", LayerNormalization(n_in=D, n_out=D)), HEAD],
     "not a decoder LM"),                              # no sequence layer
    ([("embed", LookupWithPositions(n_in=VOCAB, n_out=D)),
      ("attn", dataclasses.replace(ATTN, causal=False)), HEAD],
     "is not causal"),
], ids=["no-embedding", "no-sequence-layer", "not-causal"])
def test_decoder_rejects_by_what_the_layers_offer(layers, message):
    with pytest.raises(ValueError, match=message):
        TransformerDecoder(_graph(*layers))


def test_decoder_rejects_a_preprocessor():
    net = ComputationGraph(_lm_conf()).init()
    net.conf.vertices["ln0a"].preprocessor = object()
    with pytest.raises(ValueError, match="has a preprocessor"):
        TransformerDecoder(net)
