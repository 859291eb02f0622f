"""Decoder-only transformer language model — the TPU-era flagship for the
long-context story (SURVEY.md §5.7: the reference's only long-sequence
mechanism is truncated BPTT; ring attention / sequence parallelism are the
extensions this framework designs fresh). Built entirely from framework
layers: TokenAndPositionEmbedding → pre-LN blocks (LayerNormalization →
causal SelfAttentionLayer → residual add → LayerNormalization →
TransformerFeedForward → residual add) → final LN → RnnOutputLayer with
next-token cross-entropy.

Sequence-parallel long contexts run the same attention math through the
ring trainer (parallel/sequence.py) over ICI."""

from __future__ import annotations

from typing import Optional

import numpy as np

from ..nn.conf.config import NeuralNetConfiguration
from ..nn.conf.layers import (GatedFeedForward, LatentAttentionLayer,
                              LayerNormalization, Mamba2Layer,
                              RMSNormalization, RnnOutputLayer,
                              RoutedExpertsLayer, SelfAttentionLayer,
                              TokenAndPositionEmbedding, TokenEmbedding,
                              TransformerFeedForward)
from ..nn.graph.computation_graph import ComputationGraph
from ..nn.graph.vertices import ElementWiseVertex


def transformer_lm_conf(vocab_size: int, d_model: int = 128,
                        num_heads: int = 4, num_layers: int = 2,
                        ff_mult: int = 4, max_length: int = 256,
                        drop_out: float = 0.0, learning_rate: float = 3e-4,
                        seed: int = 42):
    """ComputationGraphConfiguration for a GPT-style causal LM.

    Input: token ids [N, T] (named input "tokens"); output: next-token
    distribution [N, T, vocab] (train with labels shifted left one step —
    see :func:`lm_batch`). ``drop_out`` follows the framework-wide
    DL4J convention: it is the RETENTION probability (0 disables
    dropout)."""
    g = (NeuralNetConfiguration.Builder().seed(seed)
         .learning_rate(learning_rate).updater("adam").weight_init("xavier")
         .graph_builder()
         .add_inputs("tokens"))
    keep = drop_out      # retention probability, like every layer conf
    g.add_layer("embed",
                TokenAndPositionEmbedding(n_in=vocab_size, n_out=d_model,
                                          max_length=max_length,
                                          drop_out=keep),
                "tokens")
    x = "embed"
    for i in range(num_layers):
        g.add_layer(f"ln{i}a",
                    LayerNormalization(n_in=d_model, n_out=d_model), x)
        g.add_layer(f"attn{i}",
                    SelfAttentionLayer(n_in=d_model, n_out=d_model,
                                       num_heads=num_heads, causal=True,
                                       drop_out=keep,
                                       activation="identity"),
                    f"ln{i}a")
        g.add_vertex(f"res{i}a", ElementWiseVertex(op="add"), x, f"attn{i}")
        g.add_layer(f"ln{i}b",
                    LayerNormalization(n_in=d_model, n_out=d_model),
                    f"res{i}a")
        g.add_layer(f"ffn{i}",
                    TransformerFeedForward(n_in=d_model, n_out=d_model,
                                           hidden_mult=ff_mult,
                                           drop_out=keep,
                                           activation="identity"),
                    f"ln{i}b")
        g.add_vertex(f"res{i}b", ElementWiseVertex(op="add"),
                     f"res{i}a", f"ffn{i}")
        x = f"res{i}b"
    g.add_layer("lnf", LayerNormalization(n_in=d_model, n_out=d_model), x)
    g.add_layer("out",
                RnnOutputLayer(n_in=d_model, n_out=vocab_size,
                               loss="mcxent", activation="softmax"), "lnf")
    g.set_outputs("out")
    return g.build()


def latent_moe_lm_conf(vocab_size: int, d_model: int, num_heads: int,
                       num_layers: int, *, q_rank: int, kv_rank: int,
                       nope_dim: int, rope_dim: int, v_dim: int,
                       dense_hidden: int, dense_layers: int = 1,
                       num_experts: int, top_k: int, expert_hidden: int,
                       shared_experts: int = 1, routed_scaling: float = 1.0,
                       first_expert: int = 0, experts_held: int = 0,
                       rope_theta: float = 10000.0, eps: float = 1e-6,
                       max_length: int = 4096, learning_rate: float = 3e-4,
                       seed: int = 42):
    """ComputationGraphConfiguration for a causal LM of pre-RMSNorm blocks
    with latent attention and routed experts:

        h <- h + LatentAttention(RMSNorm(h));  h <- h + FFN(RMSNorm(h))

    the first ``dense_layers`` blocks with a dense gated FFN of width
    ``dense_hidden``, the rest with ``num_experts`` routed experts (top
    ``top_k``, sigmoid scores, no drops) of width ``expert_hidden`` plus a
    shared expert; a token-only embedding (positions are rotary, inside
    attention), a final RMSNorm and an untied head with no bias. Vertices
    are named as :func:`transformer_lm_conf` names them. ``first_expert`` /
    ``experts_held`` give every expert layer its share of the experts (all
    by default). ``max_length`` is the context the model declares."""
    g = (NeuralNetConfiguration.Builder().seed(seed)
         .learning_rate(learning_rate).updater("adam").weight_init("xavier")
         .graph_builder()
         .add_inputs("tokens"))
    g.add_layer("embed", TokenEmbedding(n_in=vocab_size, n_out=d_model,
                                        max_length=max_length), "tokens")
    norm = lambda: RMSNormalization(n_in=d_model, n_out=d_model, eps=eps)
    x = "embed"
    for i in range(num_layers):
        g.add_layer(f"ln{i}a", norm(), x)
        g.add_layer(f"attn{i}",
                    LatentAttentionLayer(
                        n_in=d_model, n_out=d_model, num_heads=num_heads,
                        q_rank=q_rank, kv_rank=kv_rank, nope_dim=nope_dim,
                        rope_dim=rope_dim, v_dim=v_dim,
                        rope_theta=rope_theta, eps=eps,
                        activation="identity"),
                    f"ln{i}a")
        g.add_vertex(f"res{i}a", ElementWiseVertex(op="add"), x, f"attn{i}")
        g.add_layer(f"ln{i}b", norm(), f"res{i}a")
        if i < dense_layers:
            ffn = GatedFeedForward(n_in=d_model, n_out=d_model,
                                   hidden=dense_hidden,
                                   activation="identity")
        else:
            ffn = RoutedExpertsLayer(
                n_in=d_model, n_out=d_model, num_experts=num_experts,
                top_k=top_k, expert_hidden=expert_hidden,
                shared_experts=shared_experts,
                routed_scaling=routed_scaling, first_expert=first_expert,
                experts_held=experts_held, activation="identity")
        g.add_layer(f"ffn{i}", ffn, f"ln{i}b")
        g.add_vertex(f"res{i}b", ElementWiseVertex(op="add"),
                     f"res{i}a", f"ffn{i}")
        x = f"res{i}b"
    g.add_layer("lnf", norm(), x)
    g.add_layer("out",
                RnnOutputLayer(n_in=d_model, n_out=vocab_size, loss="mcxent",
                               activation="softmax", has_bias=False), "lnf")
    g.set_outputs("out")
    return g.build()


def shortcut_moe_lm_conf(vocab_size: int, d_model: int, num_heads: int,
                         num_layers: int, *, q_rank: int, kv_rank: int,
                         nope_dim: int, rope_dim: int, v_dim: int,
                         dense_hidden: int, num_experts: int,
                         zero_experts: int = 0, top_k: int,
                         expert_hidden: int, routed_scaling: float = 1.0,
                         first_expert: int = 0, experts_held: int = 0,
                         q_scale: float = 1.0, kv_scale: float = 1.0,
                         rope_theta: float = 10000.0, eps: float = 1e-5,
                         max_length: int = 4096, learning_rate: float = 3e-4,
                         seed: int = 42):
    """ComputationGraphConfiguration for a causal LM of shortcut-connected
    DOUBLE blocks: two latent attentions and two dense gated FFNs a layer,
    and one expert branch that leaves after the first attention and joins one
    attention and one FFN later (every norm an RMSNorm):

        a0 = x  + Attn_a(RMSNorm(x));   n0 = RMSNorm(a0)
        s  = MoE(n0)                    # vertex ``moe{i}``: the shortcut
        b0 = a0 + FFN_a(n0)
        a1 = b0 + Attn_b(RMSNorm(b0))
        x' = a1 + FFN_b(RMSNorm(a1)) + s

    Vertices of layer ``i``: ``ln{i}a..d``, ``attn{i}a`` / ``attn{i}b``,
    ``ffn{i}a`` / ``ffn{i}b`` (width ``dense_hidden``), ``moe{i}``,
    ``res{i}a..d`` (the last a three-input add). The expert branch routes by
    softmax over ``num_experts + zero_experts`` outputs, top ``top_k``,
    weights ``routed_scaling`` times the scores and not renormalised, the
    zero-compute experts the identity, no shared expert, no drops;
    ``first_expert`` / ``experts_held`` give it its share of the experts
    that have weights (all by default). ``q_scale`` / ``kv_scale`` scale the
    attentions' two normalised latents. A token-only embedding (positions
    are rotary, inside attention), a final RMSNorm and an untied head with
    no bias, as :func:`latent_moe_lm_conf` has them."""
    g = (NeuralNetConfiguration.Builder().seed(seed)
         .learning_rate(learning_rate).updater("adam").weight_init("xavier")
         .graph_builder()
         .add_inputs("tokens"))
    g.add_layer("embed", TokenEmbedding(n_in=vocab_size, n_out=d_model,
                                        max_length=max_length), "tokens")
    norm = lambda: RMSNormalization(n_in=d_model, n_out=d_model, eps=eps)
    attention = lambda: LatentAttentionLayer(
        n_in=d_model, n_out=d_model, num_heads=num_heads, q_rank=q_rank,
        kv_rank=kv_rank, nope_dim=nope_dim, rope_dim=rope_dim, v_dim=v_dim,
        rope_theta=rope_theta, eps=eps, q_scale=q_scale, kv_scale=kv_scale,
        activation="identity")
    dense = lambda: GatedFeedForward(n_in=d_model, n_out=d_model,
                                     hidden=dense_hidden,
                                     activation="identity")
    add = lambda: ElementWiseVertex(op="add")
    x = "embed"
    for i in range(num_layers):
        g.add_layer(f"ln{i}a", norm(), x)
        g.add_layer(f"attn{i}a", attention(), f"ln{i}a")
        g.add_vertex(f"res{i}a", add(), x, f"attn{i}a")
        g.add_layer(f"ln{i}b", norm(), f"res{i}a")
        g.add_layer(f"moe{i}",
                    RoutedExpertsLayer(
                        n_in=d_model, n_out=d_model, num_experts=num_experts,
                        zero_experts=zero_experts, top_k=top_k,
                        expert_hidden=expert_hidden, shared_experts=0,
                        score_function="softmax", renormalize=False,
                        routed_scaling=routed_scaling,
                        first_expert=first_expert,
                        experts_held=experts_held, activation="identity"),
                    f"ln{i}b")
        g.add_layer(f"ffn{i}a", dense(), f"ln{i}b")
        g.add_vertex(f"res{i}b", add(), f"res{i}a", f"ffn{i}a")
        g.add_layer(f"ln{i}c", norm(), f"res{i}b")
        g.add_layer(f"attn{i}b", attention(), f"ln{i}c")
        g.add_vertex(f"res{i}c", add(), f"res{i}b", f"attn{i}b")
        g.add_layer(f"ln{i}d", norm(), f"res{i}c")
        g.add_layer(f"ffn{i}b", dense(), f"ln{i}d")
        g.add_vertex(f"res{i}d", add(), f"res{i}c", f"ffn{i}b", f"moe{i}")
        x = f"res{i}d"
    g.add_layer("lnf", norm(), x)
    g.add_layer("out",
                RnnOutputLayer(n_in=d_model, n_out=vocab_size, loss="mcxent",
                               activation="softmax", has_bias=False), "lnf")
    g.set_outputs("out")
    return g.build()


def hybrid_ssm_lm_conf(vocab_size: int, d_model: int, num_heads: int,
                       num_kv_heads: int, layer_types, *, ffn_hidden: int,
                       ssm_heads: int, ssm_head_dim: int, ssm_state: int,
                       conv_kernel: int = 4, chunk_size: int = 256,
                       attention_scale: float = 0.0,
                       embedding_scale: float = 1.0,
                       residual_scale: float = 1.0,
                       logit_divisor: float = 1.0, eps: float = 1e-5,
                       tie_embeddings: bool = True, max_length: int = 4096,
                       learning_rate: float = 3e-4, seed: int = 42):
    """ComputationGraphConfiguration for a causal LM of pre-RMSNorm blocks
    whose mixer is, layer by layer as ``layer_types`` says, a Mamba-2
    state-space layer (``"mamba"``: vertex ``ssm{i}``) or grouped-query
    attention with no positional encoding (``"attention"``: ``attn{i}``,
    ``num_kv_heads`` KV heads, logits scaled by ``attention_scale``, no
    bias); every layer then a gated FFN of width ``ffn_hidden``:

        h <- h + r * Mixer(RMSNorm(h));   h <- h + r * FFN(RMSNorm(h))

    with ``r = residual_scale`` applied where the graph adds the branch
    (``res{i}a`` / ``res{i}b``). A token-only embedding times
    ``embedding_scale``; a final RMSNorm; the head tied to the embedding's
    table (``tie_embeddings``) with its logits divided by
    ``logit_divisor``. Norms and the Mamba gate norm use ``eps``."""
    g = (NeuralNetConfiguration.Builder().seed(seed)
         .learning_rate(learning_rate).updater("adam").weight_init("xavier")
         .graph_builder()
         .add_inputs("tokens"))
    g.add_layer("embed", TokenEmbedding(n_in=vocab_size, n_out=d_model,
                                        max_length=max_length,
                                        multiplier=embedding_scale), "tokens")
    norm = lambda: RMSNormalization(n_in=d_model, n_out=d_model, eps=eps)
    add = lambda: ElementWiseVertex(op="add", branch_scale=residual_scale)
    x = "embed"
    for i, kind in enumerate(layer_types):
        g.add_layer(f"ln{i}a", norm(), x)
        if kind == "mamba":
            mixer = f"ssm{i}"
            layer = Mamba2Layer(n_in=d_model, n_out=d_model,
                                num_heads=ssm_heads, head_dim=ssm_head_dim,
                                state_size=ssm_state, conv_kernel=conv_kernel,
                                chunk_size=chunk_size, eps=eps,
                                activation="identity")
        elif kind == "attention":
            mixer = f"attn{i}"
            layer = SelfAttentionLayer(n_in=d_model, n_out=d_model,
                                       num_heads=num_heads,
                                       num_kv_heads=num_kv_heads,
                                       scale=attention_scale, causal=True,
                                       bias=False, activation="identity")
        else:
            raise ValueError(f"layer {i}: unknown layer type {kind!r} "
                             "(\"mamba\" or \"attention\")")
        g.add_layer(mixer, layer, f"ln{i}a")
        g.add_vertex(f"res{i}a", add(), x, mixer)
        g.add_layer(f"ln{i}b", norm(), f"res{i}a")
        g.add_layer(f"ffn{i}", GatedFeedForward(n_in=d_model, n_out=d_model,
                                                hidden=ffn_hidden,
                                                activation="identity"),
                    f"ln{i}b")
        g.add_vertex(f"res{i}b", add(), f"res{i}a", f"ffn{i}")
        x = f"res{i}b"
    g.add_layer("lnf", norm(), x)
    g.add_layer("out",
                RnnOutputLayer(n_in=d_model, n_out=vocab_size, loss="mcxent",
                               activation="softmax", has_bias=False,
                               tied_to="embed" if tie_embeddings else "",
                               logit_divisor=logit_divisor), "lnf")
    g.set_outputs("out")
    return g.build()


def lm_batch_sparse(tokens: np.ndarray):
    """(features, integer labels) for next-token training from token ids
    [N, T+1] — the fused-CE path (kernels/fused_ce.py): labels stay [N, T]
    int32 (4 bytes/token) instead of the [N, T, V] one-hot (2·V bytes/token
    at bf16), and the graph train step fuses projection + softmax-CE."""
    return (np.asarray(tokens[:, :-1], np.int32),
            np.asarray(tokens[:, 1:], np.int32))


def lm_batch(tokens: np.ndarray, vocab_size: int):
    """(features, one-hot labels) for next-token training from token ids
    [N, T+1]: inputs are tokens[:, :-1], labels tokens[:, 1:]. The one-hot
    is built directly (np.eye at vocab 32k would transiently allocate a
    4 GB identity matrix)."""
    x = np.asarray(tokens[:, :-1], np.int32)
    tgt = np.asarray(tokens[:, 1:], np.int64)
    y = np.zeros(tgt.shape + (vocab_size,), np.float32)
    np.put_along_axis(y, tgt[..., None], 1.0, axis=-1)
    return x, y


def generate(net: ComputationGraph, prompt_ids, length: int,
             temperature: float = 1.0,
             rng: Optional[np.random.Generator] = None,
             bucket: Optional[int] = None) -> np.ndarray:
    """Autoregressive sampling WITHOUT a KV cache: every emitted token
    recomputes the full O(T²) forward over the padded bucket. This is the
    no-cache reference baseline (decode-vs-recompute A/B in
    BENCH_MODE=generate); the serving path is models/generation.py's
    TransformerDecoder, which prefills once and decodes O(T) per token.
    The context is right-padded to a fixed ``bucket`` length (default:
    the model's max_length) and the logit at the true last position is
    read — causal attention never looks right, so padding is invisible
    and every step reuses ONE compiled program (a growing context would
    recompile per token: seconds each). Greedy when
    temperature == 0."""
    rng = rng or np.random.default_rng(0)
    ids = list(np.asarray(prompt_ids, np.int32).reshape(-1))
    if bucket is None:
        embed = net.conf.vertices["embed"].layer
        bucket = getattr(embed, "max_length", len(ids) + length)
    for _ in range(length):
        t = len(ids)
        if t > bucket:
            raise ValueError(f"context {t} exceeds bucket {bucket}")
        ctx = np.zeros((1, bucket), np.int32)
        ctx[0, :t] = ids
        probs = np.asarray(net.output(ctx)[0])[0, t - 1]
        if temperature <= 0:
            nxt = int(np.argmax(probs))
        else:
            logits = np.log(np.maximum(probs, 1e-9)) / temperature
            p = np.exp(logits - logits.max())
            p /= p.sum()
            nxt = int(rng.choice(len(p), p=p))
        ids.append(nxt)
    return np.asarray(ids, np.int32)
