"""Batched skip-gram / CBOW training steps (reference
models/embeddings/learning/impl/elements/{SkipGram,CBOW}.java; the reference
batches pairs into a native ``AggregateSkipGram`` op executed on the
executioner (SkipGram.java:271-279, SURVEY.md §3.5) — here the batch is a
fixed-shape device array and one jitted XLA step does the whole aggregate:
gather → dot → sigmoid loss → scatter-add updates.

Both hierarchical softmax (padded Huffman code rows) and negative sampling
are implemented; updates use ``.at[].add`` scatters, which XLA lowers to
efficient TPU scatter ops. Learning-rate is passed per step (the word2vec
linear decay lives in the caller)."""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax



def _scatter_mean_add(table, idx, updates, lr):
    """Add lr * (per-row summed updates / sqrt(occurrence count)) — the
    stable batched analog of word2vec's sequential per-pair updates. Plain
    scatter-ADD amplifies hot rows (the Huffman root appears in every pair's
    path) linearly in batch size and diverges; full mean-normalization
    under-trains (one batch collapses to one step). sqrt scaling matches the
    variance growth of accumulated same-direction noise and empirically
    preserves word2vec convergence at standard learning rates across batch
    sizes (see tests/test_nlp_graph.py topic-similarity oracle)."""
    return _segment_update(table, idx, updates,
                           jnp.ones(idx.shape, table.dtype), lr)

@functools.partial(jax.jit, static_argnames=("hs",), donate_argnums=(0, 1))
def skipgram_hs_step(syn0, syn1, centers, targets, codes, points, lengths,
                     lr, hs: bool = True):
    """Hierarchical-softmax skip-gram batch.

    syn0 [V, D] input vectors; syn1 [V-1, D] inner-node vectors;
    centers [B] int32; targets [B] int32 (the word whose code we predict);
    codes [B, L] float 0/1; points [B, L] int32; lengths [B] int32.
    Returns (syn0, syn1, mean_loss).
    """
    h = syn0[centers]                              # [B, D]
    pts = points                                   # [B, L]
    v = syn1[pts]                                  # [B, L, D]
    dots = jnp.einsum("bd,bld->bl", h, v)
    mask = (jnp.arange(codes.shape[1])[None, :] <
            lengths[:, None]).astype(syn0.dtype)   # [B, L]
    # word2vec: label = 1 - code; grad_scale = (label - sigma(dot))
    label = 1.0 - codes
    sig = jax.nn.sigmoid(dots)
    g = (label - sig) * mask                       # [B, L]
    loss = -jnp.sum(mask * jnp.log(jnp.clip(
        jnp.where(label > 0.5, sig, 1.0 - sig), 1e-10, 1.0))) / \
        jnp.maximum(jnp.sum(mask), 1.0)
    dh = jnp.einsum("bl,bld->bd", g, v)            # neu1e
    dv = jnp.einsum("bl,bd->bld", g, h)
    syn0 = _scatter_mean_add(syn0, centers, dh, lr)
    syn1 = _scatter_mean_add(syn1, pts.reshape(-1),
                             dv.reshape(-1, dv.shape[-1]), lr)
    return syn0, syn1, loss


def _skipgram_ns_core(syn0, syn1neg, centers, pos, negs, lr):
    h = syn0[centers]                              # [B, D]
    tgt = jnp.concatenate([pos[:, None], negs], axis=1)   # [B, 1+K]
    label = jnp.concatenate(
        [jnp.ones_like(pos[:, None], dtype=syn0.dtype),
         jnp.zeros(negs.shape, syn0.dtype)], axis=1)
    v = syn1neg[tgt]                               # [B, 1+K, D]
    dots = jnp.einsum("bd,bkd->bk", h, v)
    sig = jax.nn.sigmoid(dots)
    g = label - sig
    loss = -jnp.mean(jnp.log(jnp.clip(
        jnp.where(label > 0.5, sig, 1.0 - sig), 1e-10, 1.0)))
    dh = jnp.einsum("bk,bkd->bd", g, v)
    dv = jnp.einsum("bk,bd->bkd", g, h)
    syn0 = _scatter_mean_add(syn0, centers, dh, lr)
    syn1neg = _scatter_mean_add(syn1neg, tgt.reshape(-1),
                                dv.reshape(-1, dv.shape[-1]), lr)
    return syn0, syn1neg, loss


@functools.partial(jax.jit, donate_argnums=(0, 1))
def skipgram_ns_step(syn0, syn1neg, centers, pos, negs, lr):
    """Negative-sampling skip-gram batch.

    centers [B], pos [B], negs [B, K] sampled negatives.
    syn1neg [V, D] output vectors. Returns (syn0, syn1neg, mean_loss)."""
    return _skipgram_ns_core(syn0, syn1neg, centers, pos, negs, lr)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def cbow_hs_step(syn0, syn1, context, context_mask, target, codes, points,
                 lengths, lr):
    """CBOW with hierarchical softmax: context [B, C] int32 (padded),
    context_mask [B, C], target [B]."""
    cm = context_mask.astype(syn0.dtype)
    vecs = syn0[context] * cm[..., None]           # [B, C, D]
    denom = jnp.maximum(jnp.sum(cm, axis=1, keepdims=True), 1.0)
    h = jnp.sum(vecs, axis=1) / denom              # [B, D]
    v = syn1[points]
    dots = jnp.einsum("bd,bld->bl", h, v)
    lmask = (jnp.arange(codes.shape[1])[None, :] <
             lengths[:, None]).astype(syn0.dtype)
    label = 1.0 - codes
    sig = jax.nn.sigmoid(dots)
    g = (label - sig) * lmask
    loss = -jnp.sum(lmask * jnp.log(jnp.clip(
        jnp.where(label > 0.5, sig, 1.0 - sig), 1e-10, 1.0))) / \
        jnp.maximum(jnp.sum(lmask), 1.0)
    dh = jnp.einsum("bl,bld->bd", g, v)            # [B, D]
    dv = jnp.einsum("bl,bd->bld", g, h)
    syn1 = _scatter_mean_add(syn1, points.reshape(-1),
                             dv.reshape(-1, dv.shape[-1]), lr)
    dctx = (dh / denom)[:, None, :] * cm[..., None]     # distribute to context
    syn0 = _scatter_mean_add(syn0, context.reshape(-1),
                             dctx.reshape(-1, dctx.shape[-1]), lr)
    return syn0, syn1, loss


@functools.partial(jax.jit, static_argnames=("k",),
                   donate_argnums=(0, 1))
def skipgram_ns_step_rng(syn0, syn1neg, centers, pos, neg_table, key, lr,
                         k: int):
    """Negative-sampling step with ON-DEVICE negative draws: the unigram
    table stays device-resident and negatives are sampled inside the jitted
    program (one fold of ``key`` per step), removing the host RNG + transfer
    from the hot loop (the AggregateSkipGram throughput analog,
    SURVEY.md §7 hard-parts #4)."""
    negs = neg_table[jax.random.randint(key, (centers.shape[0], k), 0,
                                        neg_table.shape[0])]
    return _skipgram_ns_core(syn0, syn1neg, centers, pos, negs, lr)


# bounds for the one-hot matmul segment-sum: the update runs on the MXU
# (O(B·V) one-hot contraction — duplicate-index scatters serialize on hot
# zipf rows, the matmul doesn't) only while BOTH the vocab axis and the
# total one-hot footprint stay small; beyond either bound the one-hot
# HBM traffic exceeds the scatter cost (e.g. HS updates with B·L rows at a
# large V would materialize multi-GB one-hots) and the scatter path wins
ONEHOT_SEGMENT_MAX_V = 32768
ONEHOT_SEGMENT_MAX_ELEMS = 1 << 28        # bf16 one-hot cap: 512 MB


def _segment_update(table, idx, updates, weights, lr):
    """table[v] += lr * Σ_{i: idx_i=v} updates_i / sqrt(Σ weights_i) — the
    sqrt-count-normalized segment update behind every embedding table write.
    MXU one-hot contraction for small problems, scatter-add otherwise."""
    V = table.shape[0]
    if V <= ONEHOT_SEGMENT_MAX_V and \
            int(idx.shape[0]) * V <= ONEHOT_SEGMENT_MAX_ELEMS:
        oh = jax.nn.one_hot(idx, V, dtype=jnp.bfloat16)          # [B, V]
        u = jnp.concatenate(
            [updates.astype(jnp.bfloat16), weights[:, None].astype(
                jnp.bfloat16)], axis=1)                          # [B, D+1]
        r = lax.dot_general(oh, u, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [V, D+1]
        sums = r[:, :-1].astype(table.dtype)
        counts = r[:, -1].astype(table.dtype)
    else:
        counts = jnp.zeros((V,), table.dtype).at[idx].add(weights)
        sums = jnp.zeros_like(table).at[idx].add(updates)
    return table + lr * sums / jnp.sqrt(jnp.maximum(counts, 1.0))[:, None]


def _masked_ns_update(syn0, syn1neg, centers, ctx, valid, negs, lr, dtype):
    """Negative-sampling update over a FIXED-SHAPE masked pair block
    [B] centers, [B] contexts, [B] validity. Invalid pairs contribute zero
    gradient and zero occurrence count, so padding/out-of-window/cross-
    sentence slots are exactly neutral."""
    vm = valid.astype(dtype)
    c_safe = jnp.where(valid, centers, 0)
    t_safe = jnp.where(valid, ctx, 0)
    h = syn0[c_safe]                                    # [B, D]
    tgt = jnp.concatenate([t_safe[:, None], negs], axis=1)   # [B, 1+K]
    label = jnp.concatenate(
        [jnp.ones((len(c_safe), 1), dtype),
         jnp.zeros(negs.shape, dtype)], axis=1)
    v = syn1neg[tgt]                                    # [B, 1+K, D]
    dots = jnp.einsum("bd,bkd->bk", h, v)
    sig = jax.nn.sigmoid(dots)
    g = (label - sig) * vm[:, None]
    loss_sum = -jnp.sum(vm[:, None] * jnp.log(jnp.clip(
        jnp.where(label > 0.5, sig, 1.0 - sig), 1e-10, 1.0)))
    dh = jnp.einsum("bk,bkd->bd", g, v)
    dv = jnp.einsum("bk,bd->bkd", g, h)
    # sqrt-count normalization counting only VALID occurrences
    syn0 = _segment_update(syn0, c_safe, dh, vm, lr)
    syn1neg = _segment_update(
        syn1neg, tgt.reshape(-1), dv.reshape(-1, dv.shape[-1]),
        jnp.repeat(vm, tgt.shape[1]), lr)
    return syn0, syn1neg, loss_sum, jnp.sum(vm)


def _masked_ns_update_shared(syn0, syn1neg, centers, ctx, valid, negs, lr,
                             dtype):
    """Shared-negative variant: the SAME ``k`` negative rows serve every
    pair in the block (the BlazingText / GPU-word2vec batching of
    word2vec.c's per-pair draws). Per-pair expectation of the gradient is
    unchanged; what changes is covariance within one step. The payoff on
    TPU is structural: the [B, K, D] row-gather of per-pair negatives (the
    dominant HBM cost of the scan — ~64 GB per 2M-token chunk) becomes a
    [B,D]x[D,K] MXU matmul against a K-row table slice.

    negs: [K] shared negative indices."""
    vm = valid.astype(dtype)
    c_safe = jnp.where(valid, centers, 0)
    t_safe = jnp.where(valid, ctx, 0)
    h = syn0[c_safe]                                    # [B, D]
    vpos = syn1neg[t_safe]                              # [B, D]
    vneg = syn1neg[negs]                                # [K, D]
    dot_pos = jnp.sum(h * vpos, axis=1)                 # [B]
    dots_neg = h @ vneg.T                               # [B, K] (MXU)
    sig_pos = jax.nn.sigmoid(dot_pos)
    sig_neg = jax.nn.sigmoid(dots_neg)
    g_pos = (1.0 - sig_pos) * vm                        # [B]
    g_neg = -sig_neg * vm[:, None]                      # [B, K]
    loss_sum = -(jnp.sum(vm * jnp.log(jnp.clip(sig_pos, 1e-10, 1.0))) +
                 jnp.sum(vm[:, None] * jnp.log(jnp.clip(1.0 - sig_neg,
                                                        1e-10, 1.0))))
    dh = g_pos[:, None] * vpos + g_neg @ vneg           # [B, D]
    syn0 = _segment_update(syn0, c_safe, dh, vm, lr)
    # positive rows: per-pair scatter; negative rows: dense [K, D] grad
    syn1neg = _segment_update(syn1neg, t_safe, g_pos[:, None] * h, vm, lr)
    dv_neg = g_neg.T @ h                                # [K, D]
    neg_counts = jnp.full((negs.shape[0],), jnp.sum(vm), dtype)
    syn1neg = syn1neg.at[negs].add(
        lr * dv_neg / jnp.sqrt(jnp.maximum(neg_counts, 1.0))[:, None])
    return syn0, syn1neg, loss_sum, jnp.sum(vm)


@functools.partial(jax.jit,
                   static_argnames=("k", "window", "n_steps", "p",
                                    "shared_negatives"),
                   donate_argnums=(0, 1))
def skipgram_ns_corpus_scan(syn0, syn1neg, corpus, sep_cum, neg_table, key,
                            start_step, lr0, lr_min, frac0, frac_per_step,
                            k: int, window: int, n_steps: int, p: int,
                            shared_negatives: bool = True):
    """Whole-chunk skip-gram NS training as ONE device program (the
    AggregateSkipGram role, SkipGram.java:271-279, redesigned TPU-first).

    The indexed corpus (−1 sentence separators, padded with −1 so that
    every step's window read stays in range) is shipped to the device ONCE;
    a ``lax.scan`` walks it in slices of ``p`` center positions starting at
    position ``start_step*p``. Each step gathers the 2·window contexts per
    center, masks them by dynamic-window draw / separator crossing
    (``sep_cum`` prefix-sum guard) / validity, samples negatives on device,
    and applies the masked segment-sum update. ``n_steps`` is a FIXED
    segment size — callers loop ``start_step`` over the corpus, so one
    compilation serves any corpus length (compile time, not compute, was
    the end-to-end bottleneck: ~10 s vs ~2.5 ms/step marginal).

    No host transfer or dispatch happens inside the loop; per 32k-pair
    step this removes ~0.5 MB of pair traffic + one host round-trip
    (BASELINE.md r2/r3 accounting).

    lr decays linearly in scan progress: lr(i) = max(lr0*(1−frac0−
    i*frac_per_step), lr_min) — word2vec's schedule by tokens seen.
    ``key`` is the per-chunk BASE key; the per-segment fold_in(key,
    start_step) happens INSIDE the program — an eager fold_in per segment
    cost ~1 s of dispatch each (BASELINE.md r4).
    Returns (syn0, syn1neg, loss_sum, pair_count)."""
    key = jax.random.fold_in(key, start_step)
    dtype = syn0.dtype
    offs = jnp.asarray([d * sgn for d in range(1, window + 1)
                        for sgn in (-1, 1)], jnp.int32)       # [2W]
    dmag = jnp.asarray([d for d in range(1, window + 1)
                        for _ in (0, 1)], jnp.int32)          # [2W]

    def body(carry, i):
        syn0, syn1neg, key, loss_sum, cnt = carry
        pos = (start_step + i) * p + window + jnp.arange(p)   # [p]
        centers = corpus[pos]
        cum_c = sep_cum[pos]
        key, kb, kn = jax.random.split(key, 3)
        b = jax.random.randint(kb, (p,), 1, window + 1)
        idx = pos[:, None] + offs[None, :]                    # [p, 2W]
        ctx = corpus[idx]
        valid = ((centers >= 0)[:, None] & (ctx >= 0) &
                 (sep_cum[idx] == cum_c[:, None]) &
                 (b[:, None] >= dmag[None, :]))
        ctx = ctx.reshape(-1)
        valid = valid.reshape(-1)
        cflat = jnp.repeat(centers, 2 * window)
        frac = frac0 + (start_step + i).astype(dtype) * frac_per_step
        lr = jnp.maximum(lr0 * (1.0 - jnp.minimum(frac, 1.0)), lr_min)
        if shared_negatives:
            negs = neg_table[jax.random.randint(
                kn, (k,), 0, neg_table.shape[0])]
            syn0, syn1neg, ls, n = _masked_ns_update_shared(
                syn0, syn1neg, cflat, ctx, valid, negs, lr, dtype)
        else:
            negs = neg_table[jax.random.randint(
                kn, (cflat.shape[0], k), 0, neg_table.shape[0])]
            syn0, syn1neg, ls, n = _masked_ns_update(
                syn0, syn1neg, cflat, ctx, valid, negs, lr, dtype)
        return (syn0, syn1neg, key, loss_sum + ls, cnt + n), None

    (syn0, syn1neg, _, loss_sum, cnt), _ = lax.scan(
        body, (syn0, syn1neg, key, jnp.asarray(0.0, dtype),
               jnp.asarray(0.0, dtype)), jnp.arange(n_steps))
    return syn0, syn1neg, loss_sum, cnt


@functools.partial(jax.jit,
                   static_argnames=("window", "n_steps", "p"),
                   donate_argnums=(0, 1))
def skipgram_hs_corpus_scan(syn0, syn1, corpus, sep_cum, codes_tab,
                            points_tab, lengths_tab, key, start_step,
                            lr0, lr_min, frac0, frac_per_step,
                            window: int, n_steps: int, p: int):
    """Hierarchical-softmax sibling of :func:`skipgram_ns_corpus_scan`:
    Huffman code/point tables stay device-resident ([V, L]) and are gathered
    per target inside the scan (per-segment key fold inside the program,
    like the NS scan)."""
    key = jax.random.fold_in(key, start_step)
    dtype = syn0.dtype
    L = codes_tab.shape[1]
    offs = jnp.asarray([d * sgn for d in range(1, window + 1)
                        for sgn in (-1, 1)], jnp.int32)
    dmag = jnp.asarray([d for d in range(1, window + 1)
                        for _ in (0, 1)], jnp.int32)

    def body(carry, i):
        syn0, syn1, key, loss_sum, cnt = carry
        pos = (start_step + i) * p + window + jnp.arange(p)
        centers = corpus[pos]
        cum_c = sep_cum[pos]
        key, kb = jax.random.split(key)
        b = jax.random.randint(kb, (p,), 1, window + 1)
        idx = pos[:, None] + offs[None, :]
        ctx = corpus[idx]
        valid = ((centers >= 0)[:, None] & (ctx >= 0) &
                 (sep_cum[idx] == cum_c[:, None]) &
                 (b[:, None] >= dmag[None, :]))
        ctx = ctx.reshape(-1)
        valid = valid.reshape(-1)
        cflat = jnp.repeat(centers, 2 * window)
        vm = valid.astype(dtype)
        c_safe = jnp.where(valid, cflat, 0)
        t_safe = jnp.where(valid, ctx, 0)
        h = syn0[c_safe]                               # [B, D]
        codes = codes_tab[t_safe]                      # [B, L]
        pts = points_tab[t_safe]                       # [B, L]
        lens = lengths_tab[t_safe]                     # [B]
        lmask = ((jnp.arange(L)[None, :] < lens[:, None]) &
                 valid[:, None]).astype(dtype)
        v = syn1[pts]                                  # [B, L, D]
        dots = jnp.einsum("bd,bld->bl", h, v)
        label = 1.0 - codes
        sig = jax.nn.sigmoid(dots)
        g = (label - sig) * lmask
        loss_sum_b = -jnp.sum(lmask * jnp.log(jnp.clip(
            jnp.where(label > 0.5, sig, 1.0 - sig), 1e-10, 1.0)))
        dh = jnp.einsum("bl,bld->bd", g, v)
        dv = jnp.einsum("bl,bd->bld", g, h)
        frac = frac0 + (start_step + i).astype(dtype) * frac_per_step
        lr = jnp.maximum(lr0 * (1.0 - jnp.minimum(frac, 1.0)), lr_min)
        syn0 = _segment_update(syn0, c_safe, dh, vm, lr)
        syn1 = _segment_update(syn1, pts.reshape(-1),
                               dv.reshape(-1, dv.shape[-1]),
                               lmask.reshape(-1), lr)
        return (syn0, syn1, key, loss_sum + loss_sum_b,
                cnt + jnp.sum(vm)), None

    (syn0, syn1, _, loss_sum, cnt), _ = lax.scan(
        body, (syn0, syn1, key, jnp.asarray(0.0, dtype),
               jnp.asarray(0.0, dtype)), jnp.arange(n_steps))
    return syn0, syn1, loss_sum, cnt


def generate_skipgram_pairs(indexed_seq: np.ndarray, window: int,
                            rng: np.random.Generator,
                            dynamic_window: bool = True
                            ) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side pair generation: (center, context) with word2vec's random
    window shrink (reference SkipGram.learnSequence iteration order)."""
    centers, contexts = [], []
    n = len(indexed_seq)
    for i in range(n):
        b = rng.integers(1, window + 1) if dynamic_window else window
        lo, hi = max(0, i - b), min(n, i + b + 1)
        for j in range(lo, hi):
            if j != i:
                centers.append(indexed_seq[i])
                contexts.append(indexed_seq[j])
    return (np.asarray(centers, np.int32), np.asarray(contexts, np.int32))


def vectorized_skipgram_pairs(corpus: np.ndarray, window: int,
                              rng: np.random.Generator,
                              dynamic_window: bool = True
                              ) -> Tuple[np.ndarray, np.ndarray]:
    """Corpus-wide vectorized pair generation. ``corpus`` is the whole
    (sub-sampled) token-index stream with ``-1`` sentence separators; one
    numpy pass per window offset replaces the per-token Python loop of
    :func:`generate_skipgram_pairs` (~3 orders of magnitude faster on large
    corpora, same (center, context) multiset given the same window draws)."""
    corpus = np.asarray(corpus, np.int32)
    n = len(corpus)
    if n < 2:
        return (np.zeros(0, np.int32), np.zeros(0, np.int32))
    b = rng.integers(1, window + 1, n) if dynamic_window \
        else np.full(n, window)
    # segment id per position: a pair is valid only within one sentence —
    # endpoint checks alone would let d>=2 windows jump a short sentence
    seg = np.cumsum(corpus < 0)
    centers, contexts = [], []
    for d in range(1, window + 1):
        # context d positions to the right of the center...
        c, t, bb = corpus[:n - d], corpus[d:], b[:n - d]
        same = seg[:n - d] == seg[d:]
        valid = (c >= 0) & (t >= 0) & same & (bb >= d)
        centers.append(c[valid])
        contexts.append(t[valid])
        # ...and d positions to the left
        c, t, bb = corpus[d:], corpus[:n - d], b[d:]
        valid = (c >= 0) & (t >= 0) & same & (bb >= d)
        centers.append(c[valid])
        contexts.append(t[valid])
    return (np.concatenate(centers), np.concatenate(contexts))


def vectorized_cbow_windows(corpus: np.ndarray, window: int,
                            rng: np.random.Generator,
                            dynamic_window: bool = True):
    """Corpus-wide CBOW window extraction: returns (targets [M],
    context [M, 2*window] zero-padded, context_mask [M, 2*window]).
    Separator-aware like :func:`vectorized_skipgram_pairs`."""
    corpus = np.asarray(corpus, np.int32)
    n = len(corpus)
    if n < 2:
        return (np.zeros(0, np.int32),
                np.zeros((0, 2 * window), np.int32),
                np.zeros((0, 2 * window), np.float32))
    b = rng.integers(1, window + 1, n) if dynamic_window \
        else np.full(n, window)
    seg = np.cumsum(corpus < 0)     # same-sentence guard as skip-gram pairs
    ctx = np.full((n, 2 * window), -1, np.int32)
    slot = 0
    for d in range(1, window + 1):
        for sign in (-1, 1):
            src = np.full(n, -1, np.int32)
            same = np.zeros(n, bool)
            if sign < 0:
                src[d:] = corpus[:n - d]
                same[d:] = seg[d:] == seg[:n - d]
            else:
                src[:n - d] = corpus[d:]
                same[:n - d] = seg[:n - d] == seg[d:]
            ctx[:, slot] = np.where((b >= d) & same, src, -1)
            slot += 1
    mask = ctx >= 0
    rows = (corpus >= 0) & mask.any(axis=1)
    ctx = ctx[rows]
    mask = mask[rows]
    return (corpus[rows],
            np.where(mask, ctx, 0).astype(np.int32),
            mask.astype(np.float32))


@functools.partial(jax.jit, static_argnames=("k",),
                   donate_argnums=(0, 1))
def cbow_ns_step_rng(syn0, syn1neg, context, context_mask, target,
                     neg_table, key, lr, k: int):
    """CBOW negative-sampling step with on-device negative draws (see
    skipgram_ns_step_rng)."""
    negs = neg_table[jax.random.randint(key, (target.shape[0], k), 0,
                                        neg_table.shape[0])]
    return _cbow_ns_core(syn0, syn1neg, context, context_mask, target, negs,
                         lr)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def cbow_ns_step(syn0, syn1neg, context, context_mask, target, negs, lr):
    """CBOW with negative sampling: mean-of-context hidden vector, same
    pos/neg head as skip-gram NS, gradient distributed over the context."""
    return _cbow_ns_core(syn0, syn1neg, context, context_mask, target, negs,
                         lr)


def _cbow_ns_core(syn0, syn1neg, context, context_mask, target, negs, lr):
    cm = context_mask.astype(syn0.dtype)
    vecs = syn0[context] * cm[..., None]
    denom = jnp.maximum(jnp.sum(cm, axis=1, keepdims=True), 1.0)
    h = jnp.sum(vecs, axis=1) / denom
    tgt = jnp.concatenate([target[:, None], negs], axis=1)
    label = jnp.concatenate(
        [jnp.ones_like(target[:, None], dtype=syn0.dtype),
         jnp.zeros(negs.shape, syn0.dtype)], axis=1)
    v = syn1neg[tgt]
    dots = jnp.einsum("bd,bkd->bk", h, v)
    sig = jax.nn.sigmoid(dots)
    g = label - sig
    loss = -jnp.mean(jnp.log(jnp.clip(
        jnp.where(label > 0.5, sig, 1.0 - sig), 1e-10, 1.0)))
    dh = jnp.einsum("bk,bkd->bd", g, v)
    dv = jnp.einsum("bk,bd->bkd", g, h)
    syn1neg = _scatter_mean_add(syn1neg, tgt.reshape(-1),
                                dv.reshape(-1, dv.shape[-1]), lr)
    dctx = (dh / denom)[:, None, :] * cm[..., None]
    syn0 = _scatter_mean_add(syn0, context.reshape(-1),
                             dctx.reshape(-1, dctx.shape[-1]), lr)
    return syn0, syn1neg, loss
