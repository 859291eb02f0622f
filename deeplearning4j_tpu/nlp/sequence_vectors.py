"""SequenceVectors: the generic embedding trainer (reference
models/sequencevectors/SequenceVectors.java, 1,218 LoC — vocab build :103,
AsyncSequencer prefetch :996, VectorCalculationsThread workers :1101,
pluggable learning algorithms :161-168; SURVEY.md §2.5, §3.5).

TPU redesign: the reference's thread pool + native AggregateSkipGram becomes
a host-side pair generator feeding fixed-size batches into ONE jitted scatter
step (skipgram.py). Elements learning algorithms: skipgram | cbow; sequence
learning algorithms (paragraph vectors): dbow | dm. Both HS and negative
sampling; word2vec's linear lr decay over total expected words."""

from __future__ import annotations

from typing import Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .huffman import apply_huffman, pad_codes
from .skipgram import (skipgram_hs_step, skipgram_ns_step,
                       skipgram_ns_step_rng, cbow_hs_step, cbow_ns_step,
                       cbow_ns_step_rng, generate_skipgram_pairs,
                       skipgram_hs_corpus_scan, skipgram_ns_corpus_scan,
                       vectorized_skipgram_pairs, vectorized_cbow_windows)
from .vocab import VocabCache, VocabConstructor



@jax.jit
def _stage_corpus(corpus_wire):
    """Device-side corpus staging for the scan path: upcast the (int16/
    int32) pre-padded wire corpus and compute the separator prefix-sum —
    one dispatch. The caller pads ON HOST to the quantized ``pad_len``
    (a cheap memcpy; wire cost of the -1 tail is ~2 bytes/slot), so this
    program has ONE shape per (n_steps-bucket, p) — a raw-length-shaped
    argument would recompile per chunk (~0.65 s each, measured r4)."""
    corpus_d = corpus_wire.astype(jnp.int32)
    return corpus_d, jnp.cumsum((corpus_d < 0).astype(jnp.int32))


class InMemoryLookupTable:
    """syn0/syn1/syn1neg arrays (reference
    models/embeddings/inmemory/InMemoryLookupTable)."""

    def __init__(self, vocab: VocabCache, vector_length: int, seed: int = 42,
                 use_hs: bool = True, negative: int = 0):
        self.vocab = vocab
        self.vector_length = vector_length
        V = len(vocab)
        rng = np.random.default_rng(seed)
        # word2vec init distribution (uniform(-0.5, 0.5)/dim). Generated
        # host-side in f32 and staged with an ASYNC device_put: the old
        # f64 jnp.asarray form paid a synchronous 2x-sized transfer plus an
        # on-device convert (~2 s of single-pass fixed cost, measured r4);
        # device-side jax.random was measured far worse (~12 s of
        # compile on that installation, BASELINE.md r4) — host f32 +
        # overlap wins.
        self.syn0 = jax.device_put(
            ((rng.random((V, vector_length), np.float32) - 0.5)
             / vector_length))
        self.syn1 = jnp.zeros((max(V - 1, 1), vector_length), jnp.float32) \
            if use_hs else None
        self.syn1neg = jnp.zeros((V, vector_length), jnp.float32) \
            if negative > 0 else None

    def vector(self, word: str) -> Optional[np.ndarray]:
        idx = self.vocab.index_of(word)
        if idx < 0:
            return None
        return np.asarray(self.syn0[idx])


class SequenceVectors:
    def __init__(self, vector_length: int = 100, window: int = 5,
                 min_word_frequency: int = 1, learning_rate: float = 0.025,
                 min_learning_rate: float = 1e-4, epochs: int = 1,
                 negative: int = 0, use_hierarchic_softmax: bool = True,
                 sample: float = 0.0, batch_size: int = 2048,
                 elements_algorithm: str = "skipgram", seed: int = 42,
                 shared_negatives: bool = True,
                 scan_min_tokens: Optional[int] = None):
        self.vector_length = vector_length
        self.window = window
        self.min_word_frequency = min_word_frequency
        self.learning_rate = learning_rate
        self.min_learning_rate = min_learning_rate
        self.epochs = epochs
        self.negative = negative
        self.use_hs = use_hierarchic_softmax or negative == 0
        self.sample = sample
        self.batch_size = batch_size
        self.elements_algorithm = elements_algorithm
        self.seed = seed
        # Negative-sampling variance tradeoff: the corpus-scan device program
        # (used at >= scan_min_tokens) defaults to drawing ONE set of k
        # negatives per ~32k-pair scan step (shared across the step — cheaper
        # table gathers, slightly correlated updates), while the per-batch
        # path draws per-pair negatives. Set shared_negatives=False to force
        # per-pair draws in the scan too, or scan_min_tokens to move/disable
        # the corpus-size switchover (word2vec.c itself draws per-pair).
        self.shared_negatives = bool(shared_negatives)
        if scan_min_tokens is not None:
            self.SCAN_MIN_TOKENS = int(scan_min_tokens)
        self.vocab: Optional[VocabCache] = None
        self.lookup: Optional[InMemoryLookupTable] = None
        self._codes = self._points = self._lengths = None
        self._neg_table = None

    # ------------------------------------------------------------------ fit
    def build_vocab(self, sequences: Iterable[List[str]]):
        self.vocab = VocabConstructor(self.min_word_frequency).build(sequences)
        if self.use_hs:
            apply_huffman(self.vocab)
            codes, points, lengths = pad_codes(self.vocab)
            self._codes = jnp.asarray(codes)
            self._points = jnp.asarray(points)
            self._lengths = jnp.asarray(lengths)
        if self.negative > 0:
            self._neg_table = self.vocab.unigram_table()
        self.lookup = InMemoryLookupTable(self.vocab, self.vector_length,
                                          self.seed, self.use_hs,
                                          self.negative)
        return self

    # tokens per vectorized chunk: bounds host memory for the pair set the
    # way the old streaming buffer did (~chunk * 2*window pairs in flight)
    CHUNK_TOKENS = 2_000_000

    def _index_chunks(self, sequences: Sequence[List[str]]):
        """Yield the corpus as int32 index streams with ``-1`` sentence
        separators (windows never cross a separator), in whole-sentence
        chunks of ~CHUNK_TOKENS so arbitrarily large corpora stream.

        One flat dict.get pass over a chained iterator with an interleaved
        separator sentinel — the per-sentence np.fromiter + double-lookup
        form cost ~1 s per 2M tokens of pure Python (BASELINE.md r4).
        Out-of-vocab words are DROPPED (-2 sentinel filtered out), never
        turned into separators: a trimmed word must not break window
        adjacency, matching the reference's vocab-filtered iteration."""
        lookup = {w: vw.index for w, vw in self.vocab.words.items()}
        # "\x00" is the interleaved separator sentinel (a pathological real
        # vocab word "\x00" would be treated as a separator)
        lookup["\x00"] = -1
        batch: List[List[str]] = []
        size = raw = 0
        for seq in sequences:
            batch.append(seq)
            size += len(seq)        # chunk threshold: tokens, like always —
            raw += len(seq) + 1     # a +1/sentence drift would move the
            # boundary and change the scan program's (cached) corpus shape
            if size >= self.CHUNK_TOKENS:
                yield self._index_batch(batch, lookup, raw)
                batch, size, raw = [], 0, 0
        if batch:
            yield self._index_batch(batch, lookup, raw)

    @staticmethod
    def _index_batch(batch, lookup, count) -> np.ndarray:
        from itertools import chain
        get = lookup.get
        it = chain.from_iterable(chain(s, ("\x00",)) for s in batch)
        arr = np.fromiter((get(w, -2) for w in it), np.int32, count=count)
        return arr[arr != -2]                     # drop out-of-vocab words

    def fit(self, sequences: Sequence[List[str]]):
        """Train over the corpus (reference SequenceVectors.fit).

        The reference's thread pool + native AggregateSkipGram becomes:
        vectorized corpus-wide window extraction (one numpy pass per window
        offset), shuffled fixed-size batches, and one jitted scatter step per
        batch with on-device negative sampling — no per-token Python and no
        host sync inside the loop."""
        if self.vocab is None:
            self.build_vocab(sequences)
        rng = np.random.default_rng(self.seed)
        keep = self.vocab.subsample_keep_prob(self.sample)
        total = max(self.vocab.total_word_count * self.epochs, 1)
        seen = 0
        loss = None
        import jax
        base_key = jax.random.PRNGKey(self.seed)
        chunk_id = 0
        for epoch in range(self.epochs):
            for corpus in self._index_chunks(sequences):
                if keep is not None and len(corpus):
                    m = rng.random(len(corpus)) < np.where(
                        corpus >= 0, keep[np.maximum(corpus, 0)], 1.0)
                    corpus = corpus[m]
                ntokens = int((corpus >= 0).sum())
                nskey = jax.random.fold_in(base_key, chunk_id)
                chunk_id += 1
                if self.elements_algorithm == "cbow":
                    tgt, ctx, cmask = vectorized_cbow_windows(
                        corpus, self.window, rng)
                    perm = rng.permutation(len(tgt))
                    loss = self._run_cbow(tgt[perm], ctx[perm], cmask[perm],
                                          seen, ntokens, total, nskey)
                elif (self.use_hs and self.negative > 0) or \
                        ntokens < self.SCAN_MIN_TOKENS:
                    # combined HS+NS, or a small corpus: per-batch path with
                    # globally shuffled pairs (better mixing; dispatch
                    # overhead is irrelevant at this size)
                    c, t = vectorized_skipgram_pairs(corpus, self.window,
                                                     rng)
                    perm = rng.permutation(len(c))
                    loss = self._run_skipgram(c[perm], t[perm], seen,
                                              ntokens, total, nskey)
                else:
                    # single-objective skip-gram at scale: the whole chunk
                    # trains as segmented device programs in corpus order
                    # (word2vec.c's own order) — per-batch host transfers
                    # and dispatch round-trips are the bottleneck here
                    loss = self._run_skipgram_scan(corpus, seen, ntokens,
                                                   total, nskey)
                seen += ntokens
        if loss is not None:
            import os as _os
            if _os.environ.get("DL4J_W2V_TRACE") == "1":
                import time as _time
                t0 = _time.perf_counter()
                self._last_loss = float(loss)
                print(f"  final device sync (drain): "
                      f"{_time.perf_counter() - t0:.3f}s", flush=True)
            else:
                self._last_loss = float(loss)   # one sync, at the end
        return self

    def _lr_now(self, seen: float, total: int) -> float:
        """word2vec linear decay by tokens seen."""
        frac = min(seen / max(total, 1), 1.0)
        return max(self.learning_rate * (1.0 - frac), self.min_learning_rate)

    @staticmethod
    def _pad(a: np.ndarray, size: int) -> np.ndarray:
        if len(a) == size:
            return a
        pad = np.zeros((size - len(a),) + a.shape[1:], a.dtype)
        return np.concatenate([a, pad])
        # padded entries train word 0 on itself once per epoch — negligible,
        # and shapes stay static for jit

    # corpora below this size train via the shuffled per-batch path; the
    # corpus-scan program pays off only when transfer+dispatch per batch
    # dominates (large chunks)
    SCAN_MIN_TOKENS = 100_000

    # scan steps per program dispatch: the (n_steps, p) pair is static, so
    # EVERY corpus length reuses one compilation — the callers loop
    # ``start_step`` in SEG-sized segments (compile ~10 s dominated the
    # end-to-end time; marginal cost is ~2.5 ms/step). Large corpora run
    # SUPER_SEGMENT-step programs first (fewer program dispatches),
    # with SEGMENT-step programs for the tail.
    SCAN_SEGMENT = 64
    SCAN_SUPER_SEGMENT = 512

    def _run_skipgram_scan(self, corpus, seen, ntokens, total, nskey):
        """Whole-chunk skip-gram as jitted lax.scan programs: the corpus
        crosses the host→device boundary once (4 bytes/token) instead of
        ~2·window·8 bytes of pair traffic plus a dispatch round-trip per
        batch (the 73k tokens/s bottleneck, BASELINE.md r2/r3).

        Update granularity follows ``batch_size`` exactly like the per-batch
        path: each scan step covers ~batch_size/(2·window) center positions,
        so the sqrt-count-normalized update count per epoch is unchanged —
        one giant step would silently under-train small corpora."""
        from ..ops.platform import configure_compilation_cache
        configure_compilation_cache(min_compile_secs=0.0)
        lt = self.lookup
        window = self.window
        p = max(32, self.batch_size // (2 * window))
        seg = self.SCAN_SEGMENT
        n = len(corpus)
        n_steps = max((n + p - 1) // p, 1)
        n_total = (n_steps + seg - 1) // seg * seg
        # Stage the corpus at int16 when the vocab allows (ids and the -1
        # separator fit; halves the bytes) and build the separator
        # prefix-sum ON DEVICE in ONE jitted call: the padded int32 corpus
        # plus host-side cumsum shipped ~18 MB host-to-device (~4.5 s of
        # the 2M-token single pass on the r4 installation's slow link),
        # and separate eager staging ops cost ~1 s of dispatch/
        # compile-lookup EACH there (both measured, BASELINE.md r4).
        wire = np.int16 if len(self.vocab) < 2 ** 15 else np.int32
        pad_len = n_total * p + 2 * window
        padded = np.full((pad_len,), -1, wire)
        padded[window:window + n] = corpus
        corpus_d, sep_d = _stage_corpus(jax.device_put(padded))
        frac0 = seen / max(total, 1)
        frac_per_step = (ntokens / max(total, 1)) / n_steps
        # host numpy scalars: a jnp.float32(x) wrapper is an EAGER device
        # op (one dispatch each); np scalars ride along
        # with the jitted call for free
        lr0 = np.float32(self.learning_rate)
        lr_min = np.float32(self.min_learning_rate)
        loss_sum = jnp.float32(0.0)
        cnt = jnp.float32(0.0)
        if self.negative > 0 and \
                getattr(self, "_neg_table_dev", None) is None:
            self._neg_table_dev = jnp.asarray(self._neg_table)
        # Adaptive segmenting: big corpora ride SCAN_SUPER_SEGMENT-step
        # programs (one compile each, persistently cached) so the number
        # of dispatches stays small (~0.2 s each, measured r4);
        # the remainder runs in SCAN_SEGMENT-step programs. Per-step
        # update math is identical — a segment boundary only changes
        # where the host folds the RNG key.
        sup = self.SCAN_SUPER_SEGMENT
        start = 0
        # DL4J_W2V_TRACE=1: print per-dispatch SUBMISSION walls — the loop
        # never syncs (loss stays a lazy device scalar), so any host time
        # here is submission cost, not device compute; the r5
        # measurement that settles VERDICT r4 item #3 (BASELINE.md r5)
        import os as _os
        import time as _time
        trace = _os.environ.get("DL4J_W2V_TRACE") == "1"
        while start < n_total:
            t_sub = _time.perf_counter() if trace else 0.0
            use = sup if n_total - start >= sup else seg
            if self.negative > 0:
                lt.syn0, lt.syn1neg, ls, c = skipgram_ns_corpus_scan(
                    lt.syn0, lt.syn1neg, corpus_d, sep_d,
                    self._neg_table_dev, nskey, np.int32(start), lr0,
                    lr_min, np.float32(frac0), np.float32(frac_per_step),
                    k=self.negative, window=window, n_steps=use, p=p,
                    shared_negatives=self.shared_negatives)
            else:
                lt.syn0, lt.syn1, ls, c = skipgram_hs_corpus_scan(
                    lt.syn0, lt.syn1, corpus_d, sep_d, self._codes,
                    self._points, self._lengths, nskey, np.int32(start),
                    lr0, lr_min, np.float32(frac0),
                    np.float32(frac_per_step), window=window,
                    n_steps=use, p=p)
            loss_sum = loss_sum + ls
            cnt = cnt + c
            start += use
            if trace:
                print(f"  dispatch steps[{start - use}:{start}] submitted "
                      f"in {_time.perf_counter() - t_sub:.3f}s", flush=True)
        return loss_sum / jnp.maximum(cnt, 1.0)   # device scalar; lazy sync

    def _run_skipgram(self, centers, targets, seen, ntokens, total, nskey):
        import jax
        B = self.batch_size
        lt = self.lookup
        loss = None
        nb = (len(centers) + B - 1) // B
        neg_table = jnp.asarray(self._neg_table) if self.negative > 0 \
            else None
        for i in range(nb):
            c = jnp.asarray(self._pad(centers[i * B:(i + 1) * B], B))
            t = jnp.asarray(self._pad(targets[i * B:(i + 1) * B], B))
            lr = jnp.float32(self._lr_now(seen + ntokens * i / nb, total))
            if self.use_hs:
                lt.syn0, lt.syn1, loss = skipgram_hs_step(
                    lt.syn0, lt.syn1, c, t, self._codes[t],
                    self._points[t], self._lengths[t], lr)
            if self.negative > 0:
                nskey, sub = jax.random.split(nskey)
                lt.syn0, lt.syn1neg, loss = skipgram_ns_step_rng(
                    lt.syn0, lt.syn1neg, c, t, neg_table, sub, lr,
                    self.negative)
        return loss

    def _run_cbow(self, targets, contexts, cmasks, seen, ntokens, total,
                  nskey):
        import jax
        B = self.batch_size
        lt = self.lookup
        loss = None
        nb = (len(targets) + B - 1) // B
        neg_table = jnp.asarray(self._neg_table) if self.negative > 0 \
            else None
        for i in range(nb):
            t = jnp.asarray(self._pad(targets[i * B:(i + 1) * B], B))
            ctx = jnp.asarray(self._pad(contexts[i * B:(i + 1) * B], B))
            cm = jnp.asarray(self._pad(cmasks[i * B:(i + 1) * B], B))
            lr = jnp.float32(self._lr_now(seen + ntokens * i / nb, total))
            if self.use_hs:
                lt.syn0, lt.syn1, loss = cbow_hs_step(
                    lt.syn0, lt.syn1, ctx, cm, t, self._codes[t],
                    self._points[t], self._lengths[t], lr)
            if self.negative > 0:
                nskey, sub = jax.random.split(nskey)
                lt.syn0, lt.syn1neg, loss = cbow_ns_step_rng(
                    lt.syn0, lt.syn1neg, ctx, cm, t, neg_table, sub, lr,
                    self.negative)
        return loss

    # ------------------------------------------------------------ query API
    def get_word_vector(self, word: str) -> Optional[np.ndarray]:
        return self.lookup.vector(word)

    def similarity(self, a: str, b: str) -> float:
        va, vb = self.lookup.vector(a), self.lookup.vector(b)
        if va is None or vb is None:
            return float("nan")
        denom = np.linalg.norm(va) * np.linalg.norm(vb)
        return float(va @ vb / denom) if denom else 0.0

    def words_nearest(self, word: str, n: int = 10) -> List[str]:
        v = self.lookup.vector(word)
        if v is None:
            return []
        syn0 = np.asarray(self.lookup.syn0)
        norms = np.linalg.norm(syn0, axis=1) * np.linalg.norm(v)
        sims = syn0 @ v / np.maximum(norms, 1e-12)
        idx = self.vocab.index_of(word)
        sims[idx] = -np.inf
        top = np.argsort(-sims)[:n]
        return [self.vocab.word_for(int(i)) for i in top]
