"""Multi-head self-attention layer — a TPU-era extension beyond the
reference's RNN-only sequence modeling (SURVEY.md §5.7 prescribes designing
this fresh). Integrates with the framework seams: helper registry kind
="attention" lets a Pallas flash kernel override the jnp path, and
``ring=True`` + an active mesh routes through ring attention
(parallel/sequence.py) for sequence-parallel long contexts."""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..input_type import InputType
from ..serde import register_config
from .base import BaseRecurrentLayerConf
from ...helpers import get_helper, note_attention_plan, note_slab_reads


@dataclasses.dataclass(frozen=True)
class Window:
    """What one pass of the decode walk (models/generation.py) advances, the
    same for every layer it meets: a window of tokens [B, C], or one token
    a row (a decode step: ids [B], ``valid`` None), and where it sits in
    each row's sequence. Made at trace time from a program's arguments. An
    embedding reads it in ``embed(params, ids, window)``; a layer that keeps
    sequence state in ``advance(params, x, cache, window) -> (y,
    new_cache)``, next to its ``init_cache`` and ``init_page_pool``; a layer
    whose class says ``counts_tokens`` gets ``alive`` as its mask and its
    second return is kept."""
    #: [B] int32, the absolute position of each row's first cell; None: a
    #: fresh prompt from position 0, which rides the ``attention`` helper seam
    start: Optional[jax.Array] = None
    #: [B] int32, how many of a row's cells are real (a prompt's length, a
    #: chunk's or a verify window's count); None: a decode step
    valid: Optional[jax.Array] = None
    #: writes are masked cell by cell to ``valid`` (speculative verify);
    #: unmasked, a window slides left to fit the cache (chunked prefill)
    masked: bool = False
    #: [B, NP] int32 page tables; None on the slab
    pages: Optional[jax.Array] = None
    #: [B] bool, the lanes whose tokens count; None where nothing counts
    alive: Optional[jax.Array] = None
    #: [B, C] float32, 1.0 on a fresh prompt's real cells (:meth:`fresh`)
    mask: Optional[jax.Array] = None

    @classmethod
    def fresh(cls, width: int, lengths) -> "Window":
        """Prompts of ``lengths`` [B] padded to ``width``, from position 0."""
        mask = (jnp.arange(width, dtype=jnp.int32)[None, :] <
                lengths[:, None]).astype(jnp.float32)
        return cls(valid=lengths, mask=mask)


@register_config
@dataclasses.dataclass
class SelfAttentionLayer(BaseRecurrentLayerConf):
    """Input [N, T, n_in] → [N, T, n_out]; n_out = num_heads * head_size.
    ``num_kv_heads`` (0: ``num_heads``) groups the keys and values: query
    head ``i`` reads KV head ``i // (num_heads // num_kv_heads)``.
    ``scale`` (0: ``1/sqrt(head_size)``) multiplies the logits."""
    num_heads: int = 4
    head_size: int = 0            # inferred as n_out // num_heads
    causal: bool = False
    project_out: bool = True
    num_kv_heads: int = 0
    scale: float = 0.0
    #: False: the output projection has no bias
    bias: bool = True

    #: a decode step reads this layer's k/v slab (:meth:`_slab_attend`): the
    #: decoder counts what it reads (models/generation.py SLAB_COUNTERS)
    slab_reads = True

    def _head_size(self) -> int:
        return self.head_size or max(self.n_out // self.num_heads, 1)

    def _kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    def get_output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.n_out, it.timesteps)

    def init_params(self, key, dtype=jnp.float32) -> Dict:
        hs = self._head_size()
        inner = self.num_heads * hs
        kv_inner = self._kv_heads() * hs
        kq, kk, kv, ko = jax.random.split(key, 4)
        p = {"Wq": self._winit(kq, (self.n_in, inner), self.n_in, inner, dtype),
             "Wk": self._winit(kk, (self.n_in, kv_inner), self.n_in,
                               kv_inner, dtype),
             "Wv": self._winit(kv, (self.n_in, kv_inner), self.n_in,
                               kv_inner, dtype)}
        if self.project_out:
            p["Wo"] = self._winit(ko, (inner, self.n_out), inner, self.n_out,
                                  dtype)
            if self.bias:
                p["bo"] = jnp.zeros((self.n_out,), dtype)
        return p

    def regularizable(self):
        return ("Wq", "Wk", "Wv", "Wo")

    def _project_qkv(self, params, x):
        """x [N, T, n_in] → q [N, T, H, Dh], k and v [N, T, H_kv, Dh]."""
        n, t, _ = x.shape
        hcount, hs = self.num_heads, self._head_size()
        q = (x @ params["Wq"]).reshape(n, t, hcount, hs)
        k = (x @ params["Wk"]).reshape(n, t, self._kv_heads(), hs)
        v = (x @ params["Wv"]).reshape(n, t, self._kv_heads(), hs)
        return q, k, v

    # graftlint: traced
    def _attend(self, q, k, v, mask, dtype):
        """Full [N, T, H, Dh] attention through the helper seam (flash /
        short-T Pallas kernels) with the materialized-softmax path as the
        always-available fallback. Grouped KV heads are repeated to the
        query heads and an explicit ``scale`` rides on q (the kernels apply
        ``1/sqrt(Dh)``). Returns [N, T, H, Dh]."""
        hs = self._head_size()
        t = q.shape[1]
        rep = self.num_heads // self._kv_heads()
        if rep > 1:
            k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        if self.scale:
            q = q * jnp.asarray(self.scale * math.sqrt(hs), q.dtype)
        helper = get_helper("attention")
        out = helper(self, q, k, v, mask) if helper is not None else None
        if out is None:
            # no helper, or the helper declined (e.g. flash kernel below
            # its min_seq_len): built-in materialized-softmax path
            note_attention_plan("materialized")
            scale = 1.0 / jnp.sqrt(jnp.asarray(hs, dtype))
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
            neg = jnp.asarray(-1e30, dtype)
            if self.causal:
                cmask = jnp.tril(jnp.ones((t, t), bool))
                logits = jnp.where(cmask[None, None], logits, neg)
            if mask is not None:
                key_keep = mask.astype(bool)[:, None, None, :]   # [N,1,1,T]
                logits = jnp.where(key_keep, logits, neg)
            probs = jax.nn.softmax(logits, axis=-1)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        return out

    def _project_out(self, params, out):
        """[N, T, H, Dh] heads → activation([N, T, n_out])."""
        n, t = out.shape[:2]
        out = out.reshape(n, t, self.num_heads * self._head_size())
        if self.project_out:
            out = out @ params["Wo"]
            if self.bias:
                out = out + params["bo"][None, None, :]
        return self.activation_fn()(out)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        q, k, v = self._project_qkv(params, x)
        out = self._attend(q, k, v, mask, x.dtype)
        return self._project_out(params, out), state

    # ---- KV-cache autoregressive decoding (models/generation.py) ----
    # graftlint: traced
    def advance(self, params, x, cache, window: Window):
        """x [B, C, n_in] of ``window`` → (out [B, C, n_out], new cache):
        the one door the decode walk comes through. Which of the bodies
        below runs is read off the window: no cache at all is the no-cache
        reference's plain ``forward``; page tables are the paged pool's two;
        on the slab a fresh prompt, one token a row, or a window from
        ``start``, its writes masked to ``valid`` or sliding."""
        if cache is None:
            return self.forward(params, None, x, mask=window.mask)[0], None
        if window.pages is not None:
            if window.valid is None:
                return self.paged_decode_forward(params, x, cache,
                                                 window.pages, window.start)
            return self.paged_chunk_forward(params, x, cache, window.pages,
                                            window.start, window.valid)
        if window.start is None:
            return self.prefill_forward(params, x, cache, mask=window.mask)
        if window.valid is None:
            return self.decode_forward(params, x, cache, window.start,
                                       window.alive)
        return self.chunk_forward(params, x, cache, window.start,
                                  window.valid if window.masked else None)

    def latent_bytes_per_token(self, dtype) -> int:
        """Bytes a cached token takes where the cache holds one compressed
        row a token and nothing per head; 0 for this layer's k/v slabs."""
        return 0

    #: lanes of one TPU vector row: a slab row narrower than this is padded
    #: to it on the device, read and written at half speed and twice the
    #: bytes (PERF.md, PR 27)
    LANES = 128

    def heads_per_row(self, tp: int = 1) -> int:
        """``g``: how many heads share one row of the slab cache. A head
        narrower than a 128-lane row is packed ``g = 128 // Dh`` to a row
        when that fills the row exactly, the head count divides by ``g``
        and the ``H/g`` head groups still divide over the mesh's ``tp``
        axis; otherwise 1, the unpacked ``[B, H, T_max, Dh]`` slab. Derived
        from what the layer and the mesh are — there is no option."""
        hs = self._head_size()
        if hs >= self.LANES or self.LANES % hs:
            return 1
        g = self.LANES // hs
        if self.num_heads % g or (self.num_heads // g) % max(int(tp), 1):
            return 1
        return g

    def init_cache(self, batch: int, t_max: int, dtype=jnp.float32,
                   sharding=None) -> Dict:
        """Preallocated decode cache: {"k", "v"} each
        [B, H_kv/g, T_max, g·Dh] — ``g`` KV heads side by side in one row, so
        that the minor dimension is a whole 128-lane row and the decode
        programs update and read the slab in the layout it is stored in
        (``g`` from :meth:`heads_per_row`; 1 keeps [B, H, T_max, Dh]).
        KV head ``h`` lives in row group ``h // g`` at lanes
        ``[(h % g)·Dh, (h % g + 1)·Dh)``. Every reader and writer takes
        ``g`` from the cache it is handed (``shape[3] // Dh``), so the one
        decision is made here.
        ``sharding`` (a NamedSharding, slots over data / head groups over
        tp) places the buffers distributed at birth — the cache is the
        dominant serving allocation and must never materialize
        replicated on one device of a mesh."""
        if not self.causal:
            raise ValueError("KV-cache decoding needs causal=True "
                             "(autoregressive attention)")
        tp = 1
        if sharding is not None and len(sharding.spec) > 1 \
                and sharding.spec[1] is not None:
            tp = sharding.mesh.shape[sharding.spec[1]]
        g = self.heads_per_row(tp)
        shape = (batch, self._kv_heads() // g, t_max, g * self._head_size())
        if sharding is not None:
            # allocate UNDER the sharding: zeros-then-device_put would
            # materialize the full buffer on one device first — the
            # dominant serving allocation must be born distributed
            return {"k": jnp.zeros(shape, dtype, device=sharding),
                    "v": jnp.zeros(shape, dtype, device=sharding)}
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    # graftlint: traced
    def _slab_rows(self, x, cache):
        """k or v of a window [B, C, H, Dh] → the slab's rows
        [B, H/g, C, g·Dh] in the cache's dtype (``g`` read off the
        cache): the one place the writers spell the layout."""
        b, c = x.shape[:2]
        return x.reshape(b, c, cache.shape[1], cache.shape[3]) \
            .transpose(0, 2, 1, 3).astype(cache.dtype)

    # graftlint: traced
    def _slab_write(self, cache: Dict, k, v, pos):
        """Write a window's k/v [B, C, H, Dh] into the slab at each
        row's own position ``pos`` [B] (in range: the callers clamp):
        one ``dynamic_update_slice`` of [1, H/g, C, g·Dh] per row, in
        place on the carried cache. Unrolled over the rows rather than
        vmapped: a vmapped update is a scatter, which the TPU runs as a
        loop of bounds check + select + update per row — 2.4 ms of a
        13.2 ms decode step at 16 slots against 0.25 ms for the plain
        updates (PERF.md, PR 27)."""
        zero = np.int32(0)                # match pos dtype under x64 mode
        new_cache = {}
        with jax.named_scope("cache_update"):
            # the unroll over the B rows is the point (docstring)
            at = [jax.lax.index_in_dim(pos, b, keepdims=False)
                  for b in range(pos.shape[0])]  # graftlint: disable=GL002
            for kk, u in (("k", k), ("v", v)):
                rows = self._slab_rows(u, cache[kk])
                c = cache[kk]
                for b, p in enumerate(at):
                    # static numpy indices and no negative-index fix-up:
                    # each costs traced ops, 1152 times a decode step
                    c = jax.lax.dynamic_update_slice(
                        c, jax.lax.slice_in_dim(rows, b, b + 1),
                        (np.int32(b), zero, p, zero),
                        allow_negative_indices=False)
                new_cache[kk] = c
        return new_cache

    # graftlint: traced
    def _slab_attend(self, q, ck, cv, qpos, alive=None):
        """Length-masked attention of a window's queries over the slab:
        q [B, C, H, Dh], ck/cv [B, H_kv/g, T, g·Dh], ``qpos`` [B, C] the
        absolute position of each query (it attends cells ``<= qpos``),
        ``alive`` [B] the lanes whose rows count (None: every lane; a lane
        that does not count gets finite rows of no meaning, zeros from the
        kernel).
        Both contractions run over whole rows: the logits of a row group
        are ``K_row[T, g·Dh] · Qblk[g·Dh, G]`` over its ``G = H/(H_kv/g)``
        query heads with ``Qblk`` block-diagonal (query head j reads KV
        head ``j // rep`` of the row, ``rep = H/H_kv``: its query in lanes
        [(j//rep)·Dh, (j//rep+1)·Dh), zeros elsewhere — the zeros
        contribute exact 0.0), the weighted sum is ``P[G, T] · V_row[T,
        g·Dh]`` of which head j keeps its KV head's Dh lanes. f32 logits
        and softmax, every position under the mask; ``g = 1`` is plain
        ``bqhd,bhtd->bhqt`` (every query head of a group reads its one KV
        head). The ``slab_attention``
        helper (kernels/slab_attention.py on the TPU: K and V streamed in
        position tiles, an online softmax across them) takes the call
        where it serves the shapes, and reads of a slot only the tiles up
        to its queries' last position, and nothing of a lane that does not
        count; the einsum body below is the always-available path, and
        reads every position. Either notes what it read
        (``nn.helpers.note_slab_reads``). Returns [B, C, H, Dh]."""
        b, c, h, hs = q.shape
        hg = ck.shape[1]
        g = h // hg                          # query heads of a row group
        kv_g = ck.shape[3] // hs             # KV heads of a row
        rep = g // kv_g                      # query heads of a KV head
        # math.sqrt: GL004 (x64)
        scale = self.scale or 1.0 / math.sqrt(hs)
        qg = q.reshape(b, c, hg, g, 1, hs)
        if kv_g > 1:
            own = jnp.eye(kv_g, dtype=q.dtype)
            if rep > 1:
                own = jnp.repeat(own, rep, axis=0)
            own = own[None, None, None, :, :, None]
            qg = qg * own                    # [B, C, H/g, g, g, Dh]
        qblk = qg.reshape(b, c, hg, g, kv_g * hs)
        helper = get_helper("slab_attention")
        rows = helper(self, qblk, ck, cv, qpos, scale, alive) \
            if helper is not None else None
        if rows is None:
            # no helper, or it declined (a row that is not whole lanes, a T
            # no tile divides, a long window): the built-in body
            note_attention_plan("slab_einsum")
            note_slab_reads(b * ck.shape[2], b * ck.shape[2])
            logits = jnp.einsum("bqgjl,bgtl->bgjqt", qblk, ck,
                                preferred_element_type=jnp.float32) * scale
            kpos = jnp.arange(ck.shape[2], dtype=jnp.int32)
            keep = kpos[None, None, :] <= qpos[:, :, None]   # [B, C, T]
            logits = jnp.where(keep[:, None, None, :, :], logits, -1e30)
            probs = jax.nn.softmax(logits, axis=-1)           # f32
            rows = jnp.einsum("bgjqt,bgtl->bqgjl", probs.astype(cv.dtype),
                              cv)
        if kv_g > 1 and rep == 1:
            # head j's own lanes of its row: the diagonal blocks. The branch
            # below computes the same with rep = 1; this one is kept only so
            # that the MHA models (gpt2-large) lower to the text they did
            # before grouped heads (scripts/program_fingerprints.py checks it)
            rows = jnp.diagonal(rows.reshape(b, c, hg, g, g, hs),
                                axis1=3, axis2=4)    # [B, C, H/g, Dh, g]
            rows = jnp.moveaxis(rows, -1, 3)
        elif kv_g > 1:
            # query head (j, r) keeps the lanes of its KV head j
            rows = jnp.diagonal(rows.reshape(b, c, hg, kv_g, rep, kv_g, hs),
                                axis1=3, axis2=5)  # [B, C, hg, rep, Dh, kv_g]
            rows = jnp.moveaxis(rows, -1, 3)
        return rows.reshape(b, c, h, hs)

    # graftlint: traced
    def prefill_forward(self, params, x, cache: Dict, mask=None):
        """Teacher-forced pass over the prompt [B, T, n_in] that also fills
        cache[:, :, :T] ([B, H/g, T_max, g·Dh], see :meth:`init_cache`)
        with this layer's k/v — attention itself rides the
        SAME helper seam as forward() (flash / short-T Pallas kernels), so
        prefill costs one ordinary forward. Positions beyond a row's true
        length carry garbage k/v; decode_forward's length mask never
        attends to them. Returns (out [B, T, n_out], new_cache)."""
        q, k, v = self._project_qkv(params, x)
        out = self._attend(q, k, v, mask, x.dtype)
        with jax.named_scope("cache_update"):
            new_cache = {
                kk: jax.lax.dynamic_update_slice(
                    cache[kk], self._slab_rows(u, cache[kk]), (0, 0, 0, 0))
                for kk, u in (("k", k), ("v", v))}
        return self._project_out(params, out), new_cache

    # graftlint: traced
    def decode_forward(self, params, x, cache: Dict, positions,
                       alive=None):
        """One decode step: x [B, 1, n_in] is the token at ``positions``
        ([B] int32, per-row — slots in a continuous batch sit at different
        lengths). Writes k/v into the [B, H/g, T_max, g·Dh] cache at each
        row's position (:meth:`_slab_write`: one
        ``lax.dynamic_update_slice`` of whole rows per slot, in place —
        fixed-shape, ONE compile serves every step) and attends q over
        cache[:, :, :pos+1] via a length mask (:meth:`_slab_attend`: f32
        logits and softmax, both contractions over whole rows — no
        relayout of the cache). ``alive`` ([B] bool, the decode block's
        ``~stop``; None: every row) marks the rows whose output counts: the
        kernel reads a slot's tiles up to its position and nothing of a row
        that does not count, whose output is then zero. Returns (out
        [B, 1, n_out], new_cache).

        Positions are clamped to the cache depth: a fused decode block
        (models/generation.py decode_block) lets finished lanes overshoot
        their stop on device, and an overshooting lane must keep writing
        inside its own last cell rather than rely on the backend's
        out-of-range scatter behaviour."""
        q, k, v = self._project_qkv(params, x)       # [B, 1, H, Dh]
        pos = jnp.minimum(jnp.asarray(positions, jnp.int32).reshape(-1),
                          cache["k"].shape[2] - 1)
        new_cache = self._slab_write(cache, k, v, pos)
        out = self._slab_attend(q, new_cache["k"], new_cache["v"],
                                pos[:, None], alive)
        return self._project_out(params, out.astype(x.dtype)), new_cache

    # graftlint: traced
    def chunk_forward(self, params, x, cache: Dict, pos0, valid=None):
        """Chunked-prefill step (µ-cuDNN-style micro-batching of a long
        prompt): x [B, C, n_in] is a WINDOW of C prompt tokens whose
        first token sits at absolute position ``pos0`` ([B] int32).
        Writes the window's k/v into the [B, H/g, T_max, g·Dh] cache at
        [pos0, pos0+C) (:meth:`_slab_write` — fixed shape, ONE compile
        per chunk size) and attends each query i over
        cache[:, :, :pos0+i+1] via a per-query length mask
        (:meth:`_slab_attend`), so earlier chunks' context is read back
        through the SAME cache decode_forward uses. Positions past
        a window's true length carry garbage k/v exactly like padded
        prefill positions — the length masks never attend them before
        the decode write-head overwrites them. ``pos0`` is clamped so
        the window always fits the cache depth (the caller may slide the
        final window left over already-filled cells; rewriting a cell
        from the same tokens is idempotent up to float reassociation).

        ``valid`` ([B] int32, default the full window) switches the
        write to a PER-CELL masked scatter: only cells [pos0, pos0 +
        valid) are written, everything else (including the whole row
        when valid == 0) is dropped. Speculative verify windows need
        this — a frozen/parked lane must write NOTHING (its parked cell
        holds real prompt KV a chunk admission is still filling), and a
        lane near the context edge must not slide its window left over
        accepted history. valid=None keeps the original path
        bit-identical. Returns (out [B, C, n_out], new_cache)."""
        q, k, v = self._project_qkv(params, x)         # [B, C, H, Dh]
        c = x.shape[1]
        t_max = cache["k"].shape[2]
        if valid is None:
            p0 = jnp.clip(jnp.asarray(pos0, jnp.int32).reshape(-1), 0,
                          max(t_max - c, 0))
            new_cache = self._slab_write(cache, k, v, p0)
            qpos = p0[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
        else:
            p0 = jnp.asarray(pos0, jnp.int32).reshape(-1)   # UNclamped
            vcount = jnp.asarray(valid, jnp.int32).reshape(-1)
            w = p0[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
            keep_w = (jnp.arange(c, dtype=jnp.int32)[None, :] <
                      vcount[:, None]) & (w < t_max)
            # invalid cells index past the cache depth and are DROPPED
            # (the slab twin of the paged path's null-page redirect)
            wpos = jnp.where(keep_w, w, t_max)
            rows = jnp.arange(x.shape[0], dtype=jnp.int32)[:, None]
            with jax.named_scope("cache_update"):
                # advanced indices (dims 0 and 2) around the group
                # slice: the update lands as [B, C, H/g, g·Dh]
                new_cache = {
                    kk: cache[kk].at[rows, :, wpos, :].set(
                        self._slab_rows(u, cache[kk]).transpose(0, 2, 1, 3),
                        mode="drop")
                    for kk, u in (("k", k), ("v", v))}
            qpos = w
        out = self._slab_attend(q, new_cache["k"], new_cache["v"], qpos)
        return self._project_out(params, out.astype(x.dtype)), new_cache

    # ---- paged KV cache (models/paging.py + models/generation.py) ----
    def init_page_pool(self, num_pages: int, page_size: int,
                       dtype=jnp.float32, sharding=None) -> Dict:
        """Paged decode cache: {"k", "v"} each [P, H, page_size, Dh] —
        a pool of fixed-size pages shared by every slot, addressed
        through per-slot page tables instead of contiguous rows. Heads
        shard over tp exactly like the slab cache's H dim (pages do NOT
        shard over data: any slot may hold any page). Page 0 is the
        reserved null/trash page — unmapped table entries and freed
        lanes' redirected writes land there, and length masks keep it
        from ever being attended."""
        if not self.causal:
            raise ValueError("KV-cache decoding needs causal=True "
                             "(autoregressive attention)")
        if self._kv_heads() != self.num_heads or self.scale:
            raise NotImplementedError(
                "the paged pool has no grouped KV heads and no explicit "
                "scale yet (ROADMAP R-M2): serve this layer from the slab")
        hs = self._head_size()
        shape = (num_pages, self.num_heads, page_size, hs)
        if sharding is not None:
            # born distributed, like init_cache: the pool is the
            # dominant serving allocation
            return {"k": jnp.zeros(shape, dtype, device=sharding),
                    "v": jnp.zeros(shape, dtype, device=sharding)}
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    # graftlint: traced
    def _paged_gather(self, pool, ptable):
        """Page table [B, NP] → the slot's contiguous logical view
        [B, H, NP*page_size, Dh]. The gather reconstructs logical token
        order (table entry j covers positions [j*ps, (j+1)*ps)), so the
        downstream attention math is IDENTICAL to the slab path — cells
        beyond a row's mapped pages read the null page and are length-
        masked exactly like a slab row's unwritten tail. The transient
        gather materialization is the documented cost of the kernel-free
        paged route; the fused paged-attention kernel (ROADMAP item 5)
        removes it."""
        b, n_pages = ptable.shape
        ps = pool.shape[2]
        g = pool[ptable]                     # [B, NP, H, ps, Dh]
        return g.transpose(0, 2, 1, 3, 4).reshape(
            b, self.num_heads, n_pages * ps, -1)

    # graftlint: traced
    def paged_decode_forward(self, params, x, pool: Dict, ptable,
                             positions):
        """One decode step over a paged cache: x [B, 1, n_in] at
        ``positions`` [B]. Writes each row's k/v into its page table's
        page for that position (one advanced-index scatter — fixed
        shape, ONE compile serves every step) and attends over the
        gathered logical view with the SAME length-masked math as
        :meth:`decode_forward`, so paged and slab logits are bitwise
        identical at every unmasked cell. Returns (out [B, 1, n_out],
        new_pool)."""
        q, k, v = self._project_qkv(params, x)      # [B, 1, H, Dh]
        ps = pool["k"].shape[2]
        t_cap = ptable.shape[1] * ps
        pos = jnp.minimum(jnp.asarray(positions, jnp.int32).reshape(-1),
                          t_cap - 1)
        rows = jnp.arange(ptable.shape[0], dtype=jnp.int32)
        pids = ptable[rows, pos // ps]              # [B]
        offs = pos % ps
        # advanced indices (dim 0 and 2) around the H slice: the update
        # lands as [B, H, Dh]. Freed/frozen lanes' tables are redirected
        # to the null page — duplicate trash-cell writes race only with
        # each other and the cell is never attended.
        with jax.named_scope("cache_update"):
            new_pool = {
                "k": pool["k"].at[pids, :, offs, :].set(
                    k[:, 0].astype(pool["k"].dtype)),
                "v": pool["v"].at[pids, :, offs, :].set(
                    v[:, 0].astype(pool["v"].dtype))}
        ck = self._paged_gather(new_pool["k"], ptable)
        cv = self._paged_gather(new_pool["v"], ptable)
        hs = self._head_size()
        scale = 1.0 / math.sqrt(hs)     # math.sqrt: GL004 (x64)
        logits = jnp.einsum("bhd,bhtd->bht", q[:, 0], ck,
                            preferred_element_type=jnp.float32) * scale
        kpos = jnp.arange(ck.shape[2], dtype=jnp.int32)
        keep = kpos[None, :] <= pos[:, None]
        logits = jnp.where(keep[:, None, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)          # f32
        out = jnp.einsum("bht,bhtd->bhd", probs.astype(cv.dtype), cv)
        out = out[:, None]                               # [B,1,H,Dh]
        return self._project_out(params, out.astype(x.dtype)), new_pool

    # graftlint: traced
    def paged_chunk_forward(self, params, x, pool: Dict, ptable, pos0,
                            valid=None):
        """Chunked/tail prefill over a paged cache: x [B, C, n_in] is a
        window whose first token sits at absolute position ``pos0``
        ([B] int32 — 0 for a fresh prompt, the shared-prefix length
        after a prefix-cache hit, a window multiple mid-chunking).
        Writes the window's k/v through the page table (positions below
        ``pos0`` are NEVER written — that is what makes mapped shared
        pages read-only) and attends each query i over the gathered
        view at positions <= pos0+i, the same per-query mask as
        :meth:`chunk_forward`. Window cells at or past a row's true
        length (``valid`` [B], default the full window) are REDIRECTED
        to the null page: unlike the slab, where padded garbage lands
        harmlessly in the row's own tail, a padded paged write could
        cross into a page another slot owns — masked writes make the
        window byte-exact to its declared extent. Returns (out [B, C,
        n_out], new_pool)."""
        q, k, v = self._project_qkv(params, x)        # [B, C, H, Dh]
        c = x.shape[1]
        ps = pool["k"].shape[2]
        n_pages = ptable.shape[1]
        t_cap = n_pages * ps
        p0 = jnp.asarray(pos0, jnp.int32).reshape(-1)
        vcount = jnp.full(p0.shape, c, jnp.int32) if valid is None \
            else jnp.asarray(valid, jnp.int32).reshape(-1)
        w = p0[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]  # [B,C]
        keep_w = (jnp.arange(c, dtype=jnp.int32)[None, :] <
                  vcount[:, None]) & (w < t_cap)
        pids = jnp.take_along_axis(ptable,
                                   jnp.minimum(w // ps, n_pages - 1),
                                   axis=1)                         # [B,C]
        pids = jnp.where(keep_w, pids, 0)           # null-page redirect
        offs = jnp.where(keep_w, w % ps, 0)
        with jax.named_scope("cache_update"):
            new_pool = {
                "k": pool["k"].at[pids, :, offs, :].set(
                    k.astype(pool["k"].dtype)),
                "v": pool["v"].at[pids, :, offs, :].set(
                    v.astype(pool["v"].dtype))}
        ck = self._paged_gather(new_pool["k"], ptable)
        cv = self._paged_gather(new_pool["v"], ptable)
        hs = self._head_size()
        scale = 1.0 / math.sqrt(hs)          # math.sqrt: GL004 (x64)
        logits = jnp.einsum("bqhd,bhtd->bhqt", q, ck,
                            preferred_element_type=jnp.float32) * scale
        kpos = jnp.arange(ck.shape[2], dtype=jnp.int32)
        keep = kpos[None, None, :] <= w[:, :, None]        # [B, C, T]
        logits = jnp.where(keep[:, None, :, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)            # f32
        out = jnp.einsum("bhqt,bhtd->bqhd", probs.astype(cv.dtype), cv)
        return self._project_out(params, out.astype(x.dtype)), new_pool


@register_config
@dataclasses.dataclass
class LayerNormalization(BaseRecurrentLayerConf):
    """Last-axis layer norm (TPU-era extension; transformers normalize per
    token, BatchNormalization's batch statistics do not apply to
    variable-length autoregressive training). Statistics in f32 regardless
    of compute dtype."""
    eps: float = 1e-5

    def set_n_in(self, it: InputType) -> None:
        if not self.n_in:
            self.n_in = it.size
        if not self.n_out:
            self.n_out = self.n_in

    def get_output_type(self, it: InputType) -> InputType:
        return it

    def init_params(self, key, dtype=jnp.float32) -> Dict:
        d = self.n_out or self.n_in
        return {"gamma": jnp.ones((d,), dtype),
                "beta": jnp.zeros((d,), dtype)}

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        # statistics at >= f32 (bf16 upcast; f64 stays f64 for the
        # finite-difference gradient oracle). The analytic custom VJP
        # (kernels/layernorm.py) stores only per-token (mean, rstd) and
        # rebuilds x_hat in backward — autodiff of the naive form re-reads
        # f32 [N, T, C] intermediates and ran ~6x the bandwidth floor
        # (BASELINE.md r4).
        from ....kernels.layernorm import layernorm
        return layernorm(x, params["gamma"], params["beta"],
                         float(self.eps)), state


@register_config
@dataclasses.dataclass
class RMSNormalization(LayerNormalization):
    """Last-axis RMS norm: ``x / sqrt(mean(x^2) + eps) * gamma`` — no mean
    subtracted, no shift. Statistics in f32 regardless of compute dtype."""
    eps: float = 1e-6

    def init_params(self, key, dtype=jnp.float32) -> Dict:
        return {"gamma": jnp.ones((self.n_out or self.n_in,), dtype)}

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        return rms_norm(x, params["gamma"], self.eps), state


# graftlint: traced
def rms_norm(x, gamma, eps: float):
    """RMS norm over the last axis at >= f32, back in ``x``'s type."""
    xf = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    g = gamma.astype(xf.dtype).reshape((1,) * (x.ndim - 1) + (-1,))
    return (xf * inv * g).astype(x.dtype)


@register_config
@dataclasses.dataclass
class TransformerFeedForward(BaseRecurrentLayerConf):
    """Per-token two-layer MLP (the transformer FFN block): [N, T, C] →
    gelu(x W1 + b1) W2 + b2 → [N, T, C]. Time-distributed by construction —
    no reshape preprocessors, the matmul broadcasts over [N, T]."""
    hidden_mult: int = 4

    def set_n_in(self, it: InputType) -> None:
        if not self.n_in:
            self.n_in = it.size
        if not self.n_out:
            self.n_out = self.n_in

    def get_output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.n_out, it.timesteps)

    def init_params(self, key, dtype=jnp.float32) -> Dict:
        h = self.hidden_mult * self.n_in
        k1, k2 = jax.random.split(key)
        return {"W1": self._winit(k1, (self.n_in, h), self.n_in, h, dtype),
                "b1": jnp.zeros((h,), dtype),
                "W2": self._winit(k2, (h, self.n_out), h, self.n_out, dtype),
                "b2": jnp.zeros((self.n_out,), dtype)}

    def regularizable(self):
        return ("W1", "W2")

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        h = jax.nn.gelu(x @ params["W1"] + params["b1"][None, None, :])
        h = self.maybe_dropout(h, train=train, rng=rng)
        return h @ params["W2"] + params["b2"][None, None, :], state


@register_config
@dataclasses.dataclass
class GatedFeedForward(BaseRecurrentLayerConf):
    """Per-token gated MLP, no bias: [N, T, C] →
    ``(silu(x Wg) * (x Wu)) Wd`` → [N, T, C], hidden width ``hidden``."""
    hidden: int = 0

    def set_n_in(self, it: InputType) -> None:
        if not self.n_in:
            self.n_in = it.size
        if not self.n_out:
            self.n_out = self.n_in

    def init_params(self, key, dtype=jnp.float32) -> Dict:
        h = self.hidden or 4 * self.n_in
        kg, ku, kd = jax.random.split(key, 3)
        return {"Wg": self._winit(kg, (self.n_in, h), self.n_in, h, dtype),
                "Wu": self._winit(ku, (self.n_in, h), self.n_in, h, dtype),
                "Wd": self._winit(kd, (h, self.n_out), h, self.n_out, dtype)}

    def regularizable(self):
        return ("Wg", "Wu", "Wd")

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        return gated_ffn(x, params["Wg"], params["Wu"], params["Wd"]), state


#: tokens a wide per-token layer takes at once: a longer input (a batched
#: admission's prompts) is walked in blocks of this many, which bounds the
#: hidden activations (and an expert layer's token-expert rows) held at a time
TOKEN_BLOCK = 4096


# graftlint: traced
def token_block(rows_per_token: int = 1) -> int:
    """``TOKEN_BLOCK``, halved until a block lays out at most the rows that
    eight a token make of it (an expert layer's ``top_k`` choices a token:
    4096 tokens up to top-8, 2048 at top-12)."""
    block = TOKEN_BLOCK
    while block * rows_per_token > TOKEN_BLOCK * 8:
        block //= 2
    return block


def in_token_blocks(fn, *arrays, block: int = 0):
    """``fn(*arrays)`` over arrays that share a leading token axis [N, ...],
    ``block`` (``TOKEN_BLOCK`` unless given) tokens at a time where N is a
    longer multiple of it (the results, a pytree of [N, ...] arrays, put
    together again)."""
    block = block or TOKEN_BLOCK
    n = arrays[0].shape[0]
    if n <= block or n % block:
        return fn(*arrays)
    blocks = tuple(a.reshape((n // block, block) + a.shape[1:])
                   for a in arrays)
    out = jax.lax.map(lambda blk: fn(*blk), blocks)
    return jax.tree_util.tree_map(
        lambda a: a.reshape((n,) + a.shape[2:]), out)


# graftlint: traced
def gated_ffn(x, wg, wu, wd):
    """``(silu(x Wg) * (x Wu)) Wd`` over the last axis of x [..., d]."""
    y = in_token_blocks(
        lambda blk: (jax.nn.silu(blk @ wg) * (blk @ wu)) @ wd,
        x.reshape(-1, x.shape[-1]))
    return y.reshape(x.shape[:-1] + (wd.shape[-1],))


@register_config
@dataclasses.dataclass
class TokenAndPositionEmbedding(BaseRecurrentLayerConf):
    """Token ids [N, T] → embeddings + learned positions [N, T, n_out]
    (the transformer input block; reference EmbeddingLayer handles [N]
    only). ``n_in`` is the vocabulary size; sequences longer than
    ``max_length`` are rejected at trace time."""
    max_length: int = 512

    def get_output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.n_out, it.timesteps)

    def init_params(self, key, dtype=jnp.float32) -> Dict:
        kw, kp = jax.random.split(key)
        return {"W": jax.random.normal(kw, (self.n_in, self.n_out),
                                       dtype) * 0.02,
                "P": jax.random.normal(kp, (self.max_length, self.n_out),
                                       dtype) * 0.02}

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        ids = x.astype(jnp.int32)
        if ids.ndim == 3:              # one-hot [N, T, V]
            ids = jnp.argmax(ids, axis=-1)
        t = ids.shape[1]
        if t > self.max_length:
            raise ValueError(f"sequence length {t} > max_length "
                             f"{self.max_length}")
        out = params["W"][ids] + params["P"][None, :t]
        return self.maybe_dropout(out, train=train, rng=rng), state

    # graftlint: traced
    def embed(self, params, ids, window: Window):
        """The decode walk's door (see :class:`Window`): ids [B, C] of a
        fresh prompt are ``forward``'s, one token a row ``embed_at``'s, a
        window from ``start`` ``embed_chunk``'s."""
        if window.start is None:
            return self.forward(params, None, ids)[0]
        if window.valid is None:
            return self.embed_at(params, ids, window.start)
        return self.embed_chunk(params, ids, window.start)

    # graftlint: traced
    def embed_at(self, params, ids, positions):
        """Single-position decode embedding: ids [B] + per-row positions
        [B] → [B, 1, n_out]. Positions clamp to max_length - 1 (a fused
        decode block's overshooting lanes sit at the context edge); no
        dropout (inference only)."""
        ids = jnp.asarray(ids, jnp.int32).reshape(-1)
        pos = jnp.minimum(jnp.asarray(positions, jnp.int32).reshape(-1),
                          self.max_length - 1)
        return (params["W"][ids] + params["P"][pos])[:, None, :]

    # graftlint: traced
    def embed_chunk(self, params, ids, pos0):
        """Chunked-prefill embedding: ids [B, C] embedded at absolute
        positions pos0 + [0, C) per row (``pos0`` [B] int32, clamped so
        the window sits inside max_length) → [B, C, n_out]. The chunk
        analogue of :meth:`embed_at`; no dropout (inference only)."""
        ids = jnp.asarray(ids, jnp.int32)
        c = ids.shape[1]
        p0 = jnp.asarray(pos0, jnp.int32).reshape(-1)
        pos = jnp.minimum(p0[:, None] +
                          jnp.arange(c, dtype=jnp.int32)[None, :],
                          self.max_length - 1)               # [B, C]
        return params["W"][ids] + params["P"][pos]


@register_config
@dataclasses.dataclass
class TokenEmbedding(BaseRecurrentLayerConf):
    """Token ids [N, T] → embeddings [N, T, n_out] and nothing else: a
    lookup with no position table, for models whose positions enter inside
    attention (rotary) or nowhere. ``max_length`` is only the context the
    model declares — what bounds a decoder's ``t_max``. ``multiplier``
    scales the rows looked up."""
    max_length: int = 512
    multiplier: float = 1.0

    # graftlint: traced
    def _scaled(self, rows):
        if self.multiplier == 1.0:
            return rows
        return rows * jnp.asarray(self.multiplier, rows.dtype)

    def get_output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.n_out, it.timesteps)

    def init_params(self, key, dtype=jnp.float32) -> Dict:
        return {"W": jax.random.normal(key, (self.n_in, self.n_out),
                                       dtype) * 0.02}

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        ids = x.astype(jnp.int32)
        if ids.ndim == 3:              # one-hot [N, T, V]
            ids = jnp.argmax(ids, axis=-1)
        return self.maybe_dropout(self._scaled(params["W"][ids]),
                                  train=train, rng=rng), state

    # graftlint: traced
    def embed(self, params, ids, window: Window):
        """The decode walk's door: the lookup, wherever the window sits."""
        if window.start is None:
            return self.forward(params, None, ids)[0]
        if window.valid is None:
            return self._scaled(
                params["W"][jnp.asarray(ids, jnp.int32).reshape(-1)][:, None])
        return self._scaled(params["W"][jnp.asarray(ids, jnp.int32)])
