"""Latent attention: keys and values of every head are decompressed from ONE
low-rank row a token, and that row is all the cache holds.

    c_q = RMSNorm(x Wqa) * q_scale              [q_rank]
    [q_nope_h ; q_rope_h] = c_q Wqb             per head: nope_dim + rope_dim
    [c_kv ; k_r] = x Wkva                       [kv_rank + rope_dim]
    c_kv <- RMSNorm(c_kv) * kv_scale
    k_rope = RoPE(k_r)                          one k_rope for all heads
    [k_nope_h ; v_h] = c_kv Wkvb                per head: nope_dim + v_dim
    s_h = (q_nope_h . k_nope_h + RoPE(q_rope_h) . k_rope) / sqrt(nope + rope)
    out = concat_h(softmax(s_h) v_h) Wo

No bias anywhere. ``q_scale`` / ``kv_scale`` (1.0: none) are one scalar each
on the two normalised latents — a family that scales them by
``sqrt(n_in / rank)`` — in every path alike, so the cache's row holds the
SCALED ``c_kv``; ``k_r`` is not scaled. The slab cache is ``{"kv": [B, 1, T_max, kv_rank +
rope_dim]}``: a token's row is ``[c_kv after its norm ; k_rope after
rotation]``, nothing per head (the singleton axis stands where the k/v slabs
have their head groups, so the decoder's slot slicing is the same code).

Two attention paths over that one store. ``forward`` / ``prefill_forward``
decompress ``k_nope`` and ``v`` and ride the ``attention`` helper seam like
any layer (flash kernels on long prompts). ``decode_forward`` /
``chunk_forward`` read the slab in place, absorbed: with ``Wkvb`` split per
head into ``W_K`` [kv_rank, nope] and ``W_V`` [kv_rank, v],

    q~_h = q_nope_h W_K^T            [kv_rank]
    s_h = ([q~_h ; q_rope_h] . row) / sqrt(nope + rope)
    o_h = (sum_t p_t c_kv_t) W_V

so a step moves ``kv_rank + rope_dim`` values a cached token, not
``heads x (nope + rope + v)``. Both paths write the same rows.

RoPE rotates ADJACENT pairs ``(x[2i], x[2i+1])`` by ``pos * theta^(-2i/d)``
and leaves them in place (the published code of this family permutes the
pairs to the half-split layout first, queries and keys alike: every q.k is
the same number)."""

from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..serde import register_config
from .attention import TOKEN_BLOCK, SelfAttentionLayer, rms_norm


_NO_PAGES = (
    "latent attention: the compressed-KV cache has no paged pool (a page of "
    "[c_kv ; k_rope] rows, its prefix cache and its gather are not built); "
    "serve it from the slab (paged=False)")


# graftlint: traced
def rope(x, pos, theta: float):
    """Rotate adjacent pairs of the last axis of x [B, T, ..., d] by the
    angles of ``pos`` [B, T]; computed in f32, returned in ``x``'s type."""
    half = x.shape[-1] // 2
    inv = jnp.asarray(theta, jnp.float32) ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * inv[None, None, :]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (half, 2))
    x0, x1 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


@register_config
@dataclasses.dataclass
class LatentAttentionLayer(SelfAttentionLayer):
    """Input [N, T, n_in] → [N, T, n_out]; see the module docstring."""
    causal: bool = True
    q_rank: int = 0
    kv_rank: int = 0
    nope_dim: int = 0
    rope_dim: int = 0
    v_dim: int = 0
    rope_theta: float = 10000.0
    eps: float = 1e-6
    q_scale: float = 1.0
    kv_scale: float = 1.0
    #: the absorbed decode reads the latent slab, never through
    #: ``_slab_attend``: nothing to count there
    slab_reads = False

    def _head_size(self) -> int:
        """The width a score contracts over (``_attend`` scales by it)."""
        return self.nope_dim + self.rope_dim

    @property
    def row_width(self) -> int:
        """Values the cache holds a token."""
        return self.kv_rank + self.rope_dim

    def heads_per_row(self, tp: int = 1) -> int:
        return 1

    def latent_bytes_per_token(self, dtype) -> int:
        return self.row_width * jnp.dtype(dtype).itemsize

    def init_params(self, key, dtype=jnp.float32) -> Dict:
        h, d = self.num_heads, self.n_in
        ks = jax.random.split(key, 5)
        qb, kvb = h * self._head_size(), h * (self.nope_dim + self.v_dim)
        w = lambda k, a, b: self._winit(k, (a, b), a, b, dtype)
        return {"Wqa": w(ks[0], d, self.q_rank),
                "gq": jnp.ones((self.q_rank,), dtype),
                "Wqb": w(ks[1], self.q_rank, qb),
                "Wkva": w(ks[2], d, self.row_width),
                "gkv": jnp.ones((self.kv_rank,), dtype),
                "Wkvb": w(ks[3], self.kv_rank, kvb),
                "Wo": w(ks[4], h * self.v_dim, self.n_out)}

    def regularizable(self):
        return ("Wqa", "Wqb", "Wkva", "Wkvb", "Wo")

    # graftlint: traced
    def _latent(self, params, x, pos):
        """x [B, T, n_in] at positions ``pos`` [B, T] → (q_nope
        [B, T, H, nope], q_rope rotated [B, T, H, rope], the cache's rows
        [B, T, kv_rank + rope])."""
        b, t, _ = x.shape
        cq = rms_norm(x @ params["Wqa"], params["gq"], self.eps)
        if self.q_scale != 1.0:
            cq = cq * self.q_scale
        q = (cq @ params["Wqb"]).reshape(b, t, self.num_heads,
                                         self._head_size())
        q_rope = rope(q[..., self.nope_dim:], pos, self.rope_theta)
        kva = x @ params["Wkva"]
        ckv = rms_norm(kva[..., :self.kv_rank], params["gkv"], self.eps)
        if self.kv_scale != 1.0:
            ckv = ckv * self.kv_scale
        k_rope = rope(kva[..., self.kv_rank:], pos, self.rope_theta)
        return (q[..., :self.nope_dim], q_rope,
                jnp.concatenate([ckv, k_rope], axis=-1))

    # graftlint: traced
    def _decompressed(self, params, x, mask):
        """The full-sequence path: (out [B, T, n_out], rows). A batch of
        more than ``TOKEN_BLOCK`` tokens (a batched admission of long
        prompts) is walked a few rows at a time: per-head q, k and v of all
        of it at once are gigabytes."""
        b, t, _ = x.shape
        per = max(TOKEN_BLOCK // t, 1)
        if b > per and b % per == 0:
            split = lambda a: None if a is None else \
                a.reshape((b // per, per) + a.shape[1:])
            out, rows = jax.lax.map(
                lambda xm: self._decompressed(params, *xm),
                (split(x), split(mask)))
            return out.reshape((b,) + out.shape[2:]), \
                rows.reshape((b,) + rows.shape[2:])
        h = self.num_heads
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
        q_nope, q_rope, rows = self._latent(params, x, pos)
        kvb = (rows[..., :self.kv_rank] @ params["Wkvb"]).reshape(
            b, t, h, self.nope_dim + self.v_dim)
        k_rope = jnp.broadcast_to(rows[:, :, None, self.kv_rank:],
                                  (b, t, h, self.rope_dim))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([kvb[..., :self.nope_dim], k_rope], axis=-1)
        # the helper seam's kernels take one head width for q, k and v: v
        # rides zero-padded to it, and the pad's columns are cut off again
        v = jnp.pad(kvb[..., self.nope_dim:],
                    ((0, 0), (0, 0), (0, 0),
                     (0, self._head_size() - self.v_dim)))
        out = self._attend(q, k, v, mask, x.dtype)[..., :self.v_dim]
        return out.reshape(b, t, h * self.v_dim) @ params["Wo"], rows

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        return self._decompressed(params, x, mask)[0], state

    # ---- the latent slab (models/generation.py) ----
    def init_cache(self, batch: int, t_max: int, dtype=jnp.float32,
                   sharding=None) -> Dict:
        """{"kv": [B, 1, T_max, kv_rank + rope_dim]}."""
        if sharding is not None:
            raise NotImplementedError(
                "latent attention: the compressed-KV slab has no layout "
                "under a mesh (SpecLayout has no latent rule)")
        return {"kv": jnp.zeros((batch, 1, t_max, self.row_width), dtype)}

    # graftlint: traced
    def _write(self, cache: Dict, rows, pos):
        """rows [B, C, W] into the slab at each row's own ``pos`` [B]: one
        ``dynamic_update_slice`` a slot, unrolled (``_slab_write``'s
        reason: a vmapped update is a scatter)."""
        zero = np.int32(0)
        slab = cache["kv"]
        rows = rows[:, None].astype(slab.dtype)
        with jax.named_scope("cache_update"):
            for b in range(pos.shape[0]):  # graftlint: disable=GL002
                slab = jax.lax.dynamic_update_slice(
                    slab, jax.lax.slice_in_dim(rows, b, b + 1),
                    (np.int32(b), zero,
                     jax.lax.index_in_dim(pos, b, keepdims=False), zero),
                    allow_negative_indices=False)
        return {"kv": slab}

    # graftlint: traced
    def _absorbed(self, params, q_nope, q_rope, slab, qpos, dtype):
        """Queries [B, C, H, ·] at absolute positions ``qpos`` [B, C] over
        the slab [B, 1, T, W], read in place; each attends cells
        ``<= qpos``. f32 logits and softmax. Returns [B, C, n_out]."""
        b, c, h, _ = q_nope.shape
        rows = slab[:, 0]
        with jax.named_scope("absorb"):
            wkvb = params["Wkvb"].reshape(self.kv_rank, h,
                                          self.nope_dim + self.v_dim)
            qt = jnp.einsum("bqhn,chn->bqhc", q_nope,
                            wkvb[..., :self.nope_dim])
            q = jnp.concatenate([qt, q_rope], axis=-1).astype(rows.dtype)
        scale = 1.0 / math.sqrt(self._head_size())
        logits = jnp.einsum("bqhw,btw->bhqt", q, rows,
                            preferred_element_type=jnp.float32) * scale
        kpos = jnp.arange(rows.shape[1], dtype=jnp.int32)
        keep = kpos[None, None, :] <= qpos[:, :, None]        # [B, C, T]
        logits = jnp.where(keep[:, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)                # f32
        # over the whole row, so that the slab is not sliced (copied); the
        # k_rope columns of the sum are dropped instead
        ctx = jnp.einsum("bhqt,btw->bqhw", probs.astype(rows.dtype), rows)
        with jax.named_scope("absorb"):
            out = jnp.einsum("bqhc,chv->bqhv", ctx[..., :self.kv_rank],
                             wkvb[..., self.nope_dim:])
        return out.reshape(b, c, h * self.v_dim).astype(dtype) @ params["Wo"]

    # graftlint: traced
    def prefill_forward(self, params, x, cache: Dict, mask=None):
        """The prompt [B, T, n_in] through the decompressed path, its rows
        written to cache[:, :, :T]. Returns (out, new_cache)."""
        out, rows = self._decompressed(params, x, mask)
        with jax.named_scope("cache_update"):
            slab = jax.lax.dynamic_update_slice(
                cache["kv"], rows[:, None].astype(cache["kv"].dtype),
                (0, 0, 0, 0))
        return out, {"kv": slab}

    # graftlint: traced
    def decode_forward(self, params, x, cache: Dict, positions, alive=None):
        """One decode step, absorbed: x [B, 1, n_in] at ``positions`` [B]
        (clamped to the slab's depth, as ``SelfAttentionLayer`` does).
        ``alive`` is not read: the absorbed sums read every position (a
        latent kernel could skip past them with the slab kernel's
        ``live_tiles``, ROADMAP S16)."""
        pos = jnp.minimum(jnp.asarray(positions, jnp.int32).reshape(-1),
                          cache["kv"].shape[2] - 1)
        q_nope, q_rope, rows = self._latent(params, x, pos[:, None])
        new_cache = self._write(cache, rows, pos)
        return self._absorbed(params, q_nope, q_rope, new_cache["kv"],
                              pos[:, None], x.dtype), new_cache

    # graftlint: traced
    def chunk_forward(self, params, x, cache: Dict, pos0, valid=None):
        """A window of C tokens from ``pos0`` [B], absorbed; ``valid`` [B]
        masks the writes cell by cell (speculative verify), exactly as
        ``SelfAttentionLayer.chunk_forward`` has it."""
        c = x.shape[1]
        t_max = cache["kv"].shape[2]
        steps = jnp.arange(c, dtype=jnp.int32)[None, :]
        if valid is None:
            p0 = jnp.clip(jnp.asarray(pos0, jnp.int32).reshape(-1), 0,
                          max(t_max - c, 0))
            qpos = p0[:, None] + steps
            q_nope, q_rope, rows = self._latent(params, x, qpos)
            new_cache = self._write(cache, rows, p0)
        else:
            qpos = jnp.asarray(pos0, jnp.int32).reshape(-1)[:, None] + steps
            q_nope, q_rope, rows = self._latent(params, x, qpos)
            vcount = jnp.asarray(valid, jnp.int32).reshape(-1)
            keep_w = (steps < vcount[:, None]) & (qpos < t_max)
            wpos = jnp.where(keep_w, qpos, t_max)        # past the end: drop
            slots = jnp.arange(x.shape[0], dtype=jnp.int32)[:, None]
            with jax.named_scope("cache_update"):
                new_cache = {"kv": cache["kv"].at[slots, 0, wpos, :].set(
                    rows.astype(cache["kv"].dtype), mode="drop")}
        return self._absorbed(params, q_nope, q_rope, new_cache["kv"], qpos,
                              x.dtype), new_cache

    # ---- no paged pool yet ----
    def init_page_pool(self, *args, **kwargs):
        raise NotImplementedError(_NO_PAGES)

    # graftlint: traced
    def advance(self, params, x, cache, window):
        """``SelfAttentionLayer.advance`` over this layer's three bodies;
        a window that carries page tables has no pool to go to."""
        if window.pages is not None:
            raise NotImplementedError(_NO_PAGES)
        return super().advance(params, x, cache, window)
