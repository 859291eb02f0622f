"""KV-cache autoregressive decoding + slot-based continuous batching — the
inference-side performance subsystem for the transformer LM flagship.

The teacher-forced ``models.generate`` recomputes the full O(T²) forward
per emitted token; at T=512 that is ~T× more attention FLOPs and T× more
weight traffic per token than necessary. This module adds the serving
path the ROADMAP's "heavy traffic" north star needs:

- :class:`TransformerDecoder` — graph-driven prefill/decode over any
  causal decoder-only ComputationGraph built from framework layers
  (TokenAndPositionEmbedding / LayerNormalization / SelfAttentionLayer /
  ElementWiseVertex add / TransformerFeedForward / RnnOutputLayer).
  ``prefill()`` runs ONE ordinary forward over the prompt (the attention
  helper seam — flash / short-T Pallas kernels — is reused unchanged)
  while filling a preallocated [B, H/g, T_max, g·Dh] KV cache per
  attention layer (g heads to a 128-lane row, see
  ``SelfAttentionLayer.init_cache``); ``decode_step()`` is a jitted
  fixed-shape single-token step (one ``lax.dynamic_update_slice`` row
  write per slot + length-masked dot-product attention over the cache
  in the layout it is stored in). Next-token selection (greedy /
  temperature, per-row) happens
  on-device; only the [B] token ids cross to the host each step, so ONE
  compile serves every request shape.

- :class:`SlotGenerationEngine` — continuous batching: B cache slots, a
  request queue, and a decode loop in which a finished sequence frees
  its slot mid-loop and the next queued prompt is prefetched into it
  (per-slot prefill scatters batch-1 k/v into the shared cache at the
  slot index). A mixed-length request stream keeps the device batch full
  instead of draining to the stragglers; ``refill=False`` degrades to
  static wave batching (the A/B baseline).

Reference analog: the BatchedInferenceObservable request-coalescing idea
of parallel/inference.py, extended from one-shot classification to the
autoregressive loop that dominates LM serving traffic.
"""

from __future__ import annotations

import collections
import functools
import itertools
import threading
import uuid
import weakref
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from ..nn.conf.layers import Window
from ..nn.conf.layers.feedforward import head_params
from ..nn.graph.computation_graph import scoped
from ..nn.graph.vertices import LayerVertex
from ..nn.helpers import attention_spmd, slab_read_tally
from ..observability.flightrec import default_flight_recorder
from ..observability.metrics import default_registry
from ..observability.profiler import default_profiler
from ..observability.slo import default_slo_tracker
from ..observability import tracing
from ..observability.tracing import (Seam, Trace, default_trace_ring,
                                     interval_now)
from ..ops.platform import train_donate_argnums
from ..ops.transfer import device_fetch
from ..parallel.faults import (Cancelled, DeadlineExceeded, NULL_INJECTOR,
                               RejectedError)
from .speculative import NGramDrafter

#: decode-block key-schedule salts: the engine's sampling keys must never
#: collide with TransformerDecoder.generate's (legacy: 1 << 20 | step_no)
#: or with batched-admission prefill keys
ENGINE_KEY_SALT = 1 << 20
PREFILL_BATCH_SALT = 1 << 21
CHUNK_SALT = 1 << 22

#: submission order for the EDF tie-break: two requests with the same
#: deadline (or none) pop FIFO — rides the request across requeues and
#: migrations, so recovered work keeps its place in the tie order
_REQ_SEQ = itertools.count()

#: registry-backed serving counters (ISSUE 5): stats() keys → help text.
#: The source of truth is the metrics registry (one labeled child per
#: engine instance); the engine's legacy integer attributes
#: (``eng.emitted_tokens`` etc.) are read-only properties over the same
#: children, so stats() and four PRs of callers stay exact per engine
#: while ``/metrics`` aggregates across the process.
_ENGINE_COUNTERS = {
    "emitted_tokens": "tokens emitted to requests",
    "completed": "requests completed",
    "decode_steps": "decode steps executed (K per fused block)",
    "decode_blocks": "decode device programs dispatched",
    "host_readbacks": "deliberate device→host syncs in the serve loop",
    "prefills": "requests admitted (prefilled into a cache slot)",
    "prefill_batches": "coalesced batched-admission prefill calls",
    "prefill_chunks": "chunked-prefill device dispatches (long prompts)",
    "rejected": "admission-control sheds (queue bound or projected "
                "deadline miss)",
    "headroom_shed": "admission sheds on projected deadline miss "
                     "(headroom policy; subset of rejected)",
    "deadline_exceeded": "requests failed by per-request deadline",
    "cancelled": "requests cancelled by their caller",
    "requeued": "requests recovered into this engine after a takeover",
    "failed": "requests failed by engine crash/shutdown",
    "page_preempted": "requests preempted mid-decode on KV page-pool "
                      "pressure (re-queued at the head; exactly-once "
                      "preserved — re-admission re-prefills)",
    "handoffs": "prefilled requests handed off to the disagg tier "
                "(prefill-only engines: KV pages exported, request "
                "leaves through the handoff sink)",
    "adopted": "requests adopted with imported KV state (decode-only "
               "engines: the disaggregated handoff receive path)",
    "spec_blocks": "speculative verify blocks dispatched (ISSUE 16)",
    "spec_drafted": "candidate tokens drafted for speculative "
                    "verification",
    "spec_accepted_tokens": "drafted tokens accepted by the verify "
                            "forward (the per-length account is "
                            "generation_spec_accepted_total{len=})",
    "spec_fallbacks": "decode blocks dispatched by the low-acceptance "
                      "adaptive fallback while speculation is enabled",
    # routed-expert load of alive lanes, a decode step and an expert layer at
    # a time (MOE_COUNTERS; they ride each block's one readback). A stopped
    # lane's choices are cast out before the experts, so what a step reads
    # is what its alive lanes hit: moe_experts_read equal to moe_experts_hit
    # says the cast engaged on every step
    "moe_step_layers": "decode steps x expert layers with an alive lane",
    "moe_assignments": "token-expert assignments of alive lanes",
    "moe_experts_hit": "distinct experts HELD HERE chosen by some alive "
                       "lane, summed over steps and layers",
    "moe_experts_read": "distinct experts held here that some lane's "
                        "choice REACHED (what the step computed and read; "
                        "a stopped lane's reach none, so this equals "
                        "moe_experts_hit), summed over steps and layers",
    "moe_zero_assignments": "alive lanes' choices of zero-compute experts",
    "moe_held_assignments": "alive lanes' choices of experts held here "
                            "(all of moe_assignments where every expert is)",
    # state-space layers (SSM_COUNTERS ride each block's one readback)
    "ssm_step_layers": "decode steps x state-space layers x ALIVE lanes: "
                       "the state updates requests needed",
    "ssm_lane_layers": "decode steps x state-space layers x every lane: "
                       "the state updates a block computed",
    "ssm_state_resets": "slot states overwritten by admission (admitted "
                        "requests x state-space layers)",
    # slab attention (SLAB_COUNTERS ride each block's one readback): what
    # the decode steps' attention read of the k/v slab, and what it holds
    "slab_positions_read": "decode steps x slab attention layers: "
                           "positions read, summed over slots (the "
                           "kernel: a slot's tiles up to its position, "
                           "none of a stopped lane's)",
    "slab_positions_held": "decode steps x slab attention layers: "
                           "positions the slab holds (slots x T_max)",
}
#: the columns a decode block of a model with expert layers appends to its
#: token matrix, in this order (every row carries the same sums)
MOE_COUNTERS = ("moe_step_layers", "moe_assignments", "moe_experts_hit",
                "moe_experts_read", "moe_zero_assignments",
                "moe_held_assignments")
#: the columns a decode block of a model with state-space layers appends
#: after those, in this order
SSM_COUNTERS = ("ssm_step_layers", "ssm_lane_layers")
#: the columns a decode block of a model with slab attention layers appends
#: last, in this order
SLAB_COUNTERS = ("slab_positions_read", "slab_positions_held")
#: unique per-engine metric label values (e0, e1, ...)
_ENGINE_SEQ = itertools.count()


def _round_up_pow2(n: int, floor: int = 16) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


def _abstract_spec(x):
    """Array leaf → ShapeDtypeStruct (the cost seam's signature record);
    scalar leaves keep their numpy-inferred dtype."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
    return jax.ShapeDtypeStruct((), np.asarray(x).dtype)


def compiled_peak_bytes(compiled) -> Optional[int]:
    """A compiled program's peak by its own ``memory_analysis``:
    arguments + outputs + temporaries − what is aliased (donated
    arguments written in place); None where the backend gives none."""
    ma = compiled.memory_analysis()
    if ma is None:
        return None
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


class TransformerDecoder:
    """Cache-aware executor for a causal decoder-only ComputationGraph.

    ``t_max`` bounds the context (prompt + generated) a cache slot can
    hold; it defaults to the embedding's max_length and may not exceed
    it (position embeddings end there).

    ``mesh`` (r12): a named device mesh — canonically ``(data, tp)``
    from ``parallel.mesh.generation_mesh`` — shards the decoder
    end-to-end: parameters by role through a
    ``parallel.spec_layout.SpecLayout`` (embeddings/projections on
    ``tp``, optional fsdp axis), the per-layer [B, H/g, T_max, g·Dh] KV
    cache with head groups on ``tp`` and batch/slots on ``data``, and every
    jitted impl compiled with NamedSharding-constrained in/out
    shardings (pure GSPMD — the traced math is unchanged, XLA inserts
    the collectives). Divisibility (heads by tp, batch rows by data) is
    validated up front; impl names gain a ``__m<data>x<tp>`` suffix so
    the compile auditor attributes per-mesh lowerings instead of
    misreading two meshes as one blown jit cache."""

    def __init__(self, net, t_max: Optional[int] = None, mesh=None,
                 spec_layout=None, sentinel: bool = False,
                 logit_bound: Optional[float] = 1e4):
        # on-device numerics sentinel (ISSUE 15): when enabled, the
        # serving impls (decode blocks, batched/chunked prefill) fold a
        # per-row finite/abs-bound check over the logits into their
        # carries and append the verdict to the SAME array the engine
        # already reads back — one extra int32 column, zero extra
        # readbacks, `{}` steady compiles. Opt-in at construction: the
        # sentinel and non-sentinel programs have different output
        # shapes, so an engine must match its decoder's setting.
        self.sentinel = bool(sentinel)
        self.logit_bound = None if logit_bound is None \
            else float(logit_bound)
        net._ensure_init()
        self.net = net
        conf = net.conf
        if len(conf.network_inputs) != 1 or len(conf.network_outputs) != 1:
            raise ValueError("TransformerDecoder needs a single-input, "
                             "single-output graph")
        self.input_name = conf.network_inputs[0]
        self.output_name = conf.network_outputs[0]
        # what a layer offers says what it is to the walk (``Window``):
        # ``advance`` keeps sequence state, ``embed`` turns ids into rows
        self.attn_names: List[str] = []
        # vertices whose class says ``counts_tokens`` (routed experts): their
        # per-expert token counts (the layer's second return) leave a decode
        # block as MOE_COUNTERS columns; none, and every program is what it
        # was
        self.moe_names: List[str] = []
        # vertices that keep sequence state of a FIXED size a slot (a
        # state-space mixer): among attn_names; admission overwrites their
        # slot whole, and their update counts leave a block as
        # SSM_COUNTERS columns
        self.state_names: List[str] = []
        # vertices whose class says ``slab_reads`` (a k/v slab read in a
        # decode step): among attn_names; what their attention reads leaves
        # a block as SLAB_COUNTERS columns
        self.slab_names: List[str] = []
        embed = None
        for name in conf.topological_order:
            v = conf.vertices[name]
            if not isinstance(v, LayerVertex):
                continue
            if v.preprocessor is not None:
                raise ValueError(f"vertex '{name}' has a preprocessor; the "
                                 "decode walk supports plain transformer "
                                 "topologies only")
            if hasattr(v.layer, "advance"):
                if not v.layer.causal:
                    raise ValueError(f"attention vertex '{name}' is not "
                                     "causal — cannot decode "
                                     "autoregressively")
                self.attn_names.append(name)
                if getattr(v.layer, "fixed_state", False):
                    self.state_names.append(name)
                if getattr(v.layer, "slab_reads", False):
                    self.slab_names.append(name)
            elif hasattr(v.layer, "embed"):
                embed, self._embed_name = v.layer, name
            elif getattr(v.layer, "counts_tokens", False):
                self.moe_names.append(name)
        if embed is None or not self.attn_names:
            raise ValueError("graph has no TokenAndPositionEmbedding / "
                             "causal SelfAttentionLayer — not a decoder LM")
        out_v = conf.vertices[self.output_name]
        if not (isinstance(out_v, LayerVertex) and
                hasattr(out_v.layer, "preoutput")):
            raise ValueError("output vertex must be a projection head "
                             "(RnnOutputLayer/OutputLayer)")
        self.embed = embed
        if t_max is None:
            t_max = embed.max_length
        if t_max > embed.max_length:
            raise ValueError(f"t_max {t_max} > embedding max_length "
                             f"{embed.max_length}")
        self.t_max = int(t_max)
        self.vocab_size = out_v.layer.n_out
        self._jit: Dict = {}
        # cost seam: impl audit name → [jitted fn, first-dispatch
        # abstract arg specs, memoized memory_analysis peak
        # (program_peak_bytes)]
        self._cost_seam: Dict[str, List] = {}
        self._cast_src = None
        self._cast_params = None
        # ---- mesh sharding (r12) ----
        self.mesh = mesh
        self._layout = None
        self._param_specs = None
        self._cache_sharding = None
        self._impl_suffix = ""          # per-mesh compile attribution
        self._row_shardings = None
        self._pool_shardings_cached = None   # paged-pool NamedShardings
        if mesh is not None:
            if self.moe_names or self.latent_cache_bytes_per_token or \
                    self.state_names:
                raise NotImplementedError(
                    "a latent (compressed-KV) cache, routed experts and a "
                    "state-space layer's state have no layout under a "
                    "mesh: SpecLayout has no latent, expert or state rule; "
                    "decode them on one device")
            from ..parallel.mesh import mesh_tag, validate_decode_mesh
            from ..parallel.spec_layout import (SpecLayout,
                                                decoder_param_specs,
                                                validate_param_specs)
            self._layout = spec_layout if spec_layout is not None \
                else SpecLayout()
            for name in self.kv_names:
                validate_decode_mesh(
                    mesh, num_heads=conf.vertices[name].layer.num_heads,
                    data_axis=self._layout.data_axis,
                    tp_axis=self._layout.tp_axis)
            self._param_specs = decoder_param_specs(self, self._layout)
            validate_param_specs(mesh, self._param_specs, net.params)
            self._cache_sharding = NamedSharding(mesh,
                                                 self._layout.kv_cache())
            self._impl_suffix = "__m" + mesh_tag(mesh)

    @property
    def kv_names(self) -> List[str]:
        """The sequence-state vertices whose cache is rows by position."""
        return [n for n in self.attn_names if n not in self.state_names]

    # ------------------------------------------------------------ sharding
    @property
    def data_axis_size(self) -> int:
        """Rows-per-dispatch divisor: batch/slot counts must divide by
        the data axis (1 for an unsharded decoder)."""
        if self.mesh is None:
            return 1
        return int(self.mesh.shape.get(self._layout.data_axis, 1))

    def _ns(self, spec) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def _sharding_sets(self):
        """(params tree, caches tree, row [B...], matrix [B, T]) —
        NamedSharding pytrees for the jit in/out constraints, built once
        per decoder (the structures never change)."""
        if self._row_shardings is None:
            from ..parallel.spec_layout import param_shardings
            psh = param_shardings(self.mesh, self._param_specs,
                                  self.net.params)
            csh = jax.tree_util.tree_map(
                lambda _: self._cache_sharding,
                jax.eval_shape(lambda: self.init_cache(
                    self.data_axis_size)))
            self._row_shardings = (psh, csh,
                                   self._ns(self._layout.batch(1)),
                                   self._ns(self._layout.batch(2)))
        return self._row_shardings

    # ------------------------------------------------------------- params
    def _device_params(self):
        """Params cast once to the net's compute dtype (inference decode is
        read-only; recast only when net.params is replaced by training).
        With a mesh, the cast params are also PLACED once per the
        SpecLayout's role table — a model larger than one device lives
        distributed from here on."""
        if self._cast_params is None or self._cast_src is not self.net.params:
            if self.mesh is not None:
                # cast INSIDE a jit whose out_shardings are the role
                # table: the bf16 copy is born sharded instead of
                # materializing whole on one device and being re-put —
                # for a model that only fits distributed, that interim
                # replica is exactly the OOM tp exists to avoid
                psh, _, _, _ = self._sharding_sets()

                # no donation: the f32 master params stay live on the
                # net (training updates them; this is a read-only cast)
                def cast_params_impl(p):
                    return self.net._cast_params(p)

                # per-mesh audit name, like every other sharded impl:
                # two meshes' casts share the dynamic signature and a
                # bare shared name would read as a blown jit cache
                cast_params_impl.__name__ += self._impl_suffix
                cast = jax.jit(  # graftlint: disable=GL005
                    cast_params_impl,
                    out_shardings=psh)(self.net.params)
            else:
                cast = self.net._cast_params(self.net.params)
            self._cast_params = cast
            self._cast_src = self.net.params
        return self._cast_params

    # -------------------------------------------------------------- cache
    def init_cache(self, batch: int) -> Dict[str, Dict]:
        """{attn_name: {"k","v" [B, H/g, t_max, g·Dh]}} for every
        attention vertex (g = :attr:`kv_heads_per_row`), preallocated in
        the net's compute dtype. With a mesh the cache is BORN sharded
        (slots over ``data``, head groups over ``tp``) — it is the
        dominant serving allocation and must never materialize
        replicated."""
        return {name: self.net.conf.vertices[name].layer.init_cache(
                    batch, self.t_max, self.net.compute_dtype,
                    sharding=self._cache_sharding)
                for name in self.attn_names}

    @property
    def kv_heads_per_row(self) -> int:
        """``g`` of the slab this decoder allocates: heads sharing one
        128-lane cache row (``SelfAttentionLayer.heads_per_row`` under
        this decoder's tp axis; 1 is the unpacked [B, H, T_max, Dh])."""
        tp = 1 if self.mesh is None else \
            int(self.mesh.shape.get(self._layout.tp_axis, 1))
        return min(self.net.conf.vertices[n].layer.heads_per_row(tp)
                   for n in self.kv_names)

    @property
    def latent_cache_bytes_per_token(self) -> int:
        """Bytes one cached token takes over all latent-attention layers
        (each holds one ``[c_kv ; k_rope]`` row a token, nothing per
        head); 0 for a model whose cache is per-head k/v."""
        return sum(self.net.conf.vertices[n].layer.latent_bytes_per_token(
            self.net.compute_dtype) for n in self.kv_names)

    def program_peak_bytes(self, impl_name: str) -> Optional[int]:
        """:func:`compiled_peak_bytes` of an impl that has been
        dispatched once (``decode_block4_impl``): compiled again from the
        signature the cost seam recorded at that dispatch, once, and
        kept. None before the first dispatch or where the backend gives
        no analysis."""
        entry = self._cost_seam.get(impl_name)
        if entry is None or entry[1] is None:
            return None
        if entry[2] is None:
            entry[2] = compiled_peak_bytes(
                entry[0].lower(*entry[1]).compile())
        return entry[2]

    def _pool_shardings(self):
        """Paged-pool NamedSharding tree (heads over tp, pages and the
        in-page dim unsharded) for the paged impls' in/out constraints;
        None on an unsharded decoder."""
        if self.mesh is None:
            return None
        if self._pool_shardings_cached is None:
            psh = NamedSharding(self.mesh, self._layout.kv_pages())
            self._pool_shardings_cached = {n: {"k": psh, "v": psh}
                                           for n in self.attn_names}
        return self._pool_shardings_cached

    def init_paged_pool(self, num_pages: int,
                        page_size: int) -> Dict[str, Dict]:
        """{attn_name: {"k","v" [P, H, page_size, Dh]}} — one paged
        pool per attention vertex, replacing the contiguous slab.
        With a mesh the pool is BORN sharded heads-over-tp (the same
        axis the slab shards H on); pages replicate over data, since
        any slot may map any page."""
        sharding = None
        if self.mesh is not None:
            sharding = NamedSharding(self.mesh, self._layout.kv_pages())
        return {name: self.net.conf.vertices[name].layer.init_page_pool(
                    int(num_pages), int(page_size),
                    self.net.compute_dtype, sharding=sharding)
                for name in self.attn_names}

    # --------------------------------------------------------------- walk
    # graftlint: traced
    def _walk(self, params, state, caches, tokens, window, every=False):
        """The graph, vertex by vertex, over ``tokens`` — padded prompts or
        a window [B, C], or one id a row [B] — placed by ``window`` (a
        :class:`Window`: fresh prompt, decode step, chunk, verify window;
        slab or pages). The embedding and every vertex that keeps sequence
        state read the window themselves (``embed`` / ``advance``: a prompt
        rides the attention helper seam — flash/short-T kernels — while it
        fills the cache, a step or a window attends earlier context through
        it); ``caches`` None is the NO-CACHE reference, a full
        teacher-forced forward that writes nothing. Where ``window.alive``
        is given, a counting vertex gets it as its mask and its second
        return is kept. The head projects each row's LAST real position
        (the one position of a step) or, with ``every``, all of them
        (speculative acceptance needs every position's distribution).
        Returns (logits [B, V] or [B, C, V] f32, new caches, counts)."""
        conf = self.net.conf
        acts = {self.input_name: tokens}
        new_caches, tally = {}, []
        counting = self.moe_names if window.alive is not None else ()
        logits = None
        for name in scoped(conf.topological_order):
            v = conf.vertices[name]
            xs = [acts[i] for i in conf.vertex_inputs[name]]
            if name == self._embed_name:
                acts[name] = v.layer.embed(params[name], xs[0], window)
            elif name in self.attn_names:
                acts[name], new_caches[name] = v.layer.advance(
                    params[name], xs[0],
                    None if caches is None else caches[name], window)
            elif name == self.output_name and every:
                logits = v.layer.preoutput(head_params(conf, params, name),
                                           xs[0])
            elif name == self.output_name:
                h = xs[0]
                if window.valid is not None:
                    # gather each row's last real hidden state BEFORE the
                    # vocab projection: [B, Tp, V] logits would be GBs at
                    # a 32k vocab; [B, 1, V] is what sampling needs
                    idx = jnp.clip(window.valid - 1, 0)[:, None, None]
                    h = jnp.take_along_axis(h, idx, axis=1)
                logits = v.layer.preoutput(head_params(conf, params, name),
                                           h)[:, 0]
            elif name in counting:
                acts[name], load = v.forward(
                    params[name], state[name], xs, train=False, rng=None,
                    masks=[window.alive[:, None]])
                tally.append(load)
            else:
                y, _ = v.forward(params[name], state[name], xs, train=False,
                                 rng=None, masks=[None] * len(xs))
                acts[name] = y
        return logits.astype(jnp.float32), new_caches, tally

    def recompute_logits(self, tokens, lengths, temps=None, seed: int = 0):
        """No-cache baseline step: one full forward over [B, Tp] plus the
        same on-device next-token selection decode_step does. Returns
        (ids [B], logits [B, V] f32)."""
        b = tokens.shape[0]
        temps = np.zeros(b, np.float32) if temps is None \
            else np.broadcast_to(np.asarray(temps, np.float32), (b,))
        fn = self._jit.get("recompute")
        if fn is None:
            def recompute_impl(params, state, tokens, lengths, temps, key):
                logits, _, _ = self._walk(
                    params, state, None, tokens,
                    Window.fresh(tokens.shape[1], lengths))
                return self._select(logits, temps, key), logits
            # no donation on purpose: the baseline recomputes from the SAME
            # tokens every step and mutates no carried state
            fn = jax.jit(recompute_impl)   # graftlint: disable=GL005
            self._jit["recompute"] = fn
        return fn(self._device_params(), self.net._inference_state(),
                  jnp.asarray(tokens, jnp.int32),
                  jnp.asarray(lengths, jnp.int32), jnp.asarray(temps),
                  jax.random.PRNGKey(seed))

    @staticmethod
    # graftlint: traced
    def _select(logits, temps, key):
        """Per-row next token: greedy where temps <= 0, temperature
        sampling elsewhere — one compile serves mixed batches.

        Sampling draws from bf16-ROUNDED logits (r12): GSPMD partitions
        matmul reductions differently per mesh shape, wiggling f32
        logits by ~1e-5, and a categorical draw that flips on that
        noise forks the whole downstream token stream — so fixed-seed
        sampled outputs could never be token-identical across meshes.
        Rounding to bf16 (~0.4% quanta, far below the noise temperature
        sampling injects by design) makes the sampled stream
        insensitive to sub-quantum differences. Greedy stays on raw f32
        logits: its argmax gaps are macroscopic for any trained model,
        and the r6 contract (greedy == teacher-forced reference) must
        not move."""
        with jax.named_scope("sample"):
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            t = jnp.maximum(temps, 1e-6)[:, None]
            ql = logits.astype(jnp.bfloat16).astype(jnp.float32)
            sampled = jax.random.categorical(key, ql / t,
                                             axis=-1).astype(jnp.int32)
            return jnp.where(temps <= 0, greedy, sampled)

    # graftlint: traced
    def _moe_sums(self, tally):
        """One decode step's MOE_COUNTERS int32 from its expert layers'
        counts: of alive lanes' tokens, and of the choices that reached the
        experts. A choice is of a zero-compute expert (counted apart, by
        the layer), of an expert held here, or of one held elsewhere; the
        distinct experts are counted among those held here."""
        layers = [self.net.conf.vertices[n].layer for n in self.moe_names]

        def held(key):
            return jnp.stack([
                t[key][v.first_expert:v.first_expert + v.experts_held]
                if v.experts_held else t[key]
                for t, v in zip(tally, layers)])
        load = jnp.stack([t["expert_tokens"] for t in tally])   # [L, E]
        zero = jnp.stack([t.get("zero_tokens", jnp.int32(0))
                          for t in tally])                       # [L]
        chosen = jnp.sum(load, axis=1) + zero
        here = held("expert_tokens")
        return jnp.stack([
            jnp.sum(chosen > 0), jnp.sum(chosen), jnp.sum(here > 0),
            jnp.sum(held("expert_rows") > 0), jnp.sum(zero),
            jnp.sum(here)]).astype(jnp.int32)

    # graftlint: traced
    def _step_sums(self, stop, reads):
        """One decode step's :attr:`step_counters` int32: of the
        state-space layers, alive lanes and every lane, each times the
        layers (SSM_COUNTERS); of the slab attention layers, the
        ``(read, held)`` notes of their calls summed
        (``nn.helpers.slab_read_tally``; none noted, a paged pool, reads 0,
        0: SLAB_COUNTERS)."""
        sums = []
        if self.state_names:
            n = len(self.state_names)
            sums += [jnp.sum(~stop) * n, stop.shape[0] * n]
        if self.slab_names:
            sums += [sum(r for r, _ in reads), sum(h for _, h in reads)]
        return jnp.stack([jnp.asarray(x, jnp.int32) for x in sums])

    # graftlint: traced
    def _block_columns(self, toks, fault, moe, counted=None):
        """A decode block's ONE read-back matrix: its tokens [B, K], then
        the sentinel's verdict column (sentinel decoders), then the
        MOE_COUNTERS sums (models with expert layers), then the
        :attr:`step_counters` sums ``counted``, the same in every row. A
        model with none reads back [B, K], as ever."""
        cols = [toks]
        if self.sentinel:
            cols.append(fault.astype(jnp.int32)[:, None])
        for sums in (moe if self.moe_names else None, counted):
            if sums is not None:
                cols.append(jnp.broadcast_to(sums[None, :],
                                             (toks.shape[0], sums.shape[0])))
        return toks if len(cols) == 1 else jnp.concatenate(cols, axis=1)

    @property
    def step_counters(self) -> Tuple[str, ...]:
        """The counters a decode block carries step by step after the
        expert layers': SSM_COUNTERS (models with state-space layers),
        then SLAB_COUNTERS (models with slab attention layers)."""
        return (SSM_COUNTERS if self.state_names else ()) + \
            (SLAB_COUNTERS if self.slab_names else ())

    @property
    def counter_names(self) -> Tuple[str, ...]:
        """The counters a decode block's matrix ends in, in order."""
        return (MOE_COUNTERS if self.moe_names else ()) + self.step_counters

    def split_block(self, host: np.ndarray):
        """(matrix without the counter columns, their sums in the order of
        :attr:`counter_names`, or None) of a fetched decode-block matrix
        (see :meth:`_block_columns`)."""
        n = len(self.counter_names)
        if not n:
            return host, None
        return host[:, :-n], host[0, -n:]

    # graftlint: traced
    def _fault_of(self, logits, stop=None):
        """Per-row sentinel verdict over traced logits (sentinel
        decoders only): non-finite or out-of-bound rows flag True;
        frozen lanes (``stop``) are exempt — their overshoot logits are
        never consumed, so they must not fail a finished request."""
        from ..observability.integrity import logits_fault
        bad = logits_fault(logits, self.logit_bound)
        if stop is not None:
            bad = bad & ~stop
        return bad

    # graftlint: traced
    def _verify_accept(self, logits, ids, positions, draft, stopped,
                       temps, eos_ids, key, step0, key_salt):
        """Device-side acceptance for the verify impls (ISSUE 16):
        ``logits`` [B, K+1, V] are the drafted window's per-position
        next-token distributions, ``draft`` [B, K] the candidates.
        Selection replays the EXACT per-step machinery — same
        :meth:`_select` (greedy raw-f32 argmax, sampled from
        bf16-rounded logits per r12), same absolute-step key fold — so
        position j's selection is bitwise what ``decode_block`` would
        have emitted there. Acceptance is exact-match longest-prefix:
        every accepted token equals the model's own selection, so the
        output stream is IDENTICAL to non-speculative decoding (greedy
        provably; fixed-seed sampling by the same determinism the r12
        parity suites gate), and each verified block always emits at
        least the bonus token at the first mismatch. Emission is cut at
        the first emitted eos and at the context edge, and frozen lanes
        emit nothing. Returns (out [B, K+1 tokens | emit | (fault)],
        new_ids, new_positions, new_stopped)."""
        kq = logits.shape[1]                       # K+1 window positions
        kd = kq - 1
        sels = []
        for j in range(kq):                        # static unroll: small K
            kk = jax.random.fold_in(
                key, jnp.bitwise_or(key_salt, step0 + j + 1))
            sels.append(self._select(logits[:, j], temps, kk))
        sel = jnp.stack(sels, axis=1)              # [B, K+1]
        idxs = jnp.arange(kq, dtype=jnp.int32)[None, :]
        match = jnp.cumprod((sel[:, :kd] == draft).astype(jnp.int32),
                            axis=1)
        emit = jnp.sum(match, axis=1).astype(jnp.int32) + 1   # + bonus
        hit = jnp.logical_and(eos_ids[:, None] >= 0,
                              sel == eos_ids[:, None])
        first_eos = jnp.min(jnp.where(hit, idxs, kq),
                            axis=1).astype(jnp.int32)
        emit = jnp.minimum(emit, first_eos + 1)    # eos ends the stream
        emit = jnp.minimum(emit, jnp.clip(self.t_max - positions, 0, kq))
        emit = jnp.where(stopped, 0, emit)
        new_pos = positions + emit
        last = jnp.take_along_axis(
            sel, jnp.clip(emit - 1, 0, kq - 1)[:, None], axis=1)[:, 0]
        new_ids = jnp.where(emit > 0, last, ids)
        # emit == first_eos + 1 can only hold with first_eos < kq
        # (emit <= kq), and whichever cut produced it, the final
        # emitted token IS the eos — freeze the lane
        new_stop = stopped | (emit == first_eos + 1) | \
            (new_pos >= self.t_max)
        out = jnp.concatenate([sel, emit[:, None]], axis=1)
        if self.sentinel:
            # only the positions whose selections are actually EMITTED
            # can fault a request: rejected-tail logits are garbage by
            # construction (they conditioned on a rejected draft), and
            # frozen lanes are exempt exactly like decode_block
            faults = jnp.stack(
                [self._fault_of(logits[:, j], stopped)
                 for j in range(kq)], axis=1)
            fault = jnp.any(faults & (idxs < emit[:, None]), axis=1)
            out = jnp.concatenate(
                [out, fault.astype(jnp.int32)[:, None]], axis=1)
        return out, new_ids, new_pos, new_stop

    # ---------------------------------------------------------- jit entry
    def _jit_sharded(self, impl, donate, in_specs=None, out_specs=None):
        """jit with optional NamedSharding-constrained in/out shardings.
        Unsharded decoders compile exactly as before (and keep the bare
        impl names the audit budgets reference); sharded ones pin the
        param/cache/row layouts so steady state never reshards, and the
        impl name carries the mesh suffix for per-mesh compile
        attribution."""
        if self.mesh is None:
            return jax.jit(impl, donate_argnums=donate)

        @functools.wraps(impl)
        def sharded_impl(*args):
            # tracers carry no sharding: tell the attention kernels which
            # mesh this jit partitions over (Mosaic needs a shard_map)
            with attention_spmd(self.mesh, self._layout.data_axis,
                                self._layout.tp_axis):
                return impl(*args)
        sharded_impl.__name__ = impl.__name__ + self._impl_suffix
        return jax.jit(sharded_impl, donate_argnums=donate,
                       in_shardings=in_specs, out_shardings=out_specs)

    def _jit_twin(self, impl, paged, donate, in_specs, out_specs):
        """One body, two programs: ``impl(params, state, caches, ptables,
        *rest)`` jitted as it stands, under ``paged_`` + its name, or as
        the slab program, which has no ``ptables`` argument. The specs are
        the slab's; the paged twin has the pools' in the caches' place and
        the tables' after them."""
        if paged:
            pool_sh = self._pool_shardings()
            mat = None if self.mesh is None else self._sharding_sets()[3]
            impl.__name__ = "paged_" + impl.__name__
            return self._jit_sharded(
                impl, donate,
                in_specs=in_specs[:2] + (pool_sh, mat) + in_specs[3:],
                out_specs=out_specs[:-1] + (pool_sh,))

        def slab(params, state, caches, *rest):
            return impl(params, state, caches, None, *rest)
        slab.__name__ = impl.__name__
        return self._jit_sharded(slab, donate, in_specs, out_specs)

    def _fn(self, name):
        fn = self._jit.get(name)
        if fn is not None:
            return fn
        donate = train_donate_argnums((2,))
        psh = csh = row = mat = None
        if self.mesh is not None:
            psh, csh, row, mat = self._sharding_sets()
        # distinct impl names: the compile auditor attributes compiles by
        # the wrapped function's __name__ (three fns named "impl" would
        # collapse into one audit row)
        if name == "prefill":
            def prefill_impl(params, state, caches, tokens, lengths, temps,
                             key):
                logits, caches, _ = self._walk(
                    params, state, caches, tokens,
                    Window.fresh(tokens.shape[1], lengths))
                return self._select(logits, temps, key), logits, caches
            fn = self._jit_sharded(
                prefill_impl, donate,
                in_specs=(psh, None, csh, mat, row, row, None),
                out_specs=(row, None, csh))
        elif name == "step":
            def decode_step_impl(params, state, caches, ids, positions,
                                 temps, key):
                logits, caches, _ = self._walk(
                    params, state, caches, ids,
                    Window(start=positions))
                return self._select(logits, temps, key), logits, caches
            fn = self._jit_sharded(
                decode_step_impl, donate,
                in_specs=(psh, None, csh, row, row, row, None),
                out_specs=(row, None, csh))
        elif name == "prefill_slots":
            def prefill_slots_impl(params, state, caches, tokens, lengths,
                                   slots, temps, key):
                # batched admission: ONE forward over [M, Tp] fills a
                # fresh M-slot cache, then each row scatters into the
                # shared cache at its slot index. M and Tp are bucketed
                # by the caller (pow2), so the signature set is finite.
                m, tp = tokens.shape
                # the fresh cache takes the SHARED cache's row layout
                # (heads per row are decided once, by init_cache under
                # the decoder's mesh)
                c1 = {n: {kk: jnp.zeros((m,) + leaf.shape[1:], leaf.dtype)
                          for kk, leaf in caches[n].items()}
                      for n in self.attn_names}
                logits, c1, _ = self._walk(params, state, c1, tokens,
                                           Window.fresh(tp, lengths))
                z = jnp.zeros((), jnp.int32)  # match slot dtype under x64
                merged = caches
                for i in range(m):    # static unroll: M <= num_slots
                    merged = {
                        n: {kk: jax.lax.dynamic_update_slice(
                                merged[n][kk],
                                jax.lax.dynamic_slice_in_dim(
                                    c1[n][kk], i, 1, axis=0)[:, :, :tp],
                                (slots[i], z, z, z))
                            for kk in caches[n]}
                        for n in self.kv_names} | {
                        n: merged[n] for n in self.state_names}
                # a fixed-size state is OVERWRITTEN whole, so that the
                # slot's last request leaves nothing behind: one scatter a
                # leaf, not a write a row (pad rows repeat row 0, the same
                # write twice; 36 layers x M rows unrolled took most of
                # an admission program's lowering)
                merged |= {n: {kk: caches[n][kk].at[slots].set(c1[n][kk])
                               for kk in caches[n]}
                           for n in self.state_names}
                sel = self._select(logits, temps, key)
                if self.sentinel:
                    # verdict rides the SAME readback as the sampled
                    # ids: [M] → [M, 2] (id, fault) — no extra sync
                    sel = jnp.stack(
                        [sel, self._fault_of(logits).astype(jnp.int32)],
                        axis=1)
                return sel, logits, merged
            # admission buckets (M = pow2 <= num_slots) may undershoot
            # the data axis, so the batch-side inputs stay unconstrained;
            # the SHARED cache keeps its pinned layout through the
            # scatter either way
            fn = self._jit_sharded(
                prefill_slots_impl, donate,
                in_specs=(psh, None, csh, None, None, None, None, None),
                out_specs=(None, None, csh))
        elif isinstance(name, tuple) and name[0] == "chunk":
            c_len = int(name[1])

            def prefill_chunk_impl(params, state, caches, tokens, pos0,
                                   valid, slot, temps, key, fault_in):
                # one slot's [1, C] prompt window prefilled into the
                # SHARED cache at [pos0, pos0+C): slice the slot row,
                # run the chunk walk (embed at absolute positions,
                # chunk attention over the already-filled cells),
                # scatter the row back. Bounded device work per
                # dispatch — decode blocks interleave between chunks,
                # so one 10k-token prompt cannot stall every stream.
                z = jnp.zeros((), jnp.int32)
                c1 = {n: {kk: jax.lax.dynamic_slice_in_dim(
                              caches[n][kk], slot[0], 1, axis=0)
                          for kk in caches[n]}
                      for n in self.attn_names}
                logits, c1, _ = self._walk(params, state, c1, tokens,
                                           Window(start=pos0, valid=valid))
                merged = {n: {kk: jax.lax.dynamic_update_slice(
                                  caches[n][kk], c1[n][kk],
                                  (slot[0], z, z, z))
                              for kk in caches[n]}
                          for n in self.attn_names}
                sel = self._select(logits, temps, key)
                if self.sentinel:
                    # windowed prefill has no per-window readback — the
                    # verdict ACCUMULATES on device (fault_in is the
                    # previous windows' OR) and is fetched only with
                    # the final window's single readback
                    fault = fault_in | \
                        self._fault_of(logits).astype(jnp.int32)
                    sel = jnp.stack([sel, fault], axis=1)
                return sel, merged
            # per-chunk-size name, like the per-K decode blocks: two
            # chunk sizes share every input rank and a bare shared name
            # would read as a blown jit cache in the compile audit
            prefill_chunk_impl.__name__ = f"prefill_chunk{c_len}_impl"
            # the batch-1 slice/scatter crosses the data axis on a
            # sharded cache; like prefill_slots, only the SHARED cache
            # keeps its pinned layout through the scatter
            fn = self._jit_sharded(
                prefill_chunk_impl, donate,
                in_specs=(psh, None, csh, None, None, None, None, None,
                          None, None),
                out_specs=(None, csh))
        elif name == "paged_prefill":
            def paged_prefill_impl(params, state, caches, tokens, pos0,
                                   valid, ptables, temps, key, fault_in):
                # batched PAGED admission: every row is a tail window
                # [pos0, pos0+valid) prefilled straight through its page
                # table — a prefix-cache hit never recomputes the shared
                # prefix's forward, it only attends its resident pages.
                # Count and window-length are bucketed by the caller
                # (pow2), so the signature set is finite. ``fault_in``
                # [M] is the sentinel's accumulated verdict for chunked
                # windows (zeros on direct admission; unused — and
                # DCE'd — on a non-sentinel decoder).
                logits, caches, _ = self._walk(
                    params, state, caches, tokens,
                    Window(start=pos0, valid=valid, pages=ptables))
                sel = self._select(logits, temps, key)
                if self.sentinel:
                    fault = fault_in | \
                        self._fault_of(logits).astype(jnp.int32)
                    sel = jnp.stack([sel, fault], axis=1)
                return sel, caches
            pool_sh = self._pool_shardings()
            # admission buckets may undershoot the data axis, so the
            # batch-side inputs stay unconstrained (like prefill_slots);
            # only the POOL keeps its pinned layout through the scatter
            fn = self._jit_sharded(
                paged_prefill_impl, donate,
                in_specs=(psh, None, pool_sh, None, None, None, None,
                          None, None, None),
                out_specs=(None, pool_sh))
        elif name == "kv_export":
            def kv_export_impl(caches, pids):
                # gather ``pids``'s page contents out of every layer's
                # pool — the device half of a KV handoff export
                # (streaming/disagg). Page count is pow2-bucketed by
                # the caller; pad rows gather the null/trash page and
                # are sliced off on host. Read-only: no donation.
                return {n: {kk: caches[n][kk][pids] for kk in ("k", "v")}
                        for n in self.attn_names}
            pool_sh = self._pool_shardings()
            fn = self._jit_sharded(kv_export_impl, (),
                                   in_specs=(pool_sh, None),
                                   out_specs=None)
        elif name == "kv_import":
            def kv_import_impl(caches, pids, frames):
                # scatter imported page frames into this pool — the
                # receive half of a KV handoff. Pad rows target the
                # null page: duplicate index-0 writes land in trash in
                # unspecified order, which is exactly what the trash
                # page is for.
                return {n: {kk: caches[n][kk].at[pids].set(frames[n][kk])
                            for kk in ("k", "v")}
                        for n in self.attn_names}
            pool_sh = self._pool_shardings()
            fn = self._jit_sharded(kv_import_impl,
                                   train_donate_argnums((0,)),
                                   in_specs=(pool_sh, None, None),
                                   out_specs=pool_sh)
        elif isinstance(name, tuple) and name[0] in ("block", "paged_block"):
            k_steps = int(name[1])

            def decode_block_impl(params, state, caches, ptables, ids,
                                  positions, stopped, temps, eos_ids, key,
                                  step0, key_salt):
                # K decode steps fused into ONE device program
                # (lax.scan): cache state, per-row stop flags, the
                # sentinel's fault accumulator, the expert counters and
                # the absolute step counter ride the carry; only the
                # [B, K(+1)(+6)] matrix ever needs to cross to the host.
                # The key schedule folds the ABSOLUTE step index, so a
                # given lane samples identically for every block size.
                # Over PAGED pools the page tables are one more input of
                # the dispatch (None on the slab): the host grows them
                # between blocks (lazy page allocation), the scan never
                # re-maps — carry, freeze and key schedule are these
                # same lines, which is what token-for-token parity
                # paged-vs-slab rests on
                def body(carry, _):
                    caches, ids, pos, stop, fault, moe, step = carry[:7]
                    pos_c = jnp.minimum(pos, self.t_max - 1)
                    with slab_read_tally() as reads:
                        logits, caches, tally = self._walk(
                            params, state, caches, ids,
                            Window(start=pos_c, pages=ptables, alive=~stop))
                    if tally:
                        moe = moe + self._moe_sums(tally)
                    # a model with state-space or slab attention layers
                    # carries their counts as one more element (neither:
                    # the carry is as it was)
                    counted = (carry[7] + self._step_sums(stop, reads),) \
                        if self.step_counters else ()
                    if self.sentinel:
                        fault = fault | self._fault_of(logits, stop)
                    kk = jax.random.fold_in(
                        key, jnp.bitwise_or(key_salt, step + 1))
                    nxt = self._select(logits, temps, kk)
                    # a stopped lane re-emits its last token and freezes
                    # its position: overshoot past eos/t_max stays inside
                    # the lane's own cache cell and is truncated on host
                    nxt = jnp.where(stop, ids, nxt)
                    hit_eos = jnp.logical_and(eos_ids >= 0, nxt == eos_ids)
                    new_pos = jnp.where(stop, pos, pos + 1)
                    new_stop = stop | hit_eos | (new_pos >= self.t_max)
                    return (caches, nxt, new_pos, new_stop, fault, moe,
                            step + 1) + counted, nxt
                fault0 = jnp.zeros_like(stopped)
                moe0 = jnp.zeros(len(MOE_COUNTERS), jnp.int32)
                counted0 = (jnp.zeros(len(self.step_counters), jnp.int32),) \
                    if self.step_counters else ()
                carry, toks = jax.lax.scan(
                    body, (caches, ids, positions, stopped, fault0, moe0,
                           step0) + counted0, None, length=k_steps)
                caches, ids, positions, stopped, fault, moe = carry[:6]
                out = self._block_columns(
                    toks.T, fault, moe,
                    carry[7] if self.step_counters else None)
                return out, ids, positions, stopped, caches
            # per-K name: the compile auditor attributes by __name__, and
            # two K values share every input shape — one shared name
            # would read as a blown-cache duplicate-signature compile
            # (_jit_sharded appends the per-mesh suffix the same way)
            decode_block_impl.__name__ = f"decode_block{k_steps}_impl"
            fn = self._jit_twin(
                decode_block_impl, name[0] == "paged_block", donate,
                in_specs=(psh, None, csh, row, row, row, row, row, None,
                          None, None),
                out_specs=(mat, row, row, row, csh))
        elif isinstance(name, tuple) and name[0] in ("verify",
                                                     "paged_verify"):
            k_draft = int(name[1])

            def verify_block_impl(params, state, caches, ptables, ids,
                                  positions, draft, stopped, temps, eos_ids,
                                  key, step0, key_salt):
                # speculative verify (ISSUE 16): ONE cache-aware forward
                # over the window [last id | K drafted candidates] scores
                # all K+1 next-token positions — roughly the memory
                # traffic of decoding ONE token (the r18 roofline
                # motivation) — then device-side longest-prefix
                # acceptance. Write validity clamps to the context edge
                # and zeroes for frozen lanes; rejected cells are
                # rewritten before ever attended, so rewind is the
                # returned position itself (host clamps nothing extra).
                # Over PAGED pools (``ptables``; None on the slab) the
                # window's writes ride the paged chunk path's null-page
                # redirect, and the HOST rewinds the page tables
                # afterwards (truncate + refcount release) — the device
                # program never re-maps
                window = jnp.concatenate([ids[:, None], draft], axis=1)
                wvalid = jnp.where(stopped, 0,
                                   jnp.clip(self.t_max - positions, 0,
                                            k_draft + 1))
                logits, caches, _ = self._walk(
                    params, state, caches, window,
                    Window(start=positions, valid=wvalid, masked=True,
                           pages=ptables), every=True)
                out, ids, positions, stopped = self._verify_accept(
                    logits, ids, positions, draft, stopped, temps,
                    eos_ids, key, step0, key_salt)
                return out, ids, positions, stopped, caches
            # per-K name, like the decode blocks: the compile auditor
            # attributes by __name__ and two K values share input ranks
            verify_block_impl.__name__ = f"verify_block{k_draft}_impl"
            fn = self._jit_twin(
                verify_block_impl, name[0] == "paged_verify", donate,
                in_specs=(psh, None, csh, row, row, mat, row, row, row,
                          None, None, None),
                out_specs=(mat, row, row, row, csh))
        elif name == "scrub_slot":
            def scrub_slot_impl(caches, slots):
                # slab twin of scrub_pages_impl: zero the given slots'
                # whole cache rows after a sentinel fault. Batched
                # prefill rewrites [0, tp) on refill, but a CHUNK-
                # admitted successor writes only its windows — residual
                # NaN past its fill point would poison it through the
                # masked probs·V contraction. Pad rows repeat a victim
                # slot (idempotent zeroing), keeping signatures finite.
                return {n: {kk: leaf.at[slots].set(0.0)
                            for kk, leaf in caches[n].items()}
                        for n in self.attn_names}
            fn = self._jit_sharded(scrub_slot_impl,
                                   train_donate_argnums((0,)),
                                   in_specs=(csh, None),
                                   out_specs=csh)
        elif name == "scrub_pages":
            def scrub_pages_impl(caches, pids):
                # corruption response (ISSUE 15): zero the given pages
                # before they re-enter the free list. Freed-page
                # contents are normally don't-care (masked attention
                # weights them 0.0), but 0.0 × NaN = NaN — non-finite
                # residue from a detected fault would poison the NEXT
                # stream mapped onto the page through the masked
                # probs·V contraction. pids are pow2-bucketed; pad
                # rows scrub the null/trash page (harmless by
                # definition).
                return {n: {kk: leaf.at[pids].set(0.0)
                            for kk, leaf in caches[n].items()}
                        for n in self.attn_names}
            pool_sh = self._pool_shardings()
            fn = self._jit_sharded(scrub_pages_impl,
                                   train_donate_argnums((0,)),
                                   in_specs=(pool_sh, None),
                                   out_specs=pool_sh)
        elif name == "corrupt_page":
            def corrupt_page_impl(caches, pid, mode):
                # CHAOS ONLY (device.corrupt_page): scripted silent-
                # data-corruption of one pool page — NaN fill (mode 0)
                # or a deterministic value flip (mode 1, sign-negate:
                # plausible magnitudes, wrong values — exactly what the
                # content checksums and the golden canary must catch
                # without the sentinel's finite check ever tripping).
                # Named + jitted like every impl so the compile auditor
                # attributes the chaos compile instead of flagging an
                # anonymous scatter.
                out = {}
                for n in self.attn_names:
                    out[n] = {}
                    for kk in ("k", "v"):
                        page = caches[n][kk][pid]
                        poison = jnp.where(mode == 0,
                                           jnp.full_like(page, jnp.nan),
                                           -page)
                        out[n][kk] = caches[n][kk].at[pid].set(poison)
                return out
            pool_sh = self._pool_shardings()
            fn = self._jit_sharded(corrupt_page_impl,
                                   train_donate_argnums((0,)),
                                   in_specs=(pool_sh, None, None),
                                   out_specs=pool_sh)
        elif name == "corrupt_cache":
            def corrupt_cache_impl(caches, slot, pos, mode):
                # CHAOS ONLY (device.corrupt_logits, slab path): poison
                # one slot's cache CELL at an always-attended position —
                # the next decode step's attention reads it and the
                # logits go non-finite (NaN) or wrong (flip). A state
                # layer has no positions: its slot's state goes whole
                out = {}
                for n in self.kv_names:
                    out[n] = {}
                    for kk in caches[n]:
                        cell = caches[n][kk][slot, :, pos, :]
                        poison = jnp.where(mode == 0,
                                           jnp.full_like(cell, jnp.nan),
                                           -cell)
                        out[n][kk] = \
                            caches[n][kk].at[slot, :, pos, :].set(poison)
                for n in self.state_names:
                    out[n] = {}
                    for kk in caches[n]:
                        state = caches[n][kk][slot]
                        poison = jnp.where(mode == 0,
                                           jnp.full_like(state, jnp.nan),
                                           -state)
                        out[n][kk] = caches[n][kk].at[slot].set(poison)
                return {n: out[n] for n in self.attn_names}
            fn = self._jit_sharded(corrupt_cache_impl,
                                   train_donate_argnums((0,)),
                                   in_specs=(csh, None, None, None),
                                   out_specs=csh)
        else:                                 # pragma: no cover
            raise KeyError(name)
        fn = self._with_cost_seam(fn)
        self._jit[name] = fn
        return fn

    def _impl_audit_name(self, name) -> str:
        """The impl's __name__ as the compile auditor sees it (per-K,
        per-mesh), read off the function :meth:`_fn` built for the key —
        devstats keys its cost table the same way, so the two views line
        up row for row."""
        return self._fn(name).__name__

    def _with_cost_seam(self, jitted):
        """Wrap a jitted impl so its FIRST dispatch captures the
        abstract arg signature (ShapeDtypeStructs — host-side, no device
        work) into ``_cost_seam``; devstats lowers from those specs on
        demand for the per-impl cost_analysis table. Steady-state cost:
        one dict-entry check per dispatch."""
        entry = [jitted, None, None]
        self._cost_seam[jitted.__name__] = entry

        def dispatch(*args):
            if entry[1] is None:
                entry[1] = jax.tree_util.tree_map(_abstract_spec, args)
            return jitted(*args)
        dispatch.__name__ = jitted.__name__
        return dispatch

    def prefill(self, caches, tokens, lengths, temps=None, seed: int = 0):
        """Fill ``caches`` from padded prompts [B, Tp] (+ true lengths
        [B]) and return (first sampled ids [B], last-position logits
        [B, V] f32, caches)."""
        b = tokens.shape[0]
        temps = np.zeros(b, np.float32) if temps is None \
            else np.broadcast_to(np.asarray(temps, np.float32), (b,))
        return self._fn("prefill")(
            self._device_params(), self.net._inference_state(), caches,
            jnp.asarray(tokens, jnp.int32), jnp.asarray(lengths, jnp.int32),
            jnp.asarray(temps), jax.random.PRNGKey(seed))

    def decode_step(self, caches, ids, positions, temps=None, key=None):
        """One fixed-shape decode step; returns (next ids [B], logits
        [B, V] f32, caches)."""
        b = np.shape(ids)[0]
        temps = np.zeros(b, np.float32) if temps is None \
            else np.broadcast_to(np.asarray(temps, np.float32), (b,))
        if key is None:
            key = jax.random.PRNGKey(0)
        return self._fn("step")(
            self._device_params(), self.net._inference_state(), caches,
            jnp.asarray(ids, jnp.int32), jnp.asarray(positions, jnp.int32),
            jnp.asarray(temps), key)

    def decode_block(self, caches, ids, positions, temps=None, key=None, *,
                     block_size: int, eos_ids=None, stopped=None,
                     step0=0, key_salt: int = 0):
        """``block_size`` fused decode steps in ONE device program.

        Returns ``(toks [B, K] int32, ids [B], positions [B], stopped
        [B] bool, caches)`` — everything device-resident, so the caller
        can dispatch the NEXT block from the carry before reading this
        block's tokens (double buffering: one host readback per block,
        overlapped with the next block's compute). ``eos_ids`` ([B]
        int32, -1 = no eos) freezes a lane on device the step after it
        emits its eos; frozen lanes re-emit their last token (truncated
        on host), so greedy output is token-for-token identical to the
        K=1 loop. ``step0`` is the absolute index of this block's first
        step: sampling keys fold the absolute step (+ ``key_salt``), so
        a fixed seed draws the same tokens for every block size."""
        b = np.shape(ids)[0]
        temps = np.zeros(b, np.float32) if temps is None \
            else np.broadcast_to(np.asarray(temps, np.float32), (b,))
        if key is None:
            key = jax.random.PRNGKey(0)
        eos = np.full(b, -1, np.int32) if eos_ids is None \
            else np.broadcast_to(np.asarray(eos_ids, np.int32), (b,))
        if stopped is None:
            stopped = np.zeros(b, bool)
        return self._fn(("block", int(block_size)))(
            self._device_params(), self.net._inference_state(), caches,
            jnp.asarray(ids, jnp.int32), jnp.asarray(positions, jnp.int32),
            jnp.asarray(stopped, jnp.bool_), jnp.asarray(temps),
            jnp.asarray(eos), key, jnp.asarray(step0, jnp.int32),
            jnp.asarray(key_salt, jnp.int32))

    # ------------------------------------------------------------- paged
    def paged_prefill(self, caches, tokens, pos0, valid, ptables,
                      temps=None, key=None, fault_in=None):
        """Batched tail prefill over PAGED pools: tokens [M, C] are
        each row's prompt tail starting at absolute position ``pos0``
        [M] (0 on a prefix-cache miss), ``valid`` [M] real tokens per
        row, ``ptables`` [M, NP] the rows' page tables. Returns
        (sampled next ids [M], pools) — ONE readback serves the whole
        admission wave, exactly like the slab's batched admission."""
        m = np.shape(tokens)[0]
        temps = np.zeros(m, np.float32) if temps is None \
            else np.broadcast_to(np.asarray(temps, np.float32), (m,))
        if key is None:
            key = jax.random.PRNGKey(0)
        if fault_in is None:
            fault_in = np.zeros(m, np.int32)
        return self._fn("paged_prefill")(
            self._device_params(), self.net._inference_state(), caches,
            jnp.asarray(tokens, jnp.int32), jnp.asarray(pos0, jnp.int32),
            jnp.asarray(valid, jnp.int32), jnp.asarray(ptables, jnp.int32),
            jnp.asarray(temps), key, jnp.asarray(fault_in, jnp.int32))

    def paged_decode_block(self, caches, ptables, ids, positions,
                           temps=None, key=None, *, block_size: int,
                           eos_ids=None, stopped=None, step0=0,
                           key_salt: int = 0):
        """``block_size`` fused decode steps over PAGED pools — the
        paged twin of :meth:`decode_block` (same carry contract, same
        absolute-step key schedule, so outputs are token-for-token
        identical to the slab path). ``ptables`` [B, NP] is a
        per-dispatch input: the host allocates pages lazily between
        blocks and passes the grown tables with the next dispatch."""
        b = np.shape(ids)[0]
        temps = np.zeros(b, np.float32) if temps is None \
            else np.broadcast_to(np.asarray(temps, np.float32), (b,))
        if key is None:
            key = jax.random.PRNGKey(0)
        eos = np.full(b, -1, np.int32) if eos_ids is None \
            else np.broadcast_to(np.asarray(eos_ids, np.int32), (b,))
        if stopped is None:
            stopped = np.zeros(b, bool)
        return self._fn(("paged_block", int(block_size)))(
            self._device_params(), self.net._inference_state(), caches,
            jnp.asarray(ptables, jnp.int32), jnp.asarray(ids, jnp.int32),
            jnp.asarray(positions, jnp.int32),
            jnp.asarray(stopped, jnp.bool_), jnp.asarray(temps),
            jnp.asarray(eos), key, jnp.asarray(step0, jnp.int32),
            jnp.asarray(key_salt, jnp.int32))

    def verify_block(self, caches, ids, positions, draft, temps=None,
                     key=None, *, eos_ids=None, stopped=None, step0=0,
                     key_salt: int = 0):
        """Speculatively verify ``draft`` [B, K] candidate tokens in ONE
        cache-aware forward over the K+1 window [last id | draft]
        (ISSUE 16). Returns ``(out [B, K+1 tokens | emit col |
        (fault col)] int32, ids [B], positions [B], stopped [B],
        caches)``: row b emits ``out[b, :out[b, K+1]]`` — the accepted
        draft prefix plus the model's own token at the first mismatch —
        and the returned carry is already REWOUND to the accepted
        length (a position clamp; paged callers additionally truncate
        their page tables). ``step0``/``key_salt`` follow
        :meth:`decode_block`'s absolute-step key schedule, so emitted
        tokens are exactly what the non-speculative path would emit."""
        b = np.shape(ids)[0]
        draft = np.asarray(draft, np.int32)
        temps = np.zeros(b, np.float32) if temps is None \
            else np.broadcast_to(np.asarray(temps, np.float32), (b,))
        if key is None:
            key = jax.random.PRNGKey(0)
        eos = np.full(b, -1, np.int32) if eos_ids is None \
            else np.broadcast_to(np.asarray(eos_ids, np.int32), (b,))
        if stopped is None:
            stopped = np.zeros(b, bool)
        return self._fn(("verify", int(draft.shape[1])))(
            self._device_params(), self.net._inference_state(), caches,
            jnp.asarray(ids, jnp.int32), jnp.asarray(positions, jnp.int32),
            jnp.asarray(draft), jnp.asarray(stopped, jnp.bool_),
            jnp.asarray(temps), jnp.asarray(eos), key,
            jnp.asarray(step0, jnp.int32), jnp.asarray(key_salt, jnp.int32))

    def paged_verify_block(self, caches, ptables, ids, positions, draft,
                           temps=None, key=None, *, eos_ids=None,
                           stopped=None, step0=0, key_salt: int = 0):
        """Paged twin of :meth:`verify_block` — same window, same
        acceptance, same rewound carry; ``ptables`` [B, NP] ride as a
        per-dispatch input exactly like :meth:`paged_decode_block`."""
        b = np.shape(ids)[0]
        draft = np.asarray(draft, np.int32)
        temps = np.zeros(b, np.float32) if temps is None \
            else np.broadcast_to(np.asarray(temps, np.float32), (b,))
        if key is None:
            key = jax.random.PRNGKey(0)
        eos = np.full(b, -1, np.int32) if eos_ids is None \
            else np.broadcast_to(np.asarray(eos_ids, np.int32), (b,))
        if stopped is None:
            stopped = np.zeros(b, bool)
        return self._fn(("paged_verify", int(draft.shape[1])))(
            self._device_params(), self.net._inference_state(), caches,
            jnp.asarray(ptables, jnp.int32), jnp.asarray(ids, jnp.int32),
            jnp.asarray(positions, jnp.int32), jnp.asarray(draft),
            jnp.asarray(stopped, jnp.bool_), jnp.asarray(temps),
            jnp.asarray(eos), key, jnp.asarray(step0, jnp.int32),
            jnp.asarray(key_salt, jnp.int32))

    def kv_export(self, caches, pids):
        """Gather page contents ({layer: {"k","v"} [n, H, page_size,
        Dh]}) off the paged pools — the device half of a disaggregated
        KV handoff (streaming/disagg). ``pids`` should arrive
        pow2-bucketed (pad with the null page) so the signature set
        stays finite; the pools are read, never donated."""
        return self._fn("kv_export")(caches, jnp.asarray(pids, jnp.int32))

    def kv_import(self, caches, pids, frames):
        """Scatter imported page frames into the paged pools at
        ``pids`` (donating the old pools) — the receive half of a KV
        handoff. Same bucketing contract as :meth:`kv_export`; pad
        rows target the null/trash page."""
        return self._fn("kv_import")(caches, jnp.asarray(pids, jnp.int32),
                                     frames)

    def corrupt_page(self, caches, pid: int, mode: str = "nan"):
        """CHAOS ONLY: scripted silent corruption of pool page ``pid``
        (``device.corrupt_page`` payload) — returns the poisoned pools
        (old ones donated). ``mode``: "nan" trips the sentinel's
        finite check; "flip" (sign-negate) leaves plausible magnitudes
        that only content checksums / the golden canary can catch."""
        return self._fn("corrupt_page")(
            caches, jnp.asarray(pid, jnp.int32),
            jnp.asarray(0 if mode == "nan" else 1, jnp.int32))

    def corrupt_cache(self, caches, slot: int, pos: int,
                      mode: str = "nan"):
        """CHAOS ONLY: scripted corruption of one slab cache cell
        (``device.corrupt_logits`` payload on the slab path)."""
        return self._fn("corrupt_cache")(
            caches, jnp.asarray(slot, jnp.int32),
            jnp.asarray(pos, jnp.int32),
            jnp.asarray(0 if mode == "nan" else 1, jnp.int32))

    # ----------------------------------------------------------- generate
    def generate(self, prompts: Sequence, max_new_tokens: int,
                 temperature=0.0, eos_id: Optional[int] = None,
                 seed: int = 0, block_size: int = 1) -> List[np.ndarray]:
        """Batched autoregressive generation: ragged int prompts →
        [prompt + generated] per row. Greedy where the (scalar or
        per-row) temperature is <= 0, temperature sampling elsewhere;
        per-row stop on ``eos_id``, ``max_new_tokens``, or a full
        context (t_max). The decode loop is fixed-shape — ONE compile
        serves every request mix.

        ``block_size=1`` is the legacy per-step loop ([B] ids cross to
        the host every step). ``block_size=K>1`` runs K steps per device
        program and pipelines: block t+1 is dispatched from the
        on-device carry BEFORE block t's [B, K] token matrix is read
        back, so host bookkeeping overlaps device compute and there is
        exactly ONE readback per block. Outputs are token-for-token
        identical across block sizes (greedy AND fixed-seed sampling:
        the key schedule folds the absolute step index)."""
        prompts = [np.asarray(p, np.int32).reshape(-1) for p in prompts]
        n_real = len(prompts)
        if n_real == 0:
            return []
        # mesh: batch rows shard over the data axis — pad to a multiple
        # with copies of row 0 (their outputs are dropped below), so any
        # request count decodes on the full mesh
        pad = (-n_real) % self.data_axis_size
        if pad:
            prompts = prompts + [prompts[0].copy() for _ in range(pad)]
        b = len(prompts)
        lengths = np.asarray([len(p) for p in prompts], np.int32)
        if (lengths < 1).any():
            raise ValueError("empty prompt")
        if int(lengths.max()) > self.t_max:
            raise ValueError(f"prompt length {int(lengths.max())} > t_max "
                             f"{self.t_max}")
        tp = min(_round_up_pow2(int(lengths.max())), self.t_max)
        tokens = np.zeros((b, tp), np.int32)
        for i, p in enumerate(prompts):
            tokens[i, :len(p)] = p
        # per-row temps broadcast against the REAL row count; pad rows
        # (outputs dropped) reuse row 0's temp like they reuse its prompt
        temps = np.broadcast_to(
            np.asarray(temperature, np.float32), (n_real,)).copy()
        if pad:
            temps = np.concatenate([temps, np.repeat(temps[:1], pad)])
        key = jax.random.PRNGKey(seed)
        nxt, _, caches = self.prefill(self.init_cache(b), tokens, lengths,
                                      temps, seed=seed)
        gen: List[List[int]] = [[] for _ in range(b)]
        finished = np.zeros(b, bool)

        def consume(tok_cols: np.ndarray) -> None:
            """Host bookkeeping for a [B, k] column block: append until a
            row's stop (eos / budget / full context); later columns of a
            finished row are device overshoot and are dropped."""
            for c in range(tok_cols.shape[1]):
                for i in range(b):
                    if finished[i]:
                        continue
                    tok = int(tok_cols[i, c])
                    gen[i].append(tok)
                    if (eos_id is not None and tok == eos_id) or \
                            len(gen[i]) >= max_new_tokens or \
                            int(lengths[i]) + len(gen[i]) >= self.t_max:
                        finished[i] = True

        if int(block_size) <= 1:
            # legacy per-step loop: dispatch, read [B] ids, repeat — the
            # deliberate K=1 baseline of the block-sweep A/B; the
            # per-step sync IS the measured quantity, so GL007's fix
            # (fuse into blocks) is the pipelined path below, not here
            nxt_host = np.asarray(nxt)
            for step in range(int(max_new_tokens)):
                consume(nxt_host[:, None])
                if finished.all() or step == int(max_new_tokens) - 1:
                    break
                positions = np.minimum(lengths + step, self.t_max - 1)
                nxt, _, caches = self.decode_step(
                    caches, nxt_host, positions, temps,
                    key=jax.random.fold_in(key, step + 1))
                nxt_host = np.asarray(nxt)   # graftlint: disable=GL007
            return [np.concatenate([p, np.asarray(g, np.int32)])
                    for p, g in zip(prompts[:n_real], gen[:n_real])]

        # ---- pipelined block path ----
        k = int(block_size)
        if int(max_new_tokens) >= 1:     # K=1 parity: no tokens requested,
            consume(device_fetch(          # none emitted (prefill included)
                nxt, tag="generate.prefill")[:, None])
        n_steps = int(max_new_tokens) - 1
        if finished.all() or n_steps <= 0:
            return [np.concatenate([p, np.asarray(g, np.int32)])
                    for p, g in zip(prompts[:n_real], gen[:n_real])]
        eos_arr = np.full(b, -1 if eos_id is None else int(eos_id), np.int32)
        ids_d, pos_d = nxt, jnp.asarray(lengths, jnp.int32)
        stop_d = np.zeros(b, bool)
        n_blocks = -(-n_steps // k)          # ceil

        def fetch_block(dev) -> np.ndarray:
            # sentinel decoders append the per-row fault verdict as one
            # extra column on the block matrix (same single readback):
            # a tripped REAL row fails the whole batch call — this is
            # the library entry point, with no per-request recovery
            # seam; the serving engine fails only the tripped request
            arr, _ = self.split_block(
                device_fetch(dev, tag="generate.decode"))
            if self.sentinel:
                bad = np.nonzero(arr[:n_real, -1])[0]
                if len(bad):
                    from ..observability.integrity import NumericalFault
                    raise NumericalFault(
                        f"numerics sentinel tripped on row(s) "
                        f"{bad.tolist()}: non-finite or out-of-bound "
                        "logits in a decode block — tokens dropped")
                arr = arr[:, :-1]
            return arr

        pending = None
        for blk in range(n_blocks):
            toks, ids_d, pos_d, stop_d, caches = self.decode_block(
                caches, ids_d, pos_d, temps, key=key, block_size=k,
                eos_ids=eos_arr, stopped=stop_d, step0=blk * k)
            if pending is not None:
                # read block t WHILE block t+1 computes (double buffer)
                consume(fetch_block(pending))
                if finished.all():
                    pending = None     # in-flight block is pure overshoot
                    break
            pending = toks
        if pending is not None:
            consume(fetch_block(pending))
        return [np.concatenate([p, np.asarray(g, np.int32)])
                for p, g in zip(prompts[:n_real], gen[:n_real])]


def _book_gap(gaps: list, req: "GenerationRequest", t: float,
              adm_bid: int) -> None:
    """The lane gap ledger of one retiring block: add the gap from
    ``req``'s previous emission to ``t`` to ``gaps`` — ``[plain n, plain
    seconds, admission n, admission seconds]`` — as admission-held where
    an admission (id ``adm_bid``, the newest dispatched before this
    block) entered the device queue after the work of that emission."""
    if req._emissions:
        if req._emit_block < adm_bid:
            gaps[2] += 1
            gaps[3] += t - req._emissions[-1][0]
        else:
            gaps[0] += 1
            gaps[1] += t - req._emissions[-1][0]


class GenerationRequest:
    """Handle for one queued prompt; ``result()`` blocks until the
    engine completes it (the full [prompt + generated] id array).

    Lifecycle states (``.state``): PENDING (queued), RUNNING (holds a
    cache slot), DONE, FAILED, CANCELLED. ``deadline`` (seconds from
    submission) is enforced by the engine mid-decode — an expired
    request's slot is freed for the queue and ``result()`` raises
    :class:`DeadlineExceeded`. ``cancel()`` requests the same slot-free
    path with :class:`Cancelled`; it is honored at the next engine
    sweep, whether the request is still queued or already decoding."""

    PENDING = "PENDING"
    RUNNING = "RUNNING"
    DONE = "DONE"
    FAILED = "FAILED"
    CANCELLED = "CANCELLED"

    def __init__(self, prompt, max_new_tokens: int, temperature: float,
                 eos_id: Optional[int], deadline: Optional[float] = None):
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.eos_id = eos_id
        self.deadline = None if deadline is None else float(deadline)
        self._deadline_t = None if deadline is None \
            else interval_now() + float(deadline)
        self.generated: List[int] = []
        self._seq = next(_REQ_SEQ)       # EDF tie-break: FIFO by creation
        self._done = threading.Event()
        self._result: Optional[np.ndarray] = None
        self._error: Optional[BaseException] = None
        self._running = False              # holds a cache slot right now
        self._cancel_requested = False
        self._engine = None                # set at submit; woken on cancel
        # completion hooks (fleet tier): fired exactly once per callback
        # when the request reaches a terminal state, outside every engine
        # lock — the fleet router's dedup ledger hangs off this seam
        self._cb_lock = threading.Lock()
        self._callbacks: List = []
        # observability: one Trace per request for its WHOLE life — it
        # rides on the request through supervisor quarantine/requeue, so
        # a recovered request keeps its original timeline (plus a
        # `takeover` span per restart) instead of starting a second one
        self.trace: Optional[Trace] = None
        self._submit_t = interval_now()
        # SLO clocks (observability/slo.py): anchored at the ORIGINAL
        # submission and written once — requeue resets _submit_t (the
        # per-engine queued-span clock) but never these, so deadline
        # headroom / TTFT / queue-wait survive takeovers and migrations
        self._created_t = self._submit_t
        self._admitted_t: Optional[float] = None
        self._first_token_t: Optional[float] = None
        self._done_t: Optional[float] = None
        # (interval-clock time, tokens) each time tokens became visible
        # in ``generated``: one entry a prefill or block retire, on or
        # off tracing; like the SLO clocks it rides the request through
        # requeue
        self._emissions: List[Tuple[float, int]] = []
        # id of the dispatch whose readback made the last emission (the
        # engines' lane gap ledger: was an admission dispatched between)
        self._emit_block = 0
        self._slo = None                   # SLOTracker, set at submit
        self._slo_done = False             # an observe_request happened
        self._slo_labels: Dict = {}
        # durability (ISSUE 10): the id this request journals under —
        # stable across requeues, takeovers, and migrations (a fleet
        # clone inherits it; the zombie's is detached). None = not
        # journaled. _journal_hooked latches the terminal-state journal
        # callback so engine hops never double-attach it.
        self.journal_id: Optional[str] = None
        self._journal_hooked = False

    def clocks(self) -> Dict[str, Optional[float]]:
        """The request's clocks on ``interval_now()``: ``created`` (the
        original submission), ``admitted`` (its prefill's dispatch),
        ``first_token`` (that prefill's readback) and ``done`` (terminal
        state reached); None where not reached yet. Written once each —
        a takeover or migration never resets them."""
        return {"created": self._created_t, "admitted": self._admitted_t,
                "first_token": self._first_token_t, "done": self._done_t}

    def emissions(self) -> List[Tuple[float, int]]:
        """``(t, n_tokens)`` for each time tokens became visible in
        ``generated`` — one entry a prefill or retired block, ``t`` the
        readback's stamp on ``interval_now()``; the ``n`` sum to
        ``len(generated)``."""
        return list(self._emissions)

    def _complete(self):
        self._result = np.concatenate(
            [self.prompt, np.asarray(self.generated, np.int32)])
        self._running = False
        # a finished request does not pin its engine: a caller that keeps
        # the handle would keep the caches and the weights with it
        self._engine = None
        self._done_t = interval_now()
        if self.trace is not None:
            self.trace.finish("ok", tokens=len(self.generated))
        self._done.set()
        self._notify_slo("ok")
        self._fire_callbacks()

    def _fail(self, exc: BaseException):
        self._error = exc
        self._running = False
        self._engine = None      # as _complete; requeue() sets it again
        self._done_t = interval_now()
        if self.trace is not None:
            self.trace.finish(f"failed:{type(exc).__name__}",
                              tokens=len(self.generated))
        self._done.set()
        self._notify_slo(self._slo_status(exc))
        self._fire_callbacks()

    @staticmethod
    def _slo_status(exc: BaseException) -> str:
        """Map a terminal exception to its SLO outcome class (the fleet
        completion gate reuses this for sync-failed inner handles)."""
        if isinstance(exc, DeadlineExceeded):
            return "deadline"
        if isinstance(exc, Cancelled):
            return "cancelled"
        if isinstance(exc, RejectedError):
            return "shed"
        return "failed"

    def _notify_slo(self, status: str) -> None:
        # exactly once per request (racing completion paths included):
        # the tracker handle is consumed by the first notifier, UNDER
        # _cb_lock — the fleet clone path clears a zombie's handle from
        # the router thread, and without the lock the zombie's engine
        # thread could load a still-armed reference concurrently and
        # double-count the request its clone now owns.
        with self._cb_lock:
            slo, self._slo = self._slo, None
        if slo is None:
            return
        self._slo_done = True
        try:
            slo.observe_request(self, status)
        except Exception:   # noqa: BLE001 — accounting must not strand
            pass            # the engine thread that completed us

    def _fire_callbacks(self):
        # drain-under-lock then fire outside it: a callback that submits
        # or requeues (the fleet migration path) must never run inside
        # _cb_lock, and each registered callback fires exactly once even
        # when racing add_done_callback
        with self._cb_lock:
            cbs, self._callbacks = self._callbacks, []
        for fn in cbs:
            try:
                fn(self)
            except Exception:   # noqa: BLE001 — a bad hook can't strand
                pass            # the engine thread that completed us

    def add_done_callback(self, fn) -> None:
        """Register ``fn(request)`` to fire when the request reaches a
        terminal state (DONE / FAILED / CANCELLED). Fires from whichever
        thread completes the request — or immediately, in the calling
        thread, if the request is already done. Exactly once per
        registered callback."""
        with self._cb_lock:
            if not self._done.is_set():
                self._callbacks.append(fn)
                return
        try:
            fn(self)
        except Exception:   # noqa: BLE001 — same contract as the
            pass            # completion-path fire: a bad hook is swallowed

    def _expired(self, now: Optional[float] = None) -> bool:
        return self._deadline_t is not None and \
            (now if now is not None else interval_now()) > self._deadline_t

    def done(self) -> bool:
        return self._done.is_set()

    @property
    def state(self) -> str:
        if self._done.is_set():
            if self._error is None:
                return self.DONE
            if isinstance(self._error, Cancelled):
                return self.CANCELLED
            return self.FAILED
        return self.RUNNING if self._running else self.PENDING

    def cancel(self) -> bool:
        """Request cancellation; returns False if already finished.
        The engine honors it at its next sweep: a queued request fails
        before ever taking a slot, a decoding one frees its slot."""
        if self._done.is_set():
            return False
        self._cancel_requested = True
        eng = self._engine
        if eng is not None:
            eng._work.set()               # wake an idle serve loop promptly
        return True

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        if not self._done.wait(timeout):
            raise TimeoutError("generation not finished")
        if self._error is not None:
            raise self._error
        return self._result

    def __repr__(self) -> str:
        dl = "" if self.deadline is None else f" deadline={self.deadline}s"
        err = "" if self._error is None \
            else f" error={type(self._error).__name__}"
        return (f"<GenerationRequest {self.state} prompt_len="
                f"{len(self.prompt)} generated={len(self.generated)}/"
                f"{self.max_new_tokens}{dl}{err}>")


class SlotGenerationEngine:
    """Slot-based continuous batching over a TransformerDecoder.

    ``num_slots`` cache slots share one [S, H, t_max, Dh] cache per
    attention layer. The loop decodes all occupied slots each step; a
    slot that finishes (eos / max_new_tokens / full context) completes
    its request mid-loop and — with ``refill=True`` — is immediately
    re-prefilled from the queue, so a mixed-length stream keeps the
    device batch full. ``refill=False`` is the static-batching baseline:
    a wave is admitted, decoded until EVERY slot drains, then the next
    wave starts (the A/B in BENCH_MODE=generate).

    ``block_size=K>1`` pipelines the decode hot loop (ISSUE 4): each
    dispatch runs K steps on device (``decode_block{K}_impl``), the
    next block launches from the on-device carry BEFORE the previous
    block's [S, K] token matrix is read back (double buffering — host
    bookkeeping overlaps device compute, ONE readback per block), and
    slot frees/refills land at block boundaries. Admission is batched
    either way: every admittable pending request coalesces into one
    bucketed ``prefill_slots_impl`` call with a single readback.
    Greedy outputs are token-for-token identical across block sizes;
    a lane's overshoot past its stop is truncated on host.

    Resilience surface (ISSUE 3): ``max_pending`` bounds the queue —
    submissions beyond it are SHED with :class:`RejectedError` carrying
    the observed depth, instead of growing without limit. Per-request
    ``deadline`` and ``cancel()`` are enforced mid-decode by freeing the
    slot (the refill seam immediately reuses it). A supervisor
    (parallel/failures.py EngineSupervisor) may attach: the engine then
    beats a heartbeat each loop iteration, reports crashes through
    ``_on_crash`` instead of failing in-flight requests, and
    ``quarantine()``/``requeue()`` implement exactly-once takeover —
    recovered requests resume by re-prefilling prompt + tokens emitted
    so far. ``fault_injector`` arms the ``engine.step`` /
    ``engine.prefill`` injection points (parallel/faults.py).

    Scheduling tier (ISSUE 11) — all off by default, legacy behaviour
    bit-preserved: ``scheduling="edf"`` pops the earliest absolute
    deadline first (FIFO tie-break, no-deadline last);
    ``shed_headroom=True`` rejects a request at admission when the
    measured prefill/per-step EWMAs project it cannot make its
    deadline (``RejectedError.projected_miss_s``, exactly one SLO miss
    per shed); ``prefill_chunk=C`` fills long prompts' caches in
    C-token windows interleaved with decode blocks (one window per
    serve-loop cycle — a 10k-token prompt cannot stall every stream);
    ``adaptive_block=True`` chooses K live per wave from queue depth,
    capped by the measured block latency, over ``block_ladder`` rungs
    that are all warmed at construction (a burst's first escalation to
    a bigger K must never stall the loop on a compile).

    Synchronous use: ``submit(...)`` then ``run_until_drained()``.
    Serving use: ``start()`` spins a worker thread that blocks on the
    queue (ParallelInference.generate / GenerationServingRoute)."""

    def __init__(self, net, num_slots: int = 8,
                 t_max: Optional[int] = None, refill: bool = True,
                 seed: int = 0, decoder: Optional[TransformerDecoder] = None,
                 max_pending: int = 256, fault_injector=None,
                 block_size: int = 1, registry=None, trace_store=None,
                 tracing: bool = True, mesh=None, spec_layout=None,
                 slo=None, slo_label=None, flight_recorder=None,
                 journal=None, scheduling: str = "fifo",
                 shed_headroom: bool = False,
                 headroom_margin: float = 1.0,
                 prefill_chunk: Optional[int] = None,
                 adaptive_block: bool = False,
                 block_ladder: Optional[Sequence[int]] = None,
                 block_latency_target: float = 0.25,
                 paged: bool = False, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 prefix_cache: bool = True,
                 profiler=None, profiling: Optional[bool] = None,
                 phase: str = "both", handoff=None,
                 integrity=None, speculative: bool = False,
                 spec_k: Optional[int] = None, spec_ngram: int = 3,
                 spec_threshold: float = 0.35,
                 spec_probe_every: int = 16):
        if decoder is not None and t_max is not None and \
                decoder.t_max != t_max:
            raise ValueError(f"shared decoder has t_max {decoder.t_max}, "
                             f"engine asked for {t_max}")
        if decoder is not None and mesh is not None and \
                decoder.mesh is not mesh:
            raise ValueError("shared decoder was built for a different "
                             "mesh; pass mesh= only when the engine owns "
                             "its decoder")
        # ---- silent-data-corruption defense (ISSUE 15) ----
        # integrity=None keeps every legacy path bit-identical. With a
        # config: the decoder's impls fold the numerics sentinel into
        # their carries (the engine then must unpack the verdict
        # column), and paged engines content-verify prefix-cache pages.
        from ..observability.integrity import (PageVerifier, as_integrity)
        self._integrity = as_integrity(integrity)
        want_sentinel = self._integrity is not None and \
            self._integrity.sentinel
        if decoder is not None and decoder.sentinel != want_sentinel:
            raise ValueError(
                f"shared decoder sentinel={decoder.sentinel} but the "
                f"engine's integrity config wants {want_sentinel}: the "
                "sentinel changes the impls' output shapes, so decoder "
                "and engine must agree (build the shared decoder with "
                "sentinel=, or drop integrity=)")
        # a shared decoder reuses its jitted prefill/decode programs
        # across engines (the A/B benches build several engines per run,
        # and a supervisor restart MUST reuse it: zero new compiles in
        # the post-restart steady state is the acceptance bar); a
        # sharded decoder carries its mesh/spec layout with it, so a
        # restart rebuilds the SAME sharded decode path for free
        self.decoder = decoder if decoder is not None \
            else TransformerDecoder(
                net, t_max=t_max, mesh=mesh, spec_layout=spec_layout,
                sentinel=want_sentinel,
                logit_bound=None if self._integrity is None
                else self._integrity.logit_bound)
        self._sentinel_on = want_sentinel
        if self.decoder.state_names:
            # a state-space layer's cache is one fixed-size state a slot:
            # it cannot be rewound to a position, shared between slots or
            # filled a window at a time (ROADMAP R-M7)
            missing = [what for what, asked in (
                ("the paged KV pool (paged=True)", paged),
                ("a prefix cache (paged=True, prefix_cache=True)",
                 paged and prefix_cache),
                ("a speculative drafter (speculative=True)", speculative),
                ("chunked prefill (prefill_chunk)",
                 prefill_chunk is not None)) if asked]
            if missing:
                raise ValueError(
                    f"state-space layers {self.decoder.state_names} keep a "
                    "fixed-size state a slot, which nothing can rewind, "
                    "share or fill in windows yet: "
                    + "; ".join(missing) + " (ROADMAP R-M7)")
        # chain-digest-keyed content checksums (recorded at prefix
        # registration, verified on hits/adopts at the sampled rate)
        self._kv_verifier = None
        if self._integrity is not None and self._integrity.kv_verify \
                and self._integrity.verify_every and paged:
            self._kv_verifier = PageVerifier()
        self._kv_hit_ctr = 0
        self._adopt_ctr = 0
        self.mesh = self.decoder.mesh
        if self.mesh is not None:
            from ..parallel.mesh import validate_decode_mesh
            layout = self.decoder._layout
            validate_decode_mesh(self.mesh, num_slots=int(num_slots),
                                 data_axis=layout.data_axis,
                                 tp_axis=layout.tp_axis)
        self.num_slots = int(num_slots)
        self.refill = bool(refill)
        self.seed = int(seed)
        self.max_pending = int(max_pending)
        self.t_max = self.decoder.t_max
        # ---- scheduling policy tier (ISSUE 11) ----
        # queue order: "fifo" (legacy) or "edf" — earliest absolute
        # deadline pops first, FIFO tie-break on equal deadlines,
        # no-deadline requests order FIFO after every deadlined one
        if scheduling not in ("fifo", "edf"):
            raise ValueError(f"scheduling must be 'fifo' or 'edf', "
                             f"got {scheduling!r}")
        self.scheduling = scheduling
        # shed-by-headroom: a request whose projected service time
        # (measured prefill + per-step EWMAs) exceeds its remaining
        # deadline headroom is REJECTED at admission with the projected
        # miss, instead of decoded into a guaranteed DeadlineExceeded
        self.shed_headroom = bool(shed_headroom)
        self.headroom_margin = float(headroom_margin)
        # chunked prefill: prompts longer than this prefill in bounded
        # windows interleaved with decode blocks (None = whole-prompt
        # batched admission, the legacy path)
        if prefill_chunk is not None:
            prefill_chunk = int(prefill_chunk)
            if not 1 <= prefill_chunk <= self.t_max:
                raise ValueError(f"prefill_chunk {prefill_chunk} must be "
                                 f"in [1, t_max={self.t_max}]")
        self.prefill_chunk = prefill_chunk
        # adaptive decode block size: K chosen live per wave from queue
        # depth and the measured per-step latency, over a ladder of
        # already-compiled decode_block{K}_impl rungs
        self.adaptive_block = bool(adaptive_block)
        ladder = tuple(sorted({int(k) for k in
                               (block_ladder or (1, 2, 4, 8))}))
        if any(k < 1 for k in ladder):
            raise ValueError(f"block_ladder rungs must be >= 1: {ladder}")
        self.block_ladder = ladder if self.adaptive_block \
            else (max(1, int(block_size)),)
        self.block_size = max(self.block_ladder) if self.adaptive_block \
            else max(1, int(block_size))
        self.block_latency_target = float(block_latency_target)
        # ---- speculative decoding (ISSUE 16) ----
        # draft/verify over the fused-block machinery: a host-side
        # prompt-lookup drafter (models/speculative.py — zero new
        # params) proposes spec_k candidates per lane, ONE cache-aware
        # verify forward scores the whole K+1 window, and rejection
        # rewinds the write-head (position clamp on the slab;
        # page-table truncate + refcount release when paged). Greedy
        # output is token-for-token identical to spec-off. When the
        # rolling acceptance EWMA drops below spec_threshold the loop
        # falls back to the already-compiled decode_block rungs
        # (switching compiles NOTHING) and probes speculation again
        # every spec_probe_every fallback blocks.
        self.speculative = bool(speculative)
        self.spec_k = max(1, int(spec_k)) if spec_k is not None \
            else max(self.block_size, 4)
        self.spec_ngram = max(1, int(spec_ngram))
        self.spec_threshold = float(spec_threshold)
        self.spec_probe_every = max(1, int(spec_probe_every))
        self._spec_ewma: Optional[float] = None   # rolling acceptance
        self._spec_cool = 0       # fallback blocks until the next probe
        self._drafters: Dict[int, "NGramDrafter"] = {}
        # latency account the policies read: EWMA seconds per decode
        # step and per prefill dispatch, written under the engine lock
        self._est_step: Optional[float] = None
        self._est_prefill: Optional[float] = None
        self._faults = fault_injector if fault_injector is not None \
            else NULL_INJECTOR
        # ---- paged KV cache + prefix caching (ISSUE 12) ----
        # paged=True replaces the [S, H, t_max, Dh] slab with per-layer
        # page POOLS [P, H, page_size, Dh] + per-slot page tables: a
        # slot holds only the pages its live context needs (lazy
        # allocation as it grows), so max concurrency is bounded by
        # ACTUAL footprint, not worst-case length — and identical
        # prompt prefixes map already-resident pages read-only instead
        # of re-prefilling (content-hashed prefix cache, page-granular
        # copy-on-write: shared pages are always full and never
        # rewritten; the first divergent token starts a private page).
        self.page_size = int(page_size)
        self.prefix_cache = bool(prefix_cache)
        self._pager = None
        self._pages_per_slot = 0
        if paged:
            from .paging import PageAllocator
            if self.t_max % self.page_size:
                raise ValueError(
                    f"page_size {self.page_size} must divide t_max "
                    f"{self.t_max}: page-aligned logical views keep the "
                    "paged attention shapes (and therefore its logits) "
                    "identical to the slab path")
            self._pages_per_slot = self.t_max // self.page_size
            if num_pages is None:
                # slab-equivalent capacity (+1 for the reserved null
                # page): the default can never admit LESS than the slab
                # did — pool sizing below that is the operator's
                # concurrency-vs-memory lever
                num_pages = self.num_slots * self._pages_per_slot + 1
            self._pager = PageAllocator(int(num_pages), self.page_size,
                                        prefix_cache=self.prefix_cache)
        self.num_pages = None if self._pager is None \
            else self._pager.num_pages
        # ---- phase specialization (disaggregated serving tier) ----
        # "prefill": this engine fills KV pages and hands every
        # non-finished request to the ``handoff`` sink (the disagg
        # router) instead of decoding it; "decode": fresh prompts are
        # rejected (the router never sends any) and requests arrive
        # through adopt() with their KV state imported. Pages are the
        # transfer unit, so both roles require the paged cache.
        # Recovery re-prefill (supervisor requeue) stays allowed on
        # decode engines — role purity is a ROUTING contract, not a
        # capability cut.
        if phase not in ("both", "prefill", "decode"):
            raise ValueError(f"phase must be 'both', 'prefill' or "
                             f"'decode', got {phase!r}")
        if phase != "both" and self._pager is None:
            raise ValueError("phase-specialized engines need paged=True: "
                             "KV pages are the handoff transfer unit")
        self.phase = phase
        self._handoff = handoff
        # handoff-received (request, PageFrameSet) pairs awaiting a free
        # slot + page import, admitted by the serve loop ahead of the
        # prefill queue (they are mid-stream — their tokens are already
        # flowing to a caller)
        self._adopted: collections.deque = collections.deque()
        if self._pager is not None:
            self._caches = self.decoder.init_paged_pool(
                self._pager.num_pages, self.page_size)
        else:
            self._caches = self.decoder.init_cache(self.num_slots)
        # per-slot page state (paged mode): the logical page list (the
        # single source of truth for this slot's mapping refs) and the
        # host page-table matrix shipped with every paged dispatch
        self._slot_pages: List[List[int]] = \
            [[] for _ in range(self.num_slots)]
        self._ptables = np.zeros(
            (self.num_slots, max(1, self._pages_per_slot)), np.int32)
        self._slots: List[Optional[GenerationRequest]] = \
            [None] * self.num_slots
        self._last_ids = np.zeros(self.num_slots, np.int32)
        self._positions = np.zeros(self.num_slots, np.int32)
        self._temps = np.zeros(self.num_slots, np.float32)
        self._eos_ids = np.full(self.num_slots, -1, np.int32)
        # block-decode pipeline state (block_size > 1): the device-side
        # carry of the LAST dispatched block (ids/positions/stop flags —
        # lets the next block launch without any host readback) and the
        # dispatched-but-unread block whose [S, K] token matrix is
        # fetched one cycle later (double buffering)
        self._carry = None
        self._inflight = None
        # id of the newest admission (prefill batch or window) dispatched:
        # a decode dispatch notes it, so its retire can tell which lanes'
        # gaps an admission lengthened
        self._last_admit_bid = 0
        # chunked-prefill state: slot → [request, full context array,
        # tokens filled so far]. A chunking slot is OCCUPIED (the free
        # list skips it) but not decoding yet — its lanes launch frozen
        # until the final chunk lands the first token. Round-robin
        # pointer interleaves multiple long prompts fairly.
        self._chunking: Dict[int, List] = {}
        self._chunk_rr = 0
        self._pending: collections.deque = collections.deque()
        # requests popped from the queue but not yet landed in a slot:
        # parked here so a concurrent quarantine()/shutdown() drain can
        # always harvest them (batched admission parks the whole batch)
        self._admitting: List[GenerationRequest] = []
        self._lock = threading.Lock()
        self._work = threading.Event()
        self._key = jax.random.PRNGKey(seed)
        self._step_no = 0
        self._worker: Optional[threading.Thread] = None
        self._shutdown = False
        self._dead: Optional[BaseException] = None   # worker crash cause
        # durable request journal (ISSUE 10): lifecycle records append
        # OUTSIDE the engine lock, on the readback thread, batched per
        # decode block — GL010-clean by construction, and journal I/O
        # failures degrade durability without ever failing serving
        self._journal = journal
        # preemption drain (parallel/preemption.py): _draining sheds new
        # submissions, _drain_stop parks the serve loop at the next
        # block boundary so the in-flight block can be retired before
        # the quarantine harvest
        self._draining = False
        self._drain_stop = False
        # supervision hooks (EngineSupervisor._attach)
        self._supervised = False
        self._quarantined = False
        self._first_step_done = False   # gates wedge detection: a first
        # decode/prefill LOWERING can exceed any sane heartbeat timeout
        self._on_crash = None       # callable(engine, exc)
        self._beat = None           # callable() — heartbeat per iteration
        # serving stats (ISSUE 5): registry-backed counters, one labeled
        # child per engine instance. stats() and the legacy attribute
        # reads (properties below the class) are thin views over these.
        self._registry = registry if registry is not None \
            else default_registry()
        self._trace_store = trace_store if trace_store is not None \
            else default_trace_ring()
        self._tracing = bool(tracing)
        self.engine_id = f"e{next(_ENGINE_SEQ)}"
        # SLO + flight-recorder sinks (ISSUE 9): the tracker accounts
        # deadline headroom / TTFT / queue-wait per request at its
        # exactly-once completion; slo_label keeps one STABLE replica
        # label across supervisor-rebuilt engines (the supervisor passes
        # the old label through), so attainment never fragments across
        # takeovers. The flight recorder gets lifecycle events
        # (admission waves, block retires, sheds) for post-mortems.
        self._slo = slo if slo is not None else default_slo_tracker()
        self.slo_label = str(slo_label) if slo_label is not None \
            else self.engine_id
        self._flightrec = flight_recorder if flight_recorder is not None \
            else default_flight_recorder()
        # hot-loop phase profiler (ISSUE 13): per-block phase/bubble
        # decomposition + measured steady durations per impl, recorded
        # from the serve thread only — ``profiling`` defaults to the
        # tracing flag (the telemetry-off baseline records nothing),
        # and the channel is keyed by the STABLE slo_label, so a
        # supervisor-rebuilt engine continues the same phase account
        # and the timeline ring survives the takeover
        self._profiling = self._tracing if profiling is None \
            else bool(profiling)
        self._profiler = profiler if profiler is not None \
            else default_profiler()
        self._prof = self._profiler.channel(
            self.slo_label, num_slots=self.num_slots) \
            if self._profiling else None
        self._prof_impl_names: Dict = {}
        # the loop's seams (observability.tracing.Seam) always take
        # their two stamps — EWMAs and SLO clocks need them — and mirror
        # them onto a profiler session's trace unless telemetry is off
        self._mirror = self._tracing or self._profiling
        reg = self._registry
        self._m = {key: reg.counter(f"generation_{key}_total", desc,
                                    ("engine",)).labels(self.engine_id)
                   for key, desc in _ENGINE_COUNTERS.items()}
        # host wall time per decode block (dispatch→retire) — the p50/p99
        # the telemetry endpoint serves; recorded only while tracing is
        # on (the telemetry-off A/B baseline skips it)
        self._h_block = reg.histogram(
            "generation_decode_block_seconds",
            "host wall time per decode block, dispatch to retire",
            ("engine",)).labels(self.engine_id)
        # adaptive-K visibility (ISSUE 11): blocks dispatched per chosen
        # rung — the policy's live distribution on /metrics
        self._m_k = reg.counter(
            "generation_adaptive_k_total",
            "decode blocks dispatched, by adaptively chosen K",
            ("engine", "k"))
        # speculative-decoding visibility (ISSUE 16): the acceptance-
        # length distribution (one count per retired verify block per
        # lane, labeled by how many of its K drafts were accepted) and
        # the host-side drafting cost — the scrape view's spec-acc
        # column and the A/B bench read these
        self._m_spec_len = reg.counter(
            "generation_spec_accepted_total",
            "speculative verify lanes retired, by accepted draft "
            "length (0..K)",
            ("engine", "len"))
        self._h_spec_draft = reg.histogram(
            "generation_spec_draft_seconds",
            "host wall time drafting candidates per speculative block",
            ("engine",)).labels(self.engine_id)
        # prefix-cache visibility (ISSUE 12): hit/miss per admitted
        # request plus the prompt tokens whose prefill compute the
        # shared pages saved — the SAME content hash keys the fleet's
        # sticky_prefix routing (models/paging.prefix_route_key), so
        # these counters measure exactly what that routing optimizes
        self._m_prefix_hit = reg.counter(
            "prefix_cache_hit_total",
            "requests admitted with >= 1 shared prefix page mapped",
            ("engine",)).labels(self.engine_id)
        self._m_prefix_miss = reg.counter(
            "prefix_cache_miss_total",
            "requests admitted with no resident prefix page",
            ("engine",)).labels(self.engine_id)
        self._m_prefix_tokens = reg.counter(
            "prefix_cache_hit_tokens_total",
            "prompt tokens served from shared prefix pages "
            "(prefill compute skipped)",
            ("engine",)).labels(self.engine_id)
        # SDC defense outcomes (ISSUE 15): sentinel trips and detected
        # page corruptions, one labeled child per engine — the fleet's
        # burn-rate quarantine and the scrape columns read these
        from ..observability.integrity import (KV_CORRUPTION_COUNTER,
                                               NUMERICAL_FAULT_COUNTER)
        self._m_numfault = reg.counter(
            *NUMERICAL_FAULT_COUNTER).labels(self.engine_id)
        self._m_kv_corrupt = reg.counter(
            *KV_CORRUPTION_COUNTER).labels(self.engine_id)
        # depth gauges evaluate lazily at collection time through a WEAK
        # reference: the process-default registry must never keep a dead
        # engine (and its device caches) alive
        wself = weakref.ref(self)
        reg.gauge("generation_queue_depth", "pending requests queued "
                  "(incl. adopted handoffs awaiting a slot)",
                  ("engine",)).labels(self.engine_id).set_function(
            lambda: (lambda s: 0 if s is None else
                     len(s._pending) + len(s._adopted))(wself()))
        if self.phase != "both":
            # phase-specialized role marker (disagg tier): the scrape
            # view derives each replica's P/D column from this family
            reg.gauge("generation_engine_role",
                      "phase-specialized engine role (1 = this engine "
                      "serves the labeled role)",
                      ("engine", "role")).labels(
                self.engine_id, self.phase).set(1)
        reg.gauge("generation_active_slots",
                  "cache slots decoding, chunk-prefilling or being "
                  "admitted",
                  ("engine",)).labels(self.engine_id).set_function(
            lambda: (lambda s: 0 if s is None else
                     sum(r is not None for r in s._slots) +
                     len(s._chunking) + len(s._admitting))(wself()))
        if self._pager is None and self.decoder.slab_names:
            # what the decode steps' attention read of the slab it holds,
            # over the engine's life (the SLAB_COUNTERS' ratio)
            reg.gauge("generation_slab_read_share",
                      "slab positions the decode attention read / "
                      "positions the slab held, over retired blocks",
                      ("engine",)).labels(self.engine_id).set_function(
                lambda: (lambda s: 0.0 if s is None else
                         s._slab_read_share())(wself()))
        if self._pager is not None:
            # page-granular KV accounting (ISSUE 12 satellite): pool
            # state by page, pool bytes, and the internal-fragmentation
            # gauge — all weakref'd collection-time reads like the
            # depth gauges above
            pg = reg.gauge("generation_kv_pages",
                           "KV page pool, pages by state",
                           ("engine", "state"))
            for st in ("free", "used", "cached", "shared"):
                pg.labels(self.engine_id, st).set_function(
                    lambda _st=st: (lambda s: 0 if s is None else
                                    s._pager.stats()[_st])(wself()))
            reg.gauge("generation_kv_pool_bytes",
                      "paged KV pool bytes allocated (global, all "
                      "layers)", ("engine",)).labels(
                self.engine_id).set_function(
                lambda: (lambda s: 0 if s is None else
                         s._pool_bytes())(wself()))
            reg.gauge("generation_kv_page_fragmentation",
                      "allocated-but-unwritten fraction of mapped "
                      "pages (internal fragmentation)",
                      ("engine",)).labels(self.engine_id).set_function(
                lambda: (lambda s: 0.0 if s is None else
                         (s.kv_page_stats() or {}).get(
                             "fragmentation", 0.0))(wself()))
        # adaptive-K rungs warm at CONSTRUCTION: the first escalation
        # to a bigger K under a traffic burst must not block the serve
        # loop on a jit compile — that stall would land exactly when
        # the queue is deepest, blowing the deadlines EDF/headroom
        # protect. All lanes dispatch frozen at the parking cell
        # (t_max-1), so the warmup writes only cells the decode
        # write-head overwrites before they are ever attended; caches
        # are donated per dispatch, so the returned ones thread through.
        if self.adaptive_block or self.speculative:
            w_ids = np.zeros(self.num_slots, np.int32)
            w_pos = np.full(self.num_slots, self.t_max - 1, np.int32)
            w_stop = np.ones(self.num_slots, bool)
            # a speculative engine warms its fallback rungs too: the
            # low-acceptance switch to plain decode blocks must cost
            # zero compiles even on a non-adaptive engine
            for k in self.block_ladder:
                if self._pager is not None:
                    # all-zero page tables: every frozen warmup write
                    # lands in the reserved null page
                    _, _, _, _, self._caches = \
                        self.decoder.paged_decode_block(
                            self._caches, self._ptables, w_ids, w_pos,
                            stopped=w_stop, block_size=k)
                else:
                    _, _, _, _, self._caches = self.decoder.decode_block(
                        self._caches, w_ids, w_pos, stopped=w_stop,
                        block_size=k)
        if self.speculative:
            # the verify impl warms at construction for the same
            # reason: a supervisor restart's post-recovery steady state
            # must add ZERO compiles (the chaos bar), and the first
            # spec block under a burst must not stall the loop. Frozen
            # lanes carry write-validity 0 — the warmup writes nothing.
            w_draft = np.zeros((self.num_slots, self.spec_k), np.int32)
            if self._pager is not None:
                _, _, _, _, self._caches = self.decoder.paged_verify_block(
                    self._caches, self._ptables, w_ids, w_pos, w_draft,
                    stopped=w_stop)
            else:
                _, _, _, _, self._caches = self.decoder.verify_block(
                    self._caches, w_ids, w_pos, w_draft, stopped=w_stop)
        # mesh topology gauges (r12): one child per mesh axis so the
        # telemetry endpoint can chart per-axis sizes; set once — the
        # mesh never changes for an engine's lifetime
        if self.mesh is not None:
            ax_g = reg.gauge("generation_mesh_axis_size",
                             "serving-mesh axis size (data/tp)",
                             ("engine", "axis"))
            for ax in self.mesh.axis_names:
                ax_g.labels(self.engine_id, str(ax)).set(
                    int(self.mesh.shape[ax]))

    # ------------------------------------------------------------- intake
    def submit(self, prompt, max_new_tokens: int, temperature: float = 0.0,
               eos_id: Optional[int] = None,
               deadline: Optional[float] = None,
               route: Optional[str] = None,
               journal_id: Optional[str] = None,
               _slo_sync_fail: bool = True,
               _canary: bool = False) -> GenerationRequest:
        req = GenerationRequest(prompt, max_new_tokens, temperature, eos_id,
                                deadline=deadline)
        req._engine = self
        # durable id (ISSUE 10): callers may pin one (the fleet router
        # reuses its request id so ledger fencing arbitrates recovery);
        # otherwise a journaled engine mints a process-unique id.
        # _canary=True (ISSUE 15, the fleet's golden-canary prober) is
        # a synthetic probe: never journaled (a recovery must not
        # resurrect it) and never SLO-accounted (probe outcomes must
        # not move attainment) — it takes the REAL serving path
        # otherwise, which is the whole point of the probe.
        if journal_id is not None:
            req.journal_id = str(journal_id)
        elif self._journal is not None and not _canary:
            req.journal_id = uuid.uuid4().hex[:16]
        # the engine opens the request's trace; route-side spans
        # (consume/publish) are appended onto it afterwards. The
        # early-failure paths below finish it through req._fail.
        if self._tracing:
            req.trace = Trace(store=self._trace_store)
            req.trace.event("submit", engine=self.engine_id,
                            prompt_len=len(req.prompt))
        # SLO accounting rides every request (completion is once per
        # request, not per token — outside the ≤5% A/B's hot loop).
        # _slo_sync_fail=False is the FLEET seam: the router spills past
        # this engine's synchronous fast-fails (queue-full shed, dead
        # engine) and retries another replica, so those outcomes must
        # not be accounted as misses here — the tracker is armed only
        # once the request is actually accepted (the fleet completion
        # gate accounts any sync failure it ends up propagating).
        req._slo_labels = {"replica": self.slo_label, "route": route}
        if _canary:
            req._slo_done = True      # SLO sink stays unarmed everywhere
        elif _slo_sync_fail:
            req._slo = self._slo
        with self._lock:
            dead = self._dead
            stopped = self._shutdown or dead is not None
        if stopped:
            # a dead/stopped engine beats argument validation: the caller
            # must learn the engine is gone even for no-op requests
            req._fail(dead or RuntimeError(
                "SlotGenerationEngine shut down"))
            return req
        if self.phase == "decode":
            # routing-contract safety net: the disagg router dispatches
            # fresh prompts to PREFILL workers only; a prompt landing
            # here is a router bug, not a degradation to absorb.
            # (requeue()/adopt() remain open — recovery re-prefill and
            # the handoff receive are this role's legitimate intakes.)
            req._fail(RuntimeError(
                "decode-only engine: fresh prompts belong on a prefill "
                "worker (handoff receives arrive via adopt())"))
            return req
        if len(req.prompt) < 1:
            req._fail(ValueError("empty prompt"))
            return req
        if req.max_new_tokens <= 0:          # nothing to generate — match
            req._complete()                  # TransformerDecoder.generate
            return req
        if len(req.prompt) >= self.t_max:
            req._fail(ValueError(
                f"prompt length {len(req.prompt)} leaves no room to "
                f"generate within t_max {self.t_max}"))
            return req
        # RE-check under the same critical section as the append: a dying
        # worker sets _dead under this lock BEFORE draining the queue
        # (shutdown() likewise flags before draining), so either we see
        # the flag here and fail fast, or our append lands before the
        # drain and the drain fails it — a request can never be queued
        # after the last drain and strand its caller in result(None).
        # Admission control shares the section: the observed depth and
        # the append/shed decision are atomic.
        shed_depth = None
        draining = False
        headroom_shed = False
        # headroom policy (ISSUE 11): projected service time vs the
        # request's remaining deadline headroom, from the measured
        # per-step / prefill EWMAs — a request that cannot make its
        # deadline is shed NOW with the projected miss, not decoded
        # into a guaranteed DeadlineExceeded. Cold estimates admit.
        headroom_exc = None
        if self.shed_headroom and req._deadline_t is not None:
            headroom_exc = self._headroom_check(req)
        with self._lock:
            dead = self._dead
            queued = not (self._shutdown or dead is not None)
            if queued and self._draining:
                # preemption drain (ISSUE 10): admission is CLOSED — new
                # work is shed (the caller retries another replica);
                # inherited/queued work keeps decoding until harvest
                self._m["rejected"].inc()
                draining = True
                queued = False
            if queued and headroom_exc is not None:
                self._m["rejected"].inc()
                self._m["headroom_shed"].inc()
                headroom_shed = True
                queued = False
            if queued:
                depth = len(self._pending)
                if depth >= self.max_pending:
                    self._m["rejected"].inc()
                    shed_depth = depth
                    queued = False
                else:
                    # past every synchronous fast-fail: arm the SLO sink
                    # BEFORE the append (the worker may complete the
                    # request the instant it is visible in the queue);
                    # canary probes stay unarmed (synthetic traffic)
                    if not _canary:
                        req._slo = self._slo
                    self._pending.append(req)
        if headroom_shed:
            self._flightrec.record("shed", engine=self.engine_id,
                                   reason="headroom",
                                   projected_miss_s=round(
                                       headroom_exc.projected_miss_s, 4))
            req._fail(headroom_exc)
            return req
        if draining:
            self._flightrec.record("shed", engine=self.engine_id,
                                   reason="draining")
            req._fail(RejectedError(
                "engine draining for preemption — request shed"))
            return req
        if shed_depth is not None:
            self._flightrec.record("shed", engine=self.engine_id,
                                   queue_depth=shed_depth)
            req._fail(RejectedError(
                f"pending queue full ({shed_depth} queued, "
                f"max_pending={self.max_pending}) — request shed",
                queue_depth=shed_depth))
            return req
        if not queued:
            req._fail(dead or RuntimeError(
                "SlotGenerationEngine shut down"))
            return req
        jr = self._journal
        if jr is not None and req.journal_id is not None:
            # write-ahead: the sub record lands before the caller can
            # observe acceptance; a SIGKILL from here on recovers it
            jr.submitted(req, route=route)
            self._hook_journal(req)
        self._work.set()
        return req

    def requeue(self, req: GenerationRequest) -> None:
        """Re-queue a recovered request (supervisor restart path): it
        resumes by re-prefilling prompt + tokens emitted so far, then
        decoding on — exactly-once, token-for-token with an
        uninterrupted run under greedy selection. Recovery bypasses
        admission control: a restart must not shed work it inherited."""
        if req.trace is not None:
            # same trace, new engine: the takeover span is the ONLY seam
            # a restarted request shows in its timeline
            req.trace.event("takeover", engine=self.engine_id,
                            generated=len(req.generated))
        # SLO continuity: re-point the sink at THIS engine's tracker and
        # replica label, but never touch the created/admitted/first-token
        # clocks — the takeover must not reset any SLO clock. A clone
        # whose zombie already accounted the request (_slo_done inherited
        # in the fleet's _clone_inner) is NOT re-armed: one record per
        # request even across the migrate-vs-complete race.
        if not req._slo_done:
            req._slo = self._slo
        req._slo_labels = dict(req._slo_labels or {},
                               replica=self.slo_label)
        req._submit_t = interval_now()
        with self._lock:
            dead = self._dead
            alive = not (self._shutdown or dead is not None)
            if alive:
                req._running = False
                req._engine = self
                self._pending.append(req)
                self._m["requeued"].inc()
        if not alive:
            req._fail(dead or RuntimeError(
                "SlotGenerationEngine shut down"))
            return
        jr = self._journal
        if jr is not None and req.journal_id is not None:
            # takeover/migration/recovery marker: replay-inert (the sub
            # + ret records already carry the durable state), but the
            # forensic timeline shows where each resume happened
            jr.requeued(req)
            self._hook_journal(req)
        self._work.set()

    def _hook_journal(self, req: GenerationRequest) -> None:
        """Attach the terminal-state journal callback exactly once per
        request — the latch rides the request, so supervisor takeovers
        and fleet migrations through other journaled engines never
        double-attach. Fires outside every engine lock (the
        done-callback contract); a zombie whose ``journal_id`` was
        detached by migration journals nothing."""
        with req._cb_lock:
            hooked, req._journal_hooked = req._journal_hooked, True
        if hooked:
            return
        jr = self._journal

        def _fin(r, _jr=jr):
            rid = r.journal_id
            if rid is None:
                return
            err = r._error
            if err is None:
                _jr.finished(rid, "done")
            elif isinstance(err, Cancelled):
                _jr.finished(rid, "cancelled")
            else:
                _jr.finished(rid, "failed",
                             error=f"{type(err).__name__}: {err}")
        req.add_done_callback(_fin)

    # --------------------------------------------------------- scheduling
    def _headroom_check(self, req: GenerationRequest,
                        remaining: Optional[int] = None
                        ) -> Optional[RejectedError]:
        """Projected-miss shed decision: RejectedError iff the measured
        account (prefill + per-step EWMAs) projects the request cannot
        finish inside its deadline; None while the estimates are cold (a
        fresh engine admits everything rather than shed on no data) or
        while headroom suffices. ``remaining`` overrides the token
        budget (a recovered request re-checks with what is left)."""
        with self._lock:
            est, pre = self._est_step, self._est_prefill
        if est is None or req._deadline_t is None:
            return None
        tokens = req.max_new_tokens if remaining is None else remaining
        # a chunked long prompt pays ONE prefill dispatch per window,
        # not one total — charge every window, or a 10k-token prompt
        # would pass the check and still die mid-chunking
        ctx = len(req.prompt) + len(req.generated)
        dispatches = 1
        if self.prefill_chunk is not None and ctx > self.prefill_chunk:
            dispatches = -(-ctx // self.prefill_chunk)      # ceil
        need = ((pre or 0.0) * dispatches +
                max(0, tokens) * est) * self.headroom_margin
        headroom = req._deadline_t - interval_now()
        if need <= headroom:
            return None
        return RejectedError(
            f"projected deadline miss: needs ~{need:.3f}s (margin "
            f"{self.headroom_margin:g}) against {headroom:.3f}s headroom "
            f"— shed at admission", projected_miss_s=need - headroom)

    def _ewma_locked(self, attr: str, value: float) -> None:
        """Fold one observation into a latency EWMA (caller holds the
        engine lock) — the measured account the headroom shed and the
        adaptive-K policy read."""
        old = getattr(self, attr)
        setattr(self, attr, value if old is None
                else 0.8 * old + 0.2 * value)

    def _choose_block_size(self) -> int:
        """Adaptive K, chosen live per wave (ISSUE 11): deep queue →
        the largest compiled rung (throughput: dispatch overhead
        amortizes over K steps), idle queue → K=1 (latency: tokens
        retire every step). The measured per-step EWMA then caps K so
        one block's wall time stays under ``block_latency_target`` —
        a deep queue of slow steps must not turn into multi-second
        blocks that blow every deadline the EDF order protects. Every
        rung reuses an already-compiled ``decode_block{K}_impl``, so
        steady-state switching compiles nothing."""
        with self._lock:
            depth = len(self._pending)
            est = self._est_step
        ladder = self.block_ladder
        k = ladder[0]
        for rung in ladder:
            if rung <= max(1, depth):
                k = rung
        if est is not None and est > 0:
            while k > ladder[0] and k * est > self.block_latency_target:
                k = max(r for r in ladder if r < k)
        return k

    def _edf_key(self, req: GenerationRequest):
        # earliest absolute deadline first; no deadline sorts after
        # every deadlined request; FIFO (creation order) breaks ties —
        # equal-headroom requests can never starve each other
        return (req._deadline_t if req._deadline_t is not None
                else float("inf"), req._seq)

    # -------------------------------------------------------------- slots
    def _pop_for_admit(self) -> Optional[GenerationRequest]:
        """Pop the next queued request AND park it in ``_admitting`` in
        one critical section: from this moment until it lands in a slot
        (or is failed), a concurrent quarantine()/shutdown() drain can
        always see it — a request is never invisible to takeover.
        ``scheduling="edf"`` pops the earliest deadline instead of the
        queue head (FIFO tie-break via the request's creation seq) —
        a linear scan per pop, O(depth²) per drain: fine at the default
        max_pending=256; revisit with a lazy-deletion heap if queues
        grow to many thousands."""
        with self._lock:
            req = None
            if self._pending:
                if self.scheduling == "edf":
                    best = min(range(len(self._pending)),
                               key=lambda i: self._edf_key(
                                   self._pending[i]))
                    req = self._pending[best]
                    del self._pending[best]
                else:
                    req = self._pending.popleft()
            if req is not None:
                self._admitting.append(req)
            return req

    def _unpark(self, req: GenerationRequest) -> bool:
        """Remove ``req`` from the admission park under the caller's
        held lock; False means a takeover drain already harvested it
        (the drain owns the request now — touch nothing)."""
        if self._quarantined or self._shutdown or \
                req not in self._admitting:
            return False
        self._admitting.remove(req)
        return True

    # -------------------------------------------------------------- pages
    def _map_slot_pages(self, s: int, pages: List[int]) -> None:
        """Install ``pages`` as slot ``s``'s logical mapping (caller
        holds the engine lock; the pages already carry this mapping's
        refs — matched shared pages via match_and_ref, fresh ones via
        alloc)."""
        self._slot_pages[s] = list(pages)
        self._ptables[s, :] = 0
        self._ptables[s, :len(pages)] = pages

    def _release_slot_pages(self, s: int) -> None:
        """Unmap slot ``s`` (caller holds the engine lock): one unref
        per mapped page, and the page-table row redirected to the null
        page so a stale frozen lane's per-block rewrite lands in trash
        instead of pages the allocator may hand to the next request.
        Pages the prefix index retains stay resident (refcount falls to
        the index's 1) — that retention IS the prefix cache."""
        if self._pager is None:
            return
        pages, self._slot_pages[s] = self._slot_pages[s], []
        self._ptables[s, :] = 0
        for pid in pages:
            self._pager.unref(pid)

    def _release_all_pages(self) -> None:
        """Caller holds the engine lock — the quarantine/shutdown/crash
        drains release every mapping so the harvest leaves refcounts
        balanced (audit-clean: only prefix-index retention remains)."""
        if self._pager is None:
            return
        for s in range(self.num_slots):
            self._release_slot_pages(s)

    # ------------------------------------------------- disagg handoff
    def _export_pages(self, pids: List[int],
                      tag: str = "kv_handoff") -> Dict:
        """Gather ``pids``'s page contents to host numpy (pow2-bucketed
        ``kv_export_impl`` dispatch; pad rows gather the trash page and
        are sliced off). 2·layers readbacks, all under the given
        transfer tag (``kv_handoff`` for disagg exports,
        ``integrity.verify`` for content checksums) — neither is a
        decode block, so the ≤1-readback-per-block audit is untouched."""
        nb = _round_up_pow2(len(pids), floor=1)
        pad = np.zeros(nb, np.int32)
        pad[:len(pids)] = pids
        tree = self.decoder.kv_export(self._caches, pad)
        return {n: {kk: device_fetch(kv[kk], tag=tag)[:len(pids)]
                    for kk in ("k", "v")}
                for n, kv in tree.items()}

    # --------------------------------------------- KV content integrity
    def _page_sums(self, pids: List[int]) -> List[bytes]:
        """Content checksums for ``pids`` (ISSUE 15): one bucketed
        export + one blake2b per page, hashing every layer's k then v
        bytes in sorted-layer order — the SAME recipe PageFrameSet
        stamps on handoff frames, so the two views of a page agree."""
        from ..observability.integrity import page_content_checksum
        frames = self._export_pages(pids, tag="integrity.verify")
        names = sorted(frames)
        return [page_content_checksum(
                    [frames[n][kk][j] for n in names for kk in ("k", "v")])
                for j in range(len(pids))]

    def _record_page_sums(self, entries: List[Tuple[np.ndarray,
                                                    int]]) -> None:
        """Record content references for freshly registered prefix
        chains. ``entries`` are (ctx, full page count) rows from this
        wave; the references hash the pages the INDEX retains (the
        allocator's resident page per digest), deduped by (digest,
        pid) so each unique content is exported and hashed exactly
        once for its cached lifetime. Serve-loop thread, no engine
        lock held — cached pages are never rewritten, so the read is
        race-free by the prefix cache's own immutability contract."""
        from .paging import chain_digests
        need: List[Tuple[bytes, int]] = []
        seen = set()
        for ctx, n_full in entries:
            digests = chain_digests(ctx[:n_full * self.page_size],
                                    self.page_size)
            for dg in digests:
                if dg in seen:
                    continue
                seen.add(dg)
                pid = self._pager.cached_page(dg)
                if pid is None or \
                        self._kv_verifier.expected(dg, pid) is not None:
                    continue
                need.append((dg, int(pid)))
        if not need:
            return
        sums = self._page_sums([pid for _, pid in need])
        for (dg, pid), cs in zip(need, sums):
            self._kv_verifier.record(dg, pid, cs)

    def _verify_matched(self, ctx: np.ndarray,
                        shared: List[int]) -> Optional[int]:
        """Sampled content verification of a prefix-cache hit: export
        the matched pages, hash, and compare against the recorded
        references. Returns the first corrupt page INDEX (into
        ``shared``) or None. On corruption: the whole chain from the
        corrupt page is evicted (no new stream can map it), this
        match's refs are returned, streams still mapping a corrupt
        page are preempted to re-prefill (requeue-at-head — the
        existing exactly-once machinery), and the caller degrades the
        match to a miss."""
        from .paging import chain_digests
        digests = chain_digests(
            ctx[:len(shared) * self.page_size], self.page_size)
        sums = self._page_sums(shared)
        bad = None
        for j, (dg, pid) in enumerate(zip(digests, shared)):
            verdict = self._kv_verifier.check(dg, int(pid), sums[j])
            if verdict is False:
                bad = j
                break
        if bad is None:
            return None
        # release THIS match's refs (taken by match_and_ref) and evict
        # the chain from the corrupt page on — then scrub whatever is
        # now free (pages a healthy holder still maps keep their bytes
        # until that holder releases; nothing NEW can map them)
        for pid in shared:
            self._pager.unref(pid)
        evicted = self._pager.evict_digests(digests[bad:])
        self._kv_verifier.forget(digests[bad:])
        self._scrub_pages(shared[bad:])
        self._m_kv_corrupt.inc()
        self._flightrec.record(
            "kv_corruption", engine=self.engine_id, page=int(shared[bad]),
            chain_evicted=evicted, detector="prefix_hit")
        self._preempt_corrupt_holders(set(shared[bad:]))
        return bad

    def _preempt_corrupt_holders(self, pids: set) -> None:
        """Requeue every stream currently mapping a corrupt page: its
        tokens so far ride the request, re-admission re-prefills them
        through fresh pages (the poisoned chain is already evicted, so
        the re-prefill cannot re-map it) — the same exactly-once
        requeue-at-head path pool-pressure preemption uses."""
        victims: List[GenerationRequest] = []
        scrub: List[int] = []
        with self._lock:
            for s in range(self.num_slots):
                if not pids.intersection(self._slot_pages[s]):
                    continue
                req = None
                if self._slots[s] is not None:
                    req = self._slots[s]
                    self._slots[s] = None
                elif s in self._chunking:
                    req = self._chunking.pop(s)[0]
                # the victim's PRIVATE tail pages were computed
                # attending the corrupt chain — scrub them too
                scrub.extend(self._slot_pages[s])
                self._release_slot_pages(s)
                if req is not None and not req.done():
                    req._running = False
                    self._pending.appendleft(req)
                    self._m["page_preempted"].inc()
                    victims.append(req)
                self._carry = None   # graftlint: disable=GL006 — under
                #                      self._lock (the _locked contract)
        self._scrub_pages(scrub)
        for req in victims:
            if req.trace is not None:
                req.trace.event("kv_corruption_preempt",
                                engine=self.engine_id,
                                generated=len(req.generated))
            self._flightrec.record("page_preempt", engine=self.engine_id,
                                   reason="kv_corruption",
                                   generated=len(req.generated))
            if self._journal is not None and req.journal_id is not None:
                self._journal.requeued(req)

    def _scrub_pages(self, pids: List[int]) -> None:
        """Zero pages on device (corruption response — see
        ``scrub_pages_impl``). Serve-loop thread; pow2-bucketed like
        every page-indexed dispatch, pad rows target the null page.
        Safe on already-freed pages: allocation happens only on this
        thread, so nothing can map them mid-scrub."""
        if self._pager is None or not pids:
            return
        # only truly-free pages are zeroed: a suspect page a HEALTHY
        # stream still maps keeps its bytes until that holder releases
        # (its index entry is already evicted, so no new mapper exists)
        pids = self._pager.free_subset(pids)
        if not pids:
            return
        nb = _round_up_pow2(len(pids), floor=1)
        pad = np.zeros(nb, np.int32)
        pad[:len(pids)] = pids
        self._caches = self.decoder._fn("scrub_pages")(  # graftlint: disable=GL006
            self._caches, jnp.asarray(pad))

    def _scrub_slots(self, slots: List[int]) -> None:
        """Slab twin of :meth:`_scrub_pages`: zero faulted slots' cache
        rows before the refill seam can hand them to a successor (a
        chunk-admitted tenant writes only its windows, so non-finite
        residue past its fill point would otherwise poison it)."""
        if self._pager is not None or not slots:
            return
        nb = _round_up_pow2(len(slots), floor=1)
        pad = np.full(nb, slots[0], np.int32)   # idempotent re-zeroing
        pad[:len(slots)] = slots
        self._caches = self.decoder._fn("scrub_slot")(  # graftlint: disable=GL006
            self._caches, jnp.asarray(pad))

    # ------------------------------------------- scripted corruption
    def _corrupt_registered_page(self, ctx: np.ndarray,
                                 mode: str) -> None:
        """CHAOS ONLY (device.corrupt_page@registered): poison the
        FIRST cached page of ``ctx``'s prefix chain on device — the
        at-rest silent-corruption injection the sampled verification
        and the golden canary must catch. Serve-loop thread; the pools
        thread through like any dispatch."""
        from .paging import chain_digests
        digests = chain_digests(ctx[:self.page_size], self.page_size)
        pid = None if not digests \
            else self._pager.cached_page(digests[0])
        if pid is None:
            return
        # serve-loop-owned pools, same single-thread contract as every
        # dispatch site
        self._caches = self.decoder.corrupt_page(  # graftlint: disable=GL006
            self._caches, int(pid), mode)
        self._flightrec.record(
            "corruption_injected", engine=self.engine_id,
            point="device.corrupt_page", where="registered",
            page=int(pid), mode=mode)

    def _inject_corrupt_logits(self, mode: str, s: int) -> None:
        """CHAOS ONLY (device.corrupt_logits): poison lane ``s``'s
        always-attended KV state right before a block dispatch — the
        block's logits go non-finite (nan) or silently wrong (flip),
        which is exactly what the sentinel / burn-rate quarantine must
        detect end-to-end."""
        detail = {}
        if self._pager is not None:
            with self._lock:
                pages = list(self._slot_pages[s])
            if not pages:
                return
            self._caches = self.decoder.corrupt_page(  # graftlint: disable=GL006
                self._caches, int(pages[0]), mode)
            detail["page"] = int(pages[0])
        else:
            self._caches = self.decoder.corrupt_cache(  # graftlint: disable=GL006
                self._caches, int(s), 0, mode)
            detail["slot"] = int(s)
        self._flightrec.record(
            "corruption_injected", engine=self.engine_id,
            point="device.corrupt_logits", mode=mode, **detail)

    def _import_pages(self, pids: List[int], frames: Dict) -> None:
        """Scatter host page frames into this pool at ``pids``
        (pow2-bucketed ``kv_import_impl``; pad rows write the trash
        page). Serve-loop thread only — the pools are donated per
        dispatch like every other impl."""
        nb = _round_up_pow2(len(pids), floor=1)
        pad = np.zeros(nb, np.int32)
        pad[:len(pids)] = pids
        dev = {}
        for n, kv in frames.items():
            dev[n] = {}
            for kk in ("k", "v"):
                arr = np.asarray(kv[kk])
                if len(pids) != nb:
                    buf = np.zeros((nb,) + arr.shape[1:], arr.dtype)
                    buf[:len(pids)] = arr
                    arr = buf
                dev[n][kk] = jnp.asarray(arr)
        # _caches is serve-loop-thread-owned (every dispatch site
        # threads the donated pools the same way); the analyzer can't
        # see the single-thread ownership contract
        self._caches = self.decoder.kv_import(  # graftlint: disable=GL006
            self._caches, pad, dev)

    def _handoff_one(self, req: GenerationRequest, s: int,
                     ctx: np.ndarray) -> None:
        """Export slot ``s``'s KV pages and pass the request to the
        disagg handoff sink (prefill-only engines; serve-loop thread).
        The request holds its first token already; the frames cover the
        context cells ``[0, len(ctx))`` the receiver's decode attends.
        Quarantine/shutdown racing the export: the drain owns the
        request (and released the pages) — ship nothing."""
        from .paging import PageFrameSet
        ps = self.page_size
        n_xfer = (len(ctx) - 1) // ps + 1
        with self._lock:
            if self._quarantined or self._shutdown:
                return
            pages = list(self._slot_pages[s][:n_xfer])
        t0 = interval_now()
        frames = self._export_pages(pages)
        t1 = interval_now()
        # content checksums are stamped only when the integrity config
        # arms verification: the integrity-off handoff path must stay
        # bit-and-cost-identical to r19 (CRC-only)
        state = PageFrameSet(
            ps, ctx, frames,
            checksums=None if self._kv_verifier is not None else False)
        # scripted MID-HANDOFF corruption (device.corrupt_page, site
        # "handoff"): flip the host frames AFTER their content
        # checksums were stamped — every CRC downstream still passes,
        # only content verification (wire decode / adopt intake) can
        # catch it
        plan = self._faults.corruption("device.corrupt_page",
                                       where="handoff")
        if plan is not None:
            from ..observability.integrity import corrupt_host_frames
            corrupt_host_frames(state, plan["mode"])
            self._flightrec.record(
                "corruption_injected", engine=self.engine_id,
                point="device.corrupt_page", where="handoff",
                mode=plan["mode"])
        cancelled = req._cancel_requested
        with self._lock:
            if self._quarantined or self._shutdown:
                return          # drain released the mapping already
            self._release_slot_pages(s)
            if cancelled:
                self._m["cancelled"].inc()
            else:
                self._m["handoffs"].inc()
        if cancelled:
            req._fail(Cancelled("cancelled at prefill handoff"))
            return
        if req.trace is not None:
            req.trace.add_span("kv_export", t0, t1, pages=len(pages),
                               bytes=state.nbytes)
        if self._tracing:
            self._flightrec.record(
                "kv_handoff", engine=self.engine_id, stage="export",
                pages=len(pages), bytes=state.nbytes,
                ms=round((t1 - t0) * 1e3, 3))
        sink = self._handoff
        if sink is None:
            # a prefill-only engine without a tier wired must not
            # strand its caller in result(None) forever
            req._fail(RuntimeError(
                "prefill-only engine has no handoff sink"))
            return
        try:
            sink(req, state)
        except Exception as exc:   # noqa: BLE001 — a broken sink must
            req._fail(exc)         # not kill the serve loop

    def adopt(self, req: GenerationRequest, kv) -> None:
        """Adopt a prefilled request WITH its exported KV state — the
        decode-side intake of the disaggregated handoff. ``kv``
        duck-types :class:`models.paging.PageFrameSet` (``page_size``,
        ``tokens``, ``layers``). Geometry is validated synchronously
        (:class:`ValueError` — the router's fall-back-to-re-prefill
        seam); the import itself runs on the serve loop: pages allocate
        from THIS pool (resident same-content chains are reused
        read-only — the decode-side shared-prefix tier), frames scatter
        in, and decode resumes token-identically at position
        ``len(kv.tokens)``. Like ``requeue``, adoption bypasses
        admission control: inherited mid-stream work is never shed by a
        queue bound (pool pressure still applies)."""
        if self._pager is None:
            raise ValueError("adopt() needs a paged engine (pages are "
                             "the handoff transfer unit)")
        if int(kv.page_size) != self.page_size:
            raise ValueError(
                f"page_size mismatch: frames carry {kv.page_size}, this "
                f"pool uses {self.page_size} — disaggregated roles must "
                "share one page geometry")
        for n, pool in self._caches.items():
            lf = kv.layers.get(n)
            if lf is None:
                raise ValueError(f"page frames missing attention vertex "
                                 f"{n!r}")
            for kk in ("k", "v"):
                arr = lf[kk]
                want = tuple(int(x) for x in pool[kk].shape[1:])
                if tuple(int(x) for x in np.shape(arr)[1:]) != want:
                    raise ValueError(
                        f"frame shape {tuple(np.shape(arr))} does not "
                        f"match pool page geometry {want} at {n!r}")
                if np.dtype(arr.dtype) != np.dtype(pool[kk].dtype):
                    raise ValueError(
                        f"frame dtype {arr.dtype} != pool dtype "
                        f"{pool[kk].dtype} at {n!r}")
        expect = len(req.prompt) + len(req.generated) - 1
        if len(kv.tokens) != expect:
            raise ValueError(
                f"frame set covers {len(kv.tokens)} context tokens; the "
                f"request resumes at {expect}")
        # sampled CONTENT verification at intake (ISSUE 15): re-hash
        # the frames against the checksums stamped at export — a flip
        # anywhere in the export→ship→intake window fails HERE, before
        # a single corrupt byte is scattered into this pool (the
        # router's except path re-prefills on a prefill worker, fenced
        # exactly-once)
        if self._kv_verifier is not None and hasattr(kv, "verify") and \
                not getattr(kv, "_verified", False):
            # _verified: a serialized transport's wire decode already
            # swept these exact frames — re-hashing here would double
            # the cost for zero coverage (the in-process handle-passing
            # path is what this sampled check exists for)
            with self._lock:
                self._adopt_ctr += 1
                due = self._adopt_ctr % self._integrity.verify_every == 0
            if due:
                bad = kv.verify()
                if bad:
                    from .paging import PageCorruptionError
                    self._m_kv_corrupt.inc()
                    self._flightrec.record(
                        "kv_corruption", engine=self.engine_id,
                        detector="adopt", pages=len(bad))
                    raise PageCorruptionError(
                        f"adopt intake: page content checksum mismatch "
                        f"on page(s) {bad} — corrupt frames refused")
        if req.trace is not None:
            req.trace.event("adopt", engine=self.engine_id,
                            ctx=len(kv.tokens))
        # SLO continuity: same contract as requeue — re-point the sink
        # and replica label, never touch the created/admitted/first-
        # token clocks (the handoff must not reset any SLO clock)
        if not req._slo_done:
            req._slo = self._slo
        req._slo_labels = dict(req._slo_labels or {},
                               replica=self.slo_label)
        req._submit_t = interval_now()
        with self._lock:
            dead = self._dead
            alive = not (self._shutdown or dead is not None)
            if alive:
                req._running = False
                req._engine = self
                self._adopted.append((req, kv))
                self._m["adopted"].inc()
        if not alive:
            req._fail(dead or RuntimeError(
                "SlotGenerationEngine shut down"))
            return
        jr = self._journal
        if jr is not None and req.journal_id is not None:
            # hop marker, like a takeover: replay-inert, forensically
            # visible — the WAL shows where the stream changed workers
            jr.requeued(req)
            self._hook_journal(req)
        self._work.set()

    def _admit_adopted(self):
        """Admit adopted handoffs (serve-loop thread): map + import
        each request's KV pages into this pool and install decode state
        directly — NO prefill dispatch; the shipped pages ARE the
        prefill. Resident same-content chains are reused read-only
        (match_and_ref) and only the remaining frames scatter in."""
        ps = self.page_size
        while True:
            entry = None
            with self._lock:
                if self._adopted and not (self._quarantined or
                                          self._shutdown):
                    free = [s for s in range(self.num_slots)
                            if self._slots[s] is None and
                            s not in self._chunking and
                            not self._slot_pages[s]]
                    if free:
                        req, kv = self._adopted.popleft()
                        self._admitting.append(req)
                        entry = (free[0], req, kv)
            if entry is None:
                return
            s, req, kv = entry
            exc = None
            if req._cancel_requested:
                exc = Cancelled("cancelled before adoption")
            elif req._expired():
                exc = DeadlineExceeded(
                    f"deadline of {req.deadline}s passed in handoff")
            if exc is not None:
                with self._lock:
                    if not self._unpark(req):
                        return
                    self._m["cancelled" if isinstance(exc, Cancelled)
                            else "deadline_exceeded"].inc()
                req._fail(exc)
                continue
            tokens = np.asarray(kv.tokens, np.int32).reshape(-1)
            n_ctx = len(tokens)
            total = n_ctx // ps + 1     # incl. the next write cell
            shared, start = self._pager.match_and_ref(tokens,
                                                      max_tokens=n_ctx)
            fresh = self._pager.alloc(total - len(shared))
            if fresh is None:
                for pid in shared:
                    self._pager.unref(pid)
                # pool-exhausted receiver: with work in flight, wait at
                # the head (completions free pages); with nothing in
                # flight this pool can NEVER hold the import — shed,
                # and the router's completion gate sees the rejection
                requeued = False
                with self._lock:
                    if not self._unpark(req):
                        return
                    if any(r is not None for r in self._slots) or \
                            self._chunking:
                        self._adopted.appendleft((req, kv))
                        requeued = True
                    else:
                        self._m["rejected"].inc()
                if requeued:
                    return
                self._flightrec.record(
                    "shed", engine=self.engine_id, reason="kv_pool_adopt",
                    pages_needed=total - len(shared))
                req._fail(RejectedError(
                    f"KV page pool exhausted on handoff receive: "
                    f"{total - len(shared)} pages needed, none free "
                    "after eviction and nothing in flight to free one"))
                continue
            pages = shared + fresh
            n_xfer = min((n_ctx - 1) // ps + 1, int(kv.n_pages))
            import_idx = list(range(len(shared), n_xfer))
            t0 = interval_now()
            if import_idx:
                frames = {n: {kk: np.asarray(lf[kk])[import_idx]
                              for kk in ("k", "v")}
                          for n, lf in kv.layers.items()}
                self._import_pages([pages[j] for j in import_idx],
                                   frames)
            t1 = interval_now()
            finish = None
            with self._lock:
                if self._quarantined or self._shutdown or \
                        not self._unpark(req):
                    # the drain owns the request; our unmapped refs go
                    # back now so its harvest audits balanced
                    for pid in pages:
                        self._pager.unref(pid)
                    return
                self._map_slot_pages(s, pages)
                # the imported context's full pages become shareable:
                # a second stream with the same prefix adopted here
                # maps them instead of importing its own copies
                self._pager.register_chain(tokens,
                                           pages[:n_ctx // ps])
                if req._admitted_t is None:
                    req._admitted_t = t0
                tok = int(req.generated[-1])
                if len(req.prompt) + len(req.generated) >= self.t_max \
                        or len(req.generated) >= req.max_new_tokens:
                    # defensive: senders complete finishers themselves
                    self._m["completed"].inc()
                    finish = req
                    self._release_slot_pages(s)
                else:
                    self._slots[s] = req
                    req._running = True
                    self._last_ids[s] = tok
                    self._positions[s] = n_ctx
                    self._temps[s] = req.temperature
                    self._eos_ids[s] = -1 if req.eos_id is None \
                        else int(req.eos_id)
                    self._carry = None    # pipeline resync: new lane
            if req.trace is not None:
                req.trace.add_span("queued", req._submit_t, t0)
                req.trace.add_span("kv_import", t0, t1,
                                   pages=len(import_idx),
                                   shared_pages=len(shared),
                                   shared_tokens=start)
            if self._tracing:
                self._flightrec.record(
                    "kv_handoff", engine=self.engine_id, stage="import",
                    pages=len(import_idx), shared=len(shared),
                    ms=round((t1 - t0) * 1e3, 3))
            if self._kv_verifier is not None:
                # adopted chains are shareable on THIS pool now: record
                # their content references like any registration
                self._record_page_sums([(tokens, n_ctx // ps)])
            if finish is not None:
                finish._complete()

    def _ensure_decode_pages_locked(self, k: int
                                    ) -> List[GenerationRequest]:
        """Grow each active lane's page table to cover this block's
        furthest write (position + k - 1, clamped to the context edge);
        caller holds the engine lock. A lane the pool cannot serve —
        even after evicting cache-only prefix pages — is PREEMPTED:
        unmapped, re-queued at the head, and returned for the caller's
        out-of-lock bookkeeping (exactly-once holds: generated tokens
        ride the request and re-admission re-prefills them). Highest
        slots are visited first, so their released pages immediately
        serve the surviving lower lanes."""
        ps = self.page_size
        preempted: List[GenerationRequest] = []
        # pipeline lead: with a block in flight, the device carry (and
        # therefore the NEXT dispatch's write positions) runs one block
        # ahead of the host positions — cover it, or a boundary-
        # crossing write would silently redirect to the null page
        lead = self._inflight[2] if self._inflight is not None else 0
        for s in reversed(range(self.num_slots)):
            req = self._slots[s]
            if req is None:
                continue
            upto = min(int(self._positions[s]) + lead + k - 1,
                       self.t_max - 1)
            delta = upto // ps + 1 - len(self._slot_pages[s])
            if delta <= 0:
                continue
            fresh = self._pager.alloc(delta)
            if fresh is not None:
                base = len(self._slot_pages[s])
                self._slot_pages[s].extend(fresh)
                self._ptables[s, base:base + len(fresh)] = fresh
                continue
            self._slots[s] = None
            self._release_slot_pages(s)
            req._running = False
            self._pending.appendleft(req)
            self._m["page_preempted"].inc()
            # freed lane: resync the pipeline. Caller holds the engine
            # lock (the _locked contract), the analyzer just can't see
            # across the call boundary.
            self._carry = None   # graftlint: disable=GL006
            preempted.append(req)
        return preempted

    def _pool_bytes(self) -> int:
        if self._pager is None:
            return 0
        total = 0
        for layer in self._caches.values():
            for leaf in layer.values():
                total += int(leaf.size) * int(leaf.dtype.itemsize)
        return total

    def kv_page_stats(self) -> Optional[Dict]:
        """Page-granular KV accounting (devstats `/snapshot` +
        telemetry_dump --scrape): allocator pool state, mapped pages,
        pool bytes, and internal fragmentation (the fraction of mapped
        page cells no live context has written — the page-size waste
        knob). None on a slab engine."""
        if self._pager is None:
            return None
        st = self._pager.stats()
        with self._lock:
            mapped = sum(len(p) for p in self._slot_pages)
            written = 0
            for s in range(self.num_slots):
                if not self._slot_pages[s]:
                    continue
                if s in self._chunking:
                    written += int(self._chunking[s][2])
                elif self._slots[s] is not None:
                    written += int(self._positions[s])
        st["mapped"] = mapped
        st["pool_bytes"] = self._pool_bytes()
        span = mapped * self.page_size
        st["fragmentation"] = 0.0 if not span else round(
            max(0.0, 1.0 - written / span), 4)
        return st

    def _seam(self, name: str, block: Optional[int] = None,
              lanes: Optional[int] = None, k: Optional[int] = None) -> Seam:
        """One seam of this engine's loop: ``with self._seam(...) as s``
        takes the two stamps (``s.t0``, ``s.t1``) the sinks then get; the
        profiler channel watches it for the serve thread's stalls."""
        return Seam(name, block, lanes, k, self._mirror, watch=self._prof)

    def _prof_impl(self, kind: str, k: Optional[int] = None) -> str:
        """Audit-keyed impl name for the profiler's per-impl account
        (memoized — one dict hit per record in steady state): the same
        per-K, per-mesh key CompileAudit uses."""
        name = self._prof_impl_names.get((kind, k))
        if name is None:
            if kind == "block":
                key = ("paged_block" if self._pager is not None
                       else "block", int(k))
            elif kind == "verify":
                key = ("paged_verify" if self._pager is not None
                       else "verify", int(k))
            elif kind == "prefill":
                key = "paged_prefill" if self._pager is not None \
                    else "prefill_slots"
            else:
                key = kind
            name = self.decoder._impl_audit_name(key)
            self._prof_impl_names[(kind, k)] = name
        return name

    def _req_finished(self, req: GenerationRequest, tok: int) -> bool:
        return (req.eos_id is not None and tok == req.eos_id) or \
            len(req.generated) >= req.max_new_tokens or \
            len(req.prompt) + len(req.generated) >= self.t_max

    def _fail_faulted(self, faulted: List[GenerationRequest],
                      where: str) -> None:
        """Fail sentinel-tripped requests with a typed NumericalFault —
        outside the engine lock (``_fail`` fires done-callbacks: the
        fleet's completion gate re-dispatches and may quarantine the
        replica). The poisoned tokens were already dropped by the
        caller; the request's ``generated`` holds only clean tokens, so
        a fleet re-dispatch resumes token-identically elsewhere."""
        if not faulted:
            return
        from ..observability.integrity import NumericalFault
        for req in faulted:
            if req.trace is not None:
                req.trace.event("numerical_fault", engine=self.engine_id,
                                where=where,
                                generated=len(req.generated))
            self._flightrec.record(
                "numerical_fault", engine=self.engine_id, where=where,
                generated=len(req.generated))
            req._fail(NumericalFault(
                f"numerics sentinel tripped on engine {self.engine_id} "
                f"({where}): non-finite or out-of-bound logits after "
                f"{len(req.generated)} clean tokens — the poisoned "
                "tokens were dropped, nothing was served"))

    def _sweep_pending(self):
        """Fail queued requests that were cancelled or ran out of
        deadline before ever taking a slot — a caller must not wait on
        a request the engine will never run."""
        now = interval_now()
        doomed: List[Tuple[GenerationRequest, BaseException]] = []
        with self._lock:
            if self._pending:
                keep: collections.deque = collections.deque()
                for req in self._pending:
                    if req._cancel_requested:
                        self._m["cancelled"].inc()
                        doomed.append((req, Cancelled(
                            "cancelled while queued")))
                    elif req._expired(now):
                        self._m["deadline_exceeded"].inc()
                        doomed.append((req, DeadlineExceeded(
                            f"deadline of {req.deadline}s passed while "
                            "queued")))
                    else:
                        keep.append(req)
                self._pending = keep
            if self._adopted:
                keep_a: collections.deque = collections.deque()
                for req, kv in self._adopted:
                    if req._cancel_requested:
                        self._m["cancelled"].inc()
                        doomed.append((req, Cancelled(
                            "cancelled while awaiting adoption")))
                    elif req._expired(now):
                        self._m["deadline_exceeded"].inc()
                        doomed.append((req, DeadlineExceeded(
                            f"deadline of {req.deadline}s passed while "
                            "awaiting adoption")))
                    else:
                        keep_a.append((req, kv))
                self._adopted = keep_a
        for req, exc in doomed:
            req._fail(exc)

    def _enforce_slots(self):
        """Free slots whose requests were cancelled or exceeded their
        deadline MID-DECODE; the refill seam reuses the slot for the
        next queued prompt."""
        now = interval_now()
        doomed: List[Tuple[GenerationRequest, BaseException]] = []
        with self._lock:
            for s in range(self.num_slots):
                req = self._slots[s]
                if req is None:
                    continue
                if req._cancel_requested:
                    self._slots[s] = None
                    self._release_slot_pages(s)
                    self._m["cancelled"].inc()
                    doomed.append((req, Cancelled(
                        f"cancelled mid-decode after "
                        f"{len(req.generated)} tokens")))
                elif req._expired(now):
                    self._slots[s] = None
                    self._release_slot_pages(s)
                    self._m["deadline_exceeded"].inc()
                    doomed.append((req, DeadlineExceeded(
                        f"deadline of {req.deadline}s exceeded after "
                        f"{len(req.generated)} tokens")))
        for req, exc in doomed:
            req._fail(exc)

    def _count_bucket(self, m: int) -> int:
        """Admission-count bucket: pow2 capped at num_slots, so the
        batched-prefill signature set is finite ({1, 2, 4, ...} × the
        pow2 prompt buckets) and steady serving compiles nothing new."""
        b = 1
        while b < m:
            b *= 2
        return min(b, self.num_slots)

    def _next_admittable(self) -> Tuple[Optional[GenerationRequest],
                                        Optional[np.ndarray], bool]:
        """Pop the next queued request through the lifecycle gates
        (cancel / deadline / headroom re-projection / recovered-already-
        finished), parked in ``_admitting`` throughout — shared by the
        slab and paged admission paths. Returns (req, ctx, aborted):
        req None + aborted False means the queue drained; aborted True
        means a takeover drain owns the popped request and the caller
        must stop admitting entirely."""
        while True:
            req = self._pop_for_admit()
            if req is None:
                return None, None, False
            # lifecycle beats admission: never spend prefill compute on
            # a request that is already cancelled / out of deadline /
            # (recovered) already finished — and the headroom policy
            # re-projects with what the queue wait left (a request that
            # can no longer make its deadline sheds here, not after
            # decoding)
            exc = None
            if req._cancel_requested:
                exc = Cancelled("cancelled while queued")
            elif req._expired():
                exc = DeadlineExceeded(
                    f"deadline of {req.deadline}s passed while "
                    "queued")
            elif self.shed_headroom:
                exc = self._headroom_check(
                    req, remaining=req.max_new_tokens -
                    len(req.generated))
            if exc is not None:
                with self._lock:
                    if not self._unpark(req):
                        return None, None, True   # a drain owns it now
                    if isinstance(exc, Cancelled):
                        self._m["cancelled"].inc()
                    elif isinstance(exc, RejectedError):
                        self._m["rejected"].inc()
                        self._m["headroom_shed"].inc()
                    else:
                        self._m["deadline_exceeded"].inc()
                req._fail(exc)
                continue
            ctx = np.concatenate(
                [req.prompt, np.asarray(req.generated, np.int32)])
            if len(ctx) >= self.t_max or \
                    len(req.generated) >= req.max_new_tokens:
                # recovered request already at a stop condition
                with self._lock:
                    if not self._unpark(req):
                        return None, None, True
                    self._m["completed"].inc()
                req._complete()
                continue
            return req, ctx, False

    def _enter_chunking(self, s: int, req: GenerationRequest,
                        ctx: np.ndarray, filled: int) -> bool:
        """Occupy slot ``s`` for windowed prefill: the slot is taken
        but prefill proceeds in bounded windows interleaved with decode
        blocks (_advance_chunks) — one burst of 10k-token prompts
        degrades throughput gracefully instead of stalling every
        stream. ``filled`` is the absolute resume position (0 on the
        slab; the shared-prefix length after a paged prefix-cache
        hit). False = a takeover drain owns the request — stop
        admitting."""
        with self._lock:
            if not self._unpark(req):
                return False
            # [request, full context, tokens filled, sentinel fault
            # accumulator (device [1] int32, None until the first
            # window — non-final windows never read back, so the
            # verdict ORs on device and crosses only with the final
            # window's single readback)]
            self._chunking[s] = [req, ctx, filled, None]
            # park the lane's decode write-head at the LAST cache cell:
            # a frozen lane re-writes its own cell every block, and a
            # stale position would clobber chunk-prefilled cells
            # mid-fill. Cell t_max-1 is attended only at position
            # t_max-1, which the decode write-head overwrites first.
            # (A paged lane's cell maps through its page table, whose
            # unallocated tail entries redirect the write to the null
            # page.)
            self._positions[s] = self.t_max - 1
            self._last_ids[s] = 0
            # and resync the block pipeline: the device carry may still
            # hold this lane frozen at its PREVIOUS occupant's
            # position, whose per-block rewrite would clobber the cells
            # the chunks are about to fill
            self._carry = None
            req._running = True
            self._m["prefills"].inc()
        if req.trace is not None:
            req.trace.add_span("queued", req._submit_t)
        return True

    def _admit(self):
        """Batched admission: coalesce EVERY admittable pending request
        into one bucketed prefill call with a single host readback —
        the per-request prefill + per-token ``int(np.asarray(...))``
        sync of the r6 loop cost (requests × RTT) per refill wave, and
        supervisor recovery (``requeue``) re-prefills through this same
        path. A recovered request re-prefills prompt + generated-so-far,
        so decoding resumes exactly where the dead engine stopped.
        Count and prompt-length are both pow2-bucketed; padded rows
        replicate row 0 (identical scatter → harmless write ordering).
        Paged engines route to :meth:`_admit_paged` — same gates, same
        bucketing, page-table mapping + prefix-cache matching on top.
        Adopted handoffs (decode role) admit FIRST: they are mid-stream
        work whose callers are already consuming tokens."""
        if self._pager is not None:
            self._admit_adopted()
            return self._admit_paged()
        while True:
            with self._lock:
                free = [s for s in range(self.num_slots)
                        if self._slots[s] is None and
                        s not in self._chunking]
            if not free:
                return
            batch: List[Tuple[GenerationRequest, int, np.ndarray]] = []
            drained = False
            for s in free:
                req, ctx, aborted = self._next_admittable()
                if aborted:
                    return
                if req is None:
                    drained = True
                    break
                if self.prefill_chunk is not None and \
                        len(ctx) > self.prefill_chunk:
                    if not self._enter_chunking(s, req, ctx, 0):
                        return
                    continue           # this slot is occupied; next one
                batch.append((req, s, ctx))
            if not batch:
                return
            m = len(batch)
            mb = self._count_bucket(m)
            tp = min(_round_up_pow2(max(len(c) for _, _, c in batch)),
                     self.t_max)
            tokens = np.zeros((mb, tp), np.int32)
            lengths = np.zeros(mb, np.int32)
            slot_idx = np.zeros(mb, np.int32)
            temps = np.zeros(mb, np.float32)
            for i in range(mb):
                req, s, ctx = batch[i if i < m else 0]   # pad = row 0
                tokens[i, :len(ctx)] = ctx
                lengths[i] = len(ctx)
                slot_idx[i] = s
                temps[i] = req.temperature
            with self._lock:
                if self._shutdown or self._quarantined:
                    return   # batch stays parked in _admitting; the
                             # quarantine/shutdown drain owns it now
                self._m["prefills"].inc(m)
                self._m["ssm_state_resets"].inc(
                    m * len(self.decoder.state_names))
                batch_no = self._m["prefill_batches"].inc()
            bid = self._last_admit_bid = tracing.next_block_id()
            # a decode block in flight: the prefill queues behind it on
            # the device, which is then not idle before this dispatch
            overlapped = self._inflight is not None
            with self._seam(tracing.ADMIT, bid, m) as adm:
                self._faults.fire("engine.prefill")
                nxt, _, self._caches = self.decoder._fn("prefill_slots")(
                    self.decoder._device_params(),
                    self.decoder.net._inference_state(), self._caches,
                    jnp.asarray(tokens), jnp.asarray(lengths),
                    jnp.asarray(slot_idx), jnp.asarray(temps),
                    jax.random.fold_in(self._key,
                                       PREFILL_BATCH_SALT | batch_no))
            with self._seam(tracing.PREFILL_READBACK, bid, m) as rb:
                toks = device_fetch(nxt, tag="engine.prefill")  # ONE
            t_pre0, t_pre1 = adm.t0, rb.t1                  # readback
            fault_col = None
            if self._sentinel_on:
                # verdict packed with the sampled ids: [M, 2] → split
                fault_col, toks = toks[:, 1], toks[:, 0]
            finishers: List[GenerationRequest] = []
            faulted: List[GenerationRequest] = []
            scrub_slots: List[int] = []
            jlog: List[Tuple] = []       # journal appends, written
            #                              OUTSIDE the engine lock below
            with self._seam(tracing.RETIRE, bid, m) as ret, self._lock:
                if self._shutdown or self._quarantined:
                    # a drain harvested the batch while we were in the
                    # device call; it owns the requests now — drop our
                    # tokens (re-prefill regenerates them)
                    return
                self._m["host_readbacks"].inc()
                self._ewma_locked("_est_prefill", t_pre1 - t_pre0)
                for i, (req, s, ctx) in enumerate(batch):
                    if req not in self._admitting:
                        continue          # pragma: no cover — defensive
                    self._admitting.remove(req)
                    if fault_col is not None and fault_col[i]:
                        # sentinel tripped during this row's prefill:
                        # the first token is suspect — never appended,
                        # never journaled, the slot stays free (and is
                        # scrubbed below: the scattered row may carry
                        # non-finite residue a chunk-admitted successor
                        # would attend)
                        scrub_slots.append(s)
                        self._m_numfault.inc()
                        faulted.append(req)
                        continue
                    tok = int(toks[i])
                    req._running = True
                    if self._journal is not None and \
                            req.journal_id is not None:
                        jlog.append((req.journal_id, len(req.generated),
                                     (tok,)))
                    req.generated.append(tok)
                    req._emissions.append((t_pre1, 1))
                    req._emit_block = bid
                    # SLO clocks: admitted/first-token stamped ONCE — a
                    # recovered request re-admitting after takeover keeps
                    # its original queue-wait and TTFT
                    if req._admitted_t is None:
                        req._admitted_t = t_pre0
                    if req._first_token_t is None:
                        req._first_token_t = t_pre1
                    self._m["emitted_tokens"].inc()
                    if req.trace is not None:
                        req.trace.add_span("queued", req._submit_t, t_pre0)
                        req.trace.add_span("prefill", t_pre0, t_pre1,
                                           batch=m, bucket=mb, tp=tp,
                                           ctx=len(ctx), block=bid)
                    if self._req_finished(req, tok):
                        self._m["completed"].inc()
                        finishers.append(req)   # done at the first token
                    else:
                        self._slots[s] = req
                        self._last_ids[s] = tok
                        self._positions[s] = len(ctx)  # next write pos
                        self._temps[s] = req.temperature
                        self._eos_ids[s] = -1 if req.eos_id is None \
                            else int(req.eos_id)
                # slot contents changed: the block-decode pipeline must
                # resync its device carry from host state
                self._carry = None
            with self._seam(tracing.JOURNAL, bid) as jn:
                if self._tracing:   # outside the engine lock (flightrec
                    self._flightrec.record(   # owns its own lock)
                        "admission", engine=self.engine_id, batch=m,
                        bucket=mb, tp=tp,
                        wait_ms=round((t_pre1 - t_pre0) * 1e3, 3))
                if jlog:
                    # first tokens journaled BEFORE the finishers
                    # complete, outside the engine lock (GL010) — a done
                    # record never races ahead of the tokens it summarizes
                    self._journal.retired(jlog)
            with self._seam(tracing.PUBLISH, bid) as pub:
                self._scrub_slots(scrub_slots)
                self._fail_faulted(faulted, where="prefill")
                for req in finishers:
                    req._complete()
            if self._prof is not None:
                self._prof.record_admission(
                    impl=self._prof_impl("prefill"), count=m, block=bid,
                    overlapped=overlapped, t_dispatch=t_pre0,
                    t_dispatched=adm.t1, t_fetched=t_pre1, t_host=ret.t1,
                    t_journal=jn.t1, t_publish=pub.t1,
                    seams=(adm, ret, jn, pub))
            if drained:
                return

    def _pool_blocked(self, req: GenerationRequest, n_need: int,
                      batch_live: bool = False) -> None:
        """Pool-exhausted admission decision: with work in flight the
        request waits AT THE QUEUE HEAD (completions free pages; the
        next admission round retries — graceful degradation, not
        failure). With nothing in flight to ever free a page, the pool
        simply cannot hold this request: shed with RejectedError.
        ``batch_live`` marks an admission round whose earlier rows are
        already mapped but not yet slot-assigned — they WILL decode and
        free pages, so they count as in-flight work."""
        with self._lock:
            active = batch_live or \
                any(r is not None for r in self._slots) or \
                bool(self._chunking)
            if not self._unpark(req):
                return                 # a takeover drain owns it now
            if active:
                req._running = False
                self._pending.appendleft(req)
                return
            self._m["rejected"].inc()
        self._flightrec.record("shed", engine=self.engine_id,
                               reason="kv_pool", pages_needed=n_need)
        req._fail(RejectedError(
            f"KV page pool exhausted: {n_need} pages needed, none free "
            "after eviction and nothing in flight to free one — "
            "request shed"))

    def _admit_paged(self):
        """Paged batched admission (ISSUE 12): same lifecycle gates and
        pow2 bucketing as the slab path, except each request first maps
        the longest content-hash-matched shared prefix already resident
        in the pool (read-only, refcount++) and allocates private pages
        only for its tail — then ONE bucketed ``paged_prefill_impl``
        dispatch prefills ONLY the tails, with a single readback for
        the wave. Afterwards every full prompt page is published into
        the prefix index, so the next identical prefix maps instead of
        recomputing. Pool pressure degrades gracefully via
        :meth:`_pool_blocked`."""
        ps = self.page_size
        while True:
            with self._lock:
                free = [s for s in range(self.num_slots)
                        if self._slots[s] is None and
                        s not in self._chunking]
            if not free:
                return
            batch: List[Tuple[GenerationRequest, int, np.ndarray, int]] \
                = []
            drained = blocked = False
            for s in free:
                req, ctx, aborted = self._next_admittable()
                if aborted:
                    return
                if req is None:
                    drained = True
                    break
                # longest resident chain prefix — capped one token
                # short of the context, because the tail must produce
                # the next-token logits (a fully-cached context would
                # leave nothing to prefill FROM)
                shared, start = self._pager.match_and_ref(
                    ctx, max_tokens=len(ctx) - 1)
                if shared and self._kv_verifier is not None:
                    # sampled content verification (ISSUE 15): every
                    # verify_every'th hit re-hashes the matched pages
                    # against their registration-time checksums; a
                    # mismatch evicts the chain and degrades THIS
                    # match to a miss (fresh pages, full prefill)
                    with self._lock:
                        self._kv_hit_ctr += 1
                        due = self._kv_hit_ctr % \
                            self._integrity.verify_every == 0
                    if due and \
                            self._verify_matched(ctx, shared) is not None:
                        shared, start = [], 0
                tail = len(ctx) - start
                chunked = self.prefill_chunk is not None and \
                    tail > self.prefill_chunk
                if chunked:
                    # windowed prefill allocates ITS OWN pages window
                    # by window (_advance_chunks) — reserving the whole
                    # long prompt's pages here would be exactly the
                    # up-front worst-case reservation paging removes
                    fresh = []
                else:
                    # private pages covering [start, len(ctx)] — the
                    # tail plus the cell the first decode token writes;
                    # decode growth past that allocates lazily per block
                    n_need = len(ctx) // ps + 1 - len(shared)
                    fresh = self._pager.alloc(n_need)
                    if fresh is None:
                        for pid in shared:
                            self._pager.unref(pid)
                        self._pool_blocked(req, n_need,
                                           batch_live=bool(batch))
                        blocked = True
                        break
                pages = shared + fresh
                with self._lock:
                    if self._quarantined or self._shutdown:
                        # the request stays parked for the drain's
                        # harvest; the unmapped pages go back now
                        for pid in pages:
                            self._pager.unref(pid)
                        return
                    # map BEFORE dispatch: from here the drain's
                    # _release_all_pages owns the mapping, so a
                    # takeover mid-admission leaves refcounts balanced
                    self._map_slot_pages(s, pages)
                if start:
                    self._m_prefix_hit.inc()
                    self._m_prefix_tokens.inc(start)
                    if req.trace is not None:
                        req.trace.event("prefix_hit", tokens=start,
                                        pages=len(shared))
                else:
                    self._m_prefix_miss.inc()
                if chunked:
                    # long TAIL: windowed prefill resumes at the shared
                    # prefix's end; each window ensures its own pages
                    # (incremental allocation). The slot mapping was
                    # installed above; _enter_chunking's unpark-failure
                    # path leaves it for the drain's release.
                    if not self._enter_chunking(s, req, ctx, start):
                        return
                    continue
                batch.append((req, s, ctx, start))
            if not batch:
                return
            m = len(batch)
            mb = self._count_bucket(m)
            c = min(_round_up_pow2(max(len(ctx) - start
                                       for _, _, ctx, start in batch)),
                    self.t_max)
            tokens = np.zeros((mb, c), np.int32)
            pos0 = np.zeros(mb, np.int32)
            valid = np.zeros(mb, np.int32)
            ptab = np.zeros((mb, self._pages_per_slot), np.int32)
            temps = np.zeros(mb, np.float32)
            with self._lock:
                if self._shutdown or self._quarantined:
                    return   # batch stays parked; the drain owns it
                for i in range(mb):
                    req, s, ctx, start = batch[i if i < m else 0]
                    tail_toks = ctx[start:]          # pad rows = row 0
                    tokens[i, :len(tail_toks)] = tail_toks
                    pos0[i] = start
                    valid[i] = len(tail_toks)
                    ptab[i] = self._ptables[s]
                    temps[i] = req.temperature
                self._m["prefills"].inc(m)
                batch_no = self._m["prefill_batches"].inc()
            bid = self._last_admit_bid = tracing.next_block_id()
            overlapped = self._inflight is not None
            with self._seam(tracing.ADMIT, bid, m) as adm:
                self._faults.fire("engine.prefill")
                nxt, self._caches = self.decoder.paged_prefill(
                    self._caches, tokens, pos0, valid, ptab, temps,
                    key=jax.random.fold_in(self._key,
                                           PREFILL_BATCH_SALT | batch_no))
            with self._seam(tracing.PREFILL_READBACK, bid, m) as rb:
                toks = device_fetch(nxt, tag="engine.prefill")  # ONE
            t_pre0, t_pre1 = adm.t0, rb.t1                  # readback
            fault_col = None
            if self._sentinel_on:
                fault_col, toks = toks[:, 1], toks[:, 0]
            finishers: List[GenerationRequest] = []
            faulted: List[GenerationRequest] = []
            scrub: List[int] = []
            handoffs: List[Tuple[GenerationRequest, int, np.ndarray]] = []
            to_sum: List[Tuple[np.ndarray, int]] = []
            registered_ctx: Optional[np.ndarray] = None
            jlog: List[Tuple] = []
            with self._seam(tracing.RETIRE, bid, m) as ret, self._lock:
                if self._shutdown or self._quarantined:
                    return   # the drain harvested the batch (and
                             # released its page mappings) mid-dispatch
                self._m["host_readbacks"].inc()
                self._ewma_locked("_est_prefill", t_pre1 - t_pre0)
                for i, (req, s, ctx, start) in enumerate(batch):
                    if req not in self._admitting:
                        continue          # pragma: no cover — defensive
                    self._admitting.remove(req)
                    if fault_col is not None and fault_col[i]:
                        # sentinel tripped during this row's prefill:
                        # never registered into the prefix cache, never
                        # journaled, pages scrubbed + released, slot
                        # stays free (matched SHARED pages it attended
                        # are suspect too — evicted like a decode
                        # fault's, their checksum references dropped)
                        scrub.extend(self._slot_pages[s])
                        dgs = self._pager.evict_pages(
                            self._slot_pages[s])
                        if self._kv_verifier is not None:
                            self._kv_verifier.forget(dgs)
                        self._release_slot_pages(s)
                        self._m_numfault.inc()
                        faulted.append(req)
                        continue
                    tok = int(toks[i])
                    req._running = True
                    if self._journal is not None and \
                            req.journal_id is not None:
                        jlog.append((req.journal_id, len(req.generated),
                                     (tok,)))
                    req.generated.append(tok)
                    req._emissions.append((t_pre1, 1))
                    req._emit_block = bid
                    if req._admitted_t is None:
                        req._admitted_t = t_pre0
                    if req._first_token_t is None:
                        req._first_token_t = t_pre1
                    self._m["emitted_tokens"].inc()
                    if req.trace is not None:
                        req.trace.add_span("queued", req._submit_t, t_pre0)
                        req.trace.add_span("prefill", t_pre0, t_pre1,
                                           batch=m, bucket=mb, tp=c,
                                           ctx=len(ctx), prefix=start,
                                           block=bid)
                    # publish the context's FULL pages (never written
                    # again: decode lands past the context end) into
                    # the prefix index — the next identical prefix
                    # maps these instead of recomputing their forward
                    self._pager.register_chain(
                        ctx, self._slot_pages[s][:len(ctx) // ps])
                    if len(ctx) // ps:
                        registered_ctx = ctx
                    if self._kv_verifier is not None:
                        # content references recorded OUTSIDE the lock
                        # below (the export is a device fetch)
                        to_sum.append((ctx, len(ctx) // ps))
                    if self._req_finished(req, tok):
                        self._m["completed"].inc()
                        finishers.append(req)   # done at the first token
                        self._release_slot_pages(s)  # registration
                        #            above keeps its prompt pages cached
                    elif self.phase == "prefill":
                        # phase-specialized worker: this request never
                        # decodes HERE — its pages stay mapped (the slot
                        # stays reserved via _slot_pages) until the
                        # export below ships them to a decode worker
                        handoffs.append((req, s, ctx))
                    else:
                        self._slots[s] = req
                        self._last_ids[s] = tok
                        self._positions[s] = len(ctx)  # next write pos
                        self._temps[s] = req.temperature
                        self._eos_ids[s] = -1 if req.eos_id is None \
                            else int(req.eos_id)
                # slot contents changed: the block-decode pipeline must
                # resync its device carry from host state
                self._carry = None
            with self._seam(tracing.JOURNAL, bid) as jn:
                if self._tracing:
                    self._flightrec.record(
                        "admission", engine=self.engine_id, batch=m,
                        bucket=mb, tp=c, paged=True,
                        wait_ms=round((t_pre1 - t_pre0) * 1e3, 3))
                if jlog:
                    self._journal.retired(jlog)
            with self._seam(tracing.PUBLISH, bid) as pub:
                self._scrub_pages(scrub)
                self._fail_faulted(faulted, where="paged_prefill")
                if to_sum:
                    self._record_page_sums(to_sum)
                # scripted at-rest corruption (device.corrupt_page, site
                # "registered"): poison the first page of the chain this
                # wave just published — the next prefix-cache hit
                # (sampled verification) or the golden canary must catch
                # it before any new stream attends the bytes
                if registered_ctx is not None:
                    plan = self._faults.corruption("device.corrupt_page",
                                                   where="registered")
                    if plan is not None:
                        self._corrupt_registered_page(registered_ctx,
                                                      plan["mode"])
                for req in finishers:
                    req._complete()
            if self._prof is not None:
                self._prof.record_admission(
                    impl=self._prof_impl("prefill"), count=m, block=bid,
                    overlapped=overlapped, t_dispatch=t_pre0,
                    t_dispatched=adm.t1, t_fetched=t_pre1, t_host=ret.t1,
                    t_journal=jn.t1, t_publish=pub.t1,
                    seams=(adm, ret, jn, pub))
            # prefill-role handoffs run AFTER the wave's bookkeeping,
            # still on this serve-loop thread: each export gathers the
            # slot's pages, releases them, and hands the request to the
            # disagg sink before the next admission round can reuse the
            # slot
            for req, s, ctx in handoffs:
                self._handoff_one(req, s, ctx)
            if drained or blocked:
                return

    def _advance_chunks(self):
        """One chunked-prefill dispatch (round-robin over chunking
        slots), interleaved with decode blocks by the serve loop: long
        prompts fill their cache window by window, each window a bounded
        device program, so a burst of 10k-token prompts degrades
        throughput gracefully instead of spiking every stream's p99.
        Non-final windows never read back (no host sync); the final
        window's single readback lands the first token and activates
        the slot for decode."""
        doomed: List[Tuple[GenerationRequest, BaseException]] = []
        entry = None
        with self._lock:
            if self._quarantined or self._shutdown:
                return
            # lifecycle first: a cancelled / expired chunking request
            # frees its slot without spending another window
            for s in sorted(self._chunking):
                req = self._chunking[s][0]
                if req._cancel_requested:
                    self._m["cancelled"].inc()
                    doomed.append((req, Cancelled(
                        "cancelled during chunked prefill")))
                    del self._chunking[s]
                    self._release_slot_pages(s)
                elif req._expired():
                    self._m["deadline_exceeded"].inc()
                    doomed.append((req, DeadlineExceeded(
                        f"deadline of {req.deadline}s passed during "
                        "chunked prefill")))
                    del self._chunking[s]
                    self._release_slot_pages(s)
            if self._chunking:
                slots = sorted(self._chunking)
                s = slots[self._chunk_rr % len(slots)]
                self._chunk_rr += 1
                entry = (s, *self._chunking[s])
        for req, exc in doomed:
            req._fail(exc)
        if entry is None:
            return
        s, req, ctx, filled, fdev = entry
        c = self.prefill_chunk
        # the final window may slide LEFT so it always fits the cache
        # depth (rewriting a cell from the same tokens is idempotent up
        # to float reassociation); earlier windows are aligned at
        # multiples of c by construction
        pos0 = filled if filled + c <= self.t_max else self.t_max - c
        window = ctx[pos0:pos0 + c]
        valid = len(window)
        final = pos0 + valid >= len(ctx)
        tokens = np.zeros((1, c), np.int32)
        tokens[0, :valid] = window
        ptab = None
        if self._pager is not None:
            # incremental allocation (ISSUE 12): each window ensures
            # exactly the pages IT writes (plus the first decode
            # token's cell on the final window) — a long prompt's pool
            # footprint grows with its fill, never reserved up front
            ps = self.page_size
            upto = (len(ctx) if final else pos0 + valid - 1)
            need = min(upto, self.t_max - 1) // ps + 1
            with self._lock:
                if self._quarantined or self._shutdown:
                    return
                cur = self._chunking.get(s)
                if cur is None or cur[0] is not req:
                    return
                delta = need - len(self._slot_pages[s])
                fresh = self._pager.alloc(delta) if delta > 0 else []
                if fresh is not None:
                    base = len(self._slot_pages[s])
                    self._slot_pages[s].extend(fresh)
                    self._ptables[s, base:base + len(fresh)] = fresh
                    ptab = self._ptables[s:s + 1].copy()
                else:
                    # pool pressure mid-chunking: with DECODING work in
                    # flight, skip this window and retry next cycle
                    # (completions free pages). Other chunkers don't
                    # count — they only consume more pages as they
                    # progress — so with none decoding, shedding this
                    # chunker is what frees pages for the rest.
                    if any(r is not None for r in self._slots):
                        return
                    del self._chunking[s]
                    self._release_slot_pages(s)
                    self._m["rejected"].inc()
            if ptab is None:
                req._fail(RejectedError(
                    "KV page pool exhausted mid-chunked-prefill and "
                    "nothing in flight to free a page — request shed"))
                return
        chunk_no = self._m["prefill_chunks"].inc()
        bid = self._last_admit_bid = tracing.next_block_id()
        overlapped = self._inflight is not None
        tok = None
        fault = False
        with self._seam(tracing.PREFILL_CHUNK, bid, 1) as win:
            self._faults.fire("engine.prefill")
            fault_arr = fdev if fdev is not None \
                else jnp.zeros(1, jnp.int32)
            if self._pager is not None:
                nxt, self._caches = self.decoder.paged_prefill(
                    self._caches, tokens, np.asarray([pos0], np.int32),
                    np.asarray([valid], np.int32), ptab,
                    np.asarray([req.temperature], np.float32),
                    key=jax.random.fold_in(self._key,
                                           CHUNK_SALT | chunk_no),
                    fault_in=fault_arr)
            else:
                nxt, self._caches = self.decoder._fn(("chunk", c))(
                    self.decoder._device_params(),
                    self.decoder.net._inference_state(), self._caches,
                    jnp.asarray(tokens), jnp.asarray([pos0], jnp.int32),
                    jnp.asarray([valid], jnp.int32),
                    jnp.asarray([s], jnp.int32),
                    jnp.asarray([req.temperature], jnp.float32),
                    jax.random.fold_in(self._key, CHUNK_SALT | chunk_no),
                    fault_arr)
            if final:
                arr = device_fetch(nxt, tag="engine.prefill")
                if self._sentinel_on:
                    tok, fault = int(arr[0, 0]), bool(arr[0, 1])
                else:
                    tok = int(arr[0])
        t0, t1 = win.t0, win.t1
        if req._admitted_t is None:
            req._admitted_t = t0          # SLO queue-wait ends at the
        #                                   FIRST window's dispatch
        if self._tracing:
            self._flightrec.record(
                "prefill_chunk", engine=self.engine_id, slot=s,
                pos0=pos0, valid=valid, final=final,
                ms=round((t1 - t0) * 1e3, 3))
        if self._prof is not None:
            # non-final windows never sync (t1 is dispatch-return):
            # only the device phase is attributable, but the window
            # still anchors the bubble account — it keeps the device
            # busy between decode blocks either way
            self._prof.record_chunk(t_dispatch=t0, t_done=t1,
                                    final=final, block=bid,
                                    overlapped=overlapped)
        jlog: List[Tuple] = []
        finish = None
        faulted: List[GenerationRequest] = []
        scrub: List[int] = []
        scrub_slots: List[int] = []
        registered = False
        handoff_entry = None
        with self._lock:
            if self._quarantined or self._shutdown:
                return      # the takeover harvest owns the request now
            cur = self._chunking.get(s)
            if cur is None or cur[0] is not req:
                return      # freed (cancel/deadline) while dispatching
            self._ewma_locked("_est_prefill", t1 - t0)
            if not final:
                cur[2] = pos0 + valid
                if self._sentinel_on:
                    # accumulated verdict stays ON DEVICE between
                    # windows (a lazy [1] slice, no readback)
                    cur[3] = nxt[:, 1]
            elif fault:
                # sentinel tripped somewhere in the windows: nothing
                # was emitted or registered — scrub, release, fail typed
                del self._chunking[s]
                self._m["host_readbacks"].inc()
                scrub = list(self._slot_pages[s])
                if self._pager is not None:
                    dgs = self._pager.evict_pages(scrub)
                    if self._kv_verifier is not None:
                        self._kv_verifier.forget(dgs)
                else:
                    scrub_slots.append(s)
                self._release_slot_pages(s)
                self._m_numfault.inc()
                faulted.append(req)
            else:
                del self._chunking[s]
                self._m["host_readbacks"].inc()
                if self._journal is not None and \
                        req.journal_id is not None:
                    jlog.append((req.journal_id, len(req.generated),
                                 (tok,)))
                req.generated.append(tok)
                req._emissions.append((t1, 1))
                req._emit_block = bid
                if req._first_token_t is None:
                    req._first_token_t = t1
                self._m["emitted_tokens"].inc()
                if self._pager is not None:
                    # the fully-filled context's whole pages become
                    # shareable now, exactly like direct admission
                    self._pager.register_chain(
                        ctx, self._slot_pages[s][:len(ctx) //
                                                 self.page_size])
                    registered = True
                if self._req_finished(req, tok):
                    self._m["completed"].inc()
                    finish = req
                    self._release_slot_pages(s)
                elif self.phase == "prefill":
                    # chunked long prompt on a prefill worker: the
                    # final window's token is the handoff point — pages
                    # stay mapped for the export below
                    handoff_entry = (req, s, ctx)
                else:
                    self._slots[s] = req
                    self._last_ids[s] = tok
                    self._positions[s] = len(ctx)
                    self._temps[s] = req.temperature
                    self._eos_ids[s] = -1 if req.eos_id is None \
                        else int(req.eos_id)
                    # slot contents changed: the block pipeline resyncs
                    self._carry = None
        if req.trace is not None:
            req.trace.add_span("prefill_chunk", t0, t1, pos0=pos0,
                               valid=valid, final=final, block=bid)
        if jlog:
            # first token journaled before the finisher completes,
            # outside the engine lock (GL010) — same contract as _admit
            self._journal.retired(jlog)
        self._scrub_pages(scrub)
        self._scrub_slots(scrub_slots)
        self._fail_faulted(faulted, where="prefill_chunk")
        if registered and self._kv_verifier is not None:
            self._record_page_sums([(ctx, len(ctx) // self.page_size)])
        if registered:
            plan = self._faults.corruption("device.corrupt_page",
                                           where="registered")
            if plan is not None:
                self._corrupt_registered_page(ctx, plan["mode"])
        if finish is not None:
            finish._complete()
        if handoff_entry is not None:
            self._handoff_one(*handoff_entry)

    def _any_active(self) -> bool:
        return any(r is not None for r in self._slots) or \
            bool(self._chunking)

    def _step(self):
        """One decode dispatch: a single batched step (block_size=1, the
        legacy loop) or one pipelined K-step block cycle. Chunked
        prefill interleaves here — one prompt window per cycle advances
        BEFORE the decode dispatch, so long-prompt admission and decode
        share the device fairly."""
        if self._chunking:
            self._advance_chunks()
        if self.speculative and self.phase != "prefill":
            # speculative draft/verify (ISSUE 16). Low acceptance arms
            # a cooldown that routes through the plain (pipelined,
            # already-compiled) decode rungs; a probe block every
            # spec_probe_every fallback blocks re-measures acceptance
            # so a workload shift back to draftable text recovers.
            with self._lock:
                cooling = self._spec_cool > 0
                if cooling:
                    self._spec_cool -= 1
            if cooling:
                self._m["spec_fallbacks"].inc()
                return self._step_block()
            return self._step_spec()
        if self.block_size > 1 or self._pager is not None or \
                self._sentinel_on:
            # paged engines always decode through the block path (K=1
            # blocks included): one paged_decode_block{K}_impl family
            # serves every configuration, and page growth/preemption
            # has exactly one seam. Sentinel engines do too: the
            # verdict column rides the block impls' readback (the K=1
            # block is step-for-step identical to the legacy loop).
            return self._step_block()
        self._enforce_slots()
        with self._lock:
            active = any(r is not None for r in self._slots)
            if active:
                self._step_no += 1
                self._m["decode_steps"].inc()
                self._m["decode_blocks"].inc()   # a K=1 block
            step_no = self._step_no
        if not active:
            return                # lifecycle enforcement freed every slot
        bid = tracing.next_block_id()
        adm_bid = self._last_admit_bid
        with self._seam(tracing.DISPATCH_BLOCK, bid, k=1) as disp:
            self._faults.fire("engine.step")
            nxt, _, self._caches = self.decoder.decode_step(
                self._caches, self._last_ids,
                np.minimum(self._positions, self.t_max - 1), self._temps,
                key=jax.random.fold_in(self._key,
                                       ENGINE_KEY_SALT | step_no))
        with self._seam(tracing.BLOCK_READBACK, bid, k=1) as rb:
            nxt_host = device_fetch(nxt, tag="engine.decode")
        t_disp, t_ret = disp.t0, rb.t1
        finished: List[GenerationRequest] = []
        jlog: List[Tuple] = []
        # token appends and slot frees are one critical section: a
        # concurrent quarantine() either runs before (we see empty slots
        # and append nothing) or after (it harvests the post-append
        # state) — a recovered request never loses or duplicates a token
        gaps = [0, 0.0, 0, 0.0]
        with self._seam(tracing.RETIRE, bid, k=1) as ret, self._lock:
            self._ewma_locked("_est_step", t_ret - t_disp)
            self._m["host_readbacks"].inc()
            emitted = 0
            qdepth = len(self._pending)
            for s in range(self.num_slots):
                req = self._slots[s]
                if req is None:
                    continue
                tok = int(nxt_host[s])
                if self._journal is not None and \
                        req.journal_id is not None:
                    jlog.append((req.journal_id, len(req.generated),
                                 (tok,)))
                req.generated.append(tok)
                _book_gap(gaps, req, t_ret, adm_bid)
                req._emissions.append((t_ret, 1))
                req._emit_block = bid
                emitted += 1
                self._positions[s] += 1
                self._last_ids[s] = tok
                if req.trace is not None:
                    req.trace.add_span("decode_block", t_disp, t_ret, k=1,
                                       tokens=1, block=bid)
                if self._req_finished(req, tok):
                    self._slots[s] = None
                    self._m["completed"].inc()
                    finished.append(req)
            self._m["emitted_tokens"].inc(emitted)
            self._first_step_done = True
        # the seams' stamps telescope (ISSUE 13): dispatch → fetched →
        # host → journal → publish, so the recorded phases sum to the
        # block wall time; sinks are fed outside the engine lock
        with self._seam(tracing.JOURNAL, bid) as jn:
            if self._tracing:
                self._h_block.observe(t_ret - t_disp)
                self._flightrec.record(
                    "block_retire", engine=self.engine_id, k=1,
                    ms=round((t_ret - t_disp) * 1e3, 3))
            if jlog:
                self._journal.retired(jlog)   # one batched append
        with self._seam(tracing.PUBLISH, bid) as pub:
            for req in finished:
                req._complete()
        if self._prof is not None:
            self._prof.record_block(
                impl=self._prof_impl("step"), k=1, lanes=emitted,
                queued=qdepth, block=bid, t_dispatch=t_disp,
                t_dispatched=disp.t1, t_fetched=t_ret, t_host=ret.t1,
                t_journal=jn.t1, t_publish=pub.t1, gaps=tuple(gaps),
                seams=(disp, ret, jn, pub))

    def _step_block(self):
        """One pipelined block cycle (block_size=K): dispatch the next
        K-step device program from the ON-DEVICE carry of the previous
        block, THEN read back and bookkeep the previous block's [S, K]
        token matrix — the fetch and all host-side work (appends, stop
        detection, request completions feeding streaming publishes)
        overlap the new block's device compute. Slot frees and refills
        land at block boundaries; a lane whose request finished or was
        cancelled mid-pipeline simply has its remaining in-flight tokens
        dropped as overshoot (the dispatch snapshot pins which request
        each lane's tokens belong to)."""
        k = self._choose_block_size() if self.adaptive_block \
            else self.block_size
        self._enforce_slots()
        preempted: List[GenerationRequest] = []
        # resync boundary: the device carry was invalidated (slots were
        # refilled or freed) while a block is still in flight. Host state
        # lags that block by K steps, so a host-state dispatch now would
        # REPLAY them — retire the in-flight block first (serializing
        # this one boundary), then dispatch from caught-up host state.
        # The paged page-ensure runs BEFORE this boundary: a pool-
        # pressure preemption invalidates the carry, and the stale
        # pickup below must see that invalidation in the same cycle.
        with self._lock:
            if self._pager is not None and \
                    not (self._quarantined or self._shutdown):
                # lazy growth: each active lane's table must cover this
                # block's furthest write BEFORE dispatch; lanes the
                # pool cannot serve are preempted (exactly-once: their
                # tokens ride the request, re-admission re-prefills)
                preempted = self._ensure_decode_pages_locked(k)
            stale = self._inflight if self._carry is None else None
            if stale is not None:
                self._inflight = None
        if stale is not None:
            self._retire_block(stale)
        dispatch = None
        with self._lock:
            snapshot = [(s, self._slots[s]) for s in range(self.num_slots)
                        if self._slots[s] is not None]
            prev = self._inflight
            self._inflight = None
            if snapshot:
                self._step_no += k
                self._m["decode_steps"].inc(k)
                self._m["decode_blocks"].inc()
                carry = self._carry
                if carry is None:
                    # resync from host state (after admission / frees):
                    # free lanes launch frozen so they stop touching
                    # their cache cells until a refill re-prefills them
                    carry = (self._last_ids.copy(), self._positions.copy(),
                             np.asarray([self._slots[s] is None
                                         for s in range(self.num_slots)],
                                        bool))
                ptab = None if self._pager is None \
                    else self._ptables.copy()
                dispatch = (carry, self._step_no - k, self._temps.copy(),
                            self._eos_ids.copy(), ptab,
                            len(self._pending))
        for req in preempted:
            # out-of-lock bookkeeping for pool-pressure preemptions
            if req.trace is not None:
                req.trace.event("page_preempt", engine=self.engine_id,
                                generated=len(req.generated))
            self._flightrec.record("page_preempt", engine=self.engine_id,
                                   generated=len(req.generated))
            if self._journal is not None and req.journal_id is not None:
                self._journal.requeued(req)
        if dispatch is not None:
            (ids, pos, stop), step0, temps, eos, ptab, qdepth = dispatch
            if self.adaptive_block:
                self._m_k.labels(self.engine_id, str(k)).inc()
            # scripted compute corruption (device.corrupt_logits):
            # poison an active lane's attended KV state so THIS block's
            # logits corrupt — the sentinel's verdict column must trip
            # before any token reaches a caller
            plan = self._faults.corruption("device.corrupt_logits")
            if plan is not None:
                self._inject_corrupt_logits(plan["mode"], snapshot[0][0])
            bid = tracing.next_block_id()
            with self._seam(tracing.DISPATCH_BLOCK, bid, len(snapshot),
                            k) as disp:
                self._faults.fire("engine.step")
                if self._pager is not None:
                    toks, ids_d, pos_d, stop_d, self._caches = \
                        self.decoder.paged_decode_block(
                            self._caches, ptab, ids, pos, temps,
                            key=self._key, block_size=k, eos_ids=eos,
                            stopped=stop, step0=step0,
                            key_salt=ENGINE_KEY_SALT)
                else:
                    toks, ids_d, pos_d, stop_d, self._caches = \
                        self.decoder.decode_block(
                            self._caches, ids, pos, temps, key=self._key,
                            block_size=k, eos_ids=eos, stopped=stop,
                            step0=step0, key_salt=ENGINE_KEY_SALT)
            with self._lock:
                if not (self._quarantined or self._shutdown):
                    self._carry = (ids_d, pos_d, stop_d)
                    # the dispatch's seam rides along (its stamps and
                    # block id); prev still in flight: it overlapped it
                    self._inflight = (toks, snapshot, k, disp, qdepth,
                                      prev is not None,
                                      self._last_admit_bid)
        # prev was dispatched LAST cycle and has been computing since;
        # its fetch + bookkeeping overlap the block dispatched above.
        # With no active lanes left, prev's tokens are pure overshoot
        # (every snapshot request finished/cancelled) — dropped unread.
        if prev is not None and dispatch is not None:
            self._retire_block(prev)

    # ------------------------------------------- speculative decoding
    def _draft_locked(self, snapshot) -> np.ndarray:
        """Build this spec block's [S, spec_k] draft matrix (caller
        holds the engine lock): each occupied lane's per-slot drafter
        syncs to its request's full context — the sync is incremental
        in steady state and rebuilds transparently when the slot's
        occupant changed (refill, requeue after a takeover, fleet
        migration, disagg adoption) — then proposes spec_k candidates.
        Unoccupied/chunking lanes keep zero drafts: they dispatch
        frozen and emit nothing."""
        draft = np.zeros((self.num_slots, self.spec_k), np.int32)
        for s, req in snapshot:
            d = self._drafters.get(s)
            if d is None or d.max_n != self.spec_ngram:
                d = self._drafters[s] = NGramDrafter(self.spec_ngram)
            d.sync(req, req.prompt, req.generated)
            draft[s] = d.draft(self.spec_k)
        return draft

    def _rewind_slot_pages_locked(self, s: int) -> None:
        """Page-table rewind (caller holds the engine lock): truncate
        slot ``s``'s mapping to exactly cover its retired position.
        The verify dispatch grew the table over the full K+1 window;
        pages past the accepted length are unmapped — table entries
        redirected to the null page, one unref per page back to the
        pool, so the allocator audit stays balanced and a stale frozen
        write can never land in a page the allocator re-hands out.
        Rejected cells inside KEPT pages need no scrub: the next
        dispatch rewrites them before anything attends them (the same
        write-before-attend argument as the slab position clamp)."""
        pos = int(self._positions[s])
        keep = max(1, (pos + self.page_size - 1) // self.page_size)
        pages = self._slot_pages[s]
        if len(pages) <= keep:
            return
        drop, self._slot_pages[s] = pages[keep:], pages[:keep]
        self._ptables[s, keep:] = 0
        for pid in drop:
            self._pager.unref(pid)

    def _step_spec(self):
        """One speculative draft/verify block (ISSUE 16). Speculation
        is inherently serial — the drafter extends the lane's LAST
        retired suffix — so this path trades the decode pipeline's
        double buffering for K-fold emission on acceptance: any
        in-flight fallback block retires first (host state becomes
        authoritative), drafting + dispatch run from host state, and
        the single fused readback ([S, K+1 tokens | emit | (fault)])
        is fetched immediately. One readback per block, same as the
        pipelined path."""
        kd = self.spec_k
        self._enforce_slots()
        # drain the pipeline boundary: a fallback block may still be in
        # flight from the cooldown cycles — retire it so the host
        # positions/ids this dispatch reads are caught up
        with self._lock:
            stale, self._inflight = self._inflight, None
            self._carry = None
        if stale is not None:
            self._retire_block(stale)
        preempted: List[GenerationRequest] = []
        with self._lock:
            if self._pager is not None and \
                    not (self._quarantined or self._shutdown):
                # cover the window's furthest write (position + kd);
                # the pipeline is drained, so there is no lead
                preempted = self._ensure_decode_pages_locked(kd + 1)
        for req in preempted:
            if req.trace is not None:
                req.trace.event("page_preempt", engine=self.engine_id,
                                generated=len(req.generated))
            self._flightrec.record("page_preempt", engine=self.engine_id,
                                   generated=len(req.generated))
            if self._journal is not None and req.journal_id is not None:
                self._journal.requeued(req)
        bid = tracing.next_block_id()
        dispatch = None
        with self._seam(tracing.SPEC_DRAFT, bid, k=kd) as drafting, \
                self._lock:
            if self._quarantined or self._shutdown:
                return
            snapshot = [(s, self._slots[s]) for s in range(self.num_slots)
                        if self._slots[s] is not None]
            if snapshot:
                draft = self._draft_locked(snapshot)
                self._step_no += kd + 1
                self._m["decode_steps"].inc(kd + 1)
                self._m["decode_blocks"].inc()
                self._m["spec_blocks"].inc()
                self._m["spec_drafted"].inc(kd * len(snapshot))
                stop = np.asarray([self._slots[s] is None
                                   for s in range(self.num_slots)], bool)
                dispatch = (draft, self._last_ids.copy(),
                            self._positions.copy(), stop,
                            self._step_no - (kd + 1), self._temps.copy(),
                            self._eos_ids.copy(),
                            None if self._pager is None
                            else self._ptables.copy(),
                            len(self._pending))
        if dispatch is None:
            return
        draft, ids, pos, stop, step0, temps, eos, ptab, qdepth = dispatch
        # scripted compute corruption (device.corrupt_logits): poison an
        # active lane's attended KV so THIS verify forward's logits
        # corrupt — the sentinel verdict riding the readback must trip
        # before any drafted token reaches a caller
        plan = self._faults.corruption("device.corrupt_logits")
        if plan is not None:
            self._inject_corrupt_logits(plan["mode"], snapshot[0][0])
        with self._seam(tracing.DISPATCH_BLOCK, bid, len(snapshot),
                        kd) as disp:
            self._faults.fire("engine.step")
            if self._pager is not None:
                toks, _, _, _, self._caches = \
                    self.decoder.paged_verify_block(
                        self._caches, ptab, ids, pos, draft, temps,
                        key=self._key, eos_ids=eos, stopped=stop,
                        step0=step0, key_salt=ENGINE_KEY_SALT)
            else:
                toks, _, _, _, self._caches = self.decoder.verify_block(
                    self._caches, ids, pos, draft, temps, key=self._key,
                    eos_ids=eos, stopped=stop, step0=step0,
                    key_salt=ENGINE_KEY_SALT)
        self._retire_spec(toks, snapshot, kd, drafting.t0, disp.t0, qdepth,
                          bid)

    def _retire_spec(self, toks_dev, snapshot, kd, t_draft, t_disp,
                     qdepth, bid):
        """Ragged retire of one verify block: fetch the fused [S, K+1
        tokens | emit | (fault)] matrix (ONE host readback) and append
        each lane's accepted prefix — per-lane VARIABLE lengths, with
        the journal's absolute-offset ``ret`` contract intact because
        each frame's base is the lane's own generated-length at append
        time. Open lanes' positions advance by exactly what they
        emitted (the slab rewind IS this clamp); paged lanes then
        truncate their page tables back to the accepted length."""
        lanes = len(snapshot)
        with self._seam(tracing.BLOCK_READBACK, bid, lanes, kd) as rb:
            host = device_fetch(toks_dev, tag="engine.decode")
        t_ret = rb.t1
        fault_col = host[:, kd + 2] if self._sentinel_on else None
        emit_col = host[:, kd + 1]
        finished: List[GenerationRequest] = []
        faulted: List[GenerationRequest] = []
        scrub: List[int] = []
        scrub_slots: List[int] = []
        jlog: List[Tuple] = []
        drafted = accepted = 0
        with self._seam(tracing.SPEC_REWIND, bid, lanes, kd) as rew, \
                self._lock:
            if self._quarantined or self._shutdown:
                return   # the drain owns the requests; recovery
                         # re-prefills and regenerates these tokens
            self._m["host_readbacks"].inc()
            emitted = 0
            for s, req in snapshot:
                if req.done() or self._slots[s] is not req:
                    continue   # finished/cancelled since dispatch
                if fault_col is not None and fault_col[s]:
                    # sentinel tripped inside the emitted window: every
                    # token of this block is suspect — same quarantine
                    # path as the pipelined retire
                    self._slots[s] = None
                    if self._pager is not None:
                        scrub.extend(self._slot_pages[s])
                        dgs = self._pager.evict_pages(self._slot_pages[s])
                        if self._kv_verifier is not None:
                            self._kv_verifier.forget(dgs)
                    else:
                        scrub_slots.append(s)
                    self._release_slot_pages(s)
                    self._m_numfault.inc()
                    faulted.append(req)
                    continue
                take = int(emit_col[s])
                drafted += kd
                acc = max(0, take - 1)
                accepted += acc
                self._m_spec_len.labels(self.engine_id, str(acc)).inc()
                closed = False
                took = 0
                base = len(req.generated)
                for c in range(take):
                    tok = int(host[s, c])
                    req.generated.append(tok)
                    emitted += 1
                    took += 1
                    if self._req_finished(req, tok):
                        self._slots[s] = None
                        self._release_slot_pages(s)
                        self._m["completed"].inc()
                        finished.append(req)
                        closed = True
                        break
                req._emissions.append((t_ret, took))
                req._emit_block = bid
                if self._journal is not None and \
                        req.journal_id is not None and took:
                    jlog.append((req.journal_id, base,
                                 req.generated[base:base + took]))
                if req.trace is not None:
                    req.trace.add_span("verify_block", t_disp, t_ret,
                                       k=kd, tokens=took, block=bid)
                if not closed:
                    # the accepted length IS the rewind on the slab:
                    # rejected cells sit past the new write-head and are
                    # rewritten before ever attended
                    self._positions[s] += took
                    self._last_ids[s] = int(host[s, took - 1])
                    if self._pager is not None:
                        self._rewind_slot_pages_locked(s)
            self._m["spec_accepted_tokens"].inc(accepted)
            self._m["emitted_tokens"].inc(emitted)
            self._first_step_done = True
            # rolling acceptance drives the adaptive fallback: below
            # threshold, route the next spec_probe_every blocks through
            # the plain pipelined rungs, then probe again
            if drafted:
                rate = accepted / drafted
                self._spec_ewma = rate if self._spec_ewma is None else \
                    0.7 * self._spec_ewma + 0.3 * rate
                if self._spec_ewma < self.spec_threshold:
                    self._spec_cool = self.spec_probe_every
            # per-emitted-token cost estimate: speculation's whole point
            # is that the divisor grows with acceptance
            self._ewma_locked("_est_step",
                              (t_ret - t_disp) / max(1, emitted))
        # spec_rewind holds the whole ragged retire under the lock — the
        # accepted-length clamp and the page-table rollback are most of
        # it — so the host phase ends where it does
        with self._seam(tracing.JOURNAL, bid) as jn:
            if self._tracing:
                self._h_block.observe(t_ret - t_disp)
                self._flightrec.record(
                    "block_retire", engine=self.engine_id, k=kd + 1,
                    lanes=lanes, spec=True,
                    ms=round((t_ret - t_disp) * 1e3, 3))
                self._h_spec_draft.observe(max(0.0, t_disp - t_draft))
            if jlog:
                self._journal.retired(jlog)
        with self._seam(tracing.PUBLISH, bid) as pub:
            self._scrub_pages(scrub)
            self._scrub_slots(scrub_slots)
            self._fail_faulted(faulted, where=f"verify_block{kd}")
            for req in finished:
                req._complete()
        if self._prof is not None:
            self._prof.record_spec(
                impl=self._prof_impl("verify", kd), k=kd, lanes=lanes,
                queued=qdepth, accepted=accepted, drafted=drafted,
                block=bid, t_draft=t_draft, t_dispatch=t_disp,
                t_fetched=t_ret, t_rewind=rew.t1, t_host=rew.t1,
                t_journal=jn.t1, t_publish=pub.t1)

    def _retire_block(self, block):
        """Fetch one block's [S, K] token matrix (ONE host readback) and
        run its host bookkeeping: per-lane appends until a stop, slot
        frees, request completions."""
        toks_dev, snapshot, k, disp, qdepth, overlapped, adm_bid = block
        bid, t_disp, lanes = disp.block, disp.t0, len(snapshot)
        with self._seam(tracing.BLOCK_READBACK, bid, lanes, k) as rb:
            host, counts = self.decoder.split_block(
                device_fetch(toks_dev, tag="engine.decode"))
        t_ret = rb.t1
        fault_col = None
        if self._sentinel_on:
            # the sentinel verdict is column K of the SAME fetched
            # matrix — still exactly one readback per block
            fault_col = host[:, k]
            host = host[:, :k]
        finished: List[GenerationRequest] = []
        faulted: List[GenerationRequest] = []
        scrub: List[int] = []
        scrub_slots: List[int] = []
        jlog: List[Tuple] = []
        gaps = [0, 0.0, 0, 0.0]
        with self._seam(tracing.RETIRE, bid, lanes, k) as ret, self._lock:
            self._ewma_locked("_est_step", (t_ret - t_disp) / max(1, k))
            if self._quarantined or self._shutdown:
                return   # the drain owns the requests; recovery
                         # re-prefills and regenerates these tokens
            self._m["host_readbacks"].inc()
            if counts is not None:
                for name, n in zip(self.decoder.counter_names, counts):
                    self._m[name].inc(int(n))
            emitted = 0
            for s, req in snapshot:
                if req.done() or self._slots[s] is not req:
                    continue   # finished/cancelled since dispatch:
                               # the lane's tokens are overshoot
                if fault_col is not None and fault_col[s]:
                    # numerics sentinel tripped on this lane: the whole
                    # block's tokens are suspect (the first bad step's
                    # token fed every later one) — DROP them all, free
                    # the lane, fail the request typed. Nothing from
                    # this block ever reaches the caller or the journal.
                    self._slots[s] = None
                    if self._pager is not None:
                        # every page the lane mapped is suspect — incl.
                        # prompt pages it registered: evict them from
                        # the prefix index (no future stream may map
                        # suspect bytes), drop their checksum
                        # references (a stale ref re-fires on pid
                        # reuse), then scrub before reuse
                        scrub.extend(self._slot_pages[s])
                        dgs = self._pager.evict_pages(self._slot_pages[s])
                        if self._kv_verifier is not None:
                            self._kv_verifier.forget(dgs)
                    else:
                        scrub_slots.append(s)
                    self._release_slot_pages(s)
                    self._m_numfault.inc()
                    faulted.append(req)
                    continue
                closed = False
                took = 0
                base = len(req.generated)
                for c in range(k):
                    tok = int(host[s, c])
                    req.generated.append(tok)
                    emitted += 1
                    took += 1
                    if self._req_finished(req, tok):
                        self._slots[s] = None
                        self._release_slot_pages(s)
                        self._m["completed"].inc()
                        finished.append(req)
                        closed = True
                        break
                _book_gap(gaps, req, t_ret, adm_bid)
                req._emissions.append((t_ret, took))
                req._emit_block = bid
                if self._journal is not None and \
                        req.journal_id is not None and took:
                    jlog.append((req.journal_id, base,
                                 req.generated[base:base + took]))
                if req.trace is not None:
                    req.trace.add_span("decode_block", t_disp, t_ret,
                                       k=k, tokens=took, block=bid)
                if not closed:
                    self._positions[s] += k
                    self._last_ids[s] = int(host[s, k - 1])
            self._m["emitted_tokens"].inc(emitted)
            self._first_step_done = True
            if finished or faulted:
                # freed lanes must not keep decoding from the device
                # carry: resync (and let _admit refill) next dispatch
                self._carry = None
        # the seams' stamps telescope (ISSUE 13), serve thread, outside
        # the engine lock: dispatch → fetched → host → journal →
        # publish, so the per-phase account sums exactly to the block
        # wall time
        with self._seam(tracing.JOURNAL, bid) as jn:
            if self._tracing:
                self._h_block.observe(t_ret - t_disp)
                self._flightrec.record(
                    "block_retire", engine=self.engine_id, k=k,
                    lanes=lanes, ms=round((t_ret - t_disp) * 1e3, 3))
            if jlog:
                # batched per block, OUTSIDE the engine lock
                # (GL010-clean): one buffer write (and at most one fsync
                # per the journal's policy) per decode block
                self._journal.retired(jlog)
        with self._seam(tracing.PUBLISH, bid) as pub:
            # faulted lanes' pages/cells carry potentially non-finite
            # residue: zero them before reuse (serve thread — nothing
            # can map the freed pages / refill the slot until the next
            # admission on this same thread)
            self._scrub_pages(scrub)
            self._scrub_slots(scrub_slots)
            self._fail_faulted(faulted, where=f"decode_block{k}")
            for req in finished:
                req._complete()
        if self._prof is not None:
            self._prof.record_block(
                impl=self._prof_impl("block", k), k=k, lanes=lanes,
                queued=qdepth, block=bid, overlapped=overlapped,
                t_dispatch=t_disp, t_dispatched=disp.t1, t_fetched=t_ret,
                t_host=ret.t1, t_journal=jn.t1, t_publish=pub.t1,
                gaps=tuple(gaps), seams=(disp, ret, jn, pub))

    # -------------------------------------------------------- preemption
    def begin_drain(self) -> None:
        """Close admission (new submissions shed with RejectedError)
        while queued/decoding work continues — phase 1 of a preemption
        drain (parallel/preemption.py)."""
        with self._lock:
            self._draining = True

    def preempt_drain(self, budget: float = 10.0
                      ) -> Tuple[List[GenerationRequest],
                                 Optional[BaseException]]:
        """Drain-or-die stop for preemption: close admission, park the
        serve loop at the next block boundary (waiting at most
        ``budget`` seconds — a loop wedged in a device call is
        abandoned, not waited out), retire the in-flight decode block if
        the loop stopped cleanly (its tokens are journaled and its
        finished requests complete — work the re-prefill would otherwise
        redo), then quarantine-harvest everything still live. Harvested
        requests are NOT failed: their journal records stay open, and
        post-restart recovery resumes them token-identically."""
        t_end = interval_now() + max(0.0, float(budget))
        with self._lock:
            self._draining = True
            self._drain_stop = True
        self._work.set()
        w = self._worker
        if w is not None and w is not threading.current_thread():
            w.join(timeout=max(0.0, t_end - interval_now()))
        stale = None
        with self._lock:
            loop_stopped = w is None or not w.is_alive()
            if loop_stopped and not (self._quarantined or self._shutdown):
                stale, self._inflight = self._inflight, None
        if stale is not None:
            # budget-gated: retiring fetches the block (a device sync);
            # with no budget left the tokens are abandoned instead —
            # recovery regenerates them deterministically
            if interval_now() < t_end:
                self._retire_block(stale)
        return self.quarantine()

    # ------------------------------------------------------- supervision
    def quarantine(self) -> Tuple[List[GenerationRequest],
                                  Optional[BaseException]]:
        """Detach this engine for supervised takeover: stop the loop and
        harvest every recoverable request (mid-admit, in-slot, queued —
        in that deterministic order) exactly once. The wedged/dead
        worker thread, whenever it wakes, sees ``_quarantined`` and
        touches nothing. Returns (recoverable requests, death cause)."""
        harvested: List[GenerationRequest] = []
        with self._lock:
            self._quarantined = True
            self._shutdown = True
            self._beat = None   # a stale worker must not mask the NEW
                                # engine's heartbeat when it wakes
            harvested.extend(self._admitting)
            self._admitting = []
            # adopted handoffs not yet in a slot: recovery re-prefills
            # them from prompt + generated (their shipped frames are
            # dropped — deterministic re-prefill regenerates the KV)
            harvested.extend(r for r, _ in self._adopted)
            self._adopted.clear()
            for s in sorted(self._chunking):
                # mid-chunk prefill: recovery re-prefills from scratch
                # (no tokens were emitted yet), deterministically
                harvested.append(self._chunking[s][0])
            self._chunking = {}
            for s in range(self.num_slots):
                if self._slots[s] is not None:
                    harvested.append(self._slots[s])
                    self._slots[s] = None
            harvested.extend(self._pending)
            self._pending.clear()
            # drop the decode pipeline: in-flight tokens are never read
            # (recovery re-prefills and regenerates them exactly)
            self._inflight = None
            self._carry = None
            # release every page mapping: the harvest leaves the
            # allocator audit-balanced (only prefix-index retention
            # remains; the pool dies with this engine either way)
            self._release_all_pages()
            cause = self._dead
        self._work.set()
        return [r for r in harvested if not r.done()], cause

    def stats(self) -> Dict[str, int]:
        """Serving-counter snapshot — a thin view over this engine's
        labeled registry children (ISSUE 5), same keys as ever, plus the
        two live gauges read under the engine lock."""
        out = {key: int(self._m[key].value) for key in _ENGINE_COUNTERS}
        # prefix-cache outcomes (ISSUE 12): plain ints, so supervisor
        # takeover accounting merges them like any other counter
        out["prefix_cache_hits"] = int(self._m_prefix_hit.value)
        out["prefix_cache_misses"] = int(self._m_prefix_miss.value)
        out["prefix_cache_hit_tokens"] = int(self._m_prefix_tokens.value)
        # SDC defense outcomes (ISSUE 15): plain ints, merged across
        # supervisor rebuilds like every other counter
        out["numerical_faults"] = int(self._m_numfault.value)
        out["kv_page_corruptions"] = int(self._m_kv_corrupt.value)
        with self._lock:
            # adopted handoffs awaiting a slot ARE queued work: the
            # disagg router's least-loaded decode dispatch reads this
            out["queue_depth"] = len(self._pending) + len(self._adopted)
            # a request popped for admission holds its slot from that
            # moment (its prefill is in flight): counted here, so the
            # load a router reads never drops while a wave is admitted
            out["active_slots"] = sum(r is not None
                                      for r in self._slots) + \
                len(self._chunking) + len(self._admitting)
        # mesh topology (r12): "<data>x<tp>" for a sharded engine, None
        # for single-device — /snapshot sources surface it verbatim
        from ..parallel.mesh import mesh_tag
        out["mesh_shape"] = mesh_tag(self.mesh) or None
        out["kv_heads_per_row"] = self.kv_heads_per_row
        return out

    def _slab_read_share(self) -> float:
        """slab_positions_read / slab_positions_held so far (0.0 before a
        block has been retired)."""
        held = self._m["slab_positions_held"].value
        return self._m["slab_positions_read"].value / held if held else 0.0

    @property
    def kv_heads_per_row(self) -> int:
        """Heads sharing one 128-lane row of this engine's KV cache: the
        decoder's ``g`` for the slab, 1 for a paged pool (never
        packed)."""
        return 1 if self._pager is not None \
            else self.decoder.kv_heads_per_row

    @property
    def latent_cache_bytes_per_token(self) -> int:
        """The decoder's: bytes a cached token takes over all latent-
        attention layers, 0 for a per-head k/v cache."""
        return self.decoder.latent_cache_bytes_per_token

    # ---------------------------------------------------------- execution
    def run_until_drained(self):
        """Synchronous mode: process the queue to empty. With refill on,
        finished slots re-admit mid-loop; with refill off, each admitted
        wave drains fully before the next wave starts. (Injected faults
        propagate to the caller here; supervised recovery applies to the
        ``start()`` serving mode.)"""
        while True:
            self._sweep_pending()
            self._admit()
            if not self._any_active():
                if not self._pending and not self._adopted:
                    return
                continue                      # wave finished at token 1
            while self._any_active():
                self._step()
                if self.refill:
                    self._admit()

    def _serve_loop(self):
        try:
            while not self._shutdown:
                if self._drain_stop:
                    # preemption drain: park at a block boundary — the
                    # handler retires the in-flight block and harvests
                    return
                beat = self._beat
                if beat is not None:
                    beat()                    # supervisor liveness signal
                self._sweep_pending()
                if not self._any_active():
                    self._admit()
                if not self._any_active():
                    with self._seam(tracing.IDLE_WAIT) as idle:
                        self._work.wait(timeout=0.05)
                    self._work.clear()
                    if self._prof is not None:
                        # idle for lack of work, not for the host: the
                        # next dispatch's bubble counts from the wake-up
                        self._prof.mark_idle(idle.t1)
                    continue
                self._step()
                if self.refill:
                    self._admit()
        except BaseException as exc:  # noqa: BLE001 — don't strand callers
            with self._lock:
                self._dead = exc
                quarantined = self._quarantined
                on_crash = self._on_crash if self._supervised else None
            if quarantined:
                return   # superseded: a supervisor already harvested
            if on_crash is not None:
                # supervised: the supervisor quarantines, harvests, and
                # restarts — in-flight requests are NOT failed here
                # (exactly-once: failed and re-run are mutually exclusive)
                on_crash(self, exc)
                return
            # unsupervised: a dying worker (device error, OOM) fails every
            # outstanding request instead of leaving result() blocked
            # forever, and marks the engine dead so later submit()s fail
            # fast with the death CAUSE, not a generic error
            doomed: List[GenerationRequest] = []
            with self._lock:
                doomed.extend(self._admitting)
                self._admitting = []
                doomed.extend(r for r, _ in self._adopted)
                self._adopted.clear()
                for s in sorted(self._chunking):
                    doomed.append(self._chunking[s][0])
                self._chunking = {}
                for s in range(self.num_slots):
                    if self._slots[s] is not None:
                        doomed.append(self._slots[s])
                        self._slots[s] = None
                doomed.extend(self._pending)
                self._pending.clear()
                self._inflight = None
                self._carry = None
                self._release_all_pages()
                self._m["failed"].inc(len(doomed))
            for req in doomed:
                req._fail(exc)
            raise

    def start(self) -> "SlotGenerationEngine":
        if self._worker is None or not self._worker.is_alive():
            self._shutdown = False
            self._worker = threading.Thread(target=self._serve_loop,
                                            daemon=True)
            self._worker.start()
        return self

    def shutdown(self):
        with self._lock:
            self._shutdown = True
        self._work.set()
        if self._worker is not None and \
                self._worker is not threading.current_thread():
            self._worker.join(timeout=5)
        # fail whatever is still in flight/queued — a caller blocked in
        # result() with no timeout must not hang forever; a dead engine
        # reports its death cause, a merely-stopped one the shutdown
        doomed: List[GenerationRequest] = []
        with self._lock:
            exc = self._dead or RuntimeError(
                "SlotGenerationEngine shut down")
            doomed.extend(self._admitting)
            self._admitting = []
            doomed.extend(r for r, _ in self._adopted)
            self._adopted.clear()
            for s in sorted(self._chunking):
                doomed.append(self._chunking[s][0])
            self._chunking = {}
            for s in range(self.num_slots):
                if self._slots[s] is not None:
                    doomed.append(self._slots[s])
                    self._slots[s] = None
            doomed.extend(self._pending)
            self._pending.clear()
            self._inflight = None
            self._carry = None
            self._release_all_pages()
            self._m["failed"].inc(len(doomed))
        for req in doomed:
            req._fail(exc)


# Legacy counter attributes (``eng.emitted_tokens``, ``eng.decode_steps``,
# ...) as read-only properties over the engine's registry children: the
# benches, perf scripts, and four PRs of tests keep reading them while the
# registry owns the numbers. A missed write site fails loudly (properties
# reject assignment) instead of silently forking the counts.
for _counter_name in _ENGINE_COUNTERS:
    setattr(SlotGenerationEngine, _counter_name,
            property(lambda self, _k=_counter_name: int(self._m[_k].value),
                     doc=f"registry view: generation_{_counter_name}_total"
                         f"{{engine=<id>}}"))
del _counter_name
