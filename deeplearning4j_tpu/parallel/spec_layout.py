"""Canonical per-role PartitionSpecs for the serving decoder (ROADMAP 1).

The mesh-sharded generation path needs one statically-known answer to
"how does THIS parameter shard?" — the cross-replica sharded-update work
(PAPERS.md, arXiv:2004.13336) and the Megatron-style alternation in
``parallel/tensor.py`` both assume exactly that. :class:`SpecLayout`
owns the axis names and the per-role specs; :func:`decoder_param_specs`
walks a ``TransformerDecoder``'s graph and assigns a spec to every
parameter leaf by (layer type, parameter name); and
:func:`validate_param_specs` rank- and divisibility-checks the resulting
table against the decoder's ACTUAL parameters before any device
dispatch, so a bad layout fails with the offending vertex/param named
instead of an XLA sharding error at the first prefill.

Layout (tp = tensor parallel, data = batch/cache slots, optional fsdp):

- embeddings (token table ``W`` [V, D], positions ``P`` [T, D]): model
  dim over ``tp`` (optionally rows over ``fsdp``) — the embed gather
  stays local per shard;
- attention ``Wq/Wk/Wv`` [D, H·Dh]: column-parallel over ``tp`` (head
  dim splits — exactly how the [S, H, T, Dh] KV cache shards its H);
  ``Wo`` [H·Dh, D]: row-parallel (GSPMD inserts the completing psum);
- FFN ``W1`` column-parallel, ``W2`` row-parallel, their biases
  following the sharded/replicated dim;
- layer norms replicated; the vocab head column-parallel over ``tp``
  (logits [B, V] shard on V until the argmax/sample reduces them).

``fsdp_axis`` is optional and may NAME THE DATA AXIS (the standard
FSDP trick: parameters shard over the batch axis and all-gather per
use), so a plain ``(data, tp)`` serving mesh runs TPxFSDP with no third
axis.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import DATA_AXIS, TP_AXIS


@dataclasses.dataclass(frozen=True)
class SpecLayout:
    """Canonical PartitionSpecs per parameter role for decoder serving."""

    data_axis: str = DATA_AXIS
    tp_axis: str = TP_AXIS
    #: optional parameter-sharding axis; pass the data axis name to run
    #: FSDP-style parameter sharding on a 2-axis serving mesh
    fsdp_axis: Optional[str] = None

    # ------------------------------------------------------- param roles
    def embedding(self) -> P:
        """Token/position tables [V|T, D]: model dim over tp."""
        return P(self.fsdp_axis, self.tp_axis)

    def qkv_projection(self) -> P:
        """Wq/Wk/Wv [D, H*Dh]: column-parallel — heads split over tp."""
        return P(self.fsdp_axis, self.tp_axis)

    def attn_out(self) -> P:
        """Wo [H*Dh, D]: row-parallel (contraction over the tp shards)."""
        return P(self.tp_axis, self.fsdp_axis)

    def ffn_up(self) -> P:
        return P(self.fsdp_axis, self.tp_axis)

    def ffn_down(self) -> P:
        return P(self.tp_axis, self.fsdp_axis)

    def col_bias(self) -> P:
        """Bias of a column-parallel projection: follows the tp shards."""
        return P(self.tp_axis)

    def replicated(self) -> P:
        return P()

    def head(self) -> P:
        """Vocab projection [D, V]: logits shard on V over tp."""
        return P(self.fsdp_axis, self.tp_axis)

    # ------------------------------------------------- activations/cache
    def kv_cache(self) -> P:
        """[S, H/g, T_max, g·Dh]: slots over data, head groups over tp
        (g heads to a cache row; ``SelfAttentionLayer.heads_per_row``
        keeps H/g divisible by tp or falls back to g = 1)."""
        return P(self.data_axis, self.tp_axis, None, None)

    def kv_pages(self) -> P:
        """Paged pool [P, H, page_size, Dh]: heads over tp exactly like
        the slab's H dim. Pages do NOT shard over data — any slot may
        map any page, so the pool replicates across the data axis (the
        documented memory cost of paging on data>1 meshes until the
        disaggregated tier gives pages a home replica)."""
        return P(None, self.tp_axis, None, None)

    def batch(self, ndim: int = 1) -> P:
        """Per-row host inputs (ids/positions/temps [B], tokens [B, T]):
        batch over data."""
        return P(self.data_axis, *([None] * (ndim - 1)))


def decoder_param_specs(decoder, layout: Optional[SpecLayout] = None
                        ) -> Dict[str, Dict[str, P]]:
    """{vertex_name: {param_name: PartitionSpec}} for every vertex of a
    TransformerDecoder's graph; unlisted params replicate. Assignment is
    by (layer type, parameter name) — the name-based-table idiom of
    ``parallel/tensor.py`` applied to the decode graph roles."""
    from ..nn.conf.layers.attention import (SelfAttentionLayer,
                                            TokenAndPositionEmbedding,
                                            TransformerFeedForward)
    from ..nn.graph.vertices import LayerVertex

    layout = layout or SpecLayout()
    conf = decoder.net.conf
    specs: Dict[str, Dict[str, P]] = {}
    for name in conf.topological_order:
        v = conf.vertices[name]
        if not isinstance(v, LayerVertex):
            continue
        layer = v.layer
        if isinstance(layer, TokenAndPositionEmbedding):
            specs[name] = {"W": layout.embedding(), "P": layout.embedding()}
        elif isinstance(layer, SelfAttentionLayer):
            s = {"Wq": layout.qkv_projection(),
                 "Wk": layout.qkv_projection(),
                 "Wv": layout.qkv_projection()}
            if layer.project_out:
                s["Wo"] = layout.attn_out()
                s["bo"] = layout.replicated()
            specs[name] = s
        elif isinstance(layer, TransformerFeedForward):
            specs[name] = {"W1": layout.ffn_up(), "b1": layout.col_bias(),
                           "W2": layout.ffn_down(),
                           "b2": layout.replicated()}
        elif name == decoder.output_name:
            specs[name] = {"W": layout.head(), "b": layout.col_bias()}
    return specs


def validate_param_specs(mesh: Mesh, specs: Dict[str, Dict[str, P]],
                         params) -> None:
    """Check a name-based spec table against the ACTUAL parameter tree:
    every spec's rank must not exceed its leaf's rank, every named axis
    must exist on the mesh, and every sharded dim must divide by its
    axis size. Raises ValueError naming the offending vertex/param —
    the runtime counterpart of graftlint's static GL013 rank check."""
    problems = []
    for vname, table in specs.items():
        leaves = params.get(vname, {})
        for pname, spec in table.items():
            if pname not in leaves:
                problems.append(f"{vname}.{pname}: spec for a parameter "
                                "the decoder does not have")
                continue
            leaf = leaves[pname]
            entries = tuple(spec)
            if len(entries) > leaf.ndim:
                problems.append(
                    f"{vname}.{pname}: spec {spec} has {len(entries)} "
                    f"entries but the leaf is rank {leaf.ndim} "
                    f"(shape {tuple(leaf.shape)}) — PartitionSpec rank "
                    "cannot exceed the leaf's rank")
                continue
            for dim, axis in enumerate(entries):
                if axis is None:
                    continue
                axes = axis if isinstance(axis, tuple) else (axis,)
                for ax in axes:
                    size = mesh.shape.get(ax)
                    if size is None:
                        problems.append(
                            f"{vname}.{pname}: spec {spec} names axis "
                            f"'{ax}' absent from the mesh axes "
                            f"{tuple(mesh.axis_names)}")
                    elif leaf.shape[dim] % size:
                        problems.append(
                            f"{vname}.{pname}: dim {dim} of shape "
                            f"{tuple(leaf.shape)} is not divisible by "
                            f"axis '{ax}' size {size}")
    if problems:
        raise ValueError("invalid parameter sharding layout:\n  " +
                         "\n  ".join(problems))


def param_shardings(mesh: Mesh, specs: Dict[str, Dict[str, P]],
                    params) -> Dict[str, Dict[str, NamedSharding]]:
    """NamedSharding tree exactly matching ``params``' structure (the
    jit ``in_shardings``/``out_shardings`` form); unlisted leaves
    replicate."""
    return {vname: {pname: NamedSharding(
                        mesh, specs.get(vname, {}).get(pname, P()))
                    for pname in leaves}
            for vname, leaves in params.items()}


