"""Sharding-discipline pass: GL013/GL014 on the pjit/shard_map seams.

ROADMAP item 1 (mesh-sharded generation) hinges on statically-known
partition layouts per parameter role — the cross-replica sharded
weight-update work (PAPERS.md, arxiv 2004.13336) assumes exactly that.
These rules land BEFORE the sharding PR so it is born gated:

- **GL013 PartitionSpec/mesh-axis consistency** — a ``PartitionSpec``
  naming an axis absent from every mesh declared in the module (or from
  the module's ``*_axis`` parameter vocabulary) shards onto an axis that
  does not exist: jax raises at dispatch time, per call site, long after
  review. When a ``shard_map``/``pjit`` call site's
  ``mesh=`` argument resolves to a mesh built in the same module with
  literal axis names, its ``in_specs``/``out_specs`` are checked against
  THAT mesh's axes specifically. Name-based assignment tables
  (``{"b": P(...)}`` — the parallel/tensor.py idiom) are rank-checked
  for known-rank-1 parameter names: a bias spec with two axis entries
  cannot match a [F] leaf.
- **GL014 host sync / telemetry recording inside a shard_map or pjit
  region** — GL001/GL008 generalized to the SPMD seams, where the cost
  is worse: the offending call runs at trace time once per compile
  (never per step), forces a cross-host sync under pjit, and
  ``print``/metric calls observe tracers, not values. Sanctioned
  crossings stay outside the region (the audited
  ``ops.transfer.device_fetch`` runs on the HOST side of the seam).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

#: known-rank-1 parameter names in name-based spec assignment tables
_RANK1_PARAM_NAMES = {"b", "bo", "bq", "bk", "bv", "bias", "beta",
                      "gamma", "scale", "offset"}

#: wrappers that open an SPMD region (their fn argument runs under trace
#: on the mesh)
_SPMD_WRAPPERS = {"shard_map", "pjit"}

#: host-sync call tails inside an SPMD region
_HOST_SYNC_TAILS = {"item", "tolist", "block_until_ready"}
_HOST_FETCH_NAMES = {"device_fetch", "device_get"}

#: observability recording (mirrors lint.py GL008 sets)
_OBS_RECORD_METHODS = {"inc", "observe", "observe_many", "add_span",
                       "start_span", "end_span", "record_span"}
_OBS_HINTED_METHODS = {"set", "dec", "event", "finish", "labels",
                       "annotate"}
_OBS_NAME_HINTS = ("metric", "gauge", "counter", "hist", "trace", "span",
                   "registry", "telemetry")


from .lint import (_GL016_NAME_HINTS, _GL016_RECORD_METHODS, _SEAM_CALLS,
                   _dotted_name, _dotted_tail)


def _literal_strings(node: ast.AST) -> List[str]:
    """Every string literal inside an expression (axis names in specs)."""
    out = []
    for n in ast.walk(node):
        if isinstance(n, ast.Constant) and isinstance(n.value, str):
            out.append(n.value)
    return out


def _spec_calls(node: ast.AST) -> List[ast.Call]:
    """P(...) / PartitionSpec(...) call sites inside an expression."""
    out = []
    for n in ast.walk(node):
        if isinstance(n, ast.Call) and \
                _dotted_tail(n.func) in ("P", "PartitionSpec"):
            out.append(n)
    return out


class ShardingLint:
    """Per-module GL013/GL014 pass. Pure-AST; emits via the callback
    ``emit(rule, line, func, message)`` (the runner owns Finding
    construction and suppression)."""

    def __init__(self, tree: ast.Module):
        self.tree = tree
        self.parents: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node

    # ------------------------------------------------------------ common
    def _qualname(self, node: ast.AST) -> str:
        parts: List[str] = []
        cur: Optional[ast.AST] = node
        while cur is not None and not isinstance(cur, ast.Module):
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                parts.append(cur.name)
            elif isinstance(cur, ast.Lambda):
                parts.append("<lambda>")
            cur = self.parents.get(cur)
        return ".".join(reversed(parts)) or "<module>"

    # ------------------------------------------------------------- GL013
    def _axis_vocab(self) -> Tuple[Set[str], Dict[str, Set[str]]]:
        """(module-wide axis vocabulary, mesh-variable -> its axes).

        Sources: literal ``axis_names`` of ``Mesh``/``make_mesh`` calls,
        string defaults of ``*axis*`` parameters, and string literals
        assigned to ``*axis*``-named variables. An empty vocabulary
        disables the module-wide check (the mesh lives elsewhere and we
        cannot see its axes)."""
        vocab: Set[str] = set()
        mesh_axes: Dict[str, Set[str]] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call):
                tail = _dotted_tail(node.func)
                axes: List[str] = []
                if tail == "Mesh" and len(node.args) >= 2:
                    axes = _literal_strings(node.args[1])
                for kw in node.keywords:
                    if kw.arg == "axis_names":
                        axes = _literal_strings(kw.value)
                if tail in ("Mesh", "make_mesh") and axes:
                    vocab.update(axes)
                    parent = self.parents.get(node)
                    if isinstance(parent, ast.Assign):
                        for t in parent.targets:
                            if isinstance(t, ast.Name):
                                mesh_axes[t.id] = set(axes)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                a = node.args
                pos = a.posonlyargs + a.args
                defaults = a.defaults
                for p, d in zip(pos[len(pos) - len(defaults):], defaults):
                    if "axis" in p.arg.lower() and \
                            isinstance(d, ast.Constant) and \
                            isinstance(d.value, str):
                        vocab.add(d.value)
                for p, d in zip(a.kwonlyargs, a.kw_defaults):
                    if d is not None and "axis" in p.arg.lower() and \
                            isinstance(d, ast.Constant) and \
                            isinstance(d.value, str):
                        vocab.add(d.value)
            elif isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Constant) and \
                    isinstance(node.value.value, str):
                for t in node.targets:
                    if isinstance(t, ast.Name) and "axis" in t.id.lower():
                        vocab.add(node.value.value)
            elif isinstance(node, ast.AnnAssign) and \
                    node.value is not None and \
                    isinstance(node.value, ast.Constant) and \
                    isinstance(node.value.value, str) and \
                    isinstance(node.target, ast.Name) and \
                    "axis" in node.target.id.lower():
                # annotated axis declarations — module constants AND
                # dataclass fields (`data_axis: Axis = "data"`, the
                # SpecLayout idiom): an axis-typo'd literal spec in such
                # a module must be checkable, not vocabulary-blind
                vocab.add(node.value.value)
        return vocab, mesh_axes

    def check_gl013(self, emit) -> None:
        vocab, mesh_axes = self._axis_vocab()
        checked: Set[int] = set()
        # (a) shard_map/pjit sites whose mesh resolves in-module: strict
        # per-site axis check against that mesh
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call) or \
                    _dotted_tail(node.func) not in _SPMD_WRAPPERS:
                continue
            site_axes: Optional[Set[str]] = None
            for kw in node.keywords:
                if kw.arg == "mesh" and isinstance(kw.value, ast.Name):
                    site_axes = mesh_axes.get(kw.value.id)
            if site_axes is None:
                continue
            for kw in node.keywords:
                if kw.arg not in ("in_specs", "out_specs"):
                    continue
                for spec in _spec_calls(kw.value):
                    checked.add(id(spec))
                    for ax in _literal_strings(spec):
                        if ax not in site_axes:
                            emit("GL013", spec.lineno,
                                 self._qualname(spec),
                                 f"PartitionSpec names axis '{ax}' but "
                                 "the shard_map's mesh declares axes "
                                 f"{sorted(site_axes)} — dispatch fails "
                                 "at run time; use a declared axis")
        # (b) module-wide: any other P(...) literal axis outside the
        # vocabulary (only when the module declares axes at all)
        if vocab:
            for spec in _spec_calls(self.tree):
                if id(spec) in checked:
                    continue
                for ax in _literal_strings(spec):
                    if ax not in vocab:
                        emit("GL013", spec.lineno, self._qualname(spec),
                             f"PartitionSpec names axis '{ax}' absent "
                             "from every mesh/axis declaration in this "
                             f"module ({sorted(vocab)}) — sharding onto "
                             "a nonexistent axis fails at dispatch")
        # (c) rank check on name-based assignment tables
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Dict):
                continue
            for k, v in zip(node.keys, node.values):
                if not (isinstance(k, ast.Constant) and
                        isinstance(k.value, str) and
                        k.value in _RANK1_PARAM_NAMES):
                    continue
                if isinstance(v, ast.Call) and \
                        _dotted_tail(v.func) in ("P", "PartitionSpec") \
                        and len(v.args) > 1:
                    emit("GL013", v.lineno, self._qualname(v),
                         f"spec for rank-1 parameter '{k.value}' has "
                         f"{len(v.args)} entries — PartitionSpec rank "
                         "cannot exceed the leaf's rank; a bias is "
                         "sharded (or replicated) on ONE axis")

    # ------------------------------------------------------------- GL014
    def _spmd_functions(self) -> List[Tuple[ast.AST, str]]:
        wrapped_names: Set[str] = set()
        wrapped_nodes: Set[int] = set()
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call) and \
                    _dotted_tail(node.func) in _SPMD_WRAPPERS:
                for a in node.args:
                    if isinstance(a, ast.Name):
                        wrapped_names.add(a.id)
                    elif isinstance(a, ast.Lambda):
                        wrapped_nodes.add(id(a))
        out: List[Tuple[ast.AST, str]] = []
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                wrapped = node.name in wrapped_names or any(
                    (isinstance(d, ast.Call) and
                     _dotted_tail(d.func) in _SPMD_WRAPPERS)
                    or _dotted_tail(d) in _SPMD_WRAPPERS
                    for d in node.decorator_list)
                if wrapped:
                    out.append((node, self._qualname(node)))
            elif isinstance(node, ast.Lambda) and id(node) in wrapped_nodes:
                out.append((node, self._qualname(node)))
        return out

    def check_gl014(self, emit) -> None:
        for fn, qual in self._spmd_functions():
            body = fn.body if isinstance(fn.body, list) else [fn.body]
            for node in [n for b in body for n in ast.walk(b)]:
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                tail = _dotted_tail(f)
                dn = _dotted_name(f)
                if isinstance(f, ast.Attribute) and \
                        tail in _HOST_SYNC_TAILS:
                    emit("GL014", node.lineno, qual,
                         f".{tail}() inside a shard_map/pjit region — "
                         "a host sync under SPMD trace stalls every "
                         "device in the mesh (and runs at trace time, "
                         "not per step); return the array and sync on "
                         "the host side of the seam")
                elif tail in _HOST_FETCH_NAMES or \
                        dn in ("jax.device_get", "np.asarray",
                               "numpy.asarray", "np.array", "numpy.array",
                               "np.save", "numpy.save"):
                    emit("GL014", node.lineno, qual,
                         f"{dn or tail}() inside a shard_map/pjit "
                         "region materializes a traced value on host — "
                         "cross the seam outside the region (the "
                         "audited device_fetch runs host-side)")
                elif isinstance(f, ast.Name) and f.id == "print":
                    emit("GL014", node.lineno, qual,
                         "print() inside a shard_map/pjit region "
                         "observes tracers and runs once per COMPILE — "
                         "use jax.debug.print or log on the host side")
                elif tail in _SEAM_CALLS:
                    emit("GL014", node.lineno, qual,
                         f"{tail}() stamps a seam of the host loop "
                         "inside a shard_map/pjit region — seams and "
                         "their sinks must stay host-side (GL008 "
                         "generalized to the SPMD seams)")
                elif isinstance(f, ast.Attribute):
                    recv = _dotted_name(f.value).lower()
                    hinted = any(w in recv for w in _OBS_NAME_HINTS)
                    if tail in _OBS_RECORD_METHODS or \
                            (hinted and tail in _OBS_HINTED_METHODS):
                        emit("GL014", node.lineno, qual,
                             f".{tail}() records telemetry inside a "
                             "shard_map/pjit region — instrumentation "
                             "must stay host-side (GL008 generalized "
                             "to the SPMD seams)")

    def check_gl016(self, emit) -> None:
        """Profiler/phase-stamp recording inside an SPMD region — the
        shard_map half of GL016 (lint.py's jit-body pass covers plain
        jit contexts with the same hint/method sets)."""
        for fn, qual in self._spmd_functions():
            body = fn.body if isinstance(fn.body, list) else [fn.body]
            for node in [n for b in body for n in ast.walk(b)]:
                if not (isinstance(node, ast.Call) and
                        isinstance(node.func, ast.Attribute)):
                    continue
                tail = node.func.attr
                recv = _dotted_name(node.func.value).lower()
                if tail in _GL016_RECORD_METHODS and any(
                        w in recv for w in _GL016_NAME_HINTS):
                    emit("GL016", node.lineno, qual,
                         f".{tail}() records profiler phase stamps "
                         "inside a shard_map/pjit region — stamps are "
                         "host interval-clock anchors and must be "
                         "recorded on the readback thread, outside "
                         "the SPMD seam")


def run_sharding_pass(tree: ast.Module, enabled: Sequence[str], emit
                      ) -> None:
    lint = ShardingLint(tree)
    if "GL013" in enabled:
        lint.check_gl013(emit)
    if "GL014" in enabled:
        lint.check_gl014(emit)
    if "GL016" in enabled:
        lint.check_gl016(emit)
