"""graftlint static passes: AST lint for jit/trace discipline.

What counts as "inside traced code" (jit context) is decided statically,
without interprocedural analysis, from four sources:

1. decorators — ``@jax.jit``, ``@functools.partial(jax.jit, ...)``,
   ``@jax.custom_vjp`` / ``@jax.custom_jvp`` and friends;
2. wrapper call sites — a function (or lambda) passed by name to
   ``jax.jit`` / ``jax.lax.scan`` / ``while_loop`` / ``fori_loop`` /
   ``cond`` / ``jax.vmap`` / ``jax.grad`` / ``shard_map`` anywhere in
   the same module;
3. an explicit ``# graftlint: traced`` marker on (or directly above) a
   ``def`` line — for methods that are only ever CALLED from jitted
   walks (the decode seams in nn/conf/layers/attention.py,
   models/generation.py's ``_walk_*``), which no local analysis can see;
4. nesting — any function defined inside a jit-context function.

Pallas kernel bodies (functions passed to ``pallas_call``) are NOT
treated as jit context: their shape loops/branches are over static block
shapes and idiomatic there.

Suppression: ``# graftlint: disable=GL001[,GL002...]`` on the flagged
line (or the line above) silences those rules for that line;
``analysis/baseline.json`` suppresses pre-existing findings repo-wide so
``scripts/lint.py --fail-on-new`` gates only regressions. Baseline keys
are ``rule:path:function:snippet-hash`` — stable across unrelated line
drift.
"""

from __future__ import annotations

import ast
import dataclasses
import hashlib
import json
import os
from collections import Counter
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

RULES: Dict[str, str] = {
    "GL001": "host sync inside jitted/traced code",
    "GL002": "Python loop over array dims inside traced code (hot module)",
    "GL003": "branch on a traced value inside jitted code",
    "GL004": "numpy scalar math inside traced code (dtype promotion hazard)",
    "GL005": "jax.jit call site missing donate/static argnums its module "
             "siblings use",
    "GL006": "shared attribute written from a thread target without a "
             "held lock",
    "GL007": "blocking host readback of a just-dispatched result inside "
             "a loop in a hot module",
    "GL008": "metric/trace recording inside jitted/traced code "
             "(instrumentation must stay host-side)",
    "GL009": "lock-order inversion: cycle in the cross-module "
             "lock-acquisition graph (potential deadlock)",
    "GL010": "blocking call (socket/join/sleep/device/queue/HTTP) "
             "executed while holding a lock",
    "GL011": "condition-wait discipline: wait outside a predicate "
             "re-check loop, or wait/notify without the lock",
    "GL012": "non-daemon thread started without a tracked join path",
    "GL013": "PartitionSpec/mesh-axis inconsistency (unknown axis or "
             "spec rank vs known parameter rank)",
    "GL014": "host sync or metric/trace recording inside a "
             "shard_map/pjit region",
    "GL015": "metric-family naming violation (counters must end _total, "
             "histograms _seconds/_bytes) or flight-recorder/devstats/"
             "SLO recording inside jitted/traced code",
    "GL016": "profiler/phase-stamp recording inside jit-traced or "
             "shard_map code (phase stamps are host interval-clock "
             "anchors recorded from the readback thread; under trace "
             "they would fire once per compile, never per block)",
}

#: rules decided per module (cacheable per file); the rest (GL009-GL012)
#: need the whole-package call graph
PER_FILE_RULES = frozenset({"GL001", "GL002", "GL003", "GL004", "GL005",
                            "GL006", "GL007", "GL008", "GL013", "GL014",
                            "GL015", "GL016"})
PACKAGE_RULES = frozenset({"GL009", "GL010", "GL011", "GL012"})

#: bump to invalidate cached per-file results when any pass changes
LINT_VERSION = 16

#: wrappers whose function arguments are traced when called
_TRACE_WRAPPERS = {
    "jit", "pjit", "pmap", "vmap", "grad", "value_and_grad", "scan",
    "while_loop", "fori_loop", "cond", "switch", "checkify", "remat",
    "checkpoint", "shard_map", "xmap", "linearize",
    "vjp", "jvp", "associative_scan", "map",
}
#: decorators that make the decorated def traced
_TRACE_DECORATORS = _TRACE_WRAPPERS | {"custom_vjp", "custom_jvp",
                                       "custom_gradient"}
#: modules where GL002 (python loop over dims) applies — the hot paths
_HOT_DIRS = ("kernels", "models", "nn", "parallel")
#: attribute reads on a traced value that are static at trace time
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "weak_type", "sharding",
                 "aval"}
#: numpy calls that are NOT promotion hazards (dtype constructors, array
#: creation handled by GL001, index/meta helpers)
_NP_SAFE = {"asarray", "array", "float32", "float64", "float16", "int32",
            "int64", "int8", "uint8", "bool_", "dtype", "zeros", "ones",
            "empty", "arange", "shape", "ndim", "broadcast_to", "save"}
_LOCK_FACTORIES = {"Lock", "RLock", "Condition", "Semaphore",
                   "BoundedSemaphore"}
#: GL008 — method names that ARE observability recording wherever they
#: appear (nothing else in this codebase calls .inc()/.observe()/span
#: methods), vs names generic enough (.set(), .event(), ...) that they
#: only count when the receiver expression names an observability object
_OBS_RECORD_METHODS = {"inc", "observe", "observe_many", "add_span",
                       "start_span", "end_span", "record_span"}
_OBS_HINTED_METHODS = {"set", "dec", "event", "finish", "labels",
                       "annotate"}
_OBS_NAME_HINTS = ("metric", "gauge", "counter", "hist", "trace", "span",
                   "registry", "telemetry")
#: GL008 — the engine loop's and ``fit_batch``'s one stamp source
#: (observability.tracing.Seam, the engine's ``_seam``): it takes host
#: interval-clock stamps and feeds the sinks above, so it is host-only
#: like them, by whatever receiver it is reached
_SEAM_CALLS = {"Seam", "_seam"}
#: GL015 — the ISSUE 9 sinks: flight-recorder / devstats / SLO recording
#: must stay host-side exactly like GL008's metric/trace calls (same
#: receiver-hint machinery, its own rule id so the new subsystems get
#: their own baseline rows)
_GL015_NAME_HINTS = ("flight", "recorder", "flightrec", "devstats",
                     "slo")
_GL015_RECORD_METHODS = {"record", "dump", "write_postmortem",
                         "observe_request", "snapshot", "sample",
                         "record_request"}
#: GL015 — metric-family naming: registry declaration method → the
#: suffixes a family name must carry (Prometheus conventions; gauges are
#: unconstrained). Checked at any ``<registry-ish>.counter/histogram``
#: call site with a statically visible name (string literal, or an
#: f-string whose final fragment is literal).
_GL015_NAME_SUFFIXES = {"counter": ("_total",),
                        "histogram": ("_seconds", "_bytes")}
_GL015_REGISTRY_HINTS = ("registry", "reg")
#: GL016 — the ISSUE 13 phase profiler: phase-stamp/bubble recording
#: must stay on the host readback thread (same receiver-hint machinery
#: as GL008/GL015, its own rule id so the new subsystem gets its own
#: baseline rows). The sharding pass applies the same sets inside
#: shard_map/pjit regions.
_GL016_NAME_HINTS = ("profiler", "prof", "phase", "timeline")
_GL016_RECORD_METHODS = {"record_block", "record_admission",
                         "record_chunk", "record_spec", "channel",
                         "mark_idle"}
#: callees whose results are NOT "just-dispatched device work" for GL007:
#: python builtins and host-side helpers a loop legitimately materializes
_GL007_SAFE_CALLEES = {"range", "len", "list", "tuple", "dict", "set",
                       "zip", "enumerate", "sorted", "reversed", "min",
                       "max", "sum", "abs", "int", "float", "bool", "str",
                       "copy", "deepcopy", "append", "pop", "popleft",
                       "get", "items", "keys", "values", "split", "join",
                       "format", "device_fetch"}


@dataclasses.dataclass
class Finding:
    rule: str
    path: str           # repo-relative, forward slashes
    line: int
    func: str           # enclosing function qualname ("<module>" if none)
    message: str
    snippet: str        # stripped source line

    @property
    def key(self) -> str:
        h = hashlib.md5(self.snippet.encode("utf-8")).hexdigest()[:8]
        return f"{self.rule}:{self.path}:{self.func}:{h}"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "Finding":
        return cls(**d)

    def __str__(self) -> str:
        return (f"{self.path}:{self.line}: {self.rule} [{self.func}] "
                f"{self.message}\n    {self.snippet}")


def scan_suppressions(source_lines: Sequence[str]) -> Dict[str, List[str]]:
    """{line: [rules]} from ``# graftlint: disable=...`` comments. A
    TRAILING comment suppresses its own line only; a standalone comment
    line suppresses the line below. (A trailing comment must NOT spill
    onto the next line — a new violation written directly under an
    existing suppression has to trip the --fail-on-new gate.) The ONE
    definition of this contract: the per-file passes (via ModuleLint)
    and the package passes (via callgraph.ModuleFacts) both use it.
    Keys are strings so the shape is identical fresh and after a JSON
    cache round-trip."""
    out: Dict[int, Set[str]] = {}
    for i, text in enumerate(source_lines, start=1):
        if "graftlint:" not in text:
            continue
        frag = text.split("graftlint:", 1)[1]
        if "disable=" not in frag:
            continue
        rules = {r.strip() for r in
                 frag.split("disable=", 1)[1].split("#")[0].split(",")
                 if r.strip()}
        out.setdefault(i, set()).update(rules)
        if text.strip().startswith("#"):      # standalone comment line
            out.setdefault(i + 1, set()).update(rules)
    return {str(k): sorted(v) for k, v in out.items()}


def _dotted_tail(node: ast.AST) -> str:
    """Last attribute/name segment of a call target ('jax.lax.scan' ->
    'scan')."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


def _dotted_name(node: ast.AST) -> str:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _is_np_call(func: ast.AST) -> Optional[str]:
    """'np.sqrt(x)' / 'numpy.sqrt(x)' -> 'sqrt'; None otherwise."""
    if isinstance(func, ast.Attribute) and \
            isinstance(func.value, ast.Name) and \
            func.value.id in ("np", "numpy", "onp"):
        return func.attr
    return None


def _call_wraps_traced(call: ast.Call) -> bool:
    """True when ``call`` is a trace wrapper (jax.jit(f), lax.scan(f, ..),
    functools.partial(jax.jit, ...))."""
    tail = _dotted_tail(call.func)
    if tail in _TRACE_WRAPPERS:
        return True
    if tail == "partial" and call.args:
        return _dotted_tail(call.args[0]) in _TRACE_WRAPPERS
    return False


class _ParentMap(ast.NodeVisitor):
    def __init__(self):
        self.parents: Dict[ast.AST, ast.AST] = {}

    def generic_visit(self, node):
        for child in ast.iter_child_nodes(node):
            self.parents[child] = node
        super().generic_visit(node)


class ModuleLint:
    """All per-module passes over one parsed module."""

    def __init__(self, abspath: str, relpath: str, source: str,
                 tree: Optional[ast.Module] = None):
        self.relpath = relpath
        self.source_lines = source.splitlines()
        self.tree = tree if tree is not None \
            else ast.parse(source, filename=abspath)
        pm = _ParentMap()
        pm.visit(self.tree)
        self.parents = pm.parents
        self._disabled = self._scan_suppressions()
        self._traced_markers = self._scan_traced_markers()

    # ------------------------------------------------------------ comments
    def _scan_suppressions(self) -> Dict[int, Set[str]]:
        """Delegates to the module-level :func:`scan_suppressions` (the
        one definition of the disable-comment contract)."""
        return {int(k): set(v)
                for k, v in scan_suppressions(self.source_lines).items()}

    def _scan_traced_markers(self) -> Set[int]:
        """Lines carrying '# graftlint: traced': a trailing marker tags the
        def on its own line; a standalone comment line tags the def
        below (same spillover rule as suppressions)."""
        out: Set[int] = set()
        for i, text in enumerate(self.source_lines, start=1):
            if "graftlint:" in text and "traced" in \
                    text.split("graftlint:", 1)[1]:
                out.add(i)
                if text.strip().startswith("#"):
                    out.add(i + 1)
        return out

    def _suppressed(self, rule: str, line: int) -> bool:
        return rule in self._disabled.get(line, set())

    def _snippet(self, line: int) -> str:
        if 1 <= line <= len(self.source_lines):
            return self.source_lines[line - 1].strip()
        return ""

    def _emit(self, out: List[Finding], rule: str, node: ast.AST,
              func: str, message: str) -> None:
        self._emit_at(out, rule, getattr(node, "lineno", 0), func, message)

    def _emit_at(self, out: List[Finding], rule: str, line: int,
                 func: str, message: str) -> None:
        if self._suppressed(rule, line):
            return
        out.append(Finding(rule=rule, path=self.relpath, line=line,
                           func=func, message=message,
                           snippet=self._snippet(line)))

    # ------------------------------------------------------- jit contexts
    def _collect_jit_functions(self) -> List[Tuple[ast.AST, str]]:
        """(def/lambda node, qualname) for every jit-context function."""
        wrapped_names: Set[str] = set()
        wrapped_nodes: Set[int] = set()
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call) and _call_wraps_traced(node):
                args = node.args
                tail = _dotted_tail(node.func)
                if tail == "partial":     # partial(jax.jit, f?) rare; skip f0
                    args = node.args[1:]
                for a in args:
                    if isinstance(a, ast.Name):
                        wrapped_names.add(a.id)
                    elif isinstance(a, (ast.Lambda, ast.FunctionDef)):
                        wrapped_nodes.add(id(a))
        # lambdas assigned to a wrapped name:  upd = lambda ...; vmap(upd)
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Lambda):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id in wrapped_names:
                        wrapped_nodes.add(id(node.value))

        roots: List[Tuple[ast.AST, str]] = []
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                traced = node.name in wrapped_names or \
                    id(node) in wrapped_nodes or \
                    node.lineno in self._traced_markers or any(
                        (isinstance(d, ast.Call) and _call_wraps_traced(d))
                        or _dotted_tail(d) in _TRACE_DECORATORS
                        for d in node.decorator_list)
                if traced:
                    roots.append((node, self._qualname(node)))
            elif isinstance(node, ast.Lambda) and id(node) in wrapped_nodes:
                roots.append((node, self._qualname(node)))
        return roots

    def _qualname(self, node: ast.AST) -> str:
        parts: List[str] = []
        cur: Optional[ast.AST] = node
        while cur is not None and not isinstance(cur, ast.Module):
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                parts.append(cur.name)
            elif isinstance(cur, ast.ClassDef):
                parts.append(cur.name)
            elif isinstance(cur, ast.Lambda):
                parts.append("<lambda>")
            cur = self.parents.get(cur)
        return ".".join(reversed(parts)) or "<module>"

    @staticmethod
    def _traced_params(fn: ast.AST) -> Set[str]:
        """Parameter names plausibly bound to traced arrays: positional
        params without defaults, minus self/cls (config flags like
        ``train=False`` / ``mask=None`` carry Python values) and minus
        anything the jit decorator marks static via
        ``static_argnames``/``static_argnums``."""
        a = fn.args
        pos = a.posonlyargs + a.args
        n_default = len(a.defaults)
        names = {p.arg for p in (pos[:-n_default] if n_default else pos)}
        names.discard("self")
        names.discard("cls")
        for dec in getattr(fn, "decorator_list", ()):
            if not (isinstance(dec, ast.Call) and _call_wraps_traced(dec)):
                continue
            for kw in dec.keywords:
                if kw.arg == "static_argnames":
                    for n in ast.walk(kw.value):
                        if isinstance(n, ast.Constant) and \
                                isinstance(n.value, str):
                            names.discard(n.value)
                elif kw.arg == "static_argnums":
                    for n in ast.walk(kw.value):
                        if isinstance(n, ast.Constant) and \
                                isinstance(n.value, int) and \
                                0 <= n.value < len(pos):
                            names.discard(pos[n.value].arg)
        return names

    def _name_is_static_use(self, name: ast.Name) -> bool:
        """x.shape / x.ndim / x.dtype reads are static at trace time."""
        parent = self.parents.get(name)
        return isinstance(parent, ast.Attribute) and \
            parent.attr in _STATIC_ATTRS

    # ------------------------------------------------------------ GL001-4
    def _check_jit_body(self, out: List[Finding], fn: ast.AST,
                        qual: str, enabled: Set[str]) -> None:
        traced = self._traced_params(fn)
        hot = any(f"/{d}/" in f"/{self.relpath}" for d in _HOT_DIRS)
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        for node in [n for b in body for n in ast.walk(b)]:
            if isinstance(node, ast.Call) and "GL001" in enabled:
                f = node.func
                if isinstance(f, ast.Attribute) and f.attr in (
                        "item", "tolist", "block_until_ready"):
                    self._emit(out, "GL001", node, qual,
                               f".{f.attr}() forces a host sync under "
                               "trace — return the array instead")
                np_fn = _is_np_call(f)
                if np_fn in ("asarray", "array", "save"):
                    self._emit(out, "GL001", node, qual,
                               f"np.{np_fn}() materializes a traced value "
                               "on host — use jnp")
                if isinstance(f, ast.Name) and f.id in ("float", "int",
                                                        "bool") and \
                        node.args and isinstance(node.args[0], ast.Name) \
                        and node.args[0].id in traced:
                    self._emit(out, "GL001", node, qual,
                               f"{f.id}({node.args[0].id}) forces a host "
                               "sync on a traced value")
                if _dotted_name(f) in ("jax.device_get", "device_get"):
                    self._emit(out, "GL001", node, qual,
                               "device_get inside traced code is a host "
                               "sync")
            if isinstance(node, ast.Call) and "GL008" in enabled:
                f = node.func
                if _dotted_tail(f) in _SEAM_CALLS:
                    self._emit(out, "GL008", node, qual,
                               f"{_dotted_tail(f)}() stamps a seam of the "
                               "host loop under trace — its interval-"
                               "clock stamps would be trace-time "
                               "constants and its sinks would record "
                               "once per compile; take seams outside "
                               "the jitted region")
                elif isinstance(f, ast.Attribute):
                    recv = _dotted_name(f.value).lower()
                    hinted = any(w in recv for w in _OBS_NAME_HINTS)
                    if f.attr in _OBS_RECORD_METHODS or \
                            (hinted and f.attr in _OBS_HINTED_METHODS):
                        self._emit(out, "GL008", node, qual,
                                   f".{f.attr}() records telemetry under "
                                   "trace — it would run at TRACE time "
                                   "(once per compile, never per step) "
                                   "and host-syncs any traced value; "
                                   "record outside the jitted region")
            if isinstance(node, ast.Call) and "GL015" in enabled:
                f = node.func
                if isinstance(f, ast.Attribute):
                    recv = _dotted_name(f.value).lower()
                    if f.attr in _GL015_RECORD_METHODS and any(
                            w in recv for w in _GL015_NAME_HINTS):
                        self._emit(out, "GL015", node, qual,
                                   f".{f.attr}() on an SLO/flight-"
                                   "recorder/devstats sink under trace "
                                   "— it would record at TRACE time "
                                   "(once per compile, never per "
                                   "event); record outside the jitted "
                                   "region")
            if isinstance(node, ast.Call) and "GL016" in enabled:
                f = node.func
                if isinstance(f, ast.Attribute):
                    recv = _dotted_name(f.value).lower()
                    if f.attr in _GL016_RECORD_METHODS and any(
                            w in recv for w in _GL016_NAME_HINTS):
                        self._emit(out, "GL016", node, qual,
                                   f".{f.attr}() records profiler phase "
                                   "stamps under trace — it would fire "
                                   "at TRACE time (once per compile, "
                                   "never per block) and its interval "
                                   "anchors would be trace-time "
                                   "constants; record on the readback "
                                   "thread, outside the jitted region")
            if isinstance(node, ast.Call) and "GL004" in enabled:
                np_fn = _is_np_call(node.func)
                if np_fn and np_fn not in _NP_SAFE and \
                        not np_fn.startswith("random"):
                    self._emit(out, "GL004", node, qual,
                               f"np.{np_fn}() under trace yields a float64 "
                               "weak scalar (x64) or fails on tracers — "
                               "use jnp or a Python literal")
            if "GL002" in enabled and hot and \
                    isinstance(node, (ast.For, ast.While)):
                probe = node.iter if isinstance(node, ast.For) else node.test
                if any(isinstance(n, ast.Attribute) and n.attr == "shape"
                       for n in ast.walk(probe)):
                    kind = "for" if isinstance(node, ast.For) else "while"
                    self._emit(out, "GL002", node, qual,
                               f"Python {kind} over an array dim unrolls "
                               "the trace (and retraces per shape) — use "
                               "lax.scan/fori_loop")
            if isinstance(node, ast.If) and "GL003" in enabled:
                test = node.test
                if isinstance(test, ast.Compare) and all(
                        isinstance(op, (ast.Is, ast.IsNot))
                        for op in test.ops):
                    continue                      # `x is None` guards
                hits = [n for n in ast.walk(test)
                        if isinstance(n, ast.Name) and n.id in traced
                        and not self._name_is_static_use(n)]
                if hits:
                    self._emit(out, "GL003", node, qual,
                               f"`if` on traced value(s) "
                               f"{sorted({h.id for h in hits})} — "
                               "concretization error or silent retrace; "
                               "use lax.cond/jnp.where")

    # -------------------------------------------------------------- GL005
    def _check_jit_sites(self, out: List[Finding],
                         enabled: Set[str]) -> None:
        if "GL005" not in enabled:
            return
        sites: List[Tuple[ast.Call, bool, bool]] = []
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            tail = _dotted_tail(node.func)
            target = node
            if tail == "partial" and node.args and \
                    _dotted_tail(node.args[0]) in ("jit", "pjit"):
                pass
            elif tail in ("jit", "pjit") and \
                    _dotted_name(node.func) in ("jax.jit", "jit", "pjit",
                                                "jax.experimental.pjit"):
                pass
            else:
                continue
            kws = {k.arg for k in target.keywords}
            sites.append((target,
                          bool(kws & {"donate_argnums", "donate_argnames"}),
                          bool(kws & {"static_argnums", "static_argnames"})))
        if not sites:
            return
        any_donate = any(d for _, d, _ in sites)
        any_static = any(s for _, _, s in sites)
        for node, donate, static in sites:
            missing = []
            if any_donate and not donate:
                missing.append("donate_argnums")
            if any_static and not static:
                missing.append("static_argnums")
            if missing:
                self._emit(out, "GL005", node, self._qualname(node),
                           f"jit site lacks {'/'.join(missing)} while "
                           "sibling sites in this module pass them — "
                           "confirm and annotate")

    # -------------------------------------------------------------- GL006
    def _check_lock_discipline(self, out: List[Finding],
                               enabled: Set[str]) -> None:
        if "GL006" not in enabled:
            return
        for node in ast.walk(self.tree):
            if isinstance(node, ast.ClassDef):
                self._check_class_locks(out, node)

    def _check_class_locks(self, out: List[Finding],
                           cls: ast.ClassDef) -> None:
        methods = {n.name: n for n in cls.body
                   if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
        if not methods:
            return
        # thread entry points: threading.Thread(target=self.X) anywhere in
        # the class, expanded to self._y() calls made from them (fixpoint)
        entries: Set[str] = set()
        lock_attrs: Set[str] = set()
        writes: Dict[str, Dict[str, List[ast.AST]]] = {}   # meth -> attr
        reads: Dict[str, Set[str]] = {}
        calls: Dict[str, Set[str]] = {}
        for mname, m in methods.items():
            writes[mname] = {}
            reads[mname] = set()
            calls[mname] = set()
            for n in ast.walk(m):
                if isinstance(n, ast.Call):
                    if _dotted_tail(n.func) == "Thread":
                        for kw in n.keywords:
                            if kw.arg == "target" and \
                                    isinstance(kw.value, ast.Attribute) and \
                                    isinstance(kw.value.value, ast.Name) \
                                    and kw.value.value.id == "self":
                                entries.add(kw.value.attr)
                    if isinstance(n.func, ast.Attribute) and \
                            isinstance(n.func.value, ast.Name) and \
                            n.func.value.id == "self":
                        calls[mname].add(n.func.attr)
                if isinstance(n, ast.Assign):
                    for t in n.targets:
                        if self._self_attr(t):
                            writes[mname].setdefault(
                                self._self_attr(t), []).append(n)
                    if isinstance(n.value, ast.Call) and \
                            _dotted_tail(n.value.func) in _LOCK_FACTORIES:
                        for t in n.targets:
                            if self._self_attr(t):
                                lock_attrs.add(self._self_attr(t))
                elif isinstance(n, ast.AugAssign) and \
                        self._self_attr(n.target):
                    writes[mname].setdefault(
                        self._self_attr(n.target), []).append(n)
                elif isinstance(n, ast.Attribute) and \
                        isinstance(n.value, ast.Name) and \
                        n.value.id == "self" and \
                        isinstance(n.ctx, ast.Load):
                    reads[mname].add(n.attr)
        if not entries:
            return
        # fixpoint: self-methods called from thread context run in it too
        ctx = set(entries)
        changed = True
        while changed:
            changed = False
            for m in list(ctx):
                for callee in calls.get(m, ()):
                    if callee in methods and callee not in ctx:
                        ctx.add(callee)
                        changed = True
        for mname in sorted(ctx):
            m = methods.get(mname)
            if m is None:
                continue
            for attr, nodes in writes[mname].items():
                if attr in lock_attrs:
                    continue
                shared = any(attr in writes[o] for o in methods
                             if o not in ctx and o != "__init__") or \
                    any(attr in reads[o] for o in methods
                        if o not in ctx)
                for n in nodes:
                    racy_rmw = isinstance(n, ast.AugAssign)
                    if not (shared or racy_rmw):
                        continue
                    if self._under_lock(n, lock_attrs):
                        continue
                    what = "read-modify-write of" if racy_rmw else "write to"
                    self._emit(out, "GL006", n, f"{cls.name}.{mname}",
                               f"unlocked {what} self.{attr} in "
                               "thread-context method — guard with the "
                               "instance lock")

    # -------------------------------------------------------------- GL007
    def _check_host_loop_syncs(self, out: List[Finding],
                               enabled: Set[str],
                               jit_ids: Set[int]) -> None:
        """Flag a blocking readback (np.asarray / .item() / .tolist() /
        device_get) of a name assigned from a call INSIDE the same loop,
        in hot modules — the dispatch-then-immediately-sync pattern that
        serializes XLA dispatch with host RTT once per iteration. The
        receiver may hide behind a subscript: a per-lane
        ``toks[s].item()`` on a just-dispatched verify/decode result is
        B repeated syncs where ONE fused readback of the whole
        ``[B, K+1]`` block was owed (the speculative retire contract).
        The sanctioned crossings are (a) one audited ``device_fetch``
        per decode/verify BLOCK (its result is a host array — indexing
        it is free and exempt) and (b) fetching the PREVIOUS dispatch's
        result after launching the next (double buffering) — both
        restructure the loop rather than silence the rule. Traced
        functions are GL001's domain and are skipped here."""
        if "GL007" not in enabled:
            return
        if not any(f"/{d}/" in f"/{self.relpath}" for d in _HOT_DIRS):
            return
        flagged: Set[int] = set()
        for fn in ast.walk(self.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if id(fn) in jit_ids:
                continue
            qual = self._qualname(fn)
            for loop in ast.walk(fn):
                if not isinstance(loop, (ast.For, ast.While)):
                    continue
                dispatched: Set[str] = set()
                for n in ast.walk(loop):
                    if isinstance(n, ast.Assign) and \
                            isinstance(n.value, ast.Call) and \
                            not self._gl007_safe_call(n.value):
                        for t in n.targets:
                            for el in ([t.elts] if isinstance(
                                    t, (ast.Tuple, ast.List)) else [[t]]):
                                for e in el:
                                    if isinstance(e, ast.Name):
                                        dispatched.add(e.id)
                if not dispatched:
                    continue
                for n in ast.walk(loop):
                    if not isinstance(n, ast.Call) or n.lineno in flagged:
                        continue
                    f = n.func
                    target = None
                    np_fn = _is_np_call(f)
                    if np_fn in ("asarray", "array") and n.args:
                        target = self._gl007_base_name(n.args[0])
                    elif isinstance(f, ast.Attribute) and f.attr in (
                            "item", "tolist", "block_until_ready"):
                        target = self._gl007_base_name(f.value)
                    elif _dotted_name(f) in ("jax.device_get",
                                             "device_get") and n.args:
                        target = self._gl007_base_name(n.args[0])
                    if target in dispatched:
                        flagged.add(n.lineno)
                        self._emit(out, "GL007", n, qual,
                                   f"blocking readback of '{target}' "
                                   "dispatched in the same loop "
                                   "serializes dispatch with host sync — "
                                   "fuse steps into a device block and/or "
                                   "fetch the previous dispatch via "
                                   "ops.transfer.device_fetch")

    # -------------------------------------------------------------- GL015
    @staticmethod
    def _static_metric_name(node: ast.AST) -> Optional[str]:
        """The statically visible (suffix of the) metric name at a
        declaration site: a string literal whole, an f-string's trailing
        literal fragment (the repo's ``f"route_{key}_total"`` idiom), or
        None when the name is fully dynamic (skipped — the gate only
        judges what it can read)."""
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return node.value
        if isinstance(node, ast.JoinedStr) and node.values:
            last = node.values[-1]
            if isinstance(last, ast.Constant) and \
                    isinstance(last.value, str):
                return last.value
        return None

    def _check_metric_naming(self, out: List[Finding],
                             enabled: Set[str]) -> None:
        """Metric-family naming at registry declaration sites: counters
        must end ``_total``, histograms ``_seconds``/``_bytes`` (the
        Prometheus unit conventions every dashboard and the fleet-scrape
        aggregator key on). Applies to ``<registry>.counter(...)`` /
        ``<registry>.histogram(...)`` calls whose receiver names a
        registry; standalone perf-script Histogram instances never reach
        exposition and stay unconstrained."""
        if "GL015" not in enabled:
            return
        for node in ast.walk(self.tree):
            if not (isinstance(node, ast.Call) and
                    isinstance(node.func, ast.Attribute)):
                continue
            suffixes = _GL015_NAME_SUFFIXES.get(node.func.attr)
            if suffixes is None:
                continue
            recv = _dotted_name(node.func.value).lower()
            last = recv.rsplit(".", 1)[-1]
            if not ("registry" in last or last == "reg" or
                    last.endswith("_reg")):
                continue
            name_node = node.args[0] if node.args else None
            if name_node is None:
                for kw in node.keywords:
                    if kw.arg == "name":
                        name_node = kw.value
            name = None if name_node is None \
                else self._static_metric_name(name_node)
            if name is None or name.endswith(tuple(suffixes)):
                continue
            want = "/".join(suffixes)
            self._emit(out, "GL015", node, self._qualname(node),
                       f"{node.func.attr} family {name!r} must end "
                       f"{want} (Prometheus unit conventions; the "
                       "fleet-scrape aggregator sums by suffix)")

    @staticmethod
    def _gl007_base_name(node: ast.AST) -> Optional[str]:
        """The base Name of a readback receiver: a bare name or a
        (possibly nested) subscript of one — ``toks`` in
        ``toks[s].item()``. Per-lane element syncs hide the device
        handle behind the subscript; the base name is what the loop's
        dispatch assigned."""
        while isinstance(node, ast.Subscript):
            node = node.value
        return node.id if isinstance(node, ast.Name) else None

    @staticmethod
    def _gl007_safe_call(call: ast.Call) -> bool:
        """Callees whose results are host values, not dispatched device
        work (builtins, np.*/math.* helpers, the audited fetch seam)."""
        if _is_np_call(call.func) is not None:
            return True
        tail = _dotted_tail(call.func)
        if tail in _GL007_SAFE_CALLEES:
            return True
        dn = _dotted_name(call.func)
        return dn.startswith("math.") or dn.startswith("time.")

    @staticmethod
    def _self_attr(node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Attribute) and \
                isinstance(node.value, ast.Name) and node.value.id == "self":
            return node.attr
        return None

    def _under_lock(self, node: ast.AST, lock_attrs: Set[str]) -> bool:
        """Is ``node`` inside a ``with self.<lock>`` block (any lock-like
        attr, or any attr containing 'lock' when the class builds its
        locks elsewhere)?"""
        cur = self.parents.get(node)
        while cur is not None and not isinstance(
                cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Module)):
            if isinstance(cur, ast.With):
                for item in cur.items:
                    expr = item.context_expr
                    for n in ast.walk(expr):
                        attr = self._self_attr(n)
                        if attr and (attr in lock_attrs or
                                     "lock" in attr.lower()):
                            return True
            cur = self.parents.get(cur)
        return False

    # ---------------------------------------------------------------- run
    def run(self, enabled: Set[str]) -> List[Finding]:
        out: List[Finding] = []
        jit_ids: Set[int] = set()
        for fn, qual in self._collect_jit_functions():
            self._check_jit_body(out, fn, qual, enabled)
            jit_ids.add(id(fn))
            for n in ast.walk(fn):     # nested defs trace with their root
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    jit_ids.add(id(n))
        self._check_jit_sites(out, enabled)
        self._check_lock_discipline(out, enabled)
        self._check_host_loop_syncs(out, enabled, jit_ids)
        self._check_metric_naming(out, enabled)
        if enabled & {"GL013", "GL014", "GL016"}:
            from .sharding import run_sharding_pass
            run_sharding_pass(
                self.tree, sorted(enabled & {"GL013", "GL014", "GL016"}),
                lambda rule, line, func, message:
                self._emit_at(out, rule, line, func, message))
        return out


class LintCache:
    """Per-file result cache: mtime+size fast path, content-hash slow
    path, keyed by repo-relative path and invalidated by LINT_VERSION.
    Stores the per-file findings for ALL per-file rules (rule filters
    apply at collection time, so one cache serves every ``--select``)
    plus the module's callgraph facts for the package pass."""

    def __init__(self, path: str):
        self.path = path
        self.hits = 0
        self.misses = 0
        self._dirty = False
        self._data: dict = {}
        try:
            with open(path, "r", encoding="utf-8") as f:
                data = json.load(f)
            if data.get("version") == LINT_VERSION:
                self._data = data.get("files", {})
        except (OSError, ValueError):
            self._data = {}

    @staticmethod
    def _digest(src: str) -> str:
        return hashlib.sha1(src.encode("utf-8")).hexdigest()

    def get(self, rel: str, mtime: float, size: int,
            src: str) -> Optional[dict]:
        entry = self._data.get(rel)
        if entry is None:
            self.misses += 1
            return None
        if not (entry["mtime"] == mtime and entry["size"] == size):
            if entry["sha1"] != self._digest(src):
                self.misses += 1
                return None
            # content unchanged, file merely touched: refresh the
            # stamps so the NEXT run takes the mtime fast path again
            entry["mtime"], entry["size"] = mtime, size
            self._dirty = True
        self.hits += 1
        return entry

    def put(self, rel: str, mtime: float, size: int, src: str,
            findings: Sequence["Finding"], facts) -> None:
        self._data[rel] = {
            "mtime": mtime, "size": size, "sha1": self._digest(src),
            "findings": [f.to_dict() for f in findings],
            "facts": facts.to_dict(),
        }
        self._dirty = True

    def save(self) -> None:
        if not self._dirty:
            return
        tmp = self.path + ".tmp"
        try:
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump({"version": LINT_VERSION, "files": self._data},
                          f)
            os.replace(tmp, self.path)
        except OSError:
            pass                        # cache is best-effort


class LintRunner:
    """Walk .py files under roots, run the per-module passes on each,
    then the whole-package concurrency pass over the aggregated call
    graph, and return every finding."""

    def __init__(self, repo_root: str, rules: Optional[Iterable[str]] = None,
                 cache: Optional[LintCache] = None,
                 force_facts: bool = False):
        self.repo_root = os.path.abspath(repo_root)
        self.enabled = set(rules) if rules else set(RULES)
        self.errors: List[str] = []   # unparseable files (reported, not fatal)
        self.cache = cache
        # collect callgraph facts even when no package rule is enabled
        # (collect_package_facts' contract)
        self.force_facts = bool(force_facts)
        self._facts: Dict[str, object] = {}
        self._sources: Dict[str, List[str]] = {}

    def lint_file(self, path: str) -> List[Finding]:
        from .callgraph import ModuleFacts, extract_module_facts
        rel = os.path.relpath(os.path.abspath(path),
                              self.repo_root).replace(os.sep, "/")
        try:
            st = os.stat(path)
            with open(path, "r", encoding="utf-8") as f:
                src = f.read()
        except (UnicodeDecodeError, OSError) as e:
            self.errors.append(f"{rel}: {e}")
            return []
        entry = None
        if self.cache is not None:
            entry = self.cache.get(rel, st.st_mtime, st.st_size, src)
        if entry is not None:
            found = [Finding.from_dict(d) for d in entry["findings"]]
            facts = ModuleFacts.from_dict(entry["facts"])
        else:
            try:
                tree = ast.parse(src, filename=path)
                module = ModuleLint(path, rel, src, tree=tree)
            except SyntaxError as e:
                self.errors.append(f"{rel}: {e}")
                return []
            # with a cache, run EVERY per-file pass so one entry serves
            # any later --select; without one, run only what was asked
            # (and skip facts extraction unless a package rule needs it)
            if self.cache is not None:
                found = module.run(set(PER_FILE_RULES))
                facts = extract_module_facts(rel, tree, src.splitlines())
                self.cache.put(rel, st.st_mtime, st.st_size, src,
                               found, facts)
            else:
                found = module.run(self.enabled & PER_FILE_RULES)
                facts = None
                if self.force_facts or self.enabled & PACKAGE_RULES:
                    facts = extract_module_facts(rel, tree,
                                                 src.splitlines())
        if facts is not None:
            self._facts[rel] = facts
        self._sources[rel] = src.splitlines()
        return [f for f in found if f.rule in self.enabled]

    def _package_pass(self, findings: List[Finding]) -> None:
        pkg_rules = self.enabled & PACKAGE_RULES
        if not pkg_rules or not self._facts:
            return
        from .concurrency import ConcurrencyAnalysis
        analysis = ConcurrencyAnalysis(self._facts)

        def emit(rule: str, module: str, line: int, func: str,
                 message: str) -> None:
            mf = self._facts[module]
            if mf.suppressed_at(rule, line):
                return
            lines = self._sources.get(module, [])
            snippet = lines[line - 1].strip() \
                if 1 <= line <= len(lines) else ""
            findings.append(Finding(rule=rule, path=module, line=line,
                                    func=func, message=message,
                                    snippet=snippet))

        analysis.findings(pkg_rules, emit)

    def lint(self, paths: Sequence[str]) -> List[Finding]:
        findings: List[Finding] = []
        self._facts.clear()
        self._sources.clear()
        for p in paths:
            if os.path.isdir(p):
                for dirpath, dirnames, filenames in os.walk(p):
                    dirnames[:] = [d for d in dirnames
                                   if d not in ("__pycache__", ".git")]
                    for fn in sorted(filenames):
                        if fn.endswith(".py"):
                            findings.extend(
                                self.lint_file(os.path.join(dirpath, fn)))
            elif os.path.isfile(p) and p.endswith(".py"):
                findings.extend(self.lint_file(p))
            else:
                # a stale/misspelled path must not silently shrink the
                # gate's coverage — surface it like a parse error
                self.errors.append(f"{p}: not a directory or .py file")
        self._package_pass(findings)
        if self.cache is not None:
            self.cache.save()
        # de-duplicate identical (rule, site) findings: an edge can be
        # witnessed through several call paths; the gate needs one
        seen: Set[Tuple[str, str, int, str]] = set()
        unique: List[Finding] = []
        for f in findings:
            k = (f.rule, f.path, f.line, f.message)
            if k not in seen:
                seen.add(k)
                unique.append(f)
        unique.sort(key=lambda f: (f.path, f.line, f.rule))
        return unique


def lint_paths(paths: Sequence[str], repo_root: str,
               rules: Optional[Iterable[str]] = None) -> List[Finding]:
    return LintRunner(repo_root, rules).lint(paths)


def collect_package_facts(paths: Sequence[str], repo_root: str,
                          cache: Optional[LintCache] = None) -> Dict:
    """Extract callgraph facts for every module under ``paths`` without
    running the package rules — the static side of
    ``lock_audit.LockAudit.cross_check`` and of the chaos soak's
    ``--lock-audit`` gate."""
    runner = LintRunner(repo_root, rules=["GL001"], cache=cache,
                        force_facts=True)
    runner.lint(paths)
    return dict(runner._facts)


# ------------------------------------------------------------- baseline
def baseline_counts(findings: Sequence[Finding]) -> Dict[str, int]:
    return dict(Counter(f.key for f in findings))


def write_baseline(path: str, findings: Sequence[Finding]) -> dict:
    data = {
        "version": 1,
        "rules": sorted({f.rule for f in findings}),
        "total": len(findings),
        "suppressed": baseline_counts(findings),
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    return data


def load_baseline(path: str) -> Dict[str, int]:
    if not os.path.exists(path):
        return {}
    with open(path, "r", encoding="utf-8") as f:
        data = json.load(f)
    return dict(data.get("suppressed", {}))


def new_findings(findings: Sequence[Finding],
                 baseline: Dict[str, int]) -> List[Finding]:
    """Findings beyond the baselined count for their key (line-number
    drift does not churn keys; adding a second identical violation in the
    same function DOES trip the gate)."""
    seen: Counter = Counter()
    out: List[Finding] = []
    for f in findings:
        seen[f.key] += 1
        if seen[f.key] > baseline.get(f.key, 0):
            out.append(f)
    return out
