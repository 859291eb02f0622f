"""Runtime compile auditor: count XLA compilations per jitted function.

A fixed-shape decode loop must compile ONCE and then run; a retrace per
step (shape-unstable inputs, a Python scalar riding where a device array
should, a blown jit cache) silently turns the 16.7x KV-cache decode win
into compile churn. jax already knows every lowering it performs — with
``jax_log_compiles`` on, ``jax._src.interpreters.pxla`` logs one
"Compiling jit(<name>) with global shapes and types (<avals>). Argument
mapping: ..." record per cache miss, carrying the wrapped function's name
and its full shape/dtype signature. :class:`CompileAudit` attaches a
logging handler to that seam for the duration of a ``with`` block and
aggregates:

- ``counts[fn]`` — compiles per function name;
- ``signatures[fn][sig]`` — compiles per (function, shape signature):
  a signature compiled TWICE means the cache was blown (retrace storm),
  not a new shape;
- ``retraces()`` / ``duplicate_signature_compiles`` — storm detectors;
- ``check(budget=..., total=...)`` — assert an expected-compile budget
  (raises :class:`CompileBudgetError` with the offending functions).

Works on any backend and costs one logging call per COMPILE (not per
step), so wrapping a whole bench run is free. The record is logged at
LOWERING time, before the persistent compilation cache is consulted, so
a program served from that cache still counts.

The seam is jax-private, so the audit proves it can hear before it is
trusted: ``__enter__`` compiles a uniquely named probe program and raises
:class:`CompileAuditDeafError` if the handler did not see it — an audit
that cannot observe compiles must never read as "``{}`` new compiles".

Attribution through the pjit seams (r12): a mesh-sharded decoder
compiles the SAME function names with the SAME dynamic shape signatures
as its single-device sibling — the compile log carries no sharding — so
two meshes in one process would read as one function re-lowering an
already-seen signature (a false blown-cache storm). The generation
impls therefore carry a per-mesh ``__m<data>x<tp>`` name suffix
(``decode_block4_impl__m2x1``), making every (function, mesh) pair its
own audit row; unsharded decoders keep the bare names and existing
budgets. The monitoring-events API
(``jax.monitoring``) records the same compiles without names and its
listeners cannot be unregistered individually, so the logging seam is
the instrumentation of choice; our own jit wrappers need no changes.

Usage::

    with CompileAudit() as audit:
        run_bench()
    audit.check(budget={"decode_step_impl": 1}, total=10)
    print(audit.report())
"""

from __future__ import annotations

import itertools
import logging
import re
import threading
from collections import Counter, defaultdict
from typing import Dict, Iterable, Optional

_COMPILE_RE = re.compile(
    r"Compiling (\S+) with global shapes and types (.*?)\. "
    r"Argument mapping: ", re.DOTALL)
_PXLA_LOGGER = "jax._src.interpreters.pxla"
#: name prefix of the self-test program every ``CompileAudit.__enter__``
#: compiles; never counted, whichever (possibly enclosing) audit hears it
_PROBE_PREFIX = "compile_audit_probe_"
_PROBE_SEQ = itertools.count()
#: loggers that turn chatty at WARNING while jax_log_compiles is on; muted
#: (propagate=False + NullHandler) for the audit scope so a bench run's
#: stderr stays clean
_MUTE_LOGGERS = ("jax._src.dispatch", "jax._src.compiler")


class CompileBudgetError(AssertionError):
    """An audited region compiled more than its budget allows."""


class CompileAuditDeafError(RuntimeError):
    """The audit's logging seam did not report its own probe compile, so
    nothing it counted (or failed to count) can be trusted."""


class TransferBudgetError(AssertionError):
    """An audited region read back from device more than its budget
    allows (e.g. more than one host sync per decode block)."""


class TransferAudit:
    """Counts device→host readbacks within a ``with`` block.

    The compile auditor's sibling: where CompileAudit catches the
    retrace-per-step failure mode, this catches the SYNC-per-step one —
    a decode loop that blocks on ``np.asarray`` after every dispatched
    step serializes host time behind device time and caps tok/s at
    1/RTT regardless of how fast the step program is. The serving path
    routes every deliberate readback through the
    :func:`..ops.transfer.device_fetch` seam with a tag
    (``engine.decode``, ``engine.prefill``, ``generate.decode``, ...);
    this audit snapshots the per-tag counters on entry and reports the
    delta, so concurrent engines/audits never clobber each other.

    ``check_per_block(tag, blocks)`` asserts the pipelined-decode
    invariant: at most ``max_per_block`` readbacks per decode block
    (the engine's ``decode_blocks`` stat / one ``decode_block`` call).

    Usage::

        with TransferAudit() as transfers:
            engine.run_until_drained()
        transfers.check_per_block("engine.decode",
                                  engine.stats()["decode_blocks"])
    """

    def __init__(self):
        self._start: Dict[str, int] = {}
        self._end: Optional[Dict[str, int]] = None

    def __enter__(self) -> "TransferAudit":
        from ..ops import transfer
        self._transfer = transfer
        self._start = transfer.fetch_counts()
        self._end = None
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self._end = self._transfer.fetch_counts()

    def fetches(self, tag: Optional[str] = None) -> int:
        """Readbacks since entry (one tag, or all tags summed). Live
        inside the block; frozen at exit."""
        now = self._end if self._end is not None \
            else self._transfer.fetch_counts()
        delta = {t: c - self._start.get(t, 0) for t, c in now.items()}
        if tag is not None:
            return delta.get(tag, 0)
        return sum(delta.values())

    def report(self) -> Dict[str, int]:
        """Per-tag readback deltas (zero-delta tags omitted)."""
        now = self._end if self._end is not None \
            else self._transfer.fetch_counts()
        return {t: c - self._start.get(t, 0) for t, c in sorted(now.items())
                if c - self._start.get(t, 0) > 0}

    def shards(self, tag: str) -> int:
        """Device shards the most recent fetch under ``tag`` gathered —
        attribution through the pjit seam: ONE logical readback off a
        (data, tp) serving mesh reads data×tp shards, and the audit can
        now say so instead of losing the mesh dimension entirely."""
        return self._transfer.fetch_shards(tag).get(tag, 1)

    def check_per_block(self, tag: str, blocks: int,
                        max_per_block: float = 1.0) -> None:
        """Assert ≤ ``max_per_block`` readbacks under ``tag`` per decode
        block; raises :class:`TransferBudgetError` otherwise. ``blocks``
        of 0 demands zero readbacks."""
        got = self.fetches(tag)
        if got > max_per_block * blocks:
            raise TransferBudgetError(
                f"{tag}: {got} host readbacks over {blocks} decode "
                f"block(s) exceeds {max_per_block}/block")


class AttentionPlanAudit:
    """Counts the attention calls TRACED within a ``with`` block by the
    plan each took (``nn.helpers.note_attention_plan``: ``packed`` with its
    ``g`` and tile sizes, ``folded``, ``short``, ``materialized``), so a
    test or a reader of a run can say how many of a program's attention
    calls engaged the packed 128-lane tile. Tracing only: a program served
    from jit's cache adds nothing.

    Usage::

        with AttentionPlanAudit() as plans:
            step.lower(*args)
        assert plans.calls("packed") == plans.calls() == 24
    """

    def __enter__(self) -> "AttentionPlanAudit":
        from ..nn.helpers import attention_plan_counts
        self._counts = attention_plan_counts
        self._start = attention_plan_counts()
        self._end: Optional[Dict[str, int]] = None
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self._end = self._counts()

    def plans(self) -> Dict[str, int]:
        """Calls since entry by plan (``"packed,g=2,kb=1024,qb=1024"``);
        live inside the block, frozen at exit."""
        now = self._end if self._end is not None else self._counts()
        return {k: n - self._start.get(k, 0) for k, n in sorted(now.items())
                if n != self._start.get(k, 0)}

    def calls(self, kind: Optional[str] = None) -> int:
        """Calls since entry: all, or those whose plan is ``kind``."""
        return sum(n for k, n in self.plans().items()
                   if kind is None or k.split(",")[0] == kind)


class _CompileLogHandler(logging.Handler):
    def __init__(self, audit: "CompileAudit"):
        super().__init__(level=logging.DEBUG)
        self._audit = audit

    def emit(self, record: logging.LogRecord) -> None:
        try:
            m = _COMPILE_RE.match(record.getMessage())
        except Exception:       # noqa: BLE001 — a logging handler must not throw
            return
        if m:
            name = m.group(1)
            if name.startswith("jit(") and name.endswith(")"):
                name = name[4:-1]     # module name jit(f) -> audit row f
            self._audit._record(name, m.group(2))


class CompileAudit:
    """Context manager counting per-function XLA compilations.

    ``budget``: optional {function_name: max_compiles} checked on clean
    exit (plus ``total_budget`` for the sum); violations raise
    :class:`CompileBudgetError`. Pass ``ignore`` to exclude helper
    programs (e.g. 'convert_element_type', '_threefry_split' — jax's own
    tiny utility compiles) from totals and budget checks; the default
    list covers the utility programs any real run compiles on the side,
    keeping the audit about OUR entry points. ``ignore_internal=True``
    additionally drops every name starting with '_' — do NOT use it on
    this package, whose own seams are named ``_step``/``_out``/...)."""

    #: jax-internal utility programs compiled on the side of any real run.
    #: The jax.random samplers (_normal, _uniform, ...) matter beyond
    #: noise: their SHAPE rides as a static argument that the compile log's
    #: dynamic signature does not show, so per-shape init-time compiles
    #: would read as duplicate-signature retraces (a false storm signal).
    DEFAULT_IGNORE = ("convert_element_type", "broadcast_in_dim", "copy",
                      "reshape", "concatenate", "squeeze", "transpose",
                      "iota", "eq", "fn", "<lambda>", "_threefry_split",
                      "_threefry_seed", "threefry_2x32", "_unstack",
                      "_argmax", "_where", "_normal", "_normal_real",
                      "_uniform", "_truncated_normal", "_categorical",
                      "_bernoulli", "_gumbel", "_threefry_fold_in",
                      "fold_in",
                      # jax's host-gather helper for fetching a SHARDED
                      # array (np.asarray over a mesh) — a utility
                      # program like the rest; the deliberate readback
                      # itself is what TransferAudit counts
                      "_multi_slice")

    def __init__(self, budget: Optional[Dict[str, int]] = None,
                 total_budget: Optional[int] = None,
                 ignore: Optional[Iterable[str]] = None,
                 ignore_internal: bool = False):
        self.budget = dict(budget or {})
        self.total_budget = total_budget
        self.ignore = set(self.DEFAULT_IGNORE if ignore is None else ignore)
        self.ignore_internal = ignore_internal
        self.counts: Counter = Counter()
        self.signatures: Dict[str, Counter] = defaultdict(Counter)
        self._mutex = threading.Lock()
        self._handler: Optional[_CompileLogHandler] = None
        self._prev_log_compiles = None
        self._prev_propagate = None
        self._prev_level = None
        self._muted = []      # (logger, null_handler, prev_propagate)

    # ------------------------------------------------------------ capture
    def _record(self, name: str, signature: str) -> None:
        with self._mutex:
            self.counts[name] += 1
            self.signatures[name][signature] += 1

    def _ignored(self, name: str) -> bool:
        return name in self.ignore or name.startswith(_PROBE_PREFIX) or \
            (self.ignore_internal and name.startswith("_"))

    def _probe(self) -> None:
        """Compile a program no one has compiled before and demand the
        handler heard it: a renamed logger, a reworded record or a
        silenced logging tree must fail HERE, not read as zero compiles
        for the whole audited region."""
        import jax
        import numpy as np

        def probe(x):
            return x
        probe.__name__ = name = f"{_PROBE_PREFIX}{next(_PROBE_SEQ)}"
        jax.jit(probe)(np.float32(0))   # graftlint: disable=GL005
        with self._mutex:
            heard = self.counts.pop(name, 0)
            self.signatures.pop(name, None)
        if not heard:
            raise CompileAuditDeafError(
                f"CompileAudit cannot observe jax's compile log: the probe "
                f"program {name!r} compiled but no 'Compiling ... with "
                f"global shapes and types' record reached the "
                f"{_PXLA_LOGGER!r} handler (logger disabled, or the "
                "record format changed with this jax version)")

    def __enter__(self) -> "CompileAudit":
        import jax
        logger = logging.getLogger(_PXLA_LOGGER)
        self._handler = _CompileLogHandler(self)
        self._prev_propagate = logger.propagate
        self._prev_level = logger.level
        logger.addHandler(self._handler)
        # keep the per-compile WARNING records out of the user's stderr
        # (logging.lastResort prints them when no root handler exists)
        logger.propagate = False
        logger.setLevel(logging.DEBUG)
        for lname in _MUTE_LOGGERS:
            lg = logging.getLogger(lname)
            nh = logging.NullHandler()
            lg.addHandler(nh)      # NullHandler keeps lastResort quiet
            self._muted.append((lg, nh, lg.propagate))
            lg.propagate = False
        self._prev_log_compiles = bool(getattr(jax.config,
                                               "jax_log_compiles", False))
        jax.config.update("jax_log_compiles", True)
        try:
            self._probe()
        except BaseException as e:
            self.__exit__(type(e), e, e.__traceback__)
            raise
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        import jax
        logger = logging.getLogger(_PXLA_LOGGER)
        if self._handler is not None:
            logger.removeHandler(self._handler)
            self._handler = None
        if self._prev_propagate is not None:
            logger.propagate = self._prev_propagate
        if self._prev_level is not None:
            logger.setLevel(self._prev_level)
        for lg, nh, prev in self._muted:
            lg.removeHandler(nh)
            lg.propagate = prev
        self._muted = []
        jax.config.update("jax_log_compiles",
                          bool(self._prev_log_compiles))
        if exc_type is None and (self.budget or
                                 self.total_budget is not None):
            self.check(self.budget, self.total_budget)

    # ------------------------------------------------------------ results
    @property
    def total_compiles(self) -> int:
        return sum(c for n, c in self.counts.items()
                   if not self._ignored(n))

    def compiles(self, name: str) -> int:
        return self.counts.get(name, 0)

    def retraces(self) -> Dict[str, dict]:
        """Functions compiled more than once: how many compiles, how many
        DISTINCT signatures, and how many compiles re-lowered an
        already-seen signature (cache blown — the storm signal)."""
        out = {}
        for name, c in self.counts.items():
            if c <= 1 or self._ignored(name):
                continue
            sigs = self.signatures[name]
            out[name] = {
                "compiles": c,
                "distinct_signatures": len(sigs),
                "duplicate_signature_compiles": sum(
                    k - 1 for k in sigs.values() if k > 1),
            }
        return out

    @property
    def duplicate_signature_compiles(self) -> int:
        """Total compiles that re-lowered an already-seen (function,
        signature) — steady state demands this be ZERO."""
        return sum(r["duplicate_signature_compiles"]
                   for r in self.retraces().values())

    def snapshot(self) -> Counter:
        with self._mutex:
            return Counter(self.counts)

    def delta(self, since: Counter) -> Dict[str, int]:
        """Per-function compiles since ``snapshot()`` (ignored names
        excluded) — zero in any steady-state region."""
        now = self.snapshot()
        return {n: now[n] - since.get(n, 0) for n in now
                if now[n] > since.get(n, 0) and not self._ignored(n)}

    def report(self) -> dict:
        return {
            "total_compiles": self.total_compiles,
            "per_function": {n: c for n, c in sorted(self.counts.items())
                             if not self._ignored(n)},
            "retraced": self.retraces(),
            "duplicate_signature_compiles":
                self.duplicate_signature_compiles,
        }

    def check(self, budget: Optional[Dict[str, int]] = None,
              total: Optional[int] = None,
              forbid_duplicate_signatures: bool = False) -> None:
        """Raise CompileBudgetError on any budget violation."""
        problems = []
        for name, cap in (budget or {}).items():
            got = self.counts.get(name, 0)
            if got > cap:
                problems.append(f"{name}: {got} compiles > budget {cap} "
                                f"({len(self.signatures[name])} distinct "
                                "signatures)")
        if total is not None and self.total_compiles > total:
            problems.append(f"total: {self.total_compiles} compiles > "
                            f"budget {total}")
        if forbid_duplicate_signatures and \
                self.duplicate_signature_compiles:
            problems.append(
                "duplicate-signature compiles (cache blown): "
                f"{self.retraces()}")
        if problems:
            raise CompileBudgetError("; ".join(problems))
