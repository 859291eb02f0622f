"""graftlint — trace-discipline static analysis + runtime compile auditing.

The serving path (models/generation.py decode loop, continuous batching)
and every jitted train step live or die on trace discipline: one stray
host sync, per-shape retrace, or silent dtype/rank promotion erases the
measured wins, and nothing catches it at review time. This subsystem
machine-checks those invariants:

- :mod:`.lint` — AST passes over the package flagging jit-hostility
  (host syncs inside traced code, Python loops over array dims in hot
  modules, tracer-dependent branches, numpy promotion hazards, jit
  call-site consistency, unlocked shared writes in thread targets), with
  a checked-in ``baseline.json`` so CI fails only on NEW violations
  (``python scripts/lint.py --fail-on-new``).
- :mod:`.callgraph` + :mod:`.concurrency` — the v2 interprocedural
  layer: whole-package call graph + lock-acquisition graph driving
  GL009 lock-order inversions, GL010 blocking-under-lock, GL011
  condition-wait discipline, GL012 untracked threads.
- :mod:`.sharding` — GL013 PartitionSpec/mesh-axis consistency and
  GL014 host-sync/telemetry inside shard_map/pjit regions: the static
  gate ROADMAP item 1 (mesh-sharded generation) inherits.
- :mod:`.lock_audit` — :class:`LockAudit`, the runtime counterpart of
  GL009/GL010: instrumented locks record ACTUAL acquisition orders
  during tests/chaos soaks and cross-check them against the static
  graph, so each layer catches the other's false negatives.
- :mod:`.compile_audit` — a context manager that counts XLA compilations
  per jitted function (via the ``jax_log_compiles`` lowering hook),
  detects retrace storms, and asserts expected-compile budgets in the
  benches (``BENCH_MODE=generate --audit-compiles``); plus
  :class:`TransferAudit`, its sibling for host syncs — per-tag
  device→host readback counts through the ``ops.transfer.device_fetch``
  seam, with a ≤1-readback-per-decode-block budget check; and
  :class:`AttentionPlanAudit` — the attention calls traced in a region
  by the plan each took (packed 128-lane tile, folded, short,
  materialized).
"""

from .compile_audit import (AttentionPlanAudit, CompileAudit,
                            CompileBudgetError, TransferAudit,
                            TransferBudgetError)
from .lint import (Finding, LintCache, LintRunner, RULES,
                   collect_package_facts, load_baseline, lint_paths,
                   new_findings, write_baseline)
from .lock_audit import LockAudit, LockOrderError

__all__ = [
    "AttentionPlanAudit", "CompileAudit", "CompileBudgetError",
    "TransferAudit",
    "TransferBudgetError", "Finding", "LintCache", "LintRunner", "RULES",
    "LockAudit", "LockOrderError", "collect_package_facts",
    "lint_paths", "load_baseline", "new_findings", "write_baseline",
]
