"""graftlint analyzer tests: per-rule fixture snippets (positive AND
negative), inline suppression, the traced-marker escape hatch, the
baseline round-trip, the v2 interprocedural concurrency rules
(GL009-GL012) with a deliberate deadlock fixture caught statically AND
reproduced dynamically by LockAudit, the sharding-discipline rules
(GL013-GL014), the per-file result cache, and the runtime compile
auditor (retrace detection on a deliberately shape-unstable function;
zero-retrace invariants on the real serving engine)."""

import json
import textwrap
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.analysis import (CompileAudit, CompileBudgetError,
                                         LockAudit, LockOrderError,
                                         lint_paths, load_baseline,
                                         new_findings, write_baseline)


def _lint_src(tmp_path, src, rel="deeplearning4j_tpu/kernels/mod.py",
              rules=None):
    """Write ``src`` at ``rel`` under tmp_path and lint it; rel defaults
    to a hot-module path so every rule is in scope."""
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    return lint_paths([str(p)], repo_root=str(tmp_path), rules=rules)


def _rules(findings):
    return sorted({f.rule for f in findings})


class TestHostSyncRule:
    def test_item_inside_jit_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax
            @jax.jit
            def f(x):
                return x.item()
        """)
        assert _rules(out) == ["GL001"]
        assert out[0].func == "f"

    def test_item_outside_jit_is_fine(self, tmp_path):
        out = _lint_src(tmp_path, """
            def f(x):
                return x.item()
        """)
        assert out == []

    def test_float_of_traced_param_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax
            def step(x):
                return float(x)
            g = jax.jit(step)
        """)
        assert _rules(out) == ["GL001"]

    def test_float_of_static_param_is_fine(self, tmp_path):
        out = _lint_src(tmp_path, """
            import functools, jax
            @functools.partial(jax.jit, static_argnames=("n",))
            def f(x, n):
                return x * int(n)
        """)
        assert out == []

    def test_np_asarray_inside_scan_body_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax
            import numpy as np
            def body(carry, t):
                return carry, np.asarray(t)
            def run(xs):
                return jax.lax.scan(body, 0.0, xs)
        """)
        assert "GL001" in _rules(out)


class TestLoopAndBranchRules:
    def test_shape_loop_in_hot_module_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax
            @jax.jit
            def f(x):
                acc = 0.0
                for i in range(x.shape[0]):
                    acc = acc + x[i]
                return acc
        """)
        assert "GL002" in _rules(out)

    def test_shape_loop_outside_hot_module_is_fine(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax
            @jax.jit
            def f(x):
                acc = 0.0
                for i in range(x.shape[0]):
                    acc = acc + x[i]
                return acc
        """, rel="deeplearning4j_tpu/ui/mod.py", rules=["GL002"])
        assert out == []

    def test_branch_on_traced_value_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax
            @jax.jit
            def f(x):
                if x > 0:
                    return x
                return -x
        """)
        assert _rules(out) == ["GL003"]

    def test_is_none_and_shape_branches_are_fine(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax
            @jax.jit
            def f(x, mask=None):
                if mask is not None:
                    x = x * mask
                if x.ndim == 3:
                    x = x[0]
                return x
        """)
        assert out == []


class TestPromotionAndJitSiteRules:
    def test_np_math_in_jit_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax
            import numpy as np
            @jax.jit
            def f(x):
                return x * np.sqrt(4)
        """, rules=["GL004"])
        assert _rules(out) == ["GL004"]

    def test_jnp_math_is_fine(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax
            import jax.numpy as jnp
            @jax.jit
            def f(x):
                return x * jnp.sqrt(4.0)
        """, rules=["GL004"])
        assert out == []

    def test_inconsistent_donation_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax
            def a(x):
                return x
            def b(x):
                return x
            fa = jax.jit(a, donate_argnums=(0,))
            fb = jax.jit(b)
        """, rules=["GL005"])
        assert len(out) == 1 and out[0].rule == "GL005"

    def test_consistent_donation_is_fine(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax
            def a(x):
                return x
            def b(x):
                return x
            fa = jax.jit(a, donate_argnums=(0,))
            fb = jax.jit(b, donate_argnums=(0,))
        """, rules=["GL005"])
        assert out == []


class TestLockDisciplineRule:
    def test_unlocked_shared_write_in_thread_target_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import threading
            class Worker:
                def __init__(self):
                    self.count = 0
                    self._lock = threading.Lock()
                def start(self):
                    threading.Thread(target=self._run).start()
                def _run(self):
                    self.count += 1
                def snapshot(self):
                    return self.count
        """, rel="deeplearning4j_tpu/streaming/mod.py", rules=["GL006"])
        assert len(out) == 1 and out[0].rule == "GL006"
        assert "count" in out[0].message

    def test_locked_write_is_fine(self, tmp_path):
        out = _lint_src(tmp_path, """
            import threading
            class Worker:
                def __init__(self):
                    self.count = 0
                    self._lock = threading.Lock()
                def start(self):
                    threading.Thread(target=self._run).start()
                def _run(self):
                    with self._lock:
                        self.count += 1
                def snapshot(self):
                    return self.count
        """, rel="deeplearning4j_tpu/streaming/mod.py", rules=["GL006"])
        assert out == []

    def test_transitive_thread_context_is_tracked(self, tmp_path):
        out = _lint_src(tmp_path, """
            import threading
            class Worker:
                def __init__(self):
                    self.done = 0
                    self._lock = threading.Lock()
                def start(self):
                    threading.Thread(target=self._run).start()
                def _run(self):
                    self._step()
                def _step(self):
                    self.done += 1
        """, rel="deeplearning4j_tpu/streaming/mod.py", rules=["GL006"])
        assert len(out) == 1 and out[0].func.endswith("._step")


class TestHostLoopSyncRule:
    """GL007: blocking readback of a just-dispatched result inside a
    loop in a hot module — the per-token sync the pipelined decode loop
    exists to remove."""

    def test_asarray_of_dispatched_in_loop_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import numpy as np
            def serve(dec, caches, ids, pos):
                for _ in range(8):
                    nxt, caches = dec.decode_step(caches, ids, pos)
                    ids = np.asarray(nxt)
                return ids
        """, rel="deeplearning4j_tpu/models/mod.py", rules=["GL007"])
        assert len(out) == 1 and out[0].rule == "GL007"
        assert "nxt" in out[0].message

    def test_item_of_dispatched_in_loop_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            def serve(fn, xs):
                total = 0
                for x in xs:
                    y = fn(x)
                    total += y.item()
                return total
        """, rel="deeplearning4j_tpu/models/mod.py", rules=["GL007"])
        assert len(out) == 1 and out[0].rule == "GL007"

    def test_fetch_of_loop_invariant_is_fine(self, tmp_path):
        """np.asarray of something dispatched OUTSIDE the loop is a
        one-off sync, not a per-iteration serialization."""
        out = _lint_src(tmp_path, """
            import numpy as np
            def serve(fn, x, xs):
                y = fn(x)
                out = []
                for _ in xs:
                    out.append(np.asarray(y))
                return out
        """, rel="deeplearning4j_tpu/models/mod.py", rules=["GL007"])
        assert out == []

    def test_device_fetch_seam_is_sanctioned(self, tmp_path):
        """The audited ops.transfer.device_fetch crossing (one per
        block, double-buffered) is the fix, not a violation."""
        out = _lint_src(tmp_path, """
            from deeplearning4j_tpu.ops.transfer import device_fetch
            def serve(dec, caches, ids, pos):
                for blk in range(4):
                    toks, ids, pos, caches = dec.decode_block(
                        caches, ids, pos)
                    host = device_fetch(toks, tag="serve")
                return host
        """, rel="deeplearning4j_tpu/models/mod.py", rules=["GL007"])
        assert out == []

    def test_per_lane_item_on_subscript_flags(self, tmp_path):
        """The speculative-retire anti-pattern: per-lane ``.item()``
        syncs on a just-dispatched verify result — B blocking syncs
        where ONE fused [B, K+1] readback was owed."""
        out = _lint_src(tmp_path, """
            def retire(dec, caches, ids, pos, draft):
                emitted = []
                while True:
                    toks, caches = dec.verify_block(caches, ids, pos,
                                                    draft)
                    for s in range(4):
                        emitted.append(toks[s].item())
        """, rel="deeplearning4j_tpu/models/mod.py", rules=["GL007"])
        assert len(out) == 1 and out[0].rule == "GL007"
        assert "toks" in out[0].message

    def test_asarray_of_subscript_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import numpy as np
            def retire(dec, caches, ids, pos):
                rows = []
                for _ in range(8):
                    toks, caches = dec.decode_block(caches, ids, pos)
                    rows.append(np.asarray(toks[0]))
                return rows
        """, rel="deeplearning4j_tpu/models/mod.py", rules=["GL007"])
        assert len(out) == 1 and out[0].rule == "GL007"

    def test_indexing_fetched_host_array_is_fine(self, tmp_path):
        """The sanctioned verify retire: ONE audited device_fetch of
        the whole [B, K+1] block, then free host-side indexing of the
        result (device_fetch returns numpy — not a dispatch)."""
        out = _lint_src(tmp_path, """
            from deeplearning4j_tpu.ops.transfer import device_fetch
            def retire(dec, caches, ids, pos, draft):
                emitted = []
                for blk in range(4):
                    toks, caches = dec.verify_block(caches, ids, pos,
                                                    draft)
                    host = device_fetch(toks, tag="engine.decode")
                    for s in range(4):
                        emitted.append(host[s, -1].item())
                return emitted
        """, rel="deeplearning4j_tpu/models/mod.py", rules=["GL007"])
        assert out == []

    def test_host_helper_results_are_fine(self, tmp_path):
        """Results of np.*/builtins are host values, not dispatches."""
        out = _lint_src(tmp_path, """
            import numpy as np
            def build(xs):
                out = []
                for x in xs:
                    row = np.concatenate([x, x])
                    out.append(np.asarray(row))
                return out
        """, rel="deeplearning4j_tpu/models/mod.py", rules=["GL007"])
        assert out == []

    def test_cold_module_is_fine(self, tmp_path):
        out = _lint_src(tmp_path, """
            import numpy as np
            def serve(fn, xs, x):
                for _ in xs:
                    y = fn(x)
                    x = np.asarray(y)
                return x
        """, rel="deeplearning4j_tpu/ui/mod.py", rules=["GL007"])
        assert out == []

    def test_traced_function_is_gl001_domain(self, tmp_path):
        """Inside jitted code the same pattern is GL001's finding, not a
        double report."""
        out = _lint_src(tmp_path, """
            import jax
            import numpy as np
            @jax.jit
            def f(step, xs):
                for x in xs:
                    y = step(x)
                    x = np.asarray(y)
                return x
        """, rel="deeplearning4j_tpu/models/mod.py",
            rules=["GL001", "GL007"])
        assert _rules(out) == ["GL001"]


class TestObservabilityRule:
    """GL008: metric/trace recording inside jitted/traced code — under
    trace it runs once per COMPILE (never per step) and host-syncs any
    traced value it touches; instrumentation must stay host-side."""

    def test_counter_inc_inside_jit_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax
            @jax.jit
            def step(x, m):
                m.inc()
                return x + 1
        """, rules=["GL008"])
        assert _rules(out) == ["GL008"]
        assert ".inc()" in out[0].message

    def test_histogram_observe_in_scan_body_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax
            def body(carry, t, hist):
                hist.observe(t)
                return carry, t
            def run(xs):
                return jax.lax.scan(body, 0.0, xs)
        """, rules=["GL008"])
        assert _rules(out) == ["GL008"]

    def test_span_record_in_traced_marker_method_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            class Layer:
                # graftlint: traced
                def decode(self, params, x):
                    self._trace.add_span("decode", 0.0, 1.0)
                    return x
        """, rules=["GL008"])
        assert _rules(out) == ["GL008"]

    def test_hinted_method_needs_observability_receiver(self, tmp_path):
        """Generic method names (.set()) flag only on receivers that name
        an observability object — threading.Event().set() in traced code
        is someone else's problem, not GL008's."""
        out = _lint_src(tmp_path, """
            import jax
            @jax.jit
            def f(x, gauge, ev):
                gauge.set(1.0)
                ev.set()
                return x
        """, rules=["GL008"])
        assert len(out) == 1 and "gauge.set" in out[0].snippet

    def test_recording_outside_jit_is_fine(self, tmp_path):
        out = _lint_src(tmp_path, """
            def serve(m, hist, trace):
                m.inc()
                hist.observe(0.5)
                trace.add_span("decode_block", 0.0, 0.5)
        """, rules=["GL008"])
        assert out == []

    def test_inline_disable_suppresses_gl008(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax
            @jax.jit
            def f(x, m):
                m.inc()   # graftlint: disable=GL008
                return x
        """, rules=["GL008"])
        assert out == []


class TestSuppressionAndBaseline:
    def test_inline_disable_suppresses(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax
            @jax.jit
            def f(x):
                return x.item()   # graftlint: disable=GL001
        """)
        assert out == []

    def test_trailing_disable_does_not_spill_to_next_line(self, tmp_path):
        """A new violation written directly below an existing trailing
        suppression must still trip the gate."""
        out = _lint_src(tmp_path, """
            import jax
            @jax.jit
            def f(x):
                a = x.item()   # graftlint: disable=GL001
                b = x.item()
                return a + b
        """)
        assert len(out) == 1 and out[0].rule == "GL001"

    def test_standalone_disable_covers_line_below(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax
            @jax.jit
            def f(x):
                # graftlint: disable=GL001
                return x.item()
        """)
        assert out == []

    def test_traced_marker_opts_method_in(self, tmp_path):
        out = _lint_src(tmp_path, """
            class Layer:
                # graftlint: traced
                def decode(self, params, x):
                    return x.item()
        """)
        assert _rules(out) == ["GL001"]

    def test_baseline_round_trip(self, tmp_path):
        src = """
            import jax
            @jax.jit
            def f(x):
                return x.item()
        """
        found = _lint_src(tmp_path, src)
        assert len(found) == 1
        bpath = tmp_path / "baseline.json"
        write_baseline(str(bpath), found)
        baseline = load_baseline(str(bpath))
        # same findings -> nothing new
        again = _lint_src(tmp_path, src)
        assert new_findings(again, baseline) == []
        # a SECOND violation in the same function -> exactly it is new
        worse = _lint_src(tmp_path, src + """
            @jax.jit
            def g(x):
                return x.tolist()
        """)
        fresh = new_findings(worse, baseline)
        assert len(fresh) == 1 and fresh[0].func == "g"

    def test_baseline_file_shape(self, tmp_path):
        found = _lint_src(tmp_path, """
            import jax
            @jax.jit
            def f(x):
                return x.item()
        """)
        bpath = tmp_path / "baseline.json"
        data = write_baseline(str(bpath), found)
        on_disk = json.loads(bpath.read_text())
        assert on_disk == data
        assert on_disk["total"] == 1 and on_disk["rules"] == ["GL001"]

    def test_missing_and_unparseable_paths_are_surfaced(self, tmp_path):
        """Coverage the gate cannot see must not pass silently: stale
        paths and unparseable files land in runner.errors (the CLI exits
        non-zero on any)."""
        from deeplearning4j_tpu.analysis.lint import LintRunner
        bad = tmp_path / "bad.py"
        bad.write_text("def broken(:\n")
        runner = LintRunner(str(tmp_path))
        found = runner.lint([str(tmp_path / "nope"), str(bad),
                             str(tmp_path / "not_python.txt")])
        assert found == []
        assert len(runner.errors) == 3

    def test_repo_baseline_is_clean(self):
        """The checked-in gate invariant: lint over the real package has
        ZERO findings beyond analysis/baseline.json."""
        import os
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        pkg = os.path.join(root, "deeplearning4j_tpu")
        baseline = load_baseline(os.path.join(pkg, "analysis",
                                              "baseline.json"))
        found = lint_paths([pkg, os.path.join(root, "bench.py")],
                           repo_root=root)
        fresh = new_findings(found, baseline)
        assert fresh == [], "\n".join(str(f) for f in fresh)


#: deliberate two-lock inversion: t1 takes a->b, t2 takes b->a. The
#: static pass must flag the cycle (GL009) and LockAudit must reproduce
#: it dynamically from the same interleaving (see TestLockAudit).
_DEADLOCK_FIXTURE = """
    import threading

    class Pair:
        def __init__(self):
            self.a = threading.Lock()
            self.b = threading.Lock()

        def start(self):
            threading.Thread(target=self.t1, daemon=True).start()
            threading.Thread(target=self.t2, daemon=True).start()

        def t1(self):
            with self.a:
                with self.b:
                    pass

        def t2(self):
            with self.b:
                with self.a:
                    pass
"""


class TestLockOrderRule:
    """GL009: cycles in the cross-module lock-acquisition graph."""

    def test_two_lock_inversion_flags(self, tmp_path):
        out = _lint_src(tmp_path, _DEADLOCK_FIXTURE,
                        rel="deeplearning4j_tpu/streaming/mod.py",
                        rules=["GL009"])
        assert _rules(out) == ["GL009"]
        assert len(out) >= 2            # both edges of the cycle
        assert "deadlock" in out[0].message

    def test_consistent_order_is_fine(self, tmp_path):
        out = _lint_src(tmp_path, """
            import threading

            class Pair:
                def __init__(self):
                    self.a = threading.Lock()
                    self.b = threading.Lock()

                def t1(self):
                    with self.a:
                        with self.b:
                            pass

                def t2(self):
                    with self.a:
                        with self.b:
                            pass
        """, rel="deeplearning4j_tpu/streaming/mod.py", rules=["GL009"])
        assert out == []

    def test_interprocedural_cycle_across_methods(self, tmp_path):
        """The inversion only exists THROUGH call chains: f holds m and
        calls g (acquires n); h holds n and calls k (acquires m)."""
        out = _lint_src(tmp_path, """
            import threading

            class W:
                def __init__(self):
                    self.m = threading.Lock()
                    self.n = threading.Lock()

                def f(self):
                    with self.m:
                        self.g()

                def g(self):
                    with self.n:
                        pass

                def h(self):
                    with self.n:
                        self.k()

                def k(self):
                    with self.m:
                        pass
        """, rel="deeplearning4j_tpu/streaming/mod.py", rules=["GL009"])
        assert _rules(out) == ["GL009"] and len(out) >= 2
        assert any("via" in f.message for f in out)

    def test_rlock_reentry_is_fine(self, tmp_path):
        out = _lint_src(tmp_path, """
            import threading

            class R:
                def __init__(self):
                    self.r_lock = threading.RLock()

                def f(self):
                    with self.r_lock:
                        self.g()

                def g(self):
                    with self.r_lock:
                        pass
        """, rel="deeplearning4j_tpu/streaming/mod.py", rules=["GL009"])
        assert out == []

    def test_nonreentrant_self_deadlock_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import threading

            class R:
                def __init__(self):
                    self.plain = threading.Lock()

                def f(self):
                    with self.plain:
                        self.g()

                def g(self):
                    with self.plain:
                        pass
        """, rel="deeplearning4j_tpu/streaming/mod.py", rules=["GL009"])
        assert len(out) == 1 and "single-thread deadlock" in out[0].message


class TestBlockingUnderLockRule:
    """GL010: blocking work reached (directly or through calls) from a
    critical section."""

    def test_sendall_under_lock_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import threading

            class C:
                def __init__(self, sock):
                    self.sock = sock
                    self._lock = threading.Lock()

                def send(self, frame):
                    with self._lock:
                        self.sock.sendall(frame)
        """, rel="deeplearning4j_tpu/streaming/mod.py", rules=["GL010"])
        assert len(out) == 1 and "socket send" in out[0].message

    def test_transitive_blocking_flags_at_call_site(self, tmp_path):
        out = _lint_src(tmp_path, """
            import threading, time

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def outer(self):
                    with self._lock:
                        self.helper()

                def helper(self):
                    time.sleep(1.0)
        """, rel="deeplearning4j_tpu/streaming/mod.py", rules=["GL010"])
        # the sleep itself runs lock-free in helper — exactly the CALL
        # SITE under the lock is flagged
        assert len(out) == 1
        assert out[0].func == "C.outer" and "sleep" in out[0].message

    def test_lock_argument_binding_attributes_to_caller(self, tmp_path):
        """A module helper that blocks under a lock PARAMETER is
        attributed to each caller's concrete lock (the _send_frame
        seam)."""
        out = _lint_src(tmp_path, """
            import threading

            def send_frame(sock, lock, frame):
                with lock:
                    sock.sendall(frame)

            class C:
                def __init__(self, sock):
                    self.sock = sock
                    self._send_lock = threading.Lock()
                    self._sub_lock = threading.Lock()

                def subscribe(self):
                    with self._sub_lock:
                        send_frame(self.sock, self._send_lock, b"S")
        """, rel="deeplearning4j_tpu/streaming/mod.py", rules=["GL010"])
        # the helper's own sendall-under-param-lock AND the caller's
        # transitive blocking under _sub_lock
        assert len(out) == 2
        assert any("_sub_lock" in f.message for f in out)

    def test_imported_function_resolves_by_module_not_first_wins(
            self, tmp_path):
        """Two modules define ``helper``; the caller imports the
        BLOCKING one by module path. Resolution must honor the import
        (the alphabetically-first module is the harmless one)."""
        pkg = tmp_path / "deeplearning4j_tpu" / "streaming"
        pkg.mkdir(parents=True)
        (pkg / "a_mod.py").write_text(textwrap.dedent("""
            def helper(sock):
                return sock
        """))
        (pkg / "z_mod.py").write_text(textwrap.dedent("""
            def helper(sock):
                sock.sendall(b"x")
        """))
        (pkg / "caller.py").write_text(textwrap.dedent("""
            import threading

            from z_mod import helper

            class C:
                def __init__(self, sock):
                    self.sock = sock
                    self._lock = threading.Lock()

                def f(self):
                    with self._lock:
                        helper(self.sock)
        """))
        out = lint_paths([str(pkg)], repo_root=str(tmp_path),
                         rules=["GL010"])
        # z_mod's helper holds no lock itself — exactly the caller's
        # transitive finding exists, proving the import resolved to the
        # blocking z_mod.helper, not the first-sorted a_mod.helper
        assert len(out) == 1
        assert out[0].func == "C.f" and "socket send" in out[0].message

    def test_explicit_self_call_binds_lock_args_correctly(self, tmp_path):
        """``Base.helper(self, self._lock)`` passes self positionally:
        the lock argument at index 1 must bind to the callee's second
        parameter, so the acquisition edge lands on the CALLER's
        concrete lock."""
        src = """
            import threading, time

            class Base:
                def helper(self, lock):
                    with lock:
                        time.sleep(1.0)

            class C(Base):
                def __init__(self):
                    self._other_lock = threading.Lock()
                    self._inner_lock = threading.Lock()

                def f(self):
                    with self._other_lock:
                        Base.helper(self, self._inner_lock)
        """
        out = _lint_src(tmp_path, src,
                        rel="deeplearning4j_tpu/streaming/mod.py",
                        rules=["GL010"])
        assert any(f.func == "C.f" for f in out)
        from deeplearning4j_tpu.analysis.concurrency import \
            lock_order_edges
        from deeplearning4j_tpu.analysis.lint import collect_package_facts
        p = tmp_path / "deeplearning4j_tpu" / "streaming" / "mod.py"
        facts = collect_package_facts([str(p)], repo_root=str(tmp_path))
        tails = {(a.split(":")[-1], b.split(":")[-1])
                 for a, b in lock_order_edges(facts)}
        assert ("C._other_lock", "C._inner_lock") in tails, tails

    def test_blocking_outside_lock_is_fine(self, tmp_path):
        out = _lint_src(tmp_path, """
            import threading, time

            class C:
                def __init__(self, sock):
                    self.sock = sock
                    self._lock = threading.Lock()

                def send(self, frame):
                    with self._lock:
                        self.pending = frame
                    self.sock.sendall(frame)
                    time.sleep(0.1)
        """, rel="deeplearning4j_tpu/streaming/mod.py", rules=["GL010"])
        assert out == []

    def test_acquire_release_tracking(self, tmp_path):
        out = _lint_src(tmp_path, """
            import threading, time

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def f(self):
                    self._lock.acquire()
                    time.sleep(1.0)
                    self._lock.release()
                    time.sleep(1.0)
        """, rel="deeplearning4j_tpu/streaming/mod.py", rules=["GL010"])
        # only the sleep INSIDE the acquire/release window is flagged
        assert len(out) == 1
        assert "sleep" in out[0].message
        assert out[0].snippet == "time.sleep(1.0)"

    def test_nonblocking_queue_ops_are_fine(self, tmp_path):
        out = _lint_src(tmp_path, """
            import threading

            class C:
                def __init__(self, q):
                    self.queue = q
                    self._lock = threading.Lock()

                def f(self, x):
                    with self._lock:
                        self.queue.put_nowait(x)
                        return self.queue.get_nowait()
        """, rel="deeplearning4j_tpu/streaming/mod.py", rules=["GL010"])
        assert out == []

    def test_blocking_queue_get_under_lock_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import threading

            class C:
                def __init__(self, q):
                    self.queue = q
                    self._lock = threading.Lock()

                def f(self):
                    with self._lock:
                        return self.queue.get(timeout=1.0)
        """, rel="deeplearning4j_tpu/streaming/mod.py", rules=["GL010"])
        assert len(out) == 1 and "queue" in out[0].message

    def test_condition_wait_on_held_lock_is_not_gl010(self, tmp_path):
        """Condition.wait releases the lock it waits on — that sleep is
        the sanctioned one (its discipline is GL011's job)."""
        out = _lint_src(tmp_path, """
            import threading

            class C:
                def __init__(self):
                    self.cond = threading.Condition()
                    self.ready = False

                def f(self):
                    with self.cond:
                        while not self.ready:
                            self.cond.wait()
        """, rel="deeplearning4j_tpu/streaming/mod.py", rules=["GL010"])
        assert out == []

    def test_event_wait_under_other_lock_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.done = threading.Event()

                def f(self):
                    with self._lock:
                        self.done.wait(timeout=1.0)
        """, rel="deeplearning4j_tpu/streaming/mod.py", rules=["GL010"])
        assert len(out) == 1 and ".wait()" in out[0].message


class TestWaitDisciplineRule:
    """GL011: Condition.wait/notify protocol."""

    def test_wait_outside_recheck_loop_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import threading

            class C:
                def __init__(self):
                    self.cond = threading.Condition()

                def f(self):
                    with self.cond:
                        self.cond.wait()
        """, rel="deeplearning4j_tpu/streaming/mod.py", rules=["GL011"])
        assert len(out) == 1 and "re-check loop" in out[0].message

    def test_notify_without_lock_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import threading

            class C:
                def __init__(self):
                    self.cond = threading.Condition()

                def f(self):
                    self.cond.notify()
        """, rel="deeplearning4j_tpu/streaming/mod.py", rules=["GL011"])
        assert len(out) == 1 and "notify" in out[0].message

    def test_proper_wait_loop_and_locked_notify_are_fine(self, tmp_path):
        out = _lint_src(tmp_path, """
            import threading

            class C:
                def __init__(self):
                    self.cond = threading.Condition()
                    self.ready = False

                def consume(self):
                    with self.cond:
                        while not self.ready:
                            self.cond.wait(timeout=0.5)

                def produce(self):
                    with self.cond:
                        self.ready = True
                        self.cond.notify_all()
        """, rel="deeplearning4j_tpu/streaming/mod.py", rules=["GL011"])
        assert out == []

    def test_event_wait_is_not_gl011(self, tmp_path):
        out = _lint_src(tmp_path, """
            import threading

            class C:
                def __init__(self):
                    self.done = threading.Event()

                def f(self):
                    self.done.wait(timeout=1.0)
        """, rel="deeplearning4j_tpu/streaming/mod.py", rules=["GL011"])
        assert out == []


class TestThreadTrackingRule:
    """GL012: fire-and-forget non-daemon threads."""

    def test_untracked_nondaemon_thread_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import threading

            def work():
                pass

            def spawn():
                t = threading.Thread(target=work)
                t.start()
        """, rel="deeplearning4j_tpu/streaming/mod.py", rules=["GL012"])
        assert len(out) == 1 and "non-daemon" in out[0].message

    def test_daemon_thread_is_fine(self, tmp_path):
        out = _lint_src(tmp_path, """
            import threading

            def work():
                pass

            def spawn():
                threading.Thread(target=work, daemon=True).start()
        """, rel="deeplearning4j_tpu/streaming/mod.py", rules=["GL012"])
        assert out == []

    def test_joined_thread_is_fine(self, tmp_path):
        out = _lint_src(tmp_path, """
            import threading

            def work():
                pass

            def spawn():
                t = threading.Thread(target=work)
                t.start()
                t.join()
        """, rel="deeplearning4j_tpu/streaming/mod.py", rules=["GL012"])
        assert out == []


class TestShardingRules:
    """GL013/GL014: the pjit/shard_map seam gate ROADMAP item 1
    inherits."""

    def test_unknown_axis_with_declared_mesh_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            from jax.sharding import Mesh, PartitionSpec as P

            def build(devs):
                mesh = Mesh(devs, ("data",))
                return mesh, P("model")
        """, rules=["GL013"])
        assert len(out) == 1 and "'model'" in out[0].message

    def test_shard_map_site_axis_mismatch_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax
            from jax.sharding import Mesh, PartitionSpec as P

            def run(devs, f, xs):
                mesh = Mesh(devs, ("data",))
                g = jax.shard_map(f, mesh=mesh,
                                  in_specs=(P("model"),),
                                  out_specs=P("data"))
                return g(xs)
        """, rules=["GL013"])
        assert len(out) == 1
        assert "mesh declares axes ['data']" in out[0].message

    def test_bias_rank_mismatch_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            from jax.sharding import PartitionSpec as P

            def specs(model_axis):
                return {"W": P(None, model_axis),
                        "b": P(None, "model")}
        """, rules=["GL013"])
        assert len(out) == 1 and "rank-1" in out[0].message

    def test_dataclass_axis_vocab_catches_typo(self, tmp_path):
        """r12: a module declaring its axes as dataclass fields (the
        SpecLayout idiom — AnnAssign, not Assign) still contributes to
        the axis vocabulary, so a typo'd literal axis in its spec
        tables is caught instead of being vocabulary-blind."""
        out = _lint_src(tmp_path, """
            import dataclasses
            from jax.sharding import PartitionSpec as P

            @dataclasses.dataclass(frozen=True)
            class Layout:
                data_axis: str = "data"
                tp_axis: str = "tp"

            SPECS = {"Wq": P(None, "tpp")}
        """, rules=["GL013"])
        assert len(out) == 1 and "'tpp'" in out[0].message

    def test_dataclass_axis_vocab_accepts_declared(self, tmp_path):
        out = _lint_src(tmp_path, """
            import dataclasses
            from jax.sharding import PartitionSpec as P

            @dataclasses.dataclass(frozen=True)
            class Layout:
                data_axis: str = "data"
                tp_axis: str = "tp"

            SPECS = {"Wq": P(None, "tp"), "Wo": P("tp", None)}
        """, rules=["GL013"])
        assert out == []

    def test_annotated_module_axis_constant_counts(self, tmp_path):
        out = _lint_src(tmp_path, """
            from jax.sharding import PartitionSpec as P

            TP_AXIS: str = "tp"
            TABLE = {"W1": P(None, "tp"), "W2": P("model", None)}
        """, rules=["GL013"])
        assert len(out) == 1 and "'model'" in out[0].message

    def test_consistent_specs_are_fine(self, tmp_path):
        out = _lint_src(tmp_path, """
            from jax.sharding import Mesh, PartitionSpec as P

            def build(devs, model_axis="model"):
                mesh = Mesh(devs, ("data", "model"))
                return {"W": P(None, model_axis), "b": P(model_axis)}, \\
                    P("data")
        """, rules=["GL013"])
        assert out == []

    def test_host_sync_inside_shard_map_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax

            def kernel(x, hist):
                v = x.item()
                hist.observe(v)
                print(v)
                return x

            def run(mesh, xs):
                f = jax.shard_map(kernel, mesh=mesh, in_specs=None,
                                  out_specs=None)
                return f(xs)
        """, rules=["GL014"])
        assert _rules(out) == ["GL014"] and len(out) == 3

    def test_pure_lax_shard_map_body_is_fine(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax
            import jax.numpy as jnp

            def kernel(x):
                return jnp.sum(x * 2.0)

            def run(mesh, xs):
                f = jax.shard_map(kernel, mesh=mesh, in_specs=None,
                                  out_specs=None)
                return f(xs)
        """, rules=["GL014"])
        assert out == []

    def test_real_parallel_modules_are_clean(self):
        """Acceptance: GL013/GL014 clean on mesh.py / tensor.py /
        wrapper.py (plus the other shard_map users), so ROADMAP item 1
        inherits a working gate with no baseline debt."""
        import os
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        pkg = os.path.join(root, "deeplearning4j_tpu")
        paths = [os.path.join(pkg, "parallel", f) for f in
                 ("mesh.py", "spec_layout.py", "tensor.py", "wrapper.py",
                  "sequence.py", "pipeline.py", "inference.py")]
        paths.append(os.path.join(pkg, "models", "generation.py"))
        found = lint_paths(paths, repo_root=root,
                           rules=["GL013", "GL014"])
        assert found == [], "\n".join(str(f) for f in found)


class TestMetricNamingAndSinkRule:
    """GL015 (ISSUE 9): metric-family naming conventions at registry
    declaration sites (counters end ``_total``, histograms ``_seconds``/
    ``_bytes``), plus SLO/flight-recorder/devstats recording banned from
    jit-traced contexts (GL008's machinery, new sinks)."""

    def test_counter_without_total_suffix_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            def wire(registry):
                return registry.counter("requests_served", "served")
        """, rules=["GL015"])
        assert _rules(out) == ["GL015"]
        assert "'requests_served'" in out[0].message
        assert "_total" in out[0].message

    def test_histogram_without_unit_suffix_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            def wire(reg):
                return reg.histogram("decode_latency_ms", "latency")
        """, rules=["GL015"])
        assert len(out) == 1 and "_seconds/_bytes" in out[0].message

    def test_conventional_names_and_gauges_are_fine(self, tmp_path):
        out = _lint_src(tmp_path, """
            def wire(registry):
                registry.counter("requests_total", "served")
                registry.histogram("decode_seconds", "latency")
                registry.histogram("kv_cache_bytes", "cache size")
                registry.gauge("queue_depth", "gauges unconstrained")
        """, rules=["GL015"])
        assert out == []

    def test_fstring_trailing_literal_is_judged(self, tmp_path):
        """The repo's f-string idiom: the statically visible trailing
        fragment carries the unit suffix, so it IS checkable."""
        out = _lint_src(tmp_path, """
            def wire(registry, key):
                registry.counter(f"route_{key}_total", "ok")
                registry.counter(f"route_{key}_count", "bad")
        """, rules=["GL015"])
        assert len(out) == 1 and "_count'" in out[0].message

    def test_dynamic_name_and_non_registry_receiver_skip(self, tmp_path):
        """The gate judges only what it can read: fully dynamic names
        pass, and standalone perf-script Histogram instances (no
        registry receiver) never reach exposition."""
        out = _lint_src(tmp_path, """
            from deeplearning4j_tpu.observability import Histogram

            def wire(registry, name, broker):
                registry.counter(name, "dynamic: unjudgeable")
                h = Histogram("soak_latency_ms")
                broker.counter("not_a_registry")
                return h
        """, rules=["GL015"])
        assert out == []

    def test_flightrec_record_inside_jit_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax

            @jax.jit
            def step(x, flightrec):
                flightrec.record("block_retire", k=4)
                return x + 1
        """, rules=["GL015"])
        assert _rules(out) == ["GL015"]
        assert ".record()" in out[0].message

    def test_slo_observe_in_scan_body_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax

            def body(carry, t, slo_tracker):
                slo_tracker.observe_request(t)
                return carry, t

            def run(xs):
                return jax.lax.scan(body, 0.0, xs)
        """, rules=["GL015"])
        assert _rules(out) == ["GL015"]

    def test_devstats_snapshot_under_trace_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x, devstats):
                devstats.snapshot()
                return x
        """, rules=["GL015"])
        assert _rules(out) == ["GL015"]

    def test_recording_outside_jit_is_fine(self, tmp_path):
        out = _lint_src(tmp_path, """
            def serve(flightrec, slo_tracker, req):
                flightrec.record("admission", batch=2)
                slo_tracker.observe_request(req)
        """, rules=["GL015"])
        assert out == []

    def test_unhinted_receiver_in_jit_is_not_gl015(self, tmp_path):
        """.record() on a receiver that does not name one of the ISSUE 9
        sinks is someone else's problem (same discipline as GL008's
        receiver hints)."""
        out = _lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x, session):
                session.record("frame")
                return x
        """, rules=["GL015"])
        assert out == []

    def test_inline_disable_suppresses_gl015(self, tmp_path):
        out = _lint_src(tmp_path, """
            def wire(registry):
                return registry.counter("legacy_count", "grandfathered")  # graftlint: disable=GL015
        """, rules=["GL015"])
        assert out == []


class TestSeamRule:
    """ISSUE 26: the engine loop's one stamp source
    (``observability.tracing.Seam``, the engine's ``_seam``) is a host-only
    record call like the sinks it feeds — GL008 under jit, GL014 (GL008
    generalized) inside shard_map/pjit regions, and ``mark_idle`` joins
    GL016's record methods."""

    @pytest.mark.parametrize("call", ["Seam(\"dl4j.engine.retire\", 3)",
                                      "tracing.Seam(\"dl4j.train.step\")",
                                      "eng._seam(\"dl4j.engine.admit\")"])
    def test_seam_inside_jit_flags(self, tmp_path, call):
        out = _lint_src(tmp_path, f"""
            import jax

            @jax.jit
            def step(x, eng, tracing, Seam):
                with {call} as s:
                    y = x + 1
                return y
        """, rules=["GL008"])
        assert _rules(out) == ["GL008"]
        assert "stamps a seam" in out[0].message

    def test_seam_inside_shard_map_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            from jax.experimental.shard_map import shard_map

            def region(x, eng):
                with eng._seam("dl4j.engine.dispatch_block", 1, 2, 4):
                    return x * 2

            def run(mesh, x):
                return shard_map(region, mesh=mesh, in_specs=None,
                                 out_specs=None)(x)
        """, rules=["GL008", "GL014"])
        assert set(_rules(out)) == {"GL008", "GL014"}

    def test_mark_idle_inside_jit_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax

            @jax.jit
            def step(x, prof):
                prof.mark_idle(0.0)
                return x
        """, rules=["GL016"])
        assert _rules(out) == ["GL016"]

    def test_seam_on_the_serve_thread_is_fine(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax

            @jax.jit
            def decode(x):
                return x + 1

            def loop(eng, x):
                with eng._seam("dl4j.engine.dispatch_block", 1) as s:
                    y = decode(x)
                eng._prof.mark_idle(s.t1)
                return y
        """, rules=["GL008", "GL014", "GL016"])
        assert out == []


class TestProfilerStampRule:
    """GL016 (ISSUE 13): profiler/phase-stamp recording banned from
    jit-traced AND shard_map contexts — phase stamps are host
    interval-clock anchors recorded from the readback thread; under
    trace they would fire once per compile with trace-time constants."""

    def test_record_block_inside_jit_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax

            @jax.jit
            def step(x, profiler):
                profiler.record_block(impl="step", k=1, lanes=2,
                                      queued=0, t_dispatch=0.0,
                                      t_fetched=1.0, t_host=1.0,
                                      t_journal=1.0, t_publish=1.0)
                return x + 1
        """, rules=["GL016"])
        assert _rules(out) == ["GL016"]
        assert ".record_block()" in out[0].message

    def test_record_chunk_in_scan_body_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax

            def body(carry, t, prof):
                prof.record_chunk(t_dispatch=0.0, t_done=1.0, final=True)
                return carry, t

            def run(xs):
                return jax.lax.scan(body, 0.0, xs)
        """, rules=["GL016"])
        assert _rules(out) == ["GL016"]

    def test_record_admission_inside_shard_map_flags(self, tmp_path):
        out = _lint_src(tmp_path, """
            from jax.experimental.shard_map import shard_map

            def region(x, phase_channel):
                phase_channel.record_admission(impl="prefill", count=2,
                                               t_dispatch=0.0,
                                               t_fetched=1.0, t_host=1.0,
                                               t_journal=1.0,
                                               t_publish=1.0)
                return x

            def run(mesh, x):
                return shard_map(region, mesh=mesh, in_specs=None,
                                 out_specs=None)(x)
        """, rules=["GL016"])
        # the jit-body pass (shard_map is a trace wrapper) and the
        # sharding pass both witness it — one GL016 rule either way
        assert _rules(out) == ["GL016"]
        assert any(".record_admission()" in f.message for f in out)

    def test_recording_on_readback_thread_is_fine(self, tmp_path):
        """The engine's actual call shape — record_* on the readback
        thread, outside any traced region — must stay clean."""
        out = _lint_src(tmp_path, """
            def _retire_block(self, block, profiler):
                toks, k, t_disp = block
                profiler.record_block(impl="block", k=k, lanes=2,
                                      queued=0, t_dispatch=t_disp,
                                      t_fetched=1.0, t_host=1.0,
                                      t_journal=1.0, t_publish=1.0)
        """, rules=["GL016"])
        assert out == []

    def test_unhinted_receiver_in_jit_is_not_gl016(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x, session):
                session.record_block(1)
                return x
        """, rules=["GL016"])
        assert out == []

    def test_inline_disable_suppresses_gl016(self, tmp_path):
        out = _lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x, profiler):
                profiler.record_chunk(t_dispatch=0.0, t_done=1.0, final=True)  # graftlint: disable=GL016
                return x
        """, rules=["GL016"])
        assert out == []


class TestLintCacheAndCLI:
    _SRC = textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            return x.item()
    """)

    def test_cache_round_trip(self, tmp_path):
        from deeplearning4j_tpu.analysis import LintCache
        from deeplearning4j_tpu.analysis.lint import LintRunner
        mod = tmp_path / "deeplearning4j_tpu" / "kernels" / "m.py"
        mod.parent.mkdir(parents=True)
        mod.write_text(self._SRC)
        cpath = str(tmp_path / "cache.json")
        c1 = LintCache(cpath)
        f1 = LintRunner(str(tmp_path), cache=c1).lint([str(mod)])
        assert c1.misses == 1 and c1.hits == 0
        c2 = LintCache(cpath)
        f2 = LintRunner(str(tmp_path), cache=c2).lint([str(mod)])
        assert c2.hits == 1 and c2.misses == 0
        assert [f.key for f in f1] == [f.key for f in f2] and len(f1) == 1
        # an edit invalidates the entry and changes the result
        mod.write_text(self._SRC.replace("x.item()", "x"))
        c3 = LintCache(cpath)
        f3 = LintRunner(str(tmp_path), cache=c3).lint([str(mod)])
        assert c3.misses == 1 and f3 == []

    def test_cache_refreshes_stamps_after_touch(self, tmp_path):
        """A touch (mtime change, same content) must hit via the hash
        slow path ONCE and refresh the stored stamps, so later runs are
        back on the mtime fast path."""
        import os
        from deeplearning4j_tpu.analysis import LintCache
        from deeplearning4j_tpu.analysis.lint import LintRunner
        mod = tmp_path / "deeplearning4j_tpu" / "kernels" / "m.py"
        mod.parent.mkdir(parents=True)
        mod.write_text(self._SRC)
        cpath = str(tmp_path / "cache.json")
        LintRunner(str(tmp_path), cache=LintCache(cpath)).lint([str(mod)])
        st = os.stat(mod)
        os.utime(mod, (st.st_atime + 100, st.st_mtime + 100))
        c2 = LintCache(cpath)
        LintRunner(str(tmp_path), cache=c2).lint([str(mod)])
        assert c2.hits == 1
        c3 = LintCache(cpath)
        rel = "deeplearning4j_tpu/kernels/m.py"
        assert c3._data[rel]["mtime"] == os.stat(mod).st_mtime

    def test_cache_serves_every_rule_selection(self, tmp_path):
        """One cache entry answers any --select: per-file results are
        stored for ALL rules and filtered at collection time."""
        from deeplearning4j_tpu.analysis import LintCache
        from deeplearning4j_tpu.analysis.lint import LintRunner
        mod = tmp_path / "deeplearning4j_tpu" / "kernels" / "m.py"
        mod.parent.mkdir(parents=True)
        mod.write_text(self._SRC)
        cpath = str(tmp_path / "cache.json")
        LintRunner(str(tmp_path), cache=LintCache(cpath)).lint([str(mod)])
        c = LintCache(cpath)
        got = LintRunner(str(tmp_path), rules=["GL004"],
                         cache=c).lint([str(mod)])
        assert c.hits == 1 and got == []
        c = LintCache(cpath)
        got = LintRunner(str(tmp_path), rules=["GL001"],
                         cache=c).lint([str(mod)])
        assert c.hits == 1 and len(got) == 1

    def test_cli_select_ignore_json(self, tmp_path):
        import os
        import subprocess
        import sys
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        mod = tmp_path / "m.py"
        mod.write_text(textwrap.dedent("""
            import jax
            import numpy as np

            @jax.jit
            def f(x):
                return x.item() * np.sqrt(4)
        """))
        cli = os.path.join(root, "scripts", "lint.py")

        def run(*extra):
            return subprocess.run(
                [sys.executable, cli, "--no-cache", "--json", *extra,
                 str(mod)], capture_output=True, text=True, cwd=root)

        r = run("--select", "GL001")
        data = json.loads(r.stdout)
        assert r.returncode == 1        # findings present (not a gate)
        assert {f["rule"] for f in data["findings"]} == {"GL001"}
        r = run()
        data = json.loads(r.stdout)
        assert {f["rule"] for f in data["findings"]} == {"GL001", "GL004"}
        r = run("--ignore", "GL001,GL004")
        assert r.returncode == 0
        assert json.loads(r.stdout)["findings"] == []


class TestLockAudit:
    """Runtime lock-order auditor: the dynamic half of GL009/GL010."""

    def test_order_recording_and_no_false_cycle(self):
        audit = LockAudit()
        a = audit.wrap(threading.Lock(), "A")
        b = audit.wrap(threading.Lock(), "B")
        for _ in range(3):
            with a:
                with b:
                    pass
        assert audit.edges() == {("A", "B"): 3}
        assert audit.cycles() == []
        audit.check()                   # no raise

    def test_aborted_wait_leaves_no_phantom_entry(self):
        """Regression: wait() on an un-acquired audited condition
        raises — and must NOT plant a held-stack entry that would
        fabricate lock-order edges for the rest of the thread."""
        audit = LockAudit()
        cond = audit.wrap(threading.Condition(), "C.cond")
        lock = audit.wrap(threading.Lock(), "C.lock")
        with pytest.raises(RuntimeError):
            cond.wait(timeout=0.01)
        with lock:
            pass
        assert audit.edges() == {}

    def test_patch_mode_condition_wait_works(self):
        """Regression: a bare threading.Condition() built under
        LockAudit(patch=True) wraps an audited RLock; the Condition
        protocol (_is_owned/_release_save/_acquire_restore) must be
        forwarded or every wait() raises 'cannot wait on un-acquired
        lock' (the acquire(False) fallback probe succeeds reentrantly
        on an RLock)."""
        with LockAudit(patch=True) as audit:
            cond = threading.Condition()
            ev_like = threading.Event()     # Condition(Lock()) inside
            with cond:
                assert cond.wait(timeout=0.05) is False
                cond.notify_all()
            ev_like.set()
            assert ev_like.wait(timeout=1)
            # wait released and re-acquired through the wrapper: the
            # held stack must be balanced afterwards
            assert audit._stack() == []
        assert audit.cycles() == []

    def test_deadlock_fixture_static_and_dynamic(self, tmp_path):
        """Acceptance: the deliberate two-lock inversion is caught
        statically (GL009) AND the same interleaving, actually run on
        two threads, is reproduced dynamically by LockAudit — with the
        dynamic edges matching the static graph's."""
        static_out = _lint_src(tmp_path, _DEADLOCK_FIXTURE,
                               rel="deeplearning4j_tpu/streaming/mod.py",
                               rules=["GL009"])
        assert _rules(static_out) == ["GL009"]

        audit = LockAudit()
        a = audit.wrap(threading.Lock(), "Pair.a")
        b = audit.wrap(threading.Lock(), "Pair.b")
        barrier = threading.Barrier(2)

        def t1():
            with a:
                barrier.wait(timeout=5)
                # bounded acquire: the repro must demonstrate the
                # deadlock interleaving without hanging the test run
                if b.acquire(timeout=1.0):
                    b.release()

        def t2():
            with b:
                barrier.wait(timeout=5)
                if a.acquire(timeout=1.0):
                    a.release()

        ts = [threading.Thread(target=t1, daemon=True),
              threading.Thread(target=t2, daemon=True)]
        t0 = time.monotonic()
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=10)
        assert time.monotonic() - t0 < 10
        assert audit.cycles() == [["Pair.a", "Pair.b"]]
        with pytest.raises(LockOrderError):
            audit.check()
        # static/dynamic agreement: every dynamic edge is in the static
        # graph, and the dynamic inversion is exactly what GL009 flagged
        from deeplearning4j_tpu.analysis.concurrency import \
            lock_order_edges
        from deeplearning4j_tpu.analysis.lint import collect_package_facts
        facts = collect_package_facts(
            [str(tmp_path / "deeplearning4j_tpu")],
            repo_root=str(tmp_path))
        static = lock_order_edges(facts)
        cc = audit.cross_check(static.keys())
        assert sorted(cc["inversions"]) == [("Pair.a", "Pair.b"),
                                            ("Pair.b", "Pair.a")]
        assert cc["novel"] == []

    def test_engine_supervisor_static_dynamic_agreement(self):
        """Acceptance: instrumented SlotGenerationEngine + supervisor
        locks, exercised through submit/stats/stop, produce NO dynamic
        edge the static lock-order graph cannot explain and no
        inversion."""
        import os
        from deeplearning4j_tpu.analysis.concurrency import \
            lock_order_edges
        from deeplearning4j_tpu.analysis.lint import collect_package_facts
        from deeplearning4j_tpu.models import SlotGenerationEngine
        from deeplearning4j_tpu.parallel.failures import EngineSupervisor

        net = _tiny_lm()
        eng = SlotGenerationEngine(net, num_slots=2)
        sup = EngineSupervisor(eng, timeout=60.0)
        audit = LockAudit()
        # pin inherited attrs to their DEFINING class (the identity the
        # static tokens use)
        names = audit.instrument(
            sup, names={"_lock": "HeartbeatMonitor._lock"})
        names += audit.instrument(eng)
        assert "EngineSupervisor._sup_lock" in names
        assert "SlotGenerationEngine._lock" in names
        sup.start()
        reqs = [sup.submit([1, 2, 3], 3) for _ in range(4)]
        for r in reqs:
            r.result(timeout=120)
        sup.stats()
        sup.stop()
        assert audit.cycles() == []
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        facts = collect_package_facts(
            [os.path.join(root, "deeplearning4j_tpu")], repo_root=root)
        cc = audit.cross_check(lock_order_edges(facts).keys())
        assert cc["inversions"] == [], cc
        assert cc["novel"] == [], cc
        # the submit path actually exercised the supervisor->engine edge
        assert ("EngineSupervisor._sup_lock",
                "SlotGenerationEngine._lock") in cc["explained"]

    def test_broker_static_dynamic_agreement(self):
        import os
        from deeplearning4j_tpu.analysis.concurrency import \
            lock_order_edges
        from deeplearning4j_tpu.analysis.lint import collect_package_facts
        from deeplearning4j_tpu.streaming.tcp_broker import (
            TcpBrokerServer, TcpMessageBroker)

        server = TcpBrokerServer().start()
        client = TcpMessageBroker(server.host, server.port)
        audit = LockAudit()
        names = audit.instrument(
            client, names={"_lock": "TcpMessageBroker._lock"})
        assert "TcpMessageBroker._send_lock" in names
        try:
            q = client.subscribe("t")
            client.publish("t", b"x")
            assert q.get(timeout=5) == b"x"
            client.unsubscribe("t", q)
        finally:
            client.close()
            server.close()
        assert audit.cycles() == []
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        facts = collect_package_facts(
            [os.path.join(root, "deeplearning4j_tpu")], repo_root=root)
        cc = audit.cross_check(lock_order_edges(facts).keys())
        assert cc["inversions"] == [], cc
        assert cc["novel"] == [], cc
        # subscribe held _sub_lock while sending the S frame through the
        # _send_frame seam: the param-lock binding edge, live
        assert ("TcpMessageBroker._sub_lock",
                "TcpMessageBroker._send_lock") in cc["explained"]


class TestCompileAudit:
    def test_shape_unstable_function_is_caught(self):
        import jax
        import jax.numpy as jnp

        with CompileAudit() as audit:
            @jax.jit
            def unstable(x):
                return x * 2.0
            for n in (3, 4, 5):          # deliberately retraces per shape
                unstable(jnp.ones(n))
            for _ in range(5):           # steady calls: no new compiles
                unstable(jnp.ones(3))
        assert audit.compiles("unstable") == 3
        info = audit.retraces()["unstable"]
        assert info["compiles"] == 3
        assert info["distinct_signatures"] == 3
        assert info["duplicate_signature_compiles"] == 0
        with pytest.raises(CompileBudgetError):
            audit.check(budget={"unstable": 1})
        audit.check(budget={"unstable": 3})      # at budget: fine

    def test_stable_function_compiles_once(self):
        import jax
        import jax.numpy as jnp

        with CompileAudit(budget={"stable": 1}) as audit:
            @jax.jit
            def stable(x):
                return x + 1.0
            snap = None
            for i in range(4):
                stable(jnp.arange(7.0))
                if i == 0:
                    snap = audit.snapshot()
        assert audit.compiles("stable") == 1
        assert audit.delta(snap) == {}           # steady state: no compiles
        assert audit.duplicate_signature_compiles == 0

    def test_exit_restores_log_compiles(self):
        import jax
        prev = bool(getattr(jax.config, "jax_log_compiles", False))
        with CompileAudit():
            pass
        assert bool(getattr(jax.config, "jax_log_compiles", False)) == prev

    def test_handler_parses_the_jax_0_9_record(self):
        """The exact record jax 0.9.0 logs (pxla.py lower_sharding_
        computation): a ``jit(...)``-wrapped module name and a TUPLE of
        avals — the audit row is the bare function name."""
        import logging

        from deeplearning4j_tpu.analysis.compile_audit import \
            _CompileLogHandler
        audit = CompileAudit()
        _CompileLogHandler(audit).emit(logging.LogRecord(
            "jax._src.interpreters.pxla", logging.WARNING, "pxla.py", 1943,
            "Compiling %s with global shapes and types %s. "
            "Argument mapping: %s.",
            ("jit(stable)", "(ShapedArray(float32[7]),)",
             "(UnspecifiedValue,)"), None))
        assert dict(audit.counts) == {"stable": 1}
        assert dict(audit.signatures["stable"]) == \
            {"(ShapedArray(float32[7]),)": 1}

    def test_deaf_seam_raises_instead_of_reading_empty(self):
        """A silenced pxla logger must fail the audit at entry — never
        report ``{}`` compiles for a region it could not observe — and
        the failed entry must leave no handler or config behind."""
        import logging

        import jax

        from deeplearning4j_tpu.analysis.compile_audit import \
            CompileAuditDeafError
        logger = logging.getLogger("jax._src.interpreters.pxla")
        prev = bool(getattr(jax.config, "jax_log_compiles", False))
        handlers = list(logger.handlers)
        logger.disabled = True
        try:
            with pytest.raises(CompileAuditDeafError):
                with CompileAudit():
                    pytest.fail("a deaf audit must not be entered")
        finally:
            logger.disabled = False
        assert logger.handlers == handlers
        assert bool(getattr(jax.config, "jax_log_compiles", False)) == prev
        with CompileAudit() as audit:       # hears again once unsilenced
            jax.jit(lambda x: x + 1)(1.0)
        assert audit.report()["per_function"] == {}    # probe never counted


def _tiny_lm(vocab=37, d=16, heads=2, layers=1, t_max=32):
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import transformer_lm_conf
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    conf = transformer_lm_conf(vocab_size=vocab, d_model=d, num_heads=heads,
                               num_layers=layers, max_length=t_max)
    return ComputationGraph(conf, compute_dtype=jnp.float32).init()


class TestServingCompileInvariants:
    def test_three_wave_engine_run_has_no_retraces(self):
        """Acceptance invariant: a 3-wave SlotGenerationEngine run
        compiles decode_step_impl exactly ONCE and the batched-admission
        prefill at most once per (count-bucket, length-bucket) — slot
        refills, mixed prompt lengths, and later waves reuse the
        programs — and performs at most ONE host readback per decode
        block and one per admission batch."""
        from deeplearning4j_tpu.analysis import TransferAudit
        from deeplearning4j_tpu.models import SlotGenerationEngine
        net = _tiny_lm()
        eng = SlotGenerationEngine(net, num_slots=3, refill=True, seed=0)
        rng = np.random.default_rng(5)
        with CompileAudit() as audit, TransferAudit() as transfers:
            for wave in range(3):
                reqs = [eng.submit(rng.integers(0, 37, int(n)), 4)
                        for n in rng.integers(2, 9, 6)]
                eng.run_until_drained()
                assert all(r.done() for r in reqs)
        assert audit.compiles("decode_step_impl") == 1
        # admission coalesces into count buckets {1, 2, 3(cap)} at one
        # length bucket — never more, and never a blown cache
        assert 1 <= audit.compiles("prefill_slots_impl") <= 3
        assert audit.duplicate_signature_compiles == 0
        audit.check(budget={"prefill_slots_impl": 3,
                            "decode_step_impl": 1})
        stats = eng.stats()
        transfers.check_per_block("engine.decode", stats["decode_blocks"])
        transfers.check_per_block("engine.prefill",
                                  stats["prefill_batches"])
        assert transfers.fetches("engine.decode") == stats["decode_blocks"]

    def test_block_decode_steady_state_per_k(self):
        """Per block size K: decode_block{K}_impl compiles exactly once,
        waves after the first add ZERO compiles, and the pipelined loop
        reads back at most once per block."""
        from deeplearning4j_tpu.analysis import TransferAudit
        from deeplearning4j_tpu.models import SlotGenerationEngine
        net = _tiny_lm()
        rng = np.random.default_rng(7)
        for k in (4, 8):
            eng = SlotGenerationEngine(net, num_slots=3, refill=True,
                                       seed=0, block_size=k)
            with CompileAudit() as audit, TransferAudit() as transfers:
                snap = None
                for wave in range(3):
                    reqs = [eng.submit(rng.integers(0, 37, int(n)), 5)
                            for n in rng.integers(2, 9, 6)]
                    eng.run_until_drained()
                    assert all(r.done() for r in reqs)
                    if wave == 0:
                        snap = audit.snapshot()
                steady_new = audit.delta(snap)
            name = f"decode_block{k}_impl"
            assert audit.compiles(name) == 1, (k, audit.report())
            assert audit.duplicate_signature_compiles == 0
            # waves 2-3 are steady state: nothing may lower anew
            assert steady_new.get(name, 0) == 0, steady_new
            stats = eng.stats()
            assert stats["decode_steps"] == k * stats["decode_blocks"]
            transfers.check_per_block("engine.decode",
                                      stats["decode_blocks"])
            transfers.check_per_block("engine.prefill",
                                      stats["prefill_batches"])

    def test_submit_after_shutdown_fails_fast_not_hangs(self):
        """The shutdown/dead check and the queue append are one atomic
        section: a request can never be queued after the final drain (its
        caller would hang forever in result(None))."""
        from deeplearning4j_tpu.models import SlotGenerationEngine
        net = _tiny_lm()
        eng = SlotGenerationEngine(net, num_slots=2).start()
        ok = eng.submit([1, 2, 3], 3)
        assert ok.result(timeout=60) is not None
        eng.shutdown()
        late = eng.submit([1, 2, 3], 3)
        with pytest.raises(RuntimeError):
            late.result(timeout=5)

    def test_bucketed_generate_compiles_once_across_lengths(self):
        """models.generate's fixed bucket: mixed prompt lengths share ONE
        [1, bucket] program (the compile-per-token failure mode this
        bucket exists to prevent)."""
        from deeplearning4j_tpu.models import generate
        net = _tiny_lm()
        with CompileAudit() as audit:
            for plen in (2, 5, 9):
                generate(net, list(range(1, plen + 1)), 4, temperature=0,
                         bucket=16)
        assert audit.compiles("_out") == 1
        assert audit.duplicate_signature_compiles == 0
