"""graftlint self-tests (ISSUE 9).

Fixture-driven: every rule family has a known-bad snippet that MUST fire
and a known-clean snippet that MUST stay quiet, plus pragma suppression,
baseline round-trip, the whole-package self-hosting gate (this test IS
the CI step — a new non-baselined finding fails tier-1), the runtime
sanitizer, and regression tests for the real bugs the pass surfaced:

  * telemetry/listener.py  — hot-loop-sync: TelemetryListener pulled
    float(model.score()) on EVERY iteration (a per-step device->host
    sync serializing the async dispatch pipeline); now gated on the
    report window.
  * parallel/timesource.py — blocking-call-under-lock:
    CoordinatorTimeSource.offset_ms could run the NTP network exchange
    while holding its lock, stalling every concurrent stats reader
    behind a 5 s socket timeout; refresh now runs lock-free.
  * ui/remote.py           — blocking-call-under-lock:
    RemoteUIStatsStorageRouter.put_update drained the retry queue
    (HTTP POST, up to a full timeout) under a blocking lock; the drain
    now try-locks so a training thread never stalls behind another's
    slow POST.
"""
import os
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.analysis import (Finding, LockOrderError,
                                         ThreadLeakError, run_lint,
                                         sanitize)
from deeplearning4j_tpu.analysis.engine import (baseline_diff,
                                                load_baseline,
                                                write_baseline)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "deeplearning4j_tpu")
BASELINE = os.path.join(REPO, "graftlint_baseline.json")


def lint_src(tmp_path, src, name="snippet.py", baseline=None):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src), encoding="utf-8")
    return run_lint([str(p)], baseline_path=baseline)


def rules_of(result):
    return {f.rule for f in result.findings}


# ---------------------------------------------------------------------------
# Family JH: jit/tracer hygiene
# ---------------------------------------------------------------------------
def test_host_sync_in_trace_fires(tmp_path):
    res = lint_src(tmp_path, """
        import jax
        import jax.numpy as jnp

        def step(x):
            y = jnp.sin(x)
            return float(y)

        f = jax.jit(step)
    """)
    assert "host-sync-in-trace" in rules_of(res)


def test_host_sync_item_and_numpy_fire(tmp_path):
    res = lint_src(tmp_path, """
        import jax
        import jax.numpy as jnp
        import numpy as np

        def step(x):
            y = jnp.sum(x)
            a = y.item()
            b = np.asarray(y)
            return a, b

        f = jax.jit(step)
    """)
    assert sum(f.rule == "host-sync-in-trace"
               for f in res.findings) == 2


def test_host_sync_quiet_on_static_values(tmp_path):
    # float() on a static scalar / shape element is fine under trace
    res = lint_src(tmp_path, """
        import jax
        import jax.numpy as jnp

        def step(x, eps):
            n = float(x.shape[0])
            e = float(eps)
            return jnp.sum(x) / n + e

        f = jax.jit(step)
    """)
    assert "host-sync-in-trace" not in rules_of(res)


def test_print_wallclock_rng_fire(tmp_path):
    res = lint_src(tmp_path, """
        import time
        import random
        import jax

        def step(x):
            print("debug")
            t = time.time()
            r = random.random()
            return x

        f = jax.jit(step)
    """)
    got = rules_of(res)
    assert {"print-in-trace", "wallclock-in-trace",
            "python-rng-in-trace"} <= got


def test_hygiene_quiet_outside_trace(tmp_path):
    # identical body, never jitted -> host code may do all of this
    res = lint_src(tmp_path, """
        import time
        import random

        def host_step(x):
            print("debug")
            t = time.time()
            r = random.random()
            return float(x)
    """)
    assert not rules_of(res) & {"print-in-trace", "wallclock-in-trace",
                                "python-rng-in-trace",
                                "host-sync-in-trace"}


def test_traced_value_branch_fires_and_shields(tmp_path):
    res = lint_src(tmp_path, """
        import jax
        import jax.numpy as jnp

        def bad(x):
            y = jnp.sum(x)
            if y > 0:
                return y
            return -y

        def ok(x, train):
            if train:                    # static config param
                x = x * 2
            if x.ndim == 3:              # shape shield
                x = x[0]
            if x is None:                # None shield
                return x
            return jnp.sum(x)

        f = jax.jit(bad)
        g = jax.jit(ok)
    """)
    fired = [f for f in res.findings if f.rule == "traced-value-branch"]
    assert len(fired) == 1 and fired[0].scope.endswith(":bad")


def test_trace_reaches_through_calls_and_scan(tmp_path):
    # helper reached FROM a jitted fn, and a lax.scan body, are traced
    res = lint_src(tmp_path, """
        import jax
        import jax.numpy as jnp

        def helper(x):
            y = jnp.exp(x)
            return float(y)

        def step(x):
            return helper(x)

        def body(carry, x):
            z = jnp.add(carry, x)
            return carry, z.item()

        f = jax.jit(step)

        def run(xs):
            return jax.lax.scan(body, 0.0, xs)
    """)
    scopes = {f.scope for f in res.findings
              if f.rule == "host-sync-in-trace"}
    assert any(s.endswith(":helper") for s in scopes)
    assert any(s.endswith(":body") for s in scopes)


def test_hot_loop_sync_fires_unguarded_only(tmp_path):
    res = lint_src(tmp_path, """
        class Bad:
            def iteration_done(self, model, iteration):
                self.score = float(model.score())

        class Guarded:
            def iteration_done(self, model, iteration):
                if iteration % 10 == 0:
                    self.score = float(model.score())

        class EarlyReturn:
            def iteration_done(self, model, iteration):
                if iteration % self.freq != 0:
                    return
                self.score = float(model.score())
    """)
    fired = [f for f in res.findings if f.rule == "hot-loop-sync"]
    assert len(fired) == 1 and "Bad" in fired[0].scope


def test_taint_propagates_through_derived_locals(tmp_path):
    """Review regression: values one assignment away from a jnp result
    must still be tainted (the first cut visited statements in stack
    order, so `b = a + 1` was scanned before `a` was tainted)."""
    res = lint_src(tmp_path, """
        import jax
        import jax.numpy as jnp

        def step(x):
            a = jnp.sum(x)
            b = a + 1
            if b > 0:
                return float(b)
            return b

        f = jax.jit(step)
    """)
    got = rules_of(res)
    assert "traced-value-branch" in got and "host-sync-in-trace" in got


# ---------------------------------------------------------------------------
# Family RC: recompilation hazards
# ---------------------------------------------------------------------------
def test_jit_in_loop_fires(tmp_path):
    res = lint_src(tmp_path, """
        import jax

        def rebuild_every_call(fns, x):
            outs = []
            for fn in fns:
                outs.append(jax.jit(fn)(x))
            return outs
    """)
    assert "jit-in-loop" in rules_of(res)


def test_jit_outside_loop_quiet(tmp_path):
    res = lint_src(tmp_path, """
        import jax

        def step(x):
            return x

        f = jax.jit(step)
    """)
    assert "jit-in-loop" not in rules_of(res)


def test_unhashable_static_arg_fires(tmp_path):
    res = lint_src(tmp_path, """
        import jax

        def fn(x, opts):
            return x

        g = jax.jit(fn, static_argnums=(1,))

        def call(x):
            return g(x, [1, 2])

        def call_ok(x):
            return g(x, (1, 2))
    """)
    fired = [f for f in res.findings
             if f.rule == "unhashable-static-arg"]
    assert len(fired) == 1


def test_shape_branch_fires_on_variable_comparison(tmp_path):
    res = lint_src(tmp_path, """
        import jax

        def bad(x, budget):
            if x.shape[0] > budget:
                return x
            return x

        def ok(x):
            if x.ndim == 3:
                return x[0]
            return x

        f = jax.jit(bad)
        g = jax.jit(ok)
    """)
    fired = [f for f in res.findings
             if f.rule == "shape-branch-in-trace"]
    assert len(fired) == 1 and fired[0].scope.endswith(":bad")


def test_unwatched_jit_entry_cross_check(tmp_path):
    res = lint_src(tmp_path, """
        import jax
        from deeplearning4j_tpu.telemetry.compile_watch import watch_compiles

        def a(x):
            return x

        def b(x):
            return x

        covered = watch_compiles(jax.jit(a), "test/a")
        uncovered = jax.jit(b)
    """)
    fired = [f for f in res.findings if f.rule == "unwatched-jit-entry"]
    assert len(fired) == 1
    assert "uncovered" in fired[0].snippet


def test_record_aot_comment_does_not_exempt(tmp_path):
    """Review regression: only an actual record_aot CALL exempts a
    module's jit sites from unwatched-jit-entry — a comment mentioning
    it must not bypass the gate."""
    commented = lint_src(tmp_path, """
        import jax
        # TODO: maybe use record_aot here someday

        def step(x):
            return x

        f = jax.jit(step)
    """)
    assert "unwatched-jit-entry" in rules_of(commented)
    calling = lint_src(tmp_path, """
        import jax

        def step(x):
            return x

        def build(tel):
            f = jax.jit(step)
            tel.compiles.record_aot("mod/step", 0.1)
            return f
    """, name="snippet2.py")
    assert "unwatched-jit-entry" not in rules_of(calling)


def test_rules_filter_uses_filtered_baseline(tmp_path):
    """Review regression: a --rules-restricted run must not report other
    rules' baseline entries as stale (or as anything at all)."""
    p = tmp_path / "mod.py"
    p.write_text(textwrap.dedent(BAD_SLEEP.format(pragma="")),
                 encoding="utf-8")
    bl = tmp_path / "bl.json"
    write_baseline(str(bl), run_lint([str(p)]).findings + [
        Finding("unwatched-jit-entry", "other.py", 1, 0, "m",
                scope="s", snippet="g = jax.jit(f)")])
    res = run_lint([str(p)], baseline_path=str(bl),
                   rules=["blocking-call-under-lock"])
    assert not res.new and not res.stale_baseline


def test_tools_wrapper_imports_without_jax():
    """Review regression: `python -m tools.graftlint` must not pull in
    jax / the package __init__ — the engine is pure stdlib."""
    import subprocess
    code = ("import sys; sys.path.insert(0, %r); "
            "import tools.graftlint as g; "
            "rc = g.main([%r, '--baseline', %r]); "
            "assert 'jax' not in sys.modules, 'jax was imported'; "
            "sys.exit(rc)" % (REPO, PKG, BASELINE))
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120,
                          env={**os.environ, "PYTHONPATH": ""})
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# Family DN: donation safety
# ---------------------------------------------------------------------------
def test_donated_buffer_reuse_fires(tmp_path):
    res = lint_src(tmp_path, """
        import jax

        def step(p, x):
            return p

        f = jax.jit(step, donate_argnums=(0,))

        def train(p, x):
            out = f(p, x)
            return p + out
    """)
    fired = [f for f in res.findings if f.rule == "donated-buffer-reuse"]
    assert len(fired) == 1 and "'p'" in fired[0].message


def test_donated_rebind_is_quiet(tmp_path):
    res = lint_src(tmp_path, """
        import jax

        def step(p, x):
            return p

        f = jax.jit(step, donate_argnums=(0,))

        def train(p, xs):
            for x in xs:
                p = f(p, x)
            return p
    """)
    assert "donated-buffer-reuse" not in rules_of(res)


def test_donated_loop_carry_fires(tmp_path):
    res = lint_src(tmp_path, """
        import jax

        def step(p, x):
            return p

        f = jax.jit(step, donate_argnums=(0,))

        def train(p, xs):
            outs = []
            for x in xs:
                outs.append(f(p, x))
            return outs
    """)
    assert "donated-buffer-reuse" in rules_of(res)


# ---------------------------------------------------------------------------
# Family CC: concurrency
# ---------------------------------------------------------------------------
def test_blocking_under_lock_fires_direct_and_transitive(tmp_path):
    res = lint_src(tmp_path, """
        import threading
        import time

        class A:
            def __init__(self):
                self._lock = threading.Lock()

            def bad_direct(self):
                with self._lock:
                    time.sleep(1.0)

            def _slow(self):
                time.sleep(1.0)

            def bad_transitive(self):
                with self._lock:
                    self._slow()

            def ok(self):
                with self._lock:
                    x = 1
                time.sleep(1.0)
                return x
    """)
    fired = [f for f in res.findings
             if f.rule == "blocking-call-under-lock"]
    assert {f.scope.split(".")[-1] for f in fired} == \
        {"bad_direct", "bad_transitive"}


def test_blocking_with_statement_under_lock_fires(tmp_path):
    """Review regression: `with socket.create_connection(...)` under a
    held lock must be flagged like the plain-call form (the codebase's
    own NTP-exchange idiom)."""
    res = lint_src(tmp_path, """
        import socket
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()

            def bad(self):
                with self._lock:
                    with socket.create_connection(("h", 1)) as s:
                        s.sendall(b"x")

            def ok(self):
                with socket.create_connection(("h", 1)) as s:
                    s.sendall(b"x")
    """)
    fired = [f for f in res.findings
             if f.rule == "blocking-call-under-lock"]
    assert len(fired) == 1 and fired[0].scope.endswith(".bad")


def test_lock_order_cycle_fires(tmp_path):
    res = lint_src(tmp_path, """
        import threading

        class B:
            def __init__(self):
                self.lock_a = threading.Lock()
                self.lock_b = threading.Lock()

            def one(self):
                with self.lock_a:
                    with self.lock_b:
                        pass

            def two(self):
                with self.lock_b:
                    with self.lock_a:
                        pass
    """)
    assert "lock-order-cycle" in rules_of(res)


def test_consistent_lock_order_quiet(tmp_path):
    res = lint_src(tmp_path, """
        import threading

        class B:
            def __init__(self):
                self.lock_a = threading.Lock()
                self.lock_b = threading.Lock()

            def one(self):
                with self.lock_a:
                    with self.lock_b:
                        pass

            def two(self):
                with self.lock_a:
                    with self.lock_b:
                        pass
    """)
    assert "lock-order-cycle" not in rules_of(res)


def test_unlocked_global_mutation_fires(tmp_path):
    res = lint_src(tmp_path, """
        import threading

        _events = []
        _lock = threading.Lock()

        def worker():
            _events.append(1)

        def worker_ok():
            with _lock:
                _events.append(1)

        threading.Thread(target=worker).start()
        threading.Thread(target=worker_ok).start()
    """)
    fired = [f for f in res.findings
             if f.rule == "unlocked-global-mutation"]
    assert len(fired) == 1 and fired[0].scope.endswith(":worker")


# ---------------------------------------------------------------------------
# Pragmas + baseline workflow
# ---------------------------------------------------------------------------
BAD_SLEEP = """
    import threading
    import time

    class A:
        def __init__(self):
            self._lock = threading.Lock()

        def bad(self):
            with self._lock:
                time.sleep(1.0){pragma}
"""


def test_inline_pragma_suppresses(tmp_path):
    noisy = lint_src(tmp_path, BAD_SLEEP.format(pragma=""))
    assert "blocking-call-under-lock" in rules_of(noisy)
    quiet = lint_src(
        tmp_path, BAD_SLEEP.format(
            pragma="  # graftlint: disable=blocking-call-under-lock"),
        name="snippet2.py")
    assert "blocking-call-under-lock" not in rules_of(quiet)


def test_file_pragma_and_wildcard(tmp_path):
    src = "# graftlint: disable-file=blocking-call-under-lock\n" \
        + textwrap.dedent(BAD_SLEEP.format(pragma=""))
    p = tmp_path / "filelevel.py"
    p.write_text(src, encoding="utf-8")
    res = run_lint([str(p)])
    assert "blocking-call-under-lock" not in rules_of(res)
    src2 = textwrap.dedent(BAD_SLEEP.format(
        pragma="  # graftlint: disable=*"))
    p2 = tmp_path / "wildcard.py"
    p2.write_text(src2, encoding="utf-8")
    assert "blocking-call-under-lock" not in rules_of(run_lint([str(p2)]))


def test_baseline_round_trip(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text(textwrap.dedent(BAD_SLEEP.format(pragma="")),
                 encoding="utf-8")
    res = run_lint([str(p)])
    assert res.findings and res.new == res.findings
    bl = tmp_path / "baseline.json"
    write_baseline(str(bl), res.findings)
    res2 = run_lint([str(p)], baseline_path=str(bl))
    assert res2.findings and not res2.new        # fully baselined
    # a NEW finding (another blocking call) is not covered
    extra = ("\n    def bad2(self):\n"
             "        with self._lock:\n"
             "            time.sleep(2.0)\n")
    p.write_text(p.read_text() + extra, encoding="utf-8")
    res3 = run_lint([str(p)], baseline_path=str(bl))
    assert len(res3.new) == 1
    # line drift does NOT invalidate the baseline (key is line-free)
    moved = "x = 1\n" + textwrap.dedent(BAD_SLEEP.format(pragma=""))
    p.write_text(moved, encoding="utf-8")
    res4 = run_lint([str(p)], baseline_path=str(bl))
    assert not res4.new


def test_baseline_counts_ratchet():
    f = lambda: Finding("r", "a.py", 3, 0, "m", scope="s", snippet="x()")
    two = [f(), f()]
    bl = {two[0].key(): 1}
    new, stale = baseline_diff(two, bl)
    assert len(new) == 1                         # second copy is new
    new, stale = baseline_diff([f()], {f().key(): 2})
    assert not new and stale                     # over-budgeted -> stale


# ---------------------------------------------------------------------------
# Self-hosting: the CI gate
# ---------------------------------------------------------------------------
def test_whole_package_clean_vs_baseline_under_30s():
    t0 = time.perf_counter()
    res = run_lint([PKG], baseline_path=BASELINE)
    wall = time.perf_counter() - t0
    assert wall < 30.0, f"graftlint took {wall:.1f}s on the package"
    assert res.files > 100
    msg = "\n".join(f.render() for f in res.new)
    assert not res.new, f"new graftlint findings (fix or baseline):\n{msg}"
    # the three fixed bugs must STAY fixed (no baseline entry hides them)
    for key in load_baseline(BASELINE):
        assert "hot-loop-sync" not in key, key
        assert "blocking-call-under-lock" not in key, key


def test_cli_metrics_mode():
    from deeplearning4j_tpu.analysis.cli import lint_metrics, main
    m = lint_metrics([PKG], baseline=BASELINE)
    assert m["new"] == 0 and m["total"] >= 0 and m["files"] > 100
    import contextlib
    import io
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main([PKG, "--baseline", BASELINE, "--metrics"])
    assert rc == 0
    text = buf.getvalue()
    # the findings family is always declared; labeled samples only exist
    # while findings do (the ISSUE-12 burn-down emptied the baseline, so
    # a clean tree legitimately has zero)
    assert "dl4j_lint_findings_total" in text
    if m["total"]:
        assert "dl4j_lint_findings_total{" in text
    assert "dl4j_lint_files_total" in text


def test_cli_exit_codes(tmp_path):
    from deeplearning4j_tpu.analysis.cli import main
    p = tmp_path / "bad.py"
    p.write_text(textwrap.dedent(BAD_SLEEP.format(pragma="")),
                 encoding="utf-8")
    import contextlib
    import io
    with contextlib.redirect_stdout(io.StringIO()):
        assert main([str(p), "--no-baseline"]) == 1
        bl = tmp_path / "bl.json"
        assert main([str(p), "--baseline", str(bl),
                     "--write-baseline"]) == 0
        assert main([str(p), "--baseline", str(bl)]) == 0
        assert main([str(tmp_path / "missing.py")]) == 2
    # review regression: a rule-filtered run must NEVER overwrite the
    # baseline (it would erase every other rule's accepted entries)
    with pytest.raises(SystemExit):
        with contextlib.redirect_stdout(io.StringIO()), \
                contextlib.redirect_stderr(io.StringIO()):
            main([str(p), "--baseline", str(bl), "--rules",
                  "jit-in-loop", "--write-baseline"])
    assert load_baseline(str(bl))                # untouched


# ---------------------------------------------------------------------------
# Runtime sanitizer
# ---------------------------------------------------------------------------
def test_thread_watchdog_catches_leak():
    stop = threading.Event()
    with pytest.raises(ThreadLeakError, match="leaky-worker"):
        with sanitize(thread_watchdog=True, lock_order=False,
                      grace_s=0.2):
            threading.Thread(target=stop.wait, name="leaky-worker",
                             daemon=True).start()
    stop.set()


def test_thread_watchdog_passes_joined_threads():
    with sanitize(thread_watchdog=True, lock_order=False, grace_s=2.0):
        t = threading.Thread(target=lambda: None)
        t.start()
        t.join()


def test_lock_order_shim_detects_inversion():
    from deeplearning4j_tpu.analysis.sanitizer import (LockOrderWatch,
                                                       OrderCheckedLock)
    watch = LockOrderWatch()
    a = OrderCheckedLock(threading.Lock(), "A", watch)
    b = OrderCheckedLock(threading.Lock(), "B", watch)
    with a:
        with b:
            pass
    with b:
        with a:                       # inversion of the recorded order
            pass
    assert watch.violations and "A" in watch.violations[0]


def test_sanitize_raises_lock_order_error():
    """Inverted acquisition on the serving plane's wrapped locks is
    caught by the sanitizer's own watch and raised at block exit."""
    from deeplearning4j_tpu.serving.registry import ModelRegistry, _Entry
    with pytest.raises(LockOrderError):
        with sanitize(thread_watchdog=False, lock_order=True):
            reg = ModelRegistry()
            entry = _Entry()
            with reg._lock:
                with entry.swap_lock:
                    pass
            with entry.swap_lock:
                with reg._lock:          # inversion
                    pass


def test_sanitize_wraps_serving_registry_locks():
    from deeplearning4j_tpu.analysis.sanitizer import OrderCheckedLock
    from deeplearning4j_tpu.serving.registry import ModelRegistry
    with sanitize(thread_watchdog=False, lock_order=True):
        reg = ModelRegistry()
        assert isinstance(reg._lock, OrderCheckedLock)
        assert reg.names() == []      # proxy works as a context manager
    reg2 = ModelRegistry()
    assert not isinstance(reg2._lock, OrderCheckedLock)  # patch restored


def test_sanitize_restores_jax_flags():
    import jax
    before = bool(jax.config.jax_check_tracer_leaks)
    with sanitize(tracer_leaks=True, thread_watchdog=False,
                  lock_order=False):
        assert bool(jax.config.jax_check_tracer_leaks) is True
    assert bool(jax.config.jax_check_tracer_leaks) == before


@pytest.mark.sanitize(tracer_leaks=True)
def test_sanitize_marker_smoke():
    """The conftest marker wires the sanitizer around this test: a small
    jitted computation under tracer-leak checking + thread watchdog."""
    import jax
    import jax.numpy as jnp
    out = jax.jit(lambda x: jnp.sum(x * 2))(jnp.arange(8.0))
    assert float(out) == 56.0
    t = threading.Thread(target=lambda: None)
    t.start()
    t.join()


# ---------------------------------------------------------------------------
# Regression tests for the real bugs graftlint surfaced
# ---------------------------------------------------------------------------
class _CountingScoreModel:
    """Stands in for a network in the listener chain: counts how often a
    listener forces score materialization."""

    last_batch_size = 32
    epoch_count = 0

    def __init__(self):
        self.score_calls = 0

    def score(self):
        self.score_calls += 1
        return 1.25


def test_telemetry_listener_score_sync_gated_on_window():
    """Regression (rule: hot-loop-sync): TelemetryListener must NOT call
    float(model.score()) — a device->host sync — on every iteration;
    only on the report window."""
    from deeplearning4j_tpu.telemetry import TelemetrySession
    from deeplearning4j_tpu.telemetry.listener import TelemetryListener
    from deeplearning4j_tpu.telemetry import runtime as tel_runtime

    sess = TelemetrySession(report_window=10)
    with tel_runtime.enabled(sess):
        listener = TelemetryListener(session=sess)
        model = _CountingScoreModel()
        for it in range(1, 31):
            listener.iteration_done(model, it)
    assert model.score_calls == 3, \
        f"score() pulled {model.score_calls}x in 30 iters (expected 3: " \
        "once per report_window=10) — per-step host sync regressed"
    # and the gauge still updates on the window
    assert sess.registry.get("dl4j_score").value() == 1.25
    # the static rule agrees: no hot-loop-sync finding in the listener
    res = run_lint([os.path.join(PKG, "telemetry", "listener.py")])
    assert "hot-loop-sync" not in rules_of(res)


def test_timesource_refresh_never_runs_under_lock():
    """Regression (rule: blocking-call-under-lock): offset_ms must not
    hold the lock across the NTP socket exchange."""
    from deeplearning4j_tpu.parallel.timesource import (
        CoordinatorTimeSource, TimeServer)

    with TimeServer() as srv:
        src = CoordinatorTimeSource(srv.host, srv.port,
                                    frequency_sec=10_000, samples=1)
        orig = src._refresh
        seen = []

        def checked_refresh():
            seen.append(src._lock.locked())
            orig()

        src._refresh = checked_refresh
        src._offset = None                 # force the defensive path
        assert isinstance(src.offset_ms(), float)
        assert seen == [False], \
            "offset_ms ran the network refresh while holding its lock"
        # stale-offset path: background refresh, caller returns promptly
        with src._lock:
            src._measured_at = float("-inf")
        t0 = time.perf_counter()
        src.offset_ms()
        assert time.perf_counter() - t0 < 2.0
        for _ in range(200):               # let the bg thread finish
            if not src._refreshing:
                break
            time.sleep(0.01)
        assert seen.count(False) == len(seen)
    # the static rule agrees
    res = run_lint([os.path.join(PKG, "parallel", "timesource.py")])
    assert "blocking-call-under-lock" not in rules_of(res)


def test_remote_router_put_update_never_blocks_behind_slow_drain():
    """Regression (rule: blocking-call-under-lock): a training thread's
    put_update must not stall behind another thread's slow HTTP POST;
    the active drainer delivers the late enqueue instead."""
    from deeplearning4j_tpu.ui.remote import RemoteUIStatsStorageRouter

    router = RemoteUIStatsStorageRouter("http://127.0.0.1:9")
    posted = []
    in_post, release = threading.Event(), threading.Event()

    def fake_post(payload):
        posted.append(payload["worker"])
        if len(posted) == 1:
            in_post.set()
            assert release.wait(5.0)
        return True

    router._post = fake_post
    t = threading.Thread(
        target=lambda: router.put_update("s", "t", "w1", 1.0, {}),
        daemon=True)
    t.start()
    assert in_post.wait(5.0)
    t0 = time.perf_counter()
    router.put_update("s", "t", "w2", 2.0, {})   # must NOT block
    assert time.perf_counter() - t0 < 1.0, \
        "put_update blocked behind another caller's POST"
    release.set()
    t.join(timeout=5.0)
    for _ in range(200):
        if len(posted) == 2 and not router.pending:
            break
        time.sleep(0.01)
    assert posted == ["w1", "w2"]        # order preserved, both delivered
    res = run_lint([os.path.join(PKG, "ui", "remote.py")])
    assert "blocking-call-under-lock" not in rules_of(res)


# ---------------------------------------------------------------------------
# IR tier (ISSUE 13): jaxpr/HLO verification of jit entry points
# ---------------------------------------------------------------------------
def _ir():
    from deeplearning4j_tpu.analysis import ir
    return ir


def _probes():
    from deeplearning4j_tpu.analysis import ir_probes
    return ir_probes


def _zero_mod():
    from deeplearning4j_tpu.parallel import zero
    return zero


def test_ir_selfhost_clean():
    """The IR-tier CI gate: every probe-built jit entry point (both model
    families, replicated/ZeRO-1/ZeRO-2 trainer steps, the ZeRO accum
    superstep, serving's AOT executables) traces, lowers and compiles on
    the virtual 8-device mesh and comes in clean against the
    `ir_findings` baseline section. How long the pass may take is held
    by `./runtests.sh lint`, which runs it alone: inside a suite of six
    workers a wall clock counts the neighbours."""
    ir = _ir()
    entries = _probes().build_entries()
    res = ir.run_ir_lint(entries, baseline_path=BASELINE)
    assert res.files >= 8, f"only {res.files} IR entries probed"
    msg = "\n".join(f.render() for f in res.new)
    assert not res.new, f"new IR findings (fix or baseline):\n{msg}"
    # the roster ledger saw the probes' entry points (weakrefs stay live
    # while `entries` holds the jitted fns)
    from deeplearning4j_tpu.telemetry.compile_watch import roster_names
    assert {"nn/train_step", "parallel/zero_step"} <= set(roster_names())
    del entries


def test_ir_dropped_shard_constraint_caught(monkeypatch):
    """Seeded mutation (acceptance): drop a `with_sharding_constraint`
    in zero.py — the traced program then carries fewer constraints than
    the plan's declared layout schedule and ir-implicit-reshard fires."""
    ir, probes, zmod = _ir(), _probes(), _zero_mod()
    monkeypatch.setattr(zmod._ZeroPlan, "constrain_params",
                        lambda self, t: t)
    from deeplearning4j_tpu.parallel.trainer import ShardingStrategy
    entry = probes._trainer_entry(ShardingStrategy.ZERO2,
                                  "parallel/zero2_step", bucket_mb=0.0005)
    found = ir.analyze_entry(entry)
    hits = [f for f in found if f.rule == "ir-implicit-reshard"
            and f.snippet.endswith(":constraints")]
    assert len(hits) == 1, [f.render() for f in found]
    assert "dropped" in hits[0].message


def test_ir_implicit_gspmd_reshard_caught(monkeypatch):
    """Seeded mutation (acceptance): the ZeRO moment shards materialized
    REPLICATED (the classic silent GSPMD reshard) — the plan's
    `constrain_opt` returns the replicated tree, so the compiler has no
    later constraint to fold it into, the compiled program all-gathers
    both moment trees (3,348 collective bytes against 1,680 declared)
    and ir-implicit-reshard fires on the bytes."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    ir, probes, zmod = _ir(), _probes(), _zero_mod()

    def replicated(self, tree):
        repl = NamedSharding(probes.virtual_mesh(), P())
        return jax.tree_util.tree_map(
            lambda v: jax.lax.with_sharding_constraint(v, repl), tree)

    monkeypatch.setattr(zmod._ZeroPlan, "constrain_opt", replicated)
    from deeplearning4j_tpu.parallel.trainer import ShardingStrategy
    entry = probes._trainer_entry(ShardingStrategy.ZERO2,
                                  "parallel/zero2_step", bucket_mb=0.0005)
    found = ir.analyze_entry(entry)
    hits = [f for f in found if f.rule == "ir-implicit-reshard"
            and f.snippet.endswith(":bytes")]
    assert len(hits) == 1, [f.render() for f in found]
    assert "declared" in hits[0].message


def test_ir_unaliased_donation_caught_and_clean_quiet():
    """Seeded mutation (acceptance): donate a buffer XLA cannot alias
    (dtype matches no output) -> ir-ineffective-donation; the same shape
    with a matching output stays quiet."""
    import jax
    import jax.numpy as jnp

    ir = _ir()

    def bad(p, x):
        return (p * 2).astype(jnp.bfloat16), jnp.sum(x)

    def good(p, x):
        return p * 2, jnp.sum(x)

    z = jnp.zeros(32, jnp.float32)
    fired = ir.analyze_entry(ir.IrEntry(
        "test/unaliased", "test.py",
        fn=jax.jit(bad, donate_argnums=(0,)), args=(z, z)))
    assert [f.rule for f in fired] == ["ir-ineffective-donation"]
    quiet = ir.analyze_entry(ir.IrEntry(
        "test/aliased", "test.py",
        fn=jax.jit(good, donate_argnums=(0,)), args=(z, z)))
    assert not [f for f in quiet if f.rule == "ir-ineffective-donation"]
    # review regression: donation attribute on a NON-leading arg must be
    # attributed to that arg, not smeared onto earlier args by a
    # span-crossing match — donate_argnums=(1,) is aliased and quiet
    def good_second(x, p):
        return p * 2, jnp.sum(x)

    jitted = jax.jit(good_second, donate_argnums=(1,))
    lowered = jitted.trace(z, z).lower()
    assert ir.donated_params(lowered.as_text()) == {1}
    quiet2 = ir.analyze_entry(ir.IrEntry(
        "test/aliased-second", "test.py", fn=jitted, args=(z, z)))
    assert not [f for f in quiet2 if f.rule == "ir-ineffective-donation"]


def test_ir_collective_order_divergence_caught():
    """Seeded mutation (acceptance): two per-process programs issuing
    the same collectives in different order — the divergence the elastic
    resize drills must never produce. The same digest format serves the
    static pass, per-process program texts, and the runtime hasher."""
    ir = _ir()
    seq_a = [("all-reduce", "f32[64]", "[1,8]<=[8]"),
             ("all-gather", "f32[64]", "[1,8]<=[8]")]
    seq_b = list(reversed(seq_a))
    msg = ir.check_cross_program_order([seq_a, seq_b])
    assert msg is not None and "diverges at collective 0" in msg
    assert ir.check_cross_program_order([seq_a, list(seq_a)]) is None
    assert ir.sequence_digest(seq_a) != ir.sequence_digest(seq_b)
    assert ir.sequence_digest(seq_a) == ir.sequence_digest(tuple(seq_a))
    # truncated program (a process that lost a collective entirely)
    msg2 = ir.check_cross_program_order([seq_a, seq_a[:1]])
    assert msg2 is not None and "issues 1 collectives" in msg2


def test_ir_nondeterministic_reduction_caught():
    """Seeded mutation: ZeroConfig(ordered_flush=False) removes the
    optimization_barrier token chain from the accum superstep — a
    bit-exact-asserted entry with unordered bucketed float reductions
    must trip ir-nondeterministic-reduction (the ordered default stays
    quiet via the self-host gate)."""
    ir, probes = _ir(), _probes()
    entry = probes.zero_accum_entry(ordered_flush=False)
    found = ir.analyze_entry(entry)
    assert "ir-nondeterministic-reduction" in {f.rule for f in found}, \
        [f.render() for f in found]


def test_ir_mesh2d_family_clean_and_contracted():
    """The 2-D (data, model) train-step family (ISSUE 14): the DP×TP and
    ZERO1×TP steps on both reshapes of the 8-device mesh lint clean, and
    the ZeRO entries carry the extended per-axis contract (data budget =
    the plan's declared optimizer payload, model budget = the paired TP
    step's measured activation traffic, plus the constraint schedule)."""
    ir, probes = _ir(), _probes()
    entries = probes.mesh2d_entries()
    assert {e.name for e in entries} == {
        "parallel/tp_step_2x4", "parallel/zero1_tp_step_2x4",
        "parallel/tp_step_4x2", "parallel/zero1_tp_step_4x2"}
    for e in entries:
        found = ir.analyze_entry(e)
        assert not found, [f.render() for f in found]
        if e.name.startswith("parallel/zero1_tp"):
            assert e.declared_bytes_by_axis is not None
            assert e.declared_bytes_by_axis["data"] > 0
            # the whole-mesh bucket is budgeted too: a rematerialization
            # gathered over BOTH axes must not escape the byte check
            assert "other" in e.declared_bytes_by_axis
            assert e.expected_constraints and e.expected_constraints > 0
            assert set(e.axis_sizes) == {"data", "model"}


def test_ir_mesh2d_dropped_constraint_caught():
    """Seeded mutation (ISSUE 14 satellite): the 2-D step without its
    constrain_params/constrain_opt schedule carries fewer traced
    sharding_constraints than the plan declares — ir-implicit-reshard
    fires on the constraint half."""
    ir, probes = _ir(), _probes()
    entry = probes.mesh2d_zero1_tp_entry((2, 4),
                                         mutate="drop_constraints")
    found = ir.analyze_entry(entry)
    hits = [f for f in found if f.rule == "ir-implicit-reshard"
            and f.snippet.endswith(":constraints")]
    assert len(hits) == 1, [f.render() for f in found]


def test_ir_mesh2d_dropped_model_axis_caught():
    """Seeded mutation (ISSUE 14 satellite): constraints that keep their
    COUNT but lose the `model` axis (data-only specs) force GSPMD to
    materialize the model-sharded params across the mesh inside the step
    — the per-axis byte check fires (the full rematerialization lands as
    excess collective traffic on one of the declared axes)."""
    ir, probes = _ir(), _probes()
    tp_entry, model_budget, other_budget = probes._mesh2d_tp_entry((2, 4))
    entry = probes.mesh2d_zero1_tp_entry((2, 4), model_budget=model_budget,
                                         other_budget=other_budget,
                                         mutate="drop_model_axis")
    found = ir.analyze_entry(entry)
    hits = [f for f in found if f.rule == "ir-implicit-reshard"
            and ":bytes:" in f.snippet]
    assert hits, [f.render() for f in found]


def test_ir_per_axis_byte_classification():
    """measured_collective_bytes_by_axis attributes collectives to mesh
    axes by replica-group size, parsing BOTH HLO group syntaxes; sizes
    matching no axis (or an ambiguous d == m pair) land under 'other'."""
    ir = _ir()
    text = "\n".join([
        "  %ar1 = f32[64]{0} all-reduce(f32[64]{0} %p0), "
        "replica_groups={{0,4},{1,5},{2,6},{3,7}}, to_apply=%add",
        "  %ag1 = f32[128]{0} all-gather(f32[32]{0} %p1), "
        "replica_groups=[2,4]<=[8], dimensions={0}",
        "  %ar2 = f32[16]{0} all-reduce(f32[16]{0} %p2), "
        "replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add",
    ])
    by_axis = ir.measured_collective_bytes_by_axis(
        text, {"data": 2, "model": 4})
    assert by_axis["data"] == {"all-reduce": 256}       # groups of 2
    assert by_axis["model"] == {"all-gather": 512}      # groups of 4
    assert by_axis["other"] == {"all-reduce": 64}       # global (size 8)
    # ambiguous mesh (d == m): everything falls to "other", so the
    # per-axis check cannot silently mis-attribute
    amb = ir.measured_collective_bytes_by_axis(text, {"data": 4,
                                                      "model": 4})
    assert "data" not in amb and "model" not in amb


def test_ir_redundant_reshard_and_invalid_axis_caught():
    """psum_scatter immediately all-gathered back fires the redundant-
    reshard pair rule (jaxpr AND compiled-text detectors); a collective
    over an axis the entry's mesh does not define fires ir-invalid-axis."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    ir, probes = _ir(), _probes()
    mesh = probes.virtual_mesh()

    def body(x):
        s = jax.lax.psum_scatter(x, "data", scatter_dimension=0, tiled=True)
        return jax.lax.all_gather(s, "data", axis=0, tiled=True)

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("data"),
                           out_specs=P("data")))
    z = jnp.zeros(64, jnp.float32)
    found = ir.analyze_entry(ir.IrEntry(
        "test/reshard", "test.py", fn=fn, args=(z,), mesh_axes=("data",)))
    assert "ir-redundant-reshard" in {f.rule for f in found}
    found2 = ir.analyze_entry(ir.IrEntry(
        "test/axis", "test.py", fn=fn, args=(z,), mesh_axes=("model",)))
    assert "ir-invalid-axis" in {f.rule for f in found2}


def test_ir_async_collective_pairs_counted_once():
    """Review regression: async backends emit -start/-done pairs for one
    collective — the sequence and byte accounting must count the pair
    once (at -start), or every async collective doubles the measured
    payload and trips the byte budget spuriously."""
    ir = _ir()
    text = (
        "  %ar = f32[64]{0} all-reduce-start(f32[64]{0} %p0), "
        "channel_id=1, replica_groups=[1,8]<=[8]\n"
        "  %ard = f32[64]{0} all-reduce-done(f32[64]{0} %ar)\n"
        "  %ag = f32[128]{0} all-gather(f32[16]{0} %x), channel_id=2, "
        "replica_groups=[1,8]<=[8], dimensions={0}\n")
    seq = ir.collective_sequence(text)
    assert [op for op, _, _ in seq] == ["all-reduce", "all-gather"]
    bytes_by_op = ir.measured_collective_bytes(text)
    assert bytes_by_op == {"all-reduce": 256, "all-gather": 512}


def test_ir_single_device_backend_refused():
    """Review regression: on a 1-device backend the virtual mesh
    degenerates and a 'clean' IR run verifies nothing — run_ir_lint must
    refuse loudly (and the CLI turn it into exit 2), never exit 0."""
    import subprocess

    code = (
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import sys; sys.path.insert(0, %r)\n"
        "assert jax.device_count() == 1, jax.device_count()\n"
        "from deeplearning4j_tpu.analysis.ir import run_ir_lint\n"
        "try:\n"
        "    run_ir_lint(entries=[])\n"
        "except RuntimeError as e:\n"
        "    assert 'multi-device' in str(e), e\n"
        "else:\n"
        "    raise SystemExit('run_ir_lint accepted a 1-device backend')\n"
        "from deeplearning4j_tpu.analysis.cli import main\n"
        "rc = main([%r, '--ir'])\n"
        "assert rc == 2, rc\n" % (REPO, PKG))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=180,
                          env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_ir_baseline_section_roundtrip(tmp_path):
    """The `ir_findings` baseline section ratchets independently of the
    AST section: writing one never clobbers the other, and a baselined
    IR finding stops failing the run."""
    ir = _ir()
    bl = tmp_path / "bl.json"
    ast_finding = Finding("jit-in-loop", "a.py", 1, 0, "m", scope="s",
                          snippet="jax.jit(f)")
    write_baseline(str(bl), [ast_finding])                  # AST section
    ir_finding = ir.IrEntry("e", "p.py").finding(
        "ir-implicit-reshard", "msg", "bytes")
    write_baseline(str(bl), [ir_finding], section=ir.IR_BASELINE_SECTION)
    assert load_baseline(str(bl)) == {ast_finding.key(): 1}  # preserved
    assert load_baseline(str(bl), section=ir.IR_BASELINE_SECTION) == {
        ir_finding.key(): 1}
    res = ir.run_ir_lint(entries=[], baseline_path=str(bl))
    assert not res.new and res.stale_baseline == [ir_finding.key()]


def test_cli_ir_exit_codes(monkeypatch):
    """`--ir` exit-code contract: 0 on the clean roster, 1 when a seeded
    zero.py mutation introduces a non-baselined IR finding."""
    import contextlib
    import io

    from deeplearning4j_tpu.analysis.cli import main

    zmod = _zero_mod()
    with contextlib.redirect_stdout(io.StringIO()):
        assert main([PKG, "--ir", "--baseline", BASELINE]) == 0
    monkeypatch.setattr(zmod._ZeroPlan, "constrain_params",
                        lambda self, t: t)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert main([PKG, "--ir", "--baseline", BASELINE]) == 1
    assert "ir-implicit-reshard" in buf.getvalue()


def test_cli_ir_metrics_mode():
    from deeplearning4j_tpu.analysis.cli import ir_lint_metrics

    m = ir_lint_metrics([PKG], baseline=BASELINE)
    assert m["new"] == 0 and m["entries"] >= 8 and m["wall_s"] > 0
    assert m["roster"] >= 2      # watch_compiles ledger populated


# ---------------------------------------------------------------------------
# Runtime collective-sequence hash (the dynamic half of the order check)
# ---------------------------------------------------------------------------
def test_collective_hasher_digests():
    from deeplearning4j_tpu.analysis.sanitizer import (
        CollectiveSequenceHasher, collective_hashes_agree)

    a, b, c = (CollectiveSequenceHasher() for _ in range(3))
    for h in (a, b):
        h.record("reduce_scatter", 832, n=2)
        h.record("all_gather", 832)
        h.end_step()
    c.record("all_gather", 832)              # different issue order
    c.record("reduce_scatter", 832, n=2)
    c.end_step()
    assert a.step_digests == b.step_digests
    assert a.digest() == b.digest()
    assert a.step_digests != c.step_digests
    assert a.digest() != c.digest()
    # empty steps do not emit digests
    a.end_step()
    assert len(a.step_digests) == 1
    assert collective_hashes_agree(a)        # single-process: trivially true


@pytest.mark.sanitize(collective_hash=True, lock_order=False)
def test_collective_hash_hook_observes_zero_training(request):
    """sanitize(collective_hash=True) + a ZeRO-2 trainer fit: every
    optimizer step hashes its collective issue schedule, the per-step
    digests are identical across steps (same plan, same bucket layout —
    what the multi-host kill/rejoin drills compare across processes),
    and a superstep WINDOW emits the same one-digest-per-optimizer-step
    stream as per-batch dispatch — with no telemetry session active
    (review regression: the windowed path skipped the hasher)."""
    from deeplearning4j_tpu.analysis.sanitizer import (
        current_collective_hasher)
    from deeplearning4j_tpu.analysis.ir_probes import tiny_mlp
    from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator
    from deeplearning4j_tpu.parallel.trainer import (ParallelTrainer,
                                                     ShardingStrategy)

    h = current_collective_hasher()
    assert h is not None        # installed by the sanitize marker
    x = np.random.default_rng(0).normal(size=(32, 8)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[np.arange(32) % 4]
    tr = ParallelTrainer(tiny_mlp(), strategy=ShardingStrategy.ZERO2,
                         zero_bucket_mb=0.0005)
    tr.fit(ArrayDataSetIterator(x, y, batch_size=16), epochs=1)
    assert len(h.step_digests) == 2           # one digest per step
    assert len(set(h.step_digests)) == 1      # identical schedule per step
    per_batch = list(h.step_digests)
    # one 2-step superstep window must produce the identical stream
    tr2 = ParallelTrainer(tiny_mlp(), strategy=ShardingStrategy.ZERO2,
                          zero_bucket_mb=0.0005)
    tr2.fit(ArrayDataSetIterator(x, y, batch_size=16), epochs=1,
            superstep=2)
    assert h.step_digests == per_batch * 2, h.step_digests


def test_ir_elastic_restore_clean_and_rostered():
    """The elastic-restore re-placement probe (ISSUE 19): landing
    replicated host trees onto the ZeRO-1 x TP shards is pure slicing —
    zero collective bytes on every axis (the declared budgets are the
    1KiB slack floor) — and the entry rides the self-host roster."""
    ir, probes = _ir(), _probes()
    entries = probes.elastic_entries()
    assert {e.name for e in entries} == {"parallel/elastic_restore_2x4"}
    for e in entries:
        found = ir.analyze_entry(e)
        assert not found, [f.render() for f in found]
        assert e.declared_bytes_by_axis == {"data": 0, "model": 0,
                                            "other": 0}
    assert any(e.name.startswith("parallel/elastic_restore")
               for e in probes.build_entries())


def test_ir_elastic_restore_gather_mutation_caught():
    """Seeded mutation (ISSUE 19 acceptance): invert the restore —
    sharded inputs, replicated out_shardings — and the identity step
    compiles to all-gathers (a resize that re-materializes every shard
    on every device); the per-axis byte budgets fire."""
    ir, probes = _ir(), _probes()
    entry = probes.elastic_restore_entry(mutate="gather_replicated")
    found = ir.analyze_entry(entry)
    hits = [f for f in found if f.rule == "ir-implicit-reshard"
            and ":bytes:" in f.snippet]
    assert hits, [f.render() for f in found]
    with pytest.raises(ValueError, match="unknown mutation"):
        probes.elastic_restore_entry(mutate="bogus")
