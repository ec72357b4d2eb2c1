"""Invariant guard plane (analysis/): the static checker `rtfd lint` and
the dynamic lock-order watcher.

Three layers:

1. **Seeded-violation corpus** — one minimal bad snippet per rule proves
   every rule actually fires (with the right file/line), plus stale- and
   unknown-pragma cases. No bad code ever exists on disk: the corpus goes
   through ``lint_source``.
2. **Tree enforcement** — the committed tree must be clean. This is the
   tier-1 gate: a new bare wall-clock read in a virtual-clock subsystem,
   a d2h pull in a pre-pull-safe module, a dishonest counter mirror, or
   an unlocked param mutation fails the suite here with the linter's own
   pointed message.
3. **Lockwatch** — unit pins (a deliberately inverted two-lock order must
   be detected as a cycle; a device wait under a held lock must be a
   violation) and the real thing: all six deterministic drills run clean
   under the instrumented locks.
"""

import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import realtime_fraud_detection_tpu
from realtime_fraud_detection_tpu.analysis import (
    LockWatcher,
    lint_paths,
    lint_source,
    watch_locks,
)
from realtime_fraud_detection_tpu.analysis.lockwatch import (
    LOCKWATCH_DRILLS,
    WatchedLock,
    run_drill_watched,
)

PKG_ROOT = Path(realtime_fraud_detection_tpu.__file__).parent
REPO_ROOT = PKG_ROOT.parent


def rules_of(findings):
    return sorted({f.rule for f in findings})


def lines_of(findings, rule):
    return sorted(f.line for f in findings if f.rule == rule)


# ---------------------------------------------------------------------------
# seeded-violation corpus: every rule fires, with the right file/line
# ---------------------------------------------------------------------------

class TestWallClockRule:
    def test_bare_wall_clock_in_scoped_subsystem_fires(self):
        src = ("import time\n"
               "def f():\n"
               "    return time.monotonic()\n")
        findings = lint_source(src, "qos/bad.py")
        assert rules_of(findings) == ["wall-clock"]
        assert lines_of(findings, "wall-clock") == [3]
        assert "qos/" in findings[0].message

    def test_injected_default_reference_is_not_a_call(self):
        src = ("import time\n"
               "def f(clock=time.monotonic):\n"
               "    return clock()\n")
        assert lint_source(src, "tuning/ok.py") == []

    def test_out_of_scope_subsystem_is_exempt(self):
        src = ("import time\n"
               "def f():\n"
               "    return time.perf_counter()\n")
        assert lint_source(src, "utils/whatever.py") == []

    def test_time_alias_and_from_import_are_seen(self):
        src = ("import time as _t\n"
               "from time import monotonic\n"
               "def f():\n"
               "    return _t.time() + monotonic()\n")
        findings = lint_source(src, "stream/bad.py")
        assert lines_of(findings, "wall-clock") == [4, 4]

    def test_datetime_now_is_wall_clock(self):
        src = ("from datetime import datetime\n"
               "def f():\n"
               "    return datetime.now()\n")
        assert lines_of(lint_source(src, "sim/bad.py"), "wall-clock") == [3]


class TestD2hRule:
    SRC = ("import numpy as np\n"
           "import jax\n"
           "def f(x):\n"
           "    a = np.asarray(x)\n"
           "    b = jax.device_get(x)\n"
           "    c = x.item()\n"
           "    d = float(x)\n"
           "    return a, b, c, d\n")

    def test_all_four_pull_shapes_fire_in_scoped_module(self):
        findings = lint_source(self.SRC, "scoring/host_pipeline.py")
        assert rules_of(findings) == ["d2h"]
        assert lines_of(findings, "d2h") == [4, 5, 6, 7]

    def test_unscoped_module_is_exempt(self):
        assert lint_source(self.SRC, "features/anything.py") == []

    def test_quant_calibrator_is_in_scope(self):
        """ISSUE 9: models/quant.py joined D2H_MODULES — its host-side
        calibration sites must carry justified pragmas, and anything
        unexplained reads as a dispatch-path pull."""
        findings = lint_source(self.SRC, "models/quant.py")
        assert rules_of(findings) == ["d2h"]
        assert lines_of(findings, "d2h") == [4, 5, 6, 7]

    def test_scorer_dispatch_scope_is_function_level(self):
        src = ("import numpy as np\n"
               "class FraudScorer:\n"
               "    def dispatch_assembled(self, x):\n"
               "        return np.asarray(x)\n"
               "    def finalize(self, x):\n"
               "        return np.asarray(x)\n")
        findings = lint_source(src, "scoring/scorer.py")
        # dispatch half checked; finalize is the designated pull point
        assert lines_of(findings, "d2h") == [4]

    def test_block_until_ready_is_allowed(self):
        src = ("import jax\n"
               "def f(x):\n"
               "    jax.block_until_ready(x)\n")
        assert lint_source(src, "scoring/device_pool.py") == []


METRICS_SRC = (
    "class MetricsCollector:\n"
    "    def __init__(self, r):\n"
    "        self.foo = r.counter('foo_total', 't')\n"
    "        self.dead = r.counter('dead_total', 't')\n"
    "        self.bad = r.counter('badName', 't')\n"
    "        self.g = r.gauge('oops_total', 't')\n"
    "    def sync_foo(self):\n"
    "        self.foo.inc(1)\n")


class TestMetricsRule:
    def test_name_conventions(self):
        findings = lint_source(METRICS_SRC, "obs/metrics.py")
        msgs = [f.message for f in findings if f.rule == "metrics"]
        assert any("snake_case" in m for m in msgs)          # badName
        assert any("'_total'" in m and "counter" in m
                   for m in msgs)                            # badName no suffix
        assert any("must not claim" in m for m in msgs)      # gauge oops_total

    def test_dead_series_detected(self):
        findings = lint_source(METRICS_SRC, "obs/metrics.py")
        assert any("dead series" in f.message and f.line == 4
                   for f in findings)

    def test_two_planes_writing_one_counter(self):
        plane1 = "def a(m):\n    m.foo.inc(priority='x')\n"
        plane2 = "def b(m):\n    m.foo.inc(priority='y')\n"
        findings = lint_source(plane1, "qos/p1.py", extra={
            "obs/metrics.py": METRICS_SRC, "serving/p2.py": plane2})
        two = [f for f in findings if "two planes" in f.message]
        assert len(two) == 1 and two[0].path == "serving/p2.py"

    def test_raw_cumulative_inc_outside_collector(self):
        plane = ("def a(m, snapshot):\n"
                 "    total = snapshot['scored']\n"
                 "    m.foo.inc(total)\n")
        findings = lint_source(plane, "qos/p1.py",
                               extra={"obs/metrics.py": METRICS_SRC})
        assert any("sync_*" in f.message and f.line == 3 for f in findings)


class TestLockOrderRule:
    def test_unlocked_mutation_entry_fires(self):
        src = ("def rung(scorer):\n"
               "    scorer.set_degradation(None)\n")
        findings = lint_source(src, "qos/x.py")
        assert lines_of(findings, "lock-order") == [2]
        assert "set_degradation" in findings[0].message

    def test_mutation_under_lock_is_clean(self):
        src = ("def rung(scorer, lock):\n"
               "    with lock:\n"
               "        scorer.set_degradation(None)\n")
        assert lint_source(src, "qos/x.py") == []

    def test_lock_kwarg_counts_as_held(self):
        src = ("def promote(scorer, score_lock):\n"
               "    restore_into_scorer(scorer, lock=score_lock)\n")
        assert lint_source(src, "serving/x.py") == []

    def test_caller_holding_lock_covers_callee(self):
        src = ("def inner(scorer):\n"
               "    scorer.set_models(None)\n"
               "def outer(scorer, lock):\n"
               "    with lock:\n"
               "        inner(scorer)\n")
        assert lint_source(src, "scoring/x.py") == []

    def test_blocking_ops_under_lock(self):
        src = ("import time\n"
               "class A:\n"
               "    def f(self):\n"
               "        with self._lock:\n"
               "            time.sleep(0.1)\n"
               "            self._q.get()\n"
               "            self._q.put_nowait(1)\n")
        findings = lint_source(src, "stream/x.py")
        assert lines_of(findings, "lock-order") == [5, 6]  # _nowait is fine


class TestDeterminismRule:
    def test_global_rngs_fire_in_sim_and_drills(self):
        src = ("import random\n"
               "import numpy as np\n"
               "def gen():\n"
               "    random.random()\n"
               "    np.random.rand()\n"
               "    return np.random.default_rng(0)\n")
        for rel in ("sim/bad.py", "qos/bad_drill.py"):
            findings = lint_source(src, rel)
            assert rules_of(findings) == ["determinism"], rel
            assert lines_of(findings, "determinism") == [4, 5]

    def test_non_drill_module_is_exempt(self):
        src = "import random\nx = random.random()\n"
        assert lint_source(src, "training/x.py") == []

    def test_quant_calibrator_is_in_scope(self):
        """ISSUE 9: models/quant.py is under the determinism contract —
        the same f32 weights must always calibrate to the same int8 blobs
        (replica hot-swap + checkpoint round-trips assume it)."""
        src = ("import numpy as np\n"
               "def calibrate(w):\n"
               "    return w + np.random.standard_normal(w.shape)\n")
        findings = lint_source(src, "models/quant.py")
        assert rules_of(findings) == ["determinism"]
        assert lines_of(findings, "determinism") == [3]


class TestPragmaHygiene:
    def test_valid_pragma_suppresses_and_is_not_stale(self):
        src = ("import time\n"
               "def f():\n"
               "    # rtfd-lint: allow[wall-clock] test justification\n"
               "    return time.monotonic()\n")
        assert lint_source(src, "qos/ok.py") == []

    def test_trailing_same_line_pragma(self):
        src = ("import time\n"
               "def f():\n"
               "    return time.time()  # rtfd-lint: allow[wall-clock] why\n")
        assert lint_source(src, "obs/ok.py") == []

    def test_stale_pragma_is_an_error(self):
        src = ("import time\n"
               "# rtfd-lint: allow[wall-clock] nothing underneath anymore\n"
               "X = 1\n")
        findings = lint_source(src, "qos/stale.py")
        assert rules_of(findings) == ["pragma-hygiene"]
        assert findings[0].line == 2
        assert "stale" in findings[0].message

    def test_unknown_rule_name_is_an_error_and_does_not_suppress(self):
        src = ("import time\n"
               "def f():\n"
               "    # rtfd-lint: allow[made-up-rule]\n"
               "    return time.monotonic()\n")
        findings = lint_source(src, "qos/bad.py")
        assert rules_of(findings) == ["pragma-hygiene", "wall-clock"]

    def test_pragma_inside_string_literal_is_ignored(self):
        src = ("MSG = 'annotate with # rtfd-lint: allow[wall-clock] why'\n")
        assert lint_source(src, "qos/strings.py") == []


# ---------------------------------------------------------------------------
# tree enforcement: the tier-1 gate
# ---------------------------------------------------------------------------

class TestCommittedTreeIsClean:
    def test_zero_findings_on_the_package_tree(self):
        findings = lint_paths()
        assert not findings, (
            "rtfd lint found invariant violations — fix them or (only for "
            "a genuinely legitimate site) annotate with "
            "`# rtfd-lint: allow[<rule>] <why>`:\n"
            + "\n".join(str(f) for f in findings))

    def test_cli_json_reports_clean(self):
        proc = subprocess.run(
            [sys.executable, "-m", "realtime_fraud_detection_tpu",
             "lint", "--format", "json"],
            capture_output=True, text=True, timeout=180,
            cwd=str(REPO_ROOT))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        data = json.loads(proc.stdout)
        assert data["clean"] is True and data["count"] == 0
        assert sorted(data["rules"]) == [
            "d2h", "determinism", "lock-order", "metrics",
            "pragma-hygiene", "wall-clock"]

    def test_serving_degradation_lock_regression_pin(self):
        """PR 7 fixed a real finding: the serving plane stepped the QoS
        ladder (a scorer mask mutation) without the score lock while an
        executor thread could be mid-dispatch. The fix flags the rung
        change on the event loop and applies it in _dispatch_batch_sync
        under the lock that thread already holds. Pin both directions:
        the committed code is clean, and hoisting the apply back out of
        the locked section brings the lock-order finding back — the
        linter IS the regression test."""
        app_src = (PKG_ROOT / "serving/app.py").read_text()
        plane_src = (PKG_ROOT / "qos/plane.py").read_text()
        apply_line = "self.qos.apply_degradation(self.scorer)"
        locked = ("with self._score_lock:\n"
                  "                    if self._qos_rung_dirty")
        assert locked in app_src and apply_line in app_src
        extra = {"qos/plane.py": plane_src}
        clean = lint_source(app_src, "serving/app.py", extra=extra)
        assert not [f for f in clean if f.rule == "lock-order"]
        # regression shape: apply hoisted above the locked section
        mutated = app_src.replace(
            locked,
            f"{apply_line}\n"
            "                with self._score_lock:\n"
            "                    if self._qos_rung_dirty")
        dirty = lint_source(mutated, "serving/app.py", extra=extra)
        assert [f for f in dirty if f.rule == "lock-order"
                and "set_degradation" in f.message]


# ---------------------------------------------------------------------------
# lockwatch: unit pins
# ---------------------------------------------------------------------------

class TestLockWatcher:
    def test_inverted_two_lock_order_is_detected_as_cycle(self):
        w = LockWatcher()
        la, lb = w.lock("A"), w.lock("B")

        def ab():
            with la:
                with lb:
                    pass

        def ba():
            with lb:
                with la:
                    pass

        for fn in (ab, ba):           # sequenced: no real deadlock risk
            t = threading.Thread(target=fn)
            t.start()
            t.join()
        rep = w.report()
        assert not rep["ok"]
        assert rep["cycles"], rep["edges"]
        cyc = rep["cycles"][0]
        assert set(cyc) == {"A", "B"}

    def test_consistent_order_is_clean_and_holds_recorded(self):
        w = LockWatcher()
        la, lb = w.lock("A"), w.lock("B")
        with la:
            with lb:
                time.sleep(0.01)
        rep = w.report()
        assert rep["ok"] and rep["cycles"] == []
        assert rep["edges"] == [["A", "B", 1]]
        assert rep["max_hold_ms"]["A"] >= 10.0

    def test_device_wait_under_held_lock_is_a_violation(self):
        w = LockWatcher()
        lock = w.lock("score-lock")
        with watch_locks(w):
            import jax

            with lock:
                jax.block_until_ready(np.zeros(2))
        rep = w.report()
        assert not rep["ok"]
        v = rep["violations"][0]
        assert v["kind"] == "device-wait-under-lock"
        assert v["held"] == ["score-lock"]

    def test_device_wait_without_lock_is_clean(self):
        w = LockWatcher()
        with watch_locks(w):
            import jax

            jax.block_until_ready(np.zeros(2))
        assert w.report()["ok"]

    def test_cond_wait_holding_other_lock_is_a_warning_not_failure(self):
        w = LockWatcher()
        lock, cond = w.lock("L"), w.condition("C")
        with lock:
            with cond:
                cond.wait(timeout=0.01)
        rep = w.report()
        assert rep["ok"]                      # warning, not violation
        assert rep["warnings"][0]["kind"] == "cond-wait-holding-other-lock"
        assert rep["warnings"][0]["held"] == ["L"]

    def test_watch_wraps_package_lock_creation_and_restores(self):
        from realtime_fraud_detection_tpu.obs.metrics import Registry

        with watch_locks() as w:
            r = Registry()                    # created from a package frame
            assert isinstance(r._lock, WatchedLock)
            with r._lock:
                pass
        assert w.acquisitions >= 1
        r2 = Registry()                       # after restore: a real lock
        assert not isinstance(r2._lock, WatchedLock)


# ---------------------------------------------------------------------------
# lockwatch under the real drills (the tier-1 enforcement)
# ---------------------------------------------------------------------------

class TestLockwatchUnderDrills:
    @pytest.mark.parametrize("drill", LOCKWATCH_DRILLS)
    def test_drill_runs_clean_under_instrumented_locks(self, drill):
        rep = run_drill_watched(drill, fast=True)
        assert rep["drill_passed"], drill
        lw = rep["lockwatch"]
        assert lw["ok"], (drill, lw["cycles"], lw["violations"])
        # the watcher actually watched something
        assert lw["acquisitions"] > 0 and lw["locks"]

    @pytest.mark.slow
    def test_lockwatch_cli_all_six_drills(self):
        proc = subprocess.run(
            [sys.executable, "-m", "realtime_fraud_detection_tpu",
             "lint", "--lockwatch", "--fast"],
            capture_output=True, text=True, timeout=1800,
            cwd=str(REPO_ROOT),
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        last = proc.stdout.strip().splitlines()[-1]
        verdict = json.loads(last)
        assert verdict["passed"] is True, verdict
        assert set(verdict["lockwatch"]) == set(LOCKWATCH_DRILLS)
