"""tpusc-check tier-1 wrapper + fixture tests (see LINT.md).

Three layers:

  1. the whole-tree gate: ``tfservingcache_tpu/`` must be clean under the
     checked-in waiver file, and fast enough to live in tier-1 (<5s);
  2. fixture tests proving each rule both FIRES on its target hazard and
     STAYS QUIET on the sanctioned idiom — a rule that can't catch its own
     fixture is dead weight and a rule that flags the idiom is noise;
  3. docs/config sync lints (README knob tables, ruff gate) and the
     TPUSC_LOCKCHECK dynamic-mode machinery.
"""

import os
import re
import shutil
import subprocess
import sys
import textwrap
import time
from dataclasses import fields, is_dataclass
from pathlib import Path

import pytest

from tools.tpusc_check import Violation, Waiver, load_waivers, run_check

ROOT = Path(__file__).resolve().parent.parent
WAIVERS = ROOT / "tools" / "tpusc_check" / "waivers.txt"

# Operator-facing CLIs gated alongside the package tree: these run on
# on-call laptops against live nodes, so the same lock/thread/metric
# discipline applies (the checker itself and the test client are exempt —
# one is the linter, the other is a traffic generator).
GATED_TOOLS = [
    ROOT / "tools" / "engine_dump.py",
    ROOT / "tools" / "fleet_top.py",
    ROOT / "tools" / "slo_report.py",
    ROOT / "tools" / "tenant_top.py",
]
GATE_PATHS = [ROOT / "tfservingcache_tpu", *GATED_TOOLS]


def _check(tmp_path, source, relname="mod.py", waivers=()):
    p = tmp_path / relname
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    return run_check([p], list(waivers), root=tmp_path)


def _rules(violations):
    return [v.rule for v in violations]


# -- the whole-tree gate -----------------------------------------------------

def test_repo_tree_is_clean_and_fast():
    t0 = time.monotonic()
    violations, waived = run_check(GATE_PATHS, load_waivers(WAIVERS), root=ROOT)
    elapsed = time.monotonic() - t0
    assert not violations, "unwaivered violations:\n" + "\n".join(
        v.render() for v in violations
    )
    # waivers are reviewed exceptions, not a dumping ground: each one must
    # still match something (a stale waiver hides future violations at that
    # site); allow the doc-only benchtime glob to match multiple sites
    assert len(waived) >= len(load_waivers(WAIVERS)) - 1
    assert elapsed < 5.0, f"tpusc-check took {elapsed:.1f}s; tier-1 budget is 5s"


def test_standalone_cli_runs_green():
    r = subprocess.run(
        [sys.executable, "-m", "tools.tpusc_check", "tfservingcache_tpu",
         *(str(p.relative_to(ROOT)) for p in GATED_TOOLS)],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 violation(s)" in r.stdout


def test_no_stale_waivers():
    """Every waiver entry matches at least one current violation site."""
    waivers = load_waivers(WAIVERS)
    _, waived = run_check(GATE_PATHS, waivers, root=ROOT)
    used = {w.pattern for _, w in waived}
    stale = [w.pattern for w in waivers if w.pattern not in used]
    assert not stale, f"waivers that no longer match anything: {stale}"


# -- TPUSC001: guarded-by lock discipline ------------------------------------

GUARDED_BAD = """
    import threading

    class Box:
        _tpusc_guarded = {"_items": "_lock"}

        def __init__(self):
            self._items = {}
            self._lock = threading.Lock()

        def peek(self):
            return len(self._items)
"""

GUARDED_GOOD = """
    import threading

    class Box:
        _tpusc_guarded = {"_items": "_lock"}

        def __init__(self):
            self._items = {}
            self._lock = threading.Lock()

        def peek(self):
            with self._lock:
                return len(self._items)

        def _sweep(self):  # lock-held: _lock
            self._items.clear()
"""


def test_guarded_registry_fires_on_unlocked_access(tmp_path):
    violations, _ = _check(tmp_path, GUARDED_BAD)
    assert _rules(violations) == ["TPUSC001"]
    assert "_items" in violations[0].message and "_lock" in violations[0].message
    assert violations[0].qualname == "Box.peek"


def test_guarded_registry_quiet_on_locked_and_lockheld(tmp_path):
    violations, _ = _check(tmp_path, GUARDED_GOOD)
    assert violations == []


def test_guarded_trailing_comment_form(tmp_path):
    violations, _ = _check(tmp_path, """
        import threading

        class Box:
            def __init__(self):
                self._items = {}  # guarded-by: _lock
                self._lock = threading.Lock()

            def peek(self):
                return len(self._items)
    """)
    assert _rules(violations) == ["TPUSC001"]


def test_guarded_module_global(tmp_path):
    violations, _ = _check(tmp_path, """
        import threading

        _MEMO = {}  # guarded-by: _MEMO_LOCK
        _MEMO_LOCK = threading.Lock()

        def bad(k):
            return _MEMO.get(k)

        def good(k):
            with _MEMO_LOCK:
                return _MEMO.get(k)
    """)
    assert _rules(violations) == ["TPUSC001"]
    assert violations[0].qualname == "bad"


def test_guarded_init_is_exempt(tmp_path):
    # single-owner construction: __init__ writes without the lock by design
    violations, _ = _check(tmp_path, """
        import threading

        class Box:
            _tpusc_guarded = {"_items": "_lock"}

            def __init__(self):
                self._lock = threading.Lock()
                self._items = {}
                self._items["seed"] = 1
    """)
    assert violations == []


# -- TPUSC002: thread lifecycle ----------------------------------------------

def test_thread_fire_and_forget_fires(tmp_path):
    violations, _ = _check(tmp_path, """
        import threading

        class Mgr:
            def kick(self):
                threading.Thread(target=self._work, daemon=True).start()
    """)
    assert "TPUSC002" in _rules(violations)


def test_thread_daemon_bound_is_ok(tmp_path):
    violations, _ = _check(tmp_path, """
        import threading

        class Mgr:
            def kick(self):
                t = threading.Thread(target=self._work, daemon=True)
                t.start()
    """)
    assert violations == []


def test_thread_nondaemon_needs_join(tmp_path):
    bad, _ = _check(tmp_path, """
        import threading

        class Mgr:
            def start(self):
                self._t = threading.Thread(target=self._work)
                self._t.start()
    """)
    assert "TPUSC002" in _rules(bad)

    good, _ = _check(tmp_path, """
        import threading

        class Mgr:
            def start(self):
                self._t = threading.Thread(target=self._work)
                self._t.start()

            def close(self):
                self._t.join(timeout=5.0)
    """, relname="good.py")
    assert good == []


def test_bare_acquire_fires_with_scoped_ok(tmp_path):
    bad, _ = _check(tmp_path, """
        import threading

        class Mgr:
            def __init__(self):
                self._lock = threading.Lock()

            def grab(self):
                self._lock.acquire()
    """)
    assert "TPUSC002" in _rules(bad)

    good, _ = _check(tmp_path, """
        import threading

        class Mgr:
            def __init__(self):
                self._lock = threading.Lock()

            def grab(self):
                with self._lock:
                    pass

            def try_grab(self):
                if self._lock.acquire(timeout=0.1):
                    try:
                        return True
                    finally:
                        self._lock.release()
                return False
    """, relname="good.py")
    assert good == []


# -- TPUSC003: JIT-retrace hazards --------------------------------------------

def test_jit_in_method_fires(tmp_path):
    violations, _ = _check(tmp_path, """
        import jax

        class Rt:
            def predict(self, fn, x):
                return jax.jit(fn)(x)
    """)
    assert "TPUSC003" in _rules(violations)


def test_jit_sanctioned_surfaces_are_quiet(tmp_path):
    violations, _ = _check(tmp_path, """
        import threading
        import jax

        F = jax.jit(lambda x: x + 1)  # module scope: compiled once

        class Rt:
            def __init__(self):
                self._jit_lock = threading.Lock()

            def warm(self, fn):
                with self._jit_lock:
                    self._f = jax.jit(fn)

            def rebuild(self, fn):  # jit-surface: one-shot recovery path
                return jax.jit(fn)
    """)
    assert violations == []


def test_jit_static_arg_unbounded_fires_bounded_ok(tmp_path):
    bad, _ = _check(tmp_path, """
        import jax

        def _impl(x, n):
            return x * n

        F = jax.jit(_impl, static_argnames=("n",))

        class Rt:
            def predict(self, x, n):
                return F(x, n=n)
    """)
    assert "TPUSC003" in _rules(bad)
    assert any("static" in v.message for v in bad)

    good, _ = _check(tmp_path, """
        import jax

        def _impl(x, n):
            return x * n

        F = jax.jit(_impl, static_argnames=("n",))

        class Rt:
            def predict(self, x, n):
                return F(x, n=min(n, 64))

            def declared(self, x, n):  # static-bounded: n -- caller buckets to pow2
                return F(x, n=n)
    """, relname="good.py")
    assert good == []


# -- TPUSC004: metric families only in utils/metrics.py -----------------------

def test_metric_outside_metrics_module_fires(tmp_path):
    violations, _ = _check(tmp_path, """
        from prometheus_client import Counter

        HITS = Counter("hits", "cache hits")
    """)
    assert _rules(violations) == ["TPUSC004"]


def test_metric_in_metrics_module_and_collections_counter_ok(tmp_path):
    in_place, _ = _check(tmp_path, """
        from prometheus_client import Counter

        HITS = Counter("hits", "cache hits")
    """, relname="utils/metrics.py")
    assert in_place == []

    stdlib, _ = _check(tmp_path, """
        from collections import Counter

        def tally(xs):
            return Counter(xs)
    """, relname="tally.py")
    assert stdlib == []


# -- waiver machinery ---------------------------------------------------------

def test_malformed_waiver_raises(tmp_path):
    wf = tmp_path / "waivers.txt"
    wf.write_text("TPUSC001 some/site.py::Cls.m\n")  # missing '-- reason'
    with pytest.raises(ValueError, match="malformed waiver"):
        load_waivers(wf)


def test_waiver_suppresses_matching_site(tmp_path):
    waiver = Waiver(
        rule="TPUSC001", pattern="mod.py::Box.*", reason="reviewed: lock-free by design"
    )
    violations, waived = _check(tmp_path, GUARDED_BAD, waivers=[waiver])
    assert violations == []
    assert len(waived) == 1 and waived[0][1] is waiver


def test_waiver_rule_must_match():
    v = Violation(rule="TPUSC002", path="a.py", line=1, qualname="f", message="m")
    assert not Waiver("TPUSC001", "a.py::*", "r").matches(v)
    assert Waiver("*", "a.py::*", "r").matches(v)


# -- config knob tables (docs-sync family) ------------------------------------

def test_config_knobs_match_readme():
    """Every ``config.py`` dataclass has a README knob table documenting
    exactly its fields — bidirectional, same style as the metrics ↔
    OBSERVABILITY.md sync check."""
    import tfservingcache_tpu.config as config_mod

    readme = (ROOT / "README.md").read_text()
    documented: dict[str, set[str]] = {}
    section_re = re.compile(
        r"^### [^\n(]*\(`(\w+)`\)\n(.*?)(?=^### |^## )", re.M | re.S
    )
    for m in section_re.finditer(readme):
        rows = re.findall(r"^\| `([A-Za-z_]\w*)` \|", m.group(2), re.M)
        documented[m.group(1)] = set(rows)

    declared = {
        name: {f.name for f in fields(obj)}
        for name, obj in vars(config_mod).items()
        if is_dataclass(obj) and isinstance(obj, type)
        and obj.__module__ == config_mod.__name__
    }
    assert declared, "no dataclasses found in config.py?"

    for cls_name, field_names in declared.items():
        assert cls_name in documented, (
            f"config.py dataclass {cls_name} has no '### ... (`{cls_name}`)' "
            f"knob table in README.md"
        )
        missing = field_names - documented[cls_name]
        stale = documented[cls_name] - field_names
        assert not missing, f"{cls_name} fields absent from README table: {sorted(missing)}"
        assert not stale, f"README documents nonexistent {cls_name} knobs: {sorted(stale)}"

    ghost_tables = set(documented) - set(declared)
    assert not ghost_tables, (
        f"README knob tables for classes not in config.py: {sorted(ghost_tables)}"
    )


# -- ruff gate ----------------------------------------------------------------

def test_ruff_clean():
    """Runs ruff with the [tool.ruff] config in pyproject.toml when the
    binary exists; the container image doesn't ship it, so this skips there
    and fires on dev machines / CI images that do."""
    ruff = shutil.which("ruff")
    if ruff is None:
        pytest.skip("ruff not installed in this environment")
    r = subprocess.run(
        [ruff, "check", "tfservingcache_tpu", "tools", "tests"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stdout + r.stderr


# -- TPUSC_LOCKCHECK dynamic mode ---------------------------------------------

def test_lockcheck_disabled_is_exact_noop():
    from tfservingcache_tpu.utils import lockcheck

    if lockcheck.ENABLED:
        pytest.skip("suite running under TPUSC_LOCKCHECK=1")

    class C:
        _tpusc_guarded = {"_x": "_lock"}

    assert lockcheck.lockchecked(C) is C  # same object, untouched
    lockcheck.assert_clean()  # no-op, never raises


LOCKCHECK_PROG = """
import threading
from tfservingcache_tpu.utils import lockcheck

assert lockcheck.ENABLED

@lockcheck.lockchecked
class Box:
    _tpusc_guarded = {"_items": "_lock"}

    def __init__(self):
        self._lock = threading.Lock()
        self._items = {}          # construction writes: exempt

    def good(self):
        with self._lock:
            return len(self._items)

    def bad(self):
        return len(self._items)

b = Box()
b.good()
assert lockcheck.violations() == [], lockcheck.violations()
lockcheck.assert_clean()

b.bad()
got = lockcheck.violations()
assert len(got) == 1, got
assert "Box._items read" in got[0] and "_lock" in got[0], got
b.bad()  # same site: deduped
assert len(lockcheck.violations()) == 1

try:
    lockcheck.assert_clean()
except AssertionError:
    pass
else:
    raise SystemExit("assert_clean did not raise on recorded violations")

lockcheck.reset()
assert lockcheck.violations() == []
print("LOCKCHECK_OK")
"""


def test_lockcheck_enabled_records_and_dedups():
    env = dict(os.environ, TPUSC_LOCKCHECK="1", JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-c", LOCKCHECK_PROG],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "LOCKCHECK_OK" in r.stdout


@pytest.mark.slow
def test_soak_passes_under_lockcheck():
    """The dynamic complement: re-run the shared-prefix churn soak (200
    retirements through the paged arena) with every ``_tpusc_guarded`` field
    instrumented. ``lockcheck.assert_clean()`` inside the soak raises on any
    unguarded access observed at runtime."""
    env = dict(os.environ, TPUSC_LOCKCHECK="1", JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [
            sys.executable, "-m", "pytest", "-q",
            "tests/test_soak.py::test_shared_prefix_refcount_conservation_under_churn",
            "-p", "no:cacheprovider",
        ],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stdout + r.stderr
