"""jaxsan self-test + the tier-1 repo lint gate.

Two halves:

- the FIXTURE tests seed a synthetic package with one violation per rule
  class and assert every class is detected (and that a cleaned copy of
  the same package passes) — the linter's own regression harness, so a
  precision "fix" that silently lobotomizes a rule is a test failure;
- the REPO test runs the full analysis over this repository exactly like
  `tools/check.py` and fails on any unwaived finding — the CI gate the
  ISSUE ships (every existing violation fixed or explicitly waived).
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from kubernetes_tpu.analysis.findings import RULES  # noqa: E402
from kubernetes_tpu.analysis.jaxsan import (JaxsanAnalyzer,  # noqa: E402
                                            analyze_tree)
from kubernetes_tpu.analysis.findings import (is_waived,  # noqa: E402
                                              parse_waivers)
from kubernetes_tpu.analysis.locks import LockChecker  # noqa: E402


# ---------------------------------------------------------------------------
# fixture package: one violation per rule class

# device-path violations, all reachable from the jit root `enter`
_DEVICE_BAD = '''
import functools
import numpy as np
import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("k",))
def enter(x, sel, k):
    if x[0] > 0:                      # traced-branch
        x = x * 2
    bad = np.abs(x)                   # np-in-jit
    y = jnp.zeros(x[0])               # dynamic-shape
    return helper(x, sel, k) + bad + y.sum()


SINK = []


def helper(x, sel, k):
    SINK.append(x)                    # tracer-leak (outer container)
    acc = x
    for tag in {"a", "b", "c"}:       # nondeterministic-iteration
        acc = acc + sel
    n = int(x.sum())                  # traced-branch (host cast)
    return acc * k + n
'''

# host-side violations: donated-buffer read + set feeding tensors
_HOST_BAD = '''
import numpy as np

from kubernetes_tpu.perf.ledger import device_entry

from .device import enter


@device_entry(donates=2, cost=(1.0, 1.0))
def run_batch(cfg, na, carry, pods):
    return carry


def dispatch(cfg, na, carry, pods):
    out = run_batch(cfg, na, carry, pods)
    return np.asarray(carry)          # donation-after-use


def seed(items):
    rows = [np.array(v) for v in set(items)]   # nondeterministic-iteration
    return rows
'''

# lock-discipline violations: unguarded access + opposite nesting orders
_LOCKS_BAD = '''
import threading


class Ring:
    def __init__(self):
        self._lock = threading.Lock()
        self._a = threading.Lock()
        self._b = threading.Lock()
        self._ring = []   # guarded_by: _lock

    def push(self, v):
        self._ring.append(v)          # unguarded-shared-state

    def ok(self, v):
        with self._lock:
            self._ring.append(v)

    def ab(self):
        with self._a:
            with self._b:
                return 1

    def ba(self):                     # lock-order-cycle with ab()
        with self._b:
            with self._a:
                return 2
'''

# the same package, violations repaired — the clean tree must pass
_DEVICE_CLEAN = '''
import functools
import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("k",))
def enter(x, sel, k):
    x = jnp.where(x[0] > 0, x * 2, x)
    bad = jnp.abs(x)
    y = jnp.zeros(x.shape[0])
    return helper(x, sel, k) + bad + y.sum()


def helper(x, sel, k):
    acc = x
    for tag in ("a", "b", "c"):
        acc = acc + sel
    return acc * k
'''

_HOST_CLEAN = '''
import numpy as np

from kubernetes_tpu.perf.ledger import device_entry

from .device import enter


@device_entry(donates=2, cost=(1.0, 1.0))
def run_batch(cfg, na, carry, pods):
    return carry


def dispatch(cfg, na, carry, pods):
    carry = run_batch(cfg, na, carry, pods)
    return np.asarray(carry)


def seed(items):
    rows = [np.array(v) for v in sorted(set(items))]
    return rows
'''

_LOCKS_CLEAN = '''
import threading


class Ring:
    def __init__(self):
        self._lock = threading.Lock()
        self._a = threading.Lock()
        self._b = threading.Lock()
        self._ring = []   # guarded_by: _lock

    def push(self, v):
        with self._lock:
            self._ring.append(v)

    def _push_locked(self, v):        # jaxsan: holds _lock
        self._ring.append(v)

    def ab(self):
        with self._a:
            with self._b:
                return 1

    def ab2(self):
        with self._a:
            with self._b:
                return 2
'''

ENTRIES = {"fixturepkg.device": ("enter",)}


def _write_pkg(root, device, host, locks):
    pkg = os.path.join(str(root), "fixturepkg")
    os.makedirs(pkg, exist_ok=True)
    for name, src in (("__init__.py", ""), ("device.py", device),
                      ("host.py", host), ("locks.py", locks)):
        with open(os.path.join(pkg, name), "w") as f:
            f.write(textwrap.dedent(src))
    return str(root)


@pytest.fixture()
def bad_tree(tmp_path):
    return _write_pkg(tmp_path, _DEVICE_BAD, _HOST_BAD, _LOCKS_BAD)


@pytest.fixture()
def clean_tree(tmp_path):
    return _write_pkg(tmp_path, _DEVICE_CLEAN, _HOST_CLEAN, _LOCKS_CLEAN)


class TestFixtureDetection:
    def test_all_rule_classes_detected(self, bad_tree):
        findings = analyze_tree(bad_tree, package="fixturepkg",
                                entry_points=ENTRIES)
        live = [f for f in findings if not f.waived]
        rules = {f.rule for f in live}
        expected = {"traced-branch", "np-in-jit", "dynamic-shape",
                    "tracer-leak", "donation-after-use",
                    "nondeterministic-iteration",
                    "unguarded-shared-state", "lock-order-cycle"}
        assert expected <= rules, f"missed: {expected - rules}"
        # the acceptance bar: >= 8 distinct rule classes from one seeded
        # violation each
        assert len(rules & expected) >= 8
        # every rule in the registry has a fixture violation — adding a
        # rule without a fixture is itself a failure
        assert set(RULES) <= rules

    def test_findings_carry_location_and_hint(self, bad_tree):
        findings = [f for f in analyze_tree(bad_tree, package="fixturepkg",
                                            entry_points=ENTRIES)
                    if not f.waived]
        for f in findings:
            assert f.path.startswith("fixturepkg")
            assert f.line >= 1
            assert f.hint, f"no fix-it hint for {f.rule}"
        # file:line formatting (the editor-clickable contract)
        text = findings[0].format(fix_hints=True)
        assert ":" in text and "fix:" in text

    def test_clean_tree_passes(self, clean_tree):
        findings = analyze_tree(clean_tree, package="fixturepkg",
                                entry_points=ENTRIES)
        live = [f for f in findings if not f.waived]
        assert live == [], [f.format() for f in live]

    def test_static_param_branch_is_not_flagged(self, tmp_path):
        # branching on a STATIC argname is the intended kernel-trimming
        # idiom — the discrimination the whole analyzer exists for
        root = _write_pkg(tmp_path, '''
import functools
import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("flag",))
def enter(x, flag):
    if flag:
        return x * 2
    return x
''', "", "")
        findings = [f for f in analyze_tree(root, package="fixturepkg",
                                            entry_points=ENTRIES)
                    if not f.waived and f.rule == "traced-branch"]
        assert findings == [], [f.format() for f in findings]

    def test_entry_coverage_lost_is_reported(self, bad_tree):
        an = JaxsanAnalyzer(bad_tree, package="fixturepkg",
                            entry_points={"fixturepkg.device": ("enter",),
                                          "fixturepkg.host": ("gone",)})
        an.load()
        an.run()
        missing = an.check_entry_coverage()
        assert "fixturepkg.host.gone" in missing
        assert "fixturepkg.device.enter" not in missing


class TestWaivers:
    def test_waiver_suppresses_named_rule(self, tmp_path):
        device = _DEVICE_BAD.replace(
            "bad = np.abs(x)                   # np-in-jit",
            "bad = np.abs(x)  # jaxsan: waive[np-in-jit] fixture baseline")
        root = _write_pkg(tmp_path, device, _HOST_BAD, _LOCKS_BAD)
        findings = analyze_tree(root, package="fixturepkg",
                                entry_points=ENTRIES)
        np_findings = [f for f in findings if f.rule == "np-in-jit"]
        assert np_findings and all(f.waived for f in np_findings)
        # other rules on other lines stay live
        assert any(not f.waived and f.rule == "traced-branch"
                   for f in findings)

    def test_waiver_star_and_line_above(self):
        w = parse_waivers("x = 1  # jaxsan: waive[*]\n"
                          "y = foo()\n"
                          "z = 2  # jaxsan: waive[a, b]\n")
        assert is_waived(w, 1, "anything")
        assert is_waived(w, 2, "anything")      # covers the line below
        assert is_waived(w, 3, "a") and is_waived(w, 3, "b")
        assert not is_waived(w, 3, "c")
        assert not is_waived(w, 5, "a")

    def test_holds_annotation_treats_body_as_guarded(self, tmp_path):
        locks = _LOCKS_CLEAN + '''

class Uses:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0   # guarded_by: _lock

    def bump(self):   # jaxsan: holds _lock
        self._n += 1
'''
        root = _write_pkg(tmp_path, _DEVICE_CLEAN, _HOST_CLEAN, locks)
        findings = [f for f in analyze_tree(root, package="fixturepkg",
                                            entry_points=ENTRIES)
                    if not f.waived]
        assert findings == [], [f.format() for f in findings]


class TestCheckCli:
    """tools/check.py exit-code contract, driven on the small fixture
    tree (subprocess — the exact CI invocation)."""

    def _run(self, root, *args):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "check.py"),
             "--root", root, "--package", "fixturepkg",
             "--entries", "fixturepkg.device:enter", *args],
            capture_output=True, text=True)

    def test_dirty_tree_exits_1_with_findings(self, bad_tree):
        r = self._run(bad_tree)
        assert r.returncode == 1
        assert "np-in-jit" in r.stdout
        assert "fixturepkg" in r.stdout

    def test_clean_tree_exits_0(self, clean_tree):
        r = self._run(clean_tree)
        assert r.returncode == 0, r.stdout + r.stderr

    def test_fix_hints_flag(self, bad_tree):
        r = self._run(bad_tree, "--fix-hints")
        assert "fix:" in r.stdout

    def test_json_output(self, bad_tree):
        import json
        r = self._run(bad_tree, "--json")
        payload = json.loads(r.stdout)
        assert payload["findings"]
        assert {"rule", "path", "line", "message", "hint"} <= set(
            payload["findings"][0])


class TestRepoGate:
    """The tier-1 gate: this repository must lint clean."""

    def test_repo_has_zero_unwaived_findings(self):
        findings = analyze_tree(REPO)
        live = [f for f in findings if not f.waived]
        assert live == [], "\n" + "\n".join(f.format() for f in live)

    def test_all_declared_entries_have_jit_coverage(self):
        an = JaxsanAnalyzer(REPO).load()
        an.run()
        assert an.check_entry_coverage() == []
        # the entry set jaxsan lints is exactly the declared registry
        from kubernetes_tpu.perf.ledger import device_entries
        names = {n for mod, ns in an.entry_points.items() for n in ns}
        assert names == {e.fn.__name__ for e in device_entries().values()}

    def test_threaded_subsystems_are_annotated(self):
        """The lock checker's input contract: the shared rings/queues of
        the threaded subsystems declare their lock."""
        import ast
        an = JaxsanAnalyzer(REPO).load()
        ck = LockChecker(an.modules)
        declared = {}
        for mi in an.modules.values():
            lines = mi.source.splitlines()
            for node in ast.walk(mi.tree):
                if isinstance(node, ast.ClassDef):
                    info = ck._collect(node, lines, mi.path)
                    if info.guarded:
                        declared[f"{mi.name}.{node.name}"] = set(info.guarded)
        assert "_events" in declared["kubernetes_tpu.events.EventRecorder"]
        assert "ring" in declared["kubernetes_tpu.events.FlightRecorder"]
        assert "_queue" in declared[
            "kubernetes_tpu.backend.dispatcher.APIDispatcher"]
        assert "_ring" in declared[
            "kubernetes_tpu.perf.profiler.HostProfiler"]
