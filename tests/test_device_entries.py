"""The device-entry registry (perf/ledger.py `device_entry`).

Each device entry — a public function that dispatches a jitted program —
is declared once, where it is defined. jaxsan reads the declarations from
the source; the metric pre-seed, the kernel observatory and the cost
model read them at run time. These tests hold the two readings to each
other and hold every entry to what the declaration promises.
"""

import ast
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from kubernetes_tpu.analysis.jaxsan import JaxsanAnalyzer  # noqa: E402
from kubernetes_tpu.metrics import SchedulerMetrics  # noqa: E402
from kubernetes_tpu.perf.ledger import device_entries  # noqa: E402

ENTRIES = device_entries()

# the metric families labelled by kernel
KERNEL_FAMILIES = ("scheduler_xla_compiles_total",
                   "scheduler_xla_compile_seconds",
                   "scheduler_kernel_device_seconds",
                   "scheduler_kernel_dispatch_total")


def _qualname(entry) -> str:
    return f"{entry.fn.__module__}.{entry.fn.__name__}"


@pytest.fixture(scope="module")
def analyzer():
    return JaxsanAnalyzer(REPO).load()


@pytest.fixture(scope="module")
def seeded():
    """family → kernel labels with a sample in a fresh exposition."""
    out = {fam: set() for fam in KERNEL_FAMILIES}
    for line in SchedulerMetrics().exposition().splitlines():
        m = re.match(r'^(\w+)\{kernel="([^"]+)"\} ', line)
        if m and m.group(1) in out:
            out[m.group(1)].add(m.group(2))
    return out


@pytest.mark.parametrize("label", sorted(ENTRIES))
def test_entry_is_linted_costed_and_seeded(label, analyzer, seeded):
    entry = ENTRIES[label]
    qual = _qualname(entry)
    # jaxsan finds the declaration, with the same label and donation,
    # and the entry reaches a jitted program
    assert analyzer.declared[qual] == (label, entry.donates)
    assert analyzer._reaches_root(analyzer.fns[qual], set()), qual
    if entry.donates is not None:
        assert analyzer.donating[entry.fn.__name__][0] == entry.donates
    # the cost model's host estimate has coefficients to work with
    flops_per_cell, byte_mult = entry.cost
    assert flops_per_cell > 0 and byte_mult > 0
    # dashboards see the kernel's series before its first dispatch
    for fam in KERNEL_FAMILIES:
        assert label in seeded[fam], fam


def test_static_declarations_equal_runtime_registry(analyzer):
    runtime = {_qualname(e): (label, e.donates)
               for label, e in ENTRIES.items()}
    assert analyzer.declared == runtime


def test_every_measured_call_name_is_registered():
    """Dispatches are measured only under registered labels: a literal
    kernel name passed to `measured_call` anywhere in the package must be
    declared, and the only call with a computed name is the declaration's
    own dispatch."""
    pkg = os.path.join(REPO, "kubernetes_tpu")
    calls = []
    for dirpath, _dirs, files in os.walk(pkg):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read(), filename=path)
            for node in ast.walk(tree):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "measured_call"):
                    calls.append((os.path.relpath(path, REPO), node))
    assert calls
    for path, call in calls:
        name = call.args[0] if call.args else None
        if isinstance(name, ast.Constant):
            assert name.value in ENTRIES, f"{path}:{call.lineno}"
        else:
            assert path == os.path.join("kubernetes_tpu", "perf",
                                        "ledger.py"), f"{path}:{call.lineno}"
