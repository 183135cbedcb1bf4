"""Every cell resolves to its files; BENCHMARK.json keeps the contract's
shape; cells added in a copy of the checkout run without editing a file,
one of them of a kind of deployment whose reference and builder are new."""

import json
import os
import re
import textwrap
import time

import pytest

from benchmark.harness import Cell, run_cell
from benchmark.tests.small import (REPO, SMALL_CELLS, add_cell, small_config,
                                   small_root)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves(workload):
    cell = Cell(REPO, workload)
    assert cell.chips == 1
    assert hasattr(cell.driver, "Driver")
    assert hasattr(cell.build, "Builder")
    assert hasattr(cell.reference, "check")
    assert hasattr(cell.reference, "control")
    assert set(cell.limits) >= {"unbound", "off_argmax"}
    assert int(cell.config["nodes"]) == 5000
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert hasattr(cell.readers[m["name"].split(".")[0]], "read")


def test_spec_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    cells = {w["name"] for w in SPEC["workloads"]}
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(SPEC["paths"][0] + "/")
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        reported = [m for m in SPEC["end_to_end"]
                    if w["name"] in m.get("workloads", cells)]
        assert len(reported) >= 2
        assert any(w["name"] in m["workloads"] for m in SPEC["per_layer"])


def _run(root, workload, seed):
    return run_cell(root, workload, seed, 2.0, False, time.perf_counter(),
                    require_tpu=False)


@pytest.mark.parametrize("workload", SMALL_CELLS)
def test_added_cell_runs(tmp_path, workload):
    """Cells that exist only in a copy of the checkout, added there by new
    files and new entries, run and prove correct."""
    r = _run(small_root(tmp_path), workload, 2 ** 31 + 17)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert "setup_s" in r["metrics"]
    assert len(r["metrics"]) >= 2
    assert list(r)[-1] == "checks"


# A kind of deployment the benchmark does not have: pods with a nodeSelector.
# Its reference and builder extend fit_spread's from new files alone.
SELECTOR_REFERENCE = '''
    import numpy as np
    from benchmark.kinds.fit_spread import reference as base
    from benchmark.kinds.fit_spread.reference import (  # noqa: F401
        node_capacity, node_labels, node_name, template)

    MODELLED = base.MODELLED | {"node_selector"}


    class Scorer(base.Scorer):
        modelled = MODELLED

        def _match(self, tmpl):
            n = len(self.npods)
            labels = [node_labels(self.cfg, i) for i in range(n)]
            sel = tmpl.get("node_selector", {})
            return np.array([all(lab.get(k) == v for k, v in sel.items())
                             for lab in labels])

        def feasible(self, tmpl):
            return super().feasible(tmpl) & self._match(tmpl)

        def feasible_at(self, tmpl, node):
            return super().feasible_at(tmpl, node) and self._match(tmpl)[node]


    def check(cfg, record, sample):
        return base.check(cfg, record, sample, Scorer)


    def control(cfg, record):
        return base.control(cfg, record, Scorer)
'''

SELECTOR_BUILD = '''
    from benchmark.kinds.fit_spread import build as base


    class Builder(base.Builder):
        def _proto(self, tmpl):
            plain = {k: v for k, v in tmpl.items() if k != "node_selector"}
            pod = super()._proto(plain)
            pod.spec.node_selector = dict(tmpl.get("node_selector", {}))
            return pod
'''


def test_added_kind_runs(tmp_path):
    root = small_root(tmp_path)
    kdir = os.path.join(root, "benchmark", "kinds", "fit_selector")
    os.makedirs(kdir)
    for name, body in (("reference.py", SELECTOR_REFERENCE),
                       ("build.py", SELECTOR_BUILD)):
        with open(os.path.join(kdir, name), "w") as f:
            f.write(textwrap.dedent(body))
    with open(os.path.join(root, "benchmark", "kinds", "fit_spread",
                           "checks.json")) as f:
        limits = f.read()
    with open(os.path.join(kdir, "checks.json"), "w") as f:
        f.write(limits)
    cfg = small_config("Small-Selector", True)
    cfg.update(kind="fit_selector", nodes=256)   # room for the backlog
    cfg["measure_pod"] = dict(cfg["init_pod"],
                              node_selector={
                                  "topology.kubernetes.io/zone": "moon-2"})
    cell = add_cell(root, cfg)
    r = _run(root, cell, 2 ** 31 + 41)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
