"""A small SchedulingPodAntiAffinity cell, added to a copy of the checkout
by new files and new entries only, runs on the CPU and proves correct; with
the anti term stripped from the program's pods (the reference keeps it),
`correct` comes out false on infeasible binds; and a program whose device
path cannot run the cell fails the run at its first iteration.

The fault needs a cluster too small for one pod per node. While an empty
node is free at every decision, as in the sound cell, LeastAllocated alone
picks one on these identical nodes, so a program without the filter
decides exactly as one with it and no check can tell them apart."""

import json
import os
import time

import pytest

from benchmark.harness import run_cell
from benchmark.tests.small import REPO, add_cell, small_root

TRAFFIC = "small-keepinit"


def anti_root(tmp, nodes: int) -> tuple[str, str]:
    root = small_root(tmp)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "SchedulingPodAntiAffinity-5000Nodes.json")) as f:
        cfg = json.load(f)
    cfg.update(name="Small-Anti", nodes=nodes, init_pods=32, measure_pods=224,
               resident_pods=256)
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "closed-backlog1000-keepinit.json")) as f:
        traffic = json.load(f)
    # at most 256 resident + 512 created and not yet bound on the nodes
    traffic.update(backlog=512, warmup_iterations=3, warmup_drains=8,
                   warmup_max_s=120)
    with open(os.path.join(root, "benchmark", "traffic",
                           TRAFFIC + ".json"), "w") as f:
        json.dump(traffic, f)
    return root, add_cell(root, cfg, TRAFFIC)


def _run(root, cell, seed):
    return run_cell(root, cell, seed, 3.0, False, time.perf_counter(),
                    require_tpu=False)


def test_anti_affinity_cell_runs(tmp_path, capfd):
    # a node free for every pod
    root, cell = anti_root(tmp_path, nodes=1024)
    r = _run(root, cell, 2 ** 31 + 59)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert "setup_s" in r["metrics"] and "pods_per_s" in r["metrics"]
    assert "unschedulable_attempts=0\n" in capfd.readouterr().err


def test_stripped_anti_term_is_not_correct(tmp_path, monkeypatch):
    from kubernetes_tpu.backend.apiserver import APIServer
    # fewer nodes than 256 resident + 512 not yet bound
    root, cell = anti_root(tmp_path, nodes=640)
    real = APIServer.create_pods

    def stripped(self, pods):
        for pod in pods:
            pod.spec.affinity = None     # templates share one spec
        return real(self, pods)
    monkeypatch.setattr(APIServer, "create_pods", stripped)
    r = _run(root, cell, 2 ** 31 + 61)
    assert not r["correct"], r["checks"]
    assert r["checks"]["infeasible"]["value"] > 0, r["checks"]


def test_device_fault_fails_the_run(tmp_path, monkeypatch):
    """A program whose wave kernel the device refuses degrades each drain
    to the host path; the run fails after the first iteration."""
    import kubernetes_tpu.scheduler as scheduler_mod
    root, cell = anti_root(tmp_path, nodes=1792)

    def refused(*a, **kw):
        raise RuntimeError("UNIMPLEMENTED: a kernel the device refuses")
    monkeypatch.setattr(scheduler_mod, "run_wave", refused)
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="placed by the host path"):
        _run(root, cell, 2 ** 31 + 67)
    assert time.perf_counter() - t0 < 60


def test_control_is_not_correct(tmp_path):
    """The kind's control (the reference in int32/float32) in the
    program's place: 32Gi wraps to 0 in int32, no node is feasible."""
    root, cell = anti_root(tmp_path, nodes=1792)
    r = run_cell(root, cell, 2 ** 31 + 71, 2.0, False, time.perf_counter(),
                 require_tpu=False, control=True)
    program = r["program_checks"]
    assert all(c["value"] <= c["limit"] for c in program.values()), program
    assert not r["correct"], r["checks"]
    assert r["checks"]["unbound"]["value"] == r["attempted"] > 0
