"""A small SchedulingPodAffinity cell, added to a copy of the checkout by new
files and new entries only, runs on the CPU and proves correct; with the
affinity term stripped from the program's pods (the reference keeps it),
`correct` comes out false on infeasible binds.

The cell has three zones, round robin over the nodes, and one zone holds
every pod the loop keeps on the nodes. The first pod takes the escape hatch
(no pod matches its term yet, and it matches its own), so every later pod
must land in the first pod's zone: a program without the filter spreads
over all three and the check sees it. The 5000-node cell's single zone
cannot show the veto: there every node passes once a matching pod exists."""

import json
import os
import time

from benchmark.harness import run_cell
from benchmark.tests.small import REPO, add_cell, small_root

TRAFFIC = "small-keepinit"
ZONE = "topology.kubernetes.io/zone"


def affinity_root(tmp) -> tuple[str, str]:
    root = small_root(tmp)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "SchedulingPodAffinity-5000Nodes.json")) as f:
        cfg = json.load(f)
    # 32 nodes a zone, 40 pods a node (4 cpu / 100m): one zone holds 1280
    cfg.update(name="Small-Affinity", nodes=96, init_pods=32,
               measure_pods=224, resident_pods=256,
               node_labels={ZONE: ["za", "zb", "zc"]})
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "closed-backlog5000-keepinit.json")) as f:
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


def test_affinity_cell_runs(tmp_path, capfd):
    root, cell = affinity_root(tmp_path)
    r = _run(root, cell, 2 ** 31 + 73)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert "setup_s" in r["metrics"] and "pods_per_s" in r["metrics"]
    assert "unschedulable_attempts=0\n" in capfd.readouterr().err


def test_stripped_affinity_term_is_not_correct(tmp_path, monkeypatch):
    from kubernetes_tpu.backend.apiserver import APIServer
    root, cell = affinity_root(tmp_path)
    real = APIServer.create_pods

    def stripped(self, pods):
        for pod in pods:
            pod.spec.affinity = None     # templates share one spec
        return real(self, pods)
    monkeypatch.setattr(APIServer, "create_pods", stripped)
    r = _run(root, cell, 2 ** 31 + 79)
    assert not r["correct"], r["checks"]
    assert r["checks"]["infeasible"]["value"] > 0, r["checks"]
