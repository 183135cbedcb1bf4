"""A checkout-shaped copy of the benchmark with small cells, for CPU tests.

`small_root(tmp)` copies BENCHMARK.json and benchmark/ into `tmp` and adds
two small configurations and their cells by new files and new entries only,
as a later PR would add a cell.
"""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SMALL_CELLS = ["Small-Basic.small-closed", "Small-Spread.small-closed"]


def small_config(name: str, spread: bool) -> dict:
    with open(os.path.join(REPO, "benchmark", "configs",
                           ("TopologySpreading" if spread
                            else "SchedulingBasic") + "-5000Nodes.json")) as f:
        cfg = json.load(f)
    cfg.update(name=name, nodes=64, init_pods=100, measure_pods=600,
               resident_pods=700)
    return cfg


SMALL_TRAFFIC = {"kind": "closed_backlog", "backlog": 600, "create_chunk": 128,
                 "warmup_iterations": 3, "warmup_drains": 128,
                 "warmup_max_s": 120}


def add_cell(root: str, cfg: dict, traffic: str = "small-closed") -> str:
    """Add a configuration and its cell by a new file and new entries; the
    cell's name is `<config>.<traffic>`. Returns it."""
    path = f"benchmark/configs/{cfg['name']}.json"
    with open(os.path.join(root, path), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = f"{cfg['name']}.{traffic}"
    spec["configs"].append({"name": cfg["name"], "source": "test",
                            "file": path, "reduced": ["nodes"],
                            "why": "CPU test"})
    spec["workloads"].append({"name": cell, "config": cfg["name"],
                              "traffic": traffic, "chips": 1,
                              "why": "CPU test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + [cell]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f, indent=1)
    return cell


def small_root(tmp: str) -> str:
    root = os.path.join(str(tmp), "checkout")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    with open(os.path.join(root, "benchmark", "traffic",
                           "small-closed.json"), "w") as f:
        json.dump(SMALL_TRAFFIC, f)
    add_cell(root, small_config("Small-Basic", False))
    add_cell(root, small_config("Small-Spread", True))
    return root
