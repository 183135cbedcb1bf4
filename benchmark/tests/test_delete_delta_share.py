"""delete_delta_share reads scheduler_pod_delete_carry_total: nothing from
a program without the family or from a window without a delete of a bound
pod, and 100 over a small closed loop whose deletes all fold out of the
resident carry."""

import types

from benchmark.harness import Cell, Context
from benchmark.system import System
from benchmark.tests.small import small_root


def _share(cell: Cell, before: dict, after: dict):
    reader = cell.readers["delete_delta_share"]
    delta = {k: v - before.get(k, 0.0) for k, v in after.items()}
    return reader.read(Context(delta=delta))


def test_nothing_without_the_family_or_a_delete(tmp_path):
    cell = Cell(small_root(tmp_path), "Small-Basic.small-closed")
    reader = cell.readers["delete_delta_share"]
    parent = types.SimpleNamespace(metrics=types.SimpleNamespace())
    assert reader.probe(parent) == {}
    assert _share(cell, {}, reader.probe(parent)) is None
    system = System(cell.config, cell.build.Builder(cell.config,
                                                    cell.reference))
    probe = reader.probe(system.sched)
    assert probe == {"delete_delta": 0.0, "delete_reseed": 0.0}
    assert _share(cell, probe, probe) is None


def test_all_deletes_fold_in_a_closed_loop(tmp_path):
    cell = Cell(small_root(tmp_path), "Small-Spread.small-closed")
    system = System(cell.config, cell.build.Builder(cell.config,
                                                    cell.reference))
    system.create_nodes()
    drv = cell.driver.Driver(system, cell.config, cell.traffic, 2 ** 31 + 3)
    reader = cell.readers["delete_delta_share"]
    for _ in range(3):
        drv._iteration()
    before, completed = reader.probe(system.sched), system.completed
    for _ in range(6):
        drv._iteration()
        system.sched.wait_pending()   # binds land: the next one deletes
    assert system.completed > completed
    assert _share(cell, before, reader.probe(system.sched)) == 100.0
