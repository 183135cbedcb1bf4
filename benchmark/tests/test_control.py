"""The control: the reference one precision below the configuration's
(int32 quantities and scores, float32 fractions) decides every pod in the
program's place, and the harness's own comparison finds it not correct
where the program is. Run on the chip at a cell's own size by
benchmark/control.py; here at a small size on the CPU."""

import time

import pytest

from benchmark.harness import run_cell
from benchmark.tests.small import SMALL_CELLS, small_root


@pytest.mark.parametrize("workload", SMALL_CELLS)
def test_control_is_not_correct(tmp_path, workload):
    root = small_root(tmp_path)
    r = run_cell(root, workload, 2 ** 31 + 29, 2.0, False,
                 time.perf_counter(), require_tpu=False, control=True)
    program = r["program_checks"]
    assert all(c["value"] <= c["limit"] for c in program.values()), program
    assert program["off_argmax"]["value"] == 0
    assert not r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
