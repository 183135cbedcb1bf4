#!/usr/bin/env python3
"""The control of `correct`, run on the chip at a cell's own size:

    python3 benchmark/control.py --workload <name> --seeds 1,2,3 [--seconds 5]

For each seed, one run of the cell as the benchmark runs it; then the
configuration kind's control (for `fit_spread`: the reference one precision
below the configuration's, int32 quantities and scores, float32 fractions)
decides every pod of that run in the program's place, and the harness's own
comparison judges its answers. Prints one JSON line per seed: the program's
checks and `correct`, which has to read false, with the control's checks.
The benchmark's own runs never run this.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark.harness import use_compile_cache
    use_compile_cache(ROOT)
    import kubernetes_tpu  # noqa: F401
    from benchmark.harness import run_cell

    for seed in (int(s) for s in args.seeds.split(",")):
        r = run_cell(ROOT, args.workload, seed, args.seconds, False,
                     time.perf_counter(), control=True)
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "checks": r["checks"],
                          "program_checks": r["program_checks"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
