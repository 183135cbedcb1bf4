#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the chips of this machine and prints the
result as the last line of standard output. Exits non-zero, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for, or
when the system under test is not in the checkout.
"""

import os
import sys
import time

# One string hash for every run: sets of names iterate in one order, so two
# runs of a cell do the same host work. The process re-executes itself once,
# before anything touches JAX; set-up is timed from the first start
# (perf_counter is the system's monotonic clock, the same across exec).
if os.environ.get("PYTHONHASHSEED") != "0":
    os.environ["PYTHONHASHSEED"] = "0"
    os.environ["BENCH_T_PROCESS"] = repr(time.perf_counter())
    os.execv(sys.executable, [sys.executable] + sys.argv)
T_PROCESS = float(os.environ.pop("BENCH_T_PROCESS", time.perf_counter()))

import argparse  # noqa: E402
import json  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark.harness import use_compile_cache
    use_compile_cache(ROOT)
    try:
        import kubernetes_tpu  # noqa: F401  (enables x64 before any array)
    except ImportError as e:
        print(f"the system under test is not in this checkout: {e}",
              file=sys.stderr)
        return 4
    from benchmark.harness import NoDevice, run_cell  # noqa: E402
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), T_PROCESS)
    except NoDevice as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
