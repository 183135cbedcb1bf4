#!/usr/bin/env python
"""Standalone shadow-audit replay: re-run any ledgered drain off-line.

The shadow-oracle audit (kubernetes_tpu/obs/audit.py) writes one pickle
per audited drain when `shadowAuditDir` is set — the captured NodeInfo
clones, the pod list, the input fingerprints and the committed device
decisions. This tool re-executes that record through the host oracle
WITHOUT a live scheduler and reports the diff, so "why did pod X land on
node Y" (or "did drain 1234 really diverge") is answerable from an
artifact, long after the process is gone:

  python tools/audit_replay.py /path/to/drain_00001234.pkl
  python tools/audit_replay.py record.pkl --json          # machine form
  python tools/audit_replay.py record.pkl --cap 0         # full replay

Exit codes: 0 = replay matches the recorded device decisions,
2 = divergence found, 3 = unusable record.

The replay is the live audit's own (kubernetes_tpu/obs/replay.py): the
oracle framework is rebuilt from the default plugins the record names
(the whole default set for a record that names none) with the recorded
weights and namespaces — exact for default-plugin profiles (custom
out-of-tree plugin sets need the live scheduler's ledger instead).
"""

from __future__ import annotations

import argparse
import json
import os
import pickle
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("record", help="drain_*.pkl written by the audit "
                                   "(shadowAuditDir)")
    ap.add_argument("--cap", type=int, default=64,
                    help="max pods to replay serially (0 = all; default "
                         "matches the live audit's prefix cap)")
    ap.add_argument("--json", action="store_true",
                    help="print the full machine-readable result")
    args = ap.parse_args(argv)

    from kubernetes_tpu.obs.replay import replay
    try:
        with open(args.record, "rb") as f:
            payload = pickle.load(f)
        result = replay(payload, cap=args.cap)
    except Exception as e:
        print(f"audit_replay: unusable record: {e}", file=sys.stderr)
        return 3
    result["oracle"] = {uid: (v["host"] if v else None)
                        for uid, v in result["oracle"].items()}
    del result["reasons"]

    if args.json:
        print(json.dumps(result, indent=2, default=str))
    else:
        print(f"drain {result['drainId']} ({result['profile']}): "
              f"{result['pods']} pods, replayed {result['replayed']}"
              + (" (truncated)" if result["truncated"] else ""))
        print(f"  ledger hash: "
              f"{'VALID' if result['hashValid'] else 'BROKEN'}")
        for kind, items in result["diffs"].items():
            for d in items:
                print(f"  DIVERGENCE [{kind}] {d['pod']}: "
                      f"device={d['device']!r} oracle={d['oracle']!r}")
        if not result["diffs"]:
            print("  decisions identical to the host oracle")
    if not result["hashValid"]:
        return 3
    return 2 if result["diffs"] else 0


if __name__ == "__main__":
    raise SystemExit(main())
