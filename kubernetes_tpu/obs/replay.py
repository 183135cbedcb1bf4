"""The shadow audit's host-oracle replay, and the worker process it runs in.

One replay for every caller: `replay` re-executes an audit payload
(`AuditRecord.to_payload`) through the host oracle
(framework.runtime.schedule_pod, the real plugins) and diffs it against
the device decisions the payload carries. The audit's worker process
(`serve`, driven through `ReplayWorker`), the audit thread and
`tools/audit_replay.py` all call it.

The worker process exists to keep the replay's seconds of pure-Python
plugin work off the scheduler's interpreter lock. It is a fresh
interpreter with `JAX_PLATFORMS=cpu` in its environment, so it never
opens the accelerator the scheduler holds. It rebuilds each replay
framework from the default plugins, without an API client; a record goes
there only when that rebuild decides exactly as the live framework
(`replay_plugins`).
"""

from __future__ import annotations

import json
import multiprocessing
import os
import pickle
import subprocess
import sys
import time
import weakref
from typing import Optional

from ..framework.interface import CycleState
from ..framework.types import Diagnosis, FitError, PodInfo

# the extension points schedule_pod and the reason replay call
REPLAY_POINTS = ("pre_filter", "filter", "pre_score", "score")


def replay_decisions(framework, nodes: list, pods: list,
                     device: Optional[dict] = None, cap: int = 0):
    """Serial host-oracle replay over PRIVATE NodeInfo clones (mutated in
    place). Returns (oracle {uid: verdict dict | None}, reasons
    {uid: message}, truncated).

    The verdict dict carries `host` (the oracle's own tie-break pick),
    `argmax` (EVERY node tied at max score — the reference breaks ties
    with a seeded RNG, so any member is a correct decision:
    runtime.ScheduleResult.argmax_set is the system's documented parity
    contract) and `scores`. When `device` decisions are given, the
    replay FOLLOWS the device's placements for pods the device bound, so
    each step is judged against the actual committed state and one wrong
    decision counts once instead of cascading.

    Reasons are computed against the POST-REPLAY state — mirroring the
    device path, whose mask diagnosis runs against the post-commit
    snapshot (scheduler._device_fit_error)."""
    from ..framework.runtime import schedule_pod
    limit = len(pods) if cap <= 0 else min(cap, len(pods))
    truncated = limit < len(pods)
    by_name = {ni.name: ni for ni in nodes}
    oracle: dict = {}
    failed: list = []
    for uid, pod, pi in pods[:limit]:
        state = CycleState()
        try:
            result = schedule_pod(framework, state, pod, nodes)
            oracle[uid] = {"host": result.suggested_host,
                           "argmax": set(result.argmax_set),
                           "scores": dict(result.scores)}
        except FitError:
            oracle[uid] = None
            failed.append((uid, pod))
        # apply the COMMITTED placement (fall back to the oracle's own
        # pick when no device decision is recorded for this pod)
        placed = None
        if device is not None:
            placed = device.get(uid)
        elif oracle[uid] is not None:
            placed = oracle[uid]["host"]
        if placed is not None and placed in by_name:
            assumed = pod.with_node_name(placed)
            by_name[placed].add_pod(
                PodInfo(pod=assumed, requests=pi.requests,
                        cpu_nonzero=pi.cpu_nonzero,
                        mem_nonzero=pi.mem_nonzero))
    reasons: dict = {}
    for uid, pod in failed:
        state = CycleState()
        diagnosis = Diagnosis()
        pre_result, status = framework.run_pre_filter_plugins(state, pod,
                                                              nodes)
        if not status.is_success():
            diagnosis.pre_filter_msg = "; ".join(status.reasons)
            if status.plugin:
                diagnosis.unschedulable_plugins.add(status.plugin)
        else:
            framework.find_nodes_that_pass_filters(state, pod, nodes,
                                                   pre_result, diagnosis)
        reasons[uid] = str(FitError(pod, len(nodes), diagnosis))
    return oracle, reasons, truncated


def diff_decisions(rec_device: dict, rec_reasons: dict, oracle: dict,
                   oracle_reasons: dict, reasons_ok: bool = True) -> dict:
    """Assignment/verdict/reason diffs over the replayed pod set. An
    assignment diverges when the device's choice lands OUTSIDE the
    oracle's argmax set — any tied node is a correct decision (the
    reference's randomized tie-break), so tie-order differences (e.g.
    node churn reordering the zone round-robin list against the device
    row order) are not divergences."""
    diffs: dict = {"assignment": [], "verdict": [], "reason": []}
    for uid, verdict in oracle.items():
        d_node = rec_device.get(uid)
        if (d_node is None) != (verdict is None):
            diffs["verdict"].append(
                {"pod": uid, "device": d_node,
                 "oracle": verdict["host"] if verdict else None})
        elif verdict is not None and d_node not in verdict["argmax"]:
            diffs["assignment"].append(
                {"pod": uid, "device": d_node, "oracle": verdict["host"],
                 "deviceScore": verdict["scores"].get(d_node),
                 "oracleScore": verdict["scores"].get(verdict["host"])})
        elif d_node is None and reasons_ok:
            d_msg = rec_reasons.get(uid, "")
            o_msg = oracle_reasons.get(uid, "")
            if d_msg != o_msg:
                diffs["reason"].append(
                    {"pod": uid, "device": d_msg, "oracle": o_msg})
    return {k: v for k, v in diffs.items() if v}


# ---------------------------------------------------------------------------
# the replay framework


def replay_plugins(framework, extenders=(), pods=()) -> Optional[tuple]:
    """The names, in order, of the plugins a replay of `pods` runs under
    `framework`, when a framework rebuilt from the default plugins by
    those names decides exactly as the live one; None when the replay
    needs the live framework.

    That holds when every plugin with a replayed extension point is the
    default plugin of its name with the default arguments, the profile
    has no extenders, and no pod carries volumes or resource claims: the
    client-coupled plugins (VolumeRestrictions, NodeVolumeLimits,
    VolumeBinding, VolumeZone, DynamicResources) read the API server only
    for a pod's own volumes and claims, and skip or pass a pod without
    them whatever the resident pods carry."""
    if extenders or any(p.spec.volumes or p.spec.resource_claims
                        for p in pods):
        return None
    from ..scheduler import default_plugins
    defaults = {p.name(): p for p in default_plugins()}
    names = []
    for p in framework.plugins:
        if not any(hasattr(p, m) for m in REPLAY_POINTS):
            continue
        d = defaults.get(p.name())
        if (type(p) is not type(d)
                or getattr(p, "args", None) != getattr(d, "args", None)):
            return None
        names.append(p.name())
    return tuple(names)


def namespace_map(framework) -> dict:
    """The InterPodAffinity plugin's namespace → labels map (what its
    namespace selectors resolve against), copied."""
    from ..plugins.interpodaffinity import InterPodAffinity
    for p in framework.plugins:
        if isinstance(p, InterPodAffinity):
            return {ns: dict(labels)
                    for ns, labels in p.ns_lister.namespaces.items()}
    return {}


def build_framework(profile_name: str, weights: dict, plugins=None,
                    namespaces: Optional[dict] = None):
    """A replay framework from the default plugins, without an API client:
    those named in `plugins`, in that order (None: the whole default
    set), with `namespaces` behind InterPodAffinity."""
    from ..framework.runtime import Framework
    from ..plugins.interpodaffinity import NamespaceLister
    from ..scheduler import DEFAULT_WEIGHTS, default_plugins
    w = dict(DEFAULT_WEIGHTS)
    w.update(weights or {})
    built = default_plugins(ns_lister=NamespaceLister(dict(namespaces or {})))
    if plugins is not None:
        by_name = {p.name(): p for p in built}
        built = [by_name[n] for n in plugins]
    return Framework(profile_name, built, weights=w)


def _payload_framework(payload: dict):
    return build_framework(payload.get("profile", "default-scheduler"),
                           payload.get("weights", {}),
                           payload.get("plugins"), payload.get("namespaces"))


def _framework_key(payload: dict) -> tuple:
    plugins = payload.get("plugins")
    return (payload.get("profile", "default-scheduler"),
            tuple(sorted((payload.get("weights") or {}).items())),
            None if plugins is None else tuple(plugins),
            tuple(sorted((ns, tuple(sorted(labels.items())))
                         for ns, labels in
                         (payload.get("namespaces") or {}).items())))


def replay(payload: dict, cap: int = 64, framework=None) -> dict:
    """Replay one audit payload through the host oracle and diff it against
    the device decisions it carries. `framework` defaults to one rebuilt
    from the payload (`build_framework`). The result's `oracle` holds each
    replayed pod's verdict dict (None: unschedulable) and `reasons` the
    oracle's FailedScheduling message of each unschedulable pod."""
    from .audit import _sha
    if framework is None:
        framework = _payload_framework(payload)
    nodes = [ni.snapshot_clone() for ni in payload["nodes"]]
    oracle, oracle_reasons, truncated = replay_decisions(
        framework, nodes, payload["pods"], device=payload.get("device"),
        cap=cap)
    diffs = diff_decisions(payload.get("device", {}),
                           payload.get("reasonsDevice", {}),
                           oracle, oracle_reasons,
                           reasons_ok=payload.get("reasonsOk", True)
                           and not truncated)
    # hash integrity: the payload's chain entry must still hash to itself
    chain = json.dumps({"drain": payload["drainId"],
                        "profile": payload["profile"],
                        "fingerprints": payload["fingerprints"]},
                       sort_keys=True).encode()
    hash_ok = _sha(payload.get("prevHash", ""), chain) \
        == payload.get("hash", "")
    return {
        "drainId": payload["drainId"],
        "profile": payload["profile"],
        "pods": len(payload["pods"]),
        "replayed": min(cap, len(payload["pods"])) if cap
        else len(payload["pods"]),
        "truncated": truncated,
        "hashValid": hash_ok,
        "fingerprints": payload["fingerprints"],
        "oracle": oracle,
        "reasons": oracle_reasons,
        "diffs": diffs,
        "divergences": sum(len(v) for v in diffs.values()),
    }


def verdict(payload: dict, cap: int, framework=None) -> dict:
    """The audit's verdict on one payload: outcome, diffs, `truncated` and
    the replay's wall seconds (`replay_s`). The oracle's verdicts and
    reasons (64 pods × every node's score) come back only for a divergent
    record."""
    t0 = time.perf_counter()
    res = replay(payload, cap, framework)
    out = {"outcome": "divergent" if res["diffs"] else "clean",
           "diffs": res["diffs"], "truncated": res["truncated"],
           "replay_s": time.perf_counter() - t0}
    if res["diffs"]:
        out["oracle"] = res["oracle"]
        out["reasons"] = res["reasons"]
    return out


# ---------------------------------------------------------------------------
# the worker process


def _probe() -> dict:
    """What the worker process runs on: its environment's JAX_PLATFORMS,
    the JAX backends it initialized and whether a TPU library is loaded
    (the tests' one look inside the process)."""
    from jax._src import xla_bridge
    return {"pid": os.getpid(),
            "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS"),
            "backends": sorted(xla_bridge._backends),
            "libtpu": any(m == "libtpu" or m.startswith("libtpu.")
                          for m in sys.modules)}


def serve(conn) -> None:
    """The worker process's loop: ("replay", payload bytes, cap) →
    the verdict plus the CPU seconds it took (`cpu_s`), or {"error": ...};
    ("probe",) → what the process runs on. Returns when the scheduler's
    end of the pipe closes."""
    frameworks: dict = {}
    while True:
        try:
            msg = conn.recv()
        except (EOFError, OSError):
            return
        if msg[0] == "probe":
            conn.send(_probe())
            continue
        _, data, cap = msg
        cpu0 = time.process_time()
        try:
            payload = pickle.loads(data)
            key = _framework_key(payload)
            fwk = frameworks.get(key)
            if fwk is None:
                fwk = frameworks[key] = _payload_framework(payload)
            reply = verdict(payload, cap, fwk)
        except Exception as e:      # reported as the record's error
            reply = {"error": f"replay: {e}"}
        reply["cpu_s"] = time.process_time() - cpu0
        try:
            conn.send(reply)
        except (BrokenPipeError, OSError):
            return


class WorkerDied(RuntimeError):
    """The worker process ended before it answered."""


def worker_main(fd: int) -> None:
    """Entry of the worker process: serve the connection on `fd`."""
    from multiprocessing.connection import Connection
    serve(Connection(fd))


def _stop(proc, conn) -> None:
    conn.close()
    if proc.poll() is None:
        proc.terminate()
    proc.wait()


class ReplayWorker:
    """One worker process and the scheduler's end of its pipe. Requests
    are answered in order, one at a time; the process is stopped when
    this object is closed or collected, or at exit, and ends by itself
    when the scheduler's process ends.

    The process is a fresh interpreter, as multiprocessing's `spawn`
    start makes one (a fork would copy the accelerator runtime's
    threads), started directly so that it does not re-run the parent's
    main script, with `JAX_PLATFORMS=cpu` in its environment from its
    first instruction."""

    def __init__(self):
        self._conn, child = multiprocessing.Pipe()
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["PYTHONPATH"] = os.pathsep.join(
            [root] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
        fd = child.fileno()
        try:
            self.proc = subprocess.Popen(
                [sys.executable, "-c",
                 "import sys; from kubernetes_tpu.obs.replay import "
                 "worker_main; worker_main(int(sys.argv[1]))", str(fd)],
                env=env, pass_fds=(fd,), stdin=subprocess.DEVNULL)
        finally:
            child.close()
        self._close = weakref.finalize(self, _stop, self.proc, self._conn)

    def _ask(self, msg) -> dict:
        try:
            self._conn.send(msg)
            return self._conn.recv()
        except (EOFError, OSError) as e:
            try:
                code = self.proc.wait(timeout=1.0)
            except subprocess.TimeoutExpired:
                code = None
            raise WorkerDied(f"replay worker died (exit code {code})") from e

    def replay(self, data: bytes, cap: int) -> dict:
        """Send one pickled payload; return the worker's reply (`verdict`'s
        dict plus `cpu_s`, or {"error", "cpu_s"}). Raises WorkerDied."""
        return self._ask(("replay", data, cap))

    def probe(self) -> dict:
        """`_probe` in the worker process."""
        return self._ask(("probe",))

    def close(self) -> None:
        self._close()
