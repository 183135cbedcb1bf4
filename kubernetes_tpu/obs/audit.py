"""Shadow-oracle audit: continuous production-time bind-parity verification.

The paper's headline contract — device bind decisions identical to the
default host plugins — is verified offline by the parity fuzz suites;
nothing watched it in live operation. This module is the always-on half
(`ShadowOracleAudit` gate): a sampler captures a deterministic replay
record per sampled drain, appends it to a hash-chained drain ledger, and
a background thread hands the record to the replay (`obs/replay.py`),
which re-executes it through the HOST ORACLE (framework.runtime
.schedule_pod — the real plugin implementations, not the kernels) and
diffs:

  - per-pod assignments           → oracle_divergence_total{kind=assignment}
  - scheduled/unschedulable       → oracle_divergence_total{kind=verdict}
  - FailedScheduling reason
    histograms (reference format) → oracle_divergence_total{kind=reason}

Capture runs at a QUIESCED pipeline point (the scheduler drains pending
commits and refreshes the snapshot before cloning), so the cloned
NodeInfos are exactly the state the device carry encodes — a divergence
is a real decision difference, never capture skew. The replay itself is
bounded (`shadow_audit_max_replay_pods` prefix — the serial greedy's
first K decisions depend only on prior state) and runs off the hot path:
in a worker process (`obs/replay.py`), off the scheduler's interpreter
lock, or on the audit thread when the record needs the live framework
(`replay_plugins`) or the audit is synchronous. Reason diffs only run on
fully-replayed drains and only when no external cluster event landed
between dispatch and commit (the device diagnoses against the
commit-time snapshot).

The ledger is a hash chain: each record's sha256 covers the previous
hash plus the input fingerprints (pod-table rows, node statics gen, plan
key, gate/strategy fingerprint, carry hash), so any retroactive edit of
an audited drain breaks `verify()`. With `shadow_audit_dir` set, every
audited drain also writes a standalone pickle that
`tools/audit_replay.py` re-runs without a live scheduler.

Full diffs attach to the drain's FlightRecorder entry; /debug/audit
serves recent audits + divergence detail.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import queue as _queue
import threading
import time as _time
import weakref
from dataclasses import dataclass, field
from typing import Optional

from jax.profiler import TraceAnnotation

from .replay import (ReplayWorker, WorkerDied, namespace_map,
                     replay_plugins, verdict)

GENESIS = "0" * 64

# submit-queue depth beyond which new samples are dropped (outcome
# "skipped") instead of growing without bound — the audit must never
# become a memory leak when the worker falls behind a 100%-sampled soak
MAX_QUEUE = 64
# audit threads of the process route, each with its own worker process,
# that the audits of one interpreter run at once: at most 4, and one core
# fewer than the host has, which the scheduling loop keeps. An audit adds
# one while more of its records are outstanding than it has threads (a
# 64-pod replay at 5000 nodes takes 7-9 s of a worker; 1/64 of 16
# drains/s samples one every 4 s). Every audit may always run one, so the
# schedulers of one process never queue behind each other: N audits run
# at most max(N, MAX_REPLAY_WORKERS) workers.
MAX_REPLAY_WORKERS = max(1, min(4, (os.cpu_count() or 1) - 1))
# the live process-route threads of every audit (the cap above)
_couriers: "weakref.WeakSet" = weakref.WeakSet()
_couriers_lock = threading.Lock()

def _sha(*parts) -> str:
    h = hashlib.sha256()
    for p in parts:
        h.update(p if isinstance(p, bytes) else str(p).encode())
    return h.hexdigest()


def gates_fingerprint(gates) -> str:
    """Stable fingerprint of the feature-gate configuration."""
    known = gates.known()
    return _sha(json.dumps(sorted((n, gates.enabled(n)) for n in known)))


@dataclass
class AuditRecord:
    """One sampled drain: replay inputs + device decisions + verdict."""

    drain_id: int
    profile_name: str
    strategy: str
    weights: dict                  # plugin weights (CLI framework rebuild)
    pods: list                     # [(uid, Pod, PodInfo)] in queue order
    nodes: list                    # PRIVATE NodeInfo clones at capture
    # the replay plugins by name (obs/replay.replay_plugins), when a
    # framework rebuilt from them decides as the live one; None: the
    # replay needs the live framework and runs on the audit thread
    plugins: Optional[tuple] = None
    # InterPodAffinity's namespace → labels map, for that rebuild
    namespaces: dict = field(default_factory=dict)
    # monotonic ledger sequence number, assigned at append: the tail
    # cursor for streaming subscribers (ha/standby.py). 0 = unappended.
    seq: int = 0
    framework: object = None       # live replay framework (not pickled)
    fingerprints: dict = field(default_factory=dict)
    ext_gen: int = 0               # scheduler external-mutation counter
    captured_at: float = 0.0
    prev_hash: str = GENESIS
    hash: str = ""
    # filled at commit time (scheduling thread)
    device: dict = field(default_factory=dict)      # uid → node | None
    reasons_dev: dict = field(default_factory=dict)  # uid → message
    reasons_ok: bool = True        # False: skip the reason diff
    # filled by the audit thread (the verdict)
    outcome: str = "pending"       # clean|divergent|skipped|error|pending
    skip_reason: str = ""
    oracle: dict = field(default_factory=dict)
    reasons_oracle: dict = field(default_factory=dict)
    diffs: dict = field(default_factory=dict)
    truncated: bool = False
    replay_s: float = 0.0
    # device-side replay context for exact /debug/explain (never pickled)
    explain_ctx: object = None
    # the drain's FlightRecord (diff attachment target; never pickled)
    _flight: object = None

    def chain_bytes(self) -> bytes:
        return json.dumps({"drain": self.drain_id,
                           "profile": self.profile_name,
                           "fingerprints": self.fingerprints},
                          sort_keys=True).encode()

    def divergence_count(self) -> int:
        return sum(len(v) for v in self.diffs.values())

    def to_dict(self, details: bool = False) -> dict:
        d = {"drainId": self.drain_id, "profile": self.profile_name,
             "pods": len(self.pods), "outcome": self.outcome,
             "truncated": self.truncated,
             "divergences": self.divergence_count(),
             "replaySeconds": round(self.replay_s, 4),
             "capturedAt": round(self.captured_at, 3),
             "fingerprints": dict(self.fingerprints),
             "prevHash": self.prev_hash, "hash": self.hash}
        if self.skip_reason:
            d["skipReason"] = self.skip_reason
        if details or self.diffs:
            d["diffs"] = self.diffs
        return d

    def to_payload(self) -> dict:
        """Standalone-replayable payload (obs/replay.replay; the worker
        process and tools/audit_replay.py): everything but the live
        framework and device arrays."""
        return {
            "drainId": self.drain_id, "profile": self.profile_name,
            "strategy": self.strategy, "weights": dict(self.weights),
            "plugins": self.plugins,
            "namespaces": dict(self.namespaces),
            "pods": [(uid, pod, pi) for uid, pod, pi in self.pods],
            "nodes": self.nodes,
            "fingerprints": dict(self.fingerprints),
            "prevHash": self.prev_hash, "hash": self.hash,
            "device": dict(self.device),
            "reasonsDevice": dict(self.reasons_dev),
            "reasonsOk": self.reasons_ok,
        }


@dataclass
class ExplainCtx:
    """Device-side inputs for exact after-the-fact explain: re-running
    the drain PREFIX through run_batch from the captured carry
    reconstructs the per-step state any pod's decision was made against
    (parity between run_batch and the dispatched program is the fuzzed
    system invariant — and exactly what the audit itself watches)."""

    cfg: object
    na: object
    carry0: object        # device copy of the pre-drain carry
    table: object
    gd: object
    fam: object
    sig: object           # numpy [n]
    tidx: object          # numpy [n]
    uids: tuple = ()
    names: tuple = ()     # node_names at capture (row → name decode)
    assignments: object = None   # numpy [n], filled at commit


# ---------------------------------------------------------------------------
# hash-chained drain ledger


class DrainLedger:
    """Fixed-capacity ring of AuditRecords forming a hash chain.

    Appended by the scheduling thread at capture time (chain order ==
    dispatch order), outcome fields updated in place by the audit thread,
    read by the debug HTTP thread AND tailed by a standby scheduler
    (ha/standby.py). Three threads touch live records concurrently, so
    the discipline is explicit:

    - ring/head/appended/anchor are guarded by `_lock` (annotations
      below, checked by jaxsan's lock discipline);
    - the chain fields of an appended record (seq, prev_hash, hash, and
      the `chain_bytes()` inputs drain_id/profile_name/fingerprints) are
      IMMUTABLE after `append` — `verify()` may read them from a ring
      snapshot without holding the lock;
    - every OTHER record field (device decisions, outcome, diffs, the
      replay-payload clears) mutates only under `lock` — the audit
      thread takes it via the `lock` property, so a tail subscriber
      never observes a half-written outcome or a nodes list being
      cleared mid-iteration.
    """

    def __init__(self, capacity: int = 128):
        self._lock = threading.Lock()
        self.ring: list = []        # guarded_by: _lock
        self.capacity = capacity
        self.head = GENESIS         # guarded_by: _lock
        self.appended = 0           # guarded_by: _lock
        # prev_hash of the oldest retained record: verify() anchors here
        self._window_anchor = GENESIS  # guarded_by: _lock
        # handoff annex (sharded control plane): each shard steal /
        # rebalance notes the predecessor ledger's (head, cursor) here,
        # folded into its own hash chain — see record_handoff()
        self.handoffs: list = []       # guarded_by: _lock
        self.handoff_head = GENESIS    # guarded_by: _lock

    @property
    def lock(self):
        """The ledger lock, shared with record mutators (the audit
        thread) and tail subscribers so record field updates are atomic
        with respect to reads — see the class docstring discipline."""
        return self._lock

    def append(self, rec: AuditRecord) -> AuditRecord:
        with self._lock:
            rec.prev_hash = self.head
            rec.hash = _sha(self.head, rec.chain_bytes())
            self.head = rec.hash
            self.ring.append(rec)
            self.appended += 1
            rec.seq = self.appended
            if len(self.ring) > self.capacity:
                dropped = self.ring.pop(0)
                self._window_anchor = dropped.hash
        return rec

    def verify(self) -> bool:
        """Recompute the retained window's chain; False = a record was
        edited after the fact (or the chain was spliced). Safe against a
        concurrent appender: chain fields are immutable post-append, so
        verifying a ring snapshot taken under the lock cannot see a
        half-linked record."""
        with self._lock:
            records = list(self.ring)
            anchor = self._window_anchor
            head = self.head
        prev = anchor
        for rec in records:
            if rec.prev_hash != prev:
                return False
            if _sha(prev, rec.chain_bytes()) != rec.hash:
                return False
            prev = rec.hash
        return prev == head

    # -- streaming (ha/standby.py tail subscription) --------------------------

    def cursor(self) -> int:
        """Sequence number of the newest appended record (tail cursor)."""
        with self._lock:
            return self.appended

    def head_hash(self) -> str:
        """Current chain head (splice anchor for a successor ledger)."""
        with self._lock:
            return self.head

    def tail(self, after_seq: int, limit: int = 0) -> list:
        """Retained records with seq > after_seq, oldest first. A cursor
        that fell off the ring window simply yields everything retained —
        the subscriber detects the gap via `lag()` and resyncs."""
        with self._lock:
            out = [r for r in self.ring if r.seq > after_seq]
        if limit and len(out) > limit:
            out = out[:limit]
        return out

    def lag(self, after_seq: int) -> int:
        """How many drains a subscriber at `after_seq` is behind."""
        with self._lock:
            return max(0, self.appended - after_seq)

    def splice(self, head: str, seq: int = 0) -> None:
        """Adopt a predecessor ledger's head as this EMPTY ledger's chain
        anchor (HA takeover): the successor's first record links to the
        dead leader's last, so the combined chain across the handoff
        verifies end to end. Refuses on a non-empty ledger — splicing
        mid-chain is exactly the tamper `verify()` exists to catch."""
        with self._lock:
            if self.ring or self.appended:
                raise ValueError("splice requires an empty ledger")
            self.head = head
            self._window_anchor = head
            self.appended = seq

    # -- shard handoff annex --------------------------------------------------

    def record_handoff(self, shard_id: int, head: str, seq: int) -> dict:
        """Anchor a predecessor shard ledger's chain position on THIS
        (possibly non-empty) ledger. `splice()` only works on an empty
        ledger — a cold takeover — but a shard steal lands on a live
        successor whose own chain must not be rewritten. The annex is a
        separate hash chain folding each handoff (shard id, predecessor
        head, predecessor cursor), so the handoff history is
        tamper-evident exactly like the drain chain itself."""
        with self._lock:
            prev = self.handoff_head
            h = _sha(prev, f"{shard_id}|{head}|{seq}".encode("utf-8"))
            entry = {"shard": int(shard_id), "head": head, "seq": int(seq),
                     "prev": prev, "hash": h}
            self.handoffs.append(entry)
            self.handoff_head = h
        return entry

    def verify_handoffs(self) -> bool:
        """Recompute the handoff annex chain; False = an entry was edited
        (or inserted) after the fact."""
        with self._lock:
            entries = list(self.handoffs)
            head = self.handoff_head
        prev = GENESIS
        for e in entries:
            if e["prev"] != prev:
                return False
            if _sha(prev, f"{e['shard']}|{e['head']}|{e['seq']}"
                    .encode("utf-8")) != e["hash"]:
                return False
            prev = e["hash"]
        return prev == head

    def find(self, drain_id: int) -> Optional[AuditRecord]:
        with self._lock:
            for rec in reversed(self.ring):
                if rec.drain_id == drain_id:
                    return rec
        return None

    def find_pod(self, uid: str) -> Optional[AuditRecord]:
        """Newest record whose drain contains the pod (explain lookup)."""
        with self._lock:
            for rec in reversed(self.ring):
                ctx = rec.explain_ctx
                if ctx is not None and uid in ctx.uids:
                    return rec
        return None

    def records(self, limit: int = 0) -> list:
        with self._lock:
            out = list(self.ring)
        if limit and len(out) > limit:
            out = out[-limit:]
        return out

    def dump(self, limit: int = 0, details: bool = False) -> dict:
        valid = self.verify()
        with self._lock:
            # to_dict reads thread-mutated fields (outcome, diffs):
            # serialize under the lock so a concurrent _process can't
            # hand the HTTP thread a half-written record
            recs = list(self.ring)
            if limit and len(recs) > limit:
                recs = recs[-limit:]
            return {"head": self.head, "appended": self.appended,
                    "chainValid": valid,
                    "records": [r.to_dict(details=details) for r in recs]}


# ---------------------------------------------------------------------------
# the audit sampler + audit thread


class ShadowOracleAudit:
    """See module docstring. Owned by one Scheduler; its threads are its
    own, lazy (the first record of their route) and daemons. Records that
    replay through the live framework go to one serial thread, as their
    replay holds the interpreter lock. Records routed to a worker process
    go to couriers (`_AuditThread`), each with its own worker, added while
    more such records are outstanding than couriers run, up to
    MAX_REPLAY_WORKERS, so the replays keep pace with the sampler."""

    def __init__(self, sample_rate: float = 1.0 / 64.0,
                 max_replay_pods: int = 64, dirpath: str = "",
                 metrics=None, slo=None, gates=None, capacity: int = 32,
                 synchronous: bool = False):
        self.sample_rate = float(sample_rate)
        self.max_replay_pods = int(max_replay_pods)
        self.dirpath = dirpath
        self.metrics = metrics
        self.slo = slo
        self.ledger = DrainLedger(capacity=capacity)
        self.synchronous = synchronous
        self.gates_fp = gates_fingerprint(gates) if gates is not None else ""
        self._accum = 0.0
        # submitted records by route (metrics.AUDIT_REPLAY_ROUTES): to a
        # courier's worker process, or to the serial thread
        self._queues = {"process": _queue.Queue(), "thread": _queue.Queue()}
        # guards the thread lists and the metrics the threads publish to
        self._lock = threading.Lock()
        self._threads = {"process": [], "thread": []}  # guarded_by: _lock
        # the audit threads hold their audit weakly: a dropped Scheduler
        # takes its audit, threads and worker processes with it
        weakref.finalize(self, _end_threads, self._queues)

    # -- sampling -------------------------------------------------------------

    def want(self) -> bool:
        """Deterministic rate-accumulator sampling (no RNG: replays must
        be reproducible run to run)."""
        if self.sample_rate <= 0.0:
            return False
        self._accum += self.sample_rate
        if self._accum < 1.0:
            return False
        self._accum -= 1.0
        if self._backlog() >= MAX_QUEUE:
            self._count("skipped")
            return False
        return True

    # -- capture (scheduling thread, quiesced pipeline) -----------------------

    def capture(self, drain_id: int, profile, qpis: list, snapshot,
                batch, n: int, state, builder, ext_gen: int
                ) -> AuditRecord:
        """Clone the quiesced snapshot + fingerprint the drain inputs and
        append to the hash chain. `batch` is the built PodBatch; `state`
        the ClusterState; `builder` the BatchBuilder (table identity)."""
        nodes = [ni.snapshot_clone() for ni in snapshot.node_info_list]
        # carry hash: per-node aggregate state the device carry encodes —
        # under the quiesce this IS the decision input
        ch = hashlib.sha256()
        for ni in nodes:
            ch.update(ni.name.encode())
            ch.update(str(sorted(ni.requested.items())).encode())
            ch.update(str(len(ni.pods)).encode())
        sig = batch.sig[:n]
        tidx = batch.tidx[:n]
        rows = sorted(set(int(t) for t in tidx))
        table = builder.table
        row_hash = hashlib.sha256(sig.tobytes())
        row_hash.update(tidx.tobytes())
        for u in rows:
            row_hash.update(table.req[u].tobytes())
        fingerprints = {
            "podTableRows": row_hash.hexdigest(),
            "staticsGen": int(state.statics_gen),
            "planKey": _sha(builder.reset_count, builder.table_used,
                            sig.tobytes(), tidx.tobytes(),
                            profile.score_config.strategy, self.gates_fp),
            "gates": self.gates_fp,
            "strategy": profile.score_config.strategy,
            "carry": ch.hexdigest(),
            "pods": int(n),
        }
        plugins = replay_plugins(profile.framework, profile.extenders,
                                 [q.pod for q in qpis])
        rec = AuditRecord(
            drain_id=drain_id, profile_name=profile.name,
            strategy=profile.score_config.strategy,
            weights=dict(profile.framework.weights),
            pods=[(q.pod.uid, q.pod, q.pod_info) for q in qpis],
            nodes=nodes, plugins=plugins,
            namespaces=(namespace_map(profile.framework)
                        if plugins is not None else {}),
            framework=profile.framework,
            fingerprints=fingerprints, ext_gen=ext_gen,
            captured_at=_time.time())
        return self.ledger.append(rec)

    def attach_device(self, rec: AuditRecord, cfg, na, carry, table,
                      batch, n: int, gd, fam, names=()) -> None:
        """Keep the device-side replay inputs for exact explain. The
        carry is COPIED on device (the dispatch chain donates/consumes
        the original)."""
        import jax
        import numpy as np
        carry0 = jax.tree_util.tree_map(lambda x: x.copy()
                                        if hasattr(x, "copy") else x,
                                        carry)
        rec.explain_ctx = ExplainCtx(
            cfg=cfg, na=na, carry0=carry0, table=table, gd=gd, fam=fam,
            sig=np.array(batch.sig[:n]), tidx=np.array(batch.tidx[:n]),
            uids=tuple(uid for uid, _p, _pi in rec.pods),
            names=tuple(names))

    def abandon(self, rec: AuditRecord, reason: str) -> None:
        """The drain degraded off the audited dispatch path before its
        results existed (host fallback, overlay, device fault)."""
        with self.ledger.lock:
            rec.outcome = "skipped"
            rec.skip_reason = reason
        self._count("skipped")

    # -- submit (scheduling thread, commit time) ------------------------------

    def submit(self, rec: AuditRecord, out, names: list, fail_msgs: dict,
               flight_rec=None, ext_gen: int = 0) -> None:
        """Record the committed device decisions and hand the record to
        the audit thread (or process inline in synchronous mode)."""
        import numpy as np
        device: dict = {}
        for i, (uid, _pod, _pi) in enumerate(rec.pods):
            a = int(out[i]) if i < len(out) else -1
            device[uid] = names[a] if a >= 0 else None
        with self.ledger.lock:
            rec.device = device
            rec.reasons_dev = dict(fail_msgs)
            # an external cluster event between dispatch and commit moves
            # the snapshot the device diagnosis reads — assignments stay
            # exact (computed from the captured carry), reasons are not
            # comparable
            rec.reasons_ok = ext_gen == rec.ext_gen
            if rec.explain_ctx is not None:
                rec.explain_ctx.assignments = np.array(out[:len(rec.pods)])
            rec._flight = flight_rec
        if self.synchronous:
            self._process(rec)
            return
        route = "thread" if rec.plugins is None else "process"
        self._queues[route].put(rec)
        self._ensure_thread(route)

    # -- audit threads --------------------------------------------------------

    def _backlog(self) -> int:
        return sum(q.qsize() for q in self._queues.values())

    def _ensure_thread(self, route: str) -> None:
        """Start a thread for `route` while more of its records are
        outstanding (the one just submitted included) than its threads
        run: one on the thread route; on the process route up to
        MAX_REPLAY_WORKERS over every audit, and always one."""
        q = self._queues[route]
        with self._lock:
            threads = [t for t in self._threads[route] if t.is_alive()]
            self._threads[route] = threads
            cap = MAX_REPLAY_WORKERS if route == "process" else 1
            if len(threads) >= min(cap, q.unfinished_tasks):
                return
            t = _AuditThread(weakref.ref(self), q, route)
            with _couriers_lock:
                if route == "process":
                    if threads and sum(c.is_alive() for c in _couriers) \
                            >= MAX_REPLAY_WORKERS:
                        return
                    _couriers.add(t)
                t.start()
            threads.append(t)

    def flush(self, timeout: float = 30.0) -> None:
        """Wait for every submitted record to finish replaying (tests,
        bench end)."""
        deadline = _time.monotonic() + timeout
        while _time.monotonic() < deadline:
            if all(q.unfinished_tasks == 0 for q in self._queues.values()):
                return
            _time.sleep(0.01)

    def _process(self, rec: AuditRecord, courier=None) -> None:
        """Replay + diff + publish one record, as an `audit_replay`
        profiler span on the thread that runs it: in `courier`'s worker
        process when given, else on this thread through the live
        framework. That thread's CPU seconds (the replay itself on the
        thread route; pickling, the wait and publication on the process
        route) count under scheduler_thread_cpu_seconds_total
        {thread="audit"}."""
        cpu0 = _time.thread_time()
        try:
            with TraceAnnotation("audit_replay"):
                self._replay(rec, courier)
        finally:
            if self.metrics is not None:
                with self._lock:
                    self.metrics.thread_cpu_seconds.inc(
                        "audit", by=_time.thread_time() - cpu0)

    def _replay(self, rec: AuditRecord, courier=None) -> None:
        t0 = _time.perf_counter()
        # rec.nodes is the LEDGERED capture state — the CLI pickle and
        # /debug re-read it pristine, and the replay works on clones of
        # it. Only this thread replaces the list (the clear below, under
        # the lock), so the payload's reference stays whole.
        with self.ledger.lock:
            payload = rec.to_payload()
        where = "thread" if courier is None else "process"
        if self.metrics is not None:
            with self._lock:
                self.metrics.shadow_audit_replays.inc(where)
        data = None
        try:
            if courier is not None:
                data = pickle.dumps(payload,
                                    protocol=pickle.HIGHEST_PROTOCOL)
                reply = courier.replay(data, self.max_replay_pods)
            else:
                reply = verdict(payload, self.max_replay_pods,
                                framework=rec.framework)
        except Exception as e:
            reply = {"error": f"replay: {e}"}
        if "error" in reply:
            with self.ledger.lock:
                rec.outcome = "error"
                rec.skip_reason = reply["error"]
                rec.replay_s = _time.perf_counter() - t0
            self._count("error")
            return
        diffs = reply["diffs"]
        divergent = reply["outcome"] == "divergent"
        # one atomic publication of the verdict: a tail subscriber (or
        # /debug/audit) sees either a fully "pending" record or a fully
        # replayed one — never outcome without diffs or vice versa
        with self.ledger.lock:
            rec.replay_s = reply["replay_s"]
            rec.oracle = reply.get("oracle", {})
            rec.reasons_oracle = reply.get("reasons", {})
            rec.truncated = reply["truncated"]
            rec.diffs = diffs
            rec.outcome = reply["outcome"]
        with self._lock:
            if self.metrics is not None:
                for kind, items in diffs.items():
                    self.metrics.oracle_divergence.inc(kind, by=len(items))
                self.metrics.audit_replay_duration.observe(rec.replay_s)
                if where == "process":
                    self.metrics.audit_worker_cpu_seconds.inc(
                        by=reply["cpu_s"])
            if self.slo is not None:
                self.slo.observe("divergence", good=0 if divergent else 1,
                                 bad=1 if divergent else 0)
        self._count(rec.outcome)
        flight = getattr(rec, "_flight", None)
        if flight is not None:
            flight.audit = {"outcome": rec.outcome,
                            "divergences": rec.divergence_count(),
                            "diffs": diffs,
                            "hash": rec.hash}
        if self.dirpath:
            self._persist(rec, data)
        if not divergent:
            # memory bound: a clean record's replay payload (O(nodes)
            # NodeInfo clones) is no longer needed — the hash chain,
            # fingerprints and explain context stay; divergent records
            # keep everything for the post-mortem (and the pickle, when
            # persistence is on, already captured the full payload)
            with self.ledger.lock:
                rec.nodes = []

    def _count(self, outcome: str) -> None:
        if self.metrics is not None:
            with self._lock:
                self.metrics.shadow_audit_drains.inc(outcome)

    def _persist(self, rec: AuditRecord, data: Optional[bytes]) -> None:
        """Write the record's payload: `data`, the bytes the worker
        replayed, when it went there."""
        try:
            if data is None:
                data = pickle.dumps(rec.to_payload(),
                                    protocol=pickle.HIGHEST_PROTOCOL)
            os.makedirs(self.dirpath, exist_ok=True)
            path = os.path.join(self.dirpath,
                                f"drain_{rec.drain_id:08d}.pkl")
            with open(path, "wb") as f:
                f.write(data)
        except Exception:           # persistence is best-effort
            pass

    # -- serving --------------------------------------------------------------

    def dump(self, limit: int = 32, details: bool = False) -> dict:
        d = self.ledger.dump(limit=limit, details=details)
        d["sampleRate"] = self.sample_rate
        d["maxReplayPods"] = self.max_replay_pods
        d["queued"] = self._backlog()
        return d


def _end_threads(queues: dict) -> None:
    for route, q in queues.items():
        for _ in range(MAX_REPLAY_WORKERS if route == "process" else 1):
            q.put(None)


class _AuditThread(threading.Thread):
    """An audit thread: replays the records of one route for the audit
    that `ref` still reaches, and ends when that audit has been
    collected. A courier (the process route) owns one worker process,
    started at its first record and replaced when it dies."""

    def __init__(self, ref, q: _queue.Queue, route: str):
        super().__init__(daemon=True, name="shadow-oracle-audit")
        self._ref = ref
        self._q = q
        self.courier = route == "process"
        self.worker: Optional[ReplayWorker] = None

    def replay(self, data: bytes, cap: int) -> dict:
        """One pickled payload through this courier's worker process.
        Waiting on the pipe releases the interpreter lock."""
        if self.worker is None:
            self.worker = ReplayWorker()
        try:
            return self.worker.replay(data, cap)
        except WorkerDied:
            self.worker.close()
            self.worker = None
            raise

    def run(self) -> None:
        try:
            while True:
                rec = self._q.get()
                try:
                    audit = self._ref()
                    if audit is None or rec is None:
                        return
                    try:
                        audit._process(rec, self if self.courier else None)
                    except Exception:   # the audit must never kill the thread
                        with audit.ledger.lock:
                            rec.outcome = "error"
                        audit._count("error")
                    del audit
                finally:
                    self._q.task_done()
        finally:
            if self.worker is not None:
                self.worker.close()
