"""The plain reference of the `fit_spread` kind: upstream kube-scheduler's
filter and score for deployments of identical nodes and pods that request
cpu and memory and may carry hard zone-spread constraints, in NumPy,
replayed over what the run recorded.

It imports nothing of the program and takes nothing the program made. Its
inputs are the configuration file (nodes, templates) and the client-side
record of the run: the pods the benchmark created, in creation order, the
node each was bound to as the watch saw it, and each completion (delete)
with the number of pods the scheduler had already taken from its queue when
the delete was issued.

Decision order. All benchmark pods share one priority, so the scheduler's
queue (PrioritySort: priority, then enqueue time) hands them out in creation
order, and a drain's placements are sequential in that order. A pod taken
from the queue after a delete was issued saw that delete; one taken before
did not. So the state a pod was decided against is exactly: every earlier
pod at its recorded node, minus the completions issued before it left the
queue. The replay rebuilds that state pod by pod and asks, for each pod:

- filter: does the recorded node pass NodeResourcesFit (cpu, memory, pod
  count) and PodTopologySpread's DoNotSchedule constraints?
- score: is the recorded node in the argmax set of the default score stack
  over the feasible nodes? (Upstream breaks ties at random, so any member
  of the set is a correct answer.)

Score stack (upstream default weights): NodeResourcesFit LeastAllocated
(cpu, memory, weight 1 each) x1, NodeResourcesBalancedAllocation x1,
TaintToleration x3, NodeAffinity x2, PodTopologySpread x2, InterPodAffinity
x2, ImageLocality x1. For these deployments the last five add the same
points to every node (no taints, no affinity, no images, no soft spread
constraint, no owning workload for the system defaults), so the argmax set
is that of LeastAllocated + BalancedAllocation, computed here as upstream
does: int64 integer division for LeastAllocated, float64 fractions truncated
to int64 for BalancedAllocation (least_allocated.go, balanced_allocation.go).

`control(cfg, record)` is the same scheduler one precision below (int32
quantities and scores, float32 fractions), put in the program's place: it
decides every pod of the run itself, and its decisions go through `check`.
"""

from __future__ import annotations

import numpy as np

ZONE_KEY = "topology.kubernetes.io/zone"
MAX_SCORE = 100
MODELLED = {"requests", "labels", "spread"}
_BINARY = {"Ki": 2 ** 10, "Mi": 2 ** 20, "Gi": 2 ** 30, "Ti": 2 ** 40}


def quantity(resource: str, value) -> int:
    """A Kubernetes quantity in the units the scheduler compares: cpu in
    millicores, everything else in plain units (bytes, pods)."""
    s = str(value).strip()
    if resource == "cpu":
        if s.endswith("m"):
            return int(s[:-1])
        return int(round(float(s) * 1000))
    for suffix, mult in _BINARY.items():
        if s.endswith(suffix):
            return int(s[:-len(suffix)]) * mult
    return int(s)


def node_name(i: int) -> str:
    return f"node-{i}"


def node_labels(cfg: dict, i: int) -> dict:
    """Each label key takes its values round robin over the nodes, as
    upstream's labelNodePrepareStrategy does."""
    return {k: vals[i % len(vals)]
            for k, vals in cfg.get("node_labels", {}).items()}


def node_capacity(cfg: dict) -> dict:
    """resource -> quantity string; allocatable defaults to capacity, as
    the API server's defaulting does for a node without allocatable."""
    return dict(cfg["node"]["capacity"])


def template(cfg: dict, seq: int) -> dict:
    """The template of the seq-th pod created: the first `init_pods` are
    the upstream initPods, every later one a measurePods pod."""
    return cfg["init_pod"] if seq < int(cfg["init_pods"]) else cfg["measure_pod"]


class Scorer:
    """Per-node filter and score arrays for one cluster state."""

    modelled = MODELLED     # template keys this scorer knows

    def __init__(self, cfg: dict, itype=np.int64, ftype=np.float64):
        self.cfg = cfg
        self.itype, self.ftype = itype, ftype
        n = int(cfg["nodes"])
        alloc = {r: quantity(r, v) for r, v in node_capacity(cfg).items()}
        self.alloc = {r: np.full(n, v, dtype=np.int64).astype(itype)
                      for r, v in alloc.items()}
        zones = cfg.get("node_labels", {}).get(ZONE_KEY, [])
        self.zones = len(zones)
        self.zone = np.arange(n) % max(1, self.zones)
        self.used = {r: np.zeros(n, dtype=itype) for r in ("cpu", "memory")}
        self.npods = np.zeros(n, dtype=np.int64)
        # pods per zone by label set (one label set per template)
        self.zone_labels: dict[tuple, np.ndarray] = {}
        self._reqs: dict[int, dict] = {}

    def _req(self, tmpl: dict) -> dict:
        req = self._reqs.get(id(tmpl))
        if req is None:
            req = self._reqs[id(tmpl)] = {
                r: np.array(quantity(r, tmpl["requests"].get(r, 0)),
                            dtype=np.int64).astype(self.itype)
                for r in ("cpu", "memory")}
        return req

    def move(self, node: int, tmpl: dict, sign: int) -> None:
        """Add (sign=+1) or remove (sign=-1) one pod of `tmpl` on `node`."""
        req = self._req(tmpl)
        for r in ("cpu", "memory"):
            self.used[r][node] += req[r] if sign > 0 else -req[r]
        self.npods[node] += sign
        key = tuple(sorted(tmpl.get("labels", {}).items()))
        if key not in self.zone_labels:
            self.zone_labels[key] = np.zeros(max(1, self.zones), np.int64)
        self.zone_labels[key][self.zone[node]] += sign

    def spread(self, tmpl: dict):
        """(maxSkew, pods matching the selector per zone, self match) of
        each DoNotSchedule zone constraint of `tmpl`."""
        labels = tmpl.get("labels", {})
        for c in tmpl.get("spread", []):
            if c["whenUnsatisfiable"] != "DoNotSchedule":
                continue
            if c["topologyKey"] != ZONE_KEY or not self.zones:
                raise ValueError("the reference models zone spreading over "
                                 "nodes that all carry a zone label only")
            sel = c["matchLabels"]
            per_zone = np.zeros(self.zones, np.int64)
            for key, counts in self.zone_labels.items():
                if sel and all(dict(key).get(k) == v for k, v in sel.items()):
                    per_zone += counts
            self_match = int(bool(sel) and all(labels.get(k) == v
                                               for k, v in sel.items()))
            yield int(c["maxSkew"]), per_zone, self_match

    def feasible(self, tmpl: dict) -> np.ndarray:
        extra = set(tmpl) - self.modelled
        if extra:
            raise ValueError(f"the reference does not model {sorted(extra)}")
        req = self._req(tmpl)
        ok = self.npods + 1 <= self.alloc["pods"].astype(np.int64)
        for r in ("cpu", "memory"):
            free = self.alloc[r] - self.used[r]
            ok &= ~(req[r] > free)
        for max_skew, per_zone, self_match in self.spread(tmpl):
            ok &= per_zone[self.zone] + self_match - per_zone.min() <= max_skew
        return ok

    def feasible_at(self, tmpl: dict, node: int) -> bool:
        """`feasible(tmpl)[node]`, on the one node alone."""
        req = self._req(tmpl)
        if self.npods[node] + 1 > self.alloc["pods"][node]:
            return False
        for r in ("cpu", "memory"):
            if req[r] > self.alloc[r][node] - self.used[r][node]:
                return False
        z = self.zone[node]
        return all(per_zone[z] + self_match - per_zone.min() <= max_skew
                   for max_skew, per_zone, self_match in self.spread(tmpl))

    def score(self, tmpl: dict) -> np.ndarray:
        req = self._req(tmpl)
        it, ft = self.itype, self.ftype
        hundred = it(MAX_SCORE)
        least = np.zeros(len(self.npods), dtype=it)
        weights = it(0)
        fractions = []
        for r in ("cpu", "memory"):
            cap = self.alloc[r]
            requested = self.used[r] + req[r]
            has = cap != 0
            safe = np.where(has, cap, it(1))
            s = np.where(requested > cap, it(0),
                         ((cap - requested) * hundred) // safe)
            least = least + np.where(has, s, it(0))
            weights = weights + it(1)
            f = np.minimum(requested.astype(ft) / safe.astype(ft), ft(1))
            fractions.append(np.where(has, f, ft(np.nan)))
        least = least // weights
        f0, f1 = fractions
        both = ~np.isnan(f0) & ~np.isnan(f1)
        std = np.where(both, np.abs((f0 - f1) / ft(2)), ft(0))
        balanced = ((ft(1) - std) * ft(MAX_SCORE)).astype(it)
        return least.astype(np.int64) + balanced.astype(np.int64)

    def choose(self, tmpl: dict) -> int:
        """This scorer's own decision: the smallest node index of its argmax
        set over its feasible nodes, or -1 where none is feasible."""
        ok = self.feasible(tmpl)
        if not ok.any():
            return -1
        return int(np.argmax(np.where(ok, self.score(tmpl), LOWEST)))


LOWEST = np.iinfo(np.int64).min


def _replay(cfg: dict, record, scorer: Scorer, decide) -> None:
    """Walk the run in creation order with the completions applied where
    they were issued; `decide(seq, tmpl) -> node` places each pod."""
    node_of = record.node_of
    deletes = sorted(record.deletes)
    di = 0
    for seq in range(record.created):
        while di < len(deletes) and deletes[di][0] <= seq:
            gone = deletes[di][1]
            if node_of[gone] >= 0:
                scorer.move(int(node_of[gone]), template(cfg, gone), -1)
            di += 1
        tmpl = template(cfg, seq)
        node = decide(seq, tmpl)
        if node >= 0:
            scorer.move(node, tmpl, +1)


def check(cfg: dict, record, sample: np.ndarray, scorer=Scorer) -> dict:
    """Replay the run and compare every recorded decision.

    `record` has `created` (templates are by position), `node_of` (seq ->
    node index or -1), `deletes` (list of (decided_before, seq)), `due`
    (the seqs whose answer is due: created in the window) and
    `bind_conflicts`. `sample` holds the seqs whose argmax is checked.
    Returns each number compared (the kind's checks.json holds its limit)
    and, besides, `checked` and `max_score_gap`. A kind that extends this
    one passes its own `scorer` class.
    """
    ref = scorer(cfg)
    node_of = record.node_of
    want = np.zeros(record.created, dtype=bool)
    want[sample] = True
    n = {"infeasible": 0, "off_argmax": 0, "checked": 0, "max_score_gap": 0}

    def judge(seq, tmpl):
        node = int(node_of[seq])
        if node < 0:
            return node
        if want[seq]:
            ok = ref.feasible(tmpl)
            score = np.where(ok, ref.score(tmpl), LOWEST)
            best = score.max()
            n["checked"] += 1
            if not ok[node]:
                n["infeasible"] += 1
                n["off_argmax"] += 1
            elif score[node] < best:
                n["off_argmax"] += 1
                n["max_score_gap"] = max(n["max_score_gap"],
                                         int(best - score[node]))
        elif not ref.feasible_at(tmpl, node):
            # capacity and spread of every bind, on its node alone
            n["infeasible"] += 1
        return node

    _replay(cfg, record, ref, judge)
    due = np.asarray(record.due, dtype=np.int64)
    return {"unbound": int((node_of[due] < 0).sum()) if len(due) else 0,
            "bind_conflicts": int(record.bind_conflicts), **n}


def control(cfg: dict, record, scorer=Scorer) -> np.ndarray:
    """The control's answers: every pod of the run decided by this
    reference one precision below the configuration's (int32 quantities
    and scores, float32 fractions), against the same completions. Returns
    seq -> node index (-1: it found no feasible node), to be compared by
    `check` in place of the program's."""
    sc = scorer(cfg, np.int32, np.float32)
    out = np.full(record.created, -1, dtype=np.int64)
    program = record.node_of
    record.node_of = out        # completions free the control's nodes

    def decide(seq, tmpl):
        out[seq] = sc.choose(tmpl)
        return out[seq]

    try:
        with np.errstate(over="ignore"):     # int32 wraps: that is the control
            _replay(cfg, record, sc, decide)
    finally:
        record.node_of = program
    return out
