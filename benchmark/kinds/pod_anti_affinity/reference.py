"""The plain reference of the `pod_anti_affinity` kind: the `fit_spread`
reference (kinds/fit_spread/reference.py: NodeResourcesFit, the default
score stack, the replay of the run in creation order with the completions
where they were issued) extended by upstream kube-scheduler's required
inter-pod anti-affinity, in NumPy.

It imports nothing of the program and takes nothing the program made; its
inputs are the configuration file and the client-side record, as for
`fit_spread`.

Filter (pkg/scheduler/framework/plugins/interpodaffinity/filtering.go), on
top of NodeResourcesFit, in both directions:

- the incoming pod's required anti-affinity terms (satisfyPodAntiAffinity):
  a node fails when an existing pod that the term selects (its namespace in
  the term's `namespaces`, its labels matching the term's `matchLabels`)
  runs on a node with the same value of the term's topologyKey;
- existing pods' required anti-affinity terms
  (satisfyExistingPodsAntiAffinity): a node fails when an existing pod in
  its topology domain carries a required anti term that selects the
  incoming pod.

A node without the topology key is in no domain: it neither blocks nor is
blocked (filtering.go updates its count maps only for nodes that carry the
key). A term with no `namespaces` means the owner pod's namespace
(framework/types.go newAffinityTerm).

Score. interpodaffinity/scoring.go gives points for the incoming pod's
preferred terms and for existing pods' required *affinity* terms (times
hardPodAffinityWeight) and preferred terms; existing pods' required
*anti-affinity* terms give none. These deployments have no scored term, so
InterPodAffinity adds the same (zero) points to every node and the argmax
set is `fit_spread`'s: LeastAllocated + BalancedAllocation over the
feasible nodes.

Node labels: `node_labels` round robin as in `fit_spread`, and each key in
`unique_node_labels` takes a value of its own per node, the node's name
(upstream's uniqueNodeLabelStrategy).

Any other template key, term key or term form (namespaceSelector,
matchExpressions, preferred terms, affinity) raises: the reference does not
model it.
"""

from __future__ import annotations

import numpy as np

from benchmark.kinds.fit_spread import reference as base
from benchmark.kinds.fit_spread.reference import (  # noqa: F401
    node_capacity, node_name, quantity, template)

MODELLED = base.MODELLED | {"namespace", "anti_affinity"}
TERM_KEYS = {"topologyKey", "matchLabels", "namespaces"}


def node_labels(cfg: dict, i: int) -> dict:
    labels = base.node_labels(cfg, i)
    for key in cfg.get("unique_node_labels", []):
        labels[key] = node_name(i)
    return labels


def namespace(tmpl: dict) -> str:
    return tmpl.get("namespace", "default")


def anti_terms(tmpl: dict) -> list:
    """(namespaces, matchLabels, topologyKey) of each required anti term."""
    out = []
    for t in tmpl.get("anti_affinity", []):
        extra = set(t) - TERM_KEYS
        if extra:
            raise ValueError(f"the reference does not model {sorted(extra)}")
        out.append((frozenset(t.get("namespaces") or [namespace(tmpl)]),
                    dict(t["matchLabels"]), t["topologyKey"]))
    return out


def selects(term, tmpl: dict) -> bool:
    """Does the term select pods of `tmpl`?"""
    namespaces, match, _key = term
    labels = tmpl.get("labels", {})
    return (namespace(tmpl) in namespaces
            and all(labels.get(k) == v for k, v in match.items()))


class _Class:
    """The pods of one template: its terms, and how many run in each
    domain of each topology key."""

    def __init__(self, tmpl: dict, ndom: dict):
        self.tmpl = tmpl
        self.terms = anti_terms(tmpl)
        self.on = {k: np.zeros(n, np.int64) for k, n in ndom.items()}


class Scorer(base.Scorer):
    modelled = MODELLED

    def __init__(self, cfg: dict, itype=np.int64, ftype=np.float64):
        super().__init__(cfg, itype, ftype)
        n = int(cfg["nodes"])
        labels = [node_labels(cfg, i) for i in range(n)]
        keys = {t[2] for tm in (cfg["init_pod"], cfg["measure_pod"])
                for t in anti_terms(tm)}
        self.dom: dict[str, np.ndarray] = {}    # key -> domain per node, -1
        ndom = {}
        for k in sorted(keys):
            ids: dict[str, int] = {}
            self.dom[k] = np.array([ids.setdefault(lab[k], len(ids))
                                    if k in lab else -1 for lab in labels],
                                   np.int64)
            ndom[k] = max(len(ids), 1)
        self._ndom = ndom
        self.classes: dict[int, _Class] = {}

    def _class(self, tmpl: dict) -> _Class:
        c = self.classes.get(id(tmpl))
        if c is None:
            c = self.classes[id(tmpl)] = _Class(tmpl, self._ndom)
        return c

    def move(self, node: int, tmpl: dict, sign: int) -> None:
        super().move(node, tmpl, sign)
        c = self._class(tmpl)
        for k, on in c.on.items():
            d = self.dom[k][node]
            if d >= 0:
                on[d] += sign

    def _blockers(self, tmpl: dict):
        """(topology key, pods per domain) of each resident class that
        blocks `tmpl` through a required anti term, either direction."""
        me = self._class(tmpl)
        for other in self.classes.values():
            for term in me.terms:           # the incoming pod's terms
                if selects(term, other.tmpl):
                    yield term[2], other.on[term[2]]
            for term in other.terms:        # existing pods' terms
                if selects(term, tmpl):
                    yield term[2], other.on[term[2]]

    def anti_ok(self, tmpl: dict) -> np.ndarray:
        ok = np.ones(len(self.npods), bool)
        for k, on in self._blockers(tmpl):
            d = self.dom[k]
            ok &= ~((d >= 0) & (on[np.maximum(d, 0)] > 0))
        return ok

    def anti_ok_at(self, tmpl: dict, node: int) -> bool:
        for k, on in self._blockers(tmpl):
            d = self.dom[k][node]
            if d >= 0 and on[d] > 0:
                return False
        return True

    def feasible(self, tmpl: dict) -> np.ndarray:
        return super().feasible(tmpl) & self.anti_ok(tmpl)

    def feasible_at(self, tmpl: dict, node: int) -> bool:
        return (super().feasible_at(tmpl, node)
                and self.anti_ok_at(tmpl, node))


def check(cfg: dict, record, sample: np.ndarray) -> dict:
    return base.check(cfg, record, sample, Scorer)


def control(cfg: dict, record) -> np.ndarray:
    return base.control(cfg, record, Scorer)
