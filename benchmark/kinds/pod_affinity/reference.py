"""The plain reference of the `pod_affinity` kind: the `pod_anti_affinity`
reference (kinds/pod_anti_affinity/reference.py: NodeResourcesFit, the
default score stack, required anti-affinity in both directions, the replay
of the run) extended by upstream kube-scheduler's required inter-pod
*affinity*, in NumPy.

It imports nothing of the program and takes nothing the program made; its
inputs are the configuration file and the client-side record, as for
`fit_spread`.

Filter (interpodaffinity/filtering.go satisfyPodAffinity), on top of the
anti-affinity kind's: a node passes the incoming pod's required affinity
terms when, for every term, the node carries the term's topologyKey and an
existing pod selected by *all* of the pod's terms runs in the node's domain
of that key. Otherwise it passes only as the first pod of its series: no
existing pod anywhere is selected by all of the terms (the plugin's
affinityCounts map is empty) and the pod matches all of its own terms. A
node without a term's key fails either way.

Score (interpodaffinity/scoring.go processExistingPod): each existing pod's
required affinity term that selects the incoming pod adds
hardPodAffinityWeight (1) to every node in that pod's domain of the term's
key. InterPodAffinity's NormalizeScore maps the sum min-max onto 0..100
over the feasible nodes, `int64(100 * ((s - min) / (max - min)))` in
float64, all 0 when max = min; its weight in the default stack is 2. The
incoming pod's preferred terms and existing pods' preferred terms are not
modelled (a template key for them raises).

Every other template key, term key or term form raises, as in the
anti-affinity kind.
"""

from __future__ import annotations

import numpy as np

from benchmark.kinds.fit_spread.reference import MAX_SCORE
from benchmark.kinds.pod_anti_affinity import reference as anti
from benchmark.kinds.pod_anti_affinity.reference import (  # noqa: F401
    anti_terms, namespace, node_capacity, node_labels, node_name, quantity,
    selects, template)

MODELLED = anti.MODELLED | {"affinity"}
HARD_POD_AFFINITY_WEIGHT = 1    # apis/config/v1 defaults
W_INTER_POD_AFFINITY = 2        # the default profile's weight


def affinity_terms(tmpl: dict) -> list:
    """(namespaces, matchLabels, topologyKey) of each required affinity
    term; no `namespaces` means the owner pod's namespace."""
    out = []
    for t in tmpl.get("affinity", []):
        extra = set(t) - anti.TERM_KEYS
        if extra:
            raise ValueError(f"the reference does not model {sorted(extra)}")
        out.append((frozenset(t.get("namespaces") or [namespace(tmpl)]),
                    dict(t["matchLabels"]), t["topologyKey"]))
    return out


class Scorer(anti.Scorer):
    modelled = MODELLED

    def __init__(self, cfg: dict, itype=np.int64, ftype=np.float64):
        super().__init__(cfg, itype, ftype)
        n = int(cfg["nodes"])
        keys = {t[2] for tm in (cfg["init_pod"], cfg["measure_pod"])
                for t in affinity_terms(tm)} - set(self.dom)
        labels = [node_labels(cfg, i) for i in range(n)] if keys else []
        for k in sorted(keys):
            ids: dict[str, int] = {}
            self.dom[k] = np.array([ids.setdefault(lab[k], len(ids))
                                    if k in lab else -1 for lab in labels],
                                   np.int64)
            self._ndom[k] = max(len(ids), 1)
        self._terms: dict[int, list] = {}

    def _aff(self, tmpl: dict) -> list:
        terms = self._terms.get(id(tmpl))
        if terms is None:
            terms = self._terms[id(tmpl)] = affinity_terms(tmpl)
        return terms

    def _affinity_counts(self, tmpl: dict):
        """The incoming pod's terms, and per term the pods per domain of
        its key that all of the terms select (filtering.go's
        affinityCounts)."""
        terms = self._aff(tmpl)
        peers = [c for c in self.classes.values()
                 if all(selects(t, c.tmpl) for t in terms)]
        counts = [sum((c.on[key] for c in peers),
                      np.zeros(self._ndom[key], np.int64))
                  for _ns, _match, key in terms]
        return terms, counts

    def affinity_ok(self, tmpl: dict) -> np.ndarray:
        n = len(self.npods)
        terms, counts = self._affinity_counts(tmpl)
        if not terms:
            return np.ones(n, bool)
        has_key = np.ones(n, bool)
        pods_exist = np.ones(n, bool)
        for (_ns, _match, key), per_dom in zip(terms, counts):
            d = self.dom[key]
            has_key &= d >= 0
            pods_exist &= (d >= 0) & (per_dom[np.maximum(d, 0)] > 0)
        if not any(c.any() for c in counts):      # first pod of its series
            pods_exist |= all(selects(t, tmpl) for t in terms)
        return has_key & pods_exist

    def affinity_ok_at(self, tmpl: dict, node: int) -> bool:
        terms, counts = self._affinity_counts(tmpl)
        exist = True
        for (_ns, _match, key), per_dom in zip(terms, counts):
            d = self.dom[key][node]
            if d < 0:
                return False
            exist &= bool(per_dom[d] > 0)
        return exist or (not any(c.any() for c in counts)
                         and all(selects(t, tmpl) for t in terms))

    def feasible(self, tmpl: dict) -> np.ndarray:
        return super().feasible(tmpl) & self.affinity_ok(tmpl)

    def feasible_at(self, tmpl: dict, node: int) -> bool:
        return (super().feasible_at(tmpl, node)
                and self.affinity_ok_at(tmpl, node))

    def affinity_raw(self, tmpl: dict) -> np.ndarray:
        """scoring.go's topology score of each node: hardPodAffinityWeight
        per existing pod whose required affinity term selects `tmpl`, on
        every node in that pod's domain of the term's key."""
        s = np.zeros(len(self.npods), np.int64)
        for other in self.classes.values():
            for term in self._aff(other.tmpl):
                if selects(term, tmpl):
                    d = self.dom[term[2]]
                    on = other.on[term[2]]
                    s += np.where(d >= 0, HARD_POD_AFFINITY_WEIGHT
                                  * on[np.maximum(d, 0)], 0)
        return s

    def affinity_score(self, tmpl: dict, feasible: np.ndarray) -> np.ndarray:
        """InterPodAffinity's normalised score (before its weight) over the
        `feasible` nodes; 0 elsewhere."""
        it, ft = self.itype, self.ftype
        raw = self.affinity_raw(tmpl).astype(it)
        out = np.zeros(len(raw), it)
        if not feasible.any():
            return out
        lo, hi = raw[feasible].min(), raw[feasible].max()
        if hi <= lo:
            return out
        frac = (raw - lo).astype(ft) / ft(hi - lo)
        return np.where(feasible, (ft(MAX_SCORE) * frac).astype(it), out)

    def score(self, tmpl: dict) -> np.ndarray:
        base = super().score(tmpl)
        norm = self.affinity_score(tmpl, self.feasible(tmpl))
        return base + W_INTER_POD_AFFINITY * norm.astype(np.int64)


def check(cfg: dict, record, sample: np.ndarray) -> dict:
    return anti.base.check(cfg, record, sample, Scorer)


def control(cfg: dict, record) -> np.ndarray:
    return anti.base.control(cfg, record, Scorer)
