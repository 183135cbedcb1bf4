"""The program's objects for a `pod_affinity` deployment: the
`pod_anti_affinity` builder's nodes and pods, with each template's required
pod-affinity terms set on its prototype beside its anti-affinity terms."""

from __future__ import annotations

from benchmark.kinds.pod_anti_affinity import build as base


class Builder(base.Builder):
    def _proto(self, tmpl: dict):
        from kubernetes_tpu.api.types import (Affinity, LabelSelector,
                                              PodAffinity, PodAffinityTerm)
        pod = super()._proto(tmpl)
        terms = tuple(
            PodAffinityTerm(topology_key=key,
                            label_selector=LabelSelector.of(match),
                            namespaces=tuple(sorted(namespaces)))
            for namespaces, match, key in self.ref.affinity_terms(tmpl))
        if terms:
            anti = pod.spec.affinity.pod_anti_affinity if pod.spec.affinity \
                else None
            pod.spec.affinity = Affinity(
                pod_affinity=PodAffinity(required=terms),
                pod_anti_affinity=anti)
        return pod
