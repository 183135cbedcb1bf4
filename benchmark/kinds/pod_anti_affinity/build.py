"""The program's objects for a `pod_anti_affinity` deployment: the
`fit_spread` builder's nodes and pods, with each template's namespace and
required anti-affinity terms set on its prototype."""

from __future__ import annotations

from benchmark.kinds.fit_spread import build as base


class Builder(base.Builder):
    def _proto(self, tmpl: dict):
        from kubernetes_tpu.api.types import (Affinity, LabelSelector,
                                              PodAffinityTerm,
                                              PodAntiAffinity)
        pod = super()._proto(tmpl)
        pod.metadata.namespace = self.ref.namespace(tmpl)
        terms = tuple(
            PodAffinityTerm(topology_key=key,
                            label_selector=LabelSelector.of(match),
                            namespaces=tuple(sorted(namespaces)))
            for namespaces, match, key in self.ref.anti_terms(tmpl))
        if terms:
            pod.spec.affinity = Affinity(
                pod_anti_affinity=PodAntiAffinity(required=terms))
        return pod
