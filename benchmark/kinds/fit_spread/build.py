"""The program's objects for a `fit_spread` deployment: nodes and pods
stamped from the configuration file, laid out as the kind's reference
(`reference.py`, passed in as `ref`) reads them."""

from __future__ import annotations


class Builder:
    """Each pod template becomes one prototype; a pod is a shallow copy
    with its own metadata and status, as the API's aliasing contract allows
    (spec objects are shared)."""

    def __init__(self, cfg: dict, ref):
        self.cfg = cfg
        self.ref = ref
        self._protos: dict[int, object] = {}

    def nodes(self) -> list:
        from kubernetes_tpu.api import resources as res
        from kubernetes_tpu.api.types import Node, NodeStatus, ObjectMeta
        cfg, ref = self.cfg, self.ref
        alloc = res.parse_resource_dict(ref.node_capacity(cfg))
        return [Node(metadata=ObjectMeta(name=ref.node_name(i),
                                         creation_index=i,
                                         labels=ref.node_labels(cfg, i)),
                     status=NodeStatus(capacity=dict(alloc),
                                       allocatable=dict(alloc)))
                for i in range(int(cfg["nodes"]))]

    def _proto(self, tmpl: dict):
        from kubernetes_tpu.api import resources as res
        from kubernetes_tpu.api.types import (Container, LabelSelector,
                                              ObjectMeta, Pod, PodSpec,
                                              PodStatus,
                                              TopologySpreadConstraint)
        extra = set(tmpl) - self.ref.MODELLED
        if extra:
            raise ValueError(f"the fit_spread kind has no {sorted(extra)}")
        spec = PodSpec(containers=[Container(
            name="c0",
            requests=res.parse_resource_dict(dict(tmpl["requests"])))])
        for c in tmpl.get("spread", []):
            spec.topology_spread_constraints.append(TopologySpreadConstraint(
                max_skew=int(c["maxSkew"]), topology_key=c["topologyKey"],
                when_unsatisfiable=c["whenUnsatisfiable"],
                label_selector=LabelSelector.of(dict(c["matchLabels"]))))
        return Pod(metadata=ObjectMeta(name="proto",
                                       labels=dict(tmpl.get("labels", {}))),
                   spec=spec, status=PodStatus())

    def pod(self, seq: int):
        tmpl = self.ref.template(self.cfg, seq)
        proto = self._protos.get(id(tmpl))
        if proto is None:
            proto = self._protos[id(tmpl)] = self._proto(tmpl)
        new = object.__new__
        p = new(type(proto))
        p.__dict__.update(proto.__dict__)
        meta = new(type(proto.metadata))
        meta.__dict__.update(proto.metadata.__dict__)
        meta.name = f"p-{seq}"
        meta.uid = f"{meta.namespace}/p-{seq}"
        meta.creation_index = seq
        p.metadata = meta
        st = new(type(proto.status))
        st.__dict__.update(proto.status.__dict__)
        st.conditions = []
        p.status = st
        return p
