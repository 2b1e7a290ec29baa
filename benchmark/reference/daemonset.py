"""The plain reference for deployments whose pods name their node: the
default profile with the node affinity a DaemonSet controller writes, and
the PreFilterResult that goes with it.

One pod and one node at a time, Python integers and float64; imports
`default_profile.py`'s helpers (and `antiaffinity.py`'s rendering of a
refusal) and nothing of the program.  The interface is the one stated at
`default_profile.py`'s head (KEYS, ARITHMETICS,
ReferenceScheduler(nodes, bound_pods, arith).schedule_one(pod, annotate)).

What it adds to the default profile's reference, from upstream v1.32:

  * NodeAffinity's PreFilter (plugins/nodeaffinity/node_affinity.go): a
    pod with a required term does not Skip (status "success"); where the
    term carries a `matchFields` requirement `metadata.name In [name]`,
    PreFilter returns PreFilterResult{NodeNames: {name}}, which the
    reference simulator records in the `prefilter-result` annotation
    (wrappedplugin.go -> store.AddPreFilterResult):
    {"NodeAffinity":["<name>"]};
  * findNodesThatFitPod (schedule_one.go): with a PreFilterResult, Filter
    runs on the named nodes alone.  Every other node is neither refused
    nor passed: it has no filter-result entry, and after a failed cycle
    no postfilter-result entry either (its status is the absent-nodes
    status, UnschedulableAndUnresolvable, which DefaultPreemption does not
    look at);
  * NodeAffinity's Filter on the named node: the required term, the field
    requirement included, evaluated against the node's name ("passed";
    it comes after TaintToleration and before NodeResourcesFit, as in
    getDefaultPlugins);
  * exactly one feasible node is selected without PreScore or Score:
    prescore-result, score-result and finalscore-result are empty maps;
  * the bound pods accumulate on the one node; when it refuses (Too many
    pods / Insufficient cpu / Insufficient memory), the pod stays pending
    and the rendering is `antiaffinity.py`'s: a filter-result of the one
    node ending at the refusal, a postfilter-result that lists it with an
    empty map (no pod has a lower priority: no victim).

A pod without node affinity takes `antiaffinity.py`'s cycle unchanged.
Anything else raises NotCovered, so that nothing is "checked" by being
ignored: preferred node affinity, a nodeSelector, matchExpressions beside
the field, more than one term or requirement, another field key or
operator, more or fewer than one value, and a name that is no node (the
cycle then runs Filter on no node at all: a different rendering).
"""

from __future__ import annotations

from reference.antiaffinity import (  # noqa: F401  (the interface)
    ARITHMETICS, KEYS, K_PREFILTER, PREFILTERS, Exact, NotCovered,
    _passes, marshal, render, run_filters)
from reference.antiaffinity import ReferenceScheduler as _Refusals
from reference.antiaffinity import _Pod

ERR_NODE_AFFINITY = "node(s) didn't match Pod's node affinity/selector"
_REQUIRED = "requiredDuringSchedulingIgnoredDuringExecution"


def named_node(manifest: dict) -> str | None:
    """The node a pod's required node affinity names, or None for a pod
    without node affinity."""
    spec = manifest.get("spec") or {}
    aff = spec.get("affinity") or {}
    if "nodeAffinity" not in aff:
        return None
    if set(aff) - {"nodeAffinity"}:
        raise NotCovered(f"affinity kinds {sorted(aff)} beside nodeAffinity")
    na = aff["nodeAffinity"] or {}
    if set(na) - {_REQUIRED}:
        raise NotCovered("preferred node affinity")
    terms = (na.get(_REQUIRED) or {}).get("nodeSelectorTerms") or []
    if len(terms) != 1:
        raise NotCovered(f"{len(terms)} node selector terms")
    if set(terms[0]) - {"matchFields"}:
        raise NotCovered(f"node selector term keys {sorted(terms[0])}")
    fields = terms[0].get("matchFields") or []
    if len(fields) != 1:
        raise NotCovered(f"{len(fields)} matchFields requirements")
    req = fields[0]
    if req.get("key") != "metadata.name" or req.get("operator") != "In":
        raise NotCovered(f"field requirement {req.get('key')} {req.get('operator')}")
    values = req.get("values") or []
    if len(values) != 1:
        raise NotCovered(f"{len(values)} values in the field requirement")
    return values[0]


class ReferenceScheduler(_Refusals):
    """antiaffinity.py's cluster state, plugins and refusals; the narrowed
    cycle is this file's."""

    def schedule_one(self, manifest: dict, annotate: bool = True):
        """-> (annotations or None, selected node name or ""); binds."""
        name = named_node(manifest)
        if name is None:
            return super().schedule_one(manifest, annotate)
        # what is left of the manifest is default_profile's pod
        spec = dict(manifest["spec"])
        del spec["affinity"]
        pod = _Pod(dict(manifest, spec=spec))
        if self._interpod_filter(pod) is not None:
            raise NotCovered("pod (anti-)affinity in a cluster of named pods")
        if name not in self.names:
            raise NotCovered(f"the named node {name!r} is no node")
        j = self.names.index(name)

        # Filter, on the PreFilterResult's node alone
        plugins = [("NodeUnschedulable", _passes), ("NodeName", _passes),
                   ("TaintToleration", _passes),
                   ("NodeAffinity",
                    lambda k: None if self.names[k] == name
                    else ERR_NODE_AFFINITY),
                   ("NodeResourcesFit", lambda k: self._fit_filter(pod, k))]
        entry, ok = run_filters(plugins, j)
        if ok:
            self._bind(pod, j)  # one feasible node: no PreScore, no Score
        node = name if ok else ""
        if not annotate:
            return None, node
        status = {nm: "" for nm in PREFILTERS}
        status["NodeAffinity"] = status["NodeResourcesFit"] = "success"
        out = render(status, {name: entry}, {}, {}, {}, node)
        out[K_PREFILTER] = marshal({"NodeAffinity": [name]})
        return out, node
