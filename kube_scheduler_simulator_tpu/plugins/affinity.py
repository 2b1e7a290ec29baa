"""NodeAffinity tensor kernels.

Upstream v1.32 pkg/scheduler/framework/plugins/nodeaffinity.  Both the
Filter predicate (pod.spec.nodeSelector AND
requiredDuringSchedulingIgnoredDuringExecution) and the Score raw value
(sum of weights of matching preferredDuringScheduling terms) depend only on
node labels — static during a replay — so both are precompiled host-side
into dense [P, N] arrays; the device kernels are pure gathers.

Recording semantics (reference shim):
* Filter fail message: "node(s) didn't match Pod's node affinity/selector"
  (upstream ErrReasonPod).
* PreFilter returns Skip when the pod has neither nodeSelector nor required
  affinity -> its Filter is skipped by the framework (no filter-result
  entries for this plugin on any node).
* PreFilter returns a PreFilterResult when every required term names
  nodes by `matchFields: metadata.name In [...]` (`prefilter_node_names`):
  per term the intersection of those value sets, over terms the union.
  The framework then runs Filter on those nodes only
  (framework/pipeline.py `considered_nodes`); the names are recorded in
  the prefilter-result annotation, sorted.  An empty union rejects the pod
  (UnschedulableAndUnresolvable, ERR_CONFLICT).
* PreScore returns Skip when the pod has no preferred terms -> no
  score-result entries.
* ScoreExtensions: DefaultNormalizeScore(100, reverse=false).
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from .base import default_normalize_score, prefilter_rows
from ..state.nodes import NodeTable
from ..utils.tracing import TRACER
from ..state.selectors import (
    match_labels_rows,
    node_selector_rows,
    node_selector_term_rows,
    spec_key,
)

NAME = "NodeAffinity"
ERR_REASON = "node(s) didn't match Pod's node affinity/selector"
ERR_CONFLICT = "pod affinity terms conflict"  # upstream errReasonConflict


class NodeAffinityStatic(NamedTuple):
    """Unique match rows, shared across pods.  Pods stamped from one
    template dedup to the same row, so device residency is [U, N] +
    [V, N] (U/V = unique specs) instead of two dense [P, N] tensors —
    the per-pod xs are just row indices the kernels gather.

    The rows change with the QUEUE (another pod, other terms), so they
    reach the jitted scan as arguments, keyed by shape and dtype
    (state/compile.py ARG_STATICS), and U / V are padded to a power of
    two, at least the pass's floor (_axis_floor: AXIS_FLOOR for one pod,
    twice the pass's pod axis up to AXIS_FLOOR_MAX): a pass has one
    layout whether or not its pods carry terms and however many of them
    share a spec, and another pod's terms are no new executable.  Nothing
    gathers a pad row."""

    req_rows: jnp.ndarray       # [U, N] bool  (row 0 = all-True)
    pref_rows: jnp.ndarray      # [V, N] int32 (row 0 = zeros)


AXIS_FLOOR = 2
AXIS_FLOOR_MAX = 64


def _axis_floor(pod_axis: int) -> int:
    """The least U / V of a pass on a pod axis of `pod_axis` rows: room
    for the identity row and a spec of its own for every pod (p + 1 <=
    twice the bucket), so that the extent follows the pod axis's bucket
    and not how many of the pass's pods happen to share a spec; capped,
    because a row is as long as the cluster has nodes (a pass with more
    distinct specs than AXIS_FLOOR_MAX - 1 pads to the next power of
    two, and affinity_axis_rebuckets_total says so)."""
    return max(AXIS_FLOOR, min(2 * pod_axis, AXIS_FLOOR_MAX))


def _stack_padded(pool: list[np.ndarray], floor: int) -> np.ndarray:
    """The pool's rows as [U, N], U the next power of two (at least
    `floor`); the pad rows repeat row 0."""
    extent = max(floor, 1 << (len(pool) - 1).bit_length())
    return np.stack(pool + [pool[0]] * (extent - len(pool)))


class NodeAffinityXS(NamedTuple):
    req_idx: jnp.ndarray        # [P] int32 into static.req_rows
    pref_idx: jnp.ndarray       # [P] int32 into static.pref_rows
    filter_skip: jnp.ndarray    # [P] bool (PreFilter returned Skip)
    score_skip: jnp.ndarray     # [P] bool (PreScore returned Skip)
    pf_nodes: jnp.ndarray       # [P, K] int32 PreFilterResult (base.prefilter_rows)


def _is_name_in(req: dict) -> bool:
    return req.get("key") == "metadata.name" and req.get("operator") == "In"


def prefilter_node_names(required: dict | None) -> frozenset[str] | None:
    """upstream v1.32 NodeAffinity.PreFilter's node-name narrowing: None
    when some term carries no `metadata.name In` field requirement (the
    terms are ORed, so every node stays eligible) or there is no term;
    else the union over terms of the intersection of each term's value
    sets.  An EMPTY set means the terms conflict."""
    terms = (required or {}).get("nodeSelectorTerms") or []
    if not terms:
        return None
    names: set[str] = set()
    for term in terms:
        term_names: set[str] | None = None
        for req in term.get("matchFields") or []:
            if _is_name_in(req):
                values = set(req.get("values") or [])
                term_names = (values if term_names is None
                              else term_names & values)
        if term_names is None:
            return None
        names |= term_names
    return frozenset(names)


def _names_decide(required: dict) -> bool:
    """Whether `required` says nothing beyond its PreFilterResult: every
    term is made of `metadata.name In` field requirements alone (what the
    DaemonSet controller writes).  A node then matches iff it is one of
    the names, so on the nodes the framework still asks about, the Filter
    passes."""
    return all(
        term.get("matchFields") and not term.get("matchExpressions")
        and all(_is_name_in(req) for req in term["matchFields"])
        for term in required.get("nodeSelectorTerms") or [])


def _required_row(table: NodeTable, node_sel: dict,
                  required: dict | None) -> np.ndarray:
    """[N] bool: the nodes that match the pod's nodeSelector AND its
    required terms; by spec from the node table's memo, so a spec seen
    before on this table is a lookup and not a walk over the nodes."""
    def make():
        TRACER.count("affinity_rows_built_total")
        idx = table.label_index
        row = np.ones(table.n, dtype=bool)
        if node_sel:
            row &= match_labels_rows(node_sel, idx)
        if required:
            row &= node_selector_rows(required, idx)
        return row

    return table.derived.row("affinity_required", spec_key(node_sel, required),
                             make)


def _term_row(table: NodeTable, preference: dict) -> np.ndarray:
    """[N] bool: the nodes one preferred term's `preference` matches, from
    the memo by the term alone: terms that differ in weight share it."""
    def make():
        TRACER.count("affinity_rows_built_total")
        return node_selector_term_rows(preference, table.label_index)

    return table.derived.row("affinity_term", spec_key(preference), make)


def _preferred_row(table: NodeTable, preferred: list[dict]) -> np.ndarray:
    """[N] int32: per node the summed weights of the matching terms."""
    row = np.zeros(table.n, dtype=np.int32)
    for term in preferred:
        row += int(term.get("weight", 0)) * _term_row(
            table, term.get("preference") or {})
    return row


def _count_rebuckets(table: NodeTable, axes: dict[str, int]) -> None:
    """affinity_axis_rebuckets_total{axis}: this pass's padded U / V is
    not the extent of the last pass on this node table, which is another
    layout of the pass's buffers and so another scan executable."""
    last = table.derived.swap("affinity_axes", axes) or axes
    for axis, extent in axes.items():
        # + 0 too: a series that reads 0 says the axes are padded
        TRACER.inc("affinity_axis_rebuckets_total",
                   int(extent != last[axis]), axis=axis)


def build(table: NodeTable, pods: list[dict],
          args: dict | None = None,
          host_out: dict | None = None,
          pod_axis: int = 1,
          ) -> tuple[NodeAffinityStatic, NodeAffinityXS]:
    """pod_axis: the rows of the pass's pod axis (state/compile.py
    pod_axis_bucket), which the U / V floor follows."""
    n, p = table.n, len(pods)
    floor = _axis_floor(pod_axis)
    filter_skip = np.zeros(p, dtype=bool)
    score_skip = np.zeros(p, dtype=bool)

    # addedAffinity (NodeAffinityArgs): admin-configured affinity ANDed
    # onto every pod (upstream node_affinity.go); with it present,
    # PreFilter/PreScore never Skip
    added = (args or {}).get("addedAffinity") or {}
    added_req = added.get("requiredDuringSchedulingIgnoredDuringExecution")
    added_pref = added.get("preferredDuringSchedulingIgnoredDuringExecution") or []
    added_req_row = _required_row(table, {}, added_req) if added_req else None
    added_pref_row = _preferred_row(table, added_pref) if added_pref else None

    # row 0 of each pool is the identity row — what skipped pods gather
    # (their kernel output is masked by the skip flag downstream)
    req_pool: list[np.ndarray] = [np.ones(n, dtype=bool)]
    pref_pool: list[np.ndarray] = [np.zeros(n, dtype=np.int32)]
    req_by_key: dict[str, int] = {}
    pref_by_key: dict[str, int] = {}
    req_idx = np.zeros(p, dtype=np.int32)
    pref_idx = np.zeros(p, dtype=np.int32)
    narrowed: list[frozenset[str] | None] = [None] * p
    conflicts: list[str | None] = [None] * p
    for i, pod in enumerate(pods):
        spec = pod.get("spec") or {}
        node_sel = spec.get("nodeSelector") or {}
        aff = ((spec.get("affinity") or {}).get("nodeAffinity")) or {}
        required = aff.get("requiredDuringSchedulingIgnoredDuringExecution")
        preferred = aff.get("preferredDuringSchedulingIgnoredDuringExecution") or []

        names = prefilter_node_names(required) if required else None
        if names is not None and not names:
            conflicts[i] = ERR_CONFLICT
        else:
            narrowed[i] = names
        if not node_sel and not required and added_req_row is None:
            filter_skip[i] = True
        elif (names and not node_sel and added_req_row is None
              and _names_decide(required)):
            # the identity row: the match row of such a pod would be its
            # names again, a closure constant that differs from pod to
            # pod (a new executable a pass for a DaemonSet's rollout)
            pass
        else:
            key = spec_key(node_sel, required)
            j = req_by_key.get(key)
            if j is None:
                row = _required_row(table, node_sel, required)
                if added_req_row is not None:
                    row = row & added_req_row
                j = len(req_pool)
                req_pool.append(row)
                req_by_key[key] = j
            req_idx[i] = j

        if not preferred and added_pref_row is None:
            score_skip[i] = True
        else:
            key = spec_key(preferred)
            j = pref_by_key.get(key)
            if j is None:
                row = _preferred_row(table, preferred)
                if added_pref_row is not None:
                    row += added_pref_row
                j = len(pref_pool)
                pref_pool.append(row)
                pref_by_key[key] = j
            pref_idx[i] = j

    pref_mat = _stack_padded(pref_pool, floor)
    if host_out is not None:
        # the raw score IS the precompiled row (score_kernel is a pure
        # gather), so the compact replay never transfers it back from the
        # device — the decoder reads this host copy directly
        # (framework/replay.py "host" score group).  Materialized [P, N]
        # int32, C-contiguous: the native decoder indexes it by raw
        # pointer.  A pass in which every pod's scoring is skipped stashes
        # zeros nobody reads (np.zeros is COW-cheap, as VolumeBinding's in
        # state/compile.py): the score group, and with it the compact
        # layout and the executable, is then the same whether or not the
        # pass's pods carry preferred terms
        host_out.setdefault("static_score_rows", {})[NAME] = (
            np.zeros((p, n), dtype=np.int32) if score_skip.all() else
            np.ascontiguousarray(np.take(pref_mat, pref_idx, axis=0)))
    # numpy, xs and carry too: compile_workload reads its flags and the
    # digest off the host bytes, then uploads once (pack_tree)
    static = NodeAffinityStatic(
        req_rows=_stack_padded(req_pool, floor),
        pref_rows=pref_mat,
    )
    _count_rebuckets(table, {"req": static.req_rows.shape[0],
                             "pref": pref_mat.shape[0]})
    if host_out is not None:
        if any(nm is not None for nm in narrowed):
            host_out.setdefault("prefilter_result", {})[NAME] = narrowed
        if any(msg is not None for msg in conflicts):
            host_out.setdefault("prefilter_reject", {})[NAME] = conflicts
    return static, NodeAffinityXS(
        req_idx=req_idx,
        pref_idx=pref_idx,
        filter_skip=filter_skip,
        score_skip=score_skip,
        pf_nodes=prefilter_rows(narrowed, table),
    )


def filter_kernel(static: NodeAffinityStatic, pod_xs) -> jnp.ndarray:
    row = static.req_rows[pod_xs.req_idx]
    return jnp.where(row, 0, 1).astype(jnp.int32)


def score_kernel(static: NodeAffinityStatic, pod_xs) -> jnp.ndarray:
    return static.pref_rows[pod_xs.pref_idx].astype(jnp.int64)


def normalize(raw, feasible):
    return default_normalize_score(raw, feasible, reverse=False)


def decode_filter(code: int, node_idx: int, host_aux) -> str:
    return ERR_REASON
