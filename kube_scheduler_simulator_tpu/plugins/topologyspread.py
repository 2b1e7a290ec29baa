"""PodTopologySpread tensor kernels.

Upstream v1.32 pkg/scheduler/framework/plugins/podtopologyspread.  The
dynamic quantity is the number of already-placed pods matching each
constraint's label selector PER NODE; it lives in the scan carry as a
dense counts[C, N] matrix where C indexes *unique count groups*
(namespace, topologyKey, selector) deduplicated across the pass's pods.
A pod's per-domain count for one of its constraints is folded from it
inside the step, over the nodes that constraint counts on (upstream's
PreFilter / PreScore add a node's pods to a topology pair only where the
node passes the constraint's inclusion policies and carries every key of
the pod's constraints of that kind): `_fold`.

Statics, all ARGUMENTS of the jitted scan (state/compile.py ARG_STATICS:
they change with the QUEUE, another pod being another set of groups), on
padded axes, so that a pass of other groups is no new executable:
  dom_idx[K, N]    domain index of each node for each distinct topology
                   key among the pass's groups (-1: node lacks the label;
                   a pad row is all -1), from NodeTable.domain_row's memo
  is_hostname[K]   the key is kubernetes.io/hostname (upstream's Score and
                   PreScore special-case it BY NAME)
  is_ident[K]      every keyed node is a domain of its own: the fold is
                   the identity
  group_key[C]     the dom_idx row of each count group
  elig_rows[E, N]  the nodes a constraint's inclusion policies keep, one
                   row a distinct (policies, nodeSelector, required
                   affinity, tolerations); row 0 keeps every node
  dom_iota[Dp]     0..Dp-1: Dp bounds the domains of every key that is
                   not an identity (a zone's 8 -> 8)
  log_table[N+1]   math.log(sz + 2) for sz = 0..N, float64, built on the
                   host so that the value is libm's
Per-pod xs:
  pm[P, C]         does pod p's labels+namespace match group c's selector
  per-pod constraint slots (padded to MAX_CONSTRAINTS): group id, maxSkew,
                   whenUnsatisfiable, the slot's elig_rows row, minDomains
                   unsatisfied.

Filter (DoNotSchedule): skew = count(node domain) + selfMatch - min over
the domains present among the counted nodes; fails with "node(s) didn't
match pod topology spread constraints" (or the "(missing required label)"
variant).  Constraints are checked in pod order and the first violation
wins, as upstream does.

Score (ScheduleAnyway), as upstream's PreScore and Score compute it: a
node lacking any scored key is ignored (score 0, left out of min and
max).  Per constraint the weight is log(sz + 2), sz the number of
FEASIBLE nodes that are not ignored for the hostname key and the number
of distinct values among them for any other key; the count is the node's
own matching pods for the hostname key (no inclusion policy: upstream
counts nodeInfo.Pods in Score) and the folded domain count otherwise; a
node's raw score is the sum of count * weight + (maxSkew - 1), Go
math.Round'ed.  NormalizeScore: score = 100 * (max + min - s) / max over
scored feasible nodes, 100 for all when max == 0.

Modeled knobs: matchLabelKeys (merged into the selector per incoming pod,
effective_constraints), minDomains (global minimum forced to 0 when fewer
counted domains exist), nodeAffinityPolicy (default Honor) and
nodeTaintsPolicy (default Ignore), per constraint, for both the counting
and the minimum.  One simplification remains (docs/SEMANTICS.md,
"PodTopologySpread knobs"): system-default constraints derived from
service/replicaset owners are not modeled (upstream applies them only to
a pod without constraints).
"""

from __future__ import annotations

import json
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# the module, not its names: a plugin module imported first imports the
# state package while it is itself half initialised
from . import affinity
from .base import MAX_NODE_SCORE
from ..state.nodes import NodeTable
from ..state.selectors import (
    has_untolerated_do_not_schedule_taint,
    label_selector_matches,
    spec_key,
)
from ..utils.tracing import TRACER

NAME = "PodTopologySpread"
ERR_SKEW = "node(s) didn't match pod topology spread constraints"
ERR_MISSING_LABEL = "node(s) didn't match pod topology spread constraints (missing required label)"
HOSTNAME_KEY = "kubernetes.io/hostname"   # upstream v1.LabelHostname

MAX_CONSTRAINTS = 4
_BIG = np.int64(1) << 40
# the least extents of the key axis K and of the domain bound Dp: two
# keys (a zone and a hostname constraint) and a zone label's 8 values
KEY_FLOOR = 2
DOM_FLOOR = 8


class SpreadStatic(NamedTuple):
    dom_idx: jnp.ndarray      # [K, N] int32
    is_hostname: jnp.ndarray  # [K] bool
    is_ident: jnp.ndarray     # [K] bool
    group_key: jnp.ndarray    # [C] int32 into dom_idx
    elig_rows: jnp.ndarray    # [E, N] bool (row 0 = all-True)
    dom_iota: jnp.ndarray     # [Dp] int32
    log_table: jnp.ndarray    # [N + 1] float64


class SpreadXS(NamedTuple):
    pm: jnp.ndarray          # [P, C] bool — pod matches group selector
    c_id: jnp.ndarray        # [P, MC] int32 (-1 pad)
    max_skew: jnp.ndarray    # [P, MC] int32
    is_filter: jnp.ndarray   # [P, MC] bool (DoNotSchedule)
    is_score: jnp.ndarray    # [P, MC] bool (ScheduleAnyway)
    elig_idx: jnp.ndarray    # [P, MC] int32 into static.elig_rows: the
    #   nodes the slot's inclusion policies keep
    md_unsat: jnp.ndarray    # [P, MC] bool — minDomains unsatisfied: fewer
    #   counted domains than spec.minDomains -> global minimum becomes 0
    filter_skip: jnp.ndarray  # [P] bool
    score_skip: jnp.ndarray   # [P] bool


def _pod_constraints(pod: dict) -> list[dict]:
    return (pod.get("spec") or {}).get("topologySpreadConstraints") or []


def effective_constraints(pod: dict) -> list[dict]:
    """The pod's first MAX_CONSTRAINTS topologySpreadConstraints with
    matchLabelKeys merged into the labelSelector as In-expressions
    (upstream enableMatchLabelKeysInPodTopologySpread, on by default since
    1.27: keys the incoming pod doesn't carry are skipped).  Used by BOTH
    the tensor build and the sequential oracle so group interning, counts
    and self-match all see the same selector."""
    meta = pod.get("metadata") or {}
    pod_labels = {k: str(v) for k, v in (meta.get("labels") or {}).items()}
    out = []
    for c in _pod_constraints(pod)[:MAX_CONSTRAINTS]:
        keys = c.get("matchLabelKeys") or []
        extra = [
            {"key": k, "operator": "In", "values": [pod_labels[k]]}
            for k in keys if k in pod_labels
        ]
        if extra:
            sel = dict(c.get("labelSelector") or {})
            sel["matchExpressions"] = list(sel.get("matchExpressions") or []) + extra
            c = dict(c, labelSelector=sel)
        out.append(c)
    return out


def is_hard(c: dict) -> bool:
    return c.get("whenUnsatisfiable", "DoNotSchedule") == "DoNotSchedule"


def _intern_groups(pods: list[dict]):
    """(group_list, per_pod_slots): unique (namespace, topologyKey,
    selector) count groups over the pass's effective constraints in
    first-seen order, plus each pod's [(group_id, constraint)] slots."""
    groups: dict[tuple, int] = {}
    group_list: list[tuple[str, str, dict | None]] = []
    per_pod: list[list[tuple[int, dict]]] = []
    for pod in pods:
        ns = (pod.get("metadata") or {}).get("namespace") or "default"
        slots = []
        for c in effective_constraints(pod):
            sel = c.get("labelSelector")
            gk = (ns, c.get("topologyKey", ""), json.dumps(sel, sort_keys=True))
            if gk not in groups:
                groups[gk] = len(group_list)
                group_list.append((ns, c.get("topologyKey", ""), sel))
            slots.append((groups[gk], c))
        per_pod.append(slots)
    return group_list, per_pod


def _bucket(count: int, floor: int) -> int:
    """The padded extent of an axis that holds `count` entries: the next
    power of two, at least `floor`."""
    return max(floor, 1 << max(count - 1, 0).bit_length())


def inclusion_spec(pod: dict, c: dict) -> tuple:
    """What of (pod, constraint) decides which nodes the constraint counts
    on (upstream matchNodeInclusionPolicies): (nodeSelector, required node
    affinity, tolerations), each None where its policy ignores it or the
    pod has none; all None keeps every node."""
    honor_aff = (c.get("nodeAffinityPolicy") or "Honor") == "Honor"
    honor_taints = (c.get("nodeTaintsPolicy") or "Ignore") == "Honor"
    spec = pod.get("spec") or {}
    return (
        (spec.get("nodeSelector") or None) if honor_aff else None,
        (((spec.get("affinity") or {}).get("nodeAffinity")) or {}).get(
            "requiredDuringSchedulingIgnoredDuringExecution")
        if honor_aff else None,
        (spec.get("tolerations") or []) if honor_taints else None,
    )


def _inclusion_row(table: NodeTable, incl: tuple, fragment: str):
    """([N] bool, how many nodes it leaves out) for one inclusion_spec
    (`fragment`: its spec_key): nodeAffinityPolicy Honor keeps
    the nodes matching the pod's nodeSelector + required node affinity
    (NodeAffinity's own memoised row), nodeTaintsPolicy Honor the nodes
    without a NoSchedule/NoExecute taint the pod does not tolerate
    (upstream helper.DoNotScheduleTaintsFilterFunc).  By spec from the
    node table's memo."""
    node_sel, required, tols = incl

    def make():
        TRACER.inc("spread_rows_built_total", kind="eligible")
        row = np.ones(table.n, dtype=bool)
        if node_sel or required:
            row = row & affinity._required_row(
                table, node_sel or {}, required)
        if tols is not None:
            row = row & np.asarray([
                not has_untolerated_do_not_schedule_taint(table.taints[j], tols)
                for j in range(table.n)], dtype=bool)
        return row, int(table.n - row.sum())

    return table.derived.row("spread_eligible", fragment, make)


def _count_rebuckets(table: NodeTable, axes: dict[str, int]) -> None:
    """spread_axis_rebuckets_total{axis}: a padded axis of this pass
    (groups C, keys K, rows E, domains Dp) is not the extent of the last
    pass on this node table, which is another layout of the pass's
    buffers and so another scan executable."""
    last = table.derived.swap("spread_axes", axes) or axes
    for axis, extent in axes.items():
        # + 0 too: a series that reads 0 says the axes are padded
        TRACER.inc("spread_axis_rebuckets_total",
                   int(extent != last[axis]), axis=axis)


def build(table: NodeTable, pods: list[dict], pod_axis: int = 1):
    """-> (SpreadStatic, SpreadXS, the pass's count groups in carry
    order, the carry's group extent C).  pod_axis: the rows of the pass's
    pod axis (state/compile.py pod_axis_bucket), which the C / E floor
    follows as NodeAffinity's U / V does."""
    n, p = table.n, len(pods)
    floor = affinity._axis_floor(pod_axis)
    group_list, per_pod = _intern_groups(pods)
    c_ext = _bucket(len(group_list), floor)

    # --- one domain row a distinct topology key --------------------------
    # the row depends only on (node labels, topologyKey): kept on the
    # table (NodeTable.domain_row), shared with InterPodAffinity's terms
    key_ids: dict[str, int] = {}
    key_rows: list[np.ndarray] = []
    group_key = np.zeros(c_ext, dtype=np.int32)
    hostname, ident, d_fold = [], [], 0
    for c_id, (_, key, _) in enumerate(group_list):
        k = key_ids.get(key)
        if k is None:
            k = key_ids[key] = len(key_rows)
            row, n_domains = table.domain_row(key)
            key_rows.append(row)
            hostname.append(key == HOSTNAME_KEY)
            ident.append(int(n_domains) == int((row >= 0).sum()))
            if not ident[k]:
                d_fold = max(d_fold, int(n_domains))
        group_key[c_id] = k
    k_ext = _bucket(len(key_rows), KEY_FLOOR)
    pad = k_ext - len(key_rows)
    dom_idx = np.stack(key_rows + [np.full(n, -1, dtype=np.int32)] * pad)
    is_hostname = np.asarray(hostname + [False] * pad, dtype=bool)
    is_ident = np.asarray(ident + [True] * pad, dtype=bool)
    d_ext = _bucket(d_fold, DOM_FLOOR)

    # --- pod x group selector matches ------------------------------------
    pm = np.zeros((p, c_ext), dtype=bool)
    for i, pod in enumerate(pods):
        pod_ns = (pod.get("metadata") or {}).get("namespace") or "default"
        pod_labels = {k: str(v) for k, v in ((pod.get("metadata") or {}).get("labels") or {}).items()}
        for c_id, (ns, _, sel) in enumerate(group_list):
            pm[i, c_id] = ns == pod_ns and label_selector_matches(sel, pod_labels)

    # --- per-pod constraint slots ----------------------------------------
    c_id_arr = np.full((p, MAX_CONSTRAINTS), -1, dtype=np.int32)
    max_skew = np.ones((p, MAX_CONSTRAINTS), dtype=np.int32)
    is_filter = np.zeros((p, MAX_CONSTRAINTS), dtype=bool)
    is_score = np.zeros((p, MAX_CONSTRAINTS), dtype=bool)
    elig_idx = np.zeros((p, MAX_CONSTRAINTS), dtype=np.int32)
    md_unsat = np.zeros((p, MAX_CONSTRAINTS), dtype=bool)
    filter_skip = np.ones(p, dtype=bool)
    score_skip = np.ones(p, dtype=bool)
    # row 0 keeps every node: what a slot without a pod-side selector,
    # required term or Honor'd taint policy gathers
    elig_pool: list[np.ndarray] = [np.ones(n, dtype=bool)]
    elig_by_spec: dict[tuple, tuple[int, int]] = {}
    excluded_total = 0

    for i, slots in enumerate(per_pod):
        excluded = 0
        for m, (cid, c) in enumerate(slots):
            c_id_arr[i, m] = cid
            max_skew[i, m] = int(c.get("maxSkew", 1))
            hard = is_hard(c)
            is_filter[i, m] = hard
            is_score[i, m] = not hard
            incl = inclusion_spec(pods[i], c)
            if incl != (None, None, None):
                ek = spec_key(*incl)
                hit = elig_by_spec.get(ek)
                if hit is None:
                    row, left_out = _inclusion_row(table, incl, ek)
                    hit = elig_by_spec[ek] = (len(elig_pool), left_out)
                    elig_pool.append(row)
                elig_idx[i, m] = hit[0]
                excluded = max(excluded, hit[1])
            md = c.get("minDomains")
            if hard and md is not None:
                counted = elig_pool[elig_idx[i, m]].copy()
                for cid2, c2 in slots:
                    if is_hard(c2):
                        counted &= key_rows[group_key[cid2]] >= 0
                doms = np.unique(key_rows[group_key[cid]][counted])
                # zero counted domains: no node's value is in upstream's
                # map, so every keyed node passes whatever the minimum is
                md_unsat[i, m] = 0 < len(doms) < int(md)
        filter_skip[i] = not is_filter[i].any()
        score_skip[i] = not is_score[i].any()
        excluded_total += excluded
    # + 0 too: a series that reads 0 says no policy left a node out
    TRACER.count("spread_excluded_nodes_total", excluded_total)

    e_ext = _bucket(len(elig_pool), floor)
    elig_rows = np.stack(elig_pool + [elig_pool[0]] * (e_ext - len(elig_pool)))
    _count_rebuckets(table, {"groups": c_ext, "keys": k_ext, "rows": e_ext,
                             "domains": d_ext})

    # numpy, xs and carry too: compile_workload reads its flags off the
    # host bytes, then uploads once (pack_tree)
    static = SpreadStatic(
        dom_idx=dom_idx,
        is_hostname=is_hostname,
        is_ident=is_ident,
        group_key=group_key,
        elig_rows=elig_rows,
        dom_iota=np.arange(d_ext, dtype=np.int32),
        # only a ScheduleAnyway slot gathers a weight: a pass without one
        # carries zeros in the table's place (one layout) and asks the
        # node table's memo nothing
        log_table=table.derived.once(
            "spread_log_table", lambda: np.asarray(
                [math.log(float(sz + 2)) for sz in range(n + 1)],
                dtype=np.float64))
        if is_score.any() else np.zeros(n + 1, dtype=np.float64),
    )
    xs = SpreadXS(
        pm=pm,
        c_id=c_id_arr,
        max_skew=max_skew,
        is_filter=is_filter,
        is_score=is_score,
        elig_idx=elig_idx,
        md_unsat=md_unsat,
        filter_skip=filter_skip,
        score_skip=score_skip,
    )
    return static, xs, group_list, c_ext


def _slot(static: SpreadStatic, pod, counts, m):
    """Slot m of one pod: (active, its key row k, dom[N], has_key[N], the
    group's per-node counts [N])."""
    cid = pod.c_id[m]
    c = jnp.maximum(cid, 0)
    k = static.group_key[c]
    dom = static.dom_idx[k]
    return cid >= 0, k, dom, dom >= 0, counts[c]


def _kind_keys(static: SpreadStatic, pod, counts, kind):
    """[N] bool: the nodes that carry the key of every slot of one kind
    (`kind` [MC] bool: pod.is_filter or pod.is_score) — upstream's
    nodeLabelsMatchSpreadConstraints over that kind's constraints."""
    n = static.dom_idx.shape[1]
    keyed = jnp.ones(n, dtype=bool)
    for m in range(MAX_CONSTRAINTS):
        active, _, _, has_key, _ = _slot(static, pod, counts, m)
        keyed = keyed & jnp.where(active & kind[m], has_key, True)
    return keyed


def _fold(static: SpreadStatic, k, dom, vals):
    """[N]: at every node the sum of `vals` over the nodes of its domain
    (vals is already 0 on the nodes that are not counted).  Two masked
    reductions over a [Dp, N] one-hot, no scatter and no gather; the
    identity where every node is a domain of its own."""
    with jax.named_scope("kss_spread_fold"):
        onehot = static.dom_iota[:, None] == dom[None, :]           # [Dp, N]
        per_dom = jnp.sum(jnp.where(onehot, vals[None, :], 0), axis=1)
        folded = jnp.sum(jnp.where(onehot, per_dom[:, None], 0), axis=0)
        return jnp.where(static.is_ident[k], vals, folded.astype(vals.dtype))


def filter_kernel(static: SpreadStatic, pod, counts) -> jnp.ndarray:
    """[N] int32: 0 pass; 1+2m missing-label at slot m; 2+2m skew at slot m.

    counts is per node [C, N].  A slot counts on the nodes its inclusion
    policies keep that carry every DoNotSchedule key of the pod
    (upstream calPreFilterState); the minimum over the domains present
    among them equals the minimum of the folded count over those nodes.
    minDomains (spec'd and unsatisfied -> md_unsat at build time) forces
    the global minimum to 0, upstream getMinMatchNum semantics."""
    code = jnp.zeros(static.dom_idx.shape[1], dtype=jnp.int32)
    keyed = _kind_keys(static, pod, counts, pod.is_filter)
    for m in range(MAX_CONSTRAINTS):
        active, k, dom, has_key, per_node = _slot(static, pod, counts, m)
        counted = static.elig_rows[pod.elig_idx[m]] & keyed & has_key
        cnt = _fold(static, k, dom, jnp.where(counted, per_node, 0))
        min_match = jnp.min(jnp.where(counted, cnt.astype(jnp.int64), _BIG))
        min_match = jnp.where(pod.md_unsat[m], 0, min_match)
        check = active & pod.is_filter[m]
        self_match = pod.pm[jnp.maximum(pod.c_id[m], 0)].astype(jnp.int64)
        skew = cnt + self_match - min_match
        viol = jnp.where(has_key, jnp.where(skew > pod.max_skew[m], 2 + 2 * m, 0), 1 + 2 * m)
        viol = jnp.where(check, viol, 0).astype(jnp.int32)
        code = jnp.where((code == 0) & (viol > 0), viol, code)
    return code


def score_kernel(static: SpreadStatic, pod, counts, feasible):
    """-> (raw [N] int64, ignored [N] bool).  feasible [N] bool: the
    pod's filtered nodes, which upstream's PreScore sizes the weights
    from."""
    n = static.dom_idx.shape[1]
    keyed = _kind_keys(static, pod, counts, pod.is_score)
    ignored = ~keyed
    live = feasible & keyed
    n_live = jnp.sum(live, dtype=jnp.int32)
    total = jnp.zeros(n, dtype=jnp.float64)
    for m in range(MAX_CONSTRAINTS):
        active, k, dom, has_key, per_node = _slot(static, pod, counts, m)
        by_node = static.is_hostname[k]
        counted = static.elig_rows[pod.elig_idx[m]] & keyed
        cnt = jnp.where(by_node, per_node,
                        _fold(static, k, dom, jnp.where(counted, per_node, 0)))
        with jax.named_scope("kss_spread_weight"):
            onehot = static.dom_iota[:, None] == dom[None, :]
            present = jnp.any(onehot & live[None, :], axis=1)
            sz = jnp.where(by_node | static.is_ident[k], n_live,
                           jnp.sum(present, dtype=jnp.int32))
            weight = static.log_table[sz]
        term = cnt.astype(jnp.float64) * weight + (
            pod.max_skew[m] - 1).astype(jnp.float64)
        total = total + jnp.where(active & pod.is_score[m] & keyed, term, 0.0)
    raw = jnp.floor(total + 0.5).astype(jnp.int64)  # Go math.Round for non-negative
    return jnp.where(ignored, 0, raw), ignored


def normalize(raw, ignored, feasible):
    scored = feasible & ~ignored
    mn = jnp.min(jnp.where(scored, raw, _BIG))
    mx = jnp.max(jnp.where(scored, raw, 0))
    any_scored = jnp.any(scored)
    mn = jnp.where(any_scored, mn, 0)
    out = jnp.where(
        mx == 0,
        jnp.int64(MAX_NODE_SCORE),
        MAX_NODE_SCORE * (mx + mn - raw) // jnp.maximum(mx, 1),
    )
    return jnp.where(ignored, 0, out)


def bind_update(static: SpreadStatic, pod, counts, sel):
    """The bound pod joins the per-node count of every group whose
    selector it matches, at the selected node — elementwise, no scatter
    (sel -1, an unbound or a pad row, meets no node)."""
    at_sel = jnp.arange(counts.shape[1], dtype=jnp.int32) == sel    # [N]
    return counts + (pod.pm[:, None] & at_sel[None, :]).astype(counts.dtype)


def decode_filter(code: int, node_idx: int, host_aux) -> str:
    return ERR_MISSING_LABEL if code % 2 == 1 else ERR_SKEW
