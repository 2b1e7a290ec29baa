"""InterPodAffinity tensor kernels.

Upstream v1.32 pkg/scheduler/framework/plugins/interpodaffinity.  The
pod x pod cross terms are factored through *unique affinity terms*: a term
is (topologyKey, labelSelector, namespaces); the whole workload (initial
pods + queue) mentions a small set T of distinct terms, and every pairwise
relation the plugin needs is a function of per-(term, domain) counts:

  matched[T, D]    existing pods whose labels+ns match term t, per domain
  have_req_anti    existing pods having t as a required anti-affinity term
  have_req_aff     ... as a required affinity term
  sym_pref_aff     sum of weights of existing pods having t as a preferred
                   affinity term (symmetric score credit)
  sym_pref_anti    ... preferred anti-affinity term

These five [T, D] matrices are the scan carry; per-pod statics are
t_matches[P, T] (does pod p match term t) and the pod's own term
multiplicities/weights h_*[P, T].  A 10k x 5k InterPodAffinity replay that
is O(pods^2 x nodes) pairwise in the reference becomes O(T x D) per step.

Filter (required terms), in upstream check order:
  1. pod affinity:   every t with h_req_aff>0 needs matched[t, dom(n)]>0,
     OR the self-match escape: no pod anywhere matches any of the pod's
     affinity terms AND the pod matches all its own terms AND the node has
     all term topology keys.     -> "node(s) didn't match pod affinity rules"
  2. pod anti-affinity: no t with h_req_anti>0 may have matched[t,dom]>0
                                 -> "node(s) didn't match pod anti-affinity rules"
  3. existing pods' anti-affinity: sum_t t_matches[p,t]*have_req_anti[t,dom]
     must be 0       -> "node(s) didn't satisfy existing pods anti-affinity rules"

Score: raw(n) = sum_t [ (h_pref_aff_w - h_pref_anti_w)[p,t] * matched[t,dom]
                 + t_matches[p,t] * (sym_pref_aff - sym_pref_anti
                                     + hardWeight * have_req_aff)[t,dom] ]
with hardWeight = args.hardPodAffinityWeight (default 1).
NormalizeScore: fScore = 100 * (score - min) / (max - min) over feasible
nodes, float64 then int64 truncation, 0 when max == min.

Term normalization (effective_terms, shared with the CPU oracle):
namespaceSelector resolved against the namespace manifests supplied at
compile time (explicit namespaces union selector matches; {} matches all
known namespaces), matchLabelKeys / mismatchLabelKeys merged into the
selector as In / NotIn expressions over the incoming pod's own values.
Remaining simplification (docs/SEMANTICS.md): PreFilter never returns
Skip when any pod in the workload carries required anti-affinity terms
(coarser than upstream's per-cycle check, applied identically in the CPU
reference).
"""

from __future__ import annotations

import json
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from .base import MAX_NODE_SCORE
from ..state.nodes import NodeTable
from ..state.selectors import label_selector_matches

NAME = "InterPodAffinity"
ERR_AFFINITY = "node(s) didn't match pod affinity rules"
ERR_ANTI_AFFINITY = "node(s) didn't match pod anti-affinity rules"
ERR_EXISTING_ANTI = "node(s) didn't satisfy existing pods anti-affinity rules"

CODE_AFFINITY, CODE_ANTI, CODE_EXISTING = 1, 2, 3

DEFAULT_HARD_POD_AFFINITY_WEIGHT = 1

# the four kinds of term a pod carries: (name, spec.affinity field, preferred)
KINDS = (("req_aff", "podAffinity", False), ("req_anti", "podAntiAffinity", False),
         ("pref_aff", "podAffinity", True), ("pref_anti", "podAntiAffinity", True))


class InterPodStatic(NamedTuple):
    dom_idx: jnp.ndarray     # [T, N] int32 (-1: node lacks term's key)
    hard_weight: jnp.ndarray  # scalar int64


class InterPodXS(NamedTuple):
    t_matches: jnp.ndarray     # [P, T] bool
    h_req_aff: jnp.ndarray     # [P, T] int32
    h_req_anti: jnp.ndarray    # [P, T] int32
    h_pref_aff_w: jnp.ndarray  # [P, T] int64
    h_pref_anti_w: jnp.ndarray  # [P, T] int64
    self_ok: jnp.ndarray       # [P] bool — pod matches all its own req aff terms
    filter_skip: jnp.ndarray   # [P] bool


class InterPodCarry(NamedTuple):
    """Per-(term, NODE) counts — the domain-space [T, D] matrices of the
    module docstring materialized per node (value at each node's domain,
    0 where the node lacks the key).  Node-space keeps the whole scan step
    gather/scatter-free on TPU: reading "matched at n's domain" is just
    carry.matched[:, n] (already local), and a bind updates every node of
    the selected node's domain with one elementwise compare-and-add —
    measured ~180x faster per step than the [T, D] gather/scatter form on
    a v5e.  matched_total keeps the per-term cluster-wide count that the
    self-match escape needs (the only cross-domain aggregate).

    int32: counts are bounded by #pods and weight sums by 100 x #pods
    (upstream caps per-term weights at 100), far inside int32; the score
    reduction accumulates in int64."""

    matched: jnp.ndarray        # [T, N] int32
    have_req_anti: jnp.ndarray  # [T, N] int32
    have_req_aff: jnp.ndarray   # [T, N] int32
    sym_pref_aff: jnp.ndarray   # [T, N] int32
    sym_pref_anti: jnp.ndarray  # [T, N] int32
    matched_total: jnp.ndarray  # [T] int32


def _terms_of(pod: dict, field: str, preferred: bool) -> list[tuple[dict, int]]:
    aff = ((pod.get("spec") or {}).get("affinity") or {}).get(field) or {}
    if preferred:
        return [
            (wt.get("podAffinityTerm") or {}, int(wt.get("weight", 0)))
            for wt in aff.get("preferredDuringSchedulingIgnoredDuringExecution") or []
        ]
    return [(t, 1) for t in aff.get("requiredDuringSchedulingIgnoredDuringExecution") or []]


def effective_terms(pod: dict, field: str, preferred: bool,
                    namespaces: list[dict] | None = None) -> list[tuple[dict, int]]:
    """The pod's [anti-]affinity terms, normalized the way upstream's
    framework.AffinityTerm constructor does:

    * matchLabelKeys / mismatchLabelKeys merged into the labelSelector as
      In / NotIn expressions over the incoming pod's own label values
      (MatchLabelKeysInPodAffinity, beta default-on since v1.31; keys the
      pod doesn't carry are skipped);
    * the namespace set resolved: explicit `namespaces` union namespaces
      whose labels match `namespaceSelector` (an empty selector {} matches
      every known namespace; nil adds nothing); neither field -> the
      pod's own namespace.  Resolution is against the `namespaces`
      manifests supplied at compile time — the engine passes the store's
      live list, matching upstream's per-cycle namespace lister read.

    Shared by the tensor build and the sequential oracle so term
    interning and match semantics can never diverge."""
    meta = pod.get("metadata") or {}
    pod_ns = meta.get("namespace") or "default"
    pod_labels = {k: str(v) for k, v in (meta.get("labels") or {}).items()}
    out = []
    for term, w in _terms_of(pod, field, preferred):
        extra = []
        for k in term.get("matchLabelKeys") or []:
            if k in pod_labels:
                extra.append({"key": k, "operator": "In", "values": [pod_labels[k]]})
        for k in term.get("mismatchLabelKeys") or []:
            if k in pod_labels:
                extra.append({"key": k, "operator": "NotIn", "values": [pod_labels[k]]})
        sel = term.get("labelSelector")
        if extra:
            sel = dict(sel or {})
            sel["matchExpressions"] = list(sel.get("matchExpressions") or []) + extra
        ns_selector = term.get("namespaceSelector")
        ns_set = set(term.get("namespaces") or [])
        if ns_selector is not None:
            for ns_obj in namespaces or []:
                ns_meta = ns_obj.get("metadata") or {}
                labels = {k: str(v) for k, v in (ns_meta.get("labels") or {}).items()}
                if label_selector_matches(ns_selector, labels):
                    ns_set.add(ns_meta.get("name", ""))
        if not ns_set and ns_selector is None:
            ns_set = {pod_ns}
        term = dict(term, labelSelector=sel, namespaces=sorted(ns_set))
        term.pop("namespaceSelector", None)
        out.append((term, w))
    return out


def term_key(term: dict) -> tuple:
    """The identity a term is interned under, over what effective_terms
    made of it: (topologyKey, selector, namespaces)."""
    return (term.get("topologyKey", ""),
            json.dumps(term.get("labelSelector"), sort_keys=True),
            tuple(term.get("namespaces") or ()))


def build(table: NodeTable, pods: list[dict], bound,
          hard_weight: int = DEFAULT_HARD_POD_AFFINITY_WEIGHT,
          namespaces: list[dict] | None = None):
    """-> (InterPodStatic, InterPodXS of the queue pods, InterPodCarry
    primed with bound pods).

    bound: the bound pods as a state/boundcarry.BoundCarry placed on this
    table, or a (pod, node name) list (a throw-away carry is made of it).
    The term table is interned over the queue pods in order, then over the
    terms only bound pods carry (which matter for the symmetric
    existing-pod checks), sorted: an order no bound pod's position can
    move.  Nothing of a bound pod but the carry's per-node sums is read
    here, and nothing of them reaches the device, so no shape that does
    depends on how many pods are bound."""
    if isinstance(bound, list):
        from ..state.boundcarry import carry_of_list

        bound = carry_of_list(bound, namespaces)
        bound.place(table.names)
    n, p = table.n, len(pods)

    # --- unique term table ----------------------------------------------
    terms: dict[tuple, int] = {}
    term_list: list[tuple[str, dict | None, tuple[str, ...]]] = []  # (key, selector, namespaces)

    def intern_term(term: dict) -> int:
        # effective_terms already resolved the namespace set and merged
        # matchLabelKeys into the selector
        tk = term_key(term)
        if tk not in terms:
            terms[tk] = len(term_list)
            term_list.append((tk[0], term.get("labelSelector"), tk[2]))
        return terms[tk]

    per_pod: list[dict[str, list[tuple[int, int]]]] = []
    for pod in pods:
        entry = {}
        for kind, field, preferred in KINDS:
            entry[kind] = [
                (intern_term(t), w)
                for t, w in effective_terms(pod, field, preferred, namespaces)
            ]
        per_pod.append(entry)
    bound_terms = bound.own_terms()
    for tk in sorted(tk for tk in bound_terms if tk not in terms):
        terms[tk] = len(term_list)
        term_list.append(bound_terms[tk])

    t_count = max(len(term_list), 1)

    # --- domain indexing per term key ------------------------------------
    # (NodeTable.domain_row: one row a topology key, kept on the table)
    dom_idx = np.full((t_count, n), -1, dtype=np.int32)
    for t_id, (key, _, _) in enumerate(term_list):
        dom_idx[t_id] = table.domain_row(key)[0]
    d_max = max(int(dom_idx.max()) + 1, 1)

    # --- pod x term matches + per-pod term weights -----------------------
    t_matches = np.zeros((p, t_count), dtype=bool)
    h_req_aff = np.zeros((p, t_count), dtype=np.int32)
    h_req_anti = np.zeros((p, t_count), dtype=np.int32)
    h_pref_aff_w = np.zeros((p, t_count), dtype=np.int64)
    h_pref_anti_w = np.zeros((p, t_count), dtype=np.int64)
    self_ok = np.zeros(p, dtype=bool)
    for i, pod in enumerate(pods):
        pod_ns = (pod.get("metadata") or {}).get("namespace") or "default"
        pod_labels = {k: str(v) for k, v in ((pod.get("metadata") or {}).get("labels") or {}).items()}
        for t_id, (_, sel, nss) in enumerate(term_list):
            t_matches[i, t_id] = pod_ns in nss and label_selector_matches(sel, pod_labels)
        e = per_pod[i]
        for t_id, _ in e["req_aff"]:
            h_req_aff[i, t_id] += 1
        for t_id, _ in e["req_anti"]:
            h_req_anti[i, t_id] += 1
        for t_id, w in e["pref_aff"]:
            h_pref_aff_w[i, t_id] += w
        for t_id, w in e["pref_anti"]:
            h_pref_anti_w[i, t_id] += w
        self_ok[i] = all(t_matches[i, t_id] for t_id, _ in e["req_aff"])

    # PreFilter Skip is coarser than upstream's (module docstring): the
    # bound pods' required anti-affinity terms count too
    any_workload_anti = bool(h_req_anti.any()) or bound.any_required_anti
    filter_skip = np.array(
        [
            not any_workload_anti
            and not per_pod[i]["req_aff"]
            and not per_pod[i]["req_anti"]
            for i in range(p)
        ],
        dtype=bool,
    )

    # numpy, xs and carry too: compile_workload reads its flags and the
    # digest off the host bytes, then uploads once (pack_tree)
    static = InterPodStatic(dom_idx=dom_idx, hard_weight=np.int64(hard_weight))
    xs = InterPodXS(
        t_matches=t_matches,
        h_req_aff=h_req_aff,
        h_req_anti=h_req_anti,
        h_pref_aff_w=h_pref_aff_w,
        h_pref_anti_w=h_pref_anti_w,
        self_ok=self_ok,
        filter_skip=filter_skip,
    )

    # --- bound pods -> the carry's per-(term, domain) counts ---------------
    # per-node sums of the bound pods (matches of the term; multiplicities
    # and weights of the pods carrying it), folded over each term's domains
    mats = {name: np.zeros((t_count, d_max), dtype=np.int64)
            for name in ("matched", "have_req_aff", "have_req_anti",
                         "sym_pref_aff", "sym_pref_anti")}
    for tk, t_id in terms.items():
        keyed = np.flatnonzero(dom_idx[t_id] >= 0)   # nodes with the term's key
        dom = dom_idx[t_id, keyed]

        def fold(per_node: np.ndarray) -> np.ndarray:
            # float64 weights hold these sums exactly (< 2**53)
            return np.bincount(dom, weights=per_node[keyed],
                               minlength=d_max).astype(np.int64)

        mats["matched"][t_id] = fold(
            bound.match_counts(tk[2], term_list[t_id][1]))
        own = bound.own_sums(tk)
        if own is not None:
            for name, row in zip(("have_req_aff", "have_req_anti",
                                  "sym_pref_aff", "sym_pref_anti"), own):
                mats[name][t_id] = fold(row)
    return static, xs, assemble_carry(dom_idx, mats)


def assemble_carry(dom: np.ndarray, dom_mats: dict) -> InterPodCarry:
    """[T, D] domain-space numpy mats over the host dom_idx [T, N] -> the
    node-space device carry (one take_along_axis per mat, on host)."""
    safe = np.maximum(dom, 0)

    def to_nodes(mat: np.ndarray) -> np.ndarray:
        vals = np.take_along_axis(mat, safe, axis=1)
        return np.where(dom >= 0, vals, 0).astype(np.int32)

    return InterPodCarry(
        matched=to_nodes(dom_mats["matched"]),
        have_req_anti=to_nodes(dom_mats["have_req_anti"]),
        have_req_aff=to_nodes(dom_mats["have_req_aff"]),
        sym_pref_aff=to_nodes(dom_mats["sym_pref_aff"]),
        sym_pref_anti=to_nodes(dom_mats["sym_pref_anti"]),
        matched_total=dom_mats["matched"].sum(axis=1).astype(np.int32),
    )


def filter_kernel(static: InterPodStatic, pod, carry: InterPodCarry) -> jnp.ndarray:
    matched_n = carry.matched                              # [T, N]
    has_aff = pod.h_req_aff > 0                            # [T]
    # 1. required pod affinity
    term_sat = matched_n > 0                               # [T, N]
    aff_ok_all = jnp.all(jnp.where(has_aff[:, None], term_sat, True), axis=0)  # [N]
    total_any = jnp.sum(jnp.where(has_aff, carry.matched_total, 0))
    node_has_keys = jnp.all(jnp.where(has_aff[:, None], static.dom_idx >= 0, True), axis=0)
    self_escape = (total_any == 0) & pod.self_ok & node_has_keys
    fail_aff = jnp.any(has_aff) & ~(aff_ok_all | self_escape)
    # 2. required pod anti-affinity
    has_anti = pod.h_req_anti > 0
    fail_anti = jnp.any(jnp.where(has_anti[:, None], matched_n > 0, False), axis=0)
    # 3. existing pods' anti-affinity vs this pod
    fail_existing = jnp.sum(
        jnp.where(pod.t_matches[:, None], carry.have_req_anti, 0), axis=0) > 0
    code = jnp.where(fail_existing, CODE_EXISTING, 0)
    code = jnp.where(fail_anti, CODE_ANTI, code)
    code = jnp.where(fail_aff, CODE_AFFINITY, code)
    return code.astype(jnp.int32)


def score_kernel(static: InterPodStatic, pod, carry: InterPodCarry) -> jnp.ndarray:
    own = ((pod.h_pref_aff_w - pod.h_pref_anti_w).astype(jnp.int32)[:, None]
           * carry.matched)
    sym = (carry.sym_pref_aff - carry.sym_pref_anti
           + static.hard_weight.astype(jnp.int32) * carry.have_req_aff)
    sym_contrib = jnp.where(pod.t_matches[:, None], sym, 0)
    return jnp.sum((own + sym_contrib).astype(jnp.int64), axis=0)


def normalize(raw, feasible):
    big = jnp.int64(1) << 40
    mn = jnp.min(jnp.where(feasible, raw, big))
    mx = jnp.max(jnp.where(feasible, raw, -big))
    diff = (mx - mn).astype(jnp.float64)
    f = jnp.where(
        diff > 0,
        MAX_NODE_SCORE * ((raw - mn).astype(jnp.float64) / jnp.maximum(diff, 1.0)),
        0.0,
    )
    return f.astype(jnp.int64)  # Go int64() truncation


def bind_update(static: InterPodStatic, pod, carry: InterPodCarry, sel):
    """Node-space bind: every node sharing the selected node's domain (per
    term) takes the increment — an elementwise compare-and-add, no
    scatter (the TPU-hostile op the domain-space form needed)."""
    bound = sel >= 0
    s = jnp.maximum(sel, 0)
    dom_col = static.dom_idx[:, s]                  # [T]
    valid = bound & (dom_col >= 0)                  # [T]
    same = (static.dom_idx == dom_col[:, None]) & valid[:, None]  # [T, N]

    def upd(mat, inc):
        return mat + jnp.where(same, inc.astype(mat.dtype)[:, None], 0)

    return InterPodCarry(
        matched=upd(carry.matched, pod.t_matches),
        have_req_anti=upd(carry.have_req_anti, pod.h_req_anti),
        have_req_aff=upd(carry.have_req_aff, pod.h_req_aff),
        sym_pref_aff=upd(carry.sym_pref_aff, pod.h_pref_aff_w),
        sym_pref_anti=upd(carry.sym_pref_anti, pod.h_pref_anti_w),
        matched_total=carry.matched_total
        + jnp.where(valid, pod.t_matches.astype(jnp.int32), 0),
    )


def decode_filter(code: int, node_idx: int, host_aux) -> str:
    return {CODE_AFFINITY: ERR_AFFINITY, CODE_ANTI: ERR_ANTI_AFFINITY, CODE_EXISTING: ERR_EXISTING_ANTI}[code]
