"""TaintToleration + NodeUnschedulable + NodeName tensor kernels.

All three filter predicates and the TaintToleration score depend only on
node taints/labels/names and the pod's tolerations/nodeName — static during
a replay — so they precompile to dense [P, N] arrays.

Upstream v1.32 semantics:
* TaintToleration Filter: first taint with effect NoSchedule/NoExecute not
  tolerated fails the node with
  "node(s) had untolerated taint {<key>: <value>}".  The failure code here
  is 1 + index of that taint in the node's taint list so the decoder can
  reproduce the exact message.
* TaintToleration Score: count of PreferNoSchedule taints not tolerated by
  the pod's tolerations filtered to effect in {"", PreferNoSchedule};
  NormalizeScore = DefaultNormalizeScore(100, reverse=true).
* NodeUnschedulable Filter: node.spec.unschedulable fails with
  "node(s) were unschedulable" unless the pod tolerates the
  node.kubernetes.io/unschedulable:NoSchedule taint.
* NodeName Filter: pod.spec.nodeName set and != node name fails with
  "node(s) didn't match the requested node name".
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from .base import default_normalize_score
from ..state.nodes import NodeTable, NO_EXECUTE, NO_SCHEDULE, PREFER_NO_SCHEDULE
from ..state.selectors import spec_key, tolerations_tolerate

NAME_TAINT = "TaintToleration"
NAME_UNSCHED = "NodeUnschedulable"
NAME_NODENAME = "NodeName"

ERR_UNSCHEDULABLE = "node(s) were unschedulable"
ERR_NODE_NAME = "node(s) didn't match the requested node name"

UNSCHEDULABLE_TAINT_KEY = "node.kubernetes.io/unschedulable"


class TaintXS(NamedTuple):
    filter_code: jnp.ndarray   # [P, N] int16; 0 pass, else 1 + taint index
    prefer_count: jnp.ndarray  # [P, N] int16 (intolerable PreferNoSchedule taints)


class UnschedXS(NamedTuple):
    fail: jnp.ndarray  # [P, N] bool


class NodeNameXS(NamedTuple):
    fail: jnp.ndarray  # [P, N] bool


def _taint_rows(table: NodeTable, tols: list) -> tuple[np.ndarray, np.ndarray]:
    """([N] filter code, [N] intolerable PreferNoSchedule count) of one
    pod's tolerations against every node's taints."""
    n = table.n
    tols_prefer = [t for t in tols if (t.get("effect") or "") in ("", PREFER_NO_SCHEDULE)]
    crow = np.zeros(n, dtype=np.int16)
    prow = np.zeros(n, dtype=np.int16)
    for j in range(n):
        for ti, (key, value, eff) in enumerate(table.taints[j]):
            if eff in (NO_SCHEDULE, NO_EXECUTE):
                if crow[j] == 0 and not tolerations_tolerate(tols, key, value, eff):
                    crow[j] = 1 + ti
            elif eff == PREFER_NO_SCHEDULE:
                if not tolerations_tolerate(tols_prefer, key, value, eff):
                    prow[j] += 1
    return crow, prow


def build_taints(table: NodeTable, pods: list[dict],
                 host_out: dict | None = None) -> TaintXS:
    n, p = table.n, len(pods)
    code = np.zeros((p, n), dtype=np.int16)
    prefer = np.zeros((p, n), dtype=np.int16)
    for i, pod in enumerate(pods):
        tols = (pod.get("spec") or {}).get("tolerations") or []
        # one (filter_code, prefer_count) row pair per distinct
        # tolerations, kept on the table
        code[i], prefer[i] = table.derived.row(
            "taint_rows", spec_key(tols), lambda: _taint_rows(table, tols))
    if host_out is not None:
        # the raw score IS this precompiled row (taint_score is a pure
        # pass-through): the compact replay keeps it host-resident
        # (framework/replay.py "host" score group) instead of paying D2H
        host_out.setdefault("static_score_rows", {})[NAME_TAINT] = prefer
    return TaintXS(filter_code=code, prefer_count=prefer)


def build_unschedulable(table: NodeTable, pods: list[dict]) -> UnschedXS:
    n, p = table.n, len(pods)
    fail = np.zeros((p, n), dtype=bool)
    unsched_nodes = np.flatnonzero(table.unschedulable)
    for i, pod in enumerate(pods):
        tols = (pod.get("spec") or {}).get("tolerations") or []
        tolerated = tolerations_tolerate(tols, UNSCHEDULABLE_TAINT_KEY, "", "NoSchedule")
        if not tolerated:
            fail[i, unsched_nodes] = True
    return UnschedXS(fail=fail)


def build_nodename(table: NodeTable, pods: list[dict]) -> NodeNameXS:
    """Upstream NodeName has NO PreFilter: its Filter runs (and records
    "passed") for every pod, empty nodeName matching every node."""
    n, p = table.n, len(pods)
    fail = np.zeros((p, n), dtype=bool)
    name_idx = table.name_idx
    for i, pod in enumerate(pods):
        want = (pod.get("spec") or {}).get("nodeName") or ""
        if not want:
            continue
        fail[i, :] = True
        j = name_idx.get(want)
        if j is not None:
            fail[i, j] = False
    return NodeNameXS(fail=fail)


# --- device kernels (pure gathers over the precompiled rows) ---

def taint_filter(pod_xs: TaintXS) -> jnp.ndarray:
    return pod_xs.filter_code.astype(jnp.int32)


def taint_score(pod_xs: TaintXS) -> jnp.ndarray:
    return pod_xs.prefer_count.astype(jnp.int64)


def taint_normalize(raw, feasible):
    return default_normalize_score(raw, feasible, reverse=True)


def decode_taint_filter(code: int, node_idx: int, host_aux) -> str:
    table: NodeTable = host_aux["node_table"]
    key, value, _ = table.taints[node_idx][code - 1]
    return "node(s) had untolerated taint {%s: %s}" % (key, value)


def unsched_filter(pod_xs: UnschedXS) -> jnp.ndarray:
    return jnp.where(pod_xs.fail, 1, 0).astype(jnp.int32)


def nodename_filter(pod_xs: NodeNameXS) -> jnp.ndarray:
    return jnp.where(pod_xs.fail, 1, 0).astype(jnp.int32)
