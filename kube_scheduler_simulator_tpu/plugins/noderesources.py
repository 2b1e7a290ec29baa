"""NodeResourcesFit + NodeResourcesBalancedAllocation tensor kernels.

Semantics follow upstream k8s v1.32 pkg/scheduler/framework/plugins/
noderesources/{fit.go,least_allocated.go,balanced_allocation.go} (pinned by
the reference at simulator/go.mod:59); recording behavior follows the
reference shim (simulator/scheduler/plugin/wrappedplugin.go:523-548 Filter,
:420-445 Score).

Filter (Fit): a node fails when
  * len(pods)+1 > allowedPodNumber                  -> "Too many pods"
  * request[r] > allocatable[r] - requested[r]      -> "Insufficient <r>"
All insufficient resources are reported, comma-joined, in column order
(pods, cpu, memory, ephemeral-storage, extended...) — the failure code is a
bitmask with bit 0 = too-many-pods and bit 1+r = resource column r.

Score (Fit, LeastAllocated strategy — the default scoring strategy):
  per resource: ((alloc - req) * 100) / alloc   in exact int64, 0 if
  req > alloc or alloc == 0; weighted mean by strategy weights (int64 div).
  Requested uses the *non-zero* accumulators for cpu/memory.
  Fit has no ScoreExtensions -> finalscore = raw * plugin weight.

Score (BalancedAllocation): fractions f_r = min(req_r/alloc_r, 1) over the
strategy resources; for 2 resources std = |f0-f1|/2, else population std;
score = int64((1 - std) * 100).  Computed in float64 exactly as upstream;
no ScoreExtensions.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from . import fitscoring
from .base import MAX_NODE_SCORE
from ..state.resources import CPU, MEMORY, ResourceSchema

NAME_FIT = "NodeResourcesFit"
NAME_BALANCED = "NodeResourcesBalancedAllocation"


class FitStatic(NamedTuple):
    allocatable: jnp.ndarray   # [N, R] int64
    allowed_pods: jnp.ndarray  # [N] int64
    ignored: jnp.ndarray       # [R] bool — NodeResourcesFitArgs ignored*


def fit_ignored_mask(schema: ResourceSchema, args: dict | None) -> np.ndarray:
    """[R] bool mask of schema columns excluded from the fit check by
    NodeResourcesFitArgs.ignoredResources / ignoredResourceGroups.
    Upstream fitsRequest only skips EXTENDED resources (domain-prefixed
    names); cpu/memory/ephemeral-storage are never ignorable."""
    a = args or {}
    names = set(a.get("ignoredResources") or [])
    groups = set(a.get("ignoredResourceGroups") or [])
    out = np.zeros(len(schema.columns), dtype=bool)
    for r, col in enumerate(schema.columns):
        # IsExtendedResourceName: domain-prefixed and NOT kubernetes.io/
        # (unprefixed and kubernetes.io/ names are native, never skipped)
        if "/" not in col:
            continue
        prefix = col.split("/", 1)[0]
        if prefix == "kubernetes.io" or prefix.endswith(".kubernetes.io"):
            continue
        if col in names or prefix in groups:
            out[r] = True
    return out


class FitPodXS(NamedTuple):
    requests: jnp.ndarray  # [P, R] int64 (actual; filter path)
    nonzero: jnp.ndarray   # [P, 2] int64 (scoring path)


def build_fit(table, schema: ResourceSchema, requests, nonzero,
              fit_args: dict | None = None):
    # statics and xs leave every build as numpy: compile_workload digests
    # the host bytes for the scan-cache key, then uploads (pack_tree)
    static = FitStatic(
        allocatable=np.asarray(table.allocatable),
        allowed_pods=np.asarray(table.allowed_pods),
        ignored=fit_ignored_mask(schema, fit_args),
    )
    xs = FitPodXS(requests=requests, nonzero=nonzero)
    return static, xs


def fit_filter(static: FitStatic, pod: FitPodXS, carry) -> jnp.ndarray:
    """[N] int32 bitmask; 0 == pass."""
    free = static.allocatable - carry.requested          # [N, R]
    insufficient = (pod.requests[None, :] > free) & ~static.ignored[None, :]  # [N, R]
    too_many = (carry.num_pods + 1) > static.allowed_pods  # [N]
    bits = jnp.where(insufficient, jnp.int32(2) << jnp.arange(insufficient.shape[1], dtype=jnp.int32), 0)
    res_code = jnp.sum(bits, axis=1, dtype=jnp.int32)
    # upstream fitsRequest early-returns after the pod-count check when the
    # pod requests nothing — an overcommitted node (free < 0) still fits a
    # zero-request pod
    res_code = jnp.where(jnp.all(pod.requests == 0), 0, res_code)
    return res_code + jnp.where(too_many, 1, 0).astype(jnp.int32)


def fit_refuses_empty(static: FitStatic, requests: np.ndarray) -> np.ndarray:
    """[N] bool: fit_filter(static, pod, carry) != 0 on a carry of zeros,
    in host numpy over the arrays build_fit was given (no device is read).

    The carry only enters fit_filter as `allocatable - requested` and
    `num_pods + 1` with requested, num_pods >= 0, so a node refused here is
    refused under every carry: removing pods from it cannot help
    (framework/preemption.py, "The screen").  Kept beside fit_filter: the
    two are one expression (tests/test_fit_refuses_empty.py)."""
    requests = np.asarray(requests)
    insufficient = ((requests[None, :] > static.allocatable)
                    & ~static.ignored[None, :]).any(axis=1)
    # a pod that requests nothing is checked against the pod count only
    return (insufficient & bool(requests.any())) | (1 > static.allowed_pods)


def decode_fit_filter(code: int, schema: ResourceSchema) -> str:
    reasons = []
    if code & 1:
        reasons.append("Too many pods")
    for r, name in enumerate(schema.columns):
        if code & (2 << r):
            reasons.append(f"Insufficient {name}")
    return ", ".join(reasons)


def _resource_req_alloc(static: FitStatic, pod: FitPodXS, carry, name: str,
                        schema: ResourceSchema | None,
                        use_requested: bool = False):
    """-> (requested [N], allocatable [N]) for one scored resource.
    cpu/memory use the non-zero-defaulted accumulators (upstream
    GetNonzeroRequests / NodeInfo.NonZeroRequested) unless use_requested
    (upstream resourceAllocationScorer.useRequested, true for
    RequestedToCapacityRatio) selects the raw ones; ephemeral-storage and
    scalar resources always read the raw accumulators
    (calculateResourceAllocatableRequest reads nodeInfo.Requested for
    them explicitly)."""
    if name == "cpu":
        if use_requested:
            return carry.requested[:, CPU] + pod.requests[CPU], static.allocatable[:, CPU]
        return carry.nonzero[:, 0] + pod.nonzero[0], static.allocatable[:, CPU]
    if name == "memory":
        if use_requested:
            return carry.requested[:, MEMORY] + pod.requests[MEMORY], static.allocatable[:, MEMORY]
        return carry.nonzero[:, 1] + pod.nonzero[1], static.allocatable[:, MEMORY]
    if schema is not None and name in schema.columns:
        c = schema.columns.index(name)
        return carry.requested[:, c] + pod.requests[c], static.allocatable[:, c]
    # untracked resource: requested 0 against capacity 0 — the zero
    # capacity makes _resource_active exclude it everywhere, like
    # upstream's allocatable==0 skip
    n = static.allocatable.shape[0]
    return jnp.zeros(n, dtype=jnp.int64), jnp.zeros(n, dtype=jnp.int64)


def _resource_active(static: FitStatic, pod: FitPodXS, name: str,
                     alloc, schema: ResourceSchema | None):
    """[N] bool — does this resource participate in the weighted mean on
    each node?  Upstream resource_allocation.go skips a resource whose
    allocatable is 0 (`continue` before the scorer), and
    calculateResourceAllocatableRequest returns (0,0) — also skipped —
    for scalar (extended) resources the pod does not request."""
    active = alloc > 0
    if name not in fitscoring.NATIVE_RESOURCES:
        if schema is not None and name in schema.columns:
            c = schema.columns.index(name)
            active = active & (pod.requests[c] > 0)
        else:
            active = jnp.zeros_like(active)
    return active


def fit_score(static: FitStatic, pod: FitPodXS, carry,
              strategy: fitscoring.FitStrategy | None = None,
              schema: ResourceSchema | None = None) -> jnp.ndarray:
    """scoringStrategy-driven weighted mean of per-resource scores, with
    inactive resources excluded from the weight sum per node and 0 when
    every resource is inactive (upstream leastResourceScorer /
    mostResourceScorer / requestedToCapacityRatioScorer).  Least/Most use
    truncating int64 division; RequestedToCapacityRatio additionally
    drops resources whose resourceScore is 0 from the weight sum and
    rounds the mean to nearest (math.Round).  Default: LeastAllocated
    over cpu+memory, weight 1 each."""
    if strategy is None:
        strategy = fitscoring.FitStrategy(
            fitscoring.LEAST_ALLOCATED, fitscoring.DEFAULT_RESOURCES, ())
    rtcr = strategy.stype == fitscoring.REQUESTED_TO_CAPACITY_RATIO
    n = static.allocatable.shape[0]
    total = jnp.zeros(n, dtype=jnp.int64)
    wsum = jnp.zeros(n, dtype=jnp.int64)
    for name, w in strategy.resources:
        req, alloc = _resource_req_alloc(static, pod, carry, name, schema,
                                         use_requested=rtcr)
        active = _resource_active(static, pod, name, alloc, schema)
        s = fitscoring.score_resource_vec(strategy, req, alloc)
        if rtcr:
            active = active & (s > 0)
        total = total + jnp.where(active, s * jnp.int64(w), 0)
        wsum = wsum + jnp.where(active, jnp.int64(w), 0)
    if rtcr:
        # round half away from zero; scores are non-negative here
        return jnp.where(
            wsum > 0, (2 * total + wsum) // jnp.maximum(2 * wsum, 1), 0)
    return jnp.where(wsum > 0, total // jnp.maximum(wsum, 1), 0)


def balanced_score(static: FitStatic, pod: FitPodXS, carry,
                   resources: tuple[str, ...] = ("cpu", "memory"),
                   schema: ResourceSchema | None = None) -> jnp.ndarray:
    """balanced_allocation.go: std of per-resource utilization fractions
    (cap==0 resources and unrequested scalar resources skipped, same
    calculateResourceAllocatableRequest bypass as fit_score),
    score = int64((1-std)·100)."""
    fracs = []
    masks = []
    for name in resources:
        req, alloc = _resource_req_alloc(static, pod, carry, name, schema)
        a = alloc.astype(jnp.float64)
        f = jnp.minimum(req.astype(jnp.float64) / jnp.maximum(a, 1.0), 1.0)
        fracs.append(f)
        masks.append(_resource_active(static, pod, name, alloc, schema))
    f = jnp.stack(fracs, axis=1)       # [N, K]
    m = jnp.stack(masks, axis=1)       # [N, K] cap>0
    cnt = jnp.sum(m, axis=1)
    if len(resources) == 2:
        # both present -> |f0-f1|/2; one missing -> single fraction, std 0
        both = cnt == 2
        std = jnp.where(both, jnp.abs(f[:, 0] - f[:, 1]) / 2.0, 0.0)
    else:
        fm = jnp.where(m, f, 0.0)
        denom = jnp.maximum(cnt, 1).astype(jnp.float64)
        mean = jnp.sum(fm, axis=1) / denom
        var = jnp.sum(jnp.where(m, (f - mean[:, None]) ** 2, 0.0), axis=1) / denom
        # exactly two present fractions a,b (positions unknown):
        # |a-b| = sqrt(2·Σf² - (Σf)²)
        s1 = jnp.sum(fm, axis=1)
        s2 = jnp.sum(jnp.where(m, f * f, 0.0), axis=1)
        two_std = jnp.sqrt(jnp.maximum(2.0 * s2 - s1 * s1, 0.0)) / 2.0
        std = jnp.where(cnt > 2, jnp.sqrt(var),
                        jnp.where(cnt == 2, two_std, 0.0))
    return ((1.0 - std) * MAX_NODE_SCORE).astype(jnp.int64)


def core_bind_update(carry, pod: FitPodXS, sel: jnp.ndarray):
    """Apply a bind to the shared resource accumulators. sel == -1 leaves
    state untouched (scatter to a masked dummy row would also work, but a
    where on the gathered row keeps it branch-free and exact)."""
    bound = sel >= 0
    idx = jnp.maximum(sel, 0)
    add_req = jnp.where(bound, 1, 0).astype(carry.requested.dtype)
    requested = carry.requested.at[idx].add(pod.requests * add_req)
    nonzero = carry.nonzero.at[idx].add(pod.nonzero * add_req)
    num_pods = carry.num_pods.at[idx].add(add_req)
    return carry._replace(requested=requested, nonzero=nonzero, num_pods=num_pods)
