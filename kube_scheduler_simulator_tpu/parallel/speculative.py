"""Speculative pod-batch scheduling: the engine's default wave.

The scan replay is sequential-exact: each pod's evaluation sees every
earlier bind.  With decode (lazy materialization) and bulk D2H
(device-resident results) off the critical path, that pod-at-a-time
device scan IS the wave — so this module batches it: evaluate a BATCH of
B pending pods against one frozen carry (vmap over the batch; on a mesh
the batch axis shards over "dp" and the node axis over "nodes"), let a
CONFLICT ORACLE accept the longest provably non-interfering prefix,
fold the accepted binds into the carry in one device call, and roll the
rejected suffix into the next round re-scored against the updated
carry.  Wall-clock drops because the per-pod [N] vector work becomes
[B, N] tensor work — a contention-free queue needs ~ceil(P/B) device
steps instead of P — while results stay BIT-IDENTICAL to the scan.

Exactness argument (why the accepted prefix is sequential-parity).  Two
acceptance rules compose:

* DIRTY-NODE rule (node-local plugins, SAFE_SPECULATIVE): pod k is
  accepted only if every node bound by earlier-accepted pods was
  INFEASIBLE for k under the frozen state.  Sequentially those nodes
  carry strictly more allocation / port occupancy, and NodeResourcesFit
  and NodePorts infeasibility are monotone in that state, so they stay
  infeasible; all other nodes' node-local state is untouched, so k's
  feasible set, raw scores on it, the feasible-set-wide normalization,
  and the argmax tie-break are identical to the sequential run.  (The
  tie-break itself is pinned: both the scan and the vmapped batch select
  with the same integer-score argmax, whose first-max-index rule is
  deterministic — score ties therefore bind identically on both paths,
  and the golden suite gates them explicitly.)
* INTERACTION rule (label-coupled plugins, LABEL_COUPLED): a bound pod j
  perturbs k's PodTopologySpread / InterPodAffinity inputs only when j
  matches a selector k reads (k's constraint selectors / terms) or k
  matches a term j imposes as an existing pod (j's anti + preferred
  terms).  k is accepted only when no earlier-accepted BOUND pod
  interacts either way, so every domain count and existing-term k reads
  equals the sequential state.

The first pod of every round is unconditionally safe, so each round
commits >= 1 pod and the loop terminates.  (A queue of ONE pod is
therefore one step of the scan and nothing else; the engine never hands
this module one: SchedulerEngine._wave_plan sends a pass of fewer than
MIN_ROUND pods to the sequential scan.)  The dirty-node test runs ON
DEVICE (a [B, B] feasibility-at-selected-nodes gather; only the prefix
length and the per-pod decision rows cross to host), the interaction
walk on host over the pod manifests.  Where the win comes from:
acceptance is long exactly when feasibility is SPARSE (taints, affinity
pins, zone constraints, tight fit — i.e. realistic packed clusters).
In a fully relaxed cluster where every pod fits everywhere the rule
cuts every batch at ~1, and a cluster with room is what most simulated
clusters are — so the rounds watch their own record and get out of the
way.  A pass of ONE chunk (every served pass) whose FIRST round holds at
least MIN_ROUND pods and keeps a quarter of them or less ENDS there:
nothing has been delivered and cw.init_carry is intact, so the stream
returns no result and its caller (SchedulerEngine._device_wave) runs the
same pass as the sequential scan's one packed call, which decides the
pods the round had accepted again, bit-identically.  The session
remembers the collapse with the round's median feasible share
(CONTROLS.note_spec_collapsed), the engine's plan then sends the
session's batch passes to the scan from the start, and the rounds are
tried again once a pass's median share has fallen to half of that, on
the next batch pass whose bucket they have run on (a probe is worth a
round, not a bucket's compile; of a probe that collapses too the record
is no more than the share that asked for it).
Mid-pass, and on a pass of more chunks (whose delivered chunks stand), a
CONTENTION-AWARE controller watches the observed accept rate:
full-accept rounds climb the batch ladder,
heavily-cut rounds step it down, and a sustained accept collapse at the
bottom rung FALLS BACK to the sequential chunked scan for the rest of
the wave (the same jitted scan the non-speculative path runs, resumed
from the speculative carry — which is bit-identical to the sequential
carry at that pod by the argument above).  That conservatism is not
incidental: byte-exact annotations require that NO feasible node's
score inputs changed (normalization ranges over the whole feasible
set), so any relaxation of the rule would break the bit-parity
contract, not just the selection.

Streaming (docs/wave-pipeline.md speculative-wave stage): results are
accumulated ON DEVICE into the same fixed-size compact chunk grid the
scan emits (`_CompactChunks`), and every filled chunk is delivered
through the standard `on_chunk(rr, lo, hi)` contract — ascending,
contiguous, idempotent under width-tier re-delivery — so the pipelined
commit worker, lazy decode, device residency (chunks retain as live
device arrays under the HBM budget), gang-cut watermarks and the wave
failure protocol's uncommitted-suffix retry all compose unchanged: a
round is just (part of) a chunk.  Gangs compose as all-or-nothing
prefix units: the acceptance cut pulls back to the gang boundary
(framework/gang.py `aligned_cut`) so a round never splits a gang it
could defer whole, and admission itself stays with the vectorized
segment-reduction quorum at commit.

Commit: core-only plugin sets fold all accepted binds in one
scatter-add; sets with ports/topology/interpod carries fold the
pipeline's own _bind_phase over the batch (non-accepted selections
masked to -1, a no-op bind) — the same carry math as the scan.  The
volume family stays excluded (PV/PVC bind state is cluster-wide and not
label-gated), as do custom plugins (except the engine's vectorized gang
plugin, which the caller names in `ignore`) and extenders; those fall
back to the scan path.  Parity — full annotation bytes, bind order,
parked gangs — is asserted by tests/test_speculative.py against the
scan and the sequential oracle, and by the engine golden suite.

For node-local plugin sets the eval splits into a dense FILTER phase
(annotation parity needs every node's first-fail code) and a SPARSE
score/normalize/select tail computed only on the gathered
feasible-candidate rows (KSS_TPU_SPECULATIVE_CANDIDATES) — at sparse
feasibility the scoring work drops from [B, N] to [B, K], which is
where the measured raw-speed win over the scan lives on
throughput-bound backends.  Raw values at infeasible positions are
don't-cares by the compact layout (decode, hostnorm and attribution
read feasible positions only).

Env knobs (docs/environment-variables.md): KSS_TPU_SPECULATIVE=0
disables the engine default; KSS_TPU_SPECULATIVE_BATCH pins the batch
(one rung); KSS_TPU_SPECULATIVE_CANDIDATES caps the sparse tail's
candidate set; KSS_TPU_SPECULATIVE_MIN_ACCEPT /
KSS_TPU_SPECULATIVE_FALLBACK_ROUNDS tune the mid-pass scan-fallback
trigger (not the first round's way out, which no variable tunes);
KSS_TPU_SPECULATIVE_TILE sizes the CPU backend's cache-tiled vmap.
"""

from __future__ import annotations

import os
from types import SimpleNamespace
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.replay import (
    ReplayResult, _CompactChunks, _compact_plan, _DeviceAttribution,
    _DEVICE_BUDGET, _resolve_device_resident, _scan_for, _SCAN_CACHE,
    _copy_carry, _slice_xs, _SlimWorkload, _workload_scan_key, pass_chunk)
from ..control import CONTROLS
from ..state.compile import POD_CHUNK, CompiledWorkload
from ..utils.blackbox import BLACKBOX
from ..utils.env import env_float, env_int
from ..utils.faults import fault_point
from ..utils.tracing import TRACER
from .fuse import FUSE, fuse_enabled, session_admitted

# per-node plugins with no cross-pod coupling: filters are static or
# monotone in node allocation, scores depend only on the node's own
# accumulated resources, binds touch only carry["core"].  NodePorts is
# node-local too (a bind occupies ports on the selected node only), so
# the dirty-node rule already covers it.
SAFE_SPECULATIVE = {
    "NodeResourcesFit", "NodeResourcesBalancedAllocation", "NodeAffinity",
    "TaintToleration", "NodeUnschedulable", "NodeName", "ImageLocality",
    "NodePorts",
}

# label-coupled plugins: a bound pod j changes pod k's evaluation ONLY
# when j is visible to k's selectors (PodTopologySpread counts pods
# matching k's constraint selectors; InterPodAffinity counts pods
# matching k's terms, and j's own anti/preferred terms act on k as
# existing-pod constraints).  With the interaction rule below, batches
# stay exact for the headline configs 4 and 5.  The volume family stays
# excluded: PV/PVC bind state is cluster-wide and not label-gated.
LABEL_COUPLED = {"PodTopologySpread", "InterPodAffinity"}


def speculation_ok(cfg, have_manifests: bool = True,
                   ignore: frozenset | set = frozenset()) -> bool:
    """True when the ACTIVE plugin set (enabled list plus every per-point
    override — point_enabled can add a plugin cfg.enabled never lists)
    admits exact speculative batching.  Label-coupled plugins require the
    pod manifests (for the interaction rule); without them only the
    node-local class qualifies.  `ignore` names plugins the CALLER
    handles outside the device pipeline this wave — the engine passes
    its vectorized gang plugin, whose PreFilter ran in the prescreen and
    whose admission happens at commit, so it neither filters nor scores
    on device."""
    active = set(cfg.active_plugins()) - set(ignore)
    if any(cfg.is_custom(n) for n in active):
        return False
    if active <= SAFE_SPECULATIVE:
        return True
    return have_manifests and active <= (SAFE_SPECULATIVE | LABEL_COUPLED)


# ------------------------------------------------------------ interaction

def _pod_terms(pod: dict, namespaces: list[dict] | None) -> tuple[list, list]:
    """(selectors that OTHER pods are matched against for THIS pod's
    evaluation, terms this pod imposes ON others once bound).

    First list — "reads": k's spread-constraint selectors (same-namespace,
    matchLabelKeys merged — plugins/topologyspread.effective_constraints)
    and k's interpod terms.  Second list — "writes": j's interpod terms,
    which act on later pods as existing-pod constraints (upstream
    evaluates existing pods' anti and preferred terms against the
    incoming pod).  Interpod terms come from the PLUGIN's own normalizer
    (plugins/interpod.effective_terms) so namespaceSelector resolution
    (against the live namespace manifests) and matchLabelKeys merging can
    never diverge from what the evaluation actually matches."""
    from ..plugins.interpod import effective_terms
    from ..plugins.topologyspread import effective_constraints

    meta = pod.get("metadata") or {}
    ns = meta.get("namespace") or "default"
    reads: list[tuple[list, dict]] = []
    writes: list[tuple[list, dict]] = []
    for c in effective_constraints(pod):
        reads.append(([ns], c.get("labelSelector") or {}))
    for field in ("podAffinity", "podAntiAffinity"):
        for preferred in (False, True):
            for term, _w in effective_terms(pod, field, preferred,
                                            namespaces=namespaces):
                entry = (list(term.get("namespaces") or [ns]),
                         term.get("labelSelector") or {})
                reads.append(entry)
                writes.append(entry)
    return reads, writes


def _matches_any(terms: list, pod: dict) -> bool:
    from ..state.selectors import label_selector_matches

    meta = pod.get("metadata") or {}
    ns = meta.get("namespace") or "default"
    labels = {k: str(v) for k, v in (meta.get("labels") or {}).items()}
    for ns_list, sel in terms:
        if ns in ns_list and label_selector_matches(sel, labels):
            return True
    return False


class _InteractionOracle:
    """interacts(j, k): does pod j's bind change pod k's label-coupled
    state?  True when j matches any selector k READS, or k matches any
    term j WRITES (j's own anti/preferred terms acting as existing-pod
    constraints).  Conservative and exact: a False guarantees k's
    spread/interpod inputs are untouched by j's bind."""

    def __init__(self, pods: list[dict], namespaces: list[dict] | None = None):
        self.pods = pods
        self.namespaces = namespaces
        self._terms = [None] * len(pods)

    def _t(self, i: int):
        if self._terms[i] is None:
            self._terms[i] = _pod_terms(self.pods[i], self.namespaces)
        return self._terms[i]

    def interacts(self, j: int, k: int) -> bool:
        k_reads, _ = self._t(k)
        _, j_writes = self._t(j)
        return (_matches_any(k_reads, self.pods[j])
                or _matches_any(j_writes, self.pods[k]))


def _interaction_cut(inter: _InteractionOracle, selected: np.ndarray,
                     base: int, k: int) -> int:
    """Shrink the dirty-node-accepted prefix [0, k) to the longest
    prefix with no label-coupled interaction: pod i is kept only when
    no earlier-kept BOUND pod interacts with it either way (module
    doc).  `base` is the batch's first absolute pod index (the
    oracle's index space)."""
    bound: list[int] = []
    for i in range(k):
        if bound and any(inter.interacts(j, base + i) for j in bound):
            return i
        if int(selected[i]) >= 0:
            bound.append(base + i)
    return k


# ------------------------------------------------------ compiled pieces

def _spec_tile(batch: int) -> int:
    """Sub-batch tile for the vmapped evals: on the CPU backend a flat
    [B, N, ...] vmap materializes cache-hostile intermediates (the
    scan's [N]-sized working set is why the sequential path is already
    throughput-bound there), so the batch evaluates in lax.map tiles
    whose per-op footprint stays cache-sized — measured ~1.6x on the
    2-core geometry.  On accelerators the flat vmap is the MXU-friendly
    layout and tiling would serialize, so it stays off.  Rungs are
    powers-of-two multiples of 8, so the default 32 always divides."""
    tile = env_int("KSS_TPU_SPECULATIVE_TILE",
                   32 if jax.default_backend() == "cpu" else 0)
    if tile <= 0 or batch <= tile or batch % tile:
        return 0
    return tile


def _tiled_vmap(fn, batch: int, in_axes):
    """vmap `fn` over the batch axis, evaluated in sub-batch tiles when
    _spec_tile says so.  Axis-None args are closed over; axis-0 args
    reshape to [tiles, tile, ...] and lax.map walks the tiles."""
    vm = jax.vmap(fn, in_axes=in_axes)
    tile = _spec_tile(batch)
    if not tile:
        return vm
    mapped_pos = [i for i, ax in enumerate(in_axes) if ax == 0]

    def run(*args):
        subs = tuple(
            jax.tree.map(
                lambda x: x.reshape((batch // tile, tile) + x.shape[1:]),
                args[i])
            for i in mapped_pos)

        def body(sub_tuple):
            call = list(args)
            for j, i in enumerate(mapped_pos):
                call[i] = sub_tuple[j]
            return vm(*call)

        out = jax.lax.map(body, subs)
        return jax.tree.map(
            lambda x: x.reshape((batch,) + x.shape[2:]), out)

    return run


def _oracle_core(packed, prefilter_reject, selected, batch: int):
    """The dirty-node prefix length on device: feasibility comes from
    the packed first-fail word (0 == all filter plugins passed), the
    conflict test gathers each pod's feasibility AT every earlier pod's
    selected node ([B, B], not [B, N]), and only the prefix length K
    crosses to host.  Pad rows sit past the real rows (selected == -1,
    never bound), so a pad conflict can only push K past them — the
    caller clamps to the round's real size."""
    with jax.named_scope("kss_conflict_oracle"):
        feas = (packed == 0) & (prefilter_reject == 0)[:, None]
        bound = selected >= 0                           # [B]
        cols = jnp.maximum(selected, 0)
        feas_at_sel = jnp.take(feas, cols, axis=1)      # [B(k), B(j)]
        before = jnp.tril(jnp.ones((batch, batch), bool), k=-1)
        conflict = jnp.any(feas_at_sel & bound[None, :] & before,
                           axis=1)                      # [B]
        return jnp.where(jnp.any(conflict), jnp.argmax(conflict),
                         jnp.int32(batch)).astype(jnp.int32)


def _eval_fn(cw: CompiledWorkload, base_key, batch: int, pack_mode: str,
             score_dtypes: tuple, wide, mesh):
    """Cached jitted vmapped compact step — the DENSE eval: full
    per-node scoring for every pod, used for label-coupled plugin sets
    and as the wide-feasibility fallback of the sparse eval.  Shares
    the process-level scan-cache registry, so concurrent sessions
    serving the same workload shape compile each rung once."""
    from ..framework.pipeline import build_step

    # key on the tier STRING (None / "i32" / "i64"): build_step's
    # overflow branches and the raw32 dtype test it literally, and the
    # i32/i64 tiers must not alias to one compiled fn
    key = ("spec_eval", base_key, batch, pack_mode, score_dtypes, wide,
           _spec_tile(batch))
    slim = _SlimWorkload(cw)

    def build():
        def dense_round(carry, xs, arg_statics):
            step = build_step(slim.with_args(arg_statics), out_mode="compact",
                              pack_mode=pack_mode, score_dtypes=score_dtypes,
                              wide_raw=wide)

            def eval_only(carry, sl):
                _, out = step(carry, sl)
                return out

            with jax.named_scope("kss_speculative_round"):
                return _tiled_vmap(eval_only, batch, (None, 0))(carry, xs)

        return jax.jit(dense_round)

    return _SCAN_CACHE.get_or_build(key, build)


def _oracle_fn(batch: int, n: int, pack_mode: str):
    """Cached jitted standalone oracle (the dense eval path; the sparse
    tail fuses _oracle_core into its own jit)."""
    key = ("spec_oracle", batch, n, pack_mode)

    def build():
        def oracle(packed, prefilter_reject, selected):
            return _oracle_core(packed, prefilter_reject, selected, batch)

        return jax.jit(oracle)

    return _SCAN_CACHE.get_or_build(key, build)


# sparse scoring is exact only for plugins whose node-axis statics/xs
# rows are accessed POSITIONALLY (gathering candidate rows keeps every
# read identical); label-coupled plugins index domain tables by VALUE
# (counts[dom_idx[n]]), so they take the dense eval instead
def _sparse_ok(active: set) -> bool:
    return active <= SAFE_SPECULATIVE


def _take_nodes(x, idx, n: int):
    """Gather candidate rows along a leaf's node axis (first axis whose
    extent == n; leaves without one pass through) — the same node-axis
    identification rule parallel/mesh.py shards by."""
    if not hasattr(x, "ndim"):
        return x
    for ax in range(x.ndim):
        if x.shape[ax] == n:
            return jnp.take(x, idx, axis=ax)
    return x


def _sparse_round_fn(cw: CompiledWorkload, base_key, batch: int,
                     pack_mode: str, score_dtypes: tuple, wide, kcand: int):
    """Cached jitted sparse-eval round — ONE fused per-pod pass (each
    pod's [N]-sized intermediates stay cache-hot) plus the batch-level
    conflict oracle:

      1. DENSE filters (annotation parity needs every node's first-fail
         code), packed to the compact word, plus the prefilter reject
         and the feasible count;
      2. the first-kcand feasible node indices in ascending node order
         (the argmax tie-break's order): candidate c is the first index
         whose running feasible count reaches c+1 — a binary search
         over the cumsum, O(K log N), where lax.top_k costs a per-row
         partial sort (measured ~25x slower at 5k nodes on the CPU
         backend) and a scatter formulation lowers poorly there too;
      3. score, normalize and select on the GATHERED candidate rows
         only ([K] instead of [N] — at sparse feasibility this is where
         the speculative wave's raw-speed win lives), scattering the
         raw score columns back onto the dense compact grid (values at
         infeasible nodes are don't-cares by the compact layout:
         decode, hostnorm and attribution all read feasible positions
         only);
      4. the dirty-node oracle over the whole batch's selections.

    Exactness: every normalization reduces over the FEASIBLE set, which
    the candidate gather preserves exactly (candidates ⊇ feasible when
    max count <= kcand — the caller falls back to the dense eval
    otherwise), and argmax over candidates in ascending node order
    reproduces the dense first-max tie-break."""
    from ..framework.pipeline import (_filter_phase, _prefilter_reject,
                                      _score_phase, pack_filter_codes)

    key = ("spec_round", base_key, batch, pack_mode, score_dtypes,
           wide, kcand, _spec_tile(batch))
    score_names = cw.config.scorers()
    filter_names = cw.config.filters()
    weights = jnp.asarray([cw.config.weight(nm) for nm in score_names],
                          dtype=jnp.int64)
    slim = _SlimWorkload(cw)
    n = cw.n_nodes

    def build():
        def one(slim, carry, sl):
            codes, feasible, considered = _filter_phase(
                slim, carry, sl, filter_names)
            packed = pack_filter_codes(codes, n, pack_mode, considered)
            reject = _prefilter_reject(slim, carry, sl)
            count = jnp.sum(feasible, dtype=jnp.int32)
            count = jnp.where(reject > 0, 0, count)
            cum = jnp.cumsum(feasible.astype(jnp.int32))
            cand = jnp.searchsorted(
                cum, jnp.arange(1, kcand + 1, dtype=jnp.int32))
            cand = jnp.minimum(cand, n - 1).astype(jnp.int32)
            valid = jnp.arange(kcand, dtype=jnp.int32) < count
            g_sl = jax.tree.map(lambda x: _take_nodes(x, cand, n), sl)
            # every sparse-eligible plugin (SAFE_SPECULATIVE) reads its
            # node-axis statics/carry rows positionally, so gather ALL
            # entries — NodeAffinity keeps its match rows in statics
            # ([U, N] pools the xs index into, handed to the round as
            # arguments), not in per-pod xs
            g_statics = {k: jax.tree.map(lambda x: _take_nodes(x, cand, n), v)
                         for k, v in slim.statics.items()}
            g_carry = {k: jax.tree.map(lambda x: _take_nodes(x, cand, n), v)
                       for k, v in carry.items()}
            view = SimpleNamespace(config=slim.config, statics=g_statics,
                                   n_nodes=kcand, schema=slim.schema)
            raws, _finals, total = _score_phase(
                view, g_carry, g_sl, weights, score_names, valid)
            sel_k = jnp.argmax(total).astype(jnp.int32)
            selected = jnp.where(count > 0, cand[sel_k],
                                 jnp.int32(-1)).astype(jnp.int32)
            is_pad = g_sl.get("is_pad")
            if is_pad is not None:
                selected = jnp.where(is_pad, jnp.int32(-1), selected)
            # scatter the raw columns onto the dense grid: invalid slots
            # park in a shed column past n (duplicate indices among them
            # never touch real nodes), sliced off below
            park = jnp.where(valid, cand, jnp.int32(n))
            groups: dict[str, list] = {"i8": [], "i16": [], "i32": []}
            for s in range(len(score_names)):
                g = score_dtypes[s]
                if g == "host":
                    continue
                g = "i32" if wide else g
                groups[g].append(raws[s])

            def scatter(rows, dtype):
                if not rows:
                    return jnp.zeros((0, n), dtype=dtype)
                vals = jnp.stack(rows).astype(dtype)       # [Sg, K]
                buf = jnp.zeros((vals.shape[0], n + 1), dtype)
                return buf.at[:, park].set(vals)[:, :n]

            raw8 = scatter(groups["i8"], jnp.int8)
            raw16 = scatter(groups["i16"], jnp.int16)
            raw32 = scatter(groups["i32"],
                            jnp.int64 if wide == "i64" else jnp.int32)
            ovf = jnp.asarray(False)
            if wide is None and groups["i16"]:
                full = jnp.stack(groups["i16"])
                ovf = jnp.any(valid[None, :]
                              & (full != full.astype(jnp.int16)
                                 .astype(full.dtype)))
            elif wide == "i32" and groups["i32"]:
                full = jnp.stack(groups["i32"])
                ovf = jnp.any(valid[None, :]
                              & (full != full.astype(jnp.int32)
                                 .astype(full.dtype)))
            return packed, reject, count, raw8, raw16, raw32, ovf, selected

        def round_fn(carry, xs, arg_statics):
            view = slim.with_args(arg_statics)
            with jax.named_scope("kss_speculative_round"):
                (packed, reject, counts, raw8, raw16, raw32, ovf,
                 selected) = _tiled_vmap(
                     lambda carry, sl: one(view, carry, sl), batch,
                     (None, 0))(carry, xs)
            k_dev = _oracle_core(packed, reject, selected, batch)
            return (packed, reject, counts, raw8, raw16, raw32, ovf,
                    selected, k_dev)

        return jax.jit(round_fn)

    return _SCAN_CACHE.get_or_build(key, build)


def _commit_fn(cw: CompiledWorkload, base_key, batch: int):
    """Cached jitted (carry, xs_batch, selected, accept, arg_statics) ->
    carry with every accepted bind applied.  Core-only workloads (the carry holds
    nothing but "core") fold all binds in ONE scatter-add — accepted
    pods bind distinct nodes (the dirty-node rule), so one batched
    scatter == the sequential fold of core_bind_update.  Anything with
    ports/topology/interpod/volume carries folds the pipeline's own
    _bind_phase over the batch with non-accepted selections masked to
    -1 (a no-op bind) — exactly the sequential carry fold, so every
    plugin carry advances identically to the scan path."""
    core_only = set(cw.init_carry.keys()) <= {"core"}
    key = ("spec_commit", base_key, batch, core_only)
    slim = _SlimWorkload(cw)

    def build():
        if core_only:
            def commit(carry, xs_batch, selected, accept, arg_statics):
                core_batch = xs_batch["core"]
                core = carry["core"]
                bound = accept & (selected >= 0)
                idx = jnp.maximum(selected, 0)
                add = jnp.where(bound, 1, 0)
                requested = core.requested.at[idx].add(
                    core_batch.requests
                    * add[:, None].astype(core.requested.dtype))
                nonzero = core.nonzero.at[idx].add(
                    core_batch.nonzero
                    * add[:, None].astype(core.nonzero.dtype))
                num_pods = core.num_pods.at[idx].add(
                    add.astype(core.num_pods.dtype))
                out = dict(carry)
                out["core"] = core._replace(
                    requested=requested, nonzero=nonzero, num_pods=num_pods)
                return out
        else:
            from ..framework.pipeline import _bind_phase

            def commit(carry, xs_batch, selected, accept, arg_statics):
                sel = jnp.where(accept, selected, jnp.int32(-1))
                view = slim.with_args(arg_statics)

                def body(c, t):
                    sl, s = t
                    return _bind_phase(view, c, sl, s), None

                out, _ = jax.lax.scan(body, carry, (xs_batch, sel))
                return out

        def bind_fold(carry, xs_batch, selected, accept, arg_statics):
            with jax.named_scope("kss_bind_fold"):
                return commit(carry, xs_batch, selected, accept, arg_statics)

        return jax.jit(bind_fold, donate_argnums=(0,))

    return _SCAN_CACHE.get_or_build(key, build)


def _accum_fns(shapes_key, chunk: int):
    """Cached jitted chunk-grid accumulator ops over the compact group
    buffers (dict name -> [chunk + extra, ...]):

      append(bufs, rows, fill) — write a round's rows at the fill mark
        (the caller advances fill only past the ACCEPTED prefix, so the
        rejected suffix is overwritten by the next round);
      emit(bufs) — split off the first grid chunk and shift the
        remainder down (static shapes: the shift is always by `chunk`).
    """
    append_key = ("spec_append", shapes_key, chunk)
    emit_key = ("spec_emit", shapes_key, chunk)

    def build_append():
        def append(bufs, rows, fill):
            return {
                name: jax.lax.dynamic_update_slice_in_dim(
                    bufs[name], rows[name].astype(bufs[name].dtype), fill, 0)
                for name in bufs
            }

        return jax.jit(append, donate_argnums=(0,))

    def build_emit():
        def emit(bufs):
            heads = {name: bufs[name][:chunk] for name in bufs}
            rest = {
                name: jnp.concatenate(
                    [bufs[name][chunk:],
                     jnp.zeros((chunk,) + bufs[name].shape[1:],
                               bufs[name].dtype)], axis=0)
                for name in bufs
            }
            return heads, rest

        return jax.jit(emit)

    return (_SCAN_CACHE.get_or_build(append_key, build_append),
            _SCAN_CACHE.get_or_build(emit_key, build_emit))


# ------------------------------------------------------------- ladder

# the batch ladder's bottom rung (per dp shard), and the fewest pods a
# round must hold for its accepted prefix to be evidence of anything: a
# round of 2-7 pods that keeps one says nothing of the queue
MIN_ROUND = 8


def _batch_ladder(chunk: int, dp: int, pinned: int | None) -> list[int]:
    """Adaptive batch rungs: dp multiples (the dp shards stay balanced)
    growing x4 from 8*dp up to the chunk grid.  Each rung is one extra
    jit specialization, bounded by the ladder length; a pinned batch
    (KSS_TPU_SPECULATIVE_BATCH or an explicit batch=) is a one-rung
    ladder."""
    dp = max(dp, 1)

    def fit(b: int) -> int:
        b = max(b - b % dp, dp)
        return max(min(b, max(chunk - chunk % dp, dp)), 1)

    if pinned is not None:
        return [fit(pinned)]
    rungs: list[int] = []
    b = MIN_ROUND * dp
    while fit(b) < fit(chunk):
        rungs.append(fit(b))
        b *= 4
    rungs.append(fit(chunk))
    # dedupe while preserving order (tiny workloads collapse rungs)
    out: list[int] = []
    for r in rungs:
        if not out or r != out[-1]:
            out.append(r)
    return out


# ------------------------------------------------------------- stream

class _SpecStats:
    """Per-stream tallies; the final tier's numbers are the wave's."""

    def __init__(self):
        self.rounds: list[tuple[int, int]] = []   # (accepted, round size)
        self.scan_pods = 0
        self.fallback_at: int | None = None
        self.collapsed = False
        self.final_batch = 0

    def as_dict(self, adaptive: bool) -> dict:
        accepts = [k for k, _ in self.rounds]
        total = sum(accepts)
        rolled = sum(m - k for k, m in self.rounds)
        return {
            "rounds": len(self.rounds),
            "batch": self.final_batch,
            "adaptive": adaptive,
            "round_batches": [m for _, m in self.rounds],
            "mean_accept": round(float(np.mean(accepts)), 2) if accepts else 0,
            "accepted_first_try": int(sum(k == m for k, m in self.rounds)),
            "accepted": total,
            "rolled_back": rolled,
            "accept_rate": round(total / (total + rolled), 4)
                if total + rolled else None,
            "fallback_at": self.fallback_at,
            "collapsed": self.collapsed,
            "scan_pods": self.scan_pods,
        }


def replay_speculative_stream(
        cw: CompiledWorkload, mesh=None, chunk: int = POD_CHUNK, unroll: int = 1,
        batch: int | None = None, pods: list[dict] | None = None,
        namespaces: list[dict] | None = None, on_chunk=None,
        device_resident: bool | None = None, gang=None,
        scan_fallback: bool = True, ignore: frozenset | set = frozenset(),
) -> tuple[ReplayResult | None, dict]:
    """Schedule the whole queue in streaming speculative rounds (module
    doc).  Same consumer contract as framework.replay.replay(): compact
    chunk-grid results, on_chunk(rr, lo, hi) in ascending contiguous
    order with idempotent re-delivery from chunk 0 on a width-tier
    overflow, device residency resolved exactly like the scan.

    pods: the pod manifests, required when label-coupled plugins
    (PodTopologySpread / InterPodAffinity) are active — the interaction
    rule reads their selectors.  namespaces: the namespace manifests for
    interpod namespaceSelector resolution.  gang: an object with `gid`
    ([P] int32 pod->group, -1 for plain pods) and `start` ([G] first
    member index) — round cuts pull back to gang boundaries so gangs
    stream as all-or-nothing prefix units.

    Returns (rr, stats): rr is bit-identical to replay(cw) / the
    sequential oracle; stats records rounds, acceptance and fallback.
    rr is None, and stats["collapsed"] true, where the first round of a
    one-chunk pass collapsed (scan_fallback only; module doc): nothing
    was delivered to on_chunk, and the caller runs the pass through
    replay(), as SchedulerEngine._device_wave does.
    Caller must have checked speculation_ok(cw.config, ...)."""
    device_resident = _resolve_device_resident(device_resident, on_chunk)
    active = set(cw.config.active_plugins())
    inter: _InteractionOracle | None = None
    if active & LABEL_COUPLED:
        if pods is None:
            raise ValueError(
                "label-coupled plugins active: the speculative stream needs "
                "the pod manifests for the interaction rule")
        inter = _InteractionOracle(pods, namespaces)

    if batch is None:
        raw = os.environ.get("KSS_TPU_SPECULATIVE_BATCH")
        if raw:
            batch = env_int("KSS_TPU_SPECULATIVE_BATCH", 0) or None

    # the session's record of its rounds (control/__init__.py): which
    # buckets they have run on, and whether this pass is the probe a
    # declined pass asked for.  Once a pass, not once a width tier
    bucket = pass_chunk(cw, chunk)
    CONTROLS.note_spec_rounds(
        TRACER.current_session(), cw.config.signature(), bucket,
        probe=scan_fallback and cw.n_pods <= bucket)

    tiers = (("i64",) if "i64" in cw.host.get("score_dtypes", ())
             else (None, "i32", "i64"))
    for wide in tiers:
        # cross-session fused dispatch (parallel/fuse.py): announce this
        # stream's shape family so compatible tenants' rounds can stack
        # into one device call.  The try/finally — not the happy path —
        # is the lifecycle contract: a wave abort mid-round must not
        # leave partners counting a dead stream as a batch-mate, and the
        # retry re-opens cleanly.
        fuse_stream = None
        if fuse_enabled():
            fuse_stream = FUSE.stream_open(
                _fuse_family(cw, chunk, mesh, wide, ignore),
                admitted=session_admitted(TRACER.current_session()),
                mesh=mesh)
        try:
            result = _spec_run(cw, mesh, chunk, unroll, batch, on_chunk,
                               device_resident, wide, inter, gang,
                               scan_fallback, ignore,
                               fuse_stream=fuse_stream)
        finally:
            if fuse_stream is not None:
                FUSE.stream_close(fuse_stream)
        if result is not None:
            return result
        TRACER.count("replay_width_retries_total")
    raise AssertionError("unreachable: i64 speculative replay cannot overflow")


def _fuse_family(cw: CompiledWorkload, chunk: int, mesh, wide,
                 ignore: frozenset | set):
    """The fuse-compatibility family: everything that picks which
    compiled round executables a stream will call, short of the rung
    (which joins the per-dispatch key).  Mirrors _spec_run's own cheap
    derivations — two streams with equal families resolve the SAME
    callables from the process compile cache, which is exactly the
    stacking precondition.  Note the scan key fingerprints statics
    CONTENT but only xs/carry SHAPES: heterogeneous tenants (different
    pods, same fleet and queue size) fuse — the Gavel framing."""
    chunk = pass_chunk(cw, chunk)
    base_key = _workload_scan_key(cw, chunk, mesh)
    active_eff = set(cw.config.active_plugins()) - set(ignore)
    # the autopilot's per-session candidate cap (control/__init__.py)
    # must resolve HERE exactly as _spec_run resolves it, or two
    # streams with equal families would pick different sparse-round
    # executables and the stacking precondition would silently break
    _, ov_kcand = CONTROLS.spec_overrides(TRACER.current_session())
    kcand = min(max(ov_kcand if ov_kcand is not None
                    else env_int("KSS_TPU_SPECULATIVE_CANDIDATES", 128), 1),
                cw.n_nodes)
    sparse = _sparse_ok(active_eff) and kcand < cw.n_nodes
    return (base_key, wide, sparse, kcand if sparse else None)


def _spec_run(cw: CompiledWorkload, mesh, chunk: int, unroll: int,
              batch: int | None, on_chunk, device_resident: bool,
              wide, inter, gang, scan_fallback: bool,
              ignore: frozenset | set = frozenset(),
              fuse_stream=None,
              ) -> tuple[ReplayResult | None, dict] | None:
    from ..framework.gang import aligned_cut
    from .mesh import gather_to_host

    p = cw.n_pods
    chunk = pass_chunk(cw, chunk)
    # scan_prepare, as in the sequential replay: the compact plan and the
    # scan-cache key with its statics fingerprint here; below, the
    # workload's unpack and the carry's copy
    with TRACER.span("scan_prepare", bucket=chunk):
        pack_mode, score_dtypes, score_cols = _compact_plan(cw, wide)
        base_key = _workload_scan_key(cw, chunk, mesh)
    dp = mesh.shape.get("dp", 1) if mesh is not None else 1
    ladder = _batch_ladder(chunk, dp, batch)
    adaptive = batch is None and len(ladder) > 1
    rung = 0
    min_accept = env_float("KSS_TPU_SPECULATIVE_MIN_ACCEPT", 0.25)
    fallback_rounds = (env_int("KSS_TPU_SPECULATIVE_FALLBACK_ROUNDS", 3)
                       if scan_fallback else 0)
    check_overflow = wide != "i64"

    n = cw.n_nodes
    compact = _CompactChunks(
        packed=[], raw8=[], raw16=[], raw32=[],
        chunk=chunk, pack_mode=pack_mode, score_cols=score_cols,
    )
    selected = np.full(p, -1, dtype=np.int32)
    feasible_count = np.zeros(p, dtype=np.int32)
    prefilter_reject = np.zeros(p, dtype=np.int32)
    rr = ReplayResult(cw=cw, selected=selected,
                      feasible_count=feasible_count,
                      prefilter_reject=prefilter_reject, compact=compact)

    # device-side chunk-grid accumulator: group buffers big enough for
    # one grid chunk plus the largest single append (a top-rung round or
    # a fallback scan chunk)
    from ..framework.pipeline import PACK_MODES

    extra = max(chunk, max(ladder))
    n8, n16, n32 = 0, 0, 0
    for g, _r in score_cols:
        n8 += g == "raw8"
        n16 += g == "raw16"
        n32 += g == "raw32"
    pack_dtype = PACK_MODES[pack_mode][0]
    buf_shapes = {
        "packed": ((chunk + extra, n), pack_dtype),
        "raw8": ((chunk + extra, n8, n), jnp.int8),
        "raw16": ((chunk + extra, n16, n), jnp.int16),
        # the i64 tier's raw32 group IS int64 (the ladder's last rung
        # cannot overflow) — the buffers must not truncate it
        "raw32": ((chunk + extra, n32, n),
                  jnp.int64 if wide == "i64" else jnp.int32),
        "fc": ((chunk + extra,), jnp.int32),
    }
    shapes_key = tuple(sorted((k, tuple(s), str(d))
                              for k, (s, d) in buf_shapes.items()))
    append_jit, emit_jit = _accum_fns(shapes_key, chunk)
    bufs = {name: jnp.zeros(s, d) for name, (s, d) in buf_shapes.items()}
    fill = 0

    att_ctx = (_DeviceAttribution(cw, chunk, pack_mode, score_cols)
               if device_resident else None)
    if att_ctx is not None and not att_ctx.enabled:
        att_ctx = None

    # single-core CPU backend: XLA's worker threads spin-wait between
    # device calls and starve a concurrent on_chunk consumer — defer the
    # callbacks until the stream has fully drained (same rule as the
    # scan path's dispatch loop)
    from ..utils.platform import effective_cpu_count

    defer_chunks: list[tuple[int, int]] | None = (
        [] if on_chunk is not None and jax.default_backend() == "cpu"
        and effective_cpu_count() < 2 else None)

    def deliver(lo_c: int, hi_c: int) -> None:
        if on_chunk is None:
            return
        if defer_chunks is not None:
            defer_chunks.append((lo_c, hi_c))
        else:
            on_chunk(rr, lo_c, hi_c)

    group_of = {"packed": "packed", "raw8": "raw8", "raw16": "raw16",
                "raw32": "raw32"}

    def ingest_chunk(heads: dict) -> None:
        """Land one grid chunk (group name -> [chunk, ...] device
        arrays) in the compact result: retain on device (budgeted, with
        the jit'd attribution sums) or fetch to host, then deliver it
        to the streaming consumer."""
        ci = len(compact.packed)
        lo_c = ci * chunk
        hi_c = min(lo_c + chunk, p)
        att_host = None
        if device_resident:
            if att_ctx is not None:
                out_like = SimpleNamespace(
                    packed_filter=heads["packed"], raw8=heads["raw8"],
                    raw16=heads["raw16"], raw32=heads["raw32"],
                    feasible_count=heads["fc"])
                att_dev = att_ctx.run(out_like, lo_c)
                att_host = {k: np.asarray(v) for k, v in att_dev.items()}
                TRACER.count("wave_d2h_bytes_total",
                             sum(a.nbytes for a in att_host.values()))
            for name, group in group_of.items():
                getattr(compact, group).append(heads[name])
            _DEVICE_BUDGET.retain(compact, ci, compact.device_nbytes(ci))
        else:
            nbytes = 0
            for name, group in group_of.items():
                host = gather_to_host(heads[name])
                nbytes += host.nbytes
                getattr(compact, group).append(host)
            TRACER.count("wave_d2h_bytes_total", nbytes)
        compact.att.append(att_host)
        deliver(lo_c, hi_c)

    def emit_chunk() -> None:
        nonlocal bufs, fill
        heads, bufs = emit_jit(bufs)
        fill -= chunk
        ingest_chunk(heads)

    # the rounds gather and place xs batch by batch, so they hold the
    # workload as leaves (unpacked here, once, where compile_workload
    # left it packed).  copy: the commit/scan fold donates its carry
    # argument, and cw.init_carry must survive for later replays of the
    # same workload
    TRACER.inc("replay_route_total", route="leaves")
    TRACER.count("pass_device_dispatches_total")
    with TRACER.span("scan_prepare", bucket=chunk):
        carry = _copy_carry(cw.init_carry)
        # what the round executables take beside carry and xs
        # (state/compile.py ARG_STATICS: NodeAffinity's match rows here;
        # the volume family never speculates): keyed by shape, so another
        # pod's terms are the same executables
        arg_statics = cw.arg_statics()
    stats = _SpecStats()
    cw_scan = None       # mesh-sharded clone, built on first scan round
    scan_jit = None
    mode = "speculative"
    low_streak = 0
    # sparse-tail eligibility (docs/wave-pipeline.md): node-local plugin
    # sets score/select on the gathered candidate rows only — the raw-
    # speed win at sparse feasibility; label-coupled sets (value-indexed
    # domain tables) and wide-feasibility rounds run the dense eval
    active_eff = set(cw.config.active_plugins()) - set(ignore)
    # session control-plane overrides (control/autopilot.py): the
    # candidate cap replaces the static env default, the start rung
    # replaces the dense/sparse ramp heuristics below.  Both are
    # parity-invariant: kcand only moves the sparse/dense round split
    # (wide-feasibility rounds still fall back dense) and the rung only
    # partitions the same exact rounds differently.
    ov_rung, ov_kcand = CONTROLS.spec_overrides(TRACER.current_session())
    kcand = min(max(ov_kcand if ov_kcand is not None
                    else env_int("KSS_TPU_SPECULATIVE_CANDIDATES", 128),
                    1), n)
    sparse_ok = _sparse_ok(active_eff) and kcand < n
    if sparse_ok and adaptive:
        # sparse probes are cheap (dense filters + candidate tail), so
        # start at the TOP rung: a contention-free wave's steady-state
        # rounds are then whole aligned chunks ingested directly (no
        # accumulator passes); a collapse steps the ladder down round
        # by round and the bottom-rung fallback still engages.  The
        # dense eval keeps the climb-from-8 ramp — its probes cost a
        # full [B, N] evaluation
        rung = len(ladder) - 1
    # a round runs the sparse probe only where the session's LAST round
    # (this stream's or an earlier one's) kept every feasible set inside
    # the cap: a probe that meets a wider set is dropped for the dense
    # evaluation, and its executable is the dearest of a rung to build
    # (37-86 s for the v5e against 8-10 for the dense one, PERF.md
    # section 6, PR 50).  Either kind of round says how wide the sets
    # were, so the choice follows the queue in both directions, one
    # round late; a session no round has served yet starts dense
    session = TRACER.current_session()
    sparse = sparse_ok and CONTROLS.spec_narrow(session)
    if adaptive and ov_rung is not None:
        # autopilot starting rung (hysteresis lives in the controller;
        # the in-wave climb/drop below still reacts within the wave):
        # <0 = top rung, else clamped to this stream's ladder
        rung = (len(ladder) - 1 if ov_rung < 0
                else min(max(ov_rung, 0), len(ladder) - 1))

    # per-rung compiled pieces, resolved from the process cache once per
    # stream instead of per round
    _fns: dict[tuple, Any] = {}

    def _memo(kind: str, b: int, make):
        got = _fns.get((kind, b))
        if got is None:
            got = _fns[(kind, b)] = make()
        return got

    def eval_for(b):
        return _memo("eval", b, lambda: _eval_fn(
            cw, base_key, b, pack_mode, score_dtypes, wide, mesh))

    def oracle_for(b):
        return _memo("oracle", b, lambda: _oracle_fn(b, n, pack_mode))

    def commit_for(b):
        return _memo("commit", b, lambda: _commit_fn(cw, base_key, b))

    def round_for(b):
        return _memo("round", b, lambda: _sparse_round_fn(
            cw, base_key, b, pack_mode, score_dtypes, wide, kcand))

    def dense_round_for(b):
        # the dense round's two device calls as ONE function so fusion
        # has a single dispatch to stack; unfused it invokes the same
        # two jitted callables the dense site always ran — byte-for-byte
        # the solo path
        def make():
            ev, orc = eval_for(b), oracle_for(b)

            def both(carry_in, xs_in, args_in):
                outs = ev(carry_in, xs_in, args_in)
                return outs, orc(outs.packed_filter, outs.prefilter_reject,
                                 outs.selected)

            return both

        return _memo("dense_round", b, make)

    def fused_call(kind: str, b: int, fn, carry_in, xs_in):
        """Route one round's device call through the fuse coordinator:
        with no open stream (fusion off) or a closed one (this stream
        already fell back to the scan) it IS the direct call.  The
        dispatch key extends the family with everything else the solo
        executable was cached under, so only calls to the same compiled
        program ever stack."""
        if fuse_stream is None or fuse_stream.closed:
            return fn(carry_in, xs_in, arg_statics)
        return FUSE.dispatch(fuse_stream, (fuse_stream.family, kind, b),
                             fn, (carry_in, xs_in, arg_statics))

    def place_batch(xs_batch):
        if mesh is None:
            return xs_batch
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .mesh import _node_axis_spec

        def place(x):
            if not hasattr(x, "ndim") or x.ndim == 0:
                return x
            inner = _node_axis_spec(x[0], n, skip_leading=False)
            return jax.device_put(x, NamedSharding(mesh, P("dp", *inner)))

        return jax.tree.map(place, xs_batch)

    # a pass of one chunk delivers once, at its end: until then nothing
    # is committed and the pass can start again as another route
    restartable = scan_fallback and p <= chunk

    def tally_round(b: int, m: int, accepted: int, kept: int) -> None:
        """A round's record: `accepted` is the prefix the rules passed,
        `kept` what of it stays decided (all of it, or nothing where the
        round collapsed the pass)."""
        stats.rounds.append((kept, m))
        stats.final_batch = b
        TRACER.count("speculative_rounds_total")
        TRACER.inc("speculative_accepted_total", kept)
        if m > kept:
            TRACER.inc("speculative_rolled_back_total", m - kept)
        TRACER.observe("speculative_accept_fraction", accepted / m)
        # black-box round history (utils/blackbox.py): the evidence a
        # post-mortem needs to explain WHY the controller climbed,
        # dropped, or fell back — batch size, accept fraction, rung
        BLACKBOX.record("speculative.round", batch=m, accepted=accepted,
                        rung=b, accept_fraction=round(accepted / m, 4))

    def hand_over(at: int, **why) -> None:
        """The rounds end and the sequential scan takes pod `at` on.  No
        more rounds are dispatched: close the fuse stream NOW
        (idempotent — the tier loop's finally closes again harmlessly) so
        partner leaders stop counting this stream as a batch-mate."""
        if fuse_stream is not None:
            FUSE.stream_close(fuse_stream)
        stats.fallback_at = at
        TRACER.inc("speculative_fallbacks_total")
        BLACKBOX.record("speculative.fallback", at=at,
                        rounds=len(stats.rounds), **why)

    lo = 0
    while lo < p:
        fault_point("speculative.round")
        if mode == "scan":
            # contention fallback: the same jitted chunked scan the
            # sequential path runs, resumed from the speculative carry
            # (bit-identical to the sequential carry at pod `lo`)
            if scan_jit is None:
                cw_scan = cw
                if mesh is not None:
                    from .mesh import shard_workload

                    cw_scan = shard_workload(cw, mesh)
                scan_jit = _scan_for(cw_scan, chunk, unroll, mesh,
                                     pack_mode=pack_mode,
                                     score_dtypes=score_dtypes, wide=wide)
            # the first fallback round is sized to reach the chunk grid;
            # every later one is a whole aligned chunk whose outputs
            # ingest DIRECTLY as the compact chunk — no accumulator
            # append/emit passes, so a fully-fallen-back wave runs at
            # the sequential path's speed
            aligned = fill == 0 and lo % chunk == 0
            hi = min(lo + (chunk if aligned else chunk - fill), p)
            m = hi - lo
            fault_point("replay.scan_dispatch")
            with TRACER.span("scan_dispatch", lo=lo):
                xs_chunk = _slice_xs(cw_scan.xs, lo, hi, chunk)
                carry, out = scan_jit(carry, xs_chunk,
                                      cw_scan.arg_statics())
            fault_point("replay.decision_fetch")
            with TRACER.span("decision_fetch"):
                sel = np.asarray(out.selected)
                fc = np.asarray(out.feasible_count)
                rej = np.asarray(out.prefilter_reject)
                ovf = np.asarray(out.raw_overflow)
            TRACER.count("wave_d2h_bytes_total",
                         sel.nbytes + fc.nbytes + rej.nbytes + ovf.nbytes)
            if check_overflow and ovf[:m].any():
                return None
            selected[lo:hi] = sel[:m]
            feasible_count[lo:hi] = fc[:m]
            prefilter_reject[lo:hi] = rej[:m]
            if aligned:
                # a whole aligned chunk (or the final partial one, whose
                # pad rows are don't-cares exactly like the scan path's)
                ingest_chunk({"packed": out.packed_filter,
                              "raw8": out.raw8, "raw16": out.raw16,
                              "raw32": out.raw32,
                              "fc": out.feasible_count})
            else:
                bufs = append_jit(bufs, {
                    "packed": out.packed_filter, "raw8": out.raw8,
                    "raw16": out.raw16, "raw32": out.raw32,
                    "fc": out.feasible_count}, fill)
                fill += m
                while fill >= chunk:
                    emit_chunk()
            stats.scan_pods += m
            lo = hi
            continue

        b = ladder[rung]
        hi = min(lo + b, p)
        m = hi - lo
        with TRACER.span("speculative_round", batch=m, rung=b, bucket=chunk):
            fault_point("replay.scan_dispatch")
            # the sequential scan's two seams, under the same two names
            with TRACER.span("scan_dispatch", lo=lo):
                xs = place_batch(_slice_xs(cw.xs, lo, hi, b))
            dense = not sparse
            if sparse:
                # one fused dispatch per round; a wide-feasibility round
                # (max count past the candidate cap) simply discards the
                # sparse output and re-runs dense
                with TRACER.span("scan_dispatch", lo=lo):
                    (packed, reject_d, counts_d, raw8, raw16, raw32, ovf_d,
                     sel_dev, k_dev) = fused_call("round", b, round_for(b),
                                                  carry, xs)
                fault_point("replay.decision_fetch")
                with TRACER.span("decision_fetch"):
                    fc = np.asarray(counts_d)
                    rej = np.asarray(reject_d)
                    if int(fc[:m].max(initial=0)) > kcand:
                        dense = True  # wide feasibility: this round runs dense
                    else:
                        sel = np.asarray(sel_dev)
                        ovf = np.asarray(ovf_d)
                        rows = {"packed": packed, "raw8": raw8,
                                "raw16": raw16, "raw32": raw32,
                                "fc": counts_d}
            if dense:
                with TRACER.span("scan_dispatch", lo=lo):
                    outs, k_dev = fused_call("dense", b, dense_round_for(b),
                                             carry, xs)
                fault_point("replay.decision_fetch")
                with TRACER.span("decision_fetch"):
                    sel = np.asarray(outs.selected)
                    fc = np.asarray(outs.feasible_count)
                    rej = np.asarray(outs.prefilter_reject)
                    ovf = np.asarray(outs.raw_overflow)
                sel_dev = outs.selected
                rows = {"packed": outs.packed_filter, "raw8": outs.raw8,
                        "raw16": outs.raw16, "raw32": outs.raw32,
                        "fc": outs.feasible_count}
            if sparse_ok:
                narrow = int(fc[:m].max(initial=0)) <= kcand
                if not narrow:
                    # ran dense for wide feasibility, its probe dropped or
                    # not made: what the autopilot reads before it moves
                    # the candidate cap
                    TRACER.count("speculative_wide_rounds_total")
                if narrow != sparse:
                    sparse = narrow
                    CONTROLS.note_spec_narrow(session, narrow)
            with TRACER.span("decision_fetch"):
                k = min(int(k_dev), m)
            TRACER.count("wave_d2h_bytes_total",
                         sel.nbytes + fc.nbytes + rej.nbytes + ovf.nbytes + 4)
            if (restartable and not stats.rounds and m >= MIN_ROUND
                    and 4 * k <= m):
                # the pass's FIRST round kept a quarter of what it
                # evaluated or less: these rounds do not accept (module
                # doc).  Nothing was delivered and cw.init_carry is
                # intact, so the stream ends here and its caller runs the
                # whole pass as the sequential scan's one call
                share = float(np.median(fc[:m])) / n
                tally_round(b, m, k, 0)
                hand_over(0, restart=True, feasible_share=round(share, 4))
                stats.collapsed = True
                CONTROLS.note_spec_collapsed(session, cw.config.signature(),
                                             share)
                return None, stats.as_dict(adaptive)
            if inter is not None and k > 1:
                k = _interaction_cut(inter, sel, lo, k)
            if gang is not None:
                k = aligned_cut(gang.gid, gang.start, lo, k, p)
            if check_overflow and ovf[:k].any():
                return None
            selected[lo:lo + k] = sel[:k]
            feasible_count[lo:lo + k] = fc[:k]
            prefilter_reject[lo:lo + k] = rej[:k]
            accept = jnp.arange(b) < k
            carry = commit_for(b)(carry, xs, sel_dev, accept, arg_statics)
            if k == m == chunk and fill == 0 and lo % chunk == 0:
                # a fully-accepted top-rung round at an aligned position
                # IS a grid chunk: ingest its outputs directly — no
                # accumulator append/emit passes (the steady state of a
                # contention-free wave)
                ingest_chunk(rows)
            else:
                bufs = append_jit(bufs, rows, fill)
                fill += k
                while fill >= chunk:
                    emit_chunk()
        tally_round(b, m, k, k)
        lo += k
        # contention-aware controller: full-accept rounds climb the
        # ladder, heavily-cut rounds step down, and a sustained accept
        # collapse at the bottom rung hands the rest of the wave to the
        # sequential scan (speculation would evaluate ~B pods per
        # accepted pod — pure waste on a fully-relaxed queue)
        if adaptive:
            if k == m and rung < len(ladder) - 1:
                rung += 1
            elif k < max(1, m // 4) and rung > 0:
                rung -= 1
        if fallback_rounds > 0 and rung == 0 and lo < p:
            if k / m < min_accept:
                low_streak += 1
                if low_streak >= fallback_rounds:
                    mode = "scan"
                    hand_over(lo)
            else:
                low_streak = 0

    if fill > 0:
        emit_chunk()
    if defer_chunks:
        for lo_c, hi_c in defer_chunks:
            on_chunk(rr, lo_c, hi_c)
    return rr, stats.as_dict(adaptive)


def replay_speculative(cw: CompiledWorkload, mesh, batch: int | None = None,
                       pods: list[dict] | None = None,
                       namespaces: list[dict] | None = None,
                       ) -> tuple[ReplayResult, dict]:
    """Whole-queue speculative replay without a streaming consumer — the
    direct-call surface tests and what-if tooling use.  Results land in
    the same compact chunk grid as the scan (decode via the per-pod
    accessors / decode_pod_result exactly as before).  The scan
    fallback stays OFF here: direct callers are probing speculation
    itself, and the contention tests rely on every pod going through a
    round."""
    return replay_speculative_stream(cw, mesh, batch=batch, pods=pods,
                                     namespaces=namespaces,
                                     scan_fallback=False)
