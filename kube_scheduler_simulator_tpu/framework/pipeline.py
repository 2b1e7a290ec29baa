"""The scheduling cycle as one fused tensor program.

The reference's hot path (SURVEY.md §3.2) is, per pod:

    RunPreFilterPlugins -> Filter x (plugins x nodes) [16 goroutines]
    -> RunPreScorePlugins -> Score x (plugins x nodes) -> NormalizeScore
    -> weights -> selectHost -> Reserve/Bind

Here `build_step(cw)` composes, at trace time, the enabled plugins' tensor
kernels into a single step function

    step(carry, xs_slice) -> (carry', StepOut)

with NO plugin dispatch on device: XLA sees one fused program over [N]-
shaped arrays.  `lax.scan`ning it over the pod axis replays a whole queue
in one XLA call (framework/replay.py), because scheduling is inherently
sequential across pods — each bind mutates node state — while fully
parallel across nodes and plugins.

Fidelity notes
  * Filter plugins run in upstream order; the framework stops at the first
    failing plugin per node — all masks are computed here (cheaper than
    branching on TPU) and the stop-at-first-fail truncation is
    reconstructed by the annotation decoder (store/decode.py).
  * A PreFilterResult (upstream: Filter runs on the nodes it names and
    on no other) is a per-pod mask of CONSIDERED nodes (`considered_nodes`):
    a node outside it is neither feasible nor evaluated, which is not the
    same as refused — NOT_EVALUATED in a full StepOut's codes, a word of
    its own in the packed layout (`pack_filter_codes`) — and the decoder
    writes no filter-result entry for it.
  * Scoring runs only when >1 node is feasible (upstream schedulePod
    returns early on a single feasible node); on device we always compute
    and the decoder drops the results, but selection respects it.
  * Host selection: highest weighted-normalized total; ties broken by
    LOWEST node index (upstream picks randomly among ties via reservoir
    sampling — deterministic tie-break is this framework's documented
    divergence, applied identically in the CPU reference).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..plugins import (
    affinity, imagelocality, interpod, noderesources, nodevolumelimits, ports,
    taints, topologyspread, volumebinding, volumerestrictions, volumezone,
)
from ..plugins.base import PF_ALL
from ..plugins.registry import PLUGIN_REGISTRY
from ..state.compile import CompiledWorkload

# a full StepOut's filter code, in every plugin's row, of a node outside
# the pod's PreFilterResult: no Filter plugin ran there
NOT_EVALUATED = -1


class StepOut(NamedTuple):
    filter_codes: jnp.ndarray  # [F, N] int32, 0 == pass (already
    #   skip-masked); NOT_EVALUATED in all F rows of a node outside the
    #   pod's PreFilterResult
    score_raw: jnp.ndarray     # [S, N] int32
    score_final: jnp.ndarray   # [S, N] int32 (normalized x weight)
    selected: jnp.ndarray      # int32, -1 == unschedulable
    feasible_count: jnp.ndarray  # int32
    prefilter_reject: jnp.ndarray  # int32, >0 == dynamic PreFilter reject
    #   (currently only VolumeRestrictions' cluster-wide ReadWriteOncePod
    #   conflict; the decoder maps 1 -> its message)


class CompactOut(NamedTuple):
    """Transfer-optimized step output (what framework/replay.py's scan emits).

    The annotation decoder only ever needs, per node, the FIRST failing
    filter plugin and its code (the framework stops at the first failure;
    everything before it records "passed"), so all F filter codes pack
    into one integer per node — as small as uint8 when the compile-time
    code bounds allow (PACK_MODES); PodTopologySpread's ignore mask is
    static (dom_idx + the pod's scored slots) and is recomputed on host
    rather than transferred.  finalscore is a pure
    host-recomputable function of the raw scores + feasibility
    (framework/hostnorm.py), so only raw travels — split into int8/int16
    dtype groups by compile-time per-plugin bounds
    (state/compile.py score_dtypes) with an overflow flag that triggers a
    wide (int32) rerun.  Net: ~6x less device->host payload.
    """

    packed_filter: jnp.ndarray   # [N]; 0 = all filter plugins passed
    raw8: jnp.ndarray            # [S8, N] int8 raw scores (provably |x|<=127)
    raw16: jnp.ndarray           # [S16, N] int16 raw scores
    raw32: jnp.ndarray           # [S32, N] int32 raw scores (wide rerun)
    raw_overflow: jnp.ndarray    # bool: some raw didn't fit its group dtype
    selected: jnp.ndarray        # int32, -1 == unschedulable
    feasible_count: jnp.ndarray  # int32
    prefilter_reject: jnp.ndarray  # int32


# packed-filter layouts: mode -> (dtype, code bits, ff bits).
# Layout (LSB first): [code][first_fail_idx + 1].  A word of 0 means
# "all filter plugins passed"; first_fail_idx + 1 == n_filters + 1 (with
# code 0) means "outside the pod's PreFilterResult: no plugin ran".
PACK_MODES = {
    "p8": (jnp.uint8, 5, 3),
    "p16": (jnp.uint16, 8, 8),
    "p32": (jnp.int32, 16, 15),
    "p64": (jnp.int64, 32, 16),
}


def choose_pack_mode(max_code: int, n_filters: int,
                     narrowed: bool = False) -> str:
    """narrowed: some pod of the workload has a PreFilterResult, so the
    word needs a first-fail value past the last filter's."""
    for mode in ("p8", "p16", "p32", "p64"):
        _, code_bits, ff_bits = PACK_MODES[mode]
        # the packed word stores first_fail_idx + 1, max value n_filters,
        # and n_filters + 1 for a node that was not evaluated
        if (max_code < (1 << code_bits)
                and n_filters + narrowed < (1 << ff_bits)):
            return mode
    return "p64"


def _filter_one(name: str, cw: CompiledWorkload, carry, sl) -> jnp.ndarray:
    if cw.config.is_custom(name):
        return sl[name].codes.astype(jnp.int32)
    if name == "NodeResourcesFit":
        return noderesources.fit_filter(cw.statics["core"], sl["core"], carry["core"])
    if name == "NodeAffinity":
        return affinity.filter_kernel(cw.statics["NodeAffinity"], sl["NodeAffinity"])
    if name == "TaintToleration":
        return taints.taint_filter(sl["TaintToleration"])
    if name == "NodeUnschedulable":
        return taints.unsched_filter(sl["NodeUnschedulable"])
    if name == "NodeName":
        return taints.nodename_filter(sl["NodeName"])
    if name == "NodePorts":
        return ports.filter_kernel(cw.statics["NodePorts"], sl["NodePorts"], carry["NodePorts"])
    if name == "PodTopologySpread":
        return topologyspread.filter_kernel(
            cw.statics["PodTopologySpread"], sl["PodTopologySpread"], carry["PodTopologySpread"]
        )
    if name == "InterPodAffinity":
        return interpod.filter_kernel(
            cw.statics["InterPodAffinity"], sl["InterPodAffinity"], carry["InterPodAffinity"]
        )
    if name == "VolumeRestrictions":
        return volumerestrictions.filter_kernel(
            cw.statics["VolumeRestrictions"], sl["VolumeRestrictions"],
            carry["VolumeRestrictions"],
        )
    if name == "NodeVolumeLimits":
        return nodevolumelimits.filter_kernel(
            cw.statics["NodeVolumeLimits"], sl["NodeVolumeLimits"],
            carry["NodeVolumeLimits"],
        )
    if name == "VolumeBinding":
        return volumebinding.filter_kernel(
            cw.statics["VolumeBinding"], sl["VolumeBinding"], carry["VolumeBinding"]
        )
    if name == "VolumeZone":
        return volumezone.filter_kernel(sl["VolumeZone"])
    raise ValueError(f"no filter kernel for {name}")


def _score_one(name: str, cw: CompiledWorkload, carry, sl, feasible):
    """-> (raw int64 [N], normalized int64 [N])."""
    if cw.config.is_custom(name):
        raw = sl[name].scores.astype(jnp.int64)
        # a custom NormalizeScore cannot run inside the scan; the engine
        # routes such configs to the host path (engine._needs_host_path)
        # and replay() refuses them (framework/replay.py guard)
        return raw, raw
    if name == "NodeResourcesFit":
        from ..plugins.fitscoring import parse_fit_strategy

        raw = noderesources.fit_score(
            cw.statics["core"], sl["core"], carry["core"],
            strategy=parse_fit_strategy(cw.config.args.get(name)),
            schema=getattr(cw, "schema", None))
        return raw, raw  # no ScoreExtensions
    if name == "NodeResourcesBalancedAllocation":
        from ..plugins.fitscoring import parse_balanced_resources

        raw = noderesources.balanced_score(
            cw.statics["core"], sl["core"], carry["core"],
            resources=parse_balanced_resources(cw.config.args.get(name)),
            schema=getattr(cw, "schema", None))
        return raw, raw  # no ScoreExtensions
    if name == "ImageLocality":
        raw = imagelocality.score_kernel(sl["ImageLocality"])
        return raw, raw  # no ScoreExtensions
    if name == "VolumeBinding":
        raw = volumebinding.score_kernel(cw.n_nodes)
        return raw, raw  # scorer nil with VolumeCapacityPriority off
    if name == "NodeAffinity":
        raw = affinity.score_kernel(cw.statics["NodeAffinity"], sl["NodeAffinity"])
        return raw, affinity.normalize(raw, feasible)
    if name == "TaintToleration":
        raw = taints.taint_score(sl["TaintToleration"])
        return raw, taints.taint_normalize(raw, feasible)
    if name == "PodTopologySpread":
        raw, ignored = topologyspread.score_kernel(
            cw.statics["PodTopologySpread"], sl["PodTopologySpread"],
            carry["PodTopologySpread"], feasible)
        return raw, topologyspread.normalize(raw, ignored, feasible)
    if name == "InterPodAffinity":
        raw = interpod.score_kernel(
            cw.statics["InterPodAffinity"], sl["InterPodAffinity"], carry["InterPodAffinity"]
        )
        return raw, interpod.normalize(raw, feasible)
    raise ValueError(f"no score kernel for {name}")


def renormalize(name: str, cw, carry, sl, raw, feasible):
    """Host-side NormalizeScore recompute for one plugin: [N] raw scores
    (possibly hook-modified) + feasibility -> [N] normalized.  Used by the
    host-interleaved path when AfterScore hooks or hook-changed
    feasibility invalidate the device's fused normalization, and for
    custom plugins' NormalizeScore (arbitrary Python cannot run inside the
    device scan; upstream wraps out-of-tree ScoreExtensions the same as
    in-tree, wrappedplugin.go:388-415)."""
    import numpy as np

    if cw.config.is_custom(name):
        plugin = cw.config.custom[name]
        if getattr(plugin, "has_normalize", False):
            raw_np = np.asarray(raw)
            feas = np.asarray(feasible)
            idx = np.flatnonzero(feas)
            vals = plugin.normalize([int(raw_np[j]) for j in idx])
            out = np.zeros_like(raw_np)
            out[idx] = np.asarray(list(vals), dtype=out.dtype)
            return jnp.asarray(out)
        return raw
    if name == "NodeAffinity":
        return affinity.normalize(raw, feasible)
    if name == "TaintToleration":
        return taints.taint_normalize(raw, feasible)
    if name == "InterPodAffinity":
        return interpod.normalize(raw, feasible)
    if name == "PodTopologySpread":
        _, ignored = topologyspread.score_kernel(
            cw.statics["PodTopologySpread"], sl["PodTopologySpread"],
            carry["PodTopologySpread"], feasible)
        return topologyspread.normalize(raw, ignored, feasible)
    return raw  # no ScoreExtensions


def considered_nodes(cw, sl) -> jnp.ndarray | None:
    """The pod's merged PreFilterResult as an [N] bool mask of the nodes
    Filter runs on, or None when no PreFilter plugin of this pass narrows
    any pod (a trace-time fact: the `pf_nodes` leaves then have no
    columns, plugins/base.py prefilter_rows).  Upstream
    PreFilterResult.Merge: the intersection of the plugins' node sets, a
    plugin without a result standing for all nodes."""
    n = cw.n_nodes
    mask = None
    for name in cw.config.prefilters():
        rows = getattr(sl.get(name), "pf_nodes", None)
        if rows is None or rows.shape[-1] == 0:
            continue
        named = (jnp.arange(n, dtype=rows.dtype)[None, :]
                 == rows[:, None]).any(axis=0)
        own = (rows[0] == PF_ALL) | named
        mask = own if mask is None else mask & own
    return mask


def _eval_phase(cw: CompiledWorkload, carry, sl, weights, filter_names, score_names):
    """filter -> score -> normalize -> weight. Returns
    (filter_codes [F,N], score_raw [S,N], score_final [S,N], feasible [N],
    total [N] with infeasible forced to -1, considered [N] or None).  A
    node the PreFilterResult leaves out is not feasible and carries
    NOT_EVALUATED in every plugin's row."""
    n = cw.n_nodes
    codes = []
    feasible = jnp.ones(n, dtype=bool)
    for name in filter_names:
        # broadcast: compact builders emit [1]-shaped always-pass codes
        code = jnp.broadcast_to(_filter_one(name, cw, carry, sl), (n,))
        x = sl.get(name)
        if x is not None and hasattr(x, "filter_skip"):
            code = jnp.where(x.filter_skip, 0, code)
        codes.append(code)
        feasible = feasible & (code == 0)
    filter_codes = jnp.stack(codes) if codes else jnp.zeros((0, n), dtype=jnp.int32)
    considered = considered_nodes(cw, sl)
    if considered is not None:
        feasible = feasible & considered
        filter_codes = jnp.where(considered[None, :], filter_codes,
                                 NOT_EVALUATED)

    raws, finals = [], []
    total = jnp.zeros(n, dtype=jnp.int64)
    for i, name in enumerate(score_names):
        raw, normed = _score_one(name, cw, carry, sl, feasible)
        final = normed * weights[i]
        x = sl.get(name)
        if x is not None and hasattr(x, "score_skip"):
            skip = x.score_skip
            raw = jnp.where(skip, 0, raw)
            final = jnp.where(skip, 0, final)
        raws.append(raw)
        finals.append(final)
        total = total + final
    score_raw = jnp.stack(raws) if raws else jnp.zeros((0, n), dtype=jnp.int64)
    score_final = jnp.stack(finals) if finals else jnp.zeros((0, n), dtype=jnp.int64)
    total = jnp.where(feasible, total, jnp.int64(-1))
    return filter_codes, score_raw, score_final, feasible, total, considered


def _bind_phase(cw: CompiledWorkload, carry, sl, selected):
    """Apply a bind of this pod to node `selected` (-1: no-op)."""
    new_carry = dict(carry)
    new_carry["core"] = noderesources.core_bind_update(carry["core"], sl["core"], selected)
    if "NodePorts" in carry:
        new_carry["NodePorts"] = ports.bind_update(
            cw.statics["NodePorts"], sl["NodePorts"], carry["NodePorts"], selected
        )
    if "PodTopologySpread" in carry:
        new_carry["PodTopologySpread"] = topologyspread.bind_update(
            cw.statics["PodTopologySpread"], sl["PodTopologySpread"],
            carry["PodTopologySpread"], selected,
        )
    if "InterPodAffinity" in carry:
        new_carry["InterPodAffinity"] = interpod.bind_update(
            cw.statics["InterPodAffinity"], sl["InterPodAffinity"],
            carry["InterPodAffinity"], selected,
        )
    if "VolumeRestrictions" in carry:
        new_carry["VolumeRestrictions"] = volumerestrictions.bind_update(
            sl["VolumeRestrictions"], carry["VolumeRestrictions"], selected
        )
    if "NodeVolumeLimits" in carry:
        new_carry["NodeVolumeLimits"] = nodevolumelimits.bind_update(
            sl["NodeVolumeLimits"], carry["NodeVolumeLimits"], selected
        )
    if "VolumeBinding" in carry:
        new_carry["VolumeBinding"] = volumebinding.bind_update(
            cw.statics["VolumeBinding"], sl["VolumeBinding"],
            carry["VolumeBinding"], selected,
        )
    return new_carry


def _prefilter_reject(cw, carry, sl) -> jnp.ndarray:
    """Dynamic (replay-state-dependent) PreFilter rejects + the static
    compile-time ones (xs['force_unsched']).  >0 forces selected = -1."""
    code = jnp.int32(0)
    if "VolumeRestrictions" in carry:
        # bit 0: ReadWriteOncePod conflict (dynamic)
        code = volumerestrictions.prefilter_reject(
            sl["VolumeRestrictions"], carry["VolumeRestrictions"]
        )
    force = sl.get("force_unsched")
    if force is not None:
        # bit 1: compile-time reject; both bits can be set — the decoder
        # resolves plugin attribution in prefilter order
        code = code | jnp.where(force, jnp.int32(2), 0)
    return code


def pack_filter_codes(filter_codes: jnp.ndarray, n: int, mode: str,
                      considered: jnp.ndarray | None = None) -> jnp.ndarray:
    """[F, N] codes -> [N] packed first-fail word (see PACK_MODES): 0 =
    all pass, else (first_fail_idx + 1) << code_bits | code; outside
    `considered` (a PreFilterResult's mask), (F + 1) << code_bits."""
    dtype, code_bits, _ = PACK_MODES[mode]
    acc_dtype = jnp.int64 if mode == "p64" else jnp.int32
    if filter_codes.shape[0] == 0:
        packed = jnp.zeros(n, dtype=acc_dtype)
    else:
        fail = filter_codes != 0
        any_fail = fail.any(axis=0)
        ff = jnp.argmax(fail, axis=0)  # first True == lowest plugin index
        code_at = jnp.take_along_axis(filter_codes, ff[None, :], axis=0)[0]
        packed = jnp.where(
            any_fail,
            ((ff.astype(acc_dtype) + 1) << code_bits) | code_at.astype(acc_dtype),
            0,
        )
    if considered is not None:
        packed = jnp.where(
            considered, packed,
            jnp.asarray(filter_codes.shape[0] + 1, acc_dtype) << code_bits)
    return packed.astype(dtype)


def build_step(cw, out_mode: str = "full", pack_mode: str = "p16",
               score_dtypes: tuple = (), wide_raw: str | None = None):
    """Returns step(carry_dict, xs_slice_dict) -> (carry', out).

    cw: CompiledWorkload or any object with .config/.statics/.n_nodes
    (replay passes a slim view so cached jits don't pin per-pod data).
    out_mode "full" -> StepOut; "compact" -> CompactOut (first-fail-packed
    filters, narrow raw scores, no finalscore — see CompactOut).
    score_dtypes: per-scorer "i8"/"i16"/"i32"/"host" group assignment
    (compact mode; "host" = the raw is a precompiled host-resident row and
    is omitted from the device outputs entirely);
    wide_raw "i32"/"i64" pools every transferred scorer into the raw32
    field at that width after an overflow (the replay's widening ladder)."""
    cfg = cw.config
    filter_names = cfg.filters()
    score_names = cfg.scorers()
    weights = jnp.asarray([cfg.weight(n) for n in score_names], dtype=jnp.int64)

    def step(carry: dict[str, Any], sl: dict[str, Any]):
        (filter_codes, score_raw, score_final, feasible, total,
         considered) = _eval_phase(
            cw, carry, sl, weights, filter_names, score_names
        )
        reject = _prefilter_reject(cw, carry, sl)
        feasible_count = jnp.sum(feasible, dtype=jnp.int32)
        feasible_count = jnp.where(reject > 0, 0, feasible_count)
        selected = jnp.argmax(total).astype(jnp.int32)  # first max == lowest index
        selected = jnp.where(feasible_count > 0, selected, jnp.int32(-1))
        is_pad = sl.get("is_pad")
        if is_pad is not None:
            selected = jnp.where(is_pad, jnp.int32(-1), selected)

        new_carry = _bind_phase(cw, carry, sl, selected)
        if out_mode == "compact":
            groups: dict[str, list] = {"i8": [], "i16": [], "i32": []}
            for s in range(len(score_names)):
                g = score_dtypes[s]
                if g == "host":
                    continue  # precompiled host row: never travels D2H
                g = "i32" if wide_raw else g
                groups[g].append(score_raw[s])
            n = cw.n_nodes

            def stack(rows, dtype):
                if not rows:
                    return jnp.zeros((0, n), dtype=dtype)
                return jnp.stack(rows).astype(dtype)

            raw8 = stack(groups["i8"], jnp.int8)
            raw16 = stack(groups["i16"], jnp.int16)
            raw32 = stack(groups["i32"],
                          jnp.int64 if wide_raw == "i64" else jnp.int32)
            ovf = jnp.asarray(False)
            if wide_raw is None and groups["i16"]:
                # i8 members are provably in range (compile-time bounds);
                # only the i16 group needs the runtime check
                full = jnp.stack(groups["i16"])
                ovf = jnp.any(full != raw16.astype(full.dtype))
            elif wide_raw == "i32" and groups["i32"]:
                # custom scorers can exceed int32 (upstream scores are
                # int64): keep checking so the ladder can reach i64
                full = jnp.stack(groups["i32"])
                ovf = jnp.any(full != raw32.astype(full.dtype))
            out: Any = CompactOut(
                packed_filter=pack_filter_codes(filter_codes, n, pack_mode,
                                                considered),
                raw8=raw8,
                raw16=raw16,
                raw32=raw32,
                raw_overflow=ovf,
                selected=selected,
                feasible_count=feasible_count,
                prefilter_reject=reject,
            )
        else:
            out = StepOut(
                filter_codes=filter_codes.astype(jnp.int32),
                score_raw=score_raw.astype(jnp.int32),
                score_final=score_final.astype(jnp.int32),
                selected=selected,
                feasible_count=feasible_count,
                prefilter_reject=reject,
            )
        return new_carry, out

    def scan_step(carry: dict[str, Any], sl: dict[str, Any]):
        # a stable device-side name for the stage (docs/metrics.md)
        with jax.named_scope("kss_scan_step"):
            return step(carry, sl)

    return scan_step


def build_phased(cw: CompiledWorkload):
    """(eval_fn, bind_fn) for host-interleaved phases — the extender path:
    the host can veto/boost nodes between the device's score phase and the
    bind (reference extender round-trip, SURVEY.md §3.3).

      eval_fn(carry, xs_slice) -> StepOut (selected = the device's own
                                  choice, advisory; carry NOT updated)
      bind_fn(carry, xs_slice, selected int32) -> carry'
    """
    import jax

    cfg = cw.config
    filter_names = cfg.filters()
    score_names = cfg.scorers()
    weights = jnp.asarray([cfg.weight(n) for n in score_names], dtype=jnp.int64)

    def eval_fn(carry, sl):
        filter_codes, score_raw, score_final, feasible, total, _ = _eval_phase(
            cw, carry, sl, weights, filter_names, score_names
        )
        reject = _prefilter_reject(cw, carry, sl)
        feasible_count = jnp.sum(feasible, dtype=jnp.int32)
        feasible_count = jnp.where(reject > 0, 0, feasible_count)
        selected = jnp.argmax(total).astype(jnp.int32)
        selected = jnp.where(feasible_count > 0, selected, jnp.int32(-1))
        return StepOut(
            filter_codes=filter_codes.astype(jnp.int32),
            score_raw=score_raw.astype(jnp.int32),
            score_final=score_final.astype(jnp.int32),
            selected=selected,
            feasible_count=feasible_count,
            prefilter_reject=reject,
        )

    def bind_fn(carry, sl, selected):
        return _bind_phase(cw, carry, sl, jnp.asarray(selected, dtype=jnp.int32))

    return jax.jit(eval_fn), jax.jit(bind_fn)
