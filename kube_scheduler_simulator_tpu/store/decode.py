"""Device arrays -> per-pod result annotations.

Reconstructs exactly what the reference's result store would serialize for
each pod (reference: simulator/scheduler/plugin/resultstore/store.go:133-198
GetStoredResult -> 13 JSON blobs), from the ReplayResult tensors:

  * stop-at-first-fail truncation of the filter map (the framework stops
    running Filter plugins for a node at the first failure);
  * scoring recorded only when >1 node was feasible (upstream schedulePod
    early-returns on a single feasible node, skipping PreScore/Score);
  * score map covers only feasible nodes (only they are scored);
  * PreFilter/PreScore Skip recorded as "" (Skip status has an empty
    message; wrappedplugin.go:507-516 records status.Message());
  * finalscore = normalized score x plugin weight
    (resultstore/store.go:488-507).
"""

from __future__ import annotations

import os

import numpy as np

from . import annotations as ann
from ..utils.faults import fault_point
from ..utils.platform import effective_cpu_count
from ..utils.tracing import TRACER
from ..framework.replay import ReplayResult
from ..plugins import (
    affinity, interpod, noderesources, nodevolumelimits, ports, taints,
    topologyspread, volumebinding, volumerestrictions, volumezone,
)
from ..plugins.registry import PLUGIN_REGISTRY


def _native_ctx(cw):
    """The native-codec context this workload decodes through: the one
    its node table's memo keeps for its profile
    (native_decode.shared_context), looked up once a cw; None disables
    the fast path (or set KSS_TPU_DISABLE_NATIVE=1 to force the Python
    encoder)."""
    if os.environ.get("KSS_TPU_DISABLE_NATIVE") == "1":
        return None
    if "_native_ctx" not in cw.host:
        from . import native_decode

        cw.host["_native_ctx"] = native_decode.shared_context(cw)
    return cw.host["_native_ctx"]

_DECODERS = {
    "NodeResourcesFit": lambda code, node, aux: noderesources.decode_fit_filter(code, aux["schema"]),
    "NodeAffinity": affinity.decode_filter,
    "TaintToleration": taints.decode_taint_filter,
    "NodeUnschedulable": lambda code, node, aux: taints.ERR_UNSCHEDULABLE,
    "NodeName": lambda code, node, aux: taints.ERR_NODE_NAME,
    "NodePorts": lambda code, node, aux: ports.ERR_NODE_PORTS,
    "PodTopologySpread": topologyspread.decode_filter,
    "InterPodAffinity": interpod.decode_filter,
    "VolumeRestrictions": lambda code, node, aux: volumerestrictions.ERR_DISK_CONFLICT,
    "NodeVolumeLimits": lambda code, node, aux: nodevolumelimits.ERR_MAX_VOLUME_COUNT,
    "VolumeBinding": lambda code, node, aux: volumebinding.decode_filter(code, node, aux),
    "VolumeZone": lambda code, node, aux: volumezone.ERR_VOLUME_ZONE_CONFLICT,
}


def prefilter_reject_message(cw, i: int, dynamic_code: int) -> tuple[str, str] | None:
    """(plugin name, message) of the PreFilter reject that aborted pod i's
    cycle, or None.  Resolution follows upstream RunPreFilterPlugins: the
    first rejecting plugin in config order wins; within VolumeRestrictions
    the static (PVC-lister) reject precedes the dynamic ReadWriteOncePod
    conflict."""
    static = cw.host.get("prefilter_reject", {})
    if not static and not dynamic_code:
        return None
    for name in cw.config.prefilters():
        msgs = static.get(name)
        if msgs is not None and msgs[i] is not None:
            return name, msgs[i]
        if name == "VolumeRestrictions" and (dynamic_code & 1):
            return name, volumerestrictions.ERR_RWOP_CONFLICT
    return None


def decode_filter_message(name: str, code: int, node_idx: int, host_aux) -> str:
    dec = _DECODERS.get(name)
    if dec is None:  # custom plugin: interned message table
        return host_aux["custom_msgs"][name][code - 1]
    return dec(code, node_idx, host_aux)


def decode_pod_result(rr: ReplayResult, i: int, feasible_override=None,
                      host_index: int | None = None) -> dict[str, str]:
    """The 13 plugin annotations for pod i, values JSON-encoded as Go would.

    feasible_override: [N] bool — the extender path narrows feasibility
    after the plugin filters (upstream scores only nodes that survive the
    extender Filter round-trip too); overrides the feasibility derived
    from the plugin filter codes for the score maps.
    host_index: index into the CompiledWorkload's per-pod host tables
    (skip flags, static prefilter rejects) when it differs from `i` — the
    extender path builds single-row ReplayResults (i=0) against the full
    workload's cw."""
    cw = rr.cw
    hi = i if host_index is None else host_index
    cfg = cw.config
    names = cw.node_table.names
    filter_names = cfg.filters()
    score_names = cfg.scorers()
    fskip = cw.host["filter_skip"]
    sskip = cw.host["score_skip"]

    # --- prefilter reject: the cycle aborted before Filter --------------
    reject = prefilter_reject_message(cw, hi, int(rr.prefilter_reject[i]))
    if reject is not None:
        rej_name, rej_msg = reject
        pf: dict[str, str] = {}
        for name in cfg.prefilters():
            if name == rej_name:
                pf[name] = rej_msg
                break
            pf[name] = "" if fskip[name][hi] else ann.SUCCESS_MESSAGE
        empty = _marshal_small({})
        # a PreFilterResult returned before the rejecting plugin ran is
        # on record (the shim stores it as the plugin returns)
        narrowed = {
            name: sorted(names[hi])
            for name, names in cw.host.get("prefilter_result", {}).items()
            if name in pf and name != rej_name and names[hi] is not None}
        return {
            ann.PRE_FILTER_STATUS_RESULT: _marshal_small(pf),
            ann.PRE_FILTER_RESULT: ann.marshal(narrowed) if narrowed else empty,
            ann.FILTER_RESULT: empty,
            ann.POST_FILTER_RESULT: empty,
            ann.PRE_SCORE_RESULT: empty,
            ann.SCORE_RESULT: empty,
            ann.FINAL_SCORE_RESULT: empty,
            ann.RESERVE_RESULT: empty,
            ann.PERMIT_STATUS_RESULT: empty,
            ann.PERMIT_TIMEOUT_RESULT: empty,
            ann.PRE_BIND_RESULT: empty,
            ann.BIND_RESULT: empty,
            ann.SELECTED_NODE: "",
        }

    # --- prefilter ------------------------------------------------------
    prefilter_status = {}
    for name in cfg.prefilters():
        prefilter_status[name] = "" if fskip[name][hi] else ann.SUCCESS_MESSAGE

    native_ctx = _native_ctx(cw)

    # --- fused native path (compact replay layout only) -----------------
    if (native_ctx is not None and getattr(rr, "_compact", None) is not None
            and feasible_override is None):
        from . import native_decode

        feasible_count = int(rr.feasible_count[i])
        filter_json, score_json, final_json, failed = native_decode.decode_pod_fused(
            native_ctx, rr, i, hi, feasible_count > 1)
        TRACER.count("decode_filter_failed_entries_total", failed)
        prescore = {}
        if feasible_count > 1:
            for name in cfg.prescorers():
                prescore[name] = "" if sskip[name][hi] else ann.SUCCESS_MESSAGE
        return _assemble(cw, cfg, names, rr, i, prefilter_status, prescore,
                         filter_json, score_json, final_json, host_index)

    # --- filter (stop at first fail per node) ---------------------------
    active = [
        (f, name) for f, name in enumerate(filter_names) if not fskip[name][hi]
    ]
    codes = rr.codes_of(i)  # [F, N]
    # the refusals the filter blob below renders: nodes some active plugin
    # failed (these rungs hold the codes on the host; the fused native
    # rungs count in C, in the walk that emits the entries).  A negative
    # code (pipeline.NOT_EVALUATED) is no refusal: the node lies outside
    # the pod's PreFilterResult and gets no entry
    refused = (codes[[f for f, _ in active]] > 0).any(axis=0)
    TRACER.count("decode_filter_failed_entries_total", int(refused.sum()))

    filter_json: str | None = None
    if native_ctx is not None:
        from . import native_decode

        active_mask = np.asarray([not fskip[name][hi] for name in filter_names], np.uint8)
        filter_json = native_decode.encode_filter(native_ctx, codes, active_mask)
    else:
        filter_map: dict[str, dict[str, str]] = {}
        for n, node in enumerate(names):
            entry = {}
            for f, name in active:
                c = int(codes[f, n])
                if c < 0:
                    break
                if c == 0:
                    entry[name] = ann.PASSED_FILTER_MESSAGE
                else:
                    entry[name] = decode_filter_message(name, c, n, cw.host)
                    break
            if entry:
                filter_map[node] = entry

    # --- score (only when >1 feasible node) -----------------------------
    feasible_count = int(rr.feasible_count[i])
    prescore: dict[str, str] = {}
    score_map: dict[str, dict[str, str]] = {}
    final_map: dict[str, dict[str, str]] = {}
    score_json: str | None = None
    final_json: str | None = None
    if feasible_count > 1:
        for name in cfg.prescorers():
            prescore[name] = "" if sskip[name][hi] else ann.SUCCESS_MESSAGE
        feasible = rr.feasible_of(i)
        if feasible is None:
            feasible = (codes[[f for f, _ in active], :] == 0).all(axis=0) if active else None
        if feasible_override is not None:
            feasible = feasible_override
        raw = rr.raw_of(i)
        fin = rr.final_of(i)
        if native_ctx is not None:
            from . import native_decode

            sskip_mask = np.asarray([bool(sskip[name][hi]) for name in score_names], np.uint8)
            feas = (
                np.ones(len(names), np.uint8) if feasible is None
                else np.asarray(feasible, np.uint8)
            )
            score_json = native_decode.encode_scores(native_ctx, raw, sskip_mask, feas)
            final_json = native_decode.encode_scores(native_ctx, fin, sskip_mask, feas)
        else:
            for n, node in enumerate(names):
                if feasible is not None and not feasible[n]:
                    continue
                se, fe = {}, {}
                for s, name in enumerate(score_names):
                    if sskip[name][hi]:
                        continue
                    se[name] = str(int(raw[s, n]))
                    fe[name] = str(int(fin[s, n]))
                if se:
                    score_map[node] = se
                    final_map[node] = fe

    return _assemble(
        cw, cfg, names, rr, i, prefilter_status, prescore,
        filter_json if filter_json is not None else ann.marshal(filter_map),
        score_json if score_json is not None else ann.marshal(score_map),
        final_json if final_json is not None else ann.marshal(final_map),
        host_index)


_MARSHAL_CACHE: dict = {}


def _marshal_small(d: dict) -> str:
    """marshal() memoized for the tiny per-pod status maps — they repeat
    across pods (a handful of distinct skip patterns per workload), and
    the per-pod json.dumps churn was ~15% of an engine wave."""
    key = tuple(sorted(d.items()))
    s = _MARSHAL_CACHE.get(key)
    if s is None:
        if len(_MARSHAL_CACHE) > 4096:
            _MARSHAL_CACHE.clear()
        s = _MARSHAL_CACHE.setdefault(key, ann.marshal(d))
    return s


def _assemble(cw, cfg, names, rr, i: int, prefilter_status: dict,
              prescore: dict, filter_json: str, score_json: str | None,
              final_json: str | None,
              host_index: int | None = None) -> dict[str, str]:
    """Bind-phase maps + the 13-key annotation dict (both decode paths)."""
    sel = int(rr.selected[i])
    scheduled = sel >= 0
    bind = {"DefaultBinder": ann.SUCCESS_MESSAGE} if scheduled else {}
    # VolumeBinding is the only default plugin implementing Reserve and
    # PreBind (assume/bind the chosen PVs); the reference shim records
    # "success" for each on the happy path
    # (reference: simulator/scheduler/plugin/wrappedplugin.go:622-651, :653-700)
    reserve: dict[str, str] = {}
    prebind: dict[str, str] = {}
    if scheduled and "VolumeBinding" in cfg.enabled and not cfg.is_custom("VolumeBinding"):
        reserve["VolumeBinding"] = ann.SUCCESS_MESSAGE
        prebind["VolumeBinding"] = ann.SUCCESS_MESSAGE

    empty = _marshal_small({})
    # the PreFilterResults' node names, rendered at compile time
    # (state/compile.py _collect_prefilter_results); i is the pod's row of
    # rr, and of the host tables except on the extender path's one-row
    # results
    narrowed = cw.host.get("prefilter_json")
    if narrowed is not None:
        narrowed = narrowed[i if host_index is None else host_index]
    return {
        ann.PRE_FILTER_STATUS_RESULT: _marshal_small(prefilter_status),
        ann.PRE_FILTER_RESULT: narrowed or empty,
        ann.FILTER_RESULT: filter_json,
        ann.POST_FILTER_RESULT: empty,
        ann.PRE_SCORE_RESULT: _marshal_small(prescore),
        ann.SCORE_RESULT: score_json if score_json is not None else empty,
        ann.FINAL_SCORE_RESULT: final_json if final_json is not None else empty,
        ann.RESERVE_RESULT: _marshal_small(reserve),
        ann.PERMIT_STATUS_RESULT: empty,
        ann.PERMIT_TIMEOUT_RESULT: empty,
        ann.PRE_BIND_RESULT: _marshal_small(prebind),
        ann.BIND_RESULT: _marshal_small(bind),
        ann.SELECTED_NODE: names[sel] if scheduled else "",
    }


def decode_all(rr: ReplayResult) -> list[dict[str, str]]:
    return [decode_pod_result(rr, i) for i in range(rr.cw.n_pods)]


_DECODE_POOL = None


def _decode_pool():
    global _DECODE_POOL
    if _DECODE_POOL is None:
        from concurrent.futures import ThreadPoolExecutor

        _DECODE_POOL = ThreadPoolExecutor(
            max_workers=8, thread_name_prefix="decode")
    return _DECODE_POOL


def _chunk_skip_mask(rr, lo: int, hi: int):
    """[hi-lo] uint8 marking prefilter-rejected pods (the Python
    early-out owns them — their cycle aborted before Filter, so there
    are no blobs to decode), or None when the range has none.

    Mirrors prefilter_reject_message's not-None condition exactly: a
    static (PVC-lister) reject for the pod, or the dynamic
    ReadWriteOncePod conflict bit with VolumeRestrictions enabled.  The
    static part is a pure function of the workload, so it's vectorized
    once per cw — no per-pod Python on the chunk-decode hot path."""
    cw = rr.cw
    static = cw.host.get("prefilter_reject", {})
    dyn = np.asarray(rr.prefilter_reject[lo:hi])
    if not static and not dyn.any():
        return None
    mask = cw.host.get("_static_reject_any")
    if mask is None:
        mask = np.zeros(cw.n_pods, bool)
        for msgs in static.values():
            mask |= np.asarray([m is not None for m in msgs], bool)
        cw.host["_static_reject_any"] = mask
    skip = mask[lo:hi].copy()
    if "VolumeRestrictions" in cw.config.prefilters():
        skip |= (dyn & 1).astype(bool)
    if not skip.any():
        return None
    return np.ascontiguousarray(skip, np.uint8)


def _assemble_chunk(rr, lo: int, hi: int, triples, out: list,
                    base: int) -> None:
    """Per-pod tail of the chunk decode: blob strs -> the 13-key dicts."""
    cw = rr.cw
    cfg = cw.config
    names = cw.node_table.names
    fskip = cw.host["filter_skip"]
    sskip = cw.host["score_skip"]
    prefilters = cfg.prefilters()
    prescorers = cfg.prescorers()
    feasible_count = rr.feasible_count
    for i in range(lo, hi):
        t = triples[i - lo]
        if t is None:  # prefilter reject: the early-out path owns it
            out[i - base] = decode_pod_result(rr, i)
            continue
        filter_json, score_json, final_json = t
        prefilter_status = {
            name: "" if fskip[name][i] else ann.SUCCESS_MESSAGE
            for name in prefilters
        }
        prescore = {}
        if int(feasible_count[i]) > 1:
            for name in prescorers:
                prescore[name] = "" if sskip[name][i] else ann.SUCCESS_MESSAGE
        out[i - base] = _assemble(cw, cfg, names, rr, i, prefilter_status,
                                  prescore, filter_json, score_json,
                                  final_json)


def _decode_chunk_native(rr, lo: int, hi: int, out: list, base: int) -> bool:
    """Pods lo..hi (a range within ONE compact chunk) through the
    chunk-granular native call: one GIL-released ctx_decode_chunk runs
    the C worker pool over the whole range and hands back arena blob
    addresses; Python keeps only the prefilter-reject early-out and the
    13-key _assemble.  False -> caller falls back (no native ctx)."""
    ctx = _native_ctx(rr.cw)
    if ctx is None:
        return False
    from . import native_decode

    with TRACER.span("decode_chunk", lo=lo, hi=hi, path="native_chunk"):
        handle = native_decode.decode_chunk_start(
            ctx, rr, lo, hi, skip=_chunk_skip_mask(rr, lo, hi))
        triples = native_decode.decode_chunk_take(handle)
        _count_native_chunk(handle, hi - lo)
        _assemble_chunk(rr, lo, hi, triples, out, base)
    return True


def _count_native_chunk(handle, pods: int) -> None:
    """The counters of one ctx_decode_chunk call, from what it reported."""
    TRACER.count("decode_chunk_calls_total")
    TRACER.count("decode_native_thread_seconds",
                 round(handle.thread_seconds, 6))
    TRACER.count("decode_filter_failed_entries_total", handle.failed_entries)
    TRACER.inc("decode_path_total", pods, path="native_chunk")


def decode_chunk_into(rr, lo: int, hi: int, out: list, base: int = 0) -> None:
    """Decode pods lo..hi of one replay chunk into out[lo-base:hi-base] —
    the replay(on_chunk=...) streaming consumer: runs on the dispatch
    thread while the device executes later chunks.  Idempotent per index
    (a width-tier rerun re-delivers chunks).  base: offset for callers
    passing a chunk-local sink (out[i-base]) instead of a queue-length
    list.

    Decoder ladder (docs/wave-pipeline.md): chunk-granular native call
    (one GIL-released C call per compact chunk, C-side worker pool) ->
    per-pod fused native decode on the Python thread pool -> pure-Python
    encoder (KSS_TPU_DISABLE_NATIVE=1, or no toolchain).

    A failed decode re-raises to its caller but is VISIBLE now
    (decode_failures_total{path=...}) and never poisons the chunk: the
    lazy read path clears for retry (store/lazy.py), so a transient
    fault heals on the next read — tests/test_faults.py pins this."""
    try:
        _decode_chunk_into(rr, lo, hi, out, base)
    except Exception:
        TRACER.inc("decode_failures_total", path=_decode_path_label(rr))
        raise


def _decode_path_label(rr) -> str:
    """Best-effort decode-path label for the failure tap (the ladder
    the failed call would have taken)."""
    try:
        if _native_ctx(rr.cw) is None:
            return "python"
        return ("native_chunk" if getattr(rr, "_compact", None) is not None
                else "native_pod")
    except Exception:
        return "unknown"


def _decode_chunk_into(rr, lo: int, hi: int, out: list, base: int) -> None:
    fault_point("decode.chunk")
    cc = getattr(rr, "_compact", None)
    if cc is not None:
        # chunk-granular native decode; ranges spanning several compact
        # chunks (full-queue callers) split on chunk boundaries
        s0, routed = lo, True
        while s0 < hi:
            s1 = min(hi, (s0 // cc.chunk + 1) * cc.chunk)
            if not _decode_chunk_native(rr, s0, s1, out, base):
                routed = False
                break
            s0 = s1
        if routed:
            return
        lo = s0  # keep anything the native path already decoded
    fallback_path = ("native_pod" if _native_ctx(rr.cw) is not None
                     else "python")
    if hi - lo < 16 or effective_cpu_count() < 2:
        # single-core hosts: the pool's dispatch + recon-lock traffic
        # costs more than the GIL-released C calls can win back
        TRACER.inc("decode_path_total", hi - lo, path=fallback_path)
        for i in range(lo, hi):
            out[i - base] = decode_pod_result(rr, i)
        return
    with TRACER.span("decode_chunk", lo=lo, hi=hi, path=fallback_path):
        TRACER.inc("decode_path_total", hi - lo, path=fallback_path)
        if cc is not None and _native_ctx(rr.cw) is None:
            # pure-Python path reads codes_of/raw_of/final_of: reconstruct
            # the chunk once here so pool workers share it.  The fused
            # native path reads the compact arrays directly — warming recon
            # for it would re-create exactly the [C,F,N]/[C,S,N]
            # materialization it avoids.  (full-array results — the
            # host loop's — need no recon)
            rr._chunk_recon(lo // cc.chunk, scores=True)
        for i, a in zip(range(lo, hi),
                        _decode_pool().map(lambda i: decode_pod_result(rr, i),
                                           range(lo, hi))):
            out[i - base] = a


def decode_all_parallel(rr: ReplayResult,
                        n: int | None = None) -> list[dict[str, str]]:
    """Decode pods 0..n across a thread pool, chunk by chunk.

    The native codec runs outside the GIL — one ctx_decode_chunk call per
    compact chunk drives the C-side worker pool (decode_chunk_into's
    ladder), so the JSON encoding — the dominant cost at cluster scale —
    parallelizes without per-pod Python dispatch.  Falls back to the
    serial loop when the ReplayResult holds full arrays (host path)."""
    if n is None:
        n = rr.cw.n_pods
    cc = getattr(rr, "_compact", None)
    if cc is None:
        return [decode_pod_result(rr, i) for i in range(n)]
    out: list = [None] * n
    for lo in range(0, n, cc.chunk):
        decode_chunk_into(rr, lo, min(lo + cc.chunk, n), out)
    return out
