"""Wave flight recorder: tracing and metrics for the scheduling engine.

Additive over the reference (SURVEY.md §5: the reference has no tracing
beyond the per-Pod annotation record; the upstream scheduler only
blank-imports Prometheus registration, cmd/scheduler/scheduler.go:9-11).
Here the TPU path gets real observability:

- HIERARCHICAL spans (span/parent ids, thread ids, labels) in a bounded
  ring buffer with per-name aggregates — the span tree covers
  compile_workload -> replay_and_decode_stream -> decode_chunk /
  commit_stream -> gang_quorum -> commit_and_reflect, including spans
  recorded on the pipelined-commit worker thread (parented explicitly
  across threads);
- fixed-bucket HISTOGRAMS and LABELED counters under the upstream
  kube-scheduler metric names (scheduling_attempt_duration_seconds,
  framework_extension_point_duration_seconds,
  plugin_execution_duration_seconds — bucket layouts match upstream
  pkg/scheduler/metrics/metrics.go);
- plain counters (pods scheduled/unschedulable, preemptions, waves) —
  the pre-flight-recorder API, unchanged;
- valid Prometheus text exposition (# HELP/# TYPE, metric-name
  sanitization, label escaping; validate_exposition() is the strict
  checker the tests run against every scrape), served at /metrics;
- Perfetto / chrome://tracing JSON export of the span tree
  (GET /api/v1/trace), showing the PR-2 pipeline overlap in one
  browser load (docs/metrics.md has the walkthrough);
- optional XLA profile capture via jax.profiler (trace start/stop to a
  directory TensorBoard/xprof can read).
"""

from __future__ import annotations

import itertools
import os
import re
import threading
import time
from collections import deque
from contextlib import contextmanager

from .env import env_int

_PREFIX = "kss_tpu"

# open-span bookkeeping rides the wave black box's enable flag
# (utils/blackbox.py owns the user-facing toggle and mirrors it here —
# tracing cannot import blackbox without a cycle): with the black box
# off, span entry pays no extra lock and the post-mortem surface
# reports no open spans, keeping the KSS_TPU_BLACKBOX=0 A/B honest
BLACKBOX_OPEN_SPANS = os.environ.get("KSS_TPU_BLACKBOX", "1") != "0"

# a span that stood open this long is a STALL: half of the autopilot's
# 2 s pass target, the number a stall is measured against.  span() makes
# the one comparison when it closes; the black box, which registers the
# hook, keeps the record (docs/metrics.md "Waiting and working")
STALL_S = 1.0
# spans this long are kept a second time, in a small ring of their own:
# the watch streams' thousands of short spans roll the main ring over
# within a session's first pass, and a stall record sums a span's
# subtree from what is still held (Tracer.subtree_events)
LONG_S = 0.01


class ProfileStateError(RuntimeError):
    """Invalid XLA-profile state transition (double start, stop without
    start) — the server maps it to HTTP 409."""


def _exp_buckets(start: float, factor: float, count: int) -> tuple[float, ...]:
    out, v = [], start
    for _ in range(count):
        out.append(v)
        v *= factor
    return tuple(out)


# upstream kube-scheduler histogram layouts (pkg/scheduler/metrics):
#   scheduling_attempt_duration_seconds   ExponentialBuckets(0.001, 2, 15)
#   framework_extension_point_duration_seconds
#                                         ExponentialBuckets(0.0001, 2, 12)
#   plugin_execution_duration_seconds     ExponentialBuckets(1e-5, 1.5, 20)
BUCKETS: dict[str, tuple[float, ...]] = {
    "scheduling_attempt_duration_seconds": _exp_buckets(0.001, 2, 15),
    "framework_extension_point_duration_seconds": _exp_buckets(0.0001, 2, 12),
    "plugin_execution_duration_seconds": _exp_buckets(1e-5, 1.5, 20),
    # XLA scan compiles run ~0.1s (warm shapes) to tens of seconds (cold
    # giant meshes): a wider exponential ladder than the attempt buckets
    "scan_compile_seconds": _exp_buckets(0.01, 2, 14),
}
_DEFAULT_BUCKETS = _exp_buckets(0.001, 2, 15)

_HELP: dict[str, str] = {
    "scheduling_attempt_duration_seconds":
        "Per-pod scheduling attempt duration (wave wall amortized over the "
        "wave's pods on the batched paths), by result.",
    "framework_extension_point_duration_seconds":
        "Per-wave extension point duration; prefilter/filter/score are "
        "apportioned from the fused replay span by evaluated work, bind is "
        "the commit tail wall time.",
    "plugin_execution_duration_seconds":
        "Per-plugin execution duration: real wall time on the host path "
        "(reserve/permit/prebind/postbind), work-apportioned replay time "
        "for device-fused filter/score/prefilter (docs/metrics.md).",
    "pods_scheduled_total": "Pods bound by scheduling waves.",
    "pods_unschedulable_total": "Pods left pending after a full pass.",
    "scheduling_waves_total": "Batched scheduling waves run.",
    "plugin_pods_nodes_evaluated_total":
        "Pod x node evaluations attributed to a plugin from the replay "
        "tensors (prefilter: pods screened-in).",
    "plugin_filter_rejects_total":
        "Nodes rejected with this plugin as the first failing filter.",
    "plugin_score_sum_total":
        "Sum of this plugin's raw scores over feasible nodes of scored pods.",
    "plugin_prefilter_screens_total":
        "Pods this PreFilter plugin screened out before the wave compiled.",
    "gang_quorum_groups_total":
        "Gang groups per vectorized quorum pass, by decision.",
    "decode_path_total":
        "Pods decoded per decoder-ladder path (docs/wave-pipeline.md).",
    "native_codec_load_failures_total":
        "Failed builds/loads of the native annotation codec: the process "
        "decodes in pure Python (the parity reference, ~25x slower) — "
        "the compiler's error is on stderr.",
    "decode_on_demand_total":
        "Lazy annotation reads by outcome: miss = the read decoded (or "
        "waited on) its chunk, hit = the chunk was already materialized "
        "(docs/wave-pipeline.md lazy-decode stage).",
    "lazy_decode_cold_read_seconds":
        "Cold first-read latency of a lazily materialized pod: time from "
        "the read to its chunk's annotations being available (one "
        "GIL-released native chunk decode).",
    "d2h_on_demand_bytes_total":
        "Bytes copied device->host by on-demand materialization of "
        "device-resident replay chunks (cold reads; docs/wave-pipeline.md "
        "device-residency stage).",
    "d2h_on_demand_seconds":
        "On-demand device->host materialization latency of one "
        "device-resident replay chunk (gather included on meshes).",
    "wave_d2h_bytes_total":
        "Bytes the wave itself copied device->host while streaming: "
        "decision rows + attribution sums only in device-resident mode, "
        "the full compact tensors in host-resident/eager modes.",
    "device_chunks_retained":
        "Replay chunks currently retained as live device arrays "
        "(KSS_TPU_DEVICE_RESULT_BUDGET_MB bounds the bytes behind them).",
    "device_chunks_spilled_total":
        "Device-resident replay chunks spilled to host by the retention "
        "budget's background LRU writer (session label: the session whose "
        "per-session share of KSS_TPU_DEVICE_RESULT_BUDGET_MB was "
        "exceeded).",
    "scan_compile_cache_total":
        "Jitted-scan compile cache lookups by result: miss = a fresh "
        "jax.jit build (first wave at a new workload shape), hit = a "
        "process-level cached executable reused — across sessions, the "
        "multi-session serving win (docs/metrics.md).",
    "sessions_active":
        "Simulation sessions currently live in the SessionManager "
        "(including the default session).",
    "sessions_created_total": "Simulation sessions created.",
    "sessions_evicted_total":
        "Simulation sessions torn down, by reason (explicit DELETE, "
        "idle TTL, LRU capacity eviction, server shutdown).",
    "scheduling_loop_crashes_total":
        "Scheduling-loop waves that raised (the loop stays alive; the "
        "last crash is surfaced on /readyz).",
    "tracer_events_dropped_total":
        "Span events evicted from the tracer's fixed-size ring because "
        "it was full — a long soak whose trace tail silently scrolled "
        "away shows up here (utils/tracing.py).",
    "blackbox_dumps_total":
        "Post-mortem bundles snapshotted by the wave black box, by "
        "reason (wave_abort, degradation, chaos_failure, request, stall; "
        "docs/metrics.md post-mortem dumps).",
    "span_stalls_total":
        "Spans that closed after STALL_S (1 s) or more and were no "
        "stalled descendant's ancestor, by span and by cause (compile, "
        "gc, on_cpu, process_stopped, blocked, unseen): each is kept as "
        "a bundle of reason stall (docs/metrics.md \"Waiting and "
        "working\").",
    "span_stall_seconds_total":
        "Seconds of the spans span_stalls_total counts; 0 from the "
        "server's start, so absence says a program without the watch.",
    "process_late_seconds_total":
        "Seconds by which the watch's ticks woke later than 50 ms past "
        "their time: time in which no Python thread of the process got "
        "to run (a C call held the GIL, or the process was off the CPU).",
    "watchdog_ticks_total":
        "Ticks of the black box's watch on open spans (every 0.25 s on "
        "the telemetry thread).",
    "hbm_bytes_in_use":
        "Device memory currently in use per local device (device "
        "label) and summed across devices (unlabeled), sampled from "
        "jax memory_stats(); only exported where the backend reports "
        "memory stats — see hbm_stats_available.",
    "hbm_peak_bytes":
        "Peak device memory in use per local device (device label) "
        "and summed (unlabeled), from jax memory_stats().",
    "hbm_stats_available":
        "1 when the backend exposes device memory_stats (HBM gauges "
        "are live), 0 as the explicit no-op marker where it does not "
        "(the CPU backend).",
    "scan_compile_seconds":
        "Seconds JAX spent tracing, lowering and XLA-compiling a cached "
        "scan on its FIRST call (JAX's own compile events on the calling "
        "thread, utils/hostevents.py), labeled by the workload shape's "
        "cache key (key=<crc32 of the shape key>) and result.",
    "queue_wait_seconds_total":
        "Seconds pods waited between the store handing their ADDED event "
        "to the scheduling loop and the start of the wave that took them, "
        "summed over pods (debounce and any pass already running "
        "included).",
    "queue_wait_pods_total":
        "Pods whose queue wait was added to queue_wait_seconds_total.",
    "queue_wait_oldest_seconds_total":
        "Queue wait of the OLDEST pod of each wave, one term per wave.",
    "scheduling_work_passes_total":
        "Scheduling waves that took at least one pod "
        "(scheduling_waves_total counts empty wake-ups too).",
    "scheduling_pass_pods_total":
        "Pods taken by the waves scheduling_work_passes_total counts.",
    "pass_pad_rows_total":
        "Pad rows of the passes scheduling_work_passes_total counts: the "
        "rows of a pass's pod axis (state/compile.py pod_axis_bucket: the "
        "next power of two up to the chunk, whole chunks beyond) past "
        "its real pods.  They never bind and nothing past the scan reads "
        "them.  0 is written too.",
    "pod_axis_rebuckets_total":
        "Passes whose pod-axis bucket is not the bucket of the session's "
        "last pass: another layout of the pass's buffers and other "
        "executables (compiled only where the process has not met the "
        "bucket under this profile and node table).  0 is written too.",
    "bound_rows_built_total":
        "Bound-pod rows compile_workload built anew (state/boundcarry.py): "
        "in a steady pass, the pods bound or changed since the last one.",
    "bound_rows_carried_total":
        "Bound-pod rows compile_workload took over from the pass before "
        "as they were.",
    "bound_carry_rebuilds_total":
        "Full builds of the bound pods' carry, by reason (first, resync, "
        "nodes, namespaces, schema; uncarried = a build handed a list).",
    "bound_pods":
        "Bound pods the last pass's compile_workload saw (rows built + "
        "rows carried).",
    "jax_compile_seconds_total":
        "Seconds inside JAX's compile stages (stage=trace|lower|"
        "backend_compile), from jax.monitoring duration events.",
    "jax_compile_events_total":
        "JAX compile-stage events by stage; backend_compile counts every "
        "executable built OR fetched from the persistent cache "
        "(jax_persistent_cache_total tells them apart).",
    "jax_compiles_by_function_total":
        "Backend compiles by jitted function name (fun; past 64 distinct "
        "names, \"other\") and by the innermost tracer span open on the "
        "compiling thread (span; \"none\" outside any span).",
    "jax_persistent_cache_total":
        "Persistent compilation cache outcomes: request = a compile that "
        "consulted the cache, hit = served from it, miss = compiled and "
        "WRITTEN (compiles under the cache's time/size thresholds are "
        "requests that are neither).",
    "gc_pause_seconds_total":
        "Seconds inside CPython garbage collections, by generation "
        "(gc.callbacks; the collecting thread holds the GIL throughout).",
    "gc_collections_total": "CPython garbage collections by generation.",
    "watch_bytes_sent_total":
        "Bytes of encoded watch events written to list-watch streams.",
    "scan_compile_cache_entries":
        "Compiled scan executables currently held by the process-level "
        "LRU cache (framework/replay._ScanCacheRegistry).",
    "workload_h2d_transfers_total":
        "Host-to-device buffers compile_workload sent (state/packed.py "
        "pack_tree): one per dtype of a pass's xs and carry, and of the "
        "statics where their digest is new on the node table; one more "
        "for a leaf of many long rows (16 rows and 65,536 elements or "
        "more: a buffer of its own) and for a resident volume array "
        "sent whole.",
    "pass_device_dispatches_total":
        "Calls that handed the runtime a transfer, a jitted call or an "
        "eager jnp op on a pass's way from cw_upload's start to the "
        "scan's enqueue (state/packed.py pack_tree / PackedPass.take, "
        "state/resident.py, framework/replay.py): 2 a steady one-pod pass "
        "over the packed buffers, ~60 where every leaf is sliced apart.",
    "replay_route_total":
        "Scans by how they held the workload (route=packed|leaves): "
        "packed, compile_workload's upload taken whole by the executable "
        "of a one-chunk sequential scan; leaves, a tree of device arrays "
        "(a mesh, a pass of many chunks, a hand-built workload).",
    "volume_manifests_parsed_total":
        "PersistentVolume, PersistentVolumeClaim and CSINode manifests the "
        "volume carry parsed (state/volumecarry.py; kind=pv|pvc|csinode): "
        "the ones the store created or changed since the session's last "
        "pass; every one of them on a resync, on a StorageClass change "
        "(the claims) and where compile_workload is handed lists.",
    "volume_bound_rows_walked_total":
        "Bound pods with volumes that the volume carry resolved pod -> "
        "claim -> PV, once for the three builds: the pods bound, changed "
        "or unbound since the session's last pass and the ones whose "
        "claim or PV changed; every one of them on a rebuild.",
    "volume_carry_rebuilds_total":
        "Times the volume family's carried state was built again instead "
        "of patched, by reason: resync (a session's first pass, or a watch "
        "backlog dropped: every manifest parsed, every row resolved), "
        "nodes (another node table: the per-node arrays derived again "
        "from the carried rows, nothing parsed), classes (a StorageClass "
        "change that changes what claims resolve to: the claims parsed, "
        "the rows resolved again), drivers (another set of CSI drivers "
        "with a limit: the bound pods' aggregates laid out again), "
        "uncarried (compile_workload handed manifest lists: a throw-away "
        "carry seeded from them).",
    "volume_axis_rebuckets_total":
        "Passes in which a padded volume axis (axis=pv: PVs in the "
        "cluster; axis=csi: distinct CSI volumes of limited drivers) "
        "outgrew the extent of the session's last pass: the one volume "
        "event that compiles a new scan.",
    "affinity_axis_rebuckets_total":
        "Passes whose padded NodeAffinity axis (axis=req: the U of "
        "req_rows [U, N], unique nodeSelector + required specs among the "
        "pass's pods; axis=pref: the V of pref_rows [V, N]) is not the "
        "extent of the last pass on this node table: another layout of "
        "the pass's buffers, so another scan executable.  0 is written "
        "too (plugins/affinity.py AXIS_FLOOR).",
    "affinity_rows_built_total":
        "NodeAffinity match rows built by walking the node table's "
        "labels: one a nodeSelector + required spec, one a preferred "
        "term (whatever its weight), the first time this node table "
        "meets it; a spec or term seen before is a lookup in the table's "
        "memo (state/nodes.py NodeDerived, kinds affinity_required and "
        "affinity_term) and builds none.",
    "spread_axis_rebuckets_total":
        "Passes of which a padded PodTopologySpread axis (axis=groups: the "
        "C of pm [P, C], group_key [C] and the carry's counts [C, N], "
        "unique (namespace, topologyKey, selector) groups among the "
        "pass's pods; axis=keys: the K of dom_idx [K, N]; axis=rows: the "
        "E of elig_rows [E, N], distinct inclusion specs; axis=domains: "
        "the Dp of dom_iota, the most domains of a key that is not one "
        "node a domain) is not the extent of the last pass on this node "
        "table: another layout of the pass's buffers, so another scan "
        "executable.  0 is written too (plugins/topologyspread.py "
        "_bucket).",
    "spread_rows_built_total":
        "[N] rows built for PodTopologySpread by walking the node table: "
        "kind=dom_idx, a topology key's domain row (NodeTable.domain_row, "
        "which InterPodAffinity's terms share); kind=eligible, the nodes "
        "a constraint's inclusion policies keep for one (nodeSelector, "
        "required node affinity, tolerations).  A key or spec this node "
        "table has met is a lookup in its memo (state/nodes.py "
        "NodeDerived, kinds dom_idx and spread_eligible) and builds none.",
    "spread_excluded_nodes_total":
        "Nodes that the inclusion policies of a pod's topology spread "
        "constraints leave out of its counting, summed over the pods of "
        "the passes built: per pod, the most any one of its constraints "
        "leaves out (0 for a pod without constraints; 0 is written too).  "
        "Where it is above 0, counting a domain's pods by node (upstream) "
        "and by domain differ.",
    "volume_static_args_bytes_total":
        "Bytes that travel to the device for the volume family's statics "
        "handed to the scan as arguments (state/compile.py ARG_STATICS), "
        "with xs and carry every pass: pv_node_ok [V, N] whole, or its "
        "patch's payload where a carried session keeps it on the device "
        "(state/resident.py).",
    "volume_resident_patches_total":
        "Device-resident volume arrays (VolumeBinding's pv_node_ok [V, N], "
        "NodeVolumeLimits' on_node [N, C]) that a pass brought up to date "
        "with a jitted patch of the rows and cells the volume carry wrote "
        "since the session's last pass (state/resident.py): one an array "
        "a pass, none for an array nothing was written to.",
    "volume_resident_uploads_total":
        "Device-resident volume arrays a pass sent whole instead, by "
        "reason: first (a session's first pass), resync, nodes (another "
        "node table), drivers (another set of CSI drivers with a limit), "
        "bucket (an axis grew: another shape), overflow (more rows or "
        "cells written than a patch's payload holds).  A throw-away "
        "carry and an empty axis keep nothing and count nothing.",
    "volume_table_pvs":
        "PersistentVolumes in the last pass's volume table (the V axis "
        "before padding).",
    "preemption_attempts_total":
        "PostFilter runs of DefaultPreemption: one per pod a pass found "
        "no feasible node for (framework/preemption.py).",
    "preemption_static_refused_nodes_total":
        "Nodes holding a lower-priority pod that PostFilter took out "
        "before any dry run: NodeResourcesFit refuses the pod there even "
        "on the empty node.",
    "preemption_screen_dry_runs_total":
        "Batched dry runs (one compile_workload + one filter-only replay "
        "over every node) PostFilter made: none where the static rule "
        "left no node.",
    "preemption_screen_refused_nodes_total":
        "Candidate nodes the batched dry run ruled out: refused, with all "
        "their lower-priority pods gone, by a plugin that reads the node "
        "and its own pods only.",
    "preemption_fit_probes_total":
        "Per-node dry runs (one compile_workload + one filter-only replay "
        "each) of the nodes the batched screen could not rule out, and of "
        "the reprieve loop.",
    "pods_unschedulable_parked_total":
        "Pods the scheduling loop's pass left marked Unschedulable and "
        "parked in the unschedulable set (framework/unschedulable.py).",
    "pods_requeued_total":
        "Parked pods handed back to the pending list, by reason: event (a "
        "cluster event moved it after its backoff), backoff (moved, then "
        "waited the backoff out), flush (5 minutes parked).",
    # the decision's way out (docs/metrics.md): span families
    "span_decision_delivery":
        "From the engine writing a pod's bind or Unschedulable mark to the "
        "first watch stream's socket write of that decision returning "
        "(timed across threads; not a TraceMe in a profile).",
    "span_decision_to_read":
        "From a decision's delivery on a watch stream to the pod's first "
        "GET reaching its handler: the client's turn-around and the "
        "network, as the server sees them (not a TraceMe in a profile).",
    "span_watch_flush":
        "The watch pump draining a pod's deferred annotations before it "
        "sends the pod's event (store.materialize_reads).",
    "span_watch_encode":
        "Filling and JSON-encoding one watch event (child of watch_write).",
    "span_watch_send":
        "Stream lock wait + socket write + flush of one watch event "
        "(child of watch_write): a client that reads slowly shows here.",
    "span_http_encode":
        "JSON-encoding a response body (child of the request's span).",
    "span_http_send":
        "Status line, headers and body written to the socket (child of "
        "the request's span).",
}

_NAME_SANITIZE_RE = re.compile(r"[^a-zA-Z0-9_:]")
_METRIC_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_NAME_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")


def sanitize_metric_name(name: str) -> str:
    """A valid Prometheus metric name from an arbitrary span/counter name
    (dashes, dots, spaces -> '_'; leading digit prefixed)."""
    s = _NAME_SANITIZE_RE.sub("_", name)
    if not s or s[0].isdigit():
        s = "_" + s
    return s


def escape_label_value(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _escape_help(v: str) -> str:
    return v.replace("\\", "\\\\").replace("\n", "\\n")


def _fmt_float(v: float) -> str:
    if v == float("inf"):
        return "+Inf"
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


def _fmt_le(v: float) -> str:
    if v == float("inf"):
        return "+Inf"
    return f"{v:g}"


class Span:
    """Handle yielded by Tracer.span(): carries the span id (for explicit
    cross-thread parenting) and, after exit, the measured seconds."""

    __slots__ = ("id", "parent_id", "name", "seconds", "attrs")

    def __init__(self, span_id: int, parent_id: int | None, name: str,
                 attrs: dict):
        self.id = span_id
        self.parent_id = parent_id
        self.name = name
        self.seconds = 0.0
        # the span's event attrs: a caller may add to them until the
        # span exits (the wave root learns its node count mid-span)
        self.attrs = attrs


class _Hist:
    """One histogram series (a label set): fixed bounds, per-bucket
    counts (non-cumulative internally), sum and count."""

    __slots__ = ("counts", "sum", "count")

    def __init__(self, n_buckets: int):
        self.counts = [0] * (n_buckets + 1)  # +1: the +Inf bucket
        self.sum = 0.0
        self.count = 0


class Tracer:
    def __init__(self, capacity: int | None = None):
        if capacity is None:
            # KSS_TPU_TRACER_CAPACITY sizes the span ring: a soak whose
            # trace tail matters can grow it instead of silently losing
            # events (tracer_events_dropped_total counts evictions and
            # /readyz surfaces them as tracerDroppedEvents)
            capacity = max(64, env_int("KSS_TPU_TRACER_CAPACITY", 4096))
        self._lock = threading.Lock()
        self._events: deque = deque(maxlen=capacity)
        self._long: deque = deque(maxlen=1024)
        self._agg: dict[str, dict] = {}
        self._counters: dict[str, float] = {}
        # gauges: absolute values set by gauge() (current device-retained
        # chunk count etc.), exported with TYPE gauge; labeled series
        # (HBM per-device samples) live separately and merge into one
        # family at exposition, like counters do
        self._gauges: dict[str, float] = {}
        self._lgauges: dict[str, dict[tuple, float]] = {}
        # spans currently OPEN (entered, not yet exited): the wave black
        # box snapshots these into a post-mortem bundle so a dump shows
        # WHERE the wave was when the fault fired (utils/blackbox.py)
        self._open: dict[int, dict] = {}
        # labeled counters: name -> {((k, v), ...) sorted: value}
        self._lcounters: dict[str, dict[tuple, float]] = {}
        # histograms: name -> {((k, v), ...) sorted: _Hist}
        self._hists: dict[str, dict[tuple, _Hist]] = {}
        self._hist_bounds: dict[str, tuple[float, ...]] = {}
        self._profile_dir: str | None = None
        self._profile_lock = threading.Lock()
        # jax.profiler.TraceAnnotation while an XLA profile runs, else
        # None: span() then also opens a "kss:<name>" TraceMe, so every
        # program span sits on its thread's line of the host plane of
        # the same .xplane.pb as the device ops — one clock
        self._annotate = None
        # callables run (outside the lock) before every export: sources
        # that may not take the tracer's lock where they observe — a GC
        # callback can fire inside any allocation, this lock held —
        # accumulate on their own and hand their totals over here
        self._collectors: list = []
        # called with the event of every span that closes at STALL_S or
        # more, on the span's own thread and outside the lock: the black
        # box registers it (blackbox imports tracing, never the reverse)
        self._stall_hook = None
        self._epoch = time.time()
        self._perf_epoch = time.perf_counter()
        self._ids = itertools.count(1)
        self._tls = threading.local()
        self._tids: dict[int, tuple[int, str]] = {}  # ident -> (tid, name)
        # per-session views (multi-session serving, server/sessions.py):
        # while a session scope is active on the recording thread, spans
        # gain a session attr, labeled counters/histograms gain a
        # session label, and plain counters/span aggregates are ALSO
        # tallied here so /api/v1/metrics?session= can answer without
        # touching the aggregate families
        self._scounters: dict[str, dict[str, float]] = {}
        self._sagg: dict[str, dict[str, dict]] = {}
        # per-session gauge view: gauge() under a session scope mirrors
        # the last-set value here so snapshot(session=) can answer
        # (counters/histograms fold a session label; gauges are
        # absolute values, so the aggregate sample stays unlabeled and
        # the session view is a mirror, not a label)
        self._sgauges: dict[str, dict[str, float]] = {}
        # pending trace-id handoff, session -> trace id: the server
        # notes the request's trace id when a workload-submitting call
        # lands, and the scheduling wave that consumes the work CLAIMS
        # it (consume-once) so the wave's spans correlate back to the
        # HTTP request that caused them (docs/metrics.md)
        self._session_traces: dict[str, str] = {}

    # ---------------------------------------------------------- sessions

    def current_session(self) -> str | None:
        """The session id attached to metrics recorded on this thread
        (None outside any session scope — direct engine use, tests)."""
        st = getattr(self._tls, "sessions", None)
        return st[-1] if st else None

    @contextmanager
    def session_scope(self, session: str | None):
        """Attribute everything recorded on this thread to `session`:
        spans carry a session attr, inc()/observe() fold a session
        label in, count()/span aggregates are mirrored into the
        per-session view.  None is a no-op scope (the sessionless
        paths stay byte-identical)."""
        if session is None:
            yield
            return
        st = getattr(self._tls, "sessions", None)
        if st is None:
            st = self._tls.sessions = []
        st.append(str(session))
        try:
            yield
        finally:
            st.pop()

    # ------------------------------------------------------------ traces

    def current_trace(self) -> str | None:
        """The trace id attached to spans/events recorded on this
        thread (None outside any trace scope)."""
        st = getattr(self._tls, "traces", None)
        return st[-1] if st else None

    @contextmanager
    def trace_scope(self, trace_id: str | None):
        """Correlate everything recorded on this thread under one trace
        id: spans and black-box events gain a trace_id attr, so one id
        ties an HTTP request to the wave it caused.  Propagates exactly
        like session_scope; None is a no-op scope (an enclosing scope, if
        any, stays active)."""
        if trace_id is None:
            yield
            return
        st = getattr(self._tls, "traces", None)
        if st is None:
            st = self._tls.traces = []
        st.append(str(trace_id))
        try:
            yield
        finally:
            st.pop()

    def note_session_trace(self, session: str, trace_id: str) -> None:
        """Stash `trace_id` as the pending trace for `session`'s next
        scheduling wave (the server calls this for workload-submitting
        requests; engine.schedule_pending claims it)."""
        with self._lock:
            self._session_traces[str(session)] = str(trace_id)

    def claim_session_trace(self, session: str | None) -> str | None:
        """Pop (consume-once) the pending trace id for `session` — the
        wave that drains the submitted work owns the correlation."""
        if session is None:
            return None
        with self._lock:
            return self._session_traces.pop(str(session), None)

    # ------------------------------------------------------------- spans

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def current_span_id(self) -> int | None:
        st = self._stack()
        return st[-1].id if st else None

    def current_span_name(self) -> str | None:
        """Name of the innermost span open on this thread (the JAX
        compile listener labels a compile with it: a compile is
        synchronous on the caller's thread)."""
        st = self._stack()
        return st[-1].name if st else None

    def _tid(self) -> int:
        ident = threading.get_ident()
        ent = self._tids.get(ident)
        if ent is None:
            ent = (len(self._tids) + 1, threading.current_thread().name)
            self._tids[ident] = ent
        return ent[0]

    def annotate(self, name: str):
        """While an XLA profile runs: an ENTERED kss:<name> TraceMe (the
        caller exits it, on the same thread); None otherwise — the cost
        of the one-clock bridge when no profile runs is this test."""
        annotate = self._annotate
        if annotate is None:
            return None
        annotate = annotate("kss:" + name)
        annotate.__enter__()
        return annotate

    def record_span(self, name: str, t0: float, seconds: float,
                    session: str | None = None, **attrs) -> None:
        """A span that was timed elsewhere (t0 on time.perf_counter's
        clock) enters the ring and the aggregates after the fact: for
        sources that may not take this lock while they observe (a GC
        callback; utils/hostevents.py hands its pauses over this way)
        and for stretches that start on one thread and end on another
        (services/resourcewatcher.py DecisionStamps).  `session` files
        it under that session's aggregates as a session scope would."""
        if session is not None:
            attrs["session"] = session
        with self._lock:
            self._record_locked({
                "name": name, "t": time.time(), "seconds": seconds,
                "ts": round(t0 - self._perf_epoch, 6),
                "span_id": next(self._ids), "parent_id": None,
                "tid": self._tid(), **attrs,
            }, session)

    def _record_locked(self, event: dict, session: str | None) -> None:
        """A finished span enters the ring and the per-name aggregates
        (the caller holds the lock)."""
        if (self._events.maxlen is not None
                and len(self._events) == self._events.maxlen):
            # the ring is full: this append evicts the oldest span
            # silently — count it so long soaks can see their trace tail
            # scrolled away (summary(), /metrics
            # tracer_events_dropped_total)
            self._counters["tracer_events_dropped_total"] = \
                self._counters.get("tracer_events_dropped_total", 0) + 1
        self._events.append(event)
        if event["seconds"] >= LONG_S:
            self._long.append(event)
        aggs = [self._agg]
        if session is not None:
            aggs.append(self._sagg.setdefault(session, {}))
        for agg in aggs:
            a = agg.setdefault(event["name"], {
                "count": 0, "total_seconds": 0.0, "max_seconds": 0.0})
            a["count"] += 1
            a["total_seconds"] += event["seconds"]
            a["max_seconds"] = max(a["max_seconds"], event["seconds"])

    @contextmanager
    def span(self, name: str, parent: int | None = None, **attrs):
        """Record a span; nested spans on the same thread parent
        implicitly, `parent=` parents explicitly across threads (the
        commit worker parents its chunk spans under the wave's replay
        span).  Yields a Span whose .id other threads may use and whose
        .seconds is set on exit.  One clock, perf_counter: a span that
        stands for STALL_S gets its thread's CPU seconds from the black
        box's watch, which reads that clock from outside (a read of
        time.thread_time() here costs 6 us on the benchmark's host and
        is wrong below ~50 ms there; PERF.md section 6, PR 48)."""
        st = self._stack()
        sp = Span(next(self._ids),
                  parent if parent is not None else (st[-1].id if st else None),
                  name, attrs)
        st.append(sp)
        session = self.current_session()
        if session is not None and "session" not in attrs:
            attrs["session"] = session
        trace_id = self.current_trace()
        if trace_id is not None and "trace_id" not in attrs:
            attrs["trace_id"] = trace_id
        t0 = time.perf_counter()
        if BLACKBOX_OPEN_SPANS:
            with self._lock:
                self._open[sp.id] = {
                    "name": name, "span_id": sp.id,
                    "parent_id": sp.parent_id,
                    "tid": self._tid(), "t0": time.time(), "t0_perf": t0,
                    "ident": threading.get_ident(),
                    **({"session": session} if session is not None else {}),
                    **({"trace_id": trace_id} if trace_id is not None
                       else {}),
                }
        annotate = self.annotate(name)
        try:
            yield sp
        except BaseException as exc:
            # first (innermost) span this exception unwinds through:
            # stash the open-span tree AS OF THE FAULT so the black
            # box's post-mortem can report where the wave was, even
            # though every span has closed by the time the wave failure
            # protocol builds the bundle (utils/blackbox.py).  An
            # explicit except (not sys.exc_info() in the finally) so a
            # span exiting NORMALLY inside an outer except handler
            # never tags the handled exception with stale spans.
            if not hasattr(exc, "_kss_open_spans"):
                try:
                    exc._kss_open_spans = self.open_spans()
                # builtins with __slots__ reject attributes — best-effort
                # kss-analyze: allow(swallowed-exception)
                except Exception:
                    pass
            raise
        finally:
            if annotate is not None:
                annotate.__exit__(None, None, None)
            dt = time.perf_counter() - t0
            sp.seconds = dt
            st.pop()
            with self._lock:
                self._open.pop(sp.id, None)
                event = {
                    "name": name, "t": time.time(), "seconds": dt,
                    "ts": round(t0 - self._perf_epoch, 6),
                    "span_id": sp.id, "parent_id": sp.parent_id,
                    "tid": self._tid(), **attrs,
                }
                self._record_locked(event, session)
            if dt >= STALL_S and self._stall_hook is not None:
                self._stall_hook(event)

    # ---------------------------------------------------------- counters

    def count(self, name: str, n: float = 1) -> None:
        session = self.current_session()
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n
            if session is not None:
                sc = self._scounters.setdefault(session, {})
                sc[name] = sc.get(name, 0) + n

    def gauge(self, name: str, value: float, **labels) -> None:
        """Set a gauge to an absolute value (unlike count(), which
        accumulates): the exporter emits it with TYPE gauge.  With
        labels (e.g. the HBM sampler's device=<id>) the series lands in
        a labeled family that merges with the unlabeled sample at
        exposition, like counters.  Under an active session scope the
        last-set value is ALSO mirrored into the per-session view that
        snapshot(session=) reports — gauges are absolute, so the
        aggregate sample stays unlabeled rather than splitting into
        per-session series that would each claim the global value."""
        session = self.current_session()
        if labels and session is not None and "session" not in labels:
            labels["session"] = session
        key = (tuple(sorted((k, str(v)) for k, v in labels.items()))
               if labels else None)
        with self._lock:
            if key:
                self._lgauges.setdefault(name, {})[key] = value
            else:
                self._gauges[name] = value
            if session is not None:
                self._sgauges.setdefault(session, {})[name] = value

    def open_spans(self) -> list[dict]:
        """Spans entered but not yet exited, oldest first, with
        seconds_so_far — the black box snapshots these at fault time
        (utils/blackbox.py post-mortem bundles)."""
        now = time.time()
        with self._lock:
            spans = [dict(v) for v in self._open.values()]
        spans.sort(key=lambda s: s["t0"])
        for s in spans:
            s["seconds_so_far"] = round(max(now - s.pop("t0"), 0.0), 6)
            del s["t0_perf"], s["ident"]
        return spans

    def open_since(self, min_age: float) -> list[dict]:
        """The open spans at least `min_age` seconds old, as they are kept
        (t0_perf on time.perf_counter's clock, ident of the thread): the
        black box's watch asks every quarter of a second, and with
        nothing old this is one lock hold over a handful of entries."""
        born_by = time.perf_counter() - min_age
        with self._lock:
            return [dict(v) for v in self._open.values()
                    if v["t0_perf"] <= born_by]

    def open_chain(self, span_id: int | None) -> list[str]:
        """The names of the open span `span_id` and of its open
        ancestors, nearest first (a closed span's parent_id names the
        chain it closed under)."""
        names = []
        with self._lock:
            while span_id is not None and span_id in self._open:
                names.append(self._open[span_id]["name"])
                span_id = self._open[span_id]["parent_id"]
        return names

    def set_stall_hook(self, fn) -> None:
        self._stall_hook = fn

    def dropped_events(self) -> float:
        """Spans evicted from the full ring so far
        (tracer_events_dropped_total) — /readyz surfaces this as
        tracerDroppedEvents when nonzero."""
        with self._lock:
            return float(self._counters.get(
                "tracer_events_dropped_total", 0))

    def add_collector(self, fn) -> None:
        self._collectors.append(fn)

    def _collect(self) -> None:
        for fn in self._collectors:
            fn()

    def counter_totals(self) -> dict[str, float]:
        """Every counter flattened to one {key: value} dict: plain
        counters under their name, labeled series under
        name{k=v,...}.  The black box captures this at wave start and
        diffs at dump time — the per-wave counter deltas a post-mortem
        carries."""
        self._collect()
        with self._lock:
            out = dict(self._counters)
            for name, series in self._lcounters.items():
                for key, v in series.items():
                    if not key:
                        out[name] = out.get(name, 0) + v
                        continue
                    flat = ",".join(f"{k}={lv}" for k, lv in key)
                    out[f"{name}{{{flat}}}"] = v
        return out

    def inc(self, name: str, n: float = 1, **labels) -> None:
        """Labeled counter increment; identical label sets merge
        regardless of keyword order.  Under an active session scope a
        session label is folded in (unless the caller set one)."""
        session = self.current_session()
        if session is not None and "session" not in labels:
            labels["session"] = session
        self.inc_process(name, n, **labels)

    def inc_process(self, name: str, n: float = 1, **labels) -> None:
        """inc() for events of the PROCESS, not of a session (a garbage
        collection lands on whichever thread allocated last): no session
        label is folded in."""
        key = tuple(sorted((k, str(v)) for k, v in labels.items()))
        with self._lock:
            series = self._lcounters.setdefault(name, {})
            series[key] = series.get(key, 0) + n

    def labeled_totals(self, name: str, label: str) -> dict[str, float]:
        """Sum one labeled counter's series grouped by `label`'s value
        (series without the label fold under "").  Powers the
        per-session spill plane of the history feeder and the
        autopilot's decision counts without a full snapshot()."""
        out: dict[str, float] = {}
        with self._lock:
            series = self._lcounters.get(name, {})
            for key, v in series.items():
                val = dict(key).get(label, "")
                out[val] = out.get(val, 0) + v
        return out

    # --------------------------------------------------------- histograms

    def observe(self, name: str, value: float, n: int = 1, **labels) -> None:
        """Histogram observation (n identical observations at once — the
        batched waves amortize one wall time over many pods).  Buckets
        come from BUCKETS[name] (upstream layouts) or the default
        exponential ladder."""
        if n <= 0:
            return
        session = self.current_session()
        if session is not None and "session" not in labels:
            labels["session"] = session
        key = tuple(sorted((k, str(v)) for k, v in labels.items()))
        with self._lock:
            bounds = self._hist_bounds.get(name)
            if bounds is None:
                bounds = self._hist_bounds[name] = BUCKETS.get(
                    name, _DEFAULT_BUCKETS)
            series = self._hists.setdefault(name, {})
            h = series.get(key)
            if h is None:
                h = series[key] = _Hist(len(bounds))
            i = 0
            while i < len(bounds) and value > bounds[i]:
                i += 1
            h.counts[i] += n
            h.sum += value * n
            h.count += n

    # ------------------------------------------------------------ export

    def events(self, limit: int = 200) -> list[dict]:
        with self._lock:
            evs = list(self._events)
        return evs[-limit:]

    def subtree_events(self, since_ts: float) -> tuple[list[dict], bool]:
        """Every held event that started at `since_ts` (the tracer's
        clock) or later, from the ring and from the long spans' ring,
        each once; and whether the main ring has rolled past `since_ts`
        (short spans from before its oldest event are then lost)."""
        with self._lock:
            held = {ev["span_id"]: ev for ev in self._long}
            held.update((ev["span_id"], ev) for ev in self._events)
            rolled = (len(self._events) == self._events.maxlen
                      and self._events[0]["ts"] > since_ts)
        return [ev for ev in held.values() if ev["ts"] >= since_ts], rolled

    def summary(self) -> dict:
        """Back-compat aggregate view: span aggregates + plain counters
        (the pre-flight-recorder shape; snapshot() adds the labeled
        families)."""
        self._collect()
        with self._lock:
            spans = {
                k: {**v, "avg_seconds": v["total_seconds"] / max(v["count"], 1)}
                for k, v in self._agg.items()
            }
            return {"spans": spans, "counters": dict(self._counters)}

    def snapshot(self, session: str | None = None) -> dict:
        """Full metrics snapshot: summary() plus labeled counters and
        histogram series — what /api/v1/metrics, the SSE stream and the
        bench artifact emit.  With session=<id>, every family is
        filtered to that session's view: spans/counters come from the
        per-session tallies, labeled counters and histograms keep only
        series whose session label matches (docs/metrics.md)."""
        if session is not None:
            self._collect()
            skey = ("session", str(session))
            with self._lock:
                sagg = {
                    k: {**v,
                        "avg_seconds": v["total_seconds"] / max(v["count"], 1)}
                    for k, v in self._sagg.get(session, {}).items()
                }
                out = {
                    "session": str(session),
                    "spans": sagg,
                    "counters": dict(self._scounters.get(session, {})),
                    "time": time.time(),
                    # the session's gauge view: last values set under
                    # its scope, plus labeled series carrying its label
                    "gauges": dict(self._sgauges.get(session, {})),
                    "labeled_gauges": {
                        name: [{"labels": dict(key), "value": v}
                               for key, v in sorted(series.items())
                               if skey in key]
                        for name, series in sorted(self._lgauges.items())
                        if any(skey in key for key in series)
                    },
                    "labeled_counters": {
                        name: [{"labels": dict(key), "value": v}
                               for key, v in sorted(series.items())
                               if skey in key]
                        for name, series in sorted(self._lcounters.items())
                        if any(skey in key for key in series)
                    },
                    "histograms": {
                        name: {
                            "buckets": list(self._hist_bounds[name]),
                            "series": [
                                {"labels": dict(key), "counts": list(h.counts),
                                 "sum": round(h.sum, 9), "count": h.count}
                                for key, h in sorted(series.items())
                                if skey in key
                            ],
                        }
                        for name, series in sorted(self._hists.items())
                        if any(skey in key for key in series)
                    },
                }
            return out
        out = self.summary()
        with self._lock:
            out["time"] = time.time()
            out["gauges"] = dict(self._gauges)
            out["labeled_gauges"] = {
                name: [{"labels": dict(key), "value": v}
                       for key, v in sorted(series.items())]
                for name, series in sorted(self._lgauges.items())
            }
            out["labeled_counters"] = {
                name: [{"labels": dict(key), "value": v}
                       for key, v in sorted(series.items())]
                for name, series in sorted(self._lcounters.items())
            }
            out["histograms"] = {
                name: {
                    "buckets": list(self._hist_bounds[name]),
                    "series": [
                        {"labels": dict(key), "counts": list(h.counts),
                         "sum": round(h.sum, 9), "count": h.count}
                        for key, h in sorted(series.items())
                    ],
                }
                for name, series in sorted(self._hists.items())
            }
        return out

    # ------------------------------------------------------- prometheus

    @staticmethod
    def _render_labels(pairs: tuple, extra: str = "") -> str:
        parts = [f'{k}="{escape_label_value(v)}"' for k, v in pairs]
        if extra:
            parts.append(extra)
        return "{" + ",".join(parts) + "}" if parts else ""

    def prometheus_text(self) -> str:
        """Prometheus text exposition (the observable analogue of the
        upstream scheduler's /metrics).  Always passes
        validate_exposition(): # HELP/# TYPE per family, sanitized
        metric names, escaped label values, cumulative histogram
        buckets ending at +Inf."""
        self._collect()
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            lgauges = {n: dict(s) for n, s in self._lgauges.items()}
            lcounters = {n: dict(s) for n, s in self._lcounters.items()}
            hists = {
                n: (self._hist_bounds[n],
                    {k: (list(h.counts), h.sum, h.count)
                     for k, h in s.items()})
                for n, s in self._hists.items()
            }
            aggs = {k: dict(v) for k, v in self._agg.items()}
        out: list[str] = []

        def family(name: str, mtype: str, help_key: str | None = None) -> str:
            m = sanitize_metric_name(f"{_PREFIX}_{name}")
            h = _HELP.get(help_key or name, f"{name} ({mtype}).")
            out.append(f"# HELP {m} {_escape_help(h)}")
            out.append(f"# TYPE {m} {mtype}")
            return m

        # one family per counter NAME: a name incremented both plain
        # (sessionless paths) and labeled (session scopes fold a session
        # label in) must emit ONE # HELP/# TYPE block — the unlabeled
        # sample first, then the labeled series (duplicate TYPE lines
        # would fail validate_exposition)
        for name in sorted(set(counters) | set(lcounters)):
            m = family(name, "counter")
            if name in counters:
                out.append(f"{m} {_fmt_float(counters[name])}")
            for key, v in sorted(lcounters.get(name, {}).items()):
                out.append(f"{m}{self._render_labels(key)} {_fmt_float(v)}")
        # gauges merge plain + labeled series (the HBM sampler sets the
        # per-device labeled samples AND the unlabeled aggregate) into
        # one family, exactly like counters above
        for name in sorted(set(gauges) | set(lgauges)):
            m = family(name, "gauge")
            if name in gauges:
                out.append(f"{m} {_fmt_float(gauges[name])}")
            for key, v in sorted(lgauges.get(name, {}).items()):
                out.append(f"{m}{self._render_labels(key)} {_fmt_float(v)}")
        for name, (bounds, series) in sorted(hists.items()):
            m = family(name, "histogram")
            for key, (bcounts, hsum, hcount) in sorted(series.items()):
                cum = 0
                for bound, c in zip((*bounds, float("inf")), bcounts):
                    cum += c
                    le = f'le="{_fmt_le(bound)}"'
                    out.append(
                        f"{m}_bucket{self._render_labels(key, le)} {cum}")
                out.append(f"{m}_sum{self._render_labels(key)} "
                           f"{_fmt_float(hsum)}")
                out.append(f"{m}_count{self._render_labels(key)} {hcount}")
        for name, a in sorted(aggs.items()):
            base = f"span_{name}"
            m = family(f"{base}_seconds_total", "counter", help_key=base)
            out.append(f"{m} {_fmt_float(a['total_seconds'])}")
            m = family(f"{base}_count", "counter", help_key=base)
            out.append(f"{m} {_fmt_float(a['count'])}")
            m = family(f"{base}_seconds_max", "gauge", help_key=base)
            out.append(f"{m} {_fmt_float(a['max_seconds'])}")
        return "\n".join(out) + "\n"

    # --------------------------------------------------------- perfetto

    def perfetto(self, limit: int | None = None,
                 session: str | None = None,
                 trace_id: str | None = None) -> dict:
        """chrome://tracing / Perfetto JSON of the recorded span tree.

        Complete events ("ph": "X") on per-thread tracks; ts/dur in
        microseconds since the tracer epoch.  Span/parent ids ride in
        args so the tree survives even across thread tracks (the
        commit worker's commit_stream spans visibly overlap the
        replay_and_decode_stream parent on another track —
        docs/metrics.md walkthrough).  Black-box events (wave faults,
        autopilot decisions) ride along as instant
        ("ph": "i") events on the same timeline, so a chrome://tracing
        load shows WHAT happened inline with WHERE the wave was."""
        with self._lock:
            evs = list(self._events)
            tids = dict(self._tids)
        # black-box events become instants on the correlated timeline;
        # a function-level import — blackbox imports tracing at module
        # level, so the reverse edge must stay lazy
        from .blackbox import BLACKBOX
        instants = BLACKBOX.events()
        if session is not None:
            # ?session= filtering (docs/metrics.md): only spans recorded
            # under that session's scope — filtered BEFORE the limit cut
            # so a busy neighbor can't push this session's spans out of
            # the window
            evs = [ev for ev in evs if ev.get("session") == str(session)]
            instants = [ev for ev in instants
                        if ev.get("session") == str(session)]
        if trace_id is not None:
            # ?trace_id= filtering: the causal slice of ONE request —
            # spans and instants stamped with that id, across sessions
            tid_s = str(trace_id)
            evs = [ev for ev in evs if ev.get("trace_id") == tid_s]
            instants = [ev for ev in instants
                        if ev.get("trace_id") == tid_s]
        if limit is not None:
            evs = evs[-limit:] if limit > 0 else []  # evs[-0:] is ALL
            instants = instants[-limit:] if limit > 0 else []
        pid = os.getpid()
        trace: list[dict] = [{
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": "kss-tpu-simulator"},
        }]
        for tid, tname in sorted(tids.values()):
            trace.append({"name": "thread_name", "ph": "M", "pid": pid,
                          "tid": tid, "args": {"name": tname}})
        for ev in evs:
            if "span_id" not in ev:
                continue
            args = {k: v for k, v in ev.items()
                    if k not in ("name", "t", "ts", "seconds", "tid")}
            trace.append({
                "name": ev["name"], "cat": "wave", "ph": "X",
                "ts": int(ev["ts"] * 1e6),
                "dur": max(1, int(ev["seconds"] * 1e6)),
                "pid": pid, "tid": ev["tid"], "args": args,
            })
        for ev in instants:
            # black-box events carry wall time; place them on the span
            # timeline via the tracer's own wall/perf epoch pair
            args = {k: v for k, v in ev.items() if k not in ("kind", "t")}
            trace.append({
                "name": ev.get("kind", "event"), "cat": "blackbox",
                "ph": "i", "s": "p",
                "ts": max(0, int((ev.get("t", self._epoch)
                                  - self._epoch) * 1e6)),
                "pid": pid, "tid": 0, "args": args,
            })
        return {"traceEvents": trace, "displayTimeUnit": "ms"}

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self._long.clear()
            self._agg.clear()
            self._counters.clear()
            self._gauges.clear()
            self._lgauges.clear()
            self._lcounters.clear()
            self._hists.clear()
            self._hist_bounds.clear()
            self._scounters.clear()
            self._sagg.clear()
            self._sgauges.clear()
            self._open.clear()
            self._session_traces.clear()

    # -------------------------------------------------------- XLA profile

    def start_xla_profile(self, log_dir: str,
                          python_tracer: bool = True) -> None:
        """python_tracer=False turns the profiler's Python tracer off
        (it makes a served cycle ~3x as long, PERF.md): the program's
        own spans, as kss:<name> TraceMe events, then label the host
        side of the trace."""
        import jax

        kwargs = {}
        if not python_tracer:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            kwargs["profiler_options"] = options
        with self._profile_lock:
            if self._profile_dir is not None:
                raise ProfileStateError(
                    f"profile already running into {self._profile_dir}")
            try:
                # profiler start runs under _profile_lock BY DESIGN: the
                # lock exists solely to make the is-running check and the
                # start one transition (409 on double start); nothing on
                # the scheduling path ever takes it
                jax.profiler.start_trace(log_dir, **kwargs)  # kss-analyze: allow(device-under-lock)
            except RuntimeError as e:
                # a profiler session started outside this Tracer — still a
                # state conflict, not a server error
                raise ProfileStateError(str(e)) from e
            self._profile_dir = log_dir
            self._annotate = jax.profiler.TraceAnnotation

    def stop_xla_profile(self) -> str:
        import jax

        with self._profile_lock:
            if self._profile_dir is None:
                raise ProfileStateError("no profile running")
            self._annotate = None
            try:
                # same contract as start: _profile_lock serializes only
                # the profiler state transition itself
                jax.profiler.stop_trace()  # kss-analyze: allow(device-under-lock)
            except RuntimeError as e:
                # the profiler session died outside this Tracer — clear
                # our state (nothing is running) and report the conflict
                # as a 409, not a server error
                self._profile_dir = None
                raise ProfileStateError(str(e)) from e
            d, self._profile_dir = self._profile_dir, None
            return d

    @property
    def profiling(self) -> bool:
        return self._profile_dir is not None


# ------------------------------------------------- exposition validator


def _parse_label_body(body: str) -> list[tuple[str, str]]:
    """Parse the inside of {...}, honoring \\", \\\\ and \\n escapes.
    Raises ValueError on malformed input."""
    pairs: list[tuple[str, str]] = []
    i, n = 0, len(body)
    while i < n:
        j = body.find("=", i)
        if j < 0:
            raise ValueError(f"label without '=': {body[i:]!r}")
        name = body[i:j]
        if not _LABEL_NAME_RE.match(name):
            raise ValueError(f"invalid label name {name!r}")
        if j + 1 >= n or body[j + 1] != '"':
            raise ValueError(f"unquoted label value after {name!r}")
        i = j + 2
        val: list[str] = []
        while True:
            if i >= n:
                raise ValueError(f"unterminated label value for {name!r}")
            c = body[i]
            if c == "\\":
                if i + 1 >= n or body[i + 1] not in ('"', "\\", "n"):
                    raise ValueError(f"bad escape in label value for {name!r}")
                val.append({"\\": "\\", '"': '"', "n": "\n"}[body[i + 1]])
                i += 2
            elif c == '"':
                i += 1
                break
            elif c == "\n":
                raise ValueError("raw newline in label value")
            else:
                val.append(c)
                i += 1
        pairs.append((name, "".join(val)))
        if i < n:
            if body[i] != ",":
                raise ValueError(f"expected ',' between labels at {body[i:]!r}")
            i += 1
    return pairs


_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"          # metric name
    r"(?:\{(.*)\})?"                         # optional label body
    r" (NaN|[+-]?Inf|[+-]?[0-9]*\.?[0-9]+(?:[eE][+-]?[0-9]+)?)"  # value
    r"(?: (-?[0-9]+))?$"                     # optional timestamp
)


def validate_exposition(text: str) -> dict:
    """Strict Prometheus text-format (0.0.4) validator.

    Checks: final newline; # HELP/# TYPE syntax, at most one each per
    family and both before the family's samples; valid metric/label
    names; quoted + escaped label values; parseable sample values; no
    duplicate label names per sample; family samples not interleaved;
    histogram families carry cumulative _bucket series per label set
    ending at le="+Inf", with matching _count and a _sum.

    Returns {family: {"type", "help", "samples": [(name, labels, value)]}}.
    Raises ValueError with the offending line on any violation.
    """
    if not text.endswith("\n"):
        raise ValueError("exposition must end with a newline")
    families: dict[str, dict] = {}
    current: str | None = None
    closed: set[str] = set()

    def fam(name: str) -> dict:
        return families.setdefault(
            name, {"type": None, "help": None, "samples": []})

    for lineno, line in enumerate(text.split("\n")[:-1], 1):
        if line == "":
            continue
        try:
            if line.startswith("# HELP ") or line.startswith("# TYPE "):
                kind = line[2:6]
                rest = line[7:]
                name, _, payload = rest.partition(" ")
                if not _METRIC_NAME_RE.match(name):
                    raise ValueError(f"invalid metric name {name!r}")
                f = fam(name)
                if f["samples"]:
                    raise ValueError(f"# {kind} after samples of {name}")
                if kind == "HELP":
                    if f["help"] is not None:
                        raise ValueError(f"duplicate # HELP for {name}")
                    f["help"] = payload
                else:
                    if f["type"] is not None:
                        raise ValueError(f"duplicate # TYPE for {name}")
                    if payload not in ("counter", "gauge", "histogram",
                                       "summary", "untyped"):
                        raise ValueError(f"invalid type {payload!r}")
                    f["type"] = payload
                continue
            if line.startswith("#"):
                continue  # plain comment
            m = _SAMPLE_RE.match(line)
            if m is None:
                raise ValueError("unparseable sample line")
            name, body, value = m.group(1), m.group(2), m.group(3)
            base = name
            for suffix in ("_bucket", "_sum", "_count"):
                cand = name[: -len(suffix)] if name.endswith(suffix) else None
                if cand and families.get(cand, {}).get("type") == "histogram":
                    base = cand
                    break
            labels = _parse_label_body(body) if body else []
            seen = set()
            for k, _v in labels:
                if k in seen:
                    raise ValueError(f"duplicate label {k!r}")
                seen.add(k)
            float(value.replace("Inf", "inf"))  # parse check
            if base != current:
                if base in closed:
                    raise ValueError(f"samples of {base} interleaved")
                if current is not None:
                    closed.add(current)
                current = base
            fam(base)["samples"].append((name, dict(labels), value))
        except ValueError as e:
            raise ValueError(f"line {lineno}: {e} — {line!r}") from None

    for name, f in families.items():
        if f["type"] != "histogram":
            continue
        series: dict[tuple, dict] = {}
        for sname, labels, value in f["samples"]:
            key = tuple(sorted((k, v) for k, v in labels.items()
                               if k != "le"))
            s = series.setdefault(key, {"buckets": [], "sum": None,
                                        "count": None})
            if sname == name + "_bucket":
                if "le" not in labels:
                    raise ValueError(f"{name}_bucket without le label")
                s["buckets"].append(
                    (float(labels["le"].replace("Inf", "inf")), float(value)))
            elif sname == name + "_sum":
                s["sum"] = float(value)
            elif sname == name + "_count":
                s["count"] = float(value)
            else:
                raise ValueError(f"stray sample {sname!r} in histogram {name}")
        for key, s in series.items():
            if not s["buckets"] or s["buckets"][-1][0] != float("inf"):
                raise ValueError(f"histogram {name}{dict(key)} lacks a "
                                 "+Inf bucket")
            les = [le for le, _ in s["buckets"]]
            if les != sorted(les):
                raise ValueError(f"histogram {name} buckets out of order")
            counts = [c for _, c in s["buckets"]]
            if counts != sorted(counts):
                raise ValueError(f"histogram {name} buckets not cumulative")
            if s["sum"] is None or s["count"] is None:
                raise ValueError(f"histogram {name} missing _sum or _count")
            if s["count"] != counts[-1]:
                raise ValueError(f"histogram {name} _count != +Inf bucket")
    return families


TRACER = Tracer()
