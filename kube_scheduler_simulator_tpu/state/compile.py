"""Workload compiler: manifests -> device tensors.

This is the TPU-native replacement for the reference's per-cycle object
traversal: where the Go scheduler re-derives matches from Pod/Node objects
inside every Filter/Score call (reference:
simulator/scheduler/plugin/wrappedplugin.go:523-548), we compile the whole
workload ONCE into:

  * static per-node tensors (allocatable, allowed pods, domain indices),
  * per-pod tensors with leading axis P (requests, precompiled match rows)
    — these are the xs of the scheduling lax.scan,
  * the initial dynamic carry (resource accumulators, per-domain counts).

Already-bound pods (spec.nodeName set + status phase Running, or listed in
`bound`) are folded into the initial carry exactly like client-go informers
prime the scheduler's NodeInfo snapshots.

ONCE holds across passes too for what a pass does not change: the node
table is reused or patched by row (reuse=), what is derived of it alone is
memoised on it (state/nodes.py NodeDerived), the bound pods' rows and
aggregates are carried and patched from the pod watch (state/boundcarry.py)
and so is the volume family's state: parsed PVs, claims, classes and
CSINode counts, their arrays, and what the three builds derive of the
bound pods (state/volumecarry.py).  A pass parses the manifests that
changed and builds its pending pods' xs; handed lists instead of carries,
it seeds throw-away carries from them and runs the same code.

Numpy out of every build, one upload site.  A plugin's `build` returns
numpy arrays for its statics, its xs and its carry, never a device array:
on the chip's host every host<->device call costs ~0.2-0.35 ms whatever
its size, and a pass has 63 such leaves.  compile_workload reads what the
host needs (the decoder's skip flags, the packing bounds, the statics'
digest for the scan-cache key) off those numpy leaves, and then sends the
trees to the device once, in cw_finish's child span cw_upload (pack_tree:
one buffer per dtype; counter workload_h2d_transfers_total).  The pass's
own trees (xs, carry, argument statics, the attribution's skip masks)
STAY as they were sent (cw.packed, a PackedPass): a device buffer costs
the host ~0.055 ms to be handed out of a jitted call, so the sequential
scan of a pass of one chunk takes the five buffers and cuts its leaves
out of them inside its own executable (framework/replay.py
_packed_scan_for).  cw.xs, cw.init_carry and cw.statics still read as
trees of device arrays for whoever needs leaves (a mesh shards each, the
host-interleaved path indexes pods, a pass of many chunks slices each
chunk): unpacked on first
access, by one jitted dispatch, memoised on the workload.  The closure
statics of a changed node table are unpacked at once (upload_tree): the
jitted step closes over those arrays.  Nothing here reads a device array
back.  A new build follows the same rule: build in numpy, return numpy,
keep a host copy in `host` for whatever the decoder needs.

Two leaves do not travel every pass.  The volume family's pv_node_ok
[V, N] and on_node [N, C] are 41 MB each at 8,192 x 5,000 and change by a
row and a bit: a session's volume carry keeps them on the device
(state/resident.py) and, between the builds and the upload, hands
compile_workload the payload of a patch in their place (a few KB that
ride in the same buffers); after the upload a jitted dispatch an array
makes this pass's device array from the last pass's (child span
cw_resident_patch), and the scan takes the two arrays as arguments of
their own beside the buffers.  The builds still return the numpy arrays,
and the digest, the flags and host["volume_table"] still read host bytes.
What the carry's journal cannot say (another node table, a bucket
outgrown, a resync) goes whole in a transfer of its own, beside a payload
that writes nothing, so that the buffers keep one layout; a throw-away
carry's and an empty axis's are leaves like any other.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import Any

import jax
import numpy as np

from . import resources as res
from .nodes import (
    NodeTable,
    build_node_table,
    build_node_table_columnar,
    patch_node_table,
    patch_node_table_columnar,
)
from .boundcarry import BoundCarry, carry_of_list, pod_key, pod_request_rows
from .packed import PackedPass, pack_tree, upload_tree
from .resources import ResourceSchema
from ..utils.env import env_int
from ..utils.tracing import TRACER
from .volumecarry import VolumeCarry, carry_of_lists
from ..plugins import registry as reg
from ..plugins import (
    affinity, imagelocality, interpod, noderesources, nodevolumelimits, ports,
    taints, topologyspread, volumebinding, volumerestrictions, volumezone,
)

VOLUME_PLUGINS = ("VolumeRestrictions", "NodeVolumeLimits", "VolumeBinding", "VolumeZone")

# The plugins whose statics reach the jitted scan as ARGUMENTS: the scan
# cache keys them by shape and dtype, like xs and carry, and they are
# uploaded with these every pass; but for the one that is cluster-sized,
# VolumeBinding's pv_node_ok [V, N], which a carried session keeps on the
# device and patches (state/resident.py).  Every other plugin's statics are
# closure constants of the scan, keyed by content (statics_digest), and
# are uploaded once per content per node table.  The volume family's are
# arguments because they change with the cluster's volume objects (a PV,
# a claim or a CSINode created between two passes), which a served
# cluster creates as fast as pods.  NodeAffinity's and PodTopologySpread's
# are arguments because they change with the QUEUE: req_rows [U, N] /
# pref_rows [V, N] hold a row per node-affinity spec among the pass's
# pods, group_key [C] / dom_idx [K, N] / elig_rows [E, N] an entry per
# count group, topology key and inclusion spec among them, so as closure
# constants every pod with other terms or another selector was a new
# digest and a new executable; the axes are padded (plugins/affinity.py
# AXIS_FLOOR, plugins/topologyspread.py _bucket), the rows come from the
# node table's memo.  The rest change with nodes or the configuration
# only, with one exception that is still a closure constant and costs a
# compile per distinct set of topology keys in a pass: InterPodAffinity's
# dom_idx [T, N] (docs/wave-pipeline.md, "Statics: closure or argument").
# VolumeZone has no statics.
ARG_STATICS = ("VolumeRestrictions", "NodeVolumeLimits", "VolumeBinding",
               "NodeAffinity", "PodTopologySpread")


# the scan's default chunk: the most pods one device call takes
POD_CHUNK = 512


def pod_axis_bucket(p: int, chunk: int = POD_CHUNK) -> int:
    """The pod axis a pass of `p` pods runs on: the next power of two up
    to the chunk (1, 2, 4, ..., 512 by default), whole chunks beyond.
    The ONE rule for the pod axis: compile_workload lays the pass's xs
    and skip masks out on it, and the scan key, the packed layout, the
    resident patch's key and the scan's chunk follow from those shapes,
    so a pass of a count the process
    has not seen is a compile only where its bucket is new.  The rows
    past `p` are pad rows (xs["is_pad"]): they never bind and nothing
    past the scan reads them.  A pass of one pod or of two pads none.
    Beyond the chunk the bucket is what the scan RUNS on, chunk after
    chunk; the upload keeps such a pass at its own count and the last
    chunk is padded when it is cut (framework/replay.py _slice_xs)."""
    if p <= chunk:
        return min(1 << max(p - 1, 0).bit_length(), chunk)
    return -(-p // chunk) * chunk


def split_statics(statics: dict[str, Any]) -> tuple[dict, dict]:
    """-> (closure statics, argument statics) of one statics dict."""
    return ({k: v for k, v in statics.items() if k not in ARG_STATICS},
            {k: v for k, v in statics.items() if k in ARG_STATICS})


@dataclass
class CompiledWorkload:
    schema: ResourceSchema
    node_table: NodeTable
    pods: list[dict]
    pod_keys: list[str]                 # "namespace/name"
    config: reg.PluginSetConfig
    statics: dict[str, Any]             # plugin name -> static pytree
    xs: dict[str, Any]                  # plugin name -> per-pod pytree (leading axis P)
    init_carry: dict[str, Any]          # carry component name -> pytree
    host: dict[str, Any] = field(default_factory=dict)  # numpy skip flags etc.
    # what compile_workload uploaded, as it was uploaded (PackedPass): the
    # sequential scan of a one-chunk pass takes it whole, and xs /
    # init_carry / the argument statics are unpacked from it on first
    # access.  None for a workload
    # built any other way (dataclasses.replace, parallel/mesh.py
    # shard_workload, by hand): its trees are what it was given
    packed: PackedPass | None = field(default=None, init=False,
                                      repr=False, compare=False)

    @property
    def n_pods(self) -> int:
        return len(self.pods)

    @property
    def n_nodes(self) -> int:
        return self.node_table.n

    @property
    def pod_axis(self) -> int:
        """The rows of xs' leading axis: the bucket of n_pods where
        compile_workload laid the pass out, n_pods itself for a workload
        built by hand."""
        return self.host.get("pod_axis", self.n_pods)

    def closure_statics(self) -> dict[str, Any]:
        """The statics a jitted scan closes over; asks no unpack."""
        return split_statics(self.__dict__["_statics"])[0]

    def arg_statics(self) -> dict[str, Any]:
        """The statics a jitted scan takes as an argument."""
        return split_statics(self.statics)[1]

    def _unpack(self) -> None:
        own = self.__dict__
        own["_xs"], own["_init_carry"], args = self.packed.take(
            self.packed.tree[:3])
        own["_statics"] = {**own["_statics"], **args}


def _unpacked_on_first_access(name: str) -> property:
    """A CompiledWorkload field that, on a workload compile_workload made,
    is a tree of device arrays only once somebody reads it (one jitted
    dispatch for the three trees, memoised on the workload)."""
    slot = "_" + name

    def get(self):
        if self.packed is not None and self.__dict__.get("_xs") is None:
            self._unpack()
        return self.__dict__[slot]

    def put(self, tree):
        self.__dict__[slot] = tree

    return property(get, put)


for _name in ("statics", "xs", "init_carry"):
    setattr(CompiledWorkload, _name, _unpacked_on_first_access(_name))


class NodeTableReuse:
    """Slim handle for compile_workload(reuse=...): holds ONLY the node
    table + schema (what the reuse path reads), so callers caching it
    between waves don't pin the previous wave's per-pod device tensors."""

    __slots__ = ("host", "schema", "node_table")

    def __init__(self, cw: CompiledWorkload):
        self.host = {"node_key": cw.host.get("node_key")}
        self.schema = cw.schema
        self.node_table = cw.node_table


def compile_workload(
    nodes: list[dict],
    pods: list[dict],
    config: reg.PluginSetConfig | None = None,
    bound_pods: list[tuple[dict, str]] | None = None,
    volumes: dict | None = None,
    reuse: "CompiledWorkload | NodeTableReuse | None" = None,
    namespaces: list[dict] | None = None,
    pod_columns=None,
    bound_carry: BoundCarry | None = None,
    volume_carry: VolumeCarry | None = None,
) -> CompiledWorkload:
    """Compile (nodes, queue pods, already-bound pods) into device tensors.

    bound_pods: (pod manifest, node name) pairs folded into the initial
    carry; they also contribute to topology/affinity counts, like the
    existing cluster pods the reference scheduler sees via informers.
    bound_carry: instead of bound_pods, the bound pods' rows and per-node
    aggregates kept from pass to pass (state/boundcarry.py); it is brought
    up to date here from what its store bound, changed or deleted since.
    A list is made into a throw-away carry: the tensors are the same.
    volumes: optional {"pvcs": [...], "pvs": [...], "storageclasses": [...],
    "csinodes": [...]} manifest lists backing the volume plugin family.
    volume_carry: instead of volumes, the family's state kept from pass to
    pass (state/volumecarry.py): the volume table, the CSINode limits and
    what the builds derive of the bound pods, brought up to date here from
    the store's events on the four kinds and from bound_carry's changed
    rows.  Lists are made into a throw-away carry: the tensors are the
    same, up to the order of the C, D and R axes.
    reuse: a prior wave's workload — its NodeTable (the expensive per-node
    manifest parse) is reused when the node set, resourceVersions, and the
    discovered resource schema are unchanged (the common case between
    scheduler waves; the engine passes its previous workload).  When only
    a bounded subset of nodes changed (<= KSS_TPU_COLUMNAR_DELTA_MAX
    rows), the table is PATCHED row-wise instead of rebuilt.
    pod_columns: the pod listing's columnar view (ColumnarManifestList
    .columns) — per-pod request rows are gathered from the bank's
    pre-parsed columns by uid instead of re-parsed per wave.
    """
    config = config or reg.PluginSetConfig()
    with TRACER.span("cw_bound_delta"):
        if bound_carry is None:
            bound_carry = carry_of_list(bound_pods or [], namespaces)
        else:
            bound_carry.pull(namespaces)
    # one child span per phase (docs/metrics.md span tree); a name each,
    # because span aggregates are by name.  BUILD_SPAN_PLUGINS lists the
    # plugins that get a cw_build_<Plugin> span
    with TRACER.span("cw_schema"):
        # columnar fast path: listings from the columnar store carry their
        # bank view (cluster/columnar.ColumnarManifestList) — schema
        # discovery, the node-table identity, and the table build all read
        # columns instead of walking N manifests
        cols = getattr(nodes, "columns", None)
        if cols is not None:
            schema = ResourceSchema.discover_columnar(
                pods, cols, bound_carry.extended_names())
            node_key = cols.identity()
        else:
            schema = ResourceSchema.discover(
                pods, nodes, bound_carry.extended_names())
            node_key = tuple(
                ((n.get("metadata") or {}).get("name", ""),
                 (n.get("metadata") or {}).get("resourceVersion", ""))
                for n in nodes
            )
    with TRACER.span("cw_node_table"):
        table = None
        if (reuse is not None
                and tuple(reuse.schema.columns) == tuple(schema.columns)
                and reuse.schema.n == schema.n):
            old_key = reuse.host.get("node_key")
            if old_key == node_key:
                schema = reuse.schema
                table = reuse.node_table
                TRACER.count("node_table_reuse_total")
            else:
                delta = _node_delta(old_key, node_key, cols)
                if delta is not None:
                    schema = reuse.schema
                    if cols is not None:
                        table = patch_node_table_columnar(
                            reuse.node_table, cols, delta, schema)
                    else:
                        table = patch_node_table(
                            reuse.node_table, nodes, delta, schema)
                    TRACER.count("node_table_delta_patches_total")
                    TRACER.count("node_table_delta_rows_total", len(delta))
        if table is None:
            table = (build_node_table_columnar(cols, schema)
                     if cols is not None else build_node_table(nodes, schema))
            TRACER.count("node_table_builds_total")

    with TRACER.span("cw_bound_delta"):
        bound_carry.place(table.names)

    statics: dict[str, Any] = {}
    xs: dict[str, Any] = {}
    init_carry: dict[str, Any] = {}
    host: dict[str, Any] = {"node_table": table, "schema": schema,
                            "node_key": node_key}
    p = len(pods)
    # the pass's pod axis: a pass of one chunk (every served pass, a
    # burst) is laid out on its bucket at the upload (_pad_pod_axis).  A
    # longer pass keeps its own count: the scan cuts it into whole chunks
    # over leaves and pads the last one itself (_slice_xs), and a pad of
    # up to 511 rows at 5,000 nodes is 2.5 MB a [P, N] leaf that the
    # unpack of 10,000 pods has no room for on the chip (chip_smoke.py's
    # wave A ran out of HBM compiling unpack_leaves at 10,240 rows;
    # my chip run, PR 50)
    rows = host["pod_axis"] = pod_axis_bucket(p) if p <= POD_CHUNK else p
    enabled = set(config.active_plugins())
    with TRACER.span("cw_core"):
        requests, nonzero = pod_request_rows(pods, schema, pod_columns)

        # core resource carry, primed with bound pods
        b_req, b_nz, b_np = bound_carry.core_sums(schema, pod_columns)
        req0 = table.initial_requested + b_req
        nz0 = table.initial_nonzero + b_nz
        np0 = table.initial_num_pods + b_np

        # Fit static/xs double as the core resource tensors even when the
        # Fit plugin itself is disabled (bind updates always need pod
        # requests).
        fit_static, fit_xs = noderesources.build_fit(
            table, schema, requests, nonzero,
            fit_args=config.args.get("NodeResourcesFit"))
        statics["core"] = fit_static
        xs["core"] = fit_xs
        # the fit check's host arrays, for PostFilter's static screen
        # (framework/preemption.py _hopeless): references, no copy
        host["fit"] = (fit_static, requests)
        from ..plugins.base import CoreCarry

        init_carry["core"] = CoreCarry(
            requested=req0, nonzero=nz0, num_pods=np0)

    # per-pod PreFilter rejects (UnschedulableAndUnresolvable), keyed by
    # the plugin whose PreFilter reports them; the earliest enabled
    # prefilter plugin in DEFAULT_ORDER wins at decode time.  A build
    # that takes host_out adds its own (NodeAffinity's conflicting terms)
    rejects: dict[str, list[str | None]] = host.setdefault(
        "prefilter_reject", {})
    if "NodeAffinity" in enabled:
        with TRACER.span("cw_build_NodeAffinity"):
            st, x = affinity.build(
                table, pods, args=config.args.get("NodeAffinity"),
                host_out=host, pod_axis=rows)
            statics["NodeAffinity"] = st
            xs["NodeAffinity"] = x
    if "NodePorts" in enabled:
        with TRACER.span("cw_build_NodePorts"):
            st, x, carry = ports.build(table, pods, bound_carry.port_rows())
            statics["NodePorts"] = st
            xs["NodePorts"] = x
            init_carry["NodePorts"] = carry
    if "ImageLocality" in enabled:
        with TRACER.span("cw_build_ImageLocality"):
            xs["ImageLocality"] = imagelocality.build(table, nodes, pods,
                                                      host_out=host)
    if "TaintToleration" in enabled:
        with TRACER.span("cw_build_TaintToleration"):
            xs["TaintToleration"] = taints.build_taints(table, pods,
                                                        host_out=host)
    if "NodeUnschedulable" in enabled:
        with TRACER.span("cw_build_NodeUnschedulable"):
            xs["NodeUnschedulable"] = taints.build_unschedulable(table, pods)
    if "NodeName" in enabled:
        with TRACER.span("cw_build_NodeName"):
            xs["NodeName"] = taints.build_nodename(table, pods)
    if "PodTopologySpread" in enabled:
        with TRACER.span("cw_build_PodTopologySpread"):
            st, x, groups, c_ext = topologyspread.build(
                table, pods, pod_axis=rows)
            statics["PodTopologySpread"] = st
            xs["PodTopologySpread"] = x
            init_carry["PodTopologySpread"] = _spread_counts(
                groups, c_ext, table.n, bound_carry)
    if any(name in enabled for name in VOLUME_PLUGINS):
        with TRACER.span("cw_volume_table"):
            if volume_carry is None:
                volume_carry = carry_of_lists(volumes)
            # the carry's own table, patched again by its next pass
            vt = host["volume_table"] = volume_carry.advance(table, bound_carry)
        if "VolumeRestrictions" in enabled:
            with TRACER.span("cw_build_VolumeRestrictions"):
                st, x, carry = volumerestrictions.build(
                    vt, table, pods, volume_carry.disks, volume_carry.rwops)
                statics["VolumeRestrictions"] = st
                xs["VolumeRestrictions"] = x
                init_carry["VolumeRestrictions"] = carry
                # upstream VolumeRestrictions' PreFilter does the PVC
                # lister lookup first, so a missing PVC rejects there
                rejects["VolumeRestrictions"] = [
                    _missing_pvc_message(vt, pod) for pod in pods
                ]
        if "NodeVolumeLimits" in enabled:
            with TRACER.span("cw_build_NodeVolumeLimits"):
                st, x, carry = nodevolumelimits.build(vt, table, pods,
                                                      volume_carry.csi)
                statics["NodeVolumeLimits"] = st
                xs["NodeVolumeLimits"] = x
                init_carry["NodeVolumeLimits"] = carry
        if "VolumeBinding" in enabled:
            with TRACER.span("cw_build_VolumeBinding"):
                st, x, carry, vb_rejects = volumebinding.build(
                    vt, table, pods, volume_carry.wffc_rows())
                statics["VolumeBinding"] = st
                xs["VolumeBinding"] = x
                init_carry["VolumeBinding"] = carry
                rejects["VolumeBinding"] = vb_rejects
                # VolumeCapacityPriority is off: Score is constant 0 for
                # every (pod, node) — keep it host-resident (np.zeros is
                # COW-cheap)
                host.setdefault("static_score_rows", {})["VolumeBinding"] = (
                    np.zeros((p, table.n), dtype=np.int8))
        if "VolumeZone" in enabled:
            with TRACER.span("cw_build_VolumeZone"):
                xs["VolumeZone"] = volumezone.build(vt, table, pods)
        axes = {"pv": vt.pv_cap.shape[0]}
        if "NodeVolumeLimits" in statics:
            axes["csi"] = statics["NodeVolumeLimits"].driver_onehot.shape[0]
        _count_rebuckets(volume_carry, axes)
    if any(any(m is not None for m in msgs) for msgs in rejects.values()):
        xs["force_unsched"] = np.asarray([
            any(msgs[i] is not None for msgs in rejects.values())
            for i in range(p)
        ], dtype=bool)
    else:
        del host["prefilter_reject"]
    for name, plugin in config.custom.items():
        if name not in enabled:
            continue
        from ..plugins.custom import build_custom

        with TRACER.span("cw_build_custom", plugin=name):
            x, msg_table = build_custom(plugin, table, pods, nodes,
                                        name=name, host_out=host)
            xs[name] = x
            host.setdefault("custom_msgs", {})[name] = msg_table
    if "InterPodAffinity" in enabled:
        with TRACER.span("cw_build_InterPodAffinity"):
            st, x, carry = interpod.build(
                table, pods, bound_carry,
                hard_weight=int((config.args.get("InterPodAffinity") or {})
                                .get("hardPodAffinityWeight")
                                or interpod.DEFAULT_HARD_POD_AFFINITY_WEIGHT),
                namespaces=namespaces,
            )
            statics["InterPodAffinity"] = st
            xs["InterPodAffinity"] = x
            init_carry["InterPodAffinity"] = carry

    with TRACER.span("cw_finish"):
        cw = CompiledWorkload(
            schema=schema,
            node_table=table,
            pods=pods,
            pod_keys=[pod_key(pod) for pod in pods],
            config=config,
            statics=statics,
            xs=xs,
            init_carry=init_carry,
            host=host,
        )
        # every build above handed numpy leaves: the decoder's flags and
        # the scan-cache key's digest are taken from the host bytes, and
        # nothing is read back after the upload
        _collect_host_flags(cw)
        closure, args = split_statics(statics)
        digest = host["_statics_fp"] = statics_digest(closure)
        # the one upload site of a pass.  The closure statics are
        # node-side tensors: where the digest is the last pass's on this
        # table, so are the device arrays (one generation; the jitted step
        # closes over them, nothing donates or writes one).  xs, the carry
        # and the argument statics travel every pass, whatever they hold,
        # with the attribution's skip masks, and stay as they travelled
        # (cw.packed): the one-chunk sequential scan unpacks them inside
        # its own executable.  A carried session's two cluster-sized leaves are
        # device arrays already; what rides in their place is the payload
        # of a patch
        with TRACER.span("cw_upload"):
            cw.statics = table.derived.generation(
                "statics_device", digest, lambda: upload_tree(closure))
            resident = _resident_leaves(volume_carry, args, init_carry)
            _swap_resident(resident, (args, init_carry),
                           lambda kept, host: kept.outgoing(host))
            TRACER.count("volume_static_args_bytes_total",
                         sum(leaf.nbytes for leaf in jax.tree.leaves(
                             [args.get(name) for name in VOLUME_PLUGINS]))
                         + sum(kept.whole_nbytes for t, _n, _l, kept
                               in resident if t == 0))
            if p <= POD_CHUNK:
                _pad_pod_axis(xs, p, rows)
            cw.packed = pack_tree(
                (xs, init_carry, args, attribution_skip_masks(cw, rows)))
            cw.xs = cw.init_carry = None        # unpacked on first access
            if resident:
                # ... and in the payload's place, the array brought up to
                # date from it
                packed = cw.packed
                with TRACER.span("cw_resident_patch"):
                    _swap_resident(
                        resident, (packed.tree[2], packed.tree[1]),
                        lambda kept, rode: kept.incoming(packed, rode))
    return cw


def _pad_pod_axis(xs: dict[str, Any], p: int, rows: int) -> None:
    """Lay the pass's xs out on the `rows` of its bucket, in place: every
    leaf padded with rows of zeros, as _slice_xs pads a last chunk, and
    the pad flag beside them wherever the bucket can hold a pad row (from
    4 rows on, whether this pass pads or not: one layout and one
    executable a bucket)."""
    if rows > p:
        def pad(a):
            assert a.shape[0] == p, (a.shape, p)
            return np.pad(a, [(0, rows - p)] + [(0, 0)] * (a.ndim - 1))

        for name, tree in xs.items():
            xs[name] = jax.tree.map(pad, tree)
    if rows > 2:
        xs["is_pad"] = np.arange(rows) >= p


def _resident_leaves(volume_carry: VolumeCarry | None, args: dict,
                     init_carry: dict) -> list:
    """(which tree: 0 the argument statics, 1 the carry; plugin, field,
    its RowsResident / CellsResident) of the leaves that a carried session
    keeps on the device: VolumeBinding's pv_node_ok [V, N] and
    NodeVolumeLimits' on_node [N, C].  None for a throw-away carry, which
    has no next pass to patch for, and for an empty axis, which has
    nothing to keep."""
    if volume_carry is None or volume_carry.feed is None:
        return []
    trees = (args, init_carry)
    return [(t, name, leaf, kept) for t, name, leaf, kept in (
        (0, "VolumeBinding", "pv_node_ok", volume_carry.pv_ok_dev),
        (1, "NodeVolumeLimits", "on_node", volume_carry.on_node_dev))
        if name in trees[t] and getattr(trees[t][name], leaf).size]


def _swap_resident(resident: list, trees: tuple, put) -> None:
    """Put into each resident leaf's place in `trees` (the argument
    statics, the carry) what `put(its resident, what is there)` makes."""
    for t, name, leaf, kept in resident:
        owner = trees[t][name]
        trees[t][name] = owner._replace(
            **{leaf: put(kept, getattr(owner, leaf))})


def statics_digest(statics: dict[str, Any]) -> str:
    """The CLOSURE statics' part of the scan-cache key (framework/
    replay.py _workload_scan_key; the argument statics, ARG_STATICS, are
    keyed there by shape and dtype and are not to be handed here): SHA-1
    over name + shape + dtype + bytes of every leaf, plugins in
    sorted-name order.  Equal statics share a compiled scan (the jitted
    step closes over them), unequal ones never do.  On
    host leaves this reads no device; on a workload's device statics it
    fetches each leaf back (replay's fallback for a workload that
    compile_workload did not make)."""
    h = hashlib.sha1()
    for name in sorted(statics):
        h.update(name.encode())
        for leaf in jax.tree.leaves(statics[name]):
            a = np.asarray(leaf)
            h.update(str(a.shape).encode())
            h.update(str(a.dtype).encode())
            h.update(a.tobytes())
    return h.hexdigest()


# the plugins whose build (with its carry priming) compile_workload wraps
# in a span named cw_build_<Plugin>; NodeResourcesFit and
# NodeResourcesBalancedAllocation build inside cw_core, custom plugins
# under cw_build_custom
BUILD_SPAN_PLUGINS = (
    "NodeAffinity", "NodePorts", "ImageLocality", "TaintToleration",
    "NodeUnschedulable", "NodeName", "PodTopologySpread",
    "VolumeRestrictions", "NodeVolumeLimits", "VolumeBinding", "VolumeZone",
    "InterPodAffinity")


def _node_delta(old_key, node_key, cols):
    """Positions whose node rows changed between waves, or None when the
    delta path doesn't apply (different membership/order, too many
    changes, incomparable keys).  Bounded by KSS_TPU_COLUMNAR_DELTA_MAX
    rows — past that a full rebuild is cheaper than the patch walk."""
    delta_max = env_int("KSS_TPU_COLUMNAR_DELTA_MAX", 256)
    if delta_max <= 0 or not isinstance(old_key, tuple):
        return None
    if cols is not None:
        # columnar identity: ("columnar", bank_id, names_version, rv bytes)
        if (len(old_key) != 4 or len(node_key) != 4
                or old_key[:3] != node_key[:3]):
            return None
        old_rv = np.frombuffer(old_key[3], dtype=np.int64)
        if len(old_rv) != cols.n:
            return None
        changed = np.flatnonzero(old_rv != cols.rv)
        return changed if 0 < len(changed) <= delta_max else None
    # dict identity: ((name, rv), ...)
    if len(old_key) != len(node_key):
        return None
    changed = []
    for i, (a, b) in enumerate(zip(old_key, node_key)):
        if a == b:
            continue
        if a[0] != b[0]:
            return None  # membership/order changed: rebuild
        changed.append(i)
        if len(changed) > delta_max:
            return None
    return np.asarray(changed, dtype=np.int64) if changed else None


def _count_rebuckets(volume_carry: VolumeCarry, axes: dict[str, int]) -> None:
    """volume_axis_rebuckets_total{axis}: a padded volume axis grew past
    the extent this carry's last pass ran at, which is the one volume
    event that still compiles a scan.  A throw-away carry (a dry run, a
    direct caller) has no last pass and counts nothing."""
    last = volume_carry.axes
    for axis, extent in axes.items():
        # + 0 too: a series that reads 0 says the axes are padded
        TRACER.inc("volume_axis_rebuckets_total",
                   int(extent > last.get(axis, extent)), axis=axis)
    volume_carry.axes = axes


def _missing_pvc_message(vt, pod: dict) -> str | None:
    """upstream volumerestrictions PreFilter: the PVC lister Get fails."""
    from .volumes import pod_pvc_keys

    for key in pod_pvc_keys(pod):
        if key not in vt.pvcs:
            return f'persistentvolumeclaim "{key.split("/", 1)[1]}" not found'
    return None


def _spread_counts(groups, c_ext: int, n: int, bound_carry) -> np.ndarray:
    """PodTopologySpread's carry: [C, N] int32, per count group (a pad row
    past the pass's groups stays 0) and per NODE the bound pods its
    selector matches, as the bound carry keeps them; the step folds them
    by domain over the nodes each pod's constraint counts on
    (plugins/topologyspread.py _fold), and a bind adds one at its node."""
    counts = np.zeros((c_ext, n), dtype=np.int32)
    if bound_carry.n:
        # group selectors were interned during build; the bound pods are
        # not part of the queue, so not in x.pm
        for c_id, (gns, _, sel) in enumerate(groups):
            counts[c_id] = bound_carry.match_counts((gns,), sel)
    return counts


def _collect_host_flags(cw: CompiledWorkload):
    """The per-pod skip flags for the annotation decoder: the builds' own
    numpy leaves (cw.xs and cw.statics are not uploaded yet)."""
    skips_filter: dict[str, np.ndarray] = {}
    skips_score: dict[str, np.ndarray] = {}
    p = cw.n_pods
    for name in cw.config.active_plugins():
        x = cw.xs.get(name)
        skips_filter[name] = getattr(x, "filter_skip", np.zeros(p, bool))
        skips_score[name] = getattr(x, "score_skip", np.zeros(p, bool))
    cw.host["filter_skip"] = skips_filter
    cw.host["score_skip"] = skips_score
    _collect_prefilter_results(cw)
    cw.host["max_filter_code"] = _max_filter_code(cw)
    if "PodTopologySpread" in cw.config.scorers():
        # static inputs for the host-side recompute of the score-ignore
        # mask (framework/replay.py _tsp_ignored_chunk)
        st = cw.statics["PodTopologySpread"]
        x = cw.xs["PodTopologySpread"]
        # per slot the dom_idx row of its group's key (-1: no constraint)
        key_of_slot = np.where(x.c_id >= 0,
                               st.group_key[np.maximum(x.c_id, 0)], -1)
        cw.host["tsp_ignore"] = (st.dom_idx < 0, key_of_slot, x.is_score)
    cw.host["score_dtypes"] = tuple(
        _score_dtype(cw, name) for name in cw.config.scorers()
    )


def attribution_skip_masks(cw: CompiledWorkload, rows: int) -> tuple:
    """([F, rows], [max(S, 1), rows]) bool: per filter and per scorer, the
    pods whose PreFilter / PreScore skipped it, from the decoder's host
    flags; the rows past the pass's pods (its bucket's pad rows, a last
    chunk's) read as skipped.  The on-device attribution reduction
    (framework/replay.py) leaves such a pod out of the plugin's sums; the
    masks ride in the pass's bool buffer."""
    p = cw.n_pods

    def rows_of(names, flags, least):
        mat = np.ones((max(len(names), least), rows), np.bool_)
        mat[:, :p] = False
        for i, name in enumerate(names):
            mat[i, :p] = np.asarray(flags.get(name, False), bool)
        return mat

    return (rows_of(cw.config.filters(), cw.host.get("filter_skip", {}), 0),
            rows_of(cw.config.scorers(), cw.host.get("score_skip", {}), 1))


def _collect_prefilter_results(cw: CompiledWorkload):
    """What the builds' PreFilterResults (host["prefilter_result"]: plugin
    -> per pod a set of node names, or None) come to for the host's
    readers, where any pod has one: host["prefilter_json"], per pod the
    prefilter-result annotation (None: the constant {}), the names sorted
    (upstream's sets.UnsortedList has no order: docs/SEMANTICS.md);
    host["prefilter_narrowed"], [P] bool, the pods that have one; and
    host["considered_count"], [P], how many nodes Filter runs on — the
    plugins' sets intersected (upstream PreFilterResult.Merge), less the
    names that are no node; every node for a pod without a result."""
    results = cw.host.get("prefilter_result")
    if not results:
        return
    from ..store.annotations import marshal

    p, idx = cw.n_pods, cw.node_table.name_idx
    rendered: list[str | None] = [None] * p
    counts = np.full(p, cw.n_nodes, dtype=np.int64)
    for i in range(p):
        own = {name: names[i] for name, names in results.items()
               if names[i] is not None}
        if not own:  # most pods of a mixed queue
            continue
        rendered[i] = marshal({name: sorted(names)
                               for name, names in own.items()})
        merged = frozenset.intersection(*own.values())
        counts[i] = sum(1 for nm in merged if nm in idx)
    cw.host["prefilter_json"] = rendered
    cw.host["prefilter_narrowed"] = np.asarray(
        [r is not None for r in rendered], dtype=bool)
    cw.host["considered_count"] = counts


# static per-plugin bound on the filter codes each kernel can emit — lets
# the replay pick the uint16 first-fail packing (framework/pipeline.py
# pack_filter_codes) when every code fits a byte
_FILTER_CODE_BOUNDS = {
    "NodeAffinity": 1, "NodeUnschedulable": 1, "NodeName": 1, "NodePorts": 1,
    "VolumeRestrictions": 1, "NodeVolumeLimits": 1, "VolumeZone": 1,
    "InterPodAffinity": 3, "VolumeBinding": 7,
}


# raw scores provably bounded by framework.MaxNodeScore (100): these
# plugins score in [0, 100] by construction, so their raws transfer as int8
# in the compact replay without a runtime overflow check
_SCORE_I8_SAFE = frozenset({
    "NodeResourcesFit", "NodeResourcesBalancedAllocation", "ImageLocality",
    "VolumeBinding",
})


def _score_dtype(cw: CompiledWorkload, name: str) -> str:
    if name in cw.host.get("static_score_rows", {}):
        # raw is a precompiled host-resident [P, N] row (NodeAffinity
        # pref_raw, custom scores): it never travels back from the device
        # — the replay's compact plan reads the host copy directly
        return "host"
    if name in _SCORE_I8_SAFE:
        return "i8"
    if name == "TaintToleration":
        # raw = count of intolerable PreferNoSchedule taints on the node
        if cw.node_table.max_taints <= 127:
            return "i8"
        return "i16"
    # raws that are fully precompiled per (pod, node) have an exact
    # compile-time bound (the kernels just emit the row).  NOTE: with
    # compile_workload stashing static_score_rows, NodeAffinity,
    # TaintToleration, ImageLocality, VolumeBinding, and score-bearing
    # custom plugins all return "host" above, so the TaintToleration
    # branch, the ImageLocality/VolumeBinding _SCORE_I8_SAFE entries, and
    # this block are defensive transfer-dtype fallbacks for rows built
    # without the host stash (and for custom plugins whose CustomXS
    # carries a scores field but has_score is False -> bound 0)
    x = cw.xs.get(name)
    rows = None
    if name == "NodeAffinity":
        st = cw.statics.get(name)
        # unique pref rows bound == per-pod rows bound (xs just index
        # into them)
        rows = st.pref_rows if st is not None else None
    elif cw.config.is_custom(name) and x is not None and hasattr(x, "scores"):
        rows = x.scores
    if rows is not None:
        # NOT np.abs: |int_min| overflows to a negative bound
        bound = max(int(rows.max(initial=0)), -int(rows.min(initial=0)))
        if bound <= 0x7F:
            return "i8"
        if bound <= 0x7FFF:
            return "i16"
        if bound <= 0x7FFFFFFF:
            return "i32"
        return "i64"  # replay starts its ladder at i64 directly
    # dynamic raws (PodTopologySpread, InterPodAffinity): optimistic i16,
    # the replay's widening ladder covers overflow
    return "i16"


def _max_filter_code(cw: CompiledWorkload) -> int:
    bound = 0
    for name in cw.config.filters():
        if name == "NodeResourcesFit":
            b = (1 << (cw.schema.n + 1)) - 1
        elif name == "TaintToleration":
            b = cw.node_table.max_taints
        elif name == "PodTopologySpread":
            b = 2 * topologyspread.MAX_CONSTRAINTS
        elif name in _FILTER_CODE_BOUNDS:
            b = _FILTER_CODE_BOUNDS[name]
        elif name in cw.host.get("custom_msgs", {}):
            b = len(cw.host["custom_msgs"][name])
        else:
            b = 1 << 30  # unknown plugin: force wide packing
        bound = max(bound, b)
    return bound
