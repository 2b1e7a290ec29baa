#!/usr/bin/env python
"""Benchmark: scheduling-cycles/sec on the BASELINE configs.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"platform", "device_kind", "device_count", "extra"} — the device is
whatever jax.devices() gives; an exception or a failed parity gate is a
non-zero exit with no line.

Protocol (BASELINE.md): replay a pod queue; a completed scheduling cycle =
a pod through Filter -> Score -> Normalize -> select -> bind (the
reference counts Reserve reached).

The HEADLINE value is the END-TO-END throughput of the default config
(config 4, 10k pods x 5k nodes): warm steady-state replay with all result
tensors transferred to host — the annotations built from them ARE the
reference's product (storereflector write-back, SURVEY.md §3.2).  The
device-only number (results materialized on device, no host transfer) and
a full-annotation-decode figure are reported in `extra` along with a
config-5 (InterPodAffinity) run and an engine/serving-path measurement.

The CPU baseline divisor is the 16-way-parallel oracle
(reference_impl/parallel.py — the upstream Parallelizer fans Filter/Score
over 16 goroutines, so a single-threaded divisor would overstate the
speedup).  The sequential number is also measured for reference.  Both
run at --cpu-scale of the pod queue over the FULL node axis; per-cycle
CPU cost grows with queue position, so the reduced-scale CPU cycles/sec
OVERESTIMATES full-scale CPU throughput, keeping vs_baseline
conservative.  Known residual handicap: the oracle is Python, the
reference is Go — BASELINE.md discusses the gap.

A bit-parity gate (all five configs, --gate-scale) guards every number.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


_ORACLE_CHILD = """\
import json, resource, sys
# self-imposed address-space cap: a runaway oracle gets a MemoryError in
# its own process instead of inviting the kernel OOM killer to take the
# whole bench (round 4's exit 137).  Set here post-exec rather than via
# preexec_fn: running Python in a child forked from the
# JAX-multithreaded parent can deadlock before exec.  The parent starts
# this child with JAX_PLATFORMS=cpu: the oracle's plugin-helper imports
# pull jax in, and the chip belongs to the parent.
resource.setrlimit(resource.RLIMIT_AS, (4 << 30, 4 << 30))
sys.path.insert(0, {repo!r})
from kube_scheduler_simulator_tpu.models.workloads import baseline_config
from kube_scheduler_simulator_tpu.reference_impl.sequential import (
    SequentialScheduler)
nodes, pods, cfg = baseline_config({idx}, scale={scale}, seed={seed})
s = SequentialScheduler(nodes, pods, cfg)
w = sys.stdout
for pod in s.pods:
    anns, _ = s.schedule_one(pod)
    w.write(json.dumps(anns) + chr(10))
w.write("DONE " + str(len(s.pods)) + chr(10))
"""


def stream_oracle_parity(idx: int, scale: float, seed: int, chunk: int = 64,
                         want_digest: bool = False, heartbeat=None) -> dict:
    """Bit-parity check: device replay vs the sequential CPU oracle,
    both sides streamed so neither ever materializes the full annotation
    product (~13 GB at 10k x 5k).

    The oracle runs in ONE separate CPU-forced subprocess (address space
    self-capped via RLIMIT_AS) and streams one pod's annotations per
    line; this process decodes the same pod from the device replay and
    compares as lines arrive, holding one pod at a time.  Round 4 ran an
    8-worker parallel oracle in-process and the kernel OOM-killed the
    whole bench on a memory-starved host (exit 137) — the parity
    machinery must never be able to take the measured process down
    with it.
    Parallel-vs-sequential oracle parity is covered by
    tests/test_parallel_oracle.py; the sequential oracle is the ground
    truth here (reference semantics: simulator/scheduler/plugin/
    wrappedplugin.go recording shim, resultstore/store.go score math).

    Returns {ok, pods, compared, keys_checked, mismatches,
    first_mismatch, sha256 (of every compared value, when want_digest),
    oracle_rc, oracle_err, oracle_seconds, replay_seconds}."""
    import hashlib
    import os as _os
    import subprocess as _sp
    import tempfile

    from kube_scheduler_simulator_tpu.framework.replay import replay
    from kube_scheduler_simulator_tpu.models.workloads import baseline_config
    from kube_scheduler_simulator_tpu.state.compile import compile_workload
    from kube_scheduler_simulator_tpu.store.decode import decode_pod_result

    nodes, pods, cfg = baseline_config(idx, scale=scale, seed=seed)
    t0 = time.time()
    rr = replay(compile_workload(nodes, pods, cfg), chunk=chunk)
    replay_s = time.time() - t0
    h = hashlib.sha256() if want_digest else None
    out = {"ok": False, "pods": len(pods), "compared": 0, "keys_checked": 0,
           "mismatches": 0, "first_mismatch": None, "sha256": None,
           "oracle_rc": None, "oracle_err": "",
           "replay_seconds": round(replay_s, 1)}
    t0 = time.time()
    # child stderr goes to a temp file, not a pipe: this loop only drains
    # stdout, and a filled stderr pipe would deadlock the child mid-run
    with tempfile.TemporaryFile(mode="w+") as errf:
        child = _sp.Popen(
            [sys.executable, "-c",
             _ORACLE_CHILD.format(repo=str(Path(__file__).parent), idx=idx,
                                  scale=scale, seed=seed)],
            stdout=_sp.PIPE, stderr=errf, text=True,
            env={**_os.environ, "JAX_PLATFORMS": "cpu"},
        )
        i = 0
        done = False
        try:
            for line in child.stdout:
                if heartbeat is not None:
                    heartbeat(i)
                if line.startswith("DONE "):
                    done = int(line[5:]) == len(pods) == i
                    break
                sa = json.loads(line)
                da = decode_pod_result(rr, i)
                for k, v in sa.items():
                    out["keys_checked"] += 1
                    if h is not None:
                        h.update(v.encode())
                    # .get: a device-side MISSING key is a mismatch to
                    # record, not a KeyError that kills the whole check
                    if da.get(k, "\0missing") != v:
                        out["mismatches"] += 1
                        if out["first_mismatch"] is None:
                            out["first_mismatch"] = {
                                "pod": i, "key": k,
                                "dev": da.get(k, "<missing>")[:200],
                                "oracle": v[:200]}
                i += 1
                out["compared"] = i
        finally:
            # clean DONE: give the child a moment to exit on its own so
            # the artifact records its true rc (not a kill's -9)
            try:
                child.wait(timeout=10 if done else 0.1)
            except _sp.TimeoutExpired:
                child.kill()
                child.wait()
            errf.seek(0)
            out["oracle_err"] = errf.read().strip()[-300:]
    out["oracle_rc"] = child.returncode
    out["oracle_seconds"] = round(time.time() - t0, 1)
    out["ok"] = done and out["mismatches"] == 0
    if h is not None:
        out["sha256"] = h.hexdigest()
    if not done and out["mismatches"] == 0:
        out["oracle_died"] = True  # environment failure, not a parity one
    return out


def run_parity_gate(idx: int, scale: float, seed: int,
                    _retry: bool = True) -> bool:
    r = stream_oracle_parity(idx, scale, seed)
    if r["ok"]:
        return True
    if r["first_mismatch"]:
        m = r["first_mismatch"]
        log(f"PARITY MISMATCH config {idx} pod {m['pod']} key {m['key']}\n"
            f"  dev={m['dev']}\n  seq={m['oracle']}")
        return False
    # the oracle child died (rlimit MemoryError, OOM kill, crash) — that
    # is an environment failure, not a parity failure; shed load and
    # retry once at a smaller gate shape rather than reporting value 0
    log(f"parity-gate oracle child died at pod {r['compared']}/{r['pods']} "
        f"(rc={r['oracle_rc']}): {r['oracle_err']}")
    if _retry and scale > 0.011:
        log(f"  retrying gate config {idx} at scale {scale / 4}")
        return run_parity_gate(idx, scale / 4, seed, _retry=False)
    return False


def _available_gb() -> float:
    """MemAvailable from /proc/meminfo, in GiB (inf if unreadable)."""
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) / (1 << 20)
    except OSError:
        pass
    return float("inf")


def measure_replay(idx: int, scale: float, seed: int, chunk: int, mesh_n: int,
                   decode_sample: int = 512, decode_stream: bool = True,
                   node_scale: float | None = None, quick: bool = False,
                   unroll: int = 2):
    """Compile + warm + timed device-only + timed end-to-end + timed
    ANNOTATIONS-MATERIALIZED end-to-end (decode of every pod's result
    annotations streamed on_chunk, overlapping device compute — the
    product semantics: the reference's reflector writes this JSON for
    every pod, storereflector.go:87-161) for one config."""
    import numpy as np

    from kube_scheduler_simulator_tpu.framework.replay import replay
    from kube_scheduler_simulator_tpu.models.workloads import baseline_config
    from kube_scheduler_simulator_tpu.state.compile import compile_workload
    from kube_scheduler_simulator_tpu.store.decode import decode_release_batches

    nodes, pods, cfg = baseline_config(idx, scale=scale, seed=seed,
                                       node_scale=node_scale)
    log(f"config {idx}: {len(pods)} pods x {len(nodes)} nodes, plugins={cfg.enabled}")
    t0 = time.time()
    cw = compile_workload(nodes, pods, cfg)
    log(f"  compile_workload (host precompile): {time.time()-t0:.1f}s")

    mesh = None
    if mesh_n:
        from kube_scheduler_simulator_tpu.parallel.mesh import make_mesh

        shards = mesh_n
        while shards > 1 and len(nodes) % shards:
            shards -= 1
        if shards > 1:
            mesh = make_mesh(shards, dp=1)
            log(f"  mesh: node axis sharded over {shards} devices")

    t0 = time.time()
    rr = replay(cw, chunk=chunk, collect=False, mesh=mesh,
                unroll=unroll)  # XLA compile + run
    log(f"  warm-up replay: {time.time()-t0:.1f}s, scheduled {rr.scheduled}/{len(pods)}")

    dev_cps = e2e_cps = None
    if not quick:  # quick: only the streamed-decode figure is wanted
        t0 = time.time()
        rr = replay(cw, chunk=chunk, collect=False, mesh=mesh, unroll=unroll)
        dev_s = time.time() - t0
        dev_cps = len(pods) / dev_s
        log(f"  device-only replay: {dev_s:.2f}s -> {dev_cps:,.0f} cycles/s")

        # best of 2: the better run reflects transfer capability
        e2e_s = None
        for attempt in range(2):
            t0 = time.time()
            rr = replay(cw, chunk=chunk, collect=True, mesh=mesh,
                        unroll=unroll)
            dt = time.time() - t0
            log(f"  incl host transfer of result tensors (run {attempt + 1}): "
                f"{dt:.2f}s -> {len(pods)/dt:,.0f} cycles/s")
            e2e_s = dt if e2e_s is None else min(e2e_s, dt)
        e2e_cps = len(pods) / e2e_s

    dec_cps = None
    if decode_sample:
        # release-style sample (the product semantics: the reflector
        # PATCHes each pod's annotations out and holds nothing) — holding
        # the whole sample resident would measure this host's page
        # backing, not the decoder
        ds = min(decode_sample, len(pods))
        sample = {"bytes": 0}

        def _sample_pod(i, a):
            if i == 0:
                sample["bytes"] = sum(len(v) for v in a.values())

        t0 = time.time()
        decode_release_batches(rr, 0, ds, on_pod=_sample_pod)
        dec_s = time.time() - t0
        sample_bytes = sample["bytes"]
        dec_cps = ds / dec_s
        log(f"  annotation decode ({ds}-pod sample, released per batch): "
            f"{dec_s:.2f}s -> {dec_cps:,.0f} pods/s decoded "
            f"(~{sample_bytes/1024:.0f} KiB/pod)")

    # annotations-materialized end-to-end: one replay with EVERY pod's 13
    # result annotations decoded to their final JSON strings, streamed as
    # chunks land so decode overlaps later chunks' device compute.  Each
    # pod's strings are released once built (their total length recorded),
    # matching the reference's reflector — it PATCHes the annotations out
    # and holds nothing (storereflector.go:87-161) — and keeping the
    # harness's live set out of this host's >8 GB page-backing cliff
    # (docs/bench/r04-host-page-backing.json), which is a property of the
    # bench host, not of the decoder.
    di_cps = None
    if decode_stream:
        import numpy as _np

        ann_bytes = _np.zeros(len(pods), dtype=_np.int64)  # idempotent per pod

        def _on_pod(i, a):
            ann_bytes[i] = sum(len(v) for v in a.values())

        def _consume(r, lo, hi):
            # release-per-batch (decode_release_batches docstring): the
            # reference reflector holds one pod's annotations at a time
            decode_release_batches(r, lo, hi, on_pod=_on_pod)

        t0 = time.time()
        rr = replay(cw, chunk=chunk, collect=True, mesh=mesh, unroll=unroll,
                    on_chunk=_consume)
        di_s = time.time() - t0
        di_cps = len(pods) / di_s
        n_dec = int((ann_bytes > 0).sum())
        log(f"  e2e annotations materialized (streamed decode): {di_s:.2f}s "
            f"-> {di_cps:,.0f} cycles/s ({n_dec}/{len(pods)} pods decoded, "
            f"{ann_bytes.sum()/1e9:.1f} GB of annotation JSON built)")
    return {
        "pods": len(pods), "nodes": len(nodes),
        "device_only_cps": round(dev_cps, 1) if dev_cps else None,
        "incl_host_transfer_cps": round(e2e_cps, 1) if e2e_cps else None,
        "decode_inclusive_cps": round(di_cps, 1) if di_cps else None,
        "decode_pods_per_sec": round(dec_cps, 1) if dec_cps else None,
        "scheduled": rr.scheduled,
    }


def measure_engine(scale_pods: int, scale_nodes: int, seed: int,
                   interpod: bool = False, pipeline: bool = True,
                   gang_groups: int = 0, gang_members: int = 8):
    """Serving-path benchmark: ObjectStore -> SchedulerEngine.schedule_pending
    (compile -> replay -> decode -> commit, docs/wave-pipeline.md), with
    the tracer span breakdown.  interpod adds InterPodAffinity (the
    config-5 hard plugin) to the lineup and pod specs; pipeline=False
    forces the sequential post-pass commit (the pre-change baseline the
    commit_stream_overlap_seconds counter is measured against);
    gang_groups > 0 mixes that many PodGroups of gang_members pods into
    the queue with the Coscheduling plugin enabled, so the wave pays
    (and reports) the vectorized gang-quorum pass
    (docs/gang-scheduling.md)."""
    from kube_scheduler_simulator_tpu.cluster.store import ObjectStore
    from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
    from kube_scheduler_simulator_tpu.models.workloads import make_nodes, make_pods
    from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
    from kube_scheduler_simulator_tpu.utils.tracing import TRACER

    nodes = make_nodes(scale_nodes, seed=seed, taint_fraction=0.1)

    def _queue():
        pods = make_pods(scale_pods, seed=seed + 1, with_affinity=True,
                         with_tolerations=True, with_spread=True,
                         with_interpod=interpod)
        if gang_groups:
            from kube_scheduler_simulator_tpu.models.workloads import (
                make_gang_workload)

            pgs, gpods = make_gang_workload(gang_groups, gang_members,
                                            seed=seed + 4)
            return pods + gpods, pgs
        return pods, []

    pods, pgs = _queue()
    custom = {}
    enabled = [
        "NodeResourcesFit", "NodeResourcesBalancedAllocation", "NodeAffinity",
        "TaintToleration", "PodTopologySpread",
    ] + (["InterPodAffinity"] if interpod else [])
    store = ObjectStore()
    if gang_groups:
        from kube_scheduler_simulator_tpu.plugins.coscheduling import (
            Coscheduling, ensure_podgroup_resource)

        ensure_podgroup_resource(store)
        custom["Coscheduling"] = Coscheduling()
        enabled.append("Coscheduling")
    cfg = PluginSetConfig(enabled=enabled, custom=custom)
    for n in nodes:
        store.create("nodes", n)
    for pg in pgs:
        store.create("podgroups", pg)
    for p in pods:
        store.create("pods", p)
    engine = SchedulerEngine(store, plugin_config=cfg, chunk=512,
                             pipeline_commit=pipeline)
    log(f"engine path: {scale_pods} pods x {scale_nodes} nodes "
        "(store -> compile -> replay -> decode -> commit"
        f"{', pipelined' if pipeline else ', sequential post-pass'})")
    t0 = time.time()
    engine.schedule_pending()  # warm: XLA-compiles the wave's scan
    log(f"  warm engine wave (incl XLA compile): {time.time()-t0:.1f}s")
    # reset the pods (same statics fingerprint -> scan cache hit) and
    # measure the steady-state serving wave on fresh manifests
    for p in pods:
        meta = p["metadata"]
        store.delete("pods", meta["name"], meta.get("namespace"))
    fresh, _ = _queue()
    for p in fresh:
        store.create("pods", p)
    TRACER.reset()
    t0 = time.time()
    bound = engine.schedule_pending()
    total = time.time() - t0
    summary = TRACER.summary()
    spans = {k: v["total_seconds"] for k, v in summary["spans"].items()}
    for name, secs in sorted(spans.items(), key=lambda kv: -kv[1]):
        log(f"  span {name}: {secs:.2f}s")
    # the pipelined-commit win: commit time that ran DURING the replay
    # (docs/wave-pipeline.md) — plus the batched-write volume behind it
    counters = {
        k: summary["counters"][k] for k in (
            "commit_stream_overlap_seconds", "commit_stream_waves_total",
            "store_batch_writes_total", "store_batches_total",
            "replay_width_retries_total",
            "decode_chunk_calls_total", "decode_native_thread_seconds",
            "wave_attribution_seconds", "speculative_rounds_total",
            "wave_d2h_bytes_total", "d2h_on_demand_bytes_total",
            "device_chunks_spilled_total",
            "gang_groups_admitted_total", "gang_quorum_rollbacks_total",
            "gang_timeout_rejects_total", "gang_quorum_pass_seconds",
        ) if k in summary["counters"]
    }
    if counters.get("commit_stream_overlap_seconds"):
        log(f"  commit overlapped with replay: "
            f"{counters['commit_stream_overlap_seconds']:.2f}s")
    if counters.get("decode_chunk_calls_total"):
        log(f"  native chunk decode: "
            f"{counters['decode_chunk_calls_total']:.0f} calls, "
            f"{counters.get('decode_native_thread_seconds', 0.0):.2f}s of "
            f"C worker time")
    cps = scale_pods / total
    log(f"  engine: bound {bound}/{scale_pods} in {total:.2f}s -> {cps:,.0f} cycles/s")

    # lazy-decode headline (docs/wave-pipeline.md lazy-decode stage): how
    # much decode the wave DEFERRED, and what a consumer pays on first
    # read.  Cold = first GET of a pod (drains its deferred reflect +
    # decodes its whole chunk in one native call); warm = a chunk-mate
    # right after (memoized dict lookup + its own deferred write-back).
    lazy_reg = getattr(engine.reflector, "_lazy", None)
    deferred = lazy_reg.pending_count() if lazy_reg is not None else 0
    lazy_stats = {"deferred_pods": deferred,
                  "pods_materialized_in_wave": scale_pods - deferred}
    # device-residency headline (docs/wave-pipeline.md): how few bytes
    # the WAVE itself moved device->host (decision rows only in the
    # device-resident default), and what a cold read pays for the full
    # materialization (D2H + chunk decode + deferred reflect)
    if counters.get("wave_d2h_bytes_total") is not None:
        lazy_stats["wave_d2h_bytes"] = int(counters["wave_d2h_bytes_total"])
    if deferred:
        d2h0 = summary["counters"].get("d2h_on_demand_bytes_total", 0)
        sample = [p["metadata"] for p in pods[:2]]
        t0 = time.perf_counter()
        store.get("pods", sample[0]["name"], sample[0].get("namespace"))
        lazy_stats["cold_read_seconds"] = round(time.perf_counter() - t0, 6)
        lazy_stats["cold_read_d2h_bytes"] = int(
            TRACER.summary()["counters"].get("d2h_on_demand_bytes_total", 0)
            - d2h0)
        if len(sample) > 1:
            # second GET right after: pod 2 is pod 1's chunk-mate at
            # bench chunk sizes, so this is the memoized warm path
            t0 = time.perf_counter()
            store.get("pods", sample[1]["name"], sample[1].get("namespace"))
            lazy_stats["warm_read_seconds"] = round(
                time.perf_counter() - t0, 6)
        log(f"  lazy decode: {deferred}/{scale_pods} pods deferred past "
            f"the wave; wave D2H "
            f"{lazy_stats.get('wave_d2h_bytes', 0)/1e6:.1f}MB; first read "
            f"cold {lazy_stats['cold_read_seconds']*1e3:.1f}ms "
            f"({lazy_stats['cold_read_d2h_bytes']/1e6:.1f}MB materialized), "
            f"warm {lazy_stats.get('warm_read_seconds', 0)*1e3:.1f}ms")
    snap = TRACER.snapshot()
    return {"pods": scale_pods, "nodes": scale_nodes, "bound": bound,
            "cycles_per_sec": round(cps, 1),
            "lazy": lazy_stats,
            "spans": {k: round(v, 2) for k, v in spans.items()},
            "counters": {k: round(v, 3) for k, v in counters.items()},
            # the full flight-recorder snapshot (histograms + labeled
            # counters + per-plugin attribution, docs/metrics.md) rides
            # the BENCH artifact so perf rounds keep the whole surface
            "metrics": {"labeled_counters": snap["labeled_counters"],
                        "histograms": snap["histograms"]}}


def measure_gang(n_groups: int, members: int, scale_nodes: int, seed: int,
                 plain_pods: int = 0, park_groups: int = 0,
                 pipeline: bool = True):
    """Gang-workload serving benchmark (make bench-gang,
    docs/gang-scheduling.md): n_groups PodGroups of `members` pods
    (minMember == members, strict all-or-nothing) admitted through the
    vectorized quorum pass, optionally mixed with plain pods and
    `park_groups` below-quorum groups (one member made infeasible) that
    roll back to waiting.  Prints and returns the gang tracer counters
    so BENCH rounds can track gang throughput."""
    from kube_scheduler_simulator_tpu.cluster.store import ObjectStore
    from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
    from kube_scheduler_simulator_tpu.models.workloads import (
        make_gang_workload, make_nodes, make_pods)
    from kube_scheduler_simulator_tpu.plugins.coscheduling import (
        Coscheduling, ensure_podgroup_resource)
    from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
    from kube_scheduler_simulator_tpu.utils.tracing import TRACER

    def _build():
        store = ObjectStore()
        ensure_podgroup_resource(store)
        for n in make_nodes(scale_nodes, seed=seed):
            store.create("nodes", n)
        pgs, pods = make_gang_workload(n_groups, members, seed=seed + 1)
        if park_groups:
            ppgs, ppods = make_gang_workload(
                park_groups, members, seed=seed + 2, name_prefix="parked")
            for p in ppods:
                if p["metadata"]["name"].endswith("-member-000"):
                    # one infeasible member keeps the group below quorum
                    p["spec"]["containers"][0]["resources"]["requests"]["cpu"] \
                        = "9999999m"
            pgs += ppgs
            pods += ppods
        if plain_pods:
            pods += make_pods(plain_pods, seed=seed + 3)
        for pg in pgs:
            store.create("podgroups", pg)
        for p in pods:
            store.create("pods", p)
        cfg = PluginSetConfig(
            enabled=["NodeResourcesFit", "NodeResourcesBalancedAllocation",
                     "Coscheduling"],
            custom={"Coscheduling": Coscheduling()},
        )
        return pods, SchedulerEngine(store, plugin_config=cfg, chunk=512,
                                     pipeline_commit=pipeline)
    log(f"gang path: {n_groups} gangs x {members} members "
        f"(+{park_groups} below-quorum gangs, +{plain_pods} plain pods) "
        f"on {scale_nodes} nodes")
    _, warm = _build()
    t0 = time.time()
    warm.schedule_pending()  # warm: XLA-compiles the scan + quorum pass
    log(f"  warm gang wave (incl XLA compile): {time.time()-t0:.1f}s")
    warm.close()
    pods, engine = _build()
    TRACER.reset()
    t0 = time.time()
    bound = engine.schedule_pending()
    total = time.time() - t0
    summary = TRACER.summary()
    counters = {k: round(v, 6) for k, v in summary["counters"].items()
                if k.startswith("gang_")}
    for k, v in sorted(counters.items()):
        log(f"  {k}: {v}")
    pods_per_sec = len(pods) / total if total else 0.0
    log(f"  gang engine: bound {bound}/{len(pods)} in {total:.2f}s -> "
        f"{pods_per_sec:,.0f} pods/s ({len(engine.gang_parked)} parked)")
    snap = TRACER.snapshot()
    return {
        "metrics": {"labeled_counters": snap["labeled_counters"],
                    "histograms": snap["histograms"]},
        "groups": n_groups, "members": members, "nodes": scale_nodes,
        "park_groups": park_groups, "plain_pods": plain_pods,
        "bound": bound, "pods": len(pods), "parked": len(engine.gang_parked),
        "pods_per_sec": round(pods_per_sec, 1),
        "counters": counters,
    }


def _instrumented_compute_fraction(seq) -> float:
    """Fraction of a scheduling cycle spent in the per-node Filter/Score
    loops — the part upstream's 16-goroutine Parallelizer fans out.  Used
    to model a multi-core baseline when this host can't run one.  Run on
    a SHORT queue separate from the throughput measurement: the per-call
    timing wrappers inflate the total, so they must never touch the
    reported cycles/s figure."""
    acc = {"t": 0.0}

    def timed(fn):
        def wrap(*a, **kw):
            t0 = time.perf_counter()
            try:
                return fn(*a, **kw)
            finally:
                acc["t"] += time.perf_counter() - t0
        return wrap

    seq._filter = timed(seq._filter)
    seq._score = timed(seq._score)
    t0 = time.perf_counter()
    seq.schedule_all()
    total = time.perf_counter() - t0
    return min(acc["t"] / total, 0.99)


def _cpu_subprocess_json(snippet: str, prefix: str, timeout: float,
                         tag: str, relay_stderr: bool = False):
    """Run a CPU-forced bench snippet in a fresh subprocess and parse the
    one `<prefix> <json>` line it prints; None on failure (logged with
    the child's stderr tail, not the code string).  Shared by the
    under-cliff control and the engine-wave phase."""
    import os as _os
    import subprocess as _sp

    code = (
        "import json, sys; sys.path.insert(0, '.')\n"
        "from kube_scheduler_simulator_tpu.utils.platform import "
        "tune_host_allocator\n"
        "tune_host_allocator()\n"
        "import bench\n"
        + snippet
    )
    try:
        r = _sp.run([sys.executable, "-c", code], timeout=timeout,
                    capture_output=True, text=True,
                    env={**_os.environ, "JAX_PLATFORMS": "cpu"},
                    cwd=str(Path(__file__).parent))
        if relay_stderr:
            for ln in r.stderr.splitlines():
                log("  " + ln)
        return next(json.loads(ln[len(prefix) + 1:])
                    for ln in r.stdout.splitlines()
                    if ln.startswith(prefix + " "))
    except _sp.TimeoutExpired as e:
        err = (e.stderr or b"")
        err = err.decode(errors="replace") if isinstance(err, bytes) else err
        log(f"  {tag} subprocess timed out after {timeout:.0f}s; "
            f"stderr tail: {err.strip()[-300:]}")
    except StopIteration:
        log(f"  {tag} subprocess produced no result (rc={r.returncode}); "
            f"stderr tail: {r.stderr.strip()[-300:]}")
    return None


def _engine_wave_subprocess(pods: int, nodes: int, seed: int):
    """measure_engine in a fresh CPU-forced subprocess (see call site)."""
    return _cpu_subprocess_json(
        f"r = bench.measure_engine({pods}, {nodes}, {seed})\n"
        "print('EW ' + json.dumps(r))\n",
        "EW", 1200, "engine_10k_5k", relay_stderr=True)


def measure_serve(k_sessions: int, scale_pods: int, scale_nodes: int,
                  seed: int):
    """Multi-session serving benchmark (`make bench-serve`,
    docs/api.md sessions surface): K isolated SimulationSessions on one
    device, all at the SAME workload shape, scheduling concurrently.
    Reports aggregate cycles/s (total pods / wall), per-session and p99
    (slowest-session) cycles/s for a cold round (the first wave — one
    session pays the XLA compile, the rest reuse the process-level scan
    registry) and a warm round, plus the compile-cache hit rate the
    cross-session registry achieved (>= (K-1)/K for same-shape
    sessions: each distinct scan key compiles ONCE)."""
    import copy
    import threading

    import numpy as np

    from kube_scheduler_simulator_tpu.framework.replay import (
        scan_cache_stats)
    from kube_scheduler_simulator_tpu.models.workloads import (
        make_nodes, make_pods)
    from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
    from kube_scheduler_simulator_tpu.server.sessions import SessionManager
    from kube_scheduler_simulator_tpu.utils.tracing import TRACER

    enabled = [
        "NodeResourcesFit", "NodeResourcesBalancedAllocation", "NodeAffinity",
        "TaintToleration", "PodTopologySpread",
    ]
    log(f"serve path: {k_sessions} concurrent sessions x "
        f"({scale_pods} pods x {scale_nodes} nodes), shared compile cache")
    mgr = SessionManager(max_sessions=k_sessions + 1, idle_ttl=0,
                         start_scheduler=False)
    nodes = make_nodes(scale_nodes, seed=seed, taint_fraction=0.1)

    def fresh_pods():
        return make_pods(scale_pods, seed=seed + 1, with_affinity=True,
                         with_tolerations=True, with_spread=True)

    sessions = []
    for i in range(k_sessions):
        sess = mgr.create(f"bench-{i}")
        sess.di.engine.set_profiles(None)
        sess.di.engine.plugin_config = PluginSetConfig(enabled=list(enabled))
        for n in nodes:
            sess.di.store.create("nodes", copy.deepcopy(n))
        sessions.append(sess)
    cache0 = scan_cache_stats()
    TRACER.reset()

    def round_(tag: str) -> dict:
        for sess in sessions:
            for p in fresh_pods():
                sess.di.store.create("pods", p)
        barrier = threading.Barrier(k_sessions)
        walls = [0.0] * k_sessions
        bound = [0] * k_sessions
        errs: list = []

        def run(i: int):
            try:
                barrier.wait()
                t0 = time.perf_counter()
                bound[i] = sessions[i].di.engine.schedule_pending()
                walls[i] = time.perf_counter() - t0
            except Exception as e:  # surfaced below — a failed session
                errs.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=run, args=(i,), daemon=True)
                   for i in range(k_sessions)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errs:
            raise RuntimeError(f"serve round {tag}: {errs[0]}")
        per_session = [round(scale_pods / w, 1) for w in walls]
        agg = round(k_sessions * scale_pods / wall, 1)
        p99 = round(float(np.percentile(per_session, 1)), 1)
        log(f"  {tag}: aggregate {agg:,.0f} cycles/s, per-session "
            f"{sorted(per_session)} (p99 {p99:,.0f}), wall {wall:.2f}s, "
            f"bound {sum(bound)}/{k_sessions * scale_pods}")
        # drop each session's scheduled pods so the next round re-creates
        # the identical queue (same statics fingerprint -> cache hits)
        for sess in sessions:
            for p in sess.di.store.list("pods", copy_objects=False)[0][:]:
                meta = p["metadata"]
                sess.di.store.delete("pods", meta["name"],
                                     meta.get("namespace"))
        return {"aggregate_cycles_per_sec": agg,
                "p99_session_cycles_per_sec": p99,
                "per_session_cycles_per_sec": sorted(per_session),
                "wall_seconds": round(wall, 3),
                "bound": sum(bound)}

    cold = round_("cold (one shared compile)")
    warm = round_("warm (steady state)")
    cache1 = scan_cache_stats()
    hits = cache1["hits"] - cache0["hits"]
    misses = cache1["misses"] - cache0["misses"]
    hit_rate = round(hits / max(hits + misses, 1), 4)
    log(f"  compile cache: {hits} hits / {misses} misses "
        f"(rate {hit_rate:.2%}, floor {(k_sessions - 1) / k_sessions:.2%} "
        f"for same-shape sessions)")
    snap = TRACER.snapshot()
    # per-session speculative commit rate (docs/metrics.md): the measured
    # baseline cross-session wave batching starts from
    from kube_scheduler_simulator_tpu.server.sessions import (
        speculative_commit_rates)

    spec = speculative_commit_rates(TRACER)
    if spec:
        rates = {s: d["acceptRate"] for s, d in spec.items()}
        log(f"  speculative accept rate per session: {rates}")
    mgr.shutdown()
    return {"sessions": k_sessions, "pods": scale_pods, "nodes": scale_nodes,
            "cold": cold, "warm": warm,
            "compile_cache": {"hits": hits, "misses": misses,
                              "hit_rate": hit_rate,
                              "floor": round((k_sessions - 1) / k_sessions,
                                             4)},
            "speculative": spec,
            "metrics": {"labeled_counters": snap["labeled_counters"]}}


def measure_speculative(scale_pods: int, scale_nodes: int, seed: int,
                        reps: int = 3):
    """`make bench-spec`: same-process interleaved A/B of the DEFAULT
    speculative wave against the sequential scan (KSS_TPU_SPECULATIVE=0)
    at the engine shape, on two scenarios:

      * low_contention — the reserved-slot DL fleet
        (models/workloads.make_slot_pinned_workload): sparse, mostly
        disjoint feasibility, the shape where speculation turns P scan
        steps into ~ceil(P/B) batched rounds.  This is the headline A/B
        the >=1.5x acceptance bar measures.
      * contended — the standard broad-feasibility engine workload
        (every pod fits thousands of nodes), where byte-exact
        acceptance collapses and the contention controller must hand
        the wave to the scan fallback at ~scan cost.

    Reports best-of-`reps` cycles/s per arm (arms alternate within one
    process so host noise hits both), plus accept rate / rounds /
    fallbacks from the flight recorder — the keys bench_check gates."""
    import os

    from kube_scheduler_simulator_tpu.cluster.store import ObjectStore
    from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
    from kube_scheduler_simulator_tpu.models.workloads import (
        make_nodes, make_pods, make_slot_pinned_workload)
    from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
    from kube_scheduler_simulator_tpu.utils.tracing import TRACER

    def scenario(name: str, nodes: list, pods: list, enabled: list) -> dict:
        store = ObjectStore()
        for n in nodes:
            store.create("nodes", n)
        engine = SchedulerEngine(store,
                                 plugin_config=PluginSetConfig(
                                     enabled=list(enabled)), chunk=512)
        log(f"speculative A/B [{name}]: {len(pods)} pods x {len(nodes)} "
            f"nodes, {reps} reps/arm interleaved")

        def wave(spec_on: bool) -> tuple[float, int]:
            for p in pods:
                store.create("pods", p)
            prev = os.environ.get("KSS_TPU_SPECULATIVE")
            os.environ["KSS_TPU_SPECULATIVE"] = "1" if spec_on else "0"
            try:
                t0 = time.perf_counter()
                bound = engine.schedule_pending()
                wall = time.perf_counter() - t0
            finally:
                if prev is None:
                    os.environ.pop("KSS_TPU_SPECULATIVE", None)
                else:
                    os.environ["KSS_TPU_SPECULATIVE"] = prev
            for p in store.list("pods", copy_objects=False)[0][:]:
                meta = p["metadata"]
                store.delete("pods", meta["name"], meta.get("namespace"))
            return wall, bound

        # one warm wave per arm: XLA compiles (spec rungs + oracle +
        # commit on one side, the chunked scan on the other) stay out of
        # the measured reps
        wave(True)
        wave(False)
        spec_walls, seq_walls = [], []
        bound = 0
        spec_counters: dict = {}
        for r in range(reps):
            TRACER.reset()
            w, bound = wave(True)
            spec_walls.append(w)
            if r == 0:
                summary = TRACER.summary()["counters"]
                acc = TRACER.labeled_totals(
                    "speculative_accepted_total", "session").get("", 0)
                roll = TRACER.labeled_totals(
                    "speculative_rolled_back_total", "session").get("", 0)
                spec_counters = {
                    "rounds": int(summary.get("speculative_rounds_total", 0)),
                    "accepted": int(acc),
                    "rolled_back": int(roll),
                    "accept_rate": round(acc / (acc + roll), 4)
                        if acc + roll else None,
                    "fallbacks": int(sum(TRACER.labeled_totals(
                        "speculative_fallbacks_total", "session").values())),
                }
            w, _ = wave(False)
            seq_walls.append(w)
        spec_cps = round(scale_pods / min(spec_walls), 1)
        seq_cps = round(scale_pods / min(seq_walls), 1)
        fig = {
            "speculative_cycles_per_sec": spec_cps,
            "sequential_cycles_per_sec": seq_cps,
            "speedup": round(spec_cps / seq_cps, 3) if seq_cps else None,
            "bound": bound,
            **spec_counters,
        }
        engine.close()
        log(f"  [{name}] speculative {spec_cps:,.0f} vs sequential "
            f"{seq_cps:,.0f} cycles/s ({fig['speedup']}x), accept rate "
            f"{fig.get('accept_rate')}, {fig.get('rounds')} rounds, "
            f"{fig.get('fallbacks')} fallback(s)")
        return fig

    slot_nodes, slot_pods = make_slot_pinned_workload(
        scale_pods, scale_nodes, seed=seed)
    low = scenario("low_contention", slot_nodes, slot_pods,
                   ["NodeResourcesFit", "NodeResourcesBalancedAllocation",
                    "NodeAffinity"])
    broad_nodes = make_nodes(scale_nodes, seed=seed, taint_fraction=0.1)
    broad_pods = make_pods(scale_pods, seed=seed + 1, with_affinity=True,
                           with_tolerations=True, with_spread=True)
    contended = scenario("contended", broad_nodes, broad_pods,
                         ["NodeResourcesFit",
                          "NodeResourcesBalancedAllocation", "NodeAffinity",
                          "TaintToleration", "PodTopologySpread"])
    return {"pods": scale_pods, "nodes": scale_nodes,
            "low_contention": low, "contended": contended}


def measure_fuse(k_sessions: int, scale_pods: int, scale_nodes: int,
                 seed: int, reps: int = 2, window_ms: int = 200):
    """`make bench-fuse`: cross-session fused dispatch A/B
    (parallel/fuse.py).  K sessions over the SAME reserved-slot fleet
    shape schedule concurrently twice — once with fusion on
    (KSS_TPU_FUSE=1, a generous straggler window so batch-mates
    reliably meet) and once time-shared (KSS_TPU_FUSE=0) — arms
    interleaved in one process so host noise hits both.  Reports
    best-of-`reps` aggregate and p99 per-session cycles/s per arm, the
    coordinator's dispatch tallies, and asserts the parity bar IN THE
    SAME RUN: every session's bound state (nodeName + annotations per
    pod) byte-identical across arms."""
    import copy
    import os
    import threading

    import numpy as np

    from kube_scheduler_simulator_tpu.models.workloads import (
        make_slot_pinned_workload)
    from kube_scheduler_simulator_tpu.parallel.fuse import FUSE
    from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
    from kube_scheduler_simulator_tpu.server.sessions import SessionManager

    enabled = ["NodeResourcesFit", "NodeResourcesBalancedAllocation",
               "NodeAffinity"]
    nodes, pods = make_slot_pinned_workload(scale_pods, scale_nodes,
                                            seed=seed)
    log(f"fuse A/B: {k_sessions} sessions x ({scale_pods} pods x "
        f"{scale_nodes} nodes slot-pinned), fused vs time-shared")
    mgr = SessionManager(max_sessions=k_sessions + 1, idle_ttl=0,
                         start_scheduler=False)
    sessions = []
    for i in range(k_sessions):
        sess = mgr.create(f"fuse-{i}")
        sess.di.engine.set_profiles(None)
        sess.di.engine.plugin_config = PluginSetConfig(enabled=list(enabled))
        for n in nodes:
            sess.di.store.create("nodes", copy.deepcopy(n))
        sessions.append(sess)

    def wave(fuse_on: bool, capture: bool) -> tuple[float, list, list]:
        for sess in sessions:
            for p in pods:
                sess.di.store.create("pods", copy.deepcopy(p))
        prev = {k: os.environ.get(k)
                for k in ("KSS_TPU_FUSE", "KSS_TPU_FUSE_WINDOW_MS")}
        os.environ["KSS_TPU_FUSE"] = "1" if fuse_on else "0"
        os.environ["KSS_TPU_FUSE_WINDOW_MS"] = str(window_ms)
        barrier = threading.Barrier(k_sessions)
        walls = [0.0] * k_sessions
        errs: list = []

        def run(i: int):
            try:
                barrier.wait()
                t0 = time.perf_counter()
                sessions[i].di.engine.schedule_pending()
                walls[i] = time.perf_counter() - t0
            except Exception as e:
                errs.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=run, args=(i,), daemon=True)
                   for i in range(k_sessions)]
        try:
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
        finally:
            for k, v in prev.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        if errs:
            raise RuntimeError(f"fuse wave ({fuse_on=}): {errs[0]}")
        states = []
        for sess in sessions:
            state = None
            if capture:
                state = {}
                for p in sess.di.store.list("pods", copy_objects=False)[0]:
                    meta = p["metadata"]
                    state[meta["name"]] = (
                        (p.get("spec") or {}).get("nodeName"),
                        tuple(sorted((meta.get("annotations")
                                      or {}).items())))
            states.append(state)
            for p in sess.di.store.list("pods", copy_objects=False)[0][:]:
                meta = p["metadata"]
                sess.di.store.delete("pods", meta["name"],
                                     meta.get("namespace"))
        return wall, walls, states

    # one warm wave per arm: XLA compiles (the solo rungs, then the
    # fused K-stacked executables) stay out of the measured reps
    wave(True, capture=False)
    wave(False, capture=False)
    stats0 = FUSE.stats()
    fused_states = solo_states = None
    fused_aggs, fused_p99s, solo_aggs, solo_p99s = [], [], [], []
    for r in range(reps):
        capture = r == 0
        wall, walls, st = wave(True, capture=capture)
        if capture:
            fused_states = st
        fused_aggs.append(k_sessions * scale_pods / wall)
        fused_p99s.append(float(np.percentile(
            [scale_pods / w for w in walls], 1)))
        wall, walls, st = wave(False, capture=capture)
        if capture:
            solo_states = st
        solo_aggs.append(k_sessions * scale_pods / wall)
        solo_p99s.append(float(np.percentile(
            [scale_pods / w for w in walls], 1)))
    stats1 = FUSE.stats()
    mgr.shutdown()
    fused_calls = stats1["fusedDeviceCalls"] - stats0["fusedDeviceCalls"]
    tally = {k: stats1["dispatches"].get(k, 0)
             - stats0["dispatches"].get(k, 0)
             for k in ("fused", "timeshared", "window_timeout")}
    # the parity bar, asserted in the same run as the measurement: a
    # fused wave that drifted a single annotation byte is a wrong
    # answer, not a fast one
    parity = fused_states == solo_states
    if not parity:
        raise AssertionError(
            "fused vs time-shared session state diverged — parity bar "
            "violated")
    if fused_calls < 1:
        log("  WARNING: no fused device call happened in the fused arm "
            "(window too short or rungs diverged)")
    fig = {
        "sessions": k_sessions, "pods": scale_pods, "nodes": scale_nodes,
        "window_ms": window_ms,
        "fuse_aggregate_cycles_per_sec": round(max(fused_aggs), 1),
        "fuse_p99_session_cycles_per_sec": round(max(fused_p99s), 1),
        "timeshared_aggregate_cycles_per_sec": round(max(solo_aggs), 1),
        "timeshared_p99_session_cycles_per_sec": round(max(solo_p99s), 1),
        "aggregate_speedup": round(max(fused_aggs) / max(solo_aggs), 3)
            if solo_aggs and max(solo_aggs) else None,
        "fused_device_calls": fused_calls,
        "dispatches": tally,
        "parity_byte_identical": parity,
    }
    log(f"  fused {fig['fuse_aggregate_cycles_per_sec']:,.0f} vs "
        f"time-shared {fig['timeshared_aggregate_cycles_per_sec']:,.0f} "
        f"aggregate cycles/s ({fig['aggregate_speedup']}x), p99 "
        f"{fig['fuse_p99_session_cycles_per_sec']:,.0f} vs "
        f"{fig['timeshared_p99_session_cycles_per_sec']:,.0f}, "
        f"{fused_calls} fused device calls, parity OK")
    return fig


def measure_blackbox(scale_pods: int, scale_nodes: int, seed: int,
                     reps: int = 3):
    """Wave black-box overhead A/B (docs/metrics.md post-mortem dumps):
    the always-on event ring must stay within noise — same-process
    interleaved best-of-`reps` engine waves with recording enabled vs
    disabled (the KSS_TPU_BLACKBOX=0 lever), plus a byte-identity check
    on the annotations both arms produce (the recorder must never touch
    the product).  Reports on/off cycles/s and the overhead ratio
    bench_check gates (>=0.98 = the <=2% acceptance bar, noise-bound)."""
    from kube_scheduler_simulator_tpu.cluster.store import ObjectStore
    from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
    from kube_scheduler_simulator_tpu.models.workloads import (
        make_nodes, make_pods)
    from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
    from kube_scheduler_simulator_tpu.utils import blackbox

    nodes = make_nodes(scale_nodes, seed=seed, taint_fraction=0.1)
    enabled = ["NodeResourcesFit", "NodeResourcesBalancedAllocation",
               "NodeAffinity", "TaintToleration", "PodTopologySpread"]
    log(f"blackbox overhead A/B: {scale_pods} pods x {scale_nodes} nodes, "
        f"{reps} reps/arm interleaved")

    def run() -> tuple[float, dict]:
        store = ObjectStore()
        for n in nodes:
            store.create("nodes", n)
        for p in make_pods(scale_pods, seed=seed + 1, with_affinity=True,
                           with_tolerations=True, with_spread=True):
            store.create("pods", p)
        engine = SchedulerEngine(
            store, plugin_config=PluginSetConfig(enabled=enabled), chunk=512)
        t0 = time.perf_counter()
        engine.schedule_pending()
        wall = time.perf_counter() - t0
        # annotations read OUTSIDE the timed window (materializes the
        # lazy handles) — the byte-identity evidence per arm
        state = {}
        for p in store.list("pods")[0]:
            meta = p.get("metadata") or {}
            state[meta.get("name", "")] = (
                (p.get("spec") or {}).get("nodeName"),
                dict(meta.get("annotations") or {}))
        engine.close()
        return wall, state

    prev = blackbox.enabled()
    best = {True: float("inf"), False: float("inf")}
    states: dict = {}
    try:
        blackbox.set_enabled(True)
        run()  # warm: XLA compile stays out of the measured reps
        for _ in range(reps):
            for arm in (True, False):
                blackbox.set_enabled(arm)
                wall, state = run()
                best[arm] = min(best[arm], wall)
                states[arm] = state
    finally:
        blackbox.set_enabled(prev)
    identical = states.get(True) == states.get(False)
    if not identical:
        raise RuntimeError(
            "blackbox A/B produced different annotations — the recorder "
            "must never touch the product")
    on_cps = round(scale_pods / best[True], 1)
    off_cps = round(scale_pods / best[False], 1)
    ratio = round(on_cps / off_cps, 4) if off_cps else None
    log(f"  blackbox on {on_cps:,.0f} vs off {off_cps:,.0f} cycles/s "
        f"(ratio {ratio}); annotations byte-identical: {identical}")
    return {
        "pods": scale_pods, "nodes": scale_nodes,
        "on_cycles_per_sec": on_cps,
        "off_cycles_per_sec": off_cps,
        "overhead_ratio": ratio,
        "within_noise": ratio is not None and ratio >= 0.98,
        "annotations_identical": identical,
    }


def measure_history(scale_pods: int, scale_nodes: int, seed: int,
                    reps: int = 3):
    """Telemetry-history + trace-correlation overhead A/B
    (docs/metrics.md "History & correlation"): the always-on plane —
    columnar ring sampling (utils/history.py) and trace-id scope
    propagation — must cost <= 1.05x.  Same-process interleaved
    best-of-`reps` engine waves: the ON arm runs each wave under an
    explicit trace scope and takes a feeder sample per wave (the
    sampler thread's cadence, compressed); the OFF arm is the
    KSS_TPU_HISTORY=0 lever with no trace scope.  Annotations are
    asserted byte-identical across arms — the plane must never touch
    the product."""
    from kube_scheduler_simulator_tpu.cluster.store import ObjectStore
    from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
    from kube_scheduler_simulator_tpu.models.workloads import (
        make_nodes, make_pods)
    from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
    from kube_scheduler_simulator_tpu.utils import history
    from kube_scheduler_simulator_tpu.utils.blackbox import FEEDER
    from kube_scheduler_simulator_tpu.utils.tracing import TRACER

    nodes = make_nodes(scale_nodes, seed=seed, taint_fraction=0.1)
    enabled = ["NodeResourcesFit", "NodeResourcesBalancedAllocation",
               "NodeAffinity", "TaintToleration", "PodTopologySpread"]
    log(f"history overhead A/B: {scale_pods} pods x {scale_nodes} nodes, "
        f"{reps} reps/arm interleaved")

    def run(arm: bool) -> tuple[float, dict]:
        store = ObjectStore()
        for n in nodes:
            store.create("nodes", n)
        for p in make_pods(scale_pods, seed=seed + 1, with_affinity=True,
                           with_tolerations=True, with_spread=True):
            store.create("pods", p)
        engine = SchedulerEngine(
            store, plugin_config=PluginSetConfig(enabled=enabled), chunk=512)
        trace = "bench-trace" if arm else None
        t0 = time.perf_counter()
        with TRACER.trace_scope(trace):
            engine.schedule_pending()
        FEEDER.sample()   # the sampler tick (no-op shape when off)
        wall = time.perf_counter() - t0
        state = {}
        for p in store.list("pods")[0]:
            meta = p.get("metadata") or {}
            state[meta.get("name", "")] = (
                (p.get("spec") or {}).get("nodeName"),
                dict(meta.get("annotations") or {}))
        engine.close()
        return wall, state

    prev = history.enabled()
    best = {True: float("inf"), False: float("inf")}
    states: dict = {}
    try:
        history.set_enabled(True)
        run(True)  # warm: XLA compile stays out of the measured reps
        for _ in range(reps):
            for arm in (True, False):
                history.set_enabled(arm)
                wall, state = run(arm)
                best[arm] = min(best[arm], wall)
                states[arm] = state
    finally:
        history.set_enabled(prev)
    identical = states.get(True) == states.get(False)
    if not identical:
        raise RuntimeError(
            "history A/B produced different annotations — the telemetry "
            "plane must never touch the product")
    on_cps = round(scale_pods / best[True], 1)
    off_cps = round(scale_pods / best[False], 1)
    ratio = round(on_cps / off_cps, 4) if off_cps else None
    log(f"  history on {on_cps:,.0f} vs off {off_cps:,.0f} cycles/s "
        f"(ratio {ratio}); annotations byte-identical: {identical}")
    return {
        "pods": scale_pods, "nodes": scale_nodes,
        "on_cycles_per_sec": on_cps,
        "off_cycles_per_sec": off_cps,
        "overhead_ratio": ratio,
        # the <=1.05x acceptance bar: on/off >= 1/1.05 ~= 0.9524
        "within_bound": ratio is not None and ratio >= 0.95,
        "annotations_identical": identical,
    }


def measure_cpu_baseline(idx: int, cpu_scale: float, node_scale: float,
                         seed: int, parallelism: int, cache: dict, rev: str):
    from kube_scheduler_simulator_tpu.models.workloads import baseline_config
    from kube_scheduler_simulator_tpu.reference_impl.parallel import ParallelScheduler
    from kube_scheduler_simulator_tpu.reference_impl.sequential import SequentialScheduler
    from kube_scheduler_simulator_tpu.utils.platform import effective_cpu_count

    # effective (affinity-masked) count, matching main()'s forkserver
    # warm-up gate: a 1-CPU container on an 8-core host must not construct
    # ParallelScheduler with a cold forkserver after JAX threads exist
    cores = effective_cpu_count()
    out = {"cores": cores}

    # instrumented sequential run: throughput + the Filter/Score compute
    # fraction (what the upstream Parallelizer fans out)
    # "2": warm-slice protocol (cold-start transients excluded) — older
    # cached values measured a different thing and must not be reused
    skey = f"seqfrac2-c{idx}-s{cpu_scale}-ns{node_scale}-seed{seed}-{rev}"
    if skey in cache:
        out["sequential_cps"], frac = cache[skey]
        out["compute_fraction"] = round(frac, 3)
        log(f"CPU sequential baseline (cached): {out['sequential_cps']:,.1f} "
            f"cycles/s (compute fraction {frac:.2f})")
    else:
        cn, cp, ccfg = baseline_config(idx, scale=cpu_scale, seed=seed,
                                       node_scale=node_scale)
        log(f"CPU sequential baseline: {len(cp)} pods x {len(cn)} nodes")
        # warm slice first (untimed): the first big run in a process pays
        # allocator/THP/startup transients — measured 6.5 cycles/s for the
        # cold run vs 8.4 for the same oracle warmed, which would
        # UNDERSTATE the divisor and flatter vs_baseline
        wn, wp, wcfg = baseline_config(idx, scale=min(cpu_scale, 0.01),
                                       seed=seed, node_scale=node_scale)
        SequentialScheduler(wn, wp, wcfg).schedule_all()
        t0 = time.time()
        SequentialScheduler(cn, cp, ccfg).schedule_all()
        s = time.time() - t0
        out["sequential_cps"] = len(cp) / s
        # compute fraction from a separate SHORT instrumented run (the
        # wrappers bias the measured total)
        fn, fp, fcfg = baseline_config(idx, scale=min(cpu_scale, 0.01),
                                       seed=seed, node_scale=node_scale)
        frac = _instrumented_compute_fraction(SequentialScheduler(fn, fp, fcfg))
        cache[skey] = [out["sequential_cps"], frac]
        log(f"  {s:.2f}s -> {out['sequential_cps']:,.1f} cycles/s; "
            f"Filter/Score compute fraction {frac:.2f} "
            f"(pod queue at {cpu_scale}x, nodes at {node_scale}x; a shorter "
            "queue FAVORS the CPU — later pods see more bound pods)")
        out["compute_fraction"] = round(frac, 3)
    # queue-length bias: the divisor is measured on a short queue (0.05x);
    # quantify once how per-cycle cost shifts with a 4x longer queue so
    # the "is the short-queue divisor fair?" question has a number.
    # ratio > 1 means the short queue FAVORS the CPU (vs_baseline is
    # conservative); keyed without the git rev — it is a property of the
    # workload generator + oracle semantics, both frozen by parity gates
    bkey = f"qbias2-c{idx}-s{cpu_scale}-x4-ns{node_scale}-seed{seed}"
    if bkey in cache:
        out["queue_bias_ratio"] = cache[bkey]
        log(f"CPU queue-length bias (cached): {cache[bkey]:.3f}")
    else:
        bn, bp, bcfg = baseline_config(idx, scale=cpu_scale * 4, seed=seed,
                                       node_scale=node_scale)
        t0 = time.time()
        SequentialScheduler(bn, bp, bcfg).schedule_all()
        long_cps = len(bp) / (time.time() - t0)
        out["queue_bias_ratio"] = round(out["sequential_cps"] / long_cps, 3)
        cache[bkey] = out["queue_bias_ratio"]
        log(f"CPU queue-length bias: sequential at {cpu_scale*4}x queue = "
            f"{long_cps:,.1f} cycles/s -> short-queue bias ratio "
            f"{out['queue_bias_ratio']:.3f} (>1: the short-queue divisor "
            "FAVORS the CPU, vs_baseline is conservative)")

    # modeled 16-way baseline (upstream Parallelizer): Amdahl over the
    # measured compute fraction — the honest divisor when this host lacks
    # the cores to run the fan-out for real
    modeled = out["sequential_cps"] / ((1 - frac) + frac / parallelism)
    out["parallel_modeled_cps"] = modeled
    log(f"CPU parallel-{parallelism} baseline (MODELED from compute fraction; "
        f"this host has {cores} core{'s' if cores != 1 else ''}): "
        f"{modeled:,.1f} cycles/s")
    if cores > 1:
        pkey = f"par{parallelism}-c{idx}-s{cpu_scale}-ns{node_scale}-seed{seed}-{rev}"
        if pkey in cache:
            out["parallel_cps"] = cache[pkey]
            log(f"CPU parallel-{parallelism} baseline (cached): "
                f"{cache[pkey]:,.1f} cycles/s")
        else:
            cn, cp, ccfg = baseline_config(idx, scale=cpu_scale, seed=seed,
                                           node_scale=node_scale)
            # construct (spawns + handshakes the forkserver workers)
            # OUTSIDE the timed region: upstream's 16 goroutines pre-exist
            # in the scheduler process, and the old fork start method was
            # near-free COW — timing worker startup would silently
            # understate the divisor
            ps = ParallelScheduler(cn, cp, ccfg, parallelism=parallelism)
            t0 = time.time()
            ps.schedule_all()
            s = time.time() - t0
            out["parallel_cps"] = len(cp) / s
            cache[pkey] = out["parallel_cps"]
            log(f"CPU parallel-{parallelism} measured: {s:.2f}s -> "
                f"{out['parallel_cps']:,.1f} cycles/s")
    return out


def _device() -> dict:
    """What the run ran on, as JAX reports it: every JSON line names it,
    nothing is inferred from a missing chip."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", type=int, default=4, choices=[1, 2, 3, 4, 5])
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--gate-scale", type=float, default=0.05)
    ap.add_argument("--gate-configs", type=str, default="1,2,3,4,5")
    ap.add_argument("--cpu-scale", type=float, default=0.05,
                    help="pod-queue fraction for the CPU baseline run")
    ap.add_argument("--cpu-node-scale", type=float, default=1.0,
                    help="node-axis fraction for the CPU baseline; 1.0 "
                         "keeps the REAL cluster size so per-cycle cost is honest")
    ap.add_argument("--cpu-parallelism", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=1024)
    ap.add_argument("--unroll", type=int, default=2,
                    help="lax.scan unroll for the replay measurements "
                         "(the step's [N] ops are tiny, so per-iteration "
                         "overhead matters; 2 measured ~8%% faster than 1 "
                         "on the CPU backend, flat beyond)")
    ap.add_argument("--mesh", type=int, default=0,
                    help="shard the node axis over this many devices "
                         "(0: unsharded single-chip)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true", help="tiny sizes, fast")
    ap.add_argument("--gang", action="store_true",
                    help="run ONLY the gang-workload bench shape "
                         "(make bench-gang) and print its counters")
    ap.add_argument("--serve", action="store_true",
                    help="run ONLY the multi-session serving shape "
                         "(make bench-serve): K concurrent sessions, "
                         "aggregate + p99 cycles/s, compile-cache hit rate")
    ap.add_argument("--serve-sessions", type=int, default=4)
    ap.add_argument("--spec", action="store_true",
                    help="run ONLY the speculative-wave A/B shape "
                         "(make bench-spec): default speculative wave vs "
                         "KSS_TPU_SPECULATIVE=0 sequential scan, "
                         "low-contention + contention-heavy scenarios")
    ap.add_argument("--fuse", action="store_true",
                    help="run ONLY the cross-session fused-dispatch A/B "
                         "(make bench-fuse): K sessions fused "
                         "(KSS_TPU_FUSE=1) vs time-shared (=0), aggregate "
                         "+ p99 cycles/s as K scales, parity asserted")
    ap.add_argument("--skip-parity", action="store_true")
    ap.add_argument("--skip-config5", action="store_true")
    ap.add_argument("--skip-engine", action="store_true")
    args = ap.parse_args()
    if args.serve:
        # standalone multi-session shape (make bench-serve): K isolated
        # sessions on one device — no THP/forkserver machinery needed,
        # each session's workload is far under the page cliff
        fig = (measure_serve(max(args.serve_sessions, 2), 60, 30, args.seed)
               if args.smoke else
               measure_serve(max(args.serve_sessions, 4), 600, 300,
                             args.seed))
        print(json.dumps({"metric": "serve_bench",
                          "value": fig["warm"]["aggregate_cycles_per_sec"],
                          "unit": "cycles/s", **_device(),
                          "extra": {"serve": fig}}))
        return
    if args.spec:
        # standalone speculative A/B (make bench-spec): lazy waves never
        # materialize the 13 GB annotation product, so no THP machinery
        fig = (measure_speculative(200, 100, args.seed, reps=1)
               if args.smoke else
               measure_speculative(max(int(10000 * args.scale), 100),
                                   max(int(5000 * args.scale), 50),
                                   args.seed))
        print(json.dumps({
            "metric": "speculative_bench",
            "value": fig["low_contention"]["speculative_cycles_per_sec"],
            "unit": "cycles/s", **_device(),
            "extra": {"speculative": fig}}))
        return
    if args.fuse:
        # standalone fused-dispatch A/B (make bench-fuse): session
        # workloads are far under the page cliff, no THP machinery
        if args.smoke:
            ks, fig = [2], {2: measure_fuse(2, 60, 30, args.seed, reps=1)}
        else:
            ks = [2, 4, 8]
            fig = {k: measure_fuse(k, 600, 300, args.seed) for k in ks}
        headline = fig[4 if 4 in fig else ks[0]]
        extra = {f"k{k}": fig[k] for k in ks}
        if not args.smoke:
            # the big-fleet point: K=2 at the 10k x 5k slot-pinned
            # shape, one rep (compile-dominated past that); skip-safe so
            # a memory-starved host still ships the 600x300 sweep
            try:
                extra["k2_10k"] = measure_fuse(2, 10000, 5000, args.seed,
                                               reps=1)
            except Exception as e:  # noqa: BLE001 — reported, not fatal
                extra["k2_10k"] = {
                    "error": f"{type(e).__name__}: {e}"[:300]}
        print(json.dumps({
            "metric": "fuse_bench",
            "value": headline["fuse_aggregate_cycles_per_sec"],
            "unit": "cycles/s", **_device(),
            "extra": {"fuse": extra}}))
        return
    if args.gang:
        # standalone gang shape (make bench-gang): no THP/forkserver
        # machinery needed — the workload is far under the page cliff
        fig = (measure_gang(8, 4, 32, args.seed, plain_pods=20,
                            park_groups=2) if args.smoke else
               measure_gang(100, 8, 500, args.seed, plain_pods=400,
                            park_groups=10))
        print(json.dumps({"metric": "gang_bench",
                          "value": fig["pods_per_sec"],
                          "unit": "pods/s", **_device(),
                          "extra": fig}))
        return
    # THP for the malloc arenas (re-execs once, before anything heavy):
    # the annotation product is ~13 GB of live strings at full scale and
    # 4 KiB-page first-touch faults dominate past this host's ~8 GB
    # page-backing cliff; measured 450 -> 575 engine cycles/s
    from kube_scheduler_simulator_tpu.utils.platform import (
        ensure_malloc_hugepages)

    ensure_malloc_hugepages()
    # the measured multi-core divisor's parallel-oracle workers must not
    # fork from this process once JAX threads exist (deadlock hazard);
    # start their forkserver NOW, while we are still single-threaded.
    # Only multi-core hosts ever construct a ParallelScheduler (the
    # parity gate streams the sequential oracle from a subprocess).
    from kube_scheduler_simulator_tpu.utils.platform import (
        effective_cpu_count)

    if effective_cpu_count() > 1:
        from kube_scheduler_simulator_tpu.reference_impl.parallel import (
            warm_forkserver)

        warm_forkserver()
    _run(args)


def _run(args):
    from kube_scheduler_simulator_tpu.utils.platform import tune_host_allocator

    tune_host_allocator()  # string churn must reuse pages, not re-fault them
    if args.smoke:
        args.scale, args.cpu_scale, args.chunk = 0.02, 0.02, 64
        args.cpu_node_scale, args.gate_scale = 0.02, 0.01
        args.gate_configs = "4"
        args.skip_config5 = True

    import jax

    from kube_scheduler_simulator_tpu.models.workloads import BASELINE_CONFIGS

    log(f"devices: {jax.devices()}")

    # --- parity gate (all configs) --------------------------------------
    if not args.skip_parity:
        for idx in [int(x) for x in args.gate_configs.split(",") if x]:
            t0 = time.time()
            ok = run_parity_gate(idx, args.gate_scale, args.seed)
            log(f"parity gate (config {idx} @{args.gate_scale}): "
                f"{'OK' if ok else 'FAILED'} ({time.time()-t0:.1f}s)")
            if not ok:
                raise SystemExit(f"parity gate failed on config {idx}")

    # --- TPU measurements -----------------------------------------------
    main_fig = measure_replay(args.config, args.scale, args.seed, args.chunk,
                              args.mesh, unroll=args.unroll)
    extra = {"device_only_cps": main_fig["device_only_cps"],
             "incl_host_transfer_cps": main_fig["incl_host_transfer_cps"],
             "decode_pods_per_sec": main_fig["decode_pods_per_sec"]}

    if not args.skip_config5 and args.config != 5:
        # decode_sample on: config 5's decode rate (InterPodAffinity blobs
        # ride the same distinct-tuple codec) is a first-class figure —
        # round-4 verdict asked for decode_pods_per_sec at this config
        extra["config5"] = measure_replay(5, args.scale, args.seed, args.chunk,
                                          args.mesh, decode_sample=512,
                                          unroll=args.unroll)

    if args.scale >= 1.0:
        # under-cliff control: this bench host's first-touch page backing
        # collapses ~10x beyond ~8 GB resident (committed curve:
        # docs/bench/r04-host-page-backing.json), which bounds the
        # FULL-shape annotations-materialized figure at ~220 pods/s no
        # matter how fast the decoder is.  A 0.4x queue at the full node
        # shape holds ~5 GB and shows the code's sustained rate without
        # the host artifact.  Runs in a FRESH SUBPROCESS (on the CPU
        # backend) so the parent's already-touched memory cannot distort
        # the control in either direction.
        log("under-cliff control (0.4x queue, full node shape, subprocess):")
        uc = _cpu_subprocess_json(
            f"uc = bench.measure_replay({args.config}, 0.4, {args.seed}, "
            f"{args.chunk}, 0, decode_sample=0, node_scale=1.0, quick=True, "
            f"unroll={args.unroll})\n"
            "print('UC ' + json.dumps(uc))\n",
            "UC", 900, "under-cliff control")
        if uc is not None:
            extra["decode_inclusive_cps_undercliff"] = uc["decode_inclusive_cps"]
            extra["undercliff_shape"] = {"pods": uc["pods"], "nodes": uc["nodes"]}
            log(f"  under-cliff: {uc['decode_inclusive_cps']} cycles/s "
                f"({uc['pods']} pods x {uc['nodes']} nodes)")
        else:
            extra["decode_inclusive_cps_undercliff"] = None

    if not args.skip_engine:
        ep, en = (1000, 500) if not args.smoke else (50, 25)
        extra["engine"] = measure_engine(ep, en, args.seed)
        if not args.smoke:
            # the serving path at the full config-4 shape (annotations +
            # reflect included; the per-pod result JSON lives in the
            # store until the next reset, ~13 GB at 10k x 5k).  The
            # full-scale wave only runs when the HOST can hold that
            # product: a memory-starved TPU host must not trade its
            # headline artifact for a kernel OOM kill
            extra["engine_2k_1k"] = measure_engine(2000, 1000, args.seed)
            avail = _available_gb()
            if avail < 20:
                log(f"skipping engine_10k_5k: only {avail:.1f} GiB "
                    "available on this host (needs ~20 for the resident "
                    "result store)")
                extra["engine_10k_5k"] = None
            elif jax.default_backend() == "cpu":
                # fresh subprocess: the wave holds the full ~13 GB product
                # and THP allocation degrades late in a long process
                # (fragmentation) — in-process this phase measured 200-450
                # cycles/s vs 575 from a clean process.  A fresh process is
                # also the representative serving shape (a server boots,
                # then serves waves).  CPU backend only: a TPU subprocess
                # would contend with this process's chip claim.
                extra["engine_10k_5k"] = _engine_wave_subprocess(
                    max(int(10000 * args.scale), 100),
                    max(int(5000 * args.scale), 50), args.seed)
            else:
                extra["engine_10k_5k"] = measure_engine(
                    max(int(10000 * args.scale), 100),
                    max(int(5000 * args.scale), 50), args.seed)
            # the config-5 hard plugin on the serving path
            extra["engine_interpod"] = measure_engine(ep, en, args.seed,
                                                      interpod=True)

    # --- multi-session serving ------------------------------------------
    # the serve snapshot rides every committed BENCH round so bench-check
    # can gate the aggregate/p99/compile-cache-hit-rate trajectory
    # (union/skip semantics keep pre-session rounds green)
    try:
        extra["serve"] = (measure_serve(2, 50, 25, args.seed)
                          if args.smoke else
                          measure_serve(4, 600, 300, args.seed))
    except Exception as e:  # never trade the headline for the serve tap
        log(f"serve phase failed: {type(e).__name__}: {e}")
        extra["serve"] = None

    # --- speculative wave A/B -------------------------------------------
    # rides every committed BENCH round so bench_check can gate the
    # speculative cycles/s + accept-rate trajectory at the 10k x 5k
    # shape (union/skip semantics keep pre-speculative rounds green)
    if not args.skip_engine:
        try:
            if args.smoke:
                extra["speculative"] = measure_speculative(
                    200, 100, args.seed, reps=1)
            elif _available_gb() < 10:
                log("skipping speculative A/B: low host memory")
                extra["speculative"] = None
            else:
                extra["speculative"] = measure_speculative(
                    max(int(10000 * args.scale), 100),
                    max(int(5000 * args.scale), 50), args.seed)
        except Exception as e:  # never trade the headline for this tap
            log(f"speculative phase failed: {type(e).__name__}: {e}")
            extra["speculative"] = None

    # --- cross-session fused dispatch A/B -------------------------------
    # rides every committed BENCH round so bench_check can gate the
    # fused aggregate/p99 trajectory at K=4 (union/skip semantics keep
    # pre-fuse rounds green); parity asserted inside the measurement
    try:
        extra["fuse"] = (measure_fuse(2, 60, 30, args.seed, reps=1)
                         if args.smoke else
                         measure_fuse(4, 600, 300, args.seed))
    except Exception as e:  # never trade the headline for this tap
        log(f"fuse phase failed: {type(e).__name__}: {e}")
        extra["fuse"] = {"error": f"{type(e).__name__}: {e}"[:300]}

    # --- wave black box -------------------------------------------------
    # overhead A/B (on vs KSS_TPU_BLACKBOX=0) + byte-identity assert
    # rides every committed round so bench_check can gate the ratio, and
    # the HBM sampler's snapshot records what the device plane saw
    try:
        bp, bn = (60, 30) if args.smoke else (1000, 500)
        extra["blackbox"] = measure_blackbox(bp, bn, args.seed)
    except Exception as e:
        # record the FAILURE, not None: an annotation-divergence
        # raise must make bench_check refuse the round (the chaos
        # gate's own no-silently-vacuous principle), while still
        # never trading the headline line for this tap
        log(f"blackbox phase failed: {type(e).__name__}: {e}")
        extra["blackbox"] = {"error": f"{type(e).__name__}: {e}"[:300]}

    # --- telemetry history + trace correlation --------------------------
    # overhead A/B (on vs KSS_TPU_HISTORY=0) + byte-identity assert,
    # same discipline as the blackbox tap above: bench_check gates the
    # history_overhead_ratio, and a divergence raise lands as an error
    # payload that refuses the round rather than a silent skip
    try:
        hp, hn = (60, 30) if args.smoke else (1000, 500)
        extra["history"] = measure_history(hp, hn, args.seed)
    except Exception as e:
        log(f"history phase failed: {type(e).__name__}: {e}")
        extra["history"] = {"error": f"{type(e).__name__}: {e}"[:300]}
    try:
        from kube_scheduler_simulator_tpu.utils.blackbox import TELEMETRY
        extra["hbm"] = TELEMETRY.sample_once()
    except Exception as e:
        extra["hbm"] = {"error": f"{type(e).__name__}: {e}"[:200]}

    # --- CPU baseline ---------------------------------------------------
    cache_path = Path(__file__).parent / ".bench_cpu_cache.json"
    cache = json.loads(cache_path.read_text()) if cache_path.exists() else {}
    try:
        import subprocess

        rev = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, cwd=Path(__file__).parent,
        ).stdout.strip() or "norev"
    except OSError:
        rev = "norev"
    cpu = measure_cpu_baseline(
        args.config, args.cpu_scale, args.cpu_node_scale, args.seed,
        args.cpu_parallelism, cache, rev)
    try:
        cache_path.write_text(json.dumps(cache))
    except OSError:
        pass

    full = BASELINE_CONFIGS[args.config]
    shape = (f"{full['pods']}pods_{full['nodes']}nodes" if args.scale == 1.0
             else f"scale{args.scale}")
    # headline: the ANNOTATIONS-MATERIALIZED end-to-end figure — every
    # pod's result JSON decoded to its final string, the same per-pod
    # product the CPU oracle (and the reference's reflector) pays for
    metric = (f"scheduling_cycles_per_sec_e2e_annotations_config{args.config}"
              f"_{shape}")
    e2e = main_fig["decode_inclusive_cps"] or main_fig["incl_host_transfer_cps"]
    # divisor: the strongest CPU figure available — a measured multi-core
    # run when the host has cores, else the Amdahl-modeled 16-way number
    par_cps = max(cpu.get("parallel_cps", 0.0), cpu["parallel_modeled_cps"])
    extra.update({
        "cpu_parallel_modeled_cps": round(cpu["parallel_modeled_cps"], 1),
        "cpu_parallel_measured_cps": round(cpu["parallel_cps"], 1)
        if "parallel_cps" in cpu else None,
        "cpu_sequential_baseline_cps": round(cpu["sequential_cps"], 1),
        "cpu_compute_fraction": cpu.get("compute_fraction"),
        "cpu_cores_on_host": cpu["cores"],
        "cpu_parallelism": args.cpu_parallelism,
        "cpu_queue_bias_ratio": cpu.get("queue_bias_ratio"),
        "cpu_baseline_shape": {
            "pods": int(full["pods"] * args.cpu_scale),
            "nodes": int(full["nodes"] * args.cpu_node_scale),
        },
        "vs_baseline_device_only": round(main_fig["device_only_cps"] / par_cps, 1),
    })
    # record the kss-analyze verdict for the tree this round ran from:
    # bench-check refuses to compare a round produced with outstanding
    # analyzer findings (a hot-path pod-loop or a blocking-under-lock
    # hold skews exactly the metrics the gate protects)
    try:
        from tools.analysis import analysis_verdict
        extra["analysis"] = analysis_verdict()
    except Exception as e:  # never fail a bench run over the analyzer
        extra["analysis"] = {"error": f"{type(e).__name__}: {e}"[:200]}
    # the chaos verdict rides every round too (docs/fault-injection.md):
    # one quick seeded fault-plan run proving waves still complete via
    # retry/degradation with bit-identical results — bench-check refuses
    # rounds whose chaos run failed
    try:
        from tools.chaos import chaos_verdict
        extra["chaos"] = chaos_verdict(seeds=1, quick=True)
    except Exception as e:  # never fail a bench run over the harness
        extra["chaos"] = {"error": f"{type(e).__name__}: {e}"[:200]}

    print(json.dumps({
        "metric": metric,
        "value": e2e,
        "unit": "cycles/s",
        "vs_baseline": round(e2e / par_cps, 1),
        **_device(),
        "extra": extra,
    }))


if __name__ == "__main__":
    main()
