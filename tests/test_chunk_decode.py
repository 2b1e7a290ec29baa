"""Chunk-granular native decode: parity, edge pods, re-delivery, threads.

The three decoder rungs (chunk-granular ctx_decode_chunk -> per-pod fused
ctx_decode_pod -> pure Python) must be byte-identical on every pod,
including the shapes the chunk call special-cases: prefilter-rejected
pods (Python early-out owns them), empty-active-mask pods, host-resident
score columns, ranges that start mid-chunk, width-tier re-delivery, and
concurrent chunk calls (per-call arenas must not be shared)."""

import os
import threading

import numpy as np
import pytest

from kube_scheduler_simulator_tpu.framework.replay import replay
from kube_scheduler_simulator_tpu.models.workloads import (
    baseline_config, make_nodes, make_pods)
from kube_scheduler_simulator_tpu.native import get_lib
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.state.compile import compile_workload
from kube_scheduler_simulator_tpu.store.decode import (
    decode_chunk_into, decode_pod_result)

pytestmark = pytest.mark.skipif(get_lib() is None, reason="no native toolchain")


def _decode_three_ways(rr, n, monkeypatch):
    """(chunk, per-pod fused, pure-Python) annotation lists for pods 0..n."""
    chunk: list = [None] * n
    decode_chunk_into(rr, 0, n, chunk)
    fused = [decode_pod_result(rr, i) for i in range(n)]
    monkeypatch.setenv("KSS_TPU_DISABLE_NATIVE", "1")
    try:
        pure = [decode_pod_result(rr, i) for i in range(n)]
    finally:
        monkeypatch.delenv("KSS_TPU_DISABLE_NATIVE")
    return chunk, fused, pure


def _assert_all_equal(chunk, fused, pure):
    for i, (ca, fa, pa) in enumerate(zip(chunk, fused, pure)):
        for k in pa:
            assert ca[k] == pa[k], (
                f"pod {i} key {k} (chunk vs pure)\n chunk={ca[k][:300]}\n"
                f" pure={pa[k][:300]}")
            assert fa[k] == pa[k], f"pod {i} key {k} (fused vs pure)"


def test_chunk_decode_parity_with_rejects_and_host_columns(monkeypatch):
    """Workload mixing prefilter-rejected pods (missing PVC), plain and
    affinity pods, taints, and host-resident score columns (NodeAffinity
    + VolumeBinding): all three decoder rungs byte-identical."""
    from kube_scheduler_simulator_tpu.store import annotations as ann

    nodes = make_nodes(25, seed=3, taint_fraction=0.3)
    pods = make_pods(40, seed=4, with_affinity=True, with_tolerations=True)
    # two prefilter-rejected pods (VolumeBinding: PVC does not exist),
    # placed mid-queue so chunk ranges mix rejected and decoded pods
    for j, at in enumerate((7, 23)):
        pods.insert(at, {
            "metadata": {"name": f"pvc-pod-{j}", "namespace": "default"},
            "spec": {
                "containers": [{"name": "c", "resources": {
                    "requests": {"cpu": "100m"}}}],
                "volumes": [{"name": "v", "persistentVolumeClaim": {
                    "claimName": f"missing-{j}"}}],
            },
        })
    cfg = PluginSetConfig(enabled=[
        "NodeResourcesFit", "NodeAffinity", "TaintToleration",
        "VolumeBinding"])
    cw = compile_workload(nodes, pods, cfg)
    assert "host" in cw.host["score_dtypes"]  # host column exercised
    assert "prefilter_reject" in cw.host      # reject path exercised
    rr = replay(cw, chunk=16)

    chunk, fused, pure = _decode_three_ways(rr, len(pods), monkeypatch)
    _assert_all_equal(chunk, fused, pure)
    # the rejected pods really took the early-out: empty filter blob +
    # the rejecting plugin recorded in prefilter-status
    for j, at in enumerate((7, 23)):
        assert chunk[at][ann.FILTER_RESULT] == "{}"
        assert "missing-" + str(j) in chunk[at][ann.PRE_FILTER_STATUS_RESULT] \
            or "VolumeBinding" in chunk[at][ann.PRE_FILTER_STATUS_RESULT]


def test_chunk_decode_parity_empty_active_mask(monkeypatch):
    """Pods whose every enabled Filter is PreFilter-skipped (plain pods
    under a NodeAffinity-only lineup) emit filter-result == {} with the
    score maps still populated from the host-resident column."""
    from kube_scheduler_simulator_tpu.store import annotations as ann

    nodes = make_nodes(12, seed=5)
    pods = make_pods(20, seed=6)  # no affinity: NodeAffinity skips
    cfg = PluginSetConfig(enabled=["NodeAffinity"])
    cw = compile_workload(nodes, pods, cfg)
    assert all(cw.host["filter_skip"]["NodeAffinity"])  # masks truly empty
    rr = replay(cw, chunk=8)
    chunk, fused, pure = _decode_three_ways(rr, len(pods), monkeypatch)
    _assert_all_equal(chunk, fused, pure)
    assert chunk[0][ann.FILTER_RESULT] == "{}"
    assert chunk[0][ann.SELECTED_NODE] != ""


def test_chunk_decode_width_tier_redelivery(monkeypatch):
    """A score-width overflow makes replay() re-deliver chunks from pod 0
    at a wider dtype; the chunk decoder's per-index writes must be
    idempotent and the final annotations identical to pure Python."""
    from kube_scheduler_simulator_tpu.utils.tracing import TRACER

    import sys

    # the framework package re-exports replay() under the same name, so
    # reach the MODULE through sys.modules
    replay_mod = sys.modules["kube_scheduler_simulator_tpu.framework.replay"]

    nodes, pods, cfg = baseline_config(4, scale=0.02, seed=11)
    cw = compile_workload(nodes, pods, cfg)
    # flip the overflow flag on the 3rd fetched chunk of the FIRST tier:
    # the real ladder then re-runs the scan at i32 and re-delivers every
    # chunk from pod 0 (same values — nothing actually overflowed), which
    # is exactly the re-delivery the decoder must absorb idempotently
    real_fetch = replay_mod._fetch_chunk
    state = {"fired": False, "count": 0}

    def inject_overflow(out_dev):
        c = real_fetch(out_dev)
        state["count"] += 1
        if not state["fired"] and state["count"] == 3 and "raw_overflow" in c:
            c["raw_overflow"] = np.asarray(True)
            state["fired"] = True
        return c

    monkeypatch.setattr(replay_mod, "_fetch_chunk", inject_overflow)

    out: list = [None] * len(pods)
    deliveries: list = []

    def on_chunk(rr_, lo, hi):
        deliveries.append((lo, hi))
        decode_chunk_into(rr_, lo, hi, out)

    before = TRACER.summary()["counters"].get("replay_width_retries_total", 0)
    rr = replay(cw, chunk=32, on_chunk=on_chunk)
    retries = TRACER.summary()["counters"].get(
        "replay_width_retries_total", 0) - before
    assert retries >= 1, f"no width retry triggered (deliveries={deliveries})"
    assert deliveries.count(deliveries[0]) >= 2  # chunk 0 re-delivered

    monkeypatch.setenv("KSS_TPU_DISABLE_NATIVE", "1")
    try:
        pure = [decode_pod_result(rr, i) for i in range(len(pods))]
    finally:
        monkeypatch.delenv("KSS_TPU_DISABLE_NATIVE")
    for i, (ca, pa) in enumerate(zip(out, pure)):
        assert ca == pa, f"pod {i} diverged after width-tier re-delivery"


def test_chunk_decode_width_tier_redelivery_deferred_path(monkeypatch):
    """Regression: on single-effective-core hosts replay buffers on_chunk
    callbacks until the scan drains (deferred delivery).  When a width
    tier overflows mid-stream, the buffered pre-overflow chunks must
    still be delivered BEFORE the wider rerun re-delivers them — the
    deferred path observes the same redelivery contract as the immediate
    path, so idempotent consumers see >= 2 deliveries of chunk 0."""
    import sys

    from kube_scheduler_simulator_tpu.utils import platform as plat_mod

    replay_mod = sys.modules["kube_scheduler_simulator_tpu.framework.replay"]
    monkeypatch.setattr(plat_mod, "effective_cpu_count", lambda: 1)

    nodes, pods, cfg = baseline_config(4, scale=0.02, seed=11)
    cw = compile_workload(nodes, pods, cfg)
    real_fetch = replay_mod._fetch_chunk
    state = {"fired": False, "count": 0}

    def inject_overflow(out_dev):
        c = real_fetch(out_dev)
        state["count"] += 1
        if not state["fired"] and state["count"] == 3 and "raw_overflow" in c:
            c["raw_overflow"] = np.asarray(True)
            state["fired"] = True
        return c

    monkeypatch.setattr(replay_mod, "_fetch_chunk", inject_overflow)

    out: list = [None] * len(pods)
    deliveries: list = []

    def on_chunk(rr_, lo, hi):
        deliveries.append((lo, hi))
        decode_chunk_into(rr_, lo, hi, out)

    rr = replay(cw, chunk=32, on_chunk=on_chunk)
    assert deliveries.count(deliveries[0]) >= 2, (
        f"deferred path suppressed pre-overflow re-delivery: {deliveries}")

    monkeypatch.setenv("KSS_TPU_DISABLE_NATIVE", "1")
    try:
        pure = [decode_pod_result(rr, i) for i in range(len(pods))]
    finally:
        monkeypatch.delenv("KSS_TPU_DISABLE_NATIVE")
    for i, (ca, pa) in enumerate(zip(out, pure)):
        assert ca == pa, f"pod {i} diverged after deferred re-delivery"


def _localize_ndarrays(root) -> None:
    """Replace every numpy array reachable from `root` with a
    main-thread-owned copy.  The TSan harness (tests/test_native_tsan.py)
    sets KSS_TPU_TSAN_LOCALIZE=1 so the codec's input buffers are no
    longer the XLA-allocated pages jaxlib's (uninstrumented) device sync
    handed over — preload-TSan cannot see that happens-before and would
    report every input read as a race against the device memset.  The
    copy keeps the codec's OWN concurrency (worker pool, arenas, caches,
    output arrays) fully checked.  A replay result's compact chunks stay
    on the device until their first read (framework/replay.py
    CompactChunks.materialize): they are fetched here first, or the
    first decode would hand the codec the very pages this step exists
    to replace."""
    compact = getattr(root, "_compact", None)
    if compact is not None:
        for ci in range(len(compact.packed)):
            compact.materialize(ci)
    seen: set[int] = set()
    stack = [root]
    while stack:
        obj = stack.pop()
        if id(obj) in seen or obj is None:
            continue
        seen.add(id(obj))
        tmod = type(obj).__module__ or ""
        if tmod.partition(".")[0] in ("jax", "jaxlib", "builtins") \
                and not isinstance(obj, (dict, list)):
            continue  # never introspect device arrays / jax internals
        if isinstance(obj, dict):
            for k, v in list(obj.items()):
                if isinstance(v, np.ndarray):
                    obj[k] = np.array(v, copy=True)
                elif isinstance(v, (dict, list)) or hasattr(v, "__dict__") \
                        or hasattr(v, "__slots__"):
                    stack.append(v)
        elif isinstance(obj, list):
            for i, v in enumerate(obj):
                if isinstance(v, np.ndarray):
                    obj[i] = np.array(v, copy=True)
                else:
                    stack.append(v)
        elif isinstance(obj, (tuple, set, frozenset, str, bytes)):
            continue
        else:
            names = list(getattr(obj, "__dict__", {}) or ())
            for cls in type(obj).__mro__:
                names.extend(getattr(cls, "__slots__", ()))
            for k in names:
                try:
                    v = getattr(obj, k)
                except AttributeError:
                    continue
                if isinstance(v, np.ndarray):
                    setattr(obj, k, np.array(v, copy=True))
                elif isinstance(v, (dict, list)) or hasattr(v, "__dict__") \
                        or hasattr(v, "__slots__"):
                    stack.append(v)


def test_chunk_decode_threaded_soak():
    """Concurrent chunk calls over the same ReplayResult: every call gets
    its own arena, so parallel decoders (pipelined commit + a bench
    sampler, or several engines sharing a process) must never observe
    another chunk's blobs.  Ranges deliberately start mid-chunk."""
    nodes, pods, cfg = baseline_config(4, scale=0.02, seed=13)
    cw = compile_workload(nodes, pods, cfg)
    rr = replay(cw, chunk=32)
    if os.environ.get("KSS_TPU_TSAN_LOCALIZE") == "1":
        _localize_ndarrays(rr)
    n = len(pods)
    expected: list = [None] * n
    decode_chunk_into(rr, 0, n, expected)

    errors: list = []
    rng = np.random.RandomState(0)
    ranges = []
    for _ in range(24):
        lo = int(rng.randint(0, n - 1))
        hi = int(min(n, lo + 1 + rng.randint(0, 40)))
        ranges.append((lo, hi))

    def worker(my_ranges):
        try:
            for lo, hi in my_ranges:
                sink: list = [None] * (hi - lo)
                decode_chunk_into(rr, lo, hi, sink, base=lo)
                for j, a in enumerate(sink):
                    if a != expected[lo + j]:
                        errors.append(
                            f"pod {lo + j} (range {lo}..{hi}) diverged")
                        return
        except Exception as e:  # noqa: BLE001
            errors.append(repr(e))

    threads = [threading.Thread(target=worker, args=(ranges[k::4],))
               for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[:3]
