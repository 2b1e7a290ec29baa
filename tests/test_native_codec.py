"""Native C++ annotation codec vs pure-Python encoder: byte identity."""

import os

import pytest

from kube_scheduler_simulator_tpu.framework.replay import replay
from kube_scheduler_simulator_tpu.models.workloads import baseline_config
from kube_scheduler_simulator_tpu.native import get_lib
from kube_scheduler_simulator_tpu.state.compile import compile_workload
from kube_scheduler_simulator_tpu.store.decode import decode_pod_result

pytestmark = pytest.mark.skipif(get_lib() is None, reason="no native toolchain")


@pytest.mark.parametrize("idx,scale", [(3, 0.02), (5, 0.01)])
def test_native_matches_python(idx, scale, monkeypatch):
    nodes, pods, cfg = baseline_config(idx, scale=scale, seed=42)
    cw = compile_workload(nodes, pods, cfg)
    rr = replay(cw, chunk=64)

    native = [decode_pod_result(rr, i) for i in range(len(pods))]

    monkeypatch.setenv("KSS_TPU_DISABLE_NATIVE", "1")
    pure = [decode_pod_result(rr, i) for i in range(len(pods))]

    for i, (na, pa) in enumerate(zip(native, pure)):
        for k in pa:
            assert na[k] == pa[k], f"pod {i} key {k}\n native={na[k][:300]}\n python={pa[k][:300]}"


def test_native_escaping():
    """Message content with JSON-special and HTML-escaped characters."""
    nodes = [
        {"metadata": {"name": 'n"0'},
         "spec": {"taints": [{"key": 'a<b&"c', "value": "x\\y", "effect": "NoSchedule"}]},
         "status": {"allocatable": {"cpu": "2", "memory": "2Gi", "pods": "10"}}},
        {"metadata": {"name": "n1"},
         "status": {"allocatable": {"cpu": "2", "memory": "2Gi", "pods": "10"}}},
    ]
    pods = [{"metadata": {"name": "p", "namespace": "default"},
             "spec": {"containers": [{"name": "c", "resources": {"requests": {"cpu": "1"}}}]}}]
    from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig

    cfg = PluginSetConfig(enabled=["TaintToleration", "NodeResourcesFit"])
    cw = compile_workload(nodes, pods, cfg)
    rr = replay(cw)
    native = decode_pod_result(rr, 0)
    os.environ["KSS_TPU_DISABLE_NATIVE"] = "1"
    try:
        pure = decode_pod_result(rr, 0)
    finally:
        del os.environ["KSS_TPU_DISABLE_NATIVE"]
    assert native == pure

def test_codec_rebuilds_from_source(tmp_path):
    """`make codec` recipe: a fresh clone (no .so, or a foreign-platform
    one) must rebuild from annotation_codec.cpp and match the loader's
    library output (VERDICT r2 #10)."""
    import ctypes

    from kube_scheduler_simulator_tpu.native import build_codec

    so = str(tmp_path / "_annotation_codec.so")
    built = build_codec(so)
    assert os.path.exists(built)
    lib = ctypes.CDLL(built)
    assert lib.encode_filter_result is not None
    assert lib.encode_score_result is not None
    assert lib.codec_free is not None


def test_encode_string_map_matches_marshal():
    """The native history-record encoder is byte-identical to marshal()
    on quotes, backslashes, control chars, HTML-escaped chars, unicode."""
    import json

    from kube_scheduler_simulator_tpu.store.annotations import marshal
    from kube_scheduler_simulator_tpu.store.native_decode import encode_string_map

    cases = [
        {},
        {"k": "v"},
        {"b-key": "1", "a-key": "2"},  # sorted output
        {"blob": '{"n1":{"P":"passed"}}'},
        {"nasty": 'q"uo\\te <&> \t\n\r\b\f \x01\x1f'},
        {"uni": "üñíçødé ✓ 漢"},
    ]
    for d in cases:
        fast = encode_string_map(d)
        if fast is None:  # codec unavailable on this platform
            return
        assert fast == marshal(d)
        assert json.loads(fast) == d


def test_history_splice_matches_full_marshal():
    """Textual history append produces the same bytes as re-marshalling
    the whole parsed array."""
    import json

    from kube_scheduler_simulator_tpu.store import annotations as ann
    from kube_scheduler_simulator_tpu.store.reflector import update_result_history

    pod = {"metadata": {"name": "p"}}
    records = [
        {ann.SELECTED_NODE: "n1", ann.FILTER_RESULT: '{"n1":{"P":"passed"}}'},
        {ann.SELECTED_NODE: "", ann.FILTER_RESULT: '{"n1":{"P":"Insufficient cpu"}}'},
        {ann.SELECTED_NODE: "n2"},
    ]
    for r in records:
        update_result_history(pod, r)
    got = pod["metadata"]["annotations"][ann.RESULT_HISTORY]
    assert got == ann.marshal(records)
    assert json.loads(got) == records


def test_fused_decode_on_device_layout_strides(monkeypatch):
    """TPU fetches can return host arrays in the DEVICE layout (non-C
    strides); the fused decoder hands raw pointers to C, so a strided
    compact chunk must be renormalized, not walked as-if-contiguous
    (round-4 real-TPU parity failure: score-result read the next pod's
    value)."""
    import numpy as np

    nodes, pods, cfg = baseline_config(1, scale=0.05, seed=0)
    cw = compile_workload(nodes, pods, cfg)
    rr = replay(cw, chunk=64)
    cc = rr._compact

    def restride(a):
        # transpose-copy-transpose: same values, F-order memory like a
        # TPU minor-to-major fetch
        return np.asfortranarray(a)

    for field in ("packed", "raw8", "raw16", "raw32"):
        setattr(cc, field, [restride(x) for x in getattr(cc, field)])
        for x in getattr(cc, field):
            assert x.size == 0 or not x.flags["C_CONTIGUOUS"] or x.ndim < 2

    strided = [decode_pod_result(rr, i) for i in range(len(pods))]

    monkeypatch.setenv("KSS_TPU_DISABLE_NATIVE", "1")
    pure = [decode_pod_result(rr, i) for i in range(len(pods))]
    for i, (sa, pa) in enumerate(zip(strided, pure)):
        assert sa == pa, f"pod {i}: strided fused decode diverged"


def test_decode_chunk_into_base_offset():
    """decode_chunk_into with a chunk-local sink (base=lo) fills the same
    annotations as the whole-queue list, which a range over several
    compact chunks splits on their boundaries — the lazy read
    (store/lazy.py) decodes one chunk into such a sink."""
    nodes, pods, cfg = baseline_config(1, scale=0.05, seed=1)
    cw = compile_workload(nodes, pods, cfg)
    rr = replay(cw, chunk=4)
    from kube_scheduler_simulator_tpu.store.decode import decode_chunk_into

    whole: list = [None] * len(pods)
    decode_chunk_into(rr, 0, len(pods), whole)
    for lo in range(0, len(pods), 4):
        hi = min(lo + 4, len(pods))
        sink = [None] * (hi - lo)
        decode_chunk_into(rr, lo, hi, sink, base=lo)
        assert sink == whole[lo:hi]


def test_empty_active_mask_on_reused_cache_slot():
    """build_filter_frags must reset any_active per call: FilterFrags
    lives inside reused FilterCache slots (round-robin eviction at 8
    entries), so an empty-active-mask pod that lands on a reused slot
    used to inherit any_active=true, emit {"node":{},...} instead of {}
    — and cache the wrong blob for every later empty-mask pod of that
    ctx on that thread (ADVICE round-5 medium)."""
    import numpy as np

    from kube_scheduler_simulator_tpu.models.workloads import make_nodes, make_pods
    from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
    from kube_scheduler_simulator_tpu.store.native_decode import (
        build_context, encode_filter)

    nodes = make_nodes(3, seed=1)
    pods = make_pods(2, seed=2)
    cfg = PluginSetConfig(enabled=[
        "NodeUnschedulable", "NodeName", "TaintToleration", "NodeAffinity"])
    cw = compile_workload(nodes, pods, cfg)
    ctx = build_context(cw)
    f = len(cw.config.filters())
    codes = np.zeros((f, cw.node_table.n), np.int32)
    # churn 8 distinct non-empty masks (fills the thread-local cache),
    # so the 9th — the empty mask — lands on a round-robin-evicted slot
    for m in range(1, 9):
        active = np.array([(m >> b) & 1 for b in range(f)], np.uint8)
        assert encode_filter(ctx, codes, active).startswith("{\"")
    assert encode_filter(ctx, codes, np.zeros(f, np.uint8)) == "{}"
    # the (now-correct) cached entry serves later empty-mask pods too
    assert encode_filter(ctx, codes, np.zeros(f, np.uint8)) == "{}"
