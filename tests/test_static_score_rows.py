"""Host-static score rows never travel from the device.

NodeAffinity's raw score is a precompiled [P, N] row (plugins/affinity.py
score_kernel is a pure pass-through of pref_raw), and custom plugins'
scores are precompiled the same way — so the compact replay tags them
"host" (state/compile.py _score_dtype), excludes them from the device
outputs (framework/pipeline.py build_step), and the decoder reads the
host copy (framework/replay.py / store/native_decode.py): bytes that
can stay on host never cross the device->host link.

Parity coverage for the actual annotation bytes lives in tests/test_parity.py
(configs 3-5 all carry NodeAffinity scoring); these tests pin the layout
contract itself plus byte-parity on the skip edge cases.
"""

import numpy as np

from kube_scheduler_simulator_tpu.framework.replay import replay
from kube_scheduler_simulator_tpu.models.workloads import baseline_config
from kube_scheduler_simulator_tpu.reference_impl.sequential import SequentialScheduler
from kube_scheduler_simulator_tpu.state.compile import compile_workload
from kube_scheduler_simulator_tpu.store.decode import decode_pod_result


def _assert_host_layout(cw, rr, must_include):
    scorers = cw.config.scorers()
    static = set(cw.host["static_score_rows"]) & set(scorers)
    assert must_include <= static
    for name in static:
        assert cw.host["score_dtypes"][scorers.index(name)] == "host"
    dynamic = [n for n in scorers if n not in static]
    assert dynamic, "workload must still carry dynamic scorers"
    cc = rr._compact
    host_cols = {name for g, name in cc.score_cols if g == "host"}
    assert host_cols == static
    n_transferred = sum(1 for g, _ in cc.score_cols if g != "host")
    assert n_transferred == len(dynamic)
    rows = {g: arr.shape[1] for g, arr in (
        ("raw8", cc.raw8[0]), ("raw16", cc.raw16[0]), ("raw32", cc.raw32[0]))}
    assert sum(rows.values()) == n_transferred


def test_static_rows_are_host_tagged():
    """Every scorer whose raw is a precompiled pass-through row rides the
    "host" group; dynamic scorers (carry-dependent) still travel."""
    nodes, pods, cfg = baseline_config(3, scale=0.02, seed=7)
    cw = compile_workload(nodes, pods, cfg)
    rr = replay(cw, chunk=16)
    _assert_host_layout(cw, rr, {"NodeAffinity", "TaintToleration"})


def test_imagelocality_volumebinding_rows_are_host_tagged():
    """The default-lineup statics: ImageLocality's precompiled row and
    VolumeBinding's constant-zero score stay host-resident too."""
    from kube_scheduler_simulator_tpu.models.workloads import make_nodes, make_pods
    from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig

    nodes = make_nodes(10, seed=5)
    pods = make_pods(20, seed=6, with_affinity=True, with_tolerations=True)
    cfg = PluginSetConfig(enabled=[
        "NodeResourcesFit", "NodeResourcesBalancedAllocation", "NodeAffinity",
        "TaintToleration", "ImageLocality", "VolumeBinding"])
    cw = compile_workload(nodes, pods, cfg)
    rr = replay(cw, chunk=8)
    _assert_host_layout(
        cw, rr,
        {"NodeAffinity", "TaintToleration", "ImageLocality", "VolumeBinding"})
    assert not cw.host["static_score_rows"]["VolumeBinding"].any()


def test_host_row_parity_including_score_skip():
    """Pods WITHOUT preferred terms (score_skip) and WITH them must both
    decode byte-identically to the sequential oracle when the NodeAffinity
    raw comes from the host copy."""
    nodes, pods, cfg = baseline_config(3, scale=0.02, seed=11)
    seq = SequentialScheduler(nodes, pods, cfg).schedule_all()
    cw = compile_workload(nodes, pods, cfg)
    skip = np.asarray(cw.host["score_skip"]["NodeAffinity"])
    assert skip.any() and (~skip).any(), (
        "workload must exercise both skip branches; adjust seed/scale")
    rr = replay(cw, chunk=16)
    for i, (seq_ann, seq_sel) in enumerate(seq):
        assert int(rr.selected[i]) == seq_sel
        dev_ann = decode_pod_result(rr, i)
        for key in seq_ann:
            assert dev_ann[key] == seq_ann[key], f"pod {i} key {key}"


def test_host_row_raw_of_masks_skipped_pods():
    """raw_of keeps the pre-change contract: 0 where score_skip holds."""
    nodes, pods, cfg = baseline_config(3, scale=0.02, seed=11)
    cw = compile_workload(nodes, pods, cfg)
    rr = replay(cw, chunk=16)
    na_pos = cw.config.scorers().index("NodeAffinity")
    skip = np.asarray(cw.host["score_skip"]["NodeAffinity"])
    static = cw.host["static_score_rows"]["NodeAffinity"]
    for i in range(len(pods)):
        row = rr.raw_of(i)[na_pos]
        if skip[i]:
            assert not row.any()
        else:
            assert (row == static[i]).all()


def test_custom_plugin_scores_are_host_static():
    from kube_scheduler_simulator_tpu.models.workloads import make_nodes, make_pods
    from kube_scheduler_simulator_tpu.plugins.custom import CustomPlugin
    from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig

    class NameLen(CustomPlugin):
        name = "NameLen"

        def score(self, pod, node):
            return len(node["metadata"]["name"])

    nodes = make_nodes(8, seed=3)
    pods = make_pods(12, seed=4)
    cfg = PluginSetConfig(enabled=["NodeResourcesFit", "NameLen"],
                          custom={"NameLen": NameLen()})
    cw = compile_workload(nodes, pods, cfg)
    assert "NameLen" in cw.host["static_score_rows"]
    rr = replay(cw, chunk=8)
    assert ("host", "NameLen") in rr._compact.score_cols
    pos = cw.config.scorers().index("NameLen")
    expect = np.asarray([len(n["metadata"]["name"]) for n in nodes])
    for i in range(len(pods)):
        assert (rr.raw_of(i)[pos] == expect).all()
