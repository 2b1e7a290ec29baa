"""CPU-sequential vs TPU-tensor bit-parity over the BASELINE configs.

The correctness gate of PARITY.md ("The parity protocol"): every result annotation — most
importantly finalscore-result — must be byte-identical between the scalar
sequential reference (reference_impl/sequential.py) and the scan engine
(framework/replay.py + store/decode.py), on every pod of the queue.
"""

import pytest

from kube_scheduler_simulator_tpu.framework.replay import replay
from kube_scheduler_simulator_tpu.models.workloads import baseline_config
from kube_scheduler_simulator_tpu.reference_impl.sequential import SequentialScheduler
from kube_scheduler_simulator_tpu.state.compile import compile_workload
from kube_scheduler_simulator_tpu.store.decode import decode_pod_result


def run_both(idx: int, scale: float, seed: int = 0):
    nodes, pods, cfg = baseline_config(idx, scale=scale, seed=seed)
    seq = SequentialScheduler(nodes, pods, cfg)
    seq_results = seq.schedule_all()

    cw = compile_workload(nodes, pods, cfg)
    rr = replay(cw, chunk=64)
    return seq_results, rr


def assert_parity(seq_results, rr):
    for i, (seq_ann, seq_sel) in enumerate(seq_results):
        dev_ann = decode_pod_result(rr, i)
        dev_sel = int(rr.selected[i])
        assert dev_sel == seq_sel, (
            f"pod {i}: selected node mismatch device={dev_sel} seq={seq_sel}"
        )
        for key in seq_ann:
            assert dev_ann[key] == seq_ann[key], (
                f"pod {i}: annotation {key} mismatch\n device={dev_ann[key][:500]}\n"
                f"    seq={seq_ann[key][:500]}"
            )


@pytest.mark.parametrize("idx,scale", [(1, 1.0), (2, 0.1), (3, 0.02), (4, 0.01), (5, 0.01)])
def test_baseline_config_parity(idx, scale):
    seq_results, rr = run_both(idx, scale)
    assert_parity(seq_results, rr)


def test_some_pods_schedule():
    seq_results, rr = run_both(1, 1.0)
    assert rr.scheduled > 0
    assert (rr.selected >= 0).sum() == sum(1 for _, s in seq_results if s >= 0)


@pytest.mark.parametrize("seed", [11, 23, 47])
def test_full_plugin_set_fuzz_parity(seed):
    """Catch-all: the WHOLE default filter/score plugin lineup (all 14
    tensorized plugins incl. the volume family), randomized pods with
    affinity + tolerations + spread + interpod terms, volumes, namespaces
    and a mixed node fleet — every annotation byte-identical between the
    scalar oracle and the scan."""
    import numpy as np

    from kube_scheduler_simulator_tpu.models.workloads import make_nodes, make_pods
    from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig

    rng = np.random.default_rng(seed)
    nodes = make_nodes(16, seed=seed, taint_fraction=0.3)
    pods = make_pods(24, seed=seed + 1, with_affinity=True,
                     with_tolerations=True, with_spread=True,
                     with_interpod=True)
    # sprinkle hostPorts and nodeName pins for NodePorts/NodeName coverage
    for p in pods:
        if rng.random() < 0.2:
            p["spec"]["containers"][0]["ports"] = [
                {"hostPort": int(rng.integers(30000, 30006))}]
        if rng.random() < 0.05:
            p["spec"]["nodeName"] = f"node-{int(rng.integers(16)):05d}"
    scs = [{"metadata": {"name": "standard"},
            "provisioner": "x", "volumeBindingMode": "WaitForFirstConsumer"}]
    pvcs, pvs = [], []
    for i in range(6):
        pvcs.append({"metadata": {"name": f"claim-{i}", "namespace": "default",
                                  "uid": f"uid-{i}"},
                     "spec": {"storageClassName": "standard",
                              "accessModes": ["ReadWriteOnce"],
                              "resources": {"requests": {"storage": "1Gi"}}}})
        pvs.append({"metadata": {"name": f"pv-{i}"},
                    "spec": {"capacity": {"storage": "2Gi"},
                             "accessModes": ["ReadWriteOnce"],
                             "storageClassName": "standard"}})
    for i, p in enumerate(pods[:6]):
        p["spec"]["volumes"] = [{"name": "v",
                                 "persistentVolumeClaim": {"claimName": f"claim-{i}"}}]
    volumes = {"pvcs": pvcs, "pvs": pvs, "storageclasses": scs}
    cfg = PluginSetConfig(enabled=[
        "NodeUnschedulable", "NodeName", "TaintToleration", "NodeAffinity",
        "NodePorts", "NodeResourcesFit", "VolumeRestrictions", "VolumeZone",
        "NodeVolumeLimits", "VolumeBinding", "PodTopologySpread",
        "InterPodAffinity", "NodeResourcesBalancedAllocation", "ImageLocality",
    ])
    seq_results = SequentialScheduler(nodes, pods, cfg, volumes=volumes).schedule_all()
    cw = compile_workload(nodes, pods, cfg, volumes=volumes)
    rr = replay(cw, chunk=8)
    assert_parity(seq_results, rr)
