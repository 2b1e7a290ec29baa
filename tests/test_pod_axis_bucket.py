"""The pod axis of a pass is a bucket (state/compile.py pod_axis_bucket):
the next power of two up to the chunk, whole chunks beyond.  Held here,
over the routes a pass can take (the packed sequential scan under the
streamed commit and under the post-pass, the scan over leaves): passes
of any count decide
every pod byte for byte as the same pods served one a pass; a count in a
bucket the route has met compiles nothing; the counters count real pods
and pad rows apart; and a carried session's resident patch has one
executable a bucket."""

from __future__ import annotations

from pathlib import Path

import pytest

from kube_scheduler_simulator_tpu.cluster.store import ObjectStore, list_shared
from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
from kube_scheduler_simulator_tpu.models.workloads import make_nodes, make_pods
from kube_scheduler_simulator_tpu.parallel.mesh import make_mesh
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.state import resident
from kube_scheduler_simulator_tpu.state.compile import (
    POD_CHUNK, NodeTableReuse, compile_workload, pod_axis_bucket)
from kube_scheduler_simulator_tpu.utils import tracing
from kube_scheduler_simulator_tpu.utils.tracing import TRACER

# BASELINE config 3's profile (benchmark cells baseline_c3_1k.* and
# baseline_c3_queue_1k.*): no PostFilter, so the commit is streamed
from test_wave_path_table import CONFIG_3, _decided  # noqa: E402

CHUNK = 64
# a new bucket each (1, 2, 4, 8, 16, 32, 32 again, 64) ...
FIRST = (1, 2, 3, 5, 9, 17, 30, 33)
# ... and other counts in the buckets met
SECOND = (4, 7, 12, 25, 31, 40, 3)


@pytest.mark.parametrize("p, chunk, rows", [
    (0, 512, 1), (1, 512, 1), (2, 512, 2), (3, 512, 4), (4, 512, 4),
    (5, 512, 8), (9, 512, 16), (17, 512, 32), (30, 512, 32), (33, 512, 64),
    (257, 512, 512), (512, 512, 512), (513, 512, 1024), (1025, 512, 1536),
    (40, 48, 48), (40, 8, 40), (7, 8, 8)])
def test_the_bucket_rule(p, chunk, rows):
    assert pod_axis_bucket(p, chunk) == rows


def test_a_pass_longer_than_the_chunk_keeps_its_count_in_the_upload():
    """Only a pass of one chunk is laid out on its bucket: a longer one
    runs over leaves, whole chunks of it, and the last chunk is padded
    where it is cut (chip_smoke.py's 10,000-pod wave had no room for
    266 more rows of every [P, N] leaf in its unpack on the chip)."""
    nodes = make_nodes(6, seed=31)
    short = compile_workload(nodes, make_pods(9, seed=33))
    assert (short.n_pods, short.pod_axis) == (9, 16)
    assert "is_pad" in short.packed.tree[0]
    long = compile_workload(nodes, make_pods(POD_CHUNK + 9, seed=33))
    assert long.pod_axis == long.n_pods == POD_CHUNK + 9
    assert "is_pad" not in long.packed.tree[0]
    assert pod_axis_bucket(long.n_pods) == 2 * POD_CHUNK


def _cluster():
    store = ObjectStore()
    for n in make_nodes(48, seed=31, taint_fraction=0.1):
        store.create("nodes", n)
    return store


def _pods():
    pods = make_pods(sum(FIRST) + sum(SECOND), seed=32, with_affinity=True,
                     with_tolerations=True)
    assert len({str(p["spec"].get("affinity")) for p in pods}) > 8
    return pods


@pytest.fixture(scope="module")
def one_a_pass():
    """Every pod of _pods() served alone, in order: what any pass of any
    count has to decide for it."""
    store, pods = _cluster(), _pods()
    engine = SchedulerEngine(store, plugin_config=PluginSetConfig(
        enabled=list(CONFIG_3)), chunk=CHUNK)
    for pod in pods:
        store.create("pods", pod)
        assert engine.schedule_pending() == 1
    engine.close()
    return _decided(store, pods)


def _counter(name):
    return TRACER.counter_totals().get(name, 0)


def _labeled(name, label, value):
    return TRACER.labeled_totals(name, label).get(value, 0)


def _misses():
    return _labeled("scan_compile_cache_total", "result", "miss")


ROUTES = {
    # route -> (engine keywords, replay_route_total's label)
    "packed": ({}, "packed"),
    "leaves": ({"mesh": lambda: make_mesh(2, dp=1)}, "leaves"),
    "post_pass": ({"pipeline_commit": lambda: False}, "packed"),
}


@pytest.mark.parametrize("route", list(ROUTES))
def test_passes_of_any_count_decide_as_one_a_pass(route, one_a_pass):
    kw, label = ROUTES[route]
    store, pods = _cluster(), _pods()
    engine = SchedulerEngine(
        store, plugin_config=PluginSetConfig(enabled=list(CONFIG_3)),
        chunk=CHUNK, **{k: v() for k, v in kw.items()})
    TRACER.reset()
    at, missed = 0, {}
    for count in FIRST + SECOND:
        for pod in pods[at:at + count]:
            store.create("pods", pod)
        at += count
        before = _misses()
        assert engine.schedule_pending() == count
        missed.setdefault(pod_axis_bucket(count, CHUNK), []).append(
            _misses() - before)
    engine.close()
    assert at == len(pods)
    assert sorted(missed) == [1, 2, 4, 8, 16, 32, 64]
    # the executable a route builds is a bucket's, not a count's: a count
    # in a bucket the route has met compiles nothing
    for rows, by_pass in missed.items():
        assert by_pass[0] <= 1 and not any(by_pass[1:]), (rows, by_pass)
    # ... every pod of every pass is decided byte for byte as alone ...
    got = _decided(store, pods)
    for name, (node, annotations) in one_a_pass.items():
        assert got[name][0] == node, name
        for key, value in annotations.items():
            assert got[name][1].get(key) == value, (name, key)
        assert set(got[name][1]) == set(annotations), name
    # ... and the counters tell real pods from pad rows
    counts = FIRST + SECOND
    assert _counter("scheduling_pass_pods_total") == len(pods)
    assert _counter("pass_pad_rows_total") == sum(
        pod_axis_bucket(c, CHUNK) - c for c in counts)
    rebuckets = sum(pod_axis_bucket(a, CHUNK) != pod_axis_bucket(b, CHUNK)
                    for a, b in zip(counts, counts[1:]))
    assert _counter("pod_axis_rebuckets_total") == rebuckets
    assert _labeled("replay_route_total", "route", label) \
        >= len(counts) - 1
    assert (_counter("commit_stream_waves_total") > 0) \
        is (route != "post_pass")


def test_a_resident_patch_has_one_executable_a_bucket():
    """tests/test_volume_resident.py's setting: a carried session whose
    two cluster-sized volume arrays stay on the device.  The patch cuts
    its payload out of the pass's packed buffers, so its key holds their
    layout: a pass of 3 pods and a pass of 4 share a bucket, a layout and
    the patch's executables."""
    from test_volume_carry import VOL_CFG, _queue, _Session

    s = _Session()
    try:
        made = []
        for count in (3, 4, 3, 4):
            s.passes += 1
            queue = _queue(str(s.passes))[:count]
            before = resident._compiled.cache_info().misses
            cw = compile_workload(
                list_shared(s.store, "nodes"), queue, VOL_CFG,
                bound_carry=s.bound, volume_carry=s.volumes, reuse=s.reuse)
            s.reuse = NodeTableReuse(cw)
            assert (cw.n_pods, cw.pod_axis) == (count, 4)
            made.append(resident._compiled.cache_info().misses - before)
        # the first pass compiles the two patches (or finds them compiled
        # by an earlier test of this process); no later one does
        assert made[1:] == [0, 0, 0], made
    finally:
        s.close()


@pytest.mark.parametrize("name", ["pass_pad_rows_total",
                                  "pod_axis_rebuckets_total"])
def test_the_counters_have_their_lines(name):
    docs = Path(__file__).resolve().parent.parent / "docs"
    assert f"`{name}" in (docs / "metrics.md").read_text()
    assert "pod_axis_bucket" in (docs / "wave-pipeline.md").read_text()
    assert name in tracing._HELP
