"""Which wave path serves which profile: the table at the head of
docs/wave-pipeline.md, one case per row, read off what a wave emits.

The engine chooses scan (sequential scan / host loop), commit (streamed by the chunk worker / sequential post-pass /
host loop) and result residency (device-resident lazy / host-resident
lazy / decoded in the wave) from what it observes of the profile, the
extenders, the reflector and the residency ladder, once a wave
(`SchedulerEngine._wave_plan`), and one executor runs what it chose
(`_device_wave`; the host loop is `_schedule_host_path`).  Each case
builds a small engine with one such observation and runs one wave:
`test_wave_path` asserts the path from the spans and counters the
program already emits, `test_wave_plan` the `(scan, commit, results)`
value the plan method returned, once, for that wave."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable

import pytest

from kube_scheduler_simulator_tpu.cluster.store import ObjectStore
from kube_scheduler_simulator_tpu.framework.engine import (
    SchedulerEngine, WavePlan)
from kube_scheduler_simulator_tpu.models.workloads import (
    make_gang_workload, make_nodes, make_pods)
from kube_scheduler_simulator_tpu.plugins.coscheduling import (
    Coscheduling, ensure_podgroup_resource)
from kube_scheduler_simulator_tpu.plugins.custom import CustomPlugin
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.scheduler.debuggable import PluginExtender
from kube_scheduler_simulator_tpu.scheduler.extender import ExtenderService
from kube_scheduler_simulator_tpu.utils.tracing import TRACER

# an in-tree set with no PostFilter and nothing of the volume family:
# BASELINE config 4's plugins
BATCHABLE = ["NodeResourcesFit", "NodeResourcesBalancedAllocation",
             "NodeAffinity", "TaintToleration", "PodTopologySpread"]

# the three spans a wave's device work can open, one per wave
WAVE_SPANS = ("replay_and_decode_stream", "device_replay", "host_path_wave")


class Reserver(CustomPlugin):
    name = "Reserver"

    def reserve(self, pod, node):
        return None


class Normalizer(CustomPlugin):
    name = "Normalizer"
    default_weight = 1

    def score(self, pod, node):
        return 1

    def normalize(self, scores):
        return scores


class Observer(PluginExtender):
    def after_cycle(self, pod, annotations, result_store):
        pass


class Interceptor(PluginExtender):
    def before_filter(self, pod, node_name):
        return None


def _webhook(engine):
    engine.set_extenders(ExtenderService([
        {"urlPrefix": "http://127.0.0.1:1", "filterVerb": "filter",
         "ignorable": True}]))


def _extender(ext):
    def tweak(engine):
        engine.plugin_extenders = {"NodeResourcesFit": ext}
    return tweak


def _no_defer(engine):
    # what the remote HTTP cluster client's reflector answers
    engine.reflector.defer_supported = lambda: False


def _degraded(steps):
    # how a server reaches a lower rung: structural faults step the ladder
    def tweak(engine):
        for _ in range(steps):
            assert engine._degrade("test")
    return tweak


def _custom(*plugins, base=("NodeResourcesFit",)):
    return lambda: PluginSetConfig(
        enabled=list(base) + [p.name for p in plugins],
        custom={p.name: p for p in plugins})


@dataclass
class Row:
    id: str
    # what the engine observes
    config: Callable[[], PluginSetConfig | None] = lambda: None
    tweak: Callable | None = None
    env: dict = field(default_factory=dict)
    engine_kw: dict = field(default_factory=dict)
    gang: bool = False
    pods: int = 12                     # how many the one pass holds
    # what _wave_plan must return: (scan, commit, results)
    plan: tuple = ()
    # the path it must take
    span: str = "replay_and_decode_stream"
    route: str | None = None           # replay_route_total's, where held
    commit: str = "post_pass"          # streamed | post_pass | host_loop
    results: str = "device_lazy"       # device_lazy | host_lazy | in_wave
    mode: str = "device_resident"


def _enabled(names):
    return lambda: PluginSetConfig(enabled=list(names))


HOST_LOOP = ("host_loop", "host_loop", "by_pod")

ROWS = [
    # the stock server and eight cells of BENCHMARK.json: DefaultPreemption
    # refuses the streaming committer
    Row("row01_default_profile",
        plan=("sequential", "post_pass", "device_lazy")),
    Row("row02_webhook_extenders", tweak=_webhook, plan=HOST_LOOP,
        span="host_path_wave", commit="host_loop", results="in_wave"),
    Row("row03_plugin_extender_intercepts_cycle",
        config=_enabled(["NodeResourcesFit"]), tweak=_extender(Interceptor()),
        plan=HOST_LOOP,
        span="host_path_wave", commit="host_loop", results="in_wave"),
    Row("row04_custom_normalize_score", config=_custom(Normalizer()),
        plan=HOST_LOOP,
        span="host_path_wave", commit="host_loop", results="in_wave"),
    Row("row05_custom_lifecycle_plugin", config=_custom(Reserver()),
        plan=("sequential", "post_pass", "by_pod"),
        span="device_replay", results="in_wave"),
    Row("row06_observer_on_default_profile", tweak=_extender(Observer()),
        plan=("sequential", "post_pass", "by_chunk"),
        results="in_wave"),
    Row("row06_observer_on_batchable_profile", config=_enabled(BATCHABLE),
        tweak=_extender(Observer()),
        plan=("sequential", "post_pass", "by_chunk"),
        results="in_wave"),
    Row("row07_postfilter_in_batchable_profile",
        config=_enabled(BATCHABLE + ["DefaultPreemption"]),
        plan=("sequential", "post_pass", "device_lazy")),
    Row("row09_volume_family_without_postfilter",
        config=_enabled(["NodeResourcesFit", "VolumeBinding"]),
        plan=("sequential", "streamed", "device_lazy"),
        commit="streamed"),
    # whatever the pass holds: more than a chunk (8 here) is the chunked
    # scan over leaves, a chunk or less the packed scan's one call
    Row("row09_batchable_profile", config=_enabled(BATCHABLE),
        plan=("sequential", "streamed", "device_lazy"),
        route="leaves", commit="streamed"),
    Row("row09_batchable_profile_pass_of_eight", config=_enabled(BATCHABLE),
        pods=8, plan=("sequential", "streamed", "device_lazy"),
        route="packed", commit="streamed"),
    Row("row09_batchable_profile_pass_of_one", config=_enabled(BATCHABLE),
        pods=1, plan=("sequential", "streamed", "device_lazy"),
        route="packed", commit="streamed"),
    Row("row09_batchable_profile_pass_of_seven", config=_enabled(BATCHABLE),
        pods=7, plan=("sequential", "streamed", "device_lazy"),
        route="packed", commit="streamed"),
    Row("row10_reflector_cannot_defer_default_profile", tweak=_no_defer,
        plan=("sequential", "post_pass", "by_chunk"),
        results="in_wave"),
    Row("row10_reflector_cannot_defer_batchable_profile",
        config=_enabled(BATCHABLE), tweak=_no_defer,
        plan=("sequential", "streamed", "by_chunk"),
        commit="streamed", results="in_wave"),
    Row("row11_gang_plugin_alone_on_batchable_profile",
        config=_custom(Coscheduling(), base=BATCHABLE), gang=True,
        plan=("sequential", "streamed", "device_lazy"),
        commit="streamed"),
    # the rungs: pinned by the tests' floor, and reached as a server
    # reaches them, by the ladder stepping down
    Row("row12_rung_host_resident", engine_kw={"residency_floor": 1},
        plan=("sequential", "post_pass", "host_lazy"),
        results="host_lazy", mode="host_resident"),
    Row("row12_rung_host_resident_by_degradation", tweak=_degraded(1),
        plan=("sequential", "post_pass", "host_lazy"),
        results="host_lazy", mode="host_resident"),
    Row("row13_rung_eager_decode", engine_kw={"residency_floor": 2},
        plan=("sequential", "post_pass", "by_chunk"),
        results="in_wave", mode="eager_decode"),
    Row("row13_rung_eager_decode_by_degradation", tweak=_degraded(2),
        plan=("sequential", "post_pass", "by_chunk"),
        results="in_wave", mode="eager_decode"),
    # the pin tests and parity baselines set beside residency_floor; no
    # server sets it
    Row("pin_pipeline_commit_off", config=_enabled(BATCHABLE),
        engine_kw={"pipeline_commit": False},
        plan=("sequential", "post_pass", "device_lazy")),
]


def _counter(name):
    return TRACER.summary()["counters"].get(name, 0)


def _decoded_in_wave():
    series = TRACER.snapshot()["labeled_counters"].get("decode_path_total", [])
    return sum(s["value"] for s in series)


def _engine(row, monkeypatch):
    """The row's store, pods and engine, one wave's worth."""
    for name, value in row.env.items():
        monkeypatch.setenv(name, value)
    store = ObjectStore()
    for n in make_nodes(6, seed=11):
        store.create("nodes", n)
    pods = make_pods(row.pods, seed=12)
    if row.gang:
        ensure_podgroup_resource(store)
        pgs, gpods = make_gang_workload(1, 3, seed=13)
        for pg in pgs:
            store.create("podgroups", pg)
        pods += gpods
    for p in pods:
        store.create("pods", p)
    engine = SchedulerEngine(store, plugin_config=row.config(), chunk=8,
                             **row.engine_kw)
    if row.tweak is not None:
        row.tweak(engine)
    return store, pods, engine


@pytest.mark.parametrize("row", ROWS, ids=[r.id for r in ROWS])
def test_wave_plan(row, monkeypatch):
    """The plan method's value for the row's engine: taken once for the
    wave, and what the table says."""
    _store, pods, engine = _engine(row, monkeypatch)
    plans = []
    decide = engine._wave_plan

    def recorded(*args):
        plans.append(decide(*args))
        return plans[-1]

    monkeypatch.setattr(engine, "_wave_plan", recorded)
    assert engine.schedule_pending() == len(pods)
    assert plans == [WavePlan(*row.plan)]


@pytest.mark.parametrize("row", ROWS, ids=[r.id for r in ROWS])
def test_wave_path(row, monkeypatch):
    store, pods, engine = _engine(row, monkeypatch)

    TRACER.reset()
    assert engine.schedule_pending() == len(pods)
    events = TRACER.events(limit=2000)

    opened = [e for e in events if e["name"] in WAVE_SPANS]
    assert [e["name"] for e in opened] == [row.span]
    if row.route is not None:
        assert TRACER.labeled_totals("replay_route_total", "route") \
            == {row.route: 1}

    names = {e["name"] for e in events}
    assert (_counter("commit_stream_waves_total") == 1) \
        is (row.commit == "streamed")
    assert ("commit_stream" in names) is (row.commit == "streamed")
    # the host loop commits pod by pod inside its own span
    assert ("commit_and_reflect" in names) is (row.commit != "host_loop")
    if row.gang:
        assert _counter("gang_groups_admitted_total") == 1

    assert engine.result_mode() == row.mode
    lazy = row.results != "in_wave"
    if row.span == "replay_and_decode_stream":
        # a lazy wave decodes nothing; the others decode every pod in it
        assert _decoded_in_wave() == (0 if lazy else len(pods))
    deferred = getattr(engine.reflector, "_lazy", None)
    assert (deferred.pending_count() if deferred else 0) \
        == (len(pods) if lazy else 0)
    # only a device-resident wave leaves tensors for a cold read to fetch
    meta = pods[0]["metadata"]
    annotated = store.get("pods", meta["name"], meta.get("namespace"))
    assert annotated["metadata"]["annotations"]
    assert (_counter("d2h_on_demand_bytes_total") > 0) \
        is (row.results == "device_lazy")


# ------------------------------- a batchable profile, one pod a pass

# BASELINE config 3's profile, as benchmark cell
# baseline_c3_1k.interactive_profile posts it: no label-coupled plugin,
# no PostFilter, nothing of the volume family
CONFIG_3 = BATCHABLE[:4]


def _route(route):
    return TRACER.labeled_totals("replay_route_total", "route").get(route, 0)


def _scan_misses():
    return TRACER.labeled_totals(
        "scan_compile_cache_total", "result").get("miss", 0)


def _config3_engine(n_pods=14):
    store = ObjectStore()
    for n in make_nodes(160, seed=21, taint_fraction=0.1):
        store.create("nodes", n)
    engine = SchedulerEngine(store, plugin_config=PluginSetConfig(
        enabled=list(CONFIG_3)), chunk=8)
    pods = make_pods(n_pods, seed=22, with_affinity=True,
                     with_tolerations=True)
    assert len({str(p["spec"].get("affinity")) for p in pods}) > 4
    return store, engine, pods


def _decided(store, pods):
    """pod name -> (spec.nodeName, every annotation), as a reader sees."""
    out = {}
    for pod in pods:
        meta = pod["metadata"]
        got = store.get("pods", meta["name"], meta["namespace"])
        out[meta["name"]] = (got["spec"].get("nodeName"),
                             dict(got["metadata"]["annotations"]))
    return out


def test_row09_serves_one_pod_a_pass():
    """A UI user's traffic on a batchable profile: every pass is one pod,
    so every pass is the sequential scan's one call over the pass's packed buffers (the one
    upload and the one executable: 2 dispatches), committed by the
    streaming worker, whatever node-affinity terms and tolerations the pod
    carries; after the first passes nothing compiles."""
    store, engine, pods = _config3_engine()
    assert engine._wave_plan() == WavePlan(
        "sequential", "streamed", "device_lazy")
    TRACER.reset()
    misses, dispatches = [], []
    for i, pod in enumerate(pods):
        store.create("pods", pod)
        assert engine.schedule_pending() == 1
        assert _counter("commit_stream_waves_total") == i + 1
        assert _route("packed") == i + 1 and _route("leaves") == 0
        dispatches.append(_counter("pass_device_dispatches_total"))
        misses.append(_scan_misses())
    # a steady pass is two dispatches (the first also uploads the node
    # table's statics), and another pod's terms are no new executable:
    # NodeAffinity's rows are arguments of the scan
    steady = [b - a for a, b in zip(dispatches[3:], dispatches[4:])]
    assert steady == [2] * len(steady), dispatches
    assert misses[-1] == misses[3], misses
    for node, annotations in _decided(store, pods).values():
        assert node and annotations


def test_row09_two_pods_a_pass_are_the_scan_too():
    """The same profile and pods, two a pass: the packed scan's one call
    as for a pass of one, on the bucket of two: nothing over leaves, and
    after the first passes nothing compiles."""
    store, engine, pods = _config3_engine()
    TRACER.reset()
    misses = []
    for i in range(0, len(pods), 2):
        for pod in pods[i:i + 2]:
            store.create("pods", pod)
        assert engine.schedule_pending() == 2
        assert _counter("commit_stream_waves_total") == i // 2 + 1
        assert (_route("packed"), _route("leaves")) == (i // 2 + 1, 0)
        misses.append(_scan_misses())
    assert misses[-1] == misses[1], misses
    for node, annotations in _decided(store, pods).values():
        assert node and annotations


def test_one_pod_a_pass_and_one_pass_of_all_are_byte_equal():
    """The scan's two routes for one profile give one result: the same
    pods served one a pass (the packed scan's one call each) and as one
    pass of 12 (two chunks of 8 over leaves) carry the same
    spec.nodeName, the same 13 result annotations and the same result
    history, byte for byte."""
    store, engine, pods = _config3_engine(12)
    TRACER.reset()
    for pod in pods:
        store.create("pods", pod)
        assert engine.schedule_pending() == 1
    assert (_route("packed"), _route("leaves")) == (12, 0)
    one_a_pass = _decided(store, pods)

    store, engine, pods = _config3_engine(12)
    for pod in pods:
        store.create("pods", pod)
    assert engine.schedule_pending() == 12
    assert (_route("packed"), _route("leaves")) == (12, 1)
    one_pass = _decided(store, pods)

    assert all(len(annotations) == 13 + 1
               for _node, annotations in one_pass.values())
    differing = sorted(k for k in one_pass if one_pass[k] != one_a_pass[k])
    assert not differing, differing
