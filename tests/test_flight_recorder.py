"""Wave flight recorder (utils/tracing.py, docs/metrics.md): histogram
bucket math, labeled-counter merge, cross-thread span parenting, the
Perfetto export, the SSE/health endpoints, per-plugin attribution from
the replay tensors, and the proof that instrumentation never changes an
annotation byte."""

import json
import threading
import urllib.error
import urllib.request

import pytest

from kube_scheduler_simulator_tpu.cluster.store import ObjectStore
from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
from kube_scheduler_simulator_tpu.framework.replay import (
    plugin_attribution, replay)
from kube_scheduler_simulator_tpu.models.workloads import (
    make_gang_workload, make_nodes, make_pods)
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.state.compile import compile_workload
from kube_scheduler_simulator_tpu.store import annotations as ann
from kube_scheduler_simulator_tpu.store.decode import decode_pod_result
from kube_scheduler_simulator_tpu.utils.tracing import (
    BUCKETS, TRACER, Tracer, sanitize_metric_name, validate_exposition)


# ---------------------------------------------------------------- core


def test_histogram_bucket_math():
    t = Tracer()
    bounds = BUCKETS["scheduling_attempt_duration_seconds"]
    # le semantics: a value equal to a bound lands IN that bucket
    t.observe("scheduling_attempt_duration_seconds", bounds[0],
              result="scheduled")
    # strictly above the first bound -> second bucket
    t.observe("scheduling_attempt_duration_seconds", bounds[0] * 1.5,
              result="scheduled")
    # beyond the last bound -> the +Inf bucket; n amortizes a batched wave
    t.observe("scheduling_attempt_duration_seconds", bounds[-1] * 10, n=5,
              result="scheduled")
    snap = t.snapshot()
    h = snap["histograms"]["scheduling_attempt_duration_seconds"]
    assert h["buckets"] == list(bounds)
    (series,) = h["series"]
    assert series["labels"] == {"result": "scheduled"}
    assert series["counts"][0] == 1
    assert series["counts"][1] == 1
    assert series["counts"][-1] == 5
    assert series["count"] == 7
    assert series["sum"] == pytest.approx(
        bounds[0] + bounds[0] * 1.5 + 5 * bounds[-1] * 10)
    # exposition: cumulative buckets ending at +Inf, _count == +Inf bucket
    fams = validate_exposition(t.prometheus_text())
    fam = fams["kss_tpu_scheduling_attempt_duration_seconds"]
    assert fam["type"] == "histogram"
    buckets = [s for s in fam["samples"] if s[0].endswith("_bucket")]
    assert buckets[-1][1]["le"] == "+Inf"
    counts = [float(s[2]) for s in buckets]
    assert counts == sorted(counts) and counts[-1] == 7


def test_histogram_unknown_name_uses_default_buckets():
    t = Tracer()
    t.observe("some_custom_seconds", 0.5)
    h = t.snapshot()["histograms"]["some_custom_seconds"]
    assert len(h["buckets"]) == 15  # the default exponential ladder
    validate_exposition(t.prometheus_text())


def test_labeled_counter_merge_is_order_insensitive():
    t = Tracer()
    t.inc("plugin_execution_total", 2, plugin="Fit", extension_point="filter")
    t.inc("plugin_execution_total", 3, extension_point="filter", plugin="Fit")
    t.inc("plugin_execution_total", 1, plugin="Fit", extension_point="score")
    series = t.snapshot()["labeled_counters"]["plugin_execution_total"]
    by_labels = {tuple(sorted(s["labels"].items())): s["value"]
                 for s in series}
    assert by_labels[(("extension_point", "filter"), ("plugin", "Fit"))] == 5
    assert by_labels[(("extension_point", "score"), ("plugin", "Fit"))] == 1


def test_metric_name_sanitization_and_help_lines():
    assert sanitize_metric_name("a-b.c d") == "a_b_c_d"
    assert sanitize_metric_name("9lives") == "_9lives"
    t = Tracer()
    with t.span("weird-span.name with space"):
        pass
    t.count("dashed-counter.total")
    t.inc("labeled-weird.total", 1, result='quo"te\\back\nline')
    text = t.prometheus_text()
    fams = validate_exposition(text)  # raises on any invalid line
    assert "kss_tpu_dashed_counter_total" in fams
    assert "kss_tpu_span_weird_span_name_with_space_seconds_total" in fams
    for f in fams.values():
        assert f["help"] is not None and f["type"] is not None
    # the escaped label value round-trips through the validator's parser
    (sample,) = fams["kss_tpu_labeled_weird_total"]["samples"]
    assert sample[1]["result"] == 'quo"te\\back\nline'


@pytest.mark.parametrize("bad", [
    "no_final_newline 1",                                    # missing \n
    "1bad_name 2\n",                                         # invalid name
    'm{l="v} 1\n',                                           # unterminated
    'm{l="a",l="b"} 1\n',                                    # dup label
    "# TYPE h histogram\nh_bucket{le=\"+Inf\"} 1\nh_count 1\n",  # no _sum
    "# TYPE h histogram\nh_bucket{le=\"1\"} 1\nh_sum 1\nh_count 1\n",
    "# TYPE h histogram\nh_bucket{le=\"1\"} 5\n"
    "h_bucket{le=\"+Inf\"} 3\nh_sum 1\nh_count 3\n",         # not cumulative
    "a 1\nb 2\na 3\n",                                       # interleaved
])
def test_exposition_validator_rejects(bad):
    with pytest.raises(ValueError):
        validate_exposition(bad)


# ------------------------------------------------- engine span tree


def _pipelined_wave(n_pods=48, n_nodes=6, chunk=16, pipeline=True):
    TRACER.reset()
    store = ObjectStore()
    for n in make_nodes(n_nodes, seed=11):
        store.create("nodes", n)
    for p in make_pods(n_pods, seed=12):
        store.create("pods", p)
    # no PostFilter in the lineup so the wave takes the streaming-commit
    # path (_wave_plan; the default set's preemption forces the
    # sequential post-pass, as pipeline=False does here)
    cfg = PluginSetConfig(enabled=[
        "NodeResourcesFit", "NodeResourcesBalancedAllocation",
        "NodeAffinity", "TaintToleration", "PodTopologySpread"])
    engine = SchedulerEngine(store, plugin_config=cfg, chunk=chunk,
                             pipeline_commit=pipeline)
    assert (engine._wave_plan().commit == "streamed") is pipeline
    bound = engine.schedule_pending()
    assert bound > 0
    return TRACER.events(limit=1000)


def test_span_tree_parents_across_commit_worker_thread():
    evs = _pipelined_wave()
    replays = [e for e in evs if e["name"] == "replay_and_decode_stream"]
    assert replays, [e["name"] for e in evs]
    replay_ev = replays[-1]
    commits = [e for e in evs if e["name"] == "commit_stream"]
    assert commits, "streaming commit did not run"
    for c in commits:
        # explicit cross-thread parenting: the worker's spans hang off
        # the wave's replay span, recorded on a different thread
        assert c["parent_id"] == replay_ev["span_id"]
        assert c["tid"] != replay_ev["tid"]
    # the commit tail parents implicitly on the engine thread
    tails = [e for e in evs if e["name"] == "commit_and_reflect"]
    assert tails and tails[-1]["tid"] == replay_ev["tid"]


@pytest.mark.parametrize("pipeline", [True, False],
                         ids=["streamed", "post_pass"])
def test_wave_counters_by_commit_path(pipeline):
    """What a wave reports of its commit: the streaming committer counts
    itself, its overlap and every object it wrote through a batch; the
    sequential post-pass moves no commit_stream_* counter.  Both
    attribute the wave per plugin into a snapshot that serialises."""
    n_pods = 24
    _pipelined_wave(n_pods=n_pods, chunk=8, pipeline=pipeline)
    snap = TRACER.snapshot()
    assert {"replay_and_decode_stream", "commit_and_reflect"} \
        <= set(snap["spans"])
    counters = snap["counters"]
    if pipeline:
        assert counters["commit_stream_waves_total"] == 1
        assert "commit_stream_overlap_seconds" in counters
        # a bind per pod at the least; the reflects defer with the decode
        assert counters["store_batch_writes_total"] >= n_pods
    else:
        assert not [k for k in counters if k.startswith("commit_stream_")]
    evaluated = snap["labeled_counters"]["plugin_pods_nodes_evaluated_total"]
    assert {s["labels"]["extension_point"] for s in evaluated} \
        >= {"filter", "score"}
    assert "scheduling_attempt_duration_seconds" in snap["histograms"]
    json.dumps(snap)


def test_cold_read_pays_one_decode_and_fetch_for_its_chunk():
    """Why a cold read is slower than a warm one: the first read of a
    lazy wave's pod fetches and decodes its whole chunk, once; a
    chunk-mate's read right after finds the result memoized and opens
    neither span."""
    TRACER.reset()
    store = ObjectStore()
    for n in make_nodes(6, seed=11):
        store.create("nodes", n)
    pods = make_pods(16, seed=12)
    for p in pods:
        store.create("pods", p)
    engine = SchedulerEngine(store, chunk=16)
    assert engine.schedule_pending() == len(pods)
    assert engine.reflector._lazy.pending_count() == len(pods)

    def spans():
        agg = TRACER.summary()["spans"]
        return (agg.get("decode_lazy", {}).get("count", 0),
                agg.get("d2h_fetch", {}).get("count", 0))

    assert spans() == (0, 0)
    # one chunk holds the whole wave, so any two pods are chunk-mates
    for reads, meta in enumerate((p["metadata"] for p in pods[:2]), start=1):
        got = store.get("pods", meta["name"], meta.get("namespace"))
        assert ann.SELECTED_NODE in got["metadata"]["annotations"]
        assert spans() == (1, 1), f"after read {reads}"


def test_perfetto_export_schema_and_pipeline_overlap():
    # ten chunks, not three: with the scan already compiled (an earlier
    # test of this file ran the same wave) the replay span is ~40 ms and
    # the first commit starts ~20 ms into it, the third within ~2 ms of
    # its end; on a loaded machine the worker thread can be handed the
    # GIL later than that, and no commit STARTED inside the span.  With
    # ten the first commit starts with 60% of the span still to go
    _pipelined_wave(n_pods=160)
    doc = TRACER.perfetto()
    evs = doc["traceEvents"]
    metas = [e for e in evs if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in metas)
    assert any(e["name"] == "thread_name" for e in metas)
    xs = [e for e in evs if e["ph"] == "X"]
    assert xs
    for e in xs:
        for field in ("name", "cat", "ts", "dur", "pid", "tid", "args"):
            assert field in e, f"{field} missing from {e}"
        assert isinstance(e["ts"], int) and isinstance(e["dur"], int)
        assert e["dur"] >= 1
    parent = next(e for e in xs if e["name"] == "replay_and_decode_stream")
    kids = [e for e in xs
            if e["args"].get("parent_id") == parent["args"]["span_id"]
            and e["name"] == "commit_stream"]
    assert kids, "no commit_stream children under the replay span"
    # the PR-2 pipeline overlap, visible in one browser load: commit
    # worker spans START inside the replay span's window.  (The FINAL
    # chunk may drain after the replay span closes — finish() joins the
    # worker — so the proof is "some", not "all".)
    assert any(parent["ts"] <= k["ts"] <= parent["ts"] + parent["dur"]
               for k in kids)
    # json-serializable end to end
    json.dumps(doc)


def test_perfetto_limit():
    _pipelined_wave()
    doc = TRACER.perfetto(limit=2)
    assert len([e for e in doc["traceEvents"] if e["ph"] == "X"]) == 2
    # limit=0 means zero spans, not "all" (evs[-0:] would be the whole
    # ring buffer)
    doc = TRACER.perfetto(limit=0)
    assert [e for e in doc["traceEvents"] if e["ph"] == "X"] == []


def test_stop_profile_wraps_external_runtime_error(monkeypatch):
    import jax

    from kube_scheduler_simulator_tpu.utils.tracing import ProfileStateError

    t = Tracer()
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)

    def dead_stop():
        raise RuntimeError("no profiler session running")

    monkeypatch.setattr(jax.profiler, "stop_trace", dead_stop)
    t.start_xla_profile("/tmp/kss-test-prof")
    # the session died outside the Tracer: still a 409-able state
    # conflict, and our state clears so a new start can succeed
    with pytest.raises(ProfileStateError):
        t.stop_xla_profile()
    assert not t.profiling


# ------------------------------------------------- attribution


def _small_replay(n_pods=24, n_nodes=6):
    nodes = make_nodes(n_nodes, seed=21, taint_fraction=0.3)
    pods = make_pods(n_pods, seed=22, with_affinity=True,
                     with_tolerations=True, with_spread=True)
    cfg = PluginSetConfig(enabled=[
        "NodeResourcesFit", "NodeResourcesBalancedAllocation",
        "NodeAffinity", "TaintToleration", "PodTopologySpread"])
    cw = compile_workload(nodes, pods, cfg)
    return replay(cw, chunk=8), cw


def test_plugin_attribution_matches_annotations():
    rr, cw = _small_replay()
    anns = [decode_pod_result(rr, i) for i in range(cw.n_pods)]
    att = plugin_attribution(rr)
    filters = cw.config.filters()
    ran = {n: 0 for n in filters}
    rejects = {n: 0 for n in filters}
    score_sum = {n: 0 for n in cw.config.scorers()}
    for a in anns:
        for entries in json.loads(a[ann.FILTER_RESULT]).values():
            for name, msg in entries.items():
                ran[name] += 1
                if msg != ann.PASSED_FILTER_MESSAGE:
                    rejects[name] += 1
        for entries in json.loads(a[ann.SCORE_RESULT]).values():
            for name, v in entries.items():
                score_sum[name] += int(v)
    for name in filters:
        assert att["filter"][name]["evaluated"] == ran[name], name
        assert att["filter"][name]["rejects"] == rejects[name], name
    for name, want in score_sum.items():
        assert att["score"][name]["sum"] == want, name
    for name, d in att["prefilter"].items():
        assert 0 <= d["evaluated"] <= cw.n_pods
        assert d["screened"] == 0  # this workload has no prefilter rejects


def test_attribution_changes_no_annotation_bytes():
    """The golden proof: reading the replay tensors for attribution
    leaves every decoded annotation byte-identical."""
    rr, cw = _small_replay(n_pods=12)
    before = [decode_pod_result(rr, i) for i in range(cw.n_pods)]
    assert plugin_attribution(rr) is not None
    after = [decode_pod_result(rr, i) for i in range(cw.n_pods)]
    assert before == after


def test_engine_wave_populates_upstream_histograms():
    TRACER.reset()
    store = ObjectStore()
    for n in make_nodes(4, seed=41):
        store.create("nodes", n)
    pods = make_pods(12, seed=42)
    # one impossible pod so both result= series appear
    pods[0]["spec"]["containers"][0]["resources"]["requests"]["cpu"] = \
        "9999999m"
    for p in pods:
        store.create("pods", p)
    SchedulerEngine(store).schedule_pending()
    snap = TRACER.snapshot()
    hists = snap["histograms"]
    att = hists["scheduling_attempt_duration_seconds"]["series"]
    results = {s["labels"]["result"]: s["count"] for s in att}
    assert results.get("scheduled") == 11
    assert results.get("unschedulable") == 1
    points = {s["labels"]["extension_point"] for s in
              hists["framework_extension_point_duration_seconds"]["series"]}
    assert {"prefilter", "filter", "score", "bind"} <= points
    plugin_points = {(s["labels"]["plugin"], s["labels"]["extension_point"])
                     for s in
                     hists["plugin_execution_duration_seconds"]["series"]}
    assert any(p == "NodeResourcesFit" and e == "filter"
               for p, e in plugin_points)
    assert any(e == "score" for _, e in plugin_points)
    assert any(e == "prefilter" for _, e in plugin_points)
    # decoder-ladder attribution: the wave defers decode to first read
    # (store/lazy.py), so drain a read before asserting that every
    # decoded pod lands on some ladder path
    store.list("pods")
    snap = TRACER.snapshot()
    decode_paths = snap["labeled_counters"]["decode_path_total"]
    assert sum(s["value"] for s in decode_paths) >= 12


def test_gang_quorum_labeled_counter():
    TRACER.reset()
    from kube_scheduler_simulator_tpu.plugins.coscheduling import (
        Coscheduling, ensure_podgroup_resource)

    store = ObjectStore()
    ensure_podgroup_resource(store)
    for n in make_nodes(8, seed=51):
        store.create("nodes", n)
    pgs, pods = make_gang_workload(2, 3, seed=52)
    ppgs, ppods = make_gang_workload(1, 3, seed=53, name_prefix="parked")
    for p in ppods:
        if p["metadata"]["name"].endswith("-member-000"):
            p["spec"]["containers"][0]["resources"]["requests"]["cpu"] = \
                "9999999m"
    for pg in pgs + ppgs:
        store.create("podgroups", pg)
    for p in pods + ppods:
        store.create("pods", p)
    cfg = PluginSetConfig(
        enabled=["NodeResourcesFit", "Coscheduling"],
        custom={"Coscheduling": Coscheduling()})
    SchedulerEngine(store, plugin_config=cfg).schedule_pending()
    series = TRACER.snapshot()["labeled_counters"]["gang_quorum_groups_total"]
    decisions = {s["labels"]["decision"]: s["value"] for s in series}
    assert decisions.get("admit", 0) >= 2
    assert decisions.get("park", 0) >= 1
    # the span tree has the quorum child spans
    assert any(e["name"] == "gang_quorum" for e in TRACER.events(1000))


def test_host_path_plugin_wall_time():
    """Host-path lifecycle plugins get REAL per-plugin wall time (the
    time half of docs/metrics.md's attribution split)."""
    from kube_scheduler_simulator_tpu.plugins.custom import CustomPlugin

    class Waiter(CustomPlugin):
        name = "Waiter"

        def reserve(self, pod, node):
            return None

        def permit(self, pod, node):
            return None

    TRACER.reset()
    store = ObjectStore()
    for n in make_nodes(3, seed=61):
        store.create("nodes", n)
    for p in make_pods(2, seed=62):
        store.create("pods", p)
    cfg = PluginSetConfig(enabled=["NodeResourcesFit", "Waiter"],
                          custom={"Waiter": Waiter()})
    bound = SchedulerEngine(store, plugin_config=cfg).schedule_pending()
    assert bound == 2
    series = TRACER.snapshot()["histograms"][
        "plugin_execution_duration_seconds"]["series"]
    got = {(s["labels"]["plugin"], s["labels"]["extension_point"],
            s["labels"]["status"]): s["count"] for s in series}
    assert got.get(("Waiter", "reserve", "Success")) == 2
    assert got.get(("Waiter", "permit", "Success")) == 2


# ------------------------------------------------- HTTP surface


@pytest.fixture(scope="module")
def live_server():
    from kube_scheduler_simulator_tpu.config.config import (
        SimulatorConfiguration)
    from kube_scheduler_simulator_tpu.server.di import DIContainer
    from kube_scheduler_simulator_tpu.server.server import SimulatorServer

    di = DIContainer(SimulatorConfiguration(port=0), start_scheduler=True)
    srv = SimulatorServer(di, port=0)
    srv.start(block=False)
    yield di, f"http://127.0.0.1:{srv.port}"
    srv.shutdown()


def _get_json(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, json.load(r)


def test_health_endpoints(live_server):
    _, base = live_server
    status, body = _get_json(base + "/healthz")
    assert status == 200 and body["status"] == "ok"
    status, body = _get_json(base + "/readyz")
    assert status == 200 and body["status"] == "ready"


def test_metrics_endpoint_passes_validator_on_scheduled_wave(live_server):
    di, base = live_server
    TRACER.reset()
    for n in make_nodes(3, seed=71):
        di.store.create("nodes", n)
    for p in make_pods(8, seed=72):
        di.store.create("pods", p)
    deadline = threading.Event()
    for _ in range(100):  # the scheduling loop debounces ~50ms
        if not [p for p in di.store.list("pods")[0]
                if not (p.get("spec") or {}).get("nodeName")]:
            break
        deadline.wait(0.1)
    with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
        assert r.headers["Content-Type"].startswith("text/plain")
        fams = validate_exposition(r.read().decode())
    for name in ("kss_tpu_scheduling_attempt_duration_seconds",
                 "kss_tpu_framework_extension_point_duration_seconds",
                 "kss_tpu_plugin_execution_duration_seconds"):
        assert fams[name]["type"] == "histogram", name
    points = {s[1].get("extension_point")
              for s in fams["kss_tpu_plugin_execution_duration_seconds"]
              ["samples"]}
    assert {"filter", "score", "prefilter"} <= points
    # the JSON snapshot carries the same families
    _, snap = _get_json(base + "/api/v1/metrics")
    assert {"spans", "counters", "labeled_counters", "histograms"} \
        <= set(snap)


def test_trace_endpoint(live_server):
    _, base = live_server
    status, doc = _get_json(base + "/api/v1/trace?limit=5")
    assert status == 200
    assert "traceEvents" in doc
    assert len([e for e in doc["traceEvents"] if e["ph"] == "X"]) <= 5
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(base + "/api/v1/trace?limit=bogus", timeout=10)
    assert ei.value.code == 400


def test_metrics_stream_sse(live_server):
    _, base = live_server
    with urllib.request.urlopen(
            base + "/api/v1/metrics/stream?interval=0.05&count=3",
            timeout=10) as r:
        assert r.headers["Content-Type"] == "text/event-stream"
        body = r.read().decode()
    events = [json.loads(line[6:]) for line in body.split("\n")
              if line.startswith("data: ")]
    assert len(events) >= 2
    for snap in events:
        assert "counters" in snap and "histograms" in snap


def test_profile_conflicts_return_409(live_server, monkeypatch):
    import jax

    _, base = live_server
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)

    def post(action):
        req = urllib.request.Request(
            base + "/api/v1/profile",
            data=json.dumps({"action": action}).encode(), method="POST",
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=10) as r:
                return r.status, json.load(r)
        except urllib.error.HTTPError as e:
            return e.code, json.load(e)

    # stop without start -> 409 with a JSON error body, not a raw 500
    code, body = post("stop")
    assert code == 409 and body["reason"] == "Conflict" and body["message"]
    code, _ = post("start")
    assert code == 200
    try:
        # double start -> 409
        code, body = post("start")
        assert code == 409 and "already running" in body["message"]
    finally:
        code, _ = post("stop")
        assert code == 200


# --------------------------------------- worker exception span balance


def test_mid_chunk_exception_leaves_tracer_balanced(monkeypatch):
    """A mid-chunk failure on the commit-worker thread must not leak
    spans: the raising chunk's commit_stream span closes (with-statement
    unwind), finish()'s commit_and_reflect tail closes before the worker
    error re-raises on the engine thread, and the /api/v1/trace document
    stays well-formed (docs/static-analysis.md, unbalanced-span rule)."""
    TRACER.reset()
    # this test poisons put_decoded mid-chunk: pin the EAGER commit
    # worker (lazy mode deposits handles and never calls it in-wave),
    # and disable the wave failure protocol's retry so the ABORT path —
    # what this test pins — still surfaces the raise (with retries on,
    # the one-shot poison heals via the uncommitted-suffix retry:
    # tests/test_faults.py covers that)
    monkeypatch.setenv("KSS_TPU_WAVE_MAX_RETRIES", "0")
    store = ObjectStore()
    for n in make_nodes(6, seed=31):
        store.create("nodes", n)
    for p in make_pods(48, seed=32):
        store.create("pods", p)
    cfg = PluginSetConfig(enabled=[
        "NodeResourcesFit", "NodeResourcesBalancedAllocation",
        "NodeAffinity", "TaintToleration", "PodTopologySpread"])
    engine = SchedulerEngine(store, plugin_config=cfg, chunk=16,
                             pipeline_commit=True, residency_floor=2)
    assert engine._wave_plan().commit == "streamed"

    real = engine.result_store.put_decoded
    calls = {"n": 0}

    def poisoned(ns, name, annotations):
        calls["n"] += 1
        if calls["n"] == 20:  # second chunk, pod 4 of 16: MID-chunk
            raise RuntimeError("mid-chunk poison")
        return real(ns, name, annotations)

    monkeypatch.setattr(engine.result_store, "put_decoded", poisoned)
    with pytest.raises(RuntimeError, match="mid-chunk poison"):
        engine.schedule_pending()

    evs = TRACER.events(limit=1000)
    # the span the worker was inside when it raised was still recorded
    commits = [e for e in evs if e["name"] == "commit_stream"]
    assert commits, "raising commit_stream span was dropped"
    assert [e for e in evs if e["name"] == "commit_and_reflect"]
    # both thread stacks unwound: the engine thread's stack is empty and
    # every recorded parent_id resolves to a recorded span (a leaked
    # open span would leave a dangling reference)
    assert TRACER.current_span_id() is None
    doc = TRACER.perfetto()
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    ids = {e["args"]["span_id"] for e in xs}
    for e in xs:
        for field in ("name", "cat", "ts", "dur", "pid", "tid", "args"):
            assert field in e, f"{field} missing from {e}"
        parent = e["args"].get("parent_id")
        assert parent is None or parent in ids, \
            f"{e['name']} parents under an unrecorded span {parent}"
    json.dumps(doc)  # the /api/v1/trace body end to end

    # the recorder (and engine) are not wedged: the next wave schedules
    # normally and stays balanced
    before = calls["n"]
    assert engine.schedule_pending() > 0
    assert calls["n"] > before
    assert TRACER.current_span_id() is None
