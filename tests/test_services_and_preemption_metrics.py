"""`services` as a stored kind (cluster/store.py RESOURCES, PR 32): create,
list, get, delete over HTTP, in the export and back through an import;
not among the reference's 7 watched kinds.  And the spans and counters the
same PR brought: each is emitted where docs/metrics.md says, and has its
line there and in the tracer's help table."""

from __future__ import annotations

import json
import urllib.error
import urllib.request
from pathlib import Path

import pytest

from kube_scheduler_simulator_tpu.cluster.store import (
    DEFAULT_GVRS, RESOURCES, NotFound, ObjectStore)
from kube_scheduler_simulator_tpu.config.config import SimulatorConfiguration
from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
from kube_scheduler_simulator_tpu.server.di import DIContainer
from kube_scheduler_simulator_tpu.server.server import SimulatorServer
from kube_scheduler_simulator_tpu.utils import tracing
from kube_scheduler_simulator_tpu.utils.tracing import TRACER

DOCS = Path(__file__).resolve().parent.parent / "docs"
SPANS = ("postfilter", "preempt_screen", "preempt_probe",
         "preempt_screen_dry_run")
COUNTERS = ("preemption_attempts_total", "preemption_screen_refused_nodes_total",
            "preemption_fit_probes_total", "pods_unschedulable_parked_total",
            "pods_requeued_total", "preemption_static_refused_nodes_total",
            "preemption_screen_dry_runs_total")


def _service(name: str, ns: str = "default") -> dict:
    return {"apiVersion": "v1", "kind": "Service",
            "metadata": {"name": name, "namespace": ns},
            "spec": {"selector": {"app": "foo"},
                     "ports": [{"protocol": "TCP", "port": 8080,
                                "targetPort": 8080}]}}


def _req(port, method, path, body=None):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}", data=data,
                                 method=method)
    if data is not None:
        req.add_header("Content-Type", "application/json")
    try:
        with urllib.request.urlopen(req, timeout=60) as r:
            return r.status, json.loads(r.read() or b"null")
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"null")


@pytest.fixture()
def server():
    srv = SimulatorServer(DIContainer(SimulatorConfiguration(port=0),
                                      start_scheduler=False), port=0)
    srv.start(block=False)
    yield srv
    srv.shutdown()


def test_services_are_a_namespaced_kind_outside_the_watched_seven():
    assert RESOURCES["services"] == ("Service", True)
    assert "services" not in DEFAULT_GVRS and len(DEFAULT_GVRS) == 7


def test_services_crud_over_http(server):
    p = server.port
    assert _req(p, "POST", "/api/v1/services", _service("svc-a"))[0] == 201
    assert _req(p, "POST", "/api/v1/services", _service("svc-b", "other"))[0] == 201
    assert _req(p, "POST", "/api/v1/services", _service("svc-a"))[0] == 409
    code, got = _req(p, "GET", "/api/v1/services/default/svc-a")
    assert code == 200 and got["kind"] == "Service"
    assert got["spec"]["ports"][0]["port"] == 8080 and got["metadata"]["uid"]
    code, listed = _req(p, "GET", "/api/v1/services")
    assert sorted(i["metadata"]["name"] for i in listed["items"]) == ["svc-a", "svc-b"]
    assert _req(p, "DELETE", "/api/v1/services/default/svc-a")[0] == 200
    assert _req(p, "GET", "/api/v1/services/default/svc-a")[0] == 404
    assert _req(p, "DELETE", "/api/v1/services/default/svc-a")[0] == 404


def test_services_ride_the_export_and_an_import(server):
    p = server.port
    assert _req(p, "POST", "/api/v1/services", _service("svc-a"))[0] == 201
    code, snap = _req(p, "GET", "/api/v1/export")
    assert code == 200
    assert [s["metadata"]["name"] for s in snap["services"]] == ["svc-a"]
    assert _req(p, "PUT", "/api/v1/reset")[0] == 202
    assert _req(p, "GET", "/api/v1/services")[1]["items"] == []
    assert _req(p, "POST", "/api/v1/import", snap)[0] == 200
    code, got = _req(p, "GET", "/api/v1/services/default/svc-a")
    assert code == 200 and got["spec"]["selector"] == {"app": "foo"}


def test_services_in_the_store_itself():
    store = ObjectStore()
    store.create("services", _service("s"))
    assert store.get("services", "s", "default")["kind"] == "Service"
    store.delete("services", "s", "default")
    with pytest.raises(NotFound):
        store.get("services", "s", "default")


@pytest.mark.parametrize("name", SPANS + COUNTERS)
def test_every_new_span_and_counter_has_its_line_in_the_docs(name):
    assert f"`{name}" in (DOCS / "metrics.md").read_text() or \
        f"{name} " in (DOCS / "metrics.md").read_text(), name
    if name in COUNTERS:
        assert name in tracing._HELP


def _node(name: str, cpu: str) -> dict:
    return {"metadata": {"name": name},
            "status": {"allocatable": {"cpu": cpu, "memory": "32Gi",
                                       "pods": "110"}}}


def _pod(name: str, cpu: str, prio: int, node: str | None = None) -> dict:
    p = {"metadata": {"name": name, "namespace": "default"},
         "spec": {"priority": prio, "containers": [{
             "name": "c", "resources": {"requests": {"cpu": cpu,
                                                     "memory": "1Gi"}}}]}}
    if node:
        p["spec"]["nodeName"] = node
    return p


def _attempt(nodes, pods, preemptor_cpu: str):
    """One pass over a preemptor of priority 50 -> (counter totals, span
    aggregates, the preemptor as stored)."""
    store = ObjectStore()
    for name, cpu in nodes:
        store.create("nodes", _node(name, cpu))
    for name, cpu, prio, node in pods:
        store.create("pods", _pod(name, cpu, prio, node))
    store.create("pods", _pod("preemptor", preemptor_cpu, 50))
    engine = SchedulerEngine(store)
    TRACER.reset()
    engine.schedule_pending()
    engine.close()
    return (TRACER.counter_totals(), TRACER.snapshot()["spans"],
            store.get("pods", "preemptor", "default"))


def test_spans_and_counters_where_the_docs_say():
    """n0 is too small even when empty (the static rule, no dry run for it);
    n1 admits the pod once its low pod goes (probed, nominated); n2 holds
    no lower pod (no look); n3 is large enough empty, but its high pod
    stays (refused by the batched dry run)."""
    totals, spans, me = _attempt(
        (("n0", "2"), ("n1", "4"), ("n2", "4"), ("n3", "4")),
        (("low-0", "1", 0, "n0"), ("low-1", "3", 0, "n1"),
         ("high-2", "3", 90, "n2"), ("high-3", "2", 90, "n3"),
         ("low-3", "1", 0, "n3")), "3")
    assert totals["preemption_attempts_total"] == 1
    assert totals["preemption_static_refused_nodes_total"] == 1   # n0
    assert totals["preemption_screen_dry_runs_total"] == 1
    assert totals["preemption_screen_refused_nodes_total"] == 1   # n3
    # n1: all lower pods gone -> fits; reprieve low-1 -> does not
    assert totals["preemption_fit_probes_total"] == 2
    assert spans["postfilter"]["count"] == 1
    assert spans["preempt_screen"]["count"] == 1
    assert spans["preempt_screen_dry_run"]["count"] == 1
    assert spans["preempt_probe"]["count"] == 2
    assert me["spec"]["nodeName"] == "n1"


def test_an_attempt_whose_every_candidate_is_hopeless_makes_no_dry_run():
    """Three nodes hold a lower-priority pod and none is large enough even
    when empty: the screen's span opens, the static rule takes all three,
    and the pass's own compile_workload and replay stay the attempt's
    only ones."""
    totals, spans, me = _attempt(
        (("n0", "4"), ("n1", "4"), ("n2", "2"), ("n3", "4")),
        (("low-0", "3", 0, "n0"), ("low-1", "1", 0, "n1"),
         ("low-2", "1", 10, "n2"), ("high-3", "1", 90, "n3")), "9")
    assert totals["preemption_attempts_total"] == 1
    assert totals["preemption_static_refused_nodes_total"] == 3
    assert totals["preemption_screen_dry_runs_total"] == 0
    assert totals["preemption_screen_refused_nodes_total"] == 0
    assert totals["preemption_fit_probes_total"] == 0
    assert spans["preempt_screen"]["count"] == 1
    assert "preempt_screen_dry_run" not in spans
    assert "preempt_probe" not in spans
    # one compile_workload, one replay: the failed pass's own
    assert spans["compile_workload"]["count"] == 1
    assert spans["replay_and_decode_stream"]["count"] == 1
    assert not me["spec"].get("nodeName")
    assert not (me.get("status") or {}).get("nominatedNodeName")
    entries = json.loads(me["metadata"]["annotations"][
        "kube-scheduler-simulator.sigs.k8s.io/postfilter-result"])
    assert entries == {n: {} for n in ("n0", "n1", "n2", "n3")}


def test_an_attempt_with_one_survivor_makes_exactly_one_dry_run():
    """n1 is the one node an empty Fit check admits: one batched dry run,
    over n1 alone (the hopeless nodes' pods stay in its cluster)."""
    totals, spans, me = _attempt(
        (("n0", "2"), ("n1", "4"), ("n2", "1")),
        (("low-0", "1", 0, "n0"), ("low-1", "3", 0, "n1"),
         ("low-2", "1", 0, "n2")), "3")
    assert totals["preemption_static_refused_nodes_total"] == 2
    assert totals["preemption_screen_dry_runs_total"] == 1
    assert totals["preemption_screen_refused_nodes_total"] == 0
    assert spans["preempt_screen"]["count"] == 1
    assert spans["preempt_screen_dry_run"]["count"] == 1
    assert me["spec"]["nodeName"] == "n1"


def test_an_attempt_without_candidates_reports_zero_probes():
    """The benchmark's reader tells a measured 0 from a missing counter."""
    store = ObjectStore()
    store.create("nodes", _node("n0", "4"))
    store.create("pods", _pod("big", "9", 10))
    engine = SchedulerEngine(store)
    TRACER.reset()
    engine.schedule_pending()
    engine.close()
    totals = TRACER.counter_totals()
    assert totals["preemption_attempts_total"] == 1
    assert totals["preemption_fit_probes_total"] == 0
    assert totals["preemption_screen_refused_nodes_total"] == 0
    assert "preempt_screen" not in TRACER.snapshot()["spans"]
