"""`csinodes` as a stored kind (cluster/store.py RESOURCES, PR 36): create,
list, get, update, delete over HTTP, in the export and back through an
import; cluster-scoped, `storage.k8s.io/v1`, not among the reference's 7
watched kinds; an event on one moves the parked pods.  And the server
answers small requests sent back to back on one connection without the
40 ms a delayed ACK costs (a PV, its claim and the pod are three such)."""

from __future__ import annotations

import http.client
import json
import time

import pytest

from test_services_and_preemption_metrics import _req

from kube_scheduler_simulator_tpu.cluster.store import (
    API_VERSIONS, DEFAULT_GVRS, RESOURCES, NotFound, ObjectStore,
    volume_manifests)
from kube_scheduler_simulator_tpu.config.config import SimulatorConfiguration
from kube_scheduler_simulator_tpu.framework.unschedulable import MOVING_RESOURCES
from kube_scheduler_simulator_tpu.server.di import DIContainer
from kube_scheduler_simulator_tpu.server.server import SimulatorServer


def _csinode(name: str, count: int = 39) -> dict:
    return {"apiVersion": "storage.k8s.io/v1", "kind": "CSINode",
            "metadata": {"name": name, "annotations": {
                "storage.alpha.kubernetes.io/migrated-plugins":
                    "kubernetes.io/aws-ebs"}},
            "spec": {"drivers": [{"name": "ebs.csi.aws.com", "nodeID": name,
                                  "allocatable": {"count": count}}]}}


@pytest.fixture()
def server():
    srv = SimulatorServer(DIContainer(SimulatorConfiguration(port=0),
                                      start_scheduler=False), port=0)
    srv.start(block=False)
    yield srv
    srv.shutdown()


def test_csinodes_are_a_cluster_scoped_kind_outside_the_watched_seven():
    assert RESOURCES["csinodes"] == ("CSINode", False)
    assert API_VERSIONS["csinodes"] == "storage.k8s.io/v1"
    assert "csinodes" not in DEFAULT_GVRS and len(DEFAULT_GVRS) == 7
    assert "csinodes" in MOVING_RESOURCES


def test_csinodes_crud_over_http(server):
    p = server.port
    assert _req(p, "POST", "/api/v1/csinodes", _csinode("n-a"))[0] == 201
    assert _req(p, "POST", "/api/v1/csinodes", _csinode("n-b", 2))[0] == 201
    assert _req(p, "POST", "/api/v1/csinodes", _csinode("n-a"))[0] == 409
    code, got = _req(p, "GET", "/api/v1/csinodes/n-a")
    assert code == 200 and got["kind"] == "CSINode" and got["metadata"]["uid"]
    assert got["apiVersion"] == "storage.k8s.io/v1"
    assert got["spec"]["drivers"][0]["allocatable"]["count"] == 39
    got["spec"]["drivers"][0]["allocatable"]["count"] = 5
    assert _req(p, "PUT", "/api/v1/csinodes/n-a", got)[0] == 200
    assert _req(p, "GET", "/api/v1/csinodes/n-a")[1]["spec"]["drivers"][0][
        "allocatable"]["count"] == 5
    code, listed = _req(p, "GET", "/api/v1/csinodes")
    assert sorted(i["metadata"]["name"] for i in listed["items"]) == ["n-a", "n-b"]
    assert _req(p, "DELETE", "/api/v1/csinodes/n-a")[0] == 200
    assert _req(p, "GET", "/api/v1/csinodes/n-a")[0] == 404
    assert _req(p, "DELETE", "/api/v1/csinodes/n-a")[0] == 404


def test_csinodes_ride_the_export_and_an_import(server):
    p = server.port
    assert _req(p, "POST", "/api/v1/csinodes", _csinode("n-a", 7))[0] == 201
    code, snap = _req(p, "GET", "/api/v1/export")
    assert code == 200
    assert [c["metadata"]["name"] for c in snap["csinodes"]] == ["n-a"]
    assert _req(p, "PUT", "/api/v1/reset")[0] == 202
    assert _req(p, "GET", "/api/v1/csinodes")[1]["items"] == []
    assert _req(p, "POST", "/api/v1/import", snap)[0] == 200
    code, got = _req(p, "GET", "/api/v1/csinodes/n-a")
    assert code == 200
    assert got["spec"]["drivers"][0]["allocatable"]["count"] == 7
    assert got["metadata"]["annotations"][
        "storage.alpha.kubernetes.io/migrated-plugins"] == "kubernetes.io/aws-ebs"


def test_csinodes_in_the_store_itself_and_in_the_pass_s_volumes():
    store = ObjectStore()
    store.create("csinodes", _csinode("n"))
    assert store.get("csinodes", "n")["kind"] == "CSINode"
    vols = volume_manifests(store)
    assert set(vols) == {"pvcs", "pvs", "storageclasses", "csinodes"}
    assert [c["metadata"]["name"] for c in vols["csinodes"]] == ["n"]
    store.delete("csinodes", "n")
    with pytest.raises(NotFound):
        store.get("csinodes", "n")
    assert volume_manifests(store)["csinodes"] == []


def test_small_requests_back_to_back_meet_no_delayed_ack(server):
    """With Nagle on, a response's body waits for the ACK of its headers,
    and a client in a request-response rhythm on one connection delays
    that ACK by 40 ms: every request after the first took >= 40 ms."""
    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=60)
    took = []
    for i in range(8):
        body = json.dumps(_csinode(f"n-{i}")).encode()
        t0 = time.perf_counter()
        conn.request("POST", "/api/v1/csinodes", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        resp.read()
        took.append(time.perf_counter() - t0)
        assert resp.status == 201
    conn.close()
    assert min(took[1:]) < 0.03, took
