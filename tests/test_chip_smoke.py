"""chip_smoke.py stays runnable, and the process-ownership repair under
it holds: a chip belongs to one process, so a simulator server that runs
no engine must never initialise a JAX backend."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = ["--nodes", "20", "--pods", "40", "--ext-nodes", "10", "--ext-pods",
       "20", "--prefix", "8", "--gate-scale", "0.01"]


def _smoke(tmp_path, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # conftest's 8 virtual devices
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args, *TOY,
         "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=600)


def test_chip_smoke_fails_without_a_chip(tmp_path):
    """The contract's first half: where the server's device is not a
    TPU the run exits non-zero and prints no result."""
    r = _smoke(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "the server runs on 'cpu', not 'tpu'" in r.stderr
    assert json.loads((tmp_path / "summary.json").read_text())["ok"] is False


@pytest.mark.slow
def test_chip_smoke_cpu_rehearsal(tmp_path):
    """The explicit CPU rehearsal at toy size: every phase runs, one wave
    per profile, and the output has the contract's shape."""
    r = _smoke(tmp_path, "--platform", "cpu")
    assert r.returncode == 0, r.stderr[-3000:]
    summary_line, last = r.stdout.strip().splitlines()[-2:]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    summary = json.loads(summary_line)
    assert summary == json.loads((tmp_path / "summary.json").read_text())
    assert summary_line.endswith('"claim": null}')
    served = summary["served"]
    for wave in ("wave_a_default_profile", "wave_b_config4_profile"):
        w = served[wave]
        assert w["bound"] + w["unschedulable"] == 40
        assert w["prefix_parity"] == {"pods": 8, "keys": 13,
                                      "mismatches": 0, "ok": True}
        assert w["no_hidden_rung"]["result_mode"] == "device_resident"
    # the default profile commits after the pass; config 4's has no
    # PostFilter, so its commit is streamed, and each of its passes (all
    # of one chunk at this size) is the packed scan's one call
    assert served["wave_a_default_profile"]["commit_stream_waves"] == 0
    b = served["wave_b_config4_profile"]
    assert b["commit_stream_waves"] == len(b["passes"]) > 0
    assert b["replay_routes"] == {"packed": len(b["passes"]), "leaves": 0}
    assert all(c["ok"] for c in summary["gate"]["configs"].values())
    assert summary["external"]["bound"] == 20
    assert summary["external"]["server_device"]["available"] is False


_ENGINELESS_SERVER = """
import json, urllib.request
from jax._src import xla_bridge
from kube_scheduler_simulator_tpu.config.config import SimulatorConfiguration
from kube_scheduler_simulator_tpu.models.workloads import make_nodes, make_pods
from kube_scheduler_simulator_tpu.server.di import DIContainer
from kube_scheduler_simulator_tpu.server.server import SimulatorServer

cfg = SimulatorConfiguration(port=0, external_scheduler_enabled=True)
srv = SimulatorServer(DIContainer(cfg, start_scheduler=False), port=0)
srv.start(block=False)

def call(method, path, body=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}{path}", method=method,
        data=json.dumps(body).encode() if body is not None else None,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.read()
    except urllib.error.HTTPError as e:
        return e.read()

call("POST", "/api/v1/import?ignoreSchedulerConfiguration=true",
     {"nodes": make_nodes(4), "pods": make_pods(6)})
for path in ("/readyz", "/metrics", "/api/v1/metrics", "/api/v1/sessions",
             "/api/v1/pods", "/api/v1/export", "/api/v1/history"):
    call("GET", path)
dump = json.loads(call("GET", "/api/v1/debug/dump"))["dump"]
assert dump["device"]["available"] is False, dump["device"]
srv.shutdown()
assert not xla_bridge._backends, sorted(xla_bridge._backends)
print("NO-BACKEND")
"""


def test_engineless_server_initialises_no_backend():
    """externalSchedulerEnabled: cmd.scheduler is the process on the chip,
    so the server's sampler, dump and every read must leave JAX's backend
    table empty.  A subprocess, because this session's own backend would
    mask it."""
    r = subprocess.run([sys.executable, "-c", _ENGINELESS_SERVER],
                       env=dict(os.environ, PYTHONPATH=REPO,
                                JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().endswith("NO-BACKEND")
