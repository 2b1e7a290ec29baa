"""Web-UI verification at the highest level this environment allows.

The reference ships a Nuxt app driven by a real browser; this image has
NO JavaScript runtime (no node/bun/chromium, no selenium/playwright), so
true DOM execution is impossible here.  Two layers compensate:

1. test_browser_drive — the real headless-browser test (create node+pod,
   assert the score/filter tables and history drawer render from live
   annotations).  It runs whenever selenium + a chromium binary are
   present and SKIPS with instructions otherwise, so any environment
   with a browser exercises the shipped JS end-to-end:
       pip install selenium && apt install chromium-driver
       python -m pytest tests/test_web_ui_browser.py -k browser
2. test_ui_contract_* — executable-contract tests against the LIVE
   server: every asset index.html loads resolves; every endpoint api.js
   calls answers; the pod payload carries exactly the annotation keys
   components.js reads (ANN + selected-node / finalscore-result /
   result-history, components.js:223-260) with the JSON shapes the
   render code indexes into.
"""

from __future__ import annotations

import json
import re
import threading
import urllib.request

import pytest

from kube_scheduler_simulator_tpu.config.config import SimulatorConfiguration
from kube_scheduler_simulator_tpu.server.di import DIContainer
from kube_scheduler_simulator_tpu.server.server import SimulatorServer

ANN = "kube-scheduler-simulator.sigs.k8s.io/"


@pytest.fixture()
def live_server():
    di = DIContainer(SimulatorConfiguration(), start_scheduler=True)
    srv = SimulatorServer(di, port=0)
    srv.start(block=False)
    base = f"http://localhost:{srv.port}"
    _post(base, "/api/v1/nodes", {
        "metadata": {"name": "node-a"},
        "status": {"allocatable": {"cpu": "4", "memory": "8Gi", "pods": "10"}}})
    _post(base, "/api/v1/nodes", {
        "metadata": {"name": "node-b"},
        "status": {"allocatable": {"cpu": "2", "memory": "4Gi", "pods": "10"}}})
    _post(base, "/api/v1/pods", {
        "metadata": {"name": "ui-pod", "namespace": "default"},
        "spec": {"containers": [
            {"name": "c", "resources": {"requests": {"cpu": "1",
                                                     "memory": "1Gi"}}}]}})
    # wait for the scheduling loop to bind + reflect: on the event (the
    # pod shows its node and its sealed results), however long the first
    # pass's compile takes beside five other xdist workers; the clock is
    # the backstop of a server that never decides
    import time

    backstop = time.time() + 180
    while time.time() < backstop:
        pod = _get(base, "/api/v1/pods/default/ui-pod")
        if ((pod.get("spec") or {}).get("nodeName")
                and ANN + "result-history" in (
                    (pod.get("metadata") or {}).get("annotations") or {})):
            break
        time.sleep(0.1)
    yield base
    srv.httpd.shutdown()
    di.shutdown()


def _get(base, path):
    with urllib.request.urlopen(base + path, timeout=60) as r:
        body = r.read()
        return json.loads(body) if body.strip().startswith(b"{") else body


def _post(base, path, obj):
    req = urllib.request.Request(
        base + path, data=json.dumps(obj).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read())


def _browser_available():
    try:
        import selenium  # noqa: F401
    except ImportError:
        return False
    import shutil

    return any(shutil.which(b) for b in
               ("chromium", "chromium-browser", "google-chrome"))


@pytest.mark.skipif(not _browser_available(),
                    reason="no selenium+chromium in this image; see module "
                           "docstring for how to run the browser layer")
def test_browser_drive(live_server):
    """Real-DOM drive: the pods table renders, clicking the scheduled pod
    opens the result drawer with filter/score tables and the history
    viewer, all fed from live annotations."""
    from selenium import webdriver
    from selenium.webdriver.common.by import By
    from selenium.webdriver.support.ui import WebDriverWait

    opts = webdriver.ChromeOptions()
    opts.add_argument("--headless=new")
    opts.add_argument("--no-sandbox")
    driver = webdriver.Chrome(options=opts)
    try:
        driver.get(live_server + "/")
        wait = WebDriverWait(driver, 15)
        wait.until(lambda d: "ui-pod" in d.page_source)
        row = driver.find_element(By.XPATH, "//td[contains(.,'ui-pod')]")
        row.click()
        wait.until(lambda d: d.find_element(By.ID, "drawer").is_displayed())
        drawer = driver.find_element(By.ID, "drawer").text
        assert "finalscore" in drawer.lower() or "score" in drawer.lower()
        assert "node-a" in drawer or "node-b" in drawer
        assert "history" in drawer.lower()
    finally:
        driver.quit()


def test_ui_contract_assets_resolve(live_server):
    """Every script/style index.html references is actually served."""
    html = _get(live_server, "/").decode()
    refs = re.findall(r'(?:src|href)="(/[^"]+)"', html)
    assert refs, "index.html references no local assets?"
    for ref in refs:
        body = _get(live_server, ref)
        assert body, f"empty asset {ref}"
    for el_id in ("nav", "content", "drawer", "livedot"):
        assert f'id="{el_id}"' in html


def test_ui_contract_api_surface(live_server):
    """Every endpoint api.js calls answers with the shape the JS indexes."""
    # API.list(r) for the resource tables
    for r in ("nodes", "pods"):
        out = _get(live_server, f"/api/v1/{r}")
        assert isinstance(out["items"], list)
    assert "profiles" in _get(live_server, "/api/v1/schedulerconfiguration")
    snap = _get(live_server, "/api/v1/export")
    assert {"nodes", "pods", "schedulerConfig"} <= set(snap)
    metrics = _get(live_server, "/api/v1/metrics")
    assert metrics
    scenarios = _get(live_server, "/api/v1/scenarios")
    assert scenarios is not None


def test_ui_contract_annotations_feed_the_drawer(live_server):
    """The pod object carries every annotation key components.js reads,
    in the exact shapes its render code indexes (components.js:223-260:
    selected-node string; finalscore-result {node: {plugin: "int"}};
    result-history JSON array of records with selected-node)."""
    pod = _get(live_server, "/api/v1/pods/default/ui-pod")
    assert pod["spec"]["nodeName"] in ("node-a", "node-b")
    anns = pod["metadata"]["annotations"]
    assert anns[ANN + "selected-node"] == pod["spec"]["nodeName"]

    final = json.loads(anns[ANN + "finalscore-result"])
    assert set(final) == {"node-a", "node-b"}
    for node, per_plugin in final.items():
        for plugin, val in per_plugin.items():
            int(val)  # the UI renders these as numeric cells

    filt = json.loads(anns[ANN + "filter-result"])
    assert set(filt) == {"node-a", "node-b"}
    for per_plugin in filt.values():
        assert all(isinstance(v, str) for v in per_plugin.values())

    hist = json.loads(anns[ANN + "result-history"])
    assert isinstance(hist, list) and hist
    assert hist[-1][ANN + "selected-node"] == pod["spec"]["nodeName"]
