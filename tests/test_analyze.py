"""kss-analyze: seeded-violation fixtures, suppression, the ratchet
baseline, and the clean-at-HEAD gate (docs/static-analysis.md).

The fixtures under tests/fixtures/analysis/ are never imported — the
analyzers are pure AST.  Each seeded violation from the acceptance list
(lock-order inversion, self-deadlock, device-op-under-lock, pod-loop in
the hot path, unbalanced span, bad metric name) must be caught, the
allow() comment and the baseline must silence exactly what they claim
to, and the baseline must be unable to grow without --update-baseline.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from tools.analysis import REPO_ROOT, run_analysis
from tools.analysis.baseline import load_baseline, partition, save_baseline
from tools.analysis.cli import main as cli_main
from tools.analysis.common import load_module_file

FIXTURES = "tests/fixtures/analysis"


def _fixture_result(name: str, purity_roots=None):
    mod = load_module_file(REPO_ROOT, f"{FIXTURES}/{name}")
    return run_analysis(modules=[mod], purity_roots=purity_roots)


def _rules(findings):
    return {f.rule for f in findings}


# ------------------------------------------------------------ lock rules


def test_lock_order_inversion_detected():
    res = _fixture_result("bad_locks.py")
    inversions = [f for f in res["findings"] if f.rule == "lock-order"]
    assert inversions, "A->B/B->A inversion must be reported"
    assert any("Inverted._a" in f.detail and "Inverted._b" in f.detail
               for f in inversions)
    # both participating sites are anchored (ab and ba)
    quals = {f.qualname for f in inversions}
    assert {"Inverted.ab", "Inverted.ba"} <= quals


def test_self_deadlock_detected():
    res = _fixture_result("bad_locks.py")
    selfs = [f for f in res["findings"] if f.rule == "self-deadlock"]
    assert any(f.qualname == "SelfDeadlock.caller" for f in selfs), \
        "helper reacquiring the caller's non-reentrant lock (the PR 3 " \
        "kubeapi shape) must be reported"


def test_blocking_device_serialize_under_lock_detected():
    res = _fixture_result("bad_locks.py")
    by_rule = {}
    for f in res["findings"]:
        by_rule.setdefault(f.rule, set()).add(f.qualname)
    assert "BlockingUnderLock.sleeps" in by_rule["blocking-under-lock"]
    assert "BlockingUnderLock.spawns" in by_rule["blocking-under-lock"]
    assert "AcquireRelease.manual" in by_rule["blocking-under-lock"], \
        "acquire()/release() holds must be tracked, not just with-blocks"
    assert "BlockingUnderLock.device_work" in by_rule["device-under-lock"]
    assert "BlockingUnderLock.serializes" in by_rule["serialize-under-lock"]


def test_allow_comment_suppresses():
    res = _fixture_result("bad_locks.py")
    assert not any(f.qualname == "BlockingUnderLock.allowed"
                   for f in res["findings"])
    assert res["suppressed"] >= 1


# ---------------------------------------------------------- purity rules


_PURITY_ROOTS = [("bad_purity", "hot_entry"), ("bad_purity", "jitted_step"),
                 ("bad_purity", "allowed_loop")]


def test_pod_loop_and_host_sync_in_hot_path():
    res = _fixture_result("bad_purity.py", purity_roots=_PURITY_ROOTS)
    loops = [f for f in res["findings"] if f.rule == "pod-loop"]
    assert any(f.qualname == "hot_entry" and "pods" in f.detail
               for f in loops)
    assert any("range(len(nodes))" in f.detail for f in loops)
    syncs = [f for f in res["findings"] if f.rule == "host-sync"]
    assert any(f.qualname == "helper" for f in syncs), \
        ".item() reached through the call graph must be reported"


def test_nondeterminism_inside_jit():
    res = _fixture_result("bad_purity.py", purity_roots=_PURITY_ROOTS)
    nd = [f for f in res["findings"] if f.rule == "nondeterminism"]
    assert any(f.qualname == "jitted_step" and "time.time" in f.detail
               for f in nd)


def test_unreachable_and_allowed_not_flagged():
    res = _fixture_result("bad_purity.py", purity_roots=_PURITY_ROOTS)
    assert not any(f.qualname == "cold_helper" for f in res["findings"])
    assert not any(f.qualname == "allowed_loop" for f in res["findings"])


def test_compact_host_sync_detected():
    """Eager np.asarray/np.ascontiguousarray on a replay compact field
    (.packed/.raw8/.raw16/.raw32) outside _CompactChunks.materialize is
    flagged: device-resident chunks must cross D2H only through
    cc.host()/materialize() (docs/wave-pipeline.md device residency)."""
    roots = _PURITY_ROOTS + [("bad_purity", "eager_compact_fetch"),
                             ("bad_purity", "contiguous_compact_fetch")]
    res = _fixture_result("bad_purity.py", purity_roots=roots)
    hits = [f for f in res["findings"] if f.rule == "compact-host-sync"]
    assert any(f.qualname == "eager_compact_fetch" and "packed" in f.detail
               for f in hits), hits
    assert any(f.qualname == "contiguous_compact_fetch"
               and "raw16" in f.detail for f in hits), hits


def test_columnar_row_loop_detected():
    """A per-row Python loop over a columnar bank's row arrays
    (cluster/columnar.py) is flagged; per-column dict iteration and
    single-row subscripts are the sanctioned forms and stay clean
    (docs/data-plane.md)."""
    roots = _PURITY_ROOTS + [("bad_purity", "row_loop_over_columns"),
                             ("bad_purity", "column_dict_loop_ok")]
    res = _fixture_result("bad_purity.py", purity_roots=roots)
    hits = [f for f in res["findings"] if f.rule == "columnar-row-loop"]
    assert any(f.qualname == "row_loop_over_columns"
               and "names" in f.detail for f in hits), hits
    assert any(f.qualname == "row_loop_over_columns"
               and "range(len(cols.rv))" in f.detail for f in hits), hits
    assert not any(f.qualname == "column_dict_loop_ok" for f in hits), hits


# ------------------------------------------------------------ span rules


def test_swallowed_exception_detected():
    """The swallowed-exception rule (tools/analysis/swallowed.py): a
    handler whose body is entirely silent (pass/continue/...) is
    flagged; handlers that tap, re-raise or record state are not; an
    allow comment suppresses with a reason on record."""
    mod = load_module_file(REPO_ROOT, f"{FIXTURES}/bad_swallow.py")
    res = run_analysis(modules=[mod],
                       swallow_modules=("bad_swallow.py",))
    sw = [f for f in res["findings"] if f.rule == "swallowed-exception"]
    flagged = {f.qualname for f in sw}
    # nested siblings keep DISTINCT qualnames (distinct ratchet
    # fingerprints — a baselined inner_a must not mask a new inner_b)
    assert flagged == {"silent_pass", "silent_continue", "bare_silent",
                       "outer_with_nested.inner_a",
                       "outer_with_nested.inner_b"}, flagged
    assert any("except bare" in f.detail for f in sw)
    # the allowed site counted as suppressed, not as a finding
    assert res["suppressed"] >= 1


def test_swallowed_exception_scoped_to_hot_modules():
    """Modules outside the hot-path manifest are not policed: the rule
    exists for the fault seams' neighborhoods, not the whole tree."""
    mod = load_module_file(REPO_ROOT, f"{FIXTURES}/bad_swallow.py")
    res = run_analysis(modules=[mod])  # default manifest: no match
    assert not [f for f in res["findings"]
                if f.rule == "swallowed-exception"]


def test_unbalanced_span_and_bad_names():
    res = _fixture_result("bad_spans.py")
    rules = _rules(res["findings"])
    assert "unbalanced-span" in rules
    assert any(f.rule == "metric-name" and "bad-metric.name" in f.detail
               for f in res["findings"])
    assert any(f.rule == "label-name" and "__reserved" in f.detail
               for f in res["findings"])
    # the with-managed span is fine
    assert not any("ok_span" in f.detail for f in res["findings"])
    # a name picked into a variable is read through its literals
    assert any(f.rule == "metric-name" and "bad-picked.span" in f.detail
               for f in res["findings"])
    assert not any("ok_picked_span" in f.detail for f in res["findings"])


# ------------------------------------------------- the repo at HEAD


def test_head_is_clean_and_fast():
    """`make analyze` contract: zero NEW findings at HEAD, without a
    device, comfortably under the 30s budget."""
    t0 = time.perf_counter()
    res = run_analysis()
    dt = time.perf_counter() - t0
    new, _old, stale = partition(res["findings"], load_baseline())
    assert new == [], [f.render() for f in new]
    assert stale == [], f"stale baseline entries: {stale}"
    assert dt < 30, f"analysis took {dt:.1f}s"


def test_kubeapi_rv_lock_edge_is_acyclic():
    """The PR 3 regression, as a property: kubeapi's watch path DOES
    acquire _rv_lock under _lock (the analyzer sees the nesting), and
    that edge participates in no cycle."""
    res = run_analysis()
    edges = res["lock_edges"]
    assert any("KubeAPICluster._lock" in a and "KubeAPICluster._rv_lock" in b
               for (a, b) in edges), "expected the _lock -> _rv_lock edge"
    assert not any(f.rule in ("lock-order", "self-deadlock")
                   for f in res["findings"]), \
        "no lock-order/self-deadlock findings expected at HEAD"


# ------------------------------------------------------------ the ratchet


@pytest.fixture
def tmp_pkg(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(
        "import threading\nimport time\n\n\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._mu = threading.Lock()\n\n"
        "    def bad(self):\n"
        "        with self._mu:\n"
        "            time.sleep(1)\n")
    return tmp_path


def _cli(tmp_pkg, baseline, *extra):
    return cli_main(["--root", str(tmp_pkg), "--package", "pkg",
                     "--baseline", str(baseline), "-q", *extra])


def test_ratchet_workflow(tmp_pkg, tmp_path):
    baseline = tmp_path / "baseline.json"
    # 1. a violation with no baseline fails
    assert _cli(tmp_pkg, baseline) == 1
    # 2. --update-baseline grandfathers it; the run then exits 0
    assert _cli(tmp_pkg, baseline, "--update-baseline") == 0
    assert _cli(tmp_pkg, baseline) == 0
    entries = json.loads(baseline.read_text())["entries"]
    assert len(entries) == 1 and "blocking-under-lock" in \
        entries[0]["fingerprint"]
    # 3. the baseline cannot grow implicitly: a NEW violation fails even
    #    though the old one stays grandfathered
    mod = tmp_pkg / "pkg" / "mod.py"
    mod.write_text(mod.read_text() +
                   "\n    def worse(self):\n"
                   "        with self._mu:\n"
                   "            time.sleep(2)\n")
    assert _cli(tmp_pkg, baseline) == 1
    # 4. fixing the original violation leaves a stale entry, reported
    #    and pruned by the next --update-baseline
    mod.write_text(
        "import threading\nimport time\n\n\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._mu = threading.Lock()\n\n"
        "    def good(self):\n"
        "        time.sleep(0)\n")
    assert _cli(tmp_pkg, baseline) == 0  # stale entries never fail
    assert _cli(tmp_pkg, baseline, "--update-baseline") == 0
    assert json.loads(baseline.read_text())["entries"] == []


def test_baseline_fingerprints_are_line_free(tmp_pkg, tmp_path):
    """Unrelated edits (shifting line numbers) must not churn the
    ratchet."""
    baseline = tmp_path / "baseline.json"
    assert _cli(tmp_pkg, baseline, "--update-baseline") == 0
    mod = tmp_pkg / "pkg" / "mod.py"
    mod.write_text("# a new leading comment\n" + mod.read_text())
    assert _cli(tmp_pkg, baseline) == 0


def test_suppression_beats_baseline(tmp_pkg, tmp_path):
    """An allow() comment silences without any baseline entry."""
    baseline = tmp_path / "baseline.json"
    mod = tmp_pkg / "pkg" / "mod.py"
    mod.write_text(mod.read_text().replace(
        "time.sleep(1)",
        "time.sleep(1)  # kss-analyze: allow(blocking-under-lock)"))
    assert _cli(tmp_pkg, baseline) == 0


def test_save_and_load_roundtrip(tmp_path):
    p = tmp_path / "b.json"
    save_baseline({"rule a/b.py f detail": "why"}, str(p))
    assert load_baseline(str(p)) == {"rule a/b.py f detail": "why"}


def test_cli_json_output(tmp_pkg, tmp_path):
    baseline = tmp_path / "baseline.json"
    out = tmp_path / "out.json"
    assert _cli(tmp_pkg, baseline, "--json", str(out)) == 1
    doc = json.loads(out.read_text())
    assert doc["new"] and doc["new"][0]["rule"] == "blocking-under-lock"


def test_module_entrypoint_matches_make_analyze():
    """`python -m tools.analysis` (what `make analyze` runs) exits 0 at
    HEAD — pure AST, no JAX import needed."""
    r = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "-q"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": ""})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 new" in r.stdout
