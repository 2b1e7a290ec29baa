"""bench.py machinery the driver depends on: the streamed parity check
and the oracle-child failure handling.  These paths decide whether a
bench run's numbers are guarded by an honest parity verdict
(BASELINE.md), so they get unit coverage even though bench.py is not
part of the package."""

from __future__ import annotations

import bench  # conftest.py puts the repo root on sys.path


def test_stream_oracle_parity_ok_and_digest():
    r = bench.stream_oracle_parity(1, 0.02, 0, want_digest=True)
    assert r["ok"] is True
    assert r["compared"] == r["pods"] > 0
    assert r["keys_checked"] == 13 * r["pods"]
    assert r["mismatches"] == 0 and r["first_mismatch"] is None
    assert len(r["sha256"]) == 64
    assert r["oracle_rc"] == 0


def test_stream_oracle_parity_heartbeat_fires():
    beats = []
    r = bench.stream_oracle_parity(1, 0.02, 0, heartbeat=beats.append)
    assert r["ok"] and len(beats) >= r["pods"]


def test_oracle_child_death_is_not_a_parity_failure(monkeypatch):
    # a dying child (the round-4 OOM shape) must be reported as an
    # environment failure, not as mismatches
    monkeypatch.setattr(
        bench, "_ORACLE_CHILD",
        "import sys\nsys.exit(137)\n" + "# {repo} {idx} {scale} {seed}\n")
    r = bench.stream_oracle_parity(1, 0.02, 0)
    assert r["ok"] is False
    assert r.get("oracle_died") is True
    assert r["mismatches"] == 0
    assert r["oracle_rc"] == 137


def test_run_parity_gate_retries_smaller_on_child_death(monkeypatch):
    calls = []
    real = bench.stream_oracle_parity

    def fake(idx, scale, seed, chunk=64, want_digest=False, heartbeat=None):
        calls.append(scale)
        if len(calls) == 1:
            return {"ok": False, "pods": 10, "compared": 3,
                    "keys_checked": 39, "mismatches": 0,
                    "first_mismatch": None, "sha256": None,
                    "oracle_rc": -9, "oracle_err": "Killed",
                    "oracle_died": True, "replay_seconds": 0,
                    "oracle_seconds": 0}
        return real(idx, scale, seed, chunk=chunk, heartbeat=heartbeat)

    monkeypatch.setattr(bench, "stream_oracle_parity", fake)
    assert bench.run_parity_gate(1, 0.08, 0) is True
    assert calls == [0.08, 0.02]  # retried once at a quarter of the scale


def test_run_parity_gate_mismatch_fails(monkeypatch):
    def fake(idx, scale, seed, chunk=64, want_digest=False, heartbeat=None):
        return {"ok": False, "pods": 10, "compared": 10, "keys_checked": 130,
                "mismatches": 1, "sha256": None, "oracle_rc": 0,
                "oracle_err": "", "replay_seconds": 0, "oracle_seconds": 0,
                "first_mismatch": {"pod": 3, "key": "k", "dev": "a",
                                   "oracle": "b"}}

    monkeypatch.setattr(bench, "stream_oracle_parity", fake)
    assert bench.run_parity_gate(1, 0.08, 0) is False


def test_available_gb_positive():
    assert bench._available_gb() > 0


def test_measure_engine_reports_pipeline_spans():
    """measure_engine surfaces the wave-pipeline observability bench.py
    reports (docs/wave-pipeline.md): the commit_and_reflect span plus the
    commit_stream_overlap_seconds / store_batch_writes_total counters on
    a pipelined wave — and no stream counters when the sequential
    post-pass is forced."""
    r = bench.measure_engine(24, 6, seed=0)
    assert r["bound"] > 0
    assert "commit_and_reflect" in r["spans"]
    assert "replay_and_decode_stream" in r["spans"]
    assert r["counters"]["commit_stream_waves_total"] >= 1
    assert "commit_stream_overlap_seconds" in r["counters"]
    # binds land in-wave; the reflect write-backs defer with the lazy
    # decode (docs/wave-pipeline.md lazy-decode stage) and the bench
    # reports what was deferred plus the first-read latencies
    assert r["counters"]["store_batch_writes_total"] >= 24
    assert r["lazy"]["deferred_pods"] == 24
    assert r["lazy"]["cold_read_seconds"] > 0
    assert r["lazy"]["warm_read_seconds"] > 0

    r_seq = bench.measure_engine(24, 6, seed=0, pipeline=False)
    assert r_seq["bound"] == r["bound"]
    assert "commit_stream_waves_total" not in r_seq["counters"]


def test_measure_engine_reports_gang_counters():
    """With gang_groups mixed into the queue, measure_engine reports the
    vectorized quorum pass (gang_quorum_pass_seconds) and admission
    counters alongside the wave-pipeline ones (docs/gang-scheduling.md)."""
    r = bench.measure_engine(16, 6, seed=0, gang_groups=3, gang_members=4)
    assert r["bound"] > 0
    assert r["counters"].get("gang_quorum_pass_seconds", 0) > 0
    assert r["counters"].get("gang_groups_admitted_total", 0) >= 1


def test_measure_gang_shape_reports_counters():
    """The make bench-gang entry: admitted + rolled-back groups both
    show up in the counters, and parked members are reported."""
    r = bench.measure_gang(3, 3, 8, seed=0, plain_pods=4, park_groups=1)
    assert r["counters"].get("gang_groups_admitted_total") == 3
    assert r["counters"].get("gang_quorum_rollbacks_total", 0) >= 1
    assert r["parked"] == 2
    assert r["bound"] == 3 * 3 + 4


# ------------------------------------------------------- bench-check


def _bench_check():
    """Load docs/bench/bench_check.py (make bench-check) as a module."""
    import importlib.util
    from pathlib import Path

    path = Path(bench.__file__).parent / "docs" / "bench" / "bench_check.py"
    spec = importlib.util.spec_from_file_location("bench_check", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench_line(value=900.0, decode=1500.0, overlap=1.4, eng_cps=870.0):
    return {"metric": "m", "value": value, "unit": "cycles/s",
            "extra": {"decode_pods_per_sec": decode,
                      "engine_2k_1k": {
                          "pods": 2000, "cycles_per_sec": eng_cps,
                          "counters": {
                              "commit_stream_overlap_seconds": overlap}}}}


def test_bench_check_ok_and_regressions():
    bc = _bench_check()
    rows = bc.compare(_bench_line(), _bench_line())
    # metrics present on both sides are ok; lazy-era keys these synthetic
    # rounds don't carry SKIP instead of KeyError-ing (union semantics)
    assert all(r["status"] == "ok" for r in rows if r["old"] is not None)
    by = {r["metric"]: r for r in rows}
    assert by["engine_10k_5k_cycles_per_sec"]["status"] == "skip"
    assert by["lazy_cold_first_read_seconds"]["status"] == "skip"
    # >15% drop of a higher-is-better metric fails
    rows = {r["metric"]: r for r in bc.compare(
        _bench_line(), _bench_line(decode=1500.0 * 0.8))}
    assert rows["decode_pods_per_sec"]["status"] == "regression"
    # a 15%-tolerated drift passes
    rows = {r["metric"]: r for r in bc.compare(
        _bench_line(), _bench_line(decode=1500.0 * 0.9))}
    assert rows["decode_pods_per_sec"]["status"] == "ok"
    # wave wall is lower-is-better: slower engine (lower cps -> higher
    # wall) regresses
    rows = {r["metric"]: r for r in bc.compare(
        _bench_line(), _bench_line(eng_cps=870.0 * 0.8))}
    assert rows["engine_2k_1k_wave_wall_seconds"]["status"] == "regression"


def test_bench_check_skips_missing_metrics():
    bc = _bench_check()
    old = _bench_line()
    new = _bench_line()
    del new["extra"]["engine_2k_1k"]  # e.g. a fallback round
    rows = {r["metric"]: r for r in bc.compare(old, new)}
    assert rows["engine_2k_1k_wave_wall_seconds"]["status"] == "skip"
    assert rows["commit_stream_overlap_seconds"]["status"] == "skip"
    assert rows["headline_e2e_cycles_per_sec"]["status"] == "ok"


def test_bench_check_tolerates_keys_missing_from_older_rounds():
    """A metric introduced AFTER the previous round (the lazy-era keys)
    must compare as SKIP against the old round — never KeyError — and
    regress normally once both rounds carry it."""
    bc = _bench_check()
    old = _bench_line()
    new = _bench_line()
    new["extra"]["engine_10k_5k"] = {"pods": 10000, "cycles_per_sec": 1200.0}
    new["extra"]["engine_2k_1k"]["lazy"] = {"cold_read_seconds": 0.02}
    rows = {r["metric"]: r for r in bc.compare(old, new)}
    assert rows["engine_10k_5k_cycles_per_sec"]["status"] == "skip"
    assert rows["lazy_cold_first_read_seconds"]["status"] == "skip"
    # both rounds carrying the key: a >15% slowdown of the cold read
    # (lower-is-better) regresses
    older = _bench_line()
    older["extra"]["engine_2k_1k"]["lazy"] = {"cold_read_seconds": 0.02}
    newer = _bench_line()
    newer["extra"]["engine_2k_1k"]["lazy"] = {"cold_read_seconds": 0.05}
    rows = {r["metric"]: r for r in bc.compare(older, newer)}
    assert rows["lazy_cold_first_read_seconds"]["status"] == "regression"


def test_bench_check_multichip_sanity():
    """check_multichip: the newest MULTICHIP round must have run
    (ok=true, skipped=false); a skipped round fails the gate."""
    import json as json_mod
    import tempfile
    from pathlib import Path

    bc = _bench_check()
    with tempfile.TemporaryDirectory() as d:
        root = Path(d)
        assert bc.check_multichip(root) is None  # no rounds: nothing to gate
        (root / "MULTICHIP_r01.json").write_text(json_mod.dumps(
            {"n": 1, "ok": True, "skipped": False, "n_devices": 8}))
        assert bc.check_multichip(root) is None
        (root / "MULTICHIP_r02.json").write_text(json_mod.dumps(
            {"n": 2, "ok": True, "skipped": True, "reason": "1 device"}))
        err = bc.check_multichip(root)
        assert err is not None and "skipped" in err


def test_bench_check_scale_sanity_and_trajectory(tmp_path):
    """check_scale: the newest SCALE round must be parity-pinned and
    reuse-clean (sanity), and the 100k keys compare newest-vs-previous
    with union/skip semantics — a missing key SKIPs, a present-on-both
    regression fails."""
    import json

    bc = _bench_check()
    assert bc.check_scale(tmp_path) == (None, [])  # no rounds

    good = {"n": 1, "all_parity_ok": True,
            "never_rebuilt_on_unchanged_nodes": True,
            "scale_100k_cycles_per_sec": 12.0,
            "scale_100k_build_seconds": 0.25,
            "scale_100k_host_rss_mb": 9000.0}
    (tmp_path / "SCALE_r01.json").write_text(json.dumps(good))
    err, rows = bc.check_scale(tmp_path)
    assert err is None and rows == []  # one round: sanity only

    # second round: throughput collapsed, build time fine, RSS key absent
    bad = dict(good, n=2, scale_100k_cycles_per_sec=4.0)
    del bad["scale_100k_host_rss_mb"]
    (tmp_path / "SCALE_r02.json").write_text(json.dumps(bad))
    err, rows = bc.check_scale(tmp_path)
    assert err is None
    by = {r["metric"]: r["status"] for r in rows}
    assert by["scale_100k_cycles_per_sec"] == "regression"
    assert by["scale_100k_build_seconds"] == "ok"
    assert by["scale_100k_host_rss_mb"] == "skip"

    # a parity-broken newest round fails sanity outright
    (tmp_path / "SCALE_r03.json").write_text(json.dumps(
        dict(good, n=3, all_parity_ok=False)))
    err, rows = bc.check_scale(tmp_path)
    assert err is not None and "parity" in err and rows == []


def test_bench_check_soak_sanity_and_trajectory(tmp_path):
    """check_soak: the newest SOAK round must be green end to end
    (ok, Retry-After on every shed, ladder back on rung 0), and the
    p99/shed-rate keys compare newest-vs-previous with union/skip
    semantics."""
    import json

    bc = _bench_check()
    assert bc.check_soak(tmp_path) == (None, [])  # no rounds

    good = {"n": 1, "ok": True, "all_shed_had_retry_after": True,
            "soak_recovered_to_rung0": True,
            "soak_p99_wave_seconds": 0.12, "soak_shed_rate": 0.5}
    (tmp_path / "SOAK_r01.json").write_text(json.dumps(good))
    err, rows = bc.check_soak(tmp_path)
    assert err is None and rows == []  # one round: sanity only

    # second round: p99 doubled, shed-rate key absent
    bad = dict(good, n=2, soak_p99_wave_seconds=0.24)
    del bad["soak_shed_rate"]
    (tmp_path / "SOAK_r02.json").write_text(json.dumps(bad))
    err, rows = bc.check_soak(tmp_path)
    assert err is None
    by = {r["metric"]: r["status"] for r in rows}
    assert by["soak_p99_wave_seconds"] == "regression"
    assert by["soak_shed_rate"] == "skip"

    # a round whose ladder ended degraded fails sanity outright
    (tmp_path / "SOAK_r03.json").write_text(json.dumps(
        dict(good, n=3, soak_recovered_to_rung0=False)))
    err, rows = bc.check_soak(tmp_path)
    assert err is not None and "rung 0" in err and rows == []

    # a shed contract violation is also terminal
    (tmp_path / "SOAK_r03.json").write_text(json.dumps(
        dict(good, n=3, all_shed_had_retry_after=False)))
    err, _rows = bc.check_soak(tmp_path)
    assert err is not None and "Retry-After" in err


def test_bench_check_extracts_line_from_round_tail():
    import json

    bc = _bench_check()
    line = _bench_line()
    doc = {"n": 6, "cmd": "python bench.py", "rc": 0,
           "tail": "noise\nmore noise\n" + json.dumps(line) + "\n"}
    assert bc.extract_bench_line(doc) == line
    assert bc.extract_bench_line({"tail": "no json here"}) is None


def test_bench_check_main_exit_codes(tmp_path):
    import json

    bc = _bench_check()
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "tail": json.dumps(_bench_line()) + "\n"}))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"n": 2, "tail": json.dumps(_bench_line(decode=100.0)) + "\n"}))
    assert bc.main(["--dir", str(tmp_path)]) == 1
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"n": 2, "tail": json.dumps(_bench_line(decode=1600.0)) + "\n"}))
    assert bc.main(["--dir", str(tmp_path)]) == 0
    # a single round: nothing to compare, success
    (tmp_path / "BENCH_r02.json").unlink()
    assert bc.main(["--dir", str(tmp_path)]) == 0


def test_bench_check_refuses_tainted_round(tmp_path, capsys):
    """A round produced from a tree with outstanding kss-analyze
    findings recorded in its JSON invalidates the comparison
    (docs/static-analysis.md): refuse, don't gate on skewed numbers."""
    import json

    bc = _bench_check()
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "tail": json.dumps(_bench_line()) + "\n"}))
    tainted = _bench_line()
    tainted["extra"]["analysis"] = {
        "new_findings": 2, "grandfathered": 29,
        "findings": ["pkg/mod.py:3: [pod-loop] f: loop over pods"]}
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"n": 2, "tail": json.dumps(tainted) + "\n"}))
    assert bc.main(["--dir", str(tmp_path)]) == 2
    out = capsys.readouterr().out
    assert "REFUSING" in out and "pod-loop" in out
    # a recorded clean verdict (and rounds predating the field) compare
    clean = _bench_line()
    clean["extra"]["analysis"] = {"new_findings": 0, "grandfathered": 29}
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"n": 2, "tail": json.dumps(clean) + "\n"}))
    assert bc.main(["--dir", str(tmp_path)]) == 0


def test_bench_check_refuses_round_with_failed_chaos(tmp_path, capsys):
    """A round whose embedded chaos verdict failed invalidates the
    comparison (docs/fault-injection.md): the tree no longer survives
    injected faults with bit-identical results — refuse, and point at
    the reproducing seed."""
    import json

    bc = _bench_check()
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "tail": json.dumps(_bench_line()) + "\n"}))
    bad = _bench_line()
    bad["extra"]["chaos"] = {
        "ok": False, "seeds": [1],
        "failures": ["seed 1: chaos-a: state diverged from fault-free "
                     "run at ['p003']"]}
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"n": 2, "tail": json.dumps(bad) + "\n"}))
    assert bc.main(["--dir", str(tmp_path)]) == 2
    out = capsys.readouterr().out
    assert "chaos" in out and "REFUSING" in out and "diverged" in out
    # a green verdict (and rounds predating the field) compare normally
    ok = _bench_line()
    ok["extra"]["chaos"] = {"ok": True, "seeds": [1], "failures": []}
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"n": 2, "tail": json.dumps(ok) + "\n"}))
    assert bc.main(["--dir", str(tmp_path)]) == 0


def test_measure_engine_emits_metrics_snapshot():
    """The BENCH artifact carries the flight-recorder families
    (docs/metrics.md): upstream-named histograms + per-plugin labeled
    counters ride every measure_engine result."""
    r = bench.measure_engine(24, 6, seed=0)
    hists = r["metrics"]["histograms"]
    assert "scheduling_attempt_duration_seconds" in hists
    assert "plugin_execution_duration_seconds" in hists
    lc = r["metrics"]["labeled_counters"]
    assert "plugin_pods_nodes_evaluated_total" in lc
    assert "decode_path_total" in lc
