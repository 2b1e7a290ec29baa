"""lib/xplane_spans.py (the program's own spans on the profiler's clock)
against traces whose numbers are known: a hand-made one, every number
worked out in the comments, and the trace recorded on the v5e that
testdata/ keeps as a compact fixture with its numbers beside it.

    python3 -m pytest benchmark/tests/test_xplane_spans.py
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

from lib import xplane, xplane_spans  # noqa: E402
from tests.test_xplane import _pd  # noqa: E402


def test_deepest_segments():
    segs = xplane_spans.deepest_segments([
        (5.0, 6.0, "loop_idle"), (1.5, 1.7, "cw_core"),
        (1.2, 2.2, "compile_workload"), (1.0, 4.0, "wave")])
    assert segs == [
        (1.0, 1.2, "wave"), (1.2, 1.5, "compile_workload"),
        (1.5, 1.7, "cw_core"), (1.7, 2.2, "compile_workload"),
        (2.2, 4.0, "wave"), (5.0, 6.0, "loop_idle")]


def test_hand_made_trace():
    pd = _pd([
        ("/device:TPU:0", [
            # busy: [2.0,2.5] and [6.0,6.2]
            ("XLA Ops", [("fusion.1", 2.0, 0.5), ("copy.2", 6.0, 0.2)]),
            ("XLA Modules", [("jit_scan", 0.0, 9.0)]),  # not the Ops line
        ]),
        ("/host:CPU", [
            # the loop: 5 kss: events, range 1.0 .. 8.0; python frames and
            # other TraceMes on the same line are not read
            ("loop", [("kss:loop_idle", 1.0, 0.5), ("kss:loop_pass", 1.5, 3.5),
                      ("kss:wave", 1.6, 3.0), ("kss:compile_workload", 1.7, 1.0),
                      ("kss:loop_idle", 5.5, 2.5), ("threading.wait", 0.0, 9.0),
                      ("backend_compile_and_load", 3.0, 0.1)]),
            ("handler", [("kss:http_pod_read", 4.0, 0.1)]),
        ]),
    ])
    r = xplane_spans.reduce_spans(pd)
    assert r["threads_with_spans"] == 2 and r["thread"] == "loop"
    assert r["kss_events"] == 5
    assert abs(r["range_s"] - 7.0) < 1e-9
    # idle in 1.0..8.0: 7.0 - 0.5 - 0.2 = 6.3 s; [5.0,5.5] is in no span
    assert abs(r["idle_s"] - 6.3) < 1e-9
    assert abs(r["idle_in_spans_s"] - 5.8) < 1e-9
    # whole trace 0..9: 9.0 - 0.7
    assert abs(r["trace_idle_s"] - 8.3) < 1e-9
    by = dict(r["idle_by_span"])
    # loop_idle 0.5 + (2.5 - 0.2 busy) = 2.8; compile_workload 1.7..2.7
    # less busy 2.0..2.5 = 0.5; wave 1.6..1.7 + 2.7..4.6 = 2.0;
    # loop_pass 1.5..1.6 + 4.6..5.0 = 0.5
    assert abs(by["loop_idle"] - 2.8) < 1e-9
    assert abs(by["compile_workload"] - 0.5) < 1e-9
    assert abs(by["wave"] - 2.0) < 1e-9
    assert abs(by["loop_pass"] - 0.5) < 1e-9


def test_trace_without_spans_reads_nothing():
    pd = _pd([("/device:TPU:0", [("XLA Ops", [("fusion.1", 1.0, 0.5)])]),
              ("/host:CPU", [("loop", [("compile_workload", 0.0, 2.0)])])])
    r = xplane_spans.reduce_spans(pd)
    assert r["kss_events"] == 0 and r["thread"] is None
    assert abs(r["trace_idle_s"] - 1.5) < 1e-9


def test_recorded_trace():
    want = json.loads(
        (BENCH / "testdata" / "recorded_spans.expected.json").read_text())
    r = xplane_spans.reduce_spans(xplane.load_fixture(
        BENCH / "testdata" / "recorded_spans.json.gz"))
    for k in ("threads_with_spans", "kss_events", "thread"):
        assert r[k] == want[k], (k, r[k], want[k])
    # (trace_idle_s spans the whole trace: it depends on host events the
    # fixture does not keep, so it is not compared)
    for k in ("range_s", "idle_s", "idle_in_spans_s"):
        assert abs(r[k] - want[k]) <= 1e-9 + 1e-6 * abs(want[k]), (k, r[k], want[k])
    assert r["kss_events"] > 0 and r["idle_in_spans_s"] > 0
    assert [n for n, _ in r["idle_by_span"][:5]] == \
        [n for n, _ in want["idle_by_span"][:5]]


if __name__ == "__main__":
    test_deepest_segments()
    test_hand_made_trace()
    test_trace_without_spans_reads_nothing()
    test_recorded_trace()
    print("ok")
