"""The trace reduction (lib/xplane.py) against traces whose numbers are
known: a hand-made one (every number worked out in the comments), and the
small trace recorded on the v5e that testdata/ keeps as a compact
fixture, with the numbers recorded beside it.

    python3 -m pytest benchmark/tests/test_xplane.py
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

from lib import xplane  # noqa: E402

S = 1e9


def _pd(planes):
    return xplane._Obj(planes=[xplane._Obj(name=pn, lines=[xplane._Obj(
        name=ln, events=[xplane._Obj(name=n, start_ns=a * S, duration_ns=d * S)
                         for n, a, d in evs]) for ln, evs in lines])
        for pn, lines in planes])


def test_hand_made_trace():
    pd = _pd([
        ("/device:TPU:0", [
            # busy: [1.0,1.2] u [1.1,1.4] = 0.4 s, [3.0,3.15] = 0.15 s -> 0.55 s
            ("XLA Ops", [("fusion.1", 1.0, 0.2), ("copy.2", 1.1, 0.3),
                         ("fusion.1", 3.0, 0.15)]),
            ("XLA Modules", [("jit_scan", 0.9, 2.3)]),  # not the Ops line
        ]),
        ("/host:CPU", [
            ("python3", [("pass", 0.0, 4.0), ("compile_workload", 0.1, 0.8),
                         ("tiny", 0.5, 0.001),
                         ("backend_compile_and_load", 1.5, 0.25),
                         ("commit", 3.2, 0.7)]),
            ("other", [("idle", 0.0, 4.0)]),
        ]),
    ])
    r = xplane.reduce_profile(pd)
    assert r["device_planes"] == 1
    assert abs(r["busy_s"] - 0.55) < 1e-9
    assert abs(r["window_s"] - 4.0) < 1e-9
    assert r["device_ops"][0][0] == "fusion.1"
    assert abs(r["device_ops"][0][1] - 0.35) < 1e-9
    assert r["xla_compile_events"] == 1 and abs(r["xla_compile_s"] - 0.25) < 1e-9
    gaps = dict(r["idle_gaps"])
    # idle: [0,1.0] [1.4,3.0] [3.15,4.0] = 3.45 s, labelled by the busiest
    # thread's deepest frame of >= 20 ms: compile_workload 0.1..0.9 = 0.8,
    # the compile 1.5..1.75 = 0.25, commit 3.2..3.9 = 0.7, the rest `pass`
    assert abs(sum(gaps.values()) - 3.45) < 1e-6
    assert abs(gaps["compile_workload"] - 0.8) < 2e-3
    assert abs(gaps["backend_compile_and_load"] - 0.25) < 2e-3
    assert abs(gaps["commit"] - 0.7) < 2e-3
    assert abs(gaps["pass"] - 1.70) < 4e-3


def test_recorded_trace():
    want = json.loads((BENCH / "testdata" / "recorded_trace.expected.json").read_text())
    r = xplane.reduce_profile(xplane.load_fixture(
        BENCH / "testdata" / "recorded_trace.json.gz"))
    for k in ("device_planes", "xla_compile_events"):
        assert r[k] == want[k], (k, r[k], want[k])
    for k in ("busy_s", "window_s", "xla_compile_s"):
        assert abs(r[k] - want[k]) <= 1e-9 + 1e-6 * abs(want[k]), (k, r[k], want[k])
    assert r["busy_s"] > 0
    assert [n for n, _ in r["device_ops"]] == [n for n, _ in want["device_ops"]]
    assert [n for n, _ in r["idle_gaps"][:3]] == [n for n, _ in want["idle_gaps"][:3]]


if __name__ == "__main__":
    test_hand_made_trace()
    test_recorded_trace()
    print("ok")
