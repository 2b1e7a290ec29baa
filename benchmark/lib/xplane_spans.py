"""The program's own spans on the profiler's clock.

While a profile runs, the program's tracer opens a TraceMe named
kss:<span> for every span (utils/tracing.py), so the spans sit on their
thread's line of the host plane of the same .xplane.pb as the device
ops.  This reduces that file to: of the seconds in which no operation
ran on the device, how many fall inside a kss: span of the scheduling
loop's thread, and under which span.

    python3 benchmark/lib/xplane_spans.py <profile dir or .xplane.pb> [--out <dir>]
        -> one JSON line; with --out also <dir>/kss_idle_by_span.json
    ... --fixture <out.json.gz>    keep the trace as a compact fixture

What is read:
  thread        the host line with the most kss: events (the scheduling
                loop: ~30 spans a pass; a handler thread has 1 a request)
  range_s       first kss: start to last kss: end on that thread.  A
                TraceMe is recorded when it ENDS inside the profile, so a
                span open when the profile starts or stops leaves no
                event: outside this range the spans could not be seen,
                and it is left out of both numbers below
  idle_s        seconds of the range in which no "XLA Ops" event ran on
                any TPU plane
  idle_in_spans_s   the part of idle_s inside a kss: event of that thread
  idle_by_span  idle_in_spans_s by the DEEPEST kss: span covering it
  trace_idle_s  device-idle seconds of the whole trace, for comparison
Works whether the profiler's Python tracer is on or off: only kss:
events are read.  A trace without any returns kss_events 0.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from lib.xplane import find_xplane, merge  # noqa: E402

PREFIX = "kss:"
OUT_NAME = "kss_idle_by_span.json"


def deepest_segments(events: list[tuple[float, float, str]]
                     ) -> list[tuple[float, float, str]]:
    """Properly nested (start, end, name) events of one thread -> disjoint
    (start, end, name) segments, each named by the deepest event covering
    it, in time order."""
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[float, float, str]] = []
    cur = 0.0

    def close_until(t: float) -> None:
        nonlocal cur
        while stack and stack[-1][1] <= t:
            _a, b, name = stack.pop()
            if b > cur:
                out.append((cur, b, name))
                cur = b

    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        close_until(a)
        if stack and a > cur:
            out.append((cur, a, stack[-1][2]))
        cur = max(cur, a)
        stack.append((a, b, name))
    close_until(float("inf"))
    return out


def overlap_by_name(gaps: list[tuple[float, float]],
                    segments: list[tuple[float, float, str]]
                    ) -> dict[str, float]:
    """Seconds of `gaps` (disjoint, sorted) inside each of `segments`
    (disjoint, sorted), by segment name."""
    out: dict[str, float] = {}
    i = 0
    for a, b, name in segments:
        while i < len(gaps) and gaps[i][1] <= a:
            i += 1
        j = i
        while j < len(gaps) and gaps[j][0] < b:
            s = min(b, gaps[j][1]) - max(a, gaps[j][0])
            if s > 0:
                out[name] = out.get(name, 0.0) + s
            j += 1
    return out


def complement(busy: list[tuple[float, float]], lo: float, hi: float
               ) -> list[tuple[float, float]]:
    gaps, cur = [], lo
    for a, b in busy:
        if b <= lo:
            continue
        if a >= hi:
            break
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def reduce_spans(pd) -> dict:
    """pd: jax.profiler.ProfileData, or the same planes / lines / events
    shape (what the tests and the fixture feed it)."""
    t_min, t_max = float("inf"), float("-inf")
    busy: list[tuple[float, float]] = []
    threads: list[tuple[str, list[tuple[float, float, str]]]] = []
    for plane in pd.planes:
        is_device = plane.name.startswith("/device:") and "TPU" in plane.name
        lines = list(plane.lines)
        only_ops = is_device and any(ln.name == "XLA Ops" for ln in lines)
        for ln in lines:
            is_ops = is_device and (ln.name == "XLA Ops" or not only_ops)
            kss = []
            for e in ln.events:
                a = e.start_ns / 1e9
                b = a + e.duration_ns / 1e9
                t_min, t_max = min(t_min, a), max(t_max, b)
                if is_device:
                    if is_ops:
                        busy.append((a, b))
                elif e.name.startswith(PREFIX):
                    kss.append((a, b, e.name[len(PREFIX):]))
            if kss:
                threads.append((ln.name, kss))
    busy = merge(busy)
    out = {"threads_with_spans": len(threads), "kss_events": 0,
           "thread": None, "range_s": 0.0, "idle_s": 0.0,
           "idle_in_spans_s": 0.0, "idle_by_span": [],
           "trace_idle_s": (sum(b - a for a, b in complement(busy, t_min, t_max))
                            if t_max > t_min else 0.0)}
    if not threads:
        return out
    name, kss = max(threads, key=lambda t: len(t[1]))
    lo = min(a for a, _b, _n in kss)
    hi = max(b for _a, b, _n in kss)
    gaps = complement(busy, lo, hi)
    by_span = overlap_by_name(gaps, deepest_segments(kss))
    out.update(
        thread=name, kss_events=len(kss), range_s=hi - lo,
        idle_s=sum(b - a for a, b in gaps),
        idle_in_spans_s=sum(by_span.values()),
        idle_by_span=[[n, s] for n, s in
                      sorted(by_span.items(), key=lambda kv: -kv[1])])
    return out


def dump_fixture(pd, out: Path) -> None:
    """Write what reduce_spans reads of `pd` (every device event, every
    kss: event) in lib/xplane.py's fixture shape, for load_fixture."""
    import gzip

    planes = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for ln in plane.lines:
            evs = [[e.name, e.start_ns, e.duration_ns] for e in ln.events
                   if device or e.name.startswith(PREFIX)]
            if evs:
                lines.append({"name": ln.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    with gzip.open(out, "wt") as f:
        json.dump({"planes": planes}, f)


def work_dir_of(xplane_file: Path) -> Path:
    """<work>/profile/plugins/profile/<run>/<host>.xplane.pb -> <work>;
    a file that is not laid out so keeps its own directory."""
    for parent in xplane_file.parents:
        if parent.name == "plugins":
            return parent.parent.parent
    return xplane_file.parent


def reduce_in_child(xplane_file: Path) -> dict | None:
    """Reading the file needs jax.profiler.ProfileData: a child with
    JAX_PLATFORMS=cpu, like lib/xplane.py's.  A child that fails leaves
    its reason in xplane_spans.log and the caller gets nothing."""
    work = work_dir_of(xplane_file)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    with open(work / "xplane_spans.log", "ab") as errf:
        p = subprocess.run([sys.executable, str(Path(__file__).resolve()),
                            str(xplane_file), "--out", str(work)],
                           env=env, stdout=subprocess.PIPE, stderr=errf,
                           timeout=280)
    if p.returncode != 0:
        return None
    return json.loads(p.stdout.decode().strip().splitlines()[-1])


if __name__ == "__main__":
    from jax.profiler import ProfileData

    src = find_xplane(Path(sys.argv[1]))
    data = ProfileData.from_file(str(src))
    if len(sys.argv) == 4 and sys.argv[2] == "--fixture":
        dump_fixture(data, Path(sys.argv[3]))
    res = dict(reduce_spans(data), file=str(src))
    if len(sys.argv) == 4 and sys.argv[2] == "--out":
        (Path(sys.argv[3]) / OUT_NAME).write_text(json.dumps(res, indent=1))
    print(json.dumps(res))
