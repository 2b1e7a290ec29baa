"""From the profiler's .xplane.pb to the numbers the per-layer metrics
read.  The yardstick's own reduction: a later PR cannot change it.

    python3 benchmark/lib/xplane.py <profile dir or .xplane.pb>   -> one JSON line

Reading the file needs jax.profiler.ProfileData, so this runs as a child
with JAX_PLATFORMS=cpu (`reduce_in_child`): the benchmark's parent never
imports JAX, and the chip stays the server's.

What is read:
  busy_s      per device plane ("/device:TPU:n"), the union of the
              intervals of its "XLA Ops" line; averaged over the planes
  window_s    first event start to last event end over the whole trace
  device_ops  the ten operations with the most device time
  idle_gaps   the time no operation ran, by what the host's busiest
              Python thread (the scheduling loop) was doing: the deepest
              frame or TraceMe of at least LABEL_MIN_S that covers the
              instant, sampled every SAMPLE_S; gaps under 50 us are
              pooled as between_device_ops
  xla_compile_s / xla_compile_events
              the host's `backend_compile_and_load` TraceMe events (a real
              XLA compile; a persistent-cache hit does not open one)
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

LABEL_MIN_S = 0.020
SAMPLE_S = 0.001
SHORT_GAP_S = 50e-6
COMPILE_EVENT = "backend_compile_and_load"


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def label_gaps(gaps: list[tuple[float, float]],
               frames: list[tuple[float, float, str]]) -> dict[str, float]:
    """Seconds of `gaps` by the deepest of `frames` covering each sampled
    instant (frames: (start, end, name), any order)."""
    frames = sorted(f for f in frames if f[1] - f[0] >= LABEL_MIN_S)
    out: dict[str, float] = {}
    active: list[tuple[float, float, str]] = []
    nxt = 0
    for a, b in gaps:
        if b - a < SHORT_GAP_S:
            out["between_device_ops"] = out.get("between_device_ops", 0.0) + b - a
            continue
        n = max(1, int((b - a) / SAMPLE_S))
        dt = (b - a) / n
        for i in range(n):
            t = a + (i + 0.5) * dt
            while nxt < len(frames) and frames[nxt][0] <= t:
                active.append(frames[nxt])
                nxt += 1
            active = [f for f in active if f[1] > t]
            name = max(active)[2] if active else "no_frame_on_the_busiest_thread"
            out[name] = out.get(name, 0.0) + dt
    return out


def _plain(name: str) -> str:
    """An operation's or frame's name as letters, digits, _ . : - only."""
    return re.sub(r"[^A-Za-z0-9_.:-]+", "_", name).strip("_")[:64]


def reduce_profile(pd) -> dict:
    """pd: jax.profiler.ProfileData (or anything with the same planes /
    lines / events shape, which is what the test feeds it)."""
    t_min, t_max = float("inf"), float("-inf")
    busy_per_plane: list[float] = []
    busy_all: list[tuple[float, float]] = []
    op_time: dict[str, float] = {}
    compile_s, compile_n = 0.0, 0
    threads: list[tuple[int, list[tuple[float, float, str]]]] = []
    for plane in pd.planes:
        is_device = plane.name.startswith("/device:") and "TPU" in plane.name
        lines = list(plane.lines)
        if is_device:
            ops_lines = [ln for ln in lines if ln.name == "XLA Ops"] or lines
            iv = []
            for ln in ops_lines:
                for e in ln.events:
                    a = e.start_ns / 1e9
                    b = a + e.duration_ns / 1e9
                    iv.append((a, b))
                    op_time[e.name] = op_time.get(e.name, 0.0) + b - a
            merged = merge(iv)
            busy_per_plane.append(sum(b - a for a, b in merged))
            busy_all.extend(merged)
        for ln in lines:
            frames = []
            for e in ln.events:
                a = e.start_ns / 1e9
                b = a + e.duration_ns / 1e9
                t_min, t_max = min(t_min, a), max(t_max, b)
                if is_device:
                    continue
                if e.name == COMPILE_EVENT:
                    compile_s += b - a
                    compile_n += 1
                frames.append((a, b, e.name))
            if not is_device and plane.name.startswith("/host:"):
                threads.append((len(frames), frames))
    window_s = max(t_max - t_min, 0.0) if t_max > t_min else 0.0
    busy_s = sum(busy_per_plane) / len(busy_per_plane) if busy_per_plane else 0.0
    gaps: list[tuple[float, float]] = []
    if window_s:
        cur = t_min
        for a, b in merge(busy_all):
            if a > cur:
                gaps.append((cur, a))
            cur = max(cur, b)
        if t_max > cur:
            gaps.append((cur, t_max))
    busiest = max(threads, key=lambda t: t[0])[1] if threads else []
    by_label = label_gaps(gaps, busiest)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "device_planes": len(busy_per_plane), "busy_s": busy_s,
        "window_s": window_s,
        "device_ops": [[_plain(name), s] for name, s in top],
        "idle_gaps": [[_plain(name), s] for name, s in
                      sorted(by_label.items(), key=lambda kv: -kv[1])[:10]],
        "xla_compile_s": compile_s, "xla_compile_events": compile_n,
    }


class _Obj:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def load_fixture(path: Path):
    """A recorded trace kept as compact JSON (see `--fixture`), in the
    shape reduce_profile reads: planes -> lines -> events."""
    import gzip

    doc = json.loads(gzip.open(path).read())
    return _Obj(planes=[_Obj(name=pl["name"], lines=[_Obj(
        name=ln["name"], events=[_Obj(name=n, start_ns=a, duration_ns=d)
                                 for n, a, d in ln["events"]])
        for ln in pl["lines"]]) for pl in doc["planes"]])


def dump_fixture(pd, out: Path, host_min_ns: float = 2e6) -> None:
    """Write `pd` as a fixture: every device event, every compile event,
    and the host events of at least host_min_ns (the rest is noise to the
    reduction: gap labels take frames of LABEL_MIN_S and more)."""
    import gzip

    planes = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for ln in plane.lines:
            evs = [[e.name, e.start_ns, e.duration_ns] for e in ln.events
                   if device or e.duration_ns >= host_min_ns
                   or e.name == COMPILE_EVENT]
            if evs:
                lines.append({"name": ln.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    with gzip.open(out, "wt") as f:
        json.dump({"planes": planes}, f)


def find_xplane(path: Path) -> Path:
    if path.is_file():
        return path
    found = sorted(path.glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def reduce_in_child(profile_dir: str, work: Path, repo: Path) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    with open(work / "xplane.log", "ab") as errf:
        p = subprocess.run([sys.executable, str(Path(__file__).resolve()),
                            profile_dir], cwd=str(repo), env=env,
                           stdout=subprocess.PIPE, stderr=errf, timeout=280)
    if p.returncode != 0:
        raise RuntimeError(f"the trace reduction exited {p.returncode} "
                           f"(see {work / 'xplane.log'})")
    return json.loads(p.stdout.decode().strip().splitlines()[-1])


if __name__ == "__main__":
    from jax.profiler import ProfileData

    src = find_xplane(Path(sys.argv[1]))
    if len(sys.argv) == 4 and sys.argv[2] == "--fixture":
        dump_fixture(ProfileData.from_file(str(src)), Path(sys.argv[3]))
    print(json.dumps(dict(reduce_profile(ProfileData.from_file(str(src))),
                          file=str(src), bytes=src.stat().st_size)))
