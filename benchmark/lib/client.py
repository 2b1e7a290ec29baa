"""The benchmark's side of the HTTP API: server child, requests, the watch
stream, reads and the "no hidden rung" check.

`start_server`, `submit` (429 + Retry-After), the pod-order watch,
`read_pod` and `no_hidden_rung` are COPIES of chip_smoke.py's (PR 21),
kept here so that a later PR can change the program and its smoke test
but not the yardstick.  Nothing here imports the program, or JAX.
"""

from __future__ import annotations

import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

PKG = "kube_scheduler_simulator_tpu"


class BenchFailure(Exception):
    """The run cannot produce a result (exit code != 0, no result line)."""


def check(cond, msg: str) -> None:
    if not cond:
        raise BenchFailure(msg)


# ---------------------------------------------------------------- children

CHILDREN: list[subprocess.Popen] = []


def spawn(cmd: list[str], log_path: Path, env: dict, cwd: Path,
          stdout=None, stdin=None) -> subprocess.Popen:
    """Start a child in its own process group (stop() kills the group, so
    a child's own children go with it); stderr to log_path."""
    errf = open(log_path, "ab")
    try:
        p = subprocess.Popen(
            cmd, cwd=str(cwd), env=env, stdin=stdin,
            stdout=stdout if stdout is not None else errf, stderr=errf,
            start_new_session=True)
    finally:
        errf.close()
    CHILDREN.append(p)
    return p


def stop(p: subprocess.Popen, grace: float = 15.0) -> int:
    if p.poll() is None:
        p.terminate()
        try:
            p.wait(timeout=grace)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(p.pid, signal.SIGKILL)  # stragglers of its group
    except (ProcessLookupError, PermissionError):
        pass
    return p.wait()


def stop_children() -> None:
    for p in CHILDREN:
        stop(p, grace=5.0)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# -------------------------------------------------------------------- http

class Client:
    """One keep-alive connection to the server, used by one thread."""

    def __init__(self, port: int, timeout: float = 600,
                 retry_cap_s: float | None = None):
        self.port, self.timeout = port, timeout
        self.retry_cap_s = retry_cap_s
        self.conn: http.client.HTTPConnection | None = None
        self.shed_429s = 0
        self.requests_sent = 0

    def raw(self, method: str, path: str, body: bytes | None = None
            ) -> tuple[int, bytes]:
        """One request; `body` is already-encoded JSON.  A dropped
        keep-alive connection is reopened once."""
        for attempt in (0, 1):
            if self.conn is None:
                self.conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=self.timeout)
            try:
                self.conn.request(method, path, body=body, headers={
                    "Content-Type": "application/json"})
                resp = self.conn.getresponse()
                return resp.status, resp.read()
            except (http.client.HTTPException, ConnectionError, OSError):
                self.conn.close()
                self.conn = None
                if attempt:
                    raise
        raise AssertionError("unreachable")

    def api(self, method: str, path: str, body=None):
        data = json.dumps(body).encode() if body is not None else None
        code, raw = self.raw(method, path, data)
        return code, (json.loads(raw) if raw else None)

    def ok(self, method: str, path: str, body=None, want=(200,)):
        code, out = self.api(method, path, body)
        check(code in want, f"{method} {path} -> {code}: {str(out)[:300]}")
        return out

    def submit(self, path: str, body: bytes, deadline: float,
               want=(200, 201)) -> int:
        """POST workload like a well-behaved client: the autopilot answers
        429 + Retry-After while a session's SLO window is in breach; wait
        and try again.  The wait is the server's Retry-After; only while
        retry_cap_s is set (run.py's warm-up, which is set-up) is it cut
        to that, as chip_smoke.py cuts it to 2 s: Retry-After is 2 x the
        window's p99, tens of seconds after a cold start, while a shed
        lasts two autopilot ticks.  -> how many 429s this request met."""
        shed = 0
        while True:
            self.requests_sent += 1
            code, raw = self.raw("POST", path, body)
            if code != 429:
                check(code in want, f"POST {path} -> {code}: {raw[:300]!r}")
                return shed
            shed += 1
            self.shed_429s += 1
            check(time.time() < deadline, f"POST {path} still shed at the deadline")
            out = json.loads(raw) if raw else {}
            wait = float(out.get("retryAfterSeconds") or 1)
            time.sleep(wait if self.retry_cap_s is None
                       else min(wait, self.retry_cap_s))

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None


def spawn_server(repo: Path, log_path: Path
                 ) -> tuple[subprocess.Popen, int]:
    """`python -m ...cmd.simulator` on an ephemeral PORT with the
    environment otherwise untouched (a user's defaults: autopilot on, 50 ms
    debounce, tracer on, default profile): the child owns the chip.
    chip_smoke.py's start_server, split so that the caller can build its
    data while the child boots."""
    port = free_port()
    env = {**os.environ, "PORT": str(port)}
    p = spawn([sys.executable, "-m", f"{PKG}.cmd.simulator"], log_path,
              env=env, cwd=repo)
    return p, port


def wait_healthy(p: subprocess.Popen, port: int, log_path: Path,
                 deadline: float) -> None:
    c = Client(port, timeout=2)
    while True:
        check(p.poll() is None,
              f"the server exited {p.returncode} at start-up (see {log_path})")
        check(time.time() < deadline, "the server never answered /healthz")
        try:
            if c.raw("GET", "/healthz")[0] == 200:
                c.close()
                return
        except (http.client.HTTPException, OSError):
            time.sleep(0.1)


def device_of(c: Client) -> dict:
    fp = c.ok("GET", "/api/v1/debug/dump")["dump"]["device"]
    check(fp.get("available") and fp.get("devices"),
          f"the server reports no device: {fp}")
    d0 = fp["devices"][0]
    return {"platform": d0["platform"], "kind": d0["kind"],
            "count": len(fp["devices"])}


# ------------------------------------------------------------ watch stream

_HEAD = re.compile(rb'^\{"kind": "(\w+)", "eventType": "(\w+)"')
_NAME = re.compile(rb'"name": "([^"]+)"')
_RV = re.compile(rb'"resourceVersion": "(\d+)"')
_SMALL = 1 << 15


class WatchStream:
    """GET /api/v1/listwatchresources, held open for the whole run: the
    client learns pod creations (queue order = creation resourceVersion)
    and decisions from it, never by polling a list.

    One event arrives per HTTP chunk.  An event that carries a pod's
    result annotations is megabytes of JSON at 5,000 nodes; the client
    does not parse those (it would spend more CPU reading results than
    the server spends producing them, and fall behind the stream): it
    takes kind, type, name and resourceVersion from the head and looks
    for `"nodeName": "` / the Unschedulable mark in the bytes.  Quotes
    inside annotation values are escaped, so neither can match there.
    """

    def __init__(self, port: int):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=600)
        self.sock.sendall(b"GET /api/v1/listwatchresources HTTP/1.1\r\n"
                          b"Host: 127.0.0.1\r\n\r\n")
        self.f = self.sock.makefile("rb", buffering=1 << 20)
        status = self.f.readline()
        check(b" 200 " in status, f"watch -> {status!r}")
        while self.f.readline() not in (b"\r\n", b""):
            pass
        self.cond = threading.Condition()
        self.rv: dict[str, int] = {}        # pod name -> creation rv
        self.decided: dict[str, str] = {}   # pod name -> node ("" = mark)
        self.decided_at: dict[str, float] = {}
        self.events = 0
        self.bytes = 0
        self.error: BaseException | None = None
        self._closing = False
        self._t = threading.Thread(target=self._read, daemon=True)
        self._t.start()

    def _read(self) -> None:
        try:
            while True:
                line = self.f.readline()
                if not line:
                    return
                size = int(line.strip() or b"0", 16)
                if size == 0:
                    return
                data = self.f.read(size)
                self.f.read(2)
                self._event(data)
        except (OSError, ValueError, AttributeError) as e:
            if not self._closing:
                with self.cond:
                    self.error = e
                    self.cond.notify_all()

    def _event(self, data: bytes) -> None:
        self.events += 1
        self.bytes += len(data)
        m = _HEAD.match(data)
        if m is None or m.group(1) != b"Pod":
            return
        etype = m.group(2)
        if len(data) <= _SMALL:
            return self._event_parsed(data, etype)
        ann = data.find(b'"annotations": {')
        head = data[:ann if 0 < ann < 4096 else 4096]
        mn, mr = _NAME.search(head, m.end()), _RV.search(head)
        if mn is None or mr is None or ann < 0:
            return self._event_parsed(data, etype)
        i = data.find(b'"nodeName": "')
        node = None
        if i >= 0:
            node = data[i + 13:data.index(b'"', i + 13)].decode() or None
        if node is None and data.find(b'"reason": "Unschedulable"') >= 0:
            node = ""
        self._note(mn.group(1).decode(), int(mr.group(1)), etype, node)

    def _event_parsed(self, data: bytes, etype: bytes) -> None:
        """A small event (or one whose head could not be read): parse it."""
        obj = json.loads(data)["obj"]
        node = pod_decision(obj)
        self._note(obj["metadata"]["name"],
                   int(obj["metadata"]["resourceVersion"]), etype, node)

    def _note(self, name: str, rv: int, etype: bytes, node) -> None:
        with self.cond:
            if etype == b"ADDED":
                self.rv.setdefault(name, rv)
            if node is not None and name not in self.decided:
                self.decided[name] = node
                self.decided_at[name] = time.time()
                self.cond.notify_all()
            elif etype == b"ADDED":
                self.cond.notify_all()

    def wait_decided(self, names: list[str], deadline: float) -> float:
        """Block until every named pod carries a decision; -> the time the
        last one was seen."""
        with self.cond:
            while True:
                if all(n in self.decided for n in names):
                    return max(self.decided_at[n] for n in names)
                check(self.error is None, f"watch stream failed: {self.error!r}")
                check(time.time() < deadline,
                      f"{sum(n not in self.decided for n in names)} of "
                      f"{len(names)} pods undecided at the deadline")
                self.cond.wait(timeout=1.0)

    def queue_order(self, names: list[str]) -> list[str]:
        """PrioritySort's FIFO among equal priorities: creation order."""
        with self.cond:
            return sorted(names, key=lambda n: self.rv[n])

    def close(self) -> None:
        self._closing = True
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self.sock.close()
        self._t.join(timeout=5)


# ------------------------------------------------------------------ checks

def counters(c: Client) -> dict:
    """Flat view of /api/v1/metrics: plain counters and gauges by name,
    labeled counters as name{k=v,...} (session label dropped), spans as
    span:<name> (total seconds) and spancount:<name>."""
    snap = c.ok("GET", "/api/v1/metrics")
    flat = dict(snap.get("counters") or {})
    flat.update({f"gauge:{k}": v for k, v in (snap.get("gauges") or {}).items()})
    for name, series in (snap.get("labeled_counters") or {}).items():
        total = 0
        for s in series:
            labels = ",".join(f"{k}={v}" for k, v in sorted(s["labels"].items())
                              if k != "session")
            key = f"{name}{{{labels}}}"
            flat[key] = flat.get(key, 0) + s["value"]
            total += s["value"]
        flat[name] = flat.get(name, 0) + total
    for name, agg in (snap.get("spans") or {}).items():
        flat[f"span:{name}"] = agg.get("total_seconds", 0.0)
        flat[f"spancount:{name}"] = agg.get("count", 0)
    return flat


def no_hidden_rung(c: Client, platform: str) -> dict:
    """Nothing below the configured path served this run."""
    code, ready = c.api("GET", "/readyz")
    check(code == 200 and ready.get("status") == "ready", f"/readyz: {ready}")
    problems = []
    for k in ("lastCrash", "crashes", "degradedSessions"):
        if k in ready:
            problems.append(f"/readyz reports {k}: {ready.get(k)}")
    cs = counters(c)
    for name in ("wave_degradations_total", "wave_retries_total",
                 "decode_failures_total", "scheduling_loop_crashes_total",
                 "native_codec_load_failures_total"):
        if cs.get(name):
            problems.append(f"{name} = {cs.get(name)}")
    paths = {k: v for k, v in cs.items() if k.startswith("decode_path_total{")}
    if set(paths) != {"decode_path_total{path=native_chunk}"}:
        problems.append(f"decode paths served: {paths}")
    sess = c.ok("GET", "/api/v1/sessions/default")
    if sess.get("resultMode") != "device_resident" or sess.get("degraded"):
        problems.append(f"result mode {sess.get('resultMode')!r}, "
                        f"degraded={sess.get('degraded')}")
    hbm = cs.get("gauge:hbm_stats_available")
    # the CPU backend reports no memory stats; a chip must
    if hbm != (0 if platform == "cpu" else 1):
        problems.append(f"hbm_stats_available = {hbm}")
    return {"problems": problems, "decode_paths": paths,
            "hbm_peak_bytes": cs.get("gauge:hbm_peak_bytes"),
            "device_chunks_spilled_total":
                cs.get("device_chunks_spilled_total", 0)}


def pod_decision(pod: dict) -> str | None:
    """The node a pod is bound to, "" for an Unschedulable mark, None
    when the scheduler has not decided."""
    node = (pod.get("spec") or {}).get("nodeName")
    if node:
        return node
    for cnd in (pod.get("status") or {}).get("conditions") or []:
        if cnd.get("type") == "PodScheduled" and cnd.get("reason") == "Unschedulable":
            return ""
    return None


def read_pod(c: Client, namespace: str, name: str, keys: list[str]
             ) -> tuple[dict | None, float, str | None]:
    """One full GET of a pod over HTTP -> (pod, seconds, problem).  Well
    formed means: a decision, all 13 result annotations, each parsing."""
    t0 = time.time()
    code, raw = c.raw("GET", f"/api/v1/pods/{namespace}/{name}")
    dt = time.time() - t0
    if code != 200:
        return None, dt, f"GET pod {name} -> {code}"
    pod = json.loads(raw)
    if pod_decision(pod) is None:
        return pod, dt, f"pod {name} carries no decision"
    anns = pod["metadata"].get("annotations") or {}
    for k in keys:
        if k not in anns:
            return pod, dt, f"pod {name} lacks annotation {k}"
        if not k.endswith("/selected-node"):
            try:
                json.loads(anns[k])
            except ValueError:
                return pod, dt, f"pod {name}: annotation {k} does not parse"
    return pod, dt, None
