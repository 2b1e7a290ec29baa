#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path starts on the chip.

Drives the system the way a user does: `python -m
kube_scheduler_simulator_tpu.cmd.simulator` as a child process, a cluster
and a queue POSTed over HTTP, results read back over HTTP.  Claims no
number: every wall time below is a smoke timing, not a benchmark result.

Phases (any failure is a non-zero exit; nothing is reported as null):

  served   one simulator child owns the chip.  The parent asks it which
           device it is on (GET /api/v1/debug/dump) and FAILS unless that
           is --platform.  Wave A: BASELINE config 4's cluster and queue
           (--nodes x --pods, from --seed) under the default profile — the
           sequential scan, committed after the pass.  Wave B: after PUT
           /api/v1/reset, the same cluster and queue under the config-4
           profile — the same scan, its commit streamed chunk by chunk;
           every pass of it is counted by its route (one packed call up to
           512 pods, the chunked scan over leaves beyond).  Per wave: the
           first --prefix pods in queue order byte-equal to
           reference_impl/sequential.py, cold reads from the
           first, a middle and the last replay chunk, engine counters that
           account for every pod, and no hidden rung (no degradation,
           retry, decode failure or loop crash; native chunk decode only;
           device-resident results).
  gate     after that child has exited, one process owns the chip and
           replays BASELINE configs 1-5 at --gate-scale against a streamed
           CPU oracle, every annotation of every pod
           (reference_impl/parity_gate.py stream_oracle_parity).
  external a simulator child with externalSchedulerEnabled (it must not
           touch the chip), then `python -m ...cmd.scheduler --once` as
           the only process on the chip; every pod ends up bound.

One process per chip, always: this parent never imports JAX, children
that need none run with JAX_PLATFORMS=cpu, and chip phases run one after
another.  The compile cache is the program's own fixed directory
(JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache); this script sets
none.

Rehearsal in a sandbox without a chip is something the caller asks for:
    python chip_smoke.py --platform cpu --nodes 50 --pods 100 \\
        --ext-nodes 20 --ext-pods 40 --gate-scale 0.02
Nothing is inferred from a missing chip: without --platform cpu the run
fails when the server's device is not a TPU.

Output: <--out>/summary.json plus every child's log; the summary is
printed, and the LAST stdout line is
{"ok": true, "device": {"platform", "kind", "count"}} as JAX reports it.
"""

from __future__ import annotations

import argparse
import codecs
import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent
PKG = "kube_scheduler_simulator_tpu"
# a copying list of pods materialises EVERY pod's annotations (~1.3 MB of
# JSON per pod at 5,000 nodes): the whole-queue API listing is only taken
# below this many pod x node cells
LIST_CELLS_MAX = 2_000_000
# the pods one device call of the scan takes (state/compile.py POD_CHUNK;
# this parent imports nothing of the package): a longer pass is chunked
POD_CHUNK = 512

T0 = time.time()


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(f"[{time.time() - T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------- children

CHILDREN: list[subprocess.Popen] = []


def spawn(cmd: list[str], log_path: Path, env: dict | None = None,
          stdout=None) -> subprocess.Popen:
    """Start a child in its own process group (stop_children kills the
    group, so a child's own children go with it); stderr to log_path."""
    errf = open(log_path, "ab")
    try:
        p = subprocess.Popen(
            cmd, cwd=str(REPO), env=env if env is not None else os.environ,
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


def run_child(role: str, spec: dict, out_dir: Path, cpu: bool,
              deadline: float) -> dict:
    """Run `chip_smoke.py --child <role>` to completion and return the
    JSON object on its last stdout line.  cpu=True pins JAX_PLATFORMS=cpu
    (the child must never claim the chip)."""
    p = start_child(role, spec, out_dir, cpu)
    return finish_child(p, role, deadline)


def start_child(role: str, spec: dict, out_dir: Path,
                cpu: bool) -> subprocess.Popen:
    spec_path = out_dir / f"{role}.spec.json"
    spec_path.write_text(json.dumps(spec))
    env = dict(os.environ)
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    return spawn([sys.executable, str(Path(__file__).resolve()), "--child",
                  role, str(spec_path)], out_dir / f"{role}.log", env=env,
                 stdout=subprocess.PIPE)


def finish_child(p: subprocess.Popen, role: str, deadline: float) -> dict:
    try:
        out, _ = p.communicate(timeout=max(deadline - time.time(), 1))
    except subprocess.TimeoutExpired:
        stop(p)
        raise SmokeFailure(f"{role} child ran past the deadline")
    check(p.returncode == 0, f"{role} child exited {p.returncode} "
                             f"(see {role}.log)")
    lines = out.decode().strip().splitlines()
    check(lines, f"{role} child printed nothing")
    return json.loads(lines[-1])


# -------------------------------------------------------------------- http

def api(port: int, method: str, path: str, body=None, timeout: float = 600):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data, method=method,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            raw = resp.read()
            return resp.status, (json.loads(raw) if raw else None)
    except urllib.error.HTTPError as e:
        raw = e.read()
        return e.code, (json.loads(raw) if raw else None)


def ok_api(port: int, method: str, path: str, body=None, want=(200,),
           timeout: float = 600):
    code, out = api(port, method, path, body, timeout)
    check(code in want, f"{method} {path} -> {code}: {str(out)[:300]}")
    return out


SHED_429S = 0


def submit(port: int, path: str, body, deadline: float):
    """POST workload like a well-behaved client: the autopilot answers
    429 + Retry-After while a session's SLO window is in breach (any wave
    slower than its 2 s p99 target, so every cold compile), and lifts the
    shed once the session has been quiet for a couple of ticks."""
    global SHED_429S
    while True:
        code, out = api(port, "POST", path, body)
        if code != 429:
            check(code == 200, f"POST {path} -> {code}: {str(out)[:300]}")
            return out
        SHED_429S += 1
        check(time.time() < deadline, f"POST {path} still shed at the deadline")
        time.sleep(min(float(out.get("retryAfterSeconds") or 1), 2.0))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def start_server(out_dir: Path, name: str, extra_env: dict,
                 deadline: float) -> tuple[subprocess.Popen, int]:
    """`python -m ...cmd.simulator` on an ephemeral PORT with the
    environment otherwise untouched: the child owns the chip, across its
    own hugepage re-exec too.  cwd is the checkout (no config.yaml
    there: env + defaults, what a user gets)."""
    port = free_port()
    env = {**os.environ, "PORT": str(port), **extra_env}
    p = spawn([sys.executable, "-m", f"{PKG}.cmd.simulator"],
              out_dir / f"{name}.log", env=env)
    while True:
        check(p.poll() is None,
              f"{name} exited {p.returncode} at start-up (see {name}.log)")
        check(time.time() < deadline, f"{name} never answered /healthz")
        try:
            if api(port, "GET", "/healthz", timeout=2)[0] == 200:
                return p, port
        except (urllib.error.URLError, OSError):
            time.sleep(0.2)


class PodOrderWatch:
    """The queue order is PrioritySort's FIFO: creation resourceVersion.
    A snapshot import fans its creates out over a thread pool (the
    reference's errgroup), so that order is only known to a watcher: this
    reads ADDED pod events off /api/v1/listwatchresources while the
    import runs.  Closed as soon as the last pod was seen — an open watch
    is a reader, and would make the server decode every annotation."""

    def __init__(self, port: int):
        self.conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
        self.conn.request("GET", "/api/v1/listwatchresources")
        self.resp = self.conn.getresponse()
        check(self.resp.status == 200, f"watch -> {self.resp.status}")
        self.rv: dict[str, int] = {}
        self.error: BaseException | None = None
        self._closing = False
        self._t = threading.Thread(target=self._read, daemon=True)
        self._t.start()

    def _read(self) -> None:
        dec = json.JSONDecoder()
        utf8 = codecs.getincrementaldecoder("utf-8")()
        buf = ""
        try:
            while True:
                chunk = self.resp.read1(1 << 20)
                if not chunk:
                    return
                buf += utf8.decode(chunk)
                pos = 0
                while pos < len(buf):
                    try:
                        ev, pos = dec.raw_decode(buf, pos)
                    except json.JSONDecodeError:
                        break  # an event split across reads
                    if ev["kind"] == "Pod" and ev["eventType"] == "ADDED":
                        meta = ev["obj"]["metadata"]
                        self.rv.setdefault(meta["name"],
                                           int(meta["resourceVersion"]))
                buf = buf[pos:]
        except (OSError, http.client.HTTPException, ValueError,
                AttributeError) as e:
            # closing the connection under a blocked read surfaces as any
            # of these (http.client drops its file object: AttributeError)
            if not self._closing:
                self.error = e

    def order(self, n_pods: int, deadline: float) -> list[str]:
        while len(self.rv) < n_pods:
            check(self.error is None, f"watch stream failed: {self.error!r}")
            check(time.time() < deadline,
                  f"watch saw {len(self.rv)}/{n_pods} pod creations")
            time.sleep(0.05)
        self._closing = True
        self.conn.close()
        return sorted(self.rv, key=self.rv.get)


# ------------------------------------------------------------------ checks

def counters(port: int) -> dict:
    """Flat view of /api/v1/metrics: plain counters and gauges by name,
    labeled counters as name{k=v,...}."""
    snap = ok_api(port, "GET", "/api/v1/metrics")
    flat = dict(snap.get("counters") or {})
    flat.update({f"gauge:{k}": v for k, v in (snap.get("gauges") or {}).items()})
    for name, series in (snap.get("labeled_counters") or {}).items():
        total = 0
        for s in series:
            labels = ",".join(f"{k}={v}" for k, v in sorted(s["labels"].items())
                              if k != "session")
            flat[f"{name}{{{labels}}}"] = flat.get(f"{name}{{{labels}}}", 0) + s["value"]
            total += s["value"]
        flat[name] = flat.get(name, 0) + total
    return flat


def no_hidden_rung(port: int, platform: str) -> dict:
    """Nothing below the configured path served this run."""
    code, ready = api(port, "GET", "/readyz")
    check(code == 200 and ready.get("status") == "ready", f"/readyz: {ready}")
    for k in ("lastCrash", "crashes", "degradedSessions"):
        check(k not in ready, f"/readyz reports {k}: {ready.get(k)}")
    c = counters(port)
    for name in ("wave_degradations_total", "wave_retries_total",
                 "decode_failures_total", "scheduling_loop_crashes_total",
                 "native_codec_load_failures_total"):
        check(not c.get(name), f"{name} = {c.get(name)}")
    paths = {k: v for k, v in c.items() if k.startswith("decode_path_total{")}
    check(paths and set(paths) == {"decode_path_total{path=native_chunk}"},
          f"decode paths served: {paths}")
    sess = ok_api(port, "GET", "/api/v1/sessions/default")
    check(sess.get("resultMode") == "device_resident" and not sess.get("degraded"),
          f"result mode {sess.get('resultMode')!r}, degraded={sess.get('degraded')}")
    hbm = c.get("gauge:hbm_stats_available")
    # the CPU backend reports no memory stats; a chip must
    check(hbm == (0 if platform == "cpu" else 1), f"hbm_stats_available = {hbm}")
    return {"decode_paths": paths, "result_mode": sess["resultMode"],
            "hbm_stats_available": hbm,
            "hbm_peak_bytes": c.get("gauge:hbm_peak_bytes"),
            "device_chunks_spilled_total": c.get("device_chunks_spilled_total", 0)}


def pod_decision(pod: dict) -> str | None:
    """The node a pod is bound to, "" for an Unschedulable mark, None
    when the scheduler has not decided."""
    node = (pod.get("spec") or {}).get("nodeName")
    if node:
        return node
    for c in (pod.get("status") or {}).get("conditions") or []:
        if c.get("type") == "PodScheduled" and c.get("reason") == "Unschedulable":
            return ""
    return None


def read_pod(port: int, name: str, keys: list[str]) -> tuple[dict, float]:
    """One cold-or-warm GET of a pod: its result annotations must all be
    there and parse."""
    t0 = time.time()
    pod = ok_api(port, "GET", f"/api/v1/pods/default/{name}")
    dt = time.time() - t0
    check(pod_decision(pod) is not None, f"pod {name} carries no decision")
    anns = pod["metadata"].get("annotations") or {}
    for k in keys:
        check(k in anns, f"pod {name} lacks annotation {k}")
        if not k.endswith("/selected-node"):
            json.loads(anns[k])
    return pod, dt


def run_wave(port: int, tag: str, workload: dict, profile: dict | None,
             args, out_dir: Path, deadline: float) -> dict:
    """Import the cluster and the queue, wait for every pod's decision,
    and hold the result to the oracle and to the engine's own counters."""
    nodes = json.loads(Path(workload["nodes"]).read_text())
    pods = json.loads(Path(workload["pods"]).read_text())
    n_pods = len(pods)
    res: dict = {"nodes": len(nodes), "pods": n_pods, "smoke_seconds": {}}
    if profile is not None:
        ok_api(port, "POST", "/api/v1/schedulerconfiguration", profile,
               want=(202,))
    cfg = ok_api(port, "GET", "/api/v1/schedulerconfiguration")
    base = counters(port)

    t0 = time.time()
    submit(port, "/api/v1/import?ignoreSchedulerConfiguration=true",
           {"nodes": nodes}, deadline)
    res["smoke_seconds"]["import_nodes"] = round(time.time() - t0, 2)
    watch = PodOrderWatch(port)
    t1 = time.time()
    # the first pass starts 50 ms after the first pod lands, so most of
    # this import runs beside that pass's compile
    submit(port, "/api/v1/import?ignoreSchedulerConfiguration=true",
           {"pods": pods}, deadline)
    res["smoke_seconds"]["import_pods"] = round(time.time() - t1, 2)
    order = watch.order(n_pods, deadline)
    res["smoke_seconds"]["watch_saw_all_pods"] = round(time.time() - t1, 2)
    del nodes, pods

    # the oracle child (CPU) works on the prefix while the wave runs
    prefix = order[:args.prefix]
    oracle = start_child(f"oracle_{tag}", {
        "nodes": workload["nodes"], "pods": workload["pods"],
        "order": prefix, "scheduler_config": cfg}, out_dir, cpu=True)

    def delta(c, name):
        return c.get(name, 0) - base.get(name, 0)

    # done = the loop went idle with nothing left to wake it.  A pass
    # that starts while the import is still running takes the pods
    # created so far; the rest wake a second pass 50 ms after it ends.
    # The engine counts binds (and what a pass left pending) when a pass
    # ENDS, so "idle" is read off the black box: the last wave.* event is
    # an end, twice in a row, with the counters unchanged in between.
    last = None
    while True:
        c = counters(port)
        waves = [e["kind"] for e in ok_api(
            port, "GET", "/api/v1/debug/dump")["dump"]["events"]
            if e["kind"] in ("wave.start", "wave.end", "wave.abort")]
        now = (delta(c, "pods_scheduled_total"),
               delta(c, "scheduling_waves_total"))
        if (now[1] > 0 and waves and waves[-1] != "wave.start"
                and now == last):
            # a short pass can start and end between the two reads above
            c = counters(port)
            if now == (delta(c, "pods_scheduled_total"),
                       delta(c, "scheduling_waves_total")):
                break
        last = now
        check(not c.get("scheduling_loop_crashes_total"),
              "the scheduling loop crashed (see the server's log)")
        check(time.time() < deadline,
              f"wave {tag}: {now[0]}/{n_pods} pods bound at the deadline")
        time.sleep(1.0)
    res["smoke_seconds"]["import_to_idle"] = round(time.time() - t0, 2)
    bound = delta(c, "pods_scheduled_total")
    # every pod not bound was left pending by the last pass, which counted
    # it; whether each carries its Unschedulable mark is checked per pod
    # where a listing is affordable (below)
    check(delta(c, "pods_unschedulable_total") >= n_pods - bound,
          f"wave {tag}: {n_pods - bound} pods unbound, the engine counted "
          f"{delta(c, 'pods_unschedulable_total')} pending")
    res.update(bound=bound, unschedulable=n_pods - bound,
               scheduling_passes=delta(c, "scheduling_waves_total"),
               commit_stream_waves=delta(c, "commit_stream_waves_total"),
               replay_routes={r: delta(c, f"replay_route_total{{route={r}}}")
                              for r in ("packed", "leaves")},
               wave_d2h_bytes=delta(c, "wave_d2h_bytes_total"),
               scan_compiles=delta(c, "scan_compile_cache_total{result=miss}"))
    log(f"wave {tag}: {bound}/{n_pods} bound in "
        f"{res['smoke_seconds']['import_to_idle']}s, "
        f"{res['scheduling_passes']} pass(es), routes "
        f"{res['replay_routes']}, "
        f"{res['commit_stream_waves']} streamed commit(s)")

    # prefix parity, byte for byte, bindings included
    want = finish_child(oracle, f"oracle_{tag}", deadline)["pods"]
    check([w["name"] for w in want] == prefix, "oracle answered another prefix")
    keys = list(want[0]["annotations"])
    check(len(keys) == 13, f"oracle emitted {len(keys)} result keys")
    # reads: first, middle and last replay chunk (queue order = chunk
    # order), each a cold on-demand D2H + native chunk decode
    reads = {}
    for pos in sorted({0, n_pods // 2, n_pods - 1}):
        _, dt = read_pod(port, order[pos], keys)
        reads[f"queue_pos_{pos}"] = round(dt, 3)
    res["smoke_seconds"]["cold_reads"] = reads
    mismatches = []
    for w in want:
        pod, _ = read_pod(port, w["name"], keys)
        anns = pod["metadata"]["annotations"]
        for k in keys:
            if anns[k] != w["annotations"][k]:
                mismatches.append({"pod": w["name"], "key": k,
                                   "dev": anns[k][:200],
                                   "oracle": w["annotations"][k][:200]})
        if pod_decision(pod) != w["annotations"][keys[-1]]:
            mismatches.append({"pod": w["name"], "key": "spec.nodeName",
                               "dev": pod_decision(pod),
                               "oracle": w["annotations"][keys[-1]]})
    res["prefix_parity"] = {"pods": len(want), "keys": len(keys),
                            "mismatches": len(mismatches),
                            "ok": not mismatches}
    check(not mismatches, f"wave {tag} diverges from the sequential oracle: "
                          f"{json.dumps(mismatches[:3])}")

    # the whole queue: the engine's decision rows account for every pod;
    # at a size where a copying list is affordable the API must agree
    if res["nodes"] * n_pods <= LIST_CELLS_MAX:
        items = ok_api(port, "GET", "/api/v1/pods")["items"]
        decisions = [pod_decision(p) for p in items]
        check(len(items) == n_pods and None not in decisions,
              f"{decisions.count(None)} of {len(items)} listed pods undecided")
        api_bound = sum(1 for d in decisions if d)
        check(api_bound == bound, f"API lists {api_bound} bound pods, the "
                                  f"engine counted {bound}")
        res["api_listing"] = {"bound": api_bound,
                              "unschedulable": n_pods - api_bound}
    else:
        res["api_listing"] = "skipped: a copying list decodes every annotation"
    res["no_hidden_rung"] = no_hidden_rung(port, args.platform)
    # where the wave's time went, for whoever reads this run later: the
    # tracer's spans, counters and histograms as the server reports them,
    # and the black box's timeline (wave and compile events)
    (out_dir / f"wave_{tag}.metrics.json").write_text(
        json.dumps(ok_api(port, "GET", "/api/v1/metrics")))
    events = [e for e in ok_api(port, "GET", "/api/v1/debug/dump")["dump"]
              ["events"] if e["t"] >= t0]
    (out_dir / f"wave_{tag}.events.json").write_text(json.dumps(events))
    starts = [e for e in events if e["kind"] == "wave.start"]
    ends = [e for e in events if e["kind"] == "wave.end"]
    res["passes"] = [{"pods": a["pods"], "bound": b["bound"],
                      "smoke_seconds": round(b["t"] - a["t"], 2)}
                     for a, b in zip(starts, ends)]
    return res


def restricted_profile(plugins: list[str]) -> dict:
    return {"profiles": [{"schedulerName": "default-scheduler", "plugins": {
        "multiPoint": {"enabled": [{"name": n} for n in plugins],
                       "disabled": [{"name": "*"}]}}}]}


def device_of(port: int) -> dict:
    fp = ok_api(port, "GET", "/api/v1/debug/dump")["dump"]["device"]
    check(fp.get("available") and fp.get("devices"),
          f"the server reports no device: {fp}")
    d0 = fp["devices"][0]
    return {"platform": d0["platform"], "kind": d0["kind"],
            "count": len(fp["devices"]),
            "bytes_limit": (d0.get("memory") or {}).get("bytes_limit"),
            "versions": fp.get("versions")}


def cache_dir() -> Path:
    """Where the PROGRAM keeps its compile cache (package __init__); read
    here only to count what the run added."""
    return Path(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                or REPO / ".jax_cache")


def cache_entries() -> int:
    d = cache_dir()
    return sum(1 for _ in d.iterdir()) if d.is_dir() else 0


# ------------------------------------------------------------------ phases

def phase_served(args, out_dir: Path, summary: dict, deadline: float) -> dict:
    srv, port = start_server(out_dir, "server_served", {}, deadline)
    # first of all: which device is this?  No chip, no run.
    device = summary["device"] = device_of(port)
    log(f"server device: {json.dumps(device)}")
    check(device["platform"] == args.platform,
          f"the server runs on {device['platform']!r}, not {args.platform!r}")
    log("generating the cluster and the queue (CPU child)")
    summary["workloads"] = run_child("workload", {
        "seed": args.seed, "out": str(out_dir),
        "shapes": {"main": [args.nodes, args.pods],
                   "ext": [args.ext_nodes, args.ext_pods]}},
        out_dir, cpu=True, deadline=deadline)
    workload = summary["workloads"]["main"]
    out = {}
    out["wave_a_default_profile"] = run_wave(
        port, "a", workload, None, args, out_dir, deadline)
    t0 = time.time()
    ok_api(port, "PUT", "/api/v1/reset", want=(202,))
    log(f"reset in {time.time() - t0:.1f}s")
    out["wave_b_config4_profile"] = run_wave(
        port, "b", workload,
        restricted_profile(summary["workloads"]["config4_plugins"]),
        args, out_dir, deadline)
    b = out["wave_b_config4_profile"]
    check(b["commit_stream_waves"] > 0, "wave B's commit was not streamed")
    # no PostFilter, no retry: a pass is one scan, by its length one route
    long = sum(1 for p in b["passes"] if p["pods"] > POD_CHUNK)
    check(b["replay_routes"] == {"packed": len(b["passes"]) - long,
                                 "leaves": long},
          f"wave B's passes {b['passes']} took routes {b['replay_routes']}")
    out["shed_429s_honoured"] = SHED_429S
    rc = stop(srv)
    log(f"served-path server stopped (rc {rc})")
    return out


def phase_gate(args, out_dir: Path, deadline: float) -> dict:
    """All five BASELINE configs, every annotation of every pod, in one
    process that owns the chip (the server child has exited)."""
    res = run_child("gate", {"scale": args.gate_scale, "seed": args.seed,
                             "configs": [1, 2, 3, 4, 5],
                             "platform": args.platform}, out_dir,
                    cpu=args.platform == "cpu", deadline=deadline)
    for idx, r in res["configs"].items():
        check(r["ok"], f"parity gate config {idx}: {json.dumps(r)[:400]}")
    return res


def phase_external(args, out_dir: Path, workload: dict,
                   deadline: float) -> dict:
    """Process ownership: the server runs no engine and must not touch
    the chip; the standalone scheduler is the one process that does."""
    srv, port = start_server(out_dir, "server_external",
                             {"EXTERNAL_SCHEDULER_ENABLED": "1"}, deadline)
    fp = ok_api(port, "GET", "/api/v1/debug/dump")["dump"]["device"]
    check(not fp.get("available"),
          f"the engine-less server fingerprinted a device: {fp}")
    nodes = json.loads(Path(workload["nodes"]).read_text())
    pods = json.loads(Path(workload["pods"]).read_text())
    submit(port, "/api/v1/import?ignoreSchedulerConfiguration=true",
           {"nodes": nodes, "pods": pods}, deadline)
    t0 = time.time()
    sched = spawn([sys.executable, "-m", f"{PKG}.cmd.scheduler", "--once",
                   "--master", f"http://127.0.0.1:{port}"],
                  out_dir / "scheduler_external.log")
    try:
        rc = sched.wait(timeout=max(deadline - time.time(), 1))
    except subprocess.TimeoutExpired:
        raise SmokeFailure("the standalone scheduler ran past the deadline")
    check(rc == 0, f"cmd.scheduler --once exited {rc} "
                   "(see scheduler_external.log)")
    dt = time.time() - t0
    items = ok_api(port, "GET", "/api/v1/pods")["items"]
    bound = sum(1 for p in items if pod_decision(p))
    check(len(items) == len(pods) and bound == len(pods),
          f"external scheduler bound {bound}/{len(pods)} pods")
    stop(srv)
    return {"nodes": len(nodes), "pods": len(pods), "bound": bound,
            "server_device": fp,
            "smoke_seconds": {"scheduler_once": round(dt, 2)}}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--platform", default="tpu",
                    help="the platform the server must report (cpu: an "
                         "explicit sandbox rehearsal)")
    ap.add_argument("--nodes", type=int, default=5000)
    ap.add_argument("--pods", type=int, default=10000)
    ap.add_argument("--ext-nodes", type=int, default=500)
    ap.add_argument("--ext-pods", type=int, default=1000)
    ap.add_argument("--prefix", type=int, default=64,
                    help="pods, in queue order, compared with the oracle")
    ap.add_argument("--gate-scale", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(REPO / "chiprun_out" / "chip_smoke"))
    ap.add_argument("--deadline", type=float, default=1150.0,
                    help="seconds after which the run fails")
    args = ap.parse_args()
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    for old in out_dir.glob("*.log"):
        old.unlink()
    deadline = T0 + args.deadline
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    summary: dict = {
        "ok": False, "sizes": {"nodes": args.nodes, "pods": args.pods,
                               "prefix": args.prefix,
                               "gate_scale": args.gate_scale,
                               "ext_nodes": args.ext_nodes,
                               "ext_pods": args.ext_pods,
                               "seed": args.seed},
        "note": "smoke_seconds are smoke timings, not benchmark results",
        "host": {"os_cpu_count": os.cpu_count(),
                 "sched_affinity": len(os.sched_getaffinity(0))},
        "compile_cache": {"dir": str(cache_dir()),
                          "entries_before": cache_entries()},
    }
    try:
        for name, phase in (
                ("served", lambda: phase_served(args, out_dir, summary, deadline)),
                ("gate", lambda: phase_gate(args, out_dir, deadline)),
                ("external", lambda: phase_external(
                    args, out_dir, summary["workloads"]["ext"], deadline))):
            t0 = time.time()
            log(f"phase {name}")
            res = phase()
            res["phase_smoke_seconds"] = round(time.time() - t0, 1)
            summary[name] = res
        summary["ok"] = True
    except BaseException as e:
        summary["failure"] = f"{type(e).__name__}: {e}"
        raise
    finally:
        stop_children()
        for f in out_dir.glob("*.manifests.json"):  # MBs; --seed remakes
            f.unlink()
        cc = summary["compile_cache"]
        cc["entries_added"] = cache_entries() - cc["entries_before"]
        summary["total_smoke_seconds"] = round(time.time() - T0, 1)
        summary["claim"] = None
        (out_dir / "summary.json").write_text(json.dumps(summary, indent=1))
        if not summary["ok"]:
            log("FAILED: " + json.dumps(summary)[-3000:])
    print(json.dumps(summary))
    dev = summary["device"]
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}))


# ---------------------------------------------------------------- children
# Everything below runs in a child process: the parent never imports the
# package (it pulls JAX in).

def child_workload(spec: dict) -> dict:
    """BASELINE config 4's generators at the asked shapes -> JSON files."""
    from kube_scheduler_simulator_tpu.models.workloads import (
        BASELINE_CONFIGS, baseline_config)

    full = BASELINE_CONFIGS[4]
    out = {"config4_plugins": full["plugins"]}
    for name, (n_nodes, n_pods) in spec["shapes"].items():
        nodes, pods, _ = baseline_config(
            4, scale=(n_pods + 0.5) / full["pods"], seed=spec["seed"],
            node_scale=(n_nodes + 0.5) / full["nodes"])
        assert (len(nodes), len(pods)) == (n_nodes, n_pods)
        paths = {}
        for kind, objs in (("nodes", nodes), ("pods", pods)):
            paths[kind] = str(Path(spec["out"])
                              / f"{name}.{kind}.manifests.json")
            Path(paths[kind]).write_text(json.dumps(objs))
        out[name] = paths
    return out


def child_oracle(spec: dict) -> dict:
    """reference_impl/sequential.py over the queue prefix, in the order
    the server's queue had, under the server's scheduler configuration."""
    from kube_scheduler_simulator_tpu.reference_impl.sequential import (
        SequentialScheduler)
    from kube_scheduler_simulator_tpu.scheduler.convert import parse_plugin_set

    nodes = json.loads(Path(spec["nodes"]).read_text())
    by_name = {p["metadata"]["name"]: p
               for p in json.loads(Path(spec["pods"]).read_text())}
    pods = [by_name[n] for n in spec["order"]]
    s = SequentialScheduler(nodes, pods, parse_plugin_set(spec["scheduler_config"]))
    assert [p["metadata"]["name"] for p in s.pods] == spec["order"]
    return {"pods": [{"name": p["metadata"]["name"],
                      "annotations": s.schedule_one(p)[0]} for p in s.pods]}


def child_gate(spec: dict) -> dict:
    """This process owns the chip; stream_oracle_parity starts the
    oracle as a CPU child of its own."""
    import jax

    from kube_scheduler_simulator_tpu.reference_impl.parity_gate import (
        stream_oracle_parity)

    d0 = jax.devices()[0]
    assert d0.platform == spec["platform"], (
        f"gate runs on {d0.platform!r}, not {spec['platform']!r}")
    out = {"device": {"platform": d0.platform, "kind": d0.device_kind},
           "configs": {}}
    for idx in spec["configs"]:
        r = stream_oracle_parity(idx, spec["scale"], spec["seed"])
        print(f"gate config {idx}: ok={r['ok']} pods={r['pods']} "
              f"replay={r['replay_seconds']}s", file=sys.stderr, flush=True)
        out["configs"][str(idx)] = {
            k: r[k] for k in ("ok", "pods", "compared", "keys_checked",
                              "mismatches", "first_mismatch", "oracle_rc",
                              "oracle_err", "replay_seconds")}
    return out


if __name__ == "__main__":
    if len(sys.argv) == 4 and sys.argv[1] == "--child":
        sys.path.insert(0, str(REPO))
        fn = {"workload": child_workload, "gate": child_gate,
              "oracle": child_oracle}[sys.argv[2].split("_")[0]]
        print(json.dumps(fn(json.loads(Path(sys.argv[3]).read_text()))))
    else:
        main()
