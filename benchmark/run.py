#!/usr/bin/env python3
"""benchmark/run.py — one run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Starts the simulator server as a child process with a user's defaults (it
owns the chip; this parent never imports JAX), builds the cell's
deployment from --seed, imports it over HTTP, warms up the cell's own
shape, measures a window of --seconds, checks the window's results against
the plain reference, prints the contract's one JSON line LAST and exits.
A server that is not on a TPU is a failed run (exit 1, no result line).

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name (see benchmark/README.md):
    configs/<config>.json   generators/<name>.py   reference/<name>.py
    traffic/<mix>.json      drivers/<name>.py
    metrics/<metric>.json   readers/<name>.py
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))

from lib import client as cl  # noqa: E402
from lib.client import BenchFailure, check  # noqa: E402
from lib.stats import percentile  # noqa: E402

CYCLE_TIMEOUT_S = 150.0   # a cycle that takes longer left a pod undecided
TRACE_SECONDS = 4.0       # the profiled stretch of a --trace 1 run
RUN_DEADLINE_S = 1150.0   # a first run in a checkout compiles: 1200 s allowed


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def find_cell(bench: dict, name: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    check(name in cells, f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, config


def metrics_of(bench: dict, family: str, cell: str) -> list[dict]:
    """The metrics of `family` this cell reports.  An end-to-end metric
    without a `workloads` key is every cell's; a per-layer metric without
    one belongs to every cell that reports the end-to-end metric it moves."""
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]}
    if family == "end_to_end":
        return [m for m in bench["end_to_end"] if m["name"] in e2e]
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


class Oracle:
    """The reference child (see lib/oracle_child.py)."""

    def __init__(self, config_file: Path, seed: int, override: dict,
                 work: Path, arith: str = "exact"):
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        self.out = work / "oracle.json"
        self.p = cl.spawn([sys.executable, str(BENCH / "lib" / "oracle_child.py")],
                          work / "oracle.log", env=env, cwd=REPO,
                          stdout=subprocess.PIPE, stdin=subprocess.PIPE)
        self._send({"config_file": str(config_file), "seed": seed,
                    "override": override, "arith": arith})
        self.sent = False

    def _send(self, obj: dict) -> None:
        self.p.stdin.write((json.dumps(obj) + "\n").encode())
        self.p.stdin.flush()

    def replay(self, order: list[str], full: list[str]) -> None:
        self._send({"order": order, "check": full, "out": str(self.out)})
        self.sent = True

    def result(self, deadline: float) -> tuple[dict, dict]:
        try:
            out, _ = self.p.communicate(timeout=max(deadline - time.time(), 1))
        except subprocess.TimeoutExpired:
            cl.stop(self.p)
            raise BenchFailure("the oracle child ran past the deadline")
        check(self.p.returncode == 0,
              f"the oracle child exited {self.p.returncode} (see oracle.log)")
        info = json.loads(out.decode().strip().splitlines()[-1])
        return load_json(self.out), info


def compare(want: dict, placements_seen: dict, pods_read: dict,
            keys: list[str]) -> dict:
    """The numbers `correct` is decided by.  `want` is the oracle's
    answer; `placements_seen` what the watch stream said of every replayed
    pod; `pods_read` the checked pods as read back over HTTP in full;
    `keys` the reference's result annotations, the selected node last."""
    placement_mismatches = [
        n for n, node in want["placements"].items()
        if placements_seen.get(n) != node]
    diffs = []
    for name, anns in want["annotations"].items():
        pod = pods_read.get(name)
        got = ((pod or {}).get("metadata") or {}).get("annotations") or {}
        for k in keys:
            if got.get(k) != anns[k]:
                diffs.append((name, k))
        if pod is None or cl.pod_decision(pod) != anns[keys[-1]]:
            diffs.append((name, "spec.nodeName"))
    return {"placement_mismatches": placement_mismatches,
            "annotation_mismatches": diffs,
            "compared_values": len(want["annotations"]) * (len(keys) + 1)}


def verdict(cmp_res: dict, n_checked: int, check_pods: int, undecided: int,
            malformed: int, rung_problems: int) -> tuple[bool, list[str]]:
    """`correct`, and each number compared beside its limit.  Every limit
    is exact: the guarantees are byte equality and "every acknowledged pod
    decided", so the limit on each count of violations is 0."""
    checks = [
        ("checked_pods_compared_in_full", n_checked, ">=", min(check_pods, 2)),
        ("annotation_and_nodeName_values_differing",
         len(cmp_res["annotation_mismatches"]), "<=", 0),
        ("replayed_pods_placed_elsewhere",
         len(cmp_res["placement_mismatches"]), "<=", 0),
        ("acknowledged_pods_undecided", undecided, "<=", 0),
        ("malformed_full_reads", malformed, "<=", 0),
        ("hidden_rung_problems", rung_problems, "<=", 0)]
    correct, lines = True, []
    for name, value, sense, limit in checks:
        ok = value >= limit if sense == ">=" else value <= limit
        correct &= ok
        lines.append(f"check {name}: {value} (limit {sense} {limit}) "
                     f"{'ok' if ok else 'NOT OK'}")
    return correct, lines


def run(args, platform_required: str = "tpu", override: dict | None = None,
        bench_file: Path | None = None, tamper=None,
        warmup_override: dict | None = None) -> dict:
    """One run -> the result object.  `override` replaces generator
    parameters and `warmup_override` warm-up parameters (the CPU
    rehearsal's tiny sizes and short warm-up); `tamper(pod_bytes)` is the
    broken-path test's hook on what the server answered."""
    bench_file = bench_file or REPO / "BENCHMARK.json"
    bench = load_json(bench_file)
    bench_dir = bench_file.parent / bench["paths"][0]
    cell, config = find_cell(bench, args.workload)
    cfg_file = bench_file.parent / config["file"]
    cfg = load_json(cfg_file)
    traffic = load_json(bench_dir / "traffic" / f"{cell['traffic']}.json")
    override = override or {}
    deadline = T_START + RUN_DEADLINE_S
    seconds = float(args.seconds)

    work = REPO / ".bench_work" / args.workload
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    say(f"cell {cell['name']}: config {config['name']} x traffic "
        f"{cell['traffic']}, seed {args.seed}, window {seconds}s, "
        f"trace {args.trace}")
    say(f"host: {len(os.sched_getaffinity(0))} cpus; load average at start "
        f"{os.getloadavg()[0]:.2f}")

    # ---- set-up: server child first, data while it boots
    parts: dict[str, float] = {}
    t = time.time()
    srv, port = cl.spawn_server(REPO, work / "server.log")
    gen = importlib.import_module(f"generators.{cfg['generator']}")
    keys = importlib.import_module(f"reference.{cfg['reference']}").KEYS
    params = dict(cfg["parameters"], **override)
    dep = gen.generate(params, args.seed)
    drv_mod = importlib.import_module(f"drivers.{traffic['driver']}")
    driver = drv_mod.Driver(traffic["parameters"], dep, args.seed)
    wu = {**traffic["warmup"], **(warmup_override or {})}
    # every shape a burst can fall apart into, smallest first: a burst that
    # the 50 ms debounce splits runs as two passes of smaller counts, and a
    # count the program has not seen is an XLA compile of its own
    shapes = list(range(1, driver.burst)) if wu.get("ascending_shapes") else []
    for b in shapes:
        driver.provision(1, burst=b)
    driver.provision(int(wu["max_cycles"])
                     + int(seconds * traffic["provision_cycles_per_s"]) + 2)
    body_nodes = json.dumps({"namespaces": dep.namespaces,
                             "nodes": dep.nodes}).encode()
    body_pods = json.dumps({"pods": dep.initial_pods}).encode()
    parts["generate_and_encode"] = time.time() - t

    t = time.time()
    cl.wait_healthy(srv, port, work / "server.log", deadline)
    # the warm-up's 429s are retried after at most warmup.retry_cap_s (the
    # traffic file says why); the window's after the server's Retry-After
    c = cl.Client(port, retry_cap_s=float(wu["retry_cap_s"]))
    device = cl.device_of(c)
    parts["server_start_and_jax_init"] = time.time() - t
    say(f"device: platform {device['platform']}, kind {device['kind']!r}, "
        f"count {device['count']}")
    check(device["platform"] == platform_required,
          f"the server runs on {device['platform']!r}, not "
          f"{platform_required!r}: no result")
    check(device["count"] >= cell["chips"],
          f"the cell asks for {cell['chips']} chip(s), the server has "
          f"{device['count']}")

    oracle = Oracle(cfg_file, args.seed, override, work,
                    arith=args.oracle_arith)
    t = time.time()
    c.submit(drv_mod.IMPORT_PATH, body_nodes, deadline)
    parts["import_nodes"] = time.time() - t
    t = time.time()
    c.submit(drv_mod.IMPORT_PATH, body_pods, deadline)
    parts["import_initial_pods"] = time.time() - t
    del body_nodes, body_pods
    t = time.time()
    watch = cl.WatchStream(port)
    parts["open_watch"] = time.time() - t

    cycles: list[dict] = []
    undecided = 0

    def one_cycle(k: int) -> dict | None:
        nonlocal undecided
        try:
            r = driver.cycle(k, c, watch, keys, cl.read_pod,
                             min(deadline, time.time() + CYCLE_TIMEOUT_S))
        except BenchFailure as e:
            left = [n for n in driver.names[k] if n not in watch.decided]
            if not left:
                raise
            undecided += len(left)
            say(f"cycle {k}: {len(left)} acknowledged pods left undecided ({e})")
            return None
        cycles.append(r)
        return r

    def send_to_oracle(k_done: int) -> None:
        """The reference replays every measured pod so far, in the queue's
        order, and renders in full a sample drawn from the seed out of the
        window's first cycles, with their first and last pod in it."""
        replayed = [n for names in driver.names[:k_done] for n in names]
        first = {n for names in driver.names[warm:k_done] for n in names}
        order = watch.queue_order(replayed)
        in_window = [n for n in order if n in first]
        pick = random.Random(f"{args.seed}:check")
        full = set(pick.sample(in_window, min(int(traffic["check_pods"]),
                                              len(in_window))))
        full |= {in_window[0], in_window[-1]}
        oracle.replay(order, sorted(full))

    t = time.time()
    base_warm = cl.counters(c)
    k = clean = 0
    while True:
        r = one_cycle(k)
        check(r is not None, "a warm-up cycle left pods undecided")
        k += 1
        clean = 0 if r["shed"] else clean + 1
        if k <= len(shapes) + 3 or r["shed"] or r["t1"] - r["t0"] > 2.0:
            say(f"warm-up cycle {k - 1}: {r['pods']} pods, {r['t1'] - r['t0']:.3f}s "
                f"(decided after {r['t_decided'] - r['t0']:.3f}s, "
                f"{r['shed']} x 429)")
        # a fixed number of cycles (the same work in every run), and the
        # session has to have stopped shedding: see README, "warm-up"
        if (k >= len(shapes) + int(wu["cycles"])
                and clean >= int(wu["clean_cycles"])):
            break
        if k >= len(shapes) + int(wu["max_cycles"]):
            say(f"warm-up: the session never went {wu['clean_cycles']} cycles "
                f"without a 429 in {k} cycles; the window holds the shedding")
            break
    warm = k
    parts["warmup_cycles"] = time.time() - t
    base = cl.counters(c)
    shed_at = [r["k"] for r in cycles if r["shed"]]
    say(f"warm-up: {warm} cycles discarded ({len(shapes)} smaller shapes, then "
        f"{warm - len(shapes)} of the cell's own; {wu['cycles']} asked, "
        f"the last 429 met in cycle {shed_at[-1] if shed_at else None}); "
        f"{sum(r['shed'] for r in cycles)} x 429 in warm-up; scan compiles "
        f"{base.get('scan_compile_cache_total{result=miss}', 0) - base_warm.get('scan_compile_cache_total{result=miss}', 0)}; "
        f"scheduling passes {base.get('scheduling_waves_total', 0) - base_warm.get('scheduling_waves_total', 0)}")

    # ---- the window
    check_cycles = -(-int(traffic["check_pods"]) // driver.burst)
    c.retry_cap_s = None
    cpu0 = resource.getrusage(resource.RUSAGE_SELF)
    t_open = time.time()
    setup_s = t_open - T_START
    while time.time() - t_open < seconds and not undecided:
        if one_cycle(k) is None:
            break
        k += 1
        if not oracle.sent and k >= warm + check_cycles:
            send_to_oracle(k)
    if not oracle.sent and k > warm:  # a window shorter than the sample
        send_to_oracle(k)
    t_close = t_open + seconds
    end = cl.counters(c)
    t_end = time.time()
    cpu1 = resource.getrusage(resource.RUSAGE_SELF)
    window = cycles[warm:]
    say(f"window: {seconds}s open at set-up {setup_s:.3f}s; "
        f"{len(window)} cycles started in it, the last ended "
        f"{t_end - t_close:+.3f}s after {seconds}s")

    # ---- --trace 1: a profiled stretch of the same traffic, after the
    # window, so that the window's spans and counters stay the profiler's
    # cost free
    trace_info = None
    if args.trace and not undecided:
        prof_dir = work / "profile"
        c.ok("POST", "/api/v1/profile",
             {"action": "start", "logDir": str(prof_dir)})
        tp0 = time.time()
        traced: list[dict] = []
        while time.time() - tp0 < TRACE_SECONDS:
            r = one_cycle(k)
            if r is None:
                break
            traced.append(r)
            k += 1
        tp1 = time.time()
        c.ok("POST", "/api/v1/profile", {"action": "stop"})
        trace_info = {"dir": str(prof_dir), "cycles": traced,
                      "pods": sum(r["pods"] for r in traced)}
        say(f"trace: {tp1 - tp0:.3f}s profiled, {len(traced)} cycles, "
            f"{trace_info['pods']} pods, stop took {time.time() - tp1:.2f}s")

    # ---- correctness, after the window has closed
    pods_read: dict[str, dict] = {}
    cmp_res = {"placement_mismatches": [], "annotation_mismatches": [],
               "compared_values": 0}
    oracle_info = {}
    if oracle.sent:
        want, oracle_info = oracle.result(deadline)
        for name in want["annotations"]:
            code, raw = c.raw("GET", f"/api/v1/pods/{driver.namespace}/{name}")
            if code == 200:
                if tamper is not None:
                    raw = tamper(raw)
                pods_read[name] = json.loads(raw)
        cmp_res = compare(want, watch.decided, pods_read, keys)
        for n, key in cmp_res["annotation_mismatches"][:3]:
            say(f"  differs from the reference: pod {n} {key}")
        for n in cmp_res["placement_mismatches"][:3]:
            say(f"  placed elsewhere than the reference: pod {n} on "
                f"{watch.decided.get(n)!r}, reference {want['placements'][n]!r}")
    else:
        cl.stop(oracle.p)
    rung = cl.no_hidden_rung(c, device["platform"])
    black_box = c.ok("GET", "/api/v1/debug/dump")["dump"]["events"]
    malformed = [r for r in cycles if r["problem"]]
    for r in malformed[:3]:
        say(f"  malformed read: {r['problem']}")
    for p in rung["problems"][:5]:
        say(f"  hidden rung: {p}")
    correct, lines = verdict(cmp_res, len(pods_read), int(traffic["check_pods"]),
                             undecided, len(malformed), len(rung["problems"]))
    for ln in lines:
        say(ln)
    say(f"check: {cmp_res['compared_values']} values of {len(pods_read)} pods compared "
        f"byte for byte; reference replayed {oracle_info.get('pods', 0)} pods in "
        f"{oracle_info.get('seconds')}s (reference {oracle_info.get('reference')}, "
        f"{oracle_info.get('arith')})")

    memory_peak = rung.get("hbm_peak_bytes") or 0
    watch.close()
    c.close()
    cl.stop(srv)

    # ---- reduce.  The window is every cycle STARTED within --seconds of
    # its opening; it closes when the last of them ends (t_end, under one
    # cycle later), so a rate is all the window's work over all its time
    # and not a whole number of bursts over a fixed span
    window_s = (window[-1]["t1"] - t_open) if window else 0.0
    pods_decided = sum(r["pods"] for r in window)
    lat = [r["t1"] - r["t0"] for r in window]
    passes = end.get("scheduling_waves_total", 0) - base.get("scheduling_waves_total", 0)
    say(f"samples: {len(window)} cycles started in the window of "
        f"{seconds}s, closed after {window_s:.3f}s; {pods_decided} pods decided "
        f"in it; "
        f"loop wake-ups that ran a pass (scheduling_waves_total) {passes}")
    sub = sorted(r["submit_s"] for r in window) or [0.0]
    sizes = [e.get("pods") for e in black_box if e["kind"] == "wave.start"]
    real = [n for n in sizes if n]
    say(f"submit: POST answered after median {sub[len(sub) // 2]:.4f}s, max "
        f"{sub[-1]:.4f}s; bursts that split: of the last {len(real)} passes the "
        f"black box holds, {sum(1 for n in real if n < driver.burst)} were "
        f"smaller than the burst of {driver.burst}: {real[-24:]}")
    say("set-up parts (s): " + ", ".join(f"{k_} {v:.2f}" for k_, v in parts.items())
        + f"; total {setup_s:.2f}")
    say(f"client: cpu {cpu1.ru_utime - cpu0.ru_utime + cpu1.ru_stime - cpu0.ru_stime:.2f}s "
        f"in a {t_end - t_open:.2f}s window; load average now "
        f"{os.getloadavg()[0]:.2f}; 429s {c.shed_429s} of {c.requests_sent} "
        f"requests; watch {watch.events} events, {watch.bytes / 1e6:.1f} MB")
    say(f"reads: {sum(r['read_retries'] for r in window)} of {len(window)} full "
        f"reads had to be asked again before the annotations were there")
    if lat:
        say("latency percentiles (s): " + ", ".join(
            f"p{q} {percentile(lat, q):.5f}" for q in (25, 50, 75, 80, 90, 95))
            + f", mean {sum(lat) / len(lat):.5f}")
    say("cycle ms: " + " ".join(f"{1000 * (r['t1'] - r['t0']):.0f}" for r in window))
    if lat:
        # drift inside the run: first against second half of the window
        half = len(lat) // 2 or 1
        say(f"drift: median cycle {percentile(lat[:half], 50):.4f}s in the "
            f"first half, {percentile(lat[half:] or lat, 50):.4f}s in the second; "
            f"min {min(lat):.4f} max {max(lat):.4f}")

    ctx = {  # what the readers in readers/ may read
        "cycles": window,
        "counters": {key: end.get(key, 0) - base.get(key, 0)
                     for key in set(end) | set(base)
                     if isinstance(end.get(key, 0), (int, float))},
        "counter_window_s": t_end - t_open,
        "client": {"shed_429s": sum(r["shed"] for r in window),
                   "requests_sent": sum(r["shed"] + 1 for r in window)},
        "trace": None,
    }
    device_out = {"platform": device["platform"], "kind": device["kind"],
                  "count": device["count"], "memory_peak_bytes": memory_peak}
    breakdown = None
    if trace_info is not None:
        from lib import xplane
        red = xplane.reduce_in_child(trace_info["dir"], work, REPO)
        red["pods"] = trace_info["pods"]
        red["cycles"] = len(trace_info["cycles"])
        ctx["trace"] = red
        check(red["busy_s"] > 0 or platform_required == "cpu",
              "no operation ran on the device in the traced stretch")
        device_out["busy_s"] = red["busy_s"]
        device_out["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"][:10],
                     "idle_gaps": red["idle_gaps"][:10]}
        say(f"trace: device busy {red['busy_s']:.6f}s of {red['window_s']:.3f}s "
            f"on {red['device_planes']} device plane(s)")

    # every metric but setup_s is a file in metrics/ over a reader; a
    # per-layer reader that finds nothing to read leaves its metric out
    metrics: dict[str, dict] = {}
    family = "per_layer" if args.trace else "end_to_end"
    for m in metrics_of(bench, family, cell["name"]):
        if m["name"] == "setup_s":
            value = setup_s
        else:
            spec = load_json(bench_dir / "metrics" / f"{m['name']}.json")
            reader = importlib.import_module(f"readers.{spec['reader']}")
            value = reader.read(ctx, spec.get("parameters") or {})
        check(value is not None or args.trace,
              f"nothing to report for {m['name']}")
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(correct),
              "attempted": pods_decided + undecided,
              "failed": undecided + len([r for r in window if r["problem"]]),
              "metrics": metrics, "device": device_out}
    if breakdown is not None:
        result["breakdown"] = breakdown
    return result


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # for the control's runs only; the driver never passes it
    ap.add_argument("--oracle-arith", default="exact",
                    help="an arithmetic of the configuration's reference; "
                         "narrow32: the control (must come out not correct)")
    return ap.parse_args(argv)


def main(argv=None, **kw) -> int:
    args = parse(argv)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        result = run(args, **kw)
    except BenchFailure as e:
        print(f"benchmark failed: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        cl.stop_children()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
