"""Soak gate for the SLO-driven autopilot: `make bench-soak` /
`python -m tools.soak`.

Sustained multi-session traffic against a LIVE SimulatorServer with the
autopilot on (docs/autopilot.md), asserting the closed loop's three
promises end to end:

  * a well-behaved `standard` tenant under continuous arrival churn
    (models/workloads.py make_churn_workload) keeps its rolling p99
    wave latency inside the configured SLO target for the whole run;
  * an overloaded `best-effort` tenant is load-shed — its HTTP
    submissions get 429 with a Retry-After header AND a
    retryAfterSeconds body field, every single time — and the shed
    LIFTS once the overload stops (hysteresis both ways);
  * a tenant hit by an injected structural device fault walks the
    degradation ladder down and RECOVERS to rung 0 (device_resident)
    by run end — the autopilot never pins a session degraded.

Sessions are also created and deleted mid-run (session churn), so the
controller's per-session memory is pruned while it runs, and the final
black box must validate (`autopilot.decide` events carry the full
{effector, session, from, to, reason} shape).

`make bench-soak` asserts the verdict JSON: ok, soak_p99_wave_seconds
under the target, every shed with Retry-After, soak_recovered_to_rung0.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# Knobs must land before the simulator package is imported: the SLO
# window is read at SLOTracker construction (utils/blackbox.py) and the
# autopilot cadence/target at controller construction.  A tight window
# + fast ticks keep the whole soak under ~a minute on CPU while still
# exercising hysteresis (>= HYSTERESIS_TICKS real controller ticks per
# wave burst).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["KSS_TPU_AUTOPILOT"] = "1"
os.environ["KSS_TPU_AUTOPILOT_INTERVAL_S"] = "0.1"
os.environ["KSS_TPU_AUTOPILOT_SLO_TARGET_P99_S"] = "0.25"
os.environ["KSS_TPU_AUTOPILOT_SHED_QOS"] = "best-effort"
os.environ["KSS_TPU_SLO_WINDOW"] = "16"
os.environ["KSS_TPU_DEGRADE_PROBE_WAVES"] = "3"
# the telemetry-history ring must be ON (an inherited KSS_TPU_HISTORY=0
# would make the causal-reconstruction assertions vacuous) and deep
# enough that a ~0.1s-tick soak never scrolls the breach era away: the
# autopilot tick itself feeds the ring (control/autopilot.py pulls its
# evidence through FEEDER.sample), one row per tick
os.environ["KSS_TPU_HISTORY"] = "1"
os.environ["KSS_TPU_HISTORY_CAPACITY"] = "4096"

SLO_TARGET_S = 0.25
STD, BE, DEG = "soak-std", "soak-be", "soak-deg"

# every distinct pending-pod count is its own compiled scan shape
# (framework/replay.py _workload_scan_key includes the xs shapes), so
# the driver pads each churn wave up to a multiple of this quantum and
# precompiles the padded shapes during warmup — steady-state churn must
# measure scheduling latency, not a compile per novel Poisson draw
WAVE_QUANTUM = 16


def _req(port: int, method: str, path: str, body=None):
    """-> (status, headers dict, parsed body|None) without raising on
    4xx/5xx — the 429 shed contract IS the thing under test."""
    import urllib.error
    import urllib.request

    data = json.dumps(body).encode() if body is not None else None
    r = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data, method=method,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(r, timeout=30) as resp:
            raw = resp.read()
            return resp.status, dict(resp.headers), (
                json.loads(raw) if raw else None)
    except urllib.error.HTTPError as e:
        raw = e.read()
        return e.code, dict(e.headers), (json.loads(raw) if raw else None)


def _fill(store, pods: list[dict]) -> None:
    for p in pods:
        store.create("pods", p)


def _pods(n: int, seed: int, prefix: str, cheap: bool = False) -> list[dict]:
    """make_pods with unique names per burst — the soak submits many
    independent bursts into one store.  `cheap` shrinks requests to
    filler size so padding pods never exhaust capacity (an unbound pod
    would carry into the next wave and change its compiled shape)."""
    from kube_scheduler_simulator_tpu.models.workloads import make_pods

    pods = make_pods(n, seed=seed)
    for i, p in enumerate(pods):
        p["metadata"]["name"] = f"{prefix}-{i:05d}"
        if cheap:
            p["spec"]["containers"][0]["resources"]["requests"] = {
                "cpu": "50m", "memory": str(64 << 20)}
    return pods


def _slot_pods(n: int, seed: int, prefix: str) -> list[dict]:
    """Filler pods in the exact churn-pod shape (app-labeled, tiny
    requests): the compiled scan's schema and statics follow the pod
    features present in the wave, so padding with a DIFFERENT pod shape
    would compile a second executable family per tick."""
    return [{
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": f"{prefix}-{i:05d}", "namespace": "default",
                     "labels": {"app": f"job-{(seed + i) % 4}"}},
        "spec": {
            "containers": [{
                "name": "main",
                "image": "registry.k8s.io/pause:3.9",
                "resources": {"requests": {"cpu": "50m",
                                           "memory": str(64 << 20)}},
            }],
        },
    } for i in range(n)]


def _drop_pods(store, bound: bool, prefix: str = "") -> None:
    """Delete bound pods (completed work leaves) or pending ones (the
    backlog clients gave up on) so wave shapes stay uniform and node
    capacity never saturates across a long soak."""
    pods, _rv = store.list("pods")
    for p in pods:
        meta = p["metadata"]
        if (bool((p.get("spec") or {}).get("nodeName")) == bound
                and meta["name"].startswith(prefix)):
            store.delete("pods", meta["name"],
                         meta.get("namespace") or "default")


def _calibrate_overload(eng, store) -> int:
    """Pods per overload wave sized so ONE wave lasts ~2x the SLO
    target on THIS machine — the breach must come from sustained load,
    not a lucky slow box."""
    probe = 200
    _fill(store, _pods(probe, seed=11, prefix="soak-cal"))
    eng.schedule_pending()          # compile warmup, not timed
    _fill(store, _pods(probe, seed=12, prefix="soak-cal2"))
    t0 = time.perf_counter()
    eng.schedule_pending()
    per_pod = max(time.perf_counter() - t0, 1e-4) / probe
    _drop_pods(store, bound=True)
    _drop_pods(store, bound=False)
    return min(max(int(2 * SLO_TARGET_S / per_pod), 400), 2000)


def run_soak(ticks: int = 18) -> dict:
    from kube_scheduler_simulator_tpu.control import CONTROLS
    from kube_scheduler_simulator_tpu.models.workloads import (
        make_churn_workload, make_nodes)
    from kube_scheduler_simulator_tpu.server.server import SimulatorServer
    from kube_scheduler_simulator_tpu.server.sessions import SessionManager
    from kube_scheduler_simulator_tpu.utils import faults
    from kube_scheduler_simulator_tpu.utils.blackbox import (
        BLACKBOX, validate_dump)

    t_start = time.perf_counter()
    failures: list[str] = []
    mgr = SessionManager(max_sessions=12, idle_ttl=0,
                         start_scheduler=False)
    srv = SimulatorServer(mgr, port=0)
    srv.start(block=False)
    port = srv.port
    shed_responses = 0
    bad_shed = 0            # 429s missing the Retry-After contract
    canaries = 0
    churned = 0
    deg_tripped = False
    try:
        for sid, qos in ((STD, "standard"), (BE, "best-effort"),
                         (DEG, "standard")):
            code, _h, _b = _req(port, "POST", "/api/v1/sessions",
                                {"id": sid, "qos": qos})
            if code != 201:
                failures.append(f"session {sid} create -> {code}")
        engines = {sid: mgr.get(sid).di.engine for sid in (STD, BE, DEG)}
        stores = {sid: mgr.get(sid).di.store for sid in (STD, BE, DEG)}

        # ---- cluster seeding --------------------------------------
        nodes, schedule = make_churn_workload(
            n_nodes=48, ticks=ticks, seed=5, arrival_rate=8.0,
            departure_rate=4.0, name_prefix="soak")
        for n in nodes:
            stores[STD].create("nodes", n)
        for n in make_nodes(96, seed=6):
            stores[BE].create("nodes", n)
        for n in make_nodes(24, seed=7):
            stores[DEG].create("nodes", n)

        batch = _calibrate_overload(engines[BE], stores[BE])

        # warm + flush: pay each session's one-time scan compiles (one
        # per padded wave shape) up front, then roll them out of the
        # SLO window with fast same-shape waves so the measured loop
        # (and the p99 gate) sees steady-state churn, not compiler
        # latency
        window = int(os.environ["KSS_TPU_SLO_WINDOW"])
        for shape in (WAVE_QUANTUM, 2 * WAVE_QUANTUM):
            _fill(stores[STD], _slot_pods(shape, seed=60 + shape,
                                          prefix=f"soak-warm-{shape}"))
            engines[STD].schedule_pending()
        for t in range(window):
            _fill(stores[STD], _slot_pods(WAVE_QUANTUM, seed=800 + t,
                                          prefix=f"soak-stdflush-{t}"))
            engines[STD].schedule_pending()
        _drop_pods(stores[STD], bound=True)   # warmup filler leaves
        for t in range(3 * window):
            _fill(stores[BE], _pods(WAVE_QUANTUM, seed=700 + t,
                                    prefix=f"soak-flush-{t}", cheap=True))
            engines[BE].schedule_pending()
            time.sleep(0.02)
            # the calibration's compile wave may have tripped the shed;
            # flush until the controller reopens the tenant
            if t >= window and not CONTROLS.shed_state(BE)[0]:
                break
        _drop_pods(stores[BE], bound=True)
        if CONTROLS.shed_state(BE)[0]:
            failures.append("best-effort tenant still shed after the "
                            "warmup flush — loop would start vacuous")

        # the degradation-ladder leg: one structural device fault early
        # in the run, scoped to DEG only
        faults.arm(faults.FaultPlan([
            faults.FaultRule("replay.scan_dispatch", nth=2,
                             error="memory", times=1, sessions=[DEG])],
            seed=1))

        # ---- churn + overload main loop ---------------------------
        for t in range(ticks):
            # standard tenant: HTTP create/delete per the churn
            # schedule, padded to the precompiled wave quantum, then
            # one wave
            for pod in schedule[t]["create"]:
                code, _h, _b = _req(
                    port, "POST", f"/api/v1/sessions/{STD}/pods", pod)
                if code != 201:
                    failures.append(f"std pod create -> {code} (tick {t})")
            for name in schedule[t]["delete"]:
                _req(port, "DELETE",
                     f"/api/v1/sessions/{STD}/pods/default/{name}")
            created = len(schedule[t]["create"])
            pad = -created % WAVE_QUANTUM or WAVE_QUANTUM * (not created)
            if pad:
                _fill(stores[STD], _slot_pods(pad, seed=900 + t,
                                              prefix=f"soak-pad-{t}"))
            engines[STD].schedule_pending()
            _drop_pods(stores[STD], bound=True, prefix="soak-pad-")

            # best-effort tenant: one HTTP canary probes the shed
            # state; while open, the bulk overload lands and runs a
            # deliberately over-target wave
            canary = _pods(1, seed=100 + t, prefix=f"soak-canary-{t}")[0]
            code, hdrs, body = _req(
                port, "POST", f"/api/v1/sessions/{BE}/pods", canary)
            canaries += 1
            if code == 429:
                shed_responses += 1
                retry_hdr = hdrs.get("Retry-After")
                retry_body = (body or {}).get("retryAfterSeconds")
                if (retry_hdr is None or not str(retry_hdr).isdigit()
                        or not isinstance(retry_body, int)
                        or retry_body < 1):
                    bad_shed += 1
            elif code == 201:
                _fill(stores[BE], _pods(
                    batch, seed=200 + t, prefix=f"soak-be-{t}"))
                engines[BE].schedule_pending()
                _drop_pods(stores[BE], bound=True)   # completed work
            else:
                failures.append(f"be canary -> {code} (tick {t})")

            # faulted tenant: fresh small waves every tick — the first
            # trips the armed structural fault, the rest are the clean
            # probe waves the ladder needs to climb back
            _fill(stores[DEG], _pods(
                24, seed=300 + t, prefix=f"soak-deg-{t}"))
            engines[DEG].schedule_pending()
            _drop_pods(stores[DEG], bound=True)
            if engines[DEG].result_mode() != "device_resident":
                deg_tripped = True

            # session churn: short-lived best-effort tenants appear
            # and vanish while the controller runs
            if t % 4 == 1:
                code, _h, _b = _req(port, "POST", "/api/v1/sessions",
                                    {"id": f"soak-churn-{t}",
                                     "qos": "best-effort"})
                if code == 201:
                    churned += 1
            elif t % 4 == 3:
                _req(port, "DELETE", f"/api/v1/sessions/soak-churn-{t - 2}")
            time.sleep(0.05)    # let controller ticks interleave

        if not deg_tripped:
            failures.append("structural fault never tripped the ladder "
                            "(vacuous recovery check)")
        if shed_responses == 0:
            failures.append("overloaded best-effort tenant was never shed")
        if bad_shed:
            failures.append(
                f"{bad_shed}/{shed_responses} shed responses missing the "
                "Retry-After header / retryAfterSeconds body contract")

        # ---- cooldown: overload stops, the shed must lift ---------
        # the still-pending bulk backlog is dropped first (clients gave
        # up), then recovery is probed through the REAL client surface:
        # HTTP POSTs that keep 429ing while shed and succeed once the
        # controller reopens the gate.  Nothing feeds the engine
        # directly here — a quiesced shed session must recover on its
        # own (no new waves is no evidence of ongoing breach), which is
        # exactly what real backed-off clients would observe.
        _drop_pods(stores[BE], bound=False)
        shed_lifted = False
        for t in range(6 * window):
            probe = _pods(1, seed=500 + t, prefix=f"soak-cool-{t}",
                          cheap=True)[0]
            code, hdrs, body = _req(
                port, "POST", f"/api/v1/sessions/{BE}/pods", probe)
            if code == 201:
                shed_lifted = True
                engines[BE].schedule_pending()   # bind the probe pod
                _drop_pods(stores[BE], bound=True)
                break
            if code != 429:
                failures.append(f"cooldown probe -> {code} (tick {t})")
                break
            retry_hdr = hdrs.get("Retry-After")
            if retry_hdr is None or not str(retry_hdr).isdigit():
                failures.append(
                    f"cooldown 429 missing Retry-After (tick {t})")
            time.sleep(0.05)
        if not shed_lifted:
            failures.append("shed never lifted after the overload stopped")
        else:
            # the probe wave just recorded into a window still full of
            # breach-era percentiles, so on a slow box the controller
            # may CORRECTLY re-shed for one more quiesce/recover
            # cycle; post-recovery health means submissions are
            # accepted again within a bounded horizon, not that the
            # very next request wins a race against the closing gate
            code = None
            for t in range(6 * window):
                code, _h, _b = _req(
                    port, "POST", f"/api/v1/sessions/{BE}/pods",
                    _pods(1, seed=999 + t,
                          prefix=f"soak-after-{t}")[0])
                if code != 429:
                    break
                time.sleep(0.05)
            if code != 201:
                failures.append(f"post-recovery submit -> {code}")

        recovered = engines[DEG].result_mode() == "device_resident"
        if not recovered:
            failures.append("degradation ladder did not recover to "
                            f"rung 0: {engines[DEG].result_mode()}")

        std_slo = mgr.get(STD, touch=False).info().get("slo") or {}
        std_p99 = std_slo.get("p99WaveSeconds")
        if std_p99 is None or std_p99 > SLO_TARGET_S:
            failures.append(
                f"standard tenant p99 {std_p99} breached the "
                f"{SLO_TARGET_S}s target under churn")

        ap = mgr.stats().get("autopilot") or {}
        if not ap.get("decisions"):
            failures.append("autopilot made zero decisions all soak")
        if ap.get("failsafes"):
            failures.append(f"autopilot tripped its fail-safe "
                            f"{ap['failsafes']}x during a clean soak")

        # ---- causal reconstruction from the history ring ----------
        # the whole breach -> shed -> recovery arc must be readable
        # back out of the columnar ring (docs/metrics.md "History &
        # correlation"), and every shed decision's recorded evidence
        # must match the ring AT ITS INDEX — provenance, not vibes
        from kube_scheduler_simulator_tpu.utils.history import HISTORY
        win = HISTORY.window(series=["slo.p99", "autopilot.shed"],
                             session=BE, since=0)
        p99_col = win["series"].get(f"slo.p99{{session={BE}}}") or []
        shed_col = (win["series"].get(f"autopilot.shed{{session={BE}}}")
                    or [])
        hist_rows = len(win["index"])
        first_shed = next(
            (i for i, v in enumerate(shed_col) if v == 1.0), None)
        breach_before_shed = first_shed is not None and any(
            v is not None and v > SLO_TARGET_S
            for v in p99_col[:first_shed + 1])
        shed_lift_in_ring = first_shed is not None and any(
            v == 0.0 for v in shed_col[first_shed:])
        if first_shed is None:
            failures.append("history ring never recorded the "
                            "best-effort shed (autopilot.shed == 1)")
        else:
            if not breach_before_shed:
                failures.append(
                    "history ring shows no p99 breach at or before "
                    "the first shed sample — the causal order "
                    "breach -> shed is not reconstructible")
            if not shed_lift_in_ring:
                failures.append("history ring never recorded the shed "
                                "lifting (autopilot.shed back to 0)")

        evidence_checked = 0
        for d in (ap.get("lastDecisions") or {}).get(BE) or []:
            if d.get("effector") != "shed":
                continue
            evd = d.get("evidence") or {}
            idx = evd.get("historyIndex")
            if not isinstance(idx, int):
                failures.append("shed decision carries no historyIndex: "
                                f"{d.get('reason')}")
                continue
            ring_p99 = HISTORY.value(f"slo.p99{{session={BE}}}", idx)
            ev_p99 = evd.get("p99WaveSeconds")
            if (ring_p99 is None) != (ev_p99 is None) or (
                    ring_p99 is not None
                    and abs(ring_p99 - ev_p99) > 1e-9):
                failures.append(
                    f"shed evidence p99 {ev_p99} != ring row {idx} "
                    f"value {ring_p99} — provenance broken")
            # the row was sampled BEFORE the decision applied, so it
            # must show the pre-transition shed state
            ring_shed = HISTORY.value(
                f"autopilot.shed{{session={BE}}}", idx)
            want = 0.0 if d.get("to") == "shedding" else 1.0
            if ring_shed != want:
                failures.append(
                    f"ring row {idx} shed flag {ring_shed} != "
                    f"pre-decision state {want} ({d.get('from')} -> "
                    f"{d.get('to')})")
            if d.get("to") == "open":
                # the lift rule: back inside the 0.8x recovery band,
                # or quiesced (no fresh waves — frozen window carries
                # no evidence of ongoing breach)
                if not (ev_p99 is None
                        or ev_p99 <= 0.8 * SLO_TARGET_S
                        or int(evd.get("freshWaves") or 0) <= 0):
                    failures.append(
                        f"shed lifted outside the recovery band: p99 "
                        f"{ev_p99} with {evd.get('freshWaves')} fresh "
                        f"waves")
            evidence_checked += 1
        if evidence_checked == 0:
            failures.append("no shed decision evidence to check "
                            "against the ring (vacuous provenance)")

        doc, _path = BLACKBOX.dump("soak", write=False)
        try:
            validate_dump(doc)
        except Exception as e:  # noqa: BLE001 — verdict reports it
            failures.append(f"black box failed validation: {e}")
    finally:
        faults.disarm()
        srv.shutdown()

    return {
        "ok": not failures,
        "failures": failures,
        "soak_p99_wave_seconds": std_p99,
        "soak_shed_rate": round(shed_responses / max(canaries, 1), 3),
        "soak_recovered_to_rung0": recovered,
        "all_shed_had_retry_after": shed_responses > 0 and bad_shed == 0,
        "shed_responses": shed_responses,
        "shed_lifted": shed_lifted,
        "slo_target_p99_s": SLO_TARGET_S,
        "history_rows": hist_rows,
        "history_breach_before_shed": breach_before_shed,
        "history_shed_lift_recorded": shed_lift_in_ring,
        "shed_evidence_checked": evidence_checked,
        "ticks": ticks,
        "overload_batch": batch,
        "sessions_churned": churned,
        "autopilot": {k: ap.get(k) for k in
                      ("ticks", "decisions", "failsafes",
                       "decisionsByEffector")},
        "seconds": round(time.perf_counter() - t_start, 2),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="kss-soak", description=__doc__)
    ap.add_argument("--ticks", type=int, default=18)
    ap.add_argument("json_out", nargs="?", default=None)
    args = ap.parse_args(argv)
    verdict = run_soak(ticks=args.ticks)
    print(json.dumps(verdict, indent=2))
    if args.json_out:
        with open(args.json_out, "w", encoding="utf-8") as fh:
            json.dump(verdict, fh, indent=2)
    if not verdict["ok"]:
        for f in verdict["failures"]:
            print(f"soak: FAIL — {f}", file=sys.stderr)
        return 1
    print(f"soak: ok — p99 {verdict['soak_p99_wave_seconds']:.3f}s, "
          f"{verdict['shed_responses']} sheds (all Retry-After), "
          f"recovered to rung 0, {verdict['seconds']}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
