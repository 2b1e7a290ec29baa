"""Chaos verification harness: `make chaos` / `python -m tools.chaos`.

Runs concurrent multi-session scheduling waves under randomized, SEEDED
fault plans (kube_scheduler_simulator_tpu/utils/faults.py) and asserts
the wave failure protocol's invariants (docs/fault-injection.md):

  * waves COMPLETE under injected faults — via uncommitted-suffix retry
    or the degradation ladder — instead of aborting the backlog;
  * annotations and binds are BIT-IDENTICAL to the fault-free run of
    the same workload for every session;
  * gang atomicity holds: every PodGroup is all-bound or all-unbound;
  * per-session isolation: every fault targets one session (the plan's
    session filter) and the neighbor's results are still byte-identical
    to ITS fault-free run;
  * session admission survives create/evict faults with a consistent
    registry;
  * no lock-order cycles under the runtime lock witness
    (KSS_TPU_LOCK_WITNESS=1 — `make chaos` sets it).

Each seed derives one deterministic plan, so a failure prints the exact
reproducing command.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import threading
import time

DEFAULT_SEEDS = 3
FAULTED, NEIGHBOR = "chaos-a", "chaos-b"


def _build_cluster(store, seed: int, n_nodes: int, n_pods: int,
                   gangs: int, gang_members: int):
    from kube_scheduler_simulator_tpu.models.workloads import (
        make_gang_workload, make_nodes, make_pods)
    from kube_scheduler_simulator_tpu.plugins.coscheduling import (
        ensure_podgroup_resource)

    ensure_podgroup_resource(store)
    for n in make_nodes(n_nodes, seed=seed):
        store.create("nodes", n)
    for p in make_pods(n_pods, seed=seed):
        store.create("pods", p)
    pgs, pods = make_gang_workload(gangs, gang_members, seed=seed + 1,
                                   name_prefix=f"cg{seed}")
    for pg in pgs:
        store.create("podgroups", pg)
    for p in pods:
        store.create("pods", p)
    return [pg["metadata"]["name"] for pg in pgs]


def _engine(store, session: str, chunk: int):
    from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
    from kube_scheduler_simulator_tpu.plugins.coscheduling import Coscheduling
    from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig

    cfg = PluginSetConfig(enabled=["NodeResourcesFit", "Coscheduling"],
                          custom={"Coscheduling": Coscheduling()})
    eng = SchedulerEngine(store, plugin_config=cfg, chunk=chunk)
    eng.session = session
    return eng


def _plan_for(seed: int, target: str):
    """The seed's randomized plan, every rule scoped to `target` — the
    isolation invariant needs a provably unfaulted neighbor."""
    from kube_scheduler_simulator_tpu.utils.faults import FaultPlan, FaultRule

    rng = random.Random(seed * 7919)
    rules = [
        # transient scan/fetch faults: heal via uncommitted-suffix retry
        FaultRule("replay.scan_dispatch", nth=rng.randint(1, 3),
                  error="runtime", times=1, sessions=[target]),
        FaultRule("replay.decision_fetch", p=0.15, error="io", times=2,
                  sessions=[target]),
        # structural fault: steps the degradation ladder down a rung
        FaultRule("replay.scan_dispatch", nth=rng.randint(5, 8),
                  error="memory", times=1, sessions=[target]),
        # decode fault: heals on re-read (or via wave retry when it
        # surfaces through an in-wave reflect materialization)
        FaultRule("decode.chunk", nth=rng.randint(1, 2), error="runtime",
                  times=1, sessions=[target]),
        # write-back conflicts: heal under the reflector's own backoff
        FaultRule("reflector.write_back", p=0.2, error="conflict", times=2,
                  sessions=[target]),
        # compile fault: first failure is transient, wave retry rebuilds
        FaultRule("compile.build", nth=1, error="runtime", times=1,
                  sessions=[target]),
    ]
    return FaultPlan(rules, seed=seed)


def _collect_state(store, session: str) -> dict:
    """{pod name: (nodeName, annotations)} — reads run under the
    session's tracer scope so read-path fault rules can target them;
    the one-retry wrapper IS the heals-on-re-read invariant."""
    from kube_scheduler_simulator_tpu.utils.tracing import TRACER

    def read():
        out = {}
        with TRACER.session_scope(session):
            pods, _ = store.list("pods")
        for p in pods:
            meta = p.get("metadata") or {}
            out[meta.get("name", "")] = (
                (p.get("spec") or {}).get("nodeName"),
                dict(meta.get("annotations") or {}))
        return out

    try:
        return read()
    except Exception:
        # a transient injected decode fault surfaces to its first
        # reader and MUST heal on the next read without poisoning the
        # chunk (store/lazy.py) — a second failure is a real bug
        return read()


def _run_once(seed: int, plan, shape: dict) -> dict:
    """One concurrent two-session run; returns per-session state, gang
    names, per-session result modes and any drive errors."""
    from kube_scheduler_simulator_tpu.cluster.store import ObjectStore
    from kube_scheduler_simulator_tpu.utils import faults

    sessions = {}
    gang_names = {}
    for i, sid in enumerate((FAULTED, NEIGHBOR)):
        store = ObjectStore()
        gang_names[sid] = _build_cluster(
            store, seed=seed + 50 * i, n_nodes=shape["nodes"],
            n_pods=shape["pods"], gangs=shape["gangs"],
            gang_members=shape["gang_members"])
        sessions[sid] = (store, _engine(store, sid, chunk=shape["chunk"]))

    barrier = threading.Barrier(len(sessions))
    errors: dict[str, BaseException] = {}

    def drive(sid: str):
        _store, eng = sessions[sid]
        barrier.wait()
        try:
            eng.schedule_pending()
        except BaseException as e:  # noqa: BLE001 — the verdict reports it
            errors[sid] = e

    # set the global to exactly `plan` (None = fault-free reference) and
    # RESTORE the previous plan after: an operator's env-armed
    # KSS_TPU_FAULT_PLAN must survive an in-process chaos verdict
    prev = faults.current_plan()
    prev_retries = os.environ.get("KSS_TPU_WAVE_MAX_RETRIES")
    if plan is not None:
        faults.arm(plan)
        # the protocol completes a wave iff its retry budget covers the
        # transient faults landing in it; size the budget to this
        # plan's worst case (every bounded transient rule trips in ONE
        # wave) so the gate asserts protocol CORRECTNESS, not a lucky
        # fault spread.  An unbounded budget would hide retry storms —
        # the exact worst case keeps the bound meaningful.
        budget = sum(
            (r.times or 0) for r in plan.rules
            if r.error in ("runtime", "io", "timeout", "conflict"))
        os.environ["KSS_TPU_WAVE_MAX_RETRIES"] = str(max(budget, 3))
    else:
        faults.disarm()
    try:
        threads = [threading.Thread(target=drive, args=(sid,), daemon=True,
                                    name=f"chaos-{sid}")
                   for sid in sessions]
        for t in threads:
            t.start()
        for t, sid in zip(threads, sessions):
            t.join(timeout=120)
            if t.is_alive():
                # a wedged wave is its own failure class: report it
                # instead of reading a store the wave still mutates
                errors.setdefault(sid, TimeoutError(
                    "wave wedged: thread still alive after 120s"))
        state = {sid: (_collect_state(store, sid)
                       if sid not in errors else {})
                 for sid, (store, _e) in sessions.items()}
    finally:
        if prev is not None:
            faults.arm(prev)
        else:
            faults.disarm()
        if plan is not None:
            if prev_retries is None:
                os.environ.pop("KSS_TPU_WAVE_MAX_RETRIES", None)
            else:
                os.environ["KSS_TPU_WAVE_MAX_RETRIES"] = prev_retries
    modes = {sid: eng.result_mode() for sid, (_s, eng) in sessions.items()}
    for sid, (_store, eng) in sessions.items():
        if sid not in errors:  # never block closing a wedged engine
            eng.close()
    return {"state": state, "gangs": gang_names, "errors": errors,
            "modes": modes}


def _gang_atomicity_failures(state: dict, gang_names: list[str]) -> list[str]:
    bad = []
    for g in gang_names:
        members = {n: s for n, (s, _a) in state.items()
                   if n.startswith(g + "-")}
        bound = [n for n, s in members.items() if s]
        if bound and len(bound) != len(members):
            bad.append(f"gang {g}: {len(bound)}/{len(members)} bound")
    return bad


def _session_lifecycle_check(seed: int) -> list[str]:
    """Session create/evict seams: an injected construction failure
    must release the reservation (the id is re-creatable), an injected
    teardown failure must not wedge admission, and the registry stays
    consistent throughout."""
    from kube_scheduler_simulator_tpu.server.sessions import SessionManager
    from kube_scheduler_simulator_tpu.utils import faults

    failures: list[str] = []
    mgr = SessionManager(max_sessions=3, idle_ttl=0, start_scheduler=False)
    plan = faults.FaultPlan([
        faults.FaultRule("session.create", nth=1, error="runtime"),
        faults.FaultRule("session.evict", nth=1, error="runtime"),
    ], seed=seed)
    try:
        with faults.armed(plan):
            try:
                mgr.create("c1")
                failures.append("session.create fault did not surface")
            except faults.InjectedFault:
                pass
            try:
                mgr.create("c1")   # reservation released: same id admits
                mgr.create("c2")   # at capacity now (default + c1 + c2)
                mgr.create("c3")   # evicts LRU c1 through the faulted path
            except Exception as e:  # noqa: BLE001 — verdict reports
                failures.append(f"admission after faults failed: {e!r}")
        ids = {s["id"] for s in mgr.list_sessions()}
        if ids != {"default", "c2", "c3"}:
            failures.append(f"registry inconsistent after faults: {ids}")
    finally:
        mgr.shutdown()
    return failures


def _autopilot_failsafe_check(seed: int) -> list[str]:
    """The autopilot.decide seam (control/autopilot.py): a fault while
    a tick applies its decisions must revert EVERY effector to the
    static-knob defaults (CONTROLS.reset()), count the failsafe, and
    leave the controller able to keep ticking — fail-safe, never
    fail-wedged.  The rule is UNSCOPED because the controller thread
    runs outside any session tracer scope."""
    from kube_scheduler_simulator_tpu.control import CONTROLS
    from kube_scheduler_simulator_tpu.control.autopilot import Autopilot
    from kube_scheduler_simulator_tpu.server.sessions import SessionManager
    from kube_scheduler_simulator_tpu.utils import faults
    from kube_scheduler_simulator_tpu.utils.blackbox import SLO

    failures: list[str] = []
    mgr = SessionManager(max_sessions=4, idle_ttl=0,
                         start_scheduler=False)
    try:
        mgr.create("ap-a", qos="best-effort")
        ap = Autopilot(mgr, interval=3600, slo_target=0.05)

        def waves(seconds, n=70):   # fill the whole SLO window
            for _ in range(n):
                SLO.observe_wave("ap-a", seconds, pods=10)

        waves(1.0)
        ap.tick()
        ap.tick()                   # breach x2 ticks -> shed applied
        if not CONTROLS.shed_state("ap-a")[0]:
            failures.append("autopilot never shed under synthetic "
                            "breach")
        # a second effector's state must ALSO revert on the trip
        CONTROLS.set_budget_weight("ap-a", 2.0)
        waves(0.001)                # recovered: the next ticks plan unshed
        plan = faults.FaultPlan([
            faults.FaultRule("autopilot.decide", nth=1, error="runtime")],
            seed=seed)
        with faults.armed(plan):
            ap.tick()
            ap.tick()               # ok x2 ticks -> decision -> trip
        if plan.stats()["rules"][0]["trips"] != 1:
            failures.append("autopilot.decide fault never tripped "
                            "(vacuous)")
        if ap.stats()["failsafes"] != 1:
            failures.append("failsafe counter not bumped after the trip")
        if CONTROLS.stats() != {}:
            failures.append("controls not reverted to static defaults "
                            f"after the trip: {CONTROLS.stats()}")
        # the controller survives: clean ticks run, and a renewed
        # breach sheds again from the reset state
        ap.tick()
        waves(1.0)
        ap.tick()
        ap.tick()
        if not CONTROLS.shed_state("ap-a")[0]:
            failures.append("controller wedged after the failsafe: "
                            "renewed breach no longer sheds")
    finally:
        CONTROLS.reset()
        mgr.shutdown()
    return failures


def run_seed(seed: int, shape: dict, witness=None) -> dict:
    """Run one seed: fault-free reference, chaos run, invariants.
    Returns {ok, seed, failures, injected, modes}."""
    failures: list[str] = []
    plan = _plan_for(seed, FAULTED)
    # chaos FIRST: the scan-compile seam only fires on cache misses, and
    # the reference run would otherwise warm every shape
    chaos = _run_once(seed, plan, shape)
    ref = _run_once(seed, None, shape)
    for sid, err in chaos["errors"].items():
        failures.append(f"{sid}: wave did not complete: {err!r}")
    for sid, err in ref["errors"].items():
        failures.append(f"{sid}: fault-free reference failed: {err!r}")
    injected = sum(r["trips"] for r in plan.stats()["rules"])
    if injected == 0:
        failures.append("plan injected nothing — the seed is vacuous")
    for sid in (FAULTED, NEIGHBOR):
        got, want = chaos["state"].get(sid), ref["state"].get(sid)
        if got is None or want is None:
            continue
        if got != want:
            diff = sorted(
                set(k for k in want if want[k] != got.get(k))
                | (set(got) - set(want)))[:4]
            role = "faulted" if sid == FAULTED else "NEIGHBOR (isolation)"
            failures.append(
                f"{sid} ({role}): state diverged from fault-free run at "
                f"{diff}")
        failures.extend(
            f"{sid}: {m}" for m in _gang_atomicity_failures(
                got, chaos["gangs"][sid]))
    failures.extend(_session_lifecycle_check(seed))
    failures.extend(_autopilot_failsafe_check(seed))
    if witness is not None:
        try:
            witness.assert_no_cycles()
        except AssertionError as e:
            failures.append(f"lock witness: {e}")
    dump_path = None
    if failures:
        # a red chaos run ships its own evidence: snapshot the wave
        # black box (event ring, counter deltas, armed plan, device
        # fingerprint) next to the reproducing seed so debugging starts
        # from the dump, not from a re-run (docs/fault-injection.md)
        import tempfile

        from kube_scheduler_simulator_tpu.utils.blackbox import BLACKBOX

        _doc, dump_path = BLACKBOX.dump(
            "chaos_failure", write=True,
            directory=(os.environ.get("KSS_TPU_BLACKBOX_DIR")
                       or tempfile.gettempdir()))
    return {"ok": not failures, "seed": seed, "failures": failures,
            "injected": injected, "modes": chaos["modes"],
            "dump": dump_path}


FULL_SHAPE = {"nodes": 8, "pods": 26, "gangs": 2, "gang_members": 3,
              "chunk": 8}


def chaos_verdict(seeds: int = DEFAULT_SEEDS, seed_base: int = 1,
                  witness=None) -> dict:
    """The machine-readable verdict `make chaos` gates on."""
    t0 = time.perf_counter()
    results = [run_seed(seed_base + i, FULL_SHAPE, witness=witness)
               for i in range(seeds)]
    return {
        "ok": all(r["ok"] for r in results),
        "seeds": [r["seed"] for r in results],
        "injected_total": sum(r["injected"] for r in results),
        "failures": [f for r in results for f in
                     (f"seed {r['seed']}: {m}" for m in r["failures"])],
        # black-box dumps written for failing seeds (None entries for
        # green seeds are dropped): the first thing to open on a red run
        "dumps": [r["dump"] for r in results if r.get("dump")],
        "seconds": round(time.perf_counter() - t0, 2),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="kss-chaos", description=__doc__)
    ap.add_argument("--seeds", type=int, default=DEFAULT_SEEDS)
    ap.add_argument("--seed-base", type=int, default=1)
    ap.add_argument("--json", dest="json_out", default=None)
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    witness = None
    if os.environ.get("KSS_TPU_LOCK_WITNESS") == "1":
        # install BEFORE the simulator package creates its locks
        from tools.analysis import lockwitness

        witness = lockwitness.install()
    verdict = chaos_verdict(seeds=args.seeds, seed_base=args.seed_base,
                            witness=witness)
    print(json.dumps(verdict, indent=2))
    if args.json_out:
        with open(args.json_out, "w", encoding="utf-8") as fh:
            json.dump(verdict, fh, indent=2)
    if not verdict["ok"]:
        bad = verdict["failures"][0].split(":")[0] if verdict["failures"] \
            else f"seed {args.seed_base}"
        print(f"chaos: FAIL — reproduce with: KSS_TPU_LOCK_WITNESS=1 "
              f"JAX_PLATFORMS=cpu python -m tools.chaos --seeds 1 "
              f"--seed-base {bad.split()[-1]}", file=sys.stderr)
        for p in verdict.get("dumps") or []:
            print(f"chaos: black-box post-mortem dump: {p}",
                  file=sys.stderr)
        return 1
    print(f"chaos: ok — {len(verdict['seeds'])} seeds, "
          f"{verdict['injected_total']} faults injected, "
          f"{verdict['seconds']}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
