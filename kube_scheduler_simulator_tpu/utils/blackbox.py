"""Wave black box: crash-consistent post-mortem capture + device telemetry.

The engine's own behavior is its least observable part exactly when it
matters most: the degradation ladder (PR 12) makes load-bearing
decisions — retry, degrade — whose evidence evaporates the moment they
fire, and nothing ever read device memory even though the HBM budget
actively spills chunks.  This module is the always-on flight-data recorder:

  * `BlackBox` — a fixed-size, lock-light ring of structured engine
    events (wave start/end, fault trips with seam + classification,
    degradation transitions, retry suffixes, budget spills, compile
    builds/quarantines, session admission/eviction).  Recording is one
    short lock hold and a dict append; `KSS_TPU_BLACKBOX=0` turns it
    into a single global load + compare (the bench A/B asserts the
    enabled overhead stays within noise).
  * post-mortem **bundles**: on `_WaveAbort`, a degradation step, a
    chaos-gate failure or an explicit `GET /api/v1/debug/dump`, the
    ring is snapshotted together with the tracer's OPEN spans at the
    time of fault, the labeled-counter deltas since the wave started,
    the armed fault plan, every `KSS_TPU_*` env knob and a device-state
    fingerprint (per-device `memory_stats()`), JSON-immutable.  Wave
    aborts auto-write the bundle to `KSS_TPU_BLACKBOX_DIR` so a crashed
    wave ships its own evidence (docs/fault-injection.md).
  * **stall records**: a span that closes after `tracing.STALL_S` is
    kept as a bundle of reason `stall`, in a deque of its own that
    served cycles cannot push out, with what tells waiting from
    working: its subtree by name, the thread's compile and GC seconds,
    its CPU seconds since the watch first saw it stand (read from
    outside: spans read one clock), the process's lateness and the
    stacks the watch took while it stood, and a `cause`
    (docs/metrics.md "Waiting and working; the stall record").
  * `validate_dump()` — the schema check `make blackbox-smoke`, the
    chaos harness and the tests share.
  * `SLOTracker` — rolling per-session p50/p99 wave latency and
    cycles/s over a `KSS_TPU_SLO_WINDOW` window, surfaced on
    `/api/v1/sessions` and `/readyz` (docs/metrics.md).
  * `DeviceTelemetry` — a background sampler reading
    `jax.local_devices()[*].memory_stats()` into `hbm_bytes_in_use` /
    `hbm_peak_bytes` gauges (per-device labels + an aggregate), with an
    EXPLICIT `hbm_stats_available 0` no-op where the backend has no
    memory stats (the CPU backend) instead of silently absent gauges.

Import discipline: this module depends only on utils.tracing,
utils.history and utils.env — everything above it (engine, replay,
faults, sessions) records INTO it, never the other way around.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import traceback
from collections import deque

from . import history as _history
from . import tracing as _tracing
from .env import env_float, env_int
from .history import HISTORY
from .tracing import TRACER

DUMP_VERSION = 1

# KSS_TPU_BLACKBOX=0 turns record() into one global load + compare —
# the same zero-overhead shape as the unarmed fault_point.  Module
# global (not an instance attr) so the hot-path check never chases a
# pointer; set_enabled() is the bench A/B's lever.
_ENABLED = os.environ.get("KSS_TPU_BLACKBOX", "1") != "0"


def enabled() -> bool:
    return _ENABLED


def set_enabled(on: bool) -> bool:
    """Toggle recording (the bench overhead A/B's same-process lever;
    operators use KSS_TPU_BLACKBOX=0).  Returns the previous value.
    The tracer's open-span bookkeeping follows the same flag."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(on)
    _tracing.BLACKBOX_OPEN_SPANS = bool(on)
    return prev


def _env_knobs() -> dict[str, str]:
    """Every KSS_TPU_* knob in force — part of every bundle, so a dump
    is reproducible without asking the operator what they had set."""
    return {k: v for k, v in sorted(os.environ.items())
            if k.startswith("KSS_TPU")}


def describe_exception(exc: BaseException | None) -> dict | None:
    """{type, message, seam, classification} for a bundle's cause."""
    if exc is None:
        return None
    from .faults import classify_fault

    out = {"type": type(exc).__name__,
           "message": str(exc)[:500],
           "classification": classify_fault(exc)}
    seam = getattr(exc, "seam", None)
    if seam:
        out["seam"] = seam
    return out


NO_DEVICE = {
    "available": False, "hbm_available": False,
    "error": "this process runs no engine (externalSchedulerEnabled): "
             "the device belongs to the scheduler process"}


def _runtime_versions() -> dict:
    from importlib import metadata

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def device_fingerprint() -> dict:
    """Per-device state at dump/sample time: platform, kind, and the
    backend's memory_stats() (bytes in use / peak / limit) when the
    backend exposes them, plus the jax/jaxlib/libtpu versions.
    `hbm_available` is an EXPLICIT flag: on the CPU backend
    memory_stats() is absent and the fingerprint says so instead of
    silently omitting the numbers.

    Calling this INITIALISES the JAX backend, i.e. claims the chip — a
    chip belongs to one process, so only a process that runs an engine
    may call it (server/server.py passes NO_DEVICE otherwise)."""
    try:
        import jax

        devs = jax.local_devices()
        backend = jax.default_backend()
    except Exception as e:  # jax not initialized / no backend
        return {"available": False, "hbm_available": False,
                "error": f"{type(e).__name__}: {e}"[:200]}
    out = {"available": True, "backend": backend, "hbm_available": False,
           "versions": _runtime_versions(), "devices": []}
    for d in devs:
        ent = {"id": int(getattr(d, "id", 0)),
               "platform": str(getattr(d, "platform", "")),
               "kind": str(getattr(d, "device_kind", ""))}
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats:
            ent["memory"] = {
                k: int(stats[k]) for k in
                ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                 "bytes_reserved", "largest_free_block_bytes")
                if k in stats
            }
            if "bytes_in_use" in (ent["memory"] or {}):
                out["hbm_available"] = True
        else:
            ent["memory"] = None
        out["devices"].append(ent)
    return out


# ------------------------------------------------------ stalls: readings

# the watch's tick (DeviceTelemetry's third cadence), and the age at
# which an open span gets the baseline its readings start from
WATCH_S = 0.25
# a tick that wakes later than this says that for so long NO Python
# thread of the process got to run (a C call held the GIL, or the whole
# process was off the CPU)
LATE_S = 0.05
# how often the telemetry thread looks whether the black box is back on,
# where it was turned off at run time and no other leg is due
OFF_S = 1.0
# spans whose job is to wait: never a stall, never covering one.
# loop_idle waits for work; http_import is the import's handler, which
# waits for the applier's pool to create what the snapshot holds (1-57 s
# in every benchmark run: 44 records in 36 runs filled the deque)
EXEMPT = frozenset({"loop_idle", "http_import"})
STACK_DEPTH = 12


def _thread_status(native: int | None) -> dict:
    """One thread's state letter and its context switches, from
    /proc/self/task/<native>/status: ONE file, because every read lets
    go of the GIL and the watch waits a switch interval to get it back
    while the thread it watches works.  A host without the file leaves
    them out of the record."""
    out: dict = {}
    try:
        with open(f"/proc/self/task/{native}/status", encoding="ascii",
                  errors="replace") as fh:
            lines = fh.read().splitlines()
    except OSError:
        return out
    for line in lines:
        key, _, val = line.partition(":")
        if key == "State":
            out["state"] = val.split()[0]
        elif key == "voluntary_ctxt_switches":
            out["voluntary_switches"] = int(val)
        elif key == "nonvoluntary_ctxt_switches":
            out["involuntary_switches"] = int(val)
    return out


def _thread_cpu(ident: int) -> float | None:
    """The CPU seconds of ANOTHER thread, read from outside through its
    POSIX CPU-time clock; None where the platform has no such clock or
    the thread is gone."""
    try:
        return time.clock_gettime(time.pthread_getcpuclockid(ident))
    except (AttributeError, OSError, OverflowError):
        return None


def _stacks(first: int | None) -> list[dict]:
    """The top frames of every thread but the caller's, the thread
    `first` (an ident) first."""
    names = {t.ident: t.name for t in threading.enumerate()}
    frames = sys._current_frames()
    frames.pop(threading.get_ident(), None)
    out = []
    for ident in sorted(frames, key=lambda i: i != first)[:32]:
        lines, f = [], frames[ident]
        while f is not None and len(lines) < STACK_DEPTH:
            lines.append(f"{f.f_code.co_filename}:{f.f_lineno} "
                         f"{f.f_code.co_name}")
            f = f.f_back
        out.append({"thread": names.get(ident, str(ident)),
                    "frames": lines})
    return out


CAUSES = ("compile", "gc", "on_cpu", "process_stopped", "blocked", "unseen")


def classify_stall(seconds: float, compile_s: float = 0.0,
                   gc_s: float = 0.0, late_s: float = 0.0,
                   since_s: float | None = None,
                   cpu_since_s: float | None = None) -> str:
    """A stall's cause: the first of compile, gc, on_cpu,
    process_stopped whose seconds make up at least half of the stretch
    they were read over; `blocked` where none does (the thread slept
    while others ran: the stack taken while it stood names the frame).
    compile_s, gc_s and late_s are read over the whole span;
    cpu_since_s, the thread's CPU seconds, over the since_s since the
    watch first saw the span stand.  Where the watch saw less than half
    of the span (or none of it), working cannot be told from waiting:
    no `on_cpu`, and what would have read `blocked` reads `unseen`."""
    half = seconds / 2
    seen = (cpu_since_s is not None and since_s is not None
            and since_s >= half)
    if compile_s >= half:
        return "compile"
    if gc_s >= half:
        return "gc"
    if seen and cpu_since_s >= since_s / 2:
        return "on_cpu"
    if late_s >= half:
        return "process_stopped"
    return "blocked" if seen else "unseen"


class BlackBox:
    """The event ring + bundle builder.  One instance per process
    (`BLACKBOX`); events carry the recording thread's tracer session
    scope so multi-session dumps stay attributable."""

    def __init__(self, capacity: int | None = None):
        self._cap = (capacity if capacity is not None
                     else max(env_int("KSS_TPU_BLACKBOX_CAPACITY", 4096), 64))
        self._mu = threading.Lock()
        self._ring: deque = deque(maxlen=self._cap)
        self._dropped = 0
        self._seq = 0
        # per-session counter baselines captured at wave start, so a
        # dump reports the DELTAS over the failing wave, not process
        # lifetime totals (None = sessionless direct engine use)
        self._baselines: dict[str | None, dict[str, float]] = {}
        # the most recent stored bundles (dump()); immutable via a JSON
        # round trip so a dump never aliases live engine state
        self._dumps: deque = deque(maxlen=8)
        self._dump_n = 0  # filename uniquifier, allocated under _mu
        # stall bundles, in a deque of their own: a served cycle stores
        # nothing here, so the evidence of a stall outlives the rings
        self._stalls: deque = deque(maxlen=32)
        # the watch's notes on spans it has seen standing, by span id,
        # and its own late ticks: (perf_counter, seconds late, stacks)
        self._notes: dict[int, dict] = {}
        self._late: deque = deque(maxlen=256)
        # when the watch's running wait is due to end (perf_counter):
        # a span that closes right after the process was let run again
        # beats the watch to it, and reads the tick's lateness here
        self.watch_due: float | None = None
        # False in a server whose engine lives in another process
        # (DeviceTelemetry.start(device=False)): a bundle made here must
        # not touch the backend
        self.owns_device = True

    # ---------------------------------------------------------- record

    def record(self, kind: str, **fields) -> None:
        """Append one structured event.  Disabled: one global load."""
        if not _ENABLED:
            return
        ev = {"kind": kind, "t": round(time.time(), 6)}
        sid = TRACER.current_session()
        if sid is not None:
            ev["session"] = sid
        tid = TRACER.current_trace()
        if tid is not None:
            ev["trace_id"] = tid
        ev.update(fields)
        with self._mu:
            self._seq += 1
            ev["seq"] = self._seq
            if len(self._ring) == self._cap:
                self._dropped += 1
            self._ring.append(ev)

    def wave_start(self, session: str | None, **fields) -> None:
        """Mark a wave's start: records the event AND captures the
        counter baseline the wave's dump computes deltas against."""
        if not _ENABLED:
            return
        base = TRACER.counter_totals()
        with self._mu:
            self._baselines[session] = base
        self.record("wave.start", **fields)

    # ------------------------------------------------------------ read

    def events(self, session: str | None = None,
               limit: int | None = None) -> list[dict]:
        with self._mu:
            evs = list(self._ring)
        if session is not None:
            evs = [e for e in evs if e.get("session") == session]
        return evs[-limit:] if limit else evs

    def dropped(self) -> int:
        with self._mu:
            return self._dropped

    def counter_deltas(self, session: str | None = None) -> dict[str, float]:
        """Flight-recorder counter movement since the session's last
        wave_start (plain + flattened labeled counters; zero-delta
        entries omitted)."""
        with self._mu:
            base = dict(self._baselines.get(session) or {})
        cur = TRACER.counter_totals()
        return {k: round(v - base.get(k, 0), 6)
                for k, v in cur.items() if v != base.get(k, 0)}

    # ------------------------------------------------------------ dump

    def bundle(self, reason: str, cause: BaseException | None = None,
               session: str | None = None,
               device: bool | None = None) -> dict:
        """Build (but do not store) a post-mortem bundle.  device=False
        (a server whose engine lives in another process; None: what
        `owns_device` says) records NO_DEVICE instead of touching the
        backend."""
        from .faults import current_plan

        if device is None:
            device = self.owns_device

        plan = current_plan()
        # open spans AT THE TIME OF FAULT: the tracer stashes the
        # open-span tree on the exception at the innermost span it
        # unwinds through — by the time the wave protocol builds this
        # bundle every span has closed, so the live view would be empty
        open_spans = getattr(cause, "_kss_open_spans", None)
        if open_spans is None:
            open_spans = TRACER.open_spans()
        if session is not None:
            # same isolation rule as the event ring: a session-scoped
            # bundle must not show a neighbor's in-flight spans
            open_spans = [s for s in open_spans
                          if s.get("session") == session]
        doc = {
            "version": DUMP_VERSION,
            "reason": reason,
            "time": round(time.time(), 6),
            "session": session,
            "cause": describe_exception(cause),
            # session-scoped bundles carry ONLY that session's events —
            # in multi-tenant serving one tenant's dump must not leak a
            # neighbor's activity (the per-session /debug/dump alias)
            "events": self.events(session=session),
            "events_dropped": self.dropped(),
            "open_spans": open_spans,
            "counter_deltas": self.counter_deltas(session),
            "fault_plan": plan.stats() if plan is not None else None,
            "env": _env_knobs(),
            "device": device_fingerprint() if device else NO_DEVICE,
            # the trailing telemetry-history window (utils/history.py):
            # a wave-abort dump answers "what was trending before this"
            # by itself — p99 creep, spill bursts, autopilot moves.
            # Session-scoped bundles keep only that session's series
            # (the same isolation rule as events/open_spans above).
            "history": HISTORY.tail(64, session=session),
        }
        # JSON round trip: the bundle must be immutable evidence, never
        # an aliased view of live dicts a later wave keeps mutating
        return json.loads(json.dumps(doc, default=str))

    def dump(self, reason: str, cause: BaseException | None = None,
             session: str | None = None, write: bool = False,
             directory: str | None = None,
             stall: dict | None = None) -> tuple[dict, str | None]:
        """Snapshot a bundle, store it in the recent-dumps ring (one
        with a `stall` record: in the stalls' own), and — when `write`
        and a directory is available (`directory` arg or
        KSS_TPU_BLACKBOX_DIR) — persist it to disk.  Returns
        (bundle, path-or-None).  Never raises: a failing dump must not
        mask the fault it describes."""
        try:
            doc = self.bundle(reason, cause=cause, session=session)
        except Exception as e:  # pragma: no cover - defensive
            doc = {"version": DUMP_VERSION, "reason": reason,
                   "time": time.time(), "session": session,
                   "error": f"bundle failed: {type(e).__name__}: {e}"[:300]}
        if stall is not None:
            doc["stall"] = stall
        path = None
        if write:
            d = directory or os.environ.get("KSS_TPU_BLACKBOX_DIR")
            if d:
                try:
                    os.makedirs(d, exist_ok=True)
                    stamp = time.strftime("%Y%m%d-%H%M%S")
                    # pid + a locked counter: two aborts in the same
                    # second (or two processes sharing the dir) must
                    # never overwrite each other's evidence
                    with self._mu:
                        self._dump_n += 1
                        n = self._dump_n
                    fname = (f"blackbox-{stamp}-{os.getpid()}-{n}"
                             f"-{reason}.json")
                    path = os.path.join(d, fname)
                    with open(path, "w", encoding="utf-8") as fh:
                        json.dump(doc, fh, indent=1)
                # a full disk / bad dir must not mask the wave fault
                # kss-analyze: allow(swallowed-exception)
                except OSError:
                    path = None
        doc["path"] = path
        with self._mu:
            (self._dumps if stall is None else self._stalls).append(doc)
        TRACER.inc("blackbox_dumps_total", reason=reason)
        return doc, path

    def recent_dumps(self) -> list[dict]:
        """Metadata of stored bundles, newest last (the full bundle is
        on disk at `path`, or retrievable live via bundle())."""
        with self._mu:
            dumps = list(self._dumps)
        return [{k: d.get(k) for k in
                 ("reason", "time", "session", "cause", "path")}
                for d in dumps]

    def last_dump(self) -> dict | None:
        with self._mu:
            return self._dumps[-1] if self._dumps else None

    # ---------------------------------------------------------- stalls

    def watch_tick(self, late: float = 0.0) -> None:
        """One tick of the watch on open spans (DeviceTelemetry's
        thread, every WATCH_S).  `late`: by how much the tick's wait
        overran.  A span first seen WATCH_S old gets the baseline its
        readings start from (its thread's CPU clock, read from outside);
        one seen STALL_S old is noted once, while it still stands: every
        thread's stack, its own thread's first."""
        self.watch_due = None
        if not _ENABLED:
            return
        TRACER.count("watchdog_ticks_total")
        if late > LATE_S:
            TRACER.count("process_late_seconds_total", late - LATE_S)
            # a whole tick missed: the stacks right after name the
            # threads (the one that held the GIL is still at its call)
            stacks = _stacks(None) if late >= WATCH_S else None
            with self._mu:
                self._late.append((time.perf_counter(), late, stacks))
        standing = [sp for sp in TRACER.open_since(WATCH_S)
                    if sp["name"] not in EXEMPT]
        with self._mu:
            for gone in self._notes.keys() - {sp["span_id"]
                                              for sp in standing}:
                del self._notes[gone]
            notes = {sp["span_id"]: self._notes.get(sp["span_id"])
                     for sp in standing}
        if not standing:
            return
        natives = {t.ident: t.native_id for t in threading.enumerate()}
        status: dict = {}  # by thread: nested spans come of age together
        for sp in standing:
            note = notes[sp["span_id"]]
            native = natives.get(sp["ident"])
            if note is None:
                # the clocks first and the note kept at once: a span
                # that closes during the file read finds its baseline
                # (only of a thread that is still there: the clock's id
                # is made from the thread's own memory)
                note = {"t": time.perf_counter(),
                        "cpu": (_thread_cpu(sp["ident"])
                                if native is not None else None),
                        "process_cpu": time.process_time(), "status": {}}
                with self._mu:
                    self._notes[sp["span_id"]] = note
                if native not in status:
                    status[native] = _thread_status(native)
                note["status"] = status[native]
            age = time.perf_counter() - sp["t0_perf"]
            if "stacks" not in note and age >= _tracing.STALL_S:
                note["noted_after_s"] = round(age, 6)
                note["state"] = _thread_status(native).get("state")
                note["stacks"] = _stacks(sp["ident"])

    def _subtree(self, event: dict) -> tuple[dict, float, dict, bool]:
        """The span's descendants that the tracer still holds, summed by
        name (count, seconds); the seconds of it that descendants which
        stood for STALL_S themselves cover (the topmost ones: nothing
        twice); those descendants, all of them, {id: name}; and whether
        the ring rolled over inside the span (the sums then lack the
        early spans shorter than tracing.LONG_S)."""
        held, rolled = TRACER.subtree_events(event["ts"])
        kids: dict[int, list[dict]] = {}
        for ev in held:
            if ev.get("parent_id") is not None:
                kids.setdefault(ev["parent_id"], []).append(ev)
        by_name: dict[str, dict] = {}
        covered_s, stalled = 0.0, {}
        todo = [(event["span_id"], False)]
        while todo:
            parent, covered = todo.pop()
            for ev in kids.get(parent, ()):
                a = by_name.setdefault(
                    ev["name"], {"count": 0, "seconds": 0.0})
                a["count"] += 1
                a["seconds"] += ev["seconds"]
                stood = (ev["seconds"] >= _tracing.STALL_S
                         and ev["name"] not in EXEMPT)
                if stood:
                    stalled[ev["span_id"]] = ev["name"]
                    if not covered:
                        covered_s += ev["seconds"]
                todo.append((ev["span_id"], covered or stood))
        for a in by_name.values():
            a["seconds"] = round(a["seconds"], 6)
        return by_name, covered_s, stalled, rolled

    def on_stall(self, event: dict) -> None:
        """The tracer's hook: a span closed after STALL_S or more, on
        this thread.  Keeps the record (a bundle of reason `stall`,
        one line on stderr, the counters), or, where a stalled
        descendant covers half of the span, names the span as that
        stall's ancestor.  Never raises into the span's caller."""
        if not _ENABLED or event["name"] in EXEMPT:
            return
        try:
            self._keep_stall(event)
        except Exception:  # the evidence failed, not the pass
            print(f"kss-tpu stall: span={event['name']}: the record failed",
                  file=sys.stderr)
            traceback.print_exc()

    def _keep_stall(self, event: dict) -> None:
        from . import hostevents

        # this thread's clocks first: what follows is not the span's
        now, cpu_now = time.perf_counter(), time.thread_time()
        name, seconds = event["name"], event["seconds"]
        with self._mu:
            note = self._notes.pop(event["span_id"], None)
            late = [m for m in self._late if m[0] >= now - seconds]
        by_name, covered_s, stalled, rolled = self._subtree(event)
        record = {
            "span": name, "span_id": event["span_id"], "ts": event["ts"],
            "seconds": round(seconds, 6),
            "session": event.get("session"),
            "trace_id": event.get("trace_id"),
            "ancestors": TRACER.open_chain(event.get("parent_id")),
            "descendants": by_name, "short_descendants_lost": rolled,
        }
        if covered_s >= seconds / 2:
            # no stall of its own: the records of the stalls under it
            # name it as their ancestor
            with self._mu:
                kept = [doc["stall"] for doc in self._stalls
                        if doc["stall"]["span_id"] in stalled]
                for rec in kept:
                    rec["ancestors_closed"].append(record)
            print(f"kss-tpu stall: span={name} seconds={seconds:.3f} "
                  "ancestor of "
                  + ",".join(sorted({rec["span"] for rec in kept})),
                  file=sys.stderr, flush=True)
            return
        t0 = now - seconds
        due = self.watch_due
        overdue = now - due if due is not None and now - due > LATE_S else 0.0
        readings = {
            "compile_s": round(hostevents.compile_seconds_since(t0), 6),
            "gc_s": round(hostevents.gc_seconds_since(t0), 6),
            # the late ticks inside the span, and the one that is late
            # right now and has not woken yet
            "late_s": round(sum((m[1] for m in late), overdue), 6),
        }
        if note is not None:
            # the watch saw it standing: what has grown since
            readings["since_s"] = round(now - note["t"], 6)
            if note["cpu"] is not None:
                readings["cpu_since_s"] = round(cpu_now - note["cpu"], 6)
            readings["process_cpu_since_s"] = round(
                time.process_time() - note["process_cpu"], 6)
            after = _thread_status(threading.get_native_id())
            readings.update(
                (k, after[k] - v) for k, v in note["status"].items()
                if k != "state" and k in after)
            # the thread's state letter as the watch read it, not now
            readings["state"] = note.get("state")
            record["noted_after_s"] = note.get("noted_after_s")
            record["stacks"] = note.get("stacks", [])
        record["late_stacks"] = [m[2] for m in late if m[2]][-2:]
        record["readings"] = readings
        record["cause"] = cause = classify_stall(
            seconds, readings["compile_s"], readings["gc_s"],
            readings["late_s"], readings.get("since_s"),
            readings.get("cpu_since_s"))
        record["ancestors_closed"] = []
        TRACER.inc("span_stalls_total", span=name, cause=cause)
        TRACER.count("span_stall_seconds_total", seconds)
        _, path = self.dump("stall", session=event.get("session"),
                            write=True, stall=record)
        print(f"kss-tpu stall: span={name} seconds={seconds:.3f} "
              f"cause={cause} "
              + " ".join(f"{k}={readings[k]}" for k in
                         ("cpu_since_s", "since_s", "compile_s", "gc_s",
                          "late_s") if k in readings)
              + f" session={event.get('session')} ancestors="
              + ",".join(record["ancestors"]) + f" path={path}",
              file=sys.stderr, flush=True)

    def stalls(self, session: str | None = None) -> list[dict]:
        """The kept stall records, oldest first (each the `stall` of its
        bundle, with the bundle's time and path); the bundles themselves
        are stall_dumps()."""
        with self._mu:
            docs = list(self._stalls)
        return [{**d["stall"], "time": d.get("time"), "path": d.get("path")}
                for d in docs
                if session is None or d.get("session") == session]

    def stall_dumps(self) -> list[dict]:
        with self._mu:
            return list(self._stalls)

    def drop_session(self, session: str | None) -> None:
        """Release a torn-down session's counter baseline (session
        eviction calls this — per-session state must not outlive the
        session on a churning server)."""
        with self._mu:
            self._baselines.pop(session, None)

    def reset(self) -> None:
        """Tests only: clear the ring, baselines and stored dumps."""
        with self._mu:
            self._ring.clear()
            self._dumps.clear()
            self._stalls.clear()
            self._notes.clear()
            self._late.clear()
            self.watch_due = None
            self._baselines.clear()
            self._dropped = 0


BLACKBOX = BlackBox()
TRACER.set_stall_hook(BLACKBOX.on_stall)


# ------------------------------------------------------- dump validation


_REQUIRED_KEYS = ("version", "reason", "time", "events", "open_spans",
                  "counter_deltas", "env", "device")


def validate_dump(doc: dict, require_fault: bool = False) -> dict:
    """Schema check for a post-mortem bundle — shared by the tests,
    `make blackbox-smoke` and the chaos harness.  Raises ValueError
    with the first violation; returns {kinds: {kind: count}} on
    success.  `require_fault` additionally asserts a fault trip with
    seam + classification and a cause."""
    for k in _REQUIRED_KEYS:
        if k not in doc:
            raise ValueError(f"dump missing key {k!r}")
    if doc["version"] != DUMP_VERSION:
        raise ValueError(f"dump version {doc['version']!r} != {DUMP_VERSION}")
    if not isinstance(doc["events"], list):
        raise ValueError("dump events is not a list")
    kinds: dict[str, int] = {}
    for ev in doc["events"]:
        if "kind" not in ev or "t" not in ev or "seq" not in ev:
            raise ValueError(f"malformed event {ev!r}")
        kinds[ev["kind"]] = kinds.get(ev["kind"], 0) + 1
        if ev["kind"] == "fault.trip":
            for field in ("seam", "classification", "error"):
                if field not in ev:
                    raise ValueError(f"fault.trip missing {field!r}: {ev!r}")
        if ev["kind"] == "autopilot.decide":
            # every autopilot decision is structured evidence
            # (control/autopilot.py): which effector moved which
            # session from what to what, and why
            for field in ("effector", "session", "from", "to", "reason"):
                if field not in ev:
                    raise ValueError(
                        f"autopilot.decide missing {field!r}: {ev!r}")
            # provenance: when the decision carries an evidence block
            # it must be structured (the planes the effector read) and
            # any cited history index must be an integer
            evd = ev.get("evidence")
            if evd is not None:
                if not isinstance(evd, dict):
                    raise ValueError(
                        f"autopilot.decide evidence not a dict: {ev!r}")
                hidx = evd.get("historyIndex")
                if hidx is not None and not isinstance(hidx, int):
                    raise ValueError(
                        f"evidence historyIndex not an int: {ev!r}")
    if not isinstance(doc["counter_deltas"], dict):
        raise ValueError("counter_deltas is not a dict")
    hist = doc.get("history")
    if hist is not None:
        # the embedded trailing window must be the columnar shape
        # (utils/history.py): index/t arrays plus equal-length series
        # columns — never one dict per sample
        if (not isinstance(hist, dict) or "index" not in hist
                or "series" not in hist):
            raise ValueError("history window missing index/series")
        n_rows = len(hist["index"])
        if len(hist.get("t") or []) != n_rows:
            raise ValueError("history t column length != index length")
        if not isinstance(hist["series"], dict):
            raise ValueError("history series is not a dict of columns")
        for nm, col in hist["series"].items():
            if len(col) != n_rows:
                raise ValueError(
                    f"history column {nm!r} length {len(col)} != {n_rows}")
    dev = doc["device"]
    if not isinstance(dev, dict) or "hbm_available" not in dev:
        raise ValueError("device fingerprint missing hbm_available")
    if require_fault:
        if not kinds.get("fault.trip"):
            raise ValueError("dump has no fault.trip event")
        cause = doc.get("cause")
        if not cause or "classification" not in cause:
            raise ValueError("dump has no classified cause")
        # the action the protocol took must be on the record too
        if not (kinds.get("wave.retry") or kinds.get("wave.abort")
                or kinds.get("degrade")):
            raise ValueError("dump records no protocol action "
                             "(wave.retry / wave.abort / degrade)")
        if not doc["counter_deltas"]:
            raise ValueError("dump has empty counter deltas for the wave")
    return {"kinds": kinds}


# ------------------------------------------------------------ SLO plane


class SLOTracker:
    """Rolling per-session wave SLOs: p50/p99 wave latency and
    cycles/s over the last KSS_TPU_SLO_WINDOW waves (default 64).
    observe_wave() is one deque append under a short lock — cheap
    enough to stay on for every wave; percentiles sort the (small)
    window only when read (/api/v1/sessions, /readyz)."""

    def __init__(self, window: int | None = None):
        self._window = (window if window is not None
                        else max(env_int("KSS_TPU_SLO_WINDOW", 64), 4))
        self._mu = threading.Lock()
        self._waves: dict[str | None, deque] = {}
        # monotonic per-session wave count: the window above freezes
        # when inflow stops, so consumers judging liveness (the
        # autopilot's shed recovery) need a counter that only moves
        # when waves actually run
        self._totals: dict[str | None, int] = {}

    def observe_wave(self, session: str | None, seconds: float,
                     pods: int) -> None:
        if pods <= 0:
            return
        with self._mu:
            dq = self._waves.get(session)
            if dq is None:
                dq = self._waves[session] = deque(maxlen=self._window)
            dq.append((seconds, pods))
            self._totals[session] = self._totals.get(session, 0) + 1

    @staticmethod
    def _pct(sorted_vals: list[float], q: float) -> float:
        i = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
        return sorted_vals[i]

    def stats(self, session: str | None) -> dict | None:
        """{waves, totalWaves, p50WaveSeconds, p99WaveSeconds,
        cyclesPerSec} over the window, or None when the session never
        ran a wave.  `totalWaves` is the lifetime count — unlike
        `waves` (window occupancy, saturates at `window`) it keeps
        moving while traffic flows, so a frozen window is detectable."""
        with self._mu:
            dq = self._waves.get(session)
            entries = list(dq) if dq else None
            total = self._totals.get(session, 0)
        if not entries:
            return None
        secs = sorted(s for s, _ in entries)
        total_s = sum(s for s, _ in entries)
        total_p = sum(p for _, p in entries)
        return {
            "waves": len(entries),
            "totalWaves": total,
            "window": self._window,
            "p50WaveSeconds": round(self._pct(secs, 0.50), 6),
            "p99WaveSeconds": round(self._pct(secs, 0.99), 6),
            "cyclesPerSec": round(total_p / total_s, 1) if total_s else None,
        }

    def drop_session(self, session: str | None) -> None:
        """Release a torn-down session's window (session eviction)."""
        with self._mu:
            self._waves.pop(session, None)
            self._totals.pop(session, None)

    def snapshot(self) -> dict[str, dict]:
        """{session ("" = sessionless): stats} for every session with
        waves in the window — the /readyz surface."""
        with self._mu:
            keys = list(self._waves.keys())
        out = {}
        for k in keys:
            s = self.stats(k)
            if s is not None:
                out[k if k is not None else ""] = s
        return out

    def reset(self) -> None:
        with self._mu:
            self._waves.clear()
            self._totals.clear()


SLO = SLOTracker()


# ------------------------------------------------------- history feeder


class HistoryFeeder:
    """One tick of the observability planes -> one columnar history
    sample (utils/history.py).

    gather() reads every plane ONCE into plain dicts — SLO windows,
    per-session spill counter totals, the control-plane
    override state — and sample() derives the ring columns from them.
    The autopilot plans FROM the same returned dicts, so a decision's
    evidence cites a ring index whose values match what the effector
    read bit-for-bit (control/autopilot.py decision provenance), and
    with KSS_TPU_HISTORY=0 the planes are still returned (index -1):
    one code path, parity preserved.

    Global series are per-sample counter DELTAS (the feeder keeps its
    own baselines); per-session series are window stats / fractions at
    sample time.
    """

    # plain (unlabeled) counters whose per-sample deltas become global
    # columns; the labeled spill family is summed from the per-session
    # plane instead
    _PLAIN = ("pods_scheduled_total", "pods_unschedulable_total",
              "scheduling_waves_total")

    def __init__(self):
        self._mu = threading.Lock()
        self._base: dict[str, float] = {}

    def gather(self) -> dict:
        from ..control import CONTROLS

        return {
            "slo": SLO.snapshot(),
            "spilled": TRACER.labeled_totals(
                "device_chunks_spilled_total", "session"),
            "controls": CONTROLS.stats(),
        }

    def sample(self) -> tuple[int, dict]:
        """Gather the planes and append one ring sample.  Returns
        (absolute ring index or -1 when history is off, planes)."""
        planes = self.gather()
        if not _history.enabled():
            return -1, planes
        totals = TRACER.counter_totals()
        values: dict[str, float] = {}
        current = {name: float(totals.get(name, 0.0)) for name in self._PLAIN}
        current["device_chunks_spilled_total"] = sum(
            planes["spilled"].values())
        with self._mu:
            for name, cur in current.items():
                values[name] = cur - self._base.get(name, 0.0)
                self._base[name] = cur
            # per-session spill delta this sample (baselines keyed per
            # session)
            for sid, sp in planes["spilled"].items():
                sp_d = sp - self._base.get(f"s\x00{sid}", 0.0)
                self._base[f"s\x00{sid}"] = sp
                values[f"spill.delta{{session={sid}}}"] = sp_d
        for sid, stats in planes["slo"].items():
            tag = f"{{session={sid}}}"
            values[f"slo.p50{tag}"] = float(stats["p50WaveSeconds"])
            values[f"slo.p99{tag}"] = float(stats["p99WaveSeconds"])
            cps = stats.get("cyclesPerSec")
            if cps is not None:
                values[f"slo.cps{tag}"] = float(cps)
        # autopilot effector state, explicit for every ACTIVE session
        # (any the SLO plane has seen plus any the control plane is
        # steering): CONTROLS.stats() omits default-state sessions, but
        # the ring must record 0.0 / 1.0 there rather than a gap — a
        # shed on/off transition reconstructs from the columns without
        # guessing what a missing row meant
        ctls = planes["controls"]
        for sid in {s for s in planes["slo"] if s} | set(ctls):
            ctl = ctls.get(sid) or {}
            tag = f"{{session={sid}}}"
            values[f"autopilot.shed{tag}"] = 1.0 if ctl.get("shed") else 0.0
            values[f"autopilot.budget_weight{tag}"] = float(
                ctl.get("budgetWeight") or 1.0)
        idx = HISTORY.append(values, t_us=int(time.time() * 1e6))
        return idx, planes

    def reset(self) -> None:
        """Tests only: forget the delta baselines."""
        with self._mu:
            self._base.clear()


FEEDER = HistoryFeeder()


# ----------------------------------------------------- device telemetry


class DeviceTelemetry:
    """Background HBM sampler: every KSS_TPU_HBM_SAMPLE_S seconds
    (default 5) read each local device's memory_stats() into

      * hbm_bytes_in_use{device=<id>} / hbm_peak_bytes{device=<id>}
        labeled gauges, plus unlabeled aggregates (sums across devices);
      * hbm_stats_available — 1 where the backend reports memory stats,
        0 as the EXPLICIT no-op marker on backends that don't (CPU).

    start() is idempotent; the thread is a daemon and samples once
    immediately, so /api/v1/metrics shows the gauges right after server
    boot.  sample_once() is the direct surface bench and tests use."""

    def __init__(self):
        self._mu = threading.Lock()
        self._thread: threading.Thread | None = None
        # each start() mints a fresh stop event captured by its loop, so
        # a stale stop() can never kill a newer sampler thread
        self._stop: threading.Event | None = None
        # start()/stop() refcount: the sampler is process-global but
        # started per server — the last stopping server ends it, an
        # earlier one must not kill a still-running neighbor's sampling
        self._refs = 0
        self._last: dict | None = None

    def sample_once(self) -> dict:
        fp = device_fingerprint()
        available = bool(fp.get("hbm_available"))
        TRACER.gauge("hbm_stats_available", 1 if available else 0)
        total_use = 0
        total_peak = 0
        if available:
            for ent in fp.get("devices", ()):
                mem = ent.get("memory") or {}
                use = mem.get("bytes_in_use")
                if use is None:
                    continue
                peak = mem.get("peak_bytes_in_use", use)
                TRACER.gauge("hbm_bytes_in_use", use,
                             device=str(ent["id"]))
                TRACER.gauge("hbm_peak_bytes", peak,
                             device=str(ent["id"]))
                total_use += use
                total_peak += peak
            TRACER.gauge("hbm_bytes_in_use", total_use)
            TRACER.gauge("hbm_peak_bytes", total_peak)
        out = {"available": available,
               "backend": fp.get("backend"),
               "bytes_in_use": total_use if available else None,
               "peak_bytes": total_peak if available else None,
               "devices": len(fp.get("devices", ()))}
        with self._mu:
            self._last = out
        return out

    def last(self) -> dict | None:
        with self._mu:
            return self._last

    def start(self, interval: float | None = None,
              device: bool = True) -> None:
        """Start the sampler (idempotent).  interval <= 0 (or
        KSS_TPU_HBM_SAMPLE_S=0) disables the HBM leg, and so does
        device=False — the caller runs no engine, and sampling would
        claim the chip from the process that does; the same thread
        also feeds the telemetry history ring every
        KSS_TPU_HISTORY_SAMPLE_S seconds (utils/history.py) and, every
        WATCH_S, runs the black box's watch on open spans — three
        cadences, one thread, each with its own next-due clock.  No
        thread starts when all three legs are off.  The whole start decision
        runs under the lock so two concurrent start() calls can never
        spawn two samplers, and a fresh stop event per thread means a
        racing stop() never leaves a newly started sampler dead."""
        BLACKBOX.owns_device = device
        if not device:
            interval = 0.0
        elif interval is None:
            interval = env_float("KSS_TPU_HBM_SAMPLE_S", 5.0)
        hist_iv = _history.sample_interval() if _history.enabled() else 0.0
        if _ENABLED:
            # 0.0 is a reading; a program without the watch lists neither
            TRACER.count("process_late_seconds_total", 0)
            TRACER.count("span_stall_seconds_total", 0)
        t = None
        with self._mu:
            self._refs += 1
            # _thread is the INTENT marker (set before start(), cleared
            # only by the last stop()): an is_alive() check would let a
            # second caller slip in between thread creation and start()
            if self._thread is None:
                if interval > 0 or hist_iv > 0 or _ENABLED:
                    stop = self._stop = threading.Event()

                    def loop():
                        inf = float("inf")
                        hbm_iv = interval if interval > 0 else inf
                        h_iv = hist_iv if hist_iv > 0 else inf
                        now = time.monotonic()
                        next_hbm = now + hbm_iv
                        next_hist = now + h_iv
                        while True:
                            # the third cadence: the watch on open spans
                            # (BlackBox.watch_tick), off with the black
                            # box.  Every wake is a tick of it, and how
                            # far the WAIT overran is its lateness: the
                            # legs' own work below is not in it
                            # (turned off at run time with no other
                            # leg on, it looks again every OFF_S:
                            # wait() takes no infinite timeout)
                            watch_iv = WATCH_S if _ENABLED else OFF_S
                            t_wait = time.monotonic()
                            timeout = max(min(next_hbm, next_hist,
                                              t_wait + watch_iv) - t_wait,
                                          0.01)
                            BLACKBOX.watch_due = (time.perf_counter()
                                                   + timeout)
                            if stop.wait(timeout):
                                BLACKBOX.watch_due = None
                                return
                            now = time.monotonic()
                            BLACKBOX.watch_tick(now - t_wait - timeout)
                            if now < next_hbm and now < next_hist:
                                continue
                            # one span a sample: a rhythm in the served
                            # latency can be laid beside this thread's
                            with TRACER.span("telemetry_sample"):
                                if now >= next_hbm:
                                    try:
                                        self.sample_once()
                                    # survive a backend teardown race
                                    # kss-analyze: allow(swallowed-exception)
                                    except Exception:
                                        pass
                                    next_hbm = now + hbm_iv
                                if now >= next_hist:
                                    try:
                                        FEEDER.sample()
                                    # same contract as the HBM leg
                                    # kss-analyze: allow(swallowed-exception)
                                    except Exception:
                                        pass
                                    next_hist = now + h_iv

                    t = self._thread = threading.Thread(
                        target=loop, daemon=True, name="hbm-sampler")
        if device:
            self.sample_once()
        if t is not None:
            t.start()

    def stop(self) -> None:
        """Release one start() hold; the sampler thread ends when the
        last holder stops (server shutdown calls this)."""
        with self._mu:
            self._refs = max(self._refs - 1, 0)
            if self._refs:
                return
            if self._stop is not None:
                self._stop.set()
            self._thread = None


TELEMETRY = DeviceTelemetry()
