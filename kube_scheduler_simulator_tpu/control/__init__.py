"""Closed-loop control plane (docs/autopilot.md).

Two layers, split so the hot paths stay import-light:

  * this module — `CONTROLS`, the per-session control registry.  It is
    the ONLY thing the data-plane read sites import (the speculative
    stream's starting rung / candidate cap in parallel/speculative.py,
    the weighted HBM budget shares in framework/replay.py, the load-shed
    gate in server/server.py), and it imports nothing but the standard
    library: no telemetry, no JAX, no cycle back into the planes that
    read it.
  * control/autopilot.py — the controller thread that WRITES this
    registry from the observed telemetry planes (SLO windows, accept
    fractions, spill counters).  Two entries are the data plane's own,
    the speculative rounds' record of themselves: whether the session's
    last round found every feasible set inside the candidate cap
    (`note_spec_narrow`), which decides whether the next round runs the
    sparse probe at all; and whether the session's last tried rounds
    COLLAPSED (`note_spec_collapsed`: the first round of a pass kept a
    quarter of what it evaluated or less), which sends the session's
    next batch passes to the sequential scan without a round
    (SchedulerEngine._wave_plan, `spec_declines`) until the queue's
    feasible share has halved (`spec_recheck`) AND a batch pass comes on
    a pod-axis bucket the session's rounds have run on
    (`note_spec_rounds`): the rounds are tried again as a probe, and a
    probe is worth a round, not the compile of a bucket's executables.

The empty registry is the parity baseline: every accessor returns the
static-knob default (`None` override, weight 1.0, no shed), so a
process that never starts the autopilot — or one whose autopilot
failed safe (`reset()`) — behaves byte-identically to the pre-autopilot
engine.  kss-analyze's lock rules watch this module: every method is a
short dict operation under one lock, nothing blocking.
"""

from __future__ import annotations

import threading

# qos tiers, most-sheddable first (docs/api.md session create):
# best-effort sheds under GLOBAL overload, standard only on its own SLO
# breach, critical is never shed by the autopilot
QOS_TIERS = ("best-effort", "standard", "critical")
DEFAULT_QOS = "standard"

# per-session HBM-share weight bounds: the floor keeps every session a
# guaranteed slice (a donor is squeezed, never starved), the cap keeps
# one spilling tenant from monopolizing the pool
WEIGHT_FLOOR = 0.25
WEIGHT_CAP = 4.0


class _SessionControls:
    """Mutable per-session knob overrides; None = static default."""

    __slots__ = ("spec_start_rung", "spec_candidates", "spec_narrow",
                 "spec_collapsed", "spec_retry", "spec_floor", "spec_buckets",
                 "budget_weight", "shed", "retry_after_s")

    def __init__(self):
        self.spec_start_rung: int | None = None   # <0 = top rung
        self.spec_candidates: int | None = None
        self.spec_narrow: bool = False   # no round seen yet: dense
        # (profile, feasible share) of the collapsed first round; None:
        # the rounds are tried
        self.spec_collapsed: tuple | None = None
        # the feasible share of the declined pass that asked for the
        # rounds again (the record stands until the probe runs); the
        # share the probe's own collapse may not record more than; the
        # (profile, pod-axis bucket)s the session's rounds have run on
        self.spec_retry: float | None = None
        self.spec_floor: float | None = None
        self.spec_buckets: set = set()
        self.budget_weight: float = 1.0
        self.shed: bool = False
        self.retry_after_s: int = 1

    def default(self) -> bool:
        return (self.spec_start_rung is None and self.spec_candidates is None
                and self.budget_weight == 1.0 and not self.shed)

    def describe(self) -> dict:
        return {
            "specStartRung": self.spec_start_rung,
            "specCandidates": self.spec_candidates,
            "budgetWeight": self.budget_weight,
            "shed": self.shed,
            "retryAfterSeconds": self.retry_after_s if self.shed else None,
        }


class ControlPlane:
    """The session -> overrides registry.  Reads are one short locked
    dict lookup; a session with no entry IS the default."""

    def __init__(self):
        self._mu = threading.Lock()
        self._by_session: dict[str | None, _SessionControls] = {}

    def _ent(self, session: str | None) -> _SessionControls:
        ent = self._by_session.get(session)
        if ent is None:
            ent = self._by_session[session] = _SessionControls()
        return ent

    # ------------------------------------------------- data-plane reads

    def spec_overrides(self, session: str | None) -> tuple[int | None,
                                                           int | None]:
        """(start rung, candidate cap) for a new speculative stream —
        (None, None) means the static defaults apply."""
        with self._mu:
            ent = self._by_session.get(session)
            if ent is None:
                return None, None
            return ent.spec_start_rung, ent.spec_candidates

    def spec_narrow(self, session: str | None) -> bool:
        """Whether the session's last speculative round kept every
        pod's feasible set inside the candidate cap: the next round then
        runs the sparse probe, else the dense evaluation alone.  False
        for a session no round has served yet."""
        with self._mu:
            ent = self._by_session.get(session)
            return ent is not None and ent.spec_narrow

    def spec_collapsed(self, session: str | None, profile) -> float | None:
        """The median feasible share (feasible nodes / nodes) of the
        round that collapsed, where the session's last tried speculative
        rounds collapsed under this profile (PluginSetConfig.signature;
        another profile's record says nothing of this one); None where
        the rounds are to be tried: a session no round has served yet,
        one whose rounds accepted, another profile."""
        with self._mu:
            ent = self._by_session.get(session)
            if ent is None or ent.spec_collapsed is None:
                return None
            of_profile, share = ent.spec_collapsed
            return share if of_profile == profile else None

    def spec_declines(self, session: str | None, profile,
                      bucket: int) -> bool:
        """Whether a batch pass on pod-axis `bucket` is sent to the
        sequential scan without a round: the session's rounds collapsed
        under this profile (spec_collapsed), and either no declined pass
        has asked for them again (spec_recheck) or this pass cannot be
        the probe, because the session's rounds have not run on its
        bucket (note_spec_rounds) and would compile it first."""
        with self._mu:
            ent = self._by_session.get(session)
            if (ent is None or ent.spec_collapsed is None
                    or ent.spec_collapsed[0] != profile):
                return False
            return (ent.spec_retry is None
                    or (profile, bucket) not in ent.spec_buckets)

    def budget_milliweights(self) -> dict:
        """{session: int(weight*1000)} for sessions with a non-default
        weight; integer milli-weights so the equal-split case computes
        EXACTLY `limit // n` (framework/replay.py parity baseline)."""
        with self._mu:
            return {s: int(round(e.budget_weight * 1000))
                    for s, e in self._by_session.items()
                    if e.budget_weight != 1.0}

    def shed_state(self, session: str | None) -> tuple[bool, int]:
        """(shedding?, Retry-After seconds) — the server's 429 gate."""
        with self._mu:
            ent = self._by_session.get(session)
            if ent is None:
                return False, 0
            return ent.shed, ent.retry_after_s

    # ------------------------------------------------ data-plane writes

    def note_spec_narrow(self, session: str | None, narrow: bool) -> None:
        with self._mu:
            self._ent(session).spec_narrow = bool(narrow)

    def note_spec_rounds(self, session: str | None, profile, bucket: int,
                         probe: bool) -> None:
        """A pass of the session starts its rounds on pod-axis `bucket`.
        probe: the pass can start again as the scan (one chunk), so its
        first round is the evidence a record is made of.  Where a declined
        pass had asked for the rounds again, this is that try: the record
        is cleared, and the share that asked stays as the most a collapse
        of THIS pass may record: the rounds then collapse on a queue whose
        passes reach that share, whatever this pass's own is."""
        with self._mu:
            ent = self._ent(session)
            ent.spec_buckets.add((profile, bucket))
            ent.spec_floor = None
            if probe and ent.spec_retry is not None:
                ent.spec_floor, ent.spec_retry = ent.spec_retry, None
                ent.spec_collapsed = None

    def note_spec_collapsed(self, session: str | None, profile,
                            share: float) -> None:
        """The first round of a one-chunk pass kept a quarter of what it
        evaluated or less (parallel/speculative.py): `share` is the
        median feasible share of the round's pods, the quantity the
        dirty-node rule's acceptance turns on; of a probe (note_spec_rounds)
        no more than the share that asked for it, so that a queue whose
        passes' medians move between two levels (half its pods pinned to
        half the nodes) asks once and not at every turn."""
        with self._mu:
            ent = self._ent(session)
            if ent.spec_floor is not None:
                share = min(float(share), ent.spec_floor)
            ent.spec_collapsed = (profile, float(share))
            ent.spec_retry = ent.spec_floor = None

    def spec_recheck(self, session: str | None, share: float) -> bool:
        """A declined pass's median feasible share, read from the scan's
        own decision row: where it has fallen to half the collapsed
        round's or less (the cluster filled, the queue turned to pinned
        pods), the rounds are asked for again: the next batch pass on a
        bucket they have run on is their probe (spec_declines).  -> whether
        this pass asked (a session that has asked does not ask twice)."""
        with self._mu:
            ent = self._by_session.get(session)
            if (ent is None or ent.spec_collapsed is None
                    or ent.spec_retry is not None):
                return False
            was = ent.spec_collapsed[1]
            # (a record of 0, most of the round's pods without a node,
            # has nothing to fall to and stays until the profile changes)
            if was <= 0 or 2.0 * share > was:
                return False
            ent.spec_retry = float(share)
            return True

    # ------------------------------------------------ autopilot writes

    def set_spec(self, session: str | None, rung: int | None,
                 candidates: int | None) -> None:
        with self._mu:
            ent = self._ent(session)
            ent.spec_start_rung = rung
            ent.spec_candidates = (None if candidates is None
                                   else max(int(candidates), 1))

    def set_budget_weight(self, session: str | None, weight: float) -> None:
        with self._mu:
            self._ent(session).budget_weight = (
                1.0 if weight == 1.0
                else min(max(float(weight), WEIGHT_FLOOR), WEIGHT_CAP))

    def set_shed(self, session: str | None, shed: bool,
                 retry_after_s: int = 1) -> None:
        with self._mu:
            ent = self._ent(session)
            ent.shed = bool(shed)
            ent.retry_after_s = min(max(int(retry_after_s), 1), 600)

    # ---------------------------------------------------- lifecycle

    def drop(self, session: str | None) -> None:
        """Session teardown: overrides must not outlive the session
        (server/sessions.py calls this from _teardown)."""
        with self._mu:
            self._by_session.pop(session, None)

    def reset(self) -> None:
        """The fail-safe: revert EVERY effector to the static-knob
        defaults in one step (a faulted autopilot tick calls this —
        docs/fault-injection.md autopilot.decide seam — and tests)."""
        with self._mu:
            self._by_session.clear()

    def stats(self) -> dict:
        """{session: overrides} for non-default sessions — the
        `autopilot.controls` block on /api/v1/sessions."""
        with self._mu:
            return {(s if s is not None else ""): e.describe()
                    for s, e in self._by_session.items() if not e.default()}


CONTROLS = ControlPlane()
