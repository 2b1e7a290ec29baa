"""`closed_loop_profile.py`'s closed loop under a posted profile, for a
deployment that arrives as a QUEUE: a cycle is ONE POST /api/v1/import
carrying the next `burst` draws of the deployment's distribution, the
wait on the watch stream until every one of them is decided, and the full
read of one seeded pod of the burst; then the next burst.

Three things beside `closed_loop_profile.py`, which refuses any burst but
one pod created alone:

  * `burst` > 1 and `submit` import (closed_loop.py's own burst cycle):
    the scheduling loop takes the burst as one pass, or as two where its
    50 ms window cap cuts the import's pod events apart, so the passes of
    a run hold any count from 1 to `burst`;
  * the profile is posted and read back before the first cycle as there
    (`_post_profile`, inherited), which with `warmup.ascending_shapes` is
    the first of the smaller bursts (1, 2, ..., burst - 1, smallest
    first: run.py provisions them and counts them as cycles 0..burst-2);
  * the guard is on what this cell exists to show.  A program whose pod
    axis is the pass's own count (any commit before PR 50) compiles the
    speculative rounds' executables anew for every count it has not met:
    every one of the ascending shapes, ~20-60 s each, and then any count
    a split leaves.  A program that buckets the pod axis meets a new
    bucket at the bursts of 1, 2, 3, 5, 9 and 17 pods only, and one or
    two more executables of a met bucket in the burst after.  From cycle
    `guard.after_cycle` on (the smaller bursts are counted), the
    `guard.slow_cycles`-th cycle IN A ROW that took longer than
    `guard.slow_s` seconds ends the run at once (exit 1, "the server
    compiles for every count"); a cycle under it starts the count anew.  The traffic file's `warmup_why` has the readings the
    numbers were sized from.

Parameters (the traffic file's `parameters`): `burst`, `submit` import,
`read` and `think_s` as in closed_loop.py, and `guard`.
"""

from __future__ import annotations

from drivers.closed_loop import IMPORT_PATH  # noqa: F401  (run.py reads it)
from drivers.closed_loop import Driver as _ClosedLoop
from drivers.closed_loop_profile import Driver as _Profile
from lib.client import BenchFailure, counters


class Driver(_Profile):
    def __init__(self, params: dict, deployment, seed: int):
        # closed_loop.py's set-up: closed_loop_profile.py's own refuses a
        # burst; what it adds (the profile, the guard's numbers) is below
        _ClosedLoop.__init__(self, params, deployment, seed)
        if self.burst < 2 or self.submit != "import":
            raise ValueError("a burst of two pods or more, imported at once")
        self.configuration: dict | None = deployment.scheduler_configuration
        guard = params["guard"]
        self.guard_after = int(guard["after_cycle"])
        self.guard_slow_s = float(guard["slow_s"])
        self.guard_cycles = int(guard["slow_cycles"])
        self.slow: list[tuple[int, int, float]] = []

    def cycle(self, k: int, client, watch, keys: list[str], read_pod,
              deadline: float) -> dict:
        if self.configuration is not None:
            self._post_profile(client)
        r = _ClosedLoop.cycle(self, k, client, watch, keys, read_pod, deadline)
        took = r["t1"] - r["t0"]
        if k < self.guard_after or took <= self.guard_slow_s:
            self.slow.clear()
        else:
            self.slow.append((k, r["pods"], round(took, 3)))
            if len(self.slow) >= self.guard_cycles:
                seen = counters(client)
                raise BenchFailure(
                    f"the server compiles for every count: {len(self.slow)} "
                    f"cycles in a row from cycle {self.guard_after} on took "
                    f"over {self.guard_slow_s} s each, the last of them cycle {k} "
                    f"((cycle, pods, seconds): {self.slow}); a pass of a "
                    f"count the process has not met is a new set of "
                    f"executables on this program: "
                    f"scan_compile_cache_total{{result=miss}} "
                    f"{seen.get('scan_compile_cache_total{result=miss}')}, "
                    f"jax_compile_events_total "
                    f"{seen.get('jax_compile_events_total')} after "
                    f"{seen.get('scheduling_waves_total')} passes")
        return r
