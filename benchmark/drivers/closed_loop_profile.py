"""`closed_loop.py`'s closed loop of ONE client under a scheduler profile
the client posts itself, for a deployment whose pods differ: POST one pod,
learn its decision from the watch stream, read it in full, then the next.

Two things beside `closed_loop.py`:

  * before its first cycle (in the warm-up, so in `setup_s`) the driver
    POSTs the deployment's `scheduler_configuration` (the configuration
    file's, handed over by the generator) to
    /api/v1/schedulerconfiguration, reads it back with GET and ends the
    run unless the profile's multiPoint list holds exactly the posted
    plugins at the posted weights (a `Wrapped` suffix, which the
    simulator's converted configuration carries, is taken off).  The cluster is imported by then and
    nothing is pending, so no pass has run under another profile;
  * a guard on what this cell exists to show.  Every cycle's pod is the
    next draw of the deployment's distribution, so consecutive pods carry
    different node-affinity terms.  A program that compiles a scan for
    every distinct set of terms (NodeAffinity's match rows as closure
    constants of the jitted step: any commit before PR 46) takes 3-45 s a
    cycle for ever, and would sit in the warm-up until run.py's deadline.
    Past warm-up cycle `guard.after_cycle`, the `guard.slow_cycles`-th
    cycle that took longer than `guard.slow_s` seconds ends the run at
    once (exit 1, "the server compiles for every pod").  The traffic
    file's `warmup_why` has the readings the two numbers were sized from.

Parameters (the traffic file's `parameters`): `burst` 1, `submit` create,
`read` as in closed_loop.py, and `guard`.
"""

from __future__ import annotations

import json

from drivers.closed_loop import IMPORT_PATH  # noqa: F401  (run.py reads it)
from drivers.closed_loop import Driver as _ClosedLoop
from lib.client import BenchFailure, check, counters

CONFIG_PATH = "/api/v1/schedulerconfiguration"
_WRAPPED = "Wrapped"


def _lineup(configuration: dict) -> list[tuple[str, int]]:
    """(plugin, weight) of the first profile's multiPoint list, in order,
    a `Wrapped` suffix taken off."""
    profile = (configuration.get("profiles") or [{}])[0]
    enabled = ((profile.get("plugins") or {}).get("multiPoint") or {}) \
        .get("enabled") or []
    return [((p.get("name") or "").removesuffix(_WRAPPED),
             int(p.get("weight") or 0)) for p in enabled]


class Driver(_ClosedLoop):
    def __init__(self, params: dict, deployment, seed: int):
        super().__init__(params, deployment, seed)
        if self.burst != 1 or self.submit != "create":
            raise ValueError("one measured pod a cycle, created alone")
        self.configuration: dict | None = deployment.scheduler_configuration
        guard = params["guard"]
        self.guard_after = int(guard["after_cycle"])
        self.guard_slow_s = float(guard["slow_s"])
        self.guard_cycles = int(guard["slow_cycles"])
        self.slow: list[tuple[int, float]] = []

    def _post_profile(self, client) -> None:
        want = _lineup(self.configuration)
        code, raw = client.raw("POST", CONFIG_PATH,
                               json.dumps(self.configuration).encode())
        check(code == 202, f"POST {CONFIG_PATH} -> {code}: {raw[:200]!r}")
        got = _lineup(client.ok("GET", CONFIG_PATH))
        check(got == want, f"the posted profile did not take: the server "
              f"runs {got}, posted {want}")
        print(f"profile posted and read back: {got}", flush=True)
        self.configuration = None

    def cycle(self, k: int, client, watch, keys: list[str], read_pod,
              deadline: float) -> dict:
        if self.configuration is not None:
            self._post_profile(client)
        r = super().cycle(k, client, watch, keys, read_pod, deadline)
        took = r["t1"] - r["t0"]
        if k >= self.guard_after and took > self.guard_slow_s:
            self.slow.append((k, round(took, 3)))
            if len(self.slow) >= self.guard_cycles:
                seen = counters(client)
                raise BenchFailure(
                    f"the server compiles for every pod: {len(self.slow)} "
                    f"cycles past cycle {self.guard_after} took over "
                    f"{self.guard_slow_s} s each, the last of them cycle {k} "
                    f"({self.slow}); pods that differ in node-affinity terms "
                    f"are a new scan executable each on this program: "
                    f"scan_compile_cache_total{{result=miss}} "
                    f"{seen.get('scan_compile_cache_total{result=miss}')}, "
                    f"jax_compile_events_total "
                    f"{seen.get('jax_compile_events_total')} after "
                    f"{seen.get('scheduling_waves_total')} passes")
        return r
