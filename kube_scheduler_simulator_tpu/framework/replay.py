"""Chunked lax.scan replay of a pod queue.

The replay analogue of the reference's replayer + scheduler loop
(reference: simulator/replayer/replayer.go:37-61 applies recorded events in
order with no delays; each unscheduled pod then goes through the scheduling
cycle of SURVEY.md §3.2).  Here the entire queue is evaluated as a
`lax.scan` of the fused step (framework/pipeline.py) over the pod axis.

The scan is chunked (default 512 pods per device call) for two reasons:
  * output tensors are [chunk, .., N]; chunking bounds device memory at
    ~chunk x plugins x nodes regardless of queue length;
  * per-chunk host copies overlap with later chunks' device compute
    (dispatch is async; each chunk's blocking fetch runs on a pool thread
    while the device runs the chunks after it), pipelining transfer with
    TPU evaluate.

Every result byte crosses the device->host link, so the scan emits
pipeline.CompactOut instead of the full result tensors: filter codes pack to one int per node (the decoder
only needs the first failing plugin — the framework stops there), raw
scores travel as int16 with an overflow->int32 retry, and finalscore is
recomputed on host from raw + feasibility (framework/hostnorm.py mirrors,
bit-identical).  ReplayResult hides all of this behind per-pod accessors.

Device residency (docs/wave-pipeline.md device-residency stage): by
default, when no streaming consumer decodes in-wave, even the compact
tensors don't cross — the wave fetches only per-pod DECISION ROWS
(selected / feasible_count / prefilter_reject / raw_overflow, plus the
jit'd per-chunk attribution sums; over the packed route all of them laid
into ONE buffer by the scan's executable and fetched in one transfer,
_pack_row / _cut_row) and the heavy packed/raw arrays stay
live in device memory, materializing per chunk on first cold read
(_CompactChunks.host, memoized + exactly-once) with an LRU spill budget
(KSS_TPU_DEVICE_RESULT_BUDGET_MB) bounding HBM across waves.  The
host-resident fetch (device_resident=False) is the bit-identical rung the
engine's degradation ladder steps down to and the parity suites compare
against.

The pod axis of a pass is a bucket (state/compile.py pod_axis_bucket:
the next power of two up to the chunk, whole chunks beyond), so the rows
past the pass's pods are padded; padded steps carry `is_pad` and never
bind (pipeline masks their selection to -1), and nothing past the scan
reads them: every consumer works on [lo, hi) of the REAL pods.
"""

from __future__ import annotations

import copy
import math
import os
import threading
import time
import weakref
import zlib
from collections import OrderedDict
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .pipeline import build_step
from ..control import CONTROLS
from ..state.compile import (
    POD_CHUNK, CompiledWorkload, attribution_skip_masks, pod_axis_bucket,
    statics_digest)
from ..state.packed import Packed, unpack_leaves
from ..utils.faults import fault_point
from ..utils.tracing import TRACER


class _FailStreak:
    """PER-SESSION consecutive-failure counters for the on-demand
    materialization path: any success resets the failing session's
    streak.  The engine's wave failure protocol reads ITS session's
    streak at wave start — a streak past KSS_TPU_MATERIALIZE_FAIL_LIMIT
    is a structural device signal (repeated D2H failure), answered by
    stepping that session's degradation ladder down to host-resident
    fetch (docs/fault-injection.md).  Buckets key on the tracer session
    scope active at the failing read (None = sessionless direct engine
    use), so one tenant's flaky link never degrades a neighbor."""

    def __init__(self):
        self._mu = threading.Lock()
        self._n: dict = {}

    def fail(self) -> int:
        sid = TRACER.current_session()
        with self._mu:
            self._n[sid] = self._n.get(sid, 0) + 1
            return self._n[sid]

    def ok(self) -> None:
        sid = TRACER.current_session()
        with self._mu:
            self._n.pop(sid, None)

    def value(self, session=None) -> int:
        with self._mu:
            return self._n.get(session, 0)

    def reset(self, session=None) -> None:
        with self._mu:
            self._n.pop(session, None)


_MATERIALIZE_FAILS = _FailStreak()


def materialize_failure_streak(session: str | None = None) -> int:
    return _MATERIALIZE_FAILS.value(session)


def reset_materialize_failures(session: str | None = None) -> None:
    _MATERIALIZE_FAILS.reset(session)


class _CompactChunks:
    """Per-chunk CompactOut arrays.

    Entry residency (docs/wave-pipeline.md device-residency stage): each
    chunk's four heavy groups are either host numpy arrays (host-resident
    mode, or after materialization) or LIVE DEVICE arrays — the
    device-resident default, where the wave fetches only decision rows
    and the packed/raw tensors stay (sharded, on a mesh) in device memory
    until a cold read — or the retention budget's LRU spill — pulls them
    across.  Consumers never index the group lists directly; host()
    performs the memoized, exactly-once D2H (contiguous C order — the
    native codec walks raw pointers)."""

    GROUPS = ("packed", "raw8", "raw16", "raw32")

    __slots__ = ("packed", "raw8", "raw16", "raw32", "chunk", "pack_mode",
                 "score_cols", "att", "_mu", "_inflight", "__weakref__")

    def __init__(self, packed, raw8, raw16, raw32, chunk, pack_mode, score_cols):
        self.packed = packed      # list of [C, N]
        self.raw8 = raw8          # list of [C, S8, N] int8
        self.raw16 = raw16        # list of [C, S16, N] int16
        self.raw32 = raw32        # list of [C, S32, N] int32
        self.chunk = chunk
        self.pack_mode = pack_mode
        self.score_cols = score_cols  # per scorer: ("raw8"|"raw16"|"raw32", row)
        # per chunk: host dict of the on-device attribution sums
        # (device-resident waves), or None (host tally fallback)
        self.att: list = []
        self._mu = threading.Lock()
        self._inflight: dict[int, threading.Event] = {}

    # ------------------------------------------------------- residency

    def is_device(self, ci: int) -> bool:
        return not isinstance(self.packed[ci], np.ndarray)

    def device_nbytes(self, ci: int) -> int:
        """Device bytes pinned by chunk ci (0 once materialized)."""
        if not self.is_device(ci):
            return 0
        return sum(int(getattr(getattr(self, g)[ci], "nbytes", 0))
                   for g in self.GROUPS)

    def host(self, group: str, ci: int) -> np.ndarray:
        """Chunk ci's `group` array as host numpy, materializing the
        whole chunk on first access."""
        arrs = getattr(self, group)
        a = arrs[ci]
        if isinstance(a, np.ndarray):
            return a
        self.materialize(ci)
        return arrs[ci]

    def materialize(self, ci: int, spill: bool = False) -> None:
        """D2H of chunk ci's four groups, exactly-once under concurrent
        readers (the fetch runs OUTSIDE the lock; latecomers wait on the
        owner's event).  spill=True is the retention budget's background
        path and feeds the spill counter; everything else is an
        on-demand cold read and feeds the d2h_on_demand taps + the
        d2h_fetch span under the serving read."""
        while True:
            with self._mu:
                if isinstance(self.packed[ci], np.ndarray):
                    return
                ev = self._inflight.get(ci)
                owner = ev is None
                if owner:
                    ev = self._inflight[ci] = threading.Event()
            if owner:
                break
            ev.wait()
        from ..parallel.mesh import gather_to_host

        from contextlib import nullcontext

        try:
            t0 = time.perf_counter()
            fault_point("replay.materialize")
            # the span IS with-managed — it rides a conditional context
            # manager (spans only on-demand reads, not background spills),
            # a form the static balance rule can't see through
            with (nullcontext() if spill
                  else TRACER.span("d2h_fetch", chunk=ci)):  # kss-analyze: allow(unbalanced-span)
                fetched = {g: gather_to_host(getattr(self, g)[ci])
                           for g in self.GROUPS}
            dt = time.perf_counter() - t0
        except BaseException:
            # transient fetch failure: clear the in-flight slot so the
            # next reader retries instead of waiting forever; the streak
            # feeds the engine's structural-degradation check
            _MATERIALIZE_FAILS.fail()
            with self._mu:
                del self._inflight[ci]
            ev.set()
            raise
        _MATERIALIZE_FAILS.ok()
        nbytes = sum(a.nbytes for a in fetched.values())
        with self._mu:
            for g in self.GROUPS:
                getattr(self, g)[ci] = fetched[g]
            del self._inflight[ci]
        ev.set()
        _DEVICE_BUDGET.release(self, ci)
        if spill:
            # labeled by session when the budget attributed the chunk to
            # one (the spill thread enters the owner's session scope):
            # one fat session's spills must be visible as ITS spills
            sid = TRACER.current_session()
            if sid is not None:
                TRACER.inc("device_chunks_spilled_total", session=sid)
            else:
                TRACER.count("device_chunks_spilled_total")
            # black-box spill evidence: which chunk left HBM, how big —
            # a post-mortem for an OOM-adjacent wave needs the spill
            # timeline (utils/blackbox.py)
            from ..utils.blackbox import BLACKBOX

            BLACKBOX.record("budget.spill", chunk=ci, bytes=int(nbytes))
        else:
            TRACER.count("d2h_on_demand_bytes_total", nbytes)
            TRACER.observe("d2h_on_demand_seconds", dt)


class _DeviceResultBudget:
    """HBM retention budget for device-resident replay chunks, across
    waves: KSS_TPU_DEVICE_RESULT_BUDGET_MB caps the total bytes pinned
    by retained chunks; exceeding it spills the least-recently-retained
    chunks to host on ONE background thread (reads remove entries, so
    insertion order IS recency order).  Unset/invalid -> unlimited
    (chunks stay on device until a cold read materializes them or their
    wave is dropped); 0 -> retain nothing, spill as chunks land.
    Entries hold the _CompactChunks weakly — dropping a wave's last
    handle releases its accounting without any explicit call.

    Multi-session serving (server/sessions.py): each retained chunk is
    attributed to the session whose wave produced it (the tracer's
    session scope at retain time; None for direct engine use).  The
    global pool divides EQUALLY among the sessions currently holding
    entries, and enforcement is per-session against that share — a fat
    session spills its own least-recent chunks and never evicts a small
    neighbor's.  With a single bucket (the sessionless pre-session
    behavior) the share IS the whole pool, so nothing changes for
    direct engine use."""

    def __init__(self):
        from collections import deque

        self._mu = threading.Lock()
        # (id(cc), ci) -> [weakref(cc), ci, nbytes, spilling, attempts,
        #                  session]
        self._entries: OrderedDict[tuple[int, int], list] = OrderedDict()
        self._total = 0
        self._pool = None
        # keys whose _CompactChunks died: the weakref finalizer must NOT
        # take _mu (GC can run it on a thread already inside a locked
        # section — a non-reentrant self-deadlock), so it only appends
        # here (deque.append is atomic) and locked entry points prune
        self._dead: deque = deque()

    @staticmethod
    def limit_bytes() -> int | None:
        raw = os.environ.get("KSS_TPU_DEVICE_RESULT_BUDGET_MB")
        if not raw:
            return None
        try:
            mb = int(float(raw))
        except ValueError:
            # fail SAFE on a typo ("512MB"): retain nothing rather than
            # silently lifting the cap the operator meant to set
            return 0
        return None if mb < 0 else mb * (1 << 20)

    def _prune_locked(self) -> None:
        """Drop entries whose _CompactChunks died (queued by the
        finalizer); callers hold _mu."""
        while self._dead:
            ent = self._entries.pop(self._dead.popleft(), None)
            if ent is not None:
                self._total -= ent[2]
        TRACER.gauge("device_chunks_retained", len(self._entries))

    def retain(self, cc: _CompactChunks, ci: int, nbytes: int) -> None:
        key = (id(cc), ci)
        session = TRACER.current_session()

        def _gone(_ref, key=key):
            self._dead.append(key)  # lock-free: pruned on next locked op

        with self._mu:
            # prune BEFORE inserting: a dead chunk's queued key could
            # collide with this one (id() reuse) and drop the fresh entry
            self._prune_locked()
            self._entries[key] = [weakref.ref(cc, _gone), ci, nbytes, False,
                                  0, session]
            self._total += nbytes
            TRACER.gauge("device_chunks_retained", len(self._entries))
        self._enforce()

    def release(self, cc: _CompactChunks, ci: int) -> None:
        with self._mu:
            ent = self._entries.pop((id(cc), ci), None)
            if ent is not None:
                self._total -= ent[2]
            self._prune_locked()

    def retained_chunks(self) -> int:
        with self._mu:
            self._prune_locked()
            return len(self._entries)

    def retained_by_session(self) -> dict:
        """{session (None = sessionless): (chunks, bytes)} currently
        retained — the per-session accounting behind the shares
        (tests, /api/v1/sessions)."""
        out: dict = {}
        with self._mu:
            self._prune_locked()
            for ent in self._entries.values():
                c, b = out.get(ent[5], (0, 0))
                out[ent[5]] = (c + 1, b + ent[2])
        return out

    def _enforce(self) -> None:
        limit = self.limit_bytes()
        if limit is None:
            return
        to_spill: list[tuple[_CompactChunks, int, str | None]] = []
        # autopilot HBM rebalancing (control/autopilot.py): per-session
        # share weights in integer milli-units.  The registry is empty
        # (or a session unlisted) at weight 1000, so with no autopilot —
        # or one that failed safe — every bucket computes EXACTLY
        # limit // n, the byte-identical equal-split baseline.
        mweights = CONTROLS.budget_milliweights()
        with self._mu:
            self._prune_locked()
            # weighted split of the global pool across the sessions
            # holding entries: each bucket is enforced against ITS
            # share, in LRU order WITHIN the bucket — a fat session
            # spills its own chunks, never a neighbor's.  One bucket ->
            # share == limit, the pre-session behavior.
            totals: dict = {}
            for ent in self._entries.values():
                totals[ent[5]] = totals.get(ent[5], 0) + ent[2]
            mw = {s: max(mweights.get(s, 1000), 1) for s in totals}
            mw_sum = max(sum(mw.values()), 1)
            over = {s: t - limit * mw[s] // mw_sum
                    for s, t in totals.items()}
            for ent in self._entries.values():
                if over.get(ent[5], 0) <= 0:
                    continue
                if ent[3]:
                    over[ent[5]] -= ent[2]  # already queued for spill
                    continue
                cc = ent[0]()
                if cc is None:
                    continue  # the weakref callback prunes it
                ent[3] = True
                to_spill.append((cc, ent[1], ent[5]))
                over[ent[5]] -= ent[2]
        for cc, ci, session in to_spill:
            self._spill_pool().submit(self._spill_one, cc, ci, session)

    _SPILL_RETRIES = 3

    def _spill_one(self, cc: _CompactChunks, ci: int,
                   session: str | None = None) -> None:
        try:
            # the spill thread adopts the owning session's scope so the
            # spill counter lands as device_chunks_spilled_total{session=}
            with TRACER.session_scope(session):
                fault_point("replay.budget_spill")
                cc.materialize(ci, spill=True)
        except Exception:
            # transient fetch failure: clear the in-flight mark and
            # re-enforce (bounded — after _SPILL_RETRIES the chunk stays
            # pinned until a cold read materializes it, the documented
            # fallback, instead of hot-looping the spill thread)
            retry = False
            with self._mu:
                ent = self._entries.get((id(cc), ci))
                if ent is not None:
                    ent[4] += 1
                    retry = ent[4] < self._SPILL_RETRIES
                    ent[3] = not retry  # give up: never re-queue
            if retry:
                time.sleep(0.05)
                self._enforce()

    def _spill_pool(self):
        with self._mu:
            if self._pool is None:
                from concurrent.futures import ThreadPoolExecutor

                self._pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="d2h-spill")
            return self._pool

    def drain(self) -> None:
        """Block until every queued spill has landed (tests/bench)."""
        pool = self._pool
        if pool is not None:
            pool.submit(lambda: None).result()


_DEVICE_BUDGET = _DeviceResultBudget()


class ReplayResult:
    """Host-side replay results.

    Two storage layouts:
      * compact (the replay() path): first-fail-packed filters + narrow raw
        scores; full per-pod views are reconstructed chunk-at-a-time on
        demand (finalscore via framework/hostnorm.py);
      * full arrays (the engine's host-interleaved path constructs these
        directly from per-pod StepOuts).

    Use the per-pod accessors (codes_of/raw_of/final_of/feasible_of) —
    they avoid materializing [P, .., N] tensors.  The legacy whole-array
    properties exist for tests and small workloads.
    """

    def __init__(self, cw: CompiledWorkload, filter_codes=None, score_raw=None,
                 score_final=None, selected=None, feasible_count=None,
                 prefilter_reject=None, compact: _CompactChunks | None = None):
        self.cw = cw
        self._filter_codes = filter_codes
        self._score_raw = score_raw
        self._score_final = score_final
        self.selected = selected
        self.feasible_count = feasible_count
        self.prefilter_reject = prefilter_reject
        self._compact = compact
        self._recon_ci = -1
        self._recon: dict[str, np.ndarray] | None = None
        import threading

        self._recon_lock = threading.Lock()

    # ------------------------------------------------------------ summary

    @property
    def scheduled(self) -> int:
        return int((self.selected >= 0).sum())

    def selected_node_name(self, i: int) -> str:
        s = int(self.selected[i])
        return self.cw.node_table.names[s] if s >= 0 else ""

    # ------------------------------------------------------------ access

    def codes_of(self, i: int) -> np.ndarray:
        """[F, N] int32 filter codes for pod i (0 == pass)."""
        if self._filter_codes is not None:
            return self._filter_codes[i]
        d = self._chunk_recon(i // self._compact.chunk)
        return d["codes"][i % self._compact.chunk]

    def raw_of(self, i: int) -> np.ndarray:
        """[S, N] raw scores for pod i."""
        if self._score_raw is not None:
            return self._score_raw[i]
        d = self._chunk_recon(i // self._compact.chunk, scores=True)
        return d["raw"][i % self._compact.chunk]

    def final_of(self, i: int) -> np.ndarray:
        """[S, N] finalscore (normalized x weight) for pod i."""
        if self._score_final is not None:
            return self._score_final[i]
        d = self._chunk_recon(i // self._compact.chunk, scores=True)
        return d["final"][i % self._compact.chunk]

    def feasible_of(self, i: int) -> np.ndarray | None:
        """[N] bool plugin-filter feasibility for pod i, or None when only
        full arrays are stored (the caller derives it from codes_of)."""
        if self._compact is None:
            return None
        d = self._chunk_recon(i // self._compact.chunk)
        return d["feasible"][i % self._compact.chunk]

    def _chunk_recon(self, ci: int, scores: bool = False) -> dict[str, np.ndarray]:
        """Reconstruct one chunk's full views; single-slot cache, safe for
        concurrent decoders (store/decode.py decode_all_parallel) — a
        caller evicted mid-read keeps valid references to the old arrays.
        scores=False skips the raw/final assembly (codes-only consumers
        like the preemption fit oracle never pay the normalize mirror)."""
        with self._recon_lock:
            return self._chunk_recon_locked(ci, scores)

    def _chunk_recon_locked(self, ci: int, scores: bool) -> dict[str, np.ndarray]:
        d = self._recon if self._recon_ci == ci else None
        if d is not None and (not scores or "raw" in d):
            return d
        from . import hostnorm
        from .pipeline import PACK_MODES

        cc = self._compact
        if d is None:
            packed = cc.host("packed", ci)
            c, n = packed.shape
            f = len(self.cw.config.filters())
            _, code_bits, ff_bits = PACK_MODES[cc.pack_mode]
            p_int = packed.astype(np.int64)
            code = p_int & ((1 << code_bits) - 1)
            ffp = (p_int >> code_bits) & ((1 << ff_bits) - 1)  # 0 == all pass
            codes = np.zeros((c, f, n), np.int32)
            if f:
                idx = np.clip(ffp - 1, 0, f - 1)[:, None, :]
                np.put_along_axis(codes, idx, np.where(ffp > 0, code, 0)[:, None, :], axis=1)
                # outside the pod's PreFilterResult (pipeline.py
                # pack_filter_codes): what a full StepOut says there
                skipped = ffp > f
                if skipped.any():
                    from .pipeline import NOT_EVALUATED

                    codes[np.broadcast_to(skipped[:, None, :],
                                          codes.shape)] = NOT_EVALUATED
            feasible = ffp == 0
            d = {"codes": codes, "feasible": feasible}
            self._recon_ci, self._recon = ci, d
        if scores:
            c, n = d["feasible"].shape
            if "ignored" not in d:  # scores-only cost; codes path skips it
                d["ignored"] = self._tsp_ignored_chunk(ci, c, n)
            raw = np.empty((c, len(cc.score_cols), n), np.int64)
            static_rows = self.cw.host.get("static_score_rows", {})
            sskip = self.cw.host.get("score_skip", {})
            lo = ci * cc.chunk
            for s, (group, row) in enumerate(cc.score_cols):
                if group == "host":
                    # precompiled row, never transferred; mask skipped pods
                    # to 0 exactly as the device output did
                    src = static_rows[row]
                    hi = min(lo + c, src.shape[0])
                    m = hi - lo
                    raw[:, s, :] = 0
                    if m > 0:
                        skip = np.asarray(sskip[row][lo:hi], bool)
                        raw[:m, s, :] = np.where(skip[:, None], 0, src[lo:hi])
                    continue
                raw[:, s, :] = cc.host(group, ci)[:, row, :]
            d["raw"] = raw
            d["final"] = hostnorm.finalize_chunk(
                self.cw, raw, d["feasible"], d["ignored"], ci * cc.chunk)
        return d

    def _tsp_ignored_chunk(self, ci: int, c: int, n: int) -> np.ndarray:
        """PodTopologySpread's score-ignore mask for chunk ci, recomputed
        from STATIC inputs (a node is ignored when it lacks the topology
        key of any of the pod's scored constraints) — dom_idx and the
        per-pod slots never change during a replay, so this never needs to
        travel from the device."""
        tsp = self.cw.host.get("tsp_ignore")
        if tsp is None:
            return np.zeros((c, n), bool)
        # [K, N] bool a topology key, [P, MC] each slot's key row, [P, MC]
        dom_neg, c_id, is_score = tsp
        lo = ci * self._compact.chunk
        hi = min(lo + c, c_id.shape[0])
        out = np.zeros((c, n), bool)
        for m in range(c_id.shape[1]):
            cid = c_id[lo:hi, m]
            scored = is_score[lo:hi, m] & (cid >= 0)
            if not scored.any():
                continue  # slot unused by this chunk: skip the gather
            rows = dom_neg[np.maximum(cid, 0)]       # [hi-lo, N]
            out[: hi - lo] |= scored[:, None] & rows
        return out

    def _materialize(self) -> None:
        """Fill the whole-array caches in ONE pass over the chunks (the
        reconstruction computes every field anyway)."""
        cc = self._compact
        p = self.cw.n_pods
        n = self.cw.n_nodes
        if cc is None or not cc.packed:
            self._filter_codes = np.zeros((0, len(self.cw.config.filters()), n), np.int32)
            self._score_raw = np.zeros((0, len(self.cw.config.scorers()), n), np.int64)
            self._score_final = np.zeros((0, len(self.cw.config.scorers()), n), np.int64)
            return
        pieces = {"codes": [], "raw": [], "final": []}
        for ci in range(len(cc.packed)):
            d = self._chunk_recon(ci, scores=True)
            for k in pieces:
                pieces[k].append(d[k])
        self._filter_codes = np.concatenate(pieces["codes"], axis=0)[:p]
        self._score_raw = np.concatenate(pieces["raw"], axis=0)[:p]
        self._score_final = np.concatenate(pieces["final"], axis=0)[:p]

    # legacy whole-array views (tests / small workloads); raw/final are
    # int64 on the compact path (the engine's host-interleaved path stores
    # whatever its per-pod StepOuts held — int32)
    @property
    def filter_codes(self) -> np.ndarray:  # [P, F, N]
        if self._filter_codes is None:
            self._materialize()
        return self._filter_codes

    @property
    def score_raw(self) -> np.ndarray:     # [P, S, N]
        if self._score_raw is None:
            self._materialize()
        return self._score_raw

    @property
    def score_final(self) -> np.ndarray:   # [P, S, N]
        if self._score_final is None:
            self._materialize()
        return self._score_final


class ChunkAttribution:
    """Incremental per-chunk work attribution over a compact replay.

    The whole-wave `plugin_attribution` pass costs seconds at fleet
    scale (5.6s at 10k x 5k) and used to run on the wave's critical
    path after the replay drained.  This accumulator computes the same
    tallies one chunk at a time, so the streaming commit worker — idle
    in lazy-decode mode — runs them WHILE the device scans later chunks
    and the wave tail only pays `finish()` (prefilter section + any
    chunk the worker didn't reach).  Single-threaded by contract: the
    worker adds chunks during the wave, the engine calls finish() after
    joining it.  Attribution is observability — any failure marks the
    accumulator broken and finish() returns None, never failing a wave.
    """

    def __init__(self, rr: ReplayResult):
        self.rr = rr
        cw = rr.cw
        self.filters = cw.config.filters()
        self.scorers = cw.config.scorers()
        self.p = cw.n_pods
        self.fskip = cw.host.get("filter_skip", {})
        self.sskip = cw.host.get("score_skip", {})
        self.fskip_mat = (
            np.stack([np.asarray(self.fskip.get(n, np.zeros(self.p)), bool)
                      for n in self.filters])
            if self.filters else None)  # [F, P]
        self.static_rows = cw.host.get("static_score_rows", {})
        self.out = {
            "filter": {n: {"evaluated": 0, "rejects": 0}
                       for n in self.filters},
            "score": {n: {"evaluated": 0, "sum": 0} for n in self.scorers},
            "prefilter": {},
        }
        cc = getattr(rr, "_compact", None)
        cols = cc.score_cols if cc is not None else ()
        # scorer indices by residency of their raw column: device columns
        # fold from the on-device reduction's limb sums, host columns
        # (precompiled static rows, never transferred) tally here
        self._dev_cols = [s for s, (g, _r) in enumerate(cols) if g != "host"]
        self._host_cols = [s for s, (g, _r) in enumerate(cols) if g == "host"]
        self._done: set[int] = set()
        self.broken = False

    def add_chunk(self, ci: int) -> None:
        """Tally compact chunk ci (idempotent; width-tier re-deliveries
        are bit-identical so first-tally wins).  Device-resident chunks
        fold the jit'd per-chunk sums fetched with the decision rows —
        no compact host tensors are touched; chunks without device sums
        (host-resident/eager waves) take the host tally."""
        cc = self.rr._compact
        if self.broken or cc is None or ci in self._done:
            return
        if ci >= len(cc.packed):
            return  # not ingested (defensive; callers pass delivered chunks)
        if not self.filters and not self.scorers:
            self._done.add(ci)
            return  # nothing to tally; never touch the tensors
        self._done.add(ci)
        try:
            att = cc.att[ci] if ci < len(cc.att) else None
            if att is not None:
                self._fold_device(ci, cc, att)
            else:
                self._tally_chunk(ci, cc)
        except Exception:  # noqa: BLE001 — observability must not fail waves
            self.broken = True

    def _fold_device(self, ci: int, cc: _CompactChunks, dev: dict) -> None:
        """Fold one chunk's on-device attribution sums (the decision-row
        fetch's tiny arrays): filter counts are chunk scalars; score
        sums arrive as per-pod int32 row sums (narrow columns) or
        base-2^11 limb triples (wide columns — int32-safe on device
        without x64), recombined exactly into int64 here."""
        lo = ci * cc.chunk
        hi = min(lo + cc.chunk, self.p)
        m = hi - lo
        out = self.out
        for f, name in enumerate(self.filters):
            out["filter"][name]["rejects"] += int(dev["f_rejects"][f])
            out["filter"][name]["evaluated"] += int(dev["f_evaluated"][f])
        if self._dev_cols:
            n = self.rr.cw.n_nodes
            sums = (dev["s_sums"][:m].astype(np.int64).sum(axis=0)
                    if "s_sums" in dev else None)
            limbs = (dev["s_limbs"][:m].astype(np.int64).sum(axis=0)
                     if "s_limbs" in dev else None)
            qn = qw = 0
            for q, s in enumerate(self._dev_cols):
                name = self.scorers[s]
                out["score"][name]["evaluated"] += int(dev["s_evaluated"][q])
                if _col_needs_limbs(cc.score_cols[s][0], n):
                    out["score"][name]["sum"] += (
                        (int(limbs[qw, 2]) << 22)
                        + (int(limbs[qw, 1]) << 11) + int(limbs[qw, 0]))
                    qw += 1
                else:
                    out["score"][name]["sum"] += int(sums[qn])
                    qn += 1
        if self._host_cols:
            # host-resident static score rows never travel: their sums
            # need only the feasibility BITMAP (N/8 bytes per pod),
            # packed on device and fetched with the decision rows
            n = self.rr.cw.n_nodes
            feas = np.unpackbits(dev["feas_packed"][:m], axis=1,
                                 bitorder="little")[:, :n].astype(bool)
            feas_cnt = feas.sum(axis=1)
            fc = self.rr.feasible_count
            scored = (np.asarray(fc[lo:hi]) > 1 if fc is not None
                      else np.zeros(m, bool))
            for s in self._host_cols:
                name = self.scorers[s]
                sk = self.sskip.get(name)
                s_on = (scored if sk is None
                        else scored & ~np.asarray(sk[lo:hi], bool))
                rows = np.flatnonzero(s_on)
                if not rows.size:
                    continue
                arr = np.asarray(self.static_rows[cc.score_cols[s][1]][lo:hi])
                out["score"][name]["evaluated"] += int(feas_cnt[rows].sum())
                out["score"][name]["sum"] += int(np.sum(
                    arr[rows], dtype=np.int64, where=feas[rows]))

    def _tally_chunk(self, ci: int, cc: _CompactChunks) -> None:
        from .pipeline import PACK_MODES

        _, code_bits, _ = PACK_MODES[cc.pack_mode]
        lo = ci * cc.chunk
        hi = min(lo + cc.chunk, self.p)
        m = hi - lo
        ffp = (cc.host("packed", ci)[:m].astype(np.int64) >> code_bits)

        def arr_of(s: int) -> np.ndarray:
            group, row = cc.score_cols[s]
            if group == "host":
                return np.asarray(self.static_rows[row][lo:hi])
            # native-dtype slice view: the sum below accumulates into
            # int64 via dtype=, no whole-column up-conversion copy
            return cc.host(group, ci)[:m, row, :]

        self._tally(lo, hi, ffp, arr_of)

    def _tally(self, lo: int, hi: int, ffp: np.ndarray,
               score_arr_of) -> None:
        """ffp: [m, N] first-fail words (0 == all active filters pass,
        F + 1 == outside the pod's PreFilterResult: no plugin ran);
        score_arr_of(s) -> [m, N] raw column for scorer s (any integer
        dtype; sums accumulate in int64)."""
        out = self.out
        f_count = len(self.filters)
        m = hi - lo
        if f_count:
            # per-pod histogram of first-fail values 0..F+1, one bincount
            flat = (np.arange(m, dtype=np.int64)[:, None] * (f_count + 2)
                    + ffp).ravel()
            counts = np.bincount(flat, minlength=m * (f_count + 2)) \
                .reshape(m, f_count + 2)
            rejects = counts[:, 1:f_count + 1]             # [m, F]
            # plugin f ran on a node iff ffp == 0 or ffp > f:
            # all-pass nodes + nodes whose first fail is at a later index
            suff = np.cumsum(rejects[:, ::-1], axis=1)[:, ::-1]
            ran = counts[:, :1] + suff                     # [m, F]
            for f, name in enumerate(self.filters):
                out["filter"][name]["rejects"] += int(rejects[:, f].sum())
                col = ran[:, f]
                skips = self.fskip_mat[f, lo:hi]
                if skips.any():
                    col = np.where(skips, 0, col)
                out["filter"][name]["evaluated"] += int(col.sum())
        if self.scorers:
            feas = ffp == 0                                # [m, N]
            feas_cnt = feas.sum(axis=1)
            fc = self.rr.feasible_count
            scored = (np.asarray(fc[lo:hi]) > 1 if fc is not None
                      else np.zeros(m, bool))
            if not scored.any():
                return
            for s, name in enumerate(self.scorers):
                sk = self.sskip.get(name)
                s_on = (scored if sk is None
                        else scored & ~np.asarray(sk[lo:hi], bool))
                rows = np.flatnonzero(s_on)
                if not rows.size:
                    continue
                arr = score_arr_of(s)
                out["score"][name]["evaluated"] += int(feas_cnt[rows].sum())
                # masked sum without materializing an int64 product array
                out["score"][name]["sum"] += int(np.sum(
                    arr[rows], dtype=np.int64, where=feas[rows]))

    def _prefilter(self) -> None:
        rr = self.rr
        cw = rr.cw
        static = cw.host.get("prefilter_reject", {})
        dyn = (np.asarray(rr.prefilter_reject)
               if rr.prefilter_reject is not None
               else np.zeros(self.p, np.int64))
        for name in cw.config.prefilters():
            skips = self.fskip.get(name)
            evaluated = self.p - (
                int(np.count_nonzero(np.asarray(skips, bool)))
                if skips is not None else 0)
            screened = 0
            msgs = static.get(name)
            if msgs is not None:
                screened += sum(1 for msg in msgs if msg is not None)
            if name == "VolumeRestrictions":
                screened += int(np.count_nonzero(
                    np.asarray(dyn, np.int64) & 1))
            self.out["prefilter"][name] = {"evaluated": evaluated,
                                           "screened": screened}

    def finish(self) -> dict | None:
        """Complete the attribution: tally whatever chunks the worker
        didn't reach, add the prefilter section. None when broken."""
        cc = self.rr._compact
        if cc is not None:
            for ci in range(len(cc.packed)):
                self.add_chunk(ci)
        if self.broken:
            return None
        self._prefilter()
        return self.out


def filter_rejected_rows(rr: ReplayResult, lo: int, hi: int) -> np.ndarray:
    """[hi-lo] int64: the nodes a Filter plugin refused, for each of pods
    lo..hi — the nodes Filter ran on (all of them, or the pod's
    PreFilterResult) less feasible_count, and 0 for a pod whose cycle a
    PreFilter reject ended before any Filter ran.  Both are decision rows
    the wave has already fetched: the engine counts
    filter_rejected_nodes_total from this at commit without a device read."""
    feasible = np.asarray(rr.feasible_count[lo:hi], dtype=np.int64)
    ran = np.asarray(rr.prefilter_reject[lo:hi]) == 0
    return np.where(ran, considered_rows(rr.cw, lo, hi) - feasible, 0)


def considered_rows(cw: CompiledWorkload, lo: int, hi: int) -> np.ndarray:
    """[hi-lo] int64: how many nodes Filter runs on for each of pods
    lo..hi — the pod's PreFilterResult (state/compile.py
    _collect_prefilter_results), every node where it has none."""
    counts = cw.host.get("considered_count")
    if counts is None:
        return np.full(hi - lo, cw.n_nodes, dtype=np.int64)
    return counts[lo:hi]


def count_narrowed(cw: CompiledWorkload, rows) -> None:
    """prefilter_narrowed_pods_total / prefilter_considered_nodes_total
    for the pods `rows` (a slice or a list of rows of cw) as their cycle
    is decided: the pods a PreFilterResult narrowed, and the nodes their
    Filter phase ran on (0 and 0 where no pod is narrowed: a series that
    reads 0 says so)."""
    narrowed = cw.host.get("prefilter_narrowed")
    if narrowed is None:
        pods = nodes = 0
    else:
        narrowed = narrowed[rows]
        pods = int(narrowed.sum())
        nodes = int(cw.host["considered_count"][rows][narrowed].sum())
    TRACER.count("prefilter_narrowed_pods_total", pods)
    TRACER.count("prefilter_considered_nodes_total", nodes)


def plugin_attribution(rr: ReplayResult) -> dict | None:
    """Per-plugin work attribution reconstructed from the replay tensors
    a wave already holds — no extra device work, no annotation-path
    reads (the single-slot recon cache the decoders share is never
    touched; the compact arrays are read directly).

    Returns
      {"filter":    {name: {"evaluated": pods x nodes the plugin ran on,
                            "rejects": nodes it first-failed}},
       "score":     {name: {"evaluated": pods x feasible nodes scored,
                            "sum": raw score sum over those}},
       "prefilter": {name: {"evaluated": pods screened (not skipped),
                            "screened": pods it rejected pre-wave}}}
    or None when the result is empty or holds no compact chunks (the
    host loop's per-pod full-array results: its plugins record real wall
    time instead).

    Semantics mirror the framework: a filter plugin "ran" on (pod, node)
    when no earlier active plugin failed there (stop-at-first-fail);
    scoring only happens for pods with >1 feasible node; skipped
    (PreFilter-skip) plugins attribute nothing.  Fused device execution
    has no per-plugin wall clock — these WORK units are the per-plugin
    truth, and what the engine's apportioned plugin_execution histogram
    is derived from (docs/metrics.md).  The compact path delegates to
    ChunkAttribution (the streaming committer runs it chunk-at-a-time
    during the wave; this whole-result entry serves everything else)."""
    cc = rr._compact
    if rr.cw.n_pods == 0 or cc is None or not cc.packed:
        return None
    return ChunkAttribution(rr).finish()


def _cut_rows(xs, lo, m, pad_to: int):
    """Traced: rows lo:lo + m of every leaf of `xs` as pad_to rows, the
    rest zeros, and the flag of the rest."""
    def cut(a):
        rows = jnp.take(a, lo + jnp.arange(pad_to), axis=0, mode="clip")
        keep = (jnp.arange(pad_to) < m).reshape((-1,) + (1,) * (a.ndim - 1))
        return jnp.where(keep, rows, jnp.zeros((), a.dtype))

    out = jax.tree.map(cut, xs)
    out["is_pad"] = jnp.arange(pad_to) >= m
    return out


# one executable a tree of shapes and pad_to (a bucket): lo and the count
# are arguments, so a chunk that starts anywhere in the pass compiles
# nothing
_cut_rows_jit = jax.jit(_cut_rows, static_argnums=(3,))


def _slice_xs(xs: dict[str, Any], lo: int, hi: int, pad_to: int) -> dict[str, Any]:
    """Rows lo:hi of every leaf of `xs` as pad_to rows, the rows past
    hi - lo zeros and flagged in `is_pad` (they never bind): ONE jitted
    dispatch, for the routes that hold xs as leaves (a mesh, a pass of
    many chunks)."""
    TRACER.count("pass_device_dispatches_total")
    return _cut_rows_jit(xs, np.int32(lo), np.int32(hi - lo), pad_to)


# jitted scans shared across CompiledWorkload instances — and across
# SESSIONS (server/sessions.py): the registry is process-level BY DESIGN,
# so N isolated simulations serving the same workload shape pay the
# ~0.95s XLA compile once and every other session's first wave reuses the
# executable.  jax.jit keys on function identity, so a per-workload
# build_step closure would retrace and recompile on every
# compile_workload() (first TPU compile is tens of seconds) — even though
# successive scheduler waves, and preemption's dry-run hypotheses,
# produce workloads with byte-identical statics and shapes.  The key
# (_workload_scan_key) therefore hashes the closure statics' CONTENT (the
# step closure bakes them in as constants) plus the path, shape and dtype
# of every leaf of xs, carry and the ARGUMENT statics (state/compile.py
# ARG_STATICS: the volume family's, which the scan takes as arguments, so
# that a PV created between two passes is no new executable) and the
# plugin-set signature; any mismatch falls through to a fresh compile.
# It reads those off the leaves, or off the packed layout that stands for
# them, and is the same either way.  The statics fingerprint is computed
# once per CompiledWorkload (cached in cw.host), not on every replay()
# call.
#
# Two executables are built over one key, both from the same build_step on
# the same leaves.  What a chunk's call takes and returns:
#   _scan_for         (carry, xs_chunk, arg_statics) -> (carry, out): the
#                     workload's trees as device arrays, the chunk cut and
#                     padded by the caller (_slice_xs), the carry donated.
#                     For a mesh (the leaves are sharded one by one) and
#                     a pass of many chunks
#   _packed_scan_for  (the pass's packed buffers, the leaves that are
#                     device arrays already) -> (the four heavy tensors,
#                     the decision row): the sequential scan of a pass of
#                     ONE chunk (every served pass) of a workload that
#                     compile_workload made.  The unpack, the carry, the
#                     attribution reduction and the row's packing happen
#                     inside it; nothing is donated; its key adds the
#                     layout


class CompileQuarantined(RuntimeError):
    """A scan-cache key whose build failed repeatedly is quarantined:
    callers get this immediately (fail-fast) instead of paying another
    multi-second doomed compile — one bad workload shape must not
    poison every session sharing the process with repeated build storms
    (docs/fault-injection.md).  The quarantine expires after
    KSS_TPU_COMPILE_QUARANTINE_S; a successful rebuild clears it."""

    seam = "compile.build"

    def __init__(self, message: str):
        super().__init__(message)


def _compile_quarantine_ttl() -> float:
    from ..utils.env import env_float

    return env_float("KSS_TPU_COMPILE_QUARANTINE_S", 300.0)


class _FirstCallTimed:
    """A cached jitted callable that reports, on its FIRST call, what
    JAX spent compiling it.  jax.jit builds lazily: the registry's
    builder returns at once and the real tracing, lowering and XLA
    compile happen inside the first call, on the caller's thread —
    where utils/hostevents.py hears JAX's own compile events.  Later
    calls cost one attribute test."""

    __slots__ = ("fn", "key_id", "_timed")

    def __init__(self, fn, key_id: str):
        self.fn = fn
        self.key_id = key_id
        self._timed = False

    def __call__(self, *args, **kwargs):
        if self._timed:
            return self.fn(*args, **kwargs)
        self._timed = True
        from ..utils.blackbox import BLACKBOX
        from ..utils.hostevents import thread_compile_seconds

        before = thread_compile_seconds()
        result = "error"
        try:
            out = self.fn(*args, **kwargs)
            result = "ok"
            return out
        finally:
            dt = thread_compile_seconds() - before
            TRACER.observe("scan_compile_seconds", dt, key=self.key_id,
                           result=result)
            BLACKBOX.record("compile.build", key=self.key_id,
                            seconds=round(dt, 3), result=result)

    def __getattr__(self, name):
        return getattr(self.fn, name)


class _ScanCacheRegistry:
    """Process-level LRU registry of jitted scan callables, keyed by
    workload shape (_workload_scan_key).  Concurrent sessions' waves hit
    it from different threads, so — unlike the bare module dict it grew
    from — lookups are locked, and a miss REGISTERS an in-flight build
    before releasing the lock: a second session racing the same key
    waits for the winner's callable instead of double-compiling (the
    compile-once guarantee /api/v1/sessions reports as its
    (K-1)/K hit rate).  LRU semantics unchanged: pop-and-reinsert on
    hit, so two shapes alternating at capacity never evict each other's
    still-hot compiles.

    Build-failure containment: the first failure is treated as
    transient (waiters retry and become builders — a wave-protocol
    retry rebuilds); _QUARANTINE_AFTER consecutive failures of the SAME
    key quarantine it for _compile_quarantine_ttl() seconds, during
    which lookups raise CompileQuarantined without touching the
    compiler.  Other keys — other sessions' shapes — are unaffected,
    and a successful build clears the key's failure history."""

    _QUARANTINE_AFTER = 2

    def __init__(self, max_entries: int = 64):
        self.max_entries = max_entries
        self._mu = threading.Lock()
        self._entries: OrderedDict = OrderedDict()
        self._building: dict = {}   # key -> threading.Event
        # key -> [consecutive fails, quarantined-until monotonic, last err]
        self._failed: dict = {}
        self.hits = 0
        self.misses = 0

    def stats(self) -> dict:
        with self._mu:
            total = self.hits + self.misses
            return {"entries": len(self._entries), "hits": self.hits,
                    "misses": self.misses,
                    "quarantined": sum(
                        1 for f in self._failed.values()
                        if f[1] > time.monotonic()),
                    "hit_rate": round(self.hits / total, 4) if total else None}

    def get_or_build(self, key, builder):
        while True:
            with self._mu:
                scan_jit = self._entries.pop(key, None)
                if scan_jit is not None:
                    self._entries[key] = scan_jit  # re-insert: most recent
                    self.hits += 1
                    TRACER.inc("scan_compile_cache_total", result="hit")
                    return scan_jit
                bad = self._failed.get(key)
                if bad is not None and bad[1] > time.monotonic():
                    TRACER.inc("scan_compile_cache_total",
                               result="quarantined")
                    quarantined_err = bad[2]
                    break
                ev = self._building.get(key)
                if ev is None:
                    ev = self._building[key] = threading.Event()
                    quarantined_err = None
                    self.misses += 1
                    TRACER.inc("scan_compile_cache_total", result="miss")
                    break
            # another thread is building this key: its executable is
            # seconds away — waiting IS the cross-session compile shave
            ev.wait()
        if quarantined_err is not None:
            raise CompileQuarantined(
                "scan compile for this workload shape is quarantined "
                f"after {self._QUARANTINE_AFTER} consecutive build "
                f"failures (last: {quarantined_err}); other shapes are "
                "unaffected")
        from ..utils import hostevents
        from ..utils.blackbox import BLACKBOX

        # JAX's compile events must be heard before the first call
        # compiles (idempotent; the server also installs at start)
        hostevents.install()
        # short stable id for the shape key: a per-key label for the
        # compile-seconds histogram without exploding cardinality (the
        # cache itself holds at most max_entries keys)
        key_id = f"{zlib.crc32(repr(key).encode()) & 0xffffffff:08x}"
        try:
            # the jax.jit wrapper builds OUTSIDE the lock (kss-analyze
            # device-under-lock; jit is lazy but build_step touches jnp)
            fault_point("compile.build")
            scan_jit = builder()
        except BaseException as e:
            # the build failed before anything could compile: no seconds
            # to report, the series still counts the failure
            TRACER.observe("scan_compile_seconds", 0.0, key=key_id,
                           result="error")
            quarantined = False
            with self._mu:
                del self._building[key]
                bad = self._failed.get(key) or [0, 0.0, ""]
                bad[0] += 1
                bad[2] = f"{type(e).__name__}: {e}"[:200]
                if bad[0] >= self._QUARANTINE_AFTER:
                    bad[1] = time.monotonic() + _compile_quarantine_ttl()
                    TRACER.inc("wave_faults_total", seam="compile.build",
                               action="quarantined")
                    quarantined = True
                fails = bad[0]
                self._failed[key] = bad
            BLACKBOX.record("compile.fail", key=key_id, fails=fails,
                            quarantined=quarantined,
                            error=f"{type(e).__name__}: {e}"[:200])
            ev.set()    # waiters retry; they'll become builders
            raise
        if callable(scan_jit):
            # the compile itself happens in the first call: time it there
            scan_jit = _FirstCallTimed(scan_jit, key_id)
        with self._mu:
            while len(self._entries) >= self.max_entries:
                self._entries.popitem(last=False)
            self._entries[key] = scan_jit
            self._failed.pop(key, None)
            del self._building[key]
            TRACER.gauge("scan_compile_cache_entries", len(self._entries))
        ev.set()
        return scan_jit


_SCAN_CACHE = _ScanCacheRegistry()


def scan_cache_stats() -> dict:
    """Process-level compile-cache stats ({entries, hits, misses,
    hit_rate}) — the /api/v1/sessions surface reports these."""
    return _SCAN_CACHE.stats()


def _statics_fingerprint(cw: CompiledWorkload) -> str:
    """The closure statics' digest for the scan-cache key.
    compile_workload takes it from the host bytes before it uploads them;
    a workload made without it (hand-built in tests and tools) has its
    closure statics fetched back and hashed here, once."""
    fp = cw.host.get("_statics_fp")
    TRACER.inc("scan_key_statics_total",
               source="host" if fp is not None else "fetched")
    if fp is None:
        fp = cw.host["_statics_fp"] = statics_digest(cw.closure_statics())
    return fp


def _leaf_sig(leaf) -> tuple:
    # an array's own metadata (or a Packed's, which stands for one):
    # np.asarray(leaf) would fetch a device array (and gather a sharded
    # one) to learn what it already says
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        return tuple(leaf.shape), str(leaf.dtype)
    a = np.asarray(leaf)
    return a.shape, str(a.dtype)


def _workload_scan_key(cw: CompiledWorkload, chunk: int, mesh=None):
    mesh_sig = tuple(mesh.shape.items()) if mesh is not None else None
    # the packed layout says what the leaves would: asking for them would
    # unpack a workload that the sequential scan takes packed
    trees = (cw.packed.tree[:3] if cw.packed is not None
             else (cw.xs, cw.init_carry, cw.arg_statics()))
    shapes = tuple(
        (str(path), *_leaf_sig(leaf))
        for tree in trees
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    )
    cfg_sig = (cw.config.signature(), tuple(cw.schema.columns))
    return (_statics_fingerprint(cw), mesh_sig, shapes, cfg_sig, chunk)


@jax.jit
def _copy_carry(carry):
    """A fresh device copy of a carry tree in ONE dispatch (a jnp.array
    per leaf is 18 of them under the default profile), for the routes
    that hold the carry as leaves; the packed route cuts its carry out of
    the pass's buffers, which nothing donates.  jnp.copy puts a
    real copy into the jaxpr: a jitted identity would forward its input
    buffers, and the scan's donation would then invalidate the
    workload's own init_carry.  jax.jit caches by the leaves' shapes,
    dtypes and shardings, so it compiles once per carry shape."""
    return jax.tree.map(jnp.copy, carry)


class _SlimWorkload:
    """Just the fields build_step bakes into the jitted scan — cached
    closures must not pin per-pod xs tensors or pod manifests.  Its
    statics are the closure statics alone: the argument statics
    (state/compile.py ARG_STATICS) are not the key's by content, so a
    cached executable must not hold one; `with_args` is the view a traced
    scan builds its step from."""

    __slots__ = ("config", "statics", "n_nodes", "schema")

    def __init__(self, cw: CompiledWorkload):
        self.config = cw.config
        self.statics = cw.closure_statics()
        self.n_nodes = cw.n_nodes
        self.schema = cw.schema

    def with_args(self, arg_statics: dict[str, Any]) -> "_SlimWorkload":
        view = copy.copy(self)
        view.statics = {**self.statics, **arg_statics}
        return view


def _scan_for(cw: CompiledWorkload, chunk: int, unroll: int = 1, mesh=None,
              pack_mode: str = "p16", score_dtypes: tuple = (),
              wide: bool = False):
    key = (*_workload_scan_key(cw, chunk, mesh), unroll, "compact", pack_mode,
           score_dtypes, wide)

    def build():
        slim = _SlimWorkload(cw)

        def scan_chunk(carry, xs_chunk, arg_statics):
            step = build_step(slim.with_args(arg_statics), out_mode="compact",
                              pack_mode=pack_mode, score_dtypes=score_dtypes,
                              wide_raw=wide)
            return jax.lax.scan(step, carry, xs_chunk, unroll=unroll)

        return jax.jit(scan_chunk, donate_argnums=(0,))

    return _SCAN_CACHE.get_or_build(key, build)


def _packed_scan_for(cw: CompiledWorkload, unroll: int, pack_mode: str,
                     score_dtypes: tuple, wide, att_plan: tuple | None):
    """-> (the cached executable of cw's sequential scan as ONE chunk over
    cw.packed, its arguments).

    scan_pass(bufs, rest) -> (packed_filter, raw8, raw16, raw32, row)
      bufs    the pass's upload, a device buffer a dtype
      rest    the leaves that never were in it (a carried session's
              resident arrays, state/resident.py)
      -> the chunk's four heavy tensors, which stay on the device for the
      cold read, and ONE int32 buffer that holds everything the pass reads
      in-wave: the decision fields and every leaf of the attribution sums
      (att_plan, _att_plan; or none), end to end (_pack_row).  Five output
      buffers where the fields apiece were thirteen or fourteen: an output
      buffer costs the host what a dispatch does, and a fetch of one a
      round trip of ~0.4 ms whatever it carries.  The carry is cut out of
      bufs and not handed back (no chunk follows); nothing is donated, so
      a second replay and the width-tier rerun start from the same carry.

    The executable's `row_layout` is what _cut_row cuts the fetched row
    by.  It is static for an executable (chunk, n, att_plan and
    CompactOut's dtypes are all in the key) and is worked out when the
    executable is built, from the scan's abstract outputs."""
    packed = cw.packed
    leaves, treedef = jax.tree.flatten(packed.tree)
    # a leaf's place in the layout; None for one of `rest`
    places = tuple(leaf.k if isinstance(leaf, Packed) else None
                   for leaf in leaves)
    layout = packed.layout
    chunk = cw.pod_axis
    key = (*_workload_scan_key(cw, chunk), unroll, "packed", pack_mode,
           score_dtypes, wide, layout, places, att_plan)
    rest = [leaf for leaf in leaves if not isinstance(leaf, Packed)]

    def build():
        # nothing of `packed` but what is static: a cached closure must
        # not pin a pass's buffers
        slim = _SlimWorkload(cw)
        n = cw.n_nodes
        picks = tuple(k for k in places if k is not None)

        def scan_fields(bufs, rest):
            cut, own = iter(unpack_leaves(layout, picks, bufs)), iter(rest)
            xs, carry, arg_statics, (fskip, sskip) = jax.tree.unflatten(
                treedef, [next(own) if k is None else next(cut)
                          for k in places])
            # the real pods of the bucket: all of them where the bucket
            # is too small to hold a pad row and the upload has no flag
            m = np.int32(chunk)
            if "is_pad" in xs:
                m = chunk - jnp.sum(xs["is_pad"], dtype=jnp.int32)
            else:
                xs["is_pad"] = jnp.zeros(chunk, jnp.bool_)
            step = build_step(slim.with_args(arg_statics), out_mode="compact",
                              pack_mode=pack_mode, score_dtypes=score_dtypes,
                              wide_raw=wide)
            _, out = jax.lax.scan(step, carry, xs, unroll=unroll)
            att = None
            if att_plan is not None:
                att = _build_att_fn(chunk, n, *att_plan)(
                    out.packed_filter, out.raw8, out.raw16, out.raw32,
                    out.feasible_count, fskip, sskip, m)
            return out, att

        def scan_pass(bufs, rest):
            out, att = scan_fields(bufs, rest)
            return (out.packed_filter, out.raw8, out.raw16, out.raw32,
                    _pack_row(out, att))

        scan = jax.jit(scan_pass)
        # one more trace of the step (no compile, no device), once a key
        scan.row_layout = _row_layout(*jax.eval_shape(
            scan_fields, *jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                (packed.bufs, rest))))
        return scan

    return _SCAN_CACHE.get_or_build(key, build), (packed.bufs, rest)


_DECISION_FIELDS = ("selected", "feasible_count", "prefilter_reject",
                    "raw_overflow")
_HEAVY_FIELDS = ("packed_filter", "raw8", "raw16", "raw32")


class _PackedOut(NamedTuple):
    """What a fetch is handed for a chunk of the packed route: the four
    heavy tensors as CompactOut names them (device arrays; ingest retains
    them for the cold read) and the decision row with its layout."""

    packed_filter: Any
    raw8: Any
    raw16: Any
    raw32: Any
    row: Any             # [words] int32, on the device
    row_layout: tuple    # _row_layout


def _row_fields(out, att) -> list:
    """(key, value) for every field of a decision row in row order: the
    decision fields, then the attribution sums' leaves as `att.<leaf>`,
    sorted (the dict a trace builds and the one jax.eval_shape hands back
    differ in order)."""
    return ([(f, getattr(out, f)) for f in _DECISION_FIELDS]
            + [(f"att.{k}", v) for k, v in sorted((att or {}).items())])


def _row_layout(out, att) -> tuple:
    """(key, dtype name, shape) for every field of a decision row, from
    arrays, tracers or jax.eval_shape's structs."""
    return tuple((k, str(np.dtype(v.dtype)), tuple(v.shape))
                 for k, v in _row_fields(out, att))


def _pack_row(out, att):
    """Traced: the fields of _row_fields(out, att) end to end in one
    int32 buffer, nothing rounded or dropped: an int32 field is its own
    words, an int64 one two words an element, a narrower one
    (raw_overflow's bools as 0/1 bytes, feas_packed's uint8s) is padded
    to whole words and its bytes bitcast four to a word; lowest byte and
    low word first, which is how _cut_row's ndarray.view reads them
    back."""
    words = []
    for _key, a in _row_fields(out, att):
        a = a.reshape(-1)
        if a.dtype == jnp.bool_:
            a = a.astype(jnp.uint8)
        per = 4 // a.dtype.itemsize
        if per > 1:
            a = jnp.pad(a, (0, -a.size % per)).reshape(-1, per)
        # a wider field (the filter counts are int64 under x64) comes
        # out as [size, 2], low word first
        words.append(jax.lax.bitcast_convert_type(a, jnp.int32).reshape(-1))
    with jax.named_scope("kss_decision_row"):
        return jnp.concatenate(words)


def _cut_row(row: np.ndarray, row_layout: tuple) -> dict[str, Any]:
    """A fetched decision row as _fetch_decisions hands a chunk on:
    every field a view of the row under its own key, dtype and shape, the
    attribution sums' leaves under "att" where the row holds any."""
    raw = np.ascontiguousarray(row).view(np.uint8)
    c: dict[str, Any] = {}
    at = 0
    for key, dtype, shape in row_layout:
        nbytes = math.prod(shape) * np.dtype(dtype).itemsize
        a = raw[at:at + nbytes].view(dtype).reshape(shape)
        at += -(-nbytes // 4) * 4
        if key.startswith("att."):
            c.setdefault("att", {})[key[4:]] = a
        else:
            c[key] = a
    return c


def _fetch_chunk(out) -> dict[str, np.ndarray]:
    """Blocking D2H of one chunk's FULL outputs — the host-resident mode
    (runs on a fetch thread so the transfer overlaps later chunks'
    device compute — the copy starts the moment the chunk's results
    exist, and np.asarray releases the GIL while it waits on the
    transfer).  ascontiguousarray: on TPU the fetched array keeps the
    DEVICE layout (e.g. strides (1,10,5) for a [C,S,N] int8), and the
    native codec walks raw pointers assuming C order — a strided buffer
    silently decodes neighboring pods' values."""
    fault_point("replay.decision_fetch")
    if isinstance(out, _PackedOut):
        c = {f: np.ascontiguousarray(np.asarray(getattr(out, f)))
             for f in _HEAVY_FIELDS}
        row = np.asarray(out.row)
        c["_d2h_bytes"] = sum(a.nbytes for a in c.values()) + row.nbytes
        c.update(_cut_row(row, out.row_layout))
        return c
    c = {f: np.ascontiguousarray(np.asarray(getattr(out, f)))
         for f in out._fields}
    c["_d2h_bytes"] = sum(a.nbytes for a in c.values())
    return c


def _fetch_decisions(out, att) -> dict[str, np.ndarray]:
    """Decision-row-only D2H for a device-resident chunk: the per-pod
    scalars commit/bind/gang quorum actually consume — O(chunk) bytes
    plus the tiny on-device attribution sums — instead of the
    O(chunk x plugins x nodes) compact tensors, which stay live on
    device until a cold read materializes them (docs/wave-pipeline.md
    device-residency stage).

    Every device array pulled here is a blocking round trip of its own
    (~0.4 ms on the chip's host whatever it carries), counted in
    decision_fetch_transfers_total: ONE where the packed scan laid the
    fields into a row (`out` is a _PackedOut; its attribution sums are in
    the row and `att` is None), a field apiece over leaves."""
    fault_point("replay.decision_fetch")
    if isinstance(out, _PackedOut):
        row = np.asarray(out.row)
        TRACER.count("decision_fetch_transfers_total")
        c = _cut_row(row, out.row_layout)
        c["_d2h_bytes"] = row.nbytes
        return c
    c = {f: np.ascontiguousarray(np.asarray(getattr(out, f)))
         for f in _DECISION_FIELDS}
    nbytes = sum(a.nbytes for a in c.values())
    transfers = len(c)
    if att is not None:
        att_host = {k: np.asarray(v) for k, v in att.items()}
        nbytes += sum(a.nbytes for a in att_host.values())
        transfers += len(att_host)
        c["att"] = att_host
    TRACER.count("decision_fetch_transfers_total", transfers)
    c["_d2h_bytes"] = nbytes
    return c


# jit'd per-chunk attribution reductions, shared across workloads with
# the same static layout (the function retraces per input shape anyway,
# so only closure statics key the cache)
_ATT_CACHE: dict = {}
_ATT_CACHE_MAX = 32


def _att_fn_for(chunk: int, n: int, code_bits: int, n_filters: int,
                dev_groups: tuple, want_feas_pack: bool):
    key = (chunk, n, code_bits, n_filters, dev_groups, want_feas_pack)
    fn = _ATT_CACHE.pop(key, None)
    if fn is None:
        fn = jax.jit(_build_att_fn(chunk, n, code_bits, n_filters,
                                   dev_groups, want_feas_pack))
        while len(_ATT_CACHE) >= _ATT_CACHE_MAX:
            _ATT_CACHE.pop(next(iter(_ATT_CACHE)))
    _ATT_CACHE[key] = fn
    return fn


def _col_needs_limbs(group: str, n: int) -> bool:
    """Whether a per-pod masked row sum of this raw group can overflow
    int32 at n nodes — the STATIC rule deciding single-int32 vs
    base-2^11 limb-triple travel for a score column's device sums
    (shared by the reduction builder and ChunkAttribution's fold)."""
    bound = {"raw8": 128, "raw16": 1 << 15}.get(group)
    return bound is None or n * bound >= (1 << 31)


def _build_att_fn(chunk: int, n: int, code_bits: int, n_filters: int,
                  dev_groups: tuple, want_feas_pack: bool):
    """The per-chunk on-device attribution reduction: per-filter
    reject/evaluated counts and per-scorer masked sums straight from the
    chunk's device tensors, returned with the decision rows so the host
    never needs the heavy arrays for attribution.

    Sums stay exact without x64: per-chunk counts are < 2^31 by
    construction (chunk x nodes); narrow (int8/int16) raw columns ship
    plain per-pod int32 row sums (provably no overflow at this n —
    _col_needs_limbs), and wide columns travel as PER-POD base-2^11
    limb triples (|limb sum| <= nodes x 2^11 per pod), which
    ChunkAttribution._fold_device recombines into int64.  Cost
    discipline: ONE F x chunk x nodes pass for the filter counts (the
    per-pod first-fail histogram; `ran` derives from its suffix sums,
    not a second pass) and ~two chunk x nodes passes per score column.
    All reductions are over the node axis, so on a mesh GSPMD lowers
    them to the same ICI all-reduces the scan's selection already pays."""
    n8 = ((n + 7) // 8) * 8

    def fn(packed, raw8, raw16, raw32, fc, fskip_c, sskip_c, m):
        raws = {"raw8": raw8, "raw16": raw16, "raw32": raw32}
        valid = jnp.arange(chunk, dtype=jnp.int32) < m          # [C]
        ffp = packed.astype(jnp.int32) >> code_bits             # [C, N]
        feas = (ffp == 0) & valid[:, None]                      # [C, N]
        feas_cnt = jnp.sum(feas, axis=1, dtype=jnp.int32)       # [C]
        out = {}
        if n_filters:
            # per-pod first-fail histogram, one F x C x N pass: rejects
            # per (filter, pod); "plugin f ran on a node" = all-pass or
            # first fail at a later index = feas_cnt + suffix sums of
            # the histogram (host-tally semantics); per-pod
            # PreFilter-skips zero the pod's contribution
            fidx = jnp.arange(n_filters, dtype=jnp.int32)[:, None, None]
            rej_pp = jnp.sum(ffp[None] == fidx + 1, axis=2,
                             dtype=jnp.int32)                   # [F, C]
            rej_pp = rej_pp * valid[None, :]
            out["f_rejects"] = jnp.sum(rej_pp, axis=1)
            suffix = jnp.cumsum(rej_pp[::-1], axis=0)[::-1]     # [F, C]
            ran_pp = feas_cnt[None, :] + suffix
            out["f_evaluated"] = jnp.sum(
                jnp.where(fskip_c, 0, ran_pp), axis=1)
        if dev_groups:
            scored = (fc > 1) & valid                           # [C]
            evaluated, sums, limbs = [], [], []
            for s, group, row in dev_groups:
                s_on = scored & ~sskip_c[s]
                mask = feas & s_on[:, None]
                xm = jnp.where(mask, raws[group][:, row, :], 0) \
                    .astype(jnp.int32)                          # [C, N]
                if _col_needs_limbs(group, n):
                    limbs.append(jnp.stack([
                        jnp.sum(xm & 0x7FF, axis=1, dtype=jnp.int32),
                        jnp.sum((xm >> 11) & 0x7FF, axis=1,
                                dtype=jnp.int32),
                        jnp.sum(xm >> 22, axis=1, dtype=jnp.int32),
                    ], axis=-1))                                # [C, 3]
                else:
                    sums.append(jnp.sum(xm, axis=1, dtype=jnp.int32))
                evaluated.append(jnp.sum(jnp.where(s_on, feas_cnt, 0),
                                         dtype=jnp.int32))
            out["s_evaluated"] = jnp.stack(evaluated)
            if sums:
                out["s_sums"] = jnp.stack(sums, axis=1)         # [C, Sn]
            if limbs:
                out["s_limbs"] = jnp.stack(limbs, axis=1)       # [C, Sw, 3]
        if want_feas_pack:
            # host-resident score columns need the [C, N] feasibility on
            # host: bit-pack it (N/8 bytes per pod) instead of shipping
            # bools — ChunkAttribution unpacks with bitorder="little"
            pad = jnp.zeros((chunk, n8 - n), dtype=feas.dtype)
            fr = jnp.concatenate([feas, pad], axis=1) \
                .reshape(chunk, n8 // 8, 8).astype(jnp.int32)
            bits = jnp.asarray([1, 2, 4, 8, 16, 32, 64, 128], jnp.int32)
            out["feas_packed"] = jnp.sum(
                fr * bits[None, None, :], axis=-1).astype(jnp.uint8)
        return out

    def attribution_reduction(*args):
        with jax.named_scope("kss_attribution_reduction"):
            return fn(*args)

    return attribution_reduction


def _att_plan(cw: CompiledWorkload, pack_mode: str,
              score_cols: tuple) -> tuple | None:
    """_build_att_fn's arguments after (chunk, n) for this workload, or
    None where the profile has neither a filter nor a scorer."""
    from .pipeline import PACK_MODES

    n_filters = len(cw.config.filters())
    if not (n_filters or cw.config.scorers()):
        return None
    dev_groups = tuple((s, g, r) for s, (g, r) in enumerate(score_cols)
                       if g != "host")
    want_pack = any(g == "host" for g, _r in score_cols)
    return PACK_MODES[pack_mode][1], n_filters, dev_groups, want_pack


class _DeviceAttribution:
    """Per-replay-run context for the on-device attribution reduction
    where the scan holds its workload as leaves (a mesh, a pass of many
    chunks; the packed scan runs the reduction inside its own executable,
    the masks riding in the pass's bool buffer): pads the per-pod
    PreFilter/score skip masks to the chunk grid, puts them on device
    ONCE, and runs the cached jit'd per-chunk sums whose outputs ride the
    decision-row fetch (cc.att)."""

    __slots__ = ("enabled", "chunk", "p", "fskip_dev", "sskip_dev", "_fn")

    def __init__(self, cw: CompiledWorkload, chunk: int, pack_mode: str,
                 score_cols: tuple):
        plan = _att_plan(cw, pack_mode, score_cols)
        self.enabled = plan is not None
        if not self.enabled:
            return
        p = cw.n_pods
        self.p = p
        self.chunk = chunk
        # pad rows read as "skipped": they contribute nothing even
        # before the valid mask cuts them
        fskip, sskip = attribution_skip_masks(
            cw, max(1, -(-p // chunk)) * chunk)
        TRACER.count("pass_device_dispatches_total", 2)
        self.fskip_dev = jnp.asarray(fskip)
        self.sskip_dev = jnp.asarray(sskip)
        self._fn = _att_fn_for(chunk, cw.n_nodes, *plan)

    def run(self, out, lo: int):
        TRACER.count("pass_device_dispatches_total", 3)
        fskip_c = self.fskip_dev[:, lo:lo + self.chunk]
        sskip_c = self.sskip_dev[:, lo:lo + self.chunk]
        m = np.int32(min(lo + self.chunk, self.p) - lo)
        return self._fn(out.packed_filter, out.raw8, out.raw16, out.raw32,
                        out.feasible_count, fskip_c, sskip_c, m)


def _resolve_device_resident(device_resident: bool | None,
                             on_chunk) -> bool:
    """Result-residency mode for one replay: what the caller says (the
    engine passes its wave plan's), and where it says nothing (None),
    device-resident whenever no streaming consumer decodes in-wave
    (on_chunk is None)."""
    if device_resident is None:
        return on_chunk is None
    return bool(device_resident)


def replay(cw: CompiledWorkload, chunk: int = POD_CHUNK,
           unroll: int = 1, filter_only: bool = False,
           mesh=None, on_chunk=None,
           device_resident: bool | None = None) -> ReplayResult:
    """Run the full queue; returns host-side result arrays.

    unroll: lax.scan unroll factor — trades compile time for lower
    per-iteration overhead (the step's ops are tiny [N] vector ops, so
    fixed per-op cost dominates; unrolling lets XLA pipeline iterations).
    filter_only: the caller only consumes filter codes / prefilter rejects
    (preemption's fit oracle) — skips the custom-NormalizeScore guard,
    whose divergence touches scoring alone.
    mesh: a jax.sharding.Mesh with a "nodes" axis — the workload's node
    axis is sharded over it (parallel/mesh.py shard_workload) and GSPMD
    inserts the cross-shard collectives (feasible-count sums, normalize
    max/min, select argmax ride ICI); results are bit-identical to the
    unsharded replay (tests/test_mesh.py parity gate).  The node count
    must divide by the mesh's "nodes" extent.
    on_chunk: optional callback (rr, lo, hi) fired as each chunk's host
    fetch lands, while the device runs later chunks — stream consumers
    (the engine's decode + pipelined commit) overlap host work with
    device compute.  Chunks are delivered in ascending, contiguous
    [lo, hi) order (the engine's commit worker relies on this to
    preserve pod order).  May re-fire from the first chunk if a score
    width tier overflows, so per-pod writes must be idempotent; chunks
    that were already delivered (i.e. passed the overflow check) carry
    bit-identical values on the wider re-run, which is what lets a
    commit consumer keep a watermark and skip re-delivered pods.
    device_resident: keep the heavy compact tensors as live device
    arrays and fetch only per-pod decision rows in-wave (the default
    when no on_chunk consumer decodes in-wave); a cold read performs the
    memoized D2H per chunk.  None = auto.
    """
    device_resident = _resolve_device_resident(device_resident, on_chunk)
    if mesh is not None:
        from ..parallel.mesh import shard_workload

        cw = shard_workload(cw, mesh)
    if not filter_only:
        for name in cw.config.enabled:
            if cw.config.is_custom(name) and getattr(
                    cw.config.custom[name], "has_normalize", False):
                raise ValueError(
                    f"custom plugin {name} has NormalizeScore: the batched "
                    "scan cannot run it — schedule through the engine (it "
                    "routes to the host-interleaved path) or use "
                    "build_phased directly")
    # widening ladder: narrow groups -> int32 -> int64 (a raw overflowing
    # its group dtype triggers the next tier; int64 is the upstream score
    # type and cannot overflow).  A compile-time-proven beyond-int32 bound
    # skips straight to i64.
    tiers = (("i64",) if "i64" in cw.host.get("score_dtypes", ())
             else (None, "i32", "i64"))
    for wide in tiers:
        result = _replay_run(cw, chunk, unroll, mesh, wide=wide,
                             on_chunk=on_chunk,
                             device_resident=device_resident)
        if result is not None:
            return result
        TRACER.count("replay_width_retries_total")
    raise AssertionError("unreachable: i64 replay cannot overflow")


def _compact_plan(cw: CompiledWorkload, wide: str | None):
    """(pack_mode, score_dtypes, score_cols) for this workload."""
    from .pipeline import choose_pack_mode

    pack_mode = choose_pack_mode(
        cw.host.get("max_filter_code", 1 << 62),
        len(cw.config.filters()),
        narrowed="prefilter_json" in cw.host,
    )
    score_dtypes = cw.host.get(
        "score_dtypes", tuple("i16" for _ in cw.config.scorers()))
    counts = {"i8": 0, "i16": 0, "i32": 0}
    cols = []
    for name, g in zip(cw.config.scorers(), score_dtypes):
        if g == "host":
            # precompiled host-resident raw (cw.host["static_score_rows"]):
            # reconstructed from the host copy, never transferred
            cols.append(("host", name))
            continue
        g = "i32" if wide else g  # widened runs pool every scorer in raw32
        cols.append(({"i8": "raw8", "i16": "raw16", "i32": "raw32"}[g], counts[g]))
        counts[g] += 1
    return pack_mode, score_dtypes, tuple(cols)


# chunks allowed in flight before the dispatch loop waits on the oldest
# fetch.  Host-resident mode: bounds device memory at
# O(inflight x chunk x N) even when D2H is slower than device compute
# (the module-docstring invariant).  Device-resident mode: drained
# chunks stay on device BY DESIGN, so this only throttles undrained
# decision-row fetches — every retained chunk registers its bytes with
# _DEVICE_BUDGET as it lands, and the KSS_TPU_DEVICE_RESULT_BUDGET_MB
# LRU spill is what bounds HBM across waves
_MAX_INFLIGHT = 4


def _leaves_dispatch(cw: CompiledWorkload, chunk: int, unroll: int, mesh,
                     wide, device_resident: bool, pack_mode: str,
                     score_dtypes: tuple, score_cols: tuple):
    """A chunk's dispatch where the workload is held as leaves (a mesh
    sharded each of them; a workload compile_workload did not make; a
    pass of more than one chunk):
    (dispatch(carry, lo, hi) -> (carry, out, attribution sums), the first
    chunk's carry)."""
    scan_jit = _scan_for(cw, chunk, unroll, mesh, pack_mode=pack_mode,
                         score_dtypes=score_dtypes, wide=wide)
    # copy: the scan donates its carry argument, and cw.init_carry must
    # survive for subsequent replays of the same compiled workload
    TRACER.count("pass_device_dispatches_total")
    carry = _copy_carry(cw.init_carry)
    xs, arg_statics = cw.xs, cw.arg_statics()
    att_ctx = (_DeviceAttribution(cw, chunk, pack_mode, score_cols)
               if device_resident else None)
    if att_ctx is not None and not att_ctx.enabled:
        att_ctx = None

    def dispatch(carry, lo: int, hi: int):
        xs_chunk = _slice_xs(xs, lo, hi, chunk)
        TRACER.count("pass_device_dispatches_total")
        carry, out = scan_jit(carry, xs_chunk, arg_statics)
        return carry, out, (att_ctx.run(out, lo) if att_ctx is not None
                            else None)

    return dispatch, carry


def _packed_dispatch(cw: CompiledWorkload, unroll: int, wide,
                     device_resident: bool, pack_mode: str,
                     score_dtypes: tuple, score_cols: tuple):
    """The dispatch of a pass of one chunk over the buffers as
    compile_workload uploaded them: ONE call, of the executable
    _packed_scan_for describes.  Same return as _leaves_dispatch, but
    that the chunk's output is a _PackedOut whose row holds the
    attribution sums too; the carry is in the buffers and stays there."""
    att_plan = (_att_plan(cw, pack_mode, score_cols) if device_resident
                else None)
    scan, args = _packed_scan_for(cw, unroll, pack_mode, score_dtypes, wide,
                                  att_plan)

    def dispatch(carry, lo: int, hi: int):
        TRACER.count("pass_device_dispatches_total")
        return carry, _PackedOut(*scan(*args), scan.row_layout), None

    return dispatch, None


def _replay_run(cw: CompiledWorkload, chunk: int, unroll: int,
                mesh, wide: str | None, on_chunk=None,
                device_resident: bool = False) -> ReplayResult | None:
    p = cw.n_pods
    # the rows one device call of this pass takes: the pass's bucket, the
    # caller's chunk where the pass is longer
    chunk = min(chunk, pod_axis_bucket(p, chunk))
    # which route depends on what the replay is handed, nothing else:
    # compile_workload's upload as it was sent, and a pass of one chunk
    # (every served pass) -> the packed scan.  Leaves (parallel/mesh.py
    # shard_workload's copy, a hand-built workload) -> the scan over
    # leaves; and so does a pass of more chunks, whose leaves are unpacked
    # once: over the buffers it would cut the whole pass's xs out again
    # in every chunk, and compile a second executable (the carry in) of
    # minutes on the chip where the leaves route compiles one
    # (the chunk is then the rows the upload laid out; a caller's chunk
    # that is no power of two finds another number there and takes leaves)
    packed = cw.packed is not None and p <= chunk == cw.pod_axis
    TRACER.inc("replay_route_total", route="packed" if packed else "leaves")
    # scan_prepare: everything between the replay span's start and the
    # first chunk's dispatch: the compact plan, the scan-cache key with
    # its statics fingerprint and the registry's lookup; on the leaves
    # route also the carry's copy and the skip masks' upload
    with TRACER.span("scan_prepare", bucket=chunk):
        pack_mode, score_dtypes, score_cols = plan = _compact_plan(cw, wide)
        if packed:
            dispatch, carry = _packed_dispatch(
                cw, unroll, wide, device_resident, *plan)
        else:
            dispatch, carry = _leaves_dispatch(
                cw, chunk, unroll, mesh, wide, device_resident, *plan)
    from concurrent.futures import ThreadPoolExecutor

    # chunks are ingested in dispatch order the moment their
    # fetch lands, so a caller's on_chunk(rr, lo, hi) can decode pods
    # lo..hi while the device is still running later chunks (the host
    # decode overlaps device compute; dispatch stays ahead by up to
    # _MAX_INFLIGHT chunks).  On a width-tier overflow this returns None
    # mid-stream — the caller re-runs wider and on_chunk fires again from
    # the first chunk, so its writes must be idempotent per pod index.
    compact = _CompactChunks(
        packed=[], raw8=[], raw16=[], raw32=[],
        chunk=chunk, pack_mode=pack_mode, score_cols=score_cols,
    )
    selected = np.full(p, -1, dtype=np.int32)
    feasible_count = np.zeros(p, dtype=np.int32)
    prefilter_reject = np.zeros(p, dtype=np.int32)
    rr = ReplayResult(
        cw=cw, selected=selected, feasible_count=feasible_count,
        prefilter_reject=prefilter_reject, compact=compact,
    )
    check_overflow = wide != "i64"

    def ingest(c: dict, lo: int, dev_out) -> bool:
        if check_overflow and c["raw_overflow"].any():
            # pre-overflow chunks already ingested this tier DELIVER
            # before the wider rerun re-delivers them: the deferred-
            # delivery path (single-effective-core hosts) must observe
            # the same redelivery contract as the immediate path, where
            # on_chunk fired the moment each chunk landed — consumers
            # rely on idempotent per-pod writes either way
            flush_deferred()
            return False  # caller reruns at the next width tier
        hi = min(lo + chunk, p)
        m = hi - lo
        if dev_out is not None:
            # device-resident: retain the chunk's heavy tensors as live
            # device arrays (budget-accounted); only the decision rows
            # in `c` crossed to host
            compact.packed.append(dev_out.packed_filter)
            compact.raw8.append(dev_out.raw8)
            compact.raw16.append(dev_out.raw16)
            compact.raw32.append(dev_out.raw32)
            ci = len(compact.packed) - 1
            _DEVICE_BUDGET.retain(compact, ci, compact.device_nbytes(ci))
        else:
            compact.packed.append(c["packed_filter"])
            compact.raw8.append(c["raw8"])
            compact.raw16.append(c["raw16"])
            compact.raw32.append(c["raw32"])
        compact.att.append(c.get("att"))
        TRACER.count("wave_d2h_bytes_total", c.get("_d2h_bytes", 0))
        selected[lo:hi] = c["selected"][:m]
        feasible_count[lo:hi] = c["feasible_count"][:m]
        prefilter_reject[lo:hi] = c["prefilter_reject"][:m]
        deliver(lo, hi)
        return True

    # single-core CPU backend: XLA's worker threads spin-wait between
    # chunk executions and starve a concurrent on_chunk consumer (~3x
    # slower decode measured), so defer the callbacks until the scan has
    # fully drained.  On an accelerator (or a multi-core host) the device
    # runs elsewhere and the overlap is pure win — keep it.
    from ..utils.platform import effective_cpu_count

    defer_chunks: list[tuple[int, int]] | None = (
        [] if on_chunk is not None and jax.default_backend() == "cpu"
        and effective_cpu_count() < 2 else None)

    def deliver(lo: int, hi: int) -> None:
        if on_chunk is None:
            return
        if defer_chunks is not None:
            defer_chunks.append((lo, hi))
        else:
            on_chunk(rr, lo, hi)

    def flush_deferred() -> None:
        if defer_chunks:
            for lo, hi in defer_chunks:
                on_chunk(rr, lo, hi)
            defer_chunks.clear()

    futures: list = []
    heavy: list = []   # device-resident: the chunk's CompactOut (device refs)
    drained = 0
    # fetches run on pool workers, which don't inherit the caller's
    # thread-local tracer session scope — carry it across explicitly so
    # session-scoped fault rules (and any session-labeled taps) see the
    # owning session at the decision-fetch seam
    wave_session = TRACER.current_session()
    # ... and the enclosing replay span, so decision_fetch parents under
    # it across the thread boundary
    replay_span = TRACER.current_span_id()

    def fetch_decisions_scoped(out, att):
        with TRACER.session_scope(wave_session), \
                TRACER.span("decision_fetch", parent=replay_span):
            return _fetch_decisions(out, att)

    def fetch_chunk_scoped(out):
        with TRACER.session_scope(wave_session), \
                TRACER.span("decision_fetch", parent=replay_span):
            return _fetch_chunk(out)

    with ThreadPoolExecutor(max_workers=3) as pool:
        for lo in range(0, p, chunk):
            hi = min(lo + chunk, p)
            fault_point("replay.scan_dispatch")
            # scan_dispatch: the chunk's call (packed: one executable
            # that cuts its own leaves; leaves: the eager slices, the
            # scan, the attribution reduction): tracing, lowering and the
            # XLA compile on a miss, then the enqueue
            with TRACER.span("scan_dispatch", lo=lo):
                carry, out, att_out = dispatch(carry, lo, hi)
            # dispatch returns immediately; a fetch thread blocks on this
            # chunk's transfer while the device runs later chunks.  In
            # device-resident mode that transfer is the decision rows +
            # the jit'd attribution sums only
            if device_resident:
                futures.append(pool.submit(fetch_decisions_scoped, out,
                                           att_out))
                heavy.append(out)
            else:
                futures.append(pool.submit(fetch_chunk_scoped, out))
                heavy.append(None)
            del out
            while len(futures) - drained > _MAX_INFLIGHT:
                if not ingest(futures[drained].result(), drained * chunk,
                              heavy[drained]):
                    return None
                heavy[drained] = None
                drained += 1
        while drained < len(futures):
            if not ingest(futures[drained].result(), drained * chunk,
                          heavy[drained]):
                return None
            heavy[drained] = None
            drained += 1
    flush_deferred()
    return rr
