"""What the bound pods give a compiled workload, carried from pass to pass.

compile_workload used to walk every bound manifest in every pass, once per
plugin: for a cluster that gained one pod since the last pass.  A
BoundCarry holds, per bound pod (keyed by pod key, checked by uid +
resourceVersion), the ROW the plugins need of it, and per plugin family
the AGGREGATE those rows add up to; a pass applies the pods bound, changed
or deleted since the last one and reads the aggregates:

  core              per-node sums of requests / nonzero requests / pod
                    counts (int64: add on bind, subtract on unbind)
  schema            reference counts of the extended resource names
  label matches     (namespace, labels) signatures interned; per selector
                    a per-node count of the bound pods it matches — what
                    InterPodAffinity's `matched` and PodTopologySpread's
                    domain counts are folded from
  own terms         per [anti-]affinity term a bound pod carries, per-node
                    sums of multiplicity / weight (the symmetric checks)
  ports             the few bound pods with hostPorts, in bound order:
                    the build walks these instead of every bound pod
  volumes           the bound pods with PVC or restricted inline volumes,
                    and a journal of the ones added, changed or removed:
                    state/volumecarry.py resolves those and carries the rest

Every aggregate is an integer sum or a set, so the order rows arrive in
cannot show: a carry brought up to date by deltas gives the same bytes as
one built from scratch on the same store (tests/test_bound_carry.py).
What a carry cannot follow — another node set, namespaces changed under
namespaceSelector terms, another resource schema, a watch backlog it was
dropped from — it rebuilds from its own rows and counts under
bound_carry_rebuilds_total{reason}.  compile_workload without a carry
(the dry-run users: preemption's fit oracle) builds a throw-away one from
the list it is given: one code path for both.
"""

from __future__ import annotations

import json
import queue

import numpy as np

# modules, not names: a plugin module imported first imports this package
# while it is itself half initialised (state/compile.py does the same)
from ..plugins import interpod, ports, volumerestrictions
from ..utils.tracing import TRACER
from .resources import pod_resource_request
from .selectors import label_selector_matches
from .volumes import pod_pvc_keys

_N_KINDS, _REQ_ANTI = 4, 1    # of interpod.KINDS, and "req_anti" in it

_BASE_RES = ("cpu", "memory", "ephemeral-storage", "pods")

# a watch backlog past this is cheaper to drop than to drain: the feed
# seeds again from an atomic list (framework/pending.py has the same rule)
_RESYNC_BACKLOG = 8192
# selectors whose per-node match counts are kept up to date by deltas
_MATCH_MEMO_MAX = 64


def pod_key(pod: dict) -> str:
    meta = pod.get("metadata") or {}
    return f"{meta.get('namespace') or 'default'}/{meta.get('name', '')}"


def _ext_names(spec: dict) -> tuple[str, ...]:
    """The extended resource names a pod requests (what
    ResourceSchema.discover collects from one pod)."""
    out = None
    for group in ("containers", "initContainers"):
        for c in spec.get(group) or ():
            for name in ((c.get("resources") or {}).get("requests")) or ():
                if name not in _BASE_RES:
                    out = (out or set())
                    out.add(name)
    for name in spec.get("overhead") or ():
        if name not in _BASE_RES:
            out = (out or set())
            out.add(name)
    return tuple(out) if out else ()


def pod_request_rows(pods: list[dict], schema, pod_columns):
    """[P, R] requests + [P, 2] nonzero rows.  With a columnar pod view,
    rows are GATHERED from the bank's pre-parsed request columns by uid
    (one vectorized fancy-index per schema column); pods the bank can't
    answer (no uid match, opaque rows) fall back to the per-pod parse."""
    p = len(pods)
    requests = np.zeros((p, schema.n), dtype=np.int64)
    nonzero = np.zeros((p, 2), dtype=np.int64)
    misses = range(p)
    if pod_columns is not None and p:
        # a listing's columnar view, or the bank itself
        bank = getattr(pod_columns, "bank", pod_columns)
        by_uid = bank.row_by_uid
        rows = np.full(p, -1, dtype=np.int64)
        miss = []
        # wave-SETUP uid->row mapping: dict lookups can't vectorize; the
        # per-schema-column request gather below is the vectorized part
        # kss-analyze: allow(pod-loop)
        for i, pod in enumerate(pods):
            uid = (pod.get("metadata") or {}).get("uid")
            row = by_uid.get(uid) if uid else None
            if row is None or bank.opaque[row] or bank.deleted[row]:
                miss.append(i)
            else:
                rows[i] = row
        ok = rows >= 0
        if ok.any():
            okr = rows[ok]
            for j, rname in enumerate(schema.columns):
                col = bank.req.get(rname)
                if col is not None:
                    requests[ok, j] = col[okr]
            nonzero[ok] = bank.nonzero[okr]
        misses = miss
    for i in misses:
        requests[i], nonzero[i] = pod_resource_request(pods[i], schema)
    return requests, nonzero


class BoundFeed:
    """The bound pods of a store and what became of them since the last
    drain, from the store's own watch events (list_and_watch: an atomic
    list + subscription, so nothing is lost between them).  One consumer:
    the engine's pass."""

    def __init__(self, store):
        self.store = store
        self._q = None

    def drain(self):
        """-> ("resync", {key: pod}) with every bound pod, the first time
        and after a backlog it was cheaper to drop; else ("delta",
        {key: pod | None}): the pods bound or changed (their newest
        manifest) and unbound or deleted (None) since the last drain."""
        if self._q is None or self._q.qsize() > _RESYNC_BACKLOG:
            self.close()
            items, _rv, self._q = self.store.list_and_watch("pods")
            return "resync", {pod_key(p): p for p in items
                              if (p.get("spec") or {}).get("nodeName")}
        changes: dict[str, dict | None] = {}
        while True:
            try:
                _rv, event_type, obj = self._q.get_nowait()
            except queue.Empty:
                return "delta", changes
            bound = (event_type != "DELETED"
                     and (obj.get("spec") or {}).get("nodeName"))
            changes[pod_key(obj)] = obj if bound else None

    def close(self) -> None:
        if self._q is not None:
            self.store.unwatch("pods", self._q)
            self._q = None


class _Match:
    """One selector over the bound pods: which signatures it matches
    (filled as signatures appear) and how many matching pods each node
    holds."""

    __slots__ = ("nss", "sel", "ok", "cnt")

    def __init__(self, nss, sel):
        self.nss, self.sel = nss, sel
        self.ok: list[bool] = []
        self.cnt = None


class BoundCarry:
    def __init__(self, feed: BoundFeed | None = None):
        self.feed = feed
        self._names = None          # the node names the rows are placed on
        self._name_idx = None
        self._ns_key = None         # namespaces the own terms were resolved on
        self._namespaces = None
        # the keys of the volume rows added, changed or removed, for the
        # one VolumeCarry that follows this carry (it sets and empties the
        # set; state/volumecarry.py): not a row, so no rebuild clears it
        self.volume_journal: set | None = None
        self._clear()

    # ------------------------------------------------------------ state

    def _clear(self) -> None:
        self._slot: dict = {}                 # key -> slot
        self._free: list[int] = []
        self._pod: list = []                  # slot -> manifest
        self._node_name: list = []            # slot -> the node it runs on
        self._ident: list = []                # slot -> (uid, resourceVersion)
        self._terms: list = []                # slot -> [(kind, tk, w)] | None
        self._ext: list = []                  # slot -> extended names
        cap = 64
        self._node = np.full(cap, -1, dtype=np.int64)
        self._sig = np.zeros(cap, dtype=np.int64)
        self._alive = np.zeros(cap, dtype=bool)
        self._cols = None                     # schema columns of _req
        self._req = None                      # [cap, R] once a schema is known
        self._nz = np.zeros((cap, 2), dtype=np.int64)
        self._unplaced: set[int] = set()      # rows without a node index yet
        self._need_req: set[int] = set()      # rows without a request row yet
        self._ext_refs: dict[str, int] = {}
        self._sig_id: dict = {}
        self._sigs: list = []                 # id -> (namespace, labels)
        self._matches: dict = {}              # (nss, selector json) -> _Match
        self._own: dict = {}                  # tk -> [int64[4, N], refs, term]
        self._anti_refs = 0
        self._ports: dict = {}                # key -> slot, pods with hostPorts
        self._volumes: dict = {}              # key -> slot, pods with volumes
        self._req_sum = self._nz_sum = self._np_sum = None
        self._built = 0                       # rows built since the last count

    @property
    def n(self) -> int:
        return len(self._slot)

    def _grow(self, need: int) -> None:
        cap = len(self._alive)
        if need <= cap:
            return
        new = max(need, 2 * cap)

        def grown(a, fill=0):
            out = np.full((new,) + a.shape[1:], fill, dtype=a.dtype)
            out[:cap] = a
            return out

        self._node = grown(self._node, -1)
        self._sig = grown(self._sig)
        self._alive = grown(self._alive)
        self._nz = grown(self._nz)
        if self._req is not None:
            self._req = grown(self._req)

    # ----------------------------------------------------------- rows

    def _add(self, key, pod: dict, node_name: str) -> None:
        """The node-independent half of a row: everything read off the
        manifest.  The node index, the per-node sums and the request row
        follow in place() / core(), when the node table and the schema of
        this pass are known."""
        if self._free:
            s = self._free.pop()
        else:
            s = len(self._pod)
            self._grow(s + 1)
            self._pod.append(None)
            self._node_name.append(None)
            self._ident.append(None)
            self._terms.append(None)
            self._ext.append(())
        meta = pod.get("metadata") or {}
        spec = pod.get("spec") or {}
        self._slot[key] = s
        self._pod[s] = pod
        self._node_name[s] = node_name
        self._ident[s] = (meta.get("uid"), meta.get("resourceVersion"))
        ext = self._ext[s] = _ext_names(spec)
        for name in ext:
            self._ext_refs[name] = self._ext_refs.get(name, 0) + 1
        ns = meta.get("namespace") or "default"
        labels = meta.get("labels")
        sk = (ns, tuple(sorted((k, str(v)) for k, v in labels.items()))
              if labels else ())
        sig = self._sig_id.get(sk)
        if sig is None:
            sig = self._sig_id[sk] = len(self._sigs)
            self._sigs.append((ns, dict(sk[1])))
        self._sig[s] = sig
        self._node[s] = -1
        self._alive[s] = True
        self._unplaced.add(s)
        self._need_req.add(s)
        aff = spec.get("affinity")
        terms = None
        if aff and (aff.get("podAffinity") or aff.get("podAntiAffinity")):
            terms = []
            for kind, (_, field, preferred) in enumerate(interpod.KINDS):
                for term, w in interpod.effective_terms(pod, field, preferred,
                                                        self._namespaces):
                    tk = interpod.term_key(term)
                    terms.append((kind, tk, w))
                    own = self._own.get(tk)
                    if own is None:
                        own = self._own[tk] = [None, 0, (
                            term.get("topologyKey", ""),
                            term.get("labelSelector"), tk[2])]
                    own[1] += 1
                    self._anti_refs += kind == _REQ_ANTI
        self._terms[s] = terms or None
        if spec.get("containers"):
            if ports.pod_host_ports(pod):
                self._ports[key] = s
        if spec.get("volumes"):
            if pod_pvc_keys(pod) or volumerestrictions.pod_inline_disks(pod):
                self._volumes[key] = s
                if self.volume_journal is not None:
                    self.volume_journal.add(key)
        self._built += 1

    def _remove(self, key) -> None:
        s = self._slot.pop(key)
        for name in self._ext[s]:
            left = self._ext_refs[name] - 1
            if left:
                self._ext_refs[name] = left
            else:
                del self._ext_refs[name]
        j = int(self._node[s])
        if s in self._unplaced:
            self._unplaced.discard(s)
        elif j >= 0:
            self._place_sums(s, j, -1)
        for kind, tk, _w in self._terms[s] or ():
            own = self._own[tk]
            own[1] -= 1
            self._anti_refs -= kind == _REQ_ANTI
            if not own[1]:
                del self._own[tk]
        self._need_req.discard(s)
        self._ports.pop(key, None)
        if (self._volumes.pop(key, None) is not None
                and self.volume_journal is not None):
            self.volume_journal.add(key)
        self._alive[s] = False
        self._pod[s] = self._node_name[s] = self._ident[s] = None
        self._terms[s] = None
        self._ext[s] = ()
        self._free.append(s)

    def _place_sums(self, s: int, j: int, sign: int) -> None:
        """Add (sign +1) or take back (-1) what row s gives node j."""
        sig = int(self._sig[s])
        for m in self._matches.values():
            if m.cnt is not None and self._sig_ok(m, sig):
                m.cnt[j] += sign
        for kind, tk, w in self._terms[s] or ():
            own = self._own[tk]
            if own[0] is None:
                own[0] = np.zeros((_N_KINDS, len(self._names)), dtype=np.int64)
            own[0][kind, j] += sign * w
        if s not in self._need_req and self._req_sum is not None:
            self._req_sum[j] += sign * self._req[s]
            self._nz_sum[j] += sign * self._nz[s]
            self._np_sum[j] += sign

    def _sig_ok(self, m: _Match, sig: int) -> bool:
        ok = m.ok
        while len(ok) <= sig:
            ns, labels = self._sigs[len(ok)]
            ok.append(ns in m.nss and label_selector_matches(m.sel, labels))
        return ok[sig]

    # --------------------------------------------------- a pass, in order

    def _resolve_on(self, namespaces: list[dict] | None) -> bool:
        """Note the namespaces of this pass (the own terms resolve their
        namespaceSelector against them) -> True when rows hold terms
        resolved on others."""
        ns_key = tuple(
            ((ns.get("metadata") or {}).get("name", ""),
             (ns.get("metadata") or {}).get("resourceVersion", ""))
            for ns in namespaces or ())
        stale = ns_key != self._ns_key and bool(self._own)
        self._ns_key, self._namespaces = ns_key, namespaces
        return stale

    def pull(self, namespaces: list[dict] | None) -> None:
        """First in a pass: what the store bound, changed or deleted since
        the last one, applied as far as the manifests alone tell (the
        schema discovery that follows needs the new rows' resource
        names)."""
        first = self._ns_key is None
        stale_terms = self._resolve_on(namespaces)
        kind, changes = self.feed.drain()
        if kind == "resync":
            self._rebuild("first" if first else "resync", changes)
            return
        if stale_terms:
            self._rebuild("namespaces")
        for key, pod in changes.items():
            s = self._slot.get(key)
            if s is not None:
                meta = (pod or {}).get("metadata") or {}
                if pod is not None and self._ident[s] == (
                        meta.get("uid"), meta.get("resourceVersion")):
                    continue
                self._remove(key)
            if pod is not None:
                self._add(key, pod, pod["spec"]["nodeName"])

    def _rebuild(self, reason: str, rows: dict | None = None) -> None:
        """Every row again, from the given rows or from the manifests the
        carry holds: today's full build, counted."""
        if rows is None:
            rows = {key: self._pod[s] for key, s in self._slot.items()}
        if self.volume_journal is not None:
            self.volume_journal.update(self._volumes)
        self._clear()
        for key, pod in rows.items():
            self._add(key, pod, pod["spec"]["nodeName"])
        TRACER.inc("bound_carry_rebuilds_total", reason=reason)

    def place(self, names: list[str]) -> None:
        """After the node table: give the rows of this pass their node
        index; on another node set, every row."""
        if self._names is not names and self._names != names:
            if self._names is not None and self.n:
                TRACER.inc("bound_carry_rebuilds_total", reason="nodes")
                self._built += self.n - len(self._unplaced)
            self._unplaced = set(self._slot.values())
            self._matches.clear()
            for own in self._own.values():
                own[0] = None
            self._req_sum = None
            self._need_req = set(self._unplaced)
            self._name_idx = {name: j for j, name in enumerate(names)}
        self._names = names
        if self._unplaced:
            idx = self._name_idx
            for s in self._unplaced:
                self._node[s] = idx.get(self._node_name[s], -1)
            placed, self._unplaced = self._unplaced, set()
            for s in placed:
                j = int(self._node[s])
                if j >= 0:
                    self._place_sums(s, j, +1)
        carried = self.n - self._built
        TRACER.count("bound_rows_built_total", self._built)
        TRACER.count("bound_rows_carried_total", max(carried, 0))
        TRACER.gauge("bound_pods", self.n)
        self._built = 0

    # ------------------------------------------------------------ reads

    def extended_names(self):
        return self._ext_refs.keys()

    def core_sums(self, schema, pod_columns):
        """-> per-node (requests [N, R], nonzero [N, 2], pod counts [N])
        of the bound pods.  Rows new since the last pass get their request
        row here; another schema, all of them."""
        cols = tuple(schema.columns)
        n = len(self._names)
        if cols != self._cols:
            if self._cols is not None and self.n:
                TRACER.inc("bound_carry_rebuilds_total", reason="schema")
            self._cols = cols
            self._req = np.zeros((len(self._alive), len(cols)), dtype=np.int64)
            self._req_sum = None
        if self._req_sum is None:
            self._req_sum = np.zeros((n, len(cols)), dtype=np.int64)
            self._nz_sum = np.zeros((n, 2), dtype=np.int64)
            self._np_sum = np.zeros(n, dtype=np.int64)
            self._need_req = set(self._slot.values())
        if self._need_req:
            slots = np.fromiter(self._need_req, dtype=np.int64,
                                count=len(self._need_req))
            self._need_req = set()
            req, nz = pod_request_rows([self._pod[s] for s in slots],
                                       schema, pod_columns)
            self._req[slots] = req
            self._nz[slots] = nz
            on = self._node[slots] >= 0
            j = self._node[slots][on]
            np.add.at(self._req_sum, j, req[on])
            np.add.at(self._nz_sum, j, nz[on])
            np.add.at(self._np_sum, j, 1)
        return self._req_sum, self._nz_sum, self._np_sum

    def match_counts(self, nss: tuple[str, ...], sel: dict | None) -> np.ndarray:
        """[N] int64: per node, the bound pods in one of the namespaces
        `nss` whose labels match `sel`.  Not the caller's to change."""
        mk = (nss, json.dumps(sel, sort_keys=True))
        m = self._matches.pop(mk, None)
        if m is None:
            m = _Match(frozenset(nss), sel)
            if len(self._matches) >= _MATCH_MEMO_MAX:
                del self._matches[next(iter(self._matches))]
        self._matches[mk] = m   # most recently used last
        if m.cnt is None:
            n_sigs = len(self._sigs)
            ok = np.fromiter((self._sig_ok(m, s) for s in range(n_sigs)),
                             dtype=bool, count=n_sigs)
            hit = self._alive & (self._node >= 0)
            if n_sigs:
                hit &= ok[self._sig]
            m.cnt = np.bincount(self._node[hit],
                                minlength=len(self._names)).astype(np.int64)
        return m.cnt

    def own_terms(self) -> dict:
        """{term key: (topologyKey, selector, namespaces)} of the
        [anti-]affinity terms the bound pods carry."""
        return {tk: own[2] for tk, own in self._own.items()}

    def own_sums(self, tk) -> np.ndarray | None:
        """[4, N] int64 per-node sums (KINDS order: multiplicities of the
        required terms, weights of the preferred) of the bound pods
        carrying term tk; None when none on a known node does."""
        own = self._own.get(tk)
        return None if own is None else own[0]

    @property
    def any_required_anti(self) -> bool:
        return self._anti_refs > 0

    def _rows_of(self, subset: dict) -> list[tuple[dict, str]]:
        return [(self._pod[s], self._node_name[s])
                for _, s in sorted(subset.items())]

    def port_rows(self) -> list[tuple[dict, str]]:
        """(pod, node name) of the bound pods with hostPorts, in bound
        order."""
        return self._rows_of(self._ports)

    def volume_keys(self):
        """The keys of the bound pods with PVC-backed or restricted inline
        volumes; sorted, they are in bound order."""
        return self._volumes.keys()

    def volume_row(self, key) -> tuple[dict, str] | None:
        """(pod, node name) of one of them; None when `key` is not."""
        s = self._volumes.get(key)
        return None if s is None else (self._pod[s], self._node_name[s])

    def rows(self) -> list[tuple[dict, str]]:
        """Every bound pod, in bound order (the list a build without a
        carry is given)."""
        return self._rows_of(self._slot)

    def close(self) -> None:
        if self.feed is not None:
            self.feed.close()


def carry_of_list(bound_pods: list[tuple[dict, str]],
                  namespaces: list[dict] | None) -> BoundCarry:
    """A throw-away carry over a given (pod, node name) list, rows keyed
    by position: what compile_workload builds when it is handed a list
    (dry runs, direct callers)."""
    carry = BoundCarry()
    carry._resolve_on(namespaces)
    for i, (pod, node_name) in enumerate(bound_pods):
        carry._add(i, pod, node_name)
    TRACER.inc("bound_carry_rebuilds_total", reason="uncarried")
    return carry
