"""The wire form of a result's heavy annotation values, made once.

A decided pod at 5,000 nodes carries three annotation values of ~1 MB
each (filter-result, score-result, finalscore-result): compact ASCII
JSON, a quote every dozen bytes.  Putting the pod on a socket means
`json.dumps`, and `json.dumps` of such a value is an escape pass that
doubles every quote: the pod's GET and its reflect event on every watch
stream each walked the same megabytes (docs/wave-pipeline.md "The wire
form").  Here the escaped bytes exist once:

  * PRODUCE.  The native codec assembles a blob's wire form beside the
    blob, fragment for fragment (native/annotation_codec.cpp Blob2);
    store/native_decode.py takes it as bytes where it takes the blob as
    a str, and keeps it: origin `native`.  Whatever reaches
    the reflector's write-back without one (the Python decoder rungs, a
    merged or eager value, a context that is not all ASCII, a long
    result-history) gets it there by one `encode_basestring_ascii`
    (`make_missing`: origin `python`).
  * KEEP.  `WireForms` maps a value's IDENTITY to its wire form and
    holds the value, so the identity cannot be reused while the entry
    lives.  A str never changes, so the form can never be stale; a pod
    that was PUT, written back by a later wave or recreated carries
    other str objects and finds nothing.  A deep copy keeps the str
    objects (store.get), so the copy a handler holds finds what the
    stored pod would.  A form is UNREAD until a watch event has spliced
    it, SPLICED from then on.  Past WIRE_CAP_BYTES the spliced forms go,
    oldest first; an unread form is never pushed out by a younger one
    (a burst's store queue holds every bind before the first reflect
    event: pod 1's event is built after 29 other write-backs) and goes
    only when the unread forms alone pass WIRE_UNREAD_CEILING_BYTES:
    forms nobody will use (no stream attached, a pod never read).
    What a producer may bring follows from that: a decode call
    `decode_budget()`, a write-back only `room()`, and it keeps nothing
    that would push an unread form out.
  * CONSUME.  `body_parts(obj, consumer)` is `json.dumps(obj).encode()`
    in pieces: the object with each kept value swapped for a token is
    encoded as ever (a few KB), cut at the tokens, and the kept bytes
    stand where the tokens stood.  Byte for byte what json.dumps gives;
    None where the object carries no kept value, and the caller encodes
    as it always did.

What is heavy is the value's length, WIRE_MIN_LEN: not its key, not the
route, not the cluster.  The three numbers are constants; there is no
knob.
"""

from __future__ import annotations

import json
import secrets
import threading
from collections import OrderedDict
from json.encoder import encode_basestring_ascii

from .tracing import TRACER

# a value this long is worth a wire form: below it the escape is ~0.1 ms
WIRE_MIN_LEN = 1 << 16
# values + wire forms the registry pins at rest, its spliced forms
# included: a result of 5,000 nodes' entries is 1.33 MB of values and
# 1.43 MB of forms, a burst of 30 of them 83 MB, and the burst's read
# comes after its last event, past the decode of a split window's second
# pass: 83 MB and half as much again.  A one-pod pass keeps its last ~48
# results' forms for a late read or a second stream
WIRE_CAP_BYTES = 128 << 20
# values + wire forms that NO event has spliced yet: the two passes of a
# split burst unsent and a third behind them, 3 x 83 MB = 249 MB; a pass
# of 64 such results (177 MB) whole.  A chunk of 512 of them decoded for
# one read with no stream attached would bring 1.41 GB: it stops here, at
# a sixth, and so does a listing's or an export's flush of a keyspace
WIRE_UNREAD_CEILING_BYTES = 256 << 20

# never on a wire: body_parts swaps it back out.  Random per process, so
# no stored object can hold it on purpose
_TOKEN = f"kss-wire-{secrets.token_hex(16)}-"


def is_heavy(value) -> bool:
    return type(value) is str and len(value) >= WIRE_MIN_LEN


class WireForms:
    """id(value) -> (value, wire form) in two insertion-ordered tables,
    unread and spliced, bounded in bytes: past `cap_bytes` in all the
    spliced go, oldest first; past `unread_ceiling_bytes` of unread the
    unread do.  get() is lock-free (two dict reads); keep(), spliced()
    and the eviction run under the lock."""

    def __init__(self, cap_bytes: int = WIRE_CAP_BYTES,
                 unread_ceiling_bytes: int = WIRE_UNREAD_CEILING_BYTES):
        self._cap = cap_bytes
        self._ceiling = unread_ceiling_bytes
        self._mu = threading.Lock()
        self._unread: OrderedDict[int, tuple] = OrderedDict()
        self._spliced: OrderedDict[int, tuple] = OrderedDict()
        self._bytes = 0
        self._unread_bytes = 0

    def __len__(self) -> int:
        return len(self._unread) + len(self._spliced)

    @property
    def pinned_bytes(self) -> int:
        return self._bytes

    @property
    def unread_bytes(self) -> int:
        return self._unread_bytes

    def room(self) -> int:
        """Bytes of values + forms a producer may add and push out no
        unread form."""
        return max(0, self._ceiling - self._unread_bytes)

    def decode_budget(self) -> int:
        """What one decode call may bring (the codec's cap argument):
        the room, so that the call's forms are admitted whole beside
        every unread one.  Never less than the registry keeps at rest:
        forms that sat unread while a ceiling of younger ones came are
        nobody's, and a call that finds no room lets the oldest go."""
        return min(self._ceiling, max(self.room(), self._cap))

    def keep(self, value: str, wire, spare_unread: bool = False) -> bool:
        """Pin `wire` under `value`'s identity, unread.  With
        `spare_unread` the form is kept only where no unread one has to
        go for it.  -> whether it was kept."""
        size = len(value) + len(wire)
        with self._mu:
            if spare_unread and self._unread_bytes + size > self._ceiling:
                return False
            self._drop(id(value))
            self._unread[id(value)] = (value, wire)
            self._bytes += size
            self._unread_bytes += size
            before = len(self._spliced), len(self._unread)
            while self._bytes > self._cap and self._spliced:
                self._drop(next(iter(self._spliced)))
            while self._unread_bytes > self._ceiling and self._unread:
                self._drop(next(iter(self._unread)))
            gone = (before[0] - len(self._spliced),
                    before[1] - len(self._unread))
        for state, n in zip(("spliced", "unread"), gone):
            if n:
                TRACER.inc("wire_forms_evicted_total", n, state=state)
        return True

    def _drop(self, key: int) -> None:
        ent = self._unread.pop(key, None)
        if ent is not None:
            self._unread_bytes -= len(ent[0]) + len(ent[1])
        else:
            ent = self._spliced.pop(key, None)
        if ent is not None:
            self._bytes -= len(ent[0]) + len(ent[1])

    def spliced(self, values) -> None:
        """A watch event carried these values' forms: from now on they
        are the first to go."""
        with self._mu:
            for value in values:
                ent = self._unread.get(id(value))
                if ent is None or ent[0] is not value:
                    continue
                # into the second table before out of the first: a get()
                # between the two finds it in one of them
                self._spliced[id(value)] = ent
                del self._unread[id(value)]
                self._unread_bytes -= len(ent[0]) + len(ent[1])

    def get(self, value: str):
        ent = self._unread.get(id(value)) or self._spliced.get(id(value))
        # the entry holds its value, so an id in the table is that value's
        return ent[1] if ent is not None and ent[0] is value else None

    def clear(self) -> None:
        with self._mu:
            self._unread.clear()
            self._spliced.clear()
            self._bytes = self._unread_bytes = 0


WIRE_FORMS = WireForms()


def _touch() -> None:
    """A producer ran: the registry's gauges, and the eviction series at
    0 so that "nothing was evicted" is a reading and not "no such
    counter"."""
    for state in ("unread", "spliced"):
        TRACER.inc("wire_forms_evicted_total", 0, state=state)
    TRACER.gauge("wire_forms_pinned_bytes", WIRE_FORMS.pinned_bytes)
    TRACER.gauge("wire_forms_unread_bytes", WIRE_FORMS.unread_bytes)


def keep_native(results) -> None:
    """The codec's wire forms of one decode call: `results` yields, a
    decoded result, its (value, wire form) pairs.  Counted a result, and
    touched by every call so that a reader of the counters can tell "no
    value was heavy" from "no such counter"."""
    made = 0
    for pairs in results:
        for value, wire in pairs:
            WIRE_FORMS.keep(value, wire)
        made += bool(pairs)
    TRACER.inc("wire_forms_made_total", made, origin="native")
    _touch()


def make_missing(values) -> int:
    """The write-back's part: every heavy value among `values` (one
    result's) that has no wire form yet gets one, by the encoder
    json.dumps itself uses, while the registry has room: what the codec
    escaped is not escaped again, and no unread form goes for one made
    here, so a batch of write-backs (a listing's flush of a keyspace)
    stops at the ceiling.  -> the bytes it kept.  The result counts
    once, under the origin of its first form: `python` where it brought
    none."""
    kept, brought = 0, False
    for value in values:
        if not is_heavy(value):
            continue
        if WIRE_FORMS.get(value) is not None:
            brought = True
        # the form is at least the value's length: no room, no escape
        elif WIRE_FORMS.room() > 2 * len(value):
            wire = encode_basestring_ascii(value).encode()
            if WIRE_FORMS.keep(value, wire, spare_unread=True):
                kept += len(value) + len(wire)
    if kept and not brought:
        TRACER.inc("wire_forms_made_total", origin="python")
    # a pod was written back: its bodies are about to be built.  Touched
    # here so that "no body fell back" reads 0 and not "no such counter"
    for consumer in ("read", "watch"):
        TRACER.inc("pod_bodies_full_total", 0, consumer=consumer)
    _touch()
    return kept


def body_parts(obj, consumer: str) -> list | None:
    """`json.dumps(obj).encode()` as fragments, the kept wire forms of
    obj's heavy annotation values among them, or None where obj carries
    none (the caller encodes as ever).  A body whose every heavy value
    was spliced counts `pod_bodies_spliced_total{consumer}`; one that
    had to escape a heavy value itself, `pod_bodies_full_total`.  The
    forms a watch event carried are spliced from then on: the first the
    registry lets go."""
    meta = obj.get("metadata") if isinstance(obj, dict) else None
    anns = meta.get("annotations") if isinstance(meta, dict) else None
    if not isinstance(anns, dict):
        return None
    heavy = [(k, v) for k, v in anns.items() if is_heavy(v)]
    if not heavy:
        return None
    slim = dict(anns)
    wires: list = []
    for key, value in heavy:
        wire = WIRE_FORMS.get(value)
        if wire is not None:
            slim[key] = f"{_TOKEN}{len(wires)}"
            wires.append(wire)
    parts = _splice({**obj, "metadata": {**meta, "annotations": slim}},
                    wires) if wires else None
    if parts is None or len(wires) < len(heavy):
        TRACER.inc("pod_bodies_full_total", consumer=consumer)
    else:
        TRACER.inc("pod_bodies_spliced_total", consumer=consumer)
    if wires and consumer == "watch":
        WIRE_FORMS.spliced(value for _, value in heavy)
    return parts


def _splice(slim_obj: dict, wires: list) -> list | None:
    text = json.dumps(slim_obj)
    parts: list = []
    pos = 0
    for i, wire in enumerate(wires):
        needle = f'"{_TOKEN}{i}"'
        at = text.find(needle, pos)
        if at < 0:
            return None
        parts.append(text[pos:at].encode())
        parts.append(wire)
        pos = at + len(needle)
    parts.append(text[pos:].encode())
    return parts
