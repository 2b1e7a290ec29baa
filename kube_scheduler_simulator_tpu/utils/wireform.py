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
    stored pod would.  Bounded by WIRE_CAP_BYTES, oldest first.
  * CONSUME.  `body_parts(obj, consumer)` is `json.dumps(obj).encode()`
    in pieces: the object with each kept value swapped for a token is
    encoded as ever (a few KB), cut at the tokens, and the kept bytes
    stand where the tokens stood.  Byte for byte what json.dumps gives;
    None where the object carries no kept value, and the caller encodes
    as it always did.

What is heavy is the value's length, WIRE_MIN_LEN: not its key, not the
route, not the cluster.  Both numbers are constants; there is no knob.
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
# values + wire forms the registry may pin: four or five pods of 5,000
# nodes' entries (~7 MB each); the read and the reflect event come
# within milliseconds of the write-back
WIRE_CAP_BYTES = 32 << 20

# never on a wire: body_parts swaps it back out.  Random per process, so
# no stored object can hold it on purpose
_TOKEN = f"kss-wire-{secrets.token_hex(16)}-"


def is_heavy(value) -> bool:
    return type(value) is str and len(value) >= WIRE_MIN_LEN


class WireForms:
    """id(value) -> (value, wire form), insertion-ordered, bounded in
    bytes.  get() is lock-free (one dict read); keep() and the eviction
    run under the lock."""

    def __init__(self, cap_bytes: int = WIRE_CAP_BYTES):
        self._cap = cap_bytes
        self._mu = threading.Lock()
        self._ents: OrderedDict[int, tuple] = OrderedDict()
        self._bytes = 0

    def __len__(self) -> int:
        return len(self._ents)

    @property
    def pinned_bytes(self) -> int:
        return self._bytes

    def keep(self, value: str, wire) -> None:
        with self._mu:
            old = self._ents.pop(id(value), None)
            if old is not None:
                self._bytes -= len(old[0]) + len(old[1])
            self._ents[id(value)] = (value, wire)
            self._bytes += len(value) + len(wire)
            while self._bytes > self._cap and self._ents:
                _, (v, w) = self._ents.popitem(last=False)
                self._bytes -= len(v) + len(w)

    def get(self, value: str):
        ent = self._ents.get(id(value))
        # the entry holds its value, so an id in the table is that value's
        return ent[1] if ent is not None and ent[0] is value else None

    def clear(self) -> None:
        with self._mu:
            self._ents.clear()
            self._bytes = 0


WIRE_FORMS = WireForms()


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


def make_missing(values, budget: int = WIRE_CAP_BYTES) -> int:
    """The write-back's part: every heavy value among `values` (one
    result's) that has no wire form yet gets one, by the encoder
    json.dumps itself uses, while `budget` bytes last: a batch of
    write-backs makes no more than the registry can hold.  -> the bytes
    it kept.  The result counts once, under the origin of its first
    form: `python` where it brought none."""
    kept, brought = 0, False
    for value in values:
        if not is_heavy(value):
            continue
        if WIRE_FORMS.get(value) is not None:
            brought = True
        elif kept < budget:
            wire = encode_basestring_ascii(value).encode()
            WIRE_FORMS.keep(value, wire)
            kept += len(value) + len(wire)
    if kept and not brought:
        TRACER.inc("wire_forms_made_total", origin="python")
    # a pod was written back: its bodies are about to be built.  Touched
    # here so that "no body fell back" reads 0 and not "no such counter"
    for consumer in ("read", "watch"):
        TRACER.inc("pod_bodies_full_total", 0, consumer=consumer)
    return kept


def body_parts(obj, consumer: str) -> list | None:
    """`json.dumps(obj).encode()` as fragments, the kept wire forms of
    obj's heavy annotation values among them, or None where obj carries
    none (the caller encodes as ever).  A body whose every heavy value
    was spliced counts `pod_bodies_spliced_total{consumer}`; one that
    had to escape a heavy value itself, `pod_bodies_full_total`."""
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
