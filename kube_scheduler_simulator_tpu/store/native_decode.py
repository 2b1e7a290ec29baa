"""Native fast path for the annotation decoder.

Builds the context (name arrays, sorted orders, message LUTs) that
native/annotation_codec.cpp encodes the three heavy blobs (filter-result,
score-result, finalscore-result) from.  The context is a value derived
from the node table and the profile: it is kept on the table's memo
(shared_context) and shared by every pass that compiles against that
table; what a pass adds, its pods' plugin-ran / score-skip rows, is kept
on the pass's own cw (pass_rows).  Used by store/decode.py when the
native codec is available; output is byte-identical to the Python path
(asserted by tests/test_native_codec.py, tests/test_codec_ctx_carry.py).
"""

from __future__ import annotations

import ctypes

import numpy as np

from ..native import (
    get_lib, peek_string, peek_string_ascii, take_sized_bytes,
    take_sized_string, take_sized_string_ascii,
)
from ..plugins import (
    affinity, interpod, nodevolumelimits, ports, taints, topologyspread,
    volumebinding, volumerestrictions, volumezone,
)
from ..plugins.noderesources import decode_fit_filter
from ..utils.tracing import TRACER
from ..utils import wireform

_MAX_FIT_LUT_BITS = 16


def _c_str_array(strings: list[bytes]):
    arr = (ctypes.c_char_p * len(strings))(*strings)
    return arr


def context_key(cw) -> tuple:
    """Everything build_context reads beside the node table: the filter
    and scorer lineups, the scorers' weights, the resource schema's
    columns (NodeResourcesFit's LUT renders resource names; schema.n is
    their count) and the interned message tables of the custom plugins
    in the lineup."""
    filter_names = cw.config.filters()
    score_names = cw.config.scorers()
    custom = cw.host.get("custom_msgs", {})
    return (tuple(filter_names), tuple(score_names),
            tuple(cw.config.weight(nm) for nm in score_names),
            tuple(cw.schema.columns),
            tuple((nm, tuple(custom[nm])) for nm in filter_names
                  if nm in custom))


def shared_context(cw):
    """The codec context of cw's node table and profile, or None where
    the library is missing or the LUTs cannot express the lineup.  One
    generation on the table's memo (state/nodes.py NodeDerived, kind
    codec_ctx): a pass on an unchanged table under an unchanged profile
    is a hit, a None is carried like a context (the lineup is probed once
    a table, whether the build returned None or raised), and the C side
    is freed when the memo lets go: with the table on any node change, or
    when another key replaces the generation.  Nothing of a pass is on
    it, so passes may decode on it at once."""
    def make():
        with TRACER.span("codec_ctx_build", nodes=cw.node_table.n):
            # a build that raises is carried as None, like one that
            # declines the lineup: the Python encoder serves
            try:
                return build_context(cw)
            except Exception:
                return None

    return cw.node_table.derived.generation(
        "codec_ctx", context_key(cw), make)


def pass_rows(cw) -> tuple[np.ndarray, np.ndarray]:
    """(active [P, F], sskip [P, S]) uint8: which Filter plugins ran and
    which scorers were skipped for each pod of this pass, in lineup
    order; a row slice hands C a contiguous pointer.  Built once a cw and
    kept in cw.host."""
    rows = cw.host.get("_codec_rows")
    if rows is None:
        def columns(flags, names):
            if not names:
                return np.zeros((cw.n_pods, 0), bool)
            return np.stack([np.asarray(flags[nm], bool) for nm in names],
                            axis=1)

        rows = cw.host["_codec_rows"] = (
            np.ascontiguousarray(
                ~columns(cw.host.get("filter_skip", {}), cw.config.filters()),
                np.uint8),
            np.ascontiguousarray(
                columns(cw.host.get("score_skip", {}), cw.config.scorers()),
                np.uint8))
    return rows


def build_context(cw):
    """-> a fresh _NativeCtx from cw's node table, profile, schema and
    custom message tables (context_key), or None when the library is
    missing or a plugin's messages can't be LUT'd.  Nothing of cw's pods
    is read; decoders take the one shared_context keeps."""
    lib = get_lib()
    if lib is None:
        return None
    table = cw.node_table
    n = table.n
    filter_names = cw.config.filters()
    score_names = cw.config.scorers()

    luts: list[list[bytes]] = []
    per_node: list[int] = []
    for name in filter_names:
        if name == "NodeResourcesFit":
            bits = cw.schema.n + 1
            if bits > _MAX_FIT_LUT_BITS:
                return None
            lut = [
                decode_fit_filter(code, cw.schema).encode()
                for code in range(1, (1 << bits))
            ]
            per_node.append(0)
        elif name == "NodeAffinity":
            lut = [affinity.ERR_REASON.encode()]
            per_node.append(0)
        elif name == "NodeUnschedulable":
            lut = [taints.ERR_UNSCHEDULABLE.encode()]
            per_node.append(0)
        elif name == "NodeName":
            lut = [taints.ERR_NODE_NAME.encode()]
            per_node.append(0)
        elif name == "NodePorts":
            lut = [ports.ERR_NODE_PORTS.encode()]
            per_node.append(0)
        elif name == "TaintToleration":
            stride = table.max_taints
            if stride == 0:
                lut = [b""] * n  # never indexed (no taints -> no failures)
                stride = 1
            else:
                lut = []
                for j in range(n):
                    for ti in range(stride):
                        if ti < len(table.taints[j]):
                            key, value, _ = table.taints[j][ti]
                            lut.append(
                                ("node(s) had untolerated taint {%s: %s}" % (key, value)).encode()
                            )
                        else:
                            lut.append(b"")
            per_node.append(1)
        elif name == "PodTopologySpread":
            lut = []
            for code in range(1, 2 * topologyspread.MAX_CONSTRAINTS + 1):
                lut.append(
                    (topologyspread.ERR_MISSING_LABEL if code % 2 == 1
                     else topologyspread.ERR_SKEW).encode()
                )
            per_node.append(0)
        elif name == "InterPodAffinity":
            lut = [interpod.ERR_AFFINITY.encode(), interpod.ERR_ANTI_AFFINITY.encode(),
                   interpod.ERR_EXISTING_ANTI.encode()]
            per_node.append(0)
        elif name == "VolumeRestrictions":
            lut = [volumerestrictions.ERR_DISK_CONFLICT.encode()]
            per_node.append(0)
        elif name == "NodeVolumeLimits":
            lut = [nodevolumelimits.ERR_MAX_VOLUME_COUNT.encode()]
            per_node.append(0)
        elif name == "VolumeBinding":
            # codes are a bitmask (1 node-conflict | 2 bind-conflict |
            # 4 pv-not-exist); decode_filter renders every combination
            lut = [volumebinding.decode_filter(c, 0, None).encode() for c in range(1, 8)]
            per_node.append(0)
        elif name == "VolumeZone":
            lut = [volumezone.ERR_VOLUME_ZONE_CONFLICT.encode()]
            per_node.append(0)
        elif name in cw.host.get("custom_msgs", {}):
            lut = [m.encode() for m in cw.host["custom_msgs"][name]] or [b""]
            per_node.append(0)
        else:
            return None
        luts.append(lut)

    lut_flat: list[bytes] = []
    lut_off = [0]
    for lut in luts:
        lut_flat.extend(lut)
        lut_off.append(len(lut_flat))

    names_sorted = np.argsort(np.asarray(table.names)).astype(np.int32)
    sorted_filters = (np.argsort(np.asarray(filter_names)).astype(np.int32)
                      if filter_names else np.zeros(0, np.int32))
    sorted_scores = (np.argsort(np.asarray(score_names)).astype(np.int32)
                     if score_names else np.zeros(0, np.int32))
    lut_off_arr = np.asarray(lut_off, dtype=np.int32)
    per_node_arr = np.asarray(per_node, dtype=np.uint8)
    # score finalization params (the hostnorm.finalize_chunk dispatch,
    # matched by NAME exactly as finalize_chunk does)
    _KINDS = {"NodeAffinity": 1, "TaintToleration": 2,
              "PodTopologySpread": 3, "InterPodAffinity": 4}
    kinds = np.asarray([_KINDS.get(nm, 0) for nm in score_names], np.int32)
    weights = np.asarray([cw.config.weight(nm) for nm in score_names], np.int64)
    # the C context copies every fragment (escaped node/plugin keys, escaped
    # LUT messages) into its own storage, so the Python arrays above only
    # need to live for this call
    cptr = lib.codec_ctx_new(
        n, len(filter_names), len(score_names),
        _c_str_array([nm.encode() for nm in table.names]),
        _c_str_array([nm.encode() for nm in filter_names]),
        _c_str_array([nm.encode() for nm in score_names]),
        _i32p(np.ascontiguousarray(names_sorted)),
        _i32p(np.ascontiguousarray(sorted_filters)),
        _i32p(np.ascontiguousarray(sorted_scores)),
        _c_str_array(lut_flat or [b""]),
        _i32p(lut_off_arr), _u8p(per_node_arr),
        _i32p(kinds), _i64p(weights), int(topologyspread._BIG),
    )
    return _NativeCtx(lib, cptr, n, "PodTopologySpread" in score_names)


class _NativeCtx:
    """Owns one C-side codec context; freed with the table's memo (or
    with the last cw or in-flight _ChunkHandle that still holds it).
    Immutable once built: the C side only reads it, so any number of
    passes and threads may decode on it at once."""

    __slots__ = ("lib", "ptr", "n", "has_tsp_score", "take", "peek",
                 "__weakref__")

    def __init__(self, lib, ptr, n, has_tsp_score):
        self.lib = lib
        self.ptr = ptr
        self.n = n
        self.has_tsp_score = has_tsp_score
        # blob -> str builder: plain memcpy when the ctx proves every
        # emitted byte ASCII, else the UTF-8-validating decode
        all_ascii = lib.ctx_all_ascii(ptr)
        self.take = (take_sized_string_ascii if all_ascii
                     else take_sized_string)
        # arena variant (no free; ctx_decode_chunk's arena is released
        # in one chunk_arena_free after the whole chunk's strs exist)
        self.peek = peek_string_ascii if all_ascii else peek_string

    def __del__(self):
        if self.ptr:
            self.lib.codec_ctx_free(self.ptr)
            self.ptr = None


def _i32p(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def _i64p(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _u8p(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def encode_filter(ctx: _NativeCtx, codes: np.ndarray, active: np.ndarray) -> str:
    codes = np.ascontiguousarray(codes, dtype=np.int32)
    active = np.ascontiguousarray(active, dtype=np.uint8)
    out_len = ctypes.c_int64()
    ptr = ctx.lib.ctx_encode_filter(ctx.ptr, _i32p(codes), _u8p(active),
                                    ctypes.byref(out_len))
    return ctx.take(ctx.lib, ptr, out_len.value)


def encode_scores(ctx: _NativeCtx, values: np.ndarray, sskip: np.ndarray,
                  feasible: np.ndarray) -> str:
    values = np.ascontiguousarray(values, dtype=np.int64)
    sskip = np.ascontiguousarray(sskip, dtype=np.uint8)
    feasible = np.ascontiguousarray(feasible, dtype=np.uint8)
    out_len = ctypes.c_int64()
    ptr = ctx.lib.ctx_encode_scores(ctx.ptr, _i64p(values), _u8p(sskip),
                                    _u8p(feasible), ctypes.byref(out_len))
    return ctx.take(ctx.lib, ptr, out_len.value)


def _tsp_ignored_cached(rr, ci: int, c: int):
    """PodTopologySpread's [C, N] score-ignore mask for compact chunk ci,
    cached on the ReplayResult (shared by the per-pod fused path and the
    chunk call; double-checked under the recon lock so a chunk boundary
    doesn't make every pool worker recompute the O(C*N) mask at once)."""
    cache = getattr(rr, "_fused_ignored", None)
    if cache is None or cache[0] != ci:
        with rr._recon_lock:
            cache = getattr(rr, "_fused_ignored", None)
            if cache is None or cache[0] != ci:
                ig = np.ascontiguousarray(
                    rr._tsp_ignored_chunk(ci, c, rr.cw.n_nodes), np.uint8)
                cache = (ci, ig)
                rr._fused_ignored = cache
    return cache[1]


class _ChunkHandle:
    """An in-flight ctx_decode_chunk result: the arena pointer plus the
    per-pod blob address/length arrays.  decode_chunk_take() turns it
    into strs and frees the arena; dropping it without take leaks the
    arena (callers always pair the two)."""

    __slots__ = ("ctx", "arena", "out_ptrs", "out_lens", "out_wptrs",
                 "out_wlens", "skip", "c", "thread_seconds",
                 "failed_entries", "_keep")

    def __init__(self, ctx, arena, out_ptrs, out_lens, out_wptrs, out_wlens,
                 skip, c, thread_seconds, failed_entries, keep):
        self.ctx = ctx
        self.arena = arena
        self.out_ptrs = out_ptrs
        self.out_lens = out_lens
        # slot (3 * pod + blob) -> the heavy blob's wire form, in the
        # arena like the blob (0 = none)
        self.out_wptrs = out_wptrs
        self.out_wlens = out_wlens
        self.skip = skip
        self.c = c
        self.thread_seconds = thread_seconds
        # refusals the C side rendered into the range's filter blobs
        self.failed_entries = failed_entries
        self._keep = keep

    def discard(self) -> None:
        """Free the arena without building any strings — the error-path
        cleanup (decode_chunk_take does this in its finally on the
        normal path)."""
        if self.arena is not None:
            self.ctx.lib.chunk_arena_free(self.arena)
            self.arena = None


def decode_chunk_start(ctx: _NativeCtx, rr, lo: int, hi: int,
                       skip=None, n_threads: int | None = None) -> _ChunkHandle:
    """The GIL-released half of the chunk decode: one ctx_decode_chunk
    call covering pods lo..hi (a range inside ONE compact replay chunk).
    The C side iterates the pods with its worker pool and emits every
    pod's three heavy blobs into a per-call arena.  Runs fine on a helper
    thread (ctypes drops the GIL for the call).

    skip: optional [hi-lo] uint8 — pods Python's prefilter-reject
    early-out owns; the C side leaves their slots empty."""
    from ..framework.pipeline import PACK_MODES
    from ..utils.platform import effective_cpu_count

    cc = rr._compact
    c = hi - lo
    ci, r_lo = divmod(lo, cc.chunk)
    # cc.host(): device-resident chunks materialize here — the memoized
    # D2H this read path exists to defer (framework/replay.py)
    packed = cc.host("packed", ci)
    if not packed.flags["C_CONTIGUOUS"]:
        # device-layout fetch (TPU backends can return strided host
        # arrays); the C codec walks raw pointers in C order
        packed = cc.packed[ci] = np.ascontiguousarray(packed)
    code_bits = PACK_MODES[cc.pack_mode][1]
    n = ctx.n
    elem = packed.dtype.itemsize
    packed_ptr = packed.ctypes.data + r_lo * n * elem

    active_rows, sskip_rows = pass_rows(rr.cw)
    active = active_rows[lo:hi]   # [c, F], contiguous row slice
    sskip = sskip_rows[lo:hi]     # [c, S]
    want = np.ascontiguousarray(
        np.asarray(rr.feasible_count[lo:hi]) > 1, np.uint8)

    s = len(cc.score_cols)
    col_base = (ctypes.c_void_p * max(s, 1))()
    col_stride = (ctypes.c_int64 * max(s, 1))()
    col_elem = (ctypes.c_int32 * max(s, 1))()
    keep_alive = [packed, active, sskip, want]
    any_scores = bool(want.any())
    if any_scores and s:
        static_rows = rr.cw.host.get("static_score_rows", {})
        for q, (group, row) in enumerate(cc.score_cols):
            if group == "host":
                # precompiled host-resident raw ([P, N] C-contiguous);
                # sskip'd scorers are never read by the C codec, so the
                # unmasked rows are safe to hand over
                src = static_rows[row]
                if not src.flags["C_CONTIGUOUS"]:
                    src = static_rows[row] = np.ascontiguousarray(src)
                keep_alive.append(src)
                e = src.dtype.itemsize
                col_base[q] = src.ctypes.data + lo * n * e
                col_stride[q] = n * e
                col_elem[q] = e
            else:
                arr = cc.host(group, ci)       # [C, S_g, N]
                if not arr.flags["C_CONTIGUOUS"]:
                    arr = np.ascontiguousarray(arr)
                    getattr(cc, group)[ci] = arr
                keep_alive.append(arr)
                e = arr.dtype.itemsize
                col_base[q] = arr.ctypes.data + (r_lo * arr.shape[1] + row) * n * e
                col_stride[q] = arr.shape[1] * n * e
                col_elem[q] = e

    ig_ptr = None
    if (any_scores and ctx.has_tsp_score
            and rr.cw.host.get("tsp_ignore") is not None):
        ig = _tsp_ignored_cached(rr, ci, packed.shape[0])
        ig_rows = ig[r_lo:r_lo + c]
        keep_alive.append(ig_rows)
        ig_ptr = _u8p(ig_rows)

    out_ptrs = np.zeros(c * 3, np.int64)
    out_lens = np.zeros(c * 3, np.int64)
    out_wptrs = np.zeros(c * 3, np.int64)
    out_wlens = np.zeros(c * 3, np.int64)
    tsec = ctypes.c_double()
    failed = ctypes.c_int64()
    if n_threads is None:
        n_threads = min(8, effective_cpu_count())
    if skip is not None:
        keep_alive.append(skip)
    arena = ctx.lib.ctx_decode_chunk(
        ctx.ptr, c,
        ctypes.c_void_p(packed_ptr), elem, code_bits,
        _u8p(active), _u8p(sskip),
        col_base, col_stride, col_elem,
        ig_ptr, _u8p(want), _u8p(skip) if skip is not None else None,
        n_threads,
        _i64p(out_ptrs), _i64p(out_lens),
        # a call hands out what the registry has room for: a burst's
        # forms whole, beside those no event has spliced yet; a 512-pod
        # chunk's up to the unread ceiling, or they would push each
        # other out unread
        wireform.WIRE_MIN_LEN, wireform.WIRE_FORMS.decode_budget(),
        _i64p(out_wptrs), _i64p(out_wlens),
        ctypes.byref(tsec), ctypes.byref(failed))
    return _ChunkHandle(ctx, arena, out_ptrs, out_lens, out_wptrs, out_wlens,
                        skip, c, float(tsec.value), int(failed.value),
                        keep_alive)


def decode_chunk_take(handle: _ChunkHandle) -> list:
    """Blob strs from a decode_chunk_start handle; frees the arena.
    triples[i] is (filter_json, score_json | None, finalscore_json |
    None), or None where the skip mask was set.  A heavy blob's wire
    form is kept under the str's identity (utils/wireform.py)."""
    ctx = handle.ctx
    peek = ctx.peek
    skip = handle.skip
    out_ptrs, out_lens = handle.out_ptrs, handle.out_lens
    out_wptrs, out_wlens = handle.out_wptrs, handle.out_wlens
    any_wire = bool(out_wptrs.any())
    wired: list = []
    try:
        triples: list = []
        for i in range(handle.c):
            if skip is not None and skip[i]:
                triples.append(None)
                continue
            b = 3 * i
            fj = peek(int(out_ptrs[b]), int(out_lens[b]))
            sj = (peek(int(out_ptrs[b + 1]), int(out_lens[b + 1]))
                  if out_ptrs[b + 1] else None)
            fnj = (peek(int(out_ptrs[b + 2]), int(out_lens[b + 2]))
                   if out_ptrs[b + 2] else None)
            triples.append((fj, sj, fnj))
            if any_wire:
                wired.append([
                    (blob, ctypes.string_at(int(out_wptrs[b + k]),
                                            int(out_wlens[b + k])))
                    for k, blob in enumerate((fj, sj, fnj))
                    if out_wptrs[b + k]])
    finally:
        handle.discard()
    wireform.keep_native(wired)
    return triples


def decode_pod_fused(ctx: _NativeCtx, rr, i: int, hi: int,
                     want_scores: bool) -> tuple[str, str | None, str | None, int]:
    """(filter-result, score-result, finalscore-result, refusals rendered
    into filter-result) for pod i straight from the compact replay layout
    — one C call; no [F,N] code unpack, no
    int64 raw/final materialization, normalization computed in C
    (hostnorm mirror, asserted byte-identical by tests/test_native_codec.py).

    i indexes the compact chunks; hi indexes the workload's per-pod host
    tables (they differ only on the extender's single-row replays, which
    never take this path)."""
    from ..framework.pipeline import PACK_MODES

    cc = rr._compact
    ci, r = divmod(i, cc.chunk)
    # cc.host(): device-resident chunks materialize here (memoized D2H)
    packed = cc.host("packed", ci)
    if not packed.flags["C_CONTIGUOUS"]:
        # device-layout fetch (TPU backends can return strided host
        # arrays); the C codec walks raw pointers in C order
        packed = cc.packed[ci] = np.ascontiguousarray(packed)
    code_bits = PACK_MODES[cc.pack_mode][1]
    prow = packed[r]

    s = len(cc.score_cols)
    col_ptrs = (ctypes.c_void_p * s)()
    col_elem = (ctypes.c_int32 * s)()
    cols_alive = []
    if want_scores:
        static_rows = rr.cw.host.get("static_score_rows", {})
        for q, (group, row) in enumerate(cc.score_cols):
            if group == "host":
                # precompiled host-resident raw ([P, N] C-contiguous
                # numpy); sskip'd scorers are never read by the C codec,
                # so the unmasked row is safe to hand over
                src = static_rows[row]
                col = src[hi]
                cols_alive.append(col)
                col_ptrs[q] = col.ctypes.data
                col_elem[q] = src.dtype.itemsize
                continue
            arr = cc.host(group, ci)
            if not arr.flags["C_CONTIGUOUS"]:
                arr = np.ascontiguousarray(arr)
                getattr(cc, group)[ci] = arr
            col = arr[r, row]
            cols_alive.append(col)
            col_ptrs[q] = col.ctypes.data
            col_elem[q] = arr.dtype.itemsize

    ignored_ptr = None
    if want_scores and ctx.has_tsp_score and rr.cw.host.get("tsp_ignore") is not None:
        ig_row = _tsp_ignored_cached(rr, ci, packed.shape[0])[r]
        ignored_ptr = _u8p(ig_row)

    active_rows, sskip_rows = pass_rows(rr.cw)
    out_blobs = (ctypes.c_void_p * 3)()
    out_lens = (ctypes.c_int64 * 3)()
    out_wire = (ctypes.c_void_p * 3)()
    out_wire_lens = (ctypes.c_int64 * 3)()
    failed_entries = ctx.lib.ctx_decode_pod(
        ctx.ptr,
        prow.ctypes.data_as(ctypes.c_void_p), packed.dtype.itemsize, code_bits,
        _u8p(active_rows[hi]), _u8p(sskip_rows[hi]),
        col_ptrs, col_elem, ignored_ptr, 1 if want_scores else 0,
        out_blobs, out_lens,
        # one result: inside any decode budget (WireForms.decode_budget)
        wireform.WIRE_MIN_LEN, out_wire, out_wire_lens,
    )
    wired = [(k, take_sized_bytes(ctx.lib, out_wire[k], out_wire_lens[k]))
             for k in range(3) if out_wire[k]]
    blobs = [ctx.take(ctx.lib, out_blobs[k], out_lens[k])
             if out_blobs[k] else None for k in range(3)]
    wireform.keep_native([[(blobs[k], wire) for k, wire in wired]])
    return blobs[0], blobs[1], blobs[2], failed_entries


def encode_string_map(d: dict[str, str]) -> str | None:
    """marshal(d) for a flat str->str dict via the native escape pass —
    the result-history record encoder.  None when the codec is
    unavailable (caller falls back to the Python marshal).

    The str is built in ONE sized copy (memmove when the C side proves
    the output pure ASCII): the record is re-encoded once per pod per
    wave over ~250KB of blob values, so the NUL-scan + bytes round-trip
    of the plain take_string path was a real slice of commit time."""
    lib = get_lib()
    if lib is None:
        return None
    items = sorted(d.items())
    keys = _c_str_array([k.encode() for k, _ in items])
    vals_b = [v.encode() for _, v in items]
    vals = _c_str_array(vals_b)
    lens = (ctypes.c_longlong * len(items))(*[len(b) for b in vals_b])
    out_len = ctypes.c_longlong()
    ascii_only = ctypes.c_int32()
    ptr = lib.encode_string_map_sized(keys, vals, lens, len(items),
                                      ctypes.byref(out_len),
                                      ctypes.byref(ascii_only))
    take = take_sized_string_ascii if ascii_only.value else take_sized_string
    return take(lib, ptr, out_len.value)
