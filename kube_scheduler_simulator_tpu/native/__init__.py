"""ctypes loader for the native annotation codec.

Builds annotation_codec.cpp with g++ on first use (cached next to the
source).  When the build or the load fails the decode ladder still
serves from the pure-Python encoder (the parity reference, ~25x
slower) — but loudly: get_lib() prints the compiler's error and counts
`native_codec_load_failures_total`.  See annotation_codec.cpp for the
encoding contract.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import tempfile
import threading

_lock = threading.Lock()
_lib = None
_tried = False


BUILD_CMD = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-pthread"]

# `make native-asan` recipe: a sanitizer build of the same source for the
# slow codec-suite-under-ASan test (tests/test_native_asan.py)
ASAN_FLAGS = ["-g", "-fsanitize=address,undefined",
              "-fno-sanitize-recover=undefined"]

# `make native-tsan` recipe: ThreadSanitizer build for the concurrent
# chunk-decode soak (tests/test_native_tsan.py) — the codec's worker
# pool, per-call arenas and cross-chunk FilterCaches are exactly the
# kind of hand-rolled concurrency TSan exists for
TSAN_FLAGS = ["-g", "-fsanitize=thread"]


def build_codec(so: str | None = None,
                extra_flags: list[str] | tuple[str, ...] = ()) -> str:
    """Compile annotation_codec.cpp -> _annotation_codec.so (the recipe
    `make codec` runs); returns the .so path.  The compiler writes to a
    temporary name that is renamed into place, so a process starting
    beside the builder (server + standalone scheduler) either sees no
    library and builds its own, or loads a whole one."""
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, "annotation_codec.cpp")
    so = so or os.path.join(here, "_annotation_codec.so")
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(so), prefix=".codec-",
                               suffix=".so")
    os.close(fd)
    try:
        subprocess.run([*BUILD_CMD, *extra_flags, "-o", tmp, src],
                       check=True, capture_output=True)
        os.chmod(tmp, 0o755)  # mkstemp's 0600 would outlive the rename
        os.replace(tmp, so)
    except BaseException:
        os.unlink(tmp)
        raise
    return so


def _build_and_load():
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, "annotation_codec.cpp")
    # KSS_TPU_NATIVE_SO points the loader at a prebuilt library (the
    # sanitizer harness runs the suite against the ASan build this way);
    # no rebuild-if-stale in that mode — the harness owns the artifact
    override = os.environ.get("KSS_TPU_NATIVE_SO")
    if override:
        lib = ctypes.CDLL(override)
    else:
        so = os.path.join(here, "_annotation_codec.so")
        if not os.path.exists(so) or os.path.getmtime(so) < os.path.getmtime(src):
            build_codec(so)
        try:
            lib = ctypes.CDLL(so)
        except OSError:
            # stale or foreign-platform binary: rebuild from source
            build_codec(so)
            lib = ctypes.CDLL(so)
    P = ctypes.POINTER
    lib.encode_filter_result.restype = ctypes.c_void_p
    lib.encode_filter_result.argtypes = [
        ctypes.c_int32, ctypes.c_int32,
        P(ctypes.c_int32), P(ctypes.c_uint8),
        P(ctypes.c_char_p), P(ctypes.c_char_p),
        P(ctypes.c_int32), P(ctypes.c_int32),
        P(ctypes.c_char_p), P(ctypes.c_int32), P(ctypes.c_uint8),
    ]
    lib.encode_score_result.restype = ctypes.c_void_p
    lib.encode_score_result.argtypes = [
        ctypes.c_int32, ctypes.c_int32,
        P(ctypes.c_int64), P(ctypes.c_uint8), P(ctypes.c_uint8),
        P(ctypes.c_char_p), P(ctypes.c_char_p),
        P(ctypes.c_int32), P(ctypes.c_int32),
    ]
    lib.codec_free.restype = None
    lib.codec_free.argtypes = [ctypes.c_void_p]
    lib.encode_string_map.restype = ctypes.c_void_p
    lib.encode_string_map.argtypes = [
        P(ctypes.c_char_p), P(ctypes.c_char_p),
        P(ctypes.c_longlong), ctypes.c_longlong,
    ]
    lib.encode_string_map_sized.restype = ctypes.c_void_p
    lib.encode_string_map_sized.argtypes = [
        P(ctypes.c_char_p), P(ctypes.c_char_p),
        P(ctypes.c_longlong), ctypes.c_longlong,
        P(ctypes.c_longlong), P(ctypes.c_int32),
    ]
    lib.codec_ctx_new.restype = ctypes.c_void_p
    lib.codec_ctx_new.argtypes = [
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        P(ctypes.c_char_p), P(ctypes.c_char_p), P(ctypes.c_char_p),
        P(ctypes.c_int32), P(ctypes.c_int32), P(ctypes.c_int32),
        P(ctypes.c_char_p), P(ctypes.c_int32), P(ctypes.c_uint8),
        P(ctypes.c_int32), P(ctypes.c_int64), ctypes.c_int64,
    ]
    lib.ctx_decode_pod.restype = ctypes.c_int32
    lib.ctx_decode_pod.argtypes = [
        ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32,
        P(ctypes.c_uint8), P(ctypes.c_uint8),
        P(ctypes.c_void_p), P(ctypes.c_int32),
        P(ctypes.c_uint8),
        ctypes.c_int32,
        P(ctypes.c_void_p), P(ctypes.c_int64),
        ctypes.c_int64, P(ctypes.c_void_p), P(ctypes.c_int64),
    ]
    lib.ctx_decode_chunk.restype = ctypes.c_void_p
    lib.ctx_decode_chunk.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int32,
        ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32,
        P(ctypes.c_uint8), P(ctypes.c_uint8),
        P(ctypes.c_void_p), P(ctypes.c_int64), P(ctypes.c_int32),
        P(ctypes.c_uint8), P(ctypes.c_uint8), P(ctypes.c_uint8),
        ctypes.c_int32,
        P(ctypes.c_int64), P(ctypes.c_int64),
        ctypes.c_int64, ctypes.c_int64,
        P(ctypes.c_int64), P(ctypes.c_int64),
        P(ctypes.c_double), P(ctypes.c_int64),
    ]
    lib.chunk_arena_free.restype = None
    lib.chunk_arena_free.argtypes = [ctypes.c_void_p]
    lib.codec_ctx_free.restype = None
    lib.codec_ctx_free.argtypes = [ctypes.c_void_p]
    lib.ctx_all_ascii.restype = ctypes.c_int32
    lib.ctx_all_ascii.argtypes = [ctypes.c_void_p]
    lib.ctx_encode_filter.restype = ctypes.c_void_p
    lib.ctx_encode_filter.argtypes = [
        ctypes.c_void_p, P(ctypes.c_int32), P(ctypes.c_uint8),
        P(ctypes.c_int64)]
    lib.ctx_encode_scores.restype = ctypes.c_void_p
    lib.ctx_encode_scores.argtypes = [
        ctypes.c_void_p, P(ctypes.c_int64), P(ctypes.c_uint8), P(ctypes.c_uint8),
        P(ctypes.c_int64)]
    return lib


# str straight from the C buffer: PyUnicode_DecodeUTF8 builds the
# (compact-ASCII) str object in ONE copy, where string_at(...).decode()
# would materialize an intermediate bytes object first — at ~1.3 MB of
# JSON per pod the extra pass is real memory traffic on the decode path
try:
    _PyUnicode_DecodeUTF8 = ctypes.pythonapi.PyUnicode_DecodeUTF8
    _PyUnicode_DecodeUTF8.restype = ctypes.py_object
    _PyUnicode_DecodeUTF8.argtypes = [
        ctypes.c_void_p, ctypes.c_ssize_t, ctypes.c_char_p]
except (AttributeError, OSError):  # non-CPython / no libpython symbols:
    _PyUnicode_DecodeUTF8 = None   # keep the module's graceful fallback


def take_sized_string(lib, ptr, length: int) -> str:
    """One-copy str from a codec-allocated buffer of known length; frees
    the buffer."""
    try:
        if _PyUnicode_DecodeUTF8 is not None:
            return _PyUnicode_DecodeUTF8(ptr, length, b"strict")
        return ctypes.string_at(ptr, length).decode()
    finally:
        lib.codec_free(ptr)


# ASCII fast path: when the codec context proves every emitted byte is
# ASCII (ctx_all_ascii), the str can be built by PyUnicode_New + memmove —
# a plain vectorized copy instead of DecodeUTF8's validating scan.  The
# data offset of a compact-ASCII str is derived at runtime
# (sys.getsizeof("") counts PyASCIIObject + the NUL) and the whole path is
# self-tested once at import; any surprise falls back to the decode path.
_ASCII_TAKE_OK = False
try:
    import sys as _sys

    _PyUnicode_New = ctypes.pythonapi.PyUnicode_New
    _PyUnicode_New.restype = ctypes.py_object
    _PyUnicode_New.argtypes = [ctypes.c_ssize_t, ctypes.c_uint32]
    _ASCII_DATA_OFF = _sys.getsizeof("") - 1

    def _ascii_take(ptr, length: int) -> str:
        if length == 0:
            return ""  # PyUnicode_New(0, ...) returns the shared singleton
        s = _PyUnicode_New(length, 127)
        # copy exactly `length` bytes: PyUnicode_New already wrote the
        # NUL terminator at data[length], so the source needn't be
        # NUL-terminated (the old length+1 memmove silently imposed that
        # on every C buffer crossing this boundary — and read one byte
        # past buffers that weren't)
        ctypes.memmove(id(s) + _ASCII_DATA_OFF, ptr, length)
        return s

    # probe with trailing GARBAGE (not NUL) after the payload: proves both
    # the content copy and that PyUnicode_New supplied the terminator
    _probe = b"probe{\"x\":\"1\"}"
    _buf = (ctypes.c_char * (len(_probe) + 1)).from_buffer_copy(_probe + b"X")
    _out = _ascii_take(ctypes.addressof(_buf), len(_probe))
    _ASCII_TAKE_OK = (
        _out == _probe.decode()
        and ctypes.string_at(id(_out) + _ASCII_DATA_OFF, len(_probe) + 1)
        == _probe + b"\x00")
except Exception:
    _ASCII_TAKE_OK = False


def take_sized_string_ascii(lib, ptr, length: int) -> str:
    """take_sized_string for buffers PROVEN pure-ASCII by the codec ctx."""
    if not _ASCII_TAKE_OK:
        return take_sized_string(lib, ptr, length)
    try:
        return _ascii_take(ptr, length)
    finally:
        lib.codec_free(ptr)


# Arena string takers — str from an (address, length) pair WITHOUT
# freeing: ctx_decode_chunk's blobs live in a per-call arena released by
# ONE chunk_arena_free after every pod's strs are built, so the takers
# only copy.  peek_string_ascii is the plain-memcpy path for contexts
# proven pure-ASCII; peek_string is the UTF-8-validating fallback.

def peek_string(addr: int, length: int) -> str:
    if _PyUnicode_DecodeUTF8 is not None:
        return _PyUnicode_DecodeUTF8(addr, length, b"strict")
    return ctypes.string_at(addr, length).decode()


def peek_string_ascii(addr: int, length: int) -> str:
    if not _ASCII_TAKE_OK:
        return peek_string(addr, length)
    return _ascii_take(addr, length)


def take_sized_bytes(lib, ptr, length: int) -> bytes:
    """take_sized_string for a buffer that is wanted as bytes (a wire
    form): one copy, and the buffer freed."""
    try:
        return ctypes.string_at(ptr, length)
    finally:
        lib.codec_free(ptr)


def get_lib():
    """The loaded codec, or None when the native build is unavailable —
    reported once on stderr and counted, never silent."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is None and not _tried:
            _tried = True
            try:
                # the g++ build/dlopen runs under the module lock ON
                # PURPOSE: concurrent first users must block until the
                # one-shot build lands rather than race the compiler
                _lib = _build_and_load()  # kss-analyze: allow(blocking-under-lock)
            except Exception as e:
                from ..utils.tracing import TRACER

                TRACER.count("native_codec_load_failures_total")
                stderr = getattr(e, "stderr", None) or b""
                print("ERROR: native annotation codec unavailable "
                      f"({type(e).__name__}: {e}); annotations decode in "
                      "pure Python, ~25x slower\n"
                      + stderr.decode(errors="replace")[-2000:],
                      file=sys.stderr, flush=True)
    return _lib


def take_string(lib, ptr) -> str:
    """Copy a codec-allocated C string and free it."""
    try:
        return ctypes.string_at(ptr).decode()
    finally:
        lib.codec_free(ptr)
