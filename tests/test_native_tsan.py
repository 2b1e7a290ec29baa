"""Concurrent chunk decode under ThreadSanitizer (slow; `make test-tsan`).

The chunk-granular decoder owns real hand-rolled concurrency: a
persistent work-stealing worker pool, per-call output arenas, and
per-thread FilterCaches that survive across chunks; and one codec
context is read by every pass on its node table at once (PR 41).  This
runs the 4-thread concurrent-chunk soak from test_chunk_decode.py and
the two-passes-on-one-context soak from test_codec_ctx_carry.py against
a `-fsanitize=thread` build of the codec in a subprocess, with the TSan
runtime preloaded ahead of an uninstrumented Python.

Two harness accommodations keep the check honest (see
kube_scheduler_simulator_tpu/native/tsan_suppressions.txt):
KSS_TPU_TSAN_LOCALIZE=1 makes the soaks fetch and copy the replay buffers
to main-thread-owned memory first (preload-TSan cannot see jax's device
sync, so codec reads of XLA-allocated pages would all report), and the
suppressions file silences XLA's own internally-synchronized thread
pool.  Races between codec threads have no frames in either and fail
the subprocess.
"""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

_SUITE = [
    "tests/test_chunk_decode.py::test_chunk_decode_threaded_soak",
    # one context, two passes, four threads: the C side only reads it
    "tests/test_codec_ctx_carry.py"
    "::test_two_threads_decoding_two_passes_at_once_give_the_serial_bytes",
    # a read and a pump racing on one pod, four pods at once: the arena
    # holds the wire forms beside the blobs (PR 45)
    "tests/test_wire_form.py::test_racing_read_and_pump_soak",
]

_SUPPRESSIONS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "kube_scheduler_simulator_tpu", "native", "tsan_suppressions.txt")


def _toolchain_lib(name: str) -> str | None:
    try:
        out = subprocess.run(["gcc", f"-print-file-name={name}"],
                             capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.TimeoutExpired):
        return None
    path = (out.stdout or "").strip()
    return path if path and os.path.isabs(path) and os.path.exists(path) else None


def test_chunk_decode_soak_under_tsan(tmp_path):
    from kube_scheduler_simulator_tpu.native import TSAN_FLAGS, build_codec

    libtsan = _toolchain_lib("libtsan.so")
    # libstdc++ must be preloaded too (same reason as the ASan harness):
    # TSan resolves its __cxa_throw interceptor at init, and an
    # uninstrumented Python only maps libstdc++ with the first C++
    # extension — without it jaxlib's first throw aborts the process
    libstdcpp = _toolchain_lib("libstdc++.so.6")
    if libtsan is None or libstdcpp is None:
        pytest.skip("no libtsan/libstdc++ on this toolchain")
    so = str(tmp_path / "_annotation_codec_tsan.so")
    try:
        build_codec(so, extra_flags=TSAN_FLAGS)
    except subprocess.CalledProcessError as e:
        pytest.skip(f"TSan build unavailable: {e.stderr!r:.200}")

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(
        os.environ,
        KSS_TPU_NATIVE_SO=so,
        KSS_TPU_TSAN_LOCALIZE="1",
        LD_PRELOAD=f"{libtsan} {libstdcpp}",
        TSAN_OPTIONS=(
            "halt_on_error=1:report_thread_leaks=0:exitcode=66:"
            f"suppressions={_SUPPRESSIONS}"),
        JAX_PLATFORMS="cpu",
    )
    r = subprocess.run(
        [sys.executable, "-m", "pytest", *_SUITE,
         "-q", "-p", "no:cacheprovider"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=1800)
    tail = (r.stdout + "\n" + r.stderr)[-4000:]
    if r.returncode == 66:
        pytest.fail(f"ThreadSanitizer reported a race in the codec:\n{tail}")
    assert r.returncode == 0, f"soak under TSan failed:\n{tail}"
