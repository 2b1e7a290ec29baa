"""Codec suite under AddressSanitizer + UBSan (slow; `make test-asan`).

The C++ surface of the annotation codec keeps growing (per-pod fused
decode, chunk-granular decode with a worker pool and arena) and hands raw
pointers across the ctypes boundary; this runs the whole codec/chunk test
suite against a `-fsanitize=address,undefined` build of the library in a
subprocess (KSS_TPU_NATIVE_SO points the loader at the sanitizer build,
LD_PRELOAD injects the ASan runtime ahead of an uninstrumented Python).
Any heap overflow / UB the normal suite would silently survive fails the
subprocess here.
"""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

_SUITE = ["tests/test_native_codec.py", "tests/test_chunk_decode.py",
          # a context shared by passes and freed with its table (PR 41):
          # a use after free or a double free shows here
          "tests/test_codec_ctx_carry.py"]


def _toolchain_lib(name: str) -> str | None:
    try:
        out = subprocess.run(["gcc", f"-print-file-name={name}"],
                             capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.TimeoutExpired):
        return None
    path = (out.stdout or "").strip()
    return path if path and os.path.isabs(path) and os.path.exists(path) else None


def test_codec_suite_under_asan(tmp_path):
    from kube_scheduler_simulator_tpu.native import ASAN_FLAGS, build_codec

    libasan = _toolchain_lib("libasan.so")
    # libstdc++ must be in the preload set too: ASan resolves its
    # __cxa_throw interceptor at init, and an uninstrumented Python only
    # loads libstdc++ with the first C++ extension — without it, the
    # first C++ exception out of jaxlib aborts on a null real_cxa_throw
    libstdcpp = _toolchain_lib("libstdc++.so.6")
    if libasan is None or libstdcpp is None:
        pytest.skip("no libasan/libstdc++ on this toolchain")
    so = str(tmp_path / "_annotation_codec_asan.so")
    try:
        build_codec(so, extra_flags=ASAN_FLAGS)
    except subprocess.CalledProcessError as e:
        pytest.skip(f"sanitizer build unavailable: {e.stderr!r:.200}")

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(
        os.environ,
        KSS_TPU_NATIVE_SO=so,
        LD_PRELOAD=f"{libasan} {libstdcpp}",
        # Python "leaks" interned state by design; halt hard on real UB
        ASAN_OPTIONS="detect_leaks=0:abort_on_error=1",
        UBSAN_OPTIONS="halt_on_error=1:print_stacktrace=1",
        JAX_PLATFORMS="cpu",
    )
    r = subprocess.run(
        [sys.executable, "-m", "pytest", *_SUITE, "-q", "-p",
         "no:cacheprovider"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=1800)
    tail = (r.stdout + "\n" + r.stderr)[-4000:]
    assert r.returncode == 0, f"codec suite under ASan failed:\n{tail}"
