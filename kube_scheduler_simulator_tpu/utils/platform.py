"""Host-platform helpers: CPU forcing for tests, malloc huge pages.

Stock JAX honours `JAX_PLATFORMS=cpu` by itself, so process mains need
no guard.  force_cpu() is for callers that must pin the CPU backend from
code (tests/conftest.py, the multi-chip dry run on virtual devices).
"""

from __future__ import annotations

import os


def force_cpu(n_virtual_devices: int | None = None) -> None:
    """Pin the CPU backend, optionally split into n virtual devices.
    Call before any jax computation: both settings are read when the
    backend initialises."""
    if n_virtual_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={n_virtual_devices}".strip()
            )
    import jax

    jax.config.update("jax_platforms", "cpu")


def effective_cpu_count() -> int:
    """CPUs actually usable by THIS process: the scheduler affinity mask
    (cgroup cpusets / taskset) when available, else os.cpu_count().
    os.cpu_count() alone reports host logical cores, so a 1-CPU container
    on an 8-core host would wrongly enable the multi-core code paths."""
    try:
        return len(os.sched_getaffinity(0)) or 1
    except (AttributeError, OSError):
        return os.cpu_count() or 1


def ensure_malloc_hugepages() -> bool:
    """Re-exec this process once with GLIBC_TUNABLES=glibc.malloc.hugetlb=1
    so glibc madvise(MADV_HUGEPAGE)s its arenas.

    The annotation product is tens of GB of live strings at the full
    benchmark shape; with 4 KiB pages the first touch of every page is a
    fault, and the one-core CPU host this was tuned on collapsed to
    ~200 MB/s fault bandwidth past ~8 GB resident.  THP cuts faults
    ~512x; what it buys the served path on the chip's host: not
    measured (ROADMAP C5).  The tunable is only read by glibc at process
    start, hence the re-exec; callers must invoke this FIRST in main(),
    before heavy imports.  Returns False when already active or not
    applicable (non-Linux, THP 'never', KSS_NO_HUGEPAGE_REEXEC=1) — on
    success the process is replaced and the call never returns."""
    import sys

    if not sys.platform.startswith("linux"):
        return False
    cur = os.environ.get("GLIBC_TUNABLES", "")
    if ("glibc.malloc.hugetlb" in cur
            or os.environ.get("KSS_NO_HUGEPAGE_REEXEC") == "1"):
        return False
    try:
        with open("/sys/kernel/mm/transparent_hugepage/enabled") as f:
            if "[never]" in f.read():
                return False
    except OSError:
        return False
    env = dict(os.environ)
    env["GLIBC_TUNABLES"] = ((cur + ":") if cur else "") + "glibc.malloc.hugetlb=1"
    env["KSS_NO_HUGEPAGE_REEXEC"] = "1"  # belt+braces against exec loops
    # `python -m pkg.mod` must re-exec as -m (argv[0] is the module FILE,
    # and running it directly breaks the package's relative imports)
    main_spec = getattr(sys.modules.get("__main__"), "__spec__", None)
    if main_spec is not None and main_spec.name:
        argv = [sys.executable, "-m", main_spec.name] + sys.argv[1:]
    else:
        argv = [sys.executable] + sys.argv
    try:
        os.execve(sys.executable, argv, env)
    except OSError:
        return False
