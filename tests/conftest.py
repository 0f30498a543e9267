"""Test configuration: hermetic multi-device CPU JAX.

All tests run on an 8-virtual-device CPU platform so sharding/mesh code
exercises real multi-device paths without TPU hardware (mirrors how the
reference tests multi-node behaviour in-process,
/root/reference/testing/simulator).

The CPU pin below is for the suite only: nothing under lighthouse_tpu/
picks a platform, and the chip is reached through chip_smoke.py.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

# XLA:CPU mmaps >60k regions compiling this suite's fused programs; past
# vm.max_map_count the process segfaults in whatever XLA path is active.
# Raise the ceiling up front (root-only; best effort elsewhere).
from lighthouse_tpu.common import compile_cache  # noqa: E402
from lighthouse_tpu.ops import cache_guard  # noqa: E402

cache_guard.ensure_map_headroom()

jax.config.update("jax_platforms", "cpu")
# persistent compile cache: the BLS12-381 Miller program costs ~1 min of
# XLA compile; cache it across test runs (one rule, common/compile_cache)
compile_cache.configure()


def pytest_runtestloop(session):
    """Per-file process isolation for multi-file suite runs.

    A single long-lived process that JIT-loads every executable the suite
    compiles crosses the kernel's vm.max_map_count ceiling (~test 167 of
    571 on this image at the 65,530 default) and the next XLA compile
    segfaults inside mmap; in-process cache clearing (the module fixture
    below) only delays the ceiling and was judged not to hold.  The
    PRIMARY fix is cache_guard.ensure_map_headroom() above (raise the
    ceiling 4x); per-file children remain as defense in depth — they
    also bound each process's RSS on this 1-core box and keep one bad
    file from killing the whole run.  So when one pytest invocation
    spans more than one test file, each file's selected tests run in a
    short-lived child process — `pytest tests` stays the reference's
    one-command UX (/root/reference/Makefile:105-119) while every child
    stays far below the map ceiling.  Single-file invocations (and the
    children themselves, marked by LHTPU_ISOLATED) run in-process as
    usual.  The persistent .jax_cache keeps re-compiles across children
    cheap.
    """
    if os.environ.get("LHTPU_ISOLATED") == "1":
        return None  # already inside a per-file child
    if session.config.getoption("collectonly", default=False):
        return None
    by_file: dict[str, list] = {}
    for item in session.items:
        by_file.setdefault(str(item.path), []).append(item)
    if len(by_file) <= 1:
        return None

    import re
    import subprocess
    import sys
    import time

    env = dict(os.environ)
    env["LHTPU_ISOLATED"] = "1"
    rootdir = str(session.config.rootpath)
    # -x / --maxfail store into the `maxfail` dest (0 = unlimited)
    maxfail = int(session.config.getoption("maxfail", default=0) or 0)
    # forward the user-visible run options children would otherwise lose
    opt = session.config.option
    extra: list[str] = []
    verbose = int(getattr(opt, "verbose", 0) or 0)
    extra += ["-v"] * verbose if verbose > 0 else ["-q"]
    tb = getattr(opt, "tbstyle", "auto")
    if tb and tb != "auto":
        extra.append(f"--tb={tb}")
    for w in session.config.getoption("pythonwarnings", default=None) or []:
        extra += ["-W", w]
    child_base = [sys.executable, "-m", "pytest", "--no-header", *extra]
    failed: list[tuple[str, int]] = []
    remaining = maxfail
    files = sorted(by_file)
    t0 = time.time()
    for i, path in enumerate(files, 1):
        ids = [it.nodeid for it in by_file[path]]
        rel = os.path.relpath(path, rootdir)
        sys.stdout.write(
            f"[isolated {i}/{len(files)}] {rel} ({len(ids)} tests)\n")
        sys.stdout.flush()
        cmd = [*child_base,
               *([f"--maxfail={remaining}"] if maxfail else []), *ids]
        proc = subprocess.run(cmd, cwd=rootdir, env=env,
                              capture_output=True, text=True)
        sys.stdout.write(proc.stdout)
        sys.stderr.write(proc.stderr)
        sys.stdout.flush()
        if proc.returncode != 0:
            # count the child's failed+errored TESTS against the budget
            # (a crashed child with no summary line counts as 1)
            counted = sum(int(n) for n in re.findall(
                r"(\d+) (?:failed|error)", proc.stdout)) or 1
            failed.append((rel, proc.returncode))
            session.testsfailed += counted
            if maxfail:
                remaining -= counted
                if remaining <= 0:
                    break
    dt = time.time() - t0
    if failed:
        sys.stdout.write(
            f"[isolated] {len(failed)}/{len(files)} files FAILED "
            f"in {dt:.0f}s: {', '.join(f for f, _ in failed)}\n")
    else:
        sys.stdout.write(
            f"[isolated] all {len(files)} files passed in {dt:.0f}s\n")
    sys.stdout.flush()
    return True


@pytest.fixture(autouse=True)
def _restore_bls_backend():
    """ClientBuilder pins the process-global BLS backend (auto/fake/...);
    restore it around every test so suites stay order-independent."""
    from lighthouse_tpu.crypto import bls

    old = bls.get_backend()
    yield
    bls.set_backend(old)


@pytest.fixture(autouse=True, scope="module")
def _bound_vma_growth():
    """One full-suite process accumulates a memory map per JIT-loaded
    executable; at ~150 tests the count crosses vm.max_map_count (65530)
    and the NEXT XLA compile dies with SIGABRT/SIGSEGV inside mmap
    (reproduced: the maps monitor read 61k lines right before the
    crash).  Dropping jax's in-process executable caches when the map
    count runs high keeps the suite under the ceiling; the persistent
    compile cache makes any re-load cheap."""
    yield
    try:
        with open("/proc/self/maps") as f:
            n = sum(1 for _ in f)
    except OSError:
        return
    if n > 40_000:
        jax.clear_caches()
