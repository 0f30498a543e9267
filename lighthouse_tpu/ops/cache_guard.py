"""XLA:CPU process hardening: mmap headroom for the test suite.

ROOT CAUSE (round 5, measured): every "compile-cache segfault" seen in
rounds 4-5 was the kernel's `vm.max_map_count` ceiling (default 65,530).
XLA:CPU mmaps tens of thousands of regions (one `test_device_pairing`
run peaks >61k VMAs); past the ceiling mmap fails, XLA does not check,
and the process segfaults in whatever path is active.

`ensure_map_headroom()` raises the ceiling to 262,144 (root-only write
to /proc/sys/vm/max_map_count).  It is called once, from
`tests/conftest.py`: a host-global sysctl write is a test-harness
concern, not something the verify hot path does on a node.
"""

from __future__ import annotations

from lighthouse_tpu.common import env as envreg

_MAP_TARGET = 262144
_MAP_PATH = "/proc/sys/vm/max_map_count"


def _log():
    # lazy: common.logging pulls in the metrics registry
    from lighthouse_tpu.common.logging import Logger

    return Logger("cache_guard")


def ensure_map_headroom() -> bool:
    """Best-effort raise of vm.max_map_count to _MAP_TARGET.

    Returns True when the ceiling is at/above target (already, or after
    our write), False when it could not be raised or
    LHTPU_NO_CACHE_GUARD opts out of the write."""
    if envreg.get("LHTPU_NO_CACHE_GUARD"):
        return False
    try:
        with open(_MAP_PATH) as f:
            if int(f.read()) >= _MAP_TARGET:
                return True
        with open(_MAP_PATH, "w") as f:
            f.write(str(_MAP_TARGET))
        with open(_MAP_PATH) as f:
            raised = int(f.read()) >= _MAP_TARGET
        if raised:
            # one line per boot in practice: later processes see the
            # raised ceiling and return above without writing
            _log().info("raised vm.max_map_count sysctl",
                        target=_MAP_TARGET, path=_MAP_PATH)
        return raised
    except (OSError, ValueError):
        # unwritable/missing sysctl or a non-numeric readback
        return False
