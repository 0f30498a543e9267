"""From a profiler trace (``.xplane.pb``) to device numbers.

What a TPU trace holds (looked at by hand on a v5e, PR 25): one plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event per
executed program, named ``jit_<function>(<fingerprint>)``) and ``XLA Ops``
(one event per HLO op inside it); the host plane ``/host:CPU`` carries
``jax.profiler.TraceAnnotation`` events on the thread that made them.
Event times of both are nanoseconds on one base (device and host clocks
agree to about a millisecond).

  busy     union of the ``XLA Ops`` intervals, clipped to the request
           windows (the harness's ``bench.request`` annotations), averaged
           over the device planes
  covered  the device's trace buffer is finite: of a long window it holds
           only a part (seen on the v5e: 7 of 10 block requests, 3 of 5
           flood requests).  Where some request shows no device op at all,
           only the requests that do are kept, less those next to an empty
           one (they may be cut), and every number is of the kept requests
  modules  summed device duration and count per program name
  gaps     the stretches inside the traced span in which no op ran, each
           labelled by whether its middle lies inside a request
"""

from __future__ import annotations

import bisect
import re

REQUEST = "bench.request"
#: device and host clocks of one trace differ by about a millisecond (the
#: recorded v5e trace has the device 1.1 ms ahead): an event this close to
#: a request's edge still belongs to it
SKEW_NS = 2e6
_FINGERPRINT = re.compile(r"\(\d+\)$")


def module_name(event_name: str) -> str:
    name = _FINGERPRINT.sub("", event_name)
    return name[4:] if name.startswith("jit_") else name


def _union(intervals):
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def _clip(intervals, windows):
    """Total length of sorted disjoint ``intervals`` inside ``windows``."""
    total, j = 0.0, 0
    for lo, hi in intervals:
        while j < len(windows) and windows[j][1] <= lo:
            j += 1
        k = j
        while k < len(windows) and windows[k][0] < hi:
            total += max(0.0, min(hi, windows[k][1]) - max(lo, windows[k][0]))
            k += 1
    return total


def load(path: str) -> tuple:
    """(requests, devices) of a trace file: [(start, end)] in ns, and per
    device plane (name, [(start, end, op)], [(program, start, duration)])."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    requests, devices = [], []
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                requests += [(e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name == REQUEST]
        elif plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events]
                elif line.name == "XLA Modules":
                    modules = [(module_name(e.name), e.start_ns, e.duration_ns)
                               for e in line.events]
            if ops:
                devices.append((plane.name, ops, modules))
    return requests, devices


def reduce(path: str) -> dict:
    return reduce_events(*load(path))


def reduce_events(requests, devices) -> dict:
    """All times in seconds.  Raises if no device plane has ops, or there
    is no request annotation."""
    if not devices:
        raise ValueError("trace: no operation ran on a device")
    if not requests:
        raise ValueError(f"trace: no {REQUEST} annotation")
    requests = sorted(requests)
    all_ops = sorted((lo, hi) for _, ops, _ in devices for lo, hi, _ in ops)
    starts = [lo for lo, _ in all_ops]
    def has_ops(window):
        i = bisect.bisect_left(starts, window[0] - SKEW_NS)
        return i < len(starts) and starts[i] < window[1] + SKEW_NS

    seen = [has_ops(w) for w in requests]
    if all(seen):
        kept = requests
    else:
        kept = [w for i, w in enumerate(requests)
                if seen[i] and (i == 0 or seen[i - 1])
                and (i == len(requests) - 1 or seen[i + 1])]
    if not kept:
        raise ValueError("trace: no request is covered by device events")
    windows = _union(kept)
    window_ns = sum(hi - lo for lo, hi in windows)

    def inside(t, slack=0.0):
        return any(a - slack <= t < b + slack for a, b in windows)

    busy_ns, modules, op_time, gaps = 0.0, {}, {}, []
    for _, ops, mods in devices:
        busy = _union((lo, hi) for lo, hi, _ in ops)
        busy_ns += _clip(busy, windows)
        for name, start, ns in mods:
            if inside(start, SKEW_NS):
                m = modules.setdefault(name, [0, 0.0])
                m[0] += 1
                m[1] += ns
        for lo, hi, name in ops:
            if inside(lo, SKEW_NS):
                op_time[name] = op_time.get(name, 0.0) + (hi - lo)
        edges = [windows[0][0]] + [t for iv in busy for t in iv] + [windows[-1][1]]
        for lo, hi in zip(edges[0::2], edges[1::2]):
            lo, hi = max(lo, windows[0][0]), min(hi, windows[-1][1])
            if hi > lo:
                gaps.append(("inside a request" if inside((lo + hi) / 2)
                             else "between requests", (hi - lo) / 1e9))
    n = len(devices)
    return {
        "devices": n,
        "requests": len(kept),
        "requests_annotated": len(requests),
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "modules": {k: {"count": c, "seconds": ns / 1e9}
                    for k, (c, ns) in modules.items()},
        "ops": sorted(((k, ns / 1e9) for k, ns in op_time.items()),
                      key=lambda kv: -kv[1]),
        "gaps": sorted(gaps, key=lambda g: -g[1]),
    }


def module_seconds(reduced: dict, names) -> tuple:
    """(seconds, events) summed over the programs whose name ends with one
    of ``names``; (0.0, 0) when none ran."""
    seconds, events = 0.0, 0
    for name, m in reduced["modules"].items():
        if any(name == n or name.endswith(n) for n in names):
            seconds += m["seconds"]
            events += m["count"]
    return seconds, events
