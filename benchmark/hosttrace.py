"""Idle device time put down to host stages: the ``--profile`` trace's
``/host:CPU`` plane set against its device planes, in one window.

    python benchmark/hosttrace.py TRACE_DIR W0_NS W1_NS OUT.json [DUMP.json.gz]

Run like trace.py (a child with JAX_PLATFORMS=cpu, once the collector
has exited). It extends trace.py's reduction and leaves it as it is:
``load`` is ``trace.load`` plus the host plane's events as
``[name, start_ns, dur_ns]``, which the program's per-group spans put
there under ``--profile`` (on the clock of the trace's
``profile_start_time``, as the device events are); ``reduce`` is
``trace.reduce`` plus, per device,

- ``idle_host_ns``: for each host event name in the window, the overlap
  of that name's union of intervals with the device's idle time;
- ``gap_hosts``: for each of ``gaps``, the (up to) three host names
  that overlap it most, as ``[name, ns]``.

The device keys (``busy_ns``, ``ops_ns``, ``gaps``) are trace.reduce's
own, byte for byte. DUMP keeps the window's device and host events for
a test fixture. No span name lives here: readers under ``metrics/``
pick the names they need.
"""

import glob
import gzip
import json
import os
import sys

import trace as devtrace

HOST_PLANE = "/host:CPU"
GAP_HOSTS = 3


def host_events(trace_dir: str) -> list:
    """[[name, start_ns, dur_ns], ...] of the host plane of the newest
    .xplane.pb under trace_dir, every line of it."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    return [[e.name, int(e.start_ns), int(e.duration_ns)]
            for plane in pd.planes if plane.name == HOST_PLANE
            for line in plane.lines for e in line.events]


def load(trace_dir: str) -> dict:
    t = devtrace.load(trace_dir)
    t["host"] = host_events(trace_dir)
    return t


def merged(spans) -> list:
    """Sorted, disjoint (start, end) intervals covering ``spans``."""
    out = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def overlap(a: list, b: list) -> int:
    """Length of the intersection of two sorted, disjoint interval
    lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle(events: list, w0: int, w1: int) -> list:
    """The window's intervals in which no device op ran."""
    out, at = [], w0
    for s, e in merged((a, b) for _, a, b in devtrace.clip(events, w0, w1)):
        if s > at:
            out.append([at, s])
        at = max(at, e)
    if w1 > at:
        out.append([at, w1])
    return out


def reduce(trace: dict, w0_wall: int, w1_wall: int) -> dict:
    """trace.reduce, and per device ``idle_host_ns`` and ``gap_hosts``
    (module docstring); a trace without host events gives them empty."""
    out = devtrace.reduce(trace, w0_wall, w1_wall)
    w0, w1 = w0_wall - trace["start_ns"], w1_wall - trace["start_ns"]
    spans = {}
    for name, a, b in devtrace.clip(trace.get("host", []), w0, w1):
        spans.setdefault(name, []).append((a, b))
    host = {name: merged(v) for name, v in spans.items()}
    for plane, d in out["devices"].items():
        quiet = idle(trace["devices"][plane], w0, w1)
        d["idle_host_ns"] = {name: overlap(quiet, iv)
                             for name, iv in sorted(host.items())}
        d["gap_hosts"] = []
        for at, dur in d["gaps"]:
            gap = [[w0 + at, w0 + at + dur]]
            top = sorted(((overlap(gap, iv), name)
                          for name, iv in host.items()), reverse=True)
            d["gap_hosts"].append([[name, ns] for ns, name in
                                   top[:GAP_HOSTS] if ns > 0])
    return out


def gap_name(plane: str, at_ns: int, dur_ns: int, hosts: list) -> str:
    """A gap's name in a breakdown: the device's own name for it, then
    the host stages that overlapped it, as shares of the gap."""
    names = ", ".join(f"{name} {100.0 * ns / dur_ns:.0f}%"
                      for name, ns in hosts) if dur_ns else ""
    return f"{plane} idle from +{at_ns / 1e9:.6f} s; host: {names or 'none'}"


def window_events(trace: dict, w0_wall: int, w1_wall: int) -> dict:
    """trace.window_events, with the host events in the window."""
    w0, w1 = w0_wall - trace["start_ns"], w1_wall - trace["start_ns"]
    out = devtrace.window_events(trace, w0_wall, w1_wall)
    out["host"] = [e for e in trace.get("host", [])
                   if e[1] + e[2] > w0 and e[1] < w1]
    return out


def main() -> int:
    trace_dir, w0, w1, out = (sys.argv[1], int(sys.argv[2]),
                              int(sys.argv[3]), sys.argv[4])
    t = load(trace_dir)
    with open(out, "w", encoding="utf-8") as f:
        json.dump(reduce(t, w0, w1), f)
    if len(sys.argv) > 5:
        with gzip.open(sys.argv[5], "wt", encoding="utf-8") as f:
            json.dump(window_events(t, w0, w1), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
