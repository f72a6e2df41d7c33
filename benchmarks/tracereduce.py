"""Reduction of a profiler trace (`.xplane.pb`) to plain figures.

`load` turns the file into lists of (name, start_ns, end_ns) per device
and per host thread; `reduce` is pure arithmetic on those lists, so a
synthetic trace checks it (tests/test_harness.py).

On the device a control-flow operation (while, conditional, call) is an
event that spans the events of its body. Counting it would read every
scan as 100 % busy, so only *leaf* events count: events that contain no
other event of their line. Busy time is the union of the leaves'
intervals; a container's time outside its leaves is idle.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = re.compile(r"^/host:")


def load(trace_dir):
    """(devices, host): devices = {plane: [(name, start, end)]} from each
    TPU plane's op line; host = [(name, start, end)] over all host threads."""
    import jax
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
            devices[plane.name] = [
                (e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                for ln in ops for e in ln.events]
        elif HOST_PLANE.match(plane.name):
            host += [(e.name, float(e.start_ns),
                      float(e.start_ns + e.duration_ns))
                     for ln in plane.lines for e in ln.events
                     if e.duration_ns >= 100_000]   # spans of 0.1 ms and more
    return devices, host


def leaves(events):
    """Events that contain no other event (ties: the later, shorter one
    is the child)."""
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []          # stack of [event, has_child]
    for e in ev:
        while stack and stack[-1][0][2] <= e[1]:
            top, has_child = stack.pop()
            if not has_child:
                out.append(top)
        if stack:
            stack[-1][1] = True
        stack.append([e, False])
    out += [top for top, has_child in stack if not has_child]
    return out


def union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def clip(events, window):
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def covering(host, t):
    """Name of the shortest host span that covers instant t."""
    best = None
    for n, s, e in host:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (n, e - s)
    return best[0] if best else "(no host span)"


HLO = re.compile(r"^%?(\S+) = \(?(\w+\[[\d,]*\])?\S* ?.*? ([\w\-]+)\(")


def label(name):
    """Short name of a device event. The TPU trace names an operation by
    its HLO text, `%fusion.36 = s32[4194304,7]{0,1:T(8,128)} fusion(...)`:
    keep instruction, opcode and result shape, `fusion.36 fusion s32[4194304,7]`."""
    m = HLO.match(name)
    if not m:
        return name[:96]
    return " ".join(x for x in (m.group(1), m.group(3), m.group(2)) if x)


def seconds_of(trace, pattern):
    """Device seconds of the leaf operations whose name matches the
    compiled regex; None where nothing matches (a reader then reports
    nothing, never 0)."""
    hit = [v for n, v in trace["ops"].items() if pattern.search(n)]
    return sum(hit) if hit else None


def reduce(devices, host, window=None, top=10):
    """Figures of one traced window, averaged over the device planes.

    window: (start_ns, end_ns); default: the host span named
    `bench_block`, else first to last device event.
    Returns seconds: window_s, busy_s, ops {label of the operation:
    seconds}, and the breakdown lists device_ops and
    idle_gaps (gaps summed by the host span they fall in)."""
    if not devices:
        raise ValueError("the trace holds no TPU plane")
    if window is None:
        marks = [(s, e) for n, s, e in host if n == "bench_block"]
        if marks:
            window = max(marks, key=lambda m: m[1] - m[0])
        else:
            every = [e for ev in devices.values() for e in ev]
            window = (min(e[1] for e in every), max(e[2] for e in every))
    busy = 0.0
    by_name, gaps = {}, []
    for events in devices.values():
        lv = clip(leaves(events), window)
        merged = union([(s, e) for _, s, e in lv])
        busy += sum(e - s for s, e in merged)
        for n, s, e in lv:
            lab = label(n)
            by_name[lab] = by_name.get(lab, 0.0) + (e - s)
        edges = [window[0]] + [t for iv in merged for t in iv] + [window[1]]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    k = len(devices)
    gaps.sort(key=lambda g: g[0] - g[1])
    by_host = {}
    for s, e in gaps[:50]:
        name = covering(host, (s + e) / 2.0)
        by_host[name] = by_host.get(name, 0.0) + (e - s)
    as_list = lambda d: [[n, v / k / 1e9] for n, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": busy / k / 1e9,
        "ops": {n: v / k / 1e9 for n, v in by_name.items()},
        "device_ops": as_list(by_name),
        "idle_gaps": as_list(by_host),
    }
