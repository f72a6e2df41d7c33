#!/usr/bin/env python3
"""Reduction of a profiler trace (`.xplane.pb`) to seconds per device scope.

    python3 benchmarks/scopereduce.py [trace_dir]

The program names its device work with `jax.named_scope` (the vocabulary
below; lightgbm_tpu/telemetry/trace.py holds the program's copy, this is
the yardstick's) and its host phases with `jax.profiler.TraceAnnotation`.
A scope lands in the `tf_op` stat of an operation's *event metadata*,
beside `bytes_accessed`; `jax.profiler.ProfileData` surfaces only the
event's own stats, so this file reads the `XSpace` wire format itself
(a few dozen lines, no package beyond the standard library).

Rules, shared with tracereduce.py: the `XLA Ops` line of each TPU plane,
the `bench_block` host annotation as the window, and only *leaf* events
count (a while, conditional or call spans the events of its body). Each
leaf belongs to the first vocabulary word on its `tf_op` path. A leaf
without one (XLA's own copies, buffer allocations) takes the word of the
innermost event that spans it and has one; those seconds are shown apart
as inherited. The chip's trace gives a while or conditional no `tf_op`,
so such a container has the word on which all scoped leaves inside it
agree, if they do. What is still bare is `unscoped`. Seconds are counted as
tracereduce counts busy time, by the union of the leaves' intervals, so
the scopes sum to `busy_s`.

`table(path)` parses once a process. `ms_per_iter(ctx, words)` is what
the device readers call: None where there is no trace, no TPU plane, or
no operation in the window carries a vocabulary word of its own (an
executable from a cache entry written before the scopes existed: jax's
cache key strips debug info). `host_spans(ctx)` gives the program's
annotations inside the window.
"""

import functools
import glob
import os
import re
import struct
import sys

import tracereduce  # beside this file

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_ROOT = os.path.join(os.path.dirname(HERE), ".bench_cache", "trace")

VOCABULARY = ("gradients", "partition", "hist", "hist_reduce", "split_scan",
              "tree_state", "score_update")
SUBSCOPES = {
    "partition": ("window_in", "decide", "destinations", "invert", "move",
                  "write_back"),
    "hist": ("window", "seg_hist", "fold"),
    "tree_state": ("hist_cache", "pos_leaf"),
}
UNSCOPED = "unscoped"

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = re.compile(r"^/host:")
OPS_LINE = "XLA Ops"
WINDOW_MARK = "bench_block"


# ------------------------------------------------------ XSpace wire format
# tsl/profiler/protobuf/xplane.proto, the fields read here:
#   XSpace.planes=1
#   XPlane.name=2 .lines=3 .event_metadata=4 (map) .stat_metadata=5 (map)
#   XLine.name=2 .timestamp_ns=3 .events=4
#   XEvent.metadata_id=1 .offset_ps=2 .duration_ps=3
#   XEventMetadata.id=1 .name=2 .stats=5
#   XStatMetadata.id=1 .name=2
#   XStat.metadata_id=1 .double=2 .uint64=3 .int64=4 .str=5 .bytes=6 .ref=7

def fields(buf):
    """(field number, wire type, value) of one message: an int for
    varint and fixed-width fields, a memoryview for length-delimited."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        wire = key & 7
        if wire == 0:
            val = shift = 0
            while True:
                b = buf[i]
                i += 1
                val |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
        elif wire == 2:
            size = shift = 0
            while True:
                b = buf[i]
                i += 1
                size |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            val = buf[i:i + size]
            i += size
        elif wire == 1:
            val = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wire == 5:
            val = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, wire, val


def _stat(buf, stat_names):
    name = value = None
    for no, wire, val in fields(buf):
        if no == 1:
            name = stat_names.get(val)
        elif no == 2:
            value = struct.unpack("<d", val.to_bytes(8, "little"))[0]
        elif no in (3, 4):
            value = val
        elif no == 5:
            value = bytes(val).decode("utf-8", "replace")
        elif no == 7:
            value = stat_names.get(val)
    return name, value


def _map_entry(buf):
    key = value = None
    for no, _, val in fields(buf):
        if no == 1:
            key = val
        elif no == 2:
            value = val
    return key, value


WANT_STATS = ("tf_op", "bytes_accessed")


def _plane(buf):
    name, lines, raw_events, raw_stats = "", [], [], []
    for no, _, val in fields(buf):
        if no == 2:
            name = bytes(val).decode()
        elif no == 3:
            lines.append(val)
        elif no == 4:
            raw_events.append(val)
        elif no == 5:
            raw_stats.append(val)
    stat_names = {}
    for entry in raw_stats:
        key, meta = _map_entry(entry)
        for no, _, val in fields(meta):
            if no == 2:
                stat_names[key] = bytes(val).decode()
    metadata = {}
    for entry in raw_events:
        key, meta = _map_entry(entry)
        md = {"name": ""}
        for no, _, val in fields(meta):
            if no == 2:
                md["name"] = bytes(val).decode("utf-8", "replace")
            elif no == 5:
                sname, svalue = _stat(val, stat_names)
                if sname in WANT_STATS:
                    md[sname] = svalue
        metadata[key] = md
    out = []
    for raw in lines:
        lname, t0, events = "", 0, []
        for no, _, val in fields(raw):
            if no == 2:
                lname = bytes(val).decode()
            elif no == 3:
                t0 = val
            elif no == 4:
                mid = off = dur = 0
                for eno, _, ev in fields(val):
                    if eno == 1:
                        mid = ev
                    elif eno == 2:
                        off = ev
                    elif eno == 3:
                        dur = ev
                events.append((mid, off, dur))
        # the clock tracereduce.py reads through jax.profiler.ProfileData:
        # start and duration each cut to whole ns. Kept to the digit, so
        # that both files see the same leaves and the same busy time
        out.append((lname, [(mid, t0 + off // 1000,
                             t0 + off // 1000 + dur // 1000)
                            for mid, off, dur in events]))
    return {"name": name, "lines": out, "metadata": metadata}


def read_xspace(path):
    """The planes of an `.xplane.pb`: name, lines as (name, [(metadata
    id, start_ns, end_ns)]), and event metadata {id: {name, tf_op,
    bytes_accessed}}."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [_plane(val) for no, _, val in fields(buf) if no == 1]


def newest_trace(root=None):
    """The newest `.xplane.pb` under `root` (default TRACE_ROOT): run.py
    keeps one trace a cell and replaces it before it traces, so the
    run's own is the newest. None if there is none."""
    paths = glob.glob(os.path.join(root or TRACE_ROOT, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


# --------------------------------------------------------------- reduction
def words_of(tf_op):
    """(top-level word, sub-scope) of a `tf_op` path; (None, None) when
    no component is a vocabulary word."""
    parts = (tf_op or "").rstrip(":").split("/")
    for i, part in enumerate(parts):
        if part in VOCABULARY:
            subs = SUBSCOPES.get(part, ())
            sub = next((p for p in parts[i + 1:] if p in subs), "")
            return part, sub
    return None, None


def family(name):
    """`copy s32[7,11534336]` of `%copy.473 = s32[7,11534336]{...} copy(`:
    opcode and result shape without the instruction's number, which
    changes with every compile."""
    m = tracereduce.HLO.match(name)
    if not m:
        return name[:64]
    return " ".join(x for x in (m.group(3), m.group(2)) if x)


def attribute(events, metadata, window):
    """(rows, bare) of one `XLA Ops` line: rows {(word, sub, inherited):
    [seconds, bytes, events]}, and the unscoped seconds again by
    operation family. events: [(metadata id, start_ns, end_ns)].

    A container's word is its own `tf_op`'s if it has one (the chip's
    trace gives control flow a `source` and no `tf_op`), else the one
    top-level word on which all scoped leaves inside it agree."""
    lo, hi = window
    nodes = []      # [event, parent, has_child, own (word, sub), words below]
    stack = []      # indices of the open events

    def pop():
        i = stack.pop()
        if stack:
            nodes[stack[-1]][4] |= nodes[i][4]

    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and nodes[stack[-1]][0][2] <= ev[1]:
            pop()
        own = words_of(metadata.get(ev[0], {}).get("tf_op"))
        if stack:
            nodes[stack[-1]][2] = True
        nodes.append([ev, stack[-1] if stack else -1, False, own,
                      {own[0]} if own[0] else set()])
        stack.append(len(nodes) - 1)
    while stack:
        pop()

    rows, bare = {}, {}
    frontier = lo
    for (mid, start, end), parent, has_child, own, _ in nodes:
        if has_child or end <= lo or start >= hi:
            continue
        scope, inherited, mixed = own, False, False
        while scope[0] is None and parent >= 0:
            _, parent, _, up_own, below = nodes[parent]
            if up_own[0]:
                scope, inherited = up_own, True
            elif len(below) == 1 and not mixed:
                scope, inherited = (next(iter(below)), ""), True
            mixed = mixed or len(below) > 1
        s, e = max(start, lo, frontier), min(end, hi)
        frontier = max(frontier, e)
        key = (scope[0] or UNSCOPED, scope[1] or "", inherited)
        md = metadata.get(mid, {})
        targets = [rows.setdefault(key, [0.0, 0, 0])]
        if key[0] == UNSCOPED:
            targets.append(bare.setdefault(family(md.get("name", "")),
                                           [0.0, 0, 0]))
        for row in targets:
            row[0] += max(e - s, 0.0) / 1e9
            row[1] += int(md.get("bytes_accessed") or 0)
            row[2] += 1
    return rows, bare


def reduce(planes):
    """Scope table of a parsed trace: {"window_s", "busy_s", "scoped_s"
    (seconds under a word of the operation's own), "rows": {(word, sub,
    inherited): [seconds, bytes, events]}, "bare": the unscoped rows by
    operation family, "host": {annotation: seconds inside the window}},
    averaged over the TPU planes. None without a TPU plane."""
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    if not devices:
        return None
    host = [(p["metadata"].get(mid, {}).get("name", ""), s, e)
            for p in planes if HOST_PLANE.match(p["name"])
            for _, events in p["lines"] for mid, s, e in events]
    marks = [(s, e) for n, s, e in host if n == WINDOW_MARK]
    if marks:
        window = max(marks, key=lambda m: m[1] - m[0])
    else:
        every = [e for p in devices for _, evs in p["lines"] for e in evs]
        window = (min(e[1] for e in every), max(e[2] for e in every))
    rows, bare = {}, {}
    for p in devices:
        ops = [evs for n, evs in p["lines"] if n == OPS_LINE]
        events = ops[0] if ops else [e for _, evs in p["lines"] for e in evs]
        for into, found in zip((rows, bare),
                               attribute(events, p["metadata"], window)):
            for key, (secs, nbytes, count) in found.items():
                row = into.setdefault(key, [0.0, 0, 0])
                row[0] += secs / len(devices)
                row[1] += nbytes // len(devices)
                row[2] += count
    spans = {}
    for name, s, e in host:
        if name != WINDOW_MARK and s >= window[0] and e <= window[1]:
            spans[name] = spans.get(name, 0.0) + (e - s) / 1e9
    return {"window_s": (window[1] - window[0]) / 1e9,
            "busy_s": sum(r[0] for r in rows.values()),
            "scoped_s": sum(r[0] for (w, _, inherited), r in rows.items()
                            if w != UNSCOPED and not inherited),
            "rows": rows, "bare": bare, "host": spans}


def seconds_of(tab, words):
    """Device seconds under the given top-level words (own and
    inherited), `UNSCOPED` among them if wanted."""
    return sum(r[0] for (w, _, _), r in tab["rows"].items() if w in words)


def render(tab):
    """The scope / sub-scope table as text."""
    busy = tab["busy_s"] or 1.0
    out = [f"scope table: window {tab['window_s']:.3f} s, busy "
           f"{tab['busy_s']:.3f} s, under a word of its own "
           f"{tab['scoped_s']:.3f} s",
           f"  {'scope':34s} {'seconds':>9s} {'% busy':>7s} {'events':>7s} "
           f"{'GB accessed':>12s} {'GB/s':>8s}"]
    order = {w: i for i, w in enumerate(VOCABULARY + (UNSCOPED,))}
    by_word = {}
    for (w, sub, inh), r in tab["rows"].items():
        by_word.setdefault(w, []).append((sub, inh, r))

    def line(label, secs, nbytes, count):
        rate = nbytes / secs / 1e9 if secs > 0 else 0.0
        return (f"  {label:34s} {secs:9.4f} {100 * secs / busy:7.2f} "
                f"{count:7d} {nbytes / 1e9:12.3f} {rate:8.1f}")

    for w in sorted(by_word, key=lambda w: order.get(w, 99)):
        subs = by_word[w]
        out.append(line(w, sum(r[0] for _, _, r in subs),
                        sum(r[1] for _, _, r in subs),
                        sum(r[2] for _, _, r in subs)))
        if len(subs) > 1 or subs[0][0] or subs[0][1]:
            for sub, inh, r in sorted(subs, key=lambda x: -x[2][0]):
                label = f"  {w}/{sub or ('(bare)' if inh else '(itself)')}" + (
                    " [inherited]" if inh else "")
                out.append(line(label, *r))
    for name, r in sorted(tab["bare"].items(), key=lambda kv: -kv[1][0])[:10]:
        out.append(line(f"  {UNSCOPED}: {name}"[:34], *r))
    for name, secs in sorted(tab["host"].items(), key=lambda kv: -kv[1])[:12]:
        out.append(f"  host {name:29s} {secs:9.4f}")
    return "\n".join(out)


@functools.lru_cache(maxsize=2)
def table(path):
    """`reduce` of the trace at `path`, parsed once a process; the table
    goes to stderr as a phase mark."""
    tab = reduce(read_xspace(path))
    if tab is not None:
        print(f"[scopes] {path}\n{render(tab)}", file=sys.stderr, flush=True)
    return tab


def run_table(ctx):
    """The scope table of the run whose readers' `ctx` this is, or None:
    off a TPU (ctx["trace"] is None there) or with no trace on disk."""
    path = newest_trace() if ctx.get("trace") else None
    return table(path) if path else None


def host_spans(ctx):
    """{annotation: seconds inside the window} of the run's trace, or
    None. It does not depend on the executable's names."""
    tab = run_table(ctx)
    return tab["host"] if tab else None


def ms_per_iter(ctx, words):
    """What a device reader returns: milliseconds an iteration of the
    traced block under `words`, or None (never 0 for want of a trace)."""
    tab = run_table(ctx)
    if not tab or tab["scoped_s"] <= 0:     # no trace; a stale executable
        return None
    return 1e3 * seconds_of(tab, words) / ctx["block_iterations"]


def main(argv):
    root = argv[1] if len(argv) > 1 else TRACE_ROOT
    path = root if os.path.isfile(root) else newest_trace(root)
    if not path:
        print(f"no .xplane.pb under {root}", file=sys.stderr)
        return 2
    tab = reduce(read_xspace(path))
    if tab is None:
        print(f"{path}: the trace holds no TPU plane", file=sys.stderr)
        return 2
    print(f"{path}\n{render(tab)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
