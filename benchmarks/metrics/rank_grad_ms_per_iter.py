"""Device time under the scope `gradients` in the traced block, per
iteration: for a ranking objective the pairwise pass beside the pads and
the in-bag weights every objective has there.

`scopereduce.SUBSCOPES` has no entry for `gradients`, so this reader
carries the words of the pairwise pass itself (objectives/rank_device.py:
`rank_gather` the scores into the length-bucketed query layout,
`rank_sort` ranks and discounts, `rank_pairs` the pair tensors,
`rank_return` the way back to the rows) and prints their split to
stderr beside the scope table. The split reads the same file by the same
rules (the window, leaf operations, whole ns); a leaf belongs to the
first of these words on its `tf_op` path after `gradients`.
"""

import sys

import scopereduce
import tracereduce

SUBSCOPES = ("rank_gather", "rank_sort", "rank_pairs", "rank_return")


def split(planes):
    """{sub-scope: seconds} of the window's leaf operations whose own
    `tf_op` path has `gradients` on it ('' = none of the sub-scopes),
    averaged over the TPU planes."""
    devices = [p for p in planes if scopereduce.DEVICE_PLANE.match(p["name"])]
    marks = [(s, e) for p in planes if scopereduce.HOST_PLANE.match(p["name"])
             for _, events in p["lines"] for mid, s, e in events
             if p["metadata"].get(mid, {}).get("name") == scopereduce.WINDOW_MARK]
    out = {}
    for p in devices:
        ops = [evs for n, evs in p["lines"] if n == scopereduce.OPS_LINE]
        events = ops[0] if ops else [e for _, evs in p["lines"] for e in evs]
        lo, hi = (max(marks, key=lambda m: m[1] - m[0]) if marks else
                  (min(e[1] for e in events), max(e[2] for e in events)))
        for mid, start, end in tracereduce.leaves(events):
            parts = (p["metadata"].get(mid, {}).get("tf_op") or "").split("/")
            if "gradients" not in parts or end <= lo or start >= hi:
                continue
            after = parts[parts.index("gradients") + 1:]
            sub = next((w for w in after if w in SUBSCOPES), "")
            out[sub] = out.get(sub, 0.0) + (min(end, hi) - max(start, lo)) / 1e9
    return {k: v / len(devices) for k, v in out.items()} if devices else {}


def read(ctx):
    value = scopereduce.ms_per_iter(ctx, ("gradients",))
    if value is not None:
        parts = split(scopereduce.read_xspace(scopereduce.newest_trace()))
        if any(parts.get(w) for w in SUBSCOPES):
            print("[rank] gradients by sub-scope, seconds of the block: "
                  + ", ".join(f"{w or '(itself)'} {parts[w]:.4f}"
                              for w in SUBSCOPES + ("",) if w in parts),
                  file=sys.stderr, flush=True)
    return value
