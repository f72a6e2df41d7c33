"""What the class axis itself costs on the device, per iteration of the
traced block: what K classes cost beyond K trees.

The fused step of a K-class objective scans the classes
(models/gbdt.py `class_step`) under `jax.named_scope("class_scan")`.
The word is a path component and no scope of its own: every operation
of a class's tree still belongs to the first vocabulary word on its
path (`partition`, `hist`, ...), so no other reader moves. This reader
counts the operations of the scan itself: those whose own `tf_op` path
carries `class_scan`, no top-level scope word, and after `class_scan`
nothing but the scan's own loop (`while/body`, `while/cond`) and at
most the one primitive: a class's slice of the (K, N) gradients and
hessians, the stacking of the K trees' arrays and of the (K, n) score
update, the loop counter. What lies deeper (`class_scan/while/body/
closed_call/...`) is inside a class's tree: the builder's own
operations without a word (the decision vector's fill a split), which
one tree an iteration pays as well and `unscoped_ms_per_iter` holds.
`scopereduce.py` counts all of these seconds as `unscoped`; this reader
carries the word and reads the paths itself, as
`rank_grad_ms_per_iter.py` does, by the same rules (the window, leaf
operations, whole ns).

None where no operation in the window carries the word: a one-class
program, or one compiled before the word existed.
"""

import scopereduce
import tracereduce

WORD = "class_scan"


def leaf_seconds(planes, wanted):
    """(seconds, events) of the window's leaf operations whose own
    `tf_op` path, as a list of components, `wanted` accepts; seconds
    averaged over the TPU planes."""
    devices = [p for p in planes if scopereduce.DEVICE_PLANE.match(p["name"])]
    marks = [(s, e) for p in planes if scopereduce.HOST_PLANE.match(p["name"])
             for _, events in p["lines"] for mid, s, e in events
             if p["metadata"].get(mid, {}).get("name") == scopereduce.WINDOW_MARK]
    seconds, count = 0.0, 0
    for p in devices:
        ops = [evs for n, evs in p["lines"] if n == scopereduce.OPS_LINE]
        events = ops[0] if ops else [e for _, evs in p["lines"] for e in evs]
        lo, hi = (max(marks, key=lambda m: m[1] - m[0]) if marks else
                  (min(e[1] for e in events), max(e[2] for e in events)))
        for mid, start, end in tracereduce.leaves(events):
            parts = (p["metadata"].get(mid, {}).get("tf_op") or "").split("/")
            if end > lo and start < hi and wanted(parts):
                seconds += (min(end, hi) - max(start, lo)) / 1e9
                count += 1
    return (seconds / len(devices) if devices else 0.0), count


def ms_of(ctx, wanted):
    """Milliseconds an iteration of the run's trace, or None: no trace,
    or no operation that `wanted` accepts."""
    path = scopereduce.newest_trace() if ctx.get("trace") else None
    if not path:
        return None
    seconds, count = leaf_seconds(scopereduce.read_xspace(path), wanted)
    return 1e3 * seconds / ctx["block_iterations"] if count else None


CALLS = ("closed_call", "pjit", "while", "cond")     # a body of their own


def own(parts):
    """Whether a `tf_op` path, as components, is the class scan's own:
    the word, no scope word, and after the word only the scan's loop
    and at most one component that is no call or loop of its own."""
    if WORD not in parts or any(w in parts for w in scopereduce.VOCABULARY):
        return False
    rest = [p.rstrip(":") for p in parts[parts.index(WORD) + 1:] if p.rstrip(":")]
    if rest[:1] == ["while"]:
        rest = rest[2:] if rest[1:2] in (["body"], ["cond"]) else rest[1:]
    return len(rest) <= 1 and not any(
        r in CALLS or r.startswith("jit(") for r in rest)


def read(ctx):
    return ms_of(ctx, own)
