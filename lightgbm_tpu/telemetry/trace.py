"""Span tracer: nested, tagged wall-clock spans for the training loop.

One instance per Booster, so two Boosters trained in one process keep
their own totals. The reference's observability surface is the
cumulative network-time counters in include/LightGBM/network.h /
src/network/linkers.h:195-212 plus ad-hoc timers in application.cpp;
GPU tree-boosting systems report per-kernel phase breakdowns as the
primary tuning instrument (arXiv:1706.08359, arXiv:2005.09148) — the
tracer is that instrument for the host-visible side of training.

Three views of the same spans:

- **Accumulator** (`acc`/`cnt`/`snapshot`/`report`): per-phase total
  seconds and call counts. A top-level span counts under its name, a
  child under its path (`fused_block/wait`).
- **Deltas** (`delta_snapshot`): per-phase seconds of the TOP-LEVEL
  spans since the previous call — what the run journal attaches to each
  iteration record; children are left out so a record's phases stay a
  partition of its wall time.
- **Recent spans** (`recent`): a bounded ring of completed spans with
  nesting path, start offset and tags — the `/trainz` endpoint's live
  breakdown.

Spans nest via a thread-local stack ("train/build" style paths), are
exception-safe (the `finally` always closes the span), and pass through
to `jax.profiler.TraceAnnotation` under their PATH whenever the
embedder has already imported jax, so host spans sit on the profiler's
clock beside the XLA device trace (`profile=1`; with no trace running
an annotation costs well under a microsecond). The module itself never
imports jax.

Two more things live here because every reader of a trace needs them in
one place:

- `DEVICE_SCOPES` / `DEVICE_SUBSCOPES`: the `jax.named_scope` vocabulary
  the fused step (models/gbdt.py) and the partitioned builder
  (models/partitioned.py, ops/ordered_hist.py) write into each device
  operation's `op_name`; `scope(word)` is how they write it.
- `PROCESS_TRACER`: one process-level tracer for work that belongs to no
  Booster, and the one record of set-up: the package's `import`
  (`lightgbm_tpu/__init__.py`), `dataset/...`, `rank_layout`,
  `booster_init` (`GBDT.init`), and every compile-ledger label
  (telemetry/ledger.py), tagged `cache_hit`. A dataset precedes any
  Booster, and a reader may outlive all of them. Nothing is mirrored
  between it and a Booster's tracer.
"""

import sys
import threading
import time
from collections import defaultdict, deque

from . import disttrace

RECENT_SPANS = 256

# Top-level device scopes, in the order of one boosting iteration. A
# device operation belongs to the FIRST of these words on its op_name
# path (docs/Observability.md; benchmarks/scopereduce.py holds its own
# copy, as a yardstick must). None is the name of a jax primitive:
# those stand on the same path as components of their own.
DEVICE_SCOPES = ("gradients", "partition", "hist", "hist_reduce",
                 "split_scan", "tree_state", "score_update")
DEVICE_SUBSCOPES = {
    # the pairwise pass of a ranking objective (objectives/rank_device.py),
    # and the softmax gradient of a multiclass one (objectives.py)
    "gradients": ("rank_gather", "rank_sort", "rank_pairs", "rank_return",
                  "softmax"),
    "partition": ("window_in", "decide", "destinations", "invert", "move",
                  "write_back"),
    "hist": ("window", "seg_hist", "fold"),
    "tree_state": ("hist_cache", "pos_leaf"),
}
# Path components that are no scope of their own: an operation that
# carries one still belongs to the first of DEVICE_SCOPES on its path.
# `class_scan` stands round the fused step's scan over the class axis
# (models/gbdt.py). What carries it, no top-level word, and nothing
# after it but the scan's own `while/body` and one primitive is that
# axis' own cost: the slices of the (K, N) gradients a class, the
# stacking of the K trees and score updates. Deeper paths without a
# word are the builder's own unscoped operations.
DEVICE_PATH_WORDS = ("class_scan",)
# names of the Pallas kernels (`pallas_call(name=...)`): the segment
# kernel and the partition kernel of the fused path, and the two
# full-matrix kernels chip_smoke.py runs
KERNEL_NAMES = ("seg_hist", "partition_rows", "masked_hist",
                "frontier_hist")
_SCOPE_WORDS = frozenset(DEVICE_SCOPES + DEVICE_PATH_WORDS).union(
    *DEVICE_SUBSCOPES.values())


def scope(word):
    """`jax.named_scope(word)` for a word of the device vocabulary —
    HLO metadata only, nothing at run time. Called under a jax trace,
    so jax is imported by then."""
    if word not in _SCOPE_WORDS:
        raise ValueError(f"{word!r} is not in the device-scope vocabulary")
    return sys.modules["jax"].named_scope(word)


class Span:
    """One completed (or open) span. `path` includes parents:
    "train/build". `tid` is the recording thread's ident, so concurrent
    threads (batcher worker, heartbeat monitor, the training loop) land
    on separate tracks in an exported trace (telemetry/export.py)."""

    __slots__ = ("name", "path", "start", "duration", "tags", "tid")

    def __init__(self, name, path, start, duration=None, tags=None,
                 tid=0):
        self.name = name
        self.path = path
        self.start = start
        self.duration = duration
        self.tags = tags or {}
        self.tid = tid

    def as_dict(self):
        return {"name": self.name, "path": self.path,
                "start_s": round(self.start, 6),
                "duration_s": (round(self.duration, 6)
                               if self.duration is not None else None),
                "tid": self.tid,
                **({"tags": self.tags} if self.tags else {})}


class _SpanContext:
    """Context manager for one span; created by SpanTracer.span().
    `seconds` is the span's duration once it has closed."""

    __slots__ = ("_tracer", "_name", "_tags", "_t0", "_path", "_ann",
                 "seconds")

    def __init__(self, tracer, name, tags):
        self._tracer = tracer
        self._name = name
        self._tags = tags
        self._t0 = None
        self._path = None
        self._ann = None
        self.seconds = None

    def tag(self, **tags):
        """Add tags known only once the span's work is done; they are
        recorded when it closes."""
        self._tags.update(tags)

    def __enter__(self):
        tr = self._tracer
        stack = tr._stack()
        self._path = ("/".join(s for s in stack) + "/" + self._name
                      if stack else self._name)
        stack.append(self._name)
        # getattr: a thread may open a span while jax is mid-import
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is not None:
            self._ann = profiler.TraceAnnotation(self._path)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        elapsed = self.seconds = time.perf_counter() - self._t0
        tr = self._tracer
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        stack = tr._stack()
        if stack and stack[-1] == self._name:
            stack.pop()
        tr._record(self._name, self._path, elapsed, self._t0, self._tags)
        return False


class SpanTracer:
    """Span registry, one per Booster plus `PROCESS_TRACER` (see module
    docstring).

    The accumulator keys on the span's path: a top-level span's is its
    name. Thread-safe: concurrent threads keep independent nesting stacks and
    the shared accumulator mutates under one lock.
    """

    def __init__(self, rank=0):
        self.rank = int(rank)
        self.acc = defaultdict(float)
        self.cnt = defaultdict(int)
        self._lock = threading.Lock()
        self._last = {}            # delta_snapshot baseline
        self._recent = deque(maxlen=RECENT_SPANS)
        self._local = threading.local()
        self._epoch = time.perf_counter()
        # wall-clock time of the perf_counter epoch: span start offsets
        # + epoch_wall = journal-comparable epoch seconds, the mapping
        # the trace exporter uses to line spans up with journal records
        self.epoch_wall = time.time()

    # ------------------------------------------------------------- spans
    def _stack(self):
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name, **tags):
        """Context manager timing one (possibly nested) span."""
        return _SpanContext(self, name, tags)

    # alias the per-iteration loop reads by: `with tracer.phase("build"):`
    phase = span

    def _record(self, name, path, elapsed, t0, tags):
        with self._lock:
            self.acc[path] += elapsed
            self.cnt[path] += 1
            self._recent.append(Span(name, path, t0 - self._epoch,
                                     elapsed, tags,
                                     tid=threading.get_ident()))
        # distributed-trace mirror: when this thread runs under an
        # active X-Trace-Ctx (a traced canary retrain, a request that
        # reached training code), the span ALSO lands on that trace so
        # /tracez shows training phases inside the cross-process tree.
        # One thread-local read when no context is active
        ctx = disttrace.current()
        if ctx is not None:
            rec = disttrace.get_recorder()
            if rec.enabled:
                rec.observe("train." + name, ctx,
                            time.time() - elapsed, elapsed,
                            tags=dict(tags) if tags else None)

    def add(self, name, seconds):
        """Accumulate an externally-timed phase (e.g. the bench's
        compile window). Also lands a synthetic span in the recent ring
        — ending NOW, `seconds` long — so externally-timed phases show
        up on /trainz and in exported traces instead of vanishing from
        every per-span view."""
        seconds = float(seconds)
        with self._lock:
            self.acc[name] += seconds
            self.cnt[name] += 1
            start = time.perf_counter() - seconds - self._epoch
            self._recent.append(Span(name, name, start, seconds,
                                     {"synthetic": True},
                                     tid=threading.get_ident()))

    # ----------------------------------------------------------- readers
    def reset(self):
        with self._lock:
            self.acc.clear()
            self.cnt.clear()
            self._last.clear()
            self._recent.clear()
            self._epoch = time.perf_counter()
            self.epoch_wall = time.time()

    def snapshot(self):
        """{phase: total_seconds}, machine-readable (bench JSON)."""
        with self._lock:
            return {k: round(v, 6) for k, v in self.acc.items()}

    def delta_snapshot(self):
        """{phase: seconds since the previous delta_snapshot call} —
        only top-level phases that moved. The run journal attaches this
        to each iteration record so per-record phase seconds sum back
        to the run totals (and, children left out, to wall time)."""
        out = {}
        with self._lock:
            for name, total in self.acc.items():
                if "/" in name:
                    continue
                d = total - self._last.get(name, 0.0)
                if d > 0:
                    out[name] = round(d, 6)
                self._last[name] = total
        return out

    def recent(self, n=32):
        """Last `n` completed spans, oldest first (`/trainz`); `n=None`
        dumps the whole ring (the journal `spans` record at close)."""
        with self._lock:
            spans = list(self._recent)
        if n is not None:
            spans = spans[-int(n):]
        return [s.as_dict() for s in spans]

    def report(self):
        """One line per phase, largest first."""
        with self._lock:
            items = sorted(self.acc.items(), key=lambda kv: -kv[1])
            lines = ["%-12s %8.3fs total, %7.2fms/call x%d"
                     % (name, total, 1e3 * total / max(self.cnt[name], 1),
                        self.cnt[name])
                     for name, total in items]
        return "\n".join(lines)


PROCESS_TRACER = SpanTracer()
