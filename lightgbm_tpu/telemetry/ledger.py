"""Device-memory sampling + compile ledger.

Two halves of the "where did the device go" question the span tracer
cannot answer:

- **Memory** (`sample_memory`): allocator watermarks — device
  `bytes_in_use` / `peak_bytes_in_use` from `Device.memory_stats()`
  (TPU/GPU allocators publish them; this image's CPU jax returns None,
  so the host RSS / peak-RSS pair from `/proc` + `getrusage` always
  rides along). GBDT samples at iteration/block boundaries into
  registry gauges + journal `memory` records, so an OOM-shaped run is
  diagnosable from the timeline instead of a post-mortem.
- **Compiles** (`CompileLedger`): every jit lowering the process pays
  for, attributed to a caller-named shape bucket. jax's monitoring
  stream has the raw events but no attribution; the ledger adds a
  thread-local label stack (`with LEDGER.label("fused_scan_10it:lower"):`)
  so the fused trainer's lowerings and the serving warmup's per-bucket
  compiles are separable line items on /trainz and /metricz, and keeps
  the wall seconds each label covered (`label_seconds`), which is how
  trace + lower — served by no cache — is told from compile-or-load.
  Each label is also a span of the same name on the process tracer
  (telemetry/trace.py PROCESS_TRACER, on the profiler's clock), timed
  once for both views and tagged `cache_hit`: whether a
  persistent-cache hit fired inside it on its thread.
  On jax 0.9.0 `/jax/core/compile/backend_compile_duration` brackets
  `compile_or_get_cached`: it fires for a persistent-cache HIT too (its
  duration is then the load), after `/jax/compilation_cache/cache_hits`
  and `.../cache_retrieval_time_sec` on the same thread. The ledger
  therefore writes one entry per lowering, when the duration arrives,
  and marks it `cache_hit` if a hit event preceded it. It does not sum
  jax's `jaxpr_trace_duration` / `jaxpr_to_mlir_module_duration`: a
  nested jit reports its own inside its caller's, so the sum counts
  the inner traces twice.

The module is jax-free until `CompileLedger.install()` runs (a no-op
without jax); `sample_memory` only touches jax when the embedder
already imported it. Process-wide singleton (`LEDGER`) — jax's
monitoring stream is process-global, same shape as journal.current().
"""

import os
import threading
import time
from collections import deque

from .trace import PROCESS_TRACER

RECENT_COMPILES = 256

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class CompileLedger:
    """Process-wide ledger of jit lowerings (see module docstring).

    `install()` registers the jax.monitoring listeners once;
    `label(name)` attributes compiles on the current thread and keeps
    the wall seconds it covered; `snapshot()` is the /trainz / /metricz
    view; `drain()` hands new entries to the journal writer exactly
    once each.
    """

    def __init__(self, ring=RECENT_COMPILES):
        self._lock = threading.Lock()
        self._local = threading.local()
        self._recent = deque(maxlen=ring)
        self._undrained = []
        self.compiles = 0
        self.total_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.cache_load_s = 0.0
        self.label_seconds = {}    # label -> wall seconds it covered
        self._installed = False

    # ----------------------------------------------------------- labels
    def _labels(self):
        stack = getattr(self._local, "labels", None)
        if stack is None:
            stack = self._local.labels = []
        return stack

    def current_label(self):
        stack = self._labels()
        return stack[-1] if stack else ""

    def label(self, name):
        """Context manager attributing compiles inside it to `name`
        (innermost label wins), adding the wall seconds it covered to
        `label_seconds[name]` and recording them as a process span.
        Hits are seen only once `install()` has run."""
        return _LabelContext(self, str(name))

    def _thread_hits(self):
        """Persistent-cache hits seen on this thread so far."""
        return getattr(self._local, "hits", 0)

    # -------------------------------------------------------- listeners
    def install(self):
        """Register the jax.monitoring listeners (idempotent; a no-op
        when jax is absent — the ledger then just stays empty)."""
        with self._lock:
            if self._installed:
                return self
            self._installed = True
        try:
            import jax
            jax.monitoring.register_event_duration_secs_listener(
                self._on_duration)
            jax.monitoring.register_event_listener(self._on_event)
        except Exception:
            # monitoring API drift / missing jax must never break
            # training; the ledger simply records nothing
            pass
        return self

    def _append(self, entry):
        self._recent.append(entry)
        self._undrained.append(entry)

    def _on_duration(self, name, secs, **kwargs):
        if name == _CACHE_LOAD_EVENT:
            with self._lock:
                self.cache_load_s += float(secs)
            return
        if name != _COMPILE_EVENT:
            return
        # the hit event, if any, came first on this thread (module
        # docstring): the duration is then a load, not a compile
        hit = bool(getattr(self._local, "hit", False))
        self._local.hit = False
        entry = {"label": self.current_label(), "seconds": float(secs),
                 "ts": time.time(), "cache_hit": hit}
        with self._lock:
            if not hit:
                self.compiles += 1
                self.total_s += float(secs)
            self._append(entry)

    def _on_event(self, name, **kwargs):
        if name == _CACHE_HIT_EVENT:
            self._local.hit = True
            self._local.hits = self._thread_hits() + 1
            with self._lock:
                self.cache_hits += 1
        elif name == _CACHE_MISS_EVENT:
            self._local.hit = False
            with self._lock:
                self.cache_misses += 1

    # ----------------------------------------------------------- readers
    def snapshot(self, recent_n=32):
        """JSON-ready totals + the most recent entries."""
        with self._lock:
            recent = (list(self._recent)[-int(recent_n):]
                      if recent_n else [])
            return {"compiles": self.compiles,
                    "total_s": round(self.total_s, 6),
                    "cache_hits": self.cache_hits,
                    "cache_misses": self.cache_misses,
                    "cache_load_s": round(self.cache_load_s, 6),
                    "label_seconds": {k: round(v, 6) for k, v
                                      in self.label_seconds.items()},
                    "recent": [dict(e) for e in recent]}

    def drain(self):
        """Entries recorded since the previous drain (journal writer's
        read-once view)."""
        with self._lock:
            out, self._undrained = self._undrained, []
        return out

    def reset(self):
        """Zero the totals (tests; the listeners stay installed)."""
        with self._lock:
            self._recent.clear()
            self._undrained = []
            self.compiles = 0
            self.total_s = 0.0
            self.cache_hits = 0
            self.cache_misses = 0
            self.cache_load_s = 0.0
            self.label_seconds = {}


class _LabelContext:
    """One label: a process span of the same name whose seconds are
    also the label's."""

    __slots__ = ("_ledger", "_name", "_span", "_hits0")

    def __init__(self, ledger, name):
        self._ledger = ledger
        self._name = name
        self._span = None
        self._hits0 = 0

    def __enter__(self):
        led = self._ledger
        led._labels().append(self._name)
        self._hits0 = led._thread_hits()
        self._span = PROCESS_TRACER.span(self._name)
        self._span.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        led = self._ledger
        span = self._span
        span.tag(cache_hit=led._thread_hits() > self._hits0)
        span.__exit__(exc_type, exc, tb)
        stack = led._labels()
        if stack and stack[-1] == self._name:
            stack.pop()
        with led._lock:
            led.label_seconds[self._name] = (
                led.label_seconds.get(self._name, 0.0) + span.seconds)
        return False


LEDGER = CompileLedger()


# ------------------------------------------------------- memory sampling

_PAGE_SIZE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096


def _host_rss_bytes():
    """Current RSS from /proc/self/statm (one read, ~microseconds)."""
    try:
        with open("/proc/self/statm", "rb") as f:
            return int(f.read().split()[1]) * _PAGE_SIZE
    except (OSError, ValueError, IndexError):
        return None


def _host_peak_rss_bytes():
    try:
        import resource
        # linux ru_maxrss is kilobytes
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    except Exception:
        return None


def _device_memory():
    """(bytes_in_use, peak_bytes_in_use) from the first local device's
    allocator, or (None, None) when unavailable (CPU jax publishes no
    stats; jax not imported means no device to ask)."""
    import sys
    jax = sys.modules.get("jax")
    if jax is None:
        return None, None
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        return None, None
    if not stats:
        return None, None
    in_use = stats.get("bytes_in_use")
    peak = stats.get("peak_bytes_in_use", in_use)
    return (int(in_use) if in_use is not None else None,
            int(peak) if peak is not None else None)


def sample_memory():
    """One point-in-time memory sample: only the fields that exist on
    this backend (journal `memory` records carry exactly these keys)."""
    out = {}
    dev, dev_peak = _device_memory()
    if dev is not None:
        out["device_bytes_in_use"] = dev
    if dev_peak is not None:
        out["device_peak_bytes"] = dev_peak
    rss = _host_rss_bytes()
    if rss is not None:
        out["host_rss_bytes"] = rss
    peak = _host_peak_rss_bytes()
    if peak is not None:
        out["host_peak_rss_bytes"] = peak
    return out
