"""Unified training telemetry: span tracing, metrics, run journal,
live /trainz endpoint.

The training-side observability stack (docs/Observability.md):

- `trace.SpanTracer` — per-Booster nested span timing, each span also
  a `jax.profiler.TraceAnnotation` once the embedder has imported jax;
  `trace.PROCESS_TRACER` for work that belongs to no Booster (set-up:
  import, dataset, booster init, the compile ledger's labels);
  `trace.DEVICE_SCOPES`, the `jax.named_scope` vocabulary of the
  device program.
- `registry.MetricsRegistry` — thread-safe counters/gauges/histograms;
  the serving layer's `/metricz` accounting (serving/metrics.py) is
  built on the same primitives.
- `journal.RunJournal` — append-only JSONL run timeline (atomic line
  writes, rank-suffixed files, rank-0 merge); schema in
  `journal.SCHEMA`, linted by `tools/check_journal.py`.
- `trainz.start_trainz` — opt-in stdlib HTTP thread serving the live
  training state (`telemetry_port` knob).
- `ledger.CompileLedger` / `ledger.sample_memory` — jit-lowering
  ledger (shape-bucket labels, persistent-cache hit/miss) and device/
  host memory watermarks.
- `prometheus.render` — the registry in Prometheus text exposition
  (`?format=prometheus` on /metricz and /trainz), with the canonical
  naming contract (`canonical_name`/`lint_names`) and the labeled
  multi-source page (`render_multi`).
- `export.export_trace` — the journal (+ span-ring dump) as Chrome
  trace-event JSON for Perfetto (`tools/export_trace.py`), with
  cross-rank collective flow events.
- `comm_profile.CommProfiler` — per-collective latency attribution,
  `comm_overlap_pct` and straggler deltas (`comm_telemetry` knob).
- `aggregate.FleetAggregator` — one poller merging every rank's
  /trainz + every replica's /metricz
  (`python -m lightgbm_tpu.telemetry.aggregate`).
- `history.append_run_summary` — the append-only RUN_HISTORY.jsonl
  store `tools/sentinel.py` trends over.

Nothing here imports jax (spans annotate, and the compile ledger's
`install()` listens, only where the embedder already has), so the
supervisor and CPU test harness can import it without touching the
accelerator runtime.
"""

from . import aggregate, comm_profile, export, history  # noqa: F401
from . import journal, ledger, prometheus  # noqa: F401
from . import registry, trace, trainz  # noqa: F401
from .aggregate import FleetAggregator  # noqa: F401
from .comm_profile import CommProfiler  # noqa: F401
from .export import build_trace, export_trace, validate_trace  # noqa: F401
from .history import append_run_summary, read_history  # noqa: F401
from .journal import RunJournal, merge_journals, read_journal  # noqa: F401
from .ledger import LEDGER, CompileLedger, sample_memory  # noqa: F401
from .registry import MetricsRegistry  # noqa: F401
from .trace import PROCESS_TRACER, SpanTracer  # noqa: F401
from .trainz import start_trainz, stop_trainz  # noqa: F401
