"""Unified training telemetry (lightgbm_tpu/telemetry/): span tracer,
metrics registry, structured run journal, /trainz endpoint, and the
serving /metricz parity after its refactor onto the registry.

Covers the contracts docs/Observability.md documents: span nesting and
exception safety, per-Booster tracer isolation, registry thread-safety under
concurrent writers, journal line atomicity across a hard kill + resume
(no torn JSONL), multi-rank merge ordering, schema lint of a REAL
training journal, and phase-delta reconstruction (the bench's journal
-> phases path).
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.telemetry import (MetricsRegistry, RunJournal,
                                    SpanTracer, merge_journals,
                                    read_journal, start_trainz,
                                    stop_trainz, trainz)
from lightgbm_tpu.telemetry.journal import (journal_path, rank_files,
                                            validate_record)
from lightgbm_tpu.utils import faults

REPO = os.path.dirname(os.path.dirname(__file__))


def _train(tmp_path, tag, n_rounds=4, fobj=None, **extra_params):
    rng = np.random.RandomState(3)
    x = rng.rand(300, 5)
    y = (x[:, 0] + x[:, 1] > 1).astype(float)
    params = {"objective": "binary", "num_leaves": 7,
              "min_data_in_leaf": 10, "verbose": 0,
              "telemetry": True,
              "telemetry_dir": str(tmp_path / tag)}
    params.update(extra_params)
    return lgb.train(params, lgb.Dataset(x, y), num_boost_round=n_rounds,
                     fobj=fobj)


def _sigmoid_fobj(preds, train_data):
    labels = train_data.get_label()
    p = 1.0 / (1.0 + np.exp(-preds))
    return p - labels, p * (1 - p)


# ------------------------------------------------------------ span tracer

def test_span_nesting_and_exception_safety():
    t = SpanTracer()
    with pytest.raises(ValueError):
        with t.span("outer"):
            with t.span("inner", leaf=3):
                raise ValueError("boom")
    # both spans closed despite the exception, nesting path recorded;
    # a child counts under its path
    assert t.cnt["outer"] == 1 and t.cnt["outer/inner"] == 1
    assert t.acc["outer"] >= t.acc["outer/inner"] >= 0.0
    paths = {s["path"] for s in t.recent()}
    assert "outer/inner" in paths and "outer" in paths
    assert t._stack() == []  # stack unwound
    # next span is top-level again
    with t.span("after"):
        pass
    assert [s["path"] for s in t.recent()][-1] == "after"


def test_span_delta_snapshot_sums_to_totals():
    t = SpanTracer()
    deltas = []
    for _ in range(3):
        with t.phase("build"):
            time.sleep(0.002)
        deltas.append(t.delta_snapshot().get("build", 0.0))
    assert all(d > 0 for d in deltas)
    assert sum(deltas) == pytest.approx(t.snapshot()["build"], abs=1e-5)
    assert t.delta_snapshot() == {}  # nothing moved since


def test_per_booster_tracer_isolation(tmp_path):
    """Two Boosters trained in one process keep independent phase
    accumulators."""
    b1 = _train(tmp_path, "iso1", n_rounds=4)
    snap1 = dict(b1.gbdt.tracer.snapshot())
    b2 = _train(tmp_path, "iso2", n_rounds=2)
    assert b1.gbdt.tracer is not b2.gbdt.tracer
    # training booster 2 did not move booster 1's accumulator
    assert b1.gbdt.tracer.snapshot() == snap1
    assert b2.gbdt.tracer.snapshot()


# ------------------------------------------------------- metrics registry

def test_registry_thread_safety_under_concurrent_writers():
    reg = MetricsRegistry()
    n_threads, n_ops = 8, 500
    barrier = threading.Barrier(n_threads)

    def writer(i):
        barrier.wait()
        for k in range(n_ops):
            reg.inc("ops")
            reg.inc("bytes", 10)
            reg.set("last_writer", i)
            reg.observe("lat", (i * n_ops + k) % 97)

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    snap = reg.snapshot()
    assert snap["counters"]["ops"] == n_threads * n_ops
    assert snap["counters"]["bytes"] == 10 * n_threads * n_ops
    assert snap["histograms"]["lat"]["count"] == n_threads * n_ops
    assert 0 <= snap["gauges"]["last_writer"] < n_threads


def test_registry_histogram_percentiles_nearest_rank():
    reg = MetricsRegistry()
    h = reg.histogram("h")
    h.observe(1.0)
    h.observe(100.0)
    assert h.percentiles()[50] == pytest.approx(1.0)  # lower, not max
    h2 = reg.histogram("h2")
    for i in range(100):
        h2.observe(float(i + 1))
    pct = h2.percentiles()
    assert pct[50] == pytest.approx(50.0)
    assert pct[99] == pytest.approx(99.0)  # rank 98, not the max


# ------------------------------------------------------------ run journal

def test_journal_records_validate_and_phases_reconstruct(tmp_path):
    """A real per-iteration training run: every record passes the
    schema lint and the per-record phase deltas sum back to the
    tracer's run totals (the bench's journal -> phases path)."""
    bst = _train(tmp_path, "lint", n_rounds=4, fobj=_sigmoid_fobj)
    g = bst.gbdt
    records, bad = read_journal(g.journal.path)
    assert bad == 0
    for rec in records:
        assert validate_record(rec) == [], rec
    it_recs = [r for r in records if r["event"] == "iteration"]
    assert [r["iteration"] for r in it_recs] == [1, 2, 3, 4]
    for rec in it_recs:  # per-iteration health fields present
        assert rec["grad_norm"] > 0 and rec["hess_norm"] > 0
        assert rec["leaf_count"] > 0
    totals = {}
    for rec in it_recs:
        for name, secs in rec["phases"].items():
            totals[name] = totals.get(name, 0.0) + secs
    run_totals = g.tracer.snapshot()
    for name in ("build", "score_upd", "host_sync"):
        assert totals[name] == pytest.approx(run_totals[name], abs=1e-4)


def test_journal_fused_block_record(tmp_path):
    bst = _train(tmp_path, "fused", n_rounds=5)
    records, _ = read_journal(bst.gbdt.journal.path)
    blocks = [r for r in records if r["event"] == "iteration"]
    assert blocks and blocks[-1]["fused"] is True
    assert sum(r["block"] for r in blocks) == 5
    assert "compile_cache_hit" in blocks[-1]
    assert "fused_block" in blocks[0]["phases"]


def test_journal_atomic_lines_across_hard_kill(tmp_path):
    """A writer os._exit-killed mid-stream (the preemption analog) must
    leave only complete lines; a second writer (the resumed run)
    appends past them and the file stays fully parseable."""
    d = str(tmp_path)
    code = (
        "from lightgbm_tpu.telemetry.journal import RunJournal\n"
        "import os\n"
        f"j = RunJournal({d!r}, rank=0)\n"
        "for i in range(200):\n"
        "    j.iteration(i + 1, phases={'build': 0.001})\n"
        "os._exit(43)\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 43
    # resumed writer appends to the same rank file
    j2 = RunJournal(d, rank=0, emit_run_start=False)
    j2.event("resume", iteration=200)
    j2.close()
    records, bad = read_journal(journal_path(d, 0))
    assert bad == 0, "torn JSONL line survived the kill"
    assert records[0]["event"] == "run_start"
    assert records[-1]["event"] == "resume"
    assert sum(r["event"] == "iteration" for r in records) == 200
    for rec in records:
        assert validate_record(rec) == []


def test_cli_crash_resume_lands_in_journal(tmp_path):
    """End to end through the CLI: a hard-killed run leaves its journal
    mid-iteration; the auto-resumed rerun appends a resume event and a
    run_end, the merged timeline lints clean, and no line is torn."""
    data = str(tmp_path / "train.tsv")
    rng = np.random.RandomState(5)
    x = rng.rand(400, 4)
    y = (x[:, 0] + x[:, 1] > 1).astype(int)
    with open(data, "w") as f:
        for i in range(400):
            f.write(str(y[i]) + "\t"
                    + "\t".join(f"{v:.6f}" for v in x[i]) + "\n")
    out_model = str(tmp_path / "model.txt")
    args = ["task=train", f"data={data}", "objective=binary",
            "num_trees=12", "num_leaves=7", "min_data_in_leaf=10",
            "metric_freq=0", "enable_load_from_binary_file=false",
            "snapshot_freq=4", f"output_model={out_model}",
            "telemetry=true"]

    def run(crash_env=None):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop(faults.ENV_VAR, None)
        if crash_env:
            env[faults.ENV_VAR] = crash_env
        return subprocess.run([sys.executable, "-m", "lightgbm_tpu"]
                              + args, cwd=REPO, env=env,
                              capture_output=True, text=True, timeout=420)

    r = run(crash_env="crash_at_iteration=8,hard_crash=1")
    assert r.returncode == faults.HARD_CRASH_EXIT_CODE
    jdir = out_model + ".snapshots"   # telemetry_dir defaults here
    records, bad = read_journal(journal_path(jdir, 0))
    assert bad == 0
    assert any(rec["event"] == "iteration" for rec in records)

    r = run()   # plain rerun auto-resumes
    assert r.returncode == 0, r.stdout + r.stderr
    merged = os.path.join(jdir, "journal.jsonl")
    assert os.path.exists(merged)   # rank 0 merged at end of training
    records, bad = read_journal(merged)
    assert bad == 0
    for rec in records:
        assert validate_record(rec) == [], rec
    events = [rec["event"] for rec in records]
    assert events.count("run_start") == 2   # both incarnations
    assert "resume" in events and "checkpoint" in events
    assert events[-1] == "run_end"
    resume = next(rec for rec in records if rec["event"] == "resume")
    assert resume["iteration"] == 8   # newest snapshot cadence point


def test_multi_rank_journal_merge(tmp_path):
    d = str(tmp_path)
    j0 = RunJournal(d, rank=0, meta={"num_ranks": 2})
    j1 = RunJournal(d, rank=1, meta={"num_ranks": 2})
    j0.iteration(1)
    time.sleep(0.01)
    j1.iteration(1)
    time.sleep(0.01)
    j1.event("abort", exit_code=117, reason="collective_watchdog",
             collective="tree_build", iteration=2)
    j0.event("run_end", iterations=1)
    j0.close()
    j1.close()
    assert len(rank_files(d)) == 2
    merged = merge_journals(d)
    records, bad = read_journal(merged)
    assert bad == 0
    ts = [rec["ts"] for rec in records]
    assert ts == sorted(ts)   # one wall-time-ordered timeline
    ranks = {rec["rank"] for rec in records}
    assert ranks == {0, 1}
    abort = next(rec for rec in records if rec["event"] == "abort")
    assert abort["rank"] == 1 and abort["exit_code"] == 117


def test_watchdog_expiry_writes_journal_abort(tmp_path):
    from lightgbm_tpu.parallel import heartbeat as hb
    from lightgbm_tpu.telemetry import journal as run_journal
    j = RunJournal(str(tmp_path), rank=2, emit_run_start=False)
    run_journal.set_current(j)
    try:
        wd = hb.CollectiveWatchdog(0.1, rank=2,
                                   on_expire=lambda n, i: None)
        wd.set_iteration(7)
        with wd.armed("hist_psum"):
            time.sleep(0.3)
    finally:
        run_journal.set_current(None)
    records, _ = read_journal(j.path)
    abort = next(rec for rec in records if rec["event"] == "abort")
    assert abort["exit_code"] == hb.EXIT_WATCHDOG
    assert abort["collective"] == "hist_psum" and abort["iteration"] == 7
    assert validate_record(abort) == []


def test_collective_timing_sink_feeds_registry():
    from lightgbm_tpu.parallel import heartbeat as hb
    reg = MetricsRegistry()
    hb.bind_timing_sink(lambda name, s: reg.observe("sync_wait_s", s))
    try:
        wd = hb.CollectiveWatchdog(30.0, rank=0)
        with wd.armed("leaf_count_sync"):
            time.sleep(0.01)
    finally:
        hb.bind_timing_sink(None)
    h = reg.histogram("sync_wait_s")
    assert h.count == 1 and h.last >= 0.01


# ---------------------------------------------------------------- /trainz

def test_trainz_endpoint_smoke(tmp_path):
    tracer = SpanTracer()
    with tracer.phase("build"):
        pass
    reg = MetricsRegistry()
    reg.inc("tree_build_dispatches", 4)
    j = RunJournal(str(tmp_path), rank=0)
    j.iteration(3, phases={"build": 0.1})
    srv = start_trainz(trainz.build_sources(
        iteration_fn=lambda: 3, tracer=tracer, registry=reg, journal=j),
        port=0)
    try:
        port = srv.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/trainz", timeout=30) as r:
            out = json.loads(r.read())
        assert out["iteration"] == 3
        assert "build" in out["phases"]
        assert out["metrics"]["counters"]["tree_build_dispatches"] == 4
        assert out["journal_tail"][-1]["event"] == "iteration"
        assert out["heartbeats"] is None   # no service running
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=30) as r:
            assert json.loads(r.read())["status"] == "ok"
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=30) as r:
            pass
    except urllib.error.HTTPError as e:
        assert e.code == 404
    finally:
        stop_trainz(srv)
        j.close()


def test_trainz_via_config_knob(tmp_path):
    """`telemetry_port` wires the live endpoint to a real training
    run's booster."""
    bst = _train(tmp_path, "tz", n_rounds=3, telemetry_port=0)
    # port 0 disables via config (0 = off); start explicitly instead
    g = bst.gbdt
    assert g._trainz_server is None
    srv = start_trainz(trainz.build_sources(
        iteration_fn=lambda: g.iter, tracer=g.tracer, registry=g.metrics,
        journal=g.journal), port=0)
    try:
        port = srv.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/trainz", timeout=30) as r:
            out = json.loads(r.read())
        assert out["iteration"] == 3
        assert out["journal_tail"]
    finally:
        stop_trainz(srv)


# ------------------------------------------------------- serving /metricz

def test_serving_metrics_parity_after_registry_refactor():
    """ServingMetrics moved onto telemetry.registry: the public
    attribute surface, percentile semantics, and the exact /metricz
    field set must be unchanged (tests/test_serving.py pins behavior in
    situ; this pins the contract directly)."""
    from lightgbm_tpu.serving.metrics import ServingMetrics
    m = ServingMetrics()
    m.record_request(5, 0.002)
    m.record_request(3, 0.004)
    m.record_batch(8, 2)
    m.record_error()
    assert (m.request_count, m.rows_served, m.error_count) == (2, 8, 1)
    assert (m.batch_count, m.batched_rows, m.batched_requests) == (1, 8, 2)
    snap = m.snapshot()
    assert set(snap) == {
        "uptime_s", "request_count", "rows_served", "error_count",
        "shed_count", "deadline_expired_count", "brownout_active",
        "batch_count", "batch_occupancy_rows",
        "batch_occupancy_requests", "latency_p50_ms", "latency_p95_ms",
        "latency_p99_ms", "latency_window"}
    assert snap["batch_occupancy_rows"] == pytest.approx(8.0)
    assert snap["latency_p50_ms"] == pytest.approx(2.0)
    assert snap["latency_window"] == 2
    # registry view exposes the same counts (one source of truth)
    reg = m.registry.snapshot()
    assert reg["counters"]["request_count"] == 2
    assert reg["histograms"]["latency_ms"]["count"] == 2


# -------------------------------------------------------------- log modes

def test_log_json_mode_and_rank_prefix(capsys, monkeypatch):
    from lightgbm_tpu.utils.log import Log
    monkeypatch.setenv("LIGHTGBM_TPU_LOG_JSON", "1")
    Log.set_rank(1)
    try:
        Log.info("hello %d", 42)
    finally:
        Log.set_rank(None)
    line = capsys.readouterr().out.strip()
    rec = json.loads(line)
    assert rec["level"] == "Info" and rec["msg"] == "hello 42"
    assert rec["rank"] == 1
    assert "T" in rec["ts"]   # ISO-8601


def test_log_timestamp_mode(capsys, monkeypatch):
    from lightgbm_tpu.utils.log import Log
    monkeypatch.setenv("LIGHTGBM_TPU_LOG_TS", "1")
    Log.info("stamped")
    out = capsys.readouterr().out
    assert out.startswith("[LightGBM-TPU] [2")   # ISO year prefix
    assert "stamped" in out
    monkeypatch.delenv("LIGHTGBM_TPU_LOG_TS")
    Log.info("plain")
    assert capsys.readouterr().out.startswith("[LightGBM-TPU] [Info]")


# ----------------------------------------------------------- schema lint

def test_check_journal_cli_flags_violations(tmp_path):
    good = tmp_path / "journal.rank0000.jsonl"
    rec = {"ts": time.time(), "event": "iteration", "rank": 0,
           "iteration": 1}
    good.write_text(json.dumps(rec) + "\n")
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps(rec) + "\n"
                   + '{"ts": 1.0, "event": "nope", "rank": 0}\n'
                   + '{"torn...\n')
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    ok = subprocess.run([sys.executable, "tools/check_journal.py",
                         str(tmp_path)], cwd=REPO, env=env,
                        capture_output=True, text=True, timeout=120)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    fail = subprocess.run([sys.executable, "tools/check_journal.py",
                           str(bad)], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120)
    assert fail.returncode == 1
    assert "unknown event" in fail.stderr
    assert "torn/garbled" in fail.stderr


# ----------------------------------- performance introspection (PR 8)
#
# The introspection layer on top of the PR-5 instrument: synthetic
# spans for externally-timed phases, the compile ledger, the live
# roofline table, Prometheus exposition, /metricz, and the Chrome
# trace-event exporter (docs/Observability.md).

def test_add_records_synthetic_span_with_tid():
    """SpanTracer.add() used to bump acc/cnt only — externally-timed
    phases (the bench compile window) vanished from /trainz and every
    exported trace. It must land a synthetic span stamped with the
    recording thread's id."""
    t = SpanTracer()
    t.add("compile", 1.5)
    spans = t.recent()
    assert len(spans) == 1
    assert spans[0]["name"] == "compile"
    assert spans[0]["duration_s"] == pytest.approx(1.5)
    assert spans[0]["tags"] == {"synthetic": True}
    assert spans[0]["tid"] == threading.get_ident()
    assert t.acc["compile"] == pytest.approx(1.5) and t.cnt["compile"] == 1
    # a span recorded on another thread carries ITS tid (separate
    # export track); n=None dumps the whole ring (the journal's
    # `spans` record at close)
    th = threading.Thread(target=lambda: t.add("other", 0.1))
    th.start()
    th.join()
    dump = t.recent(n=None)
    assert len(dump) == 2
    assert len({s["tid"] for s in dump}) == 2


def test_compile_ledger_attribution_and_drain():
    from lightgbm_tpu.telemetry.ledger import (_CACHE_HIT_EVENT,
                                               _CACHE_MISS_EVENT,
                                               _COMPILE_EVENT,
                                               CompileLedger)
    led = CompileLedger()
    with led.label("fused_scan_10it"):
        led._on_event(_CACHE_MISS_EVENT)
        led._on_duration(_COMPILE_EVENT, 1.25)
    # a hit's entry is written when its duration (the load) arrives
    led._on_event(_CACHE_HIT_EVENT)
    led._on_duration(_COMPILE_EVENT, 0.02)
    led._on_duration("/jax/unrelated/event", 9.0)   # ignored
    snap = led.snapshot()
    assert snap["compiles"] == 1
    assert snap["total_s"] == pytest.approx(1.25)
    assert snap["cache_hits"] == 1 and snap["cache_misses"] == 1
    assert [e["label"] for e in snap["recent"]] == ["fused_scan_10it", ""]
    hit = snap["recent"][-1]
    assert hit["cache_hit"] is True and hit["seconds"] == 0.02
    # label stack unwinds: a compile after the context is unattributed
    assert led.current_label() == ""
    # drain() hands each entry to the journal writer exactly once;
    # totals survive the drain (the /trainz view is cumulative)
    assert len(led.drain()) == 2
    assert led.drain() == []
    assert led.snapshot()["compiles"] == 1
    assert led.snapshot(recent_n=0)["recent"] == []


def test_ledger_memory_sample_has_host_watermarks():
    from lightgbm_tpu.telemetry.ledger import sample_memory
    mem = sample_memory()
    # this image's CPU jax publishes no device allocator stats, but the
    # host RSS pair from /proc + getrusage must always ride along
    assert mem["host_rss_bytes"] > 0
    assert mem["host_peak_rss_bytes"] >= 0


def test_prometheus_render_parse_roundtrip():
    from lightgbm_tpu.telemetry import prometheus
    reg = MetricsRegistry()
    reg.inc("tree_build_dispatches", 7)
    reg.set("device_bytes_in_use", 12345)
    h = reg.histogram("latency_ms")
    for v in range(1, 101):
        h.observe(float(v))
    text = prometheus.render(reg.snapshot(),
                             extra_gauges={"stream hist/bytes": 3.5,
                                           "iteration": 9,
                                           "not a number": "skipped"})
    parsed = prometheus.parse(text)   # raises on malformed exposition
    # the naming audit's canonical exposition names: counters end
    # _total, `_ms` metrics scale to base-unit `_seconds`
    assert parsed["lightgbm_tpu_tree_build_dispatches_total"] == 7
    assert parsed["lightgbm_tpu_device_bytes_in_use"] == 12345
    assert parsed['lightgbm_tpu_latency_seconds{quantile="0.5"}'] \
        in (0.050, 0.051)
    assert parsed["lightgbm_tpu_latency_seconds_count"] == 100
    assert parsed["lightgbm_tpu_latency_seconds_sum"] == pytest.approx(
        5.050)
    # illegal chars sanitize instead of corrupting the page; the
    # non-numeric extra is skipped entirely
    assert parsed["lightgbm_tpu_stream_hist_bytes"] == 3.5
    assert parsed["lightgbm_tpu_iteration"] == 9
    assert not any("not" in k for k in parsed)
    assert "# TYPE lightgbm_tpu_tree_build_dispatches_total counter" \
        in text
    assert "# TYPE lightgbm_tpu_latency_seconds summary" in text
    assert prometheus.lint_names(text) == []


def test_prometheus_parse_rejects_malformed():
    from lightgbm_tpu.telemetry import prometheus
    with pytest.raises(ValueError):
        prometheus.parse("lightgbm_tpu_x 1 2 extra junk words\n")
    with pytest.raises(ValueError):
        prometheus.parse("9bad_name 1\n")
    with pytest.raises(ValueError):
        prometheus.parse("lightgbm_tpu_x notafloat\n")


def test_trainz_metricz_and_prometheus_endpoints(tmp_path):
    from lightgbm_tpu.telemetry import prometheus
    tracer = SpanTracer()
    with tracer.phase("build"):
        pass
    reg = MetricsRegistry()
    reg.inc("tree_build_dispatches", 4)
    j = RunJournal(str(tmp_path), rank=0)
    j.iteration(3, phases={"build": 0.1})
    srv = start_trainz(trainz.build_sources(
        iteration_fn=lambda: 3, tracer=tracer, registry=reg, journal=j),
        port=0)
    try:
        port = srv.server_address[1]

        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=30) as r:
                return r.headers.get("Content-Type"), r.read()

        # /metricz JSON: the registry + introspection scalars only
        _, raw = get("/metricz")
        out = json.loads(raw)
        assert out["metrics"]["counters"]["tree_build_dispatches"] == 4
        assert out["iteration"] == 3
        assert out["memory"]["host_rss_bytes"] > 0
        assert "compiles" in out["compile"]
        # /trainz carries the introspection sources too
        _, raw = get("/trainz")
        full = json.loads(raw)
        for key in ("memory", "compile"):
            assert key in full
        assert "roofline" not in full
        # ?format=prometheus on BOTH paths: parseable text exposition
        for path in ("/metricz?format=prometheus",
                     "/trainz?format=prometheus"):
            ctype, raw = get(path)
            assert ctype.startswith("text/plain")
            parsed = prometheus.parse(raw.decode())
            assert parsed["lightgbm_tpu_tree_build_dispatches_total"] \
                == 4
            assert parsed["lightgbm_tpu_iteration"] == 3
            assert parsed["lightgbm_tpu_host_rss_bytes"] > 0
            assert prometheus.lint_names(raw.decode()) == []
    finally:
        stop_trainz(srv)
        j.close()


def test_concurrent_scrape_during_training(tmp_path):
    """/trainz and /metricz snapshots taken WHILE a Booster trains:
    every scrape returns consistent JSON / parseable exposition — no
    torn reads, no 500s (the satellite's acceptance)."""
    from lightgbm_tpu.telemetry import prometheus
    rng = np.random.RandomState(11)
    x = rng.rand(400, 5)
    y = (x[:, 0] + x[:, 1] > 1).astype(float)
    holder, errors, scrapes = {}, [], []
    stop = threading.Event()

    def scraper():
        port = holder["port"]
        while not stop.is_set():
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/trainz",
                        timeout=30) as r:
                    out = json.loads(r.read())
                    assert "phases" in out
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metricz"
                        "?format=prometheus", timeout=30) as r:
                    prometheus.parse(r.read().decode())
                scrapes.append(1)
            except Exception as e:   # noqa: BLE001 - recorded for assert
                errors.append(repr(e))
                return

    threads = [threading.Thread(target=scraper) for _ in range(2)]

    def cb(env):
        g = env.model.gbdt
        if "port" not in holder:
            srv = start_trainz(trainz.build_sources(
                iteration_fn=lambda: g.iter, tracer=g.tracer,
                registry=g.metrics, journal=g.journal), port=0)
            holder["srv"], holder["port"] = srv, srv.server_address[1]
            for t in threads:
                t.start()
        time.sleep(0.005)   # guarantee scrapes overlap live training

    try:
        lgb.train({"objective": "binary", "num_leaves": 7,
                   "min_data_in_leaf": 10, "verbose": 0,
                   "telemetry": True,
                   "telemetry_dir": str(tmp_path / "conc")},
                  lgb.Dataset(x, y), num_boost_round=30, callbacks=[cb])
    finally:
        stop.set()
        for t in threads:
            if t.ident is not None:
                t.join(timeout=30)
        if "srv" in holder:
            stop_trainz(holder["srv"])
    assert not errors, errors
    assert scrapes, "no scrape overlapped the training run"


def test_memory_compile_spans_records_land_in_journal(tmp_path):
    """Iteration boundaries append `memory` watermarks; close drains
    the span ring into ONE `spans` record (telemetry_trace knob) and
    everything validates against the schema."""
    bst = _train(tmp_path, "intro", n_rounds=3, telemetry_trace=True)
    g = bst.gbdt
    jdir = g.journal.directory
    g.close_telemetry()
    records, bad = read_journal(journal_path(jdir, 0))
    assert bad == 0
    for rec in records:
        assert validate_record(rec) == [], rec
    mems = [r for r in records if r["event"] == "memory"]
    # one per iteration/BLOCK boundary (the fused path emits one record
    # per compiled block) + the final close-time drain
    assert len(mems) >= 2
    assert all(m["host_rss_bytes"] > 0 for m in mems)
    assert all(m["iteration"] >= 0 for m in mems)
    dumps = [r for r in records if r["event"] == "spans"]
    assert len(dumps) == 1     # once-only, even if close runs twice
    assert dumps[0]["epoch_ts"] > 0
    assert dumps[0]["spans"], "span ring dump is empty"
    assert all("tid" in s and "start_s" in s for s in dumps[0]["spans"])
    # registry gauges mirror the latest memory sample
    assert g.metrics.gauge("host_rss_bytes").value > 0


def test_export_trace_multirank_crash_restart(tmp_path):
    """The acceptance shape: a 2-rank crash -> restart -> resume
    journal exports to ONE valid Chrome trace-event JSON with per-rank
    tracks covering iterations, the abort and the restart."""
    from lightgbm_tpu.telemetry import export
    d = str(tmp_path)
    j0 = RunJournal(d, rank=0, meta={"num_ranks": 2})
    j1 = RunJournal(d, rank=1, meta={"num_ranks": 2})
    for i in (1, 2):
        j0.iteration(i, phases={"build": 0.01, "score_upd": 0.002})
        j1.iteration(i, phases={"build": 0.012})
    j1.event("abort", exit_code=117, reason="collective_watchdog",
             collective="tree_build", iteration=3)
    j0.event("restart", attempt=1, exit_code=117, source="supervisor")
    j0.event("resume", iteration=2)
    j0.event("memory", iteration=2, host_rss_bytes=123456789)
    j0.event("checkpoint", iteration=2, path="snap", write_s=0.004)
    j0.event("compile", label="fused_scan_2it", seconds=0.5,
             cache_hit=False)
    j0.event("spans", epoch_ts=time.time() - 1.0,
             spans=[{"name": "build", "path": "train/build",
                     "start_s": 0.5, "duration_s": 0.01, "tid": 1111},
                    {"name": "hb", "path": "hb",
                     "start_s": 0.6, "duration_s": 0.002, "tid": 2222}])
    j0.event("run_end", iterations=2)
    j0.close()
    j1.close()

    trace, out_path = export.export_trace(d)
    assert export.validate_trace(trace) == []
    with open(out_path, encoding="utf-8") as f:
        loaded = json.load(f)          # the verify-obs roundtrip
    assert export.validate_trace(loaded) == []
    events = loaded["traceEvents"]
    by_pid = {e["pid"] for e in events}
    assert by_pid == {0, 1}            # one process track per rank
    names = [e["name"] for e in events]
    assert "iteration 1" in names and "iteration 2" in names
    assert any(n.startswith("abort exit=117") for n in names)
    assert any(n.startswith("restart attempt=1") for n in names)
    assert any(n.startswith("resume @2") for n in names)
    assert any(n.startswith("compile fused_scan_2it") for n in names)
    assert any(n.startswith("checkpoint @2") for n in names)
    # phase children lie INSIDE their iteration slice
    it0 = next(e for e in events if e["name"] == "iteration 1"
               and e["pid"] == 0)
    build = next(e for e in events if e["name"] == "build"
                 and e["pid"] == 0 and e["tid"] == export.TID_TRAIN)
    assert it0["ts"] <= build["ts"]
    assert build["ts"] + build["dur"] <= it0["ts"] + it0["dur"] + 1
    # the spans dump lands on per-thread lanes
    span_lanes = {e["tid"] for e in events
                  if e.get("ph") == "X" and e["tid"] >= export.TID_SPAN_BASE}
    assert len(span_lanes) == 2
    # memory became a counter track Perfetto can plot
    assert any(e["ph"] == "C" and e["name"] == "memory_bytes"
               for e in events)
    # supervisor-sourced records get their own thread lane
    sup = next(e for e in events if e["name"].startswith("restart"))
    assert sup["tid"] == export.TID_SUPERVISOR
    # timestamps rebased: everything starts at/after t=0
    assert min(e["ts"] for e in events if e["ph"] != "M") >= 0


def test_export_trace_cli(tmp_path):
    """tools/export_trace.py end to end: journal dir -> trace.json on
    disk, --validate runs the invariant check."""
    d = str(tmp_path)
    j = RunJournal(d, rank=0)
    j.iteration(1, phases={"build": 0.01})
    j.event("run_end", iterations=1)
    j.close()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "tools/export_trace.py", d,
                        "--validate"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "trace invariants OK" in r.stdout
    with open(os.path.join(d, "trace.json"), encoding="utf-8") as f:
        trace = json.load(f)
    assert trace["traceEvents"]
    # empty dir exits 2, not a stack trace
    empty = tmp_path / "empty"
    empty.mkdir()
    r2 = subprocess.run([sys.executable, "tools/export_trace.py",
                         str(empty)], cwd=REPO, env=env,
                        capture_output=True, text=True, timeout=120)
    assert r2.returncode == 2


def test_structured_log_record_modes(capsys, monkeypatch):
    """Log.structured: one JSON object (fields merged) in JSON mode,
    `event k=v` text otherwise — the serving access-log contract."""
    from lightgbm_tpu.utils.log import Log
    monkeypatch.delenv("LIGHTGBM_TPU_LOG_JSON", raising=False)
    Log.structured("Info", "access", request_id="r1", path="/predict",
                   rows=3, status=200)
    out = capsys.readouterr().out
    assert "access request_id=r1 path=/predict rows=3 status=200" in out
    monkeypatch.setenv("LIGHTGBM_TPU_LOG_JSON", "1")
    Log.structured("Warning", "slow_request", request_id="r2",
                   total_ms=12.5)
    rec = json.loads(capsys.readouterr().out)
    assert rec["event"] == "slow_request" and rec["level"] == "Warning"
    assert rec["request_id"] == "r2" and rec["total_ms"] == 12.5
    # gated below the active level: nothing is written
    monkeypatch.setattr(Log, "_level", 0)
    Log.structured("Info", "access", request_id="r3")
    assert capsys.readouterr().out == ""
