"""Fleet-wide observability: collective latency/overlap attribution
(telemetry/comm_profile.py), cross-rank Perfetto flow events, the
unified aggregator (telemetry/aggregate.py), the run-history store +
regression sentinel (telemetry/history.py, tools/sentinel.py), and
the Prometheus naming audit — ISSUE 13's acceptance surface.

The 2-process gloo rung at the bottom is THE acceptance path: per-rank
`comm` journal records with per-collective waits, straggler deltas
consistent across ranks, the aggregator merging two live /trainz
endpoints mid-training, and the merged trace export carrying
cross-rank flow events through validate_trace.
"""

import json
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.telemetry import export, prometheus, trainz
from lightgbm_tpu.telemetry import history as history_mod
from lightgbm_tpu.telemetry.aggregate import FleetAggregator, Target
from lightgbm_tpu.telemetry.comm_profile import (CommProfiler,
                                                 overlap_pct)
from lightgbm_tpu.telemetry.journal import (RunJournal,
                                            detect_clock_skew,
                                            merge_journals,
                                            read_journal,
                                            validate_record)
from lightgbm_tpu.telemetry.registry import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------ comm profiler

def test_comm_profiler_wait_vs_dispatch_split():
    prof = CommProfiler(rank=3)
    prof.record("data:tree_build", 0.40)       # dispatch window
    prof.record("fused_block", 0.10)           # dispatch window
    prof.record("leaf_count_sync", 0.05)       # sync wait
    prof.record("leaf_count_sync", 0.05)
    prof.record("data:row_leaf_gather", 0.02)  # sync wait
    rec = prof.flush(7)
    assert rec["iteration"] == 7
    assert rec["wait_s"] == pytest.approx(0.12)
    assert rec["dispatch_s"] == pytest.approx(0.50)
    assert rec["waits"]["leaf_count_sync"] == pytest.approx(0.10)
    assert 0.0 <= rec["overlap_pct"] <= 100.0
    assert validate_record({"ts": 1.0, "event": "comm", "rank": 3,
                            **rec}) == []
    # cumulative split survives the flush
    assert prof.cum_wait_s == pytest.approx(0.12)
    assert prof.cum_dispatch_s == pytest.approx(0.50)
    # nothing measured since -> no record (quiet when idle)
    assert prof.flush(8) is None
    snap = prof.snapshot()
    assert snap["rank"] == 3
    assert snap["totals"]["leaf_count_sync"]["count"] == 2
    assert snap["overlap_pct"] == rec["overlap_pct"]


def test_overlap_pct_bounds():
    assert overlap_pct(0.0, 1.0) == 100.0
    assert overlap_pct(1.0, 1.0) == 0.0
    assert overlap_pct(2.0, 1.0) == 0.0     # clipped
    assert overlap_pct(0.25, 1.0) == 75.0
    assert overlap_pct(0.0, 0.0) == 100.0   # degenerate window


def test_straggler_deltas_from_heartbeat_beats(tmp_path):
    from lightgbm_tpu.parallel import heartbeat
    d = str(tmp_path)
    svc = heartbeat.HeartbeatService(d, rank=0, num_ranks=3,
                                     timeout_s=60)
    # peers published their cumulative waits via the beat piggyback
    heartbeat.atomic_write_json(
        heartbeat.heartbeat_path(d, 1),
        {"rank": 1, "seq": 4, "comm_wait_s": 0.9})
    heartbeat.atomic_write_json(
        heartbeat.heartbeat_path(d, 2),
        {"rank": 2, "seq": 2, "comm_wait_s": 0.1})
    prof = CommProfiler(rank=0)
    prof.record("leaf_count_sync", 0.3)
    deltas = prof.straggler_deltas(svc)
    assert deltas == {"0": pytest.approx(0.2), "1": pytest.approx(0.8),
                      "2": 0.0}


def test_beat_extra_lands_in_published_beat(tmp_path):
    from lightgbm_tpu.parallel import heartbeat
    svc = heartbeat.HeartbeatService(str(tmp_path), rank=0,
                                     num_ranks=2, timeout_s=60)
    heartbeat.bind_beat_extra(lambda: {"comm_wait_s": 1.25})
    try:
        svc.publish()
    finally:
        heartbeat.bind_beat_extra(None)
    beat = heartbeat.read_heartbeat(
        heartbeat.heartbeat_path(str(tmp_path), 0))
    assert beat["comm_wait_s"] == 1.25
    assert beat["seq"] == 1   # piggyback must not clobber core fields


def test_timing_sink_measures_without_armed_watchdog():
    """Binding a timing sink makes guarded sections measure even with
    the watchdog timer disarmed (comm telemetry must not require an
    abort timer)."""
    from lightgbm_tpu.parallel import heartbeat
    assert heartbeat.WATCHDOG.timeout_s == 0.0
    seen = []
    heartbeat.bind_timing_sink(lambda name, s: seen.append((name, s)))
    try:
        with heartbeat.collective_guard("probe_sync"):
            pass
    finally:
        heartbeat.bind_timing_sink(None)
    assert seen and seen[0][0] == "probe_sync"
    # unbound again -> zero-overhead no-measure path
    with heartbeat.collective_guard("probe_sync2"):
        pass
    assert len(seen) == 1


# ----------------------------------------- comm records e2e (1 process)

def _train_telemetry(tmp_path, n_rounds=3, **params):
    rng = np.random.RandomState(5)
    x = rng.rand(500, 8)
    y = (x[:, 0] + x[:, 1] > 1).astype(float)
    base = {"objective": "binary", "num_leaves": 7,
            "min_data_in_leaf": 10, "verbose": 0, "metric_freq": 0,
            "telemetry": True, "telemetry_dir": str(tmp_path)}
    base.update(params)
    return lgb.train(base, lgb.Dataset(x, y), num_boost_round=n_rounds)


def test_comm_records_journal_and_gauges(tmp_path):
    bst = _train_telemetry(tmp_path, tree_learner="data",
                           num_machines=2, device_row_chunk=256)
    g = bst.gbdt
    assert g.comm_profile is not None
    records, bad = read_journal(g.journal.path)
    assert bad == 0
    comm = [r for r in records if r["event"] == "comm"]
    assert comm, "no comm records from a meshed telemetry run"
    for rec in comm:
        assert validate_record(rec) == [], rec
        assert 0.0 <= rec["overlap_pct"] <= 100.0
        assert rec["wait_s"] >= 0 and rec["wall_s"] > 0
        assert "mono" in rec
    # the guarded dispatch was attributed as dispatch, not wait: with
    # tree_learner=data on the fused scan, the block's one guarded sync
    all_waits = {k for r in comm for k in (r.get("waits") or {})}
    assert any(k.endswith("fused_block") for k in all_waits)
    snap = g.metrics.snapshot()["gauges"]
    assert 0.0 <= snap["comm_overlap_pct"] <= 100.0
    assert snap["comm_wait_s"] >= 0.0
    # /trainz comm source carries the same view
    comm_snap = g.comm_profile.snapshot()
    assert comm_snap["overlap_pct"] == comm[-1]["overlap_pct"]


def test_comm_telemetry_off_knob(tmp_path):
    bst = _train_telemetry(tmp_path, comm_telemetry=False)
    g = bst.gbdt
    assert g.comm_profile is None
    records, _ = read_journal(g.journal.path)
    assert not [r for r in records if r["event"] == "comm"]


# ------------------------------------------------- journal mono + skew

def test_merge_preserves_within_rank_order_despite_clock_step(tmp_path):
    d = str(tmp_path)
    j = RunJournal(d, rank=0, emit_run_start=False)
    j.event("note", msg="first")
    j.event("note", msg="second")
    j.close()
    # simulate a wall-clock step backwards mid-run: rewrite ts so wall
    # order contradicts append order
    path = j.path
    records, _ = read_journal(path)
    records[0]["ts"] = records[1]["ts"] + 100.0
    with open(path, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    merged = merge_journals(d)
    out, _ = read_journal(merged)
    msgs = [r["msg"] for r in out if r["event"] == "note"]
    # append order won within the rank (a reboot-reset `mono` must not
    # reorder either — file order is the truth)
    assert msgs == ["first", "second"]


def test_merge_flags_cross_rank_clock_skew(tmp_path):
    d = str(tmp_path)
    now = time.time()
    for rank, skew in ((0, 0.0), (1, 30.0)):   # rank 1's clock +30s
        j = RunJournal(d, rank=rank, emit_run_start=False)
        j.close()
        with open(j.path, "w") as f:
            for i in (1, 2):
                f.write(json.dumps(
                    {"ts": now + i + skew, "mono": float(i),
                     "event": "iteration", "rank": rank,
                     "iteration": i}) + "\n")
    skew_s, it = detect_clock_skew(
        {p: read_journal(p)[0]
         for p in [os.path.join(d, f"journal.rank000{r}.jsonl")
                   for r in (0, 1)]})
    assert skew_s == pytest.approx(30.0)
    merged = merge_journals(d, skew_threshold_s=2.0)
    out, _ = read_journal(merged)
    notes = [r for r in out if r["event"] == "note"
             and "clock_skew" in (r.get("msg") or "")]
    assert len(notes) == 1
    assert validate_record(notes[0]) == []
    # a skew-free merge stays note-free
    clean = str(tmp_path / "clean")
    for rank in (0, 1):
        j = RunJournal(clean, rank=rank, emit_run_start=False)
        j.event("iteration", iteration=1)
        j.close()
    out, _ = read_journal(merge_journals(clean))
    assert not [r for r in out if r["event"] == "note"]


# ------------------------------------------------- flow events (export)

def test_export_comm_slices_and_cross_rank_flows(tmp_path):
    d = str(tmp_path)
    for rank, wait in ((0, 0.01), (1, 0.05)):
        j = RunJournal(d, rank=rank, emit_run_start=False)
        for i in (1, 2):
            j.iteration(i, phases={"build": 0.1})
            j.event("comm", iteration=i,
                    waits={"leaf_count_sync": wait,
                           "data:tree_build": 0.08},
                    wait_s=wait, dispatch_s=0.08, wall_s=0.2,
                    overlap_pct=round(100 * (1 - wait / 0.2), 2))
        j.close()
    trace, out_path = export.export_trace(d)
    assert export.validate_trace(trace) == []
    events = trace["traceEvents"]
    comm_slices = [e for e in events
                   if e.get("ph") == "X" and e["tid"] == export.TID_COMM]
    assert len(comm_slices) == 8   # 2 ranks x 2 iters x 2 collectives
    flows = [e for e in events if e.get("ph") in ("s", "t", "f")]
    assert len(flows) == 8         # 2 iters x 2 collectives x 2 ranks
    # each flow id starts on one rank and finishes on the other
    by_id = {}
    for e in flows:
        by_id.setdefault(e["id"], []).append(e)
    for fid, evs in by_id.items():
        assert sorted(e["ph"] for e in evs) == ["f", "s"]
        assert {e["pid"] for e in evs} == {0, 1}
        assert all(e["tid"] == export.TID_COMM for e in evs)
    # overlap became a counter track
    assert any(e.get("ph") == "C" and e["name"] == "comm_overlap"
               for e in events)
    with open(out_path, encoding="utf-8") as f:
        assert export.validate_trace(json.load(f)) == []


def test_validate_trace_rejects_unpaired_flow():
    trace = {"traceEvents": [
        {"name": "x", "ph": "X", "ts": 0, "dur": 5, "pid": 0, "tid": 0},
        {"name": "flow", "ph": "s", "cat": "c", "id": 1, "ts": 1,
         "pid": 0, "tid": 0}]}
    errors = export.validate_trace(trace)
    assert any("flow id" in e for e in errors)


# ------------------------------------------- prometheus naming audit

def test_canonical_names_and_lint():
    cn = prometheus.canonical_name
    assert cn("sync_wait_s", "summary") == ("sync_wait_seconds", 1.0)
    assert cn("latency_ms", "summary") == ("latency_seconds", 1e-3)
    assert cn("prefetch_overlap_pct", "gauge") == (
        "prefetch_overlap_ratio", 1e-2)
    assert cn("hist_bytes_per_s", "gauge") == (
        "hist_bytes_per_second", 1.0)
    assert cn("transfer_bytes", "counter") == (
        "transfer_bytes_total", 1.0)
    assert cn("request_count", "counter") == ("request_total", 1.0)
    assert cn("leaves_total", "counter") == ("leaves_total", 1.0)
    assert cn("drift_psi_Column_0", "gauge") == (
        "drift_psi_column_0", 1.0)
    bad = ("# TYPE lightgbm_tpu_foo_s gauge\nlightgbm_tpu_foo_s 1\n"
           "# TYPE lightgbm_tpu_bar counter\nlightgbm_tpu_bar 2\n"
           "# TYPE unprefixed_total counter\nunprefixed_total 3\n")
    violations = prometheus.lint_names(bad)
    assert len(violations) == 3
    assert any("legacy unit suffix" in v for v in violations)
    assert any("must end _total" in v for v in violations)
    assert any("prefix" in v for v in violations)


def test_every_registry_renders_lint_clean(tmp_path):
    """The audit's acceptance: a real training registry, a real
    serving registry and the aggregator page all render conformant."""
    bst = _train_telemetry(tmp_path, quality_telemetry=True)
    g = bst.gbdt
    text = prometheus.render(g.metrics.snapshot())
    assert prometheus.lint_names(text) == []
    prometheus.parse(text)

    from lightgbm_tpu.serving.metrics import ServingMetrics
    sm = ServingMetrics()
    sm.record_request(8, 0.004)
    sm.record_batch(8, 2)
    sm.record_error()
    reg = sm.registry.snapshot()
    # registry-owned names ride the registry render; only derived
    # scalars go in as extra gauges (the server's own /metricz filter,
    # serving/server.py _prometheus)
    owned = (set(reg["counters"]) | set(reg["gauges"])
             | set(reg["histograms"]))
    text = prometheus.render(reg,
                             extra_gauges={k: v for k, v in
                                           sm.snapshot().items()
                                           if isinstance(v, (int, float))
                                           and k not in owned})
    assert prometheus.lint_names(text) == []
    prometheus.parse(text)


# ---------------------------------------------------------- aggregator

class _FakeServeHandler(BaseHTTPRequestHandler):
    doc = {"request_count": 10, "error_count": 1,
           "latency_p99_ms": 7.5, "uptime_s": 3.0}

    def log_message(self, *a):
        pass

    def do_GET(self):
        if self.path.startswith("/metricz"):
            data = json.dumps(self.doc).encode()
            self.send_response(200)
        else:
            data = b"{}"
            self.send_response(404)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)


def _fake_train_rank(rank, wait):
    reg = MetricsRegistry()
    reg.histogram("sync_wait_s").observe(wait)
    reg.set("prefetch_overlap_pct", 95.0 + rank)
    comm = {"rank": rank, "cum_wait_s": wait,
            "overlap_pct": 90.0 + rank, "last": {}}
    return trainz.start_trainz(trainz.build_sources(
        iteration_fn=lambda r=rank: 5 + r, registry=reg,
        comm_fn=lambda c=comm: c), port=0)


def test_aggregator_merges_train_and_serving_targets():
    trainers = [_fake_train_rank(0, 0.1), _fake_train_rank(1, 0.4)]
    serve_srv = ThreadingHTTPServer(("127.0.0.1", 0),
                                    _FakeServeHandler)
    serve_srv.daemon_threads = True
    threading.Thread(target=serve_srv.serve_forever,
                     daemon=True).start()
    dead_port = socket.socket()
    dead_port.bind(("127.0.0.1", 0))
    targets = ([f"127.0.0.1:{s.server_address[1]}" for s in trainers]
               + [f"serve=127.0.0.1:{serve_srv.server_address[1]}",
                  f"127.0.0.1:{dead_port.getsockname()[1]}"])
    dead_port.close()
    try:
        agg = FleetAggregator(targets, poll_s=0.2, timeout_s=5.0)
        snap = agg.poll_once()
        fleet = snap["fleet"]
        assert fleet["train_ranks"] == 2
        assert fleet["serve_replicas"] == 1
        assert fleet["unreachable"] == 1
        assert fleet["max_sync_wait_s"] == pytest.approx(0.4)
        assert fleet["straggler_s"] == {"0": 0.0,
                                        "1": pytest.approx(0.3)}
        assert fleet["min_comm_overlap_pct"] == 90.0
        assert fleet["min_prefetch_overlap_pct"] == 95.0
        assert fleet["iteration_lag"] == 1
        assert fleet["worst_latency_p99_ms"] == 7.5
        assert fleet["request_count"] == 10
        assert fleet["error_count"] == 1
        # one labeled exposition page, lint-clean, parseable, with
        # every family's TYPE line unique
        text = agg.prometheus()
        assert prometheus.lint_names(text) == []
        prometheus.parse(text)
        assert 'rank="0"' in text and 'rank="1"' in text
        assert 'role="serve"' in text
        assert "lightgbm_tpu_fleet_max_sync_wait_seconds" in text
        # serving counters carry the SAME canonical name + kind the
        # replica's own /metricz exposition uses — a dashboard built
        # against one page must match the other
        assert "# TYPE lightgbm_tpu_request_total counter" in text
        assert 'lightgbm_tpu_request_total{replica=' in text
        assert "lightgbm_tpu_request_count" not in text
        type_lines = [ln for ln in text.splitlines()
                      if ln.startswith("# TYPE")]
        assert len(type_lines) == len(set(type_lines))
        # the HTTP view serves the merged snapshot + exposition
        hs = agg.serve(0)
        port = hs.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/fleetz", timeout=30) as r:
            out = json.loads(r.read())
        assert out["fleet"]["train_ranks"] == 2
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metricz?format=prometheus",
                timeout=30) as r:
            prometheus.parse(r.read().decode())
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=30) as r:
            health = json.loads(r.read())
        assert sum(health["targets"].values()) == 3
        agg.stop()
    finally:
        for s in trainers:
            trainz.stop_trainz(s)
        serve_srv.shutdown()
        serve_srv.server_close()


def test_aggregator_target_parsing():
    assert Target("train=127.0.0.1:80").role == "train"
    assert Target("127.0.0.1:80").role == "auto"
    with pytest.raises(ValueError):
        Target("bogus=127.0.0.1:80")
    with pytest.raises(ValueError):
        Target("no-port")
    with pytest.raises(ValueError):
        FleetAggregator([])


# ------------------------------------------------- history + sentinel

def test_history_append_read_and_schema(tmp_path):
    path = str(tmp_path / "RUN_HISTORY.jsonl")
    for t in (2.0, 2.1):
        assert history_mod.append_run_summary(
            path, "bench", rows=1000, iterations=5, train_s=t,
            auc=0.87, comm_overlap_pct=97.0, dropped_field=None)
    records = history_mod.read_history(path)
    assert len(records) == 2
    for rec in records:
        assert validate_record(rec) == []
        assert "dropped_field" not in rec
        assert "mono" in rec
    # a torn line + a foreign record do not break reading
    with open(path, "a") as f:
        f.write('{"event": "iteration", "ts": 1.0, "rank": 0, '
                '"iteration": 1}\n{"torn')
    assert len(history_mod.read_history(path)) == 2


def test_booster_summary_fields(tmp_path):
    bst = _train_telemetry(tmp_path, tree_learner="data",
                           num_machines=2, device_row_chunk=256)
    fields = history_mod.booster_summary(bst.gbdt, train_s=1.5)
    assert fields["iterations"] == 3
    assert fields["train_s"] == 1.5
    assert fields["rows"] == 500
    assert fields["peak_memory_bytes"] > 0
    assert fields["collective_bytes"] > 0
    assert fields["collective_bytes_per_tree"] > 0
    assert 0.0 <= fields["comm_overlap_pct"] <= 100.0
    path = history_mod.append_run_summary(
        str(tmp_path / "h.jsonl"), "train", **fields)
    assert len(history_mod.read_history(path)) == 1


def test_sentinel_trips_on_injected_regression(tmp_path):
    from tools.sentinel import run_sentinel
    base = dict(kind="t", rows=1000, iterations=5, auc=0.87)
    clean = str(tmp_path / "clean.jsonl")
    for t in (2.0, 1.97, 2.02, 1.99, 2.01, 2.0):
        history_mod.append_run_summary(clean, train_s=t, **base)
    rc, lines = run_sentinel(clean)
    assert rc == 0, lines
    bad = str(tmp_path / "bad.jsonl")
    for t in (2.0, 1.97, 2.02, 1.99, 2.01, 2.0 * 1.22):
        history_mod.append_run_summary(bad, train_s=t, **base)
    rc, lines = run_sentinel(bad)
    assert rc == 1
    assert any("REGRESSION" in ln and "train_s" in ln for ln in lines)
    # workload groups do not cross-contaminate: a slower DIFFERENT
    # shape is new history, not a regression
    history_mod.append_run_summary(bad, train_s=50.0,
                                   **dict(base, rows=100000))
    rc2, _ = run_sentinel(bad)
    assert rc2 == 1   # still only the injected one


def test_sentinel_insufficient_history_passes(tmp_path):
    from tools.sentinel import run_sentinel
    path = str(tmp_path / "short.jsonl")
    for t in (2.0, 9.0):
        history_mod.append_run_summary(path, "t", rows=10,
                                       iterations=1, train_s=t)
    rc, lines = run_sentinel(path)
    assert rc == 0
    assert any("not enough history" in ln for ln in lines)


def test_sentinel_cli_self_check():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "sentinel.py"),
         "--self-check"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "sentinel self-check: OK" in r.stdout


# ------------------------------------- 2-process gloo acceptance rung

def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_comm_records_aggregator_and_flows(tmp_path):
    """THE acceptance path (ISSUE 13): a real 2-process gloo CPU
    data-parallel CLI run with telemetry on. While it trains, an
    in-process aggregator scrapes BOTH ranks' /trainz endpoints (ports
    are telemetry_port + rank) into one merged snapshot. Afterwards:
    per-rank `comm` records with per-collective waits are schema-valid,
    overlap is in [0,100], straggler deltas are mutually consistent,
    and the merged Perfetto export carries cross-rank flow events
    through validate_trace."""
    rng = np.random.RandomState(11)
    x = rng.rand(3000, 6)
    y = ((x[:, 0] + x[:, 1] * x[:, 2]) > 0.9).astype(int)
    csv = tmp_path / "tr.csv"
    np.savetxt(csv, np.column_stack([y, x]), delimiter=",", fmt="%.6f")
    gang_port = _free_port()
    tz_port = _free_port()
    mlist = tmp_path / "mlist.txt"
    mlist.write_text(f"127.0.0.1 {gang_port}\n"
                     f"127.0.0.1 {gang_port + 1}\n")
    tdir = tmp_path / "telemetry"
    args = ["task=train", f"data={csv}", "objective=binary",
            "num_leaves=7", "num_iterations=12", "tree_learner=data",
            "num_machines=2", f"machine_list_file={mlist}",
            "min_data_in_leaf=10", "metric_freq=0",
            "enable_load_from_binary_file=false",
            f"snapshot_dir={tmp_path / 'snaps'}",
            "telemetry=true", f"telemetry_dir={tdir}",
            f"telemetry_port={tz_port}",
            "heartbeat_timeout_s=120", "collective_timeout_s=300",
            f"output_model={tmp_path / 'model.txt'}"]
    procs = []
    for rank in range(2):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=2",
                   LIGHTGBM_TPU_RANK=str(rank),
                   PYTHONPATH=REPO)
        env.pop("LIGHTGBM_TPU_FAULTS", None)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "lightgbm_tpu"] + args, cwd=REPO,
            env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))

    # rank r serves /trainz on telemetry_port + r (application.py)
    agg = FleetAggregator([f"127.0.0.1:{tz_port}",
                           f"127.0.0.1:{tz_port + 1}"],
                          poll_s=0.2, timeout_s=3.0)
    merged_live = None
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        if all(p.poll() is not None for p in procs):
            break
        snap = agg.poll_once()
        if snap["fleet"].get("train_ranks") == 2:
            merged_live = snap
            # grab the labeled exposition page while both are live
            prom_text = agg.prometheus()
            break
        time.sleep(0.2)
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        outs.append((p.returncode, out))
    assert all(rc == 0 for rc, _ in outs), outs

    # the aggregator merged two LIVE /trainz endpoints mid-training
    assert merged_live is not None, \
        f"aggregator never saw both ranks live: {outs}"
    ranks_seen = {doc["data"]["comm"]["rank"]
                  for doc in merged_live["targets"].values()
                  if doc.get("ok")}
    assert ranks_seen == {0, 1}
    assert "straggler_s" in merged_live["fleet"]
    assert prometheus.lint_names(prom_text) == []
    assert 'role="train"' in prom_text

    # per-rank comm records: schema-valid, bounded overlap, and
    # mutually consistent straggler deltas at matching iterations
    per_rank = {}
    for rank in range(2):
        records, bad = read_journal(
            os.path.join(str(tdir), f"journal.rank000{rank}.jsonl"))
        assert bad == 0
        comm = {r["iteration"]: r for r in records
                if r["event"] == "comm"}
        assert comm, f"rank {rank} journaled no comm records"
        for rec in comm.values():
            assert validate_record(rec) == [], rec
            assert 0.0 <= rec["overlap_pct"] <= 100.0
            assert rec["wait_s"] >= 0
            assert rec["waits"], rec
        per_rank[rank] = comm
    shared_iters = sorted(set(per_rank[0]) & set(per_rank[1]))
    assert shared_iters, "no iteration has comm records on both ranks"
    for it in shared_iters:
        waits = [per_rank[r][it]["wait_s"] for r in (0, 1)]
        deltas = [w - min(waits) for w in waits]
        assert min(deltas) == 0.0
        assert all(d >= 0.0 for d in deltas)
        assert sum(deltas) == pytest.approx(sum(waits)
                                            - 2 * min(waits))

    # merged Perfetto export: cross-rank flow events, valid trace
    trace, _ = export.export_trace(str(tdir))
    assert export.validate_trace(trace) == []
    flows = [e for e in trace["traceEvents"]
             if e.get("ph") in ("s", "t", "f")]
    assert flows, "merged trace has no cross-rank flow events"
    assert {e["pid"] for e in flows} == {0, 1}
