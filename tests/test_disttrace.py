"""Distributed request tracing + crash flight recorder
(lightgbm_tpu/telemetry/disttrace.py, docs/Observability.md).

Covers the contracts end to end: X-Trace-Ctx header roundtrip and
garbage tolerance, deterministic tail sampling (errors/slow always
kept, hash fraction elsewhere, identical on every process), recorder
fragment assembly through the async drain, the collector stitching
per-process journal fragments into one cross-process tree (/tracez),
Perfetto flow export through validate_trace, the chaos-rung trace
shape (retry after a dead replica, hedge losers cancelled), the live
router + 2-replica acceptance trace, and the flight recorder's
blackbox dump from the collective watchdog's abort path.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.fleet.router import Router, make_router_server
from lightgbm_tpu.parallel import heartbeat
from lightgbm_tpu.serving import CompiledPredictor, make_server
from lightgbm_tpu.telemetry import disttrace
from lightgbm_tpu.telemetry.aggregate import (FleetAggregator,
                                              TraceCollector,
                                              read_trace_records,
                                              stitch_traces)
from lightgbm_tpu.telemetry.export import export_trace, validate_trace
from lightgbm_tpu.utils import faults


@pytest.fixture(autouse=True)
def _trace_hygiene():
    """The FLIGHT singleton and fault table are process-global — every
    test starts and ends with both empty."""
    faults.clear_faults()
    disttrace.FLIGHT.disarm()
    yield
    disttrace.FLIGHT.disarm()
    faults.clear_faults()


def _train_binary(n=300, f=5, rounds=6, seed=17):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.2 * rng.randn(n) > 0).astype(float)
    params = {"objective": "binary", "metric": "binary_logloss",
              "num_leaves": 15, "min_data_in_leaf": 5, "verbose": -1}
    bst = lgb.train(params, lgb.Dataset(X, y, params=params),
                    num_boost_round=rounds, verbose_eval=False)
    return bst, X


@pytest.fixture(scope="module")
def binary_model():
    return _train_binary()


class _TracedReplica:
    """One in-process serving replica journaling traces into a shared
    directory (its own rank file), with guaranteed teardown."""

    def __init__(self, binary_model, trace_dir, rank, **make_kwargs):
        bst, _ = binary_model
        pred = CompiledPredictor.from_booster(bst.gbdt,
                                              max_batch_rows=32)
        make_kwargs.setdefault("max_wait_ms", 1.0)
        make_kwargs.setdefault("trace_sample_rate", 1.0)
        self.srv = make_server(pred, port=0, trace_dir=str(trace_dir),
                               trace_rank=rank, **make_kwargs)
        self.port = self.srv.server_address[1]
        self.target = f"127.0.0.1:{self.port}"
        self._thread = threading.Thread(target=self.srv.serve_forever,
                                        daemon=True)
        self._thread.start()
        self.alive = True

    def flush(self):
        if self.srv.trace_recorder is not None:
            self.srv.trace_recorder.flush_pending()

    def kill(self):
        if self.alive:
            self.alive = False
            self.srv.shutdown()
            self.srv.server_close()
            self.srv.batcher.close()
            if self.srv.trace_recorder is not None:
                self.srv.trace_recorder.close()

    close = kill


def _post(port, rows, headers=None, path="/predict", timeout=30):
    h = {"Content-Type": "application/json"}
    h.update(headers or {})
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps({"rows": np.asarray(rows).tolist()}).encode(),
        headers=h)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        body = e.read()
        return e.code, (json.loads(body) if body else {}), dict(e.headers)


def _get(port, path):
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                    timeout=30) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


# ------------------------------------------------- context + header
def test_header_roundtrip_and_garbage():
    ctx = disttrace.TraceContext(disttrace.new_trace_id(),
                                 disttrace.new_span_id(),
                                 flags=disttrace.FLAG_SAMPLED)
    back = disttrace.parse_header(ctx.header_value())
    assert (back.trace_id, back.span_id, back.flags) == \
        (ctx.trace_id, ctx.span_id, ctx.flags)
    # anything malformed degrades to None (fresh trace), never raises
    for bad in (None, "", "deadbeef", "a/b", "a/b/c/d",
                "nothex!/deadbeefdeadbeef/1",
                "deadbeefdeadbeef/deadbeefdeadbeef/x", 42):
        assert disttrace.parse_header(bad) is None


def test_inject_headers_and_activation():
    # no context anywhere: headers pass through unstamped
    out = disttrace.inject_headers({"A": "1"})
    assert disttrace.TRACE_HEADER not in out and out["A"] == "1"
    ctx = disttrace.TraceContext("ab" * 8, "cd" * 8, flags=1)
    with disttrace.activate(ctx):
        assert disttrace.current() is ctx
        stamped = disttrace.inject_headers({})
        assert stamped[disttrace.TRACE_HEADER] == ctx.header_value()
        inner = disttrace.TraceContext("ef" * 8, "01" * 8)
        with disttrace.activate(inner):
            assert disttrace.current() is inner
        assert disttrace.current() is ctx   # stack pops cleanly
    assert disttrace.current() is None
    # explicit ctx beats the (absent) thread context
    assert disttrace.TRACE_HEADER in disttrace.inject_headers(ctx=ctx)


def test_hash_fraction_is_deterministic_and_spread():
    ids = [disttrace.new_trace_id() for _ in range(400)]
    fr = [disttrace.hash_fraction(t) for t in ids]
    assert fr == [disttrace.hash_fraction(t) for t in ids]
    assert all(0.0 <= f < 1.0 for f in fr)
    # crude uniformity: a 50% cut keeps roughly half
    kept = sum(1 for f in fr if f < 0.5)
    assert 120 < kept < 280


# ------------------------------------------------- recorder + sampling
def _recorder(tmp_path, **kw):
    kw.setdefault("sample_rate", 0.0)   # only tail reasons keep
    return disttrace.TraceRecorder(directory=str(tmp_path), rank=0,
                                   service="test", **kw)


def _trace_events(tmp_path):
    recs = read_trace_records(str(tmp_path))
    return recs


def test_recorder_fragment_assembly_and_error_keep(tmp_path):
    rec = _recorder(tmp_path)
    try:
        with rec.span("hop.root", kind="server") as root:
            root.set_tag("http.status", 500)   # error -> 100% kept
            with rec.span("hop.child"):
                pass
            rec.observe("hop.stamped", root.ctx, time.time(), 0.001)
        rec.flush_pending()
        recs = _trace_events(tmp_path)
        assert {r["name"] for r in recs} == \
            {"hop.root", "hop.child", "hop.stamped"}
        (root_rec,) = [r for r in recs if r["name"] == "hop.root"]
        assert all(r["trace_id"] == root_rec["trace_id"] for r in recs)
        assert all(r.get("parent_span_id") == root_rec["span_id"]
                   for r in recs if r is not root_rec)
        assert root_rec["service"] == "test"
        st = rec.stats()
        assert st["traces_kept"] == 1
        assert st["trace_spans_recorded"] == 3
    finally:
        rec.close()


def test_recorder_tail_drops_ok_traces_at_zero_rate(tmp_path):
    rec = _recorder(tmp_path)
    try:
        for _ in range(5):
            with rec.span("hop.ok"):
                pass
        rec.flush_pending()
        assert _trace_events(tmp_path) == []
        assert rec.stats()["traces_dropped"] == 5
    finally:
        rec.close()


def test_recorder_keeps_slow_and_flagged_traces(tmp_path):
    rec = _recorder(tmp_path, slow_ms=1.0)
    try:
        sp = rec.start("hop.slow")
        sp.duration = 0.05          # 50 ms >> 1 ms slow bar
        rec.finish(sp)
        # FLAG_SAMPLED from an upstream head keeps regardless of rate
        ctx = disttrace.TraceContext(disttrace.new_trace_id(),
                                     disttrace.new_span_id(),
                                     flags=disttrace.FLAG_SAMPLED)
        with rec.span("hop.flagged", ctx=ctx):
            pass
        rec.flush_pending()
        names = {r["name"] for r in _trace_events(tmp_path)}
        assert names == {"hop.slow", "hop.flagged"}
    finally:
        rec.close()


def test_recorder_slow_only_mode(tmp_path):
    rec = _recorder(tmp_path, sample_rate=1.0, slow_only=True,
                    slow_ms=1000.0)
    try:
        with rec.span("hop.fast"):
            pass
        rec.flush_pending()
        assert _trace_events(tmp_path) == []   # fast + ok -> dropped
        sp = rec.start("hop.slow")
        sp.duration = 2.0
        rec.finish(sp)
        rec.flush_pending()
        assert [r["name"] for r in _trace_events(tmp_path)] == \
            ["hop.slow"]
    finally:
        rec.close()


def test_disabled_recorder_is_noop():
    rec = disttrace.TraceRecorder(enabled=False)
    h = rec.span("anything")
    assert h is rec.span("anything else")   # shared no-op handle
    with h as sp:
        sp.set_tag("k", "v")
    assert rec.stats()["trace_spans_recorded"] == 0


def test_sampling_decision_identical_across_recorders(tmp_path):
    """Two independent recorders (different processes in production)
    must keep/drop the SAME trace ids — the collector can only stitch
    trees whose every hop survived."""
    a = _recorder(tmp_path / "a", sample_rate=0.3)
    b = _recorder(tmp_path / "b", sample_rate=0.3)
    try:
        for _ in range(60):
            tid = disttrace.new_trace_id()
            ctx = disttrace.TraceContext(tid, disttrace.new_span_id())
            with a.span("hop.a", ctx=ctx):
                pass
            with b.span("hop.b", ctx=ctx):
                pass
        a.flush_pending()
        b.flush_pending()
        kept_a = {r["trace_id"] for r in _trace_events(tmp_path / "a")}
        kept_b = {r["trace_id"] for r in _trace_events(tmp_path / "b")}
        assert kept_a == kept_b
        assert 0 < len(kept_a) < 60
    finally:
        a.close()
        b.close()


# ------------------------------------------------------- collector
def _mk_rec(trace_id, span_id, name, start, dur, parent=None,
            service="svc", status="ok", tags=None, links=None):
    r = {"event": "trace", "ts": start, "rank": 0,
         "trace_id": trace_id, "span_id": span_id, "name": name,
         "start": start, "duration_s": dur, "kind": "internal",
         "status": status, "flags": 0, "service": service}
    if parent:
        r["parent_span_id"] = parent
    if tags:
        r["tags"] = tags
    if links:
        r["links"] = links
    return r


def test_stitch_traces_roots_orders_and_grafts_links():
    t0 = 1000.0
    recs = [
        # trace A: router root + serving child (child arrives first)
        _mk_rec("aa" * 8, "02" * 8, "serve.request", t0 + 0.001, 0.004,
                parent="01" * 8, service="serving"),
        _mk_rec("aa" * 8, "01" * 8, "router.request", t0, 0.006,
                service="router"),
        # trace B: single error span
        _mk_rec("bb" * 8, "03" * 8, "router.request", t0 + 1.0, 0.002,
                service="router", tags={"http.status": 503}),
        # a coalesced batch span on trace A linking trace B
        _mk_rec("aa" * 8, "04" * 8, "batch.dispatch", t0 + 0.002,
                0.002, parent="02" * 8, service="serving",
                links=["bb" * 8]),
    ]
    traces = stitch_traces(recs)
    assert len(traces) == 2
    by_id = {t["trace_id"]: t for t in traces}
    ta, tb = by_id["aa" * 8], by_id["bb" * 8]
    # error traces sort first regardless of duration
    assert traces[0] is tb and tb["status"] == "error"
    assert ta["root"] == "router.request"
    assert ta["services"] == ["router", "serving"]
    assert [s["name"] for s in ta["spans"]] == \
        ["router.request", "serve.request", "batch.dispatch"]
    # the linked batch span is grafted into B, marked shared
    shared = [s for s in tb["spans"] if s.get("shared")]
    assert [s["name"] for s in shared] == ["batch.dispatch"]
    # per-hop breakdown: offsets are relative to the trace start
    assert ta["spans"][0]["offset_ms"] == 0.0
    assert ta["spans"][1]["offset_ms"] == pytest.approx(1.0, abs=1e-6)


def test_trace_collector_tracez_counts(tmp_path):
    rec = _recorder(tmp_path, sample_rate=1.0)
    try:
        with rec.span("hop.a"):
            pass
        with rec.span("hop.b") as h:
            h.set_tag("http.status", 500)
        rec.flush_pending()
        z = TraceCollector(str(tmp_path)).tracez()
        assert z["trace_count"] == 2 and z["error_count"] == 1
        assert z["traces"][0]["status"] == "error"   # errors first
    finally:
        rec.close()


def test_aggregator_tracez_endpoint(tmp_path):
    rec = _recorder(tmp_path, sample_rate=1.0)
    with rec.span("hop.only"):
        pass
    rec.close()
    # the target is never polled — serve() only binds the HTTP view
    agg = FleetAggregator(["127.0.0.1:9"], trace_dir=str(tmp_path))
    srv = agg.serve(port=0)
    try:
        port = srv.server_address[1]
        status, body = _get(port, "/tracez")
        assert status == 200
        z = json.loads(body)
        assert z["trace_count"] == 1
        assert z["traces"][0]["spans"][0]["name"] == "hop.only"
    finally:
        srv.shutdown()
        srv.server_close()
    # without --trace-dir the endpoint 404s with a hint, not a 500
    agg2 = FleetAggregator(["127.0.0.1:9"])
    srv2 = agg2.serve(port=0)
    try:
        status, body = _get(srv2.server_address[1], "/tracez")
        assert status == 404 and b"trace" in body
    finally:
        srv2.shutdown()
        srv2.server_close()


# ---------------------------------------------------------- export
def test_export_trace_flow_events_pair_and_validate(tmp_path):
    """Cross-process trace -> Perfetto: one flow chain per trace id,
    every flow id pairing exactly one start with one finish, and the
    whole file passing validate_trace after a JSON reload."""
    tid = disttrace.new_trace_id()
    a = disttrace.TraceRecorder(directory=str(tmp_path), rank=0,
                                service="router", sample_rate=1.0)
    b = disttrace.TraceRecorder(directory=str(tmp_path), rank=1,
                                service="serving", sample_rate=1.0)
    ctx = disttrace.TraceContext(tid, disttrace.new_span_id(),
                                 flags=disttrace.FLAG_SAMPLED)
    with a.span("router.request", ctx=ctx):
        with b.span("serve.request"):
            time.sleep(0.002)
    a.close()
    b.close()
    trace, out_path = export_trace(str(tmp_path))
    assert validate_trace(trace) == []
    with open(out_path) as f:
        reloaded = json.load(f)
    assert validate_trace(reloaded) == []
    flows = [e for e in reloaded["traceEvents"]
             if e.get("cat") == "trace_flow"]
    assert flows, "cross-process trace produced no flow events"
    by_id = {}
    for ev in flows:
        by_id.setdefault(ev["id"], []).append(ev["ph"])
    for fid, phases in by_id.items():
        assert fid.startswith("trace:")
        assert phases.count("s") == 1, fid
        assert phases.count("f") == 1, fid
    # both ranks appear on the chain
    assert {e["pid"] for e in flows} == {0, 1}


# ------------------------------------------------- chaos-rung traces
def test_chaos_retry_trace_shows_both_attempts(tmp_path, binary_model):
    """PR 14 rung, traced: replica A drops the connection mid-request;
    the stitched trace shows attempt 1 erroring on A and attempt 2
    landing ok on a healthy replica, under one router root."""
    a = _TracedReplica(binary_model, tmp_path, 1)
    b = _TracedReplica(binary_model, tmp_path, 2)
    rsrv = make_router_server([a.target, b.target], port=0,
                              retry_budget=1.0, health_poll_s=30.0,
                              trace_dir=str(tmp_path), trace_rank=0,
                              trace_sample_rate=1.0)
    rthread = threading.Thread(target=rsrv.serve_forever, daemon=True)
    rthread.start()
    rport = rsrv.server_address[1]
    try:
        _, X = binary_model
        a.srv.chaos["drop_connection"] = 1
        status, body, _ = _post(rport, X[:3])
        assert status == 200 and len(body["predictions"]) == 3
        rsrv.router.trace.flush_pending()
        a.flush()
        b.flush()
        traces = stitch_traces(read_trace_records(str(tmp_path)))
        # one request -> exactly one stitched trace with a router root
        routed = [t for t in traces if t["root"] == "router.request"]
        assert len(routed) == 1
        spans = routed[0]["spans"]
        attempts = sorted(
            (s for s in spans if s["name"] == "router.attempt"),
            key=lambda s: s["tags"]["attempt"])
        assert len(attempts) == 2
        assert attempts[0]["status"] == "error"
        assert attempts[0]["tags"]["replica"] == a.target
        assert attempts[1]["status"] == "ok"
        assert attempts[1]["tags"]["replica"] == b.target
        # the healthy replica's serving spans joined the same tree
        names = {s["name"] for s in spans}
        assert {"serve.request", "serve.queue"} <= names
    finally:
        rsrv.shutdown()
        rsrv.router.stop()
        rsrv.server_close()
        if rsrv.router.trace is not disttrace.NOOP_RECORDER:
            rsrv.router.trace.close()
        a.kill()
        b.kill()


def test_hedge_loser_span_is_cancelled(tmp_path, binary_model):
    """A hedged request's losing attempt closes as status=cancelled —
    never as an error that would poison error-rate dashboards."""
    trace_dir = tmp_path / "hedge"
    a = _TracedReplica(binary_model, trace_dir, 1)
    b = _TracedReplica(binary_model, trace_dir, 2)
    recorder = disttrace.TraceRecorder(directory=str(trace_dir),
                                       rank=0, service="router",
                                       sample_rate=1.0)
    router = Router([a.target, b.target], breaker_failures=100,
                    retry_budget=1.0, hedge_quantile=0.5,
                    trace_recorder=recorder)
    try:
        _, X = binary_model
        body = json.dumps({"rows": X[:2].tolist()}).encode()
        headers = {"Content-Type": "application/json",
                   "Content-Length": str(len(body))}
        for _ in range(25):          # warm the ring past the gate
            assert router.dispatch("/predict", body, headers)[0] == 200
        a.srv.chaos["slow_replica_ms"] = 800
        status, _, _ = router.dispatch("/predict", body, headers)
        assert status == 200
        deadline = time.monotonic() + 3.0
        cancelled = []
        while time.monotonic() < deadline and not cancelled:
            # the loser's span closes when its slowed socket dies;
            # poll the journal until it lands
            time.sleep(0.05)
            recorder.flush_pending()
            cancelled = [r for r in read_trace_records(str(trace_dir))
                         if r["name"] == "router.attempt"
                         and r["status"] == "cancelled"]
        assert cancelled, "hedge loser never closed as cancelled"
        # whichever attempt lost (primary or hedge), it carries the
        # hedge-race tag and did NOT close as an error
        assert "hedge" in cancelled[0]["tags"]
    finally:
        a.srv.chaos.clear()
        router.stop()
        recorder.close()
        a.kill()
        b.kill()


# ----------------------------------------------- live e2e acceptance
def test_e2e_router_two_replicas_one_stitched_trace(tmp_path,
                                                    binary_model):
    """The acceptance rung: router + 2 replicas, one traced request;
    the collector assembles ONE cross-process tree holding the router
    root, attempt, queue, batch-dispatch and kernel spans for the same
    trace id; the Perfetto export passes validate_trace; the client
    sees its request id and the replica's timing echoed back."""
    a = _TracedReplica(binary_model, tmp_path, 1)
    b = _TracedReplica(binary_model, tmp_path, 2)
    rsrv = make_router_server([a.target, b.target], port=0,
                              health_poll_s=30.0,
                              trace_dir=str(tmp_path), trace_rank=0,
                              trace_sample_rate=1.0)
    rthread = threading.Thread(target=rsrv.serve_forever, daemon=True)
    rthread.start()
    rport = rsrv.server_address[1]
    try:
        _, X = binary_model
        head = disttrace.TraceContext(disttrace.new_trace_id(),
                                      disttrace.new_span_id(),
                                      flags=disttrace.FLAG_SAMPLED)
        status, body, resp_headers = _post(
            rport, X[:2],
            headers={disttrace.TRACE_HEADER: head.header_value(),
                     "X-Request-Id": "e2e-req-1"})
        assert status == 200 and len(body["predictions"]) == 2
        # satellite: the router echoes the upstream's ids + timing
        assert resp_headers.get("X-Request-Id") == "e2e-req-1"
        assert "X-Timing-Ms" in resp_headers
        rsrv.router.trace.flush_pending()
        a.flush()
        b.flush()
        traces = stitch_traces(read_trace_records(str(tmp_path)))
        mine = [t for t in traces if t["trace_id"] == head.trace_id]
        assert len(mine) == 1, "client's trace id did not stitch"
        tr = mine[0]
        assert tr["root"] == "router.request"
        assert set(tr["services"]) == {"router", "serving"}
        names = {s["name"] for s in tr["spans"]}
        assert {"router.request", "router.attempt", "serve.request",
                "serve.queue", "batch.dispatch",
                "serve.kernel"} <= names
        # every span in the tree belongs to the client's trace
        own = [s for s in tr["spans"] if not s.get("shared")]
        assert all(s["duration_ms"] >= 0.0 for s in own)
        # Perfetto export of the same directory round-trips clean
        trace, _ = export_trace(str(tmp_path))
        assert validate_trace(trace) == []
        # satellite: /metricz exposes per-replica upstream quantiles
        _, metricz = _get(rport, "/metricz?format=prometheus")
        text = metricz.decode()
        # render scales _ms gauges to canonical _seconds families
        assert "replica_0_upstream_latency_p50_seconds" in text
        assert "replica_1_upstream_latency_p99_seconds" in text
        snap = json.loads(_get(rport, "/metricz")[1])
        for entry in snap["replicas"]:
            assert "upstream_latency_p50_ms" in entry
            assert "upstream_latency_p99_ms" in entry
    finally:
        rsrv.shutdown()
        rsrv.router.stop()
        rsrv.server_close()
        if rsrv.router.trace is not disttrace.NOOP_RECORDER:
            rsrv.router.trace.close()
        a.kill()
        b.kill()


def test_router_forwards_trace_and_request_id(tmp_path, binary_model):
    """Satellite bugfix: the replica must RECEIVE the X-Request-Id and
    X-Trace-Ctx the client sent the router (the old router swallowed
    both). The replica's own trace journal proves arrival: its root
    span continues the client's trace id."""
    a = _TracedReplica(binary_model, tmp_path, 1)
    rsrv = make_router_server([a.target], port=0, health_poll_s=30.0)
    rthread = threading.Thread(target=rsrv.serve_forever, daemon=True)
    rthread.start()
    try:
        _, X = binary_model
        head = disttrace.TraceContext(disttrace.new_trace_id(),
                                      disttrace.new_span_id(),
                                      flags=disttrace.FLAG_SAMPLED)
        status, body, _ = _post(
            rsrv.server_address[1], X[:1],
            headers={disttrace.TRACE_HEADER: head.header_value(),
                     "X-Request-Id": "fwd-1"})
        assert status == 200
        assert body.get("request_id") == "fwd-1"
        # the replica ends its root span after the response is written:
        # the client can hold the answer before the span exists
        deadline = time.time() + 5.0
        while True:
            a.flush()
            recs = read_trace_records(str(tmp_path))
            roots = [r for r in recs if r["name"] == "serve.request"]
            if roots or time.time() > deadline:
                break
            time.sleep(0.05)
        assert roots and roots[0]["trace_id"] == head.trace_id
    finally:
        rsrv.shutdown()
        rsrv.router.stop()
        rsrv.server_close()
        a.kill()


# ------------------------------------------------- flight recorder
def test_watchdog_abort_leaves_parseable_blackbox(tmp_path):
    """The collective watchdog's abort path dumps the blackbox BEFORE
    os._exit: it names the hung collective and carries the registered
    evidence sources (here: the recorder's final spans)."""
    disttrace.FLIGHT.configure(str(tmp_path), rank=0)
    rec = disttrace.TraceRecorder(directory=str(tmp_path), rank=0,
                                  service="train", sample_rate=1.0)
    with rec.span("train.boost_round"):
        pass
    rec.flush_pending()
    disttrace.FLIGHT.add_source("trace_stats", rec.stats)
    expired = []
    wd = heartbeat.CollectiveWatchdog(
        timeout_s=0.05, rank=0,
        on_expire=lambda name, it: expired.append((name, it)))
    wd.set_iteration(7)
    with wd.armed("allreduce_hist"):
        deadline = time.monotonic() + 3.0
        while not expired and time.monotonic() < deadline:
            time.sleep(0.01)       # hang inside the collective
    assert expired == [("allreduce_hist", 7)]
    path = disttrace.blackbox_path(str(tmp_path), 0)
    with open(path) as f:
        box = json.load(f)
    assert box["reason"] == "collective_watchdog"
    assert box["collective"] == "allreduce_hist"
    assert box["iteration"] == 7
    assert box["sources"]["trace_stats"]["traces_kept"] == 1
    rec.close()


def test_flight_dump_survives_bad_source_and_is_atomic(tmp_path):
    disttrace.FLIGHT.configure(str(tmp_path), rank=3)
    disttrace.FLIGHT.add_source("good", lambda: {"ok": True})

    def _bomb():
        raise RuntimeError("evidence source exploded")

    disttrace.FLIGHT.add_source("bad", _bomb)
    path = disttrace.FLIGHT.dump("sigquit")
    assert path == disttrace.blackbox_path(str(tmp_path), 3)
    with open(path) as f:
        box = json.load(f)
    assert box["sources"]["good"] == {"ok": True}
    assert "RuntimeError" in box["sources"]["bad"]["error"]
    # atomic: no tmp droppings next to the blackbox
    leftovers = [p for p in os.listdir(tmp_path) if ".tmp." in p]
    assert leftovers == []
    # a second dump overwrites in place
    assert disttrace.FLIGHT.dump("again") == path


def test_flight_dump_unconfigured_is_silent_noop():
    assert disttrace.FLIGHT.dump("whatever") is None


def test_unhandled_server_exception_dumps_blackbox(tmp_path,
                                                   binary_model):
    """An exception escaping the serving handler leaves a blackbox
    (reason=unhandled_server_exception) before the 500 goes out."""
    rep = _TracedReplica(binary_model, tmp_path, 0)
    try:
        # poison the handler itself — batcher-level errors are CAUGHT
        # (isolated 500s); only an escape from _serve_predict counts
        # as unhandled
        def _boom(self):
            raise RuntimeError("handler exploded")

        rep.srv.RequestHandlerClass._serve_predict = _boom
        _, X = binary_model
        try:
            _post(rep.port, X[:1], timeout=5)
        except (urllib.error.URLError, ConnectionError, OSError):
            pass   # the dying handler may tear the socket; that's fine
        deadline = time.monotonic() + 3.0
        path = disttrace.blackbox_path(str(tmp_path), 0)
        while time.monotonic() < deadline and not os.path.exists(path):
            time.sleep(0.02)
        with open(path) as f:
            box = json.load(f)
        assert box["reason"] == "unhandled_server_exception"
        assert "trace_stats" in box["sources"]
    finally:
        rep.kill()
