"""Online inference subsystem tests (lightgbm_tpu/serving/).

Parity contract: CompiledPredictor must match GBDT.predict /
predict_raw / predict_leaf_index to 1e-6 across regression, binary
(sigmoid), multiclass (softmax), categorical-split, and NaN-bearing
inputs — the exact-reduce path is bit-identical by construction
(device traversal decisions equal the f64 host reference, reduction in
f64 on host), so the assertions use much tighter tolerances.

Plus: NaN categorical-routing regression (the pre-fix behavior mapped
NaN to category 0 via nan_to_num), micro-batcher coalescing/slicing
under concurrent clients, streaming predict_file chunk-boundary
equality, and an end-to-end `python -m lightgbm_tpu.serve` smoke test.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.models.tree import Tree
from lightgbm_tpu.serving import (CompiledPredictor, MicroBatcher,
                                  make_server)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------- fixtures
def _train(objective, num_class=1, n=400, f=6, rounds=10, seed=7):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    if objective == "regression":
        y = X[:, 0] * 2.0 - X[:, 1] + 0.1 * rng.randn(n)
        params = {"objective": "regression", "metric": "l2"}
    elif objective == "binary":
        y = (X[:, 0] + 0.5 * X[:, 1] + 0.2 * rng.randn(n) > 0).astype(float)
        params = {"objective": "binary", "metric": "binary_logloss"}
    else:
        y = np.floor(rng.rand(n) * num_class)
        y[X[:, 0] > 0.5] = 0  # give the trees something to split on
        params = {"objective": "multiclass", "metric": "multi_logloss",
                  "num_class": num_class}
    params.update({"num_leaves": 15, "min_data_in_leaf": 5, "verbose": -1})
    bst = lgb.train(params, lgb.Dataset(X, y, params=params),
                    num_boost_round=rounds, verbose_eval=False)
    return bst.gbdt, X


@pytest.fixture(scope="module")
def binary_model():
    return _train("binary")


def _cat_model():
    """Handcrafted 2-feature model with a CATEGORY-0 split at the root:
    go left iff feature 1 is category 0 — the shape that exposed the
    NaN-matches-category-0 bug."""
    t = Tree(3)
    t.split_feature_real = np.array([1, 0], dtype=np.int32)
    t.split_feature = t.split_feature_real.copy()
    t.threshold = np.array([0.0, 0.5], dtype=np.float64)
    t.decision_type = np.array([Tree.CATEGORICAL, Tree.NUMERICAL],
                               dtype=np.int8)
    t.left_child = np.array([1, ~0], dtype=np.int32)   # cat-0 -> numeric
    t.right_child = np.array([~2, ~1], dtype=np.int32)
    t.leaf_value = np.array([10.0, 20.0, 30.0], dtype=np.float64)
    g = GBDT()
    g.load_model_from_string("\n".join([
        "gbdt", "num_class=1", "label_index=0", "max_feature_idx=1",
        "objective=regression", "sigmoid=-1", "feature_names=A B", "",
        "Tree=0", t.to_string()]))
    return g


# ---------------------------------------------------------------- parity
def _assert_parity(gbdt, X, tol=1e-6):
    cp = CompiledPredictor.from_booster(gbdt, max_batch_rows=64)
    np.testing.assert_allclose(cp.predict(X), gbdt.predict(X), atol=tol,
                               rtol=0)
    np.testing.assert_allclose(cp.predict_raw(X), gbdt.predict_raw(X),
                               atol=tol, rtol=0)
    np.testing.assert_array_equal(cp.predict_leaf_index(X),
                                  gbdt.predict_leaf_index(X))
    return cp


def test_parity_regression():
    gbdt, X = _train("regression")
    _assert_parity(gbdt, X)


def test_parity_binary_sigmoid(binary_model):
    gbdt, X = binary_model
    assert gbdt.sigmoid > 0  # the transform path is actually exercised
    cp = _assert_parity(gbdt, X)
    p = cp.predict(X)
    assert np.all((p > 0) & (p < 1))


def test_parity_multiclass_softmax():
    gbdt, X = _train("multiclass", num_class=3)
    cp = _assert_parity(gbdt, X)
    np.testing.assert_allclose(cp.predict(X).sum(axis=1), 1.0, atol=1e-9)


def test_parity_categorical_and_nan():
    g = _cat_model()
    X = np.array([[0.2, 0.0],    # cat 0 -> left -> numeric leaf 0
                  [0.9, 0.0],    # cat 0 -> left -> leaf 1
                  [0.2, 3.0],    # cat 3 -> right leaf 2
                  [0.2, np.nan],  # NaN -> RIGHT (not category 0!)
                  [np.nan, 0.0]])  # numeric NaN -> right leaf
    cp = CompiledPredictor.from_booster(g, max_batch_rows=8)
    np.testing.assert_allclose(cp.predict(X), g.predict(X), atol=0)
    np.testing.assert_array_equal(cp.predict_leaf_index(X),
                                  g.predict_leaf_index(X))
    # and the values are the ones reference default-direction gives
    np.testing.assert_allclose(g.predict(X).ravel(),
                               [10.0, 20.0, 30.0, 30.0, 20.0])


def test_parity_nan_on_trained_model(binary_model):
    gbdt, X = binary_model
    Xn = X[:50].copy()
    Xn[::3, 0] = np.nan
    Xn[::7, 3] = np.nan
    _assert_parity(gbdt, Xn)


def test_parity_from_model_file(tmp_path, binary_model):
    gbdt, X = binary_model
    path = str(tmp_path / "model.txt")
    gbdt.save_model_to_file(-1, path)
    cp = CompiledPredictor.from_model_file(path, max_batch_rows=32)
    np.testing.assert_allclose(cp.predict(X), gbdt.predict(X), atol=1e-6,
                               rtol=0)


def test_chunking_beyond_max_batch_rows(binary_model):
    """Requests larger than the biggest bucket chunk through it with no
    recompilation and identical results."""
    gbdt, X = binary_model
    cp = CompiledPredictor.from_booster(gbdt, max_batch_rows=32)
    np.testing.assert_allclose(cp.predict(X), gbdt.predict(X), atol=1e-6,
                               rtol=0)
    assert cp.stats["cold_dispatches"] == 0


def test_width_canonicalization(binary_model):
    """Narrow input pads with 0.0; wide input ignores the extra columns
    (no split reads past max_feature_idx) — and neither recompiles."""
    gbdt, X = binary_model
    cp = CompiledPredictor.from_booster(gbdt, max_batch_rows=32)
    wide = np.hstack([X[:5], np.full((5, 3), 99.0)])
    np.testing.assert_allclose(cp.predict(wide), gbdt.predict(X[:5]),
                               atol=1e-6, rtol=0)
    narrow = X[:5, :4]
    padded = np.hstack([narrow, np.zeros((5, X.shape[1] - 4))])
    np.testing.assert_allclose(cp.predict(narrow), gbdt.predict(padded),
                               atol=1e-6, rtol=0)
    assert cp.stats["cold_dispatches"] == 0


def test_device_reduce_close(binary_model):
    """The all-device f32 throughput path stays within float32 rounding
    of the exact path."""
    gbdt, X = binary_model
    cp = CompiledPredictor.from_booster(gbdt, max_batch_rows=64)
    np.testing.assert_allclose(cp.predict_raw_device(X),
                               cp.predict_raw(X), atol=5e-5, rtol=1e-5)
    np.testing.assert_allclose(cp.predict_device(X), cp.predict(X),
                               atol=5e-5, rtol=1e-5)


def test_empty_model_and_empty_input(binary_model):
    g = GBDT()
    g.load_model_from_string("\n".join([
        "gbdt", "num_class=1", "label_index=0", "max_feature_idx=1",
        "sigmoid=-1", "feature_names=A B", ""]))
    cp = CompiledPredictor.from_booster(g, max_batch_rows=4)
    assert cp.predict(np.zeros((3, 2))).shape == (3, 1)
    assert cp.predict_leaf_index(np.zeros((3, 2))).shape == (3, 0)
    gbdt, X = binary_model
    cp2 = CompiledPredictor.from_booster(gbdt, max_batch_rows=4)
    assert cp2.predict(np.zeros((0, X.shape[1]))).shape == (0, 1)


# --------------------------------------------------- NaN routing regression
def test_tree_nan_routes_right_on_categorical():
    """Regression: Tree.predict used nan_to_num before the categorical
    `== threshold` compare, so NaN silently matched category 0."""
    g = _cat_model()
    tree = g.models[0]
    nan_row = np.array([[0.2, np.nan]])
    cat0_row = np.array([[0.2, 0.0]])
    assert tree.predict(nan_row)[0] == 30.0       # right child
    assert tree.predict(cat0_row)[0] == 10.0      # genuinely category 0
    assert g.predict(nan_row)[0, 0] == 30.0       # host stacked traversal


def test_gbdt_device_path_nan_categorical(monkeypatch):
    """The jitted device traversal agrees with the fixed host path."""
    g = _cat_model()
    X = np.array([[0.2, 0.0], [0.2, np.nan], [np.nan, 0.0], [0.9, 2.0]])
    host = g.predict(X)
    monkeypatch.setenv("LIGHTGBM_TPU_DEVICE_PREDICT", "force")
    dev = g.predict(X)
    np.testing.assert_allclose(dev, host, atol=1e-6, rtol=0)


def test_device_predict_knob(monkeypatch, binary_model):
    gbdt, X = binary_model
    n_used = gbdt._num_used_models(-1)
    monkeypatch.setenv("LIGHTGBM_TPU_DEVICE_PREDICT", "0")
    assert not gbdt._use_device_predict(10**9, n_used)
    monkeypatch.setenv("LIGHTGBM_TPU_DEVICE_PREDICT", "force")
    assert gbdt._use_device_predict(1, n_used)
    monkeypatch.delenv("LIGHTGBM_TPU_DEVICE_PREDICT")
    gbdt.device_predict = "false"
    assert not gbdt._use_device_predict(10**9, n_used)
    gbdt.device_predict = "auto"
    gbdt.DEVICE_PREDICT_CELLS = 10
    assert gbdt._use_device_predict(11, 1)
    assert not gbdt._use_device_predict(9, 1)
    gbdt.DEVICE_PREDICT_CELLS = GBDT.DEVICE_PREDICT_CELLS


# ------------------------------------------------------------- batcher
def test_batcher_coalesces_and_slices(binary_model):
    """Concurrent clients released together land in ONE coalesced
    dispatch (max_wait_ms holds the batch open), and every client gets
    exactly its own slice back."""
    gbdt, X = binary_model
    cp = CompiledPredictor.from_booster(gbdt, max_batch_rows=256)
    from lightgbm_tpu.serving import ServingMetrics
    metrics = ServingMetrics()
    mb = MicroBatcher(cp, max_wait_ms=300.0, metrics=metrics)
    n_clients = 6
    barrier = threading.Barrier(n_clients)
    results = [None] * n_clients
    slices = [X[i * 5:(i + 1) * 5 + i] for i in range(n_clients)]

    def client(i):
        barrier.wait()
        results[i] = mb.predict(slices[i], timeout=30)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    mb.close()
    for i in range(n_clients):
        np.testing.assert_allclose(results[i], gbdt.predict(slices[i]),
                                   atol=1e-6, rtol=0)
    assert metrics.batch_count < n_clients  # coalescing actually happened
    assert metrics.batched_requests == n_clients


def test_batcher_kinds_never_mix(binary_model):
    gbdt, X = binary_model
    cp = CompiledPredictor.from_booster(gbdt, max_batch_rows=64)
    mb = MicroBatcher(cp, max_wait_ms=50.0)
    futs = [mb.submit(X[:3], kind="predict"),
            mb.submit(X[3:5], kind="leaf"),
            mb.submit(X[5:9], kind="raw")]
    np.testing.assert_allclose(futs[0].result(30), gbdt.predict(X[:3]),
                               atol=1e-6, rtol=0)
    np.testing.assert_array_equal(futs[1].result(30),
                                  gbdt.predict_leaf_index(X[3:5]))
    np.testing.assert_allclose(futs[2].result(30),
                               gbdt.predict_raw(X[5:9]), atol=1e-6, rtol=0)
    mb.close()


def test_batcher_survives_mixed_widths(binary_model):
    """Regression: two individually-valid requests with different
    feature widths must coalesce (submit canonicalizes width) — the
    concat mismatch used to kill the single worker thread and hang
    every later request."""
    gbdt, X = binary_model
    cp = CompiledPredictor.from_booster(gbdt, max_batch_rows=64)
    mb = MicroBatcher(cp, max_wait_ms=100.0)
    f_narrow = mb.submit(X[:2, :3])            # 3 cols: padded
    f_wide = mb.submit(np.hstack([X[2:4], np.ones((2, 2))]))  # 8 cols
    pad = np.hstack([X[:2, :3], np.zeros((2, X.shape[1] - 3))])
    np.testing.assert_allclose(f_narrow.result(30), gbdt.predict(pad),
                               atol=1e-6, rtol=0)
    np.testing.assert_allclose(f_wide.result(30), gbdt.predict(X[2:4]),
                               atol=1e-6, rtol=0)
    # and the worker is still alive for the next request
    np.testing.assert_allclose(mb.predict(X[4:6], timeout=30),
                               gbdt.predict(X[4:6]), atol=1e-6, rtol=0)
    mb.close()


def test_metrics_nearest_rank_percentiles():
    from lightgbm_tpu.serving import ServingMetrics
    m = ServingMetrics()
    m.record_request(1, 0.001)
    m.record_request(1, 0.100)
    pct = m.latency_percentiles()
    assert pct[50] == pytest.approx(1.0)   # p50 of 2 = lower, not max
    m2 = ServingMetrics()
    for i in range(100):
        m2.record_request(1, (i + 1) / 1000.0)
    pct = m2.latency_percentiles()
    assert pct[50] == pytest.approx(50.0)
    assert pct[99] == pytest.approx(99.0)  # rank 98, not the max


def test_batcher_error_propagates():
    class Boom:
        max_batch_rows = 8

        def predict(self, rows):
            raise RuntimeError("boom")

    mb = MicroBatcher(Boom(), max_wait_ms=1.0)
    fut = mb.submit(np.zeros((2, 3)))
    with pytest.raises(RuntimeError, match="boom"):
        fut.result(10)
    mb.close()
    with pytest.raises(RuntimeError, match="closed"):
        mb.submit(np.zeros((1, 3)))


# -------------------------------------------------- streaming predict_file
def _write_csv(path, n_rows, n_cols, seed=3, bad_rows=()):
    rng = np.random.RandomState(seed)
    data = rng.randn(n_rows, n_cols).round(4)
    with open(path, "w") as f:
        for i, row in enumerate(data):
            if i in bad_rows:
                f.write(",".join(str(v) for v in row[:-1]) + ",oops\n")
            else:
                f.write(",".join(str(v) for v in row) + "\n")
    return data


def test_predict_file_chunk_boundaries(tmp_path, binary_model):
    """Chunked streaming output is byte-identical to the one-chunk
    parse, including a chunk size that does NOT divide the row count."""
    from lightgbm_tpu.application import Predictor
    gbdt, X = binary_model
    data_f = str(tmp_path / "rows.csv")
    _write_csv(data_f, 23, X.shape[1] + 1)  # col 0 = label
    pred = Predictor(gbdt)
    out_chunked = str(tmp_path / "chunked.tsv")
    out_whole = str(tmp_path / "whole.tsv")
    pred.predict_file(data_f, out_chunked, chunk_rows=7)
    pred.predict_file(data_f, out_whole, chunk_rows=10**6)
    with open(out_chunked) as a, open(out_whole) as b:
        assert a.read() == b.read()
    assert len(open(out_chunked).read().splitlines()) == 23


def test_predict_file_libsvm_width_padding(tmp_path, binary_model):
    """LibSVM chunks whose local max feature index is narrower than the
    model pad to the model width — a chunk of all-low indices must not
    crash or shift columns."""
    from lightgbm_tpu.application import Predictor
    gbdt, X = binary_model
    f = X.shape[1]
    data_f = str(tmp_path / "rows.libsvm")
    with open(data_f, "w") as fh:
        # rows 0-3 only use feature 0; row 4 uses the last feature
        for i in range(4):
            fh.write(f"1 0:{0.1 * (i + 1):.2f}\n")
        fh.write(f"0 {f - 1}:2.5\n")
    pred = Predictor(gbdt)
    out_chunked = str(tmp_path / "chunked.tsv")
    out_whole = str(tmp_path / "whole.tsv")
    pred.predict_file(data_f, out_chunked, chunk_rows=2)
    pred.predict_file(data_f, out_whole, chunk_rows=10**6)
    with open(out_chunked) as a, open(out_whole) as b:
        assert a.read() == b.read()


def test_predict_file_preserves_missing_values(tmp_path):
    """`task=predict` ingestion must keep NA cells as NaN so they ride
    the default-direction routing (right child) — the pre-fix parse
    collapsed them to 0.0, silently matching category 0."""
    from lightgbm_tpu.application import Predictor
    g = _cat_model()
    data_f = str(tmp_path / "rows.csv")
    with open(data_f, "w") as f:
        f.write("0,0.2,0.0\n")    # label, numeric A, categorical B=0
        f.write("0,0.2,na\n")     # missing categorical -> RIGHT child
        f.write("0,na,0.0\n")     # missing numeric -> right child
    out = str(tmp_path / "out.tsv")
    Predictor(g).predict_file(data_f, out)
    vals = [float(ln) for ln in open(out).read().split()]
    assert vals == [10.0, 30.0, 20.0]


def test_predict_file_quarantine_budget_spans_chunks(tmp_path,
                                                     binary_model):
    from lightgbm_tpu.application import Predictor
    from lightgbm_tpu.basic import LightGBMError
    gbdt, X = binary_model
    data_f = str(tmp_path / "messy.csv")
    _write_csv(data_f, 20, X.shape[1] + 1, bad_rows=(2, 15))  # 2 chunks
    pred = Predictor(gbdt)
    out = str(tmp_path / "out.tsv")
    pred.predict_file(data_f, out, chunk_rows=8, max_bad_rows=2)
    assert len(open(out).read().splitlines()) == 18
    with pytest.raises(LightGBMError, match="max_bad_rows"):
        pred.predict_file(data_f, out, chunk_rows=8, max_bad_rows=1)


# ------------------------------------------------------------ HTTP server
def test_server_in_process(binary_model):
    """make_server wiring: routes, batching, metrics accounting."""
    gbdt, X = binary_model
    cp = CompiledPredictor.from_booster(gbdt, max_batch_rows=32)
    srv = make_server(cp, port=0, max_wait_ms=1.0)
    port = srv.server_address[1]
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=30) as r:
                return json.loads(r.read())

        def post(path, body, ct="application/json"):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}{path}", data=body,
                headers={"Content-Type": ct})
            with urllib.request.urlopen(req, timeout=30) as r:
                return json.loads(r.read())

        health = get("/healthz")
        assert health["status"] == "ok"
        assert health["model"]["num_trees"] == cp.num_trees
        out = post("/predict",
                   json.dumps({"rows": X[:3].tolist()}).encode())
        np.testing.assert_allclose(out["predictions"], gbdt.predict(X[:3]),
                                   atol=1e-6, rtol=0)
        # null -> NaN -> default-direction routing, single-row form
        row = X[0].tolist()
        row[0] = None
        nan_row = X[0].copy()
        nan_row[0] = np.nan
        out1 = post("/predict", json.dumps({"row": row}).encode())
        np.testing.assert_allclose(out1["predictions"],
                                   gbdt.predict(nan_row[None, :]),
                                   atol=1e-6, rtol=0)
        # CSV body
        csv = "\n".join(",".join(f"{v:.6f}" for v in r)
                        for r in X[:2]).encode()
        out2 = post("/predict_raw", csv, "text/csv")
        np.testing.assert_allclose(out2["predictions"],
                                   gbdt.predict_raw(X[:2]), atol=1e-6,
                                   rtol=0)
        bad = post_error = None
        try:
            post("/predict", b"{}")
        except urllib.error.HTTPError as e:
            post_error = e.code
            bad = json.loads(e.read())
        assert post_error == 400 and "error" in bad
        # POST to an unknown path must drain the body: the SAME
        # keep-alive connection then serves a valid request (regression:
        # unread bytes used to poison the next request line)
        import http.client
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        payload = json.dumps({"rows": X[:2].tolist()}).encode()
        conn.request("POST", "/predict_rows", body=payload,
                     headers={"Content-Type": "application/json"})
        assert conn.getresponse().read() and True  # 404, body drained
        conn.request("POST", "/predict", body=payload,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        np.testing.assert_allclose(
            json.loads(resp.read())["predictions"], gbdt.predict(X[:2]),
            atol=1e-6, rtol=0)
        conn.close()
        m = get("/metricz")
        assert m["request_count"] == 4
        assert m["rows_served"] == 8
        assert m["error_count"] == 1
        assert m["cold_dispatches"] == 0
        assert m["latency_p50_ms"] > 0
        assert m["batch_count"] >= 1
    finally:
        srv.shutdown()
        srv.server_close()
        srv.batcher.close()


def test_serve_cli_end_to_end(tmp_path, binary_model):
    """`python -m lightgbm_tpu.serve`: load model, POST rows, check
    /healthz + /metricz, shut down cleanly."""
    gbdt, X = binary_model
    model_f = str(tmp_path / "model.txt")
    gbdt.save_model_to_file(-1, model_f)
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu"})
    proc = subprocess.Popen(
        [sys.executable, "-m", "lightgbm_tpu.serve", model_f,
         "--port", "0", "--max-batch-rows", "16", "--max-wait-ms", "1"],
        cwd=REPO_ROOT, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    try:
        url = None
        deadline = time.time() + 180
        while time.time() < deadline:
            line = proc.stdout.readline()
            if not line:
                assert proc.poll() is None, "server died during startup"
                time.sleep(0.1)
                continue
            if line.startswith("SERVING "):
                url = line.split()[1].strip()
                break
        assert url, "server never printed its readiness line"

        with urllib.request.urlopen(url + "/healthz", timeout=30) as r:
            health = json.loads(r.read())
        assert health["status"] == "ok"
        assert health["model"]["num_trees"] == len(gbdt.models)

        req = urllib.request.Request(
            url + "/predict",
            data=json.dumps({"rows": X[:4].tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            out = json.loads(r.read())
        np.testing.assert_allclose(out["predictions"], gbdt.predict(X[:4]),
                                   atol=1e-6, rtol=0)

        with urllib.request.urlopen(url + "/metricz", timeout=30) as r:
            m = json.loads(r.read())
        assert m["request_count"] == 1
        assert m["rows_served"] == 4
        assert m["cold_dispatches"] == 0  # warm request: zero recompiles
        assert "compile_cache_hit" in m
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)


# ---------------------------------------- request-level traces (PR 8)

def test_request_id_and_timing_breakdown(binary_model):
    """Every POST echoes a request id (caller's X-Request-Id or a
    generated one) and a parse/queue/compute latency split in both the
    JSON body and response headers."""
    gbdt, X = binary_model
    cp = CompiledPredictor.from_booster(gbdt, max_batch_rows=32)
    srv = make_server(cp, port=0, max_wait_ms=1.0)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/predict",
            data=json.dumps({"rows": X[:3].tolist()}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "client-id-7"})
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.headers["X-Request-Id"] == "client-id-7"
            timing_hdr = r.headers["X-Timing-Ms"]
            body = json.loads(r.read())
        assert body["request_id"] == "client-id-7"
        timing = body["timing_ms"]
        for k in ("parse_ms", "queue_ms", "compute_ms", "total_ms"):
            assert timing[k] >= 0.0, timing
        # the split is consistent: parts cannot exceed the total
        assert (timing["parse_ms"] + timing["queue_ms"]
                + timing["compute_ms"]) <= timing["total_ms"] + 0.5
        assert body["latency_ms"] == timing["total_ms"]
        # header mirrors the body split
        assert "queue=" in timing_hdr and "compute=" in timing_hdr

        # no header -> a generated id, still echoed both places
        req2 = urllib.request.Request(
            f"http://127.0.0.1:{port}/predict_raw",
            data=json.dumps({"row": X[0].tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req2, timeout=30) as r:
            gen = r.headers["X-Request-Id"]
            body2 = json.loads(r.read())
        assert gen and body2["request_id"] == gen
        assert gen != "client-id-7"

        # hostile ids are sanitized (header-injection chars dropped)
        req3 = urllib.request.Request(
            f"http://127.0.0.1:{port}/predict",
            data=json.dumps({"rows": X[:1].tolist()}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "a b<c>d" + "x" * 200})
        with urllib.request.urlopen(req3, timeout=30) as r:
            echoed = r.headers["X-Request-Id"]
            r.read()
        assert "<" not in echoed and " " not in echoed
        assert len(echoed) <= 64

        # errors carry the id too (the greppable failure story)
        req4 = urllib.request.Request(
            f"http://127.0.0.1:{port}/predict", data=b"{}",
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "err-1"})
        try:
            urllib.request.urlopen(req4, timeout=30)
            assert False, "expected 400"
        except urllib.error.HTTPError as e:
            assert e.code == 400
            assert e.headers["X-Request-Id"] == "err-1"
            assert json.loads(e.read())["request_id"] == "err-1"
    finally:
        srv.shutdown()
        srv.server_close()
        srv.batcher.close()


def test_access_and_slow_request_logs(binary_model, capsys, monkeypatch):
    """One structured access-log record per request honoring
    LIGHTGBM_TPU_LOG_JSON, and a slow-request record above the
    threshold with the same latency split."""
    from lightgbm_tpu.utils.log import Log
    gbdt, X = binary_model
    monkeypatch.setenv("LIGHTGBM_TPU_LOG_JSON", "1")
    # the fixture trained with verbose=-1 (fatal-only): raise to Info
    # so the access records (and the Warning slow line) are emitted
    monkeypatch.setattr(Log, "_level", 1)
    cp = CompiledPredictor.from_booster(gbdt, max_batch_rows=32)
    # threshold 0.0001 ms: every request is "slow" deterministically
    srv = make_server(cp, port=0, max_wait_ms=1.0,
                      slow_request_ms=0.0001)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        capsys.readouterr()   # drop warmup/server noise
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/predict",
            data=json.dumps({"rows": X[:2].tolist()}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "slowone"})
        urllib.request.urlopen(req, timeout=30).read()
        time.sleep(0.05)   # handler thread flushes its log lines
        lines = [json.loads(ln) for ln in
                 capsys.readouterr().out.splitlines()
                 if ln.startswith("{")]
        access = [r for r in lines if r.get("event") == "access"]
        assert len(access) == 1, lines
        rec = access[0]
        assert rec["request_id"] == "slowone"
        assert rec["path"] == "/predict" and rec["rows"] == 2
        assert rec["status"] == 200
        for k in ("parse_ms", "queue_ms", "compute_ms", "total_ms"):
            assert k in rec
        slow = [r for r in lines if r.get("event") == "slow_request"]
        assert len(slow) == 1
        assert slow[0]["request_id"] == "slowone"
        assert slow[0]["level"] == "Warning"
        assert slow[0]["total_ms"] >= 0.0001
    finally:
        srv.shutdown()
        srv.server_close()
        srv.batcher.close()


def test_metricz_prometheus_under_live_traffic(binary_model):
    """/metricz?format=prometheus parses while the batcher actively
    serves concurrent clients — no torn reads, counters land."""
    from lightgbm_tpu.telemetry import prometheus
    gbdt, X = binary_model
    cp = CompiledPredictor.from_booster(gbdt, max_batch_rows=32)
    srv = make_server(cp, port=0, max_wait_ms=2.0)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    errors, stop = [], threading.Event()

    def client():
        body = json.dumps({"rows": X[:4].tolist()}).encode()
        while not stop.is_set():
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/predict", data=body,
                    headers={"Content-Type": "application/json"})
                urllib.request.urlopen(req, timeout=30).read()
            except Exception as e:   # noqa: BLE001
                errors.append(repr(e))
                return

    workers = [threading.Thread(target=client) for _ in range(3)]
    try:
        for w in workers:
            w.start()
        parsed_pages = 0
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and parsed_pages < 20:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metricz?format=prometheus",
                    timeout=30) as r:
                assert r.headers["Content-Type"].startswith("text/plain")
                page = prometheus.parse(r.read().decode())
            # canonical exposition names: counters end _total, `_ms`
            # metrics render in base-unit seconds (the naming audit,
            # telemetry/prometheus.py)
            assert "lightgbm_tpu_request_total" in page
            assert "lightgbm_tpu_queue_depth" in page
            parsed_pages += 1
        stop.set()
        for w in workers:
            w.join(timeout=30)
        assert not errors, errors
        assert parsed_pages >= 20
        final_text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metricz?format=prometheus",
            timeout=30).read().decode()
        assert prometheus.lint_names(final_text) == []
        final = prometheus.parse(final_text)
        assert final["lightgbm_tpu_request_total"] > 0
        assert final["lightgbm_tpu_rows_served_total"] > 0
        assert 'lightgbm_tpu_latency_seconds{quantile="0.5"}' in final
        # JSON view still intact next to the exposition view
        snap = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metricz", timeout=30).read())
        assert snap["request_count"] == int(
            final["lightgbm_tpu_request_total"])
    finally:
        stop.set()
        srv.shutdown()
        srv.server_close()
        srv.batcher.close()


def test_serving_warmup_lands_in_compile_ledger(binary_model):
    """The AOT warmup's lowerings are attributed to their row bucket in
    the process-wide compile ledger (`serving_bucket_N` labels)."""
    from lightgbm_tpu.telemetry.ledger import LEDGER
    gbdt, _ = binary_model
    CompiledPredictor.from_booster(gbdt, max_batch_rows=16)
    snap = LEDGER.snapshot(recent_n=256)
    # in-process jit caching means THIS warmup may add no new entries
    # when an earlier test already compiled the same (kernel, bucket)
    # pairs — but some warmup in this process must have been attributed
    labels = {e["label"] for e in snap["recent"]}
    assert any(lbl.startswith("serving_bucket_") for lbl in labels), labels
