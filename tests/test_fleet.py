"""Fleet subsystem tests (lightgbm_tpu/fleet/, docs/Fleet.md).

- ModelRegistry: atomic publish + CRC manifest verification, promote /
  quarantine / rollback pointer semantics (rollback restores the prior
  version BYTE-identically), torn-pointer and bit-rot detection, and
  the jax-free admin CLI.
- Hot-swap: concurrent /predict traffic during a flip never mixes
  model versions inside one response, suffers zero 5xx, and keeps
  cold_dispatches at 0 (the challenger AOT-warms behind the incumbent
  on the shape-stable padded kernels).
- bf16 serving_precision: pinned accuracy bound holds, leaf decisions
  stay exact, and the skew monitor wired through build_monitors stays
  quiet at its default threshold on bench-shaped traffic.
- Graceful drain: /quiescez, draining 503s, SIGTERM drain of the CLI.
- The end-to-end acceptance rung: serve incumbent -> shifted replay
  trips psi_warn -> pipeline retrains on fresh data -> challenger
  validates better -> atomic promote -> the following server hot-swaps
  (new version on /metricz, cold_dispatches 0) -> registry rollback
  restores the prior bytes; every transition journaled and exportable
  to a valid Perfetto trace.
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
from lightgbm_tpu.fleet import ModelRegistry, RegistryError
from lightgbm_tpu.fleet.hotswap import HotSwapper, RegistryFollower
from lightgbm_tpu.fleet.loadgen import LoadGenerator
from lightgbm_tpu.fleet.pipeline import FleetPipeline, auc_score
from lightgbm_tpu.serving import (CompiledPredictor, build_monitors,
                                  make_server, swap_model)
from lightgbm_tpu.serving.server import drain

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PARAMS = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 10,
          "verbose": -1}


def _data(n=1200, f=4, seed=5):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, f)
    y = (x[:, 0] + x[:, 1] > 1).astype(float)
    return x, y


def _train_model(tmp_path, name, rounds=5, seed=5, shuffle_labels=False):
    """Train + save (model file + profile sidecar). Returns (path,
    gbdt)."""
    x, y = _data(seed=seed)
    if shuffle_labels:   # a deliberately WORSE challenger
        y = np.random.RandomState(0).permutation(y)
    b = lgb.train(dict(PARAMS), lgb.Dataset(x, y, params=dict(PARAMS)),
                  num_boost_round=rounds, verbose_eval=False)
    path = str(tmp_path / f"{name}.txt")
    b.save_model(path)
    return path, b.gbdt


def _post(url, rows, path="/predict"):
    req = urllib.request.Request(
        url + path, data=json.dumps({"rows": np.asarray(rows).tolist()})
        .encode(), headers={"Content-Type": "application/json"})
    return json.loads(urllib.request.urlopen(req, timeout=30).read())


def _get(url, path):
    return json.loads(urllib.request.urlopen(url + path,
                                             timeout=30).read())


@pytest.fixture
def registry(tmp_path):
    return ModelRegistry(str(tmp_path / "registry"))


# ------------------------------------------------------------- registry
def test_registry_publish_promote_current(tmp_path, registry):
    m1, _ = _train_model(tmp_path, "m1")
    v1 = registry.publish(m1)
    assert v1 == 1
    assert registry.versions() == [1]
    # profile sidecar rode along automatically
    assert registry.profile_path(v1) is not None
    assert registry.current() is None        # publish does not promote
    ptr = registry.promote(v1, reason="bootstrap")
    assert ptr["version"] == 1 and ptr["generation"] == 1
    assert registry.current_version() == 1
    registry.verify(v1)                      # CRC manifest validates
    meta = registry.metadata(v1)
    assert "published_ts" in meta


def test_registry_crc_detects_bit_rot(tmp_path, registry):
    m1, _ = _train_model(tmp_path, "m1")
    v1 = registry.publish(m1)
    target = registry.model_path(v1)
    blob = bytearray(open(target, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(target, "wb").write(bytes(blob))
    with pytest.raises(RegistryError, match="crc32"):
        registry.verify(v1)
    with pytest.raises(RegistryError):       # promote re-verifies
        registry.promote(v1)


def test_registry_rollback_byte_identical(tmp_path, registry):
    m1, _ = _train_model(tmp_path, "m1", rounds=4)
    m2, _ = _train_model(tmp_path, "m2", rounds=8)
    v1, v2 = registry.publish(m1), registry.publish(m2)
    registry.promote(v1)
    v1_bytes = open(registry.model_path(v1), "rb").read()
    registry.promote(v2)
    assert registry.current_version() == v2
    ptr = registry.rollback(reason="bad rollout")
    assert ptr["version"] == v1
    assert open(registry.model_path(v1), "rb").read() == v1_bytes
    # generation keeps increasing: a follower sees the rollback as a
    # fresh transition even though the version number went backwards
    assert ptr["generation"] == 3
    with pytest.raises(RegistryError, match="prior"):
        registry.rollback()                  # history exhausted


def test_registry_quarantine_rules(tmp_path, registry):
    m1, _ = _train_model(tmp_path, "m1")
    m2, _ = _train_model(tmp_path, "m2", rounds=8)
    v1, v2 = registry.publish(m1), registry.publish(m2)
    registry.promote(v1)
    registry.quarantine(v2, reason="failed validation")
    assert registry.is_quarantined(v2)
    with pytest.raises(RegistryError, match="quarantined"):
        registry.promote(v2)
    registry.promote(v2, force=True)         # operator override
    assert registry.current_version() == v2
    with pytest.raises(RegistryError, match="live"):
        registry.quarantine(v2)              # never quarantine the live


def test_registry_torn_pointer_reads_none(tmp_path, registry):
    m1, _ = _train_model(tmp_path, "m1")
    registry.promote(registry.publish(m1))
    with open(os.path.join(registry.directory, "CURRENT"), "w") as f:
        f.write('{"version": 1, "gen')     # torn write (foreign writer)
    assert registry.current() is None


def test_registry_abandoned_stage_is_invisible(tmp_path, registry):
    m1, _ = _train_model(tmp_path, "m1")
    v1 = registry.publish(m1)
    # a crash mid-publish leaves a .tmp stage dir: never listed, and
    # the next publish allocates past it
    stage = os.path.join(registry.versions_dir, ".tmp.v00000099.123")
    os.makedirs(stage)
    open(os.path.join(stage, "model.txt"), "w").write("partial")
    assert registry.versions() == [v1]
    v2 = registry.publish(m1)
    assert v2 == v1 + 1


@pytest.mark.slow
def test_fleet_cli_admin_roundtrip(tmp_path):
    """The jax-free registry admin CLI: publish -> list -> promote ->
    rollback -> verify. (slow: five subprocess invocations; runs in
    `make verify-fleet`.)"""
    m1, _ = _train_model(tmp_path, "m1")
    m2, _ = _train_model(tmp_path, "m2", rounds=8)
    reg_dir = str(tmp_path / "reg")
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def cli(*args):
        r = subprocess.run(
            [sys.executable, "-m", "lightgbm_tpu.fleet", *args],
            capture_output=True, text=True, timeout=120, cwd=REPO_ROOT,
            env=env)
        assert r.returncode == 0, r.stderr
        return r.stdout

    assert "published v1" in cli("publish", "--registry", reg_dir, m1,
                                 "--promote")
    assert "published v2" in cli("publish", "--registry", reg_dir, m2)
    cli("promote", "--registry", reg_dir, "--version", "2")
    listing = json.loads(cli("list", "--registry", reg_dir))
    assert [v["version"] for v in listing["versions"]] == [1, 2]
    assert listing["current"]["version"] == 2
    assert "rolled back to v1" in cli("rollback", "--registry", reg_dir)
    out = cli("verify", "--registry", reg_dir)
    assert "v1: OK" in out and "v2: OK" in out


# ------------------------------------------------------ profile sidecar
def test_from_model_file_autodiscovers_profile(tmp_path):
    m1, gbdt = _train_model(tmp_path, "m1")
    cp = CompiledPredictor.from_model_file(m1, max_batch_rows=32)
    assert cp.model_path == m1
    assert cp.profile is not None
    assert cp.profile.num_features == 4
    assert cp.describe()["has_profile"]
    # build_monitors rides the discovered baseline: drift monitoring
    # without an explicit --profile flag
    drift, skew = build_monitors(cp, drift_sample_rate=1.0,
                                 skew_sample_rate=1.0)
    assert drift is not None and skew is not None
    # and a model saved WITHOUT a sidecar degrades gracefully
    bare = str(tmp_path / "bare.txt")
    gbdt.save_model_to_file(-1, bare)
    os.unlink(bare + ".profile.json")
    cp2 = CompiledPredictor.from_model_file(bare, max_batch_rows=32)
    assert cp2.profile is None
    d2, s2 = build_monitors(cp2, drift_sample_rate=1.0,
                            skew_sample_rate=1.0)
    assert d2 is None and s2 is not None


# -------------------------------------------------------- bf16 precision
def test_bf16_pinned_bound_and_exact_leaves(tmp_path):
    m1, gbdt = _train_model(tmp_path, "m1", rounds=10)
    x, _ = _data()
    exact = CompiledPredictor.from_model_file(m1, max_batch_rows=64)
    bf16 = CompiledPredictor.from_model_file(m1, max_batch_rows=64,
                                             serving_precision="bf16")
    assert bf16.accuracy_bound > 0 and exact.accuracy_bound == 0.0
    for fn in ("predict", "predict_raw"):
        err = np.abs(getattr(bf16, fn)(x) - getattr(exact, fn)(x)).max()
        assert err <= bf16.accuracy_bound, (fn, err, bf16.accuracy_bound)
    # traversal decisions are EXACT: identical leaves, identical shape
    np.testing.assert_array_equal(bf16.predict_leaf_index(x),
                                  exact.predict_leaf_index(x))
    assert bf16.stats["cold_dispatches"] == 0
    with pytest.raises(ValueError, match="serving_precision"):
        CompiledPredictor.from_model_file(m1, serving_precision="fp8")


def test_bf16_skew_monitor_quiet_at_default_threshold(tmp_path):
    """The acceptance bar: the skew monitor (default skew_warn=1,
    tolerance = the pinned bound) stays SILENT serving bf16 on
    bench-shaped traffic — reduced precision is monitored, not
    exempted."""
    m1, _ = _train_model(tmp_path, "m1", rounds=10)
    bf16 = CompiledPredictor.from_model_file(m1, max_batch_rows=256,
                                             serving_precision="bf16")
    drift, skew = build_monitors(bf16, drift_sample_rate=1.0,
                                 skew_sample_rate=1.0)
    assert skew.tol == pytest.approx(bf16.accuracy_bound)
    srv = make_server(bf16, port=0, max_wait_ms=1.0, drift=drift,
                      skew=skew)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        rng = np.random.RandomState(3)
        for _ in range(4):
            _post(f"http://127.0.0.1:{port}", rng.rand(64, 4))
        dz = _get(f"http://127.0.0.1:{port}", "/driftz")
        assert dz["skew"]["skew_rows_checked"] > 0
        assert dz["skew"]["skew_count"] == 0
        assert dz["skew"]["skew_max_abs_diff"] <= bf16.accuracy_bound
        mz = _get(f"http://127.0.0.1:{port}", "/metricz")
        assert mz["serving_precision"] == "bf16"
        assert mz["accuracy_bound"] == pytest.approx(bf16.accuracy_bound)
    finally:
        srv.shutdown()
        srv.server_close()
        srv.batcher.close()


# ------------------------------------------------------------- hot-swap
def test_concurrent_predict_during_hot_swap(tmp_path, registry):
    """The satellite contract: under concurrent /predict traffic a flip
    produces (1) zero 5xx, (2) responses that each match EXACTLY one
    model version — never a mix, (3) cold_dispatches 0 after the flip,
    and (4) /metricz showing the new version."""
    m1, g1 = _train_model(tmp_path, "m1", rounds=5)
    m2, g2 = _train_model(tmp_path, "m2", rounds=10)
    v1, v2 = registry.publish(m1), registry.publish(m2)
    registry.promote(v1)
    x, _ = _data()
    probe_rows = x[:16]
    want = {1: g1.predict(probe_rows), 2: g2.predict(probe_rows)}
    assert np.abs(want[1] - want[2]).max() > 1e-4  # distinguishable
    pred = CompiledPredictor.from_model_file(registry.model_path(v1),
                                             max_batch_rows=256)
    srv = make_server(pred, port=0, max_wait_ms=1.0, model_version=v1)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    stop = threading.Event()
    responses, errors = [], []

    def client():
        while not stop.is_set():
            try:
                out = np.asarray(_post(url, probe_rows)["predictions"])
                responses.append(out)
            except Exception as e:   # noqa: BLE001 — any 5xx fails below
                errors.append(repr(e))
                return

    workers = [threading.Thread(target=client) for _ in range(4)]
    try:
        for w in workers:
            w.start()
        time.sleep(0.4)
        swapper = HotSwapper(srv, registry)
        swapper.swap_to(v2, reason="test flip")
        time.sleep(0.4)
        stop.set()
        for w in workers:
            w.join(timeout=30)
        assert not errors, errors
        assert len(responses) > 20
        n_v1 = n_v2 = 0
        for out in responses:
            if np.allclose(out, want[1], atol=1e-6):
                n_v1 += 1
            elif np.allclose(out, want[2], atol=1e-6):
                n_v2 += 1
            else:                      # a mixed-version response
                raise AssertionError(
                    "response matches neither model version")
        assert n_v1 > 0 and n_v2 > 0   # traffic really spanned the flip
        # the flip was warm: the challenger never traced at request time
        assert srv.predictor.stats["cold_dispatches"] == 0
        mz = _get(url, "/metricz")
        assert mz["model_version"] == v2
        assert mz["swap_count"] == 1
        assert _get(url, "/healthz")["model_version"] == v2
        # and one more request serves the new model
        final = np.asarray(_post(url, probe_rows)["predictions"])
        np.testing.assert_allclose(final, want[2], atol=1e-6, rtol=0)
    finally:
        stop.set()
        srv.shutdown()
        srv.server_close()
        srv.batcher.close()


def test_follower_picks_up_promotion_and_failure_is_safe(tmp_path,
                                                         registry):
    m1, _ = _train_model(tmp_path, "m1", rounds=5)
    m2, _ = _train_model(tmp_path, "m2", rounds=8)
    v1 = registry.publish(m1)
    registry.promote(v1)
    pred = CompiledPredictor.from_model_file(registry.model_path(v1),
                                             max_batch_rows=64)
    srv = make_server(pred, port=0, max_wait_ms=1.0, model_version=v1)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        follower = RegistryFollower(HotSwapper(srv, registry),
                                    poll_s=999)
        follower.start()      # seeds the seen generation, no swap
        assert follower.poll_once() is None
        v2 = registry.publish(m2)
        registry.promote(v2)
        assert follower.poll_once() == v2
        assert srv.model_version == v2
        # corrupt the NEXT version: the follower must keep serving v2
        m3, _ = _train_model(tmp_path, "m3", rounds=6)
        v3 = registry.publish(m3)
        blob = bytearray(open(registry.model_path(v3), "rb").read())
        blob[10] ^= 0xFF
        open(registry.model_path(v3), "wb").write(bytes(blob))
        registry._write_pointer(v3, registry.current(), "bad")
        assert follower.poll_once() is None
        assert srv.model_version == v2
        assert follower.swapper.stats["failed_swaps"] == 1
        follower.stop()
    finally:
        srv.shutdown()
        srv.server_close()
        srv.batcher.close()


# ------------------------------------------------------- graceful drain
def test_quiescez_and_draining_503(tmp_path):
    m1, _ = _train_model(tmp_path, "m1")
    pred = CompiledPredictor.from_model_file(m1, max_batch_rows=32)
    srv = make_server(pred, port=0, max_wait_ms=1.0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        x, _ = _data()
        _post(url, x[:4])
        q = _get(url, "/quiescez")          # idle: 200 + quiescent
        assert q["quiescent"] and q["in_flight"] == 0
        assert not q["draining"]
        srv.draining = True                 # drain mode: POSTs bounce
        try:
            _post(url, x[:4])
            raise AssertionError("expected 503 while draining")
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert "draining" in json.loads(e.read())["error"]
        assert drain(srv, timeout_s=10)
        q = _get(url, "/quiescez")
        assert q["draining"] and q["quiescent"]
    finally:
        srv.shutdown()
        srv.server_close()
        srv.batcher.close()


@pytest.mark.slow
def test_serve_cli_sigterm_drains(tmp_path):
    """`python -m lightgbm_tpu.serve`: SIGTERM finishes in-flight work
    and exits 0 with the drain record. (slow: full serve subprocess
    startup; runs in `make verify-fleet`.)"""
    m1, _ = _train_model(tmp_path, "m1")
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "LIGHTGBM_TPU_LOG_JSON": "1"})
    proc = subprocess.Popen(
        [sys.executable, "-m", "lightgbm_tpu.serve", m1,
         "--port", "0", "--max-batch-rows", "16", "--max-wait-ms", "1"],
        cwd=REPO_ROOT, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    try:
        url = None
        deadline = time.time() + 180
        while time.time() < deadline:
            line = proc.stdout.readline()
            if line.startswith("SERVING "):
                url = line.split()[1].strip()
                break
            assert proc.poll() is None, "server died during startup"
        assert url
        x, _ = _data()
        _post(url, x[:4])
        assert _get(url, "/quiescez")["quiescent"]
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=60)
        assert proc.returncode == 0
        assert '"event": "drain"' in out.replace("'", '"') \
            or '"drained": true' in out or "drained" in out
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)


def test_serve_cli_fleet_flags_exist():
    r = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.serve", "--help"],
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT)
    assert r.returncode == 0
    for flag in ("--registry", "--follow", "--poll-s",
                 "--serving-precision", "--drain-timeout-s"):
        assert flag in r.stdout


# -------------------------------------------------------------- pipeline
def test_auc_score_matches_simple_cases():
    assert auc_score([0, 1], [0.1, 0.9]) == 1.0
    assert auc_score([1, 0], [0.1, 0.9]) == 0.0
    assert auc_score([0, 1, 0, 1], [0.5, 0.5, 0.5, 0.5]) == 0.5
    assert auc_score([1, 1, 1], [0.1, 0.2, 0.3]) == 0.5  # degenerate


def test_psi_warn_constant_mirrors_serving():
    from lightgbm_tpu.fleet.pipeline import DEFAULT_PSI_WARN as fleet_warn
    from lightgbm_tpu.serving.drift import DEFAULT_PSI_WARN as serve_warn
    assert fleet_warn == serve_warn


def test_pipeline_drift_gate():
    pipe = FleetPipeline.__new__(FleetPipeline)   # gate logic only
    pipe.psi_warn = 0.2
    quiet = {"enabled": True, "rows_sampled": 500, "min_psi_rows": 200,
             "psi_max": 0.05, "warnings": [], "features": {}}
    assert pipe.drift_excursion(quiet) is None
    cold = dict(quiet, rows_sampled=10, psi_max=5.0)
    assert pipe.drift_excursion(cold) is None     # too few rows to act
    hot = dict(quiet, psi_max=0.9,
               warnings=[{"feature": "Column_0", "psi": 0.9}],
               features={"Column_0": {"psi": 0.9},
                         "Column_1": {"psi": 0.01}})
    exc = pipe.drift_excursion(hot)
    assert exc["feature"] == "Column_0" and exc["psi"] == 0.9
    assert pipe.drift_excursion(None) is None


def test_pipeline_retrain_rides_checkpoints_and_block_store(tmp_path,
                                                            registry):
    """The retrain leg arms PR-2 checkpoints (snapshot files appear;
    an immediate re-run resumes) and streams through a PR-7 block
    store when the params say out_of_core."""
    snap_dir = str(tmp_path / "snaps")
    params = dict(PARAMS, out_of_core=True, block_rows=256)
    pipe = FleetPipeline(registry, params,
                         workdir=str(tmp_path / "work"),
                         snapshot_dir=snap_dir, snapshot_period=2)
    x, y = _data(n=800)
    path = pipe.retrain(x, y, num_boost_round=4, tag="a")
    assert os.path.exists(path)
    snaps = [f for f in os.listdir(snap_dir) if f.endswith(".ckpt")]
    assert snaps, "checkpoint callback did not fire"
    # a COMPLETED retrain leaves the RETRAIN_DONE marker, so the next
    # retrain starts FRESH (stale snapshots cleared — resuming a
    # finished run would train zero new rounds); same data/params =>
    # the same model bytes either way
    assert os.path.exists(os.path.join(snap_dir, "RETRAIN_DONE"))
    path2 = pipe.retrain(x, y, num_boost_round=4, tag="b")
    assert open(path).read() == open(path2).read()
    # an INTERRUPTED retrain (snapshots present, no marker) resumes:
    # wipe the marker, rerun, and the result still matches
    os.unlink(os.path.join(snap_dir, "RETRAIN_DONE"))
    path3 = pipe.retrain(x, y, num_boost_round=4, tag="c")
    assert open(path).read() == open(path3).read()


# -------------------------------------------------------- e2e acceptance
@pytest.mark.slow
def test_fleet_e2e_drift_retrain_promote_rollback(tmp_path):
    """The ISSUE acceptance rung: incumbent serves -> shifted replay
    fires psi_warn -> supervisor retrains on fresh data -> challenger
    validates better -> atomic promote -> the following server swaps
    (new version, cold_dispatches 0, p99 during swap bounded) ->
    rollback restores the prior version byte-identically. Plus the
    reject leg: a worse challenger quarantines instead of promoting.
    Every transition lands in the journal and exports to a valid
    Perfetto trace."""
    from lightgbm_tpu.telemetry.export import build_trace, validate_trace
    from lightgbm_tpu.telemetry.journal import (RunJournal, read_journal,
                                                validate_record)
    rng = np.random.RandomState(11)
    journal = RunJournal(str(tmp_path / "journal"), source="fleet",
                         meta={"source": "fleet"})
    registry = ModelRegistry(str(tmp_path / "registry"), journal=journal)
    # the incumbent trains on UNSHIFTED data
    m1, g1 = _train_model(tmp_path, "incumbent", rounds=5)
    v1 = registry.publish(m1)
    registry.promote(v1, reason="bootstrap")
    v1_bytes = open(registry.model_path(v1), "rb").read()

    pred = CompiledPredictor.from_model_file(registry.model_path(v1),
                                             max_batch_rows=256)
    settings = dict(drift_sample_rate=1.0, skew_sample_rate=1.0)
    dmon, smon = build_monitors(pred, **settings)
    srv = make_server(pred, port=0, max_wait_ms=1.0, drift=dmon,
                      skew=smon, model_version=v1,
                      monitor_settings=settings)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    follower = RegistryFollower(HotSwapper(srv, registry), poll_s=999)
    follower.start()
    try:
        # ---- phase 1: shifted replay trips psi_warn ----
        def shifted(n):
            rows = rng.rand(n, 4)
            rows[:, 0] += 3.0        # feature 0 leaves the train range
            return rows

        for _ in range(6):
            _post(url, shifted(100))
        driftz = _get(url, "/driftz")
        assert driftz["psi_max"] >= 0.2
        assert driftz["warnings"], "psi_warn never fired"

        # ---- phase 2: the supervisor retrains, validates, promotes --
        # fresh data reflects the shifted world (same concept, feature
        # 0 shifted), so the challenger genuinely fits current traffic
        fx = rng.rand(2500, 4)
        fx[:, 0] += 3.0
        fy = ((fx[:, 0] - 3.0) + fx[:, 1] > 1).astype(float)
        hx, hy = fx[2000:], fy[2000:]
        pipe = FleetPipeline(registry, PARAMS,
                             workdir=str(tmp_path / "work"),
                             journal=journal)
        result = pipe.run_once(driftz, fx[:2000], fy[:2000], hx, hy,
                               num_boost_round=12)
        assert result["action"] == "promote", result
        v2 = result["version"]
        assert result["challenger"] >= result["incumbent"]

        # ---- phase 3: the following server hot-swaps, load on ----
        gen = LoadGenerator(url, [rng.rand(8, 4) for _ in range(4)],
                            qps=60, workers=3, duration_s=2.5)
        gen.run(background=True)
        time.sleep(0.5)
        gen.mark_start("swap")
        assert follower.poll_once() == v2
        time.sleep(0.5)
        gen.mark_end("swap")
        gen.join(timeout=60)
        rep = gen.report()
        assert rep["errors"] == 0
        assert srv.predictor.stats["cold_dispatches"] == 0
        mz = _get(url, "/metricz")
        assert mz["model_version"] == v2
        assert mz["cold_dispatches"] == 0
        # p99 during the swap within 2x steady-state p99 (both sides
        # of the window measured under identical load)
        if rep["swap_window_requests"] >= 20:
            assert rep["p99_during_swap_ms"] <= max(
                2.0 * rep["steady_p99_ms"], rep["steady_p99_ms"] + 25.0)

        # ---- phase 4: reject leg — a WORSE challenger quarantines ---
        bad_x, bad_y = _data(n=1200, seed=99)
        bad_y = rng.permutation(bad_y)       # garbage labels
        result2 = pipe.run_once(driftz, bad_x, bad_y, hx, hy,
                                num_boost_round=4)
        assert result2["action"] == "reject", result2
        assert registry.is_quarantined(result2["version"])
        assert registry.current_version() == v2   # still the good one
        assert follower.poll_once() is None       # no generation move

        # ---- phase 5: rollback restores v1 byte-identically ----
        registry.rollback(reason="operator")
        assert follower.poll_once() == v1
        assert open(registry.model_path(v1), "rb").read() == v1_bytes
        assert _get(url, "/metricz")["model_version"] == v1
        final = np.asarray(_post(url, bad_x[:8])["predictions"])
        np.testing.assert_allclose(final, g1.predict(bad_x[:8]),
                                   atol=1e-6, rtol=0)

        # ---- the journal carries every transition, trace-exportable -
        journal.close()
        records, bad = read_journal(journal.path)
        assert bad == 0
        events = [r["event"] for r in records]
        assert events.count("promote") == 2      # bootstrap + v2
        assert "reject" in events and "rollback" in events
        for rec in records:
            assert validate_record(rec) == [], rec
        trace = build_trace(records)
        assert validate_trace(trace) == []
        names = {e.get("name") for e in trace["traceEvents"]}
        assert f"promote v{v2}" in names
        assert f"rollback v{v1}" in names
    finally:
        follower.stop()
        srv.shutdown()
        srv.server_close()
        srv.batcher.close()
