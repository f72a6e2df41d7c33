"""End-to-end fault-tolerance suite (ISSUE 2).

Every recovery path is exercised through the fault-injection harness
(lightgbm_tpu/utils/faults.py): crash-at-iteration-k resume determinism
(per-iteration AND fused blockwise paths, bagging + feature sampling
on), corrupt/truncated-checkpoint fallback, atomic model saves,
non-finite gradient policies, and distributed-init retry hardening.
"""

import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import callback
from lightgbm_tpu.utils import faults
from lightgbm_tpu.utils.checkpoint import (CheckpointError, CheckpointManager,
                                           atomic_write_text,
                                           decode_checkpoint,
                                           encode_checkpoint)
from lightgbm_tpu.utils.log import LightGBMError

PARAMS = {"objective": "binary", "metric": "binary_logloss", "num_leaves": 7,
          "min_data_in_leaf": 10, "verbose": -1, "bagging_fraction": 0.7,
          "bagging_freq": 2, "feature_fraction": 0.6, "learning_rate": 0.2}
N_ROUNDS = 20


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear_faults()
    yield
    faults.clear_faults()


def _data():
    rng = np.random.RandomState(7)
    x = rng.randn(600, 10)
    y = (x[:, 0] + 0.5 * rng.randn(600) > 0).astype(np.float64)
    return (x[:500], y[:500]), (x[500:], y[500:])


def _user_cb(env):
    """A non-engine callback: forces the true per-iteration loop."""


def _train(ckpt_dir=None, crash_at=None, resume=False, with_valid=True,
           per_iteration=False, params=PARAMS, early_stopping=None):
    (x, y), (xv, yv) = _data()
    train_set = lgb.Dataset(x, y, params=params)
    valid = [lgb.Dataset(xv, yv, reference=train_set, params=params)] \
        if with_valid else None
    cbs = []
    if ckpt_dir is not None:
        cbs.append(callback.checkpoint(ckpt_dir, period=5))
    if per_iteration:
        cbs.append(_user_cb)
    evals_result = {}
    if crash_at is not None:
        faults.set_fault("crash_at_iteration", crash_at)
    try:
        booster = lgb.train(params, train_set, num_boost_round=N_ROUNDS,
                            valid_sets=valid, verbose_eval=False,
                            evals_result=evals_result,
                            early_stopping_rounds=early_stopping,
                            callbacks=cbs,
                            resume_from=ckpt_dir if resume else None)
    except faults.InjectedFault:
        return None, evals_result
    finally:
        faults.clear_faults()
    return booster.gbdt.save_model_to_string(-1), evals_result


def _plain(evals_result):
    return {k: {m: list(v) for m, v in h.items()}
            for k, h in evals_result.items()}


# ------------------------------------------------------- resume determinism

def test_resume_bit_identical_fused_fast_path(tmp_path):
    """No valid sets -> the fused whole-scan path, chopped into
    snapshot-cadence blocks; kill at iteration 12, resume from the
    iteration-10 snapshot, byte-identical final model (bagging AND
    feature_fraction active, so RNG capture is what's being proven)."""
    ref, _ = _train(with_valid=False)
    d = str(tmp_path / "ck")
    crashed, _ = _train(ckpt_dir=d, crash_at=12, with_valid=False)
    assert crashed is None  # the injected preemption fired
    assert [it for it, _ in CheckpointManager(d).checkpoints()] == [5, 10]
    got, _ = _train(ckpt_dir=d, resume=True, with_valid=False)
    assert got == ref


def test_resume_bit_identical_fused_blockwise(tmp_path):
    """Valid set present -> the fused blockwise path with checkpoints
    fired at block boundaries only."""
    ref, _ = _train()
    d = str(tmp_path / "ck")
    crashed, _ = _train(ckpt_dir=d, crash_at=12)
    assert crashed is None
    got, _ = _train(ckpt_dir=d, resume=True)
    assert got == ref


def test_resume_bit_identical_per_iteration(tmp_path):
    """A user callback forces the true per-iteration loop; crash on an
    off-cadence iteration (13) so the resume replays 3 lost rounds."""
    ref, _ = _train(per_iteration=True)
    d = str(tmp_path / "ck")
    crashed, _ = _train(ckpt_dir=d, crash_at=13, per_iteration=True)
    assert crashed is None
    got, _ = _train(ckpt_dir=d, resume=True, per_iteration=True)
    assert got == ref


def test_resume_restores_eval_history_and_early_stopping(tmp_path):
    """evals_result continuity + early-stop tracker state ride inside
    the snapshot: like-for-like (same snapshot cadence) histories are
    identical element-wise."""
    d_ref = str(tmp_path / "ref")
    ref, er_ref = _train(ckpt_dir=d_ref, early_stopping=8)
    d = str(tmp_path / "ck")
    crashed, _ = _train(ckpt_dir=d, crash_at=11, early_stopping=8)
    assert crashed is None
    got, er_res = _train(ckpt_dir=d, resume=True, early_stopping=8)
    assert got == ref
    assert _plain(er_res) == _plain(er_ref)


def test_resume_bit_identical_dart(tmp_path):
    """DART re-scores EXISTING trees every iteration (drop/normalize in
    bin space), so this pins the checkpoint's bin-encoding sidecar and
    the drop-sampler RNG capture."""
    params = dict(PARAMS, boosting_type="dart", drop_rate=0.3)
    params.pop("metric")
    ref, _ = _train(with_valid=False, params=params)
    d = str(tmp_path / "ck")
    crashed, _ = _train(ckpt_dir=d, crash_at=12, with_valid=False,
                        params=params)
    assert crashed is None
    got, _ = _train(ckpt_dir=d, resume=True, with_valid=False,
                    params=params)
    assert got == ref


def test_resume_off_cadence_realigns_snapshot_boundaries(tmp_path):
    """Resume from an iteration-10 snapshot (period 5) with period=4:
    the fused fast path must re-align its blocks so snapshots land on
    multiples of 4 again (12, 16, 20) instead of never firing."""
    ref, _ = _train(with_valid=False)
    d = str(tmp_path / "ck")
    _train(ckpt_dir=d, crash_at=12, with_valid=False)
    (x, y), _ = _data()
    booster = lgb.train(PARAMS, lgb.Dataset(x, y, params=PARAMS),
                        num_boost_round=N_ROUNDS, verbose_eval=False,
                        callbacks=[callback.checkpoint(d, period=4)],
                        resume_from=d)
    assert booster.gbdt.save_model_to_string(-1) == ref
    saved = {it for it, _ in CheckpointManager(d).checkpoints()}
    assert saved == {12, 16, 20}  # re-aligned cadence, keep_last_k=3


def test_checkpoint_period_zero_is_disabled(tmp_path):
    """period<=0 constructs a disabled callback: training runs the
    plain fused scan and writes no snapshots."""
    ref, _ = _train(with_valid=False)
    d = str(tmp_path / "ck")
    (x, y), _ = _data()
    booster = lgb.train(PARAMS, lgb.Dataset(x, y, params=PARAMS),
                        num_boost_round=N_ROUNDS, verbose_eval=False,
                        callbacks=[callback.checkpoint(d, period=0)])
    assert booster.gbdt.save_model_to_string(-1) == ref
    assert CheckpointManager(d).checkpoints() == []


def test_cli_metric_freq_with_snapshots_stays_fused_and_identical(tmp_path):
    """Training-metric output (metric_freq) + snapshots: boundaries
    align to both cadences, the run completes, and the model matches a
    snapshot-free run byte-for-byte."""
    from lightgbm_tpu.application import Application
    data = str(tmp_path / "train.tsv")
    _write_cli_data(data)
    base = ["task=train", f"data={data}", "objective=binary",
            "metric=auc", "is_training_metric=true", "metric_freq=3",
            "num_trees=16", "num_leaves=7", "min_data_in_leaf=10",
            "verbose=-1", "bagging_fraction=0.7", "bagging_freq=2",
            "feature_fraction=0.6"]
    ref_model = str(tmp_path / "ref.txt")
    Application(base + [f"output_model={ref_model}"]).run()
    snap_model = str(tmp_path / "snap.txt")
    Application(base + [f"output_model={snap_model}",
                        "snapshot_freq=5"]).run()
    assert open(snap_model).read() == open(ref_model).read()
    snaps = CheckpointManager(snap_model + ".snapshots").checkpoints()
    assert [it for it, _ in snaps] == [5, 10, 15]


def test_distributed_init_already_initialized_is_tolerated(monkeypatch):
    """jax phrases the double-init error as 'should only be
    called once' — that must stay a warning + fallthrough (external
    launcher case), never a retry-then-fatal."""
    from lightgbm_tpu.parallel import distributed

    def fake_initialize(**kwargs):
        raise RuntimeError("distributed.initialize should only be "
                           "called once.")

    monkeypatch.setattr(distributed.jax.distributed, "initialize",
                        fake_initialize)
    ok = distributed._initialize_with_retry("10.0.0.1:12400", 2, 0,
                                            retries=3, backoff_s=0.0)
    assert ok is False  # tolerated, not fatal


def test_resume_without_checkpoint_is_cold_start(tmp_path):
    """resume_from pointing at an empty directory trains from scratch."""
    ref, _ = _train(with_valid=False)
    got, _ = _train(ckpt_dir=str(tmp_path / "empty"), resume=True,
                    with_valid=False)
    assert got == ref


# ---------------------------------------------------- checkpoint validation

def test_checkpoint_roundtrip_and_digest():
    state = {"state_version": 1, "iter": 3, "name": "abc",
             "score": np.arange(12, dtype=np.float32).reshape(3, 4),
             "scores_list": [np.ones(2), np.zeros(3)]}
    blob = encode_checkpoint(state)
    out = decode_checkpoint(blob)
    assert out["iter"] == 3 and out["name"] == "abc"
    np.testing.assert_array_equal(out["score"], state["score"])
    assert len(out["scores_list"]) == 2
    np.testing.assert_array_equal(out["scores_list"][1], np.zeros(3))
    # any flipped byte in the payload must fail the digest
    bad = blob[:-1] + bytes([blob[-1] ^ 1])
    with pytest.raises(CheckpointError, match="digest"):
        decode_checkpoint(bad)
    with pytest.raises(CheckpointError, match="truncated"):
        decode_checkpoint(blob[:len(blob) - 4])
    with pytest.raises(CheckpointError, match="magic"):
        decode_checkpoint(b"garbage" + blob)


def test_corrupt_newest_falls_back_to_previous(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last_k=3)
    mgr.save({"state_version": 1, "tag": "good"}, 5)
    with faults.injected_faults(corrupt_digest=1):
        mgr.save({"state_version": 1, "tag": "bad"}, 10)
    state, path = mgr.load_latest()
    assert state["tag"] == "good"
    assert path.endswith("iter00000005.ckpt")


def test_truncated_newest_falls_back_to_previous(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last_k=3)
    mgr.save({"state_version": 1, "tag": "good"}, 5)
    with faults.injected_faults(truncate_checkpoint=1):
        mgr.save({"state_version": 1, "tag": "bad"}, 10)
    state, path = mgr.load_latest()
    assert state["tag"] == "good"
    assert path.endswith("iter00000005.ckpt")


def test_all_checkpoints_corrupt_returns_none(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last_k=3)
    with faults.injected_faults(corrupt_digest=-1):
        mgr.save({"state_version": 1}, 5)
        mgr.save({"state_version": 1}, 10)
    state, path = mgr.load_latest()
    assert state is None and path is None


def test_rotation_keeps_last_k(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last_k=2)
    for it in (5, 10, 15, 20):
        mgr.save({"state_version": 1}, it)
    assert [it for it, _ in mgr.checkpoints()] == [15, 20]


def test_resumed_run_skips_corrupt_newest_checkpoint(tmp_path):
    """The end-to-end promise: corrupt the newest snapshot ON DISK,
    resume anyway — the loader falls back to the previous valid one and
    the final model still matches the uninterrupted run."""
    ref, _ = _train(with_valid=False)
    d = str(tmp_path / "ck")
    _train(ckpt_dir=d, crash_at=12, with_valid=False)
    newest = CheckpointManager(d).checkpoints()[-1][1]
    blob = open(newest, "rb").read()
    with open(newest, "wb") as f:  # torn write that made it to disk
        f.write(blob[:len(blob) // 2])
    got, _ = _train(ckpt_dir=d, resume=True, with_valid=False)
    assert got == ref


# ------------------------------------------------------------- atomic saves

def test_atomic_write_leaves_no_tmp_and_survives_existing(tmp_path):
    target = tmp_path / "model.txt"
    atomic_write_text(str(target), "v1\n")
    atomic_write_text(str(target), "v2\n")
    assert target.read_text() == "v2\n"
    assert os.listdir(tmp_path) == ["model.txt"]  # no tmp litter


def test_save_model_to_file_is_atomic(tmp_path, monkeypatch):
    """A crash mid-save must leave the OLD model intact: make the write
    of the new bytes explode and check the previous file survives."""
    (x, y), _ = _data()
    booster = lgb.train(PARAMS, lgb.Dataset(x, y, params=PARAMS),
                        num_boost_round=3, verbose_eval=False)
    target = str(tmp_path / "model.txt")
    booster.save_model(target)
    good = open(target).read()

    import lightgbm_tpu.utils.checkpoint as ckpt

    real_replace = os.replace

    def exploding_replace(src, dst):
        raise OSError("injected crash before rename")

    monkeypatch.setattr(ckpt.os, "replace", exploding_replace)
    with pytest.raises(OSError):
        booster.save_model(target)
    monkeypatch.setattr(ckpt.os, "replace", real_replace)
    assert open(target).read() == good
    # no tmp litter; the dataset-profile sidecar (written atomically by
    # the first, successful save) is a legitimate artifact
    assert sorted(os.listdir(tmp_path)) == [
        "model.txt", "model.txt.profile.json"]


# ------------------------------------------------------ non-finite guardrails

def test_nan_gradients_raise_with_diagnostic():
    (x, y), _ = _data()
    with faults.injected_faults(nan_grad_at_iteration=3, nan_grad_row=5):
        with pytest.raises(LightGBMError) as exc:
            lgb.train(PARAMS, lgb.Dataset(x, y, params=PARAMS),
                      num_boost_round=6, verbose_eval=False,
                      callbacks=[_user_cb])
    msg = str(exc.value)
    assert "iteration 3" in msg and "class 0" in msg and "row 5" in msg
    assert "nonfinite_guard" in msg  # actionable: names the knob


def test_nan_gradients_warn_skip_trains_through():
    (x, y), _ = _data()
    params = dict(PARAMS, nonfinite_guard="warn_skip")
    with faults.injected_faults(nan_grad_at_iteration=3):
        booster = lgb.train(params, lgb.Dataset(x, y, params=params),
                            num_boost_round=6, verbose_eval=False,
                            callbacks=[_user_cb])
    # rounds at the poisoned iteration are skipped, never trained on
    assert 0 < booster.gbdt.iter < 6
    for tree in booster.gbdt.models:
        assert np.isfinite(np.asarray(tree.leaf_value)).all()


def test_nan_gradients_clamp_trains_all_rounds():
    (x, y), _ = _data()
    params = dict(PARAMS, nonfinite_guard="clamp")
    with faults.injected_faults(nan_grad_at_iteration=3):
        booster = lgb.train(params, lgb.Dataset(x, y, params=params),
                            num_boost_round=6, verbose_eval=False,
                            callbacks=[_user_cb])
    assert booster.gbdt.iter == 6
    for tree in booster.gbdt.models:
        assert np.isfinite(np.asarray(tree.leaf_value)).all()


def test_bad_custom_objective_nan_raises_with_diagnostic():
    """The motivating case: a user fobj emitting NaN must produce an
    actionable error, not silently train garbage trees."""
    (x, y), _ = _data()
    params = dict(PARAMS, objective="none")
    params.pop("metric")

    def bad_fobj(preds, dataset):
        g = preds - y
        h = np.ones_like(g)
        g[9] = np.nan
        return g, h

    with pytest.raises(LightGBMError) as exc:
        lgb.train(params, lgb.Dataset(x, y, params=params),
                  num_boost_round=3, verbose_eval=False, fobj=bad_fobj)
    assert "row 9" in str(exc.value)


def test_nonfinite_label_fails_fast():
    (x, y), _ = _data()
    y = y.copy()
    y[17] = np.nan
    with pytest.raises(LightGBMError, match="row 17"):
        lgb.train(PARAMS, lgb.Dataset(x, y, params=PARAMS),
                  num_boost_round=2, verbose_eval=False)


def test_bad_nonfinite_guard_value_rejected():
    with pytest.raises(LightGBMError, match="nonfinite_guard"):
        from lightgbm_tpu.config import Config
        Config.from_params({"nonfinite_guard": "explode"})


# -------------------------------------------------- distributed hardening

def test_distributed_init_retries_then_succeeds(monkeypatch):
    from lightgbm_tpu.parallel import distributed

    calls = []

    def fake_initialize(coordinator_address, num_processes, process_id,
                        **kwargs):
        calls.append(coordinator_address)

    monkeypatch.setattr(distributed.jax.distributed, "initialize",
                        fake_initialize)
    with faults.injected_faults(fail_distributed_init=2):
        ok = distributed._initialize_with_retry("10.0.0.1:12400", 2, 0,
                                                retries=3, backoff_s=0.0)
    assert ok and len(calls) == 1  # 2 injected failures, then success


def test_distributed_init_exhausted_retries_is_fatal(monkeypatch):
    from lightgbm_tpu.parallel import distributed
    with faults.injected_faults(fail_distributed_init=-1):
        with pytest.raises(LightGBMError, match="after 3 attempts"):
            distributed._initialize_with_retry("10.0.0.1:12400", 2, 0,
                                               retries=2, backoff_s=0.0)


def test_rank_out_of_range_is_fatal(tmp_path, monkeypatch):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.parallel import distributed
    mlist = tmp_path / "mlist.txt"
    mlist.write_text("10.0.0.1 12400\n10.0.0.2 12400\n")
    cfg = Config.from_params({"num_machines": 2, "tree_learner": "data",
                              "machine_list_file": str(mlist)})
    monkeypatch.setenv("LIGHTGBM_TPU_RANK", "7")
    monkeypatch.setattr(distributed, "_initialized", False)
    with pytest.raises(LightGBMError, match="out of range"):
        distributed.init_from_config(cfg)


# ------------------------------------------------------- machine-list parse

def test_parse_machine_list_formats(tmp_path):
    from lightgbm_tpu.parallel.distributed import parse_machine_list
    path = tmp_path / "mlist.txt"
    path.write_text(
        "# header comment\n"
        "10.0.0.1 12400\n"
        "10.0.0.2:12401   # trailing comment\n"
        "[2001:db8::1]:12402\n"
        "2001:db8::2 12403\n"
        "[2001:db8::3] 12404\n"
        "\n"
    )
    assert parse_machine_list(str(path)) == [
        ("10.0.0.1", 12400),
        ("10.0.0.2", 12401),
        ("2001:db8::1", 12402),
        ("2001:db8::2", 12403),
        ("2001:db8::3", 12404),
    ]


def test_parse_machine_list_rejects_duplicate_host_port(tmp_path):
    # two ranks cannot share one port: a duplicated line must fail with
    # the offending line number, not silently shrink the rank count
    from lightgbm_tpu.parallel.distributed import parse_machine_list
    path = tmp_path / "mlist.txt"
    path.write_text("10.0.0.1 12400\n10.0.0.2 12400\n10.0.0.1 12400\n")
    with pytest.raises(LightGBMError, match="line 3 duplicates"):
        parse_machine_list(str(path))


def test_parse_machine_list_rejects_bare_ipv6_with_port(tmp_path):
    from lightgbm_tpu.parallel.distributed import parse_machine_list
    path = tmp_path / "mlist.txt"
    path.write_text("2001:db8::1:12400\n")  # ambiguous: needs brackets
    with pytest.raises(LightGBMError, match="IPv6"):
        parse_machine_list(str(path))


def test_parse_machine_list_rejects_bad_port(tmp_path):
    from lightgbm_tpu.parallel.distributed import parse_machine_list
    path = tmp_path / "mlist.txt"
    path.write_text("10.0.0.1 https\n")
    with pytest.raises(LightGBMError, match="port"):
        parse_machine_list(str(path))


# ---------------------------------------------------- CLI + hard preemption

def _write_cli_data(path):
    rng = np.random.RandomState(11)
    x = rng.randn(400, 6)
    y = (x[:, 0] + 0.5 * rng.randn(400) > 0).astype(int)
    with open(path, "w") as f:
        for i in range(400):
            f.write(str(y[i]) + "\t"
                    + "\t".join(f"{v:.6f}" for v in x[i]) + "\n")


def test_cli_hard_crash_resume_bit_identical(tmp_path):
    """The true preemption analog, end to end through the CLI: a child
    process is os._exit-killed mid-run by the env-armed harness, a
    plain rerun of the same command auto-resumes from the snapshot
    directory, and the final model file is byte-identical to an
    uninterrupted run's."""
    import subprocess
    import sys

    data = str(tmp_path / "train.tsv")
    _write_cli_data(data)
    base = ["task=train", f"data={data}", "objective=binary",
            "num_trees=16", "num_leaves=7", "min_data_in_leaf=10",
            "verbose=-1", "metric_freq=0", "bagging_fraction=0.7",
            "bagging_freq=2", "feature_fraction=0.6"]

    def run(out_model, snapshot=False, crash_env=None):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        if crash_env:
            env[faults.ENV_VAR] = crash_env
        args = base + [f"output_model={out_model}"]
        if snapshot:
            args.append("snapshot_freq=4")
        return subprocess.run(
            [sys.executable, "-m", "lightgbm_tpu"] + args,
            cwd=os.path.dirname(os.path.dirname(__file__)),
            env=env, capture_output=True, text=True, timeout=420)

    ref_model = str(tmp_path / "ref.txt")
    r = run(ref_model)
    assert r.returncode == 0, r.stdout + r.stderr
    crash_model = str(tmp_path / "crash.txt")
    r = run(crash_model, snapshot=True,
            crash_env="crash_at_iteration=10,hard_crash=1")
    assert r.returncode == faults.HARD_CRASH_EXIT_CODE
    assert not os.path.exists(crash_model)  # died before the save
    snaps = os.listdir(crash_model + ".snapshots")
    assert any("iter00000008" in s for s in snaps)
    r = run(crash_model, snapshot=True)  # plain rerun auto-resumes
    assert r.returncode == 0, r.stdout + r.stderr
    assert open(crash_model).read() == open(ref_model).read()


# ------------------------------------------------------------ fault harness

def test_env_spec_parsing(monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR,
                       "crash_at_iteration=5, corrupt_digest=2,hard_crash")
    faults.reload_from_env()
    assert faults.get("crash_at_iteration") == 5
    assert faults.get("corrupt_digest") == 2
    assert faults.get("hard_crash") == 1
    faults.clear_faults()


def test_consume_counts_down():
    faults.set_fault("fail_distributed_init", 2)
    assert faults.consume("fail_distributed_init")
    assert faults.consume("fail_distributed_init")
    assert not faults.consume("fail_distributed_init")


# ------------------------------------- malformed-row quarantine (CSV/TSV)

def _messy_csv(tmp_path, name="messy.csv"):
    path = tmp_path / name
    path.write_text("1,0.5,0.25\n"
                    "0,oops,0.5\n"       # bad cell
                    "1,0.75,0.9\n"
                    "0,0.1,0.2,77\n"     # wrong field count
                    "1,0.3,0.4\n")
    return str(path)


def test_strict_mode_still_raises_on_malformed_row(tmp_path):
    from lightgbm_tpu.io.parser import parse_text_file
    with pytest.raises(Exception):
        parse_text_file(_messy_csv(tmp_path))  # max_bad_rows defaults to 0


def test_max_bad_rows_quarantines_and_diagnoses(tmp_path, capsys):
    from lightgbm_tpu.io.parser import parse_text_file
    label, feats, *_ = parse_text_file(_messy_csv(tmp_path),
                                       max_bad_rows=2)
    assert len(label) == 3 and feats.shape == (3, 2)
    np.testing.assert_allclose(label, [1, 1, 1])
    out = capsys.readouterr().out
    assert "quarantined 2 malformed row(s)" in out
    assert "line 2" in out and "'oops'" in out  # first offender named


def test_max_bad_rows_budget_exceeded_is_fatal(tmp_path):
    from lightgbm_tpu.io.parser import parse_text_file
    with pytest.raises(LightGBMError, match="exceed max_bad_rows=1"):
        parse_text_file(_messy_csv(tmp_path), max_bad_rows=1)


def test_max_bad_rows_na_markers_are_not_bad(tmp_path):
    # NA markers legitimately parse to NaN -> 0.0; they must not count
    # against the quarantine budget (same as the strict path)
    from lightgbm_tpu.io.parser import parse_text_file
    path = tmp_path / "na.csv"
    path.write_text("1,NA,0.25\n0,0.5,nan\n1,,0.9\n")
    label, feats, *_ = parse_text_file(str(path), max_bad_rows=1)
    assert len(label) == 3
    assert feats[0, 0] == 0.0 and feats[1, 1] == 0.0


def test_cli_max_bad_rows_trains_through(tmp_path):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import DatasetLoader
    rng = np.random.RandomState(3)
    x = rng.rand(200, 3)
    y = (x[:, 0] > 0.5).astype(int)
    rows = [",".join([str(y[i])] + [f"{v:.6f}" for v in x[i]])
            for i in range(200)]
    rows[50] = "1,corrupt,0.5,0.5"
    path = tmp_path / "tr.csv"
    path.write_text("\n".join(rows) + "\n")
    cfg = Config.from_params({"objective": "binary", "max_bad_rows": 3,
                              "min_data_in_leaf": 5,
                              "enable_load_from_binary_file": False})
    ds = DatasetLoader(cfg).load_from_file(str(path))
    assert ds.num_data == 199  # one quarantined


# ------------------------------------------- binary dataset validation

def _make_binary_dataset(tmp_path):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import DatasetLoader
    rng = np.random.RandomState(5)
    x = rng.rand(250, 4)
    y = (x[:, 0] > 0.5).astype(int)
    csv = tmp_path / "bt.csv"
    np.savetxt(csv, np.column_stack([y, x]), delimiter=",", fmt="%.6f")
    cfg = Config.from_params({"objective": "binary",
                              "is_save_binary_file": True,
                              "min_data_in_leaf": 5})
    DatasetLoader(cfg).load_from_file(str(csv))
    return str(csv), str(csv) + ".bin", cfg


def test_binary_dataset_roundtrip_and_version(tmp_path):
    from lightgbm_tpu.io.dataset import CoreDataset
    csv, bin_path, _ = _make_binary_dataset(tmp_path)
    ds = CoreDataset.load_binary(bin_path)
    assert ds.bins.shape[1] == 250
    assert ds.metadata.num_data == 250


def test_binary_dataset_truncated_fails_clearly(tmp_path):
    from lightgbm_tpu.io.dataset import BinaryDatasetError, CoreDataset
    csv, bin_path, _ = _make_binary_dataset(tmp_path)
    blob = open(bin_path, "rb").read()
    open(bin_path, "wb").write(blob[:len(blob) // 2])
    with pytest.raises(BinaryDatasetError, match="truncated or corrupt"):
        CoreDataset.load_binary(bin_path)


def test_binary_dataset_foreign_npz_fails_clearly(tmp_path):
    from lightgbm_tpu.io.dataset import BinaryDatasetError, CoreDataset
    path = tmp_path / "foreign.bin"
    with open(path, "wb") as f:
        np.savez(f, foo=np.arange(3))
    with pytest.raises(BinaryDatasetError, match="no magic entry"):
        CoreDataset.load_binary(str(path))


def test_binary_dataset_text_file_fails_clearly(tmp_path):
    from lightgbm_tpu.io.dataset import BinaryDatasetError, CoreDataset
    path = tmp_path / "plain.txt"
    path.write_text("1,2,3\n")
    with pytest.raises(BinaryDatasetError, match="bad magic") as ei:
        CoreDataset.load_binary(str(path))
    assert not ei.value.claimed  # a text file never claimed to be binary


def test_binary_cache_falls_past_corrupt_sibling(tmp_path, capsys):
    # mirror of the checkpoint loader's fall-past-corrupt: a rotten
    # sibling .bin cache warns and rebuilds from text instead of dying
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import DatasetLoader
    csv, bin_path, cfg = _make_binary_dataset(tmp_path)
    blob = open(bin_path, "rb").read()
    open(bin_path, "wb").write(blob[: len(blob) // 2])
    cfg2 = Config.from_params({"objective": "binary",
                               "min_data_in_leaf": 5})
    ds = DatasetLoader(cfg2).load_from_file(csv)
    assert ds.num_data == 250  # rebuilt from text
    assert "ignoring unusable binary cache" in capsys.readouterr().out


def test_binary_data_file_itself_corrupt_is_fatal(tmp_path):
    # when the DATA argument is a broken binary dataset, falling back
    # to the text parser would only produce garbage — fail with the
    # real diagnosis instead
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import DatasetLoader
    csv, bin_path, cfg = _make_binary_dataset(tmp_path)
    blob = open(bin_path, "rb").read()
    open(bin_path, "wb").write(blob[: len(blob) // 2])
    with pytest.raises(LightGBMError, match="truncated or corrupt"):
        DatasetLoader(cfg).load_from_file(bin_path)
