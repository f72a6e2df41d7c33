"""Out-of-core block-store training suite (ISSUE 7).

Covers the three layers of lightgbm_tpu/data/:

- block_store: build/validate/reuse of the on-disk packed-bin store,
  and every corruption mode a truncated/bit-rotted/stale store can
  produce (clear BlockStoreError naming the defect);
- prefetch: the double-buffered pipeline's ordering, zero-padding,
  bounded residency, cache hits, and error propagation;
- ooc_learner + engine integration: streamed training BIT-IDENTICAL to
  in-RAM masked-engine training on the same binning (binary /
  multiclass / bagging / GOSS / DART / feature_fraction / valid sets),
  crash-at-iteration resume determinism (soft fault and CLI
  hard-kill), and the memmap binary-cache satellite.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import callback
from lightgbm_tpu.config import Config
from lightgbm_tpu.data import (BlockPrefetcher, BlockStore, BlockStoreError,
                               BlockStoreWriter, effective_block_rows,
                               open_block_store_dataset, spill_core_dataset)
from lightgbm_tpu.data.block_store import MANIFEST_NAME
from lightgbm_tpu.io.dataset import CoreDataset, DatasetLoader
from lightgbm_tpu.utils import faults
from lightgbm_tpu.utils.log import LightGBMError

# the parity pairing: the streamed Kahan fold reproduces the MASKED
# histogram engine bit-for-bit, so the in-RAM reference always runs
# hist_compaction=false (docs/Out-of-Core.md precision contract)
BASE = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
        "learning_rate": 0.1, "verbose": -1, "hist_compaction": "false",
        "device_row_chunk": 256}
OOC = dict(BASE, out_of_core=True, block_rows=512)
N_ROUNDS = 6


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear_faults()
    yield
    faults.clear_faults()


def _data(n=3000, f=8, seed=3, noisy=True):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, f)
    y = (x[:, 0] + 0.6 * x[:, 1] * x[:, 2]
         + (0.8 * rng.randn(n) if noisy else 0) > 0).astype(np.float64)
    return x, y


def _write_csv(path, x, y):
    np.savetxt(path, np.column_stack([y, x]), delimiter=",", fmt="%.6f")


def _model(params, x, y, rounds=N_ROUNDS, **train_kw):
    booster = lgb.train(dict(params), lgb.Dataset(x, y, params=dict(params)),
                        num_boost_round=rounds, verbose_eval=False,
                        **train_kw)
    return booster


def _model_str(booster):
    return booster.gbdt.save_model_to_string(-1)


# ===================================================== block store layer

def _tiny_store(directory, rows=100, feats=3, block_rows=32, dtype=np.uint8,
                seed=0):
    rng = np.random.RandomState(seed)
    cols = rng.randint(0, 200, size=(feats, rows)).astype(dtype)
    w = BlockStoreWriter(str(directory), feats, dtype, block_rows)
    # append in ragged slices to exercise the writer's re-blocking
    for s, e in ((0, 10), (10, 45), (45, 100)):
        w.append(cols[:, s:e])
    w.finish({"payload": np.arange(3)})
    return cols


def test_writer_reblocks_ragged_appends(tmp_path):
    cols = _tiny_store(tmp_path / "st", rows=100, block_rows=32)
    store = BlockStore.open(str(tmp_path / "st"))
    assert store.num_rows == 100
    assert [b["rows"] for b in store.blocks] == [32, 32, 32, 4]
    got = np.concatenate([store.read_block(i) for i in range(4)], axis=1)
    assert np.array_equal(got, cols)
    assert store.total_bytes() == sum(b["nbytes"] for b in store.blocks)


def test_open_rejects_missing_manifest(tmp_path):
    os.makedirs(tmp_path / "not_a_store")
    with pytest.raises(BlockStoreError, match="no manifest.json"):
        BlockStore.open(str(tmp_path / "not_a_store"))


def test_open_rejects_foreign_magic(tmp_path):
    d = tmp_path / "st"
    _tiny_store(d)
    m = json.load(open(d / MANIFEST_NAME))
    m["magic"] = "someone_elses_store"
    json.dump(m, open(d / MANIFEST_NAME, "w"))
    with pytest.raises(BlockStoreError, match="foreign magic"):
        BlockStore.open(str(d))


def test_open_rejects_future_version(tmp_path):
    d = tmp_path / "st"
    _tiny_store(d)
    m = json.load(open(d / MANIFEST_NAME))
    m["format_version"] = 99
    json.dump(m, open(d / MANIFEST_NAME, "w"))
    with pytest.raises(BlockStoreError, match="format 99"):
        BlockStore.open(str(d))


def test_truncated_block_detected_at_open(tmp_path):
    d = tmp_path / "st"
    _tiny_store(d)
    path = d / "block-00001.npy"
    blob = open(path, "rb").read()
    open(path, "wb").write(blob[:-7])
    with pytest.raises(BlockStoreError, match="block-00001.npy.*truncated"):
        BlockStore.open(str(d))


def test_stale_manifest_missing_block_detected(tmp_path):
    d = tmp_path / "st"
    _tiny_store(d)
    os.remove(d / "block-00002.npy")
    with pytest.raises(BlockStoreError, match="block-00002.npy.*does not"):
        BlockStore.open(str(d))


def test_corrupt_block_detected_on_first_read(tmp_path):
    """Same-size bit rot passes the open() size check and is caught by
    the crc32 digest on first read."""
    d = tmp_path / "st"
    _tiny_store(d)
    path = d / "block-00000.npy"
    blob = bytearray(open(path, "rb").read())
    blob[-1] ^= 0xFF
    open(path, "wb").write(bytes(blob))
    store = BlockStore.open(str(d))
    with pytest.raises(BlockStoreError, match="block-00000.npy is corrupt"):
        store.read_block(0)
    # ooc_verify=false skips digests (opt-out documented in Parameters)
    assert BlockStore.open(str(d), verify=False).read_block(0) is not None


def test_interrupted_build_leaves_no_manifest(tmp_path):
    """The manifest is written LAST: a writer that never finish()ed
    leaves a directory open() refuses, and a rebuild through the writer
    clears the old manifest first."""
    d = tmp_path / "st"
    w = BlockStoreWriter(str(d), 3, np.uint8, 32)
    w.append(np.zeros((3, 40), np.uint8))  # one block flushed, no manifest
    with pytest.raises(BlockStoreError, match="interrupted build"):
        BlockStore.open(str(d))
    _tiny_store(d)  # full rebuild in the same directory is fine
    assert BlockStore.open(str(d)).num_rows == 100


# ==================================================== prefetcher layer

def _store_for_prefetch(tmp_path, rows=100, block_rows=32):
    d = tmp_path / "pst"
    cols = _tiny_store(d, rows=rows, block_rows=block_rows)
    return BlockStore.open(str(d)), cols


def test_prefetcher_order_padding_and_stats(tmp_path):
    store, cols = _store_for_prefetch(tmp_path)
    # 100 data rows padded to 128: span 4 holds 4 data rows + 28 zeros,
    # span 5 is fully virtual
    spans = [(0, 32, 32), (1, 32, 32), (2, 32, 32), (3, 32, 4), (None, 32, 0)]
    pf = BlockPrefetcher(store, spans, depth=2, stage_to_device=False)
    for _ in range(2):  # two passes reuse the same ring
        got, row = [], 0
        for s, e, blk in pf.stream():
            assert (s, e) == (row, row + 32)
            got.append(np.array(blk))
            row = e
        full = np.concatenate(got, axis=1)
        assert full.shape == (3, 160)
        assert np.array_equal(full[:, :100], cols)
        assert not full[:, 100:].any()
    st = pf.stats()
    assert st["prefetch_blocks"] == 8  # 4 data blocks x 2 passes
    assert st["prefetch_bytes"] == 2 * cols.nbytes
    pf.note_pass_wall(1.0)
    assert 0.0 <= pf.overlap_pct() <= 100.0


def test_prefetcher_cache_and_residency_bound(tmp_path):
    store, cols = _store_for_prefetch(tmp_path)
    spans = [(i, 32, 32) for i in range(3)]
    pf = BlockPrefetcher(store, spans, depth=2, cache_blocks=3,
                         stage_to_device=False)
    list(pf.stream())
    assert pf.stats()["prefetch_cache_hits"] == 0
    first = pf.stats()["prefetch_bytes"]
    out = [np.array(b) for _, _, b in pf.stream()]  # all served by cache
    assert pf.stats()["prefetch_cache_hits"] == 3
    assert pf.stats()["prefetch_bytes"] == first
    assert np.array_equal(np.concatenate(out, 1), cols[:, :96])
    item = 3 * 32 * 1
    assert pf.resident_bytes() == item * (2 * 2 + 1 + 3)


def test_prefetcher_propagates_reader_errors(tmp_path):
    store, _ = _store_for_prefetch(tmp_path)
    spans = [(0, 32, 32), (1, 32, 31)]  # span plan disagrees with block
    pf = BlockPrefetcher(store, spans, depth=1, stage_to_device=False)
    with pytest.raises(RuntimeError, match="span plan"):
        list(pf.stream())


# ============================================== dataset container layer

def test_spill_roundtrip_and_block_view(tmp_path):
    x, y = _data(n=700, f=5)
    cfg = Config.from_params({"verbose": -1})
    core = DatasetLoader(cfg).construct_from_matrix(x, label=y)
    ds = spill_core_dataset(core, str(tmp_path / "st"), 128)
    assert ds.num_data == 700
    assert ds.block_store.num_blocks == -(-700 // 128)
    assert ds.stored_bins_dtype == core.bins.dtype
    # the traversal view gathers (feature, row) pairs across blocks
    view = ds.traversal_bins()
    rng = np.random.RandomState(0)
    feats = rng.randint(0, 5, 200)
    rows = rng.randint(0, 700, 200)
    assert np.array_equal(view[feats, rows],
                          core.bins[feats, rows].astype(np.int64))
    # round-trip: materialized matrix equals the original bit-for-bit
    back = ds.materialize_in_ram()
    assert np.array_equal(back.bins, core.bins)
    assert open_block_store_dataset(str(tmp_path / "st")).num_data == 700


def test_ooc_dataset_guardrails(tmp_path):
    x, y = _data(n=400, f=4)
    cfg = Config.from_params({"verbose": -1})
    core = DatasetLoader(cfg).construct_from_matrix(x, label=y)
    ds = spill_core_dataset(core, str(tmp_path / "st"), 128)
    with pytest.raises(LightGBMError, match="no resident bin matrix"):
        ds.device_bins()
    with pytest.raises(LightGBMError, match="subset"):
        ds.subset(np.arange(10))
    with pytest.raises(LightGBMError, match="already is the binary form"):
        ds.save_binary(str(tmp_path / "x.bin"))
    # an OOC dataset handed to the serial learner names the config fix
    from lightgbm_tpu.models.tree_learner import SerialTreeLearner
    with pytest.raises(LightGBMError, match="out_of_core=true"):
        SerialTreeLearner(Config.from_params(dict(BASE))).init(ds)


def test_file_store_reuse_and_signature_rebuild(tmp_path, caplog):
    x, y = _data(n=900, f=5)
    data = str(tmp_path / "t.csv")
    _write_csv(data, x, y)
    cfg = Config.from_params(dict(OOC, verbose=-1))
    ds1 = DatasetLoader(cfg).load_from_file(data)
    store_dir = data + ".blocks"
    stamp = os.path.getmtime(os.path.join(store_dir, MANIFEST_NAME))
    # same signature -> reuse (manifest untouched)
    ds2 = DatasetLoader(cfg).load_from_file(data)
    assert os.path.getmtime(os.path.join(store_dir, MANIFEST_NAME)) == stamp
    assert np.array_equal(ds1.metadata.label, ds2.metadata.label)
    # binning change -> rebuild
    cfg3 = Config.from_params(dict(OOC, verbose=-1, max_bin=63))
    ds3 = DatasetLoader(cfg3).load_from_file(data)
    assert os.path.getmtime(
        os.path.join(store_dir, MANIFEST_NAME)) != stamp
    assert ds3.block_store.manifest["binning"]["max_bin"] == 63
    # data-file change -> rebuild (source signature mismatch)
    _write_csv(data, x[:800], y[:800])
    ds4 = DatasetLoader(cfg).load_from_file(data)
    assert ds4.num_data == 800


def test_block_rows_round_up_to_chunk():
    cfg = Config.from_params(dict(OOC, block_rows=300))
    assert effective_block_rows(cfg) == 512  # 2 x device_row_chunk=256
    cfg2 = Config.from_params(dict(OOC, block_rows=512))
    assert effective_block_rows(cfg2) == 512


# ===================================================== training parity

def _parity_case(ref_params, ooc_params, rounds=N_ROUNDS, n=3000, seed=3,
                 **train_kw):
    x, y = _data(n=n, seed=seed)
    ref = _model(ref_params, x, y, rounds=rounds, **train_kw)
    got = _model(ooc_params, x, y, rounds=rounds, **train_kw)
    assert _model_str(got) == _model_str(ref)
    assert np.array_equal(ref.predict(x), got.predict(x))
    return ref, got


def test_parity_binary_matrix_path():
    _parity_case(BASE, OOC)


def test_parity_file_path(tmp_path):
    x, y = _data(n=2500)
    data = str(tmp_path / "t.csv")
    _write_csv(data, x, y)
    ref = lgb.train(dict(BASE), lgb.Dataset(data, params=dict(BASE)),
                    num_boost_round=N_ROUNDS)
    got = lgb.train(dict(OOC), lgb.Dataset(data, params=dict(OOC)),
                    num_boost_round=N_ROUNDS)
    assert _model_str(got) == _model_str(ref)
    assert np.array_equal(ref.predict(x), got.predict(x))


def test_parity_bagging_and_feature_fraction():
    extra = {"bagging_fraction": 0.6, "bagging_freq": 2,
             "feature_fraction": 0.7}
    _parity_case(dict(BASE, **extra), dict(OOC, **extra))


def test_parity_goss():
    extra = {"boosting": "goss", "top_rate": 0.3, "other_rate": 0.2}
    _parity_case(dict(BASE, **extra), dict(OOC, **extra))


def test_parity_dart():
    extra = {"boosting": "dart", "drop_rate": 0.3, "drop_seed": 9}
    _parity_case(dict(BASE, **extra), dict(OOC, **extra))


def test_parity_multiclass():
    x, _ = _data(n=2400)
    y = (np.digitize(x[:, 0], [-0.5, 0.5])).astype(np.float64)
    extra = {"objective": "multiclass", "num_class": 3,
             "metric": "multi_logloss"}
    ref = _model(dict(BASE, **extra), x, y)
    got = _model(dict(OOC, **extra), x, y)
    assert _model_str(got) == _model_str(ref)
    assert np.array_equal(ref.predict(x), got.predict(x))


def test_parity_with_valid_set_and_early_stopping():
    """Valid sets stay in-RAM, aligned against the OOC train set's
    mappers (stored_bins_dtype path) and scored per iteration."""
    x, y = _data(n=3000)
    xt, yt, xv, yv = x[:2400], y[:2400], x[2400:], y[2400:]
    out = {}
    for name, params in (("ref", BASE), ("ooc", OOC)):
        p = dict(params, metric="binary_logloss")
        train = lgb.Dataset(xt, yt, params=p)
        valid = lgb.Dataset(xv, yv, reference=train, params=p)
        er = {}
        out[name] = (_model_str(lgb.train(
            p, train, num_boost_round=N_ROUNDS, valid_sets=[valid],
            early_stopping_rounds=4, evals_result=er, verbose_eval=False)),
            er)
    assert out["ooc"][0] == out["ref"][0]
    # eval histories agree to ulps only: the in-RAM run's valid scores
    # ride the fused train_many_eval stacked-delta path while the OOC
    # run scores per iteration — a pre-existing fused-vs-per-iteration
    # summation-order artifact, not an OOC one (models are exact above)
    ref_h = out["ref"][1]["valid_0"]["logloss"]
    ooc_h = out["ooc"][1]["valid_0"]["logloss"]
    np.testing.assert_allclose(ooc_h, ref_h, rtol=1e-6)


def test_ten_x_resident_budget_trains_bounded(tmp_path):
    """Acceptance shape in miniature: a store >= 10x the streaming
    pipeline's resident-block budget trains end-to-end, bit-identical
    to in-RAM, with the prefetcher's bin residency bound respected."""
    x, y = _data(n=8000, f=16, seed=5)
    p = dict(OOC, block_rows=256, prefetch_depth=1, num_leaves=7)
    ref = _model(dict(BASE, num_leaves=7), x, y, rounds=3)
    got = _model(p, x, y, rounds=3)
    learner = got.gbdt.tree_learner
    pf = learner._prefetcher
    data_bytes = learner.train_set.block_store.total_bytes()
    assert data_bytes >= 10 * pf.resident_bytes()
    assert pf.stats()["prefetch_bytes"] > data_bytes  # streamed many passes
    assert _model_str(got) == _model_str(ref)


# ============================================= crash / resume / telemetry

def _train_ckpt(params, ckpt_dir=None, crash_at=None, resume=False,
                rounds=12):
    x, y = _data(n=2000)
    cbs = [callback.checkpoint(ckpt_dir, period=4)] if ckpt_dir else []
    if crash_at is not None:
        faults.set_fault("crash_at_iteration", crash_at)
    try:
        booster = lgb.train(dict(params),
                            lgb.Dataset(x, y, params=dict(params)),
                            num_boost_round=rounds, callbacks=cbs,
                            verbose_eval=False,
                            resume_from=ckpt_dir if resume else None)
    except faults.InjectedFault:
        return None
    finally:
        faults.clear_faults()
    return _model_str(booster)


def test_crash_resume_bit_identical(tmp_path):
    """Soft crash mid-epoch with bagging + feature sampling armed: the
    resumed OOC run is byte-identical to the uninterrupted OOC run AND
    to the in-RAM reference."""
    params = dict(OOC, bagging_fraction=0.7, bagging_freq=2,
                  feature_fraction=0.7)
    ref_inram = _train_ckpt(dict(BASE, bagging_fraction=0.7,
                                 bagging_freq=2, feature_fraction=0.7))
    ref = _train_ckpt(params)
    assert ref == ref_inram
    d = str(tmp_path / "ck")
    crashed = _train_ckpt(params, ckpt_dir=d, crash_at=10)
    assert crashed is None
    got = _train_ckpt(params, ckpt_dir=d, resume=True)
    assert got == ref


def test_cli_hard_crash_resume_bit_identical(tmp_path):
    """End-to-end preemption through the CLI with out_of_core on: the
    os._exit-killed child's plain rerun reuses the on-disk block store
    (no rebuild), auto-resumes from the snapshot, and the model file is
    byte-identical to an uninterrupted in-RAM run's."""
    x, y = _data(n=1200, f=5, seed=11)
    data = str(tmp_path / "train.csv")
    _write_csv(data, x, y)
    base = ["task=train", f"data={data}", "objective=binary",
            "num_trees=10", "num_leaves=7", "min_data_in_leaf=10",
            "verbose=-1", "metric_freq=0", "hist_compaction=false",
            "device_row_chunk=256", "bagging_fraction=0.7",
            "bagging_freq=2"]

    def run(out_model, ooc=False, snapshot=False, crash_env=None):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        if crash_env:
            env[faults.ENV_VAR] = crash_env
        args = base + [f"output_model={out_model}"]
        if ooc:
            args += ["out_of_core=true", "block_rows=512"]
        if snapshot:
            args.append("snapshot_freq=3")
        return subprocess.run(
            [sys.executable, "-m", "lightgbm_tpu"] + args,
            cwd=os.path.dirname(os.path.dirname(__file__)),
            env=env, capture_output=True, text=True, timeout=420)

    ref_model = str(tmp_path / "ref.txt")
    r = run(ref_model)
    assert r.returncode == 0, r.stdout + r.stderr
    crash_model = str(tmp_path / "crash.txt")
    r = run(crash_model, ooc=True, snapshot=True,
            crash_env="crash_at_iteration=7,hard_crash=1")
    assert r.returncode == faults.HARD_CRASH_EXIT_CODE
    assert not os.path.exists(crash_model)
    stamp = os.path.getmtime(os.path.join(data + ".blocks", MANIFEST_NAME))
    r = run(crash_model, ooc=True, snapshot=True)  # auto-resume
    assert r.returncode == 0, r.stdout + r.stderr
    # the rerun reused the crashed run's block store
    assert os.path.getmtime(
        os.path.join(data + ".blocks", MANIFEST_NAME)) == stamp
    assert open(crash_model).read() == open(ref_model).read()


def test_prefetch_telemetry_in_registry_and_journal(tmp_path):
    """`transfer_bytes` counts streamed bytes, the prefetch gauges land
    in the MetricsRegistry snapshot (/trainz serializes exactly this),
    and every iteration journal record carries the prefetch fields."""
    from lightgbm_tpu.telemetry.journal import read_journal
    x, y = _data(n=1500)
    d = str(tmp_path / "tj")
    params = dict(OOC, telemetry=True, telemetry_dir=d)
    booster = _model(params, x, y, rounds=3)
    inner = booster.gbdt
    snap = inner.metrics.snapshot()
    data_bytes = inner.tree_learner.train_set.block_store.total_bytes()
    assert snap["counters"]["transfer_bytes"] >= data_bytes
    assert "prefetch_depth" in snap["gauges"]
    assert "prefetch_overlap_pct" in snap["gauges"]
    assert snap["histograms"]["prefetch_wait_s"]["count"] == 3
    records, bad = read_journal(inner.journal.path)
    assert bad == 0
    iters = [r for r in records if r.get("event") == "iteration"]
    assert len(iters) == 3
    for rec in iters:
        assert rec["prefetch_bytes"] > 0
        assert "prefetch_wait_s" in rec
        assert 0.0 <= rec["prefetch_overlap_pct"] <= 100.0


def test_prefetch_journal_covers_all_multiclass_builds(tmp_path):
    """A multiclass iteration runs K per-class train_device calls but
    writes ONE journal record — its prefetch delta must cover all K
    builds, so journal totals equal the registry's transfer_bytes."""
    from lightgbm_tpu.telemetry.journal import read_journal
    x, y = _data(n=1500)
    y3 = (y + (x[:, 3] > 0.8)).astype(np.float64)
    d = str(tmp_path / "tj3")
    params = dict(OOC, objective="multiclass", num_class=3,
                  telemetry=True, telemetry_dir=d)
    booster = _model(params, x, y3, rounds=3)
    inner = booster.gbdt
    records, bad = read_journal(inner.journal.path)
    assert bad == 0
    j_bytes = sum(r["prefetch_bytes"] for r in records
                  if r.get("event") == "iteration")
    assert j_bytes == int(inner.metrics.counter("transfer_bytes").value)


# ================================================ memmap cache satellite

def test_binary_cache_loads_via_memmap(tmp_path):
    """Satellite: the v2 cache's bins member is stored uncompressed and
    maps through the OS page cache instead of a full-read copy."""
    x, y = _data(n=800, f=5)
    cfg = Config.from_params({"verbose": -1})
    ds = DatasetLoader(cfg).construct_from_matrix(x, label=y)
    path = str(tmp_path / "c.bin")
    ds.save_binary(path)
    back = CoreDataset.load_binary(path)
    assert isinstance(back.bins, np.memmap)
    assert not back.bins.flags.writeable
    assert np.array_equal(np.asarray(back.bins), ds.bins)
    # a compressed (pre-mapped-IO) archive still loads, via the
    # copying fallback
    import zipfile
    legacy = str(tmp_path / "legacy.bin")
    with zipfile.ZipFile(path) as zin, \
            zipfile.ZipFile(legacy, "w", zipfile.ZIP_DEFLATED) as zout:
        for info in zin.infolist():
            zout.writestr(info.filename, zin.read(info.filename))
    old = CoreDataset.load_binary(legacy)
    assert not isinstance(old.bins, np.memmap)
    assert np.array_equal(np.asarray(old.bins), ds.bins)


def test_corrupt_memmap_cache_detected(tmp_path):
    """Mapping bypasses zipfile's decompress-time CRC, so the mapper
    verifies the member bytes itself: a bit-rotted cache must refuse to
    map (and the copying fallback then surfaces the zip CRC error)
    instead of silently training on corrupt bins."""
    import zipfile

    from lightgbm_tpu.data.mmap_io import memmap_npz_member
    x, y = _data(n=800, f=5)
    cfg = Config.from_params({"verbose": -1})
    ds = DatasetLoader(cfg).construct_from_matrix(x, label=y)
    path = str(tmp_path / "c.bin")
    ds.save_binary(path)
    with zipfile.ZipFile(path) as zf:
        info = zf.getinfo("bins.npy")
    with open(path, "rb") as f:
        f.seek(info.header_offset)
        hdr = f.read(30)
        name_len = int.from_bytes(hdr[26:28], "little")
        extra_len = int.from_bytes(hdr[28:30], "little")
    flip_at = (info.header_offset + 30 + name_len + extra_len
               + info.file_size // 2)
    with open(path, "r+b") as f:
        f.seek(flip_at)
        b = f.read(1)
        f.seek(flip_at)
        f.write(bytes([b[0] ^ 0xFF]))
    assert memmap_npz_member(path, "bins.npy") is None
    with pytest.raises(Exception):
        CoreDataset.load_binary(path)


def test_ooc_file_path_rejects_bundleable_sparse(tmp_path):
    """The block store bins per-feature; data the in-RAM path would
    EFB-bundle must fatal (same guard as spill_core_dataset), not
    silently train a different model."""
    rng = np.random.RandomState(0)
    n = 2000
    idx = np.arange(n)
    x = np.column_stack([
        np.where(idx % 10 == 0, rng.rand(n) + 0.1, 0.0),
        np.where(idx % 10 == 1, rng.rand(n) + 0.1, 0.0),
        rng.rand(n)])
    y = (x[:, 2] > 0.5).astype(np.float64)
    data = str(tmp_path / "sparse.csv")
    _write_csv(data, x, y)
    sparse_p = {"verbose": -1, "is_enable_sparse": True, "max_bin": 50}
    ref = DatasetLoader(Config.from_params(dict(sparse_p))) \
        .load_from_file(data)
    assert ref.bundle_plan is not None  # the in-RAM path does bundle
    cfg = Config.from_params(dict(sparse_p, out_of_core=True,
                                  ooc_dir=str(tmp_path / "blocks")))
    with pytest.raises(LightGBMError, match="feature bundling"):
        DatasetLoader(cfg).load_from_file(data)


def test_memmap_cache_trains_identically(tmp_path):
    x, y = _data(n=1200, f=6)
    data = str(tmp_path / "t.csv")
    _write_csv(data, x, y)
    p = dict(BASE, is_save_binary_file=True)
    ref = lgb.train(dict(p), lgb.Dataset(data, params=dict(p)),
                    num_boost_round=4)
    assert os.path.exists(data + ".bin")
    warm = lgb.train(dict(BASE), lgb.Dataset(data, params=dict(BASE)),
                     num_boost_round=4)  # served by the mapped cache
    assert _model_str(warm) == _model_str(ref)
