"""`tree_learner=data` on the fused scan, over 4 of the 8 virtual CPU
devices (conftest.py): the block is one program over the mesh with the
rows of each shard on their device, each shard is binned on its device,
and the exchange is counted and named.

Shard sizes: 13,000 rows over 4 devices pad each shard to 4,096 rows
(models/tree_learner.py chunk_pad), so the shards hold 4,096, 4,096,
4,096 and 712 rows: not a multiple of 4 x 4,096, the last shard mostly
padding.
"""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.telemetry.trace import PROCESS_TRACER

ROWS, FEATURES, BLOCK = 13000, 10, 3
BASE = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.1,
        "max_bin": 63, "min_data_in_leaf": 20, "verbose": -1,
        "metric": "none", "partitioned_build": "true"}
DATA = {"tree_learner": "data", "num_machines": 4}
TOL = 4e-5      # of the largest leaf value (or score): float32 reordering


@pytest.fixture(scope="module")
def rows():
    rng = np.random.RandomState(0)
    x = rng.randn(ROWS, FEATURES).astype(np.float32)
    x[rng.rand(ROWS, FEATURES) < 0.05] = np.nan
    y = (x[:, 0] + 0.5 * np.nan_to_num(x[:, 1]) + 0.3 * rng.randn(ROWS)
         > 0).astype(np.float32)
    return x, y


def train(x, y, params, blocks=2):
    ds = lgb.Dataset(x, label=y, params=dict(params)).construct()
    booster = lgb.train(dict(params), ds, num_boost_round=BLOCK)
    gbdt = booster.gbdt
    for _ in range(blocks - 1):
        gbdt.train_many(BLOCK)
    return ds, booster, gbdt


@pytest.fixture(scope="module")
def pair(rows):
    x, y = rows
    return {"serial": train(x, y, BASE), "data": train(x, y, dict(BASE, **DATA))}


def test_fused_data_parallel_grows_the_serial_trees(pair, rows):
    """Split features, bins and counts are the fused serial learner's;
    leaf values and the score agree to float32 reordering: a leaf's G and
    H are float32 sums of its rows, summed a shard at a time and the four
    partial histograms then all-reduced, where the serial builder sums
    them chunk by chunk in row order. Each sum's rounding is a few ulps of
    the sum of its terms' magnitudes (not of G: where G nearly cancels,
    a leaf value near 0, the error is as large as anywhere), and a leaf
    value -G/H * rate inherits it: these rows read 1.1e-5 of the largest
    leaf value at most; the bound TOL is four times that."""
    x, _ = rows
    _, _, gs = pair["serial"]
    _, booster, gd = pair["data"]
    assert type(gd.tree_learner).__name__ == "DataParallelTreeLearner"
    assert gd.tree_learner.n_shards == 4
    assert len(gs.models) == len(gd.models) == 2 * BLOCK
    for ts, td in zip(gs.models, gd.models):
        np.testing.assert_array_equal(ts.split_feature, td.split_feature)
        np.testing.assert_array_equal(ts.threshold_in_bin, td.threshold_in_bin)
        np.testing.assert_array_equal(ts.leaf_count, td.leaf_count)
        np.testing.assert_array_equal(ts.internal_count, td.internal_count)
        scale = np.abs(ts.leaf_value).max()
        np.testing.assert_allclose(td.leaf_value, ts.leaf_value, rtol=0,
                                   atol=TOL * scale)
    ss = np.asarray(gs.train_score_updater.score)
    sd = np.asarray(gd.train_score_updater.score)
    assert sd.shape == ss.shape == (1, ROWS)
    np.testing.assert_allclose(sd, ss, rtol=0, atol=TOL * np.abs(ss).max())
    # the score is in dataset row order: the model's own prediction of
    # the rows as training read them (NaN as 0.0)
    np.testing.assert_allclose(
        sd[0], booster.predict(np.nan_to_num(x, nan=0.0), raw_score=True),
        rtol=0, atol=TOL * np.abs(ss).max())


def test_fused_score_is_padded_and_sharded_after_the_last_row(pair):
    """The block carries the score as (1, N_pad), row-sharded as the bins
    are: rows 0..N-1 in dataset order, then the padding, every row on
    the device that holds its bins."""
    _, _, gd = pair["data"]
    learner = gd.tree_learner
    padded = gd.train_score_updater.padded
    assert padded.shape == (1, learner.n_pad) == (1, 4 * 4096)
    assert padded.sharding == learner.score_layout()[1]
    np.testing.assert_array_equal(np.asarray(padded)[:, :ROWS],
                                  np.asarray(gd.train_score_updater.score))
    shards = sorted(padded.addressable_shards, key=lambda s: s.index[1].start)
    devices = list(learner.mesh.devices.flat)
    assert [s.device for s in shards] == devices
    assert [s.data.shape for s in shards] == [(1, 4096)] * 4


def test_train_many_never_enters_the_per_iteration_loop(pair, monkeypatch):
    _, _, gd = pair["data"]
    assert gd._fused_eligible()

    def loop(*a, **k):
        raise AssertionError("train_many fell back to train_one_iter")
    monkeypatch.setattr(gd, "train_one_iter", loop)
    before = gd.metrics.snapshot()["counters"]["fused_blocks"]
    gd.train_many(BLOCK)
    assert gd.metrics.snapshot()["counters"]["fused_blocks"] == before + 1


@pytest.mark.parametrize("learner, fused", [
    ("serial", True), ("data", True), ("feature", False), ("voting", False)])
def test_the_score_layout_decides_the_fused_scan(rows, learner, fused):
    """A learner trains through the fused scan where it has a score
    layout (learner.score_layout): the serial learner's (N, None), the
    data-parallel learner's padded and row-sharded one; the feature- and
    voting-parallel learners have none and keep the per-iteration loop."""
    x, y = rows
    n = 2000
    params = dict(BASE, tree_learner=learner, num_machines=4)
    ds = lgb.Dataset(x[:n], label=y[:n], params=dict(params)).construct()
    gbdt = lgb.train(dict(params), ds, num_boost_round=1).gbdt
    layout = gbdt.tree_learner.score_layout()
    assert (layout is not None) == fused == gbdt._fused_eligible()
    if learner == "serial":
        assert layout == (n, None)
    elif fused:
        assert layout[0] == gbdt.tree_learner.n_pad > n


def test_each_shard_is_binned_on_its_own_device(rows, monkeypatch):
    """With device binning on (as on a TPU), each device bins its own row
    block: the bins equal the host's binning of those rows, bin 0 past
    the last row, each block lives on its device alone, the profile's
    counts are the host's, and neither construction nor training
    downloads the matrix."""
    x, y = rows
    params = dict(BASE, **DATA)
    monkeypatch.setenv("LIGHTGBM_TPU_DEVICE_BIN", "1")
    PROCESS_TRACER.reset()
    ds = lgb.Dataset(x, label=y, params=dict(params)).construct()
    spans = [s for s in PROCESS_TRACER.recent(None)
             if s["path"] == "dataset/bin_shard"]
    monkeypatch.setenv("LIGHTGBM_TPU_DEVICE_BIN", "0")
    host = lgb.Dataset(x, label=y, params=dict(BASE)).construct()._core
    core = ds._core
    shards = core.row_shards
    assert core.binned_on_device and shards is not None
    s = shards.shard_rows
    assert s == 4096
    held = [min(max(ROWS - i * s, 0), s) for i in range(4)]
    assert held == [4096, 4096, 4096, 712]
    blocks = sorted(shards.array.addressable_shards,
                    key=lambda b: b.index[1].start)
    assert len(blocks) == 4
    for i, (b, dev) in enumerate(zip(blocks, shards.devices)):
        assert b.data.devices() == {dev}
        got = np.asarray(b.data)
        np.testing.assert_array_equal(got[:, :held[i]],
                                      host.bins[:, i * s:i * s + held[i]])
        assert not got[:, held[i]:].any()
    assert [(t["tags"]["device"], t["tags"]["rows"]) for t in spans] == [
        (d.id, r) for d, r in zip(shards.devices, held)]
    for a, b in zip(core.profile.features, host.profile.features):
        np.testing.assert_array_equal(a["counts"], b["counts"])
        assert a["missing"] == b["missing"]
    booster = lgb.train(dict(params), ds, num_boost_round=BLOCK)
    learner = booster.gbdt.tree_learner
    assert learner._bins.sharding.spec == learner._bins_sharding().spec
    assert core._bins is None           # nothing was downloaded
    serial = lgb.train(dict(BASE), lgb.Dataset(x, label=y, params=dict(BASE)),
                       num_boost_round=BLOCK)
    for ts, td in zip(serial.gbdt.models, booster.gbdt.models):
        np.testing.assert_array_equal(ts.split_feature, td.split_feature)
        np.testing.assert_array_equal(ts.threshold_in_bin, td.threshold_in_bin)


def test_the_exchange_is_counted_a_reduction_for_the_root_and_each_split(pair):
    """Counters from the learner's CommPlan: one histogram all-reduce for
    each tree's root and one for each split, each (F_pad, bins, 3)
    float32 received 2 (W - 1) / W times; gauge `mesh_devices`."""
    _, _, gd = pair["data"]
    snap = gd.metrics.snapshot()
    counters, gauges = snap["counters"], snap["gauges"]
    trees = gd.models
    calls = sum(1 + t.num_leaves - 1 for t in trees)
    assert counters["hist_reduce_calls"] == calls
    learner = gd.tree_learner
    one = 2 * 3 * learner.f_pad * learner.max_bin * 3 * 4 // 4
    assert counters["hist_reduce_bytes"] == calls * one
    assert counters["collective_bytes_hist_reduce"] == calls * one
    assert gauges["mesh_devices"] == 4
    _, _, gs = pair["serial"]
    assert "hist_reduce_calls" not in gs.metrics.snapshot()["counters"]
    assert gs.metrics.snapshot()["gauges"]["mesh_devices"] == 1


def test_a_score_that_is_not_finite_is_still_caught(rows):
    """The block's guard: on the row layout the chips check their rows
    and the host reads the score only when one is not finite; then the
    guard names the row, as on one chip."""
    from lightgbm_tpu.basic import LightGBMError
    x, y = rows
    _, _, gd = train(x, y, dict(BASE, **DATA), blocks=1)
    u = gd.train_score_updater
    u.set_carried(u.carried().at[0, 5].set(np.nan))
    with pytest.raises(LightGBMError, match="class 0, row 5 is nan"):
        gd.train_many(BLOCK)
