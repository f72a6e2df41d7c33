"""Packed-bin + frontier-batched histogram engine (ISSUE 6).

Contracts pinned here:
- bins_dtype ladder: uint8 <= 256 bins, int16 <= 32768, int32 beyond;
  every loader path persists/streams at that width.
- Packed-vs-unpacked parity: histograms over uint8/int16 bins are
  BITWISE what an int32-widened matrix produces (the kernels widen
  per-chunk in registers, never in HBM), for both XLA chunk
  formulations (segment/einsum) and end-to-end across all four learners.
- Frontier batching: frontier_histograms over a leaf vector matches
  the single-leaf masked kernel per leaf (bitwise in the scatter
  formulation — same chunk decomposition and accumulation order), and
  the cache-less builder that uses it grows the same trees as the
  cached builder.
- Binary cache v2: packed dtypes round-trip; legacy uint16 narrows to
  the natural width on load; stale float matrices are rejected.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import (BinaryDatasetError, CoreDataset,
                                     DatasetLoader, bins_dtype)
from lightgbm_tpu.ops import histogram as H
from lightgbm_tpu.ops.pallas_hist import HIST_CHUNK, masked_histograms_xla


def _workload(n, f=5, b=32, leaves=6, seed=0):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, b, size=(f, n)).astype(np.uint8)
    ghc_t = rng.randn(3, n).astype(np.float32)
    row_leaf = rng.randint(0, leaves, size=n).astype(np.int32)
    return bins, ghc_t, row_leaf


def test_bins_dtype_ladder():
    assert bins_dtype(2) == np.uint8
    assert bins_dtype(256) == np.uint8
    assert bins_dtype(257) == np.int16
    assert bins_dtype(32768) == np.int16
    assert bins_dtype(32769) == np.int32


def test_dataset_stores_natural_width():
    rng = np.random.RandomState(0)
    x = rng.rand(2000, 3).astype(np.float32)
    y = (x[:, 0] > 0.5).astype(np.float32)
    cfg8 = Config(objective="binary", max_bin=255, verbose=-1)
    ds8 = DatasetLoader(cfg8).construct_from_matrix(x, label=y)
    assert ds8.bins.dtype == np.uint8
    cfg16 = Config(objective="binary", max_bin=400, verbose=-1)
    ds16 = DatasetLoader(cfg16).construct_from_matrix(x, label=y)
    assert ds16.max_num_bin > 256
    assert ds16.bins.dtype == np.int16


@pytest.mark.parametrize("mode", ["segment", "einsum"])
def test_packed_vs_widened_histograms(mode):
    """uint8/int16 bins produce BITWISE the histograms of an
    int32-widened matrix, in every chunk formulation."""
    n, b = 2 * HIST_CHUNK, 32
    bins, ghc_t, _ = _workload(n, b=b)
    fn = jax.jit(lambda bb: H.build_histograms(bb, ghc_t.T, b, 4096,
                                               mode=mode))
    ref = np.asarray(fn(jnp.asarray(bins.astype(np.int32))))
    for dt in (np.uint8, np.int16):
        got = np.asarray(fn(jnp.asarray(bins.astype(dt))))
        np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("mode", ["segment", "einsum"])
def test_frontier_matches_masked_per_leaf(mode):
    """frontier_histograms over a leaf vector == the single-leaf
    masked kernel per leaf. The scatter formulation is held to the bit:
    a leaf's segment_sum adds the same rows in the same order whether
    or not the leaf axis is batched. The one-hot contraction is held to
    float32 rounding of the collapsed pair, which is what every
    consumer reads: once vmap batches the leaf axis into the dot, XLA's
    CPU backend tiles the 4,096-row reduction otherwise and the partial
    sums round differently (2.9e-6 absolute seen on sums of ~100 terms
    of N(0, 1))."""
    n, b, leaves = 2 * HIST_CHUNK, 32, 6
    bins, ghc_t, row_leaf = _workload(n, b=b, leaves=leaves, seed=3)
    leaf_ids = jnp.asarray([0, 4, 2], jnp.int32)
    fh, fl = jax.jit(lambda: H.frontier_histograms(
        jnp.asarray(bins), jnp.asarray(ghc_t), jnp.asarray(row_leaf),
        leaf_ids, b, 4096, mode=mode))()
    for i, lid in enumerate([0, 4, 2]):
        mh, ml = jax.jit(lambda lid=lid: masked_histograms_xla(
            jnp.asarray(bins), jnp.asarray(ghc_t), jnp.asarray(row_leaf),
            jnp.int32(lid), b, 4096, mode=mode))()
        if mode == "segment":
            np.testing.assert_array_equal(np.asarray(fh[i]), np.asarray(mh))
            np.testing.assert_array_equal(np.asarray(fl[i]), np.asarray(ml))
        else:
            np.testing.assert_allclose(np.asarray(fh[i] + fl[i]),
                                       np.asarray(mh + ml),
                                       rtol=1e-5, atol=1e-5)


def test_frontier_absent_leaf_is_zero():
    n, b = HIST_CHUNK, 16
    bins, ghc_t, row_leaf = _workload(n, b=b, leaves=3)
    fh, fl = H.frontier_histograms(
        jnp.asarray(bins), jnp.asarray(ghc_t), jnp.asarray(row_leaf),
        jnp.asarray([1, 77], jnp.int32), b, 4096)
    assert np.asarray(fh[1]).max() == 0.0 and np.asarray(fh[1]).min() == 0.0
    assert np.asarray(fh[0]).any()


def test_compacted_matches_masked():
    """The gather-compacted pass stays <= 1e-6 from the full masked
    scan on every leaf (the ISSUE-1 parity contract)."""
    n, b, leaves = 3 * HIST_CHUNK, 32, 5
    bins, ghc_t, row_leaf = _workload(n, b=b, leaves=leaves, seed=7)
    bd, gd, rd = (jnp.asarray(bins), jnp.asarray(ghc_t),
                  jnp.asarray(row_leaf))
    for leaf in range(leaves):
        hc, rc = jax.jit(lambda leaf=leaf: H.compacted_histograms(
            bd, gd, rd, jnp.int32(leaf), b))()
        hm, rm = jax.jit(lambda leaf=leaf: masked_histograms_xla(
            bd, gd, rd, jnp.int32(leaf), b))()
        got, ref = np.asarray(hc + rc), np.asarray(hm + rm)
        scale = max(1.0, float(np.abs(ref).max()))
        assert np.abs(got - ref).max() / scale <= 1e-6


def test_cacheless_frontier_builder_matches_cached():
    """build_tree_device with cache_hists=False (the memory-bounded
    mode, now frontier-batched: both children in one pass) grows the
    same trees as the cached subtraction path."""
    from lightgbm_tpu.models.tree_learner import build_tree_device
    from lightgbm_tpu.ops.split import SplitParams

    rng = np.random.RandomState(11)
    n, f, b = 1500, 4, 24
    bins = jnp.asarray(rng.randint(0, b, size=(f, n)).astype(np.uint8))
    grad = jnp.asarray(rng.randn(n).astype(np.float32))
    hess = jnp.asarray(np.abs(rng.randn(n)).astype(np.float32) + 0.1)
    inbag = jnp.ones(n, jnp.float32)
    fmask = jnp.ones(f, bool)
    nbpf = jnp.full(f, b, jnp.int32)
    iscat = jnp.zeros(f, bool)
    params = SplitParams(min_data_in_leaf=20.0,
                         min_sum_hessian_in_leaf=1e-3, lambda_l1=0.0,
                         lambda_l2=0.0, min_gain_to_split=0.0)

    def build(cache):
        return jax.jit(lambda: build_tree_device(
            bins, grad, hess, inbag, fmask, nbpf, iscat, num_leaves=15,
            max_bin=b, params=params, max_depth=-1, row_chunk=4096,
            cache_hists=cache))()

    a, c = build(True), build(False)
    assert int(a["n_splits"]) == int(c["n_splits"]) > 0
    np.testing.assert_array_equal(np.asarray(a["split_feature"]),
                                  np.asarray(c["split_feature"]))
    np.testing.assert_array_equal(np.asarray(a["split_threshold_bin"]),
                                  np.asarray(c["split_threshold_bin"]))
    np.testing.assert_allclose(np.asarray(a["leaf_value"]),
                               np.asarray(c["leaf_value"]),
                               rtol=1e-5, atol=1e-7)


def _train_booster(ds, learner, extra=None):
    from lightgbm_tpu.models.gbdt import create_boosting
    from lightgbm_tpu.objectives import create_objective
    params = dict(objective="binary", num_leaves=15, learning_rate=0.1,
                  min_data_in_leaf=10, tree_learner=learner, verbose=-1,
                  num_machines=2 if learner != "serial" else 1)
    params.update(extra or {})
    cfg = Config(**params)
    cfg.check_param_conflict()
    obj = create_objective(cfg.objective, cfg)
    obj.init(ds.metadata, ds.num_data)
    g = create_boosting(cfg.boosting_type)
    g.init(cfg, ds, obj, [])
    for _ in range(6):
        if g.train_one_iter(is_eval=False):
            break
    return g


def _widened_copy(ds):
    out = CoreDataset()
    out.__dict__.update(ds.__dict__)
    out._device_bins = None
    out.bins = ds.bins.astype(np.int32)
    return out


@pytest.mark.parametrize("learner", ["serial", "data", "feature", "voting"])
def test_learner_packed_parity(learner):
    """Widening the stored bin matrix to int32 changes NOTHING: the
    kernels stream packed bins and widen per-chunk in registers, so
    trees are identical across serial + all three parallel learners."""
    from sklearn import datasets
    X, y = datasets.load_breast_cancer(return_X_y=True)
    cfg = Config(objective="binary", verbose=-1)
    ds = DatasetLoader(cfg).construct_from_matrix(X, label=y)
    assert ds.bins.dtype == np.uint8
    ga = _train_booster(ds, learner)
    gb = _train_booster(_widened_copy(ds), learner)
    assert len(ga.models) == len(gb.models) > 0
    for ta, tb in zip(ga.models, gb.models):
        assert ta.num_leaves == tb.num_leaves
        np.testing.assert_array_equal(ta.split_feature_real,
                                      tb.split_feature_real)
        np.testing.assert_array_equal(ta.threshold_in_bin,
                                      tb.threshold_in_bin)
        np.testing.assert_array_equal(ta.leaf_value, tb.leaf_value)


def test_int16_training_end_to_end():
    rng = np.random.RandomState(5)
    x = rng.rand(3000, 4).astype(np.float32)
    y = (x[:, 0] + 0.2 * rng.randn(3000) > 0.5).astype(np.float32)
    cfg = Config(objective="binary", max_bin=400, num_leaves=7,
                 min_data_in_leaf=20, verbose=-1)
    ds = DatasetLoader(cfg).construct_from_matrix(x, label=y)
    assert ds.bins.dtype == np.int16
    g = _train_booster(ds, "serial", extra=dict(max_bin=400, num_leaves=7,
                                               min_data_in_leaf=20))
    gw = _train_booster(_widened_copy(ds), "serial",
                        extra=dict(max_bin=400, num_leaves=7,
                                   min_data_in_leaf=20))
    for ta, tb in zip(g.models, gw.models):
        np.testing.assert_array_equal(ta.threshold_in_bin,
                                      tb.threshold_in_bin)
        np.testing.assert_array_equal(ta.leaf_value, tb.leaf_value)
    pred = g.predict(x[:50])
    assert np.isfinite(pred).all()


# --------------------------------------------------------- binary cache v2
def _tiny_dataset(max_bin=255):
    rng = np.random.RandomState(2)
    x = rng.rand(400, 3).astype(np.float32)
    y = (x[:, 1] > 0.5).astype(np.float32)
    cfg = Config(objective="binary", max_bin=max_bin, verbose=-1)
    return DatasetLoader(cfg).construct_from_matrix(x, label=y)


def test_binary_cache_roundtrip_packed(tmp_path):
    ds = _tiny_dataset()
    path = str(tmp_path / "ds.bin")
    ds.save_binary(path)
    z = np.load(path, allow_pickle=True)
    assert int(z["format_version"]) == 2
    assert z["bins"].dtype == np.uint8
    back = CoreDataset.load_binary(path)
    np.testing.assert_array_equal(back.bins, ds.bins)
    assert back.bins.dtype == np.uint8


def _rewrite_npz(path, **updates):
    z = np.load(path, allow_pickle=True)
    arrays = {k: z[k] for k in z.files}
    arrays.update(updates)
    with open(path, "wb") as f:  # a bare path would grow an .npz suffix
        np.savez_compressed(f, **arrays)


def test_binary_cache_legacy_uint16_narrows(tmp_path):
    ds = _tiny_dataset(max_bin=400)
    assert ds.bins.dtype == np.int16
    path = str(tmp_path / "ds.bin")
    ds.save_binary(path)
    z = np.load(path, allow_pickle=True)
    _rewrite_npz(path, bins=z["bins"].astype(np.uint16))  # v1-era width
    back = CoreDataset.load_binary(path)
    assert back.bins.dtype == np.int16
    np.testing.assert_array_equal(back.bins, ds.bins)


def test_binary_cache_rejects_stale_float(tmp_path):
    ds = _tiny_dataset()
    path = str(tmp_path / "ds.bin")
    ds.save_binary(path)
    z = np.load(path, allow_pickle=True)
    _rewrite_npz(path, bins=z["bins"].astype(np.float32))
    with pytest.raises(BinaryDatasetError) as ei:
        CoreDataset.load_binary(path)
    assert ei.value.claimed  # falls past as a rotten cache, not a crash
    assert "float32" in str(ei.value)


def test_binary_cache_rejects_future_version(tmp_path):
    ds = _tiny_dataset()
    path = str(tmp_path / "ds.bin")
    ds.save_binary(path)
    _rewrite_npz(path, format_version=np.asarray(99))
    with pytest.raises(BinaryDatasetError):
        CoreDataset.load_binary(path)


_LEARNERS = {
    "serial": {},
    "data": {"tree_learner": "data", "num_machines": 2},
    "ooc": {"out_of_core": True, "block_rows": 512},
}


@functools.lru_cache(maxsize=None)
def _grow(kinds, rounds=4):
    """Model strings of one Booster per kind (a tuple), all alive at
    once and advanced one iteration each in turn."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(13)
    x = rng.randn(1200, 5)
    y = (x[:, 0] + 0.5 * x[:, 1] * x[:, 2] > 0).astype(np.float64)
    boosters = []
    for kind in kinds:
        params = dict(objective="binary", num_leaves=7, min_data_in_leaf=5,
                      device_row_chunk=256, verbose=-1, **_LEARNERS[kind])
        boosters.append(lgb.Booster(
            params=params, train_set=lgb.Dataset(x, y, params=params)))
    for _ in range(rounds):
        for bst in boosters:
            bst.update()
    return tuple(bst.gbdt.save_model_to_string(-1) for bst in boosters)


@pytest.mark.parametrize("pair", [("serial", "data"), ("data", "ooc"),
                                  ("ooc", "serial")])
def test_boosters_in_one_process_do_not_interact(pair):
    """Two Boosters of different learners, built one after the other
    and trained in turn, grow the trees each grows alone: which
    histogram formulation a learner traces is a function of the
    platform, not of what another learner set before it."""
    assert _grow(pair) == tuple(_grow((kind,))[0] for kind in pair)
