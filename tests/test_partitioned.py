"""Partitioned (leaf-contiguous) builder: packing, segment histograms,
stable partition, and tree/functional parity with the masked builder.

The masked builder (models/tree_learner.py) is the semantic reference;
models/partitioned.py must grow the same trees up to f32 summation-
order ulps (SURVEY.md hard-part #2 semantics: tie-breaks, gain <= 0
stop, depth guard)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import DatasetLoader
from lightgbm_tpu.metrics import create_metric
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.ops.histogram import build_histograms
from lightgbm_tpu.ops.ordered_hist import (pack_feature_words,
                                           segment_histograms,
                                           unpack_feature)
from lightgbm_tpu.ops.partition import (apply_partition,
                                        invert_permutation,
                                        split_destinations)


def test_pack_unpack_roundtrip():
    rng = np.random.RandomState(0)
    bins = rng.randint(0, 256, size=(10, 64), dtype=np.uint8)
    words = pack_feature_words(bins)
    assert words.shape == (3, 64) and words.dtype == np.int32
    for f in range(10):
        got = np.asarray(unpack_feature(jnp.asarray(words), jnp.int32(f)))
        np.testing.assert_array_equal(got, bins[f].astype(np.int32))


@pytest.mark.parametrize("r", [4096, 1024, 128])
def test_segment_histogram_matches_dense(monkeypatch, r):
    """The dense histogram of the segment's rows, over whole chunks (what
    8 x 64 one-hot elements a row give) and over a ladder with rungs
    under a chunk (the named constant patched so that they give `r`, PR
    36): segments that take the lowest rung, cross a rung's and a
    chunk's boundary, end with the array, are empty, and the root's."""
    from lightgbm_tpu.ops import ordered_hist
    monkeypatch.setattr(ordered_hist, "RUNG_ELEMENTS", r * 8 * 64)
    assert ordered_hist.min_rows(8, 16) == r
    rng = np.random.RandomState(1)
    n, f, b = 3 * 4096, 6, 16
    bins = rng.randint(0, b, size=(f, n), dtype=np.uint8)
    words = jnp.asarray(pack_feature_words(bins))
    ghc = rng.rand(3, n).astype(np.float32)
    fn = jax.jit(lambda be, cn: segment_histograms(
        words, jnp.asarray(ghc), be, cn, b, f=8))
    text = str(jax.make_jaxpr(fn)(jnp.int32(0), jnp.int32(0)))
    assert f"i32[2,{r}]" in text          # the lowest rung's window
    for begin, cnt in [(0, n), (100, 500), (4000, 4096), (8000, 192), (5, 0),
                       (100, 20), (120, 20), (1000, 1500), (4000, 300),
                       (n - 64, 64), (8191, 2)]:
        got = fn(jnp.int32(begin), jnp.int32(cnt))
        ref = build_histograms(
            jnp.asarray(bins[:, begin:begin + cnt]),
            jnp.asarray(ghc[:, begin:begin + cnt].T), b,
            row_chunk=max(cnt, 1))
        np.testing.assert_allclose(np.asarray(got)[:f], np.asarray(ref),
                                   rtol=1e-5, atol=1e-4)
        # padded feature slots (f..4W-1) must stay zero except bin 0,
        # which collects every row (padded features bin everything to 0)
        assert np.all(np.asarray(got)[f:, 1:, :] == 0)


def test_split_destinations_stable_partition():
    rng = np.random.RandomState(2)
    n = 257
    go_left = rng.rand(n) > 0.4
    begin, cnt = 31, 170
    dest, n_left = jax.jit(split_destinations)(
        jnp.asarray(go_left), jnp.int32(begin), jnp.int32(cnt))
    dest = np.asarray(dest)
    seg = np.arange(begin, begin + cnt)
    expect_order = np.concatenate(
        [seg[go_left[begin:begin + cnt]], seg[~go_left[begin:begin + cnt]]])
    # dest maps old position -> new position; invert to compare order
    src = np.asarray(invert_permutation(jnp.asarray(dest)))
    np.testing.assert_array_equal(src[begin:begin + cnt], expect_order)
    assert int(n_left) == int(go_left[begin:begin + cnt].sum())
    # identity outside the segment
    outside = np.setdiff1d(np.arange(n), seg)
    np.testing.assert_array_equal(dest[outside], outside)
    # applying the permutation keeps (words, ghc, perm) aligned
    words = jnp.asarray(rng.randint(0, 2**31, size=(2, n), dtype=np.int32))
    ghc = jnp.asarray(rng.rand(3, n).astype(np.float32))
    perm = jnp.asarray(rng.permutation(n).astype(np.int32))
    w2, g2, p2 = apply_partition(jnp.asarray(src), words, ghc, perm)
    np.testing.assert_array_equal(np.asarray(w2), np.asarray(words)[:, src])
    np.testing.assert_array_equal(np.asarray(p2), np.asarray(perm)[src])


def test_partition_segment_matches_full_array():
    """The bucketed segment partition (models/partitioned.py) must equal
    the full-array stable partition on multi-chunk arrays, including
    chunk-crossing and clipped-window segments."""
    from lightgbm_tpu.models.partitioned import _partition_segment
    from lightgbm_tpu.ops.pallas_hist import HIST_CHUNK

    rng = np.random.RandomState(5)
    n = 3 * HIST_CHUNK
    f = 5
    bins = rng.randint(0, 16, size=(f, n), dtype=np.uint8)
    words = jnp.asarray(pack_feature_words(bins))
    ghc = jnp.asarray(rng.rand(3, n).astype(np.float32))
    perm = jnp.asarray(rng.permutation(n).astype(np.int32))

    for seg_b, seg_c in [(0, n), (100, HIST_CHUNK), (4000, 300),
                         (HIST_CHUNK - 5, 10), (2 * HIST_CHUNK, HIST_CHUNK),
                         (n - 200, 200), (37, 2 * HIST_CHUNK + 9)]:
        feat, thr = 2, 7
        w2, g2, p2, nl2 = jax.jit(
            lambda b, c: _partition_segment(
                words, ghc, perm, b, c, jnp.int32(feat), jnp.int32(thr),
                jnp.asarray(False),
                lambda w_sl, f_: unpack_feature(w_sl, f_),
            ))(jnp.int32(seg_b), jnp.int32(seg_c))
        # reference: full-array stable partition
        go_left = jnp.asarray(bins[feat] <= thr)
        dest, nl_ref = split_destinations(
            go_left, jnp.int32(seg_b), jnp.int32(seg_c))
        src = invert_permutation(dest)
        w_ref, g_ref, p_ref = apply_partition(src, words, ghc, perm)
        assert int(nl2) == int(nl_ref), (seg_b, seg_c)
        np.testing.assert_array_equal(np.asarray(w2), np.asarray(w_ref))
        np.testing.assert_array_equal(np.asarray(g2), np.asarray(g_ref))
        np.testing.assert_array_equal(np.asarray(p2), np.asarray(p_ref))


def _booster(x, y, params):
    cfg = Config.from_params(params)
    ds = DatasetLoader(cfg).construct_from_matrix(x, label=y)
    objective = create_objective(cfg.objective, cfg)
    objective.init(ds.metadata, ds.num_data)
    booster = GBDT()
    booster.init(cfg, ds, objective, [])
    return booster


def _train(x, y, params, n_iter=8):
    booster = _booster(x, y, params)
    booster.train_many(n_iter)
    return booster


@pytest.mark.parametrize("use_fused", [True, False])
def test_partitioned_matches_masked_trees(use_fused):
    rng = np.random.RandomState(42)
    n, f = 3000, 9
    x = rng.rand(n, f).astype(np.float32)
    logit = 3.0 * x[:, 0] - 2.0 * x[:, 1] + x[:, 2] * x[:, 3]
    y = (logit + 0.3 * rng.randn(n) > 0.6).astype(np.float32)
    base = {"objective": "binary", "num_leaves": 15, "max_bin": 64,
            "min_data_in_leaf": 20, "metric": "binary_logloss",
            "metric_freq": 0 if use_fused else 1}
    n_iter = 6
    b_mask = _train(x, y, dict(base, partitioned_build="false"), n_iter)
    b_part = _train(x, y, dict(base, partitioned_build="true"), n_iter)
    assert b_part.tree_learner._use_partitioned
    assert not b_mask.tree_learner._use_partitioned
    assert len(b_mask.models) == len(b_part.models)
    for tm, tp in zip(b_mask.models, b_part.models):
        np.testing.assert_array_equal(tm.split_feature, tp.split_feature)
        np.testing.assert_array_equal(tm.threshold_in_bin, tp.threshold_in_bin)
        np.testing.assert_array_equal(tm.left_child, tp.left_child)
        np.testing.assert_allclose(tm.leaf_value, tp.leaf_value,
                                   rtol=1e-4, atol=1e-6)
    pm = b_mask.predict(x)
    pp = b_part.predict(x)
    np.testing.assert_allclose(pm, pp, rtol=1e-4, atol=1e-5)


def test_partitioned_multiclass_fused_matches_masked():
    """Multiclass fused training scans the class axis under the
    partitioned builder (vmap would run every lax.switch branch);
    trees must match the masked builder's vmap path."""
    rng = np.random.RandomState(42)
    n, f, k = 2400, 6, 3
    x = rng.rand(n, f).astype(np.float32)
    y = (x[:, 0] * 3 + x[:, 1] * 2).astype(np.int32) % k
    base = {"objective": "multiclass", "num_class": k, "num_leaves": 7,
            "max_bin": 32, "min_data_in_leaf": 10, "metric_freq": 0}
    n_iter = 3
    bm = _train(x, y.astype(np.float32), dict(base, partitioned_build="false"),
                n_iter)
    bp = _train(x, y.astype(np.float32), dict(base, partitioned_build="true"),
                n_iter)
    assert bp.tree_learner._use_partitioned
    assert len(bm.models) == len(bp.models) == n_iter * k
    for tm, tp in zip(bm.models, bp.models):
        np.testing.assert_array_equal(tm.split_feature, tp.split_feature)
        np.testing.assert_array_equal(tm.threshold_in_bin, tp.threshold_in_bin)
    np.testing.assert_allclose(bm.predict(x), bp.predict(x),
                               rtol=1e-4, atol=1e-5)


def test_partitioned_binary_quality():
    rng = np.random.RandomState(42)
    # n > 2 chunks so the end-to-end builder exercises the multi-chunk
    # windows of both segment_histograms and _partition_segment
    n, f = 9000, 12
    x = rng.rand(n, f).astype(np.float32)
    y = ((x[:, 0] + x[:, 1] * x[:, 2] + 0.2 * rng.randn(n)) > 1.0).astype(
        np.float32)
    booster = _train(x, y, {
        "objective": "binary", "num_leaves": 31, "metric": "auc",
        "metric_freq": 0, "partitioned_build": "true"}, n_iter=30)
    cfg = Config.from_params({"objective": "binary", "metric": "auc"})
    m = create_metric("auc", cfg)
    m.init(booster.train_data.metadata, booster.train_data.num_data)
    auc = float(m.eval(booster.get_training_score())[0])
    assert auc > 0.95, auc


def test_partitioned_categorical_matches_masked():
    """Categorical splits (one-vs-rest, col == threshold) through the
    partitioned builder's packed-word decision path must match the
    masked builder's trees."""
    rng = np.random.RandomState(21)
    n = 3000
    x = np.column_stack([
        rng.randint(0, 12, size=n).astype(np.float32),   # categorical
        rng.randint(0, 5, size=n).astype(np.float32),    # categorical
        rng.rand(n).astype(np.float32),
        rng.rand(n).astype(np.float32),
    ])
    logit = (np.isin(x[:, 0], [2, 5, 7]) * 1.5 + (x[:, 1] == 3) * 1.0
             + x[:, 2] - 0.5 * x[:, 3])
    y = (logit + 0.2 * rng.randn(n) > 0.8).astype(np.float32)

    def train(partitioned):
        cfg = Config.from_params({
            "objective": "binary", "num_leaves": 15, "max_bin": 32,
            "min_data_in_leaf": 20, "metric_freq": 0,
            "partitioned_build": partitioned})
        ds = DatasetLoader(cfg).construct_from_matrix(
            x, label=y, categorical_features=(0, 1))
        obj = create_objective(cfg.objective, cfg)
        obj.init(ds.metadata, ds.num_data)
        b = GBDT()
        b.init(cfg, ds, obj, [])
        b.train_many(6)
        return b

    bm = train("false")
    bp = train("true")
    assert bp.tree_learner._use_partitioned
    assert any((t.decision_type == 1).any() for t in bm.models), \
        "data should produce at least one categorical split"
    assert len(bm.models) == len(bp.models)
    for tm, tp in zip(bm.models, bp.models):
        np.testing.assert_array_equal(tm.split_feature, tp.split_feature)
        np.testing.assert_array_equal(tm.threshold_in_bin, tp.threshold_in_bin)
        np.testing.assert_array_equal(tm.decision_type, tp.decision_type)
    np.testing.assert_allclose(bm.predict(x), bp.predict(x),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("seed", [101, 202, 303])
def test_partitioned_matches_masked_random_configs(seed):
    """Bounded fuzz: random data + random config knobs (leaves, bins,
    min_data, bagging, feature_fraction, depth) must grow identical
    trees under both builders."""
    rng = np.random.RandomState(seed)
    n = int(rng.randint(1500, 5000))
    f = int(rng.randint(4, 14))
    x = rng.rand(n, f).astype(np.float32)
    w_true = rng.randn(f)
    y = ((x @ w_true + 0.3 * rng.randn(n)) > np.median(x @ w_true)).astype(
        np.float32)
    params = {
        "objective": "binary",
        "num_leaves": int(rng.choice([7, 15, 31])),
        "max_bin": int(rng.choice([16, 64, 255])),
        "min_data_in_leaf": int(rng.choice([5, 20, 50])),
        "max_depth": int(rng.choice([-1, 4])),
        "bagging_fraction": float(rng.choice([1.0, 0.8])),
        "bagging_freq": 1,
        "feature_fraction": float(rng.choice([1.0, 0.7])),
        "metric_freq": 0,
    }
    n_iter = 4
    bm = _train(x, y, dict(params, partitioned_build="false"), n_iter)
    bp = _train(x, y, dict(params, partitioned_build="true"), n_iter)
    assert bp.tree_learner._use_partitioned  # guard against vacuous pass
    assert len(bm.models) == len(bp.models)
    for tm, tp in zip(bm.models, bp.models):
        np.testing.assert_array_equal(tm.split_feature, tp.split_feature)
        np.testing.assert_array_equal(tm.threshold_in_bin, tp.threshold_in_bin)
    np.testing.assert_allclose(bm.predict(x), bp.predict(x),
                               rtol=1e-4, atol=1e-5)


def _efb_data(n=3000, seed=9):
    """EFB-shaped data: mutually-exclusive one-hot groups + dense cols
    (same shape as tests/test_bundling.py's fixture)."""
    rng = np.random.RandomState(seed)
    cols = []
    for _ in range(3):
        idx = rng.randint(0, 10, size=n)
        onehot = np.zeros((n, 10), np.float32)
        onehot[np.arange(n), idx] = 1.0
        cols.append(onehot)
    dense = rng.randn(n, 3).astype(np.float32)
    x = np.concatenate(cols + [dense], axis=1)
    logit = (x[:, 0] + x[:, 10] - x[:, 20] + 0.5 * dense[:, 0]
             + 0.3 * rng.randn(n))
    y = (logit > 0.4).astype(np.float32)
    return x, y


def test_partitioned_bundled_matches_masked():
    """EFB datasets run the leaf-contiguous builder too (the verdict-r3
    perf cliff): packed SLOT words + expand/decode hooks must grow the
    same trees as the bundled masked builder
    (ordered_sparse_bin.hpp:25-133 is the reference's sparse analog)."""
    x, y = _efb_data()
    base = {"objective": "binary", "num_leaves": 15, "max_bin": 64,
            "min_data_in_leaf": 15, "metric": "binary_logloss",
            "metric_freq": 0, "is_enable_sparse": "true"}
    n_iter = 6
    b_mask = _train(x, y, dict(base, partitioned_build="false"), n_iter)
    b_part = _train(x, y, dict(base, partitioned_build="true"), n_iter)
    # bundling AND the partitioned core both actually engaged
    assert b_part.tree_learner._bundle is not None
    assert b_part.tree_learner._bundle.num_slots < x.shape[1]
    assert b_part.tree_learner._use_partitioned
    assert not b_mask.tree_learner._use_partitioned
    assert len(b_mask.models) == len(b_part.models) == n_iter
    for tm, tp in zip(b_mask.models, b_part.models):
        np.testing.assert_array_equal(tm.split_feature, tp.split_feature)
        np.testing.assert_array_equal(tm.threshold_in_bin,
                                      tp.threshold_in_bin)
        np.testing.assert_array_equal(tm.left_child, tp.left_child)
        np.testing.assert_allclose(tm.leaf_value, tp.leaf_value,
                                   rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(b_mask.predict(x), b_part.predict(x),
                               rtol=1e-4, atol=1e-5)
    # the model must split on bundled (one-hot) features for this data
    assert any(int(f) < 30 for t in b_part.models
               for f in t.split_feature_real)


def test_partitioned_bundled_fused_matches_per_iter():
    """The fused multi-iteration scan embeds the bundled partitioned
    core exactly like the unbundled one."""
    x, y = _efb_data(seed=17)
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 64,
              "min_data_in_leaf": 15, "metric_freq": 0,
              "is_enable_sparse": "true", "partitioned_build": "true"}
    cfg = Config.from_params(params)

    def make():
        ds = DatasetLoader(cfg).construct_from_matrix(x, label=y)
        obj = create_objective(cfg.objective, cfg)
        obj.init(ds.metadata, ds.num_data)
        b = GBDT()
        b.init(cfg, ds, obj, [])
        return b

    b_seq = make()
    for _ in range(4):
        b_seq.train_one_iter(is_eval=False)
    b_fused = make()
    assert b_fused.warm_up_fused(4)
    b_fused.train_many(4)
    assert len(b_seq.models) == len(b_fused.models) == 4
    for ts, tf in zip(b_seq.models, b_fused.models):
        np.testing.assert_array_equal(ts.split_feature, tf.split_feature)
        np.testing.assert_array_equal(ts.threshold_in_bin,
                                      tf.threshold_in_bin)


@pytest.mark.parametrize("max_bin", [63, 255])
def test_fused_matches_per_iteration_at_300_columns(max_bin):
    """Wide dense rows (PR 33): 1,000 x 300, 75 packed words a row. The
    fused scan and the per-iteration loop grow the same trees, and the
    gauges say what the kernels would be sized to (an accumulator in
    feature blocks, a partition chunk that follows the row)."""
    rng = np.random.RandomState(33)
    n, f = 1000, 300
    x = rng.randn(n, f).astype(np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    w = rng.randn(f) / np.sqrt(np.arange(1, f + 1))
    y = (x @ w + 0.02 * rng.randn(n) > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15, "max_bin": max_bin,
              "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 5.0,
              "metric_freq": 0, "partitioned_build": "true"}
    b_seq = _booster(x, y, params)
    assert b_seq.tree_learner._use_partitioned
    assert b_seq.tree_learner._bins.shape[0] == 75
    for _ in range(3):
        b_seq.train_one_iter(is_eval=False)
    b_fused = _booster(x, y, params)
    assert b_fused._fused_eligible()
    b_fused.train_many(3)
    assert len(b_seq.models) == len(b_fused.models) == 3
    for ts, tf in zip(b_seq.models, b_fused.models):
        assert len(ts.split_feature) == 14
        np.testing.assert_array_equal(ts.split_feature, tf.split_feature)
        np.testing.assert_array_equal(ts.threshold_in_bin,
                                      tf.threshold_in_bin)
        np.testing.assert_array_equal(ts.leaf_value, tf.leaf_value)
    gauges = b_fused.metrics.snapshot()["gauges"]
    assert gauges["seg_hist_feature_blocks"] == (3 if max_bin == 63 else 10)
    assert gauges["seg_hist_block_features"] == (128 if max_bin == 63 else 32)
    assert gauges["partition_rows_words"] == 80
    assert gauges["partition_rows_chunk_lanes"] == 2048
    assert gauges["partition_rows_tile_form"] == "one_perm+roll"


def test_builder_engines_grow_the_same_tree(monkeypatch):
    """The TPU engine of the partition step (ops/partition.py
    partition_rows, here through the Pallas interpreter, with the
    builder's state in the kernel's packed arrays) and the off-TPU
    engine move the same rows to the same places, so the builder grows
    the same tree to the bit, row->leaf map included."""
    from lightgbm_tpu.models import partitioned
    from lightgbm_tpu.ops.pallas_hist import HIST_CHUNK
    from lightgbm_tpu.ops.split import SplitParams

    rng = np.random.RandomState(11)
    n, f, b, leaves = 2 * HIST_CHUNK, 6, 16, 6
    bins = rng.randint(0, b, size=(f, n), dtype=np.uint8)
    words = jnp.asarray(pack_feature_words(bins))
    grad = jnp.asarray(rng.randn(n).astype(np.float32))
    hess = jnp.ones(n, jnp.float32)
    inbag = jnp.asarray((np.arange(n) < n - 100).astype(np.float32))
    is_cat = jnp.asarray([False, False, True, False, False, False,
                          False, False])

    def grow():
        return jax.jit(lambda: partitioned.build_tree_partitioned(
            words, grad, hess, inbag, jnp.ones(8, bool),
            jnp.full(8, b, jnp.int32), is_cat, num_leaves=leaves,
            max_bin=b, params=SplitParams(1.0, 1e-3, 0.0, 0.0, 0.0),
            max_depth=-1, f_real=f))()

    assert partitioned.partition_engine() == "xla"
    want = grow()
    monkeypatch.setattr(partitioned, "partition_engine", lambda: "pallas")
    kernel = partitioned.partition_rows
    monkeypatch.setattr(
        partitioned, "partition_rows",
        lambda *a, **k: kernel(*a, **dict(k, interpret=True)))
    got = grow()
    assert int(want["n_splits"]) == leaves - 1
    assert set(got) == set(want)
    for key in want:
        np.testing.assert_array_equal(np.asarray(got[key]),
                                      np.asarray(want[key]), err_msg=key)


# (num_leaves, extra params, classes): both sides of the table length at
# which `leaf_lookup` cuts the table into pieces, that length itself,
# and the piece boundaries above it
SCORE_UPDATE_CASES = {
    "l2": (2, {}, 1),
    "l63": (63, {}, 1),
    "l64": (64, {}, 1),
    "l128": (128, {}, 1),
    "l129": (129, {}, 1),
    "l255": (255, {}, 1),
    "bagging": (129, {"bagging_fraction": 0.5, "bagging_freq": 1}, 1),
    "stops_early": (255, {"min_data_in_leaf": 400}, 1),
    "class_step": (129, {}, 3),
    # the masked builder: one class, then vmapped over three with a
    # lookup a class
    "masked": (15, {"partitioned_build": "false",
                    "hist_compaction": "false"}, 1),
    "masked_classes": (129, {"partitioned_build": "false",
                             "hist_compaction": "false"}, 3),
}


@pytest.mark.parametrize("case", list(SCORE_UPDATE_CASES))
def test_fused_score_update_bit_exact(case):
    """What the fused step adds to the score is leaf_value[row_leaf] *
    shrink to the bit (numpy float32: one multiply, one add a row), for
    every row, in-bag or not, with pad rows behind them, whichever form
    the lookup takes for the table's length; and `row_leaf` as the
    per-iteration loop and linear leaves receive it is each row's leaf by
    a host traversal of the tree."""
    from lightgbm_tpu.models.score_updater import LOOKUP_PIECE, lookup_form
    leaves, extra, k = SCORE_UPDATE_CASES[case]
    assert LOOKUP_PIECE == 64
    assert lookup_form(leaves) == ("take" if leaves <= 64 else "split64")
    rng = np.random.RandomState(5)
    n, f = 5000, 4
    x = rng.rand(n, f).astype(np.float32)
    if k == 1:
        y = (x[:, 0] + 0.5 * x[:, 1] * x[:, 2]
             + 0.2 * rng.randn(n) > 0.7).astype(np.float32)
        params = {"objective": "binary"}
    else:
        y = ((x[:, 0] * 3 + x[:, 1] * 2).astype(np.int32) % k).astype(
            np.float32)
        params = {"objective": "multiclass", "num_class": k}
    params.update({"num_leaves": leaves, "max_bin": 32, "metric_freq": 0,
                   "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 1e-3,
                   "learning_rate": 0.1, "partitioned_build": "true"})
    params.update(extra)

    fused = _booster(x, y, params)
    learner = fused.tree_learner
    assert learner._use_partitioned == (not case.startswith("masked"))
    assert not learner._use_compact and fused._fused_eligible()
    assert (learner.n_pad > n) == learner._use_partitioned
    score0 = np.asarray(fused.train_score_updater.score)
    fused.train_many(1)
    got = np.asarray(fused.train_score_updater.score)
    assert got.shape == (k, n)

    # the same trees through the builder the per-iteration loop calls
    loop = _booster(x, y, params)
    grad, hess = loop.objective.get_gradients(loop._score_for_boosting())
    grad = np.asarray(grad).reshape(k, n)
    hess = np.asarray(hess).reshape(k, n)
    bag = loop._bagging_device_fn()
    inbag = None if bag is None else np.asarray(bag(jnp.int32(0)))[:n]
    assert (inbag is None) == (case != "bagging")
    shrink = np.float32(loop.shrinkage_rate)
    bins = loop.train_data.traversal_bins()
    for c in range(k):
        out = loop.tree_learner.train_device(grad[c], hess[c], inbag)
        n_splits = int(out["n_splits"])
        assert (n_splits < leaves - 1) == (case == "stops_early"), n_splits
        row_leaf = np.asarray(loop.tree_learner.local_row_leaf(out, n))
        np.testing.assert_array_equal(
            row_leaf, fused.models[c].get_leaf_by_bins(bins))
        pad_leaf = np.asarray(out["row_leaf"])[n:]
        assert pad_leaf.size == learner.n_pad - n
        assert np.all((pad_leaf >= 0) & (pad_leaf <= n_splits))
        leaf_value = np.asarray(out["leaf_value"])
        assert leaf_value.dtype == np.float32 and leaf_value.shape == (leaves,)
        want = score0[c] + (leaf_value * shrink)[row_leaf]
        assert want.dtype == np.float32
        np.testing.assert_array_equal(got[c].view(np.int32),
                                      want.view(np.int32))
        if inbag is not None:      # out-of-bag rows are updated too
            oob = inbag == 0
            assert 0.4 * n < oob.sum() < 0.6 * n
            assert np.mean(got[c][oob] != score0[c][oob]) > 0.9


@pytest.mark.parametrize("classes", [0, 3])
def test_unpermute_moves_each_value_to_its_row(classes):
    """The end-of-tree un-permute gives out[perm[i]] = values[i] by one
    key-value sort and no scatter, alone and batched (a class or a shard
    at a time, each with a permutation of its own)."""
    from lightgbm_tpu.ops.partition import unpermute
    rng = np.random.RandomState(17)
    n = 3 * 4096
    perm = np.stack([rng.permutation(n) for _ in range(max(classes, 1))]
                    ).astype(np.int32)
    values = rng.randint(0, 255, size=perm.shape).astype(np.int32)
    want = np.zeros_like(values)
    for p, v, w in zip(perm, values, want):
        w[p] = v
    fn = jax.vmap(unpermute) if classes else unpermute
    if not classes:
        perm, values, want = perm[0], values[0], want[0]
    prims = {e.primitive.name for e in jax.make_jaxpr(unpermute)(
        perm.reshape(-1, n)[0], values.reshape(-1, n)[0]).eqns}
    assert "sort" in prims and not prims & {"scatter", "scatter-add"}
    got = jax.jit(fn)(jnp.asarray(perm), jnp.asarray(values))
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("classes", [0, 3])
@pytest.mark.parametrize("best_leaf,right_id", [(0, 6), (3, 4), (5, 1)])
@pytest.mark.parametrize("left_small", [True, False])
def test_split_hist_cache_is_the_five_lines(left_small, best_leaf, right_id,
                                            classes):
    """The builders' one cache update against the lines it replaced,
    written out: the same float32 subtraction and the same rows written
    (bit-equal cache and children), the smaller child on either side,
    the first leaf as parent and the last as right child, as the
    builders call it (a `cond` inside a `fori_loop` under `jit`) and
    batched over a class axis, where the row index is batched too."""
    from lightgbm_tpu.models.tree_learner import split_hist_cache
    l, f, b = 7, 5, 9
    rng = np.random.RandomState(best_leaf * 10 + right_id)
    k = max(classes, 1)
    cache = rng.randn(k, l, f, b, 3).astype(np.float32) * 1e3
    small = rng.randn(k, f, b, 3).astype(np.float32)
    leaves = (np.int32(best_leaf) + np.arange(k, dtype=np.int32)) % l
    rights = (np.int32(right_id) + np.arange(k, dtype=np.int32)) % l

    def written_out(cache, leaf, right, small):
        hist_large = cache[leaf] - small
        hist_left = jnp.where(left_small, small, hist_large)
        hist_right = jnp.where(left_small, hist_large, small)
        cache = cache.at[leaf].set(hist_left).at[right].set(hist_right)
        return cache, hist_left, hist_right

    def in_the_loop(update):
        def run(cache, leaf, right, small):
            zeros = jnp.zeros_like(small)

            def body(i, carry):
                return jax.lax.cond(
                    i == 1,
                    lambda c: update(c[0], leaf, right, small),
                    lambda c: c, carry)
            return jax.lax.fori_loop(0, 3, body, (cache, zeros, zeros))
        return jax.jit(jax.vmap(run) if classes else run)

    args = (cache, leaves, rights, small)
    if not classes:
        args = tuple(a[0] for a in args)
    want = in_the_loop(written_out)(*args)
    got = in_the_loop(
        lambda c, leaf, right, s: split_hist_cache(
            c, leaf, right, s, jnp.asarray(left_small)))(*args)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g).view(np.int32),
                                      np.asarray(w).view(np.int32))
    # and against numpy, so that the two jitted forms cannot be wrong
    # together: class 0's children and the rows no split touches
    new_cache, hist_left, hist_right = (
        np.asarray(a)[0] if classes else np.asarray(a) for a in got)
    large = cache[0, best_leaf] - small[0]
    np.testing.assert_array_equal(hist_left, small[0] if left_small else large)
    np.testing.assert_array_equal(hist_right, large if left_small else small[0])
    np.testing.assert_array_equal(new_cache[best_leaf], hist_left)
    np.testing.assert_array_equal(new_cache[right_id], hist_right)
    rest = [i for i in range(l) if i not in (best_leaf, right_id)]
    np.testing.assert_array_equal(new_cache[rest], cache[0, rest])


# ------------- the histogram's own ladder, with rungs under a chunk (PR 36)
# (f = 4 x packed word rows, num_bins_total, chunks of the padded rows)
# of the benchmark's five cells
CELL_SHAPES = {
    "higgs10m-b255-l63": (28, 255, 2816),
    "higgs10m-b63-l255": (28, 63, 2816),
    "mslr-web30k-b63-l255": (136, 63, 576),
    "mslr-web30k-mc5-b63-l255": (136, 63, 576),
    "epsilon400k-b63-l255": (2000, 63, 104),
}


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_hist_ladder_follows_the_row(cell):
    """The lowest rung follows the one-hot elements a data row costs:
    at the four narrow shapes a rung is a whole chunk and the ladder is
    `bucket_sizes` (the program the parent traced); at 2,000 x 63 it is
    512 rows, three rungs under a chunk and then the chunk buckets."""
    from lightgbm_tpu.ops.ordered_hist import (bucket_sizes, hist_rungs,
                                               min_rows)
    from lightgbm_tpu.ops.pallas_hist import HIST_CHUNK
    f, b, n_chunks = CELL_SHAPES[cell]
    r = min_rows(f, b)
    rungs = hist_rungs(n_chunks, r)
    if cell.startswith("epsilon"):
        assert r == 512
        assert rungs == [1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 832]
        assert [u * r // HIST_CHUNK for u in rungs[3:]] == bucket_sizes(104)
    else:
        assert r == HIST_CHUNK
        assert rungs == bucket_sizes(n_chunks)
    assert rungs[-1] * r == n_chunks * HIST_CHUNK  # the root's rung


def test_hist_ladder_bounds(monkeypatch):
    """A rung never falls under one 128-lane tile nor passes a chunk,
    whatever a row costs, and is a power of two."""
    from lightgbm_tpu.ops import ordered_hist
    from lightgbm_tpu.ops.pallas_hist import HIST_CHUNK
    assert ordered_hist.min_rows(100000, 255) == 128
    assert ordered_hist.min_rows(1, 2) == HIST_CHUNK
    assert ordered_hist.hist_rungs(1, 128) == [1, 2, 4, 8, 16, 32]
    seen = {ordered_hist.min_rows(f, 63) for f in range(4, 40000, 4)}
    assert seen == {128, 256, 512, 1024, 2048, 4096}
    monkeypatch.setattr(ordered_hist, "RUNG_ELEMENTS", 1)
    assert ordered_hist.min_rows(4, 16) == 128


@pytest.mark.parametrize("r", [128, 512, 4096])
@pytest.mark.parametrize("begin,cnt", [
    (0, 0), (0, 1), (5, 0), (4095, 1), (4096, 1), (100, 300), (500, 24),
    (510, 4), (4000, 300), (4090, 4096), (0, 4096), (3, 4096),
    (0, 5 * 4096), (5 * 4096 - 1, 1), (5 * 4096 - 700, 700),
    (4 * 4096 + 1, 4095), (1, 5 * 4096 - 1), (2048, 8192), (2047, 8193)])
def test_hist_window_is_the_smallest_aligned_cover(r, begin, cnt):
    """For a segment anywhere in five chunks (empty, one row, across a
    rung's and a chunk's boundary, at the array's end): the window
    starts on a multiple of `r`, lies in bounds, covers the segment, and
    no smaller rung could from that alignment."""
    from lightgbm_tpu.ops.ordered_hist import (hist_rungs, rung_index,
                                               rung_start)
    n = 5 * 4096
    rungs = hist_rungs(5, r)
    idx, first = rung_index(jnp.int32(begin), jnp.int32(cnt), rungs, r)
    idx, first = int(idx), int(first)
    rows = rungs[idx] * r
    start = int(rung_start(first, rungs[idx], n // r, r))
    assert start % r == 0 and 0 <= start and start + rows <= n
    assert start <= begin and begin + cnt <= start + rows
    needed = (begin + max(cnt, 1) - 1) // r - begin // r + 1
    assert rungs[idx] >= needed
    assert idx == 0 or rungs[idx - 1] < needed


def test_small_rungs_grow_the_same_tree(monkeypatch):
    """One tree over a ladder whose lowest rung is 128 rows (the named
    constant patched) against the same tree over whole chunks: the same
    splits, leaf values to float32 rounding (a leaf's rows meet block
    boundaries elsewhere, so the order of float32 additions differs);
    and the gauges and the hit share the two leave in the registry."""
    from lightgbm_tpu.ops import ordered_hist
    from lightgbm_tpu.ops.pallas_hist import HIST_CHUNK
    rng = np.random.RandomState(360)
    n, f = 3 * HIST_CHUNK - 100, 8
    x = rng.randn(n, f).astype(np.float32)
    y = (x[:, 0] + x[:, 1] * x[:, 2] + 0.3 * rng.randn(n) > 0).astype(
        np.float32)
    params = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
              "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 1.0,
              "metric_freq": 0, "partitioned_build": "true"}
    whole = _booster(x, y, params)
    whole.train_many(2)
    monkeypatch.setattr(ordered_hist, "RUNG_ELEMENTS", 1)
    small = _booster(x, y, params)
    small.train_many(2)
    assert len(whole.models) == len(small.models) == 2
    for tw, ts in zip(whole.models, small.models):
        assert len(tw.split_feature) == 30
        np.testing.assert_array_equal(tw.split_feature, ts.split_feature)
        np.testing.assert_array_equal(tw.threshold_in_bin,
                                      ts.threshold_in_bin)
        np.testing.assert_array_equal(tw.leaf_count, ts.leaf_count)
        np.testing.assert_allclose(tw.leaf_value, ts.leaf_value, rtol=2e-5)
    snap_w, snap_s = whole.metrics.snapshot(), small.metrics.snapshot()
    assert snap_w["gauges"]["seg_hist_min_rows"] == HIST_CHUNK
    assert snap_w["gauges"]["seg_hist_rungs"] == 3       # 1, 2, 3 chunks
    assert snap_s["gauges"]["seg_hist_min_rows"] == 128
    assert snap_s["gauges"]["seg_hist_rungs"] == 8       # 128 .. 2,048 rows
    assert snap_w["counters"]["seg_hist_calls"] == 60
    assert snap_w["counters"]["seg_hist_subchunk_calls"] == 0
    assert snap_s["counters"]["seg_hist_calls"] == 60
    # every split whose smaller child is under a chunk, recounted
    want = 0
    for t in small.models:
        for left, right in zip(t.left_child, t.right_child):
            rows = [t.internal_count[c] if c >= 0 else t.leaf_count[~c]
                    for c in (left, right)]
            want += min(rows) < HIST_CHUNK
    assert snap_s["counters"]["seg_hist_subchunk_calls"] == want > 30
