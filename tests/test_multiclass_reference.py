"""K trees an iteration against a reference that is not the program.

The fused step of a K-class objective under the leaf-contiguous builder
*scans* the class axis (models/gbdt.py `class_step`; the form the chip
takes). Here that scan runs on the CPU (`partitioned_build=true`) over
20,000 x 136 rows of the multiclass cell's generator, three iterations
through `lgb.train`, and its 3 x K trees and (K, n) score are judged by
benchmarks/references/softmax_classes.py (numpy float64, own bins, one
gradient pass an iteration) under the cell's own limits.

The tree settings are the cell's but for `num_leaves`: 20,000 rows hold
200 rows of the rarest grade, and past some forty splits its leaves are
pure, where the true gain of every candidate is 0 and float32 and
float64 rounding each pick their own noise (a following reference can
judge no such split; the cell's 2.27M rows never get there).
"""

import os
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
sys.path.insert(0, BENCH)
import run  # noqa: E402
from datagen import load_module, make_data, train_params  # noqa: E402

CELL = "mslr-web30k-mc5-b63-l255.train"
ROWS, BLOCK, LEAVES = 20000, 3, 15


def cell_inputs(seed, k):
    """(x, y, params) of the cell at ROWS rows; for k < 5 the grades
    from k - 1 up are one class."""
    cell = run.load_cell(CELL)
    params = dict(train_params(cell["config"], cell["traffic"]),
                  num_class=k, num_leaves=LEAVES, partitioned_build="true")
    x, y, fields = make_data(dict(cell["config"]["data"], rows=ROWS), seed)
    assert fields == {}
    return x, np.minimum(y, k - 1).astype(np.float32), params, cell["limits"]


def train_block(x, y, params):
    ds = lgb.Dataset(x, label=y, params=dict(params), free_raw_data=False)
    booster = lgb.train(dict(params), ds, num_boost_round=BLOCK)
    gbdt = booster.gbdt
    assert gbdt.tree_learner._use_partitioned
    return gbdt


@pytest.mark.parametrize("k", [3, 5])
@pytest.mark.parametrize("seed", [1, 2147483999, 2147516083])
def test_class_scan_meets_the_softmax_reference(seed, k):
    x, y, params, limits = cell_inputs(seed, k)
    gbdt = train_block(x, y, params)
    snap = gbdt.metrics.snapshot()
    assert snap["gauges"]["class_axis_form"] == "scan"
    assert snap["gauges"]["trees_per_iteration"] == k
    assert snap["counters"]["class_trees"] == BLOCK * k
    assert snap["counters"]["fused_blocks"] == 1
    assert len(gbdt.models) == BLOCK * k
    trees = [run.tree_arrays(m) for m in gbdt.models]
    score = run.train_score(gbdt, ROWS)
    assert score.shape == (k, ROWS)
    numbers = load_module("references", "softmax_classes").compare(
        x, y, {}, params, trees, score, threads=4)
    numbers.update(window_compiles=0.0, failed=float(run.failed_iterations(
        trees, BLOCK, k)))
    ok, rows = run.check(numbers, limits)
    assert ok, {name: r for name, r in rows.items()
                if not r["value"] <= r["limit"]}


def test_fused_scan_and_the_loop_grow_the_same_trees():
    """K = 5: one fused block of three iterations against three calls
    of `train_one_iter` (a host loop over the classes, one gradient pass
    an iteration in both)."""
    x, y, params, _ = cell_inputs(7, 5)
    fused = train_block(x, y, params)
    ds = lgb.Dataset(x, label=y, params=dict(params), free_raw_data=False)
    loop = lgb.Booster(params=dict(params), train_set=ds).gbdt
    for _ in range(BLOCK):
        assert not loop.train_one_iter(is_eval=False)
    assert loop.metrics.snapshot()["gauges"]["class_axis_form"] == "loop"
    assert len(loop.models) == len(fused.models) == BLOCK * 5
    for a, b in zip(fused.models, loop.models):
        a, b = run.tree_arrays(a), run.tree_arrays(b)
        for key in ("split_feature", "threshold_in_bin", "left_child",
                    "right_child", "leaf_count"):
            np.testing.assert_array_equal(a[key], b[key], err_msg=key)
        np.testing.assert_allclose(a["leaf_value"], b["leaf_value"],
                                   rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(run.train_score(fused, ROWS),
                               run.train_score(loop, ROWS),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("path", ["fused", "loop"])
def test_a_stop_in_the_middle_of_an_iteration_keeps_whole_iterations(path):
    """Class 2 has no row of its own, so its tree finds no split while
    classes 0 and 1 still do: the iteration is dropped whole. The model
    list stays `iter * num_class` long and the train score is the kept
    trees' (none here), whichever path ran."""
    rng = np.random.RandomState(3)
    x = rng.rand(3000, 6).astype(np.float32)
    y = (x[:, 0] > 0.5).astype(np.float32)         # labels 0 and 1 of 3
    params = {"objective": "multiclass", "num_class": 3, "num_leaves": 7,
              "max_bin": 32, "min_data_in_leaf": 10, "verbose": -1,
              "min_gain_to_split": 1.0, "partitioned_build": "true"}
    ds = lgb.Dataset(x, label=y, params=dict(params), free_raw_data=False)
    gbdt = lgb.Booster(params=dict(params), train_set=ds).gbdt
    if path == "fused":
        assert gbdt._fused_eligible()
        assert gbdt.train_many(BLOCK) is True
    else:
        assert gbdt.train_one_iter(is_eval=False) is True
    assert len(gbdt.models) % 3 == 0
    assert len(gbdt.models) == gbdt.iter * 3 == 0
    np.testing.assert_allclose(run.train_score(gbdt, 3000), 0.0, atol=1e-7)
