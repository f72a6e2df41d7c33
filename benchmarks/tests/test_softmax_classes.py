"""The multiclass cell's own files checked without the chip.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_softmax_classes.py -q

The generator (the ranking cell's rows and grades, no query field); the
softmax reference's gradients against the loss they are the derivative
of; the control's `none` (the reference's own free-growing trees handed
to `compare`) reads 0 or rounding in every number; the bfloat16 control
and every planted fault come out as not correct by the cell's own
limits; one tree an iteration is refused; a split's regret is counted
beyond what float32 resolves of the gains' terms and no further. The
program against the reference is tier-1's:
tests/test_multiclass_reference.py.
"""

import io
import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor
from contextlib import redirect_stdout

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

import control_softmax_classes  # noqa: E402
import run  # noqa: E402
from datagen import load_module, make_data, train_params  # noqa: E402

CELL = "mslr-web30k-mc5-b63-l255.train"
RANKING = "mslr-web30k-b63-l255.train"
softmax = load_module("references", "softmax_classes")


def test_rows_and_grades_are_the_ranking_cells_without_queries():
    cell, ranking = run.load_cell(CELL), run.load_cell(RANKING)
    data = cell["config"]["data"]
    assert {k: v for k, v in data.items() if k != "kind"} == {
        k: v for k, v in ranking["config"]["data"].items() if k != "kind"}
    assert (data["rows"], data["features"]) == (2270296, 136)
    x, y, fields = make_data(dict(data, rows=20000), 2147483999)
    xr, yr, fr = make_data(dict(ranking["config"]["data"], rows=20000),
                           2147483999)
    assert fields == {} and sorted(fr) == ["group"]
    np.testing.assert_array_equal(x, xr)
    np.testing.assert_array_equal(y, yr)
    shares = np.bincount(y.astype(int), minlength=5) / len(y)
    np.testing.assert_allclose(shares, [0.52, 0.32, 0.13, 0.02, 0.01], atol=0.002)
    # all that differs from the ranking configuration's parameters
    mine, theirs = cell["config"]["params"], ranking["config"]["params"]
    assert set(mine) - set(theirs) == {"num_class"}
    assert set(theirs) - set(mine) == {"max_position", "label_gain",
                                       "ndcg_eval_at"}
    assert {k for k in set(mine) & set(theirs) if mine[k] != theirs[k]} \
        == {"objective"}
    assert (mine["objective"], mine["num_class"]) == ("multiclass", 5)
    assert cell["config"]["reduced"] == []


def test_gradients_are_the_derivative_of_the_loss():
    rng = np.random.default_rng(4)
    k, n = 5, 40
    score = rng.normal(0, 2, (k, n))
    y = rng.integers(0, k, n)
    g, h = softmax.softmax_grad(score, y)
    p = np.exp(score) / np.exp(score).sum(axis=0)
    np.testing.assert_allclose(h, 2.0 * p * (1.0 - p), rtol=1e-12)
    np.testing.assert_allclose(g.sum(axis=0), 0.0, atol=1e-12)
    eps = 1e-6
    for c, i in ((0, 0), (3, 17), (4, 39)):
        up, down = score.copy(), score.copy()
        up[c, i] += eps
        down[c, i] -= eps
        slope = n * (softmax.multi_logloss(up, y)
                     - softmax.multi_logloss(down, y)) / (2 * eps)
        assert g[c, i] == pytest.approx(slope, abs=1e-8)
    # from score 0 every class is as likely: log K, and p = 1 / K
    assert softmax.multi_logloss(np.zeros((k, n)), y) == pytest.approx(np.log(k))
    g0, h0 = softmax.softmax_grad(np.zeros((k, n)), y)
    assert set(np.round(g0.ravel(), 12)) == {0.2, -0.8}
    np.testing.assert_allclose(h0, 0.32)


def test_one_tree_an_iteration_is_refused():
    params = train_params(*(run.load_cell(CELL)[k] for k in ("config", "traffic")))
    x = np.zeros((10, 3), np.float32)
    y = np.zeros(10, np.float32)
    with pytest.raises(ValueError, match=r"reference\.py"):
        softmax.compare(x, y, {}, dict(params, num_class=1), [],
                        np.zeros((1, 10), np.float32))
    with pytest.raises(ValueError, match="no fields"):
        softmax.compare(x, y, {"group": [10]}, params, [],
                        np.zeros((5, 10), np.float32))


@pytest.mark.parametrize("offset", [0.0, 32.0])
def test_regret_is_counted_beyond_what_float32_resolves(offset):
    """One split of 4,000 rows, the second best and the worst candidate
    taken in the best one's place. With gradients about 0 the gains are
    no difference of large terms and the regret is `wide_binary`'s; with
    every gradient moved by 32 the leaf's G^2 / H is 1.3e7, RESOLVED
    spacings of the terms are about 100, and a lead of 80 in a gain of
    220 is not float32's to see, while the whole gain is, less the 100."""
    wide = softmax.wide
    p = {"num_leaves": 2, "lambda_l1": 0.0, "lambda_l2": 0.0,
         "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 0.0,
         "min_gain_to_split": 0.0, "learning_rate": 0.1}
    rng = np.random.default_rng(3)
    n, nb = 4000, 8
    bins = rng.integers(0, nb, (n, 3)).astype(np.uint8)
    g = rng.normal(0, 1, n) + 0.3 * (bins[:, 0] > 3) + offset
    h = np.full(n, 0.32)
    with ThreadPoolExecutor(2) as pool:
        gains = wide.plane_gains(wide.histogram(bins, None, g, h, nb, pool, 2), p)
        ranked = np.argsort(gains.ravel())[::-1]
        top = gains.ravel()[ranked[0]]
        parent = g.sum() ** 2 / h.sum()
        read = []
        for rank in (0, 1, int(np.isfinite(gains).sum()) - 1):
            f, t = np.unravel_index(ranked[rank], gains.shape)
            left = int((bins[:, f] <= t).sum())
            forced = {"split_feature": np.array([f]),
                      "threshold_in_bin": np.array([t]),
                      "left_child": np.array([-1]), "right_child": np.array([-2]),
                      "leaf_count": np.array([left, n - left]),
                      "internal_count": np.array([n])}
            v, rows, mine = softmax.follow_tree(bins, nb, g, h, p, pool, forced, 2)
            v_w, rows_w, theirs = wide.follow_tree(bins, nb, g, h, p, pool, forced, 2)
            np.testing.assert_array_equal(v, v_w)
            assert [len(r) for r in rows] == [len(r) for r in rows_w]
            assert mine["count_mismatch"] == theirs["count_mismatch"] == 0
            chosen = gains.ravel()[ranked[rank]]
            unseen = softmax.RESOLVED * softmax.SPACING32 * (
                top + chosen + 4 * parent)
            assert mine["split_regret"] == pytest.approx(
                max(top - chosen - unseen, 0.0) / top, abs=1e-12)
            read.append((mine["split_regret"], theirs["split_regret"]))
    assert read[0] == (0.0, 0.0)
    if offset:
        assert 90 < unseen < 110
        assert read[1][0] == 0.0 and read[1][1] > 0.05
        assert 0.1 < read[2][0] < read[2][1] - 0.2
    else:
        assert all(mine == pytest.approx(theirs, rel=1e-4) and mine > 0.3
                   for mine, theirs in read[1:])


@pytest.mark.parametrize("mode,fails", [
    ("none", None), ("bf16", "leaf_value_gap"), ("half_batch", "count_mismatch"),
    ("altered", "score_max_gap"), ("unchanged", "score_gap"),
    ("sequential_gradients", "leaf_value_gap"),
    ("single_hessian", "leaf_value_gap"), ("classes_swapped", "score_max_gap")])
def test_control_and_faults_of_the_softmax_reference(mode, fails):
    cell = run.load_cell(CELL)
    out = control_softmax_classes.one_seed(cell, 2147483701, [mode], 20000, 4)
    assert set(out[mode]) | {"window_compiles", "failed"} == set(cell["limits"])
    ok, rows = run.check(out[mode], {k: v for k, v in cell["limits"].items()
                                     if k in out[mode]})
    assert ok == (fails is None)
    if fails:
        assert rows[fails]["value"] > rows[fails]["limit"]
    else:
        # 0 or float64 rounding; the score is handed over as float32
        assert all(r["value"] < 1e-9 for name, r in rows.items()
                   if name != "score_max_gap")
        assert rows["score_max_gap"]["value"] < 1e-6


def test_a_rehearsal_drives_five_trees_an_iteration_through_the_seam():
    """The cell at 20,000 rows on the CPU: 2 blocks of 3 iterations of 5
    trees, every count, threshold, loss and score within the cell's
    limits. `split_regret` and `leaf_value_gap` are not read here: at
    255 leaves 20,000 rows leave the two rarest grades' trees nothing but
    pure leaves to split, where the true gain is 0 and float32 and
    float64 noise choose apart; tests/test_multiclass_reference.py holds
    both at 15 leaves, and a chip run at 2.27M rows."""
    out = io.StringIO()
    with redirect_stdout(out):
        assert run.main(["--workload", CELL, "--seed", "2147483701", "--trace",
                         "0", "--rehearse", "--rows", "20000", "--seconds",
                         "1"]) == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    limits = run.load_cell(CELL)["limits"]
    assert line["attempted"] == 6 and line["failed"] == 0
    assert set(line["checks"]) == set(limits)
    for name in set(limits) - {"split_regret", "leaf_value_gap"}:
        assert line["checks"][name]["value"] <= limits[name], name
