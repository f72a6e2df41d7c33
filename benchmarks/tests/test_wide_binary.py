"""The Epsilon cell's own files checked without the chip.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_wide_binary.py -q

The generator (unit rows, balanced labels, the seed orders the columns
only); the wide binary reference against `reference.py` (the same
numbers on 20,000 x 64) and against the program (a rehearsal of the cell
at 8,192 rows comes out correct); the bfloat16 control and every planted
fault come out as not correct by the cell's own limits.
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

import control  # noqa: E402
import control_wide_binary  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
from datagen import load_module, make_data, train_params  # noqa: E402

CELL = "epsilon400k-b63-l255.train"
wide = load_module("references", "wide_binary")


def test_rows_are_unit_vectors_and_the_seed_orders_the_columns():
    data = run.load_cell(CELL)["config"]["data"]
    assert (data["rows"], data["features"]) == (400000, 2000)
    small = dict(data, rows=20000)
    x1, y1, f1 = make_data(small, 1)
    x2, y2, _ = make_data(small, 2147483999)
    assert x1.shape == (20000, 2000) and x1.dtype == np.float32 and f1 == {}
    np.testing.assert_allclose(np.linalg.norm(x1.astype(np.float64), axis=1),
                               1.0, atol=1e-6)
    np.testing.assert_array_equal(y1, y2)
    assert 0.48 < y1.mean() < 0.52
    assert not np.array_equal(x1, x2)
    np.testing.assert_array_equal(np.sort(x1, axis=1), np.sort(x2, axis=1))
    # every column continuous: as many distinct values as rows, nearly
    assert len(np.unique(x1[:, 7])) > 19000


@pytest.mark.parametrize("kind", ["continuous", "few_values", "one_big_value",
                                  "sign_change"])
def test_bounds_are_the_plain_references(kind):
    rng = np.random.default_rng(5)
    v = rng.standard_normal(5000)
    if kind == "few_values":
        v = np.round(v, 1)
    elif kind == "one_big_value":
        v[:900] = 0.25
    elif kind == "sign_change":
        v = np.where(np.abs(v) < 0.05, 0.3, v)
    for max_bin in (15, 63, 255):
        np.testing.assert_array_equal(wide.find_bounds(v, max_bin),
                                      reference.find_bounds(v, max_bin))


def test_binning_and_histograms_are_the_plain_references():
    rng = np.random.default_rng(6)
    x = rng.standard_normal((6000, 40)).astype(np.float32)
    x[:50, 3] = np.nan
    cfg = {"bin_construct_sample_cnt": 2000, "data_random_seed": 1,
           "max_bin": 63}
    g, h = rng.standard_normal(6000), rng.random(6000)
    rows = np.sort(rng.choice(6000, 2500, replace=False))
    with ThreadPoolExecutor(4) as pool:
        bounds, bins = wide.prepare(x, cfg, pool)
        plain_bounds, plain_bins = reference.prepare(x, cfg, pool)
        for a, b in zip(bounds, plain_bounds):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(bins.T, plain_bins)
        for r in (None, rows, rows[:7]):
            got = wide.histogram(bins, r, g, h, 63, pool, 4)
            want = reference.histogram(plain_bins, r, g, h, 63, pool)
            np.testing.assert_allclose(np.moveaxis(got, 0, 2), want,
                                       rtol=1e-12, atol=1e-12)
        p = {"lambda_l1": 0.0, "lambda_l2": 0.0, "min_data_in_leaf": 1,
             "min_sum_hessian_in_leaf": 5.0, "min_gain_to_split": 0.0}
        np.testing.assert_allclose(wide.plane_gains(got, p),
                                   reference.split_gains(want, p), rtol=1e-12)


@pytest.mark.parametrize("mode", control.MODES)
def test_the_same_numbers_as_the_plain_reference(mode, monkeypatch):
    """20,000 x 64, three trees of the plain reference growing freely in
    `mode`: with all three followed the wide reference returns what
    `reference.compare` returns, to 1e-12; every planted fault is caught
    by both."""
    cell = run.load_cell(CELL)
    params = dict(train_params(cell["config"], cell["traffic"]),
                  num_leaves=31, min_sum_hessian_in_leaf=20.0)
    x, y, _ = make_data({"kind": "epsilon_like", "rows": 20000, "features": 64,
                         "base_seed": 3}, 11)
    trees, score = control.stand_in(x, y, params, 3, mode, threads=4)
    want = reference.compare(x, y, params, trees, score, threads=4)
    monkeypatch.setattr(wide, "FOLLOWED", 3)
    got = wide.compare(x, y, {}, params, trees, score[None, :], threads=4)
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-9, abs=1e-12), k
    ok, _ = run.check(got, {k: v for k, v in cell["limits"].items() if k in got})
    assert ok == (mode == "none")
    # with the third tree applied as given, as a run does, the verdict holds
    monkeypatch.setattr(wide, "FOLLOWED", 2)
    cut = wide.compare(x, y, {}, params, trees, score[None, :], threads=4)
    ok, _ = run.check(cut, {k: v for k, v in cell["limits"].items() if k in cut})
    assert ok == (mode == "none")


def test_the_reference_passes_the_program_at_8192_rows():
    out = io.StringIO()
    with redirect_stdout(out):
        assert run.main(["--workload", CELL, "--seed", "2147483701", "--trace",
                         "0", "--rehearse", "--rows", "8192", "--seconds",
                         "1"]) == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["checks"]) == set(run.load_cell(CELL)["limits"])
    assert line["checks"]["count_mismatch"]["value"] == 0


@pytest.mark.parametrize("mode,fails", [
    ("none", None), ("bf16", "leaf_value_gap"), ("half_batch", "count_mismatch"),
    ("altered", "score_max_gap"), ("unchanged", "score_gap")])
def test_control_and_faults_of_the_wide_reference(mode, fails):
    cell = run.load_cell(CELL)
    out = control_wide_binary.one_seed(cell, 2147483701, [mode], 8192, 4)
    ok, rows = run.check(out[mode], {k: v for k, v in cell["limits"].items()
                                     if k in out[mode]})
    assert ok == (fails is None)
    if fails:
        assert rows[fails]["value"] > 3 * rows[fails]["limit"]
