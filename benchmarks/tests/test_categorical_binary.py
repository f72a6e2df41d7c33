"""The airline cell's own files checked without the chip.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_categorical_binary.py -q

The generator (gbm-bench's 13 columns, six categorical ones that follow
the seed's column order, the label share, the NaN share); the
categorical reference against `reference.py` where no column is
categorical (the same numbers), its `none` reading 0 or float64
rounding and every planted fault failing at least one of the cell's
limits at 20,000 rows; a rehearsal of the cell at 20,000 rows comes out
correct.
"""

import io
import json
import os
import sys
from contextlib import redirect_stdout

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

import control  # noqa: E402
import control_categorical_binary  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
from datagen import load_module, make_data, train_params  # noqa: E402

CELL = "airline30m-cat-b255-l255.train"
ROWS = 20000
cat_ref = load_module("references", "categorical_binary")


def test_rows_and_the_seed_orders_the_columns():
    data = run.load_cell(CELL)["config"]["data"]
    assert (data["rows"], data["features"]) == (25000000, 13)
    small = dict(data, rows=ROWS)
    x1, y1, f1 = make_data(small, 1)
    x2, y2, f2 = make_data(small, 2147483701)
    assert x1.shape == (ROWS, 13) and x1.dtype == np.float32
    np.testing.assert_array_equal(y1, y2)
    assert 0.42 < y1.mean() < 0.48
    gen = load_module("generators", "airline_like")
    ids = {}
    for x, f in ((x1, f1), (x2, f2)):
        cat = f["categorical_feature"]
        assert len(cat) == 6
        counts = sorted(len(np.unique(x[:, j])) for j in cat)
        ids[tuple(cat)] = counts
        assert counts[:4] == [7, 12, 29, 31]
        assert counts[4] > 255 and counts[5] > 255
        assert (x[:, cat] == np.trunc(x[:, cat])).all()
        nan = np.isnan(x).mean(axis=0)
        assert ((nan > 0.01) & (nan < 0.03)).sum() == 1 and (nan > 0).sum() == 1
    assert len(ids) == 2 and len(set(map(tuple, ids.values()))) == 1
    # the same rows, the columns in another order
    key = lambda x: np.sort(np.nan_to_num(x, nan=-1.0), axis=1)  # noqa: E731
    np.testing.assert_array_equal(key(x1), key(x2))
    assert gen.COLUMNS[9] == "Origin"


def small_params(**over):
    cell = run.load_cell(CELL)
    return dict(train_params(cell["config"], cell["traffic"]), **over)


def test_without_categorical_columns_the_numbers_are_the_plain_references(
        monkeypatch):
    """With no column named categorical, every tree followed and every
    shortfall of a split counted, the reference is `reference.py`: the
    same bins and, following the plain reference's own free trees, the
    same numbers (on rows without NaN: `reference.py` finds its bounds on
    the raw sample, this reference on the sample as the program reads
    it, NaN as 0.0)."""
    monkeypatch.setattr(cat_ref, "FOLLOWED", 3)
    monkeypatch.setattr(cat_ref, "RESOLVED", 0)
    x, y, _ = make_data(dict(run.load_cell(CELL)["config"]["data"], rows=ROWS), 3)
    x = np.nan_to_num(x, nan=0.0)
    params = small_params(num_leaves=31)
    trees, score = control.stand_in(x, y, params, 3, "bf16", threads=4)
    want = reference.compare(x, y, params, trees, score, threads=4)
    got = cat_ref.compare(x, y, {}, params, trees, score[None, :], threads=4)
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-9, abs=1e-12), k


@pytest.mark.parametrize("mode,fails", [
    ("none", None), ("bf16", "leaf_value_gap"), ("half_batch", "count_mismatch"),
    ("altered", "leaf_value_gap"), ("unchanged", "score_gap"),
    ("as_numeric", "count_mismatch"), ("bin0_apart", "count_mismatch")])
def test_control_and_faults_of_the_categorical_reference(mode, fails):
    cell = run.load_cell(CELL)
    out = control_categorical_binary.one_seed(cell, 2147483701, [mode], ROWS, 4)
    numbers = out[mode]
    ok, rows = run.check(numbers, {k: v for k, v in cell["limits"].items()
                                   if k in numbers})
    assert ok == (fails is None)
    if fails:
        assert rows[fails]["value"] > 3 * rows[fails]["limit"]
    else:
        assert max(numbers[k] for k in ("count_mismatch", "threshold_gap",
                                        "split_regret", "leaf_value_gap")) == 0
        assert max(numbers.values()) < 1e-6


def test_the_reference_passes_the_program_at_20000_rows():
    out = io.StringIO()
    with redirect_stdout(out):
        assert run.main(["--workload", CELL, "--seed", "2147483701", "--trace",
                         "0", "--rehearse", "--rows", str(ROWS), "--seconds",
                         "1"]) == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["checks"]) == set(run.load_cell(CELL)["limits"])
    assert line["checks"]["count_mismatch"]["value"] == 0
