"""The data-parallel Criteo cell's own files checked without the chip.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_sharded_binary.py -q

The generator (67 columns, the integer columns' missing shares, the
positive share, the seed's column order, and its refusal of a program
that cannot bin a row shard on its own chip); the sharded reference against
`reference.py` over one shard (the same numbers); its `none` reading 0
or float64 rounding and every planted fault failing at least one of the
cell's limits at 20,000 rows; a rehearsal of the cell at 20,000 rows,
over four virtual devices, comes out correct.
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
import control_sharded_binary  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
from datagen import load_module, make_data, train_params  # noqa: E402

CELL = "criteo-dp4-b255-l255.train"
ROWS = 20000
sharded = load_module("references", "sharded_binary")
gen = load_module("generators", "criteo_like")


def test_a_program_without_row_sharded_binning_stops_before_the_rows(
        monkeypatch):
    """The generator refuses a program that cannot bin a row shard on its
    own chip (the parent of the change that added this cell): a clean
    non-zero exit before a row is drawn, where that program would put the
    whole matrix on one chip."""
    from lightgbm_tpu.io import dataset
    monkeypatch.delattr(dataset, "RowShards")
    data = run.load_cell(CELL)["config"]["data"]
    with pytest.raises(SystemExit) as stop:
        make_data(dict(data, rows=1000), 1)
    assert "RowShards" in str(stop.value.code)


def test_columns_missing_shares_and_the_seed_orders_the_columns():
    data = run.load_cell(CELL)["config"]["data"]
    assert (data["rows"], data["features"]) == (32000000, 67)
    small = dict(data, rows=200000)
    x1, y1, f1 = make_data(small, 1)
    x2, y2, _ = make_data(small, 2147483701)
    assert x1.shape == (200000, 67) and x1.dtype == np.float32 and not f1
    np.testing.assert_array_equal(y1, y2)
    assert 0.030 < y1.mean() < 0.040
    # the same rows, the columns in another order
    key = lambda x: np.sort(np.nan_to_num(x, nan=-9.0), axis=1)  # noqa: E731
    np.testing.assert_array_equal(key(x1), key(x2))
    # 13 integer columns carry NaN, each on its own share
    nan = np.isnan(x1).mean(axis=0)
    shares = sorted(s for s in gen.INT_MISSING if s > 0)
    np.testing.assert_allclose(sorted(nan[nan > 0]), shares, atol=0.01)
    assert (nan > 0).sum() == len(shares)
    ints = x1[:, np.isnan(x1).any(axis=0)]
    assert (np.nan_to_num(ints) == np.trunc(np.nan_to_num(ints))).all()


def small_params(**over):
    cell = run.load_cell(CELL)
    return dict(train_params(cell["config"], cell["traffic"]), **over)


def test_over_one_shard_the_numbers_are_the_plain_references(monkeypatch):
    """One shard, every tree followed and every shortfall of a split
    counted: the reference is `reference.py` (on rows without NaN, since
    `reference.py` finds its bounds on the raw sample, this reference on
    the sample as the program reads it, NaN as 0.0)."""
    monkeypatch.setattr(sharded, "FOLLOWED", 3)
    monkeypatch.setattr(sharded, "RESOLVED", 0)
    x, y, _ = make_data(dict(run.load_cell(CELL)["config"]["data"],
                             rows=ROWS), 3)
    x = np.nan_to_num(x, nan=0.0)
    params = small_params(num_leaves=31, num_machines=1)
    trees, score = control.stand_in(x, y, params, 3, "bf16", threads=4)
    want = reference.compare(x, y, params, trees, score, threads=4)
    got = sharded.compare(x, y, {}, params, trees, score[None, :], threads=4)
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-9, abs=1e-12), k


def test_four_shards_grow_the_one_shard_tree():
    """Summed in float64 over four shards, the free tree is the
    one-shard tree: the data-parallel semantics change no split."""
    x, y, _ = make_data(dict(run.load_cell(CELL)["config"]["data"],
                             rows=ROWS), 5)
    params = small_params(num_leaves=31)
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(4) as pool:
        _, bins, nb = sharded.prepare(x, params, pool)
        g, h = reference.binary_grad(np.zeros(ROWS), y)
        one, _, _ = sharded.grow_tree(bins, nb, g, h, params, pool,
                                      sharded.shards_of(ROWS, 1))
        four, _, _ = sharded.grow_tree(bins, nb, g, h, params, pool,
                                       sharded.shards_of(ROWS, 4))
    for k in ("split_feature", "threshold_in_bin", "leaf_count"):
        np.testing.assert_array_equal(one[k], four[k])
    np.testing.assert_allclose(one["leaf_value"], four["leaf_value"],
                               rtol=1e-12)


@pytest.mark.parametrize("mode,fails", [
    ("none", None), ("bf16", "leaf_value_gap"), ("comm_bf16", "split_regret"),
    ("drop_shard", "split_regret"), ("half_batch", "count_mismatch"),
    ("altered", "leaf_value_gap"), ("unchanged", "score_gap")])
def test_control_and_faults_of_the_sharded_reference(mode, fails):
    cell = run.load_cell(CELL)
    out = control_sharded_binary.one_seed(cell, 2147483701, [mode], ROWS, 4)
    numbers = out[mode]
    ok, rows = run.check(numbers, {k: v for k, v in cell["limits"].items()
                                   if k in numbers})
    assert ok == (fails is None)
    if fails:
        assert rows[fails]["value"] > 3 * rows[fails]["limit"]
    else:
        assert max(numbers[k] for k in ("count_mismatch", "threshold_gap",
                                        "split_regret")) == 0
        # a later tree's own leaf values are summed by leaf, not by bin:
        # float64 rounding
        assert numbers["leaf_value_gap"] < 1e-12
        assert max(numbers.values()) < 1e-6


def test_the_reference_passes_the_program_at_20000_rows(monkeypatch):
    """The cell rehearsed at 20,000 rows over four virtual devices, on the
    leaf-contiguous builder a TPU picks by itself (the CPU's default is
    the masked one), with 15 leaves where the cell grows 255: at 20,000
    rows a 255-leaf tree's last leaves hold a hundred rows under parents
    of thousands, and a leaf whose histogram is its parent's less its
    sibling's in float32 is then known to 1e-3 of its value
    (leaf_value_gap 1.7e-3 at seed 2147483701), which says nothing of the
    cell's leaves of 10^5 rows."""
    cell = run.load_cell(CELL)
    cell["config"]["params"].update(num_leaves=15, partitioned_build="true")
    monkeypatch.setattr(run, "load_cell", lambda name: cell)
    out = io.StringIO()
    with redirect_stdout(out):
        assert run.main(["--workload", CELL, "--seed", "2147483701", "--trace",
                         "0", "--rehearse", "--rows", str(ROWS), "--seconds",
                         "1"]) == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["device"]["count"] >= 4
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["checks"]) == set(cell["limits"])
    assert line["checks"]["count_mismatch"]["value"] == 0
