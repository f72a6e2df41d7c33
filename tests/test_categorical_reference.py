"""Direct categorical splits against a reference that is not the program.

`lgb.Dataset(..., categorical_feature=...)` then `lgb.train` through the
fused scan (`train_many`, one tree an iteration) on the CPU, over 20,000
rows of the airline cell's generator (six ID columns of 7 to 352 ids,
two past the 255 a column keeps), one block of three iterations at the
cell's own settings; its trees and score are judged by
benchmarks/references/categorical_binary.py (numpy float64, own bins,
own one-vs-rest gains) under the cell's own limits. Also the device
binning forced on the CPU (the same trees), and the cell's two readers.
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

CELL = "airline30m-cat-b255-l255.train"
ROWS, BLOCK = 20000, 3


def cell_inputs(seed):
    cell = run.load_cell(CELL)
    params = dict(train_params(cell["config"], cell["traffic"]),
                  partitioned_build="true")
    x, y, fields = make_data(dict(cell["config"]["data"], rows=ROWS), seed)
    return x, y, fields, params, cell["limits"]


def train_block(x, y, fields, params):
    ds = lgb.Dataset(x, label=y, params=dict(params), free_raw_data=False,
                     **fields)
    booster = lgb.train(dict(params), ds, num_boost_round=BLOCK)
    gbdt = booster.gbdt
    assert gbdt.tree_learner._use_partitioned
    return gbdt


# 2147800001 puts Diverted first: the root's totals are then summed from
# a bin that holds nearly every row, the column order that reads highest
@pytest.mark.parametrize("seed", [1, 2147483701, 3000000017, 2147800001])
def test_fused_scan_meets_the_categorical_reference(seed):
    x, y, fields, params, limits = cell_inputs(seed)
    cat = fields["categorical_feature"]
    assert len(cat) == 6
    gbdt = train_block(x, y, fields, params)
    snap = gbdt.metrics.snapshot()
    assert snap["gauges"]["class_axis_form"] == "single"
    assert snap["counters"]["fused_blocks"] == 1
    trees = [run.tree_arrays(m) for m in gbdt.models]
    assert len(trees) == BLOCK
    feats = np.concatenate([t["split_feature"] for t in trees])
    assert np.isin(feats, cat).mean() >= 0.25
    numbers = load_module("references", "categorical_binary").compare(
        x, y, fields, params, trees, run.train_score(gbdt, ROWS), threads=4)
    numbers.update(window_compiles=0.0,
                   failed=float(run.failed_iterations(trees, BLOCK, 1)))
    ok, rows = run.check(numbers, limits)
    assert ok, {name: r for name, r in rows.items()
                if not r["value"] <= r["limit"]}


def test_device_binning_grows_the_same_trees(monkeypatch):
    """The categorical columns binned by the device pass (forced on the
    CPU) give the host's bins, so the same trees to the bit."""
    x, y, fields, params, _ = cell_inputs(5)
    monkeypatch.setenv("LIGHTGBM_TPU_DEVICE_BIN", "0")
    host = train_block(x, y, fields, params)
    monkeypatch.setenv("LIGHTGBM_TPU_DEVICE_BIN", "1")
    dev = train_block(x, y, fields, params)
    assert dev.train_data.binned_on_device
    for a, b in zip(host.models, dev.models):
        a, b = run.tree_arrays(a), run.tree_arrays(b)
        for key in run.TREE_KEYS:
            np.testing.assert_array_equal(a[key], b[key], err_msg=key)


def test_a_categorical_node_holds_the_id_of_its_bin():
    """A categorical split's threshold is the id kept in its bin, and
    prediction sends exactly the rows of that id left."""
    x, y, fields, params, _ = cell_inputs(9)
    gbdt = train_block(x, y, fields, params)
    mappers = gbdt.train_data.bin_mappers
    used = gbdt.train_data.used_feature_map
    tree = run.tree_arrays(gbdt.models[0])
    is_cat = np.isin(tree["split_feature"], fields["categorical_feature"])
    assert is_cat.any()
    for f, t, thr in zip(tree["split_feature"][is_cat],
                         tree["threshold_in_bin"][is_cat],
                         tree["threshold"][is_cat]):
        assert thr == mappers[used[f]].bin_2_categorical[t]
    ref = load_module("references", "categorical_binary")
    cat = ref.categorical_mask(fields, x.shape[1])
    keys, bins, _ = ref.prepare(x, params, cat, pool=_Serial())
    leaf = ref.leaf_of(tree, bins, cat)
    np.testing.assert_array_equal(np.bincount(leaf, minlength=len(tree["leaf_value"])),
                                  tree["leaf_count"])


class _Serial:
    """A pool that maps in the caller's thread."""

    def map(self, fn, items):
        return [fn(i) for i in items]


@pytest.mark.parametrize("name", ["cat_bin_s", "cat_split_pct"])
def test_categorical_readers(name, monkeypatch):
    """`cat_bin_s` reads the span `dataset/bin_categorical`,
    `cat_split_pct` the traced block's trees against that span's
    `columns`; both nothing without a trace or without the span (a
    program from before them)."""
    from lightgbm_tpu.telemetry import trace
    held = trace.SpanTracer()
    with held.span("dataset"):
        with held.span("bin_categorical", rows=10, columns=[2, 5],
                       categories=40):
            pass
    monkeypatch.setattr(trace, "PROCESS_TRACER", held)
    trees = [{"split_feature": np.asarray([2, 0, 5, 2])},
             {"split_feature": np.asarray([1, 5])}]
    ctx = {"trace": {"busy_s": 1.0}, "trees": trees}
    read = load_module("metrics", name).read
    want = {"cat_bin_s": held.snapshot()["dataset/bin_categorical"],
            "cat_split_pct": 100.0 * 4 / 6}[name]
    assert read(ctx) == pytest.approx(want)
    assert read(dict(ctx, trace=None)) is None
    monkeypatch.setattr(trace, "PROCESS_TRACER", trace.SpanTracer())
    assert read(ctx) is None
