"""The ranking cell's own files checked without the chip.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_lambdarank.py -q

The generator's query sizes; the lambdarank reference against a loop of
one query at a time and against the program (a whole rehearsal of the
cell at 20,000 rows comes out correct); the bfloat16 control and every
planted fault come out as not correct by the cell's own limits.
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

import control_lambdarank  # noqa: E402
import run  # noqa: E402
from datagen import load_module, make_data, train_params  # noqa: E402

CELL = "mslr-web30k-b63-l255.train"
lambdarank = load_module("references", "lambdarank")
mslr_like = load_module("generators", "mslr_like")


def test_query_sizes_sum_reach_the_longest_and_ignore_the_seed():
    data = run.load_cell(CELL)["config"]["data"]
    sizes = mslr_like.query_sizes(data)
    assert len(sizes) == data["queries"] == 18919
    assert int(sizes.sum()) == data["rows"] == 2270296
    assert sizes.min() == 1 and sizes.max() == data["max_docs"] == 1251
    assert (sizes == 1251).sum() == 1
    # heavy-tailed around the mean of 120: the median well below it
    assert 80 <= np.median(sizes) <= 100 and np.quantile(sizes, 0.99) > 400
    assert lambdarank.pairs_evaluated(sizes) == float(
        np.sum(sizes.astype(np.int64) * (sizes - 1)))
    small = dict(data, rows=20000)
    x1, y1, f1 = make_data(small, 1)
    x2, y2, f2 = make_data(small, 2147483999)
    assert int(f1["group"].sum()) == 20000 and f1["group"].max() == 1251
    np.testing.assert_array_equal(f1["group"], f2["group"])
    np.testing.assert_array_equal(y1, y2)
    assert x1.shape == (20000, 136) and x1.dtype == np.float32
    # --seed draws the order of the columns and nothing else
    assert not np.array_equal(x1, x2)
    np.testing.assert_array_equal(np.sort(x1, axis=1), np.sort(x2, axis=1))
    shares = np.bincount(y1.astype(int), minlength=5) / len(y1)
    np.testing.assert_allclose(shares, [0.52, 0.32, 0.13, 0.02, 0.01], atol=0.002)


def one_query_at_a_time(sizes, label, score, params):
    """rank_objective.hpp GetGradientsForOneQuery, the loop it is."""
    gain = np.asarray(params["label_gain"])[label.astype(int)]
    g, h = np.zeros(len(label)), np.zeros(len(label))
    lo = 0
    for n in sizes:
        s, lg = score[lo:lo + n], gain[lo:lo + n]
        order = np.argsort(-s, kind="stable")
        rank = np.empty(n, int)
        rank[order] = np.arange(n)
        disc = 1.0 / np.log2(2.0 + rank)
        ideal = np.sort(lg)[::-1][:params["max_position"]]
        maxdcg = np.sum(ideal / np.log2(2.0 + np.arange(len(ideal))))
        for i in range(n):
            for j in range(n):
                if lg[i] <= lg[j]:
                    continue
                delta = (lg[i] - lg[j]) * abs(disc[i] - disc[j]) / maxdcg
                if s.max() != s.min():
                    delta /= 0.01 + abs(s[i] - s[j])
                p = 2.0 / (1.0 + np.exp(2.0 * np.clip(s[i] - s[j], -25, 25)))
                g[lo + i] -= p * delta
                g[lo + j] += p * delta
                h[lo + i] += 2.0 * p * (2.0 - p) * delta
                h[lo + j] += 2.0 * p * (2.0 - p) * delta
        lo += n
    return g, h


def test_pair_lists_match_a_loop_of_one_query_at_a_time():
    rng = np.random.default_rng(3)
    sizes = np.array([1, 2, 40, 7, 33, 5])
    n = int(sizes.sum())
    label = rng.integers(0, 5, n).astype(np.float64)
    label[3:43] = np.where(np.arange(40) < 38, 0, label[3:43])
    label[43:50] = 1.0                                  # one label only
    params = {"label_gain": [0, 1, 3, 7, 15], "max_position": 20,
              "ndcg_eval_at": [1, 3, 5, 10], "sigmoid": 1.0}
    queries = lambdarank.Queries(sizes, label, params)
    with ThreadPoolExecutor(2) as pool:
        for score in (np.zeros(n), rng.normal(0, 1, n),
                      np.round(rng.normal(0, 1, n), 1)):
            g, h = queries.gradients(score, pool)
            g_loop, h_loop = one_query_at_a_time(sizes, label, score, params)
            np.testing.assert_allclose(g, g_loop, rtol=1e-12, atol=1e-15)
            np.testing.assert_allclose(h, h_loop, rtol=1e-12, atol=1e-15)
    assert sum(len(c[2]) for c in queries.chunks) == sum(
        int(np.sum(label[lo:lo + k][:, None] > label[lo:lo + k][None, :]))
        for lo, k in zip(np.cumsum(sizes) - sizes, sizes))
    # NDCG: perfect order reads 1, and a query of zeros counts 1
    assert queries.ndcg(label) == pytest.approx(np.ones(4))


def test_the_reference_passes_the_program_at_20000_rows():
    out = io.StringIO()
    with redirect_stdout(out):
        assert run.main(["--workload", CELL, "--seed", "2147483701", "--trace",
                         "0", "--rehearse", "--rows", "20000", "--seconds",
                         "1"]) == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["checks"]) == set(run.load_cell(CELL)["limits"])
    assert line["checks"]["count_mismatch"]["value"] == 0


@pytest.mark.parametrize("mode,fails", [
    ("none", None), ("bf16", "leaf_value_gap"), ("truncated", "leaf_value_gap"),
    ("padded_docs", "leaf_value_gap"), ("half_batch", "count_mismatch"),
    ("altered", "score_max_gap"), ("unchanged", "score_gap")])
def test_control_and_faults_of_the_ranking_reference(mode, fails):
    cell = run.load_cell(CELL)
    out = control_lambdarank.one_seed(cell, 2147483701, [mode], 20000, 4)
    ok, rows = run.check(out[mode], {k: v for k, v in cell["limits"].items()
                                     if k in out[mode]})
    assert ok == (fails is None)
    if fails:
        assert rows[fails]["value"] > 3 * rows[fails]["limit"]
        assert train_params(cell["config"], cell["traffic"])["objective"] \
            == "lambdarank"
