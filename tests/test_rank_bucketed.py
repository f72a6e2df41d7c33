"""LambdaRank over the length-bucketed query layout
(objectives/rank_device.py) against the float64 host path
(LambdarankNDCG.get_gradients_host, rank_objective.hpp:19-227), on
seeded data: heavy-tailed query sizes with queries of 1 and 2 documents,
a query of one label only, tied scores, weights; the counters against a
hand count; the layout's arrays as arguments of the fused program; fused
against per-iteration training; NDCG by rung against a loop of queries.
"""

import re

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.metadata import Metadata
from lightgbm_tpu.metrics import create_metric
from lightgbm_tpu.metrics.dcg_calculator import DCGCalculator
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.objectives import rank_device
from lightgbm_tpu.telemetry.trace import DEVICE_SUBSCOPES, PROCESS_TRACER

# one query on every rung up to 512 documents, the shortest ones, and
# both edges of a rung (128 | 129, 256 | 257)
SIZES = np.array([1, 2, 5, 130, 120, 300, 17, 128, 129, 1, 64, 257, 3, 256,
                  400, 2, 90])


def objective(sizes=SIZES, seed=0, weights=False, **params):
    rng = np.random.default_rng(seed)
    n = int(np.sum(sizes))
    label = rng.integers(0, 5, n).astype(np.float32)
    lo = int(np.sum(sizes[:3]))
    label[lo:lo + sizes[3]] = 2.0                  # a query of one label only
    md = Metadata(n)
    md.set_label(label)
    md.set_query(sizes)
    if weights:
        md.set_weights(rng.uniform(0.5, 2.0, n).astype(np.float32))
    cfg = Config.from_params({"objective": "lambdarank", **params})
    obj = create_objective("lambdarank", cfg)
    obj.init(md, n)
    return obj, md, cfg, rng


def scores(rng, n):
    return {"zero": np.zeros(n, np.float32),          # every score tied
            "random": rng.normal(0, 1, n).astype(np.float32),
            "tied": np.round(rng.normal(0, 1, n), 1).astype(np.float32),
            "far_apart": (40 * rng.normal(0, 1, n)).astype(np.float32)}


@pytest.mark.parametrize("weights", [False, True], ids=["plain", "weighted"])
@pytest.mark.parametrize("which", ["zero", "random", "tied", "far_apart"])
def test_bucketed_gradients_match_host(which, weights):
    obj, md, _, rng = objective(weights=weights)
    score = scores(rng, md.num_data)[which]
    g_host, h_host = map(np.asarray, obj.get_gradients_host(score[None]))
    g_dev, h_dev = map(np.asarray, obj.get_gradients(score[None]))
    assert g_dev.shape == h_dev.shape == (1, md.num_data)
    # float32 pairs against float64 ones (docs/Objectives.md): 1e-5 of
    # the largest entry; read 2e-7 on these seeds
    assert np.abs(g_dev - g_host).max() <= 1e-5 * np.abs(g_host).max()
    assert np.abs(h_dev - h_host).max() <= 1e-5 * np.abs(h_host).max()
    # a query of one document, and one of one label, has no pair
    assert not g_dev[0, :1].any() and not h_dev[0, :1].any()
    lo = int(np.sum(SIZES[:3]))
    assert not g_dev[0, lo:lo + SIZES[3]].any()
    if not weights:    # each pair moves its two documents by opposite amounts
        assert abs(g_dev.sum()) <= 1e-4 * np.abs(g_dev).sum()


def test_rungs_sum_to_the_one_rectangle(monkeypatch):
    """With one rung as wide as the longest query the layout is the old
    `(Q, M)` rectangle; the rungs of 128 give the same numbers."""
    obj, md, _, rng = objective()
    score = scores(rng, md.num_data)["tied"]
    by_rung = [np.asarray(a) for a in obj.get_gradients(score[None])]
    assert [r["width"] for r in obj.layout.rungs] == [128, 256, 384, 512]
    monkeypatch.setattr(rank_device, "RUNG_STEP", 512)
    one, _, _, _ = objective()
    assert [r["width"] for r in one.layout.rungs] == [512]
    assert one.layout.pair_slots > obj.layout.pair_slots
    rect = [np.asarray(a) for a in one.get_gradients(score[None])]
    for a, b in zip(by_rung, rect):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_pair_counters_against_a_hand_count():
    sizes = np.array([3, 130, 1, 128])
    obj, md, _, _ = objective(sizes)
    lay = obj.layout
    # three queries on the rung of 128, one on the rung of 256
    assert [(r["width"], len(r["queries"])) for r in lay.rungs] == [
        (128, 3), (256, 1)]
    assert lay.pairs == 3 * 2 + 130 * 129 + 0 + 128 * 127
    assert lay.pair_slots == 3 * 128 * 128 + 1 * 256 * 256
    # every row sits in exactly one slot
    assert len(set(lay.slot.tolist())) == md.num_data
    assert lay.num_slots == 3 * 128 + 256

    rng = np.random.default_rng(1)
    n = md.num_data
    x = rng.standard_normal((n, 4)).astype(np.float32)
    ds = lgb.Dataset(x, label=md.label, group=sizes)
    params = {"objective": "lambdarank", "num_leaves": 4, "verbose": -1,
              "min_data_in_leaf": 5, "min_sum_hessian_in_leaf": 1e-3}
    booster = lgb.train(params, ds, num_boost_round=3)
    snap = booster.gbdt.metrics.snapshot()
    assert snap["counters"]["rank_pairs"] == 3 * lay.pairs
    assert snap["counters"]["rank_pair_slots"] == 3 * lay.pair_slots
    assert snap["gauges"]["rank_pair_fill"] == pytest.approx(
        lay.pairs / lay.pair_slots)


def test_query_counts_share_a_program_on_the_ladder():
    """The count of queries in a rung is rounded up on
    canonical_row_chunks' ladder: 1,030 and 1,080 queries of one rung
    give the same rectangle."""
    a = rank_device.BucketedQueryLayout(np.arange(1031) * 100, 103000)
    b = rank_device.BucketedQueryLayout(np.arange(1081) * 100, 108000)
    assert a.rungs[0]["idx"].shape == b.rungs[0]["idx"].shape == (1152, 128)


def ranking_data(seed, n_queries=40):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(5, 200, n_queries)
    sizes[0], sizes[1] = 1, 2
    n = int(sizes.sum())
    x = rng.standard_normal((n, 6)).astype(np.float32)
    latent = x[:, 0] + 0.5 * x[:, 1] * x[:, 2] + rng.normal(0, 0.5, n)
    y = np.digitize(latent, [-0.3, 0.8, 1.5, 2.2]).astype(np.float32)
    return x, y, sizes


PARAMS = {"objective": "lambdarank", "num_leaves": 15, "verbose": -1,
          "min_data_in_leaf": 5, "min_sum_hessian_in_leaf": 1e-3,
          "max_bin": 63}


def test_layout_arrays_are_arguments_of_the_fused_program(monkeypatch):
    """Two data sets of one shape and different labels lower to the same
    program text: `_grad_ops` ride as runtime arguments, nothing of the
    layout is closed over."""
    x, y, sizes = ranking_data(3)
    texts, compiled = [], []
    lower = jax.stages.Lowered.compile

    def recording(self, *a, **k):
        out = lower(self, *a, **k)
        text = self.as_text()
        if "jit_fused" in text or "jit(fused)" in text:
            texts.append(text)
            compiled.append(out.as_text())
        return out

    monkeypatch.setattr(jax.stages.Lowered, "compile", recording)
    for labels in (y, np.roll(y, 7)[::-1].copy()):
        ds = lgb.Dataset(x, label=labels, group=sizes)
        booster = lgb.train(dict(PARAMS), ds, num_boost_round=2)
        obj = booster.gbdt.objective
        assert obj._grad_pure is not None and "rungs" in obj._grad_ops
    assert len(texts) == 2 and texts[0] == texts[1]
    # no constant of a layout's size (the index map would be one)
    assert max(len(line) for line in texts[0].splitlines()) < 100000
    # the pairwise pass stands under `gradients`, in its four sub-scopes
    # (a reduction's own little computation is named from the scope
    # down, `rank_sort/reduce_max`: no operation of a trace)
    paths = [p for p in re.findall(r'op_name="([^"]*)"', compiled[0])
             if p.startswith("jit(fused)")]
    for word in (w for w in DEVICE_SUBSCOPES["gradients"]
                 if w.startswith("rank_")):       # the pairwise pass's four
        hit = [p.split("/") for p in paths if word in p.split("/")]
        assert hit and all("gradients" in p[:p.index(word)] for p in hit), word


def test_fused_and_per_iteration_training_agree():
    x, y, sizes = ranking_data(4)
    fused = lgb.train(dict(PARAMS), lgb.Dataset(x, label=y, group=sizes),
                      num_boost_round=3)
    assert fused.gbdt.metrics.snapshot()["counters"].get("fused_blocks") == 1
    stepwise = lgb.Booster(params=dict(PARAMS),
                           train_set=lgb.Dataset(x, label=y, group=sizes))
    for _ in range(3):
        stepwise.gbdt.train_one_iter()
    assert "fused_blocks" not in stepwise.gbdt.metrics.snapshot()["counters"]
    for a, b in zip(fused.gbdt.models, stepwise.gbdt.models):
        a = a.materialize() if hasattr(a, "materialize") else a
        b = b.materialize() if hasattr(b, "materialize") else b
        np.testing.assert_array_equal(a.split_feature, b.split_feature)
        np.testing.assert_array_equal(a.threshold_in_bin, b.threshold_in_bin)
        np.testing.assert_allclose(a.leaf_value, b.leaf_value, rtol=1e-5,
                                   atol=1e-7)


def test_ndcg_by_rung_matches_a_loop_of_queries():
    obj, md, cfg, rng = objective(weights=True)
    metric = create_metric("ndcg", cfg)
    metric.init(md, md.num_data)
    score = np.round(rng.normal(0, 1, md.num_data), 1)
    got = metric.eval(score)
    dcg = DCGCalculator(cfg.label_gain)
    qb = np.asarray(md.query_boundaries)
    qw = np.asarray(md.query_weights, np.float64)
    for k, value in zip(metric.eval_at, got):
        acc = 0.0
        for q in range(len(qb) - 1):
            lab = md.label[qb[q]:qb[q + 1]]
            ideal = dcg.cal_maxdcg_at_k(k, lab)
            acc += qw[q] * (dcg.cal_dcg_at_k(k, lab, score[qb[q]:qb[q + 1]])
                            / ideal if ideal > 0 else 1.0)
        assert value == pytest.approx(acc / qw.sum(), rel=1e-12)
    # the ideal DCG the objective normalises by: once, by rung
    want = [dcg.cal_maxdcg_at_k(20, md.label[qb[q]:qb[q + 1]])
            for q in range(len(qb) - 1)]
    inv = np.where(np.asarray(want) > 0, 1.0 / np.maximum(want, 1e-300), 0.0)
    np.testing.assert_allclose(obj.inverse_max_dcgs, inv, rtol=1e-12)


def test_spans_of_a_ranking_job():
    x, y, sizes = ranking_data(5)
    PROCESS_TRACER.reset()
    ds = lgb.Dataset(x, label=y, group=sizes).construct()
    lgb.train(dict(PARAMS), ds, num_boost_round=1)
    spans = {s["path"]: s for s in PROCESS_TRACER.recent(None)}
    assert spans["dataset"]["tags"]["queries"] == len(sizes)
    assert spans["rank_layout"]["tags"] == {"queries": len(sizes),
                                            "rows": len(y)}
    assert "rank_layout" in PROCESS_TRACER.snapshot()
    # the objective's set-up stays a path of its own, before the
    # Booster's init and outside it
    layout, init = spans["rank_layout"], spans["booster_init"]
    assert layout["start_s"] + layout["duration_s"] <= init["start_s"] + 2e-6
    assert not any(p.endswith("/rank_layout") for p in spans)
