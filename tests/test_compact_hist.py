"""Gather-compacted histogram engine (ops/histogram.py
compacted_histograms) + persistent compile cache (config.py
setup_compilation_cache).

Parity contract (ISSUE 1): compacted leaf histograms match the
full-scan masked path to <= 1e-6 — serially and under the
data-parallel shard reduction. The row-sharded learners' DEFAULT
masked engine keeps the fixed-order Kahan pair reduce, whose
pair-level agreement with serial is bounded by a few f32 ulps of each
cell's absolute mass regardless of shard count (chunk-aligned
partials); shard-local compaction is opt-in there because it regroups
within-chunk partials, widening that to ~1e-6 (parallel/learners.py
_compaction_enabled). The cache contract: a second train() in a fresh
process loads the fused program's executable from disk instead of
re-lowering it.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops.histogram import compacted_histograms
from lightgbm_tpu.ops.ordered_hist import canonical_row_chunks
from lightgbm_tpu.ops.pallas_hist import HIST_CHUNK, masked_histograms_xla
from lightgbm_tpu.ops.partition import compact_gather_indices


def _workload(n, f=6, b=32, leaves=7, seed=0):
    rng = np.random.RandomState(seed)
    bins = jnp.asarray(rng.randint(0, b, size=(f, n)).astype(np.uint8))
    ghc_t = jnp.asarray(rng.randn(3, n).astype(np.float32))
    row_leaf = jnp.asarray(rng.randint(0, leaves, size=n).astype(np.int32))
    return bins, ghc_t, row_leaf


def test_compact_gather_indices_stable():
    rng = np.random.RandomState(3)
    mask = rng.rand(257) > 0.6
    size = 128
    assert mask.sum() <= size
    src = np.asarray(compact_gather_indices(jnp.asarray(mask), size))
    expect = np.flatnonzero(mask)
    np.testing.assert_array_equal(src[:len(expect)], expect)  # stable order
    assert np.all(src[len(expect):] == len(mask))  # sentinel padding


def test_compacted_matches_full_scan_serial():
    """<= 1e-6 parity on every leaf, across bucket sizes (leaf counts
    from a handful of rows up to most of the array)."""
    n, b, leaves = 4 * HIST_CHUNK, 32, 7
    bins, ghc_t, row_leaf = _workload(n, b=b, leaves=leaves)
    # skew leaf sizes so different lax.switch buckets are exercised
    row_leaf = jnp.where(jnp.arange(n) < 3 * HIST_CHUNK, 0, row_leaf)
    compact = jax.jit(lambda rl, l: compacted_histograms(
        bins, ghc_t, rl, l, b))
    full = jax.jit(lambda rl, l: masked_histograms_xla(
        bins, ghc_t, rl, l, b))
    for leaf in range(leaves):
        hc, rc = compact(row_leaf, jnp.int32(leaf))
        hm, rm = full(row_leaf, jnp.int32(leaf))
        got, ref = np.asarray(hc + rc), np.asarray(hm + rm)
        scale = max(1.0, float(np.abs(ref).max()))
        assert np.abs(got - ref).max() / scale <= 1e-6


def test_compacted_shard_reduction_matches_serial():
    """Data-parallel contract: per-shard COMPACTED pairs reduced by the
    same fixed-order Kahan pair_allreduce sit <= 1e-6 from the f64
    truth (and hence from the serial full-scan), while the MASKED
    shard reduction — the row-sharded learners' default engine — keeps
    its chunk-aligned Kahan-pair agreement with the serial result:
    error bounded by a few f32 ulps of each cell's absolute mass,
    independent of shard count."""
    from jax.sharding import Mesh, PartitionSpec as P
    from lightgbm_tpu.parallel.learners import pair_allreduce, shard_map

    n_shards = 4
    n = n_shards * 2 * HIST_CHUNK
    b, leaves = 32, 5
    bins, ghc_t, row_leaf = _workload(n, b=b, leaves=leaves, seed=7)
    mesh = Mesh(np.asarray(jax.devices()[:n_shards]), ("data",))

    def compact_fn(bins_s, ghc_s, rl_s, leaf):
        return pair_allreduce(
            compacted_histograms(bins_s, ghc_s, rl_s, leaf, b))

    def masked_pair_fn(bins_s, ghc_s, rl_s, leaf):
        # pair_allreduce's exact arithmetic, minus the final lossy f32
        # collapse — the (s, c) pair is the object carrying the ~f64
        # agreement guarantee
        hi, lo = masked_histograms_xla(bins_s, ghc_s, rl_s, leaf, b)
        comps = jnp.concatenate([jax.lax.all_gather(hi, "data"),
                                 jax.lax.all_gather(lo, "data")], axis=0)

        def kstep(carry, x):
            s, c = carry
            y = x - c
            t = s + y
            return (t, (t - s) - y), None

        zero = jnp.zeros_like(hi)
        (s, c), _ = jax.lax.scan(kstep, (zero, zero), comps)
        return s, c

    specs = dict(in_specs=(P(None, "data"), P(None, "data"), P("data"),
                           P()), out_specs=P())
    sharded_c = jax.jit(shard_map(compact_fn, mesh=mesh, **specs))
    sharded_m = jax.jit(shard_map(masked_pair_fn, mesh=mesh, **specs))
    serial_full = jax.jit(lambda rl, l: masked_histograms_xla(
        bins, ghc_t, rl, l, b))

    for leaf in range(leaves):
        hd = np.asarray(sharded_c(bins, ghc_t, row_leaf, jnp.int32(leaf)))
        ms, mc = sharded_m(bins, ghc_t, row_leaf, jnp.int32(leaf))
        hm64 = np.asarray(ms).astype(np.float64) \
            - np.asarray(mc).astype(np.float64)
        hs_pair = serial_full(row_leaf, jnp.int32(leaf))
        hs64 = (np.asarray(hs_pair[0]).astype(np.float64)
                + np.asarray(hs_pair[1]).astype(np.float64))
        hs = np.asarray(hs_pair[0] + hs_pair[1])
        # f64 truth for the absolute bar
        mask = (np.asarray(row_leaf) == leaf)
        ref = np.zeros((bins.shape[0], b, 3))
        ref_mass = np.zeros_like(ref)  # per-cell sum of |contributions|
        bh = np.asarray(bins)
        gh = np.asarray(ghc_t).astype(np.float64) * mask[None, :]
        for f_i in range(bins.shape[0]):
            for k in range(3):
                ref[f_i, :, k] = np.bincount(bh[f_i], weights=gh[k],
                                             minlength=b)[:b]
                ref_mass[f_i, :, k] = np.bincount(
                    bh[f_i], weights=np.abs(gh[k]), minlength=b)[:b]
        scale = max(1.0, np.abs(ref).max())
        assert np.abs(hd - ref).max() / scale <= 1e-6
        assert np.abs(hs - ref).max() / scale <= 1e-6
        # masked fixed-order pair reduction: at pair level the sharded
        # reduction reproduces the serial pair within the Kahan bound —
        # a few f32 ulps of each cell's ABSOLUTE mass, independent of
        # shard count or chunk grouping (measured max ~1e-7 relative)
        eps32 = np.finfo(np.float32).eps
        assert np.all(np.abs(hm64 - hs64) <= 4 * eps32 * (ref_mass + 1.0))


def test_data_parallel_compacted_trees_match_serial():
    """End-to-end: the data-parallel learner under forced compaction
    grows trees identical to the serial learner's."""
    from sklearn import datasets
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import DatasetLoader
    from lightgbm_tpu.models.gbdt import create_boosting
    from lightgbm_tpu.objectives import create_objective

    X, y = datasets.load_breast_cancer(return_X_y=True)

    def train(learner):
        cfg = Config(objective="binary", num_leaves=15, learning_rate=0.1,
                     min_data_in_leaf=10, tree_learner=learner, verbose=-1,
                     hist_compaction="true", partitioned_build="false")
        ds = DatasetLoader(cfg).construct_from_matrix(X, label=y)
        obj = create_objective(cfg.objective, cfg)
        obj.init(ds.metadata, ds.num_data)
        g = create_boosting(cfg.boosting_type)
        g.init(cfg, ds, obj, [])
        for _ in range(8):
            if g.train_one_iter(is_eval=False):
                break
        return g

    gs, gd = train("serial"), train("data")
    assert gs.tree_learner._use_compact and gd.tree_learner._use_compact
    assert len(gs.models) == len(gd.models)
    for ta, tb in zip(gs.models, gd.models):
        assert ta.num_leaves == tb.num_leaves
        np.testing.assert_array_equal(ta.split_feature_real,
                                      tb.split_feature_real)
        np.testing.assert_array_equal(ta.threshold_in_bin,
                                      tb.threshold_in_bin)
        np.testing.assert_allclose(ta.leaf_value, tb.leaf_value,
                                   rtol=1e-5, atol=1e-7)


def test_canonical_row_chunks_grid():
    assert [canonical_row_chunks(c) for c in (1, 5, 8, 9, 15, 16, 17, 25,
                                              100, 1000)] \
        == [1, 5, 8, 9, 15, 16, 18, 26, 104, 1024]
    for c in range(1, 3000):
        cc = canonical_row_chunks(c)
        assert cc >= c and (cc - c) / c <= 0.125  # <= 1/8 waste
        assert canonical_row_chunks(cc) == cc  # idempotent


_CACHE_CHILD = r"""
import json, os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
# record every config update: with JAX_COMPILATION_CACHE_DIR set, no
# code path may set a cache directory of its own
dir_updates = []
_update = jax.config.update
def recording_update(name, value):
    if name == "jax_compilation_cache_dir":
        dir_updates.append(value)
    return _update(name, value)
jax.config.update = recording_update
import numpy as np
from lightgbm_tpu.config import Config, compile_cache_hits
from lightgbm_tpu.io.dataset import DatasetLoader
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objectives import create_objective

rng = np.random.RandomState(0)
x = rng.rand(600, 4).astype(np.float32)
y = (x[:, 0] > 0.5).astype(np.float32)
# the default CPU program is pure XLA (no host callback, whose
# custom-call target would be process-local), so it IS served across
# processes
cfg = Config.from_params({"objective": "binary", "num_leaves": 7,
                          "min_data_in_leaf": 5, "metric_freq": 0,
                          "verbose": -1})
ds = DatasetLoader(cfg).construct_from_matrix(x, label=y)
obj = create_objective(cfg.objective, cfg)
obj.init(ds.metadata, ds.num_data)
g = GBDT()
g.init(cfg, ds, obj, [])
t0 = time.time()
assert g.warm_up_fused(2)
compile_s = time.time() - t0
g.train_many(2)
print(json.dumps({"hits": compile_cache_hits(), "compile_s": compile_s,
                  "cache_hit_flag": g.last_compile_cache_hit,
                  "dir_updates": dir_updates,
                  "cache_dir": jax.config.jax_compilation_cache_dir}))
"""


def test_persistent_cache_skips_lowering_in_fresh_process(tmp_path):
    """Second train() in a fresh process must be served by the
    persistent compile cache: cache hits recorded, compile phase
    collapsing toward zero. The cache is placed from outside:
    JAX_COMPILATION_CACHE_DIR is the directory in use and no code sets
    another."""
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jc")
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")

    def run():
        r = subprocess.run([sys.executable, "-c", _CACHE_CHILD],
                           capture_output=True, text=True, timeout=300,
                           env=env)
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    first, second = run(), run()
    assert os.listdir(env["JAX_COMPILATION_CACHE_DIR"])
    for r in (first, second):
        assert r["dir_updates"] == []
        assert r["cache_dir"] == env["JAX_COMPILATION_CACHE_DIR"]
    assert second["hits"] > 0, (first, second)
    assert second["cache_hit_flag"] is True
    # the warm process skips XLA lowering of the cached executables; it
    # still pays trace time, so assert a solid drop rather than zero
    assert second["compile_s"] < max(0.75 * first["compile_s"], 2.0), \
        (first, second)
