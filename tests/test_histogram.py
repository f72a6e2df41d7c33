"""Histogram op vs a numpy oracle (reference src/io/dense_bin.hpp:16-195),
and the choice of its formulation: a function of the platform alone."""

import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops import histogram as H
from lightgbm_tpu.ops.histogram import build_histograms
from lightgbm_tpu.ops.ordered_hist import bucket_sizes


def _oracle(bins, ghc, b):
    f, n = bins.shape
    k = ghc.shape[1]
    out = np.zeros((f, b, k), dtype=np.float64)
    for fi in range(f):
        for ni in range(n):
            out[fi, bins[fi, ni]] += ghc[ni]
    return out


def test_histogram_matches_oracle(rng):
    f, n, b, k = 5, 300, 16, 3
    bins = rng.randint(0, b, size=(f, n)).astype(np.uint8)
    ghc = rng.randn(n, k).astype(np.float32)
    hist = np.asarray(build_histograms(jnp.asarray(bins), jnp.asarray(ghc), b))
    np.testing.assert_allclose(hist, _oracle(bins, ghc, b), rtol=1e-4, atol=1e-4)


def test_histogram_chunked_equals_unchunked(rng):
    f, n, b, k = 3, 4096, 8, 6
    bins = rng.randint(0, b, size=(f, n)).astype(np.uint8)
    ghc = rng.randn(n, k).astype(np.float32)
    h1 = np.asarray(build_histograms(jnp.asarray(bins), jnp.asarray(ghc), b,
                                     row_chunk=512))
    h2 = np.asarray(build_histograms(jnp.asarray(bins), jnp.asarray(ghc), b,
                                     row_chunk=n))
    np.testing.assert_allclose(h1, h2, rtol=1e-4, atol=1e-4)


def test_masked_rows_do_not_contribute(rng):
    f, n, b = 2, 100, 4
    bins = rng.randint(0, b, size=(f, n)).astype(np.uint8)
    ghc = rng.randn(n, 3).astype(np.float32)
    ghc[50:] = 0.0  # masked rows carry zeros
    hist = np.asarray(build_histograms(jnp.asarray(bins), jnp.asarray(ghc), b))
    np.testing.assert_allclose(hist, _oracle(bins[:, :50], ghc[:50], b),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("mode", ["segment", "einsum"])
def test_block_fold_reproduces_single_pass(rng, mode):
    """Folding row-ordered blocks on the chunk grid continues the
    single-pass Kahan scan to the bit, in the CPU's formulation and in
    the one the out-of-core learner folds with on a TPU."""
    f, n, b, chunk = 4, 2048, 16, 256
    bins = jnp.asarray(rng.randint(0, b, size=(f, n)).astype(np.uint8))
    ghc = jnp.asarray(rng.randn(n, 3).astype(np.float32))
    hi, lo = H.build_histograms_pair(bins, ghc, b, chunk, mode=mode)
    acc = comp = jnp.zeros((f, b, 3), jnp.float32)
    for s, e in ((0, 512), (512, 768), (768, 2048)):
        acc, comp = H.hist_pair_fold_block(acc, comp, bins[:, s:e],
                                           ghc[s:e], b, chunk, mode=mode)
    np.testing.assert_array_equal(
        np.asarray(H.hist_pair_fold_collapse(acc, comp)),
        np.asarray(hi + lo))
    with pytest.raises(ValueError, match="unknown chunk formulation"):
        H.build_histograms(bins, ghc, b, chunk, mode="bincount")


# ------------------------------------------- which formulation runs

@pytest.mark.parametrize("backend,pallas,mode", [
    ("tpu", True, "einsum"), ("cpu", False, "segment"),
    ("gpu", False, "einsum")])
def test_formulation_follows_the_backend(monkeypatch, backend, pallas, mode):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert H.use_pallas() is pallas
    assert H.chunk_mode() == mode


def test_environment_chooses_nothing():
    """The two variables that once chose the formulation and the bucket
    ladder are read by nothing: a process started with both set
    resolves what a process without them does."""
    child = ("import json\n"
             "from lightgbm_tpu.ops import histogram as H\n"
             "from lightgbm_tpu.ops.ordered_hist import bucket_sizes\n"
             "print(json.dumps([H.use_pallas(), H.chunk_mode(),\n"
             "                  bucket_sizes(2816)]))\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo,
               LIGHTGBM_TPU_HIST_MODE="einsum",
               LIGHTGBM_TPU_BUCKET_GROWTH="4")
    r = subprocess.run([sys.executable, "-c", child], env=env, cwd=repo,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    pallas, mode, rungs = json.loads(r.stdout.strip().splitlines()[-1])
    assert (pallas, mode) == (False, "segment")
    # the cells' 13 segment buckets: powers of two, then the whole array
    assert rungs == [2 ** i for i in range(12)] + [2816]
    assert rungs == bucket_sizes(2816)


@functools.lru_cache(maxsize=None)
def _small_model(*extra):
    import lightgbm_tpu as lgb
    params = dict({"objective": "binary", "num_leaves": 7,
                   "min_data_in_leaf": 5, "verbose": -1}, **dict(extra))
    rng = np.random.RandomState(4)
    x = rng.randn(800, 4)
    y = (x[:, 0] * x[:, 1] > 0).astype(np.float64)
    bst = lgb.train(dict(params), lgb.Dataset(x, y, params=dict(params)),
                    num_boost_round=3, verbose_eval=False)
    return bst.gbdt.save_model_to_string(-1)


@pytest.mark.parametrize("key,value", [("hist_mode", "einsum"),
                                       ("hist_frontier", "false")])
def test_removed_selection_keys_are_unknown_parameters(capsys, monkeypatch,
                                                       key, value):
    """A parameter file that still names a removed knob gets what every
    unknown key gets, a warning, and the model it gets without it."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.utils.log import Log
    monkeypatch.setattr(Log, "_level", 0)  # warnings on, whatever ran before
    Config.from_params({key: value})
    assert f"Unknown parameter: {key}" in "".join(capsys.readouterr())
    assert not hasattr(Config(), key)
    assert _small_model((key, value)) == _small_model()
