"""Rows shaped like the training days of LightGBM's parallel experiment
(docs/Experiments.rst, "Parallel Experiment": the Criteo terabyte click
logs, each of the 26 categorical features replaced by its CTR and its
count over the first ten days; no network, so not the real file; the
configuration's `assumed` says so). 67 float32 columns:

    I1..I13       heavy-tailed integer counts, each missing (NaN) on its
                  own share of the rows (INT_MISSING)
    C1..C26 ctr   the smoothed click-through rate of the row's category
                  over the first ten days
    C1..C26 cnt   how often that category was seen in those days
    X1, X2        counts of two crossed category pairs (the two columns
                  the docs count beyond 13 + 2 x 26)

A categorical column's category is drawn Zipf-skewed over its own
cardinality (CARDINALITY, the public release's, capped at 2**20), and
its ctr and count are looked up from the column's table: a category's
count over the ten days is Poisson in its share of them, its clicks
Binomial in its true rate, and the ctr (clicks + 1) / (count + 30). The
label is Bernoulli in a logit of per-category effects of twelve of the
columns and of the log counts, near 3.4 % positive: the terabyte logs
are not subsampled.

`make(data, seed)`: the rows come from `base_seed` alone: the columns'
tables from a generator a column, the rows a block of ROW_BLOCK at a
time on THREADS threads, each block from a generator of its own (so the
rows are the same however many threads draw them), written row-major a
block at a time; `--seed` (any non-negative whole number) draws where
each column is written, so every seed is the same rows and the same work
in another column order. Returns (x, y).
"""

import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

INTS = 13
CATS = 26
EXTRA = 2
COLUMNS = INTS + 2 * CATS + EXTRA             # 67
# share of the rows each integer column is missing on (the public
# release's, rounded)
INT_MISSING = (0.45, 0.0, 0.21, 0.22, 0.03, 0.22, 0.04, 0.0005, 0.04,
               0.45, 0.04, 0.77, 0.22)
# log-scale of each integer count: median exp(mu) - 1, spread sigma
INT_MU = (0.8, 1.5, 2.4, 1.8, 8.0, 4.0, 1.6, 2.6, 3.9, 0.3, 1.2, 0.2, 1.7)
INT_SIGMA = (1.2, 1.8, 1.6, 1.0, 2.2, 1.9, 1.4, 1.1, 1.5, 0.6, 1.0, 0.8, 1.2)
# categories a column has (the Kaggle release's, capped at 2**20) and
# the Zipf exponent of their popularity
CARDINALITY = (1460, 583, 1 << 20, 1 << 20, 305, 24, 12517, 633, 3, 93145,
               5683, 1 << 20, 3194, 27, 14992, 1 << 20, 10, 5652, 2173, 4,
               1 << 20, 18, 15, 286181, 105, 142572)
ZIPF = 1.1
EFFECT_COLUMNS = (0, 1, 3, 5, 8, 9, 12, 14, 16, 19, 22, 24)   # of C1..C26
EFFECT_SD = 0.45
BASE_LOGIT = -5.25  # the label's: near 3.4 % positive
CTR_LOGIT = -3.35   # a category's rate in the ten days, before its effect
TEN_DAYS = 1.0      # rows the first ten days held, per row drawn
CROSS = (1 << 12, 1 << 10)   # the two crossed columns' id counts
THREADS = 16
ROW_BLOCK = 1 << 18


def _zipf(rng, k, n):
    """n category ids in [0, k), id i drawn with weight (i + 1)**-ZIPF
    (continuous inverse of the tail sum: exact in shape, not in the
    last digit of a rare id's share)."""
    u = rng.random(n)
    a = 1.0 - ZIPF
    top = (k + 1.0) ** a
    r = ((top - 1.0) * u + 1.0) ** (1.0 / a) - 1.0
    return np.minimum(r.astype(np.int64), k - 1)


def _zipf_share(k):
    """Each id's share of the draws `_zipf` makes."""
    edges = np.arange(k + 1, dtype=np.float64) + 1.0
    a = 1.0 - ZIPF
    cdf = (edges ** a - 1.0) / ((k + 1.0) ** a - 1.0)
    return np.diff(cdf)


def _table(rng, k, n, effect):
    """(ctr, count, effect) of a column's k categories over the first
    ten days of n rows."""
    # the effects first: what the row count changes (how many draws the
    # Poisson and Binomial counts take) must not move the label's model
    eff = rng.normal(0.0, EFFECT_SD, k) if effect else np.zeros(k)
    share = _zipf_share(k)
    count = rng.poisson(share * n * TEN_DAYS)
    p = 1.0 / (1.0 + np.exp(-(CTR_LOGIT + eff)))
    clicks = rng.binomial(count, p)
    ctr = (clicks + 1.0) / (count + 30.0)
    return ctr.astype(np.float32), count.astype(np.float32), eff


class _Tables:
    """What every row block shares, each column's own generator spawned
    from `base_seed`: a categorical column's (ctr, count, effect) of its
    categories over the first ten days of n rows, and the crossed pairs'
    counts."""

    def __init__(self, base, n, pool):
        seeds = np.random.SeedSequence(base).spawn(CATS + EXTRA)
        self.cat = list(pool.map(
            lambda c: _table(np.random.default_rng(seeds[c]), CARDINALITY[c],
                             n, c in EFFECT_COLUMNS), range(CATS)))
        share = np.outer(_zipf_share(CROSS[0]),
                         _zipf_share(CROSS[1])).reshape(-1)
        self.cross = [np.random.default_rng(seeds[CATS + e]).poisson(
            share * n * TEN_DAYS).astype(np.float32) for e in range(EXTRA)]


def _block(t, rng, cols):
    """One block of rows into `cols` (F, rows), columns in the order
    I1..I13, C1..C26 ctr, C1..C26 count, X1, X2; returns the rows'
    label logit (float64)."""
    n = cols.shape[1]
    logit = np.full(n, BASE_LOGIT)
    for c in range(INTS):
        v = np.floor(np.exp(rng.normal(INT_MU[c], INT_SIGMA[c], n))) - 1.0
        if c == 1:
            v -= 2.0 * (rng.random(n) < 0.05)        # I2 reaches -3
        logit += 0.04 * np.log1p(np.maximum(v, 0.0))
        v[rng.random(n) < INT_MISSING[c]] = np.nan
        cols[c] = v
    for c, (ctr, count, eff) in enumerate(t.cat):
        ids = _zipf(rng, CARDINALITY[c], n)
        cols[INTS + c] = ctr[ids]
        cols[INTS + CATS + c] = count[ids]
        logit += eff[ids]
    for e, count in enumerate(t.cross):
        # a crossed pair: two independent Zipf draws' joint count
        pair = _zipf(rng, CROSS[0], n) * CROSS[1] + _zipf(rng, CROSS[1], n)
        cols[INTS + 2 * CATS + e] = count[pair]
    return logit


def require_row_sharded_binning():
    """The deployment's rule, checked before a row is drawn: each chip
    bins its own row block and no device holds the whole matrix. A
    program without row-sharded binning (`lightgbm_tpu.io.dataset`'s
    `RowShards`) would upload the whole 8.6 GB float32 matrix to one
    chip and reshape it there, more than a chip holds, after minutes of
    host work and compilation; the run stops here instead, with a
    non-zero exit and this message. Checked where the program is loaded
    in this process (run.py imports it first); the host-only control
    loads none and draws the rows as they are."""
    dataset = sys.modules.get("lightgbm_tpu.io.dataset")
    if dataset is not None and not hasattr(dataset, "RowShards"):
        raise SystemExit("criteo_like: this program cannot bin a row shard "
                         "on its own chip (no lightgbm_tpu.io.dataset."
                         "RowShards); the configuration's deployment needs it")


def make(data, seed):
    n, f = int(data["rows"]), int(data["features"])
    if f != COLUMNS:
        raise ValueError(f"criteo_like has {COLUMNS} columns, not {f}")
    require_row_sharded_binning()
    base = int(data["base_seed"])
    order = np.argsort(np.random.default_rng(int(seed)).permutation(COLUMNS))
    x = np.empty((n, f), np.float32)
    y = np.empty(n, np.float32)
    starts = range(0, n, ROW_BLOCK)

    def block(task):
        lo, child = task
        rng = np.random.default_rng(child)
        cols = np.empty((f, min(ROW_BLOCK, n - lo)), np.float32)
        logit = _block(t, rng, cols)
        x[lo:lo + ROW_BLOCK] = cols[order].T      # the seed's column order
        y[lo:lo + ROW_BLOCK] = rng.random(len(logit)) < 1.0 / (
            1.0 + np.exp(-logit))
    with ThreadPoolExecutor(THREADS) as pool:
        t = _Tables(base, n, pool)
        list(pool.map(block, zip(starts, np.random.SeedSequence(
            [base, 1]).spawn(len(starts)))))
    return x, y
