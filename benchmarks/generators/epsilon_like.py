"""Epsilon-shaped rows: few rows, thousands of dense continuous columns
(no network, so not `epsilon_normalized` itself; the configuration's
`assumed` says so).

`make(data, seed)`: float32 rows from `base_seed` (a block of 16,384
rows a generator spawned from it, drawn on eight threads, so the matrix
is the same however many threads draw it). Every column is a standard
normal, so every column is continuous and fills all of its bins; each
row is then scaled to unit L2 norm, as the published file's rows are.
The label is the sign of a noisy linear logit of the scaled row, whose
weights fall off as 1 / sqrt(column + 1): a few columns carry much of
the signal and most carry a little, as engineered feature vectors do.
Balanced by symmetry. `--seed` (any non-negative whole number) draws the
order of the columns only: every seed the same rows and the same work in
another order (PERF.md section 2).
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROW_BLOCK = 1 << 14


def make(data, seed):
    n, f = int(data["rows"]), int(data["features"])
    base = int(data["base_seed"])
    rng = np.random.default_rng(base + 1)
    w = rng.standard_normal(f, dtype=np.float32)
    w /= np.sqrt(np.arange(1, f + 1, dtype=np.float32))
    w *= np.float32(np.sqrt(f) / np.linalg.norm(w))    # x @ w ~ N(0, 1)
    order = np.random.default_rng(int(seed)).permutation(f)
    x = np.empty((n, f), np.float32)
    logit = np.empty(n, np.float32)
    starts = range(0, n, ROW_BLOCK)

    def block(task):
        # drawn, scaled and put in the seed's column order in place: a
        # second 3.2 GB array would cost more in page faults than the draw
        lo, child = task
        xb = x[lo:lo + ROW_BLOCK]
        np.random.default_rng(child).standard_normal(dtype=np.float32, out=xb)
        xb /= np.sqrt(np.einsum("ij,ij->i", xb, xb))[:, None]
        logit[lo:lo + ROW_BLOCK] = xb @ w
        xb[:] = np.take(xb, order, axis=1)
    with ThreadPoolExecutor(8) as pool:
        list(pool.map(block, zip(starts, np.random.SeedSequence(base).spawn(
            len(starts)))))
    logit += np.float32(0.5) * rng.standard_normal(n, dtype=np.float32)
    return x, (logit > 0).astype(np.float32)
