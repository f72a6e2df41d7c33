"""MSLR-WEB30K-shaped rows in query groups (no network, so not the real
file; the configuration's `assumed` says so).

`query_sizes(data)`: `queries` sizes that sum to `rows`, the smallest 1,
the largest exactly `max_docs`, log-normal around the mean (sigma 0.75:
sum of n^2 about 1.75 x queries x mean^2, the heavy tail the pairwise
objective pays for), drawn from `base_seed` alone. A rehearsal at fewer
rows keeps the mean: it takes rows // 120 queries and a longest query
of at most a quarter of the rows.

`make(data, seed)`: float32 rows from `base_seed` (a block of 65,536
rows a generator spawned from it, drawn on eight threads); of every 17 columns
5 are small counts (0..9, about a quarter of the rows on one value: no
column is sparse), 2 are quantised to quarters (about 35 distinct
values) and 10 are continuous, as the real features mix counts, flags
and real-valued scores. The relevance label 0-4 cuts a latent score (a
random linear function of the columns + an offset a query + noise) at
the quantiles of the published shares, 52 / 32 / 13 / 2 / 1 %. `--seed`
(any non-negative whole number) draws the order of the columns only:
every seed the same rows, queries and work in another order (PERF.md
section 2).
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

MEAN_DOCS = 120
SIGMA = 0.75
LABEL_SHARES = (0.52, 0.32, 0.13, 0.02, 0.01)


def query_sizes(data):
    n = int(data["rows"])
    q, longest = int(data["queries"]), int(data["max_docs"])
    if n < q * MEAN_DOCS // 2:                       # a rehearsal's rows
        q = max(n // MEAN_DOCS, 2)
        longest = min(longest, max(n // 4, 2))
    rng = np.random.default_rng(int(data["base_seed"]) + 1)
    mu = np.log(n / q) - SIGMA * SIGMA / 2.0
    sizes = np.exp(rng.normal(mu, SIGMA, q))
    while (over := sizes >= longest).any():          # redraw, not clip
        sizes[over] = np.exp(rng.normal(mu, SIGMA, int(over.sum())))
    sizes = np.maximum(np.rint(sizes).astype(np.int64), 2)
    sizes[np.argmax(sizes)] = longest
    sizes[np.argmin(sizes)] = 1
    # the rest absorb what is left to the row count, one document a
    # query at a time, in a drawn order, inside (1, longest)
    free = np.flatnonzero((sizes > 1) & (sizes < longest))
    while (gap := n - int(sizes.sum())) != 0:
        step = 1 if gap > 0 else -1
        ok = free[(sizes[free] + step > 1) & (sizes[free] + step < longest)]
        take = rng.permutation(ok)[:abs(gap)]
        sizes[take] += step
    return sizes.astype(np.int32)


ROW_BLOCK = 1 << 16


def make(data, seed):
    n, f = int(data["rows"]), int(data["features"])
    sizes = query_sizes(data)
    base = int(data["base_seed"])
    rng = np.random.default_rng(base + 2)
    w = rng.standard_normal(f, dtype=np.float32) / np.float32(np.sqrt(f))
    n_counts, n_quarters = 5 * f // 17, 2 * f // 17
    w[:n_counts] /= np.float32(2.0)
    order = np.random.default_rng(int(seed)).permutation(f)
    x = np.empty((n, f), np.float32)
    signal = np.empty(n, np.float32)
    starts = range(0, n, ROW_BLOCK)

    def block(task):
        # a block of rows a generator spawned from base_seed: the same
        # matrix however many threads draw it. Drawn, shaped and put in
        # the seed's column order in place: a second 1.2 GB array would
        # cost more seconds in page faults than the drawing does
        lo, child = task
        xb = x[lo:lo + ROW_BLOCK]
        np.random.default_rng(child).standard_normal(dtype=np.float32, out=xb)
        c = xb[:, :n_counts]
        np.abs(c, out=c)
        np.multiply(c, np.float32(3.0), out=c)
        np.floor(c, out=c)
        np.minimum(c, np.float32(9.0), out=c)
        q = xb[:, n_counts:n_counts + n_quarters]
        np.multiply(q, np.float32(4.0), out=q)
        np.rint(q, out=q)
        np.multiply(q, np.float32(0.25), out=q)
        signal[lo:lo + ROW_BLOCK] = xb @ w
        xb[:] = xb[:, order]
    with ThreadPoolExecutor(8) as pool:
        list(pool.map(block, zip(starts, np.random.SeedSequence(base).spawn(
            len(starts)))))
    offset = np.repeat(rng.standard_normal(len(sizes), dtype=np.float32), sizes)
    latent = (signal + np.float32(0.7) * offset
              + np.float32(0.8) * rng.standard_normal(n, dtype=np.float32))
    cuts = np.quantile(latent, np.cumsum(LABEL_SHARES)[:-1])
    y = np.searchsorted(cuts, latent).astype(np.float32)
    return x, y, {"group": sizes}
