"""bench.py's `make_data` recipe (copied, not imported): standard-normal
float32 features, a random linear logit plus 0.5 x noise, the label its
sign, drawn directly in float32 from `default_rng`.

The rows come from the configuration's `base_seed`; `--seed` (any
non-negative whole number) draws the order of the columns. Every seed
so gives the program the same rows and the same amount of work, in
another order: the trees of two seeds are the same trees with the
features renamed. With fresh rows for every seed the work differed by
seed (PERF.md section 2: 3.1 % between the quartiles of six seeds,
against 0.005 % between two runs of one seed), because a split whose
segment falls just over one of the builder's power-of-two buckets
costs twice what one just under it does.
"""

import numpy as np


def make(data, seed):
    n, f = int(data["rows"]), int(data["features"])
    rng = np.random.default_rng(int(data["base_seed"]))
    x = rng.standard_normal((n, f), dtype=np.float32)
    w = rng.standard_normal(f, dtype=np.float32) / np.float32(np.sqrt(f))
    logit = x @ w + np.float32(0.5) * rng.standard_normal(n, dtype=np.float32)
    order = np.random.default_rng(int(seed)).permutation(f)
    return np.ascontiguousarray(x[:, order]), (logit > 0).astype(np.float32)
