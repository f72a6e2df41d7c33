"""MSLR-WEB30K-shaped rows with their relevance grade as a class label,
and no query structure: `generators/mslr_like.py`'s rows and labels to
the bit (the same `rows`, `features`, `queries`, `max_docs`, `base_seed`
give the ranking cell's matrix and its grades 0-4 at 52 / 32 / 13 / 2 /
1 %; `--seed` draws the order of the columns only), returned as `(x, y)`
without the `group` field. A pointwise learner over graded relevance
(McRank) has no use for the queries: the offset a query that the latent
score carries is noise to it, as a query's difficulty is in the real
file.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
from datagen import load_module  # noqa: E402


def make(data, seed):
    x, y, _ = load_module("generators", "mslr_like").make(data, seed)
    return x, y
