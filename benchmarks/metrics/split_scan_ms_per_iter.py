"""Device time under the scope `split_scan` (scan_leaf: expand_fn +
find_best_split, root and both children of every split) in the traced
block, per iteration."""

from scopereduce import ms_per_iter


def read(ctx):
    return ms_per_iter(ctx, ("split_scan",))
