"""Device time under the scopes `gradients` and `score_update` (the
boosting loop around the builder: gradients, pads, in-bag weights, stat
block; row_leaf scatter, leaf-value gather, score add) in the traced
block, per iteration."""

from scopereduce import ms_per_iter


def read(ctx):
    return ms_per_iter(ctx, ("gradients", "score_update"))
