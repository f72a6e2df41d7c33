"""Device busy time of the traced block under no vocabulary word, per
iteration: operations XLA inserted outside every scoped container, and
other programs in the window. The tracing is complete when this is
small."""

from scopereduce import UNSCOPED, ms_per_iter


def read(ctx):
    return ms_per_iter(ctx, (UNSCOPED,))
