"""Device time of the leaf operations under the scope `partition`
(models/partitioned.py _partition_segment: window_in, decide,
destinations, invert, move, write_back) in the traced block, per
iteration."""

from scopereduce import ms_per_iter


def read(ctx):
    return ms_per_iter(ctx, ("partition",))
