"""Device time under the scope `tree_state` (apply_tree_split, segment
bounds, pos_leaf, the histogram cache's subtraction and update,
write_candidate) in the traced block, per iteration."""

from scopereduce import ms_per_iter


def read(ctx):
    return ms_per_iter(ctx, ("tree_state",))
