"""Device time under the scope `hist_reduce` (models/partitioned.py: the
all-reduce of each segment histogram over the row shards, the root's and
each split's, and the root totals read from the reduced histogram) in
the traced block, per iteration, averaged over the chips. None where no
operation carries the scope (one chip: the reduction is the identity and
leaves no operation)."""

from scopereduce import run_table, seconds_of

WORDS = ("hist_reduce",)


def read(ctx):
    tab = run_table(ctx)
    if not tab or not any(w in WORDS for (w, _, _) in tab["rows"]):
        return None
    return 1e3 * seconds_of(tab, WORDS) / ctx["block_iterations"]
