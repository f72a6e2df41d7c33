"""Device time of the segment-histogram Pallas kernel (ops/ordered_hist.py
`_seg_hist_tpu`, the only Pallas call on the fused serial path) in the
traced block, per iteration. KERNEL is how the trace shows it: the
Mosaic kernel is the program's only `custom-call` instruction (named
`branch_<bucket>_fun.<n>` after the lax.switch branch it sits in; the
kernel itself carries no name — PERF.md, Open questions)."""

import re

from tracereduce import seconds_of

KERNEL = re.compile(r"seg_hist|custom-call")


def kernel_seconds(ctx):
    return seconds_of(ctx["trace"], KERNEL) if ctx.get("trace") else None


def read(ctx):
    k = kernel_seconds(ctx)
    return None if k is None else 1e3 * k / ctx["block_iterations"]
