"""Device time of the segment-histogram Pallas kernel (ops/ordered_hist.py
`_seg_hist_tpu`, `pallas_call(name="seg_hist")`) in the traced block, per
iteration. KERNEL is how the trace shows it: a `custom-call` instruction
that carries the kernel's name and the compiler's number,
`%seg_hist.38 = f32[28,256,9]{...} custom-call(...)`, which
`tracereduce.label` shortens to `seg_hist.38 custom-call f32[28,256,9]`.
The pattern is anchored on the name: the partition kernel
(`partition_rows.<n>`, metrics/partition_rows_ms_per_iter.py) is a
custom call too, and any later kernel will be."""

import re

from tracereduce import seconds_of

KERNEL = re.compile(r"^seg_hist(\.\d+)? custom-call\b")


def kernel_seconds(ctx, kernel=KERNEL):
    return seconds_of(ctx["trace"], kernel) if ctx.get("trace") else None


def read(ctx):
    k = kernel_seconds(ctx)
    return None if k is None else 1e3 * k / ctx["block_iterations"]
