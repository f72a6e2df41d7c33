"""Device time of the partition Pallas kernel (ops/partition.py
`partition_rows`, `pallas_call(name="partition_rows")`, one call a
split) in the traced block, per iteration. In the trace it is the
instruction `%partition_rows.<n> = ... custom-call(...)`; the scope
`partition` (partition_ms_per_iter) holds it and the decision around it."""

import re

from metrics.seg_hist_ms_per_iter import kernel_seconds

KERNEL = re.compile(r"^partition_rows(\.\d+)? custom-call\b")


def read(ctx):
    k = kernel_seconds(ctx, KERNEL)
    return None if k is None else 1e3 * k / ctx["block_iterations"]
