"""The partition kernel's share of its roofline: the bytes a
leaf-contiguous learner must move to partition the traced block's trees
(at every split the rows of the parent's segment, each read once and
written once) over the peak HBM rate, over the kernel's device time.
The bound is memory: a stable two-way partition computes nothing. A row
is its F bytes of bins, 4 of its index and 12 of gradient, hessian and
in-bag weight, as `seg_hist_roofline` counts them; the 4 bytes of
padding the kernel's (4, N) statistics array carries beside them
(48 B a row at 28 features) are the implementation's and not counted."""

from metrics.partition_rows_ms_per_iter import KERNEL
from metrics.seg_hist_ms_per_iter import kernel_seconds
from reference import rows_partitioned


def need_bytes(trees, features):
    return 2.0 * sum(rows_partitioned(t) for t in trees) * (features + 16)


def read(ctx):
    k = kernel_seconds(ctx, KERNEL)
    if not k or not ctx.get("trees"):
        return None
    need = need_bytes(ctx["trees"], ctx["features"]) / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * need / k
