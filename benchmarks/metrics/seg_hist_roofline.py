"""The histogram kernel's share of its roofline: the bytes a histogram
must read (rows visited x (F bytes of bins + 12 of gradient, hessian
and in-bag weight)) over the peak HBM rate, over the kernel's device
time. The bound is memory: the algorithm needs three additions per
(row, feature); the one-hot contraction the kernel feeds the MXU
(2 x 9 x bins flops per bin byte) is the implementation's choice and is
not counted as work."""

from metrics.seg_hist_ms_per_iter import kernel_seconds
from reference import rows_visited


def read(ctx):
    k = kernel_seconds(ctx)
    if not k or not ctx.get("trees"):
        return None
    visited = sum(rows_visited(t, ctx["rows"]) for t in ctx["trees"])
    need = visited * (ctx["features"] + 12) / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * need / k
