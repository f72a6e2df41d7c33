"""Device busy time of the traced block outside the histogram kernel,
per iteration: partition gathers, split_stats, split scans, lax.switch
bodies, gradients and the score update."""

from metrics.seg_hist_ms_per_iter import kernel_seconds


def read(ctx):
    k = kernel_seconds(ctx)
    if k is None:
        return None
    return 1e3 * (ctx["trace"]["busy_s"] - k) / ctx["block_iterations"]
