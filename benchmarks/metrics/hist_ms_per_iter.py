"""Device time under the scope `hist` (ops/ordered_hist.py
segment_histograms: window, the kernel seg_hist, fold) in the traced
block, per iteration. Less `seg_hist_ms_per_iter` it is the data
movement around the kernel."""

from scopereduce import ms_per_iter


def read(ctx):
    return ms_per_iter(ctx, ("hist",))
