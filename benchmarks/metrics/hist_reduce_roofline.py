"""The histogram all-reduce's share of its roofline: the least time a
chip's interconnect needs for the bytes the block's reductions must
bring it, over the device time under `hist_reduce` (a chip's, averaged).

The work is counted from the trees, never from the implementation: a
data-parallel learner reduces one histogram of F x max_bin x 3 float32
(gradient, hessian, count) for the root and one for each split, and on
a ring of W chips each chip receives 2 (W - 1) / W of every one. W is
the cell's `num_machines`. The peak is the published chip-to-chip
interconnect of one TPU v5e, 1,600 Gbit/s (Google Cloud documentation,
"TPU v5e"), kept here because peaks.json holds no interconnect rate
(the harness refuses a device kind peaks.json lacks, and it lists v5e
alone)."""

from metrics.hist_reduce_ms_per_iter import read as reduce_ms_per_iter

ICI_BYTES_PER_S = 1600e9 / 8


def read(ctx):
    ms = reduce_ms_per_iter(ctx)
    w = int(ctx["params"].get("num_machines", 1))
    if not ms or not ctx.get("trees") or w < 2:
        return None
    per = ctx["features"] * ctx["max_bin"] * 3 * 4 * 2 * (w - 1) / w
    calls = sum(1 + len(t["split_feature"]) for t in ctx["trees"])
    seconds = ms * ctx["block_iterations"] / 1e3
    return 100.0 * calls * per / ICI_BYTES_PER_S / seconds
