"""Whole step's share of the chip's peak: the least time the chip could
take for one iteration's algorithmic work over the measured time of one
iteration of the traced block (host clock, whole block, its sync
included). The work is counted from the trees the block produced, never
from the implementation: a histogram learner must read, per visited
row, F bytes of bins and 8 of gradient and hessian (rows visited = N at
the root plus the smaller child of every split), and per row 12 more
for the gradient pass and the score update. The bound is HBM bandwidth
(the arithmetic is a few operations a byte)."""

from reference import rows_visited


def work_bytes(tree, rows, features):
    return rows_visited(tree, rows) * (features + 8) + rows * 12


def read(ctx):
    if not ctx.get("trees") or not ctx.get("block_wall_s"):
        return None
    need = sum(work_bytes(t, ctx["rows"], ctx["features"])
               for t in ctx["trees"]) / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * need / ctx["block_wall_s"]
