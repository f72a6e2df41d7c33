#!/usr/bin/env python3
"""The control and the planted faults of the wide binary reference
(references/wide_binary.py): that reference, growing freely, put in the
program's place, and its answer handed to the comparison a run uses.
Host only (numpy); the benchmark's own runs never call it. It is how the
upper readings of the Epsilon cell's limits were taken (PERF.md) and
what tests/test_wide_binary.py keeps at a small size.

    python3 benchmarks/control_wide_binary.py --workload <cell> --seeds 1,2 \
        --modes bf16,half_batch,altered,unchanged [--rows N]

The modes are `control.py`'s (none, bf16, half_batch, altered,
unchanged) and so is the learner (`control.stand_in`:
`reference.grow_tree` growing freely), with the plain reference's
bounds, binning and per-column `np.bincount` histogram replaced by
those of `references/wide_binary.py`: the same bounds, bins and sums,
which at 2,000 columns the plain ones would take minutes a tree for.
"""

import argparse
import json
import os
import sys
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import control  # noqa: E402
import reference  # noqa: E402
from control import MODES  # noqa: E402
from datagen import load_module, make_data, train_params  # noqa: E402


def stand_in(x, y, params, block, mode, threads=8):
    """(trees, (1, n) score) of `block` iterations as the program would
    hand them over: `control.stand_in`, the plain reference run in
    `mode`, with the wide reference's bounds, binning and histogram in
    the plain ones' place."""
    wide = load_module("references", "wide_binary")

    def prepare(x, cfg, pool):
        # `reference.grow_tree` holds (F, N) bins, the wide reference (N, F)
        bounds, bins = wide.prepare(x, cfg, pool)
        return bounds, bins.T

    def histogram(bins, rows, g, h, nb, pool):
        # ... and (F, nb, 3) sums where the wide reference has (3, F, nb)
        return np.moveaxis(wide.histogram(bins.T, rows, g, h, nb, pool,
                                          threads), 0, 2)

    plain = reference.prepare, reference.histogram
    reference.prepare, reference.histogram = prepare, histogram
    try:
        trees, score = control.stand_in(x, y, params, block, mode, threads)
    finally:
        reference.prepare, reference.histogram = plain
    return trees, score[None, :]


def one_seed(cell, seed, modes, rows, threads):
    config, traffic = cell["config"], cell["traffic"]
    params = train_params(config, traffic)
    data = dict(config["data"], **({"rows": rows} if rows else {}))
    x, y, fields = make_data(data, seed)
    wide = load_module("references", config["reference"])
    out = {}
    for mode in modes:
        trees, score = stand_in(x, y, params, int(traffic["block_iterations"]),
                                mode, threads)
        numbers = wide.compare(x, y, fields, params, trees, score, threads)
        out[mode] = numbers
        failing = sorted(k for k, v in numbers.items()
                         if k in cell["limits"] and not v <= cell["limits"][k])
        print(f"seed {seed} mode {mode}: " + " ".join(
            f"{k}={v:.4g}" for k, v in numbers.items())
              + f" | fails: {failing or 'nothing'}", flush=True)
    return out


def main(argv=None):
    from run import load_cell
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--modes", default=",".join(MODES[1:]))
    ap.add_argument("--rows", type=int)
    ap.add_argument("--threads", type=int, default=8)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    print(json.dumps({seed: one_seed(cell, int(seed), args.modes.split(","),
                                     args.rows, args.threads)
                      for seed in args.seeds.split(",")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
