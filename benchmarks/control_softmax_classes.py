#!/usr/bin/env python3
"""The control and the planted faults of the softmax reference
(references/softmax_classes.py): that reference's learner, growing
freely, put in the program's place, and its answer handed to the
comparison a run uses. Host only (numpy); the benchmark's own runs never
call it. It is how the upper readings of the multiclass cell's limits
were taken (PERF.md) and what tests/test_softmax_classes.py keeps at a
small size.

    python3 benchmarks/control_softmax_classes.py --workload <cell> \
        --seeds 1,2 --modes bf16,half_batch,... [--rows N]

Modes (`control.py`'s five, and three of this objective's own):
  none        float64, sound: every number must read (next to) nought
  bf16        the control: every class's gradients and hessians rounded
              to bfloat16 before they are summed
  half_batch  every second row left out of the trees, all rows scored
  altered     one leaf value of the block's last tree off by 1 %, in the
              tree only (the score keeps the true value)
  unchanged   the step returns its state unchanged: trees, but score 0
  sequential_gradients  the softmax recomputed after every class's tree
              inside an iteration, where it is computed once
  single_hessian        h = p (1 - p), half of this generation's
  classes_swapped       the trees of classes 0 and 1 of the last
              iteration exchanged in the model list

The learner is `reference.grow_tree` growing freely, with the plain
reference's per-column `np.bincount` histogram replaced by
`references/wide_binary.py`'s, as `control_wide_binary.py` does.
"""

import argparse
import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import reference  # noqa: E402
from control import leaf_of  # noqa: E402
from datagen import load_module, make_data, train_params  # noqa: E402

OWN_MODES = ("sequential_gradients", "single_hessian", "classes_swapped")
MODES = ("none", "bf16", "half_batch", "altered", "unchanged") + OWN_MODES


def stand_in(x, y, params, block, mode, threads=8):
    """(trees, (K, n) float32 score) of `block` iterations as the
    program would hand them over: K x block trees in class-major order,
    from the softmax reference's learner run in `mode`."""
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}")
    ref = load_module("references", "softmax_classes")
    wide = ref.wide
    k = int(params["num_class"])
    labels = ref.class_labels(y, k)
    n = x.shape[0]

    def histogram(bins, rows, g, h, nb, pool):
        # `reference.grow_tree` holds (F, N) bins and (F, nb, 3) sums, the
        # wide reference (N, F) and (3, F, nb)
        return np.moveaxis(wide.histogram(bins.T, rows, g, h, nb, pool,
                                          threads), 0, 2)

    plain = reference.histogram
    reference.histogram = histogram
    try:
        with ThreadPoolExecutor(threads) as pool:
            bounds, bins = wide.prepare(x, params, pool)
            bins = bins.T
            nb = max(len(b) for b in bounds)
            score, trees = np.zeros((k, n)), []
            rows0 = np.arange(0, n, 2) if mode == "half_batch" else None
            for _ in range(block):
                for c in range(k):
                    if c == 0 or mode == "sequential_gradients":
                        g, h = ref.softmax_grad(score, labels)
                        if mode == "single_hessian":
                            h = h / 2.0
                        if mode == "bf16":
                            g, h = reference.round_bf16(g), reference.round_bf16(h)
                    tree, _, _ = reference.grow_tree(bins, nb, g[c], h[c],
                                                     params, pool, rows0=rows0)
                    tree["threshold"] = np.asarray(
                        [bounds[f][b] for f, b in zip(tree["split_feature"],
                                                      tree["threshold_in_bin"])])
                    score[c] += tree["leaf_value"][leaf_of(tree, bins)]
                    trees.append(tree)
    finally:
        reference.histogram = plain
    if mode == "altered":
        trees[-1]["leaf_value"][1] *= 1.01
    if mode == "classes_swapped":
        trees[-k], trees[-k + 1] = trees[-k + 1], trees[-k]
    if mode == "unchanged":
        score[:] = 0.0
    return trees, score.astype(np.float32)


def one_seed(cell, seed, modes, rows, threads):
    config, traffic = cell["config"], cell["traffic"]
    params = train_params(config, traffic)
    data = dict(config["data"], **({"rows": rows} if rows else {}))
    x, y, fields = make_data(data, seed)
    ref = load_module("references", config["reference"])
    out = {}
    for mode in modes:
        trees, score = stand_in(x, y, params, int(traffic["block_iterations"]),
                                mode, threads)
        numbers = ref.compare(x, y, fields, params, trees, score, threads)
        out[mode] = numbers
        failing = sorted(name for name, v in numbers.items()
                         if name in cell["limits"]
                         and not v <= cell["limits"][name])
        print(f"seed {seed} mode {mode}: " + " ".join(
            f"{name}={v:.4g}" for name, v in numbers.items())
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
