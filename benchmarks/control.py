#!/usr/bin/env python3
"""The control and the planted faults: the plain reference, growing
freely, put in the program's place, and its answer handed to the same
comparison a run uses. Host only (numpy); the benchmark's own runs never
call it. It is how the limits' upper readings were taken (PERF.md) and
what tests/test_harness.py keeps at a small size.

    python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 \
        --modes bf16,half_batch,altered [--rows N] [--procs K]

Modes:
  none        float64, sound: every number must read (next to) nought
  bf16        the control: gradient and hessian rounded to bfloat16
              before they are summed — the precision below the float32
              the configuration states, and what an MXU contraction
              without the three-term split computes
  half_batch  every second row left out of the trees (sums, counts and
              leaf means over the rest), all rows scored
  altered     one leaf value of the last tree off by 1 %, in the tree
              only (the score keeps the true value)
  unchanged   the step returns its state unchanged: trees, but score 0
"""

import argparse
import json
import multiprocessing
import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import reference  # noqa: E402
from datagen import make_data, train_params  # noqa: E402

MODES = ("none", "bf16", "half_batch", "altered", "unchanged")


def leaf_of(tree, bins):
    """Leaf id of every row: descend the tree over the binned matrix."""
    n = bins.shape[1]
    if len(tree["split_feature"]) == 0:
        return np.zeros(n, np.int64)
    node = np.zeros(n, np.int64)
    active = np.arange(n)
    while len(active):
        nd = node[active]
        left = bins[tree["split_feature"][nd], active] <= tree["threshold_in_bin"][nd]
        nxt = np.where(left, tree["left_child"][nd], tree["right_child"][nd])
        node[active] = nxt
        active = active[nxt >= 0]
    return ~node


def stand_in(x, y, cfg, block, mode, threads=8):
    """(trees, score) of `block` iterations as the program would hand
    them over, from the reference run in `mode`."""
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}")
    n = x.shape[0]
    with ThreadPoolExecutor(threads) as pool:
        bounds, bins = reference.prepare(x, cfg, pool)
        nb = max(len(b) for b in bounds)
        score, trees = np.zeros(n), []
        for k in range(block):
            g, h = reference.binary_grad(score, y, cfg.get("sigmoid", 1.0))
            if mode == "bf16":
                g, h = reference.round_bf16(g), reference.round_bf16(h)
            rows0 = np.arange(0, n, 2) if mode == "half_batch" else None
            tree, _, _ = reference.grow_tree(bins, nb, g, h, cfg, pool,
                                             rows0=rows0)
            tree["threshold"] = np.asarray(
                [bounds[f][t] for f, t in zip(tree["split_feature"],
                                              tree["threshold_in_bin"])])
            score += tree["leaf_value"][leaf_of(tree, bins)]
            if mode == "altered" and k == block - 1:
                tree["leaf_value"][1] *= 1.01
            trees.append(tree)
    if mode == "unchanged":
        score[:] = 0.0
    return trees, score.astype(np.float32)


def one_seed(task):
    cell, seed, modes, rows, threads = task
    config, traffic = cell["config"], cell["traffic"]
    cfg = train_params(config, traffic)
    data = dict(config["data"], **({"rows": rows} if rows else {}))
    x, y, _ = make_data(data, seed)   # the binary reference takes no fields
    out = {}
    for mode in modes:
        trees, score = stand_in(x, y, cfg, int(traffic["block_iterations"]),
                                mode, threads)
        numbers = reference.compare(x, y, cfg, trees, score, threads)
        out[mode] = numbers
        failing = sorted(k for k, v in numbers.items()
                         if k in cell["limits"] and not v <= cell["limits"][k])
        print(f"seed {seed} mode {mode}: " + " ".join(
            f"{k}={v:.4g}" for k, v in numbers.items())
              + f" | fails: {failing or 'nothing'}", flush=True)
    return seed, out


def main(argv=None):
    from run import load_cell
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--modes", default="bf16,half_batch,altered")
    ap.add_argument("--rows", type=int)
    ap.add_argument("--procs", type=int, default=1)
    ap.add_argument("--threads", type=int, default=8)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    tasks = [(cell, int(s), args.modes.split(","), args.rows, args.threads)
             for s in args.seeds.split(",")]
    if args.procs > 1:
        with multiprocessing.get_context("spawn").Pool(args.procs) as pool:
            results = pool.map(one_seed, tasks)
    else:
        results = [one_seed(t) for t in tasks]
    print(json.dumps({str(seed): out for seed, out in results}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
