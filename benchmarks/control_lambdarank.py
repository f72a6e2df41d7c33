#!/usr/bin/env python3
"""The control and the planted faults of the lambdarank reference
(references/lambdarank.py): that reference, growing freely, put in the
program's place, and its answer handed to the comparison a run uses.
Host only (numpy); the benchmark's own runs never call it. It is how the
upper readings of the ranking cell's limits were taken (PERF.md) and
what tests/test_lambdarank.py keeps at a small size.

    python3 benchmarks/control_lambdarank.py --workload <cell> --seeds 1,2 \
        --modes bf16,truncated,padded_docs [--rows N]

Modes:
  none         float64, sound: every number must read (next to) nought
  bf16         the control: every row's lambda and hessian rounded to
               bfloat16 before they are summed into histograms (the
               precision below the float32 the configuration states)
  truncated    pairs beyond the first 512 documents of a query left out
  padded_docs  queries padded to the next multiple of 128 documents, the
               padding counted as label-0 documents at score 0
  half_batch   every second row left out of the trees, all rows scored
  altered      one leaf value of the last tree off by 1 %, in the tree
               only (the score keeps the true value)
  unchanged    the step returns its state unchanged: trees, but score 0
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

MODES = ("none", "bf16", "truncated", "padded_docs", "half_batch", "altered",
         "unchanged")
GRADIENT_MODES = ("bf16", "truncated", "padded_docs")


def stand_in(x, y, fields, params, block, mode, threads=8):
    """(trees, (1, n) score) of `block` iterations as the program would
    hand them over, from the reference run in `mode`."""
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}")
    lambdarank = load_module("references", "lambdarank")
    n = x.shape[0]
    with ThreadPoolExecutor(threads) as pool:
        queries = lambdarank.Queries(
            fields["group"], y, params, fields.get("weight"),
            mode=mode if mode in GRADIENT_MODES else None, pool=pool)
        bounds, bins = reference.prepare(x, params, pool)
        nb = max(len(b) for b in bounds)
        score, trees = np.zeros(n), []
        for k in range(block):
            g, h = queries.gradients(score, pool)
            rows0 = np.arange(0, n, 2) if mode == "half_batch" else None
            tree, _, _ = reference.grow_tree(bins, nb, g, h, params, pool,
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
    return trees, score.astype(np.float32)[None, :]


def one_seed(cell, seed, modes, rows, threads):
    config, traffic = cell["config"], cell["traffic"]
    params = train_params(config, traffic)
    data = dict(config["data"], **({"rows": rows} if rows else {}))
    x, y, fields = make_data(data, seed)
    lambdarank = load_module("references", config["reference"])
    out = {}
    for mode in modes:
        trees, score = stand_in(x, y, fields, params,
                                int(traffic["block_iterations"]), mode, threads)
        numbers = lambdarank.compare(x, y, fields, params, trees, score, threads)
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
