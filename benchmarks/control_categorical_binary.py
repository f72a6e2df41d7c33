#!/usr/bin/env python3
"""The control and the planted faults of the categorical binary
reference (references/categorical_binary.py): that reference, growing
freely, put in the program's place, and its answer handed to the
comparison a run uses. Host only (numpy); the benchmark's own runs never
call it. It is how the upper readings of the airline cell's limits were
taken (PERF.md) and what tests/test_categorical_binary.py keeps at a
small size.

    python3 benchmarks/control_categorical_binary.py --workload <cell> \
        --seeds 1,2 --modes bf16,half_batch,altered,unchanged,as_numeric,bin0_apart [--rows N]

The modes are `control.py`'s (none, bf16, half_batch, altered,
unchanged; `altered` here in the last tree the reference follows, where
`leaf_value_gap` sees it: the score changes by 1 % of one leaf's value on
that leaf's rows alone, which can be far under `score_max_gap`'s limit)
and two of categorical columns:

  as_numeric  the ID columns split by `bin <= t` over their bins, as if
              the ids' count order were an order of values
  bin0_apart  ids outside the kept max_bin get a bin of their own, where
              the rule merges them into bin 0 with the most frequent id
"""

import argparse
import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from control import MODES as PLAIN_MODES  # noqa: E402
from datagen import load_module, make_data, train_params  # noqa: E402
from reference import binary_grad, round_bf16  # noqa: E402

MODES = PLAIN_MODES + ("as_numeric", "bin0_apart")


def stand_in(x, y, fields, params, block, mode, threads=8):
    """(trees, (1, n) score) of `block` iterations as the program would
    hand them over, from the categorical reference growing freely in
    `mode`."""
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}")
    ref = load_module("references", "categorical_binary")
    n = x.shape[0]
    cat = ref.categorical_mask(fields, x.shape[1])
    grow_cat = np.zeros_like(cat) if mode == "as_numeric" else cat
    with ThreadPoolExecutor(threads) as pool:
        keys, bins, nb = ref.prepare(x, params, cat, pool,
                                     apart=mode == "bin0_apart")
        score, trees = np.zeros(n), []
        for k in range(block):
            g, h = binary_grad(score, y, params.get("sigmoid", 1.0))
            if mode == "bf16":
                g, h = round_bf16(g), round_bf16(h)
            rows0 = (np.arange(0, n, 2, dtype=np.int32)
                     if mode == "half_batch" else None)
            tree, _, _ = ref.grow_tree(bins, nb, g, h, grow_cat, params, pool,
                                       rows0=rows0)
            # a node's threshold: the bound, or the id, of its bin (none
            # for bin0_apart's bin of its own)
            tree["threshold"] = np.asarray(
                [keys[f][t] if t < len(keys[f]) else np.nan
                 for f, t in zip(tree["split_feature"], tree["threshold_in_bin"])])
            score += tree["leaf_value"][ref.leaf_of(tree, bins, grow_cat, pool)]
            if mode == "altered" and k == min(block, ref.FOLLOWED) - 1:
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
    ref = load_module("references", config["reference"])
    out = {}
    for mode in modes:
        trees, score = stand_in(x, y, fields, params,
                                int(traffic["block_iterations"]), mode, threads)
        numbers = ref.compare(x, y, fields, params, trees, score, threads)
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
