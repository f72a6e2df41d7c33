#!/usr/bin/env python3
"""The control and the planted faults of the sharded binary reference
(references/sharded_binary.py): that reference, growing freely over its
row shards, put in the program's place, and its answer handed to the
comparison a run uses. Host only (numpy); the benchmark's own runs never
call it. It is how the upper readings of the data-parallel cell's limits
were taken (PERF.md) and what benchmarks/tests/test_sharded_binary.py
keeps at a small size.

    python3 benchmarks/control_sharded_binary.py --workload <cell> \
        --seeds 1,2 --modes bf16,comm_bf16,drop_shard [--rows N]

The modes are `control.py`'s (none, bf16, half_batch, altered,
unchanged; `altered` here in the last tree the reference follows, where
`leaf_value_gap` sees it) and two of the exchange between the shards:

  comm_bf16   each shard's histogram rounded to bfloat16 before the
              sum, and the sum again: `comm_precision=bf16` on the wire
  drop_shard  the second shard's histogram left out of every reduction
              (its rows still split and scored)
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

MODES = PLAIN_MODES + ("comm_bf16", "drop_shard")


def _wire(mode):
    """The fault a mode plants in the exchange (sharded_binary `wire`)."""
    if mode == "comm_bf16":
        return lambda s, hist: round_bf16(hist)
    if mode == "drop_shard":
        return lambda s, hist: hist * 0.0 if s == 1 else hist
    return None


def stand_in(x, y, params, block, mode, threads=8):
    """(trees, (1, n) score) of `block` iterations as the program would
    hand them over, from the sharded reference growing freely in
    `mode`."""
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}")
    ref = load_module("references", "sharded_binary")
    n = x.shape[0]
    shards = ref.shards_of(n, int(params.get("num_machines", 1)))
    wire = _wire(mode)
    with ThreadPoolExecutor(threads) as pool:
        bounds, bins, nb = ref.prepare(x, params, pool)
        score, trees = np.zeros(n), []
        for k in range(block):
            g, h = binary_grad(score, y, params.get("sigmoid", 1.0))
            if mode == "bf16":
                g, h = round_bf16(g), round_bf16(h)
            rows = ([r[::2] for r in shards] if mode == "half_batch"
                    else shards)
            tree, _, _ = ref.grow_tree(bins, nb, g, h, params, pool, rows,
                                       wire=wire)
            tree["threshold"] = np.asarray(
                [bounds[f][t] for f, t in zip(tree["split_feature"],
                                              tree["threshold_in_bin"])])
            score += tree["leaf_value"][ref.leaf_of(tree, bins, pool)]
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
        trees, score = stand_in(x, y, params,
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
