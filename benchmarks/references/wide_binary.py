"""Plain reference for `objective=binary` over thousands of dense
columns, and the comparison that decides `correct` for a configuration
that names it.

The same learner and the same numbers as `reference.py` (own bin bounds,
own binning, own gradients from score 0, in numpy float64, *following*
the program's (leaf, feature, bin) decisions), with what costs `rows x
columns` done so that 400,000 x 2,000 fits a run's time limit
(benchmarks/README.md "Time budget"; PERF.md PR 33 has the seconds):

- bin bounds: `reference.find_bounds` walks every distinct value of a
  column in a Python loop (50,000 a column: 100M steps at 2,000
  columns). `find_bounds` here jumps from bound to bound over the
  cumulated counts, which gives the same bounds wherever no single value
  holds a mean bin's share (every continuous column); a column that has
  such a value goes to `reference.find_bounds`;
- binning: once, a block of rows at a time, transposed in cache, into a
  row-major (N, F) uint8 matrix: a leaf's rows are then gathered as
  whole 2 KB rows;
- histograms: a block of a leaf's rows a task, all three sums of every
  (column, bin) in one pass (`scatter_rows`), the tasks' tables added
  up, kept as three (F, bins) planes so that the scan along the bins
  runs over contiguous memory; only the smaller child is summed, its
  sibling is the float64 difference;
- only the first FOLLOWED trees of a block are followed; a later one is
  applied as given, as the ranking cell's are.

It imports nothing of the program.
"""

import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

try:  # the scatter runs without the interpreter's lock: threads spread it
    from scipy.sparse._sparsetools import csc_matvecs
except ImportError:                         # the same sums, a thread at a time
    csc_matvecs = None

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import reference  # noqa: E402
from control import leaf_of  # noqa: E402  (a tree descended over binned rows)
from reference import (ZERO, binary_grad, binary_logloss, child_counts,  # noqa: E402
                       leaf_gain, leaf_output, sample_rows, split_order)

FOLLOWED = 2              # trees of a block followed; the rest applied as given
ROW_BLOCK = 1 << 12       # rows binned at a time: a block transposed in cache
HIST_BLOCK = 1 << 11      # rows of a leaf a histogram task sums, at most


def find_bounds(sample, max_bin):
    """`reference.find_bounds`' bounds. Where no value holds a mean
    bin's share the greedy rule closes a bin at the first value whose
    cumulated count reaches the mean of what remains: found by a search
    over the cumulated counts, a step a bound and not a step a value."""
    v = np.where(np.abs(sample) <= ZERO, 0.0, np.asarray(sample, np.float64))
    vals, cnts = np.unique(v, return_counts=True)
    k = int(np.searchsorted(vals, 0.0))
    if 0 < k < len(vals) and vals[k] != 0.0:
        vals, cnts = np.insert(vals, k, 0.0), np.insert(cnts, k, 0)
    total = len(v)
    if len(vals) <= max_bin or (cnts >= total / max_bin).any():
        return reference.find_bounds(sample, max_bin)
    cum = np.cumsum(cnts)
    ends, base, rest_bins = [], 0, max_bin
    while len(ends) < max_bin - 1:
        mean = (total - base) / rest_bins
        i = int(np.searchsorted(cum, base + int(np.ceil(mean)), side="left"))
        if i > len(vals) - 2:
            break
        ends.append(i)
        base = int(cum[i])
        rest_bins -= 1
    ends = np.asarray(ends, np.intp)
    return np.append((vals[ends] + vals[ends + 1]) / 2.0, np.inf)


def prepare(x, cfg, pool):
    """Own bin bounds from the configuration's sample, then all rows
    binned, row-major (N, F) uint8: bin = number of bounds strictly
    below the value. The rows are float32, so a bound is compared as the
    largest float32 not above it: `bound < value` reads the same for
    every float32 value, and no block is widened to float64 first."""
    n, f = x.shape
    idx = sample_rows(n, cfg["bin_construct_sample_cnt"], cfg["data_random_seed"])
    sample = np.empty((f, len(idx)), x.dtype)

    def take(lo):
        sample[:, lo:lo + ROW_BLOCK] = x[idx[lo:lo + ROW_BLOCK]].T
    list(pool.map(take, range(0, len(idx), ROW_BLOCK)))
    bounds = [find_bounds(sample[j], cfg["max_bin"]) for j in range(f)]
    below = bounds
    if x.dtype == np.float32:
        below = []
        for b in bounds:
            b32 = b.astype(np.float32)
            below.append(np.where(b32 > b, np.nextafter(b32, np.float32(-np.inf)),
                                  b32))
    bins = np.empty((n, f), np.uint8)

    def block(lo):
        xb = np.nan_to_num(np.ascontiguousarray(x[lo:lo + ROW_BLOCK].T),
                           copy=False, nan=0.0)
        out = np.empty(xb.shape, np.uint8)
        for j in range(f):
            out[j] = below[j].searchsorted(xb[j])
        bins[lo:lo + ROW_BLOCK] = out.T
    list(pool.map(block, range(0, n, ROW_BLOCK)))
    return bounds, bins


def scatter_rows(sub, stats, nb, ones):
    """(F * nb, 3) float64: stats[i] added at (column j, bin sub[i, j])
    for every row i and column j, the rows in their order: the product
    of the one-hot matrix of (column, bin) by row with the statistics
    (`ones`: that matrix's non-zeros, rows x columns of them at least)."""
    k, f = sub.shape
    code = sub + (np.arange(f, dtype=np.int32) * nb)      # (k, F) int32
    out = np.zeros((f * nb, 3))
    if csc_matvecs is None:
        for c in range(3):
            out[:, c] = np.bincount(code.ravel(), np.repeat(stats[:, c], f),
                                    f * nb)
        return out
    csc_matvecs(f * nb, k, 3, np.arange(0, k * f + 1, f, dtype=np.int32),
                code.ravel(), ones[:k * f], stats.ravel(), out.ravel())
    return out


def histogram(bins, rows, g, h, nb, pool, threads=8):
    """(3, F, nb) float64 sums of g, h and 1 over `rows` (all rows:
    None) per (column, bin); `bins` row-major. A block of the rows a
    task, of a size that gives every thread one."""
    n, f = bins.shape
    k = n if rows is None else len(rows)
    step = min(max(-(-k // threads), HIST_BLOCK // 8), HIST_BLOCK)
    ones = np.ones(min(step, k) * f)

    def task(lo):
        r = slice(lo, lo + step) if rows is None else rows[lo:lo + step]
        gs = g[r]
        return scatter_rows(bins[r], np.stack([gs, h[r], np.ones(len(gs))], 1),
                            nb, ones)
    total = np.zeros((f * nb, 3))
    for part in pool.map(task, range(0, k, step)):
        total += part
    return np.ascontiguousarray(np.moveaxis(total.reshape(f, nb, 3), 2, 0))


def plane_gains(hist, p):
    """`reference.split_gains` on the (3, F, nb) planes: (F, nb - 1) gain
    of 'bin <= t goes left', -inf where a child breaks the minima or the
    gain does not reach min_gain_to_split (the totals are column 0's)."""
    left = np.cumsum(hist, axis=2)[:, :, :-1]
    tot = hist[:, 0, :].sum(axis=1)
    right = tot[:, None, None] - left
    l1, l2 = p["lambda_l1"], p["lambda_l2"]
    gain = (leaf_gain(left[0], left[1], l1, l2)
            + leaf_gain(right[0], right[1], l1, l2)
            - leaf_gain(tot[0], tot[1], l1, l2))
    ok = ((left[2] >= p["min_data_in_leaf"])
          & (right[2] >= p["min_data_in_leaf"])
          & (left[1] >= p["min_sum_hessian_in_leaf"])
          & (right[1] >= p["min_sum_hessian_in_leaf"])
          & (gain >= p["min_gain_to_split"]) & (gain > 0.0))
    return np.where(ok, gain, -np.inf)


def follow_tree(bins, nb, g, h, p, pool, forced, threads=8):
    """Take the (leaf, feature, bin) decisions of `forced`, a tree the
    program grew, in their order, and measure each against own float64
    histograms (`reference.grow_tree`'s following mode). Returns (leaf
    values, row indices of each leaf, readings): `split_regret`, how far
    the gain of a split taken is from the best any open leaf offered,
    and `count_mismatch`, how far a child's reported count is from the
    recount."""
    hist0 = histogram(bins, None, g, h, nb, pool, threads)
    rows = [np.arange(bins.shape[0])]
    hists, gains = [hist0], [plane_gains(hist0, p)]
    best = [float(gains[0].max())]
    order, want = split_order(forced), child_counts(forced)
    regret = count_gap = 0.0
    for i in range(p["num_leaves"] - 1):
        top = max(best)
        if i >= len(order):
            # the program stopped: sound only if nothing was left
            regret = max(regret, 1.0 if top > 0.0 else 0.0)
            break
        leaf = int(order[i])
        f, t = int(forced["split_feature"][i]), int(forced["threshold_in_bin"][i])
        chosen = gains[leaf][f, t] if 0 <= t < nb - 1 else -np.inf
        regret = max(regret, min((top - chosen) / top, 1.0) if top > 0.0 else 1.0)
        if not np.isfinite(chosen):
            break             # not a split this configuration allows
        r = rows[leaf]
        go_left = bins[r, f] <= t
        r_l, r_r = r[go_left], r[~go_left]
        count_gap = max(count_gap, abs(len(r_l) - want[i][0]),
                        abs(len(r_r) - want[i][1]))
        if len(r_l) == 0 or len(r_r) == 0:
            regret = 1.0
            break
        small_left = len(r_l) <= len(r_r)
        h_small = histogram(bins, r_l if small_left else r_r, g, h, nb, pool,
                            threads)
        h_large = hists[leaf] - h_small
        h_l, h_r = (h_small, h_large) if small_left else (h_large, h_small)
        g_l, g_r = pool.map(lambda hh: plane_gains(hh, p), (h_l, h_r))
        rows[leaf], hists[leaf], gains[leaf] = r_l, h_l, g_l
        best[leaf] = float(g_l.max())
        rows.append(r_r)
        hists.append(h_r)
        gains.append(g_r)
        best.append(float(g_r.max()))
    tot = np.stack([hh[:, 0, :].sum(axis=1) for hh in hists])
    values = (leaf_output(tot[:, 0], tot[:, 1], p["lambda_l1"], p["lambda_l2"])
              * p["learning_rate"] * (len(rows) > 1))
    return values, rows, {"split_regret": float(regret),
                          "count_mismatch": float(count_gap)}


def compare(x, y, fields, params, trees, score_after, threads=None):
    """Follow the first FOLLOWED trees the program grew in its first
    block, from score 0, apply the rest as given, and return the numbers
    `reference.compare` returns (each a worst case over the block).
    `score_after` is the program's (1, n) train score after the block.

    A tree applied as given is descended over the reference's own bins,
    its leaves recounted (`count_mismatch`) and its thresholds held to
    the reference's bounds (`threshold_gap`); its leaf values are taken
    as they are, so the score and the loss it leaves are checked and its
    split choices and leaf sums are not. Two trees at least are
    followed, because the bfloat16 control is exact in the first.
    """
    if fields:
        raise ValueError(f"the wide binary reference takes no fields, got "
                         f"{sorted(fields)}")
    sa = np.asarray(score_after, np.float64).reshape(-1)
    threads = threads or min(os.cpu_count() or 8, 12)
    sigmoid = params.get("sigmoid", 1.0)
    with ThreadPoolExecutor(threads) as pool:
        bounds, bins = prepare(x, params, pool)
        nb = max(len(b) for b in bounds)
        s_ref = np.zeros(x.shape[0])   # reference's leaf values
        s_prog = np.zeros(x.shape[0])  # program's, on the followed partition
        out = {k: 0.0 for k in ("count_mismatch", "threshold_gap",
                                "split_regret", "leaf_value_gap", "loss_gap")}

        def held(name, value):
            out[name] = max(out[name], float(value))

        for k, tree in enumerate(trees):
            v_prog = np.asarray(tree["leaf_value"], np.float64)
            if k < FOLLOWED:
                g, h = binary_grad(s_ref, y, sigmoid)
                v_own, leaf_rows, rd = follow_tree(bins, nb, g, h, params, pool,
                                                   tree, threads)
                held("split_regret", rd["split_regret"])
                held("count_mismatch", rd["count_mismatch"])
                m = len(leaf_rows) - 1          # splits followed
                if len(v_prog) != len(v_own):
                    held("leaf_value_gap", 1.0)
                    v_prog = np.resize(v_prog, len(v_own))
                floor = np.maximum(np.abs(v_own), np.median(np.abs(v_own)))
                held("leaf_value_gap", np.max(
                    np.abs(v_prog - v_own) / np.where(floor > 0, floor, 1.0)))
                for lid, r in enumerate(leaf_rows):
                    s_ref[r] += v_own[lid]
                    s_prog[r] += v_prog[lid]
            else:
                leaf = leaf_of(tree, bins.T)
                m = len(tree["split_feature"])
                held("count_mismatch", np.max(np.abs(
                    np.bincount(leaf, minlength=len(v_prog))
                    - np.asarray(tree["leaf_count"]))))
                s_ref += v_prog[leaf]
                s_prog += v_prog[leaf]
            mine = np.asarray([bounds[f][t] for f, t in zip(
                tree["split_feature"][:m], tree["threshold_in_bin"][:m])])
            thr = np.asarray(tree["threshold"], np.float64)[:m]
            held("threshold_gap", np.max(
                np.abs(thr - mine) / np.maximum(np.abs(mine), 1.0), initial=0.0))
            own_loss = binary_logloss(s_ref, y, sigmoid)
            theirs = binary_logloss(sa if k == len(trees) - 1 else s_prog, y,
                                    sigmoid)
            held("loss_gap", abs(theirs - own_loss) / own_loss)
    norm = float(np.linalg.norm(s_ref))
    out["score_gap"] = abs(float(np.linalg.norm(sa)) - norm) / norm
    out["score_max_gap"] = float(np.max(np.abs(sa - s_ref))
                                 / np.median(np.abs(s_ref)))
    return out
