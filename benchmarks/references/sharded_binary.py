"""Plain reference for `objective=binary` trained data-parallel
(`tree_learner=data` over W row shards), and the comparison that decides
`correct` for a configuration that names it.

The learner and the numbers are `reference.py`'s (own bin bounds from
the configuration's sample, own binning, own gradients from score 0,
numpy float64, *following* the program's (leaf, feature, bin) decisions
and measuring each against its own optimum), with the data-parallel
semantics the reference implementation gives its workers
(data_parallel_tree_learner.cpp): the rows are cut into W contiguous
shards (W = `num_machines`), each leaf keeps its rows a shard, and each
leaf's histogram is the sum of the W shards' histograms, in shard
order; a split divides every shard's rows by the same rule. Summed in
float64 the order changes nothing, so the trees are a one-machine
tree's, which is the guarantee the configuration states.

- **Rows as the program reads them.** The program replaces NaN by 0.0
  before anything is binned (`construct_from_matrix`), so here too a NaN
  is the value 0.0, in the sample the bounds come from and in the bins.
- **What costs rows x columns** is cut so that 32M x 67 fits a run's
  time limit: the bins are one (F, N) uint8 matrix binned a column a
  thread; a histogram is summed a (shard, block of columns) a task on a
  thread pool, and only for the smaller child (its sibling is the
  float64 difference); a leaf's rows are split a shard a task.
- **What float32 statistics cannot tell apart is left out**, as the
  categorical and multiclass references leave it out: a split falls
  short by what its gain lacks of the best gain any open leaf offered
  beyond RESOLVED float32 spacings of the terms the two gains are
  differences of (`terms`), and a child's count is held beyond float32's
  resolution of counts (`count_slack`: a count of 2**24 rows or more,
  the root's at 32M, is known to float32's spacing there).
- **The first FOLLOWED trees of a block are followed**: one, where the
  other references follow two. Two took 150 s at 32M x 67 on the chip's
  host (PR 41), over the 90 s a run can give them. A later tree is the
  program's partition judged without its splits' gains: descended over
  the reference's own bins, leaves recounted, thresholds held, and its
  leaf values held against the reference's own -G/H x rate on that
  partition from its own gradients (`leaf_values`), which then go into
  the reference's score. From score 0 the first tree is exact under
  bfloat16, so the second's leaf values are what show a bfloat16 fault.

`grow_tree(..., forced=None)` grows freely by its own argmax: the
learner that stands in for the program in the control and the planted
faults (control_sharded_binary.py), whose faults in the exchange go in
through `wire`. It imports nothing of the program.
"""

import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
from reference import (binary_grad, binary_logloss, child_counts,  # noqa: E402
                       find_bounds, leaf_gain, leaf_output, sample_rows,
                       split_gains, split_order)
from references.categorical_binary import (RESOLVED, SPACING32,  # noqa: E402
                                           count_slack)

FOLLOWED = 1              # trees of a block followed; the rest applied as given
SPLIT_BLOCK = 1 << 20     # rows of a shard a task descends at a time


# ------------------------------------------------------------------ binning
def prepare(x, params, pool):
    """(bounds, (F, N) uint8 bins, nb): own bounds from the configuration's
    sample (NaN read as 0.0), every row binned by them (a float32 value
    v is in bin #(bounds < v), a bound compared as the largest float32
    not above it, which reads alike for every float32 value)."""
    n, f = x.shape
    idx = sample_rows(n, params["bin_construct_sample_cnt"],
                      params["data_random_seed"])
    sample = np.nan_to_num(x[idx].astype(np.float64), nan=0.0)
    bounds = list(pool.map(
        lambda j: find_bounds(sample[:, j], params["max_bin"]), range(f)))
    bins = np.empty((f, n), np.uint8)

    def one(j):
        b32 = bounds[j].astype(np.float32)
        below = np.where(b32 > bounds[j],
                         np.nextafter(b32, np.float32(-np.inf)), b32)
        bins[j] = below.searchsorted(np.nan_to_num(x[:, j], nan=0.0))
    list(pool.map(one, range(f)))
    return bounds, bins, max(len(b) for b in bounds)


def shards_of(n, w):
    """The W contiguous row blocks, as index arrays (the last may be
    shorter)."""
    size = -(-n // w)
    return [np.arange(lo, min(lo + size, n), dtype=np.int64)
            for lo in range(0, n, size)]


# --------------------------------------------------------------------- tree
def histogram(bins, rows, g, h, nb, pool, wire=None):
    """(F, nb, 3) float64 sums of g, h and 1 per (column, bin) over a
    leaf's rows, given a shard at a time (`rows`: W index arrays): each
    shard's histogram summed by tasks of a block of columns (about as
    many tasks as the pool has threads), then the shards' summed in
    shard order. `wire(shard, hist)`: what a shard's histogram is in the
    sum (the control's planted faults; None: itself)."""
    f = bins.shape[0]
    blocks = max(1, min(f, -(-pool._max_workers // max(len(rows), 1))))
    cols = np.array_split(np.arange(f), blocks)

    def one(task):
        s, c = task
        r = rows[s]
        gs, hs = g[r], h[r]
        out = np.empty((len(c), nb, 3))
        for i, j in enumerate(c):
            b = bins[j][r]
            out[i, :, 0] = np.bincount(b, gs, nb)
            out[i, :, 1] = np.bincount(b, hs, nb)
            out[i, :, 2] = np.bincount(b, minlength=nb)
        return s, c, out
    per = np.zeros((len(rows), f, nb, 3))
    tasks = [(s, c) for s, r in enumerate(rows) if len(r) for c in cols]
    for s, c, out in pool.map(one, tasks):
        per[s, c] = out
    if wire is not None:
        per = np.stack([wire(s, hist) for s, hist in enumerate(per)])
    return per.sum(axis=0)


def split_rows(bins, rows, f, t, pool):
    """Each shard's rows of a leaf divided by `bin <= t` of column f, their
    order kept: (left shards, right shards)."""
    def one(r):
        go = bins[f][r] <= t
        return r[go], r[~go]
    parts = list(pool.map(one, rows))
    return [a for a, _ in parts], [b for _, b in parts]


def terms(hist, gain, p):
    """The size of what a split gain is a difference of: both children's
    G^2 / H, which sum to the gain plus the leaf's own, and the leaf's."""
    tot = hist[0].sum(axis=0)
    return gain + 2.0 * float(leaf_gain(tot[0], tot[1], p["lambda_l1"],
                                        p["lambda_l2"]))


def grow_tree(bins, nb, g, h, p, pool, shards, forced=None, wire=None):
    """One leaf-wise tree over the row shards. Free (forced=None): its
    own argmax. Following: the (leaf, feature, bin) decisions of
    `forced` in their order, each measured against own float64
    histograms. Returns (tree, leaf rows as W index arrays, readings):
    `split_regret` (how far a split taken falls short of the best gain
    any open leaf offered beyond what float32 resolves, over that best)
    and `count_mismatch` (a child's reported count against the recount,
    beyond `count_slack`), each the largest met."""
    rows = [list(shards)]
    hists = [histogram(bins, shards, g, h, nb, pool, wire)]
    gains = [split_gains(hists[0], p)]
    best = [float(gains[0].max())]
    parent_of = [(-1, 0)]                      # leaf -> (node, side)
    sf, tb, lc, rc, ic = [], [], [], [], []
    if forced is not None:
        order, want = split_order(forced), child_counts(forced)
        slack, _ = count_slack(forced)
    regret = count_gap = 0.0
    for i in range(p["num_leaves"] - 1):
        top = max(best)
        if forced is None:
            if top <= 0.0 or not np.isfinite(top):
                break
            leaf = int(np.argmax(best))
            f, t = np.unravel_index(np.argmax(gains[leaf]), gains[leaf].shape)
        else:
            if i >= len(order):
                # the program stopped: sound only if nothing was left
                regret = max(regret, 1.0 if top > 0.0 else 0.0)
                break
            leaf = int(order[i])
            f, t = int(forced["split_feature"][i]), int(forced["threshold_in_bin"][i])
            chosen = gains[leaf][f, t] if 0 <= t < nb - 1 else -np.inf
            if not np.isfinite(chosen) or top <= 0.0:
                regret = 1.0      # not a split this configuration allows
                break
            at = int(np.argmax(best))
            unseen = RESOLVED * SPACING32 * (terms(hists[leaf], chosen, p)
                                             + terms(hists[at], top, p))
            regret = max(regret, min(max(top - chosen - unseen, 0.0) / top,
                                     1.0))
        r_l, r_r = split_rows(bins, rows[leaf], f, t, pool)
        n_l, n_r = sum(map(len, r_l)), sum(map(len, r_r))
        if forced is not None:
            count_gap = max(count_gap, abs(n_l - want[i][0]) - slack[i],
                            abs(n_r - want[i][1]) - slack[i])
            if n_l == 0 or n_r == 0:
                regret = 1.0
                break
        small_left = n_l <= n_r
        h_small = histogram(bins, r_l if small_left else r_r, g, h, nb, pool,
                            wire)
        h_large = hists[leaf] - h_small
        h_l, h_r = (h_small, h_large) if small_left else (h_large, h_small)
        pn, side = parent_of[leaf]
        if pn >= 0:
            (lc if side == 0 else rc)[pn] = i
        right = len(rows)
        sf.append(int(f))
        tb.append(int(t))
        lc.append(~leaf)
        rc.append(~right)
        ic.append(n_l + n_r)
        parent_of[leaf] = (i, 0)
        parent_of.append((i, 1))
        rows[leaf], hists[leaf], gains[leaf] = r_l, h_l, split_gains(h_l, p)
        best[leaf] = float(gains[leaf].max())
        rows.append(r_r)
        hists.append(h_r)
        gains.append(split_gains(h_r, p))
        best.append(float(gains[-1].max()))
    tot = np.stack([hh[0].sum(axis=0) for hh in hists])
    tree = {
        "split_feature": np.asarray(sf, np.int64),
        "threshold_in_bin": np.asarray(tb, np.int64),
        "left_child": np.asarray(lc, np.int64),
        "right_child": np.asarray(rc, np.int64),
        "internal_count": np.asarray(ic, np.int64),
        "leaf_count": np.asarray([sum(map(len, r)) for r in rows], np.int64),
        "leaf_value": leaf_output(tot[:, 0], tot[:, 1], p["lambda_l1"],
                                  p["lambda_l2"])
        * p["learning_rate"] * (len(rows) > 1),
    }
    return tree, rows, {"split_regret": float(regret),
                        "count_mismatch": float(count_gap)}


def leaf_values(leaf, leaves, g, h, p):
    """Own float64 leaf values -G/H x rate (L1 / L2 as `reference.py`) of
    a partition given as each row's leaf id."""
    tot_g = np.bincount(leaf, g, leaves)
    tot_h = np.bincount(leaf, h, leaves)
    return (leaf_output(tot_g, tot_h, p["lambda_l1"], p["lambda_l2"])
            * p["learning_rate"] * (leaves > 1))


def leaf_of(tree, bins, pool):
    """Leaf id of every row: the tree descended over the binned (F, N)
    matrix by `bin <= threshold`, in blocks of rows on the pool."""
    n = bins.shape[1]
    if len(tree["split_feature"]) == 0:
        return np.zeros(n, np.int64)
    feat = np.asarray(tree["split_feature"])
    thr = np.asarray(tree["threshold_in_bin"])

    def descend(lo):
        hi = min(lo + SPLIT_BLOCK, n)
        node = np.zeros(hi - lo, np.int64)
        active = np.arange(hi - lo)
        while len(active):
            nd = node[active]
            left = bins[feat[nd], lo + active] <= thr[nd]
            nxt = np.where(left, tree["left_child"][nd], tree["right_child"][nd])
            node[active] = nxt
            active = active[nxt >= 0]
        return ~node
    return np.concatenate(list(pool.map(descend, range(0, n, SPLIT_BLOCK))))


# --------------------------------------------------------------- comparison
def compare(x, y, fields, params, trees, score_after, threads=None):
    """Follow the first FOLLOWED trees the program grew in its first
    block, from score 0, apply the rest as given, and return the numbers
    `reference.compare` returns (each a worst case over the block):
    `count_mismatch`, `threshold_gap`, `split_regret`, `leaf_value_gap`,
    `loss_gap`, `score_gap`, `score_max_gap`. `score_after` is the
    program's (1, n) train score after the block."""
    if fields:
        raise ValueError(f"the sharded binary reference takes no fields, "
                         f"got {sorted(fields)}")
    sa = np.asarray(score_after, np.float64).reshape(-1)
    n = x.shape[0]
    shards = shards_of(n, int(params.get("num_machines", 1)))
    threads = threads or min(os.cpu_count() or 8, 32)
    sigmoid = params.get("sigmoid", 1.0)
    with ThreadPoolExecutor(threads) as pool:
        bounds, bins, nb = prepare(x, params, pool)
        s_ref = np.zeros(n)       # reference's leaf values
        s_prog = np.zeros(n)      # program's, on the followed partition
        out = {k: 0.0 for k in ("count_mismatch", "threshold_gap",
                                "split_regret", "leaf_value_gap", "loss_gap")}

        def held(name, value):
            out[name] = max(out[name], float(value))

        for k, tree in enumerate(trees):
            v_prog = np.asarray(tree["leaf_value"], np.float64)
            g, h = binary_grad(s_ref, y, sigmoid)
            if k < FOLLOWED:
                own, leaf_rows, rd = grow_tree(bins, nb, g, h, params, pool,
                                               shards, forced=tree)
                held("split_regret", rd["split_regret"])
                held("count_mismatch", rd["count_mismatch"])
                v_own = own["leaf_value"]
                m = len(leaf_rows) - 1          # splits followed
                leaf = np.empty(n, np.int64)
                for lid, parts in enumerate(leaf_rows):
                    for r in parts:
                        leaf[r] = lid
            else:
                # the program's partition, recounted, with own leaf values
                leaf = leaf_of(tree, bins, pool)
                m = len(tree["split_feature"])
                held("count_mismatch", np.max(np.abs(
                    np.bincount(leaf, minlength=len(v_prog))
                    - np.asarray(tree["leaf_count"])) - count_slack(tree)[1]))
                v_own = leaf_values(leaf, len(v_prog), g, h, params)
            if len(v_prog) != len(v_own):
                held("leaf_value_gap", 1.0)
                v_prog = np.resize(v_prog, len(v_own))
            floor = np.maximum(np.abs(v_own), np.median(np.abs(v_own)))
            held("leaf_value_gap", np.max(
                np.abs(v_prog - v_own) / np.where(floor > 0, floor, 1.0)))
            s_ref += v_own[leaf]
            s_prog += v_prog[leaf]
            if m:
                thr = np.asarray(tree["threshold"], np.float64)[:m]
                mine = np.asarray([bounds[f][t] for f, t in zip(
                    tree["split_feature"][:m], tree["threshold_in_bin"][:m])])
                with np.errstate(invalid="ignore"):
                    gap = np.abs(thr - mine) / np.maximum(np.abs(mine), 1.0)
                # NaN: a threshold at a column's last bound (+inf), which
                # no split the configuration allows can take
                held("threshold_gap", np.max(np.nan_to_num(gap, nan=1.0)))
            own_loss = binary_logloss(s_ref, y, sigmoid)
            theirs = binary_logloss(sa if k == len(trees) - 1 else s_prog, y,
                                    sigmoid)
            held("loss_gap", abs(theirs - own_loss) / own_loss)
    norm = float(np.linalg.norm(s_ref))
    out["score_gap"] = abs(float(np.linalg.norm(sa)) - norm) / norm
    out["score_max_gap"] = float(np.max(np.abs(sa - s_ref))
                                 / np.median(np.abs(s_ref)))
    return out
