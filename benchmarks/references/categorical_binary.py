"""Plain reference for `objective=binary` with direct categorical splits
(LightGBM's one-vs-rest rule of this generation), and the comparison
that decides `correct` for a configuration that names it.

The learner and the numbers are `reference.py`'s (own bin bounds, own
binning, own gradients from score 0, numpy float64, *following* the
program's (leaf, feature, bin) decisions), with the columns that
`fields["categorical_feature"]` names binned and split as categories:

- **Rows as the program reads them.** The matrix path of the program
  replaces NaN by 0.0 before anything is binned (`construct_from_matrix`),
  so here too a NaN is the value 0.0 in every column: in a numerical
  column it falls in the bin of 0, in a categorical one it is the id 0.
- **Own categorical bins**, from the same sample rows as the numerical
  bounds: a value is the id `trunc(v)` (toward zero, C's cast), the ids
  are ordered by descending count in the sample, ties by ascending id,
  and the first `max_bin` are kept, id k in bin k. Every other id, seen
  in the sample or not, is in bin 0 with the most frequent one. Where
  the sample changes sign and holds no id 0, the id 0 is counted with 0
  rows, as LightGBM's bin finder inserts the zero (bin.cpp).
- **One-vs-rest gains** (feature_histogram.hpp FindBestThresholdFor-
  Categorical): for every bin t of a categorical column, left = the rows
  in bin t, right = the rest;
      gain = G_t^2 / H_t + (G - G_t)^2 / (H - H_t) - G^2 / H
  under min_data_in_leaf and min_sum_hessian_in_leaf on both sides, with
  bin t's own hessian sum on the left (the raw per-bin hessian the
  program's ops/split.py cites), L1 / L2 as `reference.py` has them.
  A numerical column keeps `bin <= t goes left`.
- **A categorical node's threshold** is the id of its bin: `threshold_gap`
  compares the program's threshold with the id the reference keeps in
  that bin, as it compares a numerical one with the reference's bound.
- **`split_regret` leaves out what float32 statistics cannot tell
  apart**, as `softmax_classes.py` does: a split falls short by what its
  gain lacks of the best gain any open leaf offered beyond RESOLVED
  float32 spacings of the terms the two gains are differences of. A
  one-vs-rest split peels one id off a large segment, so the segment's
  G^2 / H stays near the root's (3e5 at 30M rows from score 0) while the
  gains its late splits are chosen by are a thousandth of it, and the
  program's float32 gain of such a split is known to about a spacing of
  that term.
- **`count_mismatch` leaves out what float32 counts cannot hold**
  (`count_slack`): the program sums a leaf's rows in float32, so a count
  of m >= 2**24 rows is known to float32's spacing at m, and a child's
  count, its parent's less its sibling's, carries its ancestors'
  roundings: RESOLVED spacings an ancestor of 2**24 rows or more. Under
  2**24 rows the slack is 0 and counts are held exactly.

What costs rows x columns is done so that 30M rows fit a run's time
limit (benchmarks/README.md "Time budget"; PERF.md has the seconds): the
bins are one (F, N) uint8 matrix, a histogram is summed a column a
thread and only for the smaller child (its sibling is the float64
difference), and a leaf's rows are split in blocks on threads. The
first FOLLOWED trees of a block are followed (two, as the other
references: from score 0 the first tree is exact under bfloat16, so the
second is what shows it); a later one is applied as given (descended
over the reference's own bins, leaves recounted, thresholds held, leaf
values taken as they are).

`grow_tree(..., forced=None)` grows freely by its own argmax: the
learner that stands in for the program in the control and the planted
faults (control_categorical_binary.py). It imports nothing of the
program.
"""

import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
from reference import (ZERO, binary_grad, binary_logloss, child_counts,  # noqa: E402
                       find_bounds, leaf_gain, leaf_output, sample_rows,
                       split_order)

FOLLOWED = 2              # trees of a block followed; the rest applied as given
SPLIT_BLOCK = 1 << 20     # rows of a leaf a thread splits at a time
SPACING32 = 2.0 ** -23    # of float32 at 1
RESOLVED = 16             # spacings of its terms a gain's lead has to pass to
#                           count, softmax_classes.py's


# ------------------------------------------------------------------ binning
def kept_ids(sample, max_bin):
    """The ids a categorical column keeps, id k in bin k: the sample's
    ids (NaN read as 0.0, truncated toward zero) by descending count,
    ties by ascending id, the first `max_bin` of them."""
    v = np.nan_to_num(np.asarray(sample, np.float64), nan=0.0)
    ids, cnt = np.unique(np.trunc(np.where(np.abs(v) <= ZERO, 0.0, v)),
                         return_counts=True)
    if (v < -ZERO).any() and (v > ZERO).any() and not (ids == 0.0).any():
        k = int(np.searchsorted(ids, 0.0))
        ids, cnt = np.insert(ids, k, 0.0), np.insert(cnt, k, 0)
    order = np.argsort(-cnt, kind="stable")
    return ids[order[:max_bin]]


def id_bins(col, kept, apart=False):
    """uint8 bins of a categorical column: bin k where trunc(v) is kept
    id k, bin 0 elsewhere (`apart`: bin len(kept) elsewhere, one of the
    planted faults)."""
    t = np.trunc(np.nan_to_num(np.asarray(col, np.float64), nan=0.0))
    order = np.argsort(kept, kind="stable")
    srt = kept[order]
    pos = np.minimum(np.searchsorted(srt, t), len(srt) - 1)
    return np.where(srt[pos] == t, order[pos],
                    len(kept) if apart else 0).astype(np.uint8)


def prepare(x, params, cat, pool, apart=False):
    """Own bins of every column: (keys, (F, N) uint8 bins, nb). keys[j]
    is column j's upper bounds (numerical) or kept ids (categorical),
    what a node's threshold is held to; nb the histogram's width."""
    n, f = x.shape
    idx = sample_rows(n, params["bin_construct_sample_cnt"],
                      params["data_random_seed"])
    sample = np.nan_to_num(x[idx].astype(np.float64), nan=0.0)
    keys = [kept_ids(sample[:, j], params["max_bin"]) if cat[j]
            else find_bounds(sample[:, j], params["max_bin"]) for j in range(f)]
    bins = np.empty((f, n), np.uint8)

    def one(j):
        col = x[:, j]
        if cat[j]:
            bins[j] = id_bins(col, keys[j], apart)
            return
        # float32 rows: a bound compared as the largest float32 not above
        # it reads `bound < value` alike for every float32 value
        b32 = keys[j].astype(np.float32)
        below = np.where(b32 > keys[j], np.nextafter(b32, np.float32(-np.inf)),
                         b32)
        bins[j] = below.searchsorted(np.nan_to_num(col, nan=0.0))
    list(pool.map(one, range(f)))
    nb = max(len(k) for k in keys) + (1 if apart else 0)
    return keys, bins, nb


# --------------------------------------------------------------------- tree
def histogram(bins, rows, g, h, nb, pool):
    """(3, F, nb) float64 sums of g, h and 1 over `rows` (all: None) per
    (column, bin), a column a thread."""
    gs, hs = (g, h) if rows is None else (g[rows], h[rows])

    def one(j):
        b = bins[j] if rows is None else bins[j][rows]
        return (np.bincount(b, gs, nb), np.bincount(b, hs, nb),
                np.bincount(b, minlength=nb).astype(np.float64))
    return np.stack([np.stack(c) for c in pool.map(one, range(len(bins)))], 1)


def split_gains(hist, cat, p):
    """(F, nb) gain of every split of the leaf whose histogram is `hist`:
    `bin <= t` goes left in a numerical column (t < nb - 1), `bin == t`
    in a categorical one; -inf where a side breaks min_data_in_leaf or
    min_sum_hessian_in_leaf or the gain does not reach min_gain_to_split."""
    tot = hist[:, 0, :].sum(axis=1)
    left = np.where(cat[None, :, None], hist, np.cumsum(hist, axis=2))
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
    ok[~cat, -1] = False
    return np.where(ok, gain, -np.inf)


def terms(hist, gain, p):
    """The size of what a split gain is a difference of: both children's
    G^2 / H, which sum to the gain plus the leaf's own, and the leaf's."""
    tot = hist[:, 0, :].sum(axis=1)
    return gain + 2.0 * float(leaf_gain(tot[0], tot[1], p["lambda_l1"],
                                        p["lambda_l2"]))


def spacing32(m):
    """float32's spacing at the whole number m, 0 below 2**24 (exact)."""
    return 2.0 ** (np.floor(np.log2(m)) - 23) if m >= 2 ** 24 else 0.0


def count_slack(tree):
    """(slack of each split's children's counts, slack of each leaf's
    count): rows a count of the program's `tree` may be off by, RESOLVED
    float32 spacings of every ancestor's count from the root down."""
    m = len(tree["split_feature"])
    kids = np.zeros(m)
    leaves = np.zeros(m + 1)
    todo = [(0, RESOLVED * spacing32(tree["internal_count"][0]))] if m else []
    while todo:
        node, own = todo.pop()
        kids[node] = own + RESOLVED * spacing32(tree["internal_count"][node])
        for child in (tree["left_child"][node], tree["right_child"][node]):
            if child >= 0:
                todo.append((int(child), kids[node]))
            else:
                leaves[~int(child)] = kids[node]
    return kids, leaves


def split_rows(bins, rows, f, t, is_cat, pool):
    """The leaf's `rows` divided by the split (f, t), their order kept:
    (left, right), in blocks on threads."""
    def one(lo):
        r = rows[lo:lo + SPLIT_BLOCK]
        b = bins[f][r]
        go = b == t if is_cat else b <= t
        return r[go], r[~go]
    parts = list(pool.map(one, range(0, len(rows), SPLIT_BLOCK)))
    return (np.concatenate([a for a, _ in parts]),
            np.concatenate([b for _, b in parts]))


def grow_tree(bins, nb, g, h, cat, p, pool, forced=None, rows0=None):
    """One leaf-wise tree. Free (forced=None): its own argmax, the split
    with the largest gain of any open leaf. Following: the (leaf,
    feature, bin) decisions of `forced`, a tree the program grew, in
    their order, each measured against own float64 histograms. Returns
    (tree, leaf rows, readings): the tree in LightGBM's arrays (leaf
    values times learning_rate), the row indices of each leaf, and the
    largest `split_regret` (how far a split taken falls short of the
    best gain any open leaf offered beyond what float32 resolves, over
    that best) and `count_mismatch` (a child's reported count against
    the recount)."""
    n = bins.shape[1]
    rows = [np.arange(n, dtype=np.int32) if rows0 is None else rows0]
    hists = [histogram(bins, rows0, g, h, nb, pool)]
    gains = [split_gains(hists[0], cat, p)]
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
            chosen = gains[leaf][f, t] if 0 <= t < nb else -np.inf
            if not np.isfinite(chosen) or top <= 0.0:
                regret = 1.0      # not a split this configuration allows
                break
            at = int(np.argmax(best))
            unseen = RESOLVED * SPACING32 * (terms(hists[leaf], chosen, p)
                                             + terms(hists[at], top, p))
            regret = max(regret, min(max(top - chosen - unseen, 0.0) / top,
                                     1.0))
        r_l, r_r = split_rows(bins, rows[leaf], f, t, cat[f], pool)
        if forced is not None:
            count_gap = max(count_gap, abs(len(r_l) - want[i][0]) - slack[i],
                            abs(len(r_r) - want[i][1]) - slack[i])
            if len(r_l) == 0 or len(r_r) == 0:
                regret = 1.0
                break
        small_left = len(r_l) <= len(r_r)
        h_small = histogram(bins, r_l if small_left else r_r, g, h, nb, pool)
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
        ic.append(len(rows[leaf]))
        parent_of[leaf] = (i, 0)
        parent_of.append((i, 1))
        g_l, g_r = pool.map(lambda hh: split_gains(hh, cat, p), (h_l, h_r))
        rows[leaf], hists[leaf], gains[leaf] = r_l, h_l, g_l
        best[leaf] = float(g_l.max())
        rows.append(r_r)
        hists.append(h_r)
        gains.append(g_r)
        best.append(float(g_r.max()))
    tot = np.stack([hh[:, 0, :].sum(axis=1) for hh in hists])
    tree = {
        "split_feature": np.asarray(sf, np.int64),
        "threshold_in_bin": np.asarray(tb, np.int64),
        "left_child": np.asarray(lc, np.int64),
        "right_child": np.asarray(rc, np.int64),
        "internal_count": np.asarray(ic, np.int64),
        "leaf_count": np.asarray([len(r) for r in rows], np.int64),
        "leaf_value": leaf_output(tot[:, 0], tot[:, 1], p["lambda_l1"],
                                  p["lambda_l2"])
        * p["learning_rate"] * (len(rows) > 1),
    }
    return tree, rows, {"split_regret": float(regret),
                        "count_mismatch": float(count_gap)}


def leaf_of(tree, bins, cat, pool=None):
    """Leaf id of every row: the tree descended over the binned (F, N)
    matrix, `==` at a categorical node, `<=` at a numerical one; in
    blocks of rows on the pool's threads where one is given."""
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
            b = bins[feat[nd], lo + active]
            left = np.where(cat[feat[nd]], b == thr[nd], b <= thr[nd])
            nxt = np.where(left, tree["left_child"][nd], tree["right_child"][nd])
            node[active] = nxt
            active = active[nxt >= 0]
        return ~node
    blocks = range(0, n, SPLIT_BLOCK)
    return np.concatenate(list(map(descend, blocks) if pool is None
                               else pool.map(descend, blocks)))


# --------------------------------------------------------------- comparison
def categorical_mask(fields, f):
    cat = np.zeros(f, bool)
    cat[list(fields.get("categorical_feature", ()))] = True
    return cat


def compare(x, y, fields, params, trees, score_after, threads=None):
    """Follow the first FOLLOWED trees the program grew in its first
    block, from score 0, apply the rest as given, and return the numbers
    `reference.compare` returns (each a worst case over the block):
    `count_mismatch`, `threshold_gap`, `split_regret`, `leaf_value_gap`,
    `loss_gap`, `score_gap`, `score_max_gap`. `score_after` is the
    program's (1, n) train score after the block."""
    unknown = sorted(set(fields) - {"categorical_feature"})
    if unknown:
        raise ValueError(f"the categorical binary reference takes "
                         f"categorical_feature alone, got {unknown}")
    sa = np.asarray(score_after, np.float64).reshape(-1)
    cat = categorical_mask(fields, x.shape[1])
    threads = threads or min(os.cpu_count() or 8, 12)
    sigmoid = params.get("sigmoid", 1.0)
    with ThreadPoolExecutor(threads) as pool:
        keys, bins, nb = prepare(x, params, cat, pool)
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
                own, leaf_rows, rd = grow_tree(bins, nb, g, h, cat, params,
                                               pool, forced=tree)
                held("split_regret", rd["split_regret"])
                held("count_mismatch", rd["count_mismatch"])
                v_own = own["leaf_value"]
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
                leaf = leaf_of(tree, bins, cat, pool)
                m = len(tree["split_feature"])
                held("count_mismatch", np.max(np.abs(
                    np.bincount(leaf, minlength=len(v_prog))
                    - np.asarray(tree["leaf_count"])) - count_slack(tree)[1]))
                s_ref += v_prog[leaf]
                s_prog += v_prog[leaf]
            mine = np.asarray([keys[f][t] if t < len(keys[f]) else np.nan
                               for f, t in zip(tree["split_feature"][:m],
                                               tree["threshold_in_bin"][:m])])
            thr = np.asarray(tree["threshold"], np.float64)[:m]
            held("threshold_gap", np.max(np.nan_to_num(
                np.abs(thr - mine) / np.maximum(np.abs(mine), 1.0), nan=1.0),
                initial=0.0))
            own_loss = binary_logloss(s_ref, y, sigmoid)
            theirs = binary_logloss(sa if k == len(trees) - 1 else s_prog, y,
                                    sigmoid)
            held("loss_gap", abs(theirs - own_loss) / own_loss)
    norm = float(np.linalg.norm(s_ref))
    out["score_gap"] = abs(float(np.linalg.norm(sa)) - norm) / norm
    out["score_max_gap"] = float(np.max(np.abs(sa - s_ref))
                                 / np.median(np.abs(s_ref)))
    return out
