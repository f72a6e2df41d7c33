"""Plain reference for `objective=multiclass` (softmax over K classes, K
trees an iteration), and the comparison that decides `correct` for a
configuration that names it.

numpy float64, nothing of the program: own bin bounds, own binning, a
(K, N) score from 0. An iteration is one gradient pass and then K
trees, LightGBM's order (gbdt.cpp:210-245 computes the gradients once
and then loops the classes):

    p   = softmax(score) over the class axis, from the score at the
          START of the iteration, for all K trees of it
    g_k = p_k - [y = k]
    h_k = 2 p_k (1 - p_k)          (this LightGBM generation's hessian,
                                    multiclass_objective.hpp; not
                                    p_k (1 - p_k))

and for each class in the booster's class-major order (the tree of
iteration t and class k is `trees[t * K + k]`) a leaf-wise histogram
tree on (g_k, h_k), leaf value -G / (H + lambda_l2) x learning_rate,
added to score[k]. After the K-th tree the multi-class log-loss
    mean over rows of  logsumexp(score[:, i]) - score[y_i, i]
is read.

It *follows* the program's trees as `reference.py` does (every (leaf,
feature, bin) decision measured against the best gain any open leaf
offered, both children recounted, thresholds held to its own bounds,
every leaf value recomputed) for the first FOLLOWED iterations, 2 x K
trees: the second iteration is the first whose gradients couple the
classes through a softmax that is not uniform, and the first in which
bfloat16 statistics are not exact (from score 0 every g is 0.2 or -0.8
and every h 0.32, which bfloat16 rounds by one common factor). A later
iteration's trees are applied as given, as `wide_binary.py` and
`lambdarank.py` do: descended over the reference's own bins, leaves
recounted, thresholds held, leaf values taken as they are, so the score
and the loss they leave are checked and their split choices are not.

`split_regret` leaves out what float32 statistics cannot tell apart
(`follow_tree`, RESOLVED). From score 0, with no average to boost
from, a rare class's largest leaf keeps a gradient sum whose G^2 / H is
10^4 times the gains its trees' late splits are chosen by (grade 4, 1 %
of the rows: 22,047 against 1.85), so the program's float32 gain of
that leaf, a difference of terms of that size, is known to 0.006, 0.3 %
of the best gain on offer, and which side of a neighbour it falls on
turns with the order of the columns (PERF.md section 2 has the seed that
showed it). The regret of a split is what it falls short by beyond
RESOLVED float32 spacings of the terms the two gains are differences of.

What costs rows x columns is `wide_binary.py`'s: rows binned once in
blocks into a row-major uint8 matrix, all three sums of a block of a
leaf's rows in one pass, the smaller child summed and its sibling
subtracted (benchmarks/README.md "Time budget"; PERF.md PR 35 has the
seconds).
"""

import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
from control import leaf_of  # noqa: E402  (a tree descended over binned rows)
from datagen import load_module  # noqa: E402

wide = load_module("references", "wide_binary")

FOLLOWED = 2              # iterations followed (K trees each); the rest applied
SPACING32 = 2.0 ** -23    # of float32 at 1
RESOLVED = 16             # spacings of its terms a gain's lead has to pass to count:
#                           the largest lead the program took over 50 seeds was 5.4


def softmax_grad(score, y):
    """(g, h), each (K, N): softmax gradients of the multi-class
    log-loss for class labels `y` (N,) int, from one (K, N) score."""
    e = np.exp(score - score.max(axis=0))
    p = e / e.sum(axis=0)
    h = 2.0 * p * (1.0 - p)
    p[y, np.arange(score.shape[1])] -= 1.0
    return p, h


def multi_logloss(score, y):
    """Mean of -log softmax(score)[y_i, i] over the rows."""
    top = score.max(axis=0)
    lse = top + np.log(np.exp(score - top).sum(axis=0))
    return float(np.mean(lse - score[y, np.arange(score.shape[1])]))


def class_labels(y, k):
    """`y` as class indices; a label outside 0..k-1 is an error."""
    labels = np.asarray(y).astype(np.int64)
    if (labels != np.asarray(y)).any() or labels.min() < 0 or labels.max() >= k:
        raise ValueError(f"labels must be whole numbers in [0, {k})")
    return labels


def terms(hist, gain, p):
    """The size of what a split gain is a difference of: both children's
    G^2 / H, which sum to the gain plus the leaf's own, and the leaf's."""
    tot = hist[:, 0, :].sum(axis=1)
    return gain + 2.0 * float(wide.leaf_gain(tot[0], tot[1], p["lambda_l1"],
                                             p["lambda_l2"]))


def follow_tree(bins, nb, g, h, p, pool, forced, threads=8):
    """`wide_binary.follow_tree` with one difference: a split's regret is
    how far its gain is from the best any open leaf offered *beyond what
    float32 resolves*, RESOLVED spacings of the two gains' terms. Returns
    (leaf values, row indices of each leaf, readings)."""
    hist0 = wide.histogram(bins, None, g, h, nb, pool, threads)
    rows = [np.arange(bins.shape[0])]
    hists, gains = [hist0], [wide.plane_gains(hist0, p)]
    best = [float(gains[0].max())]
    order, want = wide.split_order(forced), wide.child_counts(forced)
    regret = count_gap = 0.0
    for i in range(p["num_leaves"] - 1):
        at = int(np.argmax(best))
        top = best[at]
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
        unseen = RESOLVED * SPACING32 * (terms(hists[leaf], chosen, p)
                                         + terms(hists[at], top, p))
        regret = max(regret, min(max(top - chosen - unseen, 0.0) / top, 1.0))
        r = rows[leaf]
        go_left = bins[r, f] <= t
        r_l, r_r = r[go_left], r[~go_left]
        count_gap = max(count_gap, abs(len(r_l) - want[i][0]),
                        abs(len(r_r) - want[i][1]))
        if len(r_l) == 0 or len(r_r) == 0:
            regret = 1.0
            break
        small_left = len(r_l) <= len(r_r)
        h_small = wide.histogram(bins, r_l if small_left else r_r, g, h, nb,
                                 pool, threads)
        h_large = hists[leaf] - h_small
        h_l, h_r = (h_small, h_large) if small_left else (h_large, h_small)
        g_l, g_r = pool.map(lambda hh: wide.plane_gains(hh, p), (h_l, h_r))
        rows[leaf], hists[leaf], gains[leaf] = r_l, h_l, g_l
        best[leaf] = float(g_l.max())
        rows.append(r_r)
        hists.append(h_r)
        gains.append(g_r)
        best.append(float(g_r.max()))
    tot = np.stack([hh[:, 0, :].sum(axis=1) for hh in hists])
    values = (wide.leaf_output(tot[:, 0], tot[:, 1], p["lambda_l1"],
                               p["lambda_l2"])
              * p["learning_rate"] * (len(rows) > 1))
    return values, rows, {"split_regret": float(regret),
                          "count_mismatch": float(count_gap)}


def compare(x, y, fields, params, trees, score_after, threads=None):
    """Follow the K x FOLLOWED trees of the program's first iterations
    from score 0, apply the later iterations' as given, and return the
    numbers `reference.compare` returns, each a worst case over the
    block and over all K classes: `count_mismatch`, `threshold_gap`,
    `split_regret`, `leaf_value_gap`, `loss_gap` (multi-class log-loss
    after each iteration, relative: the program's leaf values on the
    followed partition against the reference's; after the last
    iteration the program's own score), `score_gap` and `score_max_gap`
    over the whole (K, n) `score_after`."""
    if fields:
        raise ValueError(f"the softmax reference takes no fields, got "
                         f"{sorted(fields)}")
    k = int(params.get("num_class", 1))
    sa = np.asarray(score_after, np.float64)
    if k < 2 or sa.ndim != 2 or len(sa) != k or len(trees) % k:
        raise ValueError(
            f"the softmax reference follows K >= 2 trees an iteration, got "
            f"num_class {k}, a score of shape {sa.shape} and {len(trees)} "
            "trees: one tree an iteration is reference.py's to judge")
    labels = class_labels(y, k)
    threads = threads or min(os.cpu_count() or 8, 12)
    with ThreadPoolExecutor(threads) as pool:
        bounds, bins = wide.prepare(x, params, pool)
        nb = max(len(b) for b in bounds)
        s_ref = np.zeros(sa.shape)     # reference's leaf values
        s_prog = np.zeros(sa.shape)    # program's, on the followed partition
        out = {name: 0.0 for name in ("count_mismatch", "threshold_gap",
                                      "split_regret", "leaf_value_gap",
                                      "loss_gap")}

        def held(name, value):
            out[name] = max(out[name], float(value))

        iterations = len(trees) // k
        for t in range(iterations):
            if t < FOLLOWED:        # one gradient pass for the K trees
                g, h = softmax_grad(s_ref, labels)
            for c in range(k):
                tree = trees[t * k + c]
                v_prog = np.asarray(tree["leaf_value"], np.float64)
                if t < FOLLOWED:
                    v_own, leaf_rows, rd = follow_tree(
                        bins, nb, g[c], h[c], params, pool, tree, threads)
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
                        s_ref[c, r] += v_own[lid]
                        s_prog[c, r] += v_prog[lid]
                else:
                    leaf = leaf_of(tree, bins.T)
                    m = len(tree["split_feature"])
                    held("count_mismatch", np.max(np.abs(
                        np.bincount(leaf, minlength=len(v_prog))
                        - np.asarray(tree["leaf_count"]))))
                    s_ref[c] += v_prog[leaf]
                    s_prog[c] += v_prog[leaf]
                mine = np.asarray([bounds[f][b] for f, b in zip(
                    tree["split_feature"][:m], tree["threshold_in_bin"][:m])])
                thr = np.asarray(tree["threshold"], np.float64)[:m]
                held("threshold_gap", np.max(
                    np.abs(thr - mine) / np.maximum(np.abs(mine), 1.0),
                    initial=0.0))
            own_loss = multi_logloss(s_ref, labels)
            theirs = multi_logloss(sa if t == iterations - 1 else s_prog,
                                   labels)
            held("loss_gap", abs(theirs - own_loss) / own_loss)
    norm = float(np.linalg.norm(s_ref))
    out["score_gap"] = abs(float(np.linalg.norm(sa)) - norm) / norm
    out["score_max_gap"] = float(np.max(np.abs(sa - s_ref))
                                 / np.median(np.abs(s_ref)))
    return out
