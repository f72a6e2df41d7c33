"""Plain reference: a leaf-wise histogram gradient-boosting learner in
numpy float64, and the comparison that decides `correct`.

Independent of `lightgbm_tpu`: it imports nothing of the program and
takes nothing the program made. From the raw matrix, the labels and the
configuration it finds its own bin bounds (LightGBM's greedy
equal-frequency rule on the configuration's row sample), bins every
row, and grows trees by the textbook rule: per-leaf (feature, bin)
histograms of gradient, hessian and count, the best threshold by
    gain = GL^2/(HL+l2) + GR^2/(HR+l2) - G^2/(H+l2)
under min_data_in_leaf / min_sum_hessian_in_leaf, always splitting the
open leaf with the largest gain, leaf output -G/(H+l2) * learning_rate.

`grow_tree` has two modes. Free (forced=None): its own argmax — this is
the learner that stands in for the program in the control and the
planted faults (control.py). Following (forced=<a tree the program
produced>): it takes the program's (leaf, feature, bin) decisions in
their order and records, in float64, how far each is from its own
optimum and how far each reported count and leaf value is from its own.
Following is what makes the comparison immune to near-ties: two
candidates whose gains agree to rounding may legitimately be chosen
differently in float32; the chosen one must merely be as good.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

ZERO = 1e-10   # LightGBM treats |v| <= 1e-10 as the value 0


# ------------------------------------------------------------------ binning
def sample_rows(n, cnt, seed):
    """The configuration's bin-construction sample: the `cnt` rows with
    the smallest of n uniform keys drawn from MT19937(seed), ascending
    (uniform over cnt-subsets; stated under `assumed` in the config)."""
    if cnt >= n:
        return np.arange(n)
    u = np.random.RandomState(seed & 0xFFFFFFFF).random_sample(n)
    return np.sort(np.argpartition(u, cnt)[:cnt])


def find_bounds(sample, max_bin):
    """Upper bin bounds of one numerical feature (LightGBM bin.cpp
    GreedyFindBin): distinct values with counts; a value holding at
    least a mean bin's share gets a bin of its own, the rest are packed
    greedily to the mean of what remains; a bound is the midpoint
    between neighbouring distinct values, the last is +inf. As there,
    0 is a distinct value wherever the sample changes sign, even with
    no zero in it."""
    v = np.where(np.abs(sample) <= ZERO, 0.0, np.asarray(sample, np.float64))
    vals, cnts = np.unique(v, return_counts=True)
    k = int(np.searchsorted(vals, 0.0))
    if 0 < k < len(vals) and vals[k] != 0.0:
        vals, cnts = np.insert(vals, k, 0.0), np.insert(cnts, k, 0)
    if len(vals) <= max_bin:
        return np.append((vals[:-1] + vals[1:]) / 2.0, np.inf)
    total = len(v)
    mean = total / max_bin
    big = cnts >= mean
    rest_bins = max_bin - int(big.sum())
    rest_cnt = total - int(cnts[big].sum())
    mean = rest_cnt / rest_bins if rest_bins > 0 else np.inf
    uppers, lowers, cur = [], [vals[0]], 0
    for i in range(len(vals) - 1):
        if not big[i]:
            rest_cnt -= cnts[i]
        cur += cnts[i]
        if big[i] or cur >= mean or (big[i + 1] and cur >= max(1.0, mean / 2)):
            uppers.append(vals[i])
            lowers.append(vals[i + 1])
            if len(uppers) >= max_bin - 1:
                break
            cur = 0
            if not big[i]:
                rest_bins -= 1
                mean = rest_cnt / rest_bins if rest_bins > 0 else np.inf
    return np.append((np.asarray(uppers) + np.asarray(lowers[1:])) / 2.0,
                     np.inf)


def bin_matrix(x, bounds, pool):
    """(F, N) uint8: bin = number of bounds strictly below the value."""
    def one(j):
        col = np.nan_to_num(x[:, j].astype(np.float64), nan=0.0)
        return np.searchsorted(bounds[j], col, side="left").astype(np.uint8)
    return np.stack(list(pool.map(one, range(x.shape[1]))))


# ---------------------------------------------------------------- objective
def binary_grad(score, y, sigmoid=1.0):
    """Binary log-loss gradients on labels {0,1} (LightGBM's form)."""
    sign = np.where(y > 0, 1.0, -1.0)
    resp = -2.0 * sign * sigmoid / (1.0 + np.exp(2.0 * sign * sigmoid * score))
    a = np.abs(resp)
    return resp, a * (2.0 * sigmoid - a)


def binary_logloss(score, y, sigmoid=1.0):
    sign = np.where(y > 0, 1.0, -1.0)
    return float(np.mean(np.logaddexp(0.0, -2.0 * sign * sigmoid * score)))


def round_bf16(a):
    """Round float64 values to bfloat16 (nearest even), as float64."""
    u = np.asarray(a, np.float32).view(np.uint32).astype(np.uint64)
    u = ((u + 0x7FFF + ((u >> 16) & 1)) >> 16) << 16
    return u.astype(np.uint32).view(np.float32).astype(np.float64)


# --------------------------------------------------------------------- tree
def histogram(bins, rows, g, h, nb, pool):
    """(F, nb, 3) float64 sums of g, h and 1 over `rows` per (feature, bin)."""
    gs, hs = (g, h) if rows is None else (g[rows], h[rows])

    def one(j):
        b = bins[j] if rows is None else bins[j][rows]
        b = b.astype(np.intp)
        return np.stack([np.bincount(b, gs, nb), np.bincount(b, hs, nb),
                         np.bincount(b, minlength=nb).astype(np.float64)], 1)
    return np.stack(list(pool.map(one, range(bins.shape[0]))))


def leaf_gain(g, h, l1, l2):
    r = np.maximum(np.abs(g) - l1, 0.0)
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.where(r > 0.0, r * r / (h + l2), 0.0)


def leaf_output(g, h, l1, l2):
    r = np.maximum(np.abs(g) - l1, 0.0)
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.where(r > 0.0, -np.sign(g) * r / (h + l2), 0.0)


def split_gains(hist, p):
    """(F, nb-1) gain of 'bin <= t goes left' for every t; -inf where a
    child breaks min_data_in_leaf / min_sum_hessian_in_leaf or the gain
    does not reach min_gain_to_split."""
    left = np.cumsum(hist, axis=1)[:, :-1]
    tot = hist[0].sum(axis=0)
    right = tot - left
    l1, l2 = p["lambda_l1"], p["lambda_l2"]
    gain = (leaf_gain(left[..., 0], left[..., 1], l1, l2)
            + leaf_gain(right[..., 0], right[..., 1], l1, l2)
            - leaf_gain(tot[0], tot[1], l1, l2))
    ok = ((left[..., 2] >= p["min_data_in_leaf"])
          & (right[..., 2] >= p["min_data_in_leaf"])
          & (left[..., 1] >= p["min_sum_hessian_in_leaf"])
          & (right[..., 1] >= p["min_sum_hessian_in_leaf"])
          & (gain >= p["min_gain_to_split"]) & (gain > 0.0))
    return np.where(ok, gain, -np.inf)


def split_order(tree):
    """Leaf id that split i divided, for a tree in LightGBM's arrays:
    split i keeps the divided leaf's id on its left and opens leaf i+1
    on its right; children < 0 are leaves (~id)."""
    n = len(tree["split_feature"])
    leaf = np.zeros(n, np.int64)
    for p in range(n):
        for side, child in (("left_child", leaf[p]), ("right_child", p + 1)):
            c = int(tree[side][p])
            if c >= 0:
                leaf[c] = child
    return leaf


def child_counts(tree):
    """(n_splits, 2) row counts of each split's left and right child."""
    def cnt(c):
        return np.where(c < 0, tree["leaf_count"][np.where(c < 0, ~c, 0)],
                        tree["internal_count"][np.where(c < 0, 0, c)])
    return np.stack([cnt(np.asarray(tree["left_child"])),
                     cnt(np.asarray(tree["right_child"]))], 1)


def grow_tree(bins, nb, g, h, p, pool, forced=None, rows0=None):
    """One leaf-wise tree. Returns (tree, leaf_rows, readings): the tree
    in LightGBM's arrays (leaf_value already times learning_rate), the
    row indices of each leaf, and — when following `forced` — the
    largest regret and count gap met on the way."""
    num_leaves = p["num_leaves"]
    l1, l2 = p["lambda_l1"], p["lambda_l2"]
    hist0 = histogram(bins, rows0, g, h, nb, pool)
    rows = [np.arange(bins.shape[1]) if rows0 is None else rows0]
    hists, gains = [hist0], [split_gains(hist0, p)]
    best = [float(gains[0].max())]
    parent_of = [(-1, 0)]                      # leaf -> (node, side)
    sf, tb, lc, rc, ic, sg = [], [], [], [], [], []
    if forced is not None:
        order, want = split_order(forced), child_counts(forced)
        n_forced = len(order)
    regret = count_gap = 0.0
    for i in range(num_leaves - 1):
        top = max(best)
        if forced is None:
            if top <= 0.0 or not np.isfinite(top):
                break
            leaf = int(np.argmax(best))
            f, t = np.unravel_index(np.argmax(gains[leaf]), gains[leaf].shape)
        else:
            if i >= n_forced:
                # the program stopped: sound only if nothing was left
                regret = max(regret, 1.0 if top > 0.0 else 0.0)
                break
            leaf = int(order[i])
            f, t = int(forced["split_feature"][i]), int(forced["threshold_in_bin"][i])
            chosen = gains[leaf][f, t] if 0 <= t < nb - 1 else -np.inf
            regret = max(regret, min((top - chosen) / top, 1.0)
                         if top > 0.0 else 1.0)
            if not np.isfinite(chosen):
                break     # not a split this configuration allows
        r = rows[leaf]
        go_left = bins[f][r] <= t
        r_l, r_r = r[go_left], r[~go_left]
        if forced is not None:
            count_gap = max(count_gap, abs(len(r_l) - want[i][0]),
                            abs(len(r_r) - want[i][1]))
            if len(r_l) == 0 or len(r_r) == 0:
                regret = 1.0
                break
        small_left = len(r_l) <= len(r_r)
        h_small = histogram(bins, r_l if small_left else r_r, g, h, nb, pool)
        h_large = hists[leaf] - h_small
        node, right = i, len(rows)
        pn, side = parent_of[leaf]
        if pn >= 0:
            (lc if side == 0 else rc)[pn] = node
        sf.append(int(f)); tb.append(int(t)); sg.append(float(gains[leaf][f, t]))
        lc.append(~leaf); rc.append(~right); ic.append(len(r))
        parent_of[leaf] = (node, 0)
        parent_of.append((node, 1))
        for lid, rr, hh in ((leaf, r_l, h_small if small_left else h_large),
                            (right, r_r, h_large if small_left else h_small)):
            gm = split_gains(hh, p)
            if lid == right:
                rows.append(rr); hists.append(hh); gains.append(gm)
                best.append(float(gm.max()))
            else:
                rows[lid], hists[lid], gains[lid] = rr, hh, gm
                best[lid] = float(gm.max())
    tot = np.stack([hh[0].sum(axis=0) for hh in hists])
    tree = {
        "split_feature": np.asarray(sf, np.int64),
        "threshold_in_bin": np.asarray(tb, np.int64),
        "split_gain": np.asarray(sg),
        "left_child": np.asarray(lc, np.int64),
        "right_child": np.asarray(rc, np.int64),
        "internal_count": np.asarray(ic, np.int64),
        "leaf_count": np.asarray([len(r) for r in rows], np.int64),
        "leaf_value": leaf_output(tot[:, 0], tot[:, 1], l1, l2)
        * p["learning_rate"] * (len(rows) > 1),
    }
    return tree, rows, {"split_regret": float(regret),
                        "count_mismatch": float(count_gap)}


def rows_visited(tree, n):
    """Rows a histogram learner must read for this tree: the root's N
    and, at every split, the smaller child (the larger is a subtraction)."""
    if len(tree["split_feature"]) == 0:
        return float(n)
    return float(n + child_counts(tree).min(axis=1).sum())


def rows_partitioned(tree):
    """Rows a leaf-contiguous learner must move for this tree: at every
    split, the rows of the segment it divides (the parent's count)."""
    return float(np.sum(tree["internal_count"]))


# --------------------------------------------------------------- comparison
def prepare(x, cfg, pool):
    """Own bin bounds from the configuration's sample, then all rows binned."""
    n = x.shape[0]
    idx = sample_rows(n, cfg["bin_construct_sample_cnt"], cfg["data_random_seed"])
    bounds = [find_bounds(x[idx, j], cfg["max_bin"]) for j in range(x.shape[1])]
    return bounds, bin_matrix(x, bounds, pool)


def compare(x, y, cfg, trees, score_after, threads=8):
    """Follow the trees the program grew in its first block, from score
    0, and return the numbers compared (each a worst case over the
    block). `score_after` is the program's train score after the block.

    count_mismatch  rows: |program's child count - recount| at any split
    threshold_gap   program's real threshold vs own bound of that bin
    split_regret    (best gain any open leaf offers - gain of the split
                    the program took) / best, float64, at any split
    leaf_value_gap  |program's leaf value - own| over max(|own|, median
                    |own| of the tree), at any leaf
    loss_gap        relative gap of the training log-loss after each
                    tree: program's leaf values on the followed
                    partition (last tree: the program's own score)
                    against the reference's values
    score_gap       | ||program's score|| - ||reference's|| | / the latter
    score_max_gap   max |program's score - reference's| / median |reference's|
    """
    with ThreadPoolExecutor(threads) as pool:
        bounds, bins = prepare(x, cfg, pool)
        nb = max(len(b) for b in bounds)
        n = x.shape[0]
        s_ref = np.zeros(n)       # reference's leaf values
        s_prog = np.zeros(n)      # program's leaf values, followed partition
        out = {k: 0.0 for k in ("count_mismatch", "threshold_gap",
                                "split_regret", "leaf_value_gap", "loss_gap")}
        for k, tree in enumerate(trees):
            g, h = binary_grad(s_ref, y, cfg.get("sigmoid", 1.0))
            own, leaf_rows, rd = grow_tree(bins, nb, g, h, cfg, pool, forced=tree)
            out["split_regret"] = max(out["split_regret"], rd["split_regret"])
            out["count_mismatch"] = max(out["count_mismatch"],
                                        rd["count_mismatch"])
            m = len(own["split_feature"])
            if m:
                thr = np.asarray(tree["threshold"], np.float64)[:m]
                mine = np.asarray([bounds[f][t] for f, t in zip(
                    own["split_feature"], own["threshold_in_bin"])])
                out["threshold_gap"] = max(out["threshold_gap"], float(np.max(
                    np.abs(thr - mine) / np.maximum(np.abs(mine), 1.0))))
            v_own = own["leaf_value"]
            v_prog = np.asarray(tree["leaf_value"], np.float64)
            if len(v_prog) != len(v_own):
                out["leaf_value_gap"] = 1.0
                v_prog = np.resize(v_prog, len(v_own))
            floor = np.maximum(np.abs(v_own), np.median(np.abs(v_own)))
            out["leaf_value_gap"] = max(out["leaf_value_gap"], float(np.max(
                np.abs(v_prog - v_own) / np.where(floor > 0, floor, 1.0))))
            for lid, r in enumerate(leaf_rows):
                s_ref[r] += v_own[lid]
                s_prog[r] += v_prog[lid]
            last = k == len(trees) - 1
            mine = binary_logloss(s_ref, y)
            theirs = binary_logloss(np.asarray(score_after, np.float64)
                                    if last else s_prog, y)
            out["loss_gap"] = max(out["loss_gap"], abs(theirs - mine) / mine)
    sa = np.asarray(score_after, np.float64)
    norm = float(np.linalg.norm(s_ref))
    out["score_gap"] = abs(float(np.linalg.norm(sa)) - norm) / norm
    out["score_max_gap"] = float(np.max(np.abs(sa - s_ref))
                                 / np.median(np.abs(s_ref)))
    return out
