"""Plain reference for `objective=lambdarank`: LambdaRank with NDCG
weighting (LightGBM rank_objective.hpp GetGradientsForOneQuery) in numpy
float64, and the comparison that decides `correct` for a configuration
that names it.

It imports nothing of the program. From `reference.py` it takes what is
shared arithmetic of any histogram learner (bin bounds, binning, split
gains, leaf outputs); the objective is its own, and so is the follower
(`follow_tree`: `reference.grow_tree`'s following mode with histograms
built a group of columns a task and summed by a kernel that lets go of
the interpreter's lock, because at 136 columns a `np.bincount` a column
a split was 54 s of an 83 s comparison, PERF.md PR 29). For every query, before each
tree: documents ranked by score (stable on ties, so equal scores keep
the order of the rows), and for every pair (i, j) with label_i > label_j

    delta  = (gain_i - gain_j) * |disc(rank_i) - disc(rank_j)| / maxDCG@max_position
    delta /= 0.01 + |s_i - s_j|         where the query's best score != its worst
    p      = 2 / (1 + exp(2 * sigmoid * clip(s_i - s_j, +-25 / sigmoid)))
    lambda_i -= p * delta,  lambda_j += p * delta
    hess_i, hess_j += 2 * p * (2 - p) * delta

with disc(r) = 1 / log2(2 + r). Every pair is evaluated: the pairs of
different labels are listed once, by chunks of whole queries (no
padding, no rectangle), and each pass goes over the lists, the chunks
spread over threads; no Python loop of one query at a time.

`compare` then follows the program's trees as `reference.compare` does
(own bins, own score from 0, own lambdas before each tree) and returns
the same numbers, with `ndcg_gap` in `loss_gap`'s place.
"""

import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from control import leaf_of  # noqa: E402  (a tree descended over binned rows)
from reference import (child_counts, leaf_output, prepare, round_bf16,  # noqa: E402
                       split_gains, split_order)

PAIR_CHUNK = 1 << 20      # sum of n_q^2 a chunk of queries: about 0.3M pairs
MAX_POSITION = 10000      # LightGBM's discount table ends here
RUNG = 128                # the `padded_docs` fault pads a query to this multiple
FOLLOWED = 2              # trees of a block followed; the rest applied as given


def pairs_evaluated(group):
    """Ordered pairs of two different documents of one query, summed
    over the queries: what a pairwise pass has to look at (of each
    unordered pair one order has the larger label, or neither)."""
    g = np.asarray(group, np.int64)
    return float(np.sum(g * (g - 1)))


def label_pairs(code, qid):
    """Every (i, j) of one query with code_i > code_j, as two arrays of
    positions into `code`; `qid` is ascending. Documents sorted by
    (query, code descending) meet their partners as one run: all of
    their query that follow their own group of equal code."""
    order = np.lexsort((-code, qid))
    key = qid[order] * (int(code.max()) + 2) - code[order]
    group_end = np.searchsorted(key, key, side="right")
    query_end = np.searchsorted(qid[order], qid[order], side="right")
    count = query_end - group_end
    first = np.cumsum(count) - count
    run = np.arange(int(count.sum())) - np.repeat(first, count)
    return (order[np.repeat(np.arange(len(order)), count)].astype(np.int32),
            order[np.repeat(group_end, count) + run].astype(np.int32))


class Queries:
    """The queries and every pair of different labels in them, listed
    once (the labels do not change). `mode` plants a fault into the
    gradients (control_lambdarank.py); None is sound."""

    def __init__(self, group, label, params, weight=None, mode=None, pool=None):
        sizes = np.asarray(group, np.int64)
        label = np.asarray(label, np.int64)
        self.mode = mode
        self.rows = None
        if mode == "padded_docs":
            # padding counted as documents: every query grows to the next
            # multiple of RUNG with label-0 documents whose score stays 0
            padded = -(-sizes // RUNG) * RUNG
            at = np.repeat(np.cumsum(padded) - padded, sizes)
            self.rows = at + np.arange(sizes.sum()) - np.repeat(
                np.cumsum(sizes) - sizes, sizes)
            full = np.zeros(int(padded.sum()), np.int64)
            full[self.rows] = label
            sizes, label = padded, full
        self.n = int(sizes.sum())
        self.num_queries = len(sizes)
        self.sigmoid = float(params.get("sigmoid", 1.0))
        self.weight = None if weight is None else np.asarray(weight, np.float64)
        self.eval_at = [int(a) for a in params.get("ndcg_eval_at", (1, 3, 5, 10))]
        self.starts = np.cumsum(sizes) - sizes
        self.qid = np.repeat(np.arange(len(sizes)), sizes)
        self.pos = np.arange(self.n) - self.starts[self.qid]
        self.gain = np.asarray(params["label_gain"], np.float64)[label]
        self.discount = 1.0 / np.log2(2.0 + np.arange(
            min(int(sizes.max()), MAX_POSITION)))
        k = int(params.get("max_position", 20))
        ideal = self.gain[np.lexsort((-self.gain, self.qid))] * self.disc_at(self.pos)
        self.ideal_at = [np.bincount(self.qid, ideal * (self.pos < a),
                                     self.num_queries) for a in self.eval_at]
        maxdcg = np.bincount(self.qid, ideal * (self.pos < k), self.num_queries)
        self.inv = np.where(maxdcg > 0, 1.0 / np.where(maxdcg > 0, maxdcg, 1.0),
                            0.0)[self.qid]
        # chunks of whole queries, each with its pairs as global rows
        code = np.searchsorted(np.unique(self.gain), self.gain)
        cost = np.cumsum(sizes * sizes)
        cuts = np.searchsorted(cost, np.arange(PAIR_CHUNK, cost[-1], PAIR_CHUNK))
        edges = np.unique(np.concatenate([[0], cuts + 1, [len(sizes)]]))

        def chunk(c):
            lo = int(self.starts[edges[c]])
            hi = int(self.starts[edges[c + 1]]) if edges[c + 1] < len(sizes) else self.n
            i, j = label_pairs(code[lo:hi], self.qid[lo:hi])
            if mode == "truncated":         # documents past the 512th skipped
                keep = (self.pos[lo:hi][i] < 512) & (self.pos[lo:hi][j] < 512)
                i, j = i[keep], j[keep]
            return lo, hi, i, j
        run = pool.map if pool is not None else map
        self.chunks = list(run(chunk, range(len(edges) - 1)))

    def disc_at(self, position):
        return self.discount[np.minimum(position, len(self.discount) - 1)]

    def ranking(self, score, pool=None):
        """Rows ordered by (query, score descending), stable on ties;
        a chunk of whole queries a task."""
        def chunk(c):
            lo, hi = c[0], c[1]
            return lo + np.lexsort((-score[lo:hi], self.qid[lo:hi]))
        return np.concatenate(list((pool.map if pool else map)(chunk, self.chunks)))

    # ------------------------------------------------------------ gradients
    def gradients(self, score, pool):
        """(lambda, hessian) of every row at `score`, float64."""
        s = np.asarray(score, np.float64)
        if self.rows is not None:
            s, real = np.zeros(self.n), s
            s[self.rows] = real
        disc = np.empty(self.n)
        disc[self.ranking(s, pool)] = self.disc_at(self.pos)
        best = np.maximum.reduceat(s, self.starts)
        norm = (best != np.minimum.reduceat(s, self.starts))[self.qid]
        g, h = np.zeros(self.n), np.zeros(self.n)
        lim = 25.0 / self.sigmoid

        def chunk(c):
            lo, hi, i, j = c
            sl = slice(lo, hi)
            ds = s[sl][i] - s[sl][j]
            delta = ((self.gain[sl][i] - self.gain[sl][j])
                     * np.abs(disc[sl][i] - disc[sl][j]) * self.inv[sl][i])
            np.divide(delta, 0.01 + np.abs(ds), out=delta, where=norm[sl][i])
            p = 2.0 / (1.0 + np.exp(2.0 * self.sigmoid * np.clip(ds, -lim, lim)))
            lam = p * delta
            hes = 2.0 * p * (2.0 - p) * delta
            m = hi - lo
            g[sl] = np.bincount(j, lam, m) - np.bincount(i, lam, m)
            h[sl] = np.bincount(i, hes, m) + np.bincount(j, hes, m)
        list(pool.map(chunk, self.chunks))
        if self.rows is not None:
            g, h = g[self.rows], h[self.rows]
        if self.weight is not None:
            g, h = g * self.weight, h * self.weight
        if self.mode == "bf16":
            g, h = round_bf16(g), round_bf16(h)
        return g, h

    # ----------------------------------------------------------------- NDCG
    def ndcg(self, score, pool=None):
        """NDCG at each `ndcg_eval_at`, the mean over the queries (a
        query without a relevant document counts 1, as LightGBM's)."""
        ranked = (self.gain[self.ranking(np.asarray(score, np.float64), pool)]
                  * self.disc_at(self.pos))
        out = []
        for a, ideal in zip(self.eval_at, self.ideal_at):
            dcg = np.bincount(self.qid, ranked * (self.pos < a), self.num_queries)
            out.append(np.mean(np.where(ideal > 0, dcg / np.where(
                ideal > 0, ideal, 1.0), 1.0)))
        return np.asarray(out)


# ------------------------------------------------------------------ following
try:        # scipy's COO -> dense kernel: a weighted histogram that lets go of the lock
    from scipy.sparse._sparsetools import coo_todense
except ImportError:                                     # the same sums, a thread at a time
    coo_todense = None


def scatter_sum(rows, cols, weights, out):
    """out[rows[i], cols[i]] += weights[i], float64 (C order); `rows`,
    `cols` int32. `np.bincount` holds the interpreter's lock for the
    whole pass, so threads do not spread it (PERF.md PR 29); scipy's
    kernel does the same additions in the same order without the lock."""
    if coo_todense is None:
        out += np.bincount(rows.astype(np.intp) * out.shape[1] + cols, weights,
                           out.size).reshape(out.shape)
    else:
        coo_todense(out.shape[0], out.shape[1], len(rows), rows, cols, weights,
                    out.ravel(), 0)


SMALL_LEAF = 1 << 15      # rows below which a group's columns share one pass
ROW_BLOCK = 1 << 18       # rows of a column pair a pass takes at a time


def histogram(bins, rows, g, h, nb, pool, groups):
    """(F, nb, 3) float64 sums of g, h and 1 over `rows` per (feature,
    bin); one task a group of columns. Where the rows are many, two
    columns share a pass: the joint histogram of the pair, nb x nb,
    summed along each axis; where they are few, all of a group's columns
    share one."""
    gs, hs = (g, h) if rows is None else (g[rows], h[rows])
    ones = np.ones(len(gs))

    def one(sl):
        sub = bins[sl] if rows is None else np.take(bins[sl], rows, axis=1)
        f, k = sub.shape
        if k < SMALL_LEAF:
            code = (sub.astype(np.int32)
                    + (np.arange(f, dtype=np.int32) * nb)[:, None]).ravel()
            zero = np.zeros(f * k, np.int32)
            flat = np.zeros((3, f * nb, 1))
            for c, w in enumerate((gs, hs, ones)):
                scatter_sum(code, zero, np.tile(w, f), flat[c])
            return np.moveaxis(flat.reshape(3, f, nb), 0, -1)
        out = np.empty((f, nb, 3))
        zero = np.zeros(ROW_BLOCK, np.int32)
        for j in range(0, f, 2):
            last = j + 1 == f                   # an odd column goes alone
            joint = np.zeros((3, nb, 1 if last else nb))
            for lo in range(0, k, ROW_BLOCK):   # blocks that stay in cache
                rb = slice(lo, lo + ROW_BLOCK)
                a = sub[j, rb].astype(np.int32)
                b = zero[:len(a)] if last else sub[j + 1, rb].astype(np.int32)
                for c, w in enumerate((gs, hs, ones)):
                    scatter_sum(a, b, w[rb], joint[c])
            out[j] = joint.sum(2).T
            if not last:
                out[j + 1] = joint.sum(1).T
        return out
    return np.concatenate(list(pool.map(one, groups)))


def follow_tree(bins, nb, g, h, p, pool, forced, threads=8):
    """Take the (leaf, feature, bin) decisions of `forced`, a tree the
    program grew, in their order, and measure each against own float64
    histograms. Returns (leaf values, row indices of each leaf,
    readings): `split_regret`, how far the gain of a split taken is from
    the best any open leaf offered, and `count_mismatch`, how far a
    child's reported count is from the recount."""
    f_all = bins.shape[0]
    step = -(-f_all // threads)
    groups = [slice(a, min(a + step, f_all)) for a in range(0, f_all, step)]
    hist0 = histogram(bins, None, g, h, nb, pool, groups)
    rows = [np.arange(bins.shape[1])]
    hists, gains = [hist0], [split_gains(hist0, p)]
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
        go_left = bins[f][r] <= t
        r_l, r_r = r[go_left], r[~go_left]
        count_gap = max(count_gap, abs(len(r_l) - want[i][0]),
                        abs(len(r_r) - want[i][1]))
        if len(r_l) == 0 or len(r_r) == 0:
            regret = 1.0
            break
        small_left = len(r_l) <= len(r_r)
        h_small = histogram(bins, r_l if small_left else r_r, g, h, nb, pool,
                            groups)
        h_large = hists[leaf] - h_small
        h_l, h_r = (h_small, h_large) if small_left else (h_large, h_small)
        rows[leaf], hists[leaf], gains[leaf] = r_l, h_l, split_gains(h_l, p)
        best[leaf] = float(gains[leaf].max())
        rows.append(r_r)
        hists.append(h_r)
        gains.append(split_gains(h_r, p))
        best.append(float(gains[-1].max()))
    tot = np.stack([hh[0].sum(axis=0) for hh in hists])
    values = (leaf_output(tot[:, 0], tot[:, 1], p["lambda_l1"], p["lambda_l2"])
              * p["learning_rate"] * (len(rows) > 1))
    return values, rows, {"split_regret": float(regret),
                          "count_mismatch": float(count_gap)}


def compare(x, y, fields, params, trees, score_after, threads=None):
    """Follow the first FOLLOWED trees the program grew in its first
    block, from score 0, apply the rest as given, and return the numbers
    compared (each a worst case over the block). `score_after` is the
    program's (1, n) train score after the block.

    A tree applied as given is descended over the reference's own bins,
    its leaves recounted (`count_mismatch`) and its thresholds held to
    the reference's bounds (`threshold_gap`); its leaf values are taken
    as they are, so the score it leaves is checked (`score_gap`,
    `score_max_gap`, `ndcg_gap`) and its split choices and leaf sums are
    not. That is the cut that keeps a cold run under its time limit
    (benchmarks/README.md "Time budget"; PERF.md PR 29 has the seconds).

    count_mismatch  rows: |program's child count - recount| at any split
    threshold_gap   program's real threshold vs own bound of that bin
    split_regret    (best gain any open leaf offers - gain of the split
                    the program took) / best, float64, at any split
    leaf_value_gap  |program's leaf value - own| over max(|own|, median
                    |own| of the tree), at any leaf
    ndcg_gap        widest gap of NDCG@ndcg_eval_at after each tree:
                    program's leaf values on the followed partition
                    (last tree: the program's own score) against the
                    reference's values
    score_gap       | ||program's score|| - ||reference's|| | / the latter
    score_max_gap   max |program's score - reference's| / median |reference's|
    """
    sa = np.asarray(score_after, np.float64).reshape(-1)
    threads = threads or min(os.cpu_count() or 8, 12)
    with ThreadPoolExecutor(threads) as pool:
        queries = Queries(fields["group"], y, params, fields.get("weight"),
                          pool=pool)
        bounds, bins = prepare(x, params, pool)
        nb = max(len(b) for b in bounds)
        n = x.shape[0]
        s_ref = np.zeros(n)       # reference's leaf values
        s_prog = np.zeros(n)      # program's leaf values, followed partition
        out = {k: 0.0 for k in ("count_mismatch", "threshold_gap",
                                "split_regret", "leaf_value_gap", "ndcg_gap")}
        for k, tree in enumerate(trees):
            if k >= FOLLOWED:
                leaf = leaf_of(tree, bins)
                v_prog = np.asarray(tree["leaf_value"], np.float64)
                counts = np.bincount(leaf, minlength=len(v_prog))
                out["count_mismatch"] = max(out["count_mismatch"], float(np.max(
                    np.abs(counts - np.asarray(tree["leaf_count"])))))
                mine = np.asarray([bounds[f][t] for f, t in zip(
                    tree["split_feature"], tree["threshold_in_bin"])])
                out["threshold_gap"] = max(out["threshold_gap"], float(np.max(
                    np.abs(np.asarray(tree["threshold"], np.float64) - mine)
                    / np.maximum(np.abs(mine), 1.0), initial=0.0)))
                s_ref += v_prog[leaf]
                s_prog += v_prog[leaf]
                theirs = queries.ndcg(sa if k == len(trees) - 1 else s_prog, pool)
                out["ndcg_gap"] = max(out["ndcg_gap"], float(np.max(
                    np.abs(theirs - queries.ndcg(s_ref, pool)))))
                continue
            g, h = queries.gradients(s_ref, pool)
            v_own, leaf_rows, rd = follow_tree(bins, nb, g, h, params, pool,
                                               tree, threads)
            out["split_regret"] = max(out["split_regret"], rd["split_regret"])
            out["count_mismatch"] = max(out["count_mismatch"],
                                        rd["count_mismatch"])
            m = len(leaf_rows) - 1          # splits followed
            if m:
                thr = np.asarray(tree["threshold"], np.float64)[:m]
                mine = np.asarray([bounds[f][t] for f, t in zip(
                    tree["split_feature"][:m], tree["threshold_in_bin"][:m])])
                out["threshold_gap"] = max(out["threshold_gap"], float(np.max(
                    np.abs(thr - mine) / np.maximum(np.abs(mine), 1.0))))
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
            theirs = queries.ndcg(sa if k == len(trees) - 1 else s_prog, pool)
            out["ndcg_gap"] = max(out["ndcg_gap"], float(np.max(
                np.abs(theirs - queries.ndcg(s_ref, pool)))))
    norm = float(np.linalg.norm(s_ref))
    out["score_gap"] = abs(float(np.linalg.norm(sa)) - norm) / norm
    out["score_max_gap"] = float(np.max(np.abs(sa - s_ref))
                                 / np.median(np.abs(s_ref)))
    return out
