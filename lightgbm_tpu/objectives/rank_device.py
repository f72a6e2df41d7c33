"""Device LambdaRank: pairwise gradients over a length-bucketed query
layout, as part of one XLA program.

Reference: src/objective/rank_objective.hpp:19-227 (GetGradientsForOneQuery)
runs an OpenMP loop over queries, each an O(n_q^2) pair sweep on the CPU.
Here the queries are grouped by padded length into RUNGS (multiples of
128 documents, the lane width; the rungs a data set gets come from its
group sizes). Each rung is its own rectangle `(Qp, M)` of row indices,
walked in blocks of queries under `lax.map` so that one `(block, M, M)`
pair tensor stays under the pair budget whatever the rung. A query of
120 documents costs 128^2 pair slots, not the longest query's 1,251^2.
The count of queries in a rung is rounded up on the ladder
`canonical_row_chunks` uses for rows, so two data sets whose rungs hold
about as many queries share one program.

Every pair of every query is evaluated, in float32 (the reference uses
double on the CPU; docs/Objectives.md has the tolerance and its reason):

- ranks come from the pair tensor itself, rank_i = #{j: s_j > s_i} +
  #{j < i: s_j == s_i}, the order a stable descending sort gives;
- slot (i, j) holds the pair as document i sees it, oriented from the
  higher label to the lower, so lambda_i and hessian_i are ROW sums and
  nothing is summed down a column or scattered;
- the reference's 1M-entry sigmoid table is the exact expression over
  the same clamped range (the table is a CPU latency trick);
- the way back is one slot a document (each row sits in exactly one
  rectangle): a gather through `slot`, not a scatter-add.

Device scopes (telemetry/trace.py): under `gradients`, the sub-scopes
`rank_gather`, `rank_sort`, `rank_pairs`, `rank_return`.
"""

import jax
import jax.numpy as jnp
import numpy as np

from ..metrics.dcg_calculator import DISCOUNT, K_MAX_POSITION
from ..ops.ordered_hist import canonical_row_chunks
from ..telemetry.trace import scope

# max f32 elements in one (block, M, M) pair tensor (~64 MB)
_PAIR_BUDGET = 16 * 1024 * 1024
RUNG_STEP = 128   # documents: a rung's width is a multiple of the lane width


def _discounts(m):
    """Discount of positions 0..m-1; past the table's end its last entry
    (the gradient path clamps ranks the same way)."""
    return DISCOUNT[np.minimum(np.arange(m), K_MAX_POSITION - 1)]


class BucketedQueryLayout:
    """Static length-bucketed query indexing shared by the objective and
    the NDCG metric. `rungs` is a list of dicts, ascending by width:

      width     M, the padded query length of the rung
      queries   (Q,) ids of the rung's queries, in their own order
      idx       (Qp, M) int32 row index of every slot; padding slots (and
                the padding queries up to Qp) point at the sink row N
      block     queries a `lax.map` step takes (Qp = blocks * block)

    `slot` (N,) int32 is each row's position in the concatenation of the
    rungs' flattened rectangles."""

    def __init__(self, query_boundaries, num_data):
        qb = np.asarray(query_boundaries, dtype=np.int64)
        self.counts = np.diff(qb)
        self.num_queries = len(self.counts)
        self.num_data = int(num_data)
        self.max_docs = int(self.counts.max()) if self.num_queries else 1
        widths = np.maximum(-(-self.counts // RUNG_STEP), 1) * RUNG_STEP
        self.rungs = []
        self.slot = np.zeros(self.num_data, dtype=np.int32)
        base = 0
        for m in np.unique(widths):
            m = int(m)
            queries = np.flatnonzero(widths == m)
            want = canonical_row_chunks(len(queries))
            # as few blocks as the pair budget allows, of equal size
            blocks = -(-want // max(1, _PAIR_BUDGET // (m * m)))
            block = -(-want // blocks)
            padded = blocks * block
            pos = np.arange(m, dtype=np.int64)
            real = pos[None, :] < self.counts[queries][:, None]
            idx = np.full((padded, m), self.num_data, dtype=np.int32)
            idx[:len(queries)] = np.where(
                real, qb[queries][:, None] + pos[None, :], self.num_data)
            q_of, p_of = np.nonzero(real)
            self.slot[idx[q_of, p_of]] = base + q_of * m + p_of
            self.rungs.append({"width": m, "queries": queries, "idx": idx,
                               "block": block})
            base += padded * m
        self.num_slots = base
        # what a pairwise pass looks at against what it pays for: ordered
        # pairs of two different documents of one query; slots of all the
        # (block, M, M) tensors of an iteration
        self.pairs = int(np.sum(self.counts * (self.counts - 1)))
        self.pair_slots = int(sum(r["idx"].shape[0] * r["width"] ** 2
                                  for r in self.rungs))

    def gather(self, rung, values, fill):
        """(Q, M) of the per-row `values` in a rung's real queries,
        `fill` in the padding slots (host side, numpy)."""
        idx = rung["idx"][:len(rung["queries"])]
        ext = np.append(np.asarray(values), fill)
        return ext[idx]

    def ideal_dcg(self, gains, k):
        """(num_queries,) ideal DCG@k of per-row `gains`: every query's
        gains sorted descending against the discount, once, by rung."""
        out = np.zeros(self.num_queries)
        for rung in self.rungs:
            m = rung["width"]
            ideal = -np.sort(-self.gather(rung, gains, 0.0), axis=1)
            cum = np.cumsum(ideal * _discounts(m), axis=1)
            out[rung["queries"]] = cum[:, min(int(k), m) - 1]
        return out


def lambdarank_ops(layout, label, label_gain, max_position):
    """The device operands of `lambdarank_grad` (a pytree of arrays: the
    fused trainer passes them as runtime arguments) and the per-query
    1 / maxDCG@max_position the host path shares."""
    gains = np.asarray(label_gain, np.float64)[np.asarray(label, np.int64)]
    maxdcg = layout.ideal_dcg(gains, max_position)
    inv = np.where(maxdcg > 0, 1.0 / np.where(maxdcg > 0, maxdcg, 1.0), 0.0)
    rungs = []
    for rung in layout.rungs:
        padded, m = rung["idx"].shape
        shape = (padded // rung["block"], rung["block"])
        lg = np.zeros((padded, m), np.float32)
        lg[:len(rung["queries"])] = layout.gather(rung, gains, 0.0)
        inv_p = np.zeros(padded, np.float32)
        inv_p[:len(rung["queries"])] = inv[rung["queries"]]
        rungs.append({"idx": jnp.asarray(rung["idx"].reshape(*shape, m)),
                      "gain": jnp.asarray(lg.reshape(*shape, m)),
                      "inv": jnp.asarray(inv_p.reshape(shape))})
    discount = DISCOUNT[:max(layout.max_docs, 1)]
    ops = {"rungs": rungs, "slot": jnp.asarray(layout.slot),
           "discount": jnp.asarray(discount, dtype=jnp.float32)}
    return ops, inv


def _block_gradients(idx, gain, inv, s_ext, discount, sigmoid):
    """(lambda, hessian), each (block, M), of one block of one rung."""
    n = s_ext.shape[0] - 1
    m = idx.shape[-1]
    real = idx < n
    with scope("rank_gather"):
        s = jnp.where(real, jnp.take(s_ext, idx), -jnp.inf)
    sm = jnp.where(real, s, 0.0)
    pos = jnp.arange(m, dtype=jnp.int32)
    with scope("rank_sort"):
        # the position a stable descending sort gives: documents that
        # beat i, and on equal scores the earlier rows; padding (-inf)
        # ranks behind every real document
        ahead = ((s[:, None, :] > s[:, :, None])
                 | ((s[:, None, :] == s[:, :, None])
                    & (pos[None, None, :] < pos[None, :, None])))
        rank = jnp.sum(ahead, axis=2, dtype=jnp.int32)
        disc = jnp.take(discount, jnp.minimum(rank, discount.shape[0] - 1))
        best = jnp.max(s, axis=1)
        # rank_objective.hpp skips one kMinScore sentinel at the bottom
        worst = jnp.min(jnp.where(real & ~jnp.isneginf(s), s, jnp.inf), axis=1)
        norm = (best != worst) & jnp.isfinite(worst)
    with scope("rank_pairs"):
        gap = gain[:, :, None] - gain[:, None, :]
        pair = (gap != 0) & real[:, :, None] & real[:, None, :]
        up = gap > 0                           # i carries the higher label
        ds = sm[:, :, None] - sm[:, None, :]
        delta = (jnp.abs(gap) * jnp.abs(disc[:, :, None] - disc[:, None, :])
                 * inv[:, None, None])
        delta = jnp.where(norm[:, None, None],
                          delta / (0.01 + jnp.abs(ds)), delta)
        # the pair's score gap from its higher label to its lower one
        x = jnp.clip(jnp.where(up, ds, -ds), -25.0 / sigmoid, 25.0 / sigmoid)
        p = 2.0 / (1.0 + jnp.exp(2.0 * x * sigmoid))
        lam = jnp.where(pair, jnp.where(up, -p, p) * delta, 0.0)
        hes = jnp.where(pair, 2.0 * p * (2.0 - p) * delta, 0.0)
        return lam.sum(axis=2), hes.sum(axis=2)


def lambdarank_grad(ops, score, sigmoid):
    """Pure (ops, score (1, N)) -> (grad, hess), each (1, N) float32."""
    with scope("gradients"):
        s_ext = jnp.concatenate([score[0].astype(jnp.float32),
                                 jnp.zeros(1, jnp.float32)])
        flat_g, flat_h = [], []
        for rung in ops["rungs"]:
            g, h = jax.lax.map(
                lambda b: _block_gradients(b[0], b[1], b[2], s_ext,
                                           ops["discount"], sigmoid),
                (rung["idx"], rung["gain"], rung["inv"]))
            flat_g.append(g.reshape(-1))
            flat_h.append(h.reshape(-1))
        with scope("rank_return"):
            grad = jnp.take(jnp.concatenate(flat_g), ops["slot"])
            hess = jnp.take(jnp.concatenate(flat_h), ops["slot"])
            weights = ops.get("weights")
            if weights is not None:
                grad, hess = grad * weights, hess * weights
    return grad[None, :], hess[None, :]


def ndcg_eval_bucketed(layout, label, label_gain, eval_at, score,
                       query_weights=None):
    """Vectorized NDCG@k (rank_metric.hpp:16-165) over the layout's
    rungs: one argsort a rung instead of a Python loop over queries, and
    no rectangle wider than the rung."""
    gains = np.asarray(label_gain, np.float64)[np.asarray(label, np.int64)]
    score = np.asarray(score, dtype=np.float64)
    ndcg = np.ones((len(eval_at), layout.num_queries))
    for rung in layout.rungs:
        m = rung["width"]
        s = layout.gather(rung, score[:layout.num_data], -np.inf)
        lg = layout.gather(rung, gains, 0.0)
        order = np.argsort(-s, axis=1, kind="stable")
        disc_m = _discounts(m)
        cum = np.cumsum(np.take_along_axis(lg, order, axis=1) * disc_m, axis=1)
        cum_ideal = np.cumsum(-np.sort(-lg, axis=1) * disc_m, axis=1)
        cnt = layout.counts[rung["queries"]]
        for a, k in enumerate(eval_at):
            kk = np.maximum(np.minimum(int(k), cnt) - 1, 0)[:, None]
            dcg_k = np.take_along_axis(cum, kk, 1)[:, 0]
            max_k = np.take_along_axis(cum_ideal, kk, 1)[:, 0]
            ndcg[a, rung["queries"]] = np.where(
                (max_k > 0) & (cnt > 0), dcg_k / np.maximum(max_k, 1e-300), 1.0)
    qw = (np.ones(layout.num_queries) if query_weights is None
          else np.asarray(query_weights, dtype=np.float64))
    return [float(np.sum(qw * row) / np.sum(qw)) for row in ndcg]
