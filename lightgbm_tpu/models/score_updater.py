"""ScoreUpdater: per-dataset model scores.

Reference: src/boosting/score_updater.hpp:15-85. Scores live on device as
a (num_class, N) float32 array. Train-set updates use the tree builder's
final row->leaf partition (a pure gather — the analog of the reference's
via-partition fast path Tree::AddPredictionToScore(tree_learner)).

Valid sets are scored per iteration ON DEVICE by a vectorized bin-space
tree traversal over the dataset's device bin matrix (the analog of
Tree::AddPredictionToScore(data), tree.h:211-224, which the reference
runs OpenMP-parallel inside the hot loop): every row walks the tree in
lockstep inside a `lax.while_loop` bounded by the realized depth, so a
training iteration never leaves the device. The host numpy traversal
remains for re-scoring materialized (loaded) models.
"""

import jax
import jax.numpy as jnp
import numpy as np


# XLA's TPU compiler turns a gather from a table of up to 64 entries
# into a chain of `select(index >= k, table[k], acc)` inside the
# consumer's loop fusion, which runs near the speed of memory (0.9 ms
# for 10.5M rows at 63 entries); from 65 entries on it emits a real
# gather in a fusion of its own, about 120M indices a second (86 ms at
# 255 entries; PERF.md section 6, PR 32).
LOOKUP_PIECE = 64


def lookup_form(num_leaves):
    """The gauge's word for what `leaf_lookup` traces at that length."""
    return "take" if num_leaves <= LOOKUP_PIECE else f"split{LOOKUP_PIECE}"


def leaf_lookup(leaf_value, leaf_index):
    """leaf_value[leaf_index] for a (L,) table and indices in [0, L): the
    same element, so the same bits. The table is cut into pieces of
    LOOKUP_PIECE entries, each looked up by its own `take`, and a select
    between them; a table of one piece is that `take` alone.

    `take` in its default mode (NaN for an index outside the piece,
    which the select between pieces never picks): its select stands
    between the table's entry and the score's add. A caller that scales
    the values scales the TABLE (the fused step: `leaf_value * shrink`,
    L products of the same two floats a row would multiply); the CPU
    compiler computes such a product again a row, and with nothing
    between it and the add contracts the two into a fused multiply-add
    that rounds once where the per-iteration loop and the reference
    round twice (`mode="clip"` and `promise_in_bounds` do that)."""
    out = None
    for lo in range(0, leaf_value.shape[0], LOOKUP_PIECE):
        sub = jnp.take(leaf_value[lo:lo + LOOKUP_PIECE], leaf_index - lo)
        out = sub if out is None else jnp.where(leaf_index >= lo, sub, out)
    return out


# the per-iteration loop's call: one program, not a dispatch a piece
_leaf_lookup_jit = jax.jit(leaf_lookup)


def _traverse_add(score_row, bins_dev, is_cat, split_feature, threshold_bin,
                  left_child, right_child, leaf_value, n_splits, scale,
                  feat_slot, feat_off, feat_nb):
    """score_row + scale * leaf_value[leaf(bins)] for one tree, on device.

    bins_dev: (S, N) STORED bins (S == F when unbundled); virtual feature
    f lives in slot feat_slot[f] at bin offset feat_off[f] with
    feat_nb[f] bins (identity maps when no bundling — the decode below
    reduces to the raw bin value). Tree arrays as produced by
    build_tree_device (leaves encoded as ~leaf_index in child arrays).
    A 0-split tree contributes leaf_value[0] == 0, so it is a no-op.
    """
    n = bins_dev.shape[1]
    node0 = jnp.where(n_splits > 0, 0, -1)
    node = jnp.full((n,), node0, dtype=jnp.int32)

    def cond(state):
        i, node = state
        return jnp.logical_and(i < leaf_value.shape[0] - 1,
                               jnp.any(node >= 0))

    def body(state):
        i, node = state
        nd = jnp.maximum(node, 0)
        feat = split_feature[nd]
        sc = jnp.take_along_axis(bins_dev, feat_slot[feat][None, :],
                                 axis=0)[0].astype(jnp.int32)
        off = feat_off[feat]
        nb = feat_nb[feat]
        fv = jnp.where((sc > off) & (sc <= off + nb - 1), sc - off, 0)
        thr = threshold_bin[nd]
        go_left = jnp.where(is_cat[feat], fv == thr, fv <= thr)
        nxt = jnp.where(go_left, left_child[nd], right_child[nd])
        node = jnp.where(node < 0, node, nxt)
        return i + 1, node

    _, node = jax.lax.while_loop(cond, body, (jnp.asarray(0, jnp.int32), node))
    leaf = jnp.where(node < 0, ~node, 0)
    return score_row + scale * jnp.take(leaf_value, leaf)


_traverse_add_jit = jax.jit(_traverse_add)


@jax.jit
def _stacked_deltas(bins_dev, is_cat, sf, thr, lc, rc, lv, nsp, scale,
                    feat_slot, feat_off, feat_nb):
    """(M, ...) stacked tree arrays -> (M, N) scaled score deltas.

    One vmapped bin-space traversal over the tree axis: the whole
    block's valid/train scoring is a single device program (the
    reference re-walks the dataset per tree inside the training loop,
    gbdt.cpp:210-245 + tree.h:211-224)."""
    zero = jnp.zeros((bins_dev.shape[1],), jnp.float32)

    def one(sfi, thri, lci, rci, lvi, nspi):
        return _traverse_add(zero, bins_dev, is_cat, sfi, thri, lci, rci,
                             lvi.astype(jnp.float32), nspi, scale,
                             feat_slot, feat_off, feat_nb)

    return jax.vmap(one)(sf, thr, lc, rc, lv, nsp)


class ScoreUpdater:
    """`score` is the (num_class, N) float32 score in dataset row order.
    A row-sharded trainer (the fused data-parallel scan) keeps it as
    (num_class, N_pad) with its learner's row sharding instead
    (`set_row_layout`, `carried` / `set_carried`): rows in dataset order,
    the padding after row N - 1, every row on the chip that holds its
    bins. `score` is then the first N columns of that array, cut when
    read; an assignment to `score` is padded and placed again when the
    trainer next asks for `padded`."""

    def __init__(self, dataset, num_class):
        self.dataset = dataset
        self.num_class = int(num_class)
        n = dataset.num_data
        self.num_data = n
        self._is_cat_dev = None
        self._decode_dev = None
        self._layout = None      # (N_pad, sharding) of the padded form
        self._padded = None      # (K, N_pad) on the layout, or None
        self._score = None       # (K, N), or None while only _padded is
        init = dataset.metadata.init_score
        if init is not None:
            if len(init) != n * self.num_class:
                from ..utils.log import Log
                Log.fatal("Number of class for initial score error")
            self.score = jnp.asarray(
                np.asarray(init, dtype=np.float32).reshape(self.num_class, n))
        else:
            self.score = jnp.zeros((self.num_class, n), dtype=jnp.float32)

    @property
    def score(self):
        if self._score is None:
            self._score = self._padded[:, :self.num_data]
        return self._score

    @score.setter
    def score(self, value):
        self._score = value
        self._padded = None

    def set_row_layout(self, n_pad, sharding):
        """Keep the score padded to `n_pad` rows on `sharding` (a
        (K, N_pad) placement) from the next `padded` on."""
        if self._layout != (n_pad, sharding):
            self.score = self.score
            self._layout = (n_pad, sharding)

    @property
    def padded(self):
        """The (K, N_pad) score on the row layout: zeros past row N - 1
        when made from `score`."""
        if self._padded is None:
            n_pad, sharding = self._layout
            self._padded = jax.device_put(
                jnp.pad(self.score, ((0, 0), (0, n_pad - self.num_data))),
                sharding)
        return self._padded

    def carried(self):
        """The score a fused block takes: `padded` on a row layout,
        else `score`."""
        return self.score if self._layout is None else self.padded

    def set_carried(self, value):
        """Take the score a fused block returns (see `carried`); on a
        row layout `score` is cut from it when next read."""
        if self._layout is None:
            self.score = value
        else:
            self._padded, self._score = value, None

    def add_score_by_partition(self, leaf_values, row_leaf, curr_class):
        """score += leaf_values[row_leaf] (device lookup)."""
        upd = _leaf_lookup_jit(
            jnp.asarray(leaf_values, dtype=jnp.float32), row_leaf)
        self.score = self.score.at[curr_class].add(upd)

    def add_score_by_values(self, values, curr_class):
        """score += values: one (N,) per-row delta computed on host —
        the linear-leaf training path (models/linear_leaves.py), where
        a leaf's contribution varies per row so a leaf-value gather
        cannot express it."""
        self.score = self.score.at[curr_class].add(
            jnp.asarray(np.asarray(values, dtype=np.float32)))

    def _tree_bin_values(self, tree):
        """Bin representative table when `tree` needs one (linear
        leaves), else None — keeps the constant-leaf path allocation-
        free and works on datasets with no resident table."""
        if getattr(tree, "is_linear", False):
            return self.dataset.bin_value_table()
        return None

    def _decode_maps(self):
        """(feat_slot, feat_off, feat_nb) device arrays: bundle decode
        when the dataset is bundled, identity maps otherwise."""
        if self._decode_dev is None:
            ds = self.dataset
            nb = np.asarray(ds.num_bin_array(), dtype=np.int32)
            if ds.bundle_plan is None:
                slot = np.arange(ds.num_features, dtype=np.int32)
                off = np.zeros(ds.num_features, dtype=np.int32)
            else:
                slot = ds.bundle_plan.feat_slot
                off = ds.bundle_plan.feat_offset
            self._decode_dev = (jnp.asarray(slot), jnp.asarray(off),
                                jnp.asarray(nb))
        return self._decode_dev

    def add_score_by_device_tree(self, out, scale, curr_class):
        """Per-iteration valid-set scoring: device bin-space traversal of
        the builder's raw output dict. No host synchronization."""
        if self._is_cat_dev is None:
            self._is_cat_dev = jnp.asarray(self.dataset.feature_is_categorical())
        feat_slot, feat_off, feat_nb = self._decode_maps()
        new_row = _traverse_add_jit(
            self.score[curr_class], self.dataset.device_bins(),
            self._is_cat_dev, out["split_feature"],
            out["split_threshold_bin"], out["left_child"],
            out["right_child"],
            jnp.asarray(out["leaf_value"], dtype=jnp.float32),
            out["n_splits"], jnp.float32(scale),
            feat_slot, feat_off, feat_nb)
        self.score = self.score.at[curr_class].set(new_row)

    def deltas_by_stacked_device_trees(self, stk, scale):
        """(M, N) scaled deltas for M stacked builder-output trees (the
        dict's arrays carry a flattened leading tree axis). Device-only;
        no host sync. Used by GBDT.train_many_eval's per-iteration
        score snapshots."""
        if self._is_cat_dev is None:
            self._is_cat_dev = jnp.asarray(
                self.dataset.feature_is_categorical())
        feat_slot, feat_off, feat_nb = self._decode_maps()
        return _stacked_deltas(
            self.dataset.device_bins(), self._is_cat_dev,
            stk["split_feature"], stk["split_threshold_bin"],
            stk["left_child"], stk["right_child"], stk["leaf_value"],
            stk["n_splits"], jnp.float32(scale),
            feat_slot, feat_off, feat_nb)

    def add_score_by_tree(self, tree, curr_class):
        """Host bin-space traversal (re-scoring loaded/materialized models)."""
        vals = tree.predict_by_bins(
            self.dataset.traversal_bins(),
            self._tree_bin_values(tree)).astype(np.float32)
        self.score = self.score.at[curr_class].add(jnp.asarray(vals))

    def sub_score_by_tree(self, tree, curr_class):
        vals = tree.predict_by_bins(
            self.dataset.traversal_bins(),
            self._tree_bin_values(tree)).astype(np.float32)
        self.score = self.score.at[curr_class].add(jnp.asarray(-vals))

    def add_score_by_trees(self, trees, num_class, sign=1.0):
        """Batched update from many class-major trees: one host pass and
        ONE device update total. sign=+1: valid-score catch-up after a
        fused block (gbdt.train_many); sign=-1: early-stopping
        truncation."""
        delta = np.zeros((self.num_class, self.num_data), dtype=np.float32)
        for i, tree in enumerate(trees):
            delta[i % num_class] += sign * tree.predict_by_bins(
                self.dataset.traversal_bins(), self._tree_bin_values(tree))
        self.score = self.score + jnp.asarray(delta)

    def sub_score_by_trees(self, trees, num_class):
        self.add_score_by_trees(trees, num_class, sign=-1.0)

    def host_score(self):
        """Flat class-major (K*N,) float64 host array (the reference's
        score layout, score[k*N + i])."""
        return np.asarray(self.score, dtype=np.float64).reshape(-1)
