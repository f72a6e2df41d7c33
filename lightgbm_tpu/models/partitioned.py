"""Partitioned (leaf-contiguous) tree builder: histogram cost scales
with leaf size, not dataset size.

Reference: the combination of DataPartition (data_partition.hpp:17-201,
contiguous per-leaf row indices), OrderedSparseBin's leaf-grouped
re-partitioning (ordered_sparse_bin.hpp:25-133) and the ordered-
gradient gathers of SerialTreeLearner::BeforeFindBestSplit
(serial_tree_learner.cpp:236-337) — the reference's machinery for
making per-leaf histogram cost proportional to rows-in-leaf.

This is the heaviest of the three histogram engines (see
docs/Histogram-Engine.md): the masked builder streams ALL N rows per
split (O(N), exact), the gather-compacted builder (the dense default,
ops/histogram.py compacted_histograms) gathers the child's rows into a
bucket-padded buffer (O(child rows), no layout change), and this
builder goes one further by keeping the bin matrix PHYSICALLY sorted
by leaf — no per-split O(N) mask/rank pass at all, at the cost of
moving the packed words on every split. All three share the same
per-chunk histogram kernel (ops/histogram.py _hist_chunk: one-hot MXU
contraction on TPU, segment-sum scatter-add on CPU):

- rows live in packed words (4 features/int32, ops/ordered_hist.py);
  a leaf is a position range [seg_begin[leaf], +seg_cnt[leaf]);
- a split stable-partitions the segment (ops/partition.py) — the analog
  of DataPartition::Split's per-thread buffers + prefix-sum copy-back.
  On a TPU that is ONE streaming compaction kernel call, in place
  (`partition_rows`: the row tiles covering the segment pass through
  VMEM once; no gather, no scatter, and the only windowed thing left
  is the 0/1 decision vector XLA hands it); off the TPU one vectorized
  prefix-sum pass + one scatter + gathers inside a bucketed
  `lax.switch`. Both give the same bits; the predicate that picks the
  histogram kernel picks the engine (ops/partition.py
  partition_engine);
- the smaller child's histogram streams only the chunks covering its
  segment (geometric-bucketed `lax.switch`, ops/ordered_hist.py);
  the larger child is parent - smaller, as everywhere else.

Semantics (split scans, gain formulas, tie-breaks, depth guard,
subtraction trick, leaf-wise best-leaf order) are identical to the
masked builder; only the row-summation ORDER inside a histogram
differs, so f32 round-off can differ in the last ulps. The serial
masked builder remains the reference point for the exact
serial == parallel equality tests (tests/test_parallel.py).

Everything runs inside one `lax.fori_loop` — no host round-trips — so
the fused multi-iteration trainer (models/gbdt.py train_many) embeds
this builder exactly like the masked one.
"""

import jax
import jax.numpy as jnp

from ..ops.ordered_hist import (bucket_sizes, cover_index,
                                segment_histograms, unpack_feature,
                                window_start)
from ..ops.pallas_hist import HIST_CHUNK
from ..ops.partition import (apply_partition, invert_permutation,
                             pack_rows, partition_engine, partition_rows,
                             segment_leaves, split_destinations, unpermute)
from ..ops.split import SplitParams, find_best_split, K_MIN_SCORE
from ..telemetry.trace import scope
from .tree_learner import (apply_tree_split, init_split_state,
                           split_hist_cache, write_candidate)


def _partition_segment_rows(rows_i, rows_f, seg_b, seg_c, feat, thr, cat,
                            decode_fn, interpret=False):
    """The TPU engine of the partition step: the split decision of the
    rows covering [seg_b, seg_b+seg_c) in XLA (`decode_fn` keeps the one
    implementation of EFB's slot decode and the categorical `==`), then
    one `partition_rows` kernel call that rewrites those rows in place.

    The decision reads a word row, and a word row of a tiled (8, N)
    array costs all eight (0.64 ms at 11.5M rows, measured), so it is
    taken on the geometric chunk bucket covering the segment
    (ops/ordered_hist.py cover_index; the histogram's window has a
    ladder of its own, hist_rungs): the switch's
    branches read a slice and return a fresh decision vector and a
    count, nothing else. The kernel has no window: it takes the
    segment's bounds as scalars and reads the decisions of the chunks it
    streams.

    rows_i / rows_f are the kernel's arrays (ops/partition.py pack_rows:
    packed words with `perm` as the last row, statistics padded to 4
    rows). Returns (rows_i, rows_f, n_left); bit for bit the arrays
    `_partition_segment` gives.
    """
    wp, n = rows_i.shape
    n_chunks = n // HIST_CHUNK

    def make_branch(bk, c_first):
        length = bk * HIST_CHUNK

        def branch(seg_b, seg_c):
            with scope("window_in"):
                start = window_start(c_first, bk, n_chunks)
                w_sl = jax.lax.dynamic_slice(
                    rows_i, (jnp.int32(0), start), (wp, length))
            with scope("decide"):
                col = decode_fn(w_sl, feat)
                go_left = jnp.where(cat, col == thr, col <= thr)
            with scope("destinations"):
                pos = start + jnp.arange(length, dtype=jnp.int32)
                n_left = jnp.sum(
                    go_left & (pos >= seg_b) & (pos < seg_b + seg_c),
                    dtype=jnp.int32)
            with scope("write_back"):
                return (jax.lax.dynamic_update_slice(
                            jnp.zeros(n, jnp.int32),
                            go_left.astype(jnp.int32), (start,)),
                        n_left)

        return branch

    with scope("partition"):
        idx, c_first = cover_index(seg_b, seg_c, n_chunks)
        go_left, n_left = jax.lax.switch(
            idx, [make_branch(b, c_first) for b in bucket_sizes(n_chunks)],
            seg_b, seg_c)
        with scope("move"):
            rows_i, rows_f = partition_rows(rows_i, rows_f, go_left,
                                            seg_b, seg_c, n_left,
                                            interpret=interpret)
    return rows_i, rows_f, n_left


def _partition_segment(words, ghc, perm, seg_b, seg_c, feat, thr, cat,
                       decode_fn):
    """The off-TPU engine of the partition step: stable-partition the
    segment [seg_b, seg_b+seg_c) by the split decision, touching only
    the geometric chunk bucket covering it.

    The permutation is identical to a full-array stable partition —
    split_destinations runs on the slice with slice-local bounds, where
    the segment's relative order is the global one — but the
    slice/gather/write-back traffic is O(bucket), not O(N): ~38x less
    movement per 63-leaf tree. Chunk-cover dispatch:
    ops/ordered_hist.py cover_index/window_start.

    decode_fn(word_slice, feat) -> the VIRTUAL feature's bin column of
    the slice (plain unpack for unbundled data; slot decode for EFB).

    Returns (words, ghc, perm, n_left) with n_left counting ALL left
    rows of the segment (in-bag + out-of-bag + padding).

    Everything here sits under the device scope `partition`, its steps
    under the sub-scopes of telemetry/trace.py DEVICE_SUBSCOPES.
    """
    w, n = words.shape
    n_chunks = n // HIST_CHUNK

    def make_branch(bk, c_first):
        length = bk * HIST_CHUNK

        def branch(seg_b, seg_c):
            with scope("window_in"):
                start = window_start(c_first, bk, n_chunks)
                w_sl = jax.lax.dynamic_slice(words, (jnp.int32(0), start),
                                             (w, length))
                g_sl = jax.lax.dynamic_slice(ghc, (jnp.int32(0), start),
                                             (3, length))
                p_sl = jax.lax.dynamic_slice(perm, (start,), (length,))
            with scope("decide"):
                col = decode_fn(w_sl, feat)
                go_left = jnp.where(cat, col == thr, col <= thr)
            with scope("destinations"):
                dest, n_left = split_destinations(go_left, seg_b - start,
                                                  seg_c)
            with scope("invert"):
                src = invert_permutation(dest)
            with scope("move"):
                w_new, g_new, p_new = apply_partition(src, w_sl, g_sl, p_sl)
            with scope("write_back"):
                return (jax.lax.dynamic_update_slice(
                            words, w_new, (jnp.int32(0), start)),
                        jax.lax.dynamic_update_slice(
                            ghc, g_new, (jnp.int32(0), start)),
                        jax.lax.dynamic_update_slice(perm, p_new, (start,)),
                        n_left)

        return branch

    with scope("partition"):
        idx, c_first = cover_index(seg_b, seg_c, n_chunks)
        return jax.lax.switch(
            idx, [make_branch(b, c_first) for b in bucket_sizes(n_chunks)],
            seg_b, seg_c)


def _identity(x):
    return x


def build_tree_partitioned(words, grad, hess, inbag, feature_mask,
                           num_bin_pf, is_cat,
                           *, num_leaves, max_bin, params: SplitParams,
                           max_depth, f_real, hist_reduce_fn=_identity,
                           expand_fn=_identity, decode_fn=None,
                           cache_hists=True, evaluate_fn=None,
                           sum_psum_fn=_identity):
    """Grow one leaf-wise tree on device over the packed-word layout.

    Args:
      words: (W, N_pad) int32 packed STORED bin columns,
        N_pad % HIST_CHUNK == 0. Unbundled: stored == virtual features,
        4 * W == the padded virtual feature count. Bundled (EFB): the
        words pack the SLOT matrix; histograms build and cache in slot
        space and `expand_fn`/`decode_fn` bridge to virtual features.
      grad, hess, inbag: (N_pad,) float32 (pad rows: inbag == 0).
      feature_mask: (F_v,) bool; num_bin_pf: (F_v,) int32;
      is_cat: (F_v,) bool — all VIRTUAL-feature space (== 4 * W only
        when unbundled).
      num_leaves, max_bin, params, max_depth, f_real: static config.
      expand_fn: stored->virtual histogram expansion for bundled
        datasets (same hook as build_tree_device; identity otherwise).
        Subtraction/caching stay in stored space — expansion happens
        only at split evaluation.
      decode_fn: (word_slice, virtual_feat) -> int32 bin column of the
        slice; defaults to a plain word unpack (unbundled).
      cache_hists: False = memory-bounded mode (histogram_pool_size
        exceeded): no (L, S, B, 3) cache — both children's segment
        histograms are computed directly at each split (cost at most
        the parent's row count instead of the smaller child's).
      evaluate_fn: optional (hist3, sum_g, sum_h, cnt) -> SplitInfo
        override, same contract as build_tree_device's: the voting
        learner keeps hist_reduce_fn=identity (LOCAL histograms) and
        does its own selective reduction here.
      sum_psum_fn: reduces the scalar root sums across row shards
        (identity whenever hist_reduce_fn already globalized them).
      hist_reduce_fn: reduction applied to every segment histogram —
        `lax.psum` over the row-shard axis for the data-parallel
        learner (the reference's histogram ReduceScatter sync point,
        data_parallel_tree_learner.cpp:155-157). Called OUTSIDE the
        bucketed lax.switch, so every shard executes the collective in
        lockstep even when their segment buckets differ. Plain f32
        psum: every shard sees the identical reduced histogram, so all
        shards take identical splits (cross-shard consistency); unlike
        the masked builder's Kahan pair_allreduce this does NOT
        guarantee last-ulp equality with the SERIAL partitioned
        builder's summation order.

    Returns the same output dict as build_tree_device (tree arrays +
    original-order row->leaf partition, local rows under shard_map).

    Every operation traced here carries one of the device scopes of
    telemetry/trace.py DEVICE_SCOPES in its op_name (`jax.named_scope`:
    HLO metadata, nothing at run time), so a profiler trace can be read
    by phase (benchmarks/scopereduce.py, docs/Observability.md).
    """
    w, n_pad = words.shape
    l = num_leaves
    b = max_bin
    f32 = jnp.float32
    s_pad = 4 * w  # STORED rows in the packed words (== padded F_v
    #                only when unbundled)
    if decode_fn is None:
        def decode_fn(w_sl, feat):
            return unpack_feature(w_sl, feat)
        assert f_real <= s_pad

    if evaluate_fn is None:
        def evaluate_fn(hist3, sum_g, sum_h, cnt):
            return find_best_split(hist3, sum_g, sum_h, cnt,
                                   num_bin_pf, is_cat, feature_mask,
                                   params)

    def scan_leaf(hist3, sum_g, sum_h, cnt):
        with scope("split_scan"):
            return evaluate_fn(expand_fn(hist3), sum_g, sum_h, cnt)

    with scope("gradients"):
        g_in = grad * inbag
        h_in = hess * inbag
        ghc0 = jnp.stack([g_in, h_in, inbag], axis=0)  # (3, N_pad)

    # which engine moves the rows (resolved at trace time, as the
    # histogram's is): "pallas" keeps the words, `perm` and the
    # statistics in the kernel's two arrays for the whole tree (the
    # histogram reads the rows it needs of either form)
    kernel_engine = partition_engine() == "pallas"
    perm0 = jnp.arange(n_pad, dtype=jnp.int32)  # position -> orig row
    if kernel_engine:
        with scope("partition"), scope("window_in"):
            words, ghc0 = pack_rows(words, ghc0, perm0)

    def leaf_histogram(words_c, ghc_c, begin, cnt):
        with scope("hist"):
            hist = segment_histograms(words_c, ghc_c, begin, cnt, b, s_pad)
        with scope("hist_reduce"):
            return hist_reduce_fn(hist)

    # ---- root ----------------------------------------------------------
    hist_root = leaf_histogram(words, ghc0, jnp.int32(0), jnp.int32(n_pad))
    # root sums from the histogram: feature 0's bins partition the rows
    with scope("split_scan"):
        local_sums = [jnp.sum(hist_root[0, :, k]) for k in range(3)]
    with scope("hist_reduce"):
        root_g, root_h, root_c = (sum_psum_fn(s) for s in local_sums)
    root_split = scan_leaf(hist_root, root_g, root_h, root_c)

    with scope("tree_state"):
        state = init_split_state(l, root_split, root_c)
        state["words"] = words
        state["ghc"] = ghc0
        if not kernel_engine:
            state["perm"] = perm0
        state["seg_begin"] = jnp.zeros(l, dtype=jnp.int32)
        # FULL row counts (in-bag + oob + pad), not the tree's in-bag
        # counts
        state["seg_cnt"] = jnp.zeros(l, dtype=jnp.int32).at[0].set(n_pad)
        if cache_hists:
            with scope("hist_cache"):
                state["hist_cache"] = (
                    jnp.zeros((l, s_pad, b, 3), dtype=f32)
                    .at[0].set(hist_root))

    def body(i, st):
        with scope("tree_state"):
            best_leaf = jnp.argmax(st["best_gain"]).astype(jnp.int32)
            gain = st["best_gain"][best_leaf]
            do = jnp.logical_and(jnp.logical_not(st["done"]), gain > 0.0)

        def no_split(st):
            st = dict(st)
            with scope("tree_state"):
                st["done"] = jnp.asarray(True)
            return st

        def do_split(st):
            st = dict(st)
            with scope("tree_state"):
                st, node, right_id, feat, thr = apply_tree_split(
                    st, i, best_leaf, gain, l)
                seg_b = st["seg_begin"][best_leaf]
                seg_c = st["seg_cnt"][best_leaf]
                cat = is_cat[feat]

            # ---- physical re-partition (DataPartition::Split),
            # bucketed to the segment's chunk range
            if kernel_engine:
                st["words"], st["ghc"], n_left = _partition_segment_rows(
                    st["words"], st["ghc"], seg_b, seg_c, feat, thr, cat,
                    decode_fn)
            else:
                (st["words"], st["ghc"], st["perm"],
                 n_left) = _partition_segment(
                    st["words"], st["ghc"], st["perm"], seg_b, seg_c,
                    feat, thr, cat, decode_fn)
            with scope("tree_state"):
                st["seg_begin"] = (st["seg_begin"]
                                   .at[right_id].set(seg_b + n_left))
                st["seg_cnt"] = (st["seg_cnt"].at[best_leaf].set(n_left)
                                 .at[right_id].set(seg_c - n_left))

            if cache_hists:
                # ---- smaller-child histogram + parent subtraction
                # smaller side by GLOBAL in-bag count, matching the
                # masked builder (data_parallel_tree_learner.cpp:178-187)
                with scope("tree_state"):
                    left_is_small = (st["best_lc"][best_leaf]
                                     <= st["best_rc"][best_leaf])
                    small_b = jnp.where(left_is_small, seg_b, seg_b + n_left)
                    small_c = jnp.where(left_is_small, n_left,
                                        seg_c - n_left)
                hist_small = leaf_histogram(st["words"], st["ghc"],
                                            small_b, small_c)
                with scope("tree_state"), scope("hist_cache"):
                    st["hist_cache"], hist_left, hist_right = (
                        split_hist_cache(st["hist_cache"], best_leaf,
                                         right_id, hist_small,
                                         left_is_small))
            else:
                # memory-bounded mode: both children's segments scanned
                hist_left = leaf_histogram(st["words"], st["ghc"],
                                           seg_b, n_left)
                hist_right = leaf_histogram(st["words"], st["ghc"],
                                            seg_b + n_left,
                                            seg_c - n_left)

            # ---- children leaf state (LeafSplits::Init after split)
            with scope("tree_state"):
                child_depth = st["leaf_depth"][best_leaf] + 1
                st["leaf_depth"] = (st["leaf_depth"]
                                    .at[best_leaf].set(child_depth)
                                    .at[right_id].set(child_depth))
                lsums = [st[k][best_leaf]
                         for k in ("best_lg", "best_lh", "best_lc")]
                rsums = [st[k][best_leaf]
                         for k in ("best_rg", "best_rh", "best_rc")]

            lsplit = scan_leaf(hist_left, *lsums)
            rsplit = scan_leaf(hist_right, *rsums)

            with scope("tree_state"):
                # max_depth guard (serial_tree_learner.cpp:238-247)
                depth_ok = jnp.logical_or(max_depth < 0,
                                          child_depth < max_depth)
                lgain = jnp.where(depth_ok, lsplit.gain, K_MIN_SCORE)
                rgain = jnp.where(depth_ok, rsplit.gain, K_MIN_SCORE)

                st = write_candidate(st, best_leaf, lsplit, lgain)
                st = write_candidate(st, right_id, rsplit, rgain)
            return st

        return jax.lax.cond(do, do_split, no_split, st)

    state = jax.lax.fori_loop(0, l - 1, body, state)
    # position->leaf map: the leaves' segments tile the positions, so it
    # is made once from their bounds (no pass over N at each split)
    with scope("tree_state"), scope("pos_leaf"):
        pos_leaf = segment_leaves(state["seg_begin"], state["seg_cnt"],
                                  n_pad)
    # original-order row->leaf map: each row moved once at tree end
    with scope("score_update"):
        perm = state["words"][-1] if kernel_engine else state["perm"]
        row_leaf = unpermute(perm, pos_leaf)
    return {
        "n_splits": state["n_splits"],
        "row_leaf": row_leaf,
        "split_feature": state["split_feature"],
        "split_threshold_bin": state["split_threshold_bin"],
        "split_gain": state["split_gain"],
        "left_child": state["left_child"],
        "right_child": state["right_child"],
        "leaf_parent": state["leaf_parent"],
        "leaf_value": state["leaf_value"],
        "leaf_count": state["leaf_count"],
        "internal_value": state["internal_value"],
        "internal_count": state["internal_count"],
    }
