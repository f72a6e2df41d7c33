"""Serial tree learner: the whole leaf-wise tree build as ONE device program.

Reference: src/treelearner/serial_tree_learner.cpp:19-442 (leaf-wise loop),
src/treelearner/data_partition.hpp (row->leaf partition),
src/treelearner/leaf_splits.hpp (per-leaf state),
src/treelearner/feature_histogram.hpp:97-106 (subtraction trick).

TPU-first design (diverges deliberately from the C++ class graph):

- The entire tree grows inside one jitted `lax.fori_loop`: static
  shapes, no host round-trips per split.
- The row partition is ONLY the dense (N,) `row_leaf` map. The
  reference's DataPartition (ordered row indices per leaf,
  data_partition.hpp:90-140) exists to make per-leaf histogram cost
  proportional to leaf size via gathers; on TPU random gathers are
  latency-bound, so per-split histograms instead stream the full bin
  matrix with the leaf selected by a row_leaf mask — sequential HBM
  reads at full bandwidth (ops/pallas_hist.py). Updating the partition
  after a split is a single vectorized `where` on row_leaf.
- Histograms: only the SMALLER child (by global in-bag count) is
  computed per split; the larger child is parent − smaller from a
  per-leaf (L, F, B, 3) histogram cache (the subtraction trick; the
  reference's LRU HistogramPool becomes a fixed HBM buffer — 63 leaves
  × 28 feat × 256 bins × 3 stats ≈ 5 MB for the HIGGS shape).
- Collectives are injected through hooks so the parallel learners
  (parallel/learners.py) reuse this exact builder under `shard_map`:
  `hist_psum_fn` reduces histograms across row shards (the reference's
  ReduceScatter sync point), `sum_psum_fn` reduces root sums, and
  `evaluate_fn`/`split_col_fn` override split search and split-column
  fetch for the feature-parallel / voting learners.

Split semantics (gain formulas, epsilons, tie-breaks, max_depth guard,
min_data/min_sum_hessian constraints) follow the reference exactly; see
ops/split.py.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.histogram import compacted_histograms, frontier_histograms
from ..ops.ordered_hist import canonical_row_chunks
from ..ops.pallas_hist import masked_histograms, HIST_CHUNK
from ..ops.split import SplitParams, find_best_split, K_MIN_SCORE
from ..utils.random import Random
from ..utils.log import Log
from .tree import Tree


def _identity(x):
    return x


def _tristate(value, name):
    """Normalize a config tri-state to "auto"/"true"/"false"."""
    mode = str(value).lower()
    if mode in ("true", "1", "on", "+"):
        return "true"
    if mode in ("false", "0", "off", "-"):
        return "false"
    if mode != "auto":
        Log.fatal('%s must be "auto", "true" or "false", got [%s]',
                  name, mode)
    return "auto"


def chunk_pad(n, bucketing):
    """HIST_CHUNK-granular row padding, canonicalized to the
    shape-bucket grid (`bucketing`, config shape_bucketing) so nearby
    dataset sizes reuse one lowered executable from the persistent
    compile cache."""
    n_chunks = (n + HIST_CHUNK - 1) // HIST_CHUNK
    if bucketing:
        n_chunks = canonical_row_chunks(n_chunks)
    return n_chunks * HIST_CHUNK


def shape_bucketing(cfg):
    """Whether row padding follows the shape-bucket grid (chunk_pad)."""
    return _tristate(getattr(cfg, "shape_bucketing", "auto"),
                     "shape_bucketing") != "false"


def _partitioned_mode(cfg):
    """Validate + normalize partitioned_build to "auto"/"true"/"false"."""
    return _tristate(getattr(cfg, "partitioned_build", "auto"),
                     "partitioned_build")


def pow2_scan_chunk(chunk):
    """Largest power-of-two scan chunk <= `chunk`, capped at HIST_CHUNK —
    the only values guaranteed to divide HIST_CHUNK-padded row counts.
    Shared by the serial and meshed learners' _effective_chunk."""
    if chunk >= HIST_CHUNK:
        return HIST_CHUNK
    return 1 << (max(int(chunk), 1).bit_length() - 1)


def init_split_state(l, root_split, root_c):
    """Per-leaf candidate + tree arrays shared by both builders
    (masked build_tree_device and models/partitioned.py)."""
    f32 = jnp.float32

    def set0(arr, v):
        return arr.at[0].set(v)

    return {
        "done": jnp.asarray(False),
        "n_splits": jnp.asarray(0, dtype=jnp.int32),
        # per-leaf split candidates (LeafSplits + best_split_per_leaf_)
        "best_gain": jnp.full(l, K_MIN_SCORE, dtype=f32).at[0].set(root_split.gain),
        "best_feature": set0(jnp.zeros(l, jnp.int32), root_split.feature),
        "best_threshold": set0(jnp.zeros(l, jnp.int32), root_split.threshold),
        "best_lg": set0(jnp.zeros(l, f32), root_split.left_sum_gradient),
        "best_lh": set0(jnp.zeros(l, f32), root_split.left_sum_hessian),
        "best_lc": set0(jnp.zeros(l, f32), root_split.left_count),
        "best_rg": set0(jnp.zeros(l, f32), root_split.right_sum_gradient),
        "best_rh": set0(jnp.zeros(l, f32), root_split.right_sum_hessian),
        "best_rc": set0(jnp.zeros(l, f32), root_split.right_count),
        "best_lout": set0(jnp.zeros(l, f32), root_split.left_output),
        "best_rout": set0(jnp.zeros(l, f32), root_split.right_output),
        "leaf_depth": jnp.zeros(l, dtype=jnp.int32),
        # tree arrays (models/tree.py)
        "split_feature": jnp.zeros(l - 1, dtype=jnp.int32),
        "split_threshold_bin": jnp.zeros(l - 1, dtype=jnp.int32),
        "split_gain": jnp.zeros(l - 1, dtype=f32),
        "left_child": jnp.zeros(l - 1, dtype=jnp.int32),
        "right_child": jnp.zeros(l - 1, dtype=jnp.int32),
        "leaf_parent": jnp.full(l, -1, dtype=jnp.int32),
        "leaf_value": jnp.zeros(l, dtype=f32),
        "leaf_count": jnp.zeros(l, dtype=jnp.int32).at[0].set(root_c.astype(jnp.int32)),
        "internal_value": jnp.zeros(l - 1, dtype=f32),
        "internal_count": jnp.zeros(l - 1, dtype=jnp.int32),
    }


def apply_tree_split(st, i, best_leaf, gain, l):
    """Tree bookkeeping for splitting `best_leaf` at iteration i
    (Tree::Split, tree.cpp:51-97).
    Returns (st, node, right_id, split_feature, split_threshold_bin)."""
    node = i  # splits happen on consecutive iterations
    right_id = i + 1  # new leaf id == num_leaves so far (tree.cpp:55)
    feat = st["best_feature"][best_leaf]
    thr = st["best_threshold"][best_leaf]

    parent = st["leaf_parent"][best_leaf]
    was_left = st["left_child"][jnp.maximum(parent, 0)] == ~best_leaf
    lc = st["left_child"]
    rc = st["right_child"]
    lc = jnp.where(
        (jnp.arange(l - 1) == parent) & (parent >= 0) & was_left, node, lc)
    rc = jnp.where(
        (jnp.arange(l - 1) == parent) & (parent >= 0) & ~was_left, node, rc)
    st["left_child"] = lc.at[node].set(~best_leaf)
    st["right_child"] = rc.at[node].set(~right_id)
    st["split_feature"] = st["split_feature"].at[node].set(feat)
    st["split_threshold_bin"] = st["split_threshold_bin"].at[node].set(thr)
    st["split_gain"] = st["split_gain"].at[node].set(gain)
    st["leaf_parent"] = (st["leaf_parent"].at[best_leaf].set(node)
                         .at[right_id].set(node))
    st["internal_value"] = st["internal_value"].at[node].set(
        st["leaf_value"][best_leaf])
    st["internal_count"] = st["internal_count"].at[node].set(
        (st["best_lc"][best_leaf] + st["best_rc"][best_leaf]).astype(jnp.int32))
    st["leaf_value"] = (st["leaf_value"]
                        .at[best_leaf].set(st["best_lout"][best_leaf])
                        .at[right_id].set(st["best_rout"][best_leaf]))
    st["leaf_count"] = (st["leaf_count"]
                        .at[best_leaf].set(st["best_lc"][best_leaf].astype(jnp.int32))
                        .at[right_id].set(st["best_rc"][best_leaf].astype(jnp.int32)))
    st["n_splits"] = st["n_splits"] + 1
    return st, node, right_id, feat, thr


def write_candidate(st, leaf_id, sp, gain_v):
    """Store a leaf's best-split candidate in the per-leaf state."""
    st["best_gain"] = st["best_gain"].at[leaf_id].set(gain_v)
    st["best_feature"] = st["best_feature"].at[leaf_id].set(sp.feature)
    st["best_threshold"] = st["best_threshold"].at[leaf_id].set(sp.threshold)
    st["best_lg"] = st["best_lg"].at[leaf_id].set(sp.left_sum_gradient)
    st["best_lh"] = st["best_lh"].at[leaf_id].set(sp.left_sum_hessian)
    st["best_lc"] = st["best_lc"].at[leaf_id].set(sp.left_count)
    st["best_rg"] = st["best_rg"].at[leaf_id].set(sp.right_sum_gradient)
    st["best_rh"] = st["best_rh"].at[leaf_id].set(sp.right_sum_hessian)
    st["best_rc"] = st["best_rc"].at[leaf_id].set(sp.right_count)
    st["best_lout"] = st["best_lout"].at[leaf_id].set(sp.left_output)
    st["best_rout"] = st["best_rout"].at[leaf_id].set(sp.right_output)
    return st


def split_hist_cache(cache, best_leaf, right_id, hist_small,
                     left_is_small):
    """A split's update of the per-leaf histogram cache: the larger
    child by parent subtraction, then the parent's row takes the left
    child and row `right_id` the right one. Returns (cache, hist_left,
    hist_right).

    The parent's row is read once, before either write: both children
    pass an optimization barrier as finished values, so the compiler
    cannot fuse the subtraction a second time into the second write.
    A read of the old cache placed after a write keeps the old buffer
    alive, and the loop then copies the whole (L, F, B, 3) carry twice
    a split, where the writes are two rows in place."""
    hist_large = cache[best_leaf] - hist_small
    hist_left = jnp.where(left_is_small, hist_small, hist_large)
    hist_right = jnp.where(left_is_small, hist_large, hist_small)
    hist_left, hist_right = jax.lax.optimization_barrier(
        (hist_left, hist_right))
    cache = cache.at[best_leaf].set(hist_left).at[right_id].set(hist_right)
    return cache, hist_left, hist_right


def _collapse_pair(pair):
    """Default hist reduction hook: no shards, just collapse the
    compensated (value, residual) pair."""
    hi, lo = pair
    return hi + lo


def build_tree_device(bins, grad, hess, inbag, feature_mask,
                      num_bin_pf, is_cat,
                      *, num_leaves, max_bin, params: SplitParams,
                      max_depth, row_chunk,
                      hist_psum_fn=_collapse_pair, sum_psum_fn=_identity,
                      evaluate_fn=None, split_col_fn=None,
                      expand_fn=_identity, cache_hists=True,
                      compact_hist=False):
    """Grow one leaf-wise tree on device. All shapes static.

    Args:
      bins: (F, N_pad) int bins (pad rows have no effect: inbag=0 there).
      grad, hess: (N_pad,) float32.
      inbag: (N_pad,) float32 0/1 bagging+validity mask.
      feature_mask: (F,) bool feature_fraction mask.
      num_bin_pf: (F,) int32 bins per feature; is_cat: (F,) bool.
      num_leaves/max_bin/params/max_depth/row_chunk: static config.
      hist_psum_fn: takes the compensated (hist, residual) pair from
        masked_histograms and returns the reduced+collapsed histogram.
        Default: collapse only (single device / feature-sharded
        learner); the data-parallel learner reduces shard pairs in a
        FIXED order so every shard (and the serial learner) sees
        histograms equal to ~f64 accuracy — the reference gets the same
        guarantee from f64 accumulators (bin.h:18-26). The reduction
        may RETURN FEWER FEATURES than it was fed: the reduce-scatter
        exchange (parallel/mesh.py) hands each shard only its owned
        (f_loc, B, 3) block, and the histogram cache, subtraction trick
        and evaluate_fn all operate in that owned space (the builder
        sizes them from the reduced root histogram, not from `bins`).
      sum_psum_fn: reduces scalar root sums across row shards. Root
        sums are derived FROM the reduced histogram (any feature's bins
        partition the rows), so learners whose hist_psum_fn already
        produces the global histogram pass identity here.
      evaluate_fn: optional (hist3, sum_g, sum_h, cnt) -> SplitInfo
        override. `hist3` is the hist_psum_fn-reduced histogram for the
        serial/data-parallel learners; the voting learner keeps the
        default pair-collapse (so hist3 is its LOCAL histogram) and does
        its own selective reduction here
        (voting_parallel_tree_learner.cpp:137-293).
      split_col_fn: optional (feature_id) -> (N_pad,) int32 bin column,
        overridden by the feature-parallel learner to broadcast the
        owner shard's column, and by bundled datasets to decode a
        virtual feature out of its slot.
      expand_fn: stored->virtual histogram expansion for bundled
        datasets (io/bundling.py); identity otherwise. Histograms are
        cached and subtracted in STORED space (cheap), expanded only at
        split evaluation.
      cache_hists: keep the (L, F, B, 3) per-leaf histogram cache and
        get the larger child by parent subtraction (the reference's
        HistogramPool fast path). False = memory-bounded mode
        (histogram_pool_size exceeded, feature_histogram.hpp:337-481's
        LRU analog): both children's histograms are recomputed at each
        split, memory O(F * B) instead of O(L * F * B).
      compact_hist: per-split child histograms gather the leaf's rows
        into a bucket-padded contiguous buffer first (ops/histogram.py
        compacted_histograms) — cost O(rows-in-child) instead of the
        full-scan's O(N); N_pad must then be a multiple of HIST_CHUNK.
        The root histogram stays a full streaming scan (its bucket IS
        the whole array). Works under every collective hook: the pair
        contract is unchanged and the bucketed lax.switch holds no
        collectives, so hist_psum_fn still meets shards in lockstep.

    The root/bagging re-init pass runs through the multi-leaf frontier
    primitive (ops/histogram.py frontier_histograms), and the cache-less
    masked path builds BOTH children of a split in one data pass
    instead of two. Per-leaf values are what the single-leaf kernels
    give (same chunk decomposition and accumulation order), so this
    changes pass count, not numerics.

    Returns a dict of tree arrays + the final row->leaf partition.
    """
    f, n_pad = bins.shape
    l = num_leaves
    b = max_bin
    f32 = jnp.float32

    if evaluate_fn is None:
        def evaluate_fn(hist3, sum_g, sum_h, cnt):
            return find_best_split(hist3, sum_g, sum_h, cnt,
                                   num_bin_pf, is_cat, feature_mask, params)

    def scan_leaf(hist3, sum_g, sum_h, cnt):
        return evaluate_fn(expand_fn(hist3), sum_g, sum_h, cnt)

    if split_col_fn is None:
        def split_col_fn(feat):
            return jnp.take(bins, feat, axis=0).astype(jnp.int32)

    g_in = grad * inbag
    h_in = hess * inbag
    # packed per-row stats, stats-major for the masked histogram kernel
    ghc_t = jnp.stack([g_in, h_in, inbag], axis=0)  # (3, N_pad)

    if compact_hist:
        def leaf_histogram(row_leaf, leaf_id):
            """Gather-compacted smaller-child pass: stream only the
            geometric chunk bucket covering the leaf's rows."""
            return compacted_histograms(bins, ghc_t, row_leaf, leaf_id,
                                        b, row_chunk)
    else:
        def leaf_histogram(row_leaf, leaf_id):
            """Full-bandwidth streaming pass selecting `leaf_id`'s rows
            by mask (ops/pallas_hist.py) — the TPU replacement for the
            reference's ordered-gather ConstructHistogram."""
            return masked_histograms(bins, ghc_t, row_leaf, leaf_id, b,
                                     row_chunk)

    # ---- root ----------------------------------------------------------
    # (re)built at every tree under bagging/GOSS: the in-bag weights
    # rode in through ghc_t, so this full pass IS the bagging re-init
    row_leaf0 = jnp.zeros(n_pad, dtype=jnp.int32)
    root_pair = frontier_histograms(bins, ghc_t, row_leaf0,
                                    jnp.zeros(1, jnp.int32), b, row_chunk)
    hist_root = hist_psum_fn(root_pair)[0]
    # root sums from the reduced histogram: feature 0's bins partition
    # the rows, so its bin sums ARE the leaf totals — this keeps parent
    # sums bit-consistent with the histogram across serial/parallel
    root_g = sum_psum_fn(jnp.sum(hist_root[0, :, 0]))
    root_h = sum_psum_fn(jnp.sum(hist_root[0, :, 1]))
    root_c = sum_psum_fn(jnp.sum(hist_root[0, :, 2]))
    root_split = scan_leaf(hist_root, root_g, root_h, root_c)

    state = init_split_state(l, root_split, root_c)
    state["row_leaf"] = row_leaf0
    # feature count of the REDUCED histogram space: equals f except
    # under a scattering hist_psum_fn (reduce-scatter hands each shard
    # its owned f_loc block; cache/subtraction stay in owned space)
    f_hist = hist_root.shape[0]
    if cache_hists:
        # per-leaf histogram cache (HistogramPool, fixed buffer)
        state["hist_cache"] = (jnp.zeros((l, f_hist, b, 3), dtype=f32)
                               .at[0].set(hist_root))

    def body(i, st):
        best_leaf = jnp.argmax(st["best_gain"]).astype(jnp.int32)
        gain = st["best_gain"][best_leaf]
        do = jnp.logical_and(jnp.logical_not(st["done"]), gain > 0.0)

        def no_split(st):
            st = dict(st)
            st["done"] = jnp.asarray(True)
            return st

        def do_split(st):
            st = dict(st)
            st, node, right_id, feat, thr = apply_tree_split(
                st, i, best_leaf, gain, l)

            # ---- partition update (DataPartition::Split): one where()
            col = split_col_fn(feat)
            go_left_row = jnp.where(is_cat[feat], col == thr, col <= thr)
            in_leaf = st["row_leaf"] == best_leaf
            st["row_leaf"] = jnp.where(in_leaf & ~go_left_row, right_id,
                                       st["row_leaf"])

            if cache_hists:
                # ---- smaller-child histogram + parent subtraction
                # smaller side by GLOBAL in-bag count (consistent across
                # row shards; data_parallel_tree_learner.cpp:178-187)
                left_is_small = (st["best_lc"][best_leaf]
                                 <= st["best_rc"][best_leaf])
                small_leaf = jnp.where(left_is_small, best_leaf, right_id)
                hist_small = hist_psum_fn(leaf_histogram(
                    st["row_leaf"], small_leaf.astype(jnp.int32)))
                st["hist_cache"], hist_left, hist_right = split_hist_cache(
                    st["hist_cache"], best_leaf, right_id, hist_small,
                    left_is_small)
            elif not compact_hist:
                # memory-bounded mode, frontier-batched: BOTH children
                # from ONE streamed pass (leaf-indexed accumulator) —
                # half the full-matrix streams of the two-pass
                # recompute below
                leaf_vec = jnp.stack([best_leaf,
                                      right_id]).astype(jnp.int32)
                both = hist_psum_fn(frontier_histograms(
                    bins, ghc_t, st["row_leaf"], leaf_vec, b, row_chunk))
                hist_left, hist_right = both[0], both[1]
            else:
                # memory-bounded mode over compacted rows: both
                # children recomputed, each at its own row count
                hist_left = hist_psum_fn(
                    leaf_histogram(st["row_leaf"], best_leaf))
                hist_right = hist_psum_fn(
                    leaf_histogram(st["row_leaf"], right_id))

            # ---- children leaf state (LeafSplits::Init after split)
            child_depth = st["leaf_depth"][best_leaf] + 1
            st["leaf_depth"] = (st["leaf_depth"].at[best_leaf].set(child_depth)
                                .at[right_id].set(child_depth))

            lsplit = scan_leaf(hist_left, st["best_lg"][best_leaf],
                               st["best_lh"][best_leaf], st["best_lc"][best_leaf])
            rsplit = scan_leaf(hist_right, st["best_rg"][best_leaf],
                               st["best_rh"][best_leaf], st["best_rc"][best_leaf])

            # max_depth guard (serial_tree_learner.cpp:238-247)
            depth_ok = jnp.logical_or(max_depth < 0, child_depth < max_depth)
            lgain = jnp.where(depth_ok, lsplit.gain, K_MIN_SCORE)
            rgain = jnp.where(depth_ok, rsplit.gain, K_MIN_SCORE)

            st = write_candidate(st, best_leaf, lsplit, lgain)
            st = write_candidate(st, right_id, rsplit, rgain)
            return st

        return jax.lax.cond(do, do_split, no_split, st)

    state = jax.lax.fori_loop(0, l - 1, body, state)
    return {
        "n_splits": state["n_splits"],
        "row_leaf": state["row_leaf"],
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


def cache_hists_fits(cfg, stored, max_bin):
    """Whether the per-leaf histogram cache (the fixed-buffer
    HistogramPool analog) fits the configured budget. The reference
    LRU-pages histograms under histogram_pool_size MB
    (feature_histogram.hpp:337-481); dynamic eviction is XLA-hostile,
    so over budget we instead RECOMPUTE both children's histograms at
    each split (no parent subtraction): memory drops from
    O(num_leaves * F * B) to O(F * B), cost at most doubles.

    ONE shared rule: cache-vs-recompute changes the f32 histogram
    arithmetic (parent subtraction vs direct build), so the out-of-core
    streaming learner must make the identical decision to the in-RAM
    masked engine or its bit-parity contract breaks at configs near the
    pool boundary (lightgbm_tpu/data/ooc_learner.py)."""
    cache_mb = (int(cfg.num_leaves) * stored * max_bin * 3 * 4
                ) / (1024.0 * 1024.0)
    pool = float(cfg.histogram_pool_size)
    if 0 <= pool < cache_mb:
        Log.info("Histogram cache (%.0f MB at %d leaves x %d stored "
                 "features x %d bins) exceeds histogram_pool_size="
                 "%.0f MB: recomputing child histograms instead of "
                 "caching for subtraction", cache_mb,
                 int(cfg.num_leaves), stored, max_bin, pool)
        return False
    if pool < 0 and cache_mb > 4096:
        Log.warning("Histogram cache needs %.0f MB of device memory "
                    "(%d leaves x %d stored features x %d bins); set "
                    "histogram_pool_size (MB) to cap it via "
                    "recompute mode", cache_mb, int(cfg.num_leaves),
                    stored, max_bin)
    return True


class SerialTreeLearner:
    """Host-side driver owning the jitted builder (tree_learner.h:19-71)."""

    name = "serial"

    def __init__(self, config):
        self.config = config
        self.random = Random(config.feature_fraction_seed)
        self.train_set = None
        # persistent compile cache: the jitted builders are the
        # process's big XLA programs — make their compile a
        # once-per-machine cost (config.py setup_compilation_cache)
        from ..config import setup_compilation_cache
        setup_compilation_cache(config)

    def init(self, train_set):
        if getattr(train_set, "block_store", None) is not None:
            Log.fatal("the training data is an out-of-core block store "
                      "but out_of_core=false; set out_of_core=true (or "
                      "rebuild the dataset in-RAM)")
        self.train_set = train_set
        cfg = self.config
        self.num_features = train_set.num_features
        self.num_data = train_set.num_data
        # histogram width follows the STORED matrix (bundle slots pack
        # several features' bin ranges; io/bundling.py)
        self.max_bin = int(train_set.max_stored_bin)
        self._bundle = train_set.bundle_plan
        self._use_partitioned = self._partitioned_enabled(cfg)
        self._use_compact = self._compaction_enabled(cfg)
        self._use_shape_bucketing = shape_bucketing(cfg)
        if self._bundle is not None:
            from ..io.bundling import expansion_maps
            src, slot_of = expansion_maps(self._bundle, train_set.bin_mappers,
                                          int(train_set.max_num_bin))
            self._bundle_src = self._place_rep(src)
            self._bundle_slot_of = self._place_rep(slot_of)
            self._bundle_feat_slot = self._place_rep(self._bundle.feat_slot)
            self._bundle_feat_off = self._place_rep(self._bundle.feat_offset)
        chunk = int(cfg.device_row_chunk)
        n_pad = self._pad_rows(self.num_data, chunk)
        self.n_pad = n_pad
        chunk = self._effective_chunk(chunk)
        self.row_chunk = chunk
        # bins a mesh already holds as this learner shards them (the
        # data-parallel learner: io/dataset.py RowShards) stay there
        resident = self._resident_bins(train_set, n_pad)
        bins = train_set.bins if resident is None else None
        if resident is None and n_pad != self.num_data:
            pad = np.zeros((bins.shape[0], n_pad - self.num_data), dtype=bins.dtype)
            bins = np.concatenate([bins, pad], axis=1)
        if self._bundle is not None and self._use_partitioned:
            # bundled + partitioned: the packed words carry the STORED
            # slot matrix (padded to the packer's 4-per-word alignment)
            # while the split scan stays in VIRTUAL feature space via
            # the expand/decode hooks — so the virtual arrays
            # (num_bin_pf / is_cat / feature masks) are NOT padded
            s_rows = bins.shape[0]
            s_pad = ((s_rows + 3) // 4) * 4
            if s_pad != s_rows:
                bins = np.concatenate(
                    [bins, np.zeros((s_pad - s_rows, bins.shape[1]),
                                    dtype=bins.dtype)], axis=0)
            f_pad = self.num_features
        else:
            f_pad = self._pad_feature_count(self.num_features)
        self.f_pad = f_pad
        num_bin_pf = train_set.num_bin_array()
        is_cat = train_set.feature_is_categorical()
        if f_pad != self.num_features:
            extra = f_pad - self.num_features
            if resident is None:
                bins = np.concatenate(
                    [bins, np.zeros((extra, bins.shape[1]), dtype=bins.dtype)],
                    axis=0)
            num_bin_pf = np.concatenate([num_bin_pf, np.ones(extra, np.int32)])
            is_cat = np.concatenate([is_cat, np.zeros(extra, bool)])
        self._bins = (self._place_bins(bins) if resident is None
                      else self._place_resident_bins(resident, f_pad))
        self._num_bin_pf = self._place_rep(num_bin_pf)
        self._is_cat = self._place_rep(is_cat)
        # host-side lookup tables for vectorized device->Tree conversion:
        # bin -> representative value per feature (Feature::BinToValue) and
        # the per-feature decision type, so _to_host_tree needs no Python
        # loop over splits.
        table = np.zeros((self.num_features, self.max_bin), dtype=np.float64)
        for i, m in enumerate(train_set.bin_mappers):
            vals = (m.bin_upper_bound if m.bin_type != 1
                    else m.bin_2_categorical.astype(np.float64))
            table[i, :len(vals)] = vals
        self._bin_value_table = table
        self._decision_type_host = np.asarray(
            [1 if m.bin_type == 1 else 0 for m in train_set.bin_mappers],
            dtype=np.int8)
        self.params = SplitParams(
            min_data_in_leaf=float(cfg.min_data_in_leaf),
            min_sum_hessian_in_leaf=float(cfg.min_sum_hessian_in_leaf),
            lambda_l1=float(cfg.lambda_l1),
            lambda_l2=float(cfg.lambda_l2),
            min_gain_to_split=float(cfg.min_gain_to_split),
        )
        self._build = self._make_build_fn(cfg, chunk)
        Log.info("Number of data: %d, number of features: %d",
                 self.num_data, self.num_features)

    # which learner classes can run the leaf-contiguous builder
    # (parallel/learners.py sets True on the data-parallel learner)
    partitioned_capable = True

    def _partitioned_enabled(self, cfg):
        """Leaf-contiguous builder (models/partitioned.py): "auto"
        turns it on for TPU backends. Bundled (EFB) datasets run it
        too — the packed words carry the slot matrix and the bundle's
        expand/decode hooks bridge to virtual features. Needs
        uint8-storable bins (<= 256 stored bins per slot, which EFB's
        MAX_SLOT_BINS already guarantees for bundles)."""
        mode = _partitioned_mode(cfg)
        if not self.partitioned_capable:
            if mode == "true":
                Log.warning("partitioned_build=true ignored: the %s "
                            "learner has no leaf-contiguous core",
                            getattr(self, "name", "this"))
            return False
        if mode == "false":
            return False
        eligible = int(self.train_set.max_stored_bin) <= 256
        if mode == "true":
            if not eligible:
                Log.warning("partitioned_build=true ignored: needs "
                            "max_bin <= 256")
            return eligible
        return eligible and jax.default_backend() == "tpu"

    def _compaction_enabled(self, cfg):
        """Gather-compacted smaller-child histograms (ops/histogram.py
        compacted_histograms) on the dense masked builder. "auto" turns
        it on everywhere EXCEPT the TPU masked path, whose pallas
        streaming kernel already reads HBM at full bandwidth and where
        random gathers are latency-bound (BASELINE.md); "true" forces
        it there too. Moot when the leaf-contiguous builder is active —
        that path is already row-proportional."""
        mode = _tristate(getattr(cfg, "hist_compaction", "auto"),
                         "hist_compaction")
        if self._use_partitioned or mode == "false":
            return False
        if mode == "true":
            return True
        # single-chunk datasets gain nothing: the one bucket IS the
        # whole array, so compaction would only add the per-split
        # gather plus HIST_CHUNK row padding the masked path avoids
        return (jax.default_backend() != "tpu"
                and self.num_data > HIST_CHUNK)

    # hooks overridden by the parallel learners (parallel/learners.py) -------
    def _chunk_pad(self, n):
        return chunk_pad(n, self._use_shape_bucketing)

    def _pad_rows(self, n, chunk):
        if (jax.default_backend() == "tpu" or self._use_partitioned
                or self._use_compact):
            # the pallas/segment/compacted histogram paths grid over
            # fixed HIST_CHUNK blocks
            return self._chunk_pad(n)
        return ((n + chunk - 1) // chunk) * chunk if n > chunk else n

    def _effective_chunk(self, chunk):
        if (jax.default_backend() == "tpu" or self._use_partitioned
                or self._use_compact):
            # rows are padded to HIST_CHUNK multiples; the XLA-fallback
            # scan chunk must DIVIDE that
            return pow2_scan_chunk(chunk)
        return min(chunk, self.n_pad)

    def _pad_feature_count(self, f):
        if self._use_partitioned:
            return ((f + 3) // 4) * 4  # packed words hold 4 features
        return f

    def _place_bins(self, bins):
        if self._use_partitioned:
            from ..ops.ordered_hist import pack_feature_words
            return jnp.asarray(pack_feature_words(bins))
        return jnp.asarray(bins)

    def _resident_bins(self, train_set, n_pad):
        """Device bins to train from in place, or None: the host bins
        are placed (the serial learner always)."""
        return None

    def _place_rows(self, arr):
        return arr

    def _place_rep(self, arr):
        return jnp.asarray(arr)

    def score_layout(self):
        """(rows, sharding) of the score a fused block carries, or None
        where this learner cannot train in the fused scan. Serial: the
        (K, N) score as it is, placed by default, (N, None); the
        data-parallel learner carries it padded and row-sharded
        (parallel/learners.py)."""
        return self.num_data, None

    def local_row_leaf(self, out, n_local):
        """This process's rows of the row->leaf partition (trivial in
        single-process; overridden by the meshed learners)."""
        return out["row_leaf"][:n_local]

    def local_leaf_values(self, out):
        """Leaf values as a process-local array (overridden multi-host)."""
        return out["leaf_value"]

    def linear_fit_context(self):
        """(chunks, bin_value_table, fit_chunk) for the linear leaf fit
        (models/linear_leaves.py). The resident path exposes the whole
        dataset as ONE (lo, hi, bins, base) block over the virtual-
        space traversal bins; the fit re-chunks it on the
        device_row_chunk grid the streamed learner's blocks align to,
        which is what keeps the f64 accumulation bit-identical across
        the two paths."""
        tv = self.train_set.traversal_bins()
        chunks = [(0, self.num_data, tv, 0)]
        # the DATASET's representative table, not the learner's split-
        # threshold table: the fit must dot against the same (finite,
        # inf-clamped) values Tree.predict_by_bins will use
        return chunks, self.train_set.bin_value_table(), int(
            self.config.device_row_chunk)

    def _bundle_expand_fn(self):
        """Stored->virtual histogram expansion closure (io/bundling.py
        expansion_maps). Slices the histogram to the REAL slot count
        first: the partitioned layout pads stored rows to the packer's
        alignment, and a pad slot's bin-0 cell holds row totals — the
        gather's zero-pad index must land past the real slots only."""
        src = self._bundle_src
        slot_of = self._bundle_slot_of
        num_slots = int(self._bundle.num_slots)

        def expand(h):
            k = h.shape[-1]
            hs = h[:num_slots]
            flat = jnp.concatenate(
                [hs.reshape(-1, k), jnp.zeros((1, k), h.dtype)], axis=0)
            hv = jnp.take(flat, src, axis=0)                 # (F, B_v, 3)
            slot_tot = jnp.sum(hs, axis=1)                   # (S, 3)
            hv0 = (jnp.take(slot_tot, slot_of, axis=0)
                   - jnp.sum(hv[:, 1:, :], axis=1))
            return hv.at[:, 0, :].set(hv0)

        return expand

    def _bundle_window(self, sc, feat, num_bin_pf):
        """Stored slot column -> virtual feature's bin values: member
        `feat` owns the window (off, off + nb - 1]; anything outside it
        (another member's bins, or slot bin 0) is the member's bin 0.
        THE decode rule — every stored->virtual column path (masked
        split_col, partitioned decode) must share it."""
        off = self._bundle_feat_off[feat]
        nb = num_bin_pf[feat]
        return jnp.where((sc > off) & (sc <= off + nb - 1), sc - off, 0)

    def _bundle_kwargs(self, bins, num_bin_pf):
        """Bundled-dataset hooks for build_tree_device: stored->virtual
        histogram expansion + slot-decoding split columns. Shared with
        the row-sharded parallel learners (parallel/learners.py)."""
        if getattr(self, "_bundle", None) is None:
            return {}
        fslot = self._bundle_feat_slot

        def split_col(feat):
            sc = jnp.take(bins, fslot[feat], axis=0).astype(jnp.int32)
            return self._bundle_window(sc, feat, num_bin_pf)

        return {"expand_fn": self._bundle_expand_fn(),
                "split_col_fn": split_col}

    def _bundle_partitioned_kwargs(self, num_bin_pf):
        """Bundled-dataset hooks for build_tree_partitioned: the same
        histogram expansion, plus a word-slice slot decode for the
        segment partition step (ordered_sparse_bin.hpp:25-133 is the
        reference's leaf-grouped sparse analog)."""
        if getattr(self, "_bundle", None) is None:
            return {}
        from ..ops.ordered_hist import unpack_feature
        fslot = self._bundle_feat_slot

        def decode(w_sl, feat):
            return self._bundle_window(unpack_feature(w_sl, fslot[feat]),
                                       feat, num_bin_pf)

        return {"expand_fn": self._bundle_expand_fn(), "decode_fn": decode}

    def _cache_hists(self, cfg):
        stored = self._bins.shape[0] * (4 if self._use_partitioned else 1)
        return cache_hists_fits(cfg, stored, self.max_bin)

    def _make_build_core(self, cfg, chunk):
        """The un-jitted builder closure — also consumed directly by the
        fused multi-iteration trainer (models/gbdt.py train_many), which
        embeds it inside its own scanned program."""
        cache_hists = self._cache_hists(cfg)
        if self._use_partitioned:
            from .partitioned import build_tree_partitioned
            base_p = functools.partial(
                build_tree_partitioned,
                num_leaves=int(cfg.num_leaves),
                max_bin=self.max_bin,
                params=self.params,
                max_depth=int(cfg.max_depth),
                f_real=self.num_features,
                cache_hists=cache_hists,
            )
            if getattr(self, "_bundle", None) is None:
                return base_p

            def bundled_p(words, grad, hess, inbag, fmask, num_bin_pf,
                          is_cat):
                return base_p(words, grad, hess, inbag, fmask,
                              num_bin_pf, is_cat,
                              **self._bundle_partitioned_kwargs(num_bin_pf))
            return bundled_p
        base = functools.partial(
            build_tree_device,
            num_leaves=int(cfg.num_leaves),
            max_bin=self.max_bin,
            params=self.params,
            max_depth=int(cfg.max_depth),
            row_chunk=chunk,
            cache_hists=cache_hists,
            compact_hist=self._use_compact,
        )
        if getattr(self, "_bundle", None) is None:
            return base

        def bundled(bins, grad, hess, inbag, fmask, num_bin_pf, is_cat):
            return base(bins, grad, hess, inbag, fmask, num_bin_pf, is_cat,
                        **self._bundle_kwargs(bins, num_bin_pf))
        return bundled

    def _make_build_fn(self, cfg, chunk):
        self._build_core = self._make_build_core(cfg, chunk)
        return jax.jit(self._build_core)

    def reset_config(self, config):
        self.config = config
        if self.train_set is not None:
            self.init(self.train_set)

    def _sample_features(self):
        """feature_fraction per tree (serial_tree_learner.cpp:160-165)."""
        cfg = self.config
        if cfg.feature_fraction >= 1.0:
            mask = np.ones(self.num_features, dtype=bool)
        else:
            used_cnt = int(self.num_features * cfg.feature_fraction)
            mask = self.random.sample_mask(self.num_features, max(used_cnt, 1))
        if self.f_pad != self.num_features:
            mask = np.concatenate(
                [mask, np.zeros(self.f_pad - self.num_features, bool)])
        return mask

    def train_device(self, grad, hess, inbag=None):
        """Grow one tree entirely on device; NO host synchronization.

        Returns the raw device output dict of build_tree_device (tree
        arrays + (N_pad,) row->leaf partition). The caller decides when
        (and whether) to pull anything to host — see models/gbdt.py
        LazyTree.
        """
        n, n_pad = self.num_data, self.n_pad
        grad = jnp.asarray(grad, dtype=jnp.float32)
        hess = jnp.asarray(hess, dtype=jnp.float32)
        if inbag is None:
            inbag = jnp.ones(n, dtype=jnp.float32)
        else:
            inbag = jnp.asarray(inbag, dtype=jnp.float32)
        if n_pad != n:
            grad = jnp.pad(grad, (0, n_pad - n))
            hess = jnp.pad(hess, (0, n_pad - n))
            inbag = jnp.pad(inbag, (0, n_pad - n))
        grad = self._place_rows(grad)
        hess = self._place_rows(hess)
        inbag = self._place_rows(inbag)
        fmask = self._place_rep(self._sample_features())
        return self._build(self._bins, grad, hess, inbag, fmask,
                           self._num_bin_pf, self._is_cat)

    def train(self, grad, hess, inbag=None):
        """Grow one tree. grad/hess: (N,) device or host float32.

        Returns (Tree, row_leaf device array of shape (N,), leaf_values).
        """
        out = self.train_device(grad, hess, inbag)
        tree = self._to_host_tree(out)
        return tree, out["row_leaf"][:self.num_data], out["leaf_value"]

    def _to_host_tree(self, out, shrink=1.0) -> Tree:
        """ONE batched device->host transfer, then vectorized conversion.

        With jax's async dispatch this fetch is the FIRST blocking sync
        after the (guarded) builder launch — for the meshed learners a
        dead peer wedges the process right here, so the watchdog must
        bracket it (graftlint unguarded-collective; the guard is
        zero-overhead unarmed and feeds sync_wait_s when a timing sink
        is bound)."""
        from ..parallel.heartbeat import collective_guard
        with collective_guard("tree_host_fetch"):
            host = jax.device_get(
                {k: v for k, v in out.items() if k != "row_leaf"})
        return self.host_out_to_tree(host, shrink)

    def host_out_to_tree(self, host, shrink=1.0) -> Tree:
        """Convert one tree's host arrays (already fetched) into a Tree.
        Also used by the fused multi-iteration path on per-iteration
        slices of the scan-stacked outputs."""
        n_splits = int(host["n_splits"])
        num_leaves = n_splits + 1
        t = Tree(num_leaves)
        if n_splits == 0:
            return t
        ds = self.train_set
        sf = np.asarray(host["split_feature"])[:n_splits]
        tb = np.asarray(host["split_threshold_bin"])[:n_splits]
        t.split_feature = sf.astype(np.int32)
        t.split_feature_real = ds.real_feature_idx[sf].astype(np.int32)
        t.threshold_in_bin = tb.astype(np.int32)
        t.threshold = self._bin_value_table[sf, tb]
        t.decision_type = self._decision_type_host[sf]
        t.split_gain = np.asarray(host["split_gain"])[:n_splits].astype(np.float64)
        t.left_child = np.asarray(host["left_child"])[:n_splits]
        t.right_child = np.asarray(host["right_child"])[:n_splits]
        t.leaf_parent = np.asarray(host["leaf_parent"])[:num_leaves]
        t.leaf_value = (np.asarray(host["leaf_value"])[:num_leaves]
                        .astype(np.float64) * shrink)
        t.leaf_count = np.asarray(host["leaf_count"])[:num_leaves]
        t.internal_value = np.asarray(host["internal_value"])[:n_splits].astype(np.float64)
        t.internal_count = np.asarray(host["internal_count"])[:n_splits]
        return t


def create_tree_learner(learner_type, config):
    """Factory (src/treelearner/tree_learner.cpp:8-19). out_of_core=true
    swaps the serial learner for the block-store streaming learner
    (lightgbm_tpu/data/ooc_learner.py, docs/Out-of-Core.md); with
    tree_learner=data and num_machines>1 it becomes the gang learner
    over one shared store (lightgbm_tpu/data/ooc_parallel.py)."""
    if getattr(config, "out_of_core", False):
        if learner_type == "data" and int(getattr(config, "num_machines",
                                                  1)) > 1:
            from ..data.ooc_parallel import OutOfCoreGangLearner
            return OutOfCoreGangLearner(config)
        if learner_type != "serial":
            Log.fatal("out_of_core=true supports tree_learner=serial or "
                      "tree_learner=data with num_machines>1 (got %s); "
                      "feature/voting-parallel need per-shard feature "
                      "stores", learner_type)
        from ..data.ooc_learner import OutOfCoreTreeLearner
        return OutOfCoreTreeLearner(config)
    if learner_type == "serial":
        return SerialTreeLearner(config)
    try:
        from ..parallel.learners import (
            DataParallelTreeLearner, FeatureParallelTreeLearner,
            VotingParallelTreeLearner)
    except ImportError as e:
        Log.fatal("Parallel tree learner %s is unavailable: %s", learner_type, e)
    if learner_type == "data":
        return DataParallelTreeLearner(config)
    if learner_type == "feature":
        return FeatureParallelTreeLearner(config)
    if learner_type == "voting":
        return VotingParallelTreeLearner(config)
    Log.fatal("Unknown tree learner type %s", learner_type)
