"""Histogram construction: the hot op of GBDT training.

Reference: the per-feature scalar accumulation loops in
src/io/dense_bin.hpp:16-195 (4-way unrolled CPU scatter-add) and
src/treelearner/feature_histogram.hpp:54-79.

TPU-first design: scatter-add does not vectorize on TPU; instead the
histogram is ONE batched one-hot contraction on the MXU:

    hist[f, b, k] = sum_n [bins[f, n] == b] * ghc[n, k]

where ghc packs the per-row statistics columns (gradient, hessian,
in-leaf count mask — and both children at once: the reference's
"histogram subtraction trick" (serial_tree_learner.cpp:376-379) halves
CPU work; on the MXU both children ride in the same matmul for free
because the stat-column dimension sits far below the 128-lane tile, so
left and right child histograms come out of one pass).

Rows are processed in chunks via `lax.scan` so the one-hot operand
stays small; XLA fuses the compare into the dot operand tiles.

Formulation by platform (`use_pallas()` / `chunk_mode()`, pure functions
of `jax.default_backend()`; no option, no environment variable):

- TPU: the Pallas streaming kernels (ops/pallas_hist.py,
  ops/ordered_hist.py). The XLA chunk functions below are their
  reference there, in the "einsum" formulation.
- CPU: "segment" — jax.ops.segment_sum scatter-add, which XLA's CPU
  backend lowers to the reference's own per-row accumulation loop
  (dense_bin.hpp:16-195): O(C * K) a feature where the one-hot costs
  O(C * B).
- any other backend: "einsum" — the one-hot MXU contraction, right
  where compares are cheaper than scatters.

The chunk functions take the formulation as a static `mode` argument
whose default is the platform's, so a test can name "einsum" or
"segment"; no learner passes it. Whichever runs, a chunk's f32 partial
is a pure function of the chunk's rows and partials combine in chunk
order (build_histograms_pair): the serial == data-parallel ==
out-of-core agreement rests on that.

Smaller-child compaction (compacted_histograms): the default dense
training path (models/tree_learner.py) gathers the active leaf's rows
into a contiguous bucket-padded buffer first — per-split cost
O(rows-in-child), not O(N) — reusing the geometric bucket machinery of
ops/ordered_hist.py for static shapes under jit. This is the gather
analog of XGBoost-GPU/ThunderGBM's row compaction before the histogram
scatter (arXiv:1806.11248 §4.2, arXiv:1706.08359 §5).

Frontier batching (frontier_histograms): one data pass builds the
histograms of a STATIC VECTOR of leaves at once — a
leaf-indexed accumulator in the Pallas kernel (ops/pallas_hist.py),
one masked pass a leaf in the XLA formulations. Used for the
root/bagging re-init pass of every tree and for both children of a
split in the cache-less (memory-bounded) builder, which halves its
full-matrix streams (docs/Histogram-Engine.md).
"""

import jax
import jax.numpy as jnp

from .ordered_hist import bucket_sizes, cover_index
from .pallas_hist import HIST_CHUNK

DEFAULT_ROW_CHUNK = 8192


def use_pallas():
    """Whether the Pallas TPU kernels are the histogram and partition
    engine (resolved at trace time): a TPU backend."""
    return jax.default_backend() == "tpu"


def chunk_mode():
    """The platform's XLA chunk formulation: "segment" on the CPU
    backend, "einsum" on every other (on a TPU only the XLA reference
    paths and the out-of-core block fold reach it)."""
    return "segment" if jax.default_backend() == "cpu" else "einsum"


def build_histograms(bins, ghc, num_bins_total, row_chunk=DEFAULT_ROW_CHUNK,
                     mode=None):
    """Compute per-feature histograms of the packed row statistics.

    Args:
      bins: (F, N) integer bin matrix (uint8/int16), N a multiple of
        row_chunk when N > row_chunk (pad rows must carry ghc == 0).
      ghc: (N, K) float32 packed statistics; masked rows are zero.
      num_bins_total: static int B — histogram width (max bins over features).
      row_chunk: static chunk size for the scan.
      mode: static chunk formulation, "segment" | "einsum"; None = the
        platform's (chunk_mode).

    Returns:
      (F, B, K) float32 histogram.
    """
    hi, lo = build_histograms_pair(bins, ghc, num_bins_total, row_chunk,
                                   mode)
    return hi + lo


def build_histograms_pair(bins, ghc, num_bins_total,
                          row_chunk=DEFAULT_ROW_CHUNK, mode=None):
    """Compensated (Kahan) accumulation across row chunks: returns the
    (value, compensation) float32 pair, summing per-chunk f32 partials
    with ~f64-equivalent accuracy. The pair representation lets the
    data-parallel learner reduce shard partials in a FIXED order
    (ops-level analog of the reference's f64 accumulators, bin.h:18-26),
    so serial and data-parallel training see histograms that agree to
    ~1e-14 relative instead of f32-reduction-order ulps.

    Both formulations share this structure: a chunk's f32 partial is a
    pure function of the chunk's rows, and partials combine in chunk
    order — the property the serial == parallel contract rests on."""
    mode = mode or chunk_mode()
    f, n = bins.shape
    k = ghc.shape[1]
    b = num_bins_total

    if n <= row_chunk:
        h = _hist_chunk(bins, ghc, b, mode)
        return h, jnp.zeros_like(h)
    if n % row_chunk != 0:
        raise ValueError(f"N={n} must be padded to a multiple of {row_chunk}")
    nchunks = n // row_chunk

    bins_c = bins.reshape(f, nchunks, row_chunk).transpose(1, 0, 2)
    ghc_c = ghc.reshape(nchunks, row_chunk, k)

    def step(carry, xs):
        acc, comp = carry
        bc, gc = xs
        h = _hist_chunk(bc, gc, b, mode)
        y = h - comp
        t = acc + y
        comp = (t - acc) - y
        return (t, comp), None

    zero = jnp.zeros((f, b, k), dtype=jnp.float32)
    (acc, comp), _ = jax.lax.scan(step, (zero, zero), (bins_c, ghc_c))
    return acc, -comp  # Kahan comp holds the NEGATIVE residual


def hist_pair_fold_block(acc, comp, bins_blk, ghc_blk, num_bins_total,
                         row_chunk=DEFAULT_ROW_CHUNK, mode=None):
    """Continue build_histograms_pair's Kahan chunk scan across a block
    boundary: fold `bins_blk`'s chunks into the running (acc, comp)
    carry and return the new carry. Because a chunk's f32 partial
    depends only on the chunk's rows and the carry chain is strictly
    sequential, folding row-ordered blocks whose boundaries land on the
    chunk grid reproduces the single-pass scan BIT-FOR-BIT — the
    out-of-core streaming engine's parity contract
    (lightgbm_tpu/data/ooc_learner.py; collapse the final carry with
    hist_pair_fold_collapse).

    Args:
      acc, comp: (F, B, K) float32 running Kahan value/compensation
        (start both at zeros; `comp` is the NEGATIVE residual, Kahan's
        internal convention — build_histograms_pair returns -comp).
      bins_blk: (F, R) integer bins, R a multiple of row_chunk (or a
        single chunk when R <= row_chunk).
      ghc_blk: (R, K) float32 packed statistics.
    """
    mode = mode or chunk_mode()
    f, n = bins_blk.shape
    k = ghc_blk.shape[1]
    if n <= row_chunk:
        chunks = (bins_blk[None], ghc_blk[None])
    else:
        if n % row_chunk != 0:
            raise ValueError(
                f"block of {n} rows must be a multiple of the scan "
                f"chunk {row_chunk}")
        nchunks = n // row_chunk
        chunks = (bins_blk.reshape(f, nchunks, row_chunk)
                  .transpose(1, 0, 2),
                  ghc_blk.reshape(nchunks, row_chunk, k))

    def step(carry, xs):
        acc, comp = carry
        bc, gc = xs
        h = _hist_chunk(bc, gc, num_bins_total, mode)
        y = h - comp
        t = acc + y
        comp = (t - acc) - y
        return (t, comp), None

    (acc, comp), _ = jax.lax.scan(step, (acc, comp), chunks)
    return acc, comp


def hist_pair_fold_collapse(acc, comp):
    """Collapse a hist_pair_fold_block carry into the final histogram —
    the same `value + (-residual)` f32 add as _collapse_pair applied to
    build_histograms_pair's (acc, -comp) output."""
    return acc + (-comp)


def _hist_chunk(bins_chunk, ghc_chunk, b, mode):
    """One row chunk -> (F, B, K) partial histogram in the named
    formulation."""
    if mode == "segment":
        return _hist_chunk_segment(bins_chunk, ghc_chunk, b)
    if mode != "einsum":
        raise ValueError(f"unknown chunk formulation {mode!r}")
    return _hist_chunk_einsum(bins_chunk, ghc_chunk, b)


def _hist_chunk_einsum(bins_chunk, ghc_chunk, b):
    """One-hot contraction over a row chunk: (F, C), (C, K) -> (F, B, K)."""
    onehot = (bins_chunk.astype(jnp.int32)[:, :, None]
              == jnp.arange(b, dtype=jnp.int32)[None, None, :])
    return jnp.einsum("fcb,ck->fbk", onehot.astype(jnp.float32),
                      ghc_chunk.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


def _hist_chunk_segment(bins_chunk, ghc_chunk, b):
    """Scatter-add formulation: XLA CPU lowers segment_sum to the
    reference's own per-row accumulation loop (dense_bin.hpp:16-195),
    O(C * K) per feature instead of the one-hot's O(C * B)."""
    ghc_f32 = ghc_chunk.astype(jnp.float32)

    def one(bf):
        return jax.ops.segment_sum(ghc_f32, bf.astype(jnp.int32),
                                   num_segments=b)

    return jax.vmap(one)(bins_chunk)


def frontier_histograms(bins, ghc_t, row_leaf, leaf_ids, num_bins_total,
                        row_chunk=HIST_CHUNK, mode=None):
    """Multi-leaf histograms: ONE pass over the bin matrix builds the
    histograms of every leaf in `leaf_ids` (static length L, distinct
    ids; rows outside the frontier contribute nowhere).

    The frontier-batching primitive of docs/Histogram-Engine.md:
    - Pallas (TPU): a leaf-indexed accumulator kernel streams the bin
      matrix once into an (L, F, B, 3) VMEM output
      (ops/pallas_hist.py frontier_histograms_tpu).
    - XLA (segment / einsum): one masked pass per leaf, vmapped over
      the leaf axis (reads bins L times).

    Per-leaf values are what the single-leaf masked kernel produces for
    the same rows (same chunk decomposition, same accumulation order:
    bitwise in the scatter formulation), so callers may mix the two
    freely.

    Args:
      bins: (F, N) integer bin matrix (uint8/int16/int32).
      ghc_t: (3, N) float32 stats (grad*inbag, hess*inbag, inbag).
      row_leaf: (N,) int32 row->leaf map.
      leaf_ids: (L,) int32 DISTINCT leaf ids; L static.
      num_bins_total: static histogram width B.
      row_chunk: static chunk size of the pair scan.
      mode: static XLA chunk formulation (build_histograms); a test's
        argument, ignored by the Pallas path.

    Returns the compensated ((L, F, B, 3) value, residual) pair —
    same contract as build_histograms_pair / masked_histograms.
    """
    b = num_bins_total
    if use_pallas():
        from .pallas_hist import frontier_histograms_tpu
        return frontier_histograms_tpu(bins, ghc_t, row_leaf, leaf_ids, b)

    # the masked single-leaf pass per leaf
    def one(lid):
        mask = (row_leaf == lid).astype(jnp.float32)
        return build_histograms_pair(bins, (ghc_t * mask[None, :]).T, b,
                                     row_chunk, mode)

    his, los = jax.vmap(one)(leaf_ids.astype(jnp.int32))
    return his, los


def compacted_histograms(bins, ghc_t, row_leaf, leaf_id, num_bins_total,
                         row_chunk=HIST_CHUNK, mode=None):
    """Gather-compacted leaf histogram: cost scales with the leaf's row
    count, not the dataset.

    The leaf's rows (selected on the dense row->leaf map, original
    order preserved) are compacted into a contiguous buffer whose
    static length is the geometric chunk bucket covering the leaf's row
    count (ops/ordered_hist.py bucket_sizes / cover_index — the same
    dispatch the leaf-contiguous builder uses for position ranges), and
    only that buffer feeds the chunked Kahan accumulation. Rows past
    the count gather arbitrary bins with ZERO statistics, so padding
    never perturbs the histogram.

    Args:
      bins: (F, N) integer bin matrix, N % HIST_CHUNK == 0.
      ghc_t: (3, N) float32 stats (grad*inbag, hess*inbag, inbag);
        padding rows must be zero.
      row_leaf: (N,) int32 row->leaf map.
      leaf_id: traced int32 scalar.
      num_bins_total: static histogram width B.
      row_chunk: static scan chunk of the compacted buffer.
      mode: static XLA chunk formulation (build_histograms).

    Returns the compensated (value, residual) pair of
    build_histograms_pair — collapse with `hi + lo`, or exchange shard
    pairs in fixed order first (parallel/mesh.py pair_allreduce /
    pair_reduce_scatter; the lax.switch holds no collectives, so shards
    on different buckets still meet the reduction in lockstep).
    """
    from .partition import compact_gather_indices
    f, n = bins.shape
    if n % HIST_CHUNK != 0:
        raise ValueError(f"N={n} must be a multiple of {HIST_CHUNK}")
    n_chunks = n // HIST_CHUNK
    buckets = bucket_sizes(n_chunks)
    chunk = min(int(row_chunk), HIST_CHUNK)

    mask = row_leaf == leaf_id
    cnt = jnp.sum(mask.astype(jnp.int32))
    idx, _ = cover_index(jnp.int32(0), cnt, n_chunks)

    def make_branch(bk):
        size = bk * HIST_CHUNK

        def branch(mask):
            src = compact_gather_indices(mask, size)
            valid = (src < n).astype(jnp.float32)
            src_c = jnp.minimum(src, n - 1)
            bins_sl = jnp.take(bins, src_c, axis=1)
            ghc_sl = jnp.take(ghc_t, src_c, axis=1) * valid[None, :]
            return build_histograms_pair(bins_sl, ghc_sl.T, num_bins_total,
                                         row_chunk=min(size, chunk),
                                         mode=mode)

        return branch

    return jax.lax.switch(idx, [make_branch(b) for b in buckets], mask)
