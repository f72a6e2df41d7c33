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

Chunk-kernel selection (`hist_mode`, config knob + LIGHTGBM_TPU_HIST_MODE
env, resolved by `chunk_mode()` / `use_pallas()`):

- "pallas"  — the Pallas TPU streaming kernels (ops/pallas_hist.py /
  ops/ordered_hist.py). The auto default on TPU.
- "bincount" — per-chunk f64 `np.bincount` on host via
  `jax.pure_callback`. XLA's CPU scatter lowering costs ~60 ns per
  row-feature regardless of formulation (measured on this image);
  numpy's C bincount loop runs the same scatter at ~13 ns AND
  accumulates in f64 (better than the f32 in-chunk order the XLA
  segment path gives). The auto default on CPU. The callback keeps the
  CHUNK-ALIGNED Kahan pair structure (see build_histograms_pair), so
  the serial == data-parallel agreement guarantee is unchanged: a
  chunk's f32 partial depends only on the chunk's rows, and the pair
  combination order is identical on every shard.
- "segment" — jax.ops.segment_sum scatter-add: the XLA-native CPU
  formulation (the reference's own per-row accumulation loop,
  dense_bin.hpp:16-195). Fallback when callbacks are unwanted
  (e.g. profiling pure-XLA programs).
- "einsum" — the one-hot MXU contraction: right where compares are
  cheaper than scatters (non-TPU accelerators, TPU XLA fallback).

A non-auto mode forces that formulation everywhere it can run
(einsum/segment/bincount on TPU take the XLA path instead of the Pallas
kernels). hist_mode=pallas without a TPU backend is a fatal error: the
kernels cannot run there, and substituting another formulation would
report a path that did not run.

Smaller-child compaction (compacted_histograms): the default dense
training path (models/tree_learner.py) gathers the active leaf's rows
into a contiguous bucket-padded buffer first — per-split cost
O(rows-in-child), not O(N) — reusing the geometric bucket machinery of
ops/ordered_hist.py for static shapes under jit. This is the gather
analog of XGBoost-GPU/ThunderGBM's row compaction before the histogram
scatter (arXiv:1806.11248 §4.2, arXiv:1706.08359 §5).

Frontier batching (frontier_histograms): one data pass builds the
histograms of a STATIC VECTOR of leaves at once — a combined
leaf x feature x bin key on the bincount/segment paths, a leaf-indexed
accumulator in the Pallas kernel (ops/pallas_hist.py). Used for the
root/bagging re-init pass of every tree and for both children of a
split in the cache-less (memory-bounded) builder, which halves its
full-matrix streams (docs/Histogram-Engine.md).
"""

import contextlib
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np

from .ordered_hist import bucket_sizes, cover_index
from .pallas_hist import HIST_CHUNK

DEFAULT_ROW_CHUNK = 8192


_HIST_MODES = ("auto", "pallas", "einsum", "segment", "bincount")


def _parse_hist_mode():
    raw = os.environ.get("LIGHTGBM_TPU_HIST_MODE", "auto").lower()
    if raw not in _HIST_MODES:
        # import-time knob: warn and fall back rather than taking down
        # an embedder that only wanted prediction
        from ..utils.log import Log
        Log.warning("LIGHTGBM_TPU_HIST_MODE must be one of %s, got [%s]; "
                    "using auto", "/".join(_HIST_MODES), raw)
        return "auto"
    return raw


# Chunk-kernel formulation. Initialized from the env once at import;
# config-level `hist_mode` overrides it at learner init (set_hist_mode).
# Jitted programs bake the resolved mode in: changing it invalidates
# builders compiled earlier in the process (same contract the env knob
# always had).
_DEFAULT_HIST_MODE = _parse_hist_mode()
HIST_MODE = _DEFAULT_HIST_MODE


def set_hist_mode(mode):
    """Set the process-wide histogram formulation from config
    (models/tree_learner.py init). "auto" RESTORES the env-derived
    process default (LIGHTGBM_TPU_HIST_MODE or auto), so one Booster's
    forced mode never leaks into the next Booster's."""
    global HIST_MODE
    from ..utils.log import Log
    mode = str(mode).lower()
    if mode not in _HIST_MODES:
        Log.fatal("hist_mode must be one of %s, got [%s]",
                  "/".join(_HIST_MODES), mode)
    resolved = _DEFAULT_HIST_MODE if mode == "auto" else mode
    if resolved == "pallas" and jax.default_backend() != "tpu":
        Log.fatal("hist_mode=pallas needs a TPU backend, got [%s]",
                  jax.default_backend())
    HIST_MODE = resolved


def use_pallas():
    """Whether the Pallas TPU kernels are the active histogram engine
    (resolved at trace time): a TPU backend with hist_mode
    auto/pallas."""
    return (jax.default_backend() == "tpu"
            and HIST_MODE in ("auto", "pallas"))


_NO_CALLBACKS = threading.local()


@contextlib.contextmanager
def callbacks_disabled():
    """Trace-time guard: inside this context, "bincount" resolves to
    the XLA segment kernel. Host callbacks embedded in MULTI-DEVICE
    shard_map programs can deadlock this image's XLA CPU runtime (the
    dispatching thread blocks in a sharded execute while the callback
    worker threads park on the GIL it holds — observed as a hang in
    the data-parallel compacted build, single-device programs are
    unaffected), so the meshed learners trace their builders under
    this guard (parallel/mesh.py meshed_trace_guard)."""
    depth = getattr(_NO_CALLBACKS, "depth", 0)
    _NO_CALLBACKS.depth = depth + 1
    try:
        yield
    finally:
        _NO_CALLBACKS.depth = depth


def single_worker_host():
    """True when this process is pinned to a single CPU (checked per
    call so tests can flip it with sched_setaffinity)."""
    try:
        n = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):  # non-Linux fallback
        n = os.cpu_count() or 1
    return n <= 1


def host_callbacks_hazardous():
    """Whether an async-dispatched jit program embedding pure_callback
    can deadlock this process's XLA CPU client. Observed on 1-core
    runners with a single (non-virtualized) CPU device: the client's
    lone worker executes the builder program while the callback's
    operand delivery waits for that same thread — the compacted
    learner's per-iteration path wedges at n > HIST_CHUNK (where
    hist_compaction auto-enables the frontier/compacted callbacks; the
    PR 14 cliff). Forcing >= 2 virtual CPU devices
    (--xla_force_host_platform_device_count, what the test harness and
    bench children do) gives the callback a worker and clears it, as
    does the AOT-compiled fused block (models/gbdt.py _get_fused_fn),
    so the hazard is exactly {1 CPU} x {1 local device} x traced-jit
    dispatch. The serial learner's train_device consults this and
    traces its builder under callbacks_disabled (segment kernel:
    bit-identical per the pinned segment==bincount parity, slower, but
    today that configuration hangs forever)."""
    return single_worker_host() and jax.local_device_count() == 1


def chunk_mode():
    """Resolve the XLA/host chunk-kernel formulation:
    "bincount" | "segment" | "einsum"."""
    mode = HIST_MODE
    if mode in ("auto", "pallas"):
        # on TPU this is only reached by the XLA reference paths
        # (masked_histograms_xla / _seg_hist_xla)
        mode = ("bincount" if jax.default_backend() == "cpu"
                else "einsum")
    if mode == "bincount" and getattr(_NO_CALLBACKS, "depth", 0):
        return "segment"  # see callbacks_disabled
    return mode


def build_histograms(bins, ghc, num_bins_total, row_chunk=DEFAULT_ROW_CHUNK):
    """Compute per-feature histograms of the packed row statistics.

    Args:
      bins: (F, N) integer bin matrix (uint8/int16), N a multiple of
        row_chunk when N > row_chunk (pad rows must carry ghc == 0).
      ghc: (N, K) float32 packed statistics; masked rows are zero.
      num_bins_total: static int B — histogram width (max bins over features).
      row_chunk: static chunk size for the scan.

    Returns:
      (F, B, K) float32 histogram.
    """
    hi, lo = build_histograms_pair(bins, ghc, num_bins_total, row_chunk)
    return hi + lo


def build_histograms_pair(bins, ghc, num_bins_total, row_chunk=DEFAULT_ROW_CHUNK):
    """Compensated (Kahan) accumulation across row chunks: returns the
    (value, compensation) float32 pair, summing per-chunk f32 partials
    with ~f64-equivalent accuracy. The pair representation lets the
    data-parallel learner reduce shard partials in a FIXED order
    (ops-level analog of the reference's f64 accumulators, bin.h:18-26),
    so serial and data-parallel training see histograms that agree to
    ~1e-14 relative instead of f32-reduction-order ulps.

    All chunk modes share this structure: a chunk's f32 partial is a
    pure function of the chunk's rows, and partials combine in chunk
    order — the property the serial == parallel contract rests on. The
    bincount mode runs the whole chunk loop in ONE host callback
    (per-call numpy overhead ~1 us; the Kahan arithmetic is mirrored in
    f32 numpy, bit-identical to the lax.scan version)."""
    if chunk_mode() == "bincount":
        return _hist_pair_bincount(bins, ghc, num_bins_total, row_chunk)
    f, n = bins.shape
    k = ghc.shape[1]
    b = num_bins_total

    if n <= row_chunk:
        h = _hist_chunk(bins, ghc, b)
        return h, jnp.zeros_like(h)
    if n % row_chunk != 0:
        raise ValueError(f"N={n} must be padded to a multiple of {row_chunk}")
    nchunks = n // row_chunk

    bins_c = bins.reshape(f, nchunks, row_chunk).transpose(1, 0, 2)
    ghc_c = ghc.reshape(nchunks, row_chunk, k)

    def step(carry, xs):
        acc, comp = carry
        bc, gc = xs
        h = _hist_chunk(bc, gc, b)
        y = h - comp
        t = acc + y
        comp = (t - acc) - y
        return (t, comp), None

    zero = jnp.zeros((f, b, k), dtype=jnp.float32)
    (acc, comp), _ = jax.lax.scan(step, (zero, zero), (bins_c, ghc_c))
    return acc, -comp  # Kahan comp holds the NEGATIVE residual


def hist_pair_fold_block(acc, comp, bins_blk, ghc_blk, num_bins_total,
                         row_chunk=DEFAULT_ROW_CHUNK):
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
        h = _hist_chunk(bc, gc, num_bins_total)
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


def _chunk_bounds(n, row_chunk):
    """Chunk decomposition shared by the XLA scan and the bincount
    callback: one chunk when n <= row_chunk, else n/row_chunk chunks."""
    if n <= row_chunk:
        return 1, n
    if n % row_chunk != 0:
        raise ValueError(f"N={n} must be padded to a multiple of {row_chunk}")
    return n // row_chunk, row_chunk


def _bincount_chunk_loop(nchunks, shape, chunk_fn):
    """Numpy mirror of build_histograms_pair's Kahan chunk scan.
    `chunk_fn(ci)` -> the chunk's f32 partial of `shape`. Returns the
    stacked (2, *shape) [value, residual] f32 pair."""
    acc = np.zeros(shape, np.float32)
    comp = np.zeros(shape, np.float32)
    for ci in range(nchunks):
        h = chunk_fn(ci)
        y = h - comp
        t = acc + y
        comp = (t - acc) - y
        acc = t
    # (-comp) + 0.0 canonicalizes -0.0 residuals to +0.0, matching the
    # single-chunk XLA path's jnp.zeros_like
    return np.stack([acc, (-comp) + 0.0])


def _hist_pair_bincount(bins, ghc, b, row_chunk):
    """f64 np.bincount chunk kernel via pure_callback (see module
    docstring). The combined feature x bin key turns the whole chunk
    into K weighted bincounts; each chunk's f64 total rounds to the f32
    partial that feeds the Kahan pair, so the pair CONTRACT (chunk-
    aligned partials, fixed combine order) is preserved exactly."""
    f, n = bins.shape
    k = ghc.shape[1]
    nchunks, c = _chunk_bounds(n, row_chunk)

    def cb(bins_h, ghc_h):
        bins_h = np.asarray(bins_h)
        ghc_h = np.asarray(ghc_h, dtype=np.float64)
        base = (np.arange(f, dtype=np.int64) * b)[:, None]
        fb = f * b

        def one_chunk(ci):
            sl = slice(ci * c, (ci + 1) * c)
            key = (base + bins_h[:, sl]).ravel()
            out = np.empty((fb, k), np.float64)
            for j in range(k):
                out[:, j] = np.bincount(key,
                                        weights=np.tile(ghc_h[sl, j], f),
                                        minlength=fb)
            return out.astype(np.float32).reshape(f, b, k)

        return _bincount_chunk_loop(nchunks, (f, b, k), one_chunk)

    out = jax.pure_callback(
        cb, jax.ShapeDtypeStruct((2, f, b, k), jnp.float32), bins, ghc,
        vmap_method="sequential")
    return out[0], out[1]


def _hist_chunk(bins_chunk, ghc_chunk, b):
    """One row chunk -> (F, B, K) partial histogram; XLA formulation by
    backend (chunk_mode; the bincount mode is handled a level up so the
    whole chunk loop rides one callback)."""
    if chunk_mode() == "segment":
        return _hist_chunk_segment(bins_chunk, ghc_chunk, b)
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
                        row_chunk=HIST_CHUNK):
    """Multi-leaf histograms: ONE pass over the bin matrix builds the
    histograms of every leaf in `leaf_ids` (static length L, distinct
    ids; rows outside the frontier contribute nowhere).

    The frontier-batching primitive of docs/Histogram-Engine.md:
    - bincount mode: a combined (leaf, feature, bin) key — the leaf
      position costs one binary search per row, then the pass is the
      same K weighted bincounts as the single-leaf kernel.
    - Pallas (TPU): a leaf-indexed accumulator kernel streams the bin
      matrix once into an (L, F, B, 3) VMEM output
      (ops/pallas_hist.py frontier_histograms_tpu).
    - einsum/segment fallback: one masked pass per leaf (reads bins L
      times — these modes are non-default everywhere this primitive is
      hot).

    Per-leaf values are BITWISE what the single-leaf masked kernel
    produces for the same rows (same chunk decomposition, same
    accumulation order; zero-weight rows cannot perturb an f64 or f32
    sum), so callers may mix the two freely.

    Args:
      bins: (F, N) integer bin matrix (uint8/int16/int32).
      ghc_t: (3, N) float32 stats (grad*inbag, hess*inbag, inbag).
      row_leaf: (N,) int32 row->leaf map.
      leaf_ids: (L,) int32 DISTINCT leaf ids; L static.
      num_bins_total: static histogram width B.
      row_chunk: static chunk size of the pair scan.

    Returns the compensated ((L, F, B, 3) value, residual) pair —
    same contract as build_histograms_pair / masked_histograms.
    """
    b = num_bins_total
    if use_pallas():
        from .pallas_hist import frontier_histograms_tpu
        return frontier_histograms_tpu(bins, ghc_t, row_leaf, leaf_ids, b)
    if chunk_mode() == "bincount":
        return _frontier_pair_bincount(bins, ghc_t, row_leaf, leaf_ids, b,
                                       row_chunk)

    # einsum/segment fallback: the masked single-leaf pass per leaf
    def one(lid):
        mask = (row_leaf == lid).astype(jnp.float32)
        return build_histograms_pair(bins, (ghc_t * mask[None, :]).T, b,
                                     row_chunk)

    his, los = jax.vmap(one)(leaf_ids.astype(jnp.int32))
    return his, los


def _frontier_pair_bincount(bins, ghc_t, row_leaf, leaf_ids, b, row_chunk):
    """Combined-key bincount frontier pass. Key layout:
    pos(row) * F * B + f * B + bin, with pos(row) == L for rows outside
    the frontier (their segment is sliced off)."""
    l = leaf_ids.shape[0]
    f, n = bins.shape
    k = ghc_t.shape[0]
    nchunks, c = _chunk_bounds(n, row_chunk)

    def cb(bins_h, ghc_h, rl_h, lids_h):
        bins_h = np.asarray(bins_h)
        ghc_h = np.asarray(ghc_h, dtype=np.float64)
        rl_h = np.asarray(rl_h)
        lids_h = np.asarray(lids_h, dtype=np.int64)
        # leaf id -> position in leaf_ids (L = not in frontier)
        order = np.argsort(lids_h, kind="stable")
        sorted_ids = lids_h[order]
        idx = np.searchsorted(sorted_ids, rl_h)
        idxc = np.minimum(idx, l - 1)
        pos = np.where(sorted_ids[idxc] == rl_h, order[idxc],
                       np.int64(l))
        fb = f * b
        row_off = pos * fb                                    # (N,)
        base = (np.arange(f, dtype=np.int64) * b)[:, None]

        def one_chunk(ci):
            sl = slice(ci * c, (ci + 1) * c)
            key = (row_off[sl][None, :] + base + bins_h[:, sl]).ravel()
            out = np.empty(((l + 1) * fb, k), np.float64)
            for j in range(k):
                out[:, j] = np.bincount(key,
                                        weights=np.tile(ghc_h[j, sl], f),
                                        minlength=(l + 1) * fb)
            return out[:l * fb].astype(np.float32).reshape(l, f, b, k)

        return _bincount_chunk_loop(nchunks, (l, f, b, k), one_chunk)

    out = jax.pure_callback(
        cb, jax.ShapeDtypeStruct((2, l, f, b, k), jnp.float32),
        bins, ghc_t, row_leaf, leaf_ids, vmap_method="sequential")
    return out[0], out[1]


def _compacted_bincount(bins, ghc_t, row_leaf, leaf_id, b, chunk):
    """Host-side gather-compacted bincount: the leaf's rows are
    selected (original order, matching compact_gather_indices), sliced
    into `chunk`-row pieces (the last one ragged — no bucket padding),
    and each piece's f64 bincount feeds the f32 Kahan pair. Cost is
    O(rows-in-leaf) with no O(N) device-side compaction machinery."""
    f, n = bins.shape
    k = ghc_t.shape[0]

    def cb(bins_h, ghc_h, rl_h, lid_h):
        bins_h = np.asarray(bins_h)
        ghc_h = np.asarray(ghc_h, dtype=np.float64)
        rl_h = np.asarray(rl_h)
        src = np.flatnonzero(rl_h == lid_h)
        base = (np.arange(f, dtype=np.int64) * b)[:, None]
        fb = f * b
        nchunks = max(-(-len(src) // chunk), 1)

        def one_chunk(ci):
            sl = src[ci * chunk:(ci + 1) * chunk]
            key = (base + bins_h[:, sl]).ravel()
            g_sl = ghc_h[:, sl]
            out = np.empty((fb, k), np.float64)
            for j in range(k):
                out[:, j] = np.bincount(key,
                                        weights=np.tile(g_sl[j], f),
                                        minlength=fb)
            return out.astype(np.float32).reshape(f, b, k)

        return _bincount_chunk_loop(nchunks, (f, b, k), one_chunk)

    out = jax.pure_callback(
        cb, jax.ShapeDtypeStruct((2, f, b, k), jnp.float32),
        bins, ghc_t, row_leaf, leaf_id, vmap_method="sequential")
    return out[0], out[1]


def compacted_histograms(bins, ghc_t, row_leaf, leaf_id, num_bins_total,
                         row_chunk=HIST_CHUNK):
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

    if chunk_mode() == "bincount":
        # single-callback fast path: dynamic shapes are free on the
        # host, so the compaction (flatnonzero), the gather and the
        # chunked Kahan accumulation all happen inside ONE callback —
        # no bucketed lax.switch, no O(N) XLA cumsum/scatter/gather
        # per split. Still a pure per-shard function of (rows, stats),
        # so every collective hook contract holds unchanged.
        return _compacted_bincount(bins, ghc_t, row_leaf, leaf_id,
                                   num_bins_total, chunk)

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
                                         row_chunk=min(size, chunk))

        return branch

    return jax.lax.switch(idx, [make_branch(b) for b in buckets], mask)
