"""Segment histograms over a leaf-contiguous row layout — the hot op of
the partitioned tree builder (models/partitioned.py).

Reference semantics: ordered_sparse_bin.hpp:25-133 / data_partition.hpp
keep per-leaf row indices contiguous so per-leaf histogram cost is
proportional to leaf size. The TPU translation: rows are kept
PHYSICALLY sorted by leaf (ops/partition.py), a leaf is a position
range [begin, begin+cnt), and its histogram streams only the chunks
covering that range — sequential HBM reads, no gathers, cost
O(leaf_rows) instead of the masked builder's O(N) per split
(ops/pallas_hist.py BASELINE.md bound).

Static shapes under jit come from BUCKETING: segment lengths are
rounded up to a geometric-bucket number of HIST_CHUNK-row chunks
(power-of-two by default, see BUCKET_GROWTH) and
`lax.switch` dispatches to the matching pre-compiled variant; boundary
chunks mask rows outside the range by position (two iota compares —
there is no row_leaf array at all on this path).

The histogram's window follows a LADDER of its own (`hist_rungs`):
`r x {1, 2, 4, ...}` rows up to one chunk, then the chunk buckets, each
window starting on a multiple of `r` and the kernel's row block being
min(rung, HIST_CHUNK). `r` (`min_rows`) follows from the one-hot
elements a data row costs, `f x b_pad`: where a whole chunk costs no
more than what stands round a call (28 or 136 columns: 10-52 us) `r` is
HIST_CHUNK and the ladder is `bucket_sizes`; at 2,000 columns and 63
bins a chunk is 780 us, `r` is 512, and a 300-row leaf streams 512
rows. The row padding, the partition step's decision window
(`cover_index` / `window_start`) and `compacted_histograms` keep whole
chunks. In a trace every rung at each call site is its own
`seg_hist.<n>` instruction: calls and ms a call by rung are read from
those (docs/Observability.md).

Bins are packed 4 features per int32 word (W = ceil(F/4), feature f in
byte f%4 of word f//4): one permutation gather moves 4 features at
once, and the kernel unpacks with a shift+mask (2 VPU ops per feature
per chunk, far below the B x C one-hot compares).

The kernel's time is its one-hot elements (rows x features x padded
bins) through the MXU, so the padded bin extent follows the static
`num_bins_total` (`onehot_extent`): 64 rows a feature up to 64 bins,
multiples of 128 above. At 64 rows the four byte lanes of a packed word
row are stacked into one 256-row one-hot and take one contraction, the
operand shape a 255-bin feature has.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry.trace import scope
from .pallas_hist import (HIST_CHUNK, STAT_TERMS, fold_stats, onehot_dot,
                          split_stats)


def pack_feature_words(bins_u8):
    """(F, N) uint8 bins -> (ceil(F/4), N) int32 packed words (host)."""
    f, n = bins_u8.shape
    w = (f + 3) // 4
    padded = np.zeros((w * 4, n), dtype=np.uint8)
    padded[:f] = bins_u8
    p = padded.reshape(w, 4, n).astype(np.uint32)
    words = p[:, 0] | (p[:, 1] << 8) | (p[:, 2] << 16) | (p[:, 3] << 24)
    return words.view(np.int32)


def unpack_feature(words, feat):
    """Bin column of (traced) feature id `feat` from packed words."""
    word = jnp.take(words, feat >> 2, axis=0)
    return (word >> ((feat & 3) * 8)) & 0xFF


# Geometric growth factor of the segment buckets. 2 minimizes streaming
# waste (<2x per segment) at ~log2(n_chunks) compiled kernel variants;
# 4 would halve the variant count (faster compile) at <4x worst-case
# waste. Jitted programs bake it in.
BUCKET_GROWTH = 2


def bucket_sizes(n_chunks):
    """Geometric chunk buckets up to the full array (see BUCKET_GROWTH)."""
    growth = BUCKET_GROWTH
    sizes = []
    b = 1
    while b < n_chunks:
        sizes.append(b)
        b *= growth
    sizes.append(n_chunks)
    return sizes


def canonical_row_chunks(n_chunks):
    """Round a HIST_CHUNK-chunk count up to a 3-bit-mantissa grid
    (m * 2^e, m in [8, 15]) — the shape-bucketing half of the persistent
    compile cache (config.py setup_compilation_cache): datasets whose
    padded row counts land in the same bucket share every lowered
    executable across processes, at <= 1/8 extra padded rows. Counts
    <= 8 are already canonical (too few distinct values to fragment the
    cache)."""
    if n_chunks <= 8:
        return n_chunks
    step = 1 << (n_chunks.bit_length() - 4)
    return -(-n_chunks // step) * step


def rung_index(begin, cnt, rungs, unit):
    """`lax.switch` index of the smallest of `rungs` (ascending window
    lengths in units of `unit` rows) that covers the position range
    [begin, begin+cnt) from a multiple of `unit`, and the first covered
    unit. A consumer MUST window with `rung_start` over the same
    ladder."""
    first = begin // unit
    last = (begin + jnp.maximum(cnt, 1) - 1) // unit
    needed = last - first + 1
    idx = jnp.searchsorted(jnp.asarray(rungs, dtype=jnp.int32), needed)
    return idx, first


def rung_start(first, rung, n_units, unit):
    """First ROW of the `rung`-unit window at unit `first`, clipped
    in-bounds (a pulled-back window still covers the range; see
    rung_index)."""
    return jnp.clip(first, 0, n_units - rung) * unit


def cover_index(begin, cnt, n_chunks):
    """Chunk-cover dispatch of the partition step (models/partitioned.py
    _partition_segment) and `compacted_histograms`: `rung_index` over
    `bucket_sizes`' whole chunks. Window with `window_start`."""
    return rung_index(begin, cnt, bucket_sizes(n_chunks), HIST_CHUNK)


def window_start(c_first, bk, n_chunks):
    """First ROW of the bk-chunk window at c_first (see cover_index)."""
    return rung_start(c_first, bk, n_chunks, HIST_CHUNK)


# Feature count from which the kernel body loops over word rows instead
# of unrolling every feature. The unrolled body is what compiles
# fastest to run at a few dozen features (28: 3 s a bucket branch); its
# compile time grows with the feature count (136: 18 s a branch, eleven
# branches a program, PERF.md PR 29), the rolled body's does not.
ROLL_FEATURES = 64


def onehot_extent(num_bins_total):
    """(rows a feature's one-hot spans, features a contraction takes)
    for a static histogram width: 64 rows and the four features of a
    packed word row up to 64 bins, else whole 128-row tiles and one
    feature. 64 is a whole number of bfloat16 (16) and float32 (8)
    sublane tiles, so the stacked lanes need no relayout."""
    if num_bins_total <= 64:
        return 64, 4
    return -(-num_bins_total // 128) * 128, 1


# 136 columns at 63 bins (34 word rows of 256: 4.5 MB, written back
# through a second buffer) are what a v5e's 16 MB of scoped VMEM were
# seen to take (PERF.md PR 29); a block is the power of two below.
ONE_BLOCK_ROWS = 34 * 256
ACC_BLOCK_ROWS = 32 * 256


def feature_blocks(f, num_bins_total):
    """(feature blocks a call, features a block) of the kernel's grid.
    The accumulator's last dimension, nine statistics, pads to 128 lanes
    in VMEM, so what a block may hold beside its double-buffered words
    and statistics is counted in accumulator rows of 128 lanes: up to
    ONE_BLOCK_ROWS of them the whole accumulator is one block and the
    grid has no feature axis; above, a block holds ACC_BLOCK_ROWS (32
    word rows at 63 bins, 32 features at 255: whole (8, 128) tiles of
    words either way)."""
    b_pad, _ = onehot_extent(num_bins_total)
    if f * b_pad <= ONE_BLOCK_ROWS:
        return 1, f
    fb = ACC_BLOCK_ROWS // b_pad
    return -(-f // fb), fb


# One-hot elements (rows x features x padded bins) under which a rung of
# the histogram's ladder is not halved again. Four MXUs stream 768e9
# elements a second, so 32M elements are 44 us: about one 4,096-row
# chunk of the shapes whose chunk was never the cost beside the ~50-200
# us that stand round the kernel a call (the switch, the window's
# slices, the accumulator's write-back, `fold`): 28 x 64 is 7M a chunk,
# 28 x 256 29M, 136 x 64 36M, and their rungs stay whole chunks. At
# 2,000 x 64 a chunk is 524M elements and 780 us, which a 300-row leaf
# paid whole (PERF.md PR 34, 36), and a rung is an eighth of it.
RUNG_ELEMENTS = 32 * 1024 * 1024


def min_rows(f, num_bins_total):
    """Rows of the lowest rung of the histogram's ladder (`r`): the
    fewest, a power of two from one 128-lane tile to HIST_CHUNK, whose
    one-hot elements reach RUNG_ELEMENTS at the `f x b_pad` elements one
    data row costs (`onehot_extent`)."""
    b_pad, _ = onehot_extent(num_bins_total)
    r = HIST_CHUNK
    while r > 128 and (r // 2) * f * b_pad >= RUNG_ELEMENTS:
        r //= 2
    return r


def hist_rungs(n_chunks, r):
    """The histogram's window lengths in units of `r` rows: powers of
    two under one chunk, then `bucket_sizes`' whole chunks (`r` =
    HIST_CHUNK: `bucket_sizes` itself)."""
    per_chunk = HIST_CHUNK // r
    return ([1 << k for k in range(per_chunk.bit_length() - 1)]
            + [b * per_chunk for b in bucket_sizes(n_chunks)])


def _seg_hist_kernel(lohi_ref, words_ref, ghc_ref, out_ref, *, f, b_pad,
                     lanes, f_total=None):
    """One grid step = one row block of the sliced segment (HIST_CHUNK
    rows, or the whole of a rung under a chunk: `words_ref.shape[1]`; of
    one block of `f` features where the grid has a feature axis: then
    `f_total` is the call's feature count, the feature block is the
    outer grid axis and the row block the inner one, so an accumulator
    block is zeroed at its first row step and written back once).
    `out_ref` is (f, b_pad, 9) at `lanes` = 1 and (ceil(f / 4),
    4 * b_pad, 9) at `lanes` = 4: rows [b_pad * k, b_pad * (k + 1)) of
    word row w are feature 4 w + k."""
    step = pl.program_id(0 if f_total is None else 1)

    @pl.when(step == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    c = words_ref.shape[1]
    # 2-D iota, kept 2-D: a bare 1-D iota fails TPU pallas lowering
    # (pallas_guide.md "TPU requires at least 2D iota"), and staying
    # (C, 1) lets the mask broadcast into (C, 3) with no rank changes
    pos = step * c + jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    mask = (pos >= lohi_ref[0]) & (pos < lohi_ref[1])             # (C, 1)
    ghc_m = jnp.where(mask, ghc_ref[...], 0)                      # (C, 9)
    b_iota = jax.lax.broadcasted_iota(jnp.int32, (b_pad, c), 0)
    if lanes == 1 and f < ROLL_FEATURES:
        for i in range(f):
            word = words_ref[i >> 2, :]
            bins_f = (word >> ((i & 3) * 8)) & 0xFF
            out_ref[i, :, :] += onehot_dot(bins_f[None, :], b_iota, ghc_m)
        return

    def word_row(wi, byte_lanes):
        # a partly filled last word keeps its unused lanes out: the
        # packed padding bytes are 0 and would count in bin 0
        word = words_ref[pl.ds(wi, 1), :]                         # (1, C)
        if lanes == 1:
            for b in range(byte_lanes):  # the four byte lanes stay static
                out_ref[wi * 4 + b] += onehot_dot((word >> (b * 8)) & 0xFF,
                                                  b_iota, ghc_m)
            return
        onehot = jnp.concatenate(
            [(((word >> (b * 8)) & 0xFF) == b_iota).astype(jnp.bfloat16)
             for b in range(byte_lanes)], axis=0)
        out_ref[wi, :byte_lanes * b_pad, :] += jax.lax.dot_general(
            onehot, ghc_m, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if f_total is not None:
        # the last block's word rows end with the call's; the unused
        # lanes of a partly filled last word land in accumulator rows
        # past `f_total`, which the block has and the result has not
        rows_here = jnp.minimum(
            f // 4, -(-f_total // 4) - pl.program_id(0) * (f // 4))

        def block_body(wi, _):
            word_row(wi, 4)
            return 0

        jax.lax.fori_loop(0, rows_here, block_body, 0)
        return
    if f < ROLL_FEATURES:
        for wi in range(f // 4):
            word_row(wi, 4)
    else:
        def body(wi, _):
            word_row(wi, 4)
            return 0

        jax.lax.fori_loop(0, f // 4, body, 0)
    if f % 4:
        word_row(f // 4, f % 4)


def _seg_hist_tpu(words_sl, ghc_sl, lo, hi, f, num_bins_total, n_blocks,
                  interpret=False):
    """Pallas segment histogram over a window of `n_blocks` equal row
    blocks (one rung of the ladder: HIST_CHUNK rows a block, or the
    whole of a rung under a chunk). `interpret` runs the kernel body in
    pallas interpret mode (CPU) — used by tests to validate kernel
    semantics without TPU hardware."""
    w = words_sl.shape[0]
    block = words_sl.shape[1] // n_blocks
    b_pad, lanes = onehot_extent(num_bins_total)
    n_fb, fb = feature_blocks(f, num_bins_total)
    acc_shape = ((-(-f // 4), 4 * b_pad, STAT_TERMS) if lanes == 4
                 else (f, b_pad, STAT_TERMS))
    with scope("window"):
        lohi = jnp.stack([lo, hi]).astype(jnp.int32)
        stats = split_stats(ghc_sl)
    if n_fb == 1:
        kernel = functools.partial(_seg_hist_kernel, f=f, b_pad=b_pad,
                                   lanes=lanes)
        grid = (n_blocks,)
        words_spec = pl.BlockSpec((w, block), lambda i: (0, i),
                                  memory_space=pltpu.VMEM)
        stats_spec = pl.BlockSpec((block, STAT_TERMS), lambda i: (i, 0),
                                  memory_space=pltpu.VMEM)
        out_spec = pl.BlockSpec(acc_shape, lambda i: (0, 0, 0),
                                memory_space=pltpu.VMEM)
    else:
        kernel = functools.partial(_seg_hist_kernel, f=fb, b_pad=b_pad,
                                   lanes=lanes, f_total=f)
        grid = (n_fb, n_blocks)
        words_spec = pl.BlockSpec((fb // 4, block), lambda j, i: (j, i),
                                  memory_space=pltpu.VMEM)
        stats_spec = pl.BlockSpec((block, STAT_TERMS), lambda j, i: (i, 0),
                                  memory_space=pltpu.VMEM)
        out_spec = pl.BlockSpec((fb // lanes,) + acc_shape[1:],
                                lambda j, i: (j, 0, 0),
                                memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        kernel,
        name="seg_hist",  # the kernel's name in a trace, and its scope
        interpret=interpret,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # (2,) lo/hi
            words_spec,
            stats_spec,
        ],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(acc_shape, jnp.float32),
    )(lohi, words_sl, stats)
    with scope("fold"):
        out = out.reshape(-1, b_pad, STAT_TERMS)[:f, :num_bins_total, :]
        return fold_stats(out)


def _seg_hist_xla(words_sl, ghc_sl, lo, hi, f, num_bins_total):
    """XLA fallback (CPU tests / non-TPU): unpack + positional mask +
    the platform's chunk formulation of ops/histogram.py."""
    from .histogram import build_histograms
    w, n = words_sl.shape
    shifts = jnp.arange(4, dtype=jnp.int32) * 8
    bins = ((words_sl[:, None, :] >> shifts[None, :, None]) & 0xFF)
    bins = bins.reshape(w * 4, n)[:f]
    pos = jnp.arange(n, dtype=jnp.int32)
    mask = ((pos >= lo) & (pos < hi)).astype(jnp.float32)
    ghc_m = ghc_sl * mask[:, None]
    return build_histograms(bins, ghc_m, num_bins_total,
                            row_chunk=min(n, HIST_CHUNK))


def segment_histograms(words, ghc_t, begin, cnt, num_bins_total, f,
                       interpret_backend=None, interpret=False):
    """hist[f, b, k] over the position range [begin, begin+cnt).

    Args:
      words: (W, N) int32 packed bins (leaf-ordered), N % HIST_CHUNK == 0.
      ghc_t: (3, N) float32 leaf-ordered stats (grad*inbag, hess*inbag,
        inbag); padding rows must be zero.
      begin, cnt: traced int32 segment bounds.
      num_bins_total: static histogram width B.
      f: static real feature count (<= 4W).

    Returns (F, B, 3) float32. Cost scales with the rung of the ladder
    (`hist_rungs` over `min_rows(f, num_bins_total)` rows) covering the
    segment, not with N.

    Sub-scopes of the device scope `hist` (telemetry/trace.py): `window`
    (slices, transpose, stat split), the kernel `seg_hist`, `fold`.
    """
    w, n = words.shape
    if n % HIST_CHUNK != 0:
        raise ValueError(f"N={n} must be a multiple of {HIST_CHUNK}")
    r = min_rows(f, num_bins_total)
    n_units = n // r
    rungs = hist_rungs(n // HIST_CHUNK, r)

    begin = begin.astype(jnp.int32)
    cnt = jnp.maximum(cnt, 0).astype(jnp.int32)
    idx, u_first = rung_index(begin, cnt, rungs, r)

    if interpret_backend is None:
        # same dispatch as ops/pallas_hist.py masked_histograms: a
        # TPU backend runs the kernel, every other the XLA
        # formulation; an explicit interpret_backend wins
        from .histogram import use_pallas
        on_tpu = use_pallas()
    else:
        on_tpu = interpret_backend == "tpu"

    def make_branch(rung):
        rows = rung * r

        def branch(begin, cnt):
            with scope("window"):
                start = rung_start(u_first, rung, n_units, r)
                words_sl = jax.lax.dynamic_slice(
                    words, (jnp.int32(0), start), (w, rows))
                ghc_sl = jax.lax.dynamic_slice(
                    ghc_t, (jnp.int32(0), start), (3, rows)).T
                lo = begin - start
                hi = lo + cnt
            if on_tpu:
                # the kernel's row block: a whole rung under a chunk
                return _seg_hist_tpu(words_sl, ghc_sl, lo, hi, f,
                                     num_bins_total,
                                     max(rows // HIST_CHUNK, 1),
                                     interpret=interpret)
            return _seg_hist_xla(words_sl, ghc_sl, lo, hi, f, num_bins_total)
        return branch

    return jax.lax.switch(idx, [make_branch(u) for u in rungs], begin, cnt)
