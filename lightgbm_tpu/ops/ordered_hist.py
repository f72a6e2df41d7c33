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

The kernel's time is the operand rows it streams through the MXU (rows
x features x rows a feature), so the form follows the static
`num_bins_total` (`onehot_extent`). Up to 64 bins a feature's one-hot is
64 rows, and the four byte lanes of a packed word row are stacked into
one 256-row one-hot and take one contraction. Above, a bin is split
into high and low bits (`low_bins`, the split-bin form): a feature
streams its nine statistic terms masked by each of L = 4 low values, 40
rows with a zero tenth slot, against a one-hot of its b_pad / 4 high
values, where the one-hot form streamed b_pad (docs/Histogram-Engine.md).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry.trace import scope
from .pallas_hist import HIST_CHUNK, STAT_TERMS, fold_stats, split_stats


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


def bin_extent(num_bins_total):
    """Padded bins a feature's histogram spans in the kernel (`b_pad`):
    64 up to 64 bins, whole 128s above. 64 is a whole number of
    bfloat16 (16) and float32 (8) sublane tiles, so a word row's four
    stacked one-hots need no relayout."""
    if num_bins_total <= 64:
        return 64
    return -(-num_bins_total // 128) * 128


def low_bins(num_bins_total):
    """L, the low values a bin is split into (b = hi * L + lo), from the
    padded width alone; 0 where the one-hot form runs. Above 64 bins a
    feature streams 10 L masked statistic rows (`split_extent`) where
    the one-hot form streamed b_pad (128 or 256); up to 64 bins the
    one-hot's 64 rows are already as few. L = 4: the root call at
    11,534,336 x 28 x 255 took 18.6 ms on a TPU v5e, where L = 8 (72
    rows a feature, at the MXU's pace) took 30.8, L = 2 (a 128-row high
    one-hot a feature, at the VPU's) 27.4 and the one-hot form 108.3; at
    100 bins L = 4 and L = 2 took 17.4 and 17.3 ms, L = 8 30.8, the
    one-hot form 54.9 (docs/Histogram-Engine.md)."""
    return 0 if bin_extent(num_bins_total) <= 64 else 4


def split_extent(b_pad, low):
    """(high values H, features a contraction takes, term slots) of the
    split-bin form: as many features as fit their H-row high one-hots
    into one 128-column weight tile, at most a word row's four; and the
    statistic terms' slots a feature streams L rows each, a float32
    (8, C) sublane tile holding 8 / L of them (at L = 4 the nine terms
    take five tiles, the tenth slot zero)."""
    high = b_pad // low
    per_tile = 8 // low
    return high, min(4, 128 // high), -(-STAT_TERMS // per_tile) * per_tile


def onehot_extent(num_bins_total):
    """(rows a feature streams through the MXU, features a contraction
    takes) for a static histogram width: a 64-row one-hot and the four
    features of a packed word row up to 64 bins; above, the masked
    statistic rows of `split_extent`'s term slots (40 at L = 4) and the
    features whose high one-hots share a weight tile."""
    low = low_bins(num_bins_total)
    if not low:
        return bin_extent(num_bins_total), 4
    _, group, slots = split_extent(bin_extent(num_bins_total), low)
    return slots * low, group


# 136 columns at 63 bins (34 word rows of 256: 4.5 MB, written back
# through a second buffer) are what a v5e's 16 MB of scoped VMEM were
# seen to take (PERF.md PR 29); a block is the power of two below.
ONE_BLOCK_ROWS = 34 * 256
ACC_BLOCK_ROWS = 32 * 256


def feature_blocks(f, num_bins_total):
    """(feature blocks a call, features a block) of the kernel's grid.
    The one-hot form's accumulator has nine statistics on its last
    dimension, padded to 128 lanes in VMEM, so what a block may hold
    beside its double-buffered words and statistics is counted in
    accumulator rows of 128 lanes, `b_pad` a feature: up to
    ONE_BLOCK_ROWS of them the whole accumulator is one block and the
    grid has no feature axis; above, a block holds ACC_BLOCK_ROWS (32
    word rows at 63 bins, 32 features at 255: whole (8, 128) tiles of
    words either way). The split-bin form keeps these blocks; its
    accumulator is smaller (160 x 128 a word row, where the one-hot
    form's was 1,024 x 128 at 255 bins)."""
    b_pad = bin_extent(num_bins_total)
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
    data row costs (`bin_extent`)."""
    b_pad = bin_extent(num_bins_total)
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


def _onehot_word_row(lohi_ref, words_ref, ghc_ref, out_ref, step, b_pad):
    """The one-hot form's body for one packed word row (up to 64 bins):
    the four byte lanes' 64-row one-hots stacked into one 256-row
    operand against the (C, 9) statistic terms; `out_ref` is
    (ceil(f / 4), 4 * b_pad, 9), rows [b_pad * k, b_pad * (k + 1)) of
    word row w are feature 4 w + k."""
    c = words_ref.shape[1]
    # 2-D iota, kept 2-D: a bare 1-D iota fails TPU pallas lowering
    # (pallas_guide.md "TPU requires at least 2D iota"), and staying
    # (C, 1) lets the mask broadcast into (C, 3) with no rank changes
    pos = step * c + jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    mask = (pos >= lohi_ref[0]) & (pos < lohi_ref[1])             # (C, 1)
    ghc_m = jnp.where(mask, ghc_ref[...], 0)                      # (C, 9)
    b_iota = jax.lax.broadcasted_iota(jnp.int32, (b_pad, c), 0)

    def word_row(wi, byte_lanes):
        # a partly filled last word keeps its unused lanes out: the
        # packed padding bytes are 0 and would count in bin 0
        word = words_ref[pl.ds(wi, 1), :]                         # (1, C)
        onehot = jnp.concatenate(
            [(((word >> (b * 8)) & 0xFF) == b_iota).astype(jnp.bfloat16)
             for b in range(byte_lanes)], axis=0)
        out_ref[wi, :byte_lanes * b_pad, :] += jax.lax.dot_general(
            onehot, ghc_m, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    return word_row


def _split_word_row(lohi_ref, words_ref, stats_ref, out_ref, step, b_pad,
                    low):
    """The split-bin form's body for one packed word row (above 64 bins;
    docs/Histogram-Engine.md). With b = hi * L + lo,

        hist[f, b, s] = sum_c [hi_f(c) == hi] * ([lo_f(c) == lo] * stat_s(c))

    is one bfloat16 contraction over the row block's lanes a group of
    `g` features (`split_extent`): the streamed operand is the masked
    statistic rows (f, s, l), `where(lo_f == l, stat_s, 0)`, float32
    (8, C) tiles of 8 / L terms each (a feature's T tiles are one select
    against one compare of its low bits), packed to bfloat16, against
    the features' stacked (H, C) high one-hots, contracted lane with
    lane. The products are a term or 0, the sums float32, as in the
    one-hot form. `out_ref` is (ceil(f / 4), 4 S L, g H) for S term
    slots: group j of word row w holds rows [g S L j, g S L (j + 1)), of
    which feature 4 w + g j + k's own are the diagonal block (rows
    S L k.., columns H k..) `_split_fold` keeps. `stats_ref` is the
    (9, C) terms, lane-major."""
    c = words_ref.shape[1]
    high, group, slots = split_extent(b_pad, low)
    per_tile = 8 // low
    rows = group * slots * low
    pos = step * c + jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
    mask = (pos >= lohi_ref[0]) & (pos < lohi_ref[1])             # (1, C)
    stats = jnp.where(mask, stats_ref[...].astype(jnp.float32), 0.0)
    # the terms' tiles, once a row block: sublane r of tile t holds term
    # t * 8 / L + r // L (at L = 4 the tenth slot is zero)
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, c), 0)
    tiles = []
    for t in range(slots // per_tile):
        tile = jnp.zeros((8, c), jnp.float32)
        for q in range(per_tile):
            if t * per_tile + q < STAT_TERMS:
                tile = jnp.where(sub // low == q,
                                 stats[t * per_tile + q:t * per_tile + q + 1],
                                 tile)
        tiles.append(tile)
    tiles = jnp.stack(tiles)                                      # (T, 8, C)
    lo_iota = sub % low
    hi_iota = jax.lax.broadcasted_iota(jnp.int32, (high, c), 0)
    shift = low.bit_length() - 1

    def word_row(wi, byte_lanes):
        # all four byte lanes, whatever `byte_lanes` says: the padding
        # bytes of a partly filled last word are features past `f`,
        # whose diagonal blocks the fold cuts off
        del byte_lanes
        word = words_ref[pl.ds(wi, 1), :]                         # (1, C)
        for j in range(4 // group):
            masked, onehot = [], []
            for k in range(j * group, (j + 1) * group):
                bins = (word >> (k * 8)) & 0xFF
                at_low = (bins & (low - 1)) == lo_iota            # (8, C)
                masked.append(jnp.where(at_low, tiles, 0.0))      # (T, 8, C)
                onehot.append(((bins >> shift) == hi_iota)
                              .astype(jnp.bfloat16))              # (H, C)
            out_ref[wi, j * rows:(j + 1) * rows, :] += jax.lax.dot_general(
                jnp.concatenate(masked, axis=0).reshape(rows, c)
                .astype(jnp.bfloat16),
                jnp.concatenate(onehot, axis=0), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
    return word_row


def _seg_hist_kernel(lohi_ref, words_ref, stats_ref, out_ref, *, f, b_pad,
                     low, f_total=None):
    """One grid step = one row block of the sliced segment (HIST_CHUNK
    rows, or the whole of a rung under a chunk: `words_ref.shape[1]`; of
    one block of `f` features where the grid has a feature axis: then
    `f_total` is the call's feature count, the feature block is the
    outer grid axis and the row block the inner one, so an accumulator
    block is zeroed at its first row step and written back once). The
    packed word row is the body's one unit: `_onehot_word_row` (`low`
    = 0) or `_split_word_row` (`low` = L)."""
    step = pl.program_id(0 if f_total is None else 1)

    @pl.when(step == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    if low:
        word_row = _split_word_row(lohi_ref, words_ref, stats_ref, out_ref,
                                   step, b_pad, low)
    else:
        word_row = _onehot_word_row(lohi_ref, words_ref, stats_ref, out_ref,
                                    step, b_pad)

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
    if low:
        # the word row is traced once and lowered a copy a row (rolled
        # from ROLL_FEATURES on): a program traces and lowers this body
        # at every rung and call site (26 at 2,816 chunks), and 28
        # columns traced row by row doubled what those cost
        def split_body(wi, _):
            word_row(wi, 4)
            return 0

        jax.lax.fori_loop(0, -(-f // 4), split_body, 0,
                          unroll=f < ROLL_FEATURES)
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


def _split_fold(acc, b_pad, low):
    """The split-bin accumulator (W, 4 S L, g H) -> (4 W, b_pad, 9): each
    feature's diagonal block (its own masked rows against its own high
    columns), its nine terms of S slots, (term, lo) x hi reordered to
    bin = hi * L + lo."""
    high, group, slots = split_extent(b_pad, low)
    w = acc.shape[0]
    acc = acc.reshape(w, 4 // group, group, slots, low, group, high)
    own = jnp.stack([acc[:, :, k, :STAT_TERMS, :, k, :]
                     for k in range(group)], axis=2)
    return (own.reshape(4 * w, STAT_TERMS, low, high)
            .transpose(0, 3, 2, 1).reshape(4 * w, b_pad, STAT_TERMS))


def _seg_hist_tpu(words_sl, ghc_sl, lo, hi, f, num_bins_total, n_blocks,
                  interpret=False):
    """Pallas segment histogram over a window of `n_blocks` equal row
    blocks (one rung of the ladder: HIST_CHUNK rows a block, or the
    whole of a rung under a chunk). `interpret` runs the kernel body in
    pallas interpret mode (CPU) — used by tests to validate kernel
    semantics without TPU hardware."""
    w = words_sl.shape[0]
    block = words_sl.shape[1] // n_blocks
    b_pad = bin_extent(num_bins_total)
    low = low_bins(num_bins_total)
    n_fb, fb = feature_blocks(f, num_bins_total)
    if low:
        high, group, slots = split_extent(b_pad, low)
        acc_shape = (-(-f // 4), 4 * slots * low, group * high)
    else:
        acc_shape = (-(-f // 4), 4 * b_pad, STAT_TERMS)
    with scope("window"):
        lohi = jnp.stack([lo, hi]).astype(jnp.int32)
        # the split-bin form's selects take each term as a lane row
        stats = split_stats(ghc_sl.T, axis=0) if low else split_stats(ghc_sl)
    if low:
        stats_block, stats_index = (STAT_TERMS, block), lambda i: (0, i)
    else:
        stats_block, stats_index = (block, STAT_TERMS), lambda i: (i, 0)
    if n_fb == 1:
        kernel = functools.partial(_seg_hist_kernel, f=f, b_pad=b_pad,
                                   low=low)
        grid = (n_blocks,)
        words_spec = pl.BlockSpec((w, block), lambda i: (0, i),
                                  memory_space=pltpu.VMEM)
        stats_spec = pl.BlockSpec(stats_block, stats_index,
                                  memory_space=pltpu.VMEM)
        out_spec = pl.BlockSpec(acc_shape, lambda i: (0, 0, 0),
                                memory_space=pltpu.VMEM)
    else:
        kernel = functools.partial(_seg_hist_kernel, f=fb, b_pad=b_pad,
                                   low=low, f_total=f)
        grid = (n_fb, n_blocks)
        words_spec = pl.BlockSpec((fb // 4, block), lambda j, i: (j, i),
                                  memory_space=pltpu.VMEM)
        stats_spec = pl.BlockSpec(stats_block,
                                  lambda j, i: stats_index(i),
                                  memory_space=pltpu.VMEM)
        out_spec = pl.BlockSpec((fb // 4,) + acc_shape[1:],
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
        if low:
            out = _split_fold(out, b_pad, low)
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
