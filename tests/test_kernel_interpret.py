"""Pallas kernel semantics validated in interpret mode on CPU: the TPU
kernels' masking, packed-word unpacking, and grid accumulation must
match the XLA fallback implementations bit-for-... well, to f32
tolerance. Catches kernel-body bugs without TPU hardware (Mosaic
compilation itself is only exercised on a real chip)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from lightgbm_tpu.ops.ordered_hist import (pack_feature_words,
                                           segment_histograms)
from lightgbm_tpu.ops import pallas_hist
from lightgbm_tpu.ops.pallas_hist import (HIST_CHUNK,
                                          frontier_histograms_tpu,
                                          masked_histograms_tpu,
                                          masked_histograms_xla)


def test_masked_kernel_interpret_matches_xla():
    rng = np.random.RandomState(0)
    f, n, b = 5, 2 * HIST_CHUNK, 16
    bins = jnp.asarray(rng.randint(0, b, size=(f, n), dtype=np.uint8))
    ghc_t = jnp.asarray(rng.rand(3, n).astype(np.float32))
    row_leaf = jnp.asarray(rng.randint(0, 3, size=n).astype(np.int32))
    got = jax.jit(lambda: masked_histograms_tpu(
        bins, ghc_t, row_leaf, jnp.int32(1), b, interpret=True))()[0]
    want_hi, want_lo = jax.jit(lambda: masked_histograms_xla(
        bins, ghc_t, row_leaf, jnp.int32(1), b))()
    want = np.asarray(want_hi) + np.asarray(want_lo)
    assert got.shape == (f, b, 3)  # kernel trims the padded bin axis
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-4)


def test_segment_kernel_interpret_matches_xla():
    rng = np.random.RandomState(1)
    f, n, b = 6, 3 * HIST_CHUNK, 16
    bins = rng.randint(0, b, size=(f, n), dtype=np.uint8)
    words = jnp.asarray(pack_feature_words(bins))
    ghc_t = jnp.asarray(rng.rand(3, n).astype(np.float32))
    got_fn = jax.jit(lambda be, cn: segment_histograms(
        words, ghc_t, be, cn, b, f=8, interpret_backend="tpu",
        interpret=True))
    want_fn = jax.jit(lambda be, cn: segment_histograms(
        words, ghc_t, be, cn, b, f=8, interpret_backend="cpu"))
    for begin, cnt in [(0, n), (100, HIST_CHUNK), (HIST_CHUNK - 7, 50),
                       (2 * HIST_CHUNK + 5, HIST_CHUNK - 5)]:
        got = got_fn(jnp.int32(begin), jnp.int32(cnt))
        want = want_fn(jnp.int32(begin), jnp.int32(cnt))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)


def test_masked_kernel_interpret_packed_int16():
    """The packed-bin contract on the kernel: int16 bins (the > 256-bin
    storage width) stream through the masked kernel unchanged — the
    widening to int32 happens per-chunk in registers."""
    rng = np.random.RandomState(2)
    f, n, b = 4, 2 * HIST_CHUNK, 300
    bins = rng.randint(0, b, size=(f, n)).astype(np.int16)
    ghc_t = jnp.asarray(rng.rand(3, n).astype(np.float32))
    row_leaf = jnp.asarray(rng.randint(0, 3, size=n).astype(np.int32))
    got = jax.jit(lambda: masked_histograms_tpu(
        jnp.asarray(bins), ghc_t, row_leaf, jnp.int32(2), b,
        interpret=True))()[0]
    want_hi, want_lo = jax.jit(lambda: masked_histograms_xla(
        jnp.asarray(bins), ghc_t, row_leaf, jnp.int32(2), b))()
    want = np.asarray(want_hi) + np.asarray(want_lo)
    assert got.shape == (f, b, 3)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-4)


def test_frontier_kernel_interpret_matches_masked():
    """Multi-leaf kernel semantics: the leaf-indexed accumulator's
    per-leaf slices equal the single-leaf masked kernel's output for
    every frontier member (the builder mixes the two freely)."""
    rng = np.random.RandomState(3)
    f, n, b = 5, 2 * HIST_CHUNK, 16
    bins = jnp.asarray(rng.randint(0, b, size=(f, n), dtype=np.uint8))
    ghc_t = jnp.asarray(rng.rand(3, n).astype(np.float32))
    row_leaf = jnp.asarray(rng.randint(0, 4, size=n).astype(np.int32))
    leaf_ids = jnp.asarray([3, 0, 2], jnp.int32)
    got, res = jax.jit(lambda: frontier_histograms_tpu(
        bins, ghc_t, row_leaf, leaf_ids, b, interpret=True))()
    assert got.shape == (3, f, b, 3)
    assert np.asarray(res).max() == 0.0
    for i, lid in enumerate([3, 0, 2]):
        want = jax.jit(lambda lid=lid: masked_histograms_tpu(
            bins, ghc_t, row_leaf, jnp.int32(lid), b,
            interpret=True))()[0]
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)


def test_frontier_kernel_vmem_fallback(monkeypatch):
    """A frontier whose accumulator would blow the VMEM budget falls
    back to stacked per-leaf kernel calls with identical results. The
    budget counts TILED bytes: a trailing dim of 9 occupies 128 lanes."""
    tiled = pallas_hist.tiled_vmem_bytes
    assert tiled((28, 256, 9), jnp.float32) == 28 * 256 * 128 * 4
    assert tiled((28, HIST_CHUNK), jnp.uint8) == 32 * HIST_CHUNK
    assert tiled((HIST_CHUNK, 9), jnp.bfloat16) == HIST_CHUNK * 128 * 2
    # both children at the smoke geometry fit; a 1000-feature frontier
    # does not
    assert tiled((2, 28, 256, 9), jnp.float32) \
        <= pallas_hist.FRONTIER_VMEM_BYTES \
        < tiled((2, 1000, 256, 9), jnp.float32)
    rng = np.random.RandomState(5)
    f, n, b = 3, HIST_CHUNK, 16
    bins = jnp.asarray(rng.randint(0, b, size=(f, n), dtype=np.uint8))
    ghc_t = jnp.asarray(rng.rand(3, n).astype(np.float32))
    row_leaf = jnp.asarray(rng.randint(0, 4, size=n).astype(np.int32))
    leaf_ids = jnp.asarray([0, 1], jnp.int32)
    full = jax.jit(lambda: frontier_histograms_tpu(
        bins, ghc_t, row_leaf, leaf_ids, b, interpret=True))()[0]
    monkeypatch.setattr(pallas_hist, "FRONTIER_VMEM_BYTES", 1)
    fallback = jax.jit(lambda: frontier_histograms_tpu(
        bins, ghc_t, row_leaf, leaf_ids, b, interpret=True))()[0]
    np.testing.assert_array_equal(np.asarray(full), np.asarray(fallback))


def test_segment_kernel_interpret_bench_shape():
    """The exact histogram geometry of the driver benchmark (28
    features -> 7 packed words, max_bin 255 -> one padded 256-bin
    tile): kernel-body semantics pinned in interpret mode before the
    first real-TPU run ever happens."""
    rng = np.random.RandomState(4)
    f, n, b = 28, 2 * HIST_CHUNK, 255
    bins = rng.randint(0, b, size=(f, n), dtype=np.uint8)
    words = jnp.asarray(pack_feature_words(bins))
    ghc_t = jnp.asarray(rng.rand(3, n).astype(np.float32))
    begin, cnt = jnp.int32(HIST_CHUNK - 9), jnp.int32(HIST_CHUNK // 2)
    got = segment_histograms(words, ghc_t, begin, cnt, b, f=f,
                             interpret_backend="tpu", interpret=True)
    want = segment_histograms(words, ghc_t, begin, cnt, b, f=f,
                              interpret_backend="cpu")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


# ------------------------------------------ the partition kernel (PR 26)
def _partition_fixture():
    from lightgbm_tpu.ops.partition import PART_CHUNK
    rng = np.random.RandomState(6)
    n, f = 4 * PART_CHUNK, 7
    bins = rng.randint(0, 16, size=(f, n), dtype=np.uint8)
    words = pack_feature_words(bins)
    # every bit pattern has to survive: full-range words in the row no
    # feature of this fixture reads, signed statistics, a shuffled perm
    words[1] = rng.randint(-2**31, 2**31 - 1, size=n,
                           dtype=np.int64).astype(np.int32)
    ghc = rng.randn(3, n).astype(np.float32)
    perm = rng.permutation(n).astype(np.int32)
    return n, bins, jnp.asarray(words), jnp.asarray(ghc), jnp.asarray(perm)


def _efb_decode(w_sl, feat):
    """A stand-in for the bundled learner's slot decode: virtual feature
    v lives in stored slot v // 2, its bins offset by 5 * (v % 2)."""
    from lightgbm_tpu.ops.ordered_hist import unpack_feature
    return jnp.maximum(unpack_feature(w_sl, feat // 2) - 5 * (feat % 2), 0)


_N = 4 * 2048
PARTITION_CASES = {
    # name: (seg_b, seg_c, feat, thr, categorical, decode)
    "whole_array": (0, _N, 2, 7, False, None),
    "begin_not_tile_aligned": (100, 3000, 2, 7, False, None),
    "inside_one_tile": (37, 50, 2, 7, False, None),
    "across_a_chunk_edge": (2048 - 5, 10, 2, 7, False, None),
    "ends_at_n": (_N - 200, 200, 2, 7, False, None),
    "chunk_aligned": (2048, 4096, 2, 7, False, None),
    "empty": (5, 0, 2, 7, False, None),
    "one_row": (5, 1, 2, 7, False, None),
    "all_left": (130, 5000, 2, 15, False, None),
    "all_right": (130, 5000, 2, -1, False, None),
    "categorical": (999, 4103, 3, 4, True, None),
    "efb_decode": (1000, 5000, 5, 3, False, _efb_decode),
}


@pytest.mark.parametrize("case", sorted(PARTITION_CASES))
def test_partition_kernel_interpret_matches_xla(case):
    """`partition_rows` (one streaming compaction kernel, in place)
    against the off-TPU formulation it replaces on the chip: prefix
    sums, the inverse-permutation scatter and the three gathers. A
    permutation of 32-bit patterns: every word, statistic, perm entry
    and n_left bit-equal."""
    from lightgbm_tpu.models.partitioned import _partition_segment_rows
    from lightgbm_tpu.ops.ordered_hist import unpack_feature
    from lightgbm_tpu.ops.partition import (apply_partition,
                                            invert_permutation, pack_rows,
                                            split_destinations, unpack_rows)
    n, bins, words, ghc, perm = _partition_fixture()
    assert n == _N
    seg_b, seg_c, feat, thr, cat, decode = PARTITION_CASES[case]
    decode = decode or unpack_feature
    b, c = jnp.int32(seg_b), jnp.int32(seg_c)

    @jax.jit
    def kernel(b, c):
        rows_i, rows_f, n_left = _partition_segment_rows(
            *pack_rows(words, ghc, perm), b, c, jnp.int32(feat),
            jnp.int32(thr), jnp.asarray(cat), decode, interpret=True)
        return unpack_rows(rows_i, rows_f, words.shape[0]) + (n_left,)

    @jax.jit
    def formulation(b, c):
        col = decode(words, jnp.int32(feat))
        go_left = jnp.where(cat, col == thr, col <= thr)
        dest, n_left = split_destinations(go_left, b, c)
        return apply_partition(invert_permutation(dest), words, ghc,
                               perm) + (n_left,)

    got, want = kernel(b, c), formulation(b, c)
    if case == "all_left":
        assert int(want[3]) == seg_c
    if case == "all_right":
        assert int(want[3]) == 0
    for name, g, w in zip(("words", "ghc", "perm", "n_left"), got, want):
        np.testing.assert_array_equal(
            np.asarray(g).view(np.int32), np.asarray(w).view(np.int32),
            err_msg=f"{case}: {name}")


# ----------------------- both kernels at 136 columns, 34 words a row (PR 29)
def test_segment_kernel_interpret_136_columns():
    """MSLR-WEB30K's geometry: 136 features in 34 packed words, 63 bins
    in one padded 128-bin tile. From ROLL_FEATURES on the kernel body is
    a loop over word rows with the four byte lanes static inside; the
    sums are the unrolled body's. 135 leaves a word row partly filled."""
    from lightgbm_tpu.ops.ordered_hist import ROLL_FEATURES
    rng = np.random.RandomState(8)
    n, b = 2 * HIST_CHUNK, 63
    for f in (136, 135):
        assert f >= ROLL_FEATURES
        bins = rng.randint(0, b, size=(f, n), dtype=np.uint8)
        words = jnp.asarray(pack_feature_words(bins))
        stats = rng.randn(3, n).astype(np.float32)
        stats[2] = 1.0                      # every row in the bag
        ghc_t = jnp.asarray(stats)
        begin, cnt = jnp.int32(HIST_CHUNK - 9), jnp.int32(HIST_CHUNK // 2)
        got = segment_histograms(words, ghc_t, begin, cnt, b, f=f,
                                 interpret_backend="tpu", interpret=True)
        want = segment_histograms(words, ghc_t, begin, cnt, b, f=f,
                                  interpret_backend="cpu")
        assert got.shape == (f, b, 3)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)
        # the count column is exact
        np.testing.assert_array_equal(np.asarray(got)[..., 2].sum(axis=1),
                                      np.full(f, HIST_CHUNK // 2))


# ------------- the one-hot spans the bins a configuration has (PR 30)
def _seg_hist_128_rows(words_sl, ghc_sl, lo, hi, f, num_bins_total,
                       n_blocks):
    """The segment kernel as it was before PR 30, kept here as the
    yardstick: every feature's one-hot padded to whole 128-row tiles,
    one contraction a feature (unrolled under ROLL_FEATURES, else a
    loop over word rows)."""
    import functools
    from jax.experimental import pallas as pl
    from lightgbm_tpu.ops.ordered_hist import ROLL_FEATURES
    from lightgbm_tpu.ops.pallas_hist import (STAT_TERMS, fold_stats,
                                              onehot_dot, split_stats)
    b_pad = max(-(-num_bins_total // 128) * 128, 128)

    def kernel(lohi_ref, words_ref, ghc_ref, out_ref):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

        c = words_ref.shape[1]
        pos = step * c + jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
        mask = (pos >= lohi_ref[0]) & (pos < lohi_ref[1])
        ghc_m = jnp.where(mask, ghc_ref[...], 0)
        b_iota = jax.lax.broadcasted_iota(jnp.int32, (b_pad, c), 0)
        if f < ROLL_FEATURES:
            for i in range(f):
                bins_f = (words_ref[i >> 2, :] >> ((i & 3) * 8)) & 0xFF
                out_ref[i, :, :] += onehot_dot(bins_f[None, :], b_iota,
                                               ghc_m)
            return

        def word_row(wi, byte_lanes):
            word = words_ref[pl.ds(wi, 1), :]
            for k in range(byte_lanes):
                out_ref[wi * 4 + k] += onehot_dot((word >> (k * 8)) & 0xFF,
                                                  b_iota, ghc_m)

        def body(wi, _):
            word_row(wi, 4)
            return 0

        jax.lax.fori_loop(0, f // 4, body, 0)
        if f % 4:
            word_row(f // 4, f % 4)

    w, n = words_sl.shape
    block = n // n_blocks
    out = pl.pallas_call(
        kernel, interpret=True, grid=(n_blocks,),
        in_specs=[pl.BlockSpec((2,), lambda i: (0,)),
                  pl.BlockSpec((w, block), lambda i: (0, i)),
                  pl.BlockSpec((block, STAT_TERMS), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((f, b_pad, STAT_TERMS), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((f, b_pad, STAT_TERMS), jnp.float32),
    )(jnp.stack([lo, hi]).astype(jnp.int32), words_sl, split_stats(ghc_sl))
    return fold_stats(out[:, :num_bins_total, :])


def _seg_hist_case(columns, f, b, seed):
    rng = np.random.RandomState(seed)
    n = 2 * HIST_CHUNK
    bins = rng.randint(0, b, size=(columns, n), dtype=np.uint8)
    words = jnp.asarray(pack_feature_words(bins))
    stats = rng.randn(n, 3).astype(np.float32)
    stats[:, 2] = 1.0                       # every row in the bag
    lo, hi = jnp.int32(HIST_CHUNK - 9), jnp.int32(HIST_CHUNK + 2039)
    return (words, jnp.asarray(stats), lo, hi, f, b, 2), bins


def _kernel_out_shape(args):
    from lightgbm_tpu.ops.ordered_hist import _seg_hist_tpu
    jaxpr = jax.make_jaxpr(
        lambda w, g, lo, hi: _seg_hist_tpu(w, g, lo, hi, *args[4:],
                                           interpret=True))(*args[:4])
    (call,) = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    return call.outvars[0].aval.shape


@pytest.mark.parametrize("b,rows_a_feature,features_a_dot", [
    (63, 64, 4), (64, 64, 4), (65, 40, 4), (100, 40, 4), (255, 40, 2)])
def test_segment_kernel_onehot_extent(b, rows_a_feature, features_a_dot):
    """Up to 64 bins a feature's one-hot is 64 rows and a packed word
    row's four features share one 256-row contraction. Above, the
    split-bin form (L = 4): a feature streams its nine terms' masked
    rows in five tiles of 8 (40 rows) against its 32-row (b_pad 128:
    four features a contraction) or 64-row (b_pad 256: two) high
    one-hot. Read off the kernel call's output, the accumulator the
    streamed rows land in; the split-bin sums are the one-hot body's
    bit for bit."""
    from lightgbm_tpu.ops.ordered_hist import (_seg_hist_tpu, _seg_hist_xla,
                                               low_bins, onehot_extent)
    args, bins = _seg_hist_case(8, 8, b, seed=b)
    assert onehot_extent(b) == (rows_a_feature, features_a_dot)
    assert low_bins(b) == (0 if b <= 64 else 4)
    want_shape = (2, 256, 9) if b <= 64 else (2, 160, 128)
    assert _kernel_out_shape(args) == want_shape
    got = np.asarray(_seg_hist_tpu(*args, interpret=True))
    assert got.shape == (8, b, 3)
    np.testing.assert_array_equal(got, np.asarray(_seg_hist_128_rows(*args)))
    np.testing.assert_allclose(got, np.asarray(_seg_hist_xla(*args[:6])),
                               rtol=1e-5, atol=1e-4)
    # the highest bin is counted: no row falls off the one-hot's end
    in_range = bins[0, HIST_CHUNK - 9:HIST_CHUNK + 2039]
    assert got[0, b - 1, 2] == np.sum(in_range == b - 1) > 0


@pytest.mark.parametrize("columns,f", [(28, 28), (136, 136), (135, 135),
                                       (6, 8), (32, 28)])
def test_segment_kernel_63_bins_same_sums(columns, f):
    """At 63 bins (cells `higgs10m-b63-l255.train`, 28 columns, the
    unrolled body; `mslr-web30k-b63-l255.train`, 136, the rolled one;
    135 leaves the last word row partly filled; 6 columns read as f = 8
    count their two padding bytes in bin 0, as they always did; 28 of
    32 columns are the builder's word rows padded to a tile, which the
    accumulator leaves out) a histogram cell is the contraction of its
    own one-hot row with the same nine terms, whatever other rows share
    the operand: the sums are the 128-row body's bit for bit."""
    from lightgbm_tpu.ops.ordered_hist import (ROLL_FEATURES, _seg_hist_tpu,
                                               _seg_hist_xla)
    assert (f >= ROLL_FEATURES) == (columns >= 135)
    args, _ = _seg_hist_case(columns, f, 63, seed=columns)
    assert _kernel_out_shape(args) == ((f + 3) // 4, 256, 9)
    got = np.asarray(_seg_hist_tpu(*args, interpret=True))
    assert got.shape == (f, 63, 3)
    np.testing.assert_array_equal(got, np.asarray(_seg_hist_128_rows(*args)))
    np.testing.assert_allclose(got, np.asarray(_seg_hist_xla(*args[:6])),
                               rtol=1e-5, atol=1e-4)
    # the count column is exact
    np.testing.assert_array_equal(got[..., 2].sum(axis=1),
                                  np.full(f, 2048))


# ------------------- a feature axis in the histogram kernel's grid (PR 33)
@pytest.mark.parametrize("f,b,blocks,block_features", [
    (28, 63, 1, 28), (136, 63, 1, 136), (140, 63, 2, 128),
    (2000, 63, 16, 128), (28, 255, 1, 28), (34, 255, 1, 34),
    (35, 255, 2, 32), (2000, 255, 63, 32), (68, 100, 1, 68)])
def test_feature_blocks_follow_the_accumulator(f, b, blocks, block_features):
    """One block up to 34 x 256 accumulator rows of 128 lanes (the cells
    the benchmark had before PR 33: 28 and 136 columns at 63 bins, 28 at
    255), blocks of 32 x 256 rows above: 128 features at up to 64 bins,
    64 at up to 128, 32 at 255."""
    from lightgbm_tpu.ops.ordered_hist import feature_blocks
    assert feature_blocks(f, b) == (blocks, block_features)


@pytest.mark.parametrize("f,b,blocks,block_features", [
    (130, 63, 1, 130), (150, 63, 2, 128), (515, 63, 5, 128),
    (130, 255, 5, 32), (515, 255, 17, 32), (69, 100, 2, 64),
    (28, 255, 1, 28), (30, 255, 1, 30), (70, 255, 3, 32), (66, 100, 1, 66)])
def test_segment_kernel_feature_blocks(f, b, blocks, block_features):
    """Past 136 columns at 63 bins (34 at 255) the accumulator no longer
    fits VMEM whole, and the grid gets a feature axis: blocks of 32 word
    rows (128 features) at 63 bins, of 32 features at 255. More than one
    block, a partly filled last word row (130, 150, 515 columns) and a
    partly filled last block: counts equal to the XLA formulation's to
    the bit, sums to float32 rounding; the accumulator keeps its shape
    and a block's shape is what `feature_blocks` says. Above 64 bins the
    split-bin form's word rows, (40 x 4, 128) each, at the control
    cell's 28 columns, 30 (a partly filled last word, whose padding
    bytes count in no bin of the result), 70 (the feature axis) and 66
    at 100 bins (the rolled body, one block), are the one-hot body's
    sums bit for bit."""
    from lightgbm_tpu.ops.ordered_hist import (_seg_hist_tpu, _seg_hist_xla,
                                               feature_blocks)
    assert feature_blocks(f, b) == (blocks, block_features)
    args, _ = _seg_hist_case(f, f, b, seed=f + b)
    jaxpr = jax.make_jaxpr(
        lambda w, g, lo, hi: _seg_hist_tpu(w, g, lo, hi, *args[4:],
                                           interpret=True))(*args[:4])
    (call,) = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    grid = call.params["grid_mapping"].grid
    assert grid == ((blocks, 2) if blocks > 1 else (2,))
    assert call.outvars[0].aval.shape == (
        (-(-f // 4), 256, 9) if b <= 64 else (-(-f // 4), 160, 128))
    got = np.asarray(_seg_hist_tpu(*args, interpret=True))
    want = np.asarray(_seg_hist_xla(*args[:6]))
    assert got.shape == (f, b, 3)
    if b > 64 and f <= 70:
        np.testing.assert_array_equal(
            got, np.asarray(_seg_hist_128_rows(*args)))
    np.testing.assert_array_equal(got[..., 2], want[..., 2])
    np.testing.assert_array_equal(got[..., 2].sum(axis=1), np.full(f, 2048))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("rows", [128, 512, 2048])
@pytest.mark.parametrize("f,b", [(150, 63), (515, 63), (70, 255), (8, 63)])
def test_segment_kernel_block_under_a_chunk(rows, f, b):
    """A rung under a chunk (PR 36) is one row block of that many rows:
    with the feature axis (150 and 515 columns at 63 bins, 70 at 255: a
    partly filled last word row and last block) and without (8), the
    sums are the 128-row body's over the same block bit for bit, close
    to the XLA formulation's, the counts exact; the grid has one row
    step and the blocks are the window's rows."""
    from lightgbm_tpu.ops.ordered_hist import (_seg_hist_tpu, _seg_hist_xla,
                                               feature_blocks)
    rng = np.random.RandomState(rows + f + b)
    bins = rng.randint(0, b, size=(f, rows), dtype=np.uint8)
    words = jnp.asarray(pack_feature_words(bins))
    stats = rng.randn(rows, 3).astype(np.float32)
    stats[:, 2] = 1.0
    lo, hi = rows // 8 + 3, rows - 5
    args = (words, jnp.asarray(stats), jnp.int32(lo), jnp.int32(hi), f, b, 1)
    jaxpr = jax.make_jaxpr(
        lambda w, g, lo, hi: _seg_hist_tpu(w, g, lo, hi, *args[4:],
                                           interpret=True))(*args[:4])
    (call,) = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    mapping = call.params["grid_mapping"]
    blocks, fb = feature_blocks(f, b)
    assert mapping.grid == ((blocks, 1) if blocks > 1 else (1,))
    shapes = [tuple(getattr(d, "block_size", d) for d in bm.block_shape)
              for bm in mapping.block_mappings]
    assert shapes[1:3] == [(fb // 4 if blocks > 1 else -(-f // 4), rows),
                           (9, rows) if b > 64 else (rows, 9)]
    got = np.asarray(_seg_hist_tpu(*args, interpret=True))
    assert got.shape == (f, b, 3)
    np.testing.assert_array_equal(got, np.asarray(_seg_hist_128_rows(*args)))
    np.testing.assert_allclose(got, np.asarray(_seg_hist_xla(*args[:6])),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(got[..., 2].sum(axis=1),
                                  np.full(f, hi - lo))


@pytest.mark.parametrize("begin,cnt", [(100, 20), (500, 24), (4000, 300),
                                       (1000, 1500), (0, 8192)])
def test_segment_kernel_interpret_over_small_rungs(monkeypatch, begin, cnt):
    """`segment_histograms` through the kernel, interpreted, over a
    ladder whose lowest rung is 512 rows at 150 columns (the named
    constant patched; two feature blocks): the window's rows and mask
    agree with the XLA formulation over the same ladder for a segment
    in one rung, across a rung's and a chunk's boundary, and the root."""
    from lightgbm_tpu.ops import ordered_hist
    f, b, n = 150, 63, 2 * HIST_CHUNK
    monkeypatch.setattr(ordered_hist, "RUNG_ELEMENTS", 512 * 152 * 64)
    assert ordered_hist.min_rows(152, b) == 512
    rng = np.random.RandomState(begin)
    bins = rng.randint(0, b, size=(f, n), dtype=np.uint8)
    words = jnp.asarray(pack_feature_words(bins))
    ghc_t = rng.randn(3, n).astype(np.float32)
    ghc_t[2] = 1.0
    got, want = [np.asarray(segment_histograms(
        words, jnp.asarray(ghc_t), jnp.int32(begin), jnp.int32(cnt), b,
        f=152, interpret_backend=backend, interpret=True))
        for backend in ("tpu", "cpu")]
    np.testing.assert_array_equal(got[..., 2], want[..., 2])
    np.testing.assert_array_equal(got[:f, :, 2].sum(axis=1), np.full(f, cnt))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("f,w,b", [(28, 8, 63), (136, 40, 63), (135, 40, 63),
                                   (28, 8, 255)])
def test_segment_kernel_one_block_has_no_feature_axis(f, w, b):
    """At the columns of the cells the benchmark had before PR 33 the
    whole accumulator is one block: the kernel call has the one grid
    axis (row blocks) and the block shapes of its form (the split-bin
    form's lane-major terms and accumulator at 255 bins), and its body
    reads `program_id(0)` alone."""
    from lightgbm_tpu.ops.ordered_hist import _seg_hist_tpu
    n_blocks = 4
    n = n_blocks * HIST_CHUNK
    jaxpr = jax.make_jaxpr(
        lambda words, ghc, lo, hi: _seg_hist_tpu(words, ghc, lo, hi, f, b,
                                                 n_blocks))(
        jax.ShapeDtypeStruct((w, n), jnp.int32),
        jax.ShapeDtypeStruct((n, 3), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32))
    (call,) = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    mapping = call.params["grid_mapping"]
    assert mapping.grid == (n_blocks,)
    acc = (-(-f // 4), 256, 9) if b <= 64 else (-(-f // 4), 160, 128)
    terms = (HIST_CHUNK, 9) if b <= 64 else (9, HIST_CHUNK)
    shapes = [tuple(getattr(d, "block_size", d) for d in bm.block_shape)
              for bm in mapping.block_mappings]
    assert shapes == [(2,), (w, HIST_CHUNK), terms, acc]
    body = str(call.params["jaxpr"])
    assert "program_id[axis=0]" in body and "program_id[axis=1]" not in body


@pytest.mark.parametrize("case", ["begin_not_tile_aligned",
                                  "across_a_chunk_edge", "whole_array"])
def test_partition_kernel_interpret_40_word_rows(case):
    """`partition_rows` at wp = 40 (34 words of 136 columns + 5 of
    padding + perm: five tiles of rows a chunk, 176 byte planes a tile),
    bit-equal to the XLA formulation as at wp = 8."""
    from lightgbm_tpu.models.partitioned import _partition_segment_rows
    from lightgbm_tpu.ops.ordered_hist import unpack_feature
    from lightgbm_tpu.ops.partition import (apply_partition,
                                            invert_permutation, pack_rows,
                                            split_destinations, unpack_rows)
    rng = np.random.RandomState(9)
    n, f = _N, 136
    bins = rng.randint(0, 63, size=(f, n), dtype=np.uint8)
    words = pack_feature_words(bins)
    words[33] = rng.randint(-2**31, 2**31 - 1, size=n,
                            dtype=np.int64).astype(np.int32)
    words, ghc, perm = (jnp.asarray(words),
                        jnp.asarray(rng.randn(3, n).astype(np.float32)),
                        jnp.asarray(rng.permutation(n).astype(np.int32)))
    seg_b, seg_c = PARTITION_CASES[case][:2]
    feat, thr = 77, 30
    b, c = jnp.int32(seg_b), jnp.int32(seg_c)

    @jax.jit
    def kernel(b, c):
        packed = pack_rows(words, ghc, perm)
        assert packed[0].shape == (40, n)
        rows_i, rows_f, n_left = _partition_segment_rows(
            *packed, b, c, jnp.int32(feat), jnp.int32(thr),
            jnp.asarray(False), unpack_feature, interpret=True)
        return unpack_rows(rows_i, rows_f, words.shape[0]) + (n_left,)

    @jax.jit
    def formulation(b, c):
        go_left = unpack_feature(words, jnp.int32(feat)) <= thr
        dest, n_left = split_destinations(go_left, b, c)
        return apply_partition(invert_permutation(dest), words, ghc,
                               perm) + (n_left,)

    for name, g, w in zip(("words", "ghc", "perm", "n_left"),
                          kernel(b, c), formulation(b, c)):
        np.testing.assert_array_equal(
            np.asarray(g).view(np.int32), np.asarray(w).view(np.int32),
            err_msg=f"{case}: {name}")


# --------------------- the partition kernel at 2 KB rows (PR 33)
@pytest.mark.parametrize("wp,lanes", [(8, 2048), (40, 2048), (136, 2048),
                                      (176, 1024), (504, 512)])
def test_partition_chunk_follows_the_row(wp, lanes):
    """The chunk a DMA of `partition_rows` moves is sized so that the
    two chunks read and the two rings fit VMEM: 2,048 lanes up to 170
    word rows (the cells the benchmark had: 8 and 40), 512 at the 504 of
    2,000 columns."""
    from lightgbm_tpu.ops.partition import chunk_lanes
    assert chunk_lanes(wp) == lanes


@pytest.mark.parametrize("wp,case", [
    (136, "begin_not_tile_aligned"), (136, "whole_array"),
    (504, "begin_not_tile_aligned"), (504, "across_a_chunk_edge"),
    (504, "whole_array"), (504, "one_row")])
def test_partition_kernel_interpret_wide_rows(wp, case):
    """`partition_rows` at wp = 136 and wp = 504 (2,000 columns: 500
    words + 3 of padding + perm; chunks of 512 lanes, 2,032 byte planes
    a tile) against the numpy stable partition: every word, statistic
    and perm entry in its place, exactly."""
    from lightgbm_tpu.ops.partition import chunk_lanes, partition_rows
    rng = np.random.RandomState(wp)
    n = _N
    rows_i = rng.randint(-2**31, 2**31 - 1, size=(wp, n),
                         dtype=np.int64).astype(np.int32)
    rows_f = rng.randn(4, n).astype(np.float32)
    go = rng.rand(n) < 0.37
    seg_b, seg_c = PARTITION_CASES[case][:2]
    assert n % chunk_lanes(wp) == 0
    seg = slice(seg_b, seg_b + seg_c)
    order = np.arange(n)
    order[seg] = np.concatenate([order[seg][go[seg]], order[seg][~go[seg]]])
    got_i, got_f = jax.jit(
        lambda ri, rf, g, b, c, nl: partition_rows(ri, rf, g, b, c, nl,
                                                   interpret=True))(
        jnp.asarray(rows_i), jnp.asarray(rows_f), jnp.asarray(go),
        jnp.int32(seg_b), jnp.int32(seg_c), jnp.int32(go[seg].sum()))
    np.testing.assert_array_equal(np.asarray(got_i), rows_i[:, order])
    np.testing.assert_array_equal(np.asarray(got_f).view(np.int32),
                                  rows_f[:, order].view(np.int32))
