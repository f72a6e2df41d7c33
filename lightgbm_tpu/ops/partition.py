"""Device stable partition of a leaf-contiguous row layout.

Reference: DataPartition::Split (data_partition.hpp:100-140) — per-
thread left/right buffers merged by prefix sum keep each leaf's row
indices contiguous and in stable order. Two engines do the same here
and give the same bits (`partition_engine`): off the TPU the same
prefix-sum idea without threads — one vectorized pass computes every
row's destination position, and the permutation is applied as a single
scatter + gathers (`split_destinations`, `invert_permutation`,
`apply_partition`); on a TPU one streaming compaction kernel call a
split, in place, with no indexed operation in it (`partition_rows`,
further down).

All rows of the split segment move — including out-of-bag and padding
rows (their statistics are zero, so placement is free of side effects);
the counts used by the tree remain the in-bag histogram counts.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def split_destinations(go_left, begin, cnt):
    """Stable-partition destinations for the segment [begin, begin+cnt).

    Args:
      go_left: (N,) bool in CURRENT position order (only the segment's
        values matter).
      begin, cnt: traced int32 segment bounds.

    Returns (dest, n_left): dest (N,) int32 maps position p -> new
    position (identity outside the segment); n_left is the FULL left
    row count (in-bag + out-of-bag + padding).
    """
    n = go_left.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    in_seg = (pos >= begin) & (pos < begin + cnt)
    lm = in_seg & go_left
    rm = in_seg & ~go_left
    rank_l = jnp.cumsum(lm.astype(jnp.int32)) - 1  # 0-based within lm
    rank_r = jnp.cumsum(rm.astype(jnp.int32)) - 1
    n_left = rank_l[-1] + 1
    dest = jnp.where(
        lm, begin + rank_l,
        jnp.where(rm, begin + n_left + rank_r, pos)).astype(jnp.int32)
    return dest, n_left


def compact_gather_indices(mask, size):
    """Stable compaction of a row mask into gather indices.

    The gather-compacted histogram engine (ops/histogram.py
    compacted_histograms) needs the positions of one leaf's rows as a
    CONTIGUOUS index buffer of static length. This is the same
    prefix-sum rank idea as split_destinations, applied to a boolean
    mask: row p's destination is its rank among selected rows, and the
    scatter drops everything else.

    Args:
      mask: (N,) bool row selector.
      size: static buffer length; the caller guarantees
        sum(mask) <= size (bucketed dispatch, ordered_hist.bucket_sizes).

    Returns (size,) int32 `src` with the selected rows' positions in
    original order, padded with the out-of-range sentinel N (callers
    gather with a clamp and zero the padded rows' statistics).
    """
    n = mask.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    rank = jnp.cumsum(mask.astype(jnp.int32)) - 1
    dest = jnp.where(mask, rank, size)
    return (jnp.full(size, n, dtype=jnp.int32)
            .at[dest].set(pos, mode="drop"))


def invert_permutation(dest):
    """src such that new[q] = old[src[q]] given new[dest[p]] = old[p]."""
    n = dest.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    return jnp.zeros(n, jnp.int32).at[dest].set(pos)


def apply_partition(src, words, ghc_t, perm):
    """Permute the leaf-ordered arrays by the inverse permutation."""
    return (jnp.take(words, src, axis=1),
            jnp.take(ghc_t, src, axis=1),
            jnp.take(perm, src))


# ---------------------------------------------------------------------
# The TPU engine: one streaming compaction kernel a split, in place.
#
# On a TPU the prefix-sum + scatter + gathers above are bound by the
# gather unit (about 50M indices a second whatever the bytes, PERF.md
# section 5). `partition_rows` does the same stable partition with no
# indexed operation: the row tiles covering the segment stream through
# VMEM once, each 128-row tile is stable-partitioned by one one-hot
# permutation matrix on the MXU (the 32-bit words split into bytes, which
# bfloat16 holds exactly, one non-zero a column, f32 accumulation: the
# product moves bits and rounds nothing): left rows to its first lanes,
# right rows after them. A lane roll by the fill of each side's ring
# (`pltpu.roll`, a shift read at run time) then puts each side where its
# ring continues, and whole aligned chunks are DMA'd back. No tile's
# permutation depends on another's fill: the left counts of all tiles of
# a chunk come from one rank matmul, and a ring tile is only stored,
# never read back, so the tiles of a chunk overlap. Left rows overwrite
# the input (their write chunk never passes the read chunk); right rows
# go to an HBM scratch at their final offsets and are copied back
# chunk-aligned once the left stream is done, the boundary chunk merged
# with the left stream's residue.
#
# Mosaic slices an HBM array only in whole (8, 128) / (4, 128) tiles,
# so the kernel's arrays carry whole tiles of rows (`pack_rows`): the
# packed words padded to a multiple of 8 rows with `perm` riding as the
# last one, the three statistics padded to 4. In HBM those are the
# bytes the (7, N) and (3, N) arrays occupy anyway.

def partition_engine():
    """"pallas" where the Pallas TPU kernels are the active engine (the
    histogram's predicate, ops/histogram.py use_pallas, resolved at
    trace time), else "xla": a Mosaic kernel cannot run off the TPU, and
    on it a kernel that fails to compile is an error, not a fall-back."""
    from .histogram import use_pallas
    return "pallas" if use_pallas() else "xla"


def unpermute(perm, values):
    """out[perm[i]] = values[i], for a `perm` that holds every position
    once (`arange` moved only by the partition step): the values sorted
    by such a `perm` ARE the result, one key-value sort on every
    platform, so every test runs the form the chip runs.

    The v5e compiler's scatter is this sort (of indices and updates)
    plus a scatter of the sorted indices; told `unique_indices` its
    optimized HLO differs by that attribute alone: 80.0 ms either way
    at 11.5M rows against the sort's 18.5 (PERF.md section 6, PR 32).
    The keys are unique, so the unstable sort has one answer."""
    return jax.lax.sort((perm, values), num_keys=1, is_stable=False)[1]


PART_CHUNK = 2048   # lanes a DMA moves at most; divides HIST_CHUNK
PART_TILE = 128     # rows one permutation matrix partitions
# how a tile's rows reach the rings (`/trainz` gauge
# partition_rows_tile_form): one permutation, then a lane roll a side
TILE_FORM = "one_perm+roll"
_COPY_DEPTH = 4     # chunk copies of the right stream kept in flight
# VMEM the two chunks read and the two rings (six chunks of `wp` word
# rows in all) may take of a v5e's 16 MB of scoped VMEM, beside the
# byte planes of a tile and what the compiler keeps of them
_CHUNK_VMEM = 8 << 20


def chunk_lanes(wp):
    """Lanes (rows of the data) a DMA of the partition kernel moves: the
    largest power of two that keeps six chunks of `wp` int32 word rows
    inside `_CHUNK_VMEM`, PART_CHUNK at most (up to 170 word rows: every
    row of a few hundred columns) and a tile at least. 512 at the 504
    word rows of 2,000 columns."""
    fit = _CHUNK_VMEM // (6 * 4 * wp)
    return max(min(PART_CHUNK, 1 << (max(fit, 1).bit_length() - 1)),
               PART_TILE)


def packed_word_rows(w):
    """Rows of the kernel's int32 array for `w` word rows: `perm` rides
    as one more, in whole (8, 128) tiles."""
    return -(-(w + 1) // 8) * 8


def pack_rows(words, ghc, perm):
    """(W, N) words, (3, N) stats, (N,) perm -> the kernel's arrays:
    (WP, N) int32 [words; zeros; perm] with WP = packed_word_rows(W),
    and (4, N) float32 [stats; zeros]."""
    w, n = words.shape
    wp = packed_word_rows(w)
    rows_i = jnp.concatenate(
        [words, jnp.zeros((wp - w - 1, n), jnp.int32), perm[None, :]],
        axis=0)
    rows_f = jnp.concatenate([ghc, jnp.zeros((1, n), ghc.dtype)], axis=0)
    return rows_i, rows_f


def unpack_rows(rows_i, rows_f, w):
    """Inverse of pack_rows for `w` word rows."""
    return rows_i[:w], rows_f[:3], rows_i[-1]


def _partition_rows_kernel(sc, ri_in, rf_in, go_hbm, ri, rf, si, sf,
                           ibuf, fbuf, gbuf, li, lf, rgi, rgf, xs, ys,
                           tri, lv, sem_in, sem_fl, sem_cp, *, wp, c):
    """sc = [seg_b, seg_c, n_left]. ri/rf are the arrays, in place
    (ri_in/rf_in alias them and are not touched); si/sf the HBM scratch
    of the right stream; go_hbm the (1, N) 0/1 decision vector; `c` the
    lanes of a chunk (chunk_lanes).

    A tile: its decisions' inclusive ranks and left count `cl` (one
    matmul for the chunk's tiles), one (T, T) one-hot that sends left
    row r to lane r and right row r to lane cl + r, one permutation
    matmul over the byte planes, one re-assembly; then the left side
    rolled by its ring's fill `fl`, the right by `fr - cl`, each merged
    into the ring tile its fill stopped in."""
    del ri_in, rf_in
    t = PART_TILE
    i32, f32, bf16 = jnp.int32, jnp.float32, jnp.bfloat16
    seg_b, seg_c, n_left = sc[0], sc[1], sc[2]
    seg_e = seg_b + seg_c
    c0 = seg_b // c
    c1 = jnp.where(seg_c > 0, (seg_e - 1) // c + 1, c0)
    nch = c1 - c0
    p0 = seg_b + n_left          # where the right stream starts
    kb = p0 // c                 # the chunk both streams share

    def chunk_at(ref, k):
        return ref.at[:, pl.ds(pl.multiple_of(k * c, c), c)]

    def half_of(ring, k):
        return ring.at[:, pl.ds(pl.multiple_of((k % 2) * c, c), c)]

    def loads(k, slot):
        return [pltpu.make_async_copy(chunk_at(src, k), dst.at[slot],
                                      sem_in.at[slot, j])
                for j, (src, dst) in enumerate(
                    [(ri, ibuf), (rf, fbuf), (go_hbm, gbuf)])]

    def flushes(k, stream):
        rings, dsts = ((li, lf), (ri, rf)) if stream == 0 else \
            ((rgi, rgf), (si, sf))
        return [pltpu.make_async_copy(half_of(ring, k), chunk_at(dst, k),
                                      sem_fl.at[stream, j])
                for j, (ring, dst) in enumerate(zip(rings, dsts))]

    # constants: U[s, u] = s <= u gives inclusive ranks in lanes [0, T)
    # and, all ones, a tile's left count on every lane of [T, 2T)
    tri[...] = (jax.lax.broadcasted_iota(i32, (t, 2 * t), 0)
                <= jax.lax.broadcasted_iota(i32, (t, 2 * t), 1)
                ).astype(bf16)
    lv[...] = jnp.zeros(lv.shape, f32)
    s_iota = jax.lax.broadcasted_iota(i32, (t, t), 0)
    lane = jax.lax.broadcasted_iota(i32, (1, t), 1)

    def permute(x, dest):
        """x (R, T) bf16 byte planes; dest (1, T) lane each row goes to
        -> (R, T) int32 with the rows moved, each byte b as the bits of
        the float 2^23 + b: 0x4B000000 | b."""
        onehot = (s_iota == dest).astype(bf16)               # [n, t]
        y = jax.lax.dot_general(x, onehot, (((1,), (1,)), ((), ())),
                                preferred_element_type=f32)
        return jax.lax.bitcast_convert_type(y + 8388608.0, i32)

    def assemble(y):
        """(R, T) int32 byte planes (permute's) -> (wp, T) int32, (4, T)
        float32; a shift by 8 or more drops the 2^23's bits."""
        ys[...] = y
        wi = ys[0:wp] & 0xFF
        gi = ys[4 * wp:4 * wp + 4] & 0xFF
        for b in range(1, 4):
            wi = wi | (ys[b * wp:(b + 1) * wp] << (8 * b))
            gi = gi | (ys[4 * wp + 4 * b:4 * wp + 4 * b + 4] << (8 * b))
        return wi, jax.lax.bitcast_convert_type(gi, f32)

    def ring_tile(fill):
        """Lane offset of the ring tile position `fill` falls in (c and T
        are powers of two)."""
        return pl.multiple_of(fill & (2 * c - t), t)

    def append(ring_i, ring_f, part, wi, gf, fill, cnt):
        """Rows sit in wi/gf at lanes (fill + r) % T, r < cnt: merged into
        `part`, the ring tile `fill` falls in as far as it is filled, and
        stored whole. Returns what the next rows join: the rows that ran
        past the tile's end where they did. No ring tile is read back, so
        no tile waits on the last one's store."""
        off = fill & (t - 1)
        m = (lane >= off) & (lane < off + cnt)
        pi = jnp.where(m, wi, part[0])
        pf = jnp.where(m, gf, part[1])
        ring_i[:, pl.ds(ring_tile(fill), t)] = pi
        ring_f[:, pl.ds(ring_tile(fill), t)] = pf
        over = off + cnt >= t
        return jnp.where(over, wi, pi), jnp.where(over, gf, pf)

    def decisions(k, slot):
        """Each tile's decision (1, T), and for all tiles of the chunk in
        one matmul their inclusive left ranks and left counts, a tile a
        row: lanes [0, T) and [T, 2T) of (rows, 2T) int32."""
        lefts = []
        for j in range(c // t):
            pos = k * c + j * t + lane
            go = gbuf[slot, :, j * t:(j + 1) * t] != 0
            lefts.append((pos < seg_b) | ((pos < seg_e) & go))
            lv[j:j + 1, :] = lefts[j].astype(f32)
        ranks = jnp.dot(lv[...].astype(bf16), tri[...],
                        preferred_element_type=f32).astype(i32)
        return lefts, ranks

    def tile(slot, j, left, rank, fl, fr, parts):
        sl = slice(j * t, (j + 1) * t)
        wv = ibuf[slot, :, sl]
        gv = jax.lax.bitcast_convert_type(fbuf[slot, :, sl], i32)
        ones = left.astype(i32)
        excl = rank[:, :t] - ones               # left rows before this one
        cl = jnp.sum(ones)
        for b in range(4):
            xs[b * wp:(b + 1) * wp] = ((wv >> (8 * b)) & 0xFF).astype(f32)
            xs[4 * wp + 4 * b:4 * wp + 4 * b + 4] = (
                (gv >> (8 * b)) & 0xFF).astype(f32)
        x = xs[...].astype(bf16)
        # the tile's own stable partition: left rows to lanes [0, cl),
        # right rows to [cl, T), each side in its old order; nothing of
        # the rings' fill in it, so no tile's matmul waits on the last
        wi, gf = assemble(permute(x, jnp.where(left, excl,
                                               rank[:, t:] + lane - excl)))
        # each side turned to its ring's phase: left row r to lane
        # (fl + r) % T, right row r (at lane cl + r) to (fr + r) % T
        parts = [append(ring_i, ring_f, part, pltpu.roll(wi, shift, 1),
                        pltpu.roll(gf, shift, 1), fill, cnt)
                 for ring_i, ring_f, part, shift, fill, cnt in (
                     (li, lf, parts[0], fl & (t - 1), fl, cl),
                     (rgi, rgf, parts[1], (fr - cl) & (t - 1), fr, t - cl))]
        return fl + cl, fr + (t - cl), parts

    def wait_flush(stream, pending):
        @pl.when(pending == 1)
        def _():
            for d in flushes(0, stream):
                d.wait()

    def maybe_flush(stream, fill, done):
        """Flush chunk `done` of a stream once its fill has passed it."""
        full = fill >= (done + 1) * c

        @pl.when(full)
        def _():
            for d in flushes(done, stream):
                d.start()
        full = full.astype(i32)
        return done + full, full

    @pl.when(nch > 0)
    def _():
        for d in loads(c0, 0):
            d.start()

    def chunk_body(i, carry):
        fl, fr, done_l, done_r, pend_l, pend_r = carry
        k = c0 + i
        slot = i % 2
        for d in loads(k, slot):
            d.wait()

        @pl.when(i + 1 < nch)
        def _():
            for d in loads(k + 1, 1 - slot):
                d.start()
        # a ring half is written again a chunk after its flush started
        wait_flush(0, pend_l)
        wait_flush(1, pend_r)
        rings = ((li, lf, fl), (rgi, rgf, fr))
        parts = [(ring_i[:, pl.ds(ring_tile(fill), t)],
                  ring_f[:, pl.ds(ring_tile(fill), t)])
                 for ring_i, ring_f, fill in rings]
        lefts, ranks = decisions(k, slot)
        for j in range(c // t):
            fl, fr, parts = tile(slot, j, lefts[j], ranks[j:j + 1], fl, fr,
                                 parts)
        # the tiles the fills stopped in, rows past a tile's end included
        for (ring_i, ring_f, _), fill, (pi, pf) in zip(rings, (fl, fr),
                                                        parts):
            ring_i[:, pl.ds(ring_tile(fill), t)] = pi
            ring_f[:, pl.ds(ring_tile(fill), t)] = pf
        done_l, pend_l = maybe_flush(0, fl, done_l)
        done_r, pend_r = maybe_flush(1, fr, done_r)
        return fl, fr, done_l, done_r, pend_l, pend_r

    zero = jnp.int32(0)
    carry = jax.lax.fori_loop(
        0, nch, chunk_body, (c0 * c, p0, c0, kb, zero, zero))
    wait_flush(0, carry[4])
    wait_flush(1, carry[5])

    # ---- the right stream comes home: chunk kb merged with the left
    # stream's residue (lanes below p0 % c), the rest copied whole
    @pl.when(kb < c1)
    def _():
        back = [pltpu.make_async_copy(chunk_at(src, kb), dst.at[0],
                                      sem_in.at[0, j])
                for j, (src, dst) in enumerate([(si, ibuf), (sf, fbuf)])]
        for d in back:
            d.start()
        for d in back:
            d.wait()
        lane_c = jax.lax.broadcasted_iota(i32, (1, c), 1)
        keep = lane_c < p0 % c
        ibuf[0] = jnp.where(keep, half_of(li, kb)[...], ibuf[0])
        fbuf[0] = jnp.where(keep, half_of(lf, kb)[...], fbuf[0])
        home = [pltpu.make_async_copy(src.at[0], chunk_at(dst, kb),
                                      sem_in.at[0, j])
                for j, (src, dst) in enumerate([(ibuf, ri), (fbuf, rf)])]
        for d in home:
            d.start()
        for d in home:
            d.wait()

    def copies(k):
        return [pltpu.make_async_copy(chunk_at(src, k), chunk_at(dst, k),
                                      sem_cp.at[k % _COPY_DEPTH, j])
                for j, (src, dst) in enumerate([(si, ri), (sf, rf)])]

    def copy_body(k, _):
        @pl.when(k - _COPY_DEPTH > kb)
        def _():
            for d in copies(k - _COPY_DEPTH):
                d.wait()
        for d in copies(k):
            d.start()
        return 0

    jax.lax.fori_loop(kb + 1, c1, copy_body, 0)

    def drain_body(k, _):
        for d in copies(k):
            d.wait()
        return 0

    jax.lax.fori_loop(jnp.maximum(c1 - _COPY_DEPTH, kb + 1), c1,
                      drain_body, 0)


def partition_rows(rows_i, rows_f, go_left, seg_b, seg_c, n_left,
                   interpret=False):
    """Stable two-way partition of positions [seg_b, seg_b + seg_c) of
    the packed arrays (pack_rows), in place: rows whose `go_left` is set
    first, each side in its old order, every other position untouched.

    Args:
      rows_i: (WP, N) int32, rows_f: (4, N) float32, N a multiple of
        PART_CHUNK (of `chunk_lanes(WP)`, which divides it).
      go_left: (N,) the decision of every position (only the segment's
        values matter).
      seg_b, seg_c: traced int32 segment bounds.
      n_left: the number of set decisions inside the segment.

    Returns (rows_i, rows_f): bit for bit what apply_partition(
    invert_permutation(split_destinations(...))) gives. `interpret`
    runs the kernel body in pallas interpret mode (CPU tests).
    """
    wp, n = rows_i.shape
    c, t = chunk_lanes(wp), PART_TILE
    if n % c or wp % 8 or rows_f.shape != (4, n):
        raise ValueError(f"partition_rows: bad shapes {rows_i.shape} "
                         f"{rows_f.shape} (N a multiple of {c})")
    sc = jnp.stack([seg_b, seg_c, n_left]).astype(jnp.int32)
    go = go_left.astype(jnp.int32).reshape(1, n)
    planes = 4 * wp + 16
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_partition_rows_kernel, wp=wp, c=c),
        name="partition_rows",   # the kernel's name in a trace
        interpret=interpret,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[any_spec] * 3, out_specs=[any_spec] * 4,
            scratch_shapes=[
                pltpu.VMEM((2, wp, c), jnp.int32),      # chunks read
                pltpu.VMEM((2, 4, c), jnp.float32),
                pltpu.VMEM((2, 1, c), jnp.int32),
                pltpu.VMEM((wp, 2 * c), jnp.int32),     # left ring
                pltpu.VMEM((4, 2 * c), jnp.float32),
                pltpu.VMEM((wp, 2 * c), jnp.int32),     # right ring
                pltpu.VMEM((4, 2 * c), jnp.float32),
                pltpu.VMEM((planes, t), jnp.float32),   # byte planes in
                pltpu.VMEM((planes, t), jnp.int32),     # ... and out
                pltpu.VMEM((t, 2 * t), jnp.bfloat16),   # rank matrix
                pltpu.VMEM((PART_CHUNK // t, t), jnp.float32),  # decisions
                pltpu.SemaphoreType.DMA((2, 3)),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SemaphoreType.DMA((_COPY_DEPTH, 2)),
            ]),
        out_shape=[jax.ShapeDtypeStruct(rows_i.shape, rows_i.dtype),
                   jax.ShapeDtypeStruct(rows_f.shape, rows_f.dtype)] * 2,
        input_output_aliases={1: 0, 2: 1},
    )(sc, rows_i, rows_f, go)
    return out[0], out[1]
