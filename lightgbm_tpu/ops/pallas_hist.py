"""Pallas TPU kernels for masked gradient histograms — the hot op.

Reference semantics: the per-feature accumulation loops in
src/io/dense_bin.hpp:16-195 / ordered_sparse_bin.hpp ConstructHistogram:
for every row in one leaf, hist[feature, bin] += (grad, hess, count).

TPU-first design. The reference (and our first build) materializes the
leaf's rows via a maintained row partition and gathers them; on TPU
random gathers are latency-bound and the XLA one-hot einsum materializes
a (F, C, B) one-hot in HBM. This kernel instead streams the FULL bin
matrix once per histogram and selects the leaf with a mask on the
row->leaf map:

    hist[f, b, k] = sum_c [bins[f, c] == b] * [row_leaf[c] == leaf] * ghc[k, c]

Per grid step (a row chunk C): bins (F, C) at their NATURAL packed
width (uint8 for <= 256 bins, int16 above — the DMA moves 1-2 bytes
per cell, never a widened int32), the stat terms (C, 9) bfloat16 and
row_leaf (1, C) int32 are DMA'd to VMEM (the one-hot never touches
HBM). The one-hot is built as (B_pad, C): broadcasting the
lane-resident bins row along SUBLANES is layout-native on the VPU (the
(C, B) orientation would relayout lanes->sublanes per feature), and
the (B_pad, C) @ (C, 9) dot is the natural MXU form. HBM traffic per
histogram is bins + stat terms + row_leaf, far below the einsum path's
materialized one-hot. Which unit bounds the kernel is not measured on
the chip.

The FRONTIER variant (frontier_histograms_tpu) carries a static vector
of L leaf ids and a leaf-indexed (L, F, B_pad, 3) accumulator: the bin
matrix streams ONCE for all L histograms (the multi-leaf primitive of
docs/Histogram-Engine.md; compare cost grows with L, HBM traffic does
not). VMEM bounds keep L small — the builder uses L = 2 (both children
of a split) and L = 1 (root/bagging re-init).

The stats enter the kernel as three bfloat16 terms each (split_stats),
so the single-pass bfloat16 contraction gives f32-exact products and
f32 accumulation; the count column comes out exactly integral.

Dispatch: masked_histograms/frontier take the Pallas path where
ops/histogram.py use_pallas() says so — on a TPU backend, always; no
option turns the kernels off. Every other backend runs the XLA chunk
formulation of ops/histogram.py chunk_mode().
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows per grid step: the transient one-hot is (B_pad, CHUNK) f32 in
# VMEM (4 MB at 256 x 4096); row padding must be a multiple of this.
HIST_CHUNK = 4096

# VMEM budget for a frontier kernel's (L, F, B_pad, 9) f32 accumulator,
# in TILED bytes (tiled_vmem_bytes); larger frontiers fall back to
# per-leaf kernel calls. 64 MiB is the largest accumulator the v5e
# compiler has been shown to accept (an (8, 8, 2048, .) f32 block, AOT
# compile, PR 21) — half the chip's 128 MiB of VMEM. The 16 MiB scoped
# default does not bound it: the compiler counts the double-buffered
# input blocks against that limit (4.5 MB at F=28), not the resident
# output block.
FRONTIER_VMEM_BYTES = 64 * 1024 * 1024


def tiled_vmem_bytes(shape, dtype):
    """Bytes one buffer of `shape` occupies in VMEM's native tiling:
    the last dim pads to 128 lanes, the second-to-last to the dtype's
    sublane count (8 for 32-bit, 16 for 16-bit, 32 for 8-bit). A
    trailing dim of 3 or 9 therefore costs 128: the (28, 256, 9) f32
    accumulator is 3.7 MB, not 258 KB."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * (4 // itemsize)
    *lead, rows, cols = (1,) * max(2 - len(shape), 0) + tuple(shape)
    return (math.prod(lead) * itemsize * (-(-rows // sublanes) * sublanes)
            * (-(-cols // 128) * 128))


# The MXU multiplies bfloat16. Asked for an f32 x f32 contraction at the
# default precision it rounds BOTH operands to bfloat16 first: on the
# chip that left the count column exact (0/1 x 1.0) and the gradient and
# hessian sums wrong in the fourth digit (9.5e-4 of the largest cell on a
# TPU v5 lite; the CPU interpreter multiplies in f32 and hid it).
# Precision.HIGHEST repairs the numbers but splits both operands into
# three terms inside the kernel — six MXU passes and a 7x longer Mosaic
# compile (82 s instead of 11 s for ONE masked kernel at F=28). The
# one-hot is exact in bfloat16, so only the stats need splitting, and
# that can happen once, outside the kernel: each f32 stat becomes three
# bfloat16 terms (3 x 8 significand bits = f32's 24) riding as nine
# columns of ONE bfloat16 contraction whose products are exact and whose
# accumulation is f32. Same single MXU pass as before, true f32 sums.
STAT_TERMS = 9  # (grad, hess, count) x (hi, mid, lo)


def _bf16_floor(x):
    """f32 `x` with its low 16 bits cleared: a value bfloat16 holds
    exactly. Done on the bits, not with astype — inside a fusion XLA's
    excess-precision rule may elide an f32 -> bf16 -> f32 round trip,
    which silently turns the split below into (bf16(x), 0, 0) (seen on
    the chip: the jitted segment path was 7.5e-4 off while the same
    split dispatched op by op was exact)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def split_stats(ghc, axis=1):
    """(N, 3) f32 stats -> (N, 9) bfloat16 [hi | mid | lo] with
    hi + mid + lo == ghc exactly (finite inputs): each term takes the
    next 8 significand bits, every subtraction is exact in f32, and
    every term is exactly representable in bfloat16. `axis` is the
    stats' axis: 0 takes (3, N) to the lane-major (9, N)."""
    hi = _bf16_floor(ghc)
    rest = ghc - hi
    mid = _bf16_floor(rest)
    lo = rest - mid
    return jnp.concatenate([hi, mid, lo], axis=axis).astype(jnp.bfloat16)


def fold_stats(out):
    """(..., 9) f32 per-term histograms -> (..., 3), small terms first."""
    return (out[..., 6:9] + out[..., 3:6]) + out[..., 0:3]


def onehot_dot(bins_row, b_iota, ghc_m):
    """(B_pad, C) one-hot of a lane-resident bin row @ (C, 9) bfloat16
    stat terms -> (B_pad, 9) f32."""
    onehot = (bins_row == b_iota).astype(jnp.bfloat16)
    return jax.lax.dot_general(
        onehot, ghc_m, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _hist_kernel(leaf_ref, bins_ref, ghc_ref, rl_ref, out_ref, *, f, b_pad):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    c = bins_ref.shape[1]
    # the lane -> sublane relayout of the mask is only lowered for f32
    mask = (rl_ref[0, :] == leaf_ref[0]).astype(jnp.float32)      # (C,) lanes
    ghc_m = jnp.where(mask[:, None] != 0, ghc_ref[...], 0)        # (C, 9)
    b_iota = jax.lax.broadcasted_iota(jnp.int32, (b_pad, c), 0)
    for i in range(f):
        out_ref[i, :, :] += onehot_dot(
            bins_ref[i, :].astype(jnp.int32)[None, :], b_iota, ghc_m)


def _frontier_kernel(leaves_ref, bins_ref, ghc_ref, rl_ref, out_ref,
                     *, l, f, b_pad):
    """Leaf-indexed accumulator: one streamed chunk feeds ALL l leaves'
    histograms. Per chunk: l mask builds + l*f one-hot dots — compare
    cost scales with l, HBM traffic does not."""
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    c = bins_ref.shape[1]
    b_iota = jax.lax.broadcasted_iota(jnp.int32, (b_pad, c), 0)
    for li in range(l):
        mask = (rl_ref[0, :] == leaves_ref[li]).astype(jnp.float32)
        ghc_m = jnp.where(mask[:, None] != 0, ghc_ref[...], 0)    # (C, 9)
        for i in range(f):
            out_ref[li, i, :, :] += onehot_dot(
                bins_ref[i, :].astype(jnp.int32)[None, :], b_iota, ghc_m)


def _bin_pad(num_bins_total):
    return max(((num_bins_total + 127) // 128) * 128, 128)


def masked_histograms_tpu(bins, ghc_t, row_leaf, leaf_id, num_bins_total,
                          interpret=False):
    """hist[f, b, k] over rows with row_leaf == leaf_id (TPU kernel).

    Args:
      bins: (F, N) uint8/int16/int32 bin matrix, N % HIST_CHUNK == 0
        (streamed at its stored width).
      ghc_t: (3, N) float32 stats (grad*inbag, hess*inbag, inbag).
      row_leaf: (N,) int32 row->leaf map.
      leaf_id: int32 scalar (traced ok).
      num_bins_total: static B.

    Returns (F, B, 3) float32.
    """
    f, n = bins.shape
    if n % HIST_CHUNK != 0:
        raise ValueError(f"N={n} must be a multiple of {HIST_CHUNK}")
    b_pad = _bin_pad(num_bins_total)
    grid = (n // HIST_CHUNK,)

    kernel = functools.partial(_hist_kernel, f=f, b_pad=b_pad)
    out = pl.pallas_call(
        kernel,
        name="masked_hist",
        interpret=interpret,  # CPU kernel-semantics tests
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # leaf id (1,)
            pl.BlockSpec((f, HIST_CHUNK), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((HIST_CHUNK, STAT_TERMS), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, HIST_CHUNK), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((f, b_pad, STAT_TERMS), lambda i: (0, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((f, b_pad, STAT_TERMS), jnp.float32),
    )(jnp.asarray([leaf_id], dtype=jnp.int32), bins, split_stats(ghc_t.T),
      row_leaf.reshape(1, n))
    hist = fold_stats(out[:, :num_bins_total, :])
    # plain f32 VMEM accumulation: the compensation slot is zero (the
    # f32-vs-f64 parity guard in tests/test_hist_precision.py bounds the
    # resulting error; TPU f64 emulation would forfeit the MXU)
    return hist, jnp.zeros_like(hist)


def frontier_histograms_tpu(bins, ghc_t, row_leaf, leaf_ids, num_bins_total,
                            interpret=False):
    """Multi-leaf kernel: (L, F, B, 3) over rows of each leaf in
    `leaf_ids` (static length L, distinct ids) in ONE stream of the bin
    matrix. Values are bitwise what L masked_histograms_tpu calls
    produce (independent accumulators, same chunk order). Frontiers
    whose accumulator exceeds FRONTIER_VMEM_BYTES fall back to per-leaf
    kernel calls (still one stream per leaf)."""
    l = leaf_ids.shape[0]
    f, n = bins.shape
    if n % HIST_CHUNK != 0:
        raise ValueError(f"N={n} must be a multiple of {HIST_CHUNK}")
    b_pad = _bin_pad(num_bins_total)
    if l > 1 and tiled_vmem_bytes((l, f, b_pad, STAT_TERMS),
                                  jnp.float32) > FRONTIER_VMEM_BYTES:
        pairs = [masked_histograms_tpu(bins, ghc_t, row_leaf, leaf_ids[i],
                                       num_bins_total, interpret=interpret)
                 for i in range(l)]
        return (jnp.stack([p[0] for p in pairs]),
                jnp.stack([p[1] for p in pairs]))
    grid = (n // HIST_CHUNK,)

    kernel = functools.partial(_frontier_kernel, l=l, f=f, b_pad=b_pad)
    out = pl.pallas_call(
        kernel,
        name="frontier_hist",
        interpret=interpret,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # leaf ids (L,)
            pl.BlockSpec((f, HIST_CHUNK), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((HIST_CHUNK, STAT_TERMS), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, HIST_CHUNK), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((l, f, b_pad, STAT_TERMS),
                               lambda i: (0, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((l, f, b_pad, STAT_TERMS),
                                       jnp.float32),
    )(leaf_ids.astype(jnp.int32), bins, split_stats(ghc_t.T),
      row_leaf.reshape(1, n))
    hist = fold_stats(out[:, :, :num_bins_total, :])
    return hist, jnp.zeros_like(hist)


def masked_histograms_xla(bins, ghc_t, row_leaf, leaf_id, num_bins_total,
                          row_chunk=HIST_CHUNK, mode=None):
    """Reference XLA implementation (CPU tests / non-TPU backends): the
    chunked histogram kernel of ops/histogram.py (scatter-add on the
    CPU, one-hot einsum elsewhere — chunk_mode; `mode` lets a test name
    one) with the leaf mask folded into the stats. Returns a
    compensated (value, residual) pair."""
    from .histogram import build_histograms_pair
    mask = (row_leaf == leaf_id).astype(jnp.float32)
    ghc = (ghc_t * mask[None, :]).T
    return build_histograms_pair(bins, ghc, num_bins_total, row_chunk, mode)


def masked_histograms(bins, ghc_t, row_leaf, leaf_id, num_bins_total,
                      row_chunk=HIST_CHUNK):
    """Backend dispatch, resolved at trace time. Returns (hist, residual):
    collapse with `hist + residual`, or exchange the pair across shards
    in a fixed order first (parallel/mesh.py pair_allreduce /
    pair_reduce_scatter)."""
    from .histogram import use_pallas
    if use_pallas():
        return masked_histograms_tpu(bins, ghc_t, row_leaf, leaf_id,
                                     num_bins_total)
    return masked_histograms_xla(bins, ghc_t, row_leaf, leaf_id,
                                 num_bins_total, row_chunk)
