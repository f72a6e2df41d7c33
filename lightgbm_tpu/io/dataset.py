"""Binned dataset container + loader.

Reference: include/LightGBM/dataset.h:278-421, src/io/dataset.cpp,
include/LightGBM/dataset_loader.h, src/io/dataset_loader.cpp:162-941.

TPU-first design: the training data is stored as ONE dense features-major
integer matrix `bins` of shape (num_stored_rows, num_data) at its natural
PACKED width (bins_dtype: uint8 when every stored row has <= 256 bins,
int16 up to 32768, int32 as the escape) — pushed to device once and
streamed at that width by every histogram kernel, so a per-split scan
moves 1-2 bytes per cell instead of a widened int32's 4. The reference's per-feature Bin objects
(dense/sparse/ordered variants, src/io/dense_bin.hpp / sparse_bin.hpp /
ordered_sparse_bin.hpp) are CPU-cache layouts; on TPU one dense matrix
feeds the MXU directly. Sparse data is handled by CAPACITY, not layout:
exclusive feature bundling (io/bundling.py) packs mutually-exclusive
sparse features into shared slots so stored rows ~ slots << features,
and every ingestion path stays O(nnz) on the way there — CSC/CSR column
sources bin one column at a time, LibSVM files stream as triplet blocks
(_stream_sparse_libsvm), and EFB planning reads one sample column at a
time. A wide sparse load that would still materialize a dense F x N
matrix (nothing bundles) hits a loud budget guard (check_bins_budget)
instead of silently OOMing.

The binary dataset cache (reference dataset.cpp:133-212 with a magic
token) is an .npz with the same role: skip text parsing + binning on
reload; auto-detected next to the data file.
"""

import functools
import os

import numpy as np

from ..telemetry.ledger import LEDGER
from ..telemetry.trace import PROCESS_TRACER
from ..utils.log import Log
from ..utils.random import Random
from .bin_mapper import BinMapper, NUMERICAL, CATEGORICAL
from .metadata import Metadata
from .parser import parse_text_file, ZERO_THRESHOLD

BINARY_MAGIC = "lightgbm_tpu_dataset_v1"
# v2: bins persist at their natural PACKED width (uint8 <= 256 bins,
# int16 above — the histogram engine's streaming contract, see
# bins_dtype). v1 caches (uint8/uint16) still load, with uint16
# narrowed to int16 on the way in; anything wider (a stale f32/int32
# matrix from a foreign or pre-packing build) is rejected cleanly.
BINARY_FORMAT_VERSION = 2
_ZIP_MAGIC = b"PK\x03\x04"  # npz container prefix


def bins_dtype(num_bins):
    """Natural storage width of a bin matrix — the packed-bin contract
    every loader path and the histogram kernels share: uint8 when every
    stored row has <= 256 bins, int16 up to 32768 (TPU-native narrow
    int; bin ids are non-negative so the sign bit is free), int32
    beyond (unreachable under the reference's max_bin ceiling, kept as
    a correctness escape)."""
    if num_bins <= 256:
        return np.uint8
    if num_bins <= 32768:
        return np.int16
    return np.int32


_BINS_CACHE_DTYPES = ("uint8", "uint16", "int16", "int32")


class BinaryDatasetError(Exception):
    """A binary dataset file failed validation. `claimed` is True when
    the file LOOKS like a binary dataset (npz container) but is
    truncated/corrupt/foreign — as opposed to a text file that was
    never binary at all — so callers can fall past a rotten cache with
    a warning (mirroring the checkpoint loader's behavior) while
    staying silent for ordinary text data files."""

    def __init__(self, message, claimed=False):
        super().__init__(message)
        self.claimed = claimed


def _qid_to_counts(qid_col):
    """Row-order run-length encoding of a per-row query-id column into
    per-query counts (Metadata::LoadQueryBoundaries semantics,
    metadata.cpp:358-371)."""
    qid = np.asarray(qid_col).astype(np.int64)
    if len(qid) == 0:
        return np.zeros(0, dtype=np.int64)
    change = np.nonzero(np.diff(qid))[0] + 1
    edges = np.concatenate([[0], change, [len(qid)]])
    return np.diff(edges)


class _VirtualBinsView:
    """Fancy-indexable [feat_arr, row_arr] view over a bundled stored
    matrix (host traversal path; see io/bundling.py for the encoding)."""

    def __init__(self, stored, plan, num_bin_pf):
        self._stored = stored
        self._plan = plan
        self._nb = np.asarray(num_bin_pf)
        self.shape = (len(plan.feat_slot), stored.shape[1])

    def __getitem__(self, key):
        feat, rows = key
        feat = np.asarray(feat)
        sc = self._stored[self._plan.feat_slot[feat], rows].astype(np.int64)
        off = self._plan.feat_offset[feat]
        nb = self._nb[feat]
        return np.where((sc > off) & (sc <= off + nb - 1), sc - off, 0)


AUTO_STREAM_MIN_FEATS = 1024


def _libsvm_looks_wide(filename, has_header):
    """Cheap probe: is this a LibSVM file whose feature ids reach past
    AUTO_STREAM_MIN_FEATS within the first 1000 data lines? Wide sparse
    files auto-route to the O(nnz) streaming loader; narrow ones keep
    the (also-correct) in-memory path."""
    from .parser import detect_format, libsvm_pairs
    try:
        if detect_format(filename) != "libsvm":
            return False
        with open(filename, "r") as f:
            if has_header:
                next(f, None)
            for _, line in zip(range(1000), f):
                parts = line.split()
                if len(parts) < 2:
                    continue
                for idx, _ in libsvm_pairs(parts[1:]):
                    if idx + 1 > AUTO_STREAM_MIN_FEATS:
                        return True
    except Exception:   # unreadable / binary / undecodable: not libsvm
        return False
    return False


def check_bins_budget(rows, cols, itemsize, what):
    """Loud guard before allocating a stored bin matrix: a wide sparse
    dataset that failed to bundle would silently materialize the dense
    F x N block the reference's SparseBin exists to avoid
    (src/io/sparse_bin.hpp:17-331). Budget in GB via
    LIGHTGBM_TPU_MAX_BINS_GB (default 16; <= 0 disables)."""
    budget_gb = float(os.environ.get("LIGHTGBM_TPU_MAX_BINS_GB", "16"))
    if budget_gb <= 0:
        return
    need = rows * cols * itemsize / (1 << 30)
    if need > budget_gb:
        Log.fatal(
            "%s needs a %d x %d bin matrix (%.1f GB > budget %.0f GB). "
            "For wide sparse data enable bundling (is_enable_sparse=true"
            ") so exclusive features share slots; raise/disable the "
            "budget with LIGHTGBM_TPU_MAX_BINS_GB if the dense matrix "
            "is intended.", what, rows, cols, need, budget_gb)


def _bin_dense_on_device(mat, real_idx, mappers, dtype):
    """Full-matrix binning on the accelerator: bin k = #(bounds < v)
    == np.searchsorted(bounds, v, 'left') for every numerical mapper.
    The device compare-sum is O(N*F*B) VPU compares plus the
    raw-matrix transfer — the reference bins on CPU because it IS a
    CPU framework (bin.cpp FindBin/value_to_bin); an accelerator-first
    loader puts the scan where the FLOPs are.

    f32-exactness: bounds are f64 (sample midpoints); the f32 cast is
    rounded toward -inf so `v > bound32` equals the f64 `v > bound`
    for every f32 input v (same boundary rule as the device-predict
    thresholds, models/gbdt.py _device_model).

    Categorical columns bin by equality in a program of their own (the
    matrix is uploaded once): trunc(v) against each column's kept ids,
    BinMapper.value_to_bin's rule, under the span
    `dataset/bin_categorical`. A matrix without them traces the
    numerical program alone, with no column selection in it.

    Gated by LIGHTGBM_TPU_DEVICE_BIN (default auto = non-CPU
    backends). Returns (F, N) bins, or None when the
    inputs are ineligible or the gate is off (the caller then bins on
    the host). Those are decisions; a failure of the device pass itself
    propagates — a host result in its place would hide a broken
    device path.

    Spans `dataset/host_prep`, `upload`, `bin_device`, `download`,
    `pack` on the process tracer tell the phases apart (`upload` and
    `download` tagged with their `bytes`); the `block_until_ready`
    between upload and compute only makes visible an order that was
    serial already. The compile ledger's label `dataset_bin` inside
    `bin_device` marks the program's compile-or-load a hit or a miss
    of the persistent cache."""
    mode = os.environ.get("LIGHTGBM_TPU_DEVICE_BIN", "auto")
    if mode == "0":
        return None
    import jax
    import jax.numpy as jnp
    if mode == "auto" and jax.default_backend() == "cpu":
        return None
    if mat.dtype != np.float32:
        # the -inf-rounded f32 bounds make the compare exact for
        # f32 INPUTS only; f64 matrices (text loads keep f64 so
        # boundaries survive the last digit, parser.py) must bin
        # through the host f64 searchsorted
        return None
    n = mat.shape[0]
    f = len(real_idx)
    cat = [u for u, m in enumerate(mappers) if m.bin_type == CATEGORICAL]
    num = [u for u, m in enumerate(mappers) if m.bin_type != CATEGORICAL]
    span = functools.partial(PROCESS_TRACER.span, rows=n, features=f)
    with span("host_prep"):
        b32 = _device_bounds(mappers, num)
        chunk = 1 << 16
        n_pad = -(-n // chunk) * chunk
        all_cols = (f == mat.shape[1]
                    and np.array_equal(real_idx, np.arange(f)))
        if n_pad == n and all_cols and mat.flags.c_contiguous:
            x_used = mat            # zero-copy fast path
        else:
            # ONE full-size buffer: pad rows + column-select in place
            x_used = np.zeros((n_pad, f), np.float32)
            x_used[:n] = mat if all_cols else mat[:, real_idx]
        # host rule bins NaN like the value 0.0 (bin.h NaN->zero-bin);
        # on device NaN compares false everywhere -> raw bin 0, which
        # differs when a column has negative bounds
        if np.isnan(x_used).any():
            x_used = np.nan_to_num(x_used, nan=0.0)
        ids = _categorical_ids(mappers, cat) if cat else None
    with span("upload", bytes=int(x_used.nbytes + b32.nbytes)):
        xdev = jnp.asarray(x_used).reshape(n_pad // chunk, chunk, f)
        bdev = jnp.asarray(b32)
        jax.block_until_ready((xdev, bdev))
    out_dt = jnp.dtype(dtype)
    # the numerical columns of a matrix that has categorical ones too
    num_cols = np.asarray(num) if cat else None

    @jax.jit
    def bin_all(xc):
        def one(xb):   # (chunk, F) -> (chunk, F) narrow ints
            if num_cols is not None:
                xb = xb[:, num_cols]
            return jnp.sum(xb[:, :, None] > bdev[None, :, :],
                           axis=-1, dtype=jnp.int32).astype(out_dt)
        return jax.lax.map(one, xc)

    @jax.jit
    def bin_categorical(xc, cols, idv):
        # a value truncated toward zero is bin k where it equals kept id
        # k, bin 0 where it equals none (unseen, past the kept max_bin,
        # NaN): BinMapper.value_to_bin's rule; the columns and the ids
        # are arguments, so the program depends on shapes alone
        k = jnp.arange(idv.shape[1], dtype=jnp.int32)

        def one(xb):   # (chunk, F) -> (chunk, categorical F) narrow ints
            t = jnp.trunc(jnp.take(xb, cols, axis=1))
            return jnp.sum(jnp.where(t[:, :, None] == idv[None], k, 0),
                           axis=-1, dtype=jnp.int32).astype(out_dt)
        return jax.lax.map(one, xc)

    # no learner has set the compile cache up yet: the ledger listens
    # from here, so that the label sees a hit
    LEDGER.install()
    parts = []        # (the mappers' positions, their (C, chunk, k) bins)
    if num:
        with span("bin_device"), LEDGER.label("dataset_bin"):
            # first call: compile or load, then run
            parts.append((num, jax.block_until_ready(bin_all(xdev))))
    if cat:
        # a program of its own, so that its seconds stand apart
        with _categorical_span(n, real_idx, mappers, cat), \
                LEDGER.label("dataset_bin_categorical"):
            parts.append((cat, jax.block_until_ready(bin_categorical(
                xdev, jnp.asarray(cat, jnp.int32), jnp.asarray(ids)))))
    with span("download", bytes=int(sum(p.nbytes for _, p in parts))):
        # narrow on device: the download is N x F bytes, not 4x that
        outs = [(cols, np.asarray(p).reshape(n_pad, -1)[:n])
                for cols, p in parts]
    with span("pack"):
        if not cat:
            return np.ascontiguousarray(outs[0][1].T).astype(dtype,
                                                            copy=False)
        bins = np.empty((f, n), dtype)
        for cols, out in outs:
            bins[cols] = out.T
        return bins


def _device_bounds(mappers, num):
    """(len(num), most bounds) float32 upper bounds of the numerical
    mappers at positions `num`, each the largest float32 not above its
    float64 bound (so `v > bound32` is the float64 `v > bound` for every
    float32 v); +inf pads a row and counts nothing."""
    b_max = max((len(mappers[u].bin_upper_bound) for u in num), default=1)
    bounds = np.full((len(num), b_max), np.inf)
    for i, u in enumerate(num):
        bounds[i, :len(mappers[u].bin_upper_bound)] = (
            mappers[u].bin_upper_bound)
    b32 = bounds.astype(np.float32)
    lifted = b32.astype(np.float64) > bounds
    return np.where(lifted, np.nextafter(b32, np.float32(-np.inf),
                                         dtype=np.float32), b32)


class RowShards:
    """The bins of a dataset as a data-parallel mesh holds them: `array`
    is (F, W * shard_rows) on `devices`, sharded by row, device i
    holding rows [i * shard_rows, (i + 1) * shard_rows) of the dataset
    with zeros past row num_rows - 1 (parallel/learners.py
    row_shard_layout: the learner's own padding). `counts` are the
    per-mapper bin occupancies of the real rows and `missing` their NaN
    counts a used feature (io/profile.py)."""

    def __init__(self, array, devices, shard_rows, num_rows, dtype, counts,
                 missing):
        self.array = array
        self.devices = list(devices)
        self.shard_rows = int(shard_rows)
        self.num_rows = int(num_rows)
        self.dtype = np.dtype(dtype)
        self.counts = counts
        self.missing = missing

    def to_host(self):
        """(F, num_rows) host bins: the one download, for a reader off
        the mesh (host traversal, subset, save_binary)."""
        return np.asarray(self.array)[:, :self.num_rows].astype(
            self.dtype, copy=False)


def _bin_dense_on_shards(mat, real_idx, mappers, dtype, devices, shard_rows):
    """`_bin_dense_on_device` for a data-parallel mesh: device i takes
    rows [i * shard_rows, (i + 1) * shard_rows) of the matrix (zeros past
    the last row), already cut into the program's chunks on the host, and
    bins them where they land; the bins stay there, placed as the
    learner's row sharding expects (RowShards). No device holds another
    device's rows and nothing is downloaded. Same rules as the one-chip
    pass (numerical bounds rounded toward -inf, a categorical column by
    equality with its kept ids, NaN as 0.0), in ONE program over the mesh
    that also counts each bin's real rows, and each column's NaN, for the
    dataset profile (so the host reads the matrix for neither).

    Spans: `dataset/bin_shard` a device (tagged `device`, `rows` it holds,
    `bytes` uploaded), then `dataset/bin_device` (compile-ledger label
    `dataset_bin_shards`). Returns None where `_bin_dense_on_device`
    would (gate off, not float32)."""
    mode = os.environ.get("LIGHTGBM_TPU_DEVICE_BIN", "auto")
    if mode == "0":
        return None
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from ..parallel.mesh import AXIS
    if mode == "auto" and jax.default_backend() == "cpu":
        return None
    if mat.dtype != np.float32:
        return None
    n, f, w = mat.shape[0], len(real_idx), len(devices)
    cat = [u for u, m in enumerate(mappers) if m.bin_type == CATEGORICAL]
    num = [u for u, m in enumerate(mappers) if m.bin_type != CATEGORICAL]
    nb = max(m.num_bin for m in mappers)
    # rows a step of the program bins: a power of two dividing a shard
    chunk = min(1 << 16, shard_rows & -shard_rows)
    all_cols = f == mat.shape[1] and np.array_equal(real_idx, np.arange(f))
    b32 = _device_bounds(mappers, num)
    ids = (_categorical_ids(mappers, cat) if cat
           else np.zeros((1, 1), np.float32))
    pieces = []
    for i, dev in enumerate(devices):
        lo, hi = min(i * shard_rows, n), min((i + 1) * shard_rows, n)
        nbytes = shard_rows * f * 4
        with PROCESS_TRACER.span("bin_shard", device=int(dev.id),
                                 rows=hi - lo, bytes=nbytes):
            if hi - lo == shard_rows and all_cols and mat.flags.c_contiguous:
                block = mat[lo:hi]           # a view: no host copy
            else:
                block = np.zeros((shard_rows, f), np.float32)
                block[:hi - lo] = mat[lo:hi] if all_cols else \
                    mat[lo:hi][:, real_idx]
            pieces.append(jax.block_until_ready(jax.device_put(
                block.reshape(shard_rows // chunk, chunk, f), dev)))
    mesh = Mesh(np.asarray(devices), (AXIS,))
    x = jax.make_array_from_single_device_arrays(
        (w * shard_rows // chunk, chunk, f), NamedSharding(mesh, P(AXIS)),
        pieces)
    held = np.asarray([min(max(n - i * shard_rows, 0), shard_rows)
                       for i in range(w)], np.int32)
    program = _shard_bin_program(mesh, chunk, nb, num, cat, dtype)
    LEDGER.install()
    with PROCESS_TRACER.span("bin_device", rows=n, features=f, shards=w), \
            LEDGER.label("dataset_bin_shards"):
        bins, counts, missing = jax.block_until_ready(program(
            x, jax.device_put(held, NamedSharding(mesh, P(AXIS))),
            jnp.asarray(b32), jnp.asarray(ids)))
    del x, pieces
    total = np.asarray(counts).sum(axis=0)
    return RowShards(bins, devices, shard_rows, n, dtype,
                     [total[u, :mappers[u].num_bin].astype(np.int64)
                      for u in range(f)],
                     np.asarray(missing).sum(axis=0).astype(np.int64))


def _shard_bin_program(mesh, chunk, nb, num, cat, dtype):
    """The jitted program `_bin_dense_on_shards` runs over `mesh`:
    (x (W * C, chunk, F) float32 sharded by its first axis, held (W,)
    int32 real rows a device, bounds, ids) -> ((F, W * C * chunk) bins
    sharded by row, (W, F, nb) int32 bin counts of the real rows, (W, F)
    int32 NaN counts of the real rows)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from ..parallel.mesh import AXIS, shard_map
    f = len(num) + len(cat)
    out_dt = jnp.dtype(dtype)
    num_cols = np.asarray(num) if cat else None

    def local(xc, held, bdev, idv):
        # xc: this device's (C, chunk, F) rows; held: (1,) its real rows
        def step(carry, xs):
            counts, missing = carry
            c, xb = xs
            real = (c * chunk + jnp.arange(chunk)) < held[0]
            nan = jnp.isnan(xb)
            missing = missing + jnp.sum(nan & real[:, None], axis=0,
                                        dtype=jnp.int32)
            xb = jnp.where(nan, 0.0, xb)
            xn = xb if num_cols is None else xb[:, num_cols]
            out = jnp.sum(xn[:, :, None] > bdev[None], axis=-1,
                          dtype=jnp.int32)
            if cat:
                t = jnp.trunc(xb[:, jnp.asarray(cat)])
                k = jnp.arange(idv.shape[1], dtype=jnp.int32)
                cb = jnp.sum(jnp.where(t[:, :, None] == idv[None], k, 0),
                             axis=-1, dtype=jnp.int32)
                out = (jnp.zeros((chunk, f), jnp.int32)
                       .at[:, jnp.asarray(num)].set(out)
                       .at[:, jnp.asarray(cat)].set(cb))
            out = jnp.where(real[:, None], out, 0)   # padding: bin 0
            counts = counts + jnp.sum(
                (out[:, :, None] == jnp.arange(nb, dtype=jnp.int32))
                & real[:, None, None], axis=0, dtype=jnp.int32)
            return (counts, missing), out.T.astype(out_dt)
        steps = jnp.arange(xc.shape[0], dtype=jnp.int32)
        (counts, missing), outs = jax.lax.scan(
            step, (jnp.zeros((f, nb), jnp.int32), jnp.zeros(f, jnp.int32)),
            (steps, xc))
        # (C, F, chunk) -> (F, C * chunk): the device's rows in order
        return (outs.transpose(1, 0, 2).reshape(f, -1), counts[None],
                missing[None])

    return jax.jit(shard_map(
        local, mesh=mesh, in_specs=(P(AXIS), P(AXIS), P(), P()),
        out_specs=(P(None, AXIS), P(AXIS), P(AXIS))))


def _categorical_span(n, real_idx, mappers, cat):
    """The process span `dataset/bin_categorical` around the binning of
    the categorical columns (mappers' positions `cat`), on the device or
    on the host: tagged with the `rows`, the input's `columns` (their
    indices) and the kept ids of them all (`categories`)."""
    return PROCESS_TRACER.span(
        "bin_categorical", rows=n,
        columns=[int(real_idx[u]) for u in cat],
        categories=int(sum(mappers[u].num_bin for u in cat)))


def _categorical_ids(mappers, cat):
    """(len(cat), most kept) float32: row i holds the kept ids of
    categorical mapper cat[i], id k that of bin k. NaN pads a row, and
    stands in for an id no float32 value truncates to (past 2**24), so
    that neither ever matches, as on the host."""
    k_max = max(len(mappers[u].bin_2_categorical) for u in cat)
    ids = np.full((len(cat), k_max), np.nan, np.float32)
    for i, u in enumerate(cat):
        kept = mappers[u].bin_2_categorical
        exact = kept.astype(np.float32).astype(np.int64) == kept
        ids[i, :len(kept)] = np.where(exact, kept.astype(np.float32), np.nan)
    return ids


def _bin_on_host(src, real_idx, mappers, dtype):
    """(F, N) bins of a column source by the mappers' own value_to_bin,
    the columns on threads; the categorical ones after the rest, under
    the span `dataset/bin_categorical`."""
    bins = np.empty((len(real_idx), src.n), dtype)
    cat = [u for u, m in enumerate(mappers) if m.bin_type == CATEGORICAL]
    num = [u for u, m in enumerate(mappers) if m.bin_type != CATEGORICAL]

    def one(u):
        bins[u] = mappers[u].value_to_bin(src.col(real_idx[u]))
    _bin_columns_threaded(lambda i: one(num[i]), len(num))
    if cat:
        with _categorical_span(src.n, real_idx, mappers, cat):
            _bin_columns_threaded(lambda i: one(cat[i]), len(cat))
    return bins


def _bin_columns_threaded(col_fn, count):
    """Map col_fn over column indices with a thread pool: value_to_bin
    is searchsorted-dominated and releases the GIL, so the reference's
    OpenMP-parallel ExtractFeatures (dataset_loader.cpp:762-841) maps to
    plain threads here (~6x on the 11M x 28 HIGGS load)."""
    from concurrent.futures import ThreadPoolExecutor
    workers = min(8, os.cpu_count() or 1, max(count, 1))
    if workers <= 1 or count <= 1:
        return [col_fn(j) for j in range(count)]
    with ThreadPoolExecutor(max_workers=workers) as ex:
        return list(ex.map(col_fn, range(count)))


# values of the bin sample held as one (F, sample) matrix; a wider
# input samples a column at a time
_SAMPLE_MATRIX_VALUES = 1 << 28


def is_column_source(obj):
    """True for objects implementing the column-source protocol
    (DenseColumns / CscColumns). A bare hasattr(obj, "col") is NOT
    enough: scipy.sparse COO matrices carry a `.col` ndarray."""
    return callable(getattr(obj, "col", None)) and hasattr(obj, "num_total")


class DenseColumns:
    """Column source over a dense (N, F) matrix (see _construct)."""

    def __init__(self, mat):
        self._m = mat
        self.n, self.num_total = mat.shape

    def col(self, j):
        return self._m[:, j]

    def sample(self, idx):
        """(F, len(idx)) rows `idx` of the matrix, a column contiguous: a
        block of whole rows gathered and transposed at a time, where a
        column of the row-major matrix taken at `idx` is one cache miss
        a value (thousands of columns: seconds a hundred of them)."""
        out = np.empty((self.num_total, len(idx)), self._m.dtype)
        for lo in range(0, len(idx), 1024):
            out[:, lo:lo + 1024] = self._m[idx[lo:lo + 1024]].T
        return out


class CscColumns:
    """Column source over CSC triplets: each column materializes as ONE
    dense (N,) f32 vector at a time, so a sparse FFI input is binned in
    O(nnz + N) peak memory instead of the O(N * F) dense raw matrix —
    the TPU-side analog of the reference's row-iterator dataset
    construction (c_api.cpp:317-427)."""

    def __init__(self, colptr, indices, vals, num_row, num_col):
        self._p = np.asarray(colptr, dtype=np.int64)
        self._i = np.asarray(indices, dtype=np.int64)
        self._v = np.nan_to_num(np.asarray(vals, dtype=np.float32), nan=0.0)
        self.n = int(num_row)
        self.num_total = int(num_col)

    def col(self, j):
        out = np.zeros(self.n, dtype=np.float32)
        sl = slice(self._p[j], self._p[j + 1])
        out[self._i[sl]] = self._v[sl]
        return out

    @classmethod
    def from_csr(cls, indptr, indices, vals, num_col):
        """O(nnz log nnz) CSR -> CSC transpose (stable by row within a
        column); never builds the dense matrix."""
        indptr = np.asarray(indptr, dtype=np.int64)
        indices = np.asarray(indices, dtype=np.int64)
        vals = np.asarray(vals)
        nrow = len(indptr) - 1
        row_of = np.repeat(np.arange(nrow, dtype=np.int64),
                           np.diff(indptr))
        order = np.argsort(indices, kind="stable")
        counts = np.bincount(indices, minlength=num_col)
        colptr = np.concatenate([[0], np.cumsum(counts)])
        return cls(colptr, row_of[order], vals[order], nrow, num_col)


def encode_dataset_sidecar(ds, arrays=None):
    """npz encoding of a CoreDataset MINUS its bin matrix: feature
    maps, names, bin mappers, bundle plan, metadata. ONE encoder for
    the two binary forms — the binary cache (save_binary, bins member
    added by the caller) and the block-store sidecar
    (data/block_store.py) — so their on-disk dictionaries cannot
    drift apart."""
    arrays = {} if arrays is None else arrays
    arrays.update({
        "used_feature_map": ds.used_feature_map,
        "real_feature_idx": ds.real_feature_idx,
        "num_total_features": np.asarray(ds.num_total_features),
        "label_idx": np.asarray(ds.label_idx),
        "feature_names": np.asarray(ds.feature_names, dtype=object),
    })
    for i, m in enumerate(ds.bin_mappers):
        for k, v in m.to_dict().items():
            arrays[f"mapper{i}_{k}"] = np.asarray(v)
    if ds.bundle_plan is not None:
        for k, v in ds.bundle_plan.to_dict().items():
            arrays[f"bundle_{k}"] = np.asarray(v)
    for k, v in ds.metadata.to_dict().items():
        arrays[f"meta_{k}"] = np.asarray(v)
    if getattr(ds, "profile", None) is not None:
        # the baseline distribution rides both binary forms (counts +
        # missing only — the mappers above already carry the bounds)
        ds.profile.encode_sidecar(arrays)
    return arrays


def decode_dataset_sidecar(ds, z, truncated):
    """Inverse of encode_dataset_sidecar: populate `ds` (everything but
    bins) from npz archive `z`. `truncated(msg)` builds the exception
    to raise on a structurally incomplete archive — each binary form
    keeps its own error type."""
    ds.used_feature_map = z["used_feature_map"]
    ds.real_feature_idx = z["real_feature_idx"]
    ds.num_total_features = int(z["num_total_features"])
    ds.label_idx = int(z["label_idx"])
    ds.feature_names = [str(x) for x in z["feature_names"]]
    n_used = len(ds.real_feature_idx)
    mappers = []
    for i in range(n_used):
        d = {k[len(f"mapper{i}_"):]: z[k] for k in z.files
             if k.startswith(f"mapper{i}_")}
        if "num_bin" not in d:
            raise truncated(f"missing bin mapper {i} of {n_used}")
        mappers.append(BinMapper.from_dict(d))
    ds.bin_mappers = mappers
    bundle = {k[7:]: z[k] for k in z.files if k.startswith("bundle_")}
    if bundle:
        from .bundling import BundlePlan
        ds.bundle_plan = BundlePlan.from_dict(bundle)
    meta = {k[5:]: z[k] for k in z.files if k.startswith("meta_")}
    ds.metadata = Metadata.from_dict(meta)
    from .profile import DatasetProfile
    ds.profile = DatasetProfile.decode_sidecar(z, ds)  # None pre-profile
    return ds


class CoreDataset:
    """Eagerly-binned dataset (the reference's `Dataset`, dataset.h:278-421)."""

    def __init__(self):
        self._bins = None             # (F_used, N) packed (bins_dtype), host
        # RowShards: the bins on the devices of a data-parallel mesh,
        # a row block a device, where `bins` is read from if asked
        self.row_shards = None
        self.bin_mappers = []         # per used feature
        self.used_feature_map = None  # (num_total_features,) int32: total->used or -1
        self.real_feature_idx = None  # (F_used,) int32: used -> total
        self.feature_names = []       # one per total feature
        self.num_total_features = 0
        self.label_idx = 0
        self.metadata = Metadata()
        self._device_bins = None
        self._bin_value_cache = None
        self.raw_data = None          # optional (N, C) float32 original values
        self.global_num_data = None   # set by per-rank loading (multi-host)
        self.bundle_plan = None       # io/bundling.py BundlePlan or None
        self.binned_on_device = False  # _bin_dense_on_device produced bins
        # training-time baseline distribution (io/profile.py
        # DatasetProfile): per-feature bin occupancy + missing counts,
        # captured once at binning and persisted through the binary
        # cache / block-store sidecar / model-file sidecar
        self.profile = None

    # ------------------------------------------------------------ properties
    @property
    def bins(self):
        """(F_used, N) host bins. A dataset binned row block by row block
        on a mesh's devices (`row_shards`) downloads them here on first
        read; training on that mesh never reads them."""
        if self._bins is None and self.row_shards is not None:
            self._bins = self.row_shards.to_host()
        return self._bins

    @bins.setter
    def bins(self, value):
        self._bins = value

    @property
    def num_data(self):
        if self.row_shards is not None:
            return self.row_shards.num_rows
        return 0 if self.bins is None else self.bins.shape[1]

    @property
    def num_features(self):
        return len(self.bin_mappers)

    @property
    def max_num_bin(self):
        return max((m.num_bin for m in self.bin_mappers), default=1)

    @property
    def max_stored_bin(self):
        """Histogram width of the STORED matrix (bundle slots can pack
        several features' bin ranges into one row)."""
        if self.bundle_plan is None:
            return self.max_num_bin
        return int(self.bundle_plan.slot_bins.max())

    def traversal_bins(self):
        """Bins indexable as [feature_array, row_array] in VIRTUAL feature
        space for host tree traversal; decodes bundle slots on the fly."""
        if self.bundle_plan is None:
            return self.bins
        return _VirtualBinsView(self.bins, self.bundle_plan,
                                self.num_bin_array())

    def num_bin_array(self):
        return np.asarray([m.num_bin for m in self.bin_mappers], dtype=np.int32)

    def bin_value_table(self):
        """(F, max_num_bin) float64 bin representative values
        (Feature::BinToValue) in VIRTUAL feature space — what linear
        leaves dot against when scoring in bin space (models/
        linear_leaves.py, Tree.predict_by_bins). Cached; aligned
        train/valid sets share bin mappers so their tables match."""
        if getattr(self, "_bin_value_cache", None) is None:
            table = np.zeros((self.num_features, self.max_num_bin),
                             dtype=np.float64)
            for i, m in enumerate(self.bin_mappers):
                vals = (m.bin_upper_bound if m.bin_type != CATEGORICAL
                        else m.bin_2_categorical.astype(np.float64))
                vals = np.asarray(vals, np.float64).copy()
                # the last numeric bin's upper bound is +inf (and a
                # degenerate first bound can be -inf): clamp each
                # non-finite bound to its nearest finite neighbor so
                # the linear-leaf dot products stay finite. Bounds are
                # monotone, so this is the previous (resp. next)
                # representative.
                bad = ~np.isfinite(vals)
                if bad.any():
                    good = np.nonzero(~bad)[0]
                    if len(good) == 0:
                        vals[:] = 0.0
                    else:
                        idx = np.clip(
                            np.searchsorted(good, np.nonzero(bad)[0]),
                            1, len(good)) - 1
                        vals[bad] = vals[good[idx]]
                table[i, :len(vals)] = vals
            self._bin_value_cache = table
        return self._bin_value_cache

    @property
    def stored_bins_dtype(self):
        """dtype of the stored bin matrix — resolvable without a
        resident matrix (the out-of-core dataset forwards its block
        store's dtype), so valid sets can align against either form."""
        if self._bins is None and self.row_shards is not None:
            return self.row_shards.dtype
        return self.bins.dtype

    def feature_is_categorical(self):
        return np.asarray([m.bin_type == CATEGORICAL for m in self.bin_mappers])

    def device_bins(self):
        """The (F, N) bin matrix on the default device (cached)."""
        import jax.numpy as jnp
        if self._device_bins is None:
            self._device_bins = (
                jnp.asarray(self.bins) if self.row_shards is None
                else self.row_shards.array[:, :self.num_data])
        return self._device_bins

    # ------------------------------------------------------------- alignment
    def check_align(self, other: "CoreDataset") -> bool:
        """Bin-mapper compatibility between train/valid (dataset.h CheckAlign)."""
        if self.num_features != other.num_features:
            return False
        if self.num_total_features != other.num_total_features:
            return False
        return all(a == b for a, b in zip(self.bin_mappers, other.bin_mappers))

    # ---------------------------------------------------------------- subset
    def subset(self, indices) -> "CoreDataset":
        """Row subset sharing bin mappers (dataset.cpp Subset; used by cv)."""
        indices = np.asarray(indices, dtype=np.int64)
        out = CoreDataset()
        out.bins = np.ascontiguousarray(self.bins[:, indices])
        out.bin_mappers = self.bin_mappers
        out.used_feature_map = self.used_feature_map
        out.real_feature_idx = self.real_feature_idx
        out.feature_names = self.feature_names
        out.num_total_features = self.num_total_features
        out.label_idx = self.label_idx
        out.bundle_plan = self.bundle_plan
        out.metadata = self.metadata.subset(indices)
        if self.raw_data is not None:
            out.raw_data = self.raw_data[indices]
        return out

    # --------------------------------------------------------- binary cache
    def save_binary(self, path):
        """Binary cache (reference dataset.cpp:133-212)."""
        arrays = encode_dataset_sidecar(self, {"bins": self.bins})
        from ..utils.checkpoint import atomic_open
        # crash-atomic: a kill mid-save must never leave a truncated
        # cache where a valid one stood (the loader would fatal on it).
        # The archive streams to the tmp file (savez keeps the exact
        # path; no .npz suffix is appended to an open handle).
        # UNCOMPRESSED members (np.savez = ZIP_STORED): the bins matrix
        # sits contiguous inside the archive, so the loader maps it
        # through the OS page cache (data/mmap_io.py) instead of
        # materializing a second copy — and packed uint8/int16 bins
        # barely deflate anyway.
        with atomic_open(path) as f:
            np.savez(f, magic=np.asarray(BINARY_MAGIC),
                     format_version=np.asarray(BINARY_FORMAT_VERSION),
                     **arrays)
        Log.info("Saved binary dataset to %s", str(path))

    @classmethod
    def load_binary(cls, path) -> "CoreDataset":
        """Load + validate a binary dataset cache. Every failure mode a
        truncated, bit-rotted, or foreign file can produce surfaces as
        a BinaryDatasetError naming the file and the defect — never a
        numpy reshape traceback (reference dataset.cpp:133-152 validates
        its magic token + version the same way)."""
        # probe before np.load: a text/garbage file is "never was
        # binary" (claimed=False), not a corrupt cache
        try:
            with open(path, "rb") as f:
                head = f.read(len(_ZIP_MAGIC))
        except OSError as e:
            raise BinaryDatasetError(f"cannot read {path}: {e}")
        if head != _ZIP_MAGIC:
            raise BinaryDatasetError(
                f"{path} is not a lightgbm_tpu binary dataset (bad magic)")
        try:
            z = np.load(path, allow_pickle=True)
            files = set(z.files)
        except Exception as e:
            raise BinaryDatasetError(
                f"{path} is truncated or corrupt (unreadable archive: "
                f"{e})", claimed=True)
        if "magic" not in files:
            raise BinaryDatasetError(
                f"{path} is an npz archive but not a lightgbm_tpu "
                "dataset (no magic entry)", claimed=True)
        try:
            if str(z["magic"]) != BINARY_MAGIC:
                raise BinaryDatasetError(
                    f"{path} has foreign magic {str(z['magic'])!r} "
                    f"(expected {BINARY_MAGIC})", claimed=True)
            version = (int(z["format_version"])
                       if "format_version" in files else 1)
            if version > BINARY_FORMAT_VERSION:
                raise BinaryDatasetError(
                    f"{path} is format version {version}; this build "
                    f"reads up to {BINARY_FORMAT_VERSION}", claimed=True)
            missing = [k for k in ("bins", "used_feature_map",
                                   "real_feature_idx",
                                   "num_total_features", "label_idx",
                                   "feature_names", "meta_label")
                       if k not in files]
            if missing:
                raise BinaryDatasetError(
                    f"{path} is truncated (missing entries: "
                    f"{', '.join(missing)})", claimed=True)
            ds = cls()
            # mapped-IO fast path: an uncompressed bins member is read
            # through the OS page cache (np.memmap) instead of a full
            # read() copy, so a warm cache load no longer doubles peak
            # RSS (the mapper verifies the member's zip CRC itself,
            # streamed). Compressed members (pre-mapped-IO
            # savez_compressed caches) and anything unmappable —
            # including a CRC mismatch — fall back to the copying load,
            # which surfaces the legacy BadZipFile on a rotten cache.
            from ..data.mmap_io import memmap_npz_member
            mapped = memmap_npz_member(path, "bins.npy")
            ds.bins = mapped if mapped is not None else z["bins"]
            decode_dataset_sidecar(
                ds, z, lambda msg: BinaryDatasetError(
                    f"{path} is truncated ({msg})", claimed=True))
        except BinaryDatasetError:
            raise
        except Exception as e:
            # zip-member CRC failures surface lazily at entry access
            raise BinaryDatasetError(
                f"{path} is truncated or corrupt ({e})", claimed=True)
        # length/shape cross-checks: a partially-written file whose
        # archive still opens must not survive to a reshape traceback
        if ds.bins.ndim != 2:
            raise BinaryDatasetError(
                f"{path}: bins matrix has {ds.bins.ndim} dims, "
                "expected 2", claimed=True)
        if ds.bins.dtype.name not in _BINS_CACHE_DTYPES:
            # a stale f32/f64/int64 matrix (foreign or pre-packing
            # build) must not reach the histogram engine, which streams
            # bins at their packed width
            raise BinaryDatasetError(
                f"{path}: bins matrix is {ds.bins.dtype.name}, expected "
                f"a packed bin matrix ({'/'.join(_BINS_CACHE_DTYPES)}) — "
                "stale or foreign cache", claimed=True)
        natural = bins_dtype(int(ds.max_stored_bin))
        if ds.bins.dtype != natural:
            # v1 caches stored uint16 where the packed contract says
            # int16; bin ids < max_stored_bin make the cast lossless
            ds.bins = ds.bins.astype(natural)
        n_rows = int(ds.bins.shape[1])
        n_label = int(np.asarray(z["meta_label"]).shape[0])
        if n_label != n_rows:
            raise BinaryDatasetError(
                f"{path}: bin matrix holds {n_rows} rows but the label "
                f"has {n_label} — truncated or foreign file",
                claimed=True)
        return ds


class DatasetLoader:
    """Text/matrix -> CoreDataset pipeline (dataset_loader.cpp:162-941)."""

    def __init__(self, config=None, predict_fun=None):
        from ..config import Config
        self.config = config if config is not None else Config()
        self.predict_fun = predict_fun  # init-score hook for continued training

    # ----------------------------------------------------------- from file
    def _apply_rank_partition(self, ds, rank, num_machines):
        """Per-rank row distribution for multi-host training
        (dataset_loader.cpp:505-550): contiguous query-aligned blocks;
        bin mappers stay global (built before the cut) so CheckAlign
        holds across ranks. Only active under jax.distributed."""
        import jax
        if (num_machines <= 1 or jax.process_count() <= 1
                or self.config.is_pre_partition
                # feature-parallel replicates rows on every machine
                # (config.cpp:173-176, application.cpp:125-131)
                or self.config.tree_learner == "feature"):
            return ds
        if jax.process_count() != num_machines:
            Log.fatal("num_machines=%d but %d jax processes are running; "
                      "the row partition would drop data",
                      num_machines, jax.process_count())
        if rank >= num_machines:
            Log.fatal("rank %d out of range for num_machines=%d",
                      rank, num_machines)
        from ..parallel.distributed import partition_rows
        n = ds.num_data
        qb = ds.metadata.query_boundaries
        lo, hi = partition_rows(n, rank, num_machines, qb)
        out = ds.subset(np.arange(lo, hi))
        out.global_num_data = n
        # query-aligned blocks can be uneven; every rank pads to the
        # LARGEST block so global array shapes agree (learners._pad_rows)
        out.local_rows_max = max(
            partition_rows(n, r, num_machines, qb)[1]
            - partition_rows(n, r, num_machines, qb)[0]
            for r in range(num_machines))
        Log.info("Rank %d/%d holds rows [%d, %d) of %d",
                 rank, num_machines, lo, hi, n)
        return out

    def load_from_file(self, filename, rank=0, num_machines=1) -> CoreDataset:
        cfg = self.config
        # out-of-core: bin once into the on-disk block store next to the
        # data file (reused across runs via its manifest signature) and
        # return the streaming dataset — the (F, N) matrix never
        # materializes (lightgbm_tpu/data/, docs/Out-of-Core.md)
        if getattr(cfg, "out_of_core", False):
            if self.predict_fun is not None:
                Log.fatal("out_of_core does not support continued "
                          "training (init scores need resident raw "
                          "values)")
            if cfg.max_bad_rows > 0:
                Log.warning("max_bad_rows=%d is not applied on the "
                            "out-of-core streaming load path: malformed "
                            "rows still abort the load", cfg.max_bad_rows)
            if num_machines > 1:
                # gang training over ONE shared store: rank 0 builds,
                # peers adopt their owned block ranges — no per-rank
                # re-binning (data/block_store.py, docs/Out-of-Core.md)
                from ..data.block_store import load_block_store_gang
                return load_block_store_gang(self, filename, rank,
                                             num_machines)
            from ..data.block_store import load_or_build_block_store
            return load_or_build_block_store(self, filename)
        bin_path = str(filename) + ".bin"
        # the binary cache stores no raw values, which continued training
        # needs for init scores — fall back to the text path then
        use_cache = cfg.enable_load_from_binary_file and self.predict_fun is None
        cache_incompatible = False
        # CheckCanLoadFromBin (dataset_loader.cpp:903-940): the data path
        # may BE a binary cache file, or have a sibling <data>.bin cache.
        if use_cache:
            for cand in (str(filename), bin_path):
                if not os.path.exists(cand):
                    continue
                try:
                    ds = CoreDataset.load_binary(cand)
                except BinaryDatasetError as e:
                    if e.claimed and cand == str(filename):
                        # the data file ITSELF is a (broken) binary
                        # dataset: the text parser would only produce
                        # garbage on it — fail with the real diagnosis
                        Log.fatal("%s", e)
                    if e.claimed:
                        # rotten sibling cache: fall past it to the
                        # text parse, like the checkpoint loader falls
                        # past a corrupt snapshot
                        Log.warning("ignoring unusable binary cache: %s",
                                    e)
                    continue  # not a binary cache; fall through
                if ds.bundle_plan is not None and (
                        not cfg.is_enable_sparse
                        or getattr(ds.bundle_plan, "conflict_rate", 0.0)
                        > cfg.max_conflict_rate):
                    # cache was built with bundling this run can't use
                    # (disabled, or a MORE tolerant plan than this
                    # config allows) — rebuild from text (WITHOUT
                    # overwriting the cache, so the original config
                    # keeps its bundling). (Feature-parallel handles
                    # bundled datasets since parallel/learners.py grew
                    # per-shard slot maps — no learner restriction.)
                    Log.warning("Binary cache %s contains a bundled "
                                "dataset incompatible with this config; "
                                "rebuilding from text", cand)
                    cache_incompatible = True
                    break
                Log.info("Loaded binary dataset %s", cand)
                self._attach_init_score(ds)
                return self._apply_rank_partition(ds, rank, num_machines)

        # two-round streaming path: peak memory O(block), the full float
        # matrix never materializes (dataset_loader.cpp:505-610). Continued
        # training needs raw values for init scores, so it keeps the
        # in-memory path. Wide LibSVM auto-streams even without
        # use_two_round_loading: the dense parse would materialize the
        # (N, F) float block the O(nnz) route exists to avoid (the
        # reference gets this from per-feature sparse bins,
        # sparse_bin.hpp; here the format sniff stands in for its
        # sparse_rate auto-selection, bin.cpp:291-302). The auto-route
        # carries the SAME weight/group guard as _load_two_round's
        # sparse_route: with those columns set the streamer falls back
        # to dense (65536, num_cols) parse blocks — multi-GB at the
        # widths that trigger the probe — so such configs keep the
        # in-memory path unless the user explicitly asked to stream.
        if self.predict_fun is None and (
                cfg.use_two_round_loading
                or (cfg.weight_column == "" and cfg.group_column == ""
                    and _libsvm_looks_wide(filename, cfg.has_header))):
            if cfg.max_bad_rows > 0:
                # the block streamer parses strictly; quarantine is an
                # in-memory-path feature. Say so loudly instead of
                # silently changing behavior between load routes.
                Log.warning("max_bad_rows=%d is not applied on the "
                            "two-round/streaming load path: malformed "
                            "rows still abort the load", cfg.max_bad_rows)
            ds = self._load_two_round(filename, rank, num_machines)
            if ds.global_num_data is not None:
                if cfg.is_save_binary_file:
                    Log.warning("is_save_binary_file ignored: rank-"
                                "filtered datasets hold only a row block")
                return ds  # already rank-filtered during the stream
            if cfg.is_save_binary_file and rank == 0 and not cache_incompatible:
                ds.save_binary(bin_path)  # one writer on shared storage
            return self._apply_rank_partition(ds, rank, num_machines)

        label, feats, names, fmt, label_idx = parse_text_file(
            filename, has_header=cfg.has_header, label_column=cfg.label_column,
            max_bad_rows=cfg.max_bad_rows)
        weight_idx, group_idx, ignore, categorical = self._resolve_columns(
            names, feats.shape[1])

        meta = Metadata(len(label))
        meta.set_label(label)
        if weight_idx >= 0:
            meta.set_weights(feats[:, weight_idx])
            ignore.add(weight_idx)
        if group_idx >= 0:
            # group column holds a query id per row; run-length encode in ROW
            # order (metadata.cpp:358-371) — np.unique would sort by qid value
            # and merge non-adjacent runs
            meta.set_query(_qid_to_counts(feats[:, group_idx]))
            ignore.add(group_idx)
        meta.load_side_files(filename)

        ds = self._construct(feats, names, ignore, categorical, meta)
        ds.label_idx = label_idx
        if self.predict_fun is not None:
            ds.raw_data = feats  # continued training needs raw values
        self._attach_init_score(ds)
        if cfg.is_save_binary_file and rank == 0 and not cache_incompatible:
            ds.save_binary(bin_path)  # one writer on shared storage
        return self._apply_rank_partition(ds, rank, num_machines)

    def load_from_file_align_with_other_dataset(self, filename, train_ds) -> CoreDataset:
        """Valid-set path: bin with the TRAIN mappers (dataset_loader.cpp:222-266)."""
        cfg = self.config
        from .parser import detect_format
        if (detect_format(filename) == "libsvm"
                and self.predict_fun is None
                and cfg.weight_column == "" and cfg.group_column == ""):
            # O(nnz) aligned route: stream triplets with the TRAIN
            # mappers + bundle plan, never a dense (N, F) parse (a wide
            # sparse valid file would OOM there). predict_fun needs raw
            # values -> dense fallback.
            return self._load_sparse_aligned(filename, train_ds)
        label, feats, names, fmt, _ = parse_text_file(
            filename, has_header=cfg.has_header, label_column=cfg.label_column,
            max_bad_rows=cfg.max_bad_rows)
        meta = Metadata(len(label))
        meta.set_label(label)
        weight_idx, group_idx, ignore, _ = self._resolve_columns(names, feats.shape[1])
        if weight_idx >= 0:
            meta.set_weights(feats[:, weight_idx])
        if group_idx >= 0:
            meta.set_query(_qid_to_counts(feats[:, group_idx]))
        meta.load_side_files(filename)
        ds = self._bin_with_mappers(feats, train_ds, meta)
        if self.predict_fun is not None:
            ds.raw_data = feats
        self._attach_init_score(ds)
        return ds

    # ------------------------------------------------- two-round streaming
    def _load_two_round(self, filename, rank=0, num_machines=1) -> CoreDataset:
        """Sample pass -> mappers -> binning pass (dataset_loader.cpp:505-610,
        pipeline_reader.h/text_reader.h semantics; see io/streaming.py).

        Under jax.distributed, round two is RANK-FILTERED
        (dataset_loader.cpp:505-550): every rank streams the file but
        stores only its contiguous row block, so peak memory is
        O(block + local rows + sample). The bin-construction sample is
        drawn from the GLOBAL stream with the shared data_random_seed,
        so every rank derives identical mappers with no network — the
        TPU answer to the reference's mapper Allgather
        (dataset_loader.cpp:697-760)."""
        from .parser import detect_format
        from .streaming import scan_file, iter_blocks, collect_sample_rows
        cfg = self.config
        fmt = detect_format(filename)
        n, names, num_cols = scan_file(filename, fmt, cfg.has_header)
        if n == 0:
            Log.fatal("Data file %s is empty", str(filename))

        label_idx = self._resolve_label_idx(names, fmt)
        feat_names = ([nm for i, nm in enumerate(names) if i != label_idx]
                      if names is not None else None)
        num_feats = num_cols - 1
        feat_cols = np.asarray([j for j in range(num_cols) if j != label_idx])

        weight_idx, group_idx, ignore, categorical = self._resolve_columns(
            feat_names, num_feats)
        if weight_idx >= 0:
            ignore.add(weight_idx)
        if group_idx >= 0:
            ignore.add(group_idx)

        # O(nnz) route for LibSVM: triplet blocks + CSC sample, never a
        # dense (rows, num_cols) float block — the streaming analog of
        # the reference's SparseBin push path (src/io/sparse_bin.hpp:
        # 17-331, auto-selected at sparse_rate >= 0.8, bin.cpp:291-302).
        # Weight/group column configs fall back to the dense route
        # (LibSVM files carry those via side files, not columns).
        sparse_route = (fmt == "libsvm" and weight_idx < 0
                        and group_idx < 0)

        # round one: sample rows, find mappers (identical draws and
        # therefore identical mappers to the in-memory path)
        cnt = min(cfg.bin_construct_sample_cnt, n)
        sample_idx = (np.arange(n, dtype=np.int64) if cnt == n
                      else Random(cfg.data_random_seed).sample(n, cnt).astype(np.int64))
        if sparse_route:
            from .streaming import collect_sample_csc
            _, s_colptr, s_rows, s_vals = collect_sample_csc(
                filename, cfg.has_header, num_feats, sample_idx)

            def sample_feat_col(j):
                out = np.zeros(cnt, dtype=np.float64)
                sl = slice(s_colptr[j], s_colptr[j + 1])
                out[s_rows[sl]] = s_vals[sl]
                return out
        else:
            sample_all = collect_sample_rows(filename, fmt, cfg.has_header,
                                             num_cols, sample_idx)
            sample_feats = sample_all[:, feat_cols]

            def sample_feat_col(j):
                return sample_feats[:, j]
        mappers, used_map, real_idx = self._make_mappers(
            sample_feat_col, num_feats, ignore, categorical)

        # bundling plan from the sample — identical to the in-memory
        # path's (same sample rows, same greedy pass); per-column
        # callable so planning never builds the (F, sample) bins stack
        from .bundling import plan_bundles
        plan = None
        if cfg.is_enable_sparse:
            plan = plan_bundles(
                mappers,
                lambda u: mappers[u].value_to_bin(
                    sample_feat_col(real_idx[u])),
                enable=True, max_conflict_rate=cfg.max_conflict_rate)
            if plan.is_identity:
                plan = None

        # rank filtering: only this rank's contiguous row block is stored
        # (query-grouped data and side files need global views — those
        # fall back to full-load + subset in _apply_rank_partition)
        import jax
        from .metadata import SIDE_FILE_EXTS
        side_files = any(os.path.exists(str(filename) + ext)
                         for ext in SIDE_FILE_EXTS)
        rank_filter = (num_machines > 1
                       and jax.process_count() == num_machines
                       and rank < num_machines
                       and not cfg.is_pre_partition
                       and cfg.tree_learner != "feature"
                       and group_idx < 0 and not side_files)
        if rank_filter:
            from ..parallel.distributed import partition_rows
            lo, hi = partition_rows(n, rank, num_machines)
            n_local = hi - lo
        else:
            lo, hi = 0, n
            n_local = n

        # round two: stream blocks, pushing binned values + metadata columns
        if sparse_route:
            bins, label = self._stream_sparse_libsvm(
                filename, mappers, used_map, plan, n_local, lo, hi)
            weights = qid = None
            bundle_conflicts = 0
        elif plan is None:
            dtype = bins_dtype(max(m.num_bin for m in mappers))
            check_bins_budget(len(mappers), n_local,
                              np.dtype(dtype).itemsize,
                              "Dense (unbundled) streaming load")
            bins = np.empty((len(mappers), n_local), dtype=dtype)
        else:
            dtype = bins_dtype(int(plan.slot_bins.max()))
            check_bins_budget(plan.num_slots, n_local,
                              np.dtype(dtype).itemsize,
                              "Bundled streaming load")
            bins = np.zeros((plan.num_slots, n_local), dtype=dtype)
        if not sparse_route:
            label = np.empty(n_local, dtype=np.float32)
            weights = (np.empty(n_local, dtype=np.float32)
                       if weight_idx >= 0 else None)
            qid = (np.empty(n_local, dtype=np.float64)
                   if group_idx >= 0 else None)
            bundle_conflicts = 0
            # double-buffered: the prefetch thread parses block k+1 while
            # this loop bins block k (pipeline_reader.h:18-70)
            from .streaming import prefetch_blocks
            for start, block in prefetch_blocks(
                    iter_blocks(filename, fmt, cfg.has_header, num_cols)):
                end = start + len(block)
                if start >= hi:
                    break  # past this rank's range: skip the rest
                s0, e0 = max(start, lo), min(end, hi)
                if e0 <= s0:
                    continue  # block before this rank's range
                block = block[s0 - start:e0 - start]
                ls, le = s0 - lo, e0 - lo   # local write positions
                label[ls:le] = block[:, label_idx]
                feats_block = block[:, feat_cols]
                if weights is not None:
                    weights[ls:le] = feats_block[:, weight_idx]
                if qid is not None:
                    qid[ls:le] = feats_block[:, group_idx]
                for u, j in enumerate(real_idx):
                    col = mappers[u].value_to_bin(feats_block[:, j])
                    if plan is None:
                        bins[u, ls:le] = col.astype(dtype)
                    else:
                        s = plan.feat_slot[u]
                        off = plan.feat_offset[u]
                        seg = bins[s, ls:le]
                        nz = col > 0
                        bundle_conflicts += int((nz & (seg != 0)).sum())
                        write = nz & (seg == 0)
                        seg[write] = (col[write] + off).astype(dtype)
        if bundle_conflicts:
            Log.warning("Feature bundling: %d conflicting cells kept their "
                        "first member's bin", bundle_conflicts)

        ds = CoreDataset()
        ds.num_total_features = num_feats
        ds.feature_names = (list(feat_names) if feat_names is not None
                            else [f"Column_{i}" for i in range(num_feats)])
        ds.bins = bins
        ds.bundle_plan = plan
        ds.bin_mappers = mappers
        ds.used_feature_map = used_map
        ds.real_feature_idx = np.asarray(real_idx, dtype=np.int32)
        ds.label_idx = label_idx

        meta = Metadata(n_local)
        meta.set_label(label)
        if weights is not None:
            meta.set_weights(weights)
        if qid is not None:
            meta.set_query(_qid_to_counts(qid))
        meta.load_side_files(filename)
        ds.metadata = meta
        if rank_filter:
            from ..parallel.distributed import partition_rows
            ds.global_num_data = n
            ds.local_rows_max = max(
                partition_rows(n, r, num_machines)[1]
                - partition_rows(n, r, num_machines)[0]
                for r in range(num_machines))
            Log.info("Rank %d/%d streamed rows [%d, %d) of %d (two-round)",
                     rank, num_machines, lo, hi, n)
        else:
            # baseline distribution over the full stored matrix (a
            # rank-filtered block would profile one shard's slice —
            # skip until the pod-scale mesh gathers global profiles)
            from .profile import DatasetProfile, profiling_enabled
            if profiling_enabled():
                ds.profile = DatasetProfile.from_dataset(ds)
        Log.info("Number of data: %d, number of features: %d (two-round)",
                 n_local, len(mappers))
        return ds

    def _stream_sparse_libsvm(self, filename, mappers, used_map, plan,
                              n_local, lo, hi):
        """Round two over LibSVM triplet blocks: O(block nnz) transient
        memory, and the ONLY (rows x cols) allocation is the stored bin
        matrix itself — (slots, N) when bundling engaged. Implicit
        zeros are never touched: each stored row is pre-filled with its
        feature's zero bin (bundle members have zero-bin 0 by the
        plan's candidate rule), so only nonzero entries are binned.
        The reference's equivalent storage is the delta-encoded nonzero
        list of src/io/sparse_bin.hpp:17-331."""
        cfg = self.config
        f_used = len(mappers)
        if plan is None:
            dtype = bins_dtype(max(m.num_bin for m in mappers))
            check_bins_budget(f_used, n_local, np.dtype(dtype).itemsize,
                              "Dense (unbundled) sparse-LibSVM load")
            bins = np.zeros((f_used, n_local), dtype=dtype)
            members = None
            for u, m in enumerate(mappers):
                b0 = int(m.value_to_bin(np.zeros(1))[0])
                if b0:
                    bins[u, :] = b0
        else:
            dtype = bins_dtype(int(plan.slot_bins.max()))
            check_bins_budget(plan.num_slots, n_local,
                              np.dtype(dtype).itemsize,
                              "Bundled sparse-LibSVM load")
            bins = np.zeros((plan.num_slots, n_local), dtype=dtype)
            members = np.bincount(plan.feat_slot, minlength=plan.num_slots)
            for u, m in enumerate(mappers):
                s = int(plan.feat_slot[u])
                if members[s] == 1:
                    b0 = int(m.value_to_bin(np.zeros(1))[0])
                    if b0:
                        bins[s, :] = b0
        label = np.empty(n_local, dtype=np.float32)
        conflicts = 0
        from .streaming import iter_sparse_blocks, prefetch_blocks
        for start, lab, rows, cols, vals in prefetch_blocks(
                iter_sparse_blocks(filename, cfg.has_header)):
            end = start + len(lab)
            if start >= hi:
                break  # past this rank's range: skip the rest
            s0, e0 = max(start, lo), min(end, hi)
            if e0 <= s0:
                continue  # block before this rank's range
            rlo, rhi = s0 - start, e0 - start
            label[s0 - lo:e0 - lo] = lab[rlo:rhi]
            keep = (rows >= rlo) & (rows < rhi)
            r = rows[keep] - rlo + (s0 - lo)   # local row positions
            c = cols[keep]
            # aligned (valid) files may mention feature ids past the
            # train set's feature space: those are simply unused
            u_arr = np.where(c < len(used_map),
                             used_map[np.minimum(c, len(used_map) - 1)],
                             np.int32(-1))
            v = np.nan_to_num(vals[keep], nan=0.0)
            used = u_arr >= 0
            r, v, u_arr = r[used], v[used], u_arr[used]
            # group entries by used feature, ASCENDING u: bundle
            # conflicts keep the first (lowest-u) member's bin, the
            # same rule as the dense routes
            order = np.argsort(u_arr, kind="stable")
            r, v, u_arr = r[order], v[order], u_arr[order]
            bounds = np.flatnonzero(np.diff(u_arr)) + 1
            starts = np.concatenate([[0], bounds])
            ends = np.concatenate([bounds, [len(u_arr)]])
            for g0, g1 in zip(starts, ends):
                if g1 <= g0:
                    continue
                u = int(u_arr[g0])
                b = mappers[u].value_to_bin(v[g0:g1]).astype(np.int64)
                rr = r[g0:g1]
                if plan is None:
                    bins[u, rr] = b.astype(dtype)
                    continue
                s = int(plan.feat_slot[u])
                if members[s] == 1:
                    bins[s, rr] = b.astype(dtype)
                    continue
                off = int(plan.feat_offset[u])
                nz = b > 0
                rnz = rr[nz]
                clash = bins[s, rnz] != 0
                conflicts += int(clash.sum())
                w = ~clash
                bins[s, rnz[w]] = (b[nz][w] + off).astype(dtype)
        if conflicts:
            Log.warning("Feature bundling: %d conflicting cells kept "
                        "their first member's bin", conflicts)
        return bins, label

    def _load_sparse_aligned(self, filename, train_ds) -> CoreDataset:
        """O(nnz) valid-set LibSVM load with the TRAIN mappers + bundle
        plan (the sparse analog of the dense aligned path below)."""
        from .streaming import count_rows
        cfg = self.config
        # only the row count is needed here (the train set fixed the
        # feature space) — skip scan_file's max-feature-id token pass
        n = count_rows(filename, cfg.has_header)
        if n == 0:
            Log.fatal("Data file %s is empty", str(filename))
        bins, label = self._stream_sparse_libsvm(
            filename, train_ds.bin_mappers, train_ds.used_feature_map,
            train_ds.bundle_plan, n, 0, n)
        ds = CoreDataset()
        ds.num_total_features = train_ds.num_total_features
        ds.label_idx = train_ds.label_idx
        ds.feature_names = train_ds.feature_names
        ds.bin_mappers = train_ds.bin_mappers
        ds.used_feature_map = train_ds.used_feature_map
        ds.real_feature_idx = train_ds.real_feature_idx
        ds.bundle_plan = train_ds.bundle_plan
        ds.bins = bins.astype(train_ds.stored_bins_dtype, copy=False)
        meta = Metadata(n)
        meta.set_label(label)
        meta.load_side_files(filename)
        ds.metadata = meta
        return ds

    # --------------------------------------------------------- from matrix
    def construct_from_matrix(self, data, label=None, reference=None,
                              categorical_features=(),
                              group=None) -> CoreDataset:
        """In-memory path (c_api.cpp LGBM_DatasetCreateFromMat:268-315).
        `data` may also be a column source (CscColumns): sparse inputs
        bin column-by-column, never densified (c_api.cpp:317-427).
        `group` (per-query document counts) is known to the metadata
        before binning, so the `dataset` span can say `queries`."""
        if is_column_source(data):
            meta = Metadata(data.n)
            if label is not None:
                meta.set_label(label)
            meta.set_query(group)
            if reference is not None:
                return self._bin_with_mappers(data, reference, meta)
            categorical = set(int(c) for c in categorical_features)
            return self._maybe_spill(
                self._construct(data, None, set(), categorical, meta))
        data = np.ascontiguousarray(np.asarray(data, dtype=np.float32))
        # nothing below keeps or writes `data`, so the copy nan_to_num
        # makes (five passes: 40 ns a value) is paid only where a value
        # is not finite
        if not np.isfinite(data).all():
            data = np.nan_to_num(data, nan=0.0)
        meta = Metadata(data.shape[0])
        if label is not None:
            meta.set_label(label)
        meta.set_query(group)
        if reference is not None:
            return self._bin_with_mappers(data, reference, meta)
        categorical = set(int(c) for c in categorical_features)
        return self._maybe_spill(
            self._construct(data, None, set(), categorical, meta))

    def _maybe_spill(self, ds):
        """out_of_core on the in-memory (matrix) path: spill the freshly
        binned dataset into a block store and train from disk. Unlike
        the file path (which streams and never materializes the matrix),
        this bins in RAM first — it bounds TRAINING residency, not
        construction's. `ooc_dir` picks the store directory; default is
        a fresh temp dir (no reuse signature exists for an anonymous
        matrix)."""
        cfg = self.config
        if not getattr(cfg, "out_of_core", False):
            return ds
        import tempfile
        from ..data.block_store import effective_block_rows, spill_core_dataset
        anonymous = not cfg.ooc_dir
        directory = cfg.ooc_dir or tempfile.mkdtemp(
            prefix="lightgbm_tpu_blocks_")
        out = spill_core_dataset(ds, directory, effective_block_rows(cfg),
                                 verify=cfg.ooc_verify)
        if anonymous:
            # an unnamed spill dir has no reuse identity — reclaim the
            # full dataset's disk bytes when the dataset object dies
            # instead of leaking them in /tmp run after run
            import shutil
            import weakref
            weakref.finalize(out, shutil.rmtree, directory,
                             ignore_errors=True)
        return out

    # ------------------------------------------------------------ internals
    def _resolve_label_idx(self, names, fmt):
        """Label column resolution (parser semantics; LibSVM labels are
        always column 0). Shared by the two-round streaming path and the
        block-store builder (data/block_store.py)."""
        cfg = self.config
        if fmt == "libsvm" or cfg.label_column == "":
            return 0
        s = str(cfg.label_column)
        if s.startswith("name:"):
            if names is None or s[5:] not in names:
                Log.fatal("Could not find label column %s in data file",
                          s[5:])
            return names.index(s[5:])
        return int(s)

    def _resolve_columns(self, names, num_cols):
        """weight/group/ignore/categorical column resolution. Indices do not
        count the label column (config.h:116-131)."""
        cfg = self.config

        def resolve(spec):
            if spec == "" or spec is None:
                return -1
            s = str(spec)
            if s.startswith("name:"):
                if names is None:
                    Log.fatal("Cannot use name: column selector without header")
                return names.index(s[5:])
            return int(s)

        weight_idx = resolve(cfg.weight_column)
        group_idx = resolve(cfg.group_column)
        ignore = set()
        if cfg.ignore_column:
            for tok in str(cfg.ignore_column).split(","):
                idx = resolve(tok)
                if idx >= 0:
                    ignore.add(idx)
        categorical = set()
        if cfg.categorical_column:
            for tok in str(cfg.categorical_column).split(","):
                idx = resolve(tok)
                if idx >= 0:
                    categorical.add(idx)
        return weight_idx, group_idx, ignore, categorical

    def _sample_rows(self, n):
        cfg = self.config
        cnt = min(cfg.bin_construct_sample_cnt, n)
        if cnt == n:
            return np.arange(n, dtype=np.int64)
        rnd = Random(cfg.data_random_seed)
        return rnd.sample(n, cnt).astype(np.int64)

    def _make_mappers(self, sample_col, num_total, ignore, categorical):
        """Bin-mapper construction from sampled rows
        (ConstructBinMappersFromTextData, dataset_loader.cpp:612-760).
        `sample_col(j)` -> the j-th column's sampled values."""
        cfg = self.config
        used_map = np.full(num_total, -1, dtype=np.int32)
        mappers, real_idx = [], []
        for j in range(num_total):
            if j in ignore:
                continue
            col_sample = sample_col(j).astype(np.float64)
            nonzero = col_sample[np.abs(col_sample) > ZERO_THRESHOLD]
            btype = CATEGORICAL if j in categorical else NUMERICAL
            m = BinMapper().find_bin(nonzero, len(col_sample), cfg.max_bin, btype)
            if m.is_trivial:
                Log.warning("Ignoring Column_%d , only has one value", j)
                continue
            used_map[j] = len(mappers)
            real_idx.append(j)
            mappers.append(m)
        if not mappers:
            Log.fatal("Cannot construct Dataset since there are no useful features. "
                      "It should be at least two unique rows.")
        return mappers, used_map, real_idx

    def _construct(self, feats, names, ignore, categorical, meta) -> CoreDataset:
        """Bin-mapper construction + feature extraction
        (ConstructBinMappersFromTextData + ExtractFeatures, dataset_loader.cpp:612-841).

        `feats` is a dense (N, F) matrix or any column source with
        .n / .num_total / .col(j) (sparse FFI inputs bin one column at a
        time and never materialize the dense raw matrix, the TPU-side
        analog of c_api.cpp:317-427's row-iterator construction)."""
        src = feats if is_column_source(feats) else DenseColumns(feats)
        # a dataset precedes any Booster: its spans go to the process
        # tracer (telemetry/trace.py), under `dataset/...`
        tags = {"queries": meta.num_queries} if meta.num_queries else {}
        with PROCESS_TRACER.span("dataset", rows=src.n,
                                 features=src.num_total, **tags):
            return self._construct_spanned(src, names, ignore, categorical,
                                           meta)

    def _construct_spanned(self, src, names, ignore, categorical, meta):
        cfg = self.config
        n, num_total = src.n, src.num_total
        span = functools.partial(PROCESS_TRACER.span, rows=n,
                                 features=num_total)
        with span("sample"):
            sample_idx = self._sample_rows(n)

        if (isinstance(src, DenseColumns)
                and num_total * len(sample_idx) <= _SAMPLE_MATRIX_VALUES):
            sample = src.sample(sample_idx)

            def sample_col(j):
                return sample[j]
        else:
            def sample_col(j):
                return src.col(j)[sample_idx]

        ds = CoreDataset()
        ds.num_total_features = num_total
        ds.feature_names = (list(names) if names is not None
                            else [f"Column_{i}" for i in range(num_total)])

        with span("bin_bounds"):
            mappers, used_map, real_idx = self._make_mappers(
                sample_col, num_total, ignore, categorical)

        # exclusive feature bundling: sparse columns share dense slots
        # (io/bundling.py; replaces the reference's sparse_bin storage)
        from .bundling import plan_bundles, build_stored_matrix
        plan = None
        if cfg.is_enable_sparse:
            # per-column callable: planning a wide-sparse input never
            # builds the dense (F, sample) bins stack
            with span("bundle_plan"):
                plan = plan_bundles(
                    mappers,
                    lambda u: mappers[u].value_to_bin(
                        sample_col(real_idx[u])),
                    enable=True, max_conflict_rate=cfg.max_conflict_rate)
            if plan.is_identity:
                plan = None

        if plan is None:
            dtype = bins_dtype(max(m.num_bin for m in mappers))
            dense = isinstance(src, DenseColumns)
            # a data-parallel mesh: each device bins its own row block
            layout = self._row_shard_layout(n) if dense else None
            if layout is not None:
                ds.row_shards = _bin_dense_on_shards(
                    src._m, np.asarray(real_idx), mappers, dtype, *layout)
            dev_bins = None
            if ds.row_shards is None:
                check_bins_budget(len(real_idx), n, np.dtype(dtype).itemsize,
                                  "Dense (unbundled) dataset construction")
                dev_bins = (_bin_dense_on_device(src._m,
                                                 np.asarray(real_idx),
                                                 mappers, dtype)
                            if dense else None)
                ds.bins = (dev_bins if dev_bins is not None
                           else _bin_on_host(src, real_idx, mappers, dtype))
            ds.binned_on_device = (ds.row_shards is not None
                                   or dev_bins is not None)
        else:
            dtype = bins_dtype(int(plan.slot_bins.max()))
            check_bins_budget(plan.num_slots, n, np.dtype(dtype).itemsize,
                              "Bundled dataset construction")
            with span("bundle_plan"):
                ds.bins = build_stored_matrix(
                    plan,
                    lambda u: mappers[u].value_to_bin(src.col(real_idx[u])),
                    dtype)
            ds.bundle_plan = plan
        ds.bin_mappers = mappers
        ds.used_feature_map = used_map
        ds.real_feature_idx = np.asarray(real_idx, dtype=np.int32)
        ds.metadata = meta
        # baseline distribution: one bincount pass over the fresh bin
        # matrix (+ NaN counts where the raw matrix is at hand) — the
        # training-time half of the serving drift story
        from .profile import DatasetProfile, count_missing, profiling_enabled
        if profiling_enabled():
            with span("profile"):
                if ds.row_shards is not None:
                    # counted on the devices as they binned
                    missing = ds.row_shards.missing
                elif isinstance(src, DenseColumns):
                    missing = count_missing(src._m, ds.real_feature_idx)
                else:
                    missing = None
                ds.profile = DatasetProfile.from_dataset(ds,
                                                         missing=missing)
        Log.info("Number of data: %d, number of features: %d", n, len(mappers))
        return ds

    def _row_shard_layout(self, n):
        """(devices, rows a device) where this dataset trains with
        `tree_learner=data` over more than one device of this process:
        each device then bins its own row block (_bin_dense_on_shards),
        laid out as the learner pads them (parallel/learners.py
        row_shard_layout). None otherwise."""
        cfg = self.config
        if (getattr(cfg, "tree_learner", "serial") != "data"
                or int(getattr(cfg, "num_machines", 1)) <= 1):
            return None
        from ..parallel.learners import row_shard_layout
        return row_shard_layout(cfg, n)

    def _bin_with_mappers(self, feats, ref_ds: CoreDataset, meta) -> CoreDataset:
        src = feats if is_column_source(feats) else DenseColumns(feats)
        ds = CoreDataset()
        ds.num_total_features = ref_ds.num_total_features
        ds.label_idx = ref_ds.label_idx
        ds.feature_names = ref_ds.feature_names
        ds.bin_mappers = ref_ds.bin_mappers
        ds.used_feature_map = ref_ds.used_feature_map
        ds.real_feature_idx = ref_ds.real_feature_idx
        if src.num_total < ref_ds.num_total_features:
            Log.fatal("Validation data has fewer features than training data")
        real = ref_ds.real_feature_idx
        mappers = ref_ds.bin_mappers
        if ref_ds.bundle_plan is not None:
            # valid sets share the train plan so a wide-sparse valid set
            # stores the same O(slots x N) matrix (scoring and traversal
            # decode slots exactly like the train set's)
            from .bundling import build_stored_matrix
            check_bins_budget(ref_ds.bundle_plan.num_slots, src.n,
                              ref_ds.stored_bins_dtype.itemsize,
                              "Bundled aligned (valid set) construction")
            ds.bins = build_stored_matrix(
                ref_ds.bundle_plan,
                lambda u: mappers[u].value_to_bin(src.col(real[u])),
                ref_ds.stored_bins_dtype)
            ds.bundle_plan = ref_ds.bundle_plan
            ds.metadata = meta
            return ds
        check_bins_budget(len(mappers), src.n,
                          ref_ds.stored_bins_dtype.itemsize,
                          "Aligned (valid set) dataset construction")
        cols = _bin_columns_threaded(
            lambda u: mappers[u].value_to_bin(
                src.col(real[u])).astype(ref_ds.stored_bins_dtype),
            len(mappers))
        ds.bins = np.stack(cols, axis=0)
        ds.metadata = meta
        return ds

    def _attach_init_score(self, ds):
        """Continued-training init scores via predictor hook
        (application.cpp:108-115)."""
        if self.predict_fun is not None and ds.metadata.init_score is None:
            raw = self.predict_fun(ds)
            ds.metadata.set_init_score(np.asarray(raw, dtype=np.float64).reshape(-1, order="F"))
