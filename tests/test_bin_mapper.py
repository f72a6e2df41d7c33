"""BinMapper semantics (reference src/io/bin.cpp:44-268)."""

import numpy as np
import pytest

from lightgbm_tpu.io.bin_mapper import BinMapper, CATEGORICAL


def test_few_distinct_values_midpoint_bounds():
    # <= max_bin distinct values: bounds are midpoints, last is +inf
    vals = np.array([1.0, 2.0, 2.0, 5.0])
    m = BinMapper().find_bin(vals, total_sample_cnt=4, max_bin=255)
    assert m.num_bin == 3
    np.testing.assert_allclose(m.bin_upper_bound, [1.5, 3.5, np.inf])
    assert m.value_to_bin(np.array([0.9, 1.5, 1.6, 3.5, 100.0])).tolist() == [0, 0, 1, 1, 2]


def test_zero_block_inserted():
    # zeros are implied by total_sample_cnt - len(values)
    vals = np.array([3.0, 3.0, 7.0])
    m = BinMapper().find_bin(vals, total_sample_cnt=10, max_bin=255)
    # distinct values: 0 (cnt 7), 3 (cnt 2), 7 (cnt 1)
    assert m.num_bin == 3
    np.testing.assert_allclose(m.bin_upper_bound, [1.5, 5.0, np.inf])


def test_negative_values_zero_inserted_in_order():
    vals = np.array([-2.0, 4.0])
    m = BinMapper().find_bin(vals, total_sample_cnt=4, max_bin=255)
    assert m.num_bin == 3
    np.testing.assert_allclose(m.bin_upper_bound, [-1.0, 2.0, np.inf])
    assert m.value_to_bin(np.array([-5.0, 0.0, 9.0])).tolist() == [0, 1, 2]


def test_greedy_equal_frequency_many_values(rng):
    vals = rng.randn(20000)
    m = BinMapper().find_bin(vals, total_sample_cnt=20000, max_bin=64)
    assert m.num_bin <= 64
    assert m.num_bin > 50  # continuous data should fill most bins
    bins = m.value_to_bin(vals)
    counts = np.bincount(bins, minlength=m.num_bin)
    # equal-frequency: no bin should be wildly overloaded
    assert counts.max() < 20000 / 64 * 4
    assert np.all(np.diff(m.bin_upper_bound[:-1]) > 0)


def test_categorical_top_count_order():
    # categories sorted by count; bin 0 = most frequent
    vals = np.array([5] * 10 + [2] * 7 + [9] * 3, dtype=np.float64)
    m = BinMapper().find_bin(vals, total_sample_cnt=20, max_bin=255,
                             bin_type=CATEGORICAL)
    assert m.bin_type == CATEGORICAL
    assert m.bin_2_categorical.tolist() == [5, 2, 9]
    assert m.value_to_bin(np.array([5, 2, 9, 777])).tolist() == [0, 1, 2, 0]


def test_categorical_max_bin_cap():
    vals = np.repeat(np.arange(100), np.arange(100, 0, -1)).astype(np.float64)
    m = BinMapper().find_bin(vals, total_sample_cnt=len(vals), max_bin=10,
                             bin_type=CATEGORICAL)
    assert m.num_bin == 10
    assert m.bin_2_categorical.tolist() == list(range(10))


def test_trivial_feature():
    m = BinMapper().find_bin(np.array([]), total_sample_cnt=100, max_bin=255)
    assert m.is_trivial


def test_roundtrip_serialization(rng):
    vals = rng.randn(1000)
    m = BinMapper().find_bin(vals, total_sample_cnt=1000, max_bin=32)
    m2 = BinMapper.from_dict(m.to_dict())
    assert m == m2
    np.testing.assert_array_equal(m.value_to_bin(vals), m2.value_to_bin(vals))


def test_nan_maps_like_zero():
    m = BinMapper().find_bin(np.array([-1.0, 1.0]), total_sample_cnt=4, max_bin=255)
    b_nan = m.value_to_bin(np.array([np.nan]))[0]
    b_zero = m.value_to_bin(np.array([0.0]))[0]
    assert b_nan == b_zero


def test_device_binning_matches_host(monkeypatch):
    """The accelerator binning pass (dataset.py _bin_dense_on_device)
    must be BIT-identical to the host searchsorted rule, including f32
    inputs adjacent to f64 bin boundaries (the f32 bound cast rounds
    toward -inf, mirroring the device-predict threshold rule)."""
    import numpy as np
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import DatasetLoader

    rng = np.random.RandomState(3)
    n, f = 5000, 6
    x = rng.randn(n, f).astype(np.float32)
    # adversarial column: values clustered so bounds are non-f32 f64
    # midpoints, plus probes exactly at/next to those boundaries
    base = (rng.randint(0, 50, n) / 10.0 + 0.05).astype(np.float32)
    x[:, 0] = base
    probe = np.float64(0.15)  # midpoint of 0.1/0.2-ish grids
    x[:100, 0] = np.float32(probe)
    x[100:200, 0] = np.nextafter(np.float32(probe), np.float32(2.0))
    x[200:300, 0] = np.nextafter(np.float32(probe), np.float32(-2.0))
    y = (x[:, 1] > 0).astype(np.float32)

    def build():
        cfg = Config.from_params({"objective": "binary", "verbose": -1,
                                  "max_bin": 64})
        return DatasetLoader(cfg).construct_from_matrix(x, label=y)

    monkeypatch.setenv("LIGHTGBM_TPU_DEVICE_BIN", "0")
    host = build()
    monkeypatch.setenv("LIGHTGBM_TPU_DEVICE_BIN", "1")  # force on CPU
    dev = build()
    np.testing.assert_array_equal(host.bins, dev.bins)
    for mh, md in zip(host.bin_mappers, dev.bin_mappers):
        np.testing.assert_array_equal(mh.bin_upper_bound,
                                      md.bin_upper_bound)


def _find_bin_by_loop(values, total, max_bin):
    """Numerical find_bin as it was written before PR 33: a Python step
    a distinct value, twice (bin.cpp:52-153 line for line). Kept here as
    the oracle of the search-based form."""
    values = np.sort(np.asarray(values, dtype=np.float64))
    zero_cnt = int(total - len(values))
    dv, cn = [], []
    if len(values) == 0 or (values[0] > 0.0 and zero_cnt > 0):
        dv.append(0.0)
        cn.append(zero_cnt)
    if len(values) > 0:
        uniq, cnt = np.unique(values, return_counts=True)
        for i, (v, c) in enumerate(zip(uniq.tolist(), cnt.tolist())):
            if i > 0 and uniq[i - 1] < 0.0 and v > 0.0:
                dv.append(0.0)
                cn.append(zero_cnt)
            dv.append(v)
            cn.append(int(c))
            if v == 0.0:
                cn[-1] += zero_cnt
        if uniq[-1] < 0.0 and zero_cnt > 0:
            dv.append(0.0)
            cn.append(zero_cnt)
    dv, cn = np.asarray(dv), np.asarray(cn, dtype=np.int64)
    if len(dv) <= max_bin:
        return np.append((dv[:-1] + dv[1:]) / 2.0, np.inf), int(cn[0])
    mean = total / max_bin
    rest_bins, rest_cnt = max_bin, int(total)
    big = cn >= mean
    rest_bins -= int(big.sum())
    rest_cnt -= int(cn[big].sum())
    mean = rest_cnt / rest_bins if rest_bins > 0 else np.inf
    upper, lower = np.full(max_bin, np.inf), np.full(max_bin, np.inf)
    k, cur, first = 0, 0, 0
    lower[0] = dv[0]
    for i in range(len(dv) - 1):
        if not big[i]:
            rest_cnt -= cn[i]
        cur += cn[i]
        if (big[i] or cur >= mean
                or (big[i + 1] and cur >= max(1.0, mean * 0.5))):
            upper[k] = dv[i]
            if k == 0:
                first = cur
            k += 1
            lower[k] = dv[i + 1]
            if k >= max_bin - 1:
                break
            cur = 0
            if not big[i]:
                rest_bins -= 1
                mean = rest_cnt / rest_bins if rest_bins > 0 else np.inf
    k += 1
    return np.append((upper[:k - 1] + lower[1:k]) / 2.0, np.inf), int(first)


@pytest.mark.parametrize("kind", ["continuous", "rounded", "positive",
                                  "negative", "small_integers", "one_big_value",
                                  "tenths", "integers_and_noise"])
def test_find_bin_is_the_loop_it_replaced(kind):
    """The greedy bounds are found a bin at a time (a search over the
    cumulated counts and the next big value), not a distinct value at a
    time: the same bounds, bin-0 count and all, as the loop, over sparse
    and dense samples, big values, sign changes and every `max_bin`."""
    rng = np.random.RandomState(len(kind))
    for trial in range(120):
        n = int(rng.randint(1, 400))
        v = {
            "continuous": lambda: rng.randn(n),
            "rounded": lambda: np.round(rng.randn(n) * 3),
            "positive": lambda: np.abs(rng.randn(n)),
            "negative": lambda: -np.abs(rng.randn(n)),
            "small_integers": lambda: rng.randint(-3, 4, n).astype(float),
            "one_big_value": lambda: np.concatenate(
                [np.full(n // 2 + 1, 2.5), rng.randn(n)]),
            "tenths": lambda: np.round(rng.randn(n), 1),
            "integers_and_noise": lambda: np.concatenate(
                [rng.randint(0, 3, n).astype(float), rng.randn(n // 3)]),
        }[kind]()
        v = v[np.abs(v) > 1e-10]
        total = len(v) + int(rng.randint(0, 3)) * int(rng.randint(0, 200))
        if total == 0:
            continue
        for max_bin in (2, 3, 5, 16, 63, 255):
            m = BinMapper().find_bin(v, total, max_bin)
            bounds, first = _find_bin_by_loop(v, total, max_bin)
            np.testing.assert_array_equal(m.bin_upper_bound, bounds)
            assert m.num_bin == len(bounds)
            assert m.sparse_rate == first / float(total)


def _categorical_by_lookup(m, values):
    """value_to_bin's categorical rule as it was first written: a
    dict from the kept id to its bin, one `int(v)` a value. Kept here as
    the oracle of the vectorised form (it raises on NaN)."""
    look = {int(c): i for i, c in enumerate(m.bin_2_categorical)}
    return np.asarray([look.get(int(v), 0) for v in np.ravel(values)],
                      np.int32).reshape(np.shape(values))


@pytest.mark.parametrize("max_bin", [4, 31, 255])
def test_categorical_value_to_bin_is_the_lookup_it_replaced(max_bin):
    """Sorted ids and a search give the dict's bins bit for bit, on
    every value the dict took: fractional values truncated toward zero,
    negative ids, ids never seen in the sample, ids past the kept
    max_bin, large magnitudes; for float64, float32 and integer input."""
    rng = np.random.RandomState(max_bin)
    sample = np.concatenate([rng.zipf(1.4, 3000) % 400 - 20,
                             rng.randint(-3, 3, 200) + 0.5]).astype(float)
    m = BinMapper().find_bin(sample[np.abs(sample) > 1e-10], len(sample),
                             max_bin, bin_type=CATEGORICAL)
    assert m.num_bin == min(max_bin, len(np.unique(np.trunc(sample))))
    probe = np.concatenate([
        rng.uniform(-40, 500, 5000), np.arange(-30, 460, dtype=float),
        -np.arange(30) - 0.999, np.arange(30) + 0.999, [-0.0, 0.0, 2.0**40,
                                                        -2.0**52, 1e15]])
    for values in (probe, probe.astype(np.float32)):
        got = m.value_to_bin(values)
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, _categorical_by_lookup(m, values))
    ints = np.arange(-30, 460)
    np.testing.assert_array_equal(m.value_to_bin(ints),
                                  _categorical_by_lookup(m, ints))
    grid = probe[:600].reshape(20, 30)
    np.testing.assert_array_equal(m.value_to_bin(grid),
                                  _categorical_by_lookup(m, grid))


def test_categorical_nan_bins_to_zero():
    """A NaN in a categorical column goes to bin 0 with the unseen ids
    (the dict lookup raised ValueError on it); infinities likewise."""
    vals = np.array([5] * 10 + [2] * 7 + [9] * 3, dtype=np.float64)
    m = BinMapper().find_bin(vals, total_sample_cnt=20, max_bin=255,
                             bin_type=CATEGORICAL)
    got = m.value_to_bin(np.array([np.nan, 2.0, np.inf, -np.inf, 9.5, np.nan]))
    assert got.tolist() == [0, 1, 0, 0, 2, 0]
    assert m.value_to_bin(np.array([np.nan], np.float32)).tolist() == [0]


@pytest.mark.parametrize("max_bin", [63, 255])
def test_device_binning_matches_host_with_categorical_columns(monkeypatch,
                                                              max_bin):
    """With categorical columns beside numerical ones the device pass
    bins both (the categorical ones by equality against the kept ids, in
    a program of their own under the span `dataset/bin_categorical`) and
    is bit-equal to the host, which bins them by value_to_bin under the
    same span: ids past max_bin, unseen ids, fractional and negative
    ids, a NaN and ids past 2**24 that float32 cannot hold."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import DatasetLoader
    from lightgbm_tpu.telemetry.trace import PROCESS_TRACER

    rng = np.random.RandomState(7)
    n = 9000
    x = rng.randn(n, 6).astype(np.float32)
    x[:, 1] = rng.zipf(1.3, n) % 400                   # past max_bin
    x[:, 3] = rng.randint(-5, 40, n) + rng.rand(n).round(1)
    x[:50, 3] = np.nan
    x[:, 4] = 2.0**24 + 2 * rng.randint(0, 6, n)       # float32-exact, big
    x[-7:, 4] = 123.0                                  # never in the sample
    y = (x[:, 0] > 0).astype(np.float32)

    def build():
        cfg = Config.from_params({"objective": "binary", "verbose": -1,
                                  "max_bin": max_bin,
                                  "bin_construct_sample_cnt": 5000})
        PROCESS_TRACER.reset()
        ds = DatasetLoader(cfg).construct_from_matrix(
            x, label=y, categorical_features=(1, 3, 4))
        spans = [s for s in PROCESS_TRACER.recent(None)
                 if s["path"] == "dataset/bin_categorical"]
        assert len(spans) == 1
        assert spans[0]["tags"]["columns"] == [1, 3, 4]
        assert spans[0]["tags"]["categories"] == sum(
            m.num_bin for m in ds.bin_mappers if m.bin_type == CATEGORICAL)
        return ds

    monkeypatch.setenv("LIGHTGBM_TPU_DEVICE_BIN", "0")
    host = build()
    monkeypatch.setenv("LIGHTGBM_TPU_DEVICE_BIN", "1")  # force on CPU
    dev = build()
    assert not host.binned_on_device and dev.binned_on_device
    assert host.bin_mappers[1].num_bin == max_bin
    np.testing.assert_array_equal(host.bins, dev.bins)
    assert host.bins.dtype == dev.bins.dtype == np.uint8
    for mh, md in zip(host.bin_mappers, dev.bin_mappers):
        assert mh == md
