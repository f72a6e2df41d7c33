"""BinMapper: value -> bin discretization.

Reference: include/LightGBM/bin.h:52-170, src/io/bin.cpp:44-268.
Numeric features: greedy equal-frequency bin bounds found on a value
sample; categorical: count-sorted top-`max_bin` categories. The find-bin
algorithm below reproduces the reference's semantics exactly (including
the zero-count insertion and the big-count-value handling) because
train/valid bin compatibility ("CheckAlign") and accuracy parity both
hinge on identical bin boundaries.

value_to_bin is vectorized (np.searchsorted) instead of the reference's
per-value binary search (bin.h:353-375) — same result, one fused pass.
"""

import numpy as np

from ..utils.log import Log

NUMERICAL = 0
CATEGORICAL = 1

_ZERO = 1e-10


class BinMapper:
    def __init__(self):
        self.num_bin = 1
        self.is_trivial = True
        self.sparse_rate = 0.0
        self.bin_type = NUMERICAL
        self.bin_upper_bound = np.asarray([np.inf])
        self.bin_2_categorical = np.zeros(0, dtype=np.int64)
        self._cat_lookup = None

    # ------------------------------------------------------------------ find
    def find_bin(self, sample_values, total_sample_cnt, max_bin, bin_type=NUMERICAL):
        """Find bin bounds from sampled non-zero values (bin.cpp:44-196).

        sample_values: the non-zero sampled values of this feature;
        total_sample_cnt: total rows sampled (zeros implied by the gap).
        """
        self.bin_type = bin_type
        values = np.sort(np.asarray(sample_values, dtype=np.float64))
        zero_cnt = int(total_sample_cnt - len(values))

        distinct_values, counts = _distinct_with_zero(values, zero_cnt)

        num_values = len(distinct_values)
        sample_size = float(total_sample_cnt)
        cnt_in_bin0 = 0

        if bin_type == NUMERICAL:
            if num_values <= max_bin:
                self.num_bin = max(num_values, 1)
                if num_values == 0:
                    self.bin_upper_bound = np.asarray([np.inf])
                else:
                    ub = np.empty(num_values)
                    ub[:-1] = (distinct_values[:-1] + distinct_values[1:]) / 2.0
                    ub[-1] = np.inf
                    self.bin_upper_bound = ub
                    cnt_in_bin0 = int(counts[0])
            else:
                ub, cnt_in_bin0 = _greedy_bounds(distinct_values, counts,
                                                 sample_size, max_bin)
                self.bin_upper_bound = ub
                self.num_bin = len(ub)
        else:
            dv_int = []
            cnt_int = []
            for v, c in zip(distinct_values.tolist(), counts.tolist()):
                iv = int(v)
                if dv_int and iv == dv_int[-1]:
                    cnt_int[-1] += c
                else:
                    dv_int.append(iv)
                    cnt_int.append(c)
            order = np.argsort(-np.asarray(cnt_int), kind="stable")
            self.num_bin = min(max_bin, len(dv_int))
            self.bin_2_categorical = np.asarray(
                [dv_int[i] for i in order[:self.num_bin]], dtype=np.int64)
            self._cat_lookup = None
            used_cnt = int(sum(cnt_int[i] for i in order[:self.num_bin]))
            if sample_size > 0 and used_cnt / sample_size < 0.95:
                Log.warning("Too many categoricals are ignored, please use bigger "
                            "max_bin or partition this column")
            cnt_in_bin0 = int(sample_size) - used_cnt + (cnt_int[order[0]] if dv_int else 0)

        self.is_trivial = self.num_bin <= 1
        self.sparse_rate = (cnt_in_bin0 / sample_size) if sample_size > 0 else 0.0
        return self

    # ------------------------------------------------------------- transform
    def value_to_bin(self, values):
        """Vectorized value->bin (bin.h:353-375). Returns int32 bins."""
        values = np.asarray(values)
        if self.bin_type == NUMERICAL:
            v = np.asarray(values, dtype=np.float64)
            # NaN must bin to 0 (bin.h NaN->zero-bin); ±inf lands in the
            # edge bins with or without cleaning, so the (copying)
            # nan_to_num pass only runs when NaNs actually exist — the
            # 11M HIGGS load calls this 28 times on pre-cleaned columns
            if np.isnan(v).any():
                v = np.nan_to_num(v, nan=0.0)
            return np.searchsorted(self.bin_upper_bound, v, side="left").astype(np.int32)
        # categorical (bin.h: static_cast<int> then the id's bin, 0 for an
        # id not kept): truncated toward zero, found among the kept ids
        # sorted; unseen ids, ids past the kept max_bin and NaN go to bin 0
        if self._cat_lookup is None:
            order = np.argsort(self.bin_2_categorical, kind="stable")
            self._cat_lookup = (self.bin_2_categorical[order],
                                order.astype(np.int32))
        ids, bin_of = self._cat_lookup
        if len(ids) == 0:
            return np.zeros(values.shape, np.int32)
        if values.dtype.kind in "iub":
            key = values.astype(np.int64)
        else:
            # ids are truncated float64 sample values: exact in float64
            key, ids = np.trunc(values.astype(np.float64)), ids.astype(np.float64)
        pos = np.minimum(np.searchsorted(ids, key), len(ids) - 1)
        return np.where(ids[pos] == key, bin_of[pos], 0).astype(np.int32)

    def bin_to_value(self, bin_idx):
        """Representative real value of a bin, used as the tree's stored
        threshold (Feature::BinToValue)."""
        if self.bin_type == NUMERICAL:
            return float(self.bin_upper_bound[int(bin_idx)])
        return float(self.bin_2_categorical[int(bin_idx)])

    # --------------------------------------------------------- serialization
    def to_dict(self):
        return {
            "num_bin": int(self.num_bin),
            "is_trivial": bool(self.is_trivial),
            "sparse_rate": float(self.sparse_rate),
            "bin_type": int(self.bin_type),
            "bin_upper_bound": np.asarray(self.bin_upper_bound, dtype=np.float64),
            "bin_2_categorical": np.asarray(self.bin_2_categorical, dtype=np.int64),
        }

    @classmethod
    def from_dict(cls, d):
        m = cls()
        m.num_bin = int(d["num_bin"])
        m.is_trivial = bool(d["is_trivial"])
        m.sparse_rate = float(d["sparse_rate"])
        m.bin_type = int(d["bin_type"])
        m.bin_upper_bound = np.asarray(d["bin_upper_bound"], dtype=np.float64)
        m.bin_2_categorical = np.asarray(d["bin_2_categorical"], dtype=np.int64)
        return m

    def __eq__(self, other):
        if self.num_bin != other.num_bin or self.bin_type != other.bin_type:
            return False
        if self.bin_type == NUMERICAL:
            return np.array_equal(self.bin_upper_bound, other.bin_upper_bound)
        return np.array_equal(self.bin_2_categorical, other.bin_2_categorical)


def _distinct_with_zero(values, zero_cnt):
    """(distinct values, counts) of the sorted non-zero sample `values`
    with the implied zeros in their place (bin.cpp:52-86): 0.0 carries
    `zero_cnt`, and is a distinct value wherever the sample changes
    sign, even with no zero in it."""
    if len(values) == 0:
        return np.zeros(1), np.asarray([zero_cnt], dtype=np.int64)
    uniq, cnt = np.unique(values, return_counts=True)
    cnt = cnt.astype(np.int64)
    k = int(np.searchsorted(uniq, 0.0))       # first value >= 0
    if k < len(uniq) and uniq[k] == 0.0:
        cnt[k] += zero_cnt
    elif 0 < k < len(uniq) or zero_cnt > 0:   # a sign change, or zeros at an end
        uniq, cnt = np.insert(uniq, k, 0.0), np.insert(cnt, k, zero_cnt)
    return uniq, cnt


def _greedy_bounds(distinct_values, counts, sample_size, max_bin):
    """Greedy equal-frequency bound finding (bin.cpp:100-153): walking
    the distinct values, a bin closes at a value that holds a mean bin's
    share on its own ("big"), where the bin's count reaches the mean of
    what remains, or before a big value once it holds half that mean.
    Between two closings the mean does not change, so the next closing
    is found by a search over the cumulated counts and the next big
    value: a step a bin, not a step a distinct value (50,000 of them a
    continuous column, times thousands of columns)."""
    num_values = len(distinct_values)
    mean_bin_size = sample_size / max_bin
    rest_bin_cnt = max_bin
    is_big = counts >= mean_bin_size
    rest_bin_cnt -= int(np.sum(is_big))
    rest_sample_cnt = int(sample_size) - int(np.sum(counts[is_big]))
    mean_bin_size = rest_sample_cnt / rest_bin_cnt if rest_bin_cnt > 0 else np.inf

    cum = np.cumsum(counts)                          # counts through i
    cum_small = np.cumsum(np.where(is_big, 0, counts))
    big_at = np.flatnonzero(is_big)
    closes = []                    # the last value of every closed bin
    start, before, cnt_in_bin0 = 0, 0, 0
    while start < num_values - 1 and len(closes) < max_bin - 1:
        # the first big value at or after `start`: it closes its bin,
        # or the value before it does (with half a mean bin in it)
        nxt = int(np.searchsorted(big_at, start))
        i = int(big_at[nxt]) if nxt < len(big_at) else num_values
        if i > start and cum[i - 1] - before >= max(1.0, mean_bin_size * 0.5):
            i -= 1
        if np.isfinite(mean_bin_size):
            # integer counts: cur >= mean <=> cur >= ceil(mean)
            full = int(np.searchsorted(
                cum, before + int(np.ceil(mean_bin_size)), side="left"))
            i = min(i, max(full, start))
        if i > num_values - 2:
            break
        if not closes:
            cnt_in_bin0 = int(cum[i]) - before
        closes.append(i)
        if not is_big[i]:
            rest_bin_cnt -= 1
            rest_now = rest_sample_cnt - int(cum_small[i])
            mean_bin_size = (rest_now / rest_bin_cnt if rest_bin_cnt > 0
                             else np.inf)
        start, before = i + 1, int(cum[i])
    closes = np.asarray(closes, dtype=np.intp)
    ub = np.append((distinct_values[closes] + distinct_values[closes + 1]) / 2.0,
                   np.inf)
    return ub, cnt_in_bin0
