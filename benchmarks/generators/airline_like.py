"""Rows shaped like the ASA Data Expo 2009 airline record as NVIDIA
gbm-bench prepares it (no network, so not the real file; the
configuration's `assumed` says so): 13 columns in gbm-bench's order,
the label `ArrDelay > 0`.

    Year, Month, DayofMonth, DayOfWeek, CRSDepTime, CRSArrTime,
    UniqueCarrier, FlightNum, ActualElapsedTime, Origin, Dest,
    Distance, Diverted

Six are categorical and passed as such (LightGBM's Expo experiment):
Month, DayofMonth, DayOfWeek, UniqueCarrier, Origin and Dest, each an
integer code in float32 as gbm-bench's label encoding leaves it (12,
31, 7, CARRIERS, ORIGINS and DESTS ids). Carriers and airports are
Zipf-skewed, as traffic gathers at a few hubs; a code is a drawn
permutation of the popularity rank, as an encoding by name is. Origin
and Dest have more ids than 255 bins keep. Distance follows the
(Origin, Dest) pair over drawn airport positions, ActualElapsedTime
follows Distance and is NaN on NAN_SHARE of the rows (cancelled and
diverted flights, Diverted = 1 on a tenth of those), CRS times are hhmm.
The label is Bernoulli in a logit of per-airport, per-carrier,
per-month and per-weekday effects plus the departure hour, near 45 %
positive.

`make(data, seed)`: float32 rows from `base_seed` (a block of 65,536
rows a generator spawned from it, drawn on eight threads, so the rows
are the same however many threads draw them). `--seed` (any
non-negative whole number) draws the order of the columns only, and
`categorical_feature` follows the columns: every seed is the same rows
and the same work in another order (PERF.md section 2). Returns
`(x, y, {"categorical_feature": [...]})`.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

COLUMNS = ("Year", "Month", "DayofMonth", "DayOfWeek", "CRSDepTime",
           "CRSArrTime", "UniqueCarrier", "FlightNum", "ActualElapsedTime",
           "Origin", "Dest", "Distance", "Diverted")
CATEGORICAL = (1, 2, 3, 6, 9, 10)      # positions in COLUMNS
CARRIERS, ORIGINS, DESTS = 29, 347, 352
NAN_SHARE = 0.02
ROW_BLOCK = 1 << 16


def _zipf_cdf(k, s):
    p = 1.0 / np.arange(1, k + 1) ** s
    return np.cumsum(p / p.sum())


class _Tables:
    """What every row block shares, from `base_seed`: the popularity of
    each id and the code it is written as, the airports' positions, and
    the label's effects."""

    def __init__(self, base):
        rng = np.random.default_rng(base + 1)
        self.year_cdf = np.cumsum(np.linspace(1.0, 1.6, 22) / np.linspace(
            1.0, 1.6, 22).sum())
        self.month_cdf = np.cumsum(1 + 0.1 * np.sin(np.arange(12) / 2.0))
        self.month_cdf /= self.month_cdf[-1]
        dom = np.ones(31)
        dom[28:] = (11 / 12, 11 / 12, 7 / 12)    # the 29th-31st are rarer
        self.dom_cdf = np.cumsum(dom / dom.sum())
        dow = np.asarray([1.0, 1.0, 1.0, 1.0, 1.0, 0.85, 0.95])
        self.dow_cdf = np.cumsum(dow / dow.sum())
        hour = np.exp(-0.5 * ((np.arange(24) - 8) / 2.5) ** 2) + 0.9 * np.exp(
            -0.5 * ((np.arange(24) - 17) / 3.0) ** 2)
        hour[:5] = 0.0
        self.hour_cdf = np.cumsum(hour / hour.sum())
        self.carrier_cdf = _zipf_cdf(CARRIERS, 0.9)
        self.origin_cdf = _zipf_cdf(ORIGINS, 0.8)
        self.dest_cdf = _zipf_cdf(DESTS, 0.8)
        self.carrier_code = rng.permutation(CARRIERS)
        self.origin_code = rng.permutation(ORIGINS)
        self.dest_code = rng.permutation(DESTS)
        # airports by popularity rank, one list: the origins are the
        # ORIGINS most popular of the DESTS destinations
        self.pos = rng.uniform((0.0, 0.0), (2500.0, 1100.0), (DESTS, 2))
        self.e_origin = rng.normal(0.0, 0.45, ORIGINS)
        self.e_dest = rng.normal(0.0, 0.35, DESTS)
        self.e_carrier = rng.normal(0.0, 0.40, CARRIERS)
        self.e_month = rng.normal(0.0, 0.25, 12)
        self.e_dow = rng.normal(0.0, 0.15, 7)


def _draw(cdf, rng, n):
    return np.minimum(np.searchsorted(cdf, rng.random(n)), len(cdf) - 1)


def _block(t, rng, xb, yb):
    """Rows of one block, in COLUMNS' order, into xb (float32) and the
    label into yb."""
    n = len(xb)
    year = _draw(t.year_cdf, rng, n)
    month = _draw(t.month_cdf, rng, n)
    dom = _draw(t.dom_cdf, rng, n)
    dow = _draw(t.dow_cdf, rng, n)
    hour = _draw(t.hour_cdf, rng, n)
    dep = hour * 100 + 5 * rng.integers(0, 12, n)
    carrier = _draw(t.carrier_cdf, rng, n)
    origin = _draw(t.origin_cdf, rng, n)
    dest = _draw(t.dest_cdf, rng, n)
    d_at = np.where(dest == origin, (dest + 1) % DESTS, dest)
    dist = np.rint(np.hypot(*(t.pos[origin] - t.pos[d_at]).T) + 30.0)
    sched = dist / 7.5 + 25.0                         # minutes
    elapsed = np.rint(sched + rng.gamma(2.0, 6.0, n) - 8.0)
    arr_min = (hour * 60 + (dep % 100) + sched.astype(np.int64)) % 1440
    arr = (arr_min // 60) * 100 + arr_min % 60
    gone = rng.random(n) < NAN_SHARE
    diverted = gone & (rng.random(n) < 0.1)
    elapsed[gone] = np.nan
    flight = np.floor(np.exp(rng.uniform(0.0, np.log(7500.0), n)))
    cols = (1987 + year, month, dom, dow, dep, arr, t.carrier_code[carrier],
            flight, elapsed, t.origin_code[origin], t.dest_code[d_at], dist,
            diverted)
    for j, c in enumerate(cols):
        xb[:, j] = c
    logit = (-0.37 + t.e_origin[origin] + t.e_dest[d_at]
             + t.e_carrier[carrier] + t.e_month[month] + t.e_dow[dow]
             + 0.06 * (hour - 13))
    yb[:] = rng.random(n) < 1.0 / (1.0 + np.exp(-logit))


def make(data, seed):
    n, f = int(data["rows"]), int(data["features"])
    if f != len(COLUMNS):
        raise ValueError(f"airline_like has {len(COLUMNS)} columns, not {f}")
    base = int(data["base_seed"])
    t = _Tables(base)
    order = np.random.default_rng(int(seed)).permutation(f)
    x = np.empty((n, f), np.float32)
    y = np.empty(n, np.float32)
    starts = range(0, n, ROW_BLOCK)

    def block(task):
        lo, child = task
        xb = x[lo:lo + ROW_BLOCK]
        _block(t, np.random.default_rng(child), xb, y[lo:lo + ROW_BLOCK])
        xb[:] = np.take(xb, order, axis=1)        # the seed's column order
    with ThreadPoolExecutor(8) as pool:
        list(pool.map(block, zip(starts, np.random.SeedSequence(base).spawn(
            len(starts)))))
    cat = sorted(int(j) for j in np.flatnonzero(np.isin(order, CATEGORICAL)))
    return x, y, {"categorical_feature": cat}
