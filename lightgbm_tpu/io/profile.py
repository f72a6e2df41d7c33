"""Dataset profile: the training-time baseline feature distribution.

Captured once at binning (the only moment the full dataset streams past
the bin mappers anyway) and persisted with every durable form of the
dataset and model:

- attached to the `CoreDataset` as ``ds.profile``;
- ridden through the binary dataset cache and the block-store sidecar
  (io/dataset.py encode/decode_dataset_sidecar — ONE encoder for both
  binary forms, so the profile cannot drift between them);
- written as ``<model>.profile.json`` next to every saved model file
  (models/gbdt.py save_model_to_file), which is the artifact the
  serving-side drift monitor loads (serving/drift.py): it carries the
  bin BOUNDS as well as the occupancy counts, so a serving process can
  bin incoming rows identically to training without the dataset.

Per used feature the profile records: name, real column index, bin
type, the mapper's bin bounds (numeric upper bounds / categorical ids),
the full-dataset bin-occupancy histogram, and a missing (NaN) count.
Training ingestion collapses NaN to 0.0 BEFORE binning (io/parser.py;
bin.h NaN->zero-bin), so on the standard load paths missing mass lands
in the zero bin and the `missing` field stays 0 — the serving-side
monitor bins NaN through the same rule, which is what keeps the
training/serving occupancy histograms comparable regardless of how
missing values arrive. The zero-bin occupancy (`zero_rate`) is
therefore the zero-OR-missing rate on both sides.

`profile_bins` (docs/Parameters.md) caps the RESOLUTION drift
comparisons run at: `group_counts` folds a mapper's bins into at most
that many groups (contiguous, even in bin space) before PSI — both the
baseline and the serving-side rolling histogram fold the same way, so
the comparison stays aligned while small samples stop being noisy at
255-bin granularity.

jax-free; numpy + stdlib json only (the serving image's floor).
"""

import json
import os

import numpy as np

from ..utils.log import Log
from .bin_mapper import NUMERICAL, BinMapper

PROFILE_VERSION = 1
PROFILE_SUFFIX = ".profile.json"
# PSI's classic formulation uses ~10 quantile buckets; finer groups
# make small serving samples spuriously noisy (an empty group reads as
# drift), coarser ones hide real shifts
DEFAULT_PROFILE_BINS = 10


def profiling_enabled():
    """Capture kill-switch: LIGHTGBM_TPU_DATASET_PROFILE=0 skips the
    occupancy pass (the profile then simply does not exist; every
    consumer treats that as 'no baseline')."""
    return os.environ.get("LIGHTGBM_TPU_DATASET_PROFILE", "") != "0"


def group_counts(counts, profile_bins):
    """Fold a per-bin count vector into at most `profile_bins`
    contiguous groups (group of bin i = i * G // B — even in bin
    space). <=0 or enough room returns the counts unchanged."""
    counts = np.asarray(counts, np.int64)
    g = int(profile_bins)
    if g <= 0 or len(counts) <= g:
        return counts
    idx = (np.arange(len(counts), dtype=np.int64) * g) // len(counts)
    out = np.zeros(g, np.int64)
    np.add.at(out, idx, counts)
    return out


class DatasetProfile:
    """One dataset's per-feature baseline distribution (module
    docstring). `features` is a list of dicts with keys: name, column,
    bin_type, num_bin, upper_bounds (numeric) / categories
    (categorical), counts, missing."""

    def __init__(self, num_rows, features):
        self.num_rows = int(num_rows)
        self.features = list(features)

    # ------------------------------------------------------------ build
    @classmethod
    def from_parts(cls, mappers, real_idx, feature_names, counts_list,
                   num_rows, missing=None):
        """Assemble from a loader's pieces: the bin mappers, the
        used->total map, per-used-feature occupancy counts, and the
        optional per-used-feature NaN counts."""
        features = []
        for u, m in enumerate(mappers):
            col = int(real_idx[u])
            name = (str(feature_names[col])
                    if col < len(feature_names) and feature_names[col]
                    else f"Column_{col}")
            rec = {
                "name": name,
                "column": col,
                "bin_type": int(m.bin_type),
                "num_bin": int(m.num_bin),
                "counts": np.asarray(counts_list[u], np.int64),
                "missing": int(missing[u]) if missing is not None else 0,
            }
            if m.bin_type == NUMERICAL:
                rec["upper_bounds"] = np.asarray(m.bin_upper_bound,
                                                 np.float64)
            else:
                rec["categories"] = np.asarray(m.bin_2_categorical,
                                               np.int64)
            features.append(rec)
        return cls(num_rows, features)

    @classmethod
    def from_dataset(cls, ds, missing=None):
        """Occupancy pass over a constructed dataset: one bincount per
        used feature. Handles the three storage layouts: a plain
        (F, N) matrix, a bundled stored matrix (slots decode through
        the bundle plan), and an out-of-core block store (streamed
        block by block — never materializes the matrix)."""
        shards = getattr(ds, "row_shards", None)
        if shards is not None:
            # counted on the devices while they binned (io/dataset.py
            # _bin_dense_on_shards): the bins are never downloaded
            return cls.from_parts(ds.bin_mappers, ds.real_feature_idx,
                                  ds.feature_names, shards.counts,
                                  ds.num_data, missing=missing)
        counts = [np.zeros(m.num_bin, np.int64) for m in ds.bin_mappers]
        plan = ds.bundle_plan

        def accumulate(stored):
            slot_cache = {}     # bundled slots decode ONCE per slot,
            for u in range(len(ds.bin_mappers)):   # not per member
                nb = len(counts[u])
                if plan is None:
                    col = stored[u].astype(np.int64, copy=False)
                else:
                    slot = int(plan.feat_slot[u])
                    sc = slot_cache.get(slot)
                    if sc is None:
                        sc = stored[slot].astype(np.int64, copy=False)
                        slot_cache[slot] = sc
                    off = int(plan.feat_offset[u])
                    col = np.where((sc > off) & (sc <= off + nb - 1),
                                   sc - off, 0)
                counts[u] += np.bincount(np.minimum(col, nb - 1),
                                         minlength=nb)[:nb]

        if ds.bins is not None:
            accumulate(ds.bins)
        else:
            store = getattr(ds, "block_store", None)
            if store is None:
                return None
            for i in range(store.num_blocks):
                accumulate(np.asarray(store.read_block(i)))
        return cls.from_parts(ds.bin_mappers, ds.real_feature_idx,
                              ds.feature_names, counts, ds.num_data,
                              missing=missing)

    # --------------------------------------------------------- accessors
    @property
    def num_features(self):
        return len(self.features)

    def zero_bin(self, u):
        """The bin the value 0.0 (and therefore NaN) lands in."""
        rec = self.features[u]
        if rec["bin_type"] == NUMERICAL:
            return int(np.searchsorted(rec["upper_bounds"], 0.0,
                                       side="left"))
        cats = rec["categories"]
        hit = np.nonzero(cats == 0)[0]
        return int(hit[0]) if len(hit) else 0

    def zero_rate(self, u):
        rec = self.features[u]
        total = int(rec["counts"].sum())
        if total <= 0:
            return 0.0
        return float(rec["counts"][self.zero_bin(u)]) / total

    def missing_rate(self, u):
        if self.num_rows <= 0:
            return 0.0
        return float(self.features[u]["missing"]) / self.num_rows

    def mapper(self, u):
        """Rebuild the feature's BinMapper (value->bin for the serving
        drift monitor; identical boundaries by construction)."""
        rec = self.features[u]
        m = BinMapper()
        m.num_bin = int(rec["num_bin"])
        m.is_trivial = m.num_bin <= 1
        m.bin_type = int(rec["bin_type"])
        if m.bin_type == NUMERICAL:
            m.bin_upper_bound = np.asarray(rec["upper_bounds"],
                                           np.float64)
        else:
            m.bin_2_categorical = np.asarray(rec["categories"], np.int64)
        return m

    # ----------------------------------------------------- serialization
    def to_json_dict(self):
        features = []
        for rec in self.features:
            out = {"name": rec["name"], "column": int(rec["column"]),
                   "bin_type": int(rec["bin_type"]),
                   "num_bin": int(rec["num_bin"]),
                   "counts": [int(c) for c in rec["counts"]],
                   "missing": int(rec["missing"])}
            if rec["bin_type"] == NUMERICAL:
                # inf is not JSON: the last upper bound is always +inf
                # (bin_mapper.find_bin), encode it as null
                out["upper_bounds"] = [
                    None if not np.isfinite(b) else float(b)
                    for b in rec["upper_bounds"]]
            else:
                out["categories"] = [int(c) for c in rec["categories"]]
            features.append(out)
        return {"version": PROFILE_VERSION, "num_rows": self.num_rows,
                "features": features}

    @classmethod
    def from_json_dict(cls, d):
        if int(d.get("version", 0)) > PROFILE_VERSION:
            raise ValueError(
                f"profile version {d.get('version')} is newer than this "
                f"build reads ({PROFILE_VERSION})")
        features = []
        for rec in d.get("features", []):
            out = {"name": str(rec["name"]), "column": int(rec["column"]),
                   "bin_type": int(rec["bin_type"]),
                   "num_bin": int(rec["num_bin"]),
                   "counts": np.asarray(rec["counts"], np.int64),
                   "missing": int(rec.get("missing", 0))}
            if out["bin_type"] == NUMERICAL:
                out["upper_bounds"] = np.asarray(
                    [np.inf if b is None else float(b)
                     for b in rec["upper_bounds"]], np.float64)
            else:
                out["categories"] = np.asarray(rec["categories"],
                                               np.int64)
            features.append(out)
        return cls(int(d.get("num_rows", 0)), features)

    def save(self, path):
        """Atomic JSON write (a kill mid-save must never leave a
        truncated profile where a valid one stood)."""
        from ..utils.checkpoint import atomic_write_text
        atomic_write_text(os.fspath(path),
                          json.dumps(self.to_json_dict(),
                                     separators=(",", ":")) + "\n")

    @classmethod
    def load(cls, path):
        with open(os.fspath(path), "r", encoding="utf-8") as f:
            return cls.from_json_dict(json.load(f))

    # ------------------------------------------------- sidecar npz form
    # The binary cache and block-store sidecar persist the profile as a
    # few flat arrays next to the mappers (which already carry the
    # bounds); decode rebuilds the full profile from both.

    def encode_sidecar(self, arrays):
        b_max = max((len(r["counts"]) for r in self.features), default=1)
        counts = np.zeros((len(self.features), b_max), np.int64)
        for u, rec in enumerate(self.features):
            counts[u, :len(rec["counts"])] = rec["counts"]
        arrays["profile_counts"] = counts
        arrays["profile_missing"] = np.asarray(
            [rec["missing"] for rec in self.features], np.int64)
        arrays["profile_num_rows"] = np.asarray(self.num_rows)
        return arrays

    @classmethod
    def decode_sidecar(cls, z, ds):
        """Rebuild from a decoded dataset sidecar (mappers/maps/names
        already populated on `ds`). Returns None when the archive
        predates profiles — older caches stay loadable."""
        if "profile_num_rows" not in getattr(z, "files", ()):
            return None
        try:
            counts = np.asarray(z["profile_counts"], np.int64)
            missing = np.asarray(z["profile_missing"], np.int64)
            num_rows = int(z["profile_num_rows"])
            if counts.shape[0] != len(ds.bin_mappers):
                raise ValueError(
                    f"profile covers {counts.shape[0]} features, dataset "
                    f"has {len(ds.bin_mappers)}")
            counts_list = [counts[u, :m.num_bin]
                           for u, m in enumerate(ds.bin_mappers)]
            return cls.from_parts(ds.bin_mappers, ds.real_feature_idx,
                                  ds.feature_names, counts_list, num_rows,
                                  missing=missing)
        except (KeyError, ValueError, IndexError) as e:
            Log.warning("ignoring unusable dataset profile in cache: %s",
                        e)
            return None


def count_missing(feats, real_idx):
    """Per-used-feature NaN counts of a raw (N, F) feature matrix.
    Standard ingestion collapses NaN to 0.0 before this point
    (io/parser.py), so the counts are 0 there; paths that preserve raw
    NaN (future keep-NaN ingestion) report real counts through the
    same plumbing."""
    real_idx = np.asarray(real_idx, np.int64)
    return np.asarray([int(np.isnan(feats[:, j]).sum()) for j in real_idx],
                      np.int64)


def model_profile_path(model_path):
    return os.fspath(model_path) + PROFILE_SUFFIX
