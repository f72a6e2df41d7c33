"""CompiledPredictor: a trained ensemble frozen for online serving.

The training-side predict path (models/gbdt.py predict_raw) re-derives
stacked arrays per call and compiles on first use — fine for batch
scoring, wrong for a standing service where the FIRST request must not
pay a trace+compile. This module freezes the model once:

- the ensemble becomes immutable padded SoA device arrays (class-major
  stacked split_feature / threshold / decision_type / left_child /
  right_child / leaf_value, via GBDT._stacked_model_arrays), with the
  same round-toward--inf f32 threshold cast as the training-side device
  predictor (models/gbdt.py f32_safe_thresholds) so f32 traversal
  decisions equal the f64 host reference;
- raw-score, transformed (sigmoid/softmax, gbdt.py predict) and
  leaf-index kernels are jit-compiled once per ROW-COUNT BUCKET
  (powers of two up to max_batch_rows), and warm_up() AOT-compiles
  every bucket a request can hit at load so no request shape ever
  traces at request time (the default warms the traversal/leaf kernel
  all three serving endpoints dispatch; `warm_device_kernels=True`
  extends that to the all-device f32 variants);
- the persistent XLA compile cache (config.setup_compilation_cache) is
  wired in before the first compile, so a warm-process restart loads
  executables from disk instead of recompiling — sub-second startup.

Precision contract: traversal decisions are exact (the f32 threshold
cast preserves every f64 `<=` outcome for f32-representable inputs, and
category ids are exact in f32), so `predict_raw`/`predict` gather the
traversed leaf indices and reduce in f64 ON HOST — bit-identical to
GBDT's host predict path (a (B, T) int32 transfer plus a tiny matmul;
the traversal is the O(depth * B * T) part and stays on device). The
`_device` variants keep the whole pipeline on device in f32 (reduction
on the MXU) for throughput-bound callers that tolerate ~1e-6.

Linear-leaf models (models/linear_leaves.py) freeze their per-leaf
coefficient blocks into COEF_PAD-padded SoA arrays alongside the node
arrays and fuse the per-leaf dot product into the traversal kernels
(_linraw_kernel/_lintransformed_kernel) — one dispatch per request
block, same shape-stability rules, so a linear challenger hot-swaps
behind a constant incumbent with zero cold dispatches. The exact f32
precision keeps the linear reduce on host in f64, bit-identical to
GBDT's host path; bf16 stores coefficients in bfloat16 and the pinned
`accuracy_bound` grows a coefficient-rounding term (see
_pin_accuracy_bound).
"""

import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..config import compile_cache_hits, setup_compilation_cache
from ..models.gbdt import create_boosting, device_traverse, f32_safe_thresholds
from ..models.tree import Tree
from ..utils import common
from ..utils.log import Log

DEFAULT_MAX_BATCH_ROWS = 4096
# serving_precision values (docs/Serving.md): `f32` is the exact
# contract (device f32 traversal + host f64 reduction, bit-identical
# to the reference); `bf16` keeps the traversal DECISIONS exact (f32
# compare against f32-safe thresholds) but gathers leaf values and
# runs the class reduction in bfloat16 on device — the Booster
# accelerator result (arXiv:2011.02022): ensemble throughput lives in
# node layout + reduced value precision, and the value stage is where
# precision can drop without moving a single traversal decision. The
# bf16 path ships a PINNED accuracy bound (`accuracy_bound`, computed
# from the frozen leaf values at load) that the skew monitor adopts
# as its tolerance, so monitoring stays armed and quiet by
# construction.
SERVING_PRECISIONS = ("f32", "bf16")


@jax.jit
def _leaf_kernel(xb, sf, thr, cat, lc, rc, node0, depth):
    """(B, F) f32 rows -> (B, T) int32 leaf indices. `depth` is a
    TRACED operand (fori_loop handles dynamic trip counts), so two
    model generations of different depth share one executable — depth
    must never be a recompile trigger across a hot-swap."""
    node = device_traverse(xb, sf, thr, cat, lc, rc, node0, depth)
    return (~node).astype(jnp.int32)


@jax.jit
def _raw_kernel(xb, sf, thr, cat, lc, rc, lv, node0, cls_onehot, depth):
    """(B, F) f32 rows -> (B, K) f32 raw class sums (MXU reduction).
    HIGHEST: the TPU's default f32 contraction rounds its operands to
    bfloat16, which the ~1e-6 contract of the `_device` variants cannot
    absorb."""
    node = device_traverse(xb, sf, thr, cat, lc, rc, node0, depth)
    t_idx = jnp.arange(sf.shape[0])
    vals = lv[t_idx[None, :], ~node]                        # (B, T)
    return jnp.dot(vals, cls_onehot,
                   precision=jax.lax.Precision.HIGHEST)     # (B, K)


@functools.partial(jax.jit, static_argnums=(10,))
def _transformed_kernel(xb, sf, thr, cat, lc, rc, lv, node0, cls_onehot,
                        depth, sigmoid):
    """(B, F) f32 rows -> (B, K) f32 transformed predictions
    (gbdt.cpp:622-636 semantics: binary sigmoid / multiclass softmax /
    raw passthrough)."""
    raw = _raw_kernel(xb, sf, thr, cat, lc, rc, lv, node0, cls_onehot,
                      depth)
    if sigmoid > 0 and cls_onehot.shape[1] == 1:
        return 1.0 / (1.0 + jnp.exp(-2.0 * sigmoid * raw))
    if cls_onehot.shape[1] > 1:
        return jax.nn.softmax(raw, axis=1)
    return raw


@jax.jit
def _raw16_kernel(xb, sf, thr, cat, lc, rc, lv16, node0, onehot16, depth):
    """bf16 value stage: EXACT f32 traversal (identical decisions to
    the f32 kernels — thr stays the f32-safe cast), then a bfloat16
    leaf-value gather and a bf16 x bf16 class reduction accumulated in
    f32 on the MXU. Node arrays may ride the compact int16 layout
    (serving_precision docstring at module top)."""
    node = device_traverse(xb, sf, thr, cat, lc, rc, node0, depth)
    t_idx = jnp.arange(sf.shape[0])
    vals = lv16[t_idx[None, :], ~node]                      # (B, T) bf16
    return jax.lax.dot(vals, onehot16,
                       preferred_element_type=jnp.float32)  # (B, K) f32


@functools.partial(jax.jit, static_argnums=(10,))
def _transformed16_kernel(xb, sf, thr, cat, lc, rc, lv16, node0, onehot16,
                          depth, sigmoid):
    """bf16 raw stage + the f32 transform (sigmoid/softmax run on the
    f32 accumulator output, so the transform adds no bf16 error)."""
    raw = _raw16_kernel(xb, sf, thr, cat, lc, rc, lv16, node0, onehot16,
                        depth)
    if sigmoid > 0 and onehot16.shape[1] == 1:
        return 1.0 / (1.0 + jnp.exp(-2.0 * sigmoid * raw))
    if onehot16.shape[1] > 1:
        return jax.nn.softmax(raw, axis=1)
    return raw


def _linear_leaf_values(xb, node, lv, const, coef, cfeat, ccnt):
    """(B, T) per-lane leaf outputs for linear-leaf models, fused with
    the traversal result: gather each (row, tree) lane's leaf model —
    intercept, COEF_PAD coefficient/feature slots, live count — dot the
    row's gathered feature values against the coefficients, and fall
    back to the constant leaf value where the lane's leaf is constant
    (cnt == 0) or a live feature is NaN (missing values have no
    coordinate; Tree._linear_values host semantics). Arithmetic is f32
    throughout; bf16 precision passes bf16-stored value arrays which
    upcast at the gather, so storage rounding is the only bf16 error
    (the pinned accuracy_bound's coefficient term)."""
    leaf = ~node                                             # (B, T)
    b = xb.shape[0]
    t_idx = jnp.arange(lv.shape[0])[None, :]                 # (1, T)
    base = lv[t_idx, leaf].astype(jnp.float32)               # (B, T)
    cst = const[t_idx, leaf].astype(jnp.float32)             # (B, T)
    cn = ccnt[t_idx, leaf]                                   # (B, T)
    j = jnp.arange(coef.shape[2])[None, None, :]             # (1, 1, C)
    co = coef[t_idx[:, :, None], leaf[:, :, None], j] \
        .astype(jnp.float32)                                 # (B, T, C)
    ft = cfeat[t_idx[:, :, None], leaf[:, :, None], j]       # (B, T, C)
    xf = xb[jnp.arange(b)[:, None, None], ft]                # (B, T, C)
    valid = j < cn[:, :, None]
    live_nan = jnp.isnan(xf) & valid
    dot = jnp.sum(jnp.where(valid & ~jnp.isnan(xf), co * xf, 0.0),
                  axis=-1)
    lin = cst + dot
    use_lin = (cn > 0) & ~jnp.any(live_nan, axis=-1)
    return jnp.where(use_lin, lin, base)


@jax.jit
def _linraw_kernel(xb, sf, thr, cat, lc, rc, lv, node0, cls_onehot, depth,
                   const, coef, cfeat, ccnt):
    """(B, F) f32 rows -> (B, K) f32 raw class sums with the per-leaf
    linear dot fused into the same program as the traversal (one
    dispatch per request block, like the constant-leaf _raw_kernel;
    class reduction accumulates f32 on the MXU)."""
    node = device_traverse(xb, sf, thr, cat, lc, rc, node0, depth)
    vals = _linear_leaf_values(xb, node, lv, const, coef, cfeat, ccnt)
    return jax.lax.dot(vals, cls_onehot.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnums=(9,))
def _lintransformed_kernel(xb, sf, thr, cat, lc, rc, lv, node0, cls_onehot,
                           sigmoid, depth, const, coef, cfeat, ccnt):
    raw = _linraw_kernel(xb, sf, thr, cat, lc, rc, lv, node0, cls_onehot,
                         depth, const, coef, cfeat, ccnt)
    if sigmoid > 0 and cls_onehot.shape[1] == 1:
        return 1.0 / (1.0 + jnp.exp(-2.0 * sigmoid * raw))
    if cls_onehot.shape[1] > 1:
        return jax.nn.softmax(raw, axis=1)
    return raw


def _bf16_round(arr):
    """Host-side f64 view of an array after a round-trip through
    bfloat16 (the rounding the bf16 leaf gather applies on device)."""
    return np.asarray(jnp.asarray(arr, jnp.bfloat16).astype(jnp.float32),
                      np.float64)


def _compact_int(arr, lo=-32768, hi=32767):
    """int16 copy when every value fits (the compact node layout —
    half the traversal gather bytes), int32 otherwise."""
    a = np.asarray(arr)
    if a.size and (a.min() < lo or a.max() > hi):
        return a.astype(np.int32)
    return a.astype(np.int16)


# Shape-stable padding (hot-swap support, docs/Fleet.md): the tree
# count pads to a multiple of TREE_PAD, so two model GENERATIONS of
# the same training recipe freeze to IDENTICAL kernel shapes — a
# challenger loaded behind the incumbent warms from the in-process jit
# cache (or the persistent disk cache) instead of recompiling, which
# is what keeps p99 flat through a hot-swap. (Depth is a TRACED kernel
# operand, never a compile key — see _leaf_kernel.) Padded trees are a
# frozen root leaf with value 0 and a zero one-hot row: they
# contribute nothing to any class sum, and the leaf-index surface
# slices back to the real tree count. Cost: <= (TREE_PAD-1) extra tree
# lanes of gather work.
TREE_PAD = 16
# the node axis (max nodes/leaves per tree) pads too: two generations
# with the same num_leaves knob can still grow different ACTUAL leaf
# counts, and a one-column difference would force a full recompile
NODE_PAD = 32
# linear-leaf models: every leaf's coefficient block pads to this fixed
# width, so two generations with different realized leaf-model widths
# (or a linear challenger behind a linear incumbent) still freeze to
# identical kernel shapes. Training's `linear_max_features` knob must
# stay <= COEF_PAD (config.py enforces the default; from_model_file
# re-checks loaded models).
COEF_PAD = 8


def _pad_up(n, multiple):
    n = max(int(n), 1)
    return ((n + multiple - 1) // multiple) * multiple


def _pad_rows(arr, pad, fill=0):
    """Append `pad` rows of `fill` along axis 0 (dtype preserved)."""
    a = np.asarray(arr)
    if pad <= 0:
        return a
    return np.concatenate(
        [a, np.full((pad,) + a.shape[1:], fill, a.dtype)])


def _pad_grid(arr, row_pad, col_multiple=NODE_PAD, fill=0):
    """Row padding + column padding to a multiple (the (T, nodes) SoA
    arrays; padded node slots are unreachable — no child edge points
    at them)."""
    a = _pad_rows(arr, row_pad, fill)
    cols = _pad_up(a.shape[1], col_multiple) - a.shape[1]
    if cols <= 0:
        return a
    return np.concatenate(
        [a, np.full((a.shape[0], cols), fill, a.dtype)], axis=1)


class CompiledPredictor:
    """A frozen, pre-compiled view of one trained model.

    Build with `from_booster` (a live GBDT/DART/GOSS) or
    `from_model_file` (the text format). Immutable after construction:
    later training on the source booster never changes served results.
    """

    # set by from_model_file (sidecar auto-discovery); None when frozen
    # from a live booster
    model_path = None
    profile_path = None
    profile = None
    # flipped in __init__ when the booster carries linear-leaf trees
    # (models/linear_leaves.py); class default keeps the empty-model
    # early return consistent
    is_linear = False

    def __init__(self, booster, num_iteration=-1,
                 max_batch_rows=DEFAULT_MAX_BATCH_ROWS, row_buckets=None,
                 warmup=True, warm_device_kernels=False,
                 serving_precision="f32"):
        setup_compilation_cache(getattr(booster, "config", None))
        if serving_precision not in SERVING_PRECISIONS:
            raise ValueError(
                f"serving_precision must be one of {SERVING_PRECISIONS}, "
                f"got {serving_precision!r}")
        n_used = booster._num_used_models(num_iteration)
        self.num_class = max(int(booster.num_class), 1)
        self.sigmoid = float(booster.sigmoid)
        self.num_features = int(booster.max_feature_idx) + 1
        self.num_trees = n_used
        self.feature_names = list(getattr(booster, "feature_names", []))
        self.max_batch_rows = int(max_batch_rows)
        self.serving_precision = serving_precision
        self.accuracy_bound = 0.0
        self.buckets = tuple(sorted(set(
            int(b) for b in (row_buckets or _default_buckets(
                self.max_batch_rows)))))
        self.stats = {"warmup_s": 0.0, "compile_cache_hits": 0,
                      "warm_dispatches": 0, "cold_dispatches": 0,
                      "buckets": list(self.buckets),
                      "serving_precision": serving_precision}
        self._warmed = set()
        if n_used == 0:
            self.depth = 0
            return
        sf, thr, dt, lc, rc, lv, has_split, depth = \
            booster._stacked_model_arrays(n_used)
        self.depth = int(depth)
        # shape-stable padding (TREE_PAD comment above): the kernel
        # shapes depend on the PADDED counts only; depth rides as a
        # traced operand
        t_pad = _pad_up(n_used, TREE_PAD)
        self._depth_arg = np.int32(self.depth)
        pad = t_pad - n_used
        # frozen copies: the booster's cache arrays mutate as training
        # continues; the served model must not. The exact host-reduce
        # arrays stay UNPADDED (the (N, T) leaf gather slices back to
        # real trees); the device SoA arrays pad.
        self._lv64 = np.array(lv, dtype=np.float64)             # (T, L)
        onehot = (np.arange(n_used)[:, None] % self.num_class
                  == np.arange(self.num_class)[None, :])
        self._onehot64 = onehot.astype(np.float64)              # (T, K)
        sf_p = _pad_grid(np.array(sf), pad)
        thr_p = _pad_grid(np.array(thr), pad)
        dt_p = _pad_grid(np.array(dt), pad)
        lc_p = _pad_grid(np.array(lc), pad)
        rc_p = _pad_grid(np.array(rc), pad)
        lv_p = _pad_grid(np.array(lv), pad)          # zero leaf values
        onehot_p = _pad_rows(onehot, pad)            # zero one-hot rows
        node0_np = np.concatenate(
            [np.where(has_split, 0, ~0).astype(np.int32),
             np.full(pad, ~0, np.int32)])            # padded: root leaf
        thr32 = f32_safe_thresholds(thr_p, dt_p)
        self._dev = (
            jnp.asarray(sf_p),
            jnp.asarray(thr32, jnp.float32),
            jnp.asarray(dt_p == Tree.CATEGORICAL),
            jnp.asarray(lc_p),
            jnp.asarray(rc_p),
            jnp.asarray(node0_np),
        )
        # the f32 device value arrays back only the off-endpoint
        # `_device` throughput variants — built lazily on first use so
        # a serving fleet (exact path: host f64 reduce; bf16 path: the
        # bf16 arrays) never pays a second value buffer per model
        self._lv_np = lv_p
        self._onehot_np = onehot_p.astype(np.float32)
        self._lv32 = self._onehot32 = None
        # linear-leaf models (models/linear_leaves.py): freeze the
        # per-leaf coefficient blocks into COEF_PAD-padded SoA arrays
        # alongside the node arrays. Constant models skip all of this —
        # their kernel set and shapes are untouched.
        lin = booster._stacked_linear_arrays(n_used)
        self.is_linear = lin is not None
        if self.is_linear:
            const, coef, cfeat, ccnt = lin
            if coef.shape[2] > COEF_PAD:
                raise ValueError(
                    f"model's widest leaf model has {coef.shape[2]} "
                    f"coefficients but serving pads to COEF_PAD="
                    f"{COEF_PAD}; retrain with linear_max_features <= "
                    f"{COEF_PAD}")
            l_pad = lv_p.shape[1]
            cw = COEF_PAD - coef.shape[2]

            def pad3(a, fill=0):
                a = np.concatenate(
                    [a, np.full((a.shape[0], l_pad - a.shape[1])
                                + a.shape[2:], fill, a.dtype)], axis=1)
                if a.ndim == 3 and cw > 0:
                    a = np.concatenate(
                        [a, np.full(a.shape[:2] + (cw,), fill, a.dtype)],
                        axis=2)
                return _pad_rows(a, pad, fill)

            # host f64 exact-path arrays stay UNPADDED on the tree axis
            # (like _lv64); device arrays pad on every axis
            self._lin_const64 = np.concatenate(
                [const, np.zeros((n_used, l_pad - const.shape[1]))],
                axis=1)
            self._lin_coef64 = pad3(coef)[:n_used]
            self._lin_feat = pad3(cfeat)[:n_used]
            self._lin_cnt = pad3(ccnt)[:n_used]
            store = jnp.bfloat16 if serving_precision == "bf16" else \
                jnp.float32
            self._lin_dev = (
                jnp.asarray(pad3(const), store),
                jnp.asarray(pad3(coef), store),
                jnp.asarray(pad3(cfeat)),
                jnp.asarray(pad3(ccnt)),
            )
        if serving_precision == "bf16":
            # compact node layout (int16 where node/feature ids fit —
            # at serving tree sizes they always do) + bf16 value arrays;
            # thresholds stay the f32-safe cast so every traversal
            # decision is IDENTICAL to the exact path
            self._dev16 = (
                jnp.asarray(_compact_int(sf_p)),
                self._dev[1],
                self._dev[2],
                jnp.asarray(_compact_int(lc_p)),
                jnp.asarray(_compact_int(rc_p)),
                jnp.asarray(_compact_int(node0_np)),
            )
            self._lv16 = jnp.asarray(lv_p, jnp.bfloat16)
            self._onehot16 = jnp.asarray(onehot_p.astype(np.float32),
                                         jnp.bfloat16)   # 0/1: exact
            self.accuracy_bound = self._pin_accuracy_bound(
                n_used, np.array(sf), np.array(thr))
        if warmup:
            self.warm_up(device_kernels=warm_device_kernels)

    def _pin_accuracy_bound(self, n_used, sf=None, thr=None):
        """Worst-case |bf16 output - exact f64 output| over ANY input,
        derived from the frozen leaf values: traversal decisions are
        exact, so the only error sources are the bf16 rounding of each
        gathered leaf value (bounded per tree by its worst-rounded
        leaf) and the f32 accumulation of the class reduction. The
        transform can amplify raw error (binary: dp/draw <= sigmoid/2),
        so the pinned bound covers raw AND transformed outputs. A 2x
        margin absorbs rounding-mode asymmetries. The serving skew
        monitor adopts this as its tolerance (server.build_monitors),
        keeping shadow scoring armed and quiet by construction.

        Linear leaves add a coefficient-rounding term: per tree, the
        worst leaf's |const - bf16(const)| + sum_j |coef_j -
        bf16(coef_j)| * env(feat_j), where env(f) is the model's OWN
        calibration envelope for feature f — twice the largest
        |threshold| any split placed on f (floored at 1.0). Inputs
        inside the envelope are covered by construction; a deployment
        feeding features far outside the range its splits ever tested
        is already out of calibration, and the skew monitor (whose
        tolerance this bound becomes) will surface it."""
        err_t = np.abs(self._lv64 - _bf16_round(self._lv64)).max(axis=1)
        if getattr(self, "is_linear", False):
            env = np.ones(self.num_features, np.float64)
            if sf is not None and sf.size:
                np.maximum.at(env, sf.reshape(-1),
                              2.0 * np.abs(thr.reshape(-1)))
            cerr = (np.abs(self._lin_coef64
                           - _bf16_round(self._lin_coef64))
                    * env[self._lin_feat])
            valid = (np.arange(self._lin_coef64.shape[2])[None, None, :]
                     < self._lin_cnt[:, :, None])
            lin_err_t = (np.abs(self._lin_const64
                                - _bf16_round(self._lin_const64))
                         + np.where(valid, cerr, 0.0).sum(axis=2)
                         ).max(axis=1)
            err_t = np.maximum(err_t, lin_err_t)
        raw_bound = float((err_t @ self._onehot64).max())
        mag_t = np.abs(self._lv64).max(axis=1)
        if getattr(self, "is_linear", False):
            # the f32-accumulation slack scales with the largest value a
            # lane can contribute — for a linear leaf that is the whole
            # envelope-bounded dot, not just the constant fallback
            lin_mag_t = (np.abs(self._lin_const64)
                         + np.where(valid,
                                    np.abs(self._lin_coef64)
                                    * env[self._lin_feat],
                                    0.0).sum(axis=2)).max(axis=1)
            mag_t = np.maximum(mag_t, lin_mag_t)
        mags = float((mag_t @ self._onehot64).max())
        slack = mags * n_used * float(np.finfo(np.float32).eps)
        factor = 1.0
        if self.sigmoid > 0 and self.num_class == 1:
            factor = max(1.0, self.sigmoid / 2.0)
        return 2.0 * factor * (raw_bound + slack)

    # ---------------------------------------------------------- constructors
    @classmethod
    def from_booster(cls, booster, num_iteration=-1, **kw):
        """Freeze a live booster (GBDT/DART/GOSS or a python-API
        Booster) into a CompiledPredictor."""
        gbdt = getattr(booster, "gbdt", booster)  # basic.Booster wraps
        return cls(gbdt, num_iteration=num_iteration, **kw)

    @classmethod
    def from_model_file(cls, path, num_iteration=-1, **kw):
        """Load the text model format and freeze it. Auto-discovers the
        `<model>.profile.json` dataset-profile sidecar (io/profile.py)
        when one sits next to the model: `predictor.profile` then
        carries the training baseline the drift monitor needs, so
        serving gets drift monitoring without an explicit --profile
        flag (and a registry hot-swap rebuilds monitors against the
        NEW model's own baseline)."""
        booster = create_boosting("gbdt", path)
        with open(path) as f:
            booster.load_model_from_string(f.read())
        inst = cls(booster, num_iteration=num_iteration, **kw)
        inst.model_path = os.fspath(path)
        from ..io.profile import DatasetProfile, model_profile_path
        sidecar = model_profile_path(path)
        if os.path.exists(sidecar):
            try:
                inst.profile = DatasetProfile.load(sidecar)
                inst.profile_path = sidecar
            except (OSError, ValueError) as e:
                Log.warning("ignoring unreadable profile sidecar %s: %s",
                            sidecar, e)
        return inst

    # --------------------------------------------------------------- warmup
    def warm_up(self, device_kernels=False):
        """AOT-compile every (kernel, bucket) pair a request can hit so
        no request shape ever traces at request time. The default warms
        the traversal/leaf kernel only — predict, predict_raw AND
        predict_leaf_index all dispatch it (the f64 reduction is host-
        side); `device_kernels=True` additionally warms the all-device
        f32 raw/transformed kernels for callers using the `_device`
        throughput variants. With the persistent compile cache active,
        a warm-process restart loads executables from disk —
        `stats["compile_cache_hits"]` counts how many did."""
        t0 = time.time()
        hits0 = compile_cache_hits()
        from ..telemetry.ledger import LEDGER
        bf16 = self.serving_precision == "bf16"
        for b in self.buckets:
            xb = jnp.zeros((b, self.num_features), jnp.float32)
            # the compile ledger attributes each bucket's lowering(s):
            # /metricz shows which row bucket cost the warmup time
            with LEDGER.label(f"serving_bucket_{b}"):
                jax.block_until_ready(self._dispatch_leaf(xb))
                self._warmed.add(("leaf", b))
                if bf16 and self.is_linear:
                    # linear bf16 endpoints dispatch the fused linear
                    # kernels; the constant bf16 pair is never hit
                    jax.block_until_ready(self._dispatch_linraw(xb))
                    jax.block_until_ready(
                        self._dispatch_lintransformed(xb))
                    self._warmed.update((("linraw", b), ("lintr", b)))
                elif bf16:
                    # predict/predict_raw dispatch the bf16 kernels —
                    # every endpoint's (kernel, bucket) pair pre-warms
                    jax.block_until_ready(self._dispatch_raw16(xb))
                    jax.block_until_ready(self._dispatch_transformed16(xb))
                    self._warmed.update((("raw16", b), ("tr16", b)))
                if device_kernels and self.is_linear and not bf16:
                    jax.block_until_ready(self._dispatch_linraw(xb))
                    jax.block_until_ready(
                        self._dispatch_lintransformed(xb))
                    self._warmed.update((("linraw", b), ("lintr", b)))
                elif device_kernels and not self.is_linear:
                    jax.block_until_ready(self._dispatch_raw32(xb))
                    jax.block_until_ready(self._dispatch_transformed32(xb))
                    self._warmed.update((("raw32", b), ("tr32", b)))
        self.stats["warmup_s"] = round(time.time() - t0, 3)
        self.stats["compile_cache_hits"] = compile_cache_hits() - hits0
        Log.info("CompiledPredictor warm: %d trees, %d buckets (max %d "
                 "rows) in %.2fs (%d persistent-cache hits)",
                 self.num_trees, len(self.buckets), self.max_batch_rows,
                 self.stats["warmup_s"], self.stats["compile_cache_hits"])
        return self

    # ------------------------------------------------------------ dispatch
    def _dispatch_leaf(self, xb):
        sf, thr, cat, lc, rc, node0 = self._dev
        return _leaf_kernel(xb, sf, thr, cat, lc, rc, node0,
                            self._depth_arg)

    def _f32_values(self):
        if self._lv32 is None:
            self._lv32 = jnp.asarray(self._lv_np, jnp.float32)
            self._onehot32 = jnp.asarray(self._onehot_np)
        return self._lv32, self._onehot32

    def _dispatch_raw32(self, xb):
        sf, thr, cat, lc, rc, node0 = self._dev
        lv32, onehot32 = self._f32_values()
        return _raw_kernel(xb, sf, thr, cat, lc, rc, lv32, node0,
                           onehot32, self._depth_arg)

    def _dispatch_transformed32(self, xb):
        sf, thr, cat, lc, rc, node0 = self._dev
        lv32, onehot32 = self._f32_values()
        return _transformed_kernel(xb, sf, thr, cat, lc, rc, lv32,
                                   node0, onehot32,
                                   self._depth_arg, self.sigmoid)

    def _dispatch_raw16(self, xb):
        sf, thr, cat, lc, rc, node0 = self._dev16
        return _raw16_kernel(xb, sf, thr, cat, lc, rc, self._lv16, node0,
                             self._onehot16, self._depth_arg)

    def _dispatch_transformed16(self, xb):
        sf, thr, cat, lc, rc, node0 = self._dev16
        return _transformed16_kernel(xb, sf, thr, cat, lc, rc, self._lv16,
                                     node0, self._onehot16,
                                     self._depth_arg, self.sigmoid)

    # linear-leaf fused kernels: ONE source kernel pair serves both
    # precisions — the f32 ladder passes f32 value arrays, the bf16
    # ladder passes the bf16-stored ones plus the compact node layout
    # (values upcast at the gather; each dtype signature is its own
    # executable, warmed by warm_up). Traversal thresholds are the
    # f32-safe cast either way, so decisions never move.
    def _linear_args(self):
        if self.serving_precision == "bf16":
            return self._dev16, (self._lv16, self._onehot16)
        return self._dev, self._f32_values()

    def _dispatch_linraw(self, xb):
        (sf, thr, cat, lc, rc, node0), (lv, onehot) = self._linear_args()
        const, coef, cfeat, ccnt = self._lin_dev
        return _linraw_kernel(xb, sf, thr, cat, lc, rc, lv, node0, onehot,
                              self._depth_arg, const, coef, cfeat, ccnt)

    def _dispatch_lintransformed(self, xb):
        (sf, thr, cat, lc, rc, node0), (lv, onehot) = self._linear_args()
        const, coef, cfeat, ccnt = self._lin_dev
        return _lintransformed_kernel(
            xb, sf, thr, cat, lc, rc, lv, node0, onehot, self.sigmoid,
            self._depth_arg, const, coef, cfeat, ccnt)

    def _linear_host_values(self, x, leaves):
        """Exact-path value stage for linear models: (N, T) f64 per-tree
        outputs from device-traversed leaf indices, mirroring
        Tree._linear_values BIT-FOR-BIT — same f64 arithmetic, same
        sequential accumulation order over coefficient slots (the
        COEF_PAD padding slots add an exact 0.0, see the comment in
        tree.py), same NaN-fallback semantics."""
        t_idx = np.arange(self.num_trees)[None, :]
        base = self._lv64[t_idx, leaves]                     # (N, T)
        cst = self._lin_const64[t_idx, leaves]
        cn = self._lin_cnt[t_idx, leaves]                    # (N, T)
        co = self._lin_coef64[t_idx[:, :, None], leaves[:, :, None],
                              np.arange(COEF_PAD)[None, None, :]]
        ft = self._lin_feat[t_idx[:, :, None], leaves[:, :, None],
                            np.arange(COEF_PAD)[None, None, :]]
        xf = x.astype(np.float64)[
            np.arange(x.shape[0])[:, None, None], ft]        # (N, T, C)
        valid = (np.arange(COEF_PAD)[None, None, :] < cn[:, :, None])
        live_nan = np.isnan(xf) & valid
        lin = cst.copy()
        for j in range(COEF_PAD):
            lin += np.where(valid[:, :, j] & ~np.isnan(xf[:, :, j]),
                            co[:, :, j] * xf[:, :, j], 0.0)
        return np.where((cn > 0) & ~np.any(live_nan, axis=2), lin, base)

    def _canon(self, x):
        """(N, num_features) f32 view of arbitrary row input: width is
        CANONICALIZED (narrow pads with 0.0 — absent trailing features,
        LibSVM-style; wide truncates — no split reads past
        max_feature_idx) so every dispatch reuses the warmed shapes."""
        x = np.atleast_2d(np.asarray(x, dtype=np.float32))
        if x.ndim != 2:
            raise ValueError(f"rows must be 2-D, got shape {x.shape}")
        f = x.shape[1]
        if f < self.num_features:
            x = np.pad(x, ((0, 0), (0, self.num_features - f)))
        elif f > self.num_features:
            x = x[:, :self.num_features]
        return x

    def _bucket(self, n):
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def _blocks(self, x, dispatch, kernel):
        """Pad-to-bucket dispatch over row blocks; returns the stacked
        host result. Requests beyond max_batch_rows chunk through the
        largest bucket (still zero recompilation)."""
        n = x.shape[0]
        outs = []
        top = self.buckets[-1]
        s = 0
        while s < n:
            xb = x[s:s + top]
            b = self._bucket(xb.shape[0])
            if (kernel, b) not in self._warmed:  # un-warmed kernel/shape
                self.stats["cold_dispatches"] += 1
                self._warmed.add((kernel, b))
            else:
                self.stats["warm_dispatches"] += 1
            pad = b - xb.shape[0]
            if pad:
                xb = np.pad(xb, ((0, pad), (0, 0)))
            outs.append(np.asarray(dispatch(jnp.asarray(xb)))[:b - pad])
            s += top
        return np.concatenate(outs, axis=0)

    # ------------------------------------------------------------- predict
    def predict_leaf_index(self, x):
        """(N, T) int32 leaf indices (predictor.hpp:108-118)."""
        x = self._canon(x)
        if self.num_trees == 0 or x.shape[0] == 0:
            return np.zeros((x.shape[0], self.num_trees), dtype=np.int32)
        # slice the shape-stable tree padding back off (TREE_PAD)
        return self._blocks(x, self._dispatch_leaf,
                            "leaf")[:, :self.num_trees]

    def predict_raw(self, x):
        """(N, K) f64 raw scores. Exact precision: device traversal +
        host f64 reduction, matching GBDT.predict_raw's host path
        bit-for-bit (module docstring). `serving_precision="bf16"`:
        all-device bf16 value stage, within `accuracy_bound` of the
        exact path by construction."""
        x = self._canon(x)
        n = x.shape[0]
        if self.num_trees == 0 or n == 0:
            return np.zeros((n, self.num_class))
        if self.serving_precision == "bf16":
            if self.is_linear:
                return self._blocks(x, self._dispatch_linraw,
                                    "linraw").astype(np.float64)
            return self._blocks(x, self._dispatch_raw16,
                                "raw16").astype(np.float64)
        leaves = self._blocks(x, self._dispatch_leaf,
                              "leaf")[:, :self.num_trees]     # (N, T)
        if self.is_linear:
            vals = self._linear_host_values(x, leaves)       # (N, T) f64
            # GBDT's host path reduces each class with a pairwise
            # np.sum over its tree subset; a BLAS matmul associates
            # differently in the last ulp, so mirror the sum exactly
            cls = np.arange(self.num_trees) % self.num_class
            out = np.empty((x.shape[0], self.num_class))
            for k in range(self.num_class):
                out[:, k] = vals[:, cls == k].sum(axis=1)
            return out
        vals = self._lv64[np.arange(self.num_trees)[None, :], leaves]
        return vals @ self._onehot64                         # (N, K) f64

    def predict(self, x):
        """(N, K) f64 transformed predictions (gbdt.py predict:
        binary sigmoid / multiclass softmax / raw passthrough). The
        bf16 precision transforms on device from the f32 accumulator
        output (`accuracy_bound` covers the transformed value too)."""
        if self.serving_precision == "bf16" and self.num_trees > 0:
            x = self._canon(x)
            if x.shape[0] == 0:
                return np.zeros((0, self.num_class))
            if self.is_linear:
                return self._blocks(x, self._dispatch_lintransformed,
                                    "lintr").astype(np.float64)
            return self._blocks(x, self._dispatch_transformed16,
                                "tr16").astype(np.float64)
        raw = self.predict_raw(x)
        if self.sigmoid > 0 and self.num_class == 1:
            return 1.0 / (1.0 + np.exp(-2.0 * self.sigmoid * raw))
        if self.num_class > 1:
            return common.softmax(raw, axis=1)
        return raw

    def predict_raw_device(self, x):
        """All-device f32 raw scores (MXU reduction): the throughput
        path; ~1e-6 of predict_raw."""
        x = self._canon(x)
        n = x.shape[0]
        if self.num_trees == 0 or n == 0:
            return np.zeros((n, self.num_class))
        if self.is_linear:
            # linear models route the device variants through the fused
            # linear kernels (bf16 predictors: bf16-stored values —
            # `accuracy_bound` applies instead of the ~1e-6 f32 figure)
            return self._blocks(x, self._dispatch_linraw,
                                "linraw").astype(np.float64)
        return self._blocks(x, self._dispatch_raw32,
                            "raw32").astype(np.float64)

    def predict_device(self, x):
        """All-device f32 transformed predictions; ~1e-6 of predict."""
        x = self._canon(x)
        n = x.shape[0]
        if self.num_trees == 0 or n == 0:
            return np.zeros((n, self.num_class))
        if self.is_linear:
            return self._blocks(x, self._dispatch_lintransformed,
                                "lintr").astype(np.float64)
        return self._blocks(x, self._dispatch_transformed32,
                            "tr32").astype(np.float64)

    # --------------------------------------------------------------- info
    def describe(self):
        """JSON-ready model card for `/healthz`."""
        return {
            "num_trees": self.num_trees,
            "num_class": self.num_class,
            "num_features": self.num_features,
            "depth": self.depth,
            "sigmoid": self.sigmoid,
            "max_batch_rows": self.max_batch_rows,
            "buckets": list(self.buckets),
            "serving_precision": self.serving_precision,
            "accuracy_bound": self.accuracy_bound,
            "is_linear": self.is_linear,
            "model_path": self.model_path,
            "has_profile": self.profile is not None,
        }


def _default_buckets(max_batch_rows):
    """Powers of two up to (and including a final bucket covering)
    max_batch_rows: request row counts round up to one of O(log N)
    compiled shapes, <= 2x padded-row overhead."""
    out = []
    b = 1
    while b < max_batch_rows:
        out.append(b)
        b <<= 1
    out.append(max_batch_rows)
    return out
