#!/usr/bin/env python3
"""Chip smoke: the trainer and the predictor, once, on the TPU.

Drives the normal entry points at the full width of the model bench.py
is built around — synthetic HIGGS-shaped binary data from a seed,
1,000,000 x 28, max_bin=255, num_leaves=63, 20 iterations, every other
parameter default — and checks what comes out:

  K  kernels  each Pallas histogram kernel, compiled (never interpreted)
              at F=28/B=255, against an f64 numpy histogram; the
              partition kernel at the smoke's row count against the XLA
              formulation it replaces, every word bit-equal
  A  train    lgb.Dataset -> lgb.train (fused lax.scan, leaf-contiguous
              builder, Pallas segment kernel); 20 trees x 63 leaves; the
              root's left-child count of tree 0 recounted in numpy;
              training AUC >= 0.85
  B  predict  booster.predict on the full matrix (device traversal)
              against the host f64 path on a 10k-row sample
  C  serve    CompiledPredictor + the HTTP server in this process;
              POST /predict with 1, 256 and 10,000 rows against
              Booster.predict; /metricz cold_dispatches == 0
  D  (--devices 4) the same shape, tree_learner=data over a 4-device
              mesh, against the one-chip model of stage A

One process, so one owner of the chip. A stage that raises ends the run
with a non-zero exit; nothing is retried on another path. Without a TPU
the script exits non-zero before doing any work. `--allow-cpu --rows N`
is a dry run for a machine without one: it prints `platform: cpu`, runs
the kernels through the Pallas interpreter and whatever path `auto`
picks there, and never prints the pass line.

The last line of a passing run is the JSON object
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
Timings are printed for information (compile and run apart); they are a
smoke's, not a benchmark's.
"""

import argparse
import json
import sys
import threading
import time
import urllib.request

import numpy as np

ROWS, FEATURES, SEED = 1_000_000, 28, 42
PARAMS = {"objective": "binary", "max_bin": 255, "num_leaves": 63}
ITERATIONS = 20
AUC_FLOOR = 0.85
SAMPLE_ROWS = 10_000
# the serial == data-parallel tree comparison is a report, not a gate:
# a few iterations say whether the Kahan-pair exchange holds on the chip
EQUALITY_ITERATIONS = 3

_COMPILE_EVENTS = "/jax/core/compile/"


class _Clock:
    """Wall time of a stage, split into jit compile (every
    /jax/core/compile/* duration jax reports: trace, lower, backend
    compile or cache load) and the rest."""

    def __init__(self):
        self.compile_s = 0.0

    def listen(self, name, secs, **_):
        if name.startswith(_COMPILE_EVENTS):
            self.compile_s += secs

    def stage(self):
        return time.perf_counter(), self.compile_s

    def split(self, start):
        t0, c0 = start
        wall = time.perf_counter() - t0
        comp = min(self.compile_s - c0, wall)
        return wall, comp, wall - comp


def make_data(n, seed=SEED):
    """HIGGS-shaped synthetic binary task (bench.py's generator)."""
    rng = np.random.RandomState(seed)
    x = rng.randn(n, FEATURES).astype(np.float32)
    w = rng.randn(FEATURES).astype(np.float32) / np.sqrt(FEATURES)
    logit = x @ w + 0.5 * rng.randn(n).astype(np.float32)
    return x, (logit > 0).astype(np.float32)


def auc(y, score):
    """Rank AUC with average ranks for ties, in numpy."""
    _, inv, cnt = np.unique(score, return_inverse=True, return_counts=True)
    rank = (np.cumsum(cnt) - (cnt - 1) / 2.0)[inv]
    n1 = float(y.sum())
    n0 = float(len(y) - n1)
    return (rank[y == 1].sum() - n1 * (n1 + 1) / 2.0) / (n0 * n1)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def stage_kernels(on_tpu, rows=ROWS):
    """K: the three Pallas histogram kernels at the smoke geometry vs
    numpy f64, and the partition kernel over `rows` rows vs the XLA
    formulation.

    Tolerance: the kernels accumulate exact 0/1 x f32 products in f32
    over 4096-row chunks, so a cell's error is a few f32 roundings of
    its own mass — 1e-5 of the largest cell leaves two decimal orders
    of room and still fails a bfloat16-truncated contraction (~4e-3).
    """
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.ordered_hist import (pack_feature_words,
                                               segment_histograms)
    from lightgbm_tpu.ops.pallas_hist import (HIST_CHUNK,
                                              frontier_histograms_tpu,
                                              masked_histograms_tpu)
    f, b, n = FEATURES, PARAMS["max_bin"], 3 * HIST_CHUNK
    rng = np.random.RandomState(SEED)
    bins = rng.randint(0, b, size=(f, n)).astype(np.uint8)
    ghc = rng.randn(3, n).astype(np.float32)
    ghc[2] = 1.0
    row_leaf = rng.randint(0, 3, size=n).astype(np.int32)
    lo, cnt = HIST_CHUNK // 3, 2 * HIST_CHUNK

    def reference(mask):
        out = np.zeros((f, b, 3))
        for i in range(f):
            for k in range(3):
                out[i, :, k] = np.bincount(
                    bins[i], weights=ghc[k].astype(np.float64) * mask,
                    minlength=b)
        return out

    # jitted, as the builders call them: what XLA does to the wrapper
    # code around a kernel is part of what is checked
    interpret = not on_tpu
    got = {
        "masked": jax.jit(lambda bn, g, rl: masked_histograms_tpu(
            bn, g, rl, jnp.int32(1), b, interpret=interpret)[0])(
                bins, ghc, row_leaf),
        "frontier_l2": jax.jit(lambda bn, g, rl: frontier_histograms_tpu(
            bn, g, rl, jnp.asarray([2, 0], jnp.int32), b,
            interpret=interpret)[0])(bins, ghc, row_leaf),
        "segment": jax.jit(lambda w, g: segment_histograms(
            w, g, jnp.int32(lo), jnp.int32(cnt), b, f,
            interpret_backend=None if on_tpu else "tpu",
            interpret=interpret))(pack_feature_words(bins), ghc),
    }
    pos = np.arange(n)
    want = {
        "masked": reference(row_leaf == 1),
        "frontier_l2": np.stack([reference(row_leaf == 2),
                                 reference(row_leaf == 0)]),
        "segment": reference((pos >= lo) & (pos < lo + cnt)),
    }
    for name, ref in want.items():
        out = np.asarray(got[name], np.float64)
        err = float(np.abs(out - ref).max() / np.abs(ref).max())
        counts_exact = bool(np.array_equal(out[..., 2], ref[..., 2]))
        print(f"  kernel {name}: max err {err:.2e} of the largest cell, "
              f"counts exact: {counts_exact}")
        check(err <= 1e-5 and counts_exact,
              f"{name} kernel disagrees with the f64 histogram")

    # the partition kernel moves rows as byte planes through a bfloat16
    # one-hot product on the MXU: exact on paper (bytes are bfloat16
    # numbers, one non-zero a column, f32 accumulation), and this is the
    # chip's proof. Against the prefix-sum + scatter + gathers it
    # replaces there, on a segment whose ends share chunks with rows
    # that must stay put; any differing word fails.
    from lightgbm_tpu.models.partitioned import (_partition_segment,
                                                 _partition_segment_rows)
    from lightgbm_tpu.ops.ordered_hist import unpack_feature
    from lightgbm_tpu.ops.partition import pack_rows
    n = -(-rows // HIST_CHUNK) * HIST_CHUNK
    bins = rng.randint(0, b, size=(f, n)).astype(np.uint8)
    words = jnp.asarray(pack_feature_words(bins))
    stats = jnp.asarray(rng.randn(3, n).astype(np.float32))
    perm = jnp.asarray(rng.permutation(n).astype(np.int32))
    seg = (jnp.int32(n // 7 + 3), jnp.int32(n - n // 5), jnp.int32(11),
           jnp.int32(b // 3), jnp.asarray(False), unpack_feature)
    want_rows = jax.jit(lambda w, g, p: pack_rows(
        *_partition_segment(w, g, p, *seg)[:3]))(words, stats, perm)
    got_rows = jax.jit(lambda w, g, p: _partition_segment_rows(
        *pack_rows(w, g, p), *seg, interpret=interpret)[:2])(
            words, stats, perm)
    differing = sum(
        int(jnp.sum(jax.lax.bitcast_convert_type(a, jnp.int32)
                    != jax.lax.bitcast_convert_type(e, jnp.int32)))
        for a, e in zip(got_rows, want_rows))
    print(f"  kernel partition_rows: {n} rows, segment of {int(seg[1])}, "
          f"{differing} words differ from the XLA formulation")
    check(differing == 0,
          "partition_rows kernel disagrees with the XLA formulation")


def train(x, y, extra=None, iterations=ITERATIONS):
    import lightgbm_tpu as lgb
    params = dict(PARAMS, **(extra or {}))
    ds = lgb.Dataset(x, label=y).construct()
    return ds, lgb.train(params, ds, num_boost_round=iterations)


def training_auc(booster, y):
    return auc(y, np.asarray(booster.gbdt.get_training_score(),
                             np.float64).reshape(-1)[:len(y)])


def stage_train(x, y, on_tpu, clock):
    """A: lgb.Dataset -> lgb.train, then the model against numpy."""
    import jax
    from lightgbm_tpu.ops.histogram import use_pallas
    from lightgbm_tpu.telemetry.ledger import LEDGER

    start = clock.stage()
    ds, booster = train(x, y)
    jax.block_until_ready(booster.gbdt.get_training_score())
    wall, comp, run = clock.split(start)
    learner = booster.gbdt.tree_learner
    fused = any(e["label"] == f"fused_scan_{ITERATIONS}it:compile"
                for e in LEDGER.snapshot(recent_n=256)["recent"])
    print(f"  learner: {type(learner).__name__}, partitioned: "
          f"{learner._use_partitioned}, pallas: {use_pallas()}, "
          f"fused scan: {fused}, device binning: "
          f"{ds._core.binned_on_device}")
    print(f"  train wall {wall:.1f}s = compile {comp:.1f}s + run "
          f"{run:.1f}s (binning included in run); fused scan loaded from "
          f"the persistent cache: {booster.gbdt.last_compile_cache_hit}")
    if on_tpu:
        check(learner._use_partitioned and use_pallas(),
              "auto did not select the leaf-contiguous builder with the "
              "Pallas kernels")
        check(ds._core.binned_on_device, "device binning did not run")
    check(fused, "lgb.train did not take the fused lax.scan path")

    trees = booster.gbdt.models
    leaves = [int(t.num_leaves) for t in trees]
    check(len(trees) == ITERATIONS and set(leaves) == {PARAMS["num_leaves"]},
          f"expected {ITERATIONS} trees of {PARAMS['num_leaves']} leaves, "
          f"got {len(trees)} with leaves {sorted(set(leaves))}")

    # the root's left-child count of tree 0, recounted without the
    # library: bin k = #(upper bounds < v), a row goes left iff its
    # bin <= the threshold bin. Exact: it pins the kernel's count
    # column, the split scan's prefix sum and the partition.
    t0 = trees[0]
    feat, thr_bin = int(t0.split_feature[0]), int(t0.threshold_in_bin[0])
    col = x[:, int(t0.split_feature_real[0])].astype(np.float64)
    upper = ds._core.bin_mappers[feat].bin_upper_bound
    want = int(np.sum(np.searchsorted(upper, col, side="left") <= thr_bin))
    left = int(t0.left_child[0])
    got = int(t0.leaf_count[~left] if left < 0 else t0.internal_count[left])
    print(f"  tree 0 root: feature {feat} bin <= {thr_bin}, left count "
          f"{got} (numpy {want})")
    check(got == want, "root left-child count differs from numpy")

    score = training_auc(booster, y)
    print(f"  {len(trees)} trees x {leaves[0]} leaves, training AUC "
          f"{score:.4f}")
    check(score >= AUC_FLOOR, f"training AUC {score:.4f} < {AUC_FLOOR}")
    return booster, score


def stage_predict(booster, x, on_tpu, clock):
    """B: device batch predict vs the host f64 path.

    Bound: the device path gathers f32 leaf values and sums T of them
    in f32 (one HIGHEST-precision contraction), the host path does both
    in f64. With S the sum over trees of the largest |leaf value|, the
    raw score differs by at most about 2*T*2^-24*S (value rounding plus
    T-1 f32 additions); the binary transform's slope is <= sigmoid/2.
    Traversal decisions are identical by construction (thresholds are
    rounded toward -inf in f32 and the inputs are f32).
    """
    gbdt = booster.gbdt
    n, t_cnt = x.shape[0], len(gbdt.models)
    start = clock.stage()
    device = booster.predict(x)
    wall, comp, run = clock.split(start)
    used_device = gbdt._use_device_predict(n, t_cnt)
    idx = np.random.RandomState(SEED).choice(n, min(SAMPLE_ROWS, n),
                                             replace=False)
    check(not gbdt._use_device_predict(len(idx), t_cnt),
          "the sample is meant to stay on the host path")
    host = booster.predict(x[idx])
    s = sum(float(np.abs(t.leaf_value).max()) for t in gbdt.models)
    slope = gbdt.sigmoid / 2 if gbdt.sigmoid > 0 else 1.0
    bound = 2 * t_cnt * 2.0 ** -24 * s * slope
    err = float(np.abs(device[idx] - host).max())
    print(f"  {n} rows x {t_cnt} trees, device path: {used_device}, wall "
          f"{wall:.1f}s = compile {comp:.1f}s + run {run:.1f}s")
    print(f"  device vs host f64 on {len(idx)} rows: max |diff| "
          f"{err:.2e} (bound {bound:.2e})")
    check(device.shape == (n,) and np.isfinite(device).all(),
          "device predictions are not finite values of shape (rows,)")
    check(err <= bound, "device predict is outside the f32 bound")
    check(used_device or not on_tpu,
          "the full matrix did not take the device traversal")


def stage_serve(booster, x, clock):
    """C: CompiledPredictor + HTTP server vs Booster.predict.

    Contract (serving/compiled_model.py): traversal on the device in
    f32, reduction on the host in f64 from the same leaf values as
    Booster.predict's host path, so the two agree to f64 summation
    order (<= 1e-12 here); a moved traversal decision would show as
    ~1e-2.
    """
    from lightgbm_tpu.serving.compiled_model import CompiledPredictor
    from lightgbm_tpu.serving.server import make_server

    start = clock.stage()
    predictor = CompiledPredictor.from_booster(booster)
    wall, comp, run = clock.split(start)
    print(f"  warm-up of {len(predictor.buckets)} buckets: wall {wall:.1f}s"
          f" = compile {comp:.1f}s + run {run:.1f}s, persistent-cache "
          f"hits {predictor.stats['compile_cache_hits']}")
    srv = make_server(predictor, port=0, max_wait_ms=1.0)
    port = srv.server_address[1]
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    try:
        for rows in (1, 256, SAMPLE_ROWS):
            rows = min(rows, x.shape[0])
            body = json.dumps({"rows": x[:rows].tolist()}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/predict", data=body,
                headers={"Content-Type": "application/json"})
            t0 = time.perf_counter()
            with urllib.request.urlopen(req, timeout=120) as r:
                out = json.loads(r.read())
            dt = time.perf_counter() - t0
            got = np.asarray(out["predictions"], np.float64).reshape(-1)
            want = booster.predict(x[:rows])
            err = float(np.abs(got - want).max())
            print(f"  POST /predict {rows} rows: {dt * 1e3:.1f} ms, max "
                  f"|diff| vs Booster.predict {err:.1e}, bit-identical: "
                  f"{bool(np.array_equal(got, want))}")
            check(got.shape == (rows,) and err <= 1e-12,
                  f"served predictions differ at {rows} rows")
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metricz", timeout=30) as r:
            metricz = json.loads(r.read())
        print(f"  /metricz: cold_dispatches {metricz['cold_dispatches']}, "
              f"warm_dispatches {metricz['warm_dispatches']}, requests "
              f"{metricz['request_count']}")
        check(metricz["cold_dispatches"] == 0,
              "a request hit a kernel shape warm-up had not compiled")
    finally:
        srv.shutdown()
        srv.server_close()
        srv.batcher.close()
        thread.join(timeout=30)
    check(not thread.is_alive(), "the server thread did not stop")


def stage_four_chips(x, y, one_chip_auc, devices, clock):
    """D: tree_learner=data over `devices` chips vs the one-chip model."""
    import jax
    check(len(jax.devices()) >= devices,
          f"need {devices} devices, jax has {len(jax.devices())}")
    # num_machines must be given: with it left at 1, Config turns
    # tree_learner=data back into serial without a word
    mesh_params = {"tree_learner": "data", "num_machines": devices}
    start = clock.stage()
    _, booster = train(x, y, mesh_params)
    jax.block_until_ready(booster.gbdt.get_training_score())
    wall, comp, run = clock.split(start)
    learner = booster.gbdt.tree_learner
    shards = learner._bins.addressable_shards
    shard_rows = sorted({s.data.shape[1] for s in shards})
    on = sorted(d.id for d in learner._bins.sharding.device_set)
    print(f"  learner: {type(learner).__name__}, partitioned: "
          f"{learner._use_partitioned}, mesh devices "
          f"{learner.mesh.devices.size}, bins on devices {on}, rows per "
          f"shard {shard_rows}")
    print(f"  train wall {wall:.1f}s = compile {comp:.1f}s + run {run:.1f}s")
    check(type(learner).__name__ == "DataParallelTreeLearner",
          "tree_learner=data did not reach the data-parallel learner")
    check(learner.mesh.devices.size == devices and len(on) == devices
          and len(shards) == devices and len(shard_rows) == 1,
          f"bins are not in equal row shards on {devices} devices")
    score = training_auc(booster, y)
    print(f"  training AUC {score:.4f} (one chip {one_chip_auc:.4f})")
    check(abs(score - one_chip_auc) <= 1e-3,
          "data-parallel AUC is not within 1e-3 of the one-chip model")

    # report only: under partitioned_build=false the masked builder
    # exchanges Kahan pairs so that serial == data-parallel exactly on
    # CPU; the Pallas kernels return a zero compensation word, so on
    # the chip the guarantee may only be a tolerance
    # (hist_compaction=false pins the masked engine on CPU too, where
    # `auto` would compact the serial side only; a no-op on the TPU)
    masked = {"partitioned_build": "false", "hist_compaction": "false"}
    _, serial = train(x, y, masked, EQUALITY_ITERATIONS)
    _, meshed = train(x, y, dict(masked, **mesh_params), EQUALITY_ITERATIONS)
    a, b = serial.gbdt.models, meshed.gbdt.models
    same_structure = all(
        np.array_equal(s.split_feature, m.split_feature)
        and np.array_equal(s.threshold_in_bin, m.threshold_in_bin)
        for s, m in zip(a, b))
    same_leaves = all(s.num_leaves == m.num_leaves for s, m in zip(a, b))
    diff = max(float(np.abs(s.leaf_value - m.leaf_value).max())
               for s, m in zip(a, b)) if same_leaves else float("inf")
    # tests/test_parallel.py calls trees identical at rtol 1e-5 / atol 1e-7
    close = same_leaves and all(
        np.allclose(s.leaf_value, m.leaf_value, rtol=1e-5, atol=1e-7)
        for s, m in zip(a, b))
    print(f"  partitioned_build=false, {EQUALITY_ITERATIONS} iterations, "
          f"one chip vs {devices}: same splits {same_structure}, max "
          f"|leaf value diff| {diff:.2e}, bit-identical "
          f"{same_structure and diff == 0.0}, within the CPU tests' "
          f"tolerance {same_structure and close}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--devices", type=int, default=1,
                    help="also train tree_learner=data over this many "
                         "chips (stage D; stages B and C are skipped)")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="dry run on a CPU backend; never prints the "
                         "pass line")
    ap.add_argument("--rows", type=int, default=ROWS,
                    help="row count (only with --allow-cpu)")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not (args.allow_cpu and dev.platform == "cpu"):
        sys.exit(f"chip_smoke: jax found platform '{dev.platform}' "
                 f"({dev.device_kind}), not a TPU; nothing was run "
                 "(--allow-cpu --rows N is the dry run)")
    if on_tpu and args.rows != ROWS:
        sys.exit("chip_smoke: --rows is for the --allow-cpu dry run only")

    from lightgbm_tpu.config import setup_compilation_cache
    from lightgbm_tpu.telemetry.ledger import LEDGER
    clock = _Clock()
    jax.monitoring.register_event_duration_secs_listener(clock.listen)
    cache_dir = setup_compilation_cache()
    print(f"platform: {device['platform']}, device_kind: {device['kind']}, "
          f"devices: {device['count']}, jax {jax.__version__}")
    print(f"cache: {cache_dir or 'off'}")
    t_start = time.perf_counter()

    print("stage K: kernels")
    stage_kernels(on_tpu, args.rows)
    x, y = make_data(args.rows)
    print(f"stage A: train {args.rows} x {FEATURES}, {PARAMS}, "
          f"{ITERATIONS} iterations")
    booster, score = stage_train(x, y, on_tpu, clock)
    if args.devices > 1:
        print(f"stage D: tree_learner=data on {args.devices} devices")
        stage_four_chips(x, y, score, args.devices, clock)
    else:
        print("stage B: batch predict")
        stage_predict(booster, x, on_tpu, clock)
        print("stage C: serve")
        stage_serve(booster, x, clock)

    led = LEDGER.snapshot(recent_n=0)
    print(f"compile ledger: {led['compiles']} compiles, "
          f"{led['total_s']:.1f}s backend compile, persistent cache "
          f"hits {led['cache_hits']} misses {led['cache_misses']}")
    print(f"total wall {time.perf_counter() - t_start:.1f}s")
    if not on_tpu:
        print("dry run on platform: cpu — all stages ran; this is not a "
              "pass")
        return
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
