"""TPU primitive microbenchmarks for the partitioned-builder design.

Measures the device primitives the leaf-contiguous (ordered-partition)
tree builder depends on, so kernel/layout decisions are made from
measured numbers instead of guesses:

  - take_cols:   jnp.take along axis=1 of a (W, N) int32 word matrix
                 (the bin permutation step; 4 uint8 features packed per
                 int32 word)
  - scatter_cols: zeros.at[:, perm].set(vals) for the same shape (the
                 scatter formulation of the permutation)
  - take_rows:   jnp.take along axis=0 of (N, W) (row-major layout)
  - cumsum:      full-N f32 cumsum (stable-partition rank computation)
  - argsort:     full-N int32 argsort (alternative partition route)
  - masked_hist: the shipped pallas masked histogram
  - segment_hist / partition: the partitioned builder's two hot ops at
                 several segment sizes; `partition` times the off-TPU
                 formulation (`_partition_segment`: slice, prefix sums,
                 scatter, gathers, write-back), its `invert` + `move`
                 alone on the same window, and on a TPU the kernel
                 `partition_rows` (ops/partition.py) on the same segment,
                 after checking the two bit-equal
  - score_update: the end of a tree, generic scatter + gather against
                 the program's un-permute + leaf-value lookup, bit-equal
  - pos_leaf:    a 255-leaf tree's position -> leaf map, the per-split
                 select over all N positions (254 of them) against the
                 once-a-tree derivation from the segment bounds
                 (ops/partition.py segment_leaves), bit-equal
  - fused_iter:  one full boosting iteration (gradients + whole tree +
                 score update) for BOTH builders at the bench config

Timing methodology: each op is a K-step in-device `lax.scan` chain with
a data dependency between steps, so K executions cannot fuse away, and
the wall clock is read after `block_until_ready`.

Each line reports achieved GB/s against the chip's peak HBM bandwidth
(roofline utilization) so "fast" is an arguable MFU-style number. The
peak is looked up by `jax.devices()[0].device_kind`; a device that is
not in the table is an error, not a default.

Usage:  python tools/microbench.py [N] [K] [rows]
        rows: comma-separated row groups to run (default all): stream,
        take, cumsum, argsort, masked_hist, segment_hist, partition,
        score_update, pos_leaf, fused_iter
"""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# peak HBM bandwidth in GB/s, keyed by jax's device_kind. Source: Google
# Cloud documentation, "TPU v5e" system architecture (16 GB HBM2e at
# 819 GB/s per chip). Add a chip together with its source.
PEAK_HBM_GBS = {"TPU v5 lite": 819.0}


def _peak_gbs():
    kind = jax.devices()[0].device_kind
    if kind not in PEAK_HBM_GBS:
        raise SystemExit(
            f"microbench: no published HBM peak for device_kind {kind!r}; "
            f"known: {sorted(PEAK_HBM_GBS)}. Add it to PEAK_HBM_GBS with "
            "its source.")
    return PEAK_HBM_GBS[kind], kind


RESULTS = {}   # label -> {ms[, gbs, pct_peak_hbm]}; dumped at end of main


def chain_time(fn, make_init, k, label, step_bytes=None):
    """Median wall-clock of a k-step dependent scan chain / k over
    three timed calls (make_init(i) gives each its own initial carry).
    Prints achieved GB/s + % of peak HBM when step_bytes (bytes touched
    per step) is given."""

    def step(carry, _):
        return fn(carry), None

    @jax.jit
    def chained(x):
        out, _ = jax.lax.scan(step, x, None, length=k)
        return out

    jax.block_until_ready(chained(make_init(0)))  # compile + warm
    times = []
    for i in (1, 2, 3):
        x = make_init(i)
        t0 = time.perf_counter()
        jax.block_until_ready(chained(x))
        times.append((time.perf_counter() - t0) / k)
    ms = sorted(times)[1] * 1e3
    util = ""
    rec = {"ms": round(ms, 3)}
    if step_bytes:
        gbs = step_bytes / (ms * 1e-3) / 1e9
        peak, gen = _peak_gbs()
        util = f"{gbs:9.1f} GB/s  {100.0 * gbs / peak:5.1f}% of {gen} HBM"
        rec["gbs"] = round(gbs, 1)
        rec["pct_peak_hbm"] = round(100.0 * gbs / peak, 1)
    if label in RESULTS:          # clamped segment sizes can repeat
        label = f"{label} (dup)"
    RESULTS[label] = rec
    print(f"{label:34s} {ms:8.3f} ms {util}", flush=True)
    return ms


def bench_take(n, k, f_words, rng):
    words = jnp.asarray(rng.randint(0, 2**31, size=(f_words, n), dtype=np.int32))
    perm_h = rng.permutation(n).astype(np.int32)

    def perm_v(i):
        return jnp.asarray(np.roll(perm_h, i))

    words_b = f_words * n * 4

    # permutation applied to the word matrix, chained via perm update
    def take_cols(carry):
        w, p = carry
        return jnp.take(w, p, axis=1), jnp.roll(p, 1)

    chain_time(take_cols, lambda i: (words, perm_v(i)), k,
               f"take_cols (7,{n}) i32", step_bytes=2 * words_b + 4 * n)

    def scatter_cols(carry):
        w, p = carry
        out = jnp.zeros_like(w).at[:, p].set(w)
        return out, jnp.roll(p, 1)

    chain_time(scatter_cols, lambda i: (words, perm_v(i)), k,
               f"scatter_cols (7,{n}) i32", step_bytes=2 * words_b + 4 * n)

    words_r = words.T.copy()

    def take_rows(carry):
        w, p = carry
        return jnp.take(w, p, axis=0), jnp.roll(p, 1)

    chain_time(take_rows, lambda i: (words_r, perm_v(i)), k,
               f"take_rows ({n},7) i32", step_bytes=2 * words_b + 4 * n)

    # one-per-row gather of f32 (ghc permutation, 3 stat rows)
    ghc = jnp.asarray(rng.rand(3, n).astype(np.float32))

    def take_ghc(carry):
        g, p = carry
        return jnp.take(g, p, axis=1), jnp.roll(p, 1)

    chain_time(take_ghc, lambda i: (ghc, perm_v(i)), k,
               f"take_cols (3,{n}) f32", step_bytes=2 * 12 * n + 4 * n)


def bench_masked_hist(n_pad, k, f, ghc_t, rng):
    # baseline: shipped masked histogram at the bench shape
    from lightgbm_tpu.ops.pallas_hist import masked_histograms, HIST_CHUNK
    bins = jnp.asarray(rng.randint(0, 255, size=(f, n_pad), dtype=np.uint8))
    row_leaf = jnp.zeros(n_pad, dtype=jnp.int32)

    def hist_step(carry):
        rl, acc = carry
        h, res = masked_histograms(bins, ghc_t, rl, jnp.int32(0), 256,
                                   HIST_CHUNK)
        return rl + (h[0, 0, 0] > -1).astype(jnp.int32), acc + h[0, 0, 0]

    chain_time(hist_step, lambda i: (row_leaf, jnp.float32(i)), k,
               f"masked_hist ({f},{n_pad})x256",
               step_bytes=(f + 12) * n_pad)


def bench_segment_hist(n_pad, k, words28, ghc_t):
    # the partitioned path's segment histogram at several leaf sizes
    from lightgbm_tpu.ops.ordered_hist import segment_histograms
    from lightgbm_tpu.ops.pallas_hist import HIST_CHUNK
    for seg in [HIST_CHUNK, 16 * HIST_CHUNK, n_pad]:
        seg = min(seg, n_pad)

        def seg_step(carry, seg=seg):
            b, acc = carry
            h = segment_histograms(words28, ghc_t, b, jnp.int32(seg),
                                   256, f=28)
            return (b + (h[0, 0, 0] > -1).astype(jnp.int32) - 1,
                    acc + h[0, 0, 0])

        chain_time(seg_step, lambda i: (jnp.int32(1 + (i % 2)),
                                        jnp.float32(i)), k,
                   f"segment_hist seg={seg}", step_bytes=(28 + 12) * seg)


def bench_partition(n_pad, k, words28, ghc_t):
    """The partition step at several segment sizes, three ways: the whole
    off-TPU formulation (slice + prefix sums + scatter + gathers +
    write-back inside the bucketed switch), its `invert` + `move` alone
    on the covering window, and on a TPU the kernel `partition_rows` on
    the same segment, first checked bit-equal against the formulation."""
    from lightgbm_tpu.models.partitioned import _partition_segment
    from lightgbm_tpu.ops.histogram import use_pallas
    from lightgbm_tpu.ops.ordered_hist import unpack_feature
    from lightgbm_tpu.ops.pallas_hist import HIST_CHUNK
    from lightgbm_tpu.ops.partition import (apply_partition,
                                            invert_permutation, pack_rows,
                                            partition_rows,
                                            split_destinations)

    f = 28
    perm0_h = np.arange(n_pad, dtype=np.int32)
    feat, thr = jnp.int32(3), jnp.int32(100)
    go_full = unpack_feature(words28, feat) <= thr
    for seg in sorted({min(s, n_pad) for s in
                       (HIST_CHUNK, 64 * HIST_CHUNK, n_pad)}):
        seg_b = jnp.int32(0)
        seg_c = jnp.int32(seg)
        moved = 2 * (f + 12 + 4) * seg     # words + stats + perm, in + out

        def part_step(carry, seg_c=seg_c):
            w, g, p = carry
            # data dependency rides the threshold (doesn't change the
            # segment geometry, so the labeled bucket is what's timed)
            w2, g2, p2, nl = _partition_segment(
                w, g, p, seg_b, seg_c, feat, thr + (p[0] % 2),
                jnp.asarray(False), unpack_feature)
            return (w2, g2, p2)

        def init(i):
            return words28, ghc_t, jnp.asarray(np.roll(perm0_h, i))

        chain_time(part_step, init, k, f"partition xla seg={seg}",
                   step_bytes=moved + 12 * seg)

        # `invert` + `move` on the window alone, destinations given
        dest, n_left = split_destinations(go_full[:seg], seg_b, seg_c)

        def move_step(carry):
            w, g, p = carry
            # p >= 0: the shift is 0, and XLA cannot hoist the scatter
            src = invert_permutation(dest + (p[0] >> 31))
            return apply_partition(src, w, g, p)

        chain_time(move_step,
                   lambda i: tuple(a[..., :seg] for a in init(i)), k,
                   f"partition xla invert+move seg={seg}", step_bytes=moved)

        if not use_pallas():
            continue
        kernel = jax.jit(lambda ri, rf: partition_rows(
            ri, rf, go_full, seg_b, seg_c, n_left))
        ri0, rf0 = pack_rows(*init(0))
        ref = jax.jit(lambda w, g, p: pack_rows(*_partition_segment(
            w, g, p, seg_b, seg_c, feat, thr, jnp.asarray(False),
            unpack_feature)[:3]))(*init(0))
        got = kernel(ri0, rf0)
        same = all(bool(jnp.array_equal(
            jax.lax.bitcast_convert_type(a, jnp.int32),
            jax.lax.bitcast_convert_type(b, jnp.int32)))
            for a, b in zip(got, ref))
        print(f"partition_rows seg={seg}: bit-equal to the xla "
              f"formulation: {same}", flush=True)
        if not same:
            raise SystemExit("partition_rows differs from the xla "
                             "formulation")

        chain_time(lambda carry: partition_rows(
            *carry, go_full, seg_b, seg_c, n_left),
            lambda i: pack_rows(*init(i)), k,
            f"partition_rows seg={seg}", step_bytes=moved)


def bench_score_update(n_pad, k, rng):
    """The end of a tree (scope `score_update`): a permutation's worth of
    leaf indices back to row order, then each row's leaf value added to
    the score, at 63 and 255 leaves. The generic forms (a scatter that
    does not say its indices are unique; `jnp.take` from the whole
    table, scaled a row at a time) against the program's
    (`ops/partition.py unpermute`, `models/score_updater.py
    leaf_lookup` on the scaled table), first checked bit-equal."""
    from lightgbm_tpu.models.score_updater import leaf_lookup, lookup_form
    from lightgbm_tpu.ops.partition import unpermute

    n = n_pad - n_pad // 11            # pad rows behind the real ones
    perm = jnp.asarray(rng.permutation(n_pad).astype(np.int32))
    shrink = jnp.float32(0.1)

    def scatter(p, v):
        return jnp.zeros(n_pad, jnp.int32).at[p].set(v)

    def scatter_unique(p, v):
        return jnp.zeros(n_pad, jnp.int32).at[p].set(
            v, unique_indices=True, indices_are_sorted=False)

    moves = {"scatter": scatter, "scatter_unique": scatter_unique,
             "sort_kv": unpermute}
    pos = jnp.asarray(rng.randint(0, 255, n_pad).astype(np.int32))
    for name, move in moves.items():
        chain_time(lambda c, move=move: (c[0], move(c[0], c[1])),
                   lambda i: (perm, jnp.roll(pos, i)), k,
                   f"un-permute {name}", step_bytes=12 * n_pad)

    for leaves in (63, 255):
        pos = jnp.asarray(rng.randint(0, leaves, n_pad).astype(np.int32))
        table = jnp.asarray((rng.randn(leaves) * 0.3).astype(np.float32))
        score = jnp.asarray(rng.randn(n).astype(np.float32))
        forms = {
            "scatter+take": (scatter, lambda t, i: jnp.take(t, i) * shrink),
            f"sort_kv+{lookup_form(leaves)}":
                (unpermute, lambda t, i: leaf_lookup(t * shrink, i)),
        }
        want = None
        for name, (move, value) in forms.items():
            # the update alone: next to the add, the CPU's compiler may
            # contract the generic form's multiply into an FMA
            got = np.asarray(jax.jit(lambda move=move, value=value: value(
                table, move(perm, pos)[:n]))()).view(np.int32)
            want = got if want is None else want
            if not np.array_equal(got, want):
                raise SystemExit(f"score_update {name}: bits differ from "
                                 "the generic forms'")

            def whole(carry, move=move, value=value):
                s, v = carry
                # the table rides the carry, and the next step's leaves
                # are this step's in row order, so XLA can hoist
                # neither the lookup nor the un-permute out of the chain
                t = table + s[:1] * jnp.float32(1e-30)
                row_leaf = move(perm, v)
                return s + value(t, row_leaf[:n]), row_leaf

            chain_time(whole, lambda i: (score + np.float32(i), pos), k,
                       f"score_update {name} l={leaves}",
                       step_bytes=12 * n_pad + 12 * n)


def bench_pos_leaf(n_pad, k, rng, leaves=255):
    """A tree's position -> leaf map (scope `tree_state/pos_leaf`) two
    ways, over one drawn leaf-wise tree of `leaves` leaves (a split
    takes a leaf by its rows and sends a uniform share of them left):
    the select over all n_pad positions at every split, as the split
    loop kept it, against `segment_leaves` once from the final segment
    bounds. Checked bit-equal first; a tree is a step of the chain."""
    from lightgbm_tpu.ops.partition import segment_leaves

    seg_begin = np.zeros(leaves, np.int32)
    seg_cnt = np.zeros(leaves, np.int32)
    seg_cnt[0] = n_pad
    splits = []
    for i in range(leaves - 1):
        leaf = rng.choice(i + 1, p=seg_cnt[:i + 1] / n_pad)
        b, c = int(seg_begin[leaf]), int(seg_cnt[leaf])
        n_left = rng.randint(0, c + 1)
        splits.append((b, c, n_left, i + 1))
        seg_cnt[leaf] = n_left
        seg_begin[i + 1], seg_cnt[i + 1] = b + n_left, c - n_left
    split_arr = jnp.asarray(np.array(splits, np.int32))
    seg_begin, seg_cnt = jnp.asarray(seg_begin), jnp.asarray(seg_cnt)

    def per_split(pos_leaf):
        pos = jnp.arange(n_pad, dtype=jnp.int32)

        def select(i, pl):
            b, c, n_left, right_id = (split_arr[i, j] for j in range(4))
            return jnp.where((pos >= b + n_left) & (pos < b + c),
                             right_id, pl)

        # a bound read at run time keeps XLA from unrolling the loop and
        # fusing the selects into one pass, which the split loop, with
        # a tree's other work between them, cannot do
        return jax.lax.fori_loop(0, leaves - 1 + (pos_leaf[0] >> 31),
                                 select, pos_leaf)

    def once(pos_leaf):
        # the previous tree's map decides nothing (its ids are >= 0) but
        # keeps XLA from hoisting the derivation out of the chain
        return segment_leaves(seg_begin + (pos_leaf[0] >> 31), seg_cnt,
                              n_pad)

    want = np.asarray(jax.jit(per_split)(jnp.zeros(n_pad, jnp.int32)))
    got = np.asarray(jax.jit(once)(jnp.zeros(n_pad, jnp.int32)))
    if not np.array_equal(got, want):
        raise SystemExit("pos_leaf once_a_tree: bits differ from the "
                         "per-split select's")
    for name, form in (("per_split_select", per_split),
                       ("once_a_tree", once)):
        chain_time(form, lambda i: jnp.asarray(want) + i, k,
                   f"pos_leaf {name} n={n_pad} l={leaves}",
                   step_bytes=4 * n_pad * (
                       2 * (leaves - 1) if form is per_split else 1))


def bench_fused_iter(n_pad, k):
    # ---- the ACTUAL bench unit: one full fused boosting iteration
    # (gradients + whole partitioned tree + score update) at the bench
    # config
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import DatasetLoader
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective

    seed = int(os.environ.get("MICROBENCH_SEED", "0"))
    rng2 = np.random.RandomState(seed)
    print(f"fused_iter data seed={seed}", flush=True)
    n_real = min(n_pad, 1_000_000)
    xr = rng2.randn(n_real, 28).astype(np.float32)
    yr = (xr[:, 0] > 0).astype(np.float32)
    for part in ("true", "false"):
        cfg = Config.from_params({
            "objective": "binary", "num_leaves": 63, "max_bin": 255,
            "num_iterations": k, "metric_freq": 0, "verbose": -1,
            "partitioned_build": part})
        ds = DatasetLoader(cfg).construct_from_matrix(xr, label=yr)
        obj = create_objective(cfg.objective, cfg)
        obj.init(ds.metadata, ds.num_data)
        b = GBDT()
        b.init(cfg, ds, obj, [])
        if not b.warm_up_fused(k):
            print(f"fused_iter part={part}: ineligible, skipped")
            continue
        t0 = time.time()
        b.train_many(k)
        np.asarray(b.get_training_score())
        dt = (time.time() - t0) / k
        name = "partitioned" if part == "true" else "masked"
        RESULTS[f"fused_iter_{name}"] = {"ms": round(dt * 1e3, 2)}
        print(f"fused_iter {name} {n_real}x28x63l: {dt * 1e3:9.2f} ms/iter",
              flush=True)


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    k = int(sys.argv[2]) if len(sys.argv) > 2 else 20
    only = set(sys.argv[3].split(",")) if len(sys.argv) > 3 else None

    def want(group):
        return only is None or group in only

    f_words = 7  # 28 uint8 features packed 4-per-int32
    rng = np.random.RandomState(0)

    peak, kind = _peak_gbs()   # fails here, before any work, if unknown
    print(f"backend={jax.default_backend()} device_kind={kind} "
          f"peak_hbm={peak} GB/s n={n} k={k}", flush=True)

    # device STREAM-style analog: a dependent elementwise add chain
    # streams read+write of the buffer — the device-side copy peak
    if want("stream"):
        stream_v = jnp.asarray(rng.rand(n).astype(np.float32))
        chain_time(lambda v: v + 1.0, lambda i: stream_v + np.float32(i), k,
                   f"stream_device add ({n},) f32", step_bytes=8 * n)
    if want("take"):
        bench_take(n, k, f_words, rng)
    if want("cumsum"):
        vec = jnp.asarray(rng.rand(n).astype(np.float32))
        chain_time(lambda v: jnp.cumsum(v) * 1e-6,
                   lambda i: vec + np.float32(i), k,
                   f"cumsum ({n},) f32", step_bytes=8 * n)
    if want("argsort"):
        keys = jnp.asarray(rng.randint(0, 4, size=n, dtype=np.int32))

        def argsorted(c):
            return jnp.argsort(c, stable=True).astype(jnp.int32) % 4

        chain_time(argsorted, lambda i: (keys + i) % 4, k,
                   f"argsort ({n},) i32")

    from lightgbm_tpu.ops.ordered_hist import pack_feature_words
    from lightgbm_tpu.ops.pallas_hist import HIST_CHUNK
    f = 28
    n_pad = ((n + HIST_CHUNK - 1) // HIST_CHUNK) * HIST_CHUNK
    ghc_t = jnp.asarray(rng.rand(3, n_pad).astype(np.float32))
    if want("masked_hist"):
        bench_masked_hist(n_pad, k, f, ghc_t, rng)
    bins28 = rng.randint(0, 255, size=(f, n_pad), dtype=np.uint8)
    words28 = jnp.asarray(pack_feature_words(bins28))
    if want("segment_hist"):
        bench_segment_hist(n_pad, k, words28, ghc_t)
    if want("partition"):
        bench_partition(n_pad, k, words28, ghc_t)
    if want("score_update"):
        bench_score_update(n_pad, k, rng)
    if want("pos_leaf"):
        bench_pos_leaf(n_pad, k, rng)
    if want("fused_iter"):
        bench_fused_iter(n_pad, k)

    # machine-readable summary (one line, BASELINE-quotable)
    import json
    print("MICROBENCH_JSON " + json.dumps(
        {"backend": jax.default_backend(), "n": n, "k": k,
         "results": RESULTS}), flush=True)


if __name__ == "__main__":
    main()
