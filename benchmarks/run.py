#!/usr/bin/env python3
"""The benchmark's one runner.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one owner of the chip. Everything that belongs to a cell is
data found by name: workloads/<cell>.json names configs/<config>.json and
traffic/<mix>.json; BENCHMARK.json lists the metrics, and a per-layer
metric <name> is read by metrics/<name>.py `read(ctx)`.

Set-up (setup_s): matrix from --seed (and the fields the generator gives
beside it: group, weight, categorical_feature), lgb.Dataset (device
binning), lgb.train of the first block (trace, lower, compile or cache
load, run). Window: further blocks on the same booster through
GBDT.train_many, whole blocks until --seconds have passed. With --trace 1
one block under jax.profiler instead. Then the program's state is dropped
and the plain reference follows the first block's trees: reference.py
(binary log-loss), or references/<name>.py where the configuration names
one under `reference`. A block holds block_iterations x K trees, K what
the booster grows an iteration (num_class).

Every phase mark on stderr says the seconds of its phase, and the last
one, `run total`, all of them: the driver stops a run at 360 s.

Without a TPU the runner exits 2 and prints no result, unless --rehearse
(the harness's own flag: CPU rehearsal at --rows, platform named, never
to be read as a device number).
"""

import argparse
import gc
import json
import os
import shutil
import sys
import time

from datagen import load_module, make_data, train_params  # beside this file

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TREE_KEYS = ("split_feature", "threshold_in_bin", "threshold", "left_child",
             "right_child", "leaf_value", "leaf_count", "internal_count")
_T0 = time.perf_counter()


def mark(msg):
    """Timestamped phase mark on stderr: a run that is cut shows where."""
    print(f"[bench {time.strftime('%H:%M:%S')} +{time.perf_counter() - _T0:6.1f}s] "
          f"{msg}", file=sys.stderr, flush=True)


class Phases:
    """Where a run's seconds go: `seconds` maps each phase, in the order
    the run went through them, to how long it lasted."""

    def __init__(self, start):
        self.seconds = {}
        self._last = start

    def __call__(self, name):
        """Close the phase `name` and return its seconds: the time since
        the last one closed (the first: since `start`)."""
        now = time.perf_counter()
        self.seconds[name] = now - self._last
        self._last = now
        return self.seconds[name]


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name):
    cell = load_json(HERE, "workloads", name + ".json")
    cell["name"] = name
    cell["config"] = load_json(HERE, "configs", cell["config"] + ".json")
    cell["traffic"] = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return cell


def metrics_for(cell_name, group):
    """Metric entries of BENCHMARK.json's `group` that apply to the cell."""
    bench = load_json(ROOT, "BENCHMARK.json")
    return [m for m in bench[group]
            if cell_name in m.get("workloads", [cell_name])]


def read_metric(name, ctx):
    return load_module("metrics", name).read(ctx)


class CompileClock:
    """chip_smoke.py's `_Clock` (copied): every /jax/core/compile/*
    duration jax reports — trace, lower, backend compile or cache load —
    summed, and the backend compiles counted."""

    def __init__(self):
        self.seconds = 0.0
        self.backend_compiles = 0

    def listen(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs
            if name.endswith("backend_compile_duration"):
                self.backend_compiles += 1


def tree_arrays(model):
    import numpy as np
    tree = model.materialize() if hasattr(model, "materialize") else model
    return {k: np.asarray(getattr(tree, k)) for k in TREE_KEYS}


def train_score(gbdt, n):
    """The (K, n) train score, K = gbdt.num_class."""
    import jax
    import numpy as np
    score = jax.block_until_ready(gbdt.train_score_updater.score)
    return np.asarray(score).reshape(gbdt.num_class, -1)[:, :n]


def failed_iterations(trees, attempted, k):
    """Iterations that left fewer than their k trees, or a tree without
    a split, in the class-major model list."""
    done = len(trees) // k
    return max(attempted - done, 0) + sum(
        any(len(t["split_feature"]) == 0 for t in trees[i * k:(i + 1) * k])
        for i in range(done))


def compare(config, params, x, y, fields, trees, score):
    """The numbers `correct` is decided from. A configuration that names
    a `reference` is compared by references/<name>.py, which gets the
    fields, the merged parameters the program got and the whole (K, n)
    score; one that names none by reference.py, the binary reference,
    with the configuration's own `params` and the one class's score."""
    if "reference" in config:
        return load_module("references", config["reference"]).compare(
            x, y, fields, params, trees, score)
    if len(score) != 1 or fields:
        raise ValueError(
            f"configuration {config['name']!r} grows {len(score)} tree(s) an iteration "
            f"with fields {sorted(fields)}: reference.py is the unweighted "
            "binary reference, name another under `reference`")
    import reference
    return reference.compare(x, y, config["params"], trees, score[0])


def memory_stats(jax, chips, label):
    stats = [d.memory_stats() or {} for d in jax.local_devices()[:chips]]
    peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    s0 = stats[0] if stats else {}
    mark(f"memory {label}: in_use {s0.get('bytes_in_use', 0) / 2**30:.3f} GiB, "
         f"peak {peak / 2**30:.3f} GiB, reserved "
         f"{s0.get('bytes_reserved', 0) / 2**30:.3f} GiB (peak "
         f"{s0.get('peak_bytes_reserved', 0) / 2**30:.3f}), limit "
         f"{s0.get('bytes_limit', 0) / 2**30:.3f} GiB")
    return peak


def check(numbers, limits):
    """Each number beside its limit; correct iff all hold (NaN fails)."""
    rows = {k: {"value": numbers[k], "limit": limits[k]}
            for k in limits if k in numbers}
    ok = all(r["value"] <= r["limit"] for r in rows.values())
    return ok and len(rows) == len(limits), rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run off a TPU (CPU rehearsal); prints no device metric")
    ap.add_argument("--rows", type=int, help="rehearsal only: fewer rows")
    args = ap.parse_args(argv)
    if args.rows and not args.rehearse:
        ap.error("--rows is for --rehearse")

    sys.path[:0] = [ROOT, HERE]
    cell = load_cell(args.workload)
    config, traffic = cell["config"], cell["traffic"]
    e2e = metrics_for(cell["name"], "end_to_end")
    per_layer = metrics_for(cell["name"], "per_layer")
    # the program takes this directory (config.py setup_compilation_cache)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import jax
    dev = jax.devices()
    on_tpu = dev[0].platform == "tpu" and len(dev) >= cell["chips"]
    if not on_tpu and not args.rehearse:
        print(f"no TPU with {cell['chips']} chip(s): platform "
              f"{dev[0].platform}, {len(dev)} device(s)", file=sys.stderr)
        return 2
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock.listen)
    import lightgbm_tpu as lgb

    data = dict(config["data"], **({"rows": args.rows} if args.rows else {}))
    n, f = data["rows"], data["features"]
    block = int(traffic["block_iterations"])
    params = train_params(config, traffic)

    # ------------------------------------------------------------- set-up
    mark(f"set-up: {cell['name']} seed {args.seed}, {n} x {f}, block {block}, "
         f"platform {dev[0].platform}")
    phase = Phases(_T0)
    phase("import")
    x, y, fields = make_data(data, args.seed)
    mark(f"data generated in {phase('data'):.1f}s"
         + (f", fields {sorted(fields)}" if fields else ""))
    ds = lgb.Dataset(x, label=y, params=dict(params), free_raw_data=False,
                     **fields).construct()
    dataset_s = phase("dataset")
    mark(f"dataset constructed in {dataset_s:.1f}s (binned on device: "
         f"{ds._core.binned_on_device})")
    booster = lgb.train(dict(params), ds, num_boost_round=block)
    gbdt = booster.gbdt
    k = int(gbdt.num_class)       # trees an iteration, class-major in models
    # a later block's iteration numbers start above 0, which costs the
    # program two scalar dispatches (convert, add) that block 0 never
    # makes: warm them here, as set-up warms every shape the window uses
    jax.block_until_ready(jax.numpy.arange(block, 2 * block, dtype="int32"))
    first_score = train_score(gbdt, n).copy()
    first_trees = [tree_arrays(m) for m in gbdt.models[:block * k]]
    setup_s = time.perf_counter() - _T0
    compile_s, compiles_before = clock.seconds, clock.backend_compiles
    mark(f"first block done in {phase('first block'):.1f}s ({k} tree(s) an "
         f"iteration): set-up {setup_s:.1f}s, of it compile events "
         f"{compile_s:.1f}s; cache hit {gbdt.last_compile_cache_hit}")
    memory_stats(jax, cell["chips"], "after set-up")

    # ------------------------------------------------------------- window
    trace_dir = os.path.join(ROOT, ".bench_cache", "trace", cell["name"])
    if args.trace:
        # one trace a cell stays on disk, replaced by the next traced run
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        t_win = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench_block"):
            gbdt.train_many(block)
            train_score(gbdt, n)
        window_s = time.perf_counter() - t_win
        jax.profiler.stop_trace()
        iterations = block
    else:
        iterations = 0
        t_win = time.perf_counter()
        while time.perf_counter() - t_win < args.seconds:
            gbdt.train_many(block)
            train_score(gbdt, n)
            iterations += block
            mark(f"window: {iterations} iterations, "
                 f"{time.perf_counter() - t_win:.1f}s")
        window_s = time.perf_counter() - t_win
    window_compiles = clock.backend_compiles - compiles_before
    phase("window")
    mark(f"window closed: {iterations} iterations in {window_s:.2f}s, "
         f"{window_compiles} compilations inside")
    peak = memory_stats(jax, cell["chips"], "after window")
    window_trees = [tree_arrays(m) for m in gbdt.models[block * k:]]
    attempted = block + iterations
    failed = failed_iterations(first_trees + window_trees, attempted, k)
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": int(peak)}
    del booster, gbdt, ds
    gc.collect()
    phase("trees + free")

    # ------------------------------------------------------------ metrics
    result = {}
    breakdown = None
    if args.trace:
        import tracereduce
        peaks = load_json(HERE, "peaks.json")
        if on_tpu and dev[0].device_kind not in peaks:
            raise KeyError(f"no peaks for device kind {dev[0].device_kind!r}")
        ctx = {"trace": None, "block_iterations": block,
               "trees_per_iteration": k, "params": params,
               "block_wall_s": window_s if on_tpu else None,
               "trees": window_trees[-block * k:], "rows": n, "features": f,
               "max_bin": config["params"]["max_bin"],
               "peak": peaks.get(dev[0].device_kind),
               "memory_peak_bytes": peak if on_tpu else None,
               "dataset_s": dataset_s if on_tpu else None,
               "compile_s": compile_s if on_tpu else None}
        if on_tpu:
            ctx["trace"] = tracereduce.reduce(*tracereduce.load(trace_dir))
            device["busy_s"] = ctx["trace"]["busy_s"]
            device["window_s"] = ctx["trace"]["window_s"]
            breakdown = {key: ctx["trace"][key]
                         for key in ("device_ops", "idle_gaps")}
        for m in per_layer:
            value = read_metric(m["name"], ctx)
            if value is not None:
                result[m["name"]] = {"value": float(value), "unit": m["unit"]}
        mark(f"trace reduced, {len(result)} per-layer metrics read in "
             f"{phase('trace reduction'):.1f}s")
    elif on_tpu:
        own = {"train_s_per_iter": window_s / max(iterations, 1),
               "setup_s": setup_s}
        result = {m["name"]: {"value": own[m["name"]], "unit": m["unit"]}
                  for m in e2e}
    else:
        mark(f"rehearsal on {dev[0].platform}: {window_s / max(iterations, 1):.3f} "
             f"s an iteration here says nothing about the chip")

    # -------------------------------------------------------- correctness
    numbers = compare(config, params, x, y, fields, first_trees, first_score)
    numbers["window_compiles"] = float(window_compiles)
    numbers["failed"] = float(failed)
    correct, checks = check(numbers, cell["limits"])
    mark(f"reference followed {len(first_trees)} trees in "
         f"{phase('reference'):.1f}s")
    mark(f"run total {time.perf_counter() - _T0:.1f}s: " + ", ".join(
        f"{name} {secs:.1f}" for name, secs in phase.seconds.items()))
    for name, r in checks.items():
        print(f"  check {name}: {r['value']:.6g} (limit {r['limit']:.6g})",
              file=sys.stderr)
    print(f"  correct: {correct}", file=sys.stderr, flush=True)
    line = {"correct": bool(correct), "attempted": attempted,
            "failed": int(failed), "metrics": result, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    line["checks"] = checks
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
