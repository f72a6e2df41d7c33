"""Perf guardrail for the scaled CPU rung (`make verify-perf`).

Three checks, any failure exits non-zero:

1. **Train-time regression**: runs the bench's reduced CPU rung
   (the committed baseline's shape) in a subprocess and fails when
   train time regresses more than VERIFY_PERF_TOL (default 15%) over
   BENCH_BASELINE.json. Compile happens outside the timed loop, so
   one run is comparable.
2. **AUC drift**: |AUC - baseline| must stay within 0.002 — a speedup
   that moves accuracy is a regression, not a win.
3. **Journal/tracer consistency**: trains a small run with telemetry
   on and checks the journal's per-record phase DELTAS sum back to the
   live tracer's totals (the reconstruction bench.py's `phases` dict
   rests on), then schema-lints the journal via tools/check_journal.

Usage: python tools/verify_perf.py  (from the repo root; CI wraps it in
`timeout`, see the Makefile).
"""

import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE_PATH = os.path.join(REPO, "BENCH_BASELINE.json")
TOL = float(os.environ.get("VERIFY_PERF_TOL", "0.15"))
AUC_TOL = 0.002
# peak-memory regression gate over the baseline's recorded watermark
# (host RSS on the CPU rung; bytes_in_use where the backend has
# allocator stats) — 25% headroom absorbs allocator noise while still
# catching a leaked score copy or an accidental densification
MEM_TOL = float(os.environ.get("VERIFY_PERF_MEM_TOL", "0.25"))


def run_cpu_rung(rows, iters, timeout_s):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "BENCH_CHILD_CPU": "1",
        "BENCH_CHILD_ROWS": str(rows),
        "BENCH_CHILD_ITERS": str(iters),
        "BENCH_SKIP_PREDICT": "1",
    })
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--child"],
        capture_output=True, text=True, timeout=timeout_s, env=env)
    for line in r.stdout.splitlines():
        if line.startswith("CHILD_RESULT "):
            return json.loads(line.split(" ", 1)[1])
    raise SystemExit("verify-perf: bench child produced no result "
                     f"(rc={r.returncode}): {(r.stderr or '')[-400:]}")


def check_speed():
    with open(BASELINE_PATH) as f:
        base = json.load(f)
    rows, iters = int(base["n_rows"]), int(base["n_iters"])
    timeout_s = int(os.environ.get("VERIFY_PERF_TIMEOUT", "420"))
    # compile happens OUTSIDE the timed loop (bench.py warm_up_fused),
    # so a single run is comparable to the committed baseline
    res = run_cpu_rung(rows, iters, timeout_s)
    limit = base["train_s"] * (1.0 + TOL)
    ok_speed = res["time_s"] <= limit
    ok_auc = abs(res["auc"] - base["auc"]) <= AUC_TOL
    print(f"verify-perf: train {res['time_s']:.2f}s vs baseline "
          f"{base['train_s']:.2f}s (limit {limit:.2f}s) -> "
          f"{'OK' if ok_speed else 'REGRESSION'}")
    print(f"verify-perf: auc {res['auc']:.5f} vs baseline "
          f"{base['auc']:.5f} (tol {AUC_TOL}) -> "
          f"{'OK' if ok_auc else 'DRIFT'}")
    if res["phases"].get("hist_bytes_per_s"):
        print(f"verify-perf: hist effective bandwidth "
              f"{res['phases']['hist_bytes_per_s'] / 1e9:.2f} GB/s")
    ok_mem = check_memory(base, res)
    ok_quality = check_quality_overhead(res)
    return ok_speed and ok_auc and ok_mem and ok_quality, res


def check_memory(base, res):
    """>MEM_TOL peak-memory regression vs the committed baseline fails
    (PR 8; baseline field `peak_memory_bytes`, the bench child's
    introspection watermark). A baseline without the field passes with
    a note — re-measure and bump BENCH_BASELINE.json to arm it."""
    intro = res.get("introspection") or {}
    # device watermark where the backend publishes allocator stats
    # (TPU/GPU); host peak RSS on this image's CPU jax
    peak = intro.get("device_peak_bytes") or intro.get(
        "host_peak_rss_bytes")
    led = intro.get("compile_ledger") or {}
    if led:
        print(f"verify-perf: compile ledger: {led.get('compiles', 0)} "
              f"compile(s) {led.get('total_s', 0.0):.2f}s, "
              f"{led.get('cache_hits', 0)} persistent-cache hit(s)")
    base_peak = base.get("peak_memory_bytes")
    if not base_peak:
        print("verify-perf: baseline has no peak_memory_bytes — memory "
              "gate skipped (bump BENCH_BASELINE.json to arm)")
        return True
    if not peak:
        print("verify-perf: bench child reported no memory watermark "
              "-> MISSING")
        return False
    limit = base_peak * (1.0 + MEM_TOL)
    ok = peak <= limit
    print(f"verify-perf: peak memory {peak / 1e6:.0f} MB vs baseline "
          f"{base_peak / 1e6:.0f} MB (limit {limit / 1e6:.0f} MB) -> "
          f"{'OK' if ok else 'REGRESSION'}")
    return ok


QUALITY_TOL_PCT = float(os.environ.get("VERIFY_QUALITY_TOL_PCT", "1.0"))


def check_quality_overhead(res):
    """Model-quality observability bar (bench quality_probe): the
    split-ledger pass must cost <1% of train time on the CPU rung and
    the drift+skew monitors (default sample rates) <1% of serving
    time. A missing measurement fails — the bar only means something
    if it is actually measured."""
    ok = True
    for key, what in (("quality_train_overhead_pct", "train rung"),
                      ("quality_serving_overhead_pct", "serving probe")):
        val = res["phases"].get(key)
        if val is None:
            print(f"verify-perf: {key} missing from bench phases "
                  "-> quality probe did not run")
            ok = False
            continue
        good = val < QUALITY_TOL_PCT
        print(f"verify-perf: quality monitor overhead {val:.4f}% of "
              f"{what} (bar {QUALITY_TOL_PCT:.1f}%) -> "
              f"{'OK' if good else 'OVER BUDGET'}")
        ok = ok and good
    return ok


def check_history(res):
    """History-aware regression gate (tools/sentinel.py): append this
    run's measurement to RUN_HISTORY.jsonl, then trend the file —
    median + MAD over the last K comparable runs, so slow drift the
    single-baseline gate can't see still fails loudly. With no (or
    too-little) history the gate records and passes: the sentinel only
    judges once >= 4 comparable runs exist."""
    sys.path.insert(0, REPO)
    from lightgbm_tpu.telemetry import history as history_mod
    from tools.sentinel import run_sentinel

    path = os.environ.get("VERIFY_HISTORY_PATH",
                          os.path.join(REPO, "RUN_HISTORY.jsonl"))
    intro = res.get("introspection") or {}
    peak = intro.get("device_peak_bytes") or intro.get(
        "host_peak_rss_bytes")
    history_mod.append_run_summary(
        path, "verify_perf", rows=int(res["n_rows"]),
        iterations=int(res["n_iters"]), train_s=float(res["time_s"]),
        auc=float(res["auc"]),
        peak_memory_bytes=int(peak) if peak else None,
        telemetry_overhead_pct=res["phases"].get(
            "telemetry_overhead_pct"),
        platform=res.get("platform"))
    rc, lines = run_sentinel(path)
    for line in lines:
        print(f"verify-perf: {line}")
    if rc == 2:
        print("verify-perf: history unreadable -> sentinel skipped")
        return True
    return rc == 0


def check_journal_tracer_consistency():
    """The journal's phase deltas must reconstruct the tracer totals —
    train in-process so BOTH sides of the equality are observable."""
    import shutil

    import numpy as np

    sys.path.insert(0, REPO)
    import lightgbm_tpu as lgb
    from lightgbm_tpu.telemetry.journal import read_journal
    from tools.check_journal import main as lint_main

    d = tempfile.mkdtemp(prefix="verify_perf_journal_")
    try:
        rng = np.random.RandomState(3)
        x = rng.rand(600, 5)
        y = (x[:, 0] + x[:, 1] > 1).astype(float)
        booster = lgb.train({"objective": "binary", "num_leaves": 7,
                             "min_data_in_leaf": 10, "verbose": 0,
                             "telemetry": True, "telemetry_dir": d},
                            lgb.Dataset(x, y), num_boost_round=4)
        inner = booster.gbdt
        totals = inner.tracer.snapshot()
        records, bad = read_journal(inner.journal.path)
        if bad:
            print(f"verify-perf: journal has {bad} torn line(s)")
            return False
        sums = {}
        for rec in records:
            if rec.get("event") != "iteration":
                continue
            for name, secs in (rec.get("phases") or {}).items():
                if isinstance(secs, (int, float)):
                    sums[name] = sums.get(name, 0.0) + secs
        ok = True
        # the phases fully covered by iteration records (trailing
        # activity after the last record would skew other names —
        # same contract test_telemetry pins)
        for name in ("build", "score_upd", "host_sync"):
            total, want = sums.get(name, 0.0), totals.get(name, 0.0)
            if abs(total - want) > max(1e-4, 0.02 * max(want, total)):
                print(f"verify-perf: phase [{name}] journal sum "
                      f"{total:.6f}s != tracer total {want:.6f}s")
                ok = False
        if not sums:
            print("verify-perf: journal produced no phase deltas")
            ok = False
        if ok:
            print("verify-perf: journal phase sums match tracer totals "
                  "-> OK")
        lint_rc = lint_main([d])
        print("verify-perf: journal schema lint ->",
              "OK" if lint_rc == 0 else "FAILED")
        return ok and lint_rc == 0
    finally:
        shutil.rmtree(d, ignore_errors=True)


def check_ooc():
    """Out-of-core acceptance guard (`make verify-ooc`; the bench's
    ooc_probe in guard form): a block store >= ~10x the streaming
    pipeline's resident budget must train end-to-end with (1) a model
    BIT-IDENTICAL to in-RAM masked-engine training on the same binning,
    (2) prefetch/compute overlap >= VERIFY_OOC_MIN_OVERLAP (default
    60%), and (3) peak RSS no worse than the in-RAM run's by more than
    VERIFY_OOC_RSS_SLACK (default 10% — the streamed matrix is small at
    guard scale, so this asserts 'bounded', not a big win)."""
    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("BENCH_OOC_ROWS",
                          os.environ.get("VERIFY_OOC_ROWS", "250000"))
    import bench
    res = bench.ooc_probe(
        timeout_s=int(os.environ.get("VERIFY_OOC_TIMEOUT", "480")))
    if "error" in res:
        print(f"verify-ooc: probe failed: {res['error']}")
        return False
    min_overlap = float(os.environ.get("VERIFY_OOC_MIN_OVERLAP", "60"))
    rss_slack = float(os.environ.get("VERIFY_OOC_RSS_SLACK", "0.10"))
    ok = True
    print(f"verify-ooc: {res['rows']} rows x {res['iters']} iters, "
          f"{res['blocks']} blocks, data {res['data_mb']:.1f} MB = "
          f"{res['data_vs_resident']}x the {res['resident_budget_mb']} MB "
          f"resident budget, {res['rows_s']:.0f} rows/s")
    if not res.get("bit_identical"):
        print("verify-ooc: streamed model != in-RAM masked-engine model "
              "-> PARITY BROKEN")
        ok = False
    else:
        print("verify-ooc: streamed model bit-identical to in-RAM -> OK")
    overlap = res.get("prefetch_overlap_pct", 0.0)
    if overlap < min_overlap:
        print(f"verify-ooc: prefetch overlap {overlap:.1f}% < "
              f"{min_overlap:.0f}% -> IO NOT HIDDEN")
        ok = False
    else:
        print(f"verify-ooc: prefetch overlap {overlap:.1f}% "
              f"(>= {min_overlap:.0f}%) -> OK")
    ratio = res.get("rss_vs_inram", 99.0)
    if ratio > 1.0 + rss_slack:
        print(f"verify-ooc: peak RSS {res['peak_rss_mb']} MB is "
              f"{ratio:.2f}x the in-RAM run's {res['inram_peak_rss_mb']} "
              f"MB -> NOT BOUNDED")
        ok = False
    else:
        print(f"verify-ooc: peak RSS {res['peak_rss_mb']} MB vs in-RAM "
              f"{res['inram_peak_rss_mb']} MB ({ratio:.2f}x) -> OK")
    return ok


def check_dist():
    """Distributed comms guard (`make verify-dist-perf`; the bench's
    dist_probe in gate form): the 2-process gloo CPU data-parallel rung
    must (1) keep per-tree collective wire bytes within VERIFY_DIST_TOL
    (default 15%) of the committed `dist_collective_bytes_per_tree`
    baseline, and (2) stay >= VERIFY_DIST_MIN_REDUCTION (default 3x)
    below the legacy allgather-pair exchange measured side by side —
    the reduce-scatter refactor's acceptance bar."""
    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import bench
    res = bench.dist_probe(
        timeout_s=int(os.environ.get("VERIFY_DIST_TIMEOUT", "480")))
    if "error" in res:
        print(f"verify-dist: probe failed: {res['error']}")
        return False
    ok = True
    vs_serial = res.get("rows_s_vs_serial")
    print(f"verify-dist: {res['rows']} rows x {res['iters']} iters, "
          f"{res['trees']} trees, sync wait {res['sync_wait_s']:.2f}s, "
          f"{res['rows_s']:.0f} rows/s "
          + (f"({vs_serial:.2f}x serial)" if vs_serial is not None
             else "(serial baseline unavailable)"))
    if res.get("comm_overlap_pct") is not None:
        # the latency-side story next to the wire bytes (ISSUE 13):
        # overlap + per-rank straggler deltas + the flow-event export
        print(f"verify-dist: comm overlap {res['comm_overlap_pct']:.1f}%"
              f", straggler deltas {res.get('comm_straggler_s')}, "
              f"perfetto flow events {res.get('perfetto_flow_events')} "
              f"(valid={res.get('perfetto_valid')})")
    bpt = res["collective_bytes_per_tree"]
    reduction = res["bytes_reduction_vs_allgather"]
    min_red = float(os.environ.get("VERIFY_DIST_MIN_REDUCTION", "3.0"))
    if reduction < min_red:
        print(f"verify-dist: reduce-scatter moves only {reduction:.2f}x "
              f"fewer bytes/tree than allgather-pair "
              f"({bpt / 1e6:.2f} vs {res['allgather_bytes_per_tree'] / 1e6:.2f} MB) "
              f"-> BELOW {min_red:.0f}x BAR")
        ok = False
    else:
        print(f"verify-dist: bytes/tree {bpt / 1e6:.2f} MB vs allgather "
              f"{res['allgather_bytes_per_tree'] / 1e6:.2f} MB "
              f"({reduction:.2f}x reduction, >= {min_red:.0f}x) -> OK")
    with open(BASELINE_PATH) as f:
        base = json.load(f)
    base_bpt = base.get("dist_collective_bytes_per_tree")
    if not base_bpt:
        print("verify-dist: baseline has no dist_collective_bytes_per_tree"
              " — regression gate skipped (bump BENCH_BASELINE.json to "
              "arm)")
        return ok
    tol = float(os.environ.get("VERIFY_DIST_TOL", "0.15"))
    limit = base_bpt * (1.0 + tol)
    good = bpt <= limit
    print(f"verify-dist: bytes/tree {bpt / 1e6:.2f} MB vs baseline "
          f"{base_bpt / 1e6:.2f} MB (limit {limit / 1e6:.2f} MB) -> "
          f"{'OK' if good else 'REGRESSION'}")
    return ok and good


def check_elastic():
    """Elastic out-of-core guard (`make verify-elastic`; the bench's
    elastic_probe in gate form): over ONE shared block store, (1) the
    binning pass must run EXACTLY ONCE across the cold -> snapshot
    resume -> 2-process gang sequence (the manifest's lifetime
    build_count ledger — the zero-re-bin contract), (2) the
    snapshot-resume leg must undercut the cold re-bin restart by
    VERIFY_ELASTIC_MAX_FRAC (default 0.9 — it skips the binning pass
    and half the iteration budget, so anything close to parity means
    the store adopt or the resume is broken), (3) the gang leg must
    report BOTH comm_overlap_pct and prefetch_overlap_pct from the
    same run's journal, and (4) ooc_dist.rows_s must stay within
    VERIFY_ELASTIC_TOL (default 0.5) of the committed
    elastic_gang_rows_s baseline."""
    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import bench
    res = bench.elastic_probe(
        timeout_s=int(os.environ.get("VERIFY_ELASTIC_TIMEOUT", "480")))
    if "error" in res:
        print(f"verify-elastic: probe failed: {res['error']}")
        return False
    ok = True
    gang = res["ooc_dist"]
    print(f"verify-elastic: {res['rows']} rows x {res['iters']} iters; "
          f"cold re-bin {res['cold_rebin_s']:.2f}s, snapshot resume "
          f"{res['resume_s']:.2f}s ({res['resume_speedup']:.2f}x), "
          f"gang {gang['rows_s']:.0f} rows/s")
    counts = (res["build_count_cold"], res["build_count_resume"],
              gang["build_count"])
    if counts != (1, 1, 1):
        print(f"verify-elastic: manifest build_count across "
              f"cold/resume/gang = {counts} -> DATA WAS RE-BINNED")
        ok = False
    else:
        print("verify-elastic: build_count 1 across cold -> resume -> "
              "gang (one binning pass, two adoptions) -> OK")
    frac = float(os.environ.get("VERIFY_ELASTIC_MAX_FRAC", "0.9"))
    limit = frac * res["cold_rebin_s"]
    if res["resume_s"] > limit:
        print(f"verify-elastic: resume {res['resume_s']:.2f}s > "
              f"{frac:.2f}x cold re-bin {res['cold_rebin_s']:.2f}s "
              "-> RESUME NOT CHEAPER THAN RE-BINNING")
        ok = False
    else:
        print(f"verify-elastic: resume {res['resume_s']:.2f}s vs cold "
              f"re-bin {res['cold_rebin_s']:.2f}s (limit {limit:.2f}s) "
              "-> OK")
    if res["resume_trees"] != res["iters"]:
        print(f"verify-elastic: resumed model has {res['resume_trees']} "
              f"tree(s), expected {res['iters']} -> RESUME LOST WORK")
        ok = False
    co, po = gang["comm_overlap_pct"], gang["prefetch_overlap_pct"]
    if co is None or po is None:
        print(f"verify-elastic: gang journal missing overlap "
              f"attribution (comm={co}, prefetch={po}) -> "
              "TELEMETRY INCOMPLETE")
        ok = False
    else:
        print(f"verify-elastic: gang run reports comm overlap "
              f"{co:.1f}% AND prefetch overlap {po:.1f}% -> OK")
    with open(BASELINE_PATH) as f:
        base = json.load(f)
    base_rows_s = base.get("elastic_gang_rows_s")
    if not base_rows_s:
        print("verify-elastic: baseline has no elastic_gang_rows_s — "
              "regression gate skipped (bump BENCH_BASELINE.json to "
              "arm)")
        return ok
    tol = float(os.environ.get("VERIFY_ELASTIC_TOL", "0.5"))
    floor = base_rows_s * (1.0 - tol)
    good = gang["rows_s"] >= floor
    print(f"verify-elastic: gang {gang['rows_s']:.0f} rows/s vs "
          f"baseline {base_rows_s:.0f} (floor {floor:.0f}) -> "
          f"{'OK' if good else 'REGRESSION'}")
    return ok and good


def check_fleet():
    """Fleet/hot-swap acceptance guard (`make verify-fleet`; the
    bench's fleet_probe in gate form): the sustained-QPS CPU serving
    rung must (1) finish the run with ZERO 5xx and ZERO cold dispatches
    across the mid-run hot-swap, (2) keep p99 DURING the swap within
    VERIFY_FLEET_SWAP_FACTOR (default 2.0) of steady-state p99 and
    within VERIFY_FLEET_TOL (default 50%) of the committed
    serving_p99_during_swap_ms baseline, and (3) show the bf16
    serving_precision path within its pinned accuracy bound AND at
    least VERIFY_FLEET_MIN_BF16_RATIO (default 1.2) times the f32
    serving default's throughput."""
    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import bench
    res = bench.fleet_probe(
        timeout_s=int(os.environ.get("VERIFY_FLEET_TIMEOUT", "480")))
    if "error" in res:
        print(f"verify-fleet: probe failed: {res['error']}")
        return False
    ok = True
    print(f"verify-fleet: {res['requests']} requests @ "
          f"{res['achieved_qps']:.0f} qps, steady p50/p99 "
          f"{res['steady_p50_ms']:.1f}/{res['steady_p99_ms']:.1f} ms, "
          f"swap {res['swap_s'] * 1e3:.0f} ms (warmup "
          f"{res['swap_warmup_s'] * 1e3:.0f} ms)")
    # sample floor: a wedged server makes every latency gate pass
    # vacuously (0 samples -> p99 0.0), so thin runs FAIL loudly
    min_requests = int(os.environ.get("VERIFY_FLEET_MIN_REQUESTS",
                                      "500"))
    min_window = int(os.environ.get("VERIFY_FLEET_MIN_SWAP_SAMPLES",
                                    "20"))
    if (res["requests"] < min_requests
            or res["swap_window_requests"] < min_window):
        print(f"verify-fleet: only {res['requests']} request(s), "
              f"{res['swap_window_requests']} in the swap window "
              f"(floors {min_requests}/{min_window}) -> "
              "INSUFFICIENT SAMPLES")
        ok = False
    if res["errors"]:
        print(f"verify-fleet: {res['errors']} failed request(s) over "
              "the whole run (steady phases or swap window) -> "
              "REQUEST FAILURES UNDER LOAD")
        ok = False
    else:
        print("verify-fleet: zero failed requests across the run "
              "(incl. the hot-swap) -> OK")
    if res["cold_dispatches"]:
        print(f"verify-fleet: {res['cold_dispatches']} cold dispatch(es) "
              "after the flip -> CHALLENGER NOT AOT-WARMED")
        ok = False
    else:
        print("verify-fleet: cold_dispatches 0 across the flip -> OK")
    factor = float(os.environ.get("VERIFY_FLEET_SWAP_FACTOR", "2.0"))
    during, steady = res["p99_during_swap_ms"], res["steady_p99_ms"]
    limit = factor * steady
    if during > limit:
        print(f"verify-fleet: p99 during swap {during:.1f} ms > "
              f"{factor:.1f}x steady p99 {steady:.1f} ms -> SWAP "
              "DISTURBS SERVING")
        ok = False
    else:
        print(f"verify-fleet: p99 during swap {during:.1f} ms vs steady "
              f"{steady:.1f} ms (limit {limit:.1f} ms) -> OK")
    with open(BASELINE_PATH) as f:
        base = json.load(f)
    base_swap = base.get("serving_p99_during_swap_ms")
    if base_swap:
        tol = float(os.environ.get("VERIFY_FLEET_TOL", "0.50"))
        blimit = base_swap * (1.0 + tol)
        good = during <= blimit
        print(f"verify-fleet: p99 during swap {during:.1f} ms vs "
              f"baseline {base_swap:.1f} ms (limit {blimit:.1f} ms) -> "
              f"{'OK' if good else 'REGRESSION'}")
        ok = ok and good
    else:
        print("verify-fleet: baseline has no serving_p99_during_swap_ms "
              "— regression gate skipped (bump BENCH_BASELINE.json to "
              "arm)")
    if not res.get("bf16_within_bound"):
        print(f"verify-fleet: bf16 max error {res['bf16_max_abs_err']:.2e}"
              f" exceeds its pinned bound {res['bf16_accuracy_bound']:.2e}"
              " -> PRECISION CONTRACT BROKEN")
        ok = False
    else:
        print(f"verify-fleet: bf16 max error {res['bf16_max_abs_err']:.2e}"
              f" within pinned bound {res['bf16_accuracy_bound']:.2e} "
              "-> OK")
    min_ratio = float(os.environ.get("VERIFY_FLEET_MIN_BF16_RATIO",
                                     "1.2"))
    ratio = res["bf16_throughput_ratio"]
    if ratio < min_ratio:
        print(f"verify-fleet: bf16 throughput {ratio:.2f}x the f32 "
              f"serving default (< {min_ratio:.1f}x bar; all-device f32 "
              f"comparison: {res['bf16_vs_f32_device_ratio']:.2f}x) -> "
              "NO WIN")
        ok = False
    else:
        print(f"verify-fleet: bf16 throughput {ratio:.2f}x the f32 "
              f"serving default ({res['bf16_rows_s']:.0f} vs "
              f"{res['f32_rows_s']:.0f} rows/s; "
              f"{res['bf16_vs_f32_device_ratio']:.2f}x the all-device "
              "f32 path) -> OK")
    return ok


def check_router():
    """Front-door resilience guard (`make verify-resilience`; the
    bench's router_probe in gate form): three replicas behind the
    fleet router with a mid-run kill + 10x slow + transient error
    burst must (1) deliver ZERO 5xx and ZERO transport errors to the
    well-deadlined clients, (2) keep error amplification at or under
    VERIFY_ROUTER_AMP (default 1.05 — the retry budget's contract),
    (3) keep p99 UNDER CHAOS within VERIFY_ROUTER_CHAOS_FACTOR
    (default 3.0) of steady p99 and within VERIFY_ROUTER_TOL (default
    50%) of the committed router_p99_under_chaos_ms baseline, and
    (4) show the breaker both OPEN and RE-CLOSE on the router's own
    /metricz counters."""
    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import bench
    res = bench.router_probe(
        timeout_s=int(os.environ.get("VERIFY_ROUTER_TIMEOUT", "480")))
    if "error" in res:
        print(f"verify-router: probe failed: {res['error']}")
        return False
    ok = True
    print(f"verify-router: {res['requests']} requests @ "
          f"{res['achieved_qps']:.0f} qps, steady p50/p99 "
          f"{res['steady_p50_ms']:.1f}/{res['steady_p99_ms']:.1f} ms, "
          f"chaos p99 {res['p99_under_chaos_ms']:.1f} ms over "
          f"{res['chaos_window_requests']} request(s), shed rate "
          f"{res['shed_rate']:.3f}")
    # sample floor: a wedged run makes every latency gate pass
    # vacuously, so thin runs FAIL loudly (same rule as verify-fleet)
    min_requests = int(os.environ.get("VERIFY_ROUTER_MIN_REQUESTS",
                                      "400"))
    min_window = int(os.environ.get("VERIFY_ROUTER_MIN_CHAOS_SAMPLES",
                                    "30"))
    if (res["requests"] < min_requests
            or res["chaos_window_requests"] < min_window):
        print(f"verify-router: only {res['requests']} request(s), "
              f"{res['chaos_window_requests']} in the chaos window "
              f"(floors {min_requests}/{min_window}) -> "
              "INSUFFICIENT SAMPLES")
        ok = False
    bad = res["server_errors_5xx"] + res["transport_errors"]
    if bad:
        print(f"verify-router: {res['server_errors_5xx']} 5xx + "
              f"{res['transport_errors']} transport error(s) reached "
              f"clients ({res['status_counts']}) -> ERRORS AMPLIFIED "
              "PAST THE FRONT DOOR")
        ok = False
    else:
        print("verify-router: zero 5xx / transport errors reached "
              "clients across the kill + slow + error burst -> OK")
    amp_limit = float(os.environ.get("VERIFY_ROUTER_AMP", "1.05"))
    amp = res["error_amplification"]
    if amp > amp_limit:
        print(f"verify-router: error amplification {amp:.3f}x > "
              f"{amp_limit:.2f}x (retry budget leak) -> RETRY STORM")
        ok = False
    else:
        print(f"verify-router: error amplification {amp:.3f}x "
              f"(limit {amp_limit:.2f}x; {res['retry_count']} retries) "
              "-> OK")
    factor = float(os.environ.get("VERIFY_ROUTER_CHAOS_FACTOR", "3.0"))
    during, steady = res["p99_under_chaos_ms"], res["steady_p99_ms"]
    limit = factor * steady
    if during > limit:
        print(f"verify-router: p99 under chaos {during:.1f} ms > "
              f"{factor:.1f}x steady p99 {steady:.1f} ms -> CHAOS "
              "DISTURBS HEALTHY TRAFFIC")
        ok = False
    else:
        print(f"verify-router: p99 under chaos {during:.1f} ms vs "
              f"steady {steady:.1f} ms (limit {limit:.1f} ms) -> OK")
    with open(BASELINE_PATH) as f:
        base = json.load(f)
    base_chaos = base.get("router_p99_under_chaos_ms")
    if base_chaos:
        tol = float(os.environ.get("VERIFY_ROUTER_TOL", "0.50"))
        blimit = base_chaos * (1.0 + tol)
        good = during <= blimit
        print(f"verify-router: p99 under chaos {during:.1f} ms vs "
              f"baseline {base_chaos:.1f} ms (limit {blimit:.1f} ms) "
              f"-> {'OK' if good else 'REGRESSION'}")
        ok = ok and good
    else:
        print("verify-router: baseline has no router_p99_under_chaos_ms"
              " — regression gate skipped (bump BENCH_BASELINE.json to "
              "arm)")
    if res["breaker_open_count"] < 1 or res["breaker_close_count"] < 1:
        print(f"verify-router: breaker opened {res['breaker_open_count']}"
              f"x / re-closed {res['breaker_close_count']}x — the chaos "
              "script guarantees at least one full open -> half-open -> "
              "close cycle -> BREAKER NOT EXERCISED")
        ok = False
    else:
        print(f"verify-router: breaker opened "
              f"{res['breaker_open_count']}x and re-closed "
              f"{res['breaker_close_count']}x (ejects "
              f"{res['eject_count']}) -> OK")
    if res["healthy_replica_count_end"] < 1:
        print("verify-router: no healthy replica left at run end -> "
              "FLEET DID NOT RECOVER")
        ok = False
    return ok


def check_trace():
    """Distributed-tracing overhead guard (`make verify-obs`; bench
    trace_probe in gate form, docs/Observability.md): two identical
    serving replicas — tracing off vs the full trace pipeline at the
    default sample rate — take interleaved single-row traffic; the
    traced arm's p99 must stay within VERIFY_TRACE_OVERHEAD_PCT
    (default 1%) of the untraced arm's, with VERIFY_TRACE_SLACK_MS
    (default 0.5 ms) of absolute slack so scheduler jitter on the
    1-core CI rung can't fail a sub-0.1 ms delta. The traced arm must
    also have RECORDED spans — an accidentally-dead recorder would
    gate 0% forever."""
    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import bench
    res = bench.trace_probe(
        timeout_s=int(os.environ.get("VERIFY_TRACE_TIMEOUT", "300")))
    if "error" in res:
        print(f"verify-trace: probe failed: {res['error']}")
        return False
    ok = True
    print(f"verify-trace: {res['samples_per_arm']} samples/arm, "
          f"p99 off {res['p99_off_ms']:.3f} ms vs on "
          f"{res['p99_on_ms']:.3f} ms (sample rate "
          f"{res['sample_rate']})")
    min_samples = int(os.environ.get("VERIFY_TRACE_MIN_SAMPLES", "200"))
    if res["samples_per_arm"] < min_samples:
        print(f"verify-trace: only {res['samples_per_arm']} sample(s) "
              f"per arm (floor {min_samples}) -> INSUFFICIENT SAMPLES")
        ok = False
    if res.get("traces_seen", 0) < 1:
        print("verify-trace: traced arm saw zero traces — the "
              "overhead gate is vacuous -> RECORDER DEAD")
        ok = False
    else:
        print(f"verify-trace: traced arm saw {res['traces_seen']} "
              f"trace(s), journaled {res['trace_spans_recorded']} "
              "span(s) -> OK")
    pct = float(os.environ.get("VERIFY_TRACE_OVERHEAD_PCT", "1.0"))
    slack_ms = float(os.environ.get("VERIFY_TRACE_SLACK_MS", "0.5"))
    # the gated statistic is the median-over-rounds p99 delta (robust
    # to a scheduler hiccup landing in one arm's window; the pooled
    # delta is reported alongside) — see bench.trace_probe
    delta = res.get("p99_delta_median_ms",
                    res["p99_on_ms"] - res["p99_off_ms"])
    limit = max(res["p99_off_ms"] * pct / 100.0, slack_ms)
    pooled = res["p99_on_ms"] - res["p99_off_ms"]
    if delta > limit:
        print(f"verify-trace: median per-round p99 overhead "
              f"{delta:.3f} ms (pooled {pooled:+.3f} ms / "
              f"{res['overhead_pct']:+.2f}%) > limit {limit:.3f} ms "
              f"(max of {pct:.1f}% and {slack_ms:.2f} ms noise slack) "
              "-> TRACING COSTS THE LATENCY ENVELOPE")
        ok = False
    else:
        print(f"verify-trace: median per-round p99 overhead "
              f"{delta:+.3f} ms (pooled {pooled:+.3f} ms / "
              f"{res['overhead_pct']:+.2f}%) within limit "
              f"{limit:.3f} ms -> OK")
    return ok


def check_linear():
    """Linear-leaf acceptance guard (`make verify-linear`; bench
    linear_probe in gate form, docs/Linear-Trees.md): (1) the sample-
    efficiency win — the linear model reaches the constant baseline's
    final AUC with <= VERIFY_LINEAR_MAX_TREES_RATIO (default 0.6) of
    its trees OR beats it by >= VERIFY_LINEAR_MIN_AUC_DELTA (default
    0.003) at equal trees; (2) the latency envelope — on the all-device
    fused kernels (the apples-to-apples comparison) linear single-row
    p99 stays within VERIFY_LINEAR_P99_FACTOR (default 1.3) of the
    constant model's, and within VERIFY_LINEAR_TOL (default 50%) of
    the committed linear_serving_p99_ms baseline; (3) zero cold
    dispatches on every warmed predictor."""
    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import bench
    res = bench.linear_probe(
        timeout_s=int(os.environ.get("VERIFY_LINEAR_TIMEOUT", "420")))
    if "error" in res:
        print(f"verify-linear: probe failed: {res['error']}")
        return False
    ok = True
    print(f"verify-linear: const AUC {res['const_auc']:.5f} @ "
          f"{res['trees']} trees; linear {res['linear_auc_at_equal_trees']:.5f}"
          f" (delta {res['auc_delta_at_equal_trees']:+.5f}), matched at "
          f"{res['trees_to_match_const']} trees "
          f"(ratio {res['trees_at_equal_auc_ratio']:.3f})")
    max_ratio = float(os.environ.get("VERIFY_LINEAR_MAX_TREES_RATIO",
                                     "0.6"))
    min_delta = float(os.environ.get("VERIFY_LINEAR_MIN_AUC_DELTA",
                                     "0.003"))
    tree_win = res["trees_at_equal_auc_ratio"] <= max_ratio
    auc_win = res["auc_delta_at_equal_trees"] >= min_delta
    if not (tree_win or auc_win):
        print(f"verify-linear: neither win condition met (trees ratio "
              f"{res['trees_at_equal_auc_ratio']:.3f} > {max_ratio}, "
              f"AUC delta {res['auc_delta_at_equal_trees']:+.5f} < "
              f"{min_delta}) -> LINEAR LEAVES BUY NOTHING")
        ok = False
    else:
        wins = [w for w, hit in (("trees", tree_win), ("auc", auc_win))
                if hit]
        print(f"verify-linear: win condition(s) met: {', '.join(wins)} "
              "-> OK")
    factor = float(os.environ.get("VERIFY_LINEAR_P99_FACTOR", "1.3"))
    ratio = res["serving_p99_ratio"]
    print(f"verify-linear: fused-path p99 linear "
          f"{res['linear_bf16_serving_p99_ms']:.3f} ms vs const "
          f"{res['const_bf16_serving_p99_ms']:.3f} ms (ratio "
          f"{ratio:.2f}, exact-path ratio "
          f"{res['exact_serving_p99_ratio']:.2f})")
    if ratio > factor:
        print(f"verify-linear: fused p99 ratio {ratio:.2f} > "
              f"{factor:.1f}x -> LINEAR KERNEL COSTS THE ENVELOPE")
        ok = False
    with open(BASELINE_PATH) as f:
        base = json.load(f)
    base_p99 = base.get("linear_serving_p99_ms")
    if base_p99:
        tol = float(os.environ.get("VERIFY_LINEAR_TOL", "0.50"))
        limit = base_p99 * (1.0 + tol)
        during = res["linear_bf16_serving_p99_ms"]
        good = during <= limit
        print(f"verify-linear: linear fused p99 {during:.3f} ms vs "
              f"baseline {base_p99:.3f} ms (limit {limit:.3f} ms) -> "
              f"{'OK' if good else 'REGRESSION'}")
        ok = ok and good
    else:
        print("verify-linear: baseline has no linear_serving_p99_ms — "
              "regression gate skipped (bump BENCH_BASELINE.json to "
              "arm)")
    colds = {k: v for k, v in res.items()
             if k.endswith("_cold_dispatches") and v}
    if colds:
        print(f"verify-linear: cold dispatches after warmup: {colds} "
              "-> NOT AOT-WARMED")
        ok = False
    else:
        print("verify-linear: cold_dispatches 0 on every warmed "
              "predictor -> OK")
    return ok


def main():
    if "--trace" in sys.argv:
        if not check_trace():
            print("verify-trace: FAILED")
            return 1
        print("verify-trace: all checks passed")
        return 0
    if "--linear" in sys.argv:
        if not check_linear():
            print("verify-linear: FAILED")
            return 1
        print("verify-linear: all checks passed")
        return 0
    if "--router" in sys.argv:
        if not check_router():
            print("verify-router: FAILED")
            return 1
        print("verify-router: all checks passed")
        return 0
    if "--fleet" in sys.argv:
        if not check_fleet():
            print("verify-fleet: FAILED")
            return 1
        print("verify-fleet: all checks passed")
        return 0
    if "--ooc" in sys.argv:
        if not check_ooc():
            print("verify-ooc: FAILED")
            return 1
        print("verify-ooc: all checks passed")
        return 0
    if "--dist" in sys.argv:
        if not check_dist():
            print("verify-dist: FAILED")
            return 1
        print("verify-dist: all checks passed")
        return 0
    if "--elastic" in sys.argv:
        if not check_elastic():
            print("verify-elastic: FAILED")
            return 1
        print("verify-elastic: all checks passed")
        return 0
    ok, res = check_speed()
    ok = check_history(res) and ok
    ok = check_journal_tracer_consistency() and ok
    if not ok:
        print("verify-perf: FAILED")
        return 1
    print("verify-perf: all checks passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
