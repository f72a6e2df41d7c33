"""The harness checked without the chip.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

Data files resolve by name; the trace reduction and the work arithmetic
give figures worked out by hand; the runner's last line has the contract's
keys and refuses to run off a TPU; the control and every planted fault
come out as not correct, in the reference put in the program's place and
in the program itself, broken underneath a whole run of the harness.
"""

import glob
import io
import json
import os
import re
import subprocess
import sys
from contextlib import redirect_stdout

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

import control  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import tracereduce  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = sorted(os.path.basename(p)[:-5]
               for p in glob.glob(os.path.join(BENCH, "workloads", "*.json")))
SMALL = ["--rehearse", "--rows", "20000", "--seconds", "1"]


def bench():
    return run.load_json(ROOT, "BENCHMARK.json")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = run.load_cell(cell)
    assert c["config"]["data"]["rows"] > 0 and c["traffic"]["block_iterations"] > 0
    entry = [w for w in bench()["workloads"] if w["name"] == cell]
    assert len(entry) == 1 and entry[0]["chips"] == c["chips"]
    assert os.path.basename(entry[0]["config"]) == c["config"]["name"]
    assert set(c["limits"]) >= {"count_mismatch", "leaf_value_gap", "split_regret"}
    for group in ("end_to_end", "per_layer"):
        assert run.metrics_for(cell, group)


def test_names_units_and_readers():
    b = bench()
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(names)) == len(names)
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in b["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics", m["name"] + ".py"))
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}
    for w in b["workloads"] + b["configs"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200


def test_trace_reduction_on_a_synthetic_trace():
    # a while loop spanning everything, a conditional inside it, three
    # leaves (two kernel calls of 2 ms, one fusion of 3 ms), 3 ms of gaps
    ms = 1e6
    dev = {"/device:TPU:0": [
        ("%while.1 = (s32[]{:T(128)}, f32[8]) while(%t)", 0, 10 * ms),
        ("%cond.4 = (f32[8]{0}) conditional(%p, %a, %b)", 1 * ms, 6 * ms),
        ("%branch_1_fun.7 = f32[28,256,9]{2,1,0} custom-call(%x)", 1 * ms, 3 * ms),
        ("%fusion.12 = s32[64,7]{0,1:T(8,128)} fusion(%y), kind=kCustom", 3 * ms, 6 * ms),
        ("%branch_1_fun.7 = f32[28,256,9]{2,1,0} custom-call(%x)", 7 * ms, 9 * ms)]}
    host = [("bench_block", 0, 10 * ms), ("device_get", 9 * ms, 10 * ms)]
    tr = tracereduce.reduce(dev, host)
    assert tr["window_s"] == pytest.approx(0.010)
    assert tr["busy_s"] == pytest.approx(0.007)
    assert tracereduce.seconds_of(tr, re.compile("custom-call")) == pytest.approx(0.004)
    assert tracereduce.seconds_of(tr, re.compile("absent")) is None
    assert tr["device_ops"][:2] == [
        ["branch_1_fun.7 custom-call f32[28,256,9]", pytest.approx(0.004)],
        ["fusion.12 fusion s32[64,7]", pytest.approx(0.003)]]
    assert dict(map(tuple, tr["idle_gaps"])) == {
        "bench_block": pytest.approx(0.002), "device_get": pytest.approx(0.001)}
    ctx = {"trace": tr, "block_iterations": 2}
    assert run.read_metric("device_idle_pct", ctx) == pytest.approx(30.0)
    assert run.read_metric("seg_hist_ms_per_iter", ctx) == pytest.approx(2.0)
    assert run.read_metric("builder_other_ms_per_iter", ctx) == pytest.approx(1.5)
    assert run.read_metric("seg_hist_ms_per_iter", {"trace": None}) is None


def hand_tree():
    # 100 rows: root -> (leaf 0: 30 | node 1: 70); node 1 -> (leaf 1: 50 | leaf 2: 20)
    return {"split_feature": np.array([0, 1]), "threshold_in_bin": np.array([3, 5]),
            "left_child": np.array([-1, -2]), "right_child": np.array([1, -3]),
            "leaf_count": np.array([30, 50, 20]), "internal_count": np.array([100, 70])}


def test_work_arithmetic_on_a_hand_built_tree():
    tree = hand_tree()
    assert reference.split_order(tree).tolist() == [0, 1]
    assert reference.child_counts(tree).tolist() == [[30, 70], [50, 20]]
    assert reference.rows_visited(tree, 100) == 100 + 30 + 20
    ctx = {"trees": [tree], "rows": 100, "features": 28, "block_wall_s": 2.0,
           "peak": {"hbm_bytes_per_s": 1000.0}, "block_iterations": 1,
           "trace": {"ops": {"k custom-call f32[2]": 10.0}, "busy_s": 15.0,
                     "window_s": 20.0}}
    # 150 rows x 36 B + 100 x 12 B = 6600 B -> 6.6 s at 1000 B/s, over 2 s
    assert run.read_metric("step_mfu_pct", ctx) == pytest.approx(330.0)
    # 150 x 40 B = 6 s of the kernel's 10
    assert run.read_metric("seg_hist_roofline", ctx) == pytest.approx(60.0)
    assert run.read_metric("step_mfu_pct", {}) is None


def test_bounds_follow_lightgbm_on_a_sign_change():
    b = reference.find_bounds(np.array([-2.0, -1.0, 1.0, 3.0]), 255)
    assert b.tolist() == [-1.5, -0.5, 0.5, 2.0, np.inf]   # 0 sits between -1 and 1


def run_main(args):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(args)
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]) if lines else None


@pytest.mark.parametrize("trace", [0, 1])
def test_runner_last_line_on_cpu(trace):
    rc, line = run_main(["--workload", CELLS[0], "--seed", "2147483700",
                         "--trace", str(trace)] + SMALL)
    assert rc == 0
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks" and line["correct"] is True
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["checks"]["window_compiles"]["value"] == 0


def test_runner_refuses_to_run_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("mode,fails", [
    ("none", None), ("bf16", "leaf_value_gap"), ("half_batch", "count_mismatch"),
    ("altered", "leaf_value_gap"), ("unchanged", "score_gap")])
def test_control_and_faults_in_the_stand_in(mode, fails):
    cell = run.load_cell(CELLS[0])
    _, out = control.one_seed((cell, 2147483701, [mode], 20000, 4))
    ok, rows = run.check(out[mode], {k: v for k, v in cell["limits"].items()
                                     if k in out[mode]})
    assert ok == (fails is None)
    if fails:
        assert rows[fails]["value"] > 3 * rows[fails]["limit"]


def break_program(monkeypatch, fault):
    """Plant `fault` underneath the timed path (the fused block)."""
    import jax.numpy as jnp
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives.objectives import ObjectiveFunction
    get_fused, install = GBDT._get_fused_fn, ObjectiveFunction._install_grad

    def broken_fused(self, num_iters):
        runner = get_fused(self, num_iters)

        def wrapped(score, fmasks, iters):
            final, stacked = runner(score, fmasks, iters)
            if fault == "unchanged":
                return score, stacked
            if fault == "altered":
                lv = stacked["leaf_value"]
                stacked = dict(stacked, leaf_value=lv.at[..., 1].multiply(1.01))
            return final, stacked
        return wrapped

    def half_batch(self, grad_pure, ops):
        def halved(o, score):
            keep = (jnp.arange(score.shape[-1]) % 2 == 0).astype(score.dtype)
            g, h = grad_pure(o, score)
            return g * keep, h * keep
        return install(self, halved, ops)

    if fault == "half_batch":
        monkeypatch.setattr(ObjectiveFunction, "_install_grad", half_batch)
    else:
        monkeypatch.setattr(GBDT, "_get_fused_fn", broken_fused)


@pytest.mark.parametrize("fault,fails", [
    ("unchanged", "score_gap"), ("half_batch", "leaf_value_gap"),
    ("altered", "leaf_value_gap")])
def test_a_whole_run_sees_the_program_broken(monkeypatch, fault, fails):
    break_program(monkeypatch, fault)
    rc, line = run_main(["--workload", CELLS[0], "--seed", "2147483702",
                         "--trace", "0"] + SMALL)
    assert rc == 0 and line["correct"] is False
    c = line["checks"][fails]
    assert c["value"] > c["limit"]
