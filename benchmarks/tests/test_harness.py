"""The harness checked without the chip.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

Data files resolve by name; the trace reduction and the work arithmetic
give figures worked out by hand; the runner's last line has the contract's
keys and refuses to run off a TPU; the control and every planted fault
come out as not correct, in the reference put in the program's place and
in the program itself, broken underneath a whole run of the harness.
The seam for a deployment of another kind (data fields, a reference of
its own, K trees an iteration) is driven by the stand-in configurations
under tests/standin/, which no cell names.
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
import datagen  # noqa: E402
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
        ("%seg_hist.7 = f32[28,256,9]{2,1,0} custom-call(%x)", 1 * ms, 3 * ms),
        ("%fusion.12 = s32[64,7]{0,1:T(8,128)} fusion(%y), kind=kCustom", 3 * ms, 6 * ms),
        ("%seg_hist.7 = f32[28,256,9]{2,1,0} custom-call(%x)", 7 * ms, 9 * ms)]}
    host = [("bench_block", 0, 10 * ms), ("device_get", 9 * ms, 10 * ms)]
    tr = tracereduce.reduce(dev, host)
    assert tr["window_s"] == pytest.approx(0.010)
    assert tr["busy_s"] == pytest.approx(0.007)
    assert tracereduce.seconds_of(tr, re.compile("custom-call")) == pytest.approx(0.004)
    assert tracereduce.seconds_of(tr, re.compile("absent")) is None
    assert tr["device_ops"][:2] == [
        ["seg_hist.7 custom-call f32[28,256,9]", pytest.approx(0.004)],
        ["fusion.12 fusion s32[64,7]", pytest.approx(0.003)]]
    assert dict(map(tuple, tr["idle_gaps"])) == {
        "bench_block": pytest.approx(0.002), "device_get": pytest.approx(0.001)}
    ctx = {"trace": tr, "block_iterations": 2}
    assert run.read_metric("device_idle_pct", ctx) == pytest.approx(30.0)
    assert run.read_metric("seg_hist_ms_per_iter", ctx) == pytest.approx(2.0)
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
           "trace": {"ops": {"seg_hist.3 custom-call f32[2]": 10.0}, "busy_s": 15.0,
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


# ------------------------------------------------- one kernel, one reader
def two_kernel_trace():
    # a split loop holding two histogram calls (2 + 1 ms), two partition
    # calls (0.5 + 1.5 ms) and a fusion (1 ms); 4 ms of gaps
    ms = 1e6
    dev = {"/device:TPU:0": [
        ("%while.1 = (s32[]{:T(128)}, f32[8]) while(%t)", 0, 10 * ms),
        ("%seg_hist.38 = f32[28,256,9]{2,1,0} custom-call(%x)", 0, 2 * ms),
        ("%partition_rows.2 = (s32[8,4096]{1,0}, f32[4,4096]{1,0}) custom-call(%r)",
         2 * ms, 2.5 * ms),
        ("%seg_hist.45 = f32[28,256,9]{2,1,0} custom-call(%x)", 3 * ms, 4 * ms),
        ("%fusion.9 = s32[4096]{0} fusion(%y), kind=kLoop", 5 * ms, 6 * ms),
        ("%partition_rows.2 = (s32[8,4096]{1,0}, f32[4,4096]{1,0}) custom-call(%r)",
         7 * ms, 8.5 * ms)]}
    return tracereduce.reduce(dev, [("bench_block", 0, 10 * ms)])


def test_each_kernel_reader_counts_its_own_kernel():
    tr = two_kernel_trace()
    assert tr["busy_s"] == pytest.approx(0.006)
    ctx = {"trace": tr, "block_iterations": 2}
    assert run.read_metric("seg_hist_ms_per_iter", ctx) == pytest.approx(1.5)
    assert run.read_metric("partition_rows_ms_per_iter", ctx) == pytest.approx(1.0)
    # a custom call of neither name is neither's
    other = {"ops": {"sort_rows.3 custom-call s32[8]": 1.0,
                     "my_seg_hist.2 custom-call f32[2]": 1.0}}
    for name in ("seg_hist_ms_per_iter", "partition_rows_ms_per_iter",
                 "partition_rows_roofline", "seg_hist_roofline"):
        assert run.read_metric(name, {"trace": other, "block_iterations": 1,
                                      "trees": [hand_tree()]}) is None
        assert run.read_metric(name, {"trace": None}) is None


def test_partition_roofline_on_a_hand_built_tree():
    tree = hand_tree()
    assert reference.rows_partitioned(tree) == 100 + 70
    ctx = {"trace": two_kernel_trace(), "trees": [tree, tree], "rows": 100,
           "features": 28, "block_iterations": 2,
           "peak": {"hbm_bytes_per_s": 1e9}}
    # 2 trees x 170 rows x 44 B (28 bins + 4 index + 12 statistics), read
    # and written: 29,920 B -> 29.92 us at 1 GB/s, over the kernel's 2 ms
    assert run.read_metric("partition_rows_roofline", ctx) == pytest.approx(
        100 * 29920e-9 / 0.002)
    # and the histogram's share reads the histogram kernel's 3 ms alone:
    # 2 x 150 rows x 40 B = 12 us
    assert run.read_metric("seg_hist_roofline", ctx) == pytest.approx(
        100 * 12000e-9 / 0.003)


def test_no_reader_is_left_without_an_entry_or_an_entry_without_a_reader():
    listed = {m["name"] for m in bench()["per_layer"]}
    files = {os.path.basename(p)[:-3]
             for p in glob.glob(os.path.join(BENCH, "metrics", "*.py"))}
    assert listed == files and "builder_other_ms_per_iter" not in listed


# --------------------------------- the seam: fields, reference, K trees
STANDIN = os.path.join(HERE, "standin")


@pytest.fixture
def standin(monkeypatch):
    """Resolve cells, generators and references under tests/standin/
    first, and record what the harness hands to lgb.Dataset, to the
    named reference and to the readers. `seen["plant"]` is added to a
    number the named reference returns."""
    import lightgbm_tpu as lgb
    seen = {"dataset": [], "compare": [], "ctx": [], "plant": {}}
    real_module, real_cell, real_dataset = (datagen.load_module, run.load_cell,
                                            lgb.Dataset)

    def load_module(subdir, name):
        own = os.path.exists(os.path.join(STANDIN, subdir, name + ".py"))
        with monkeypatch.context() as m:
            m.setattr(datagen, "HERE", STANDIN if own else BENCH)
            mod = real_module(subdir, name)
        if subdir == "references":
            compare = mod.compare

            def recorded(*args):
                seen["compare"].append((name, args))
                return {k: v + seen["plant"].get(k, 0.0)
                        for k, v in compare(*args).items()}
            mod.compare = recorded
        return mod

    def load_cell(name):
        with monkeypatch.context() as m:
            m.setattr(run, "HERE", STANDIN)
            return real_cell(name)

    def dataset(data, **kwargs):
        seen["dataset"].append(kwargs)
        return real_dataset(data, **kwargs)

    monkeypatch.setattr(datagen, "load_module", load_module)
    monkeypatch.setattr(run, "load_module", load_module)
    monkeypatch.setattr(run, "load_cell", load_cell)
    monkeypatch.setattr(run, "read_metric",
                        lambda name, ctx: seen["ctx"].append(ctx))
    monkeypatch.setattr(lgb, "Dataset", dataset)
    return seen


def standin_args(cell, trace=0):
    return ["--workload", cell, "--seed", "2147483711", "--trace", str(trace),
            "--rehearse", "--seconds", "0.5"]


def test_fields_reach_the_dataset_and_the_named_reference_decides(standin):
    rc, line = run_main(standin_args("standin-k1.train"))
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    (kwargs,) = standin["dataset"]
    assert set(kwargs) == {"label", "params", "free_raw_data", "group", "weight"}
    assert kwargs["group"].sum() == 3000 and kwargs["weight"].shape == (3000,)
    (name, (x, y, fields, params, trees, score)), = standin["compare"]
    assert name == "standin" and x.shape == (3000, 6) and y.shape == (3000,)
    # untouched: the very arrays the generator made
    assert fields["group"] is kwargs["group"] and fields["weight"] is kwargs["weight"]
    # the merged set the program got: the mix's learner is in it
    assert params["tree_learner"] == "serial" and params["num_leaves"] == 7
    assert len(trees) == 2 and score.shape == (1, 3000)
    assert set(line["checks"]) == {"window_compiles", "failed", "fields_missing",
                                   "group_rows_gap", "score_shape_gap",
                                   "partial_iterations", "score_max_gap"}
    assert list(line)[-1] == "checks"
    # a wrong number from the named reference fails the run
    standin["plant"]["group_rows_gap"] = 1.0
    rc, line = run_main(standin_args("standin-k1.train"))
    assert rc == 0 and line["correct"] is False
    assert line["checks"]["group_rows_gap"] == {"value": 1.0, "limit": 0}


def test_a_block_holds_k_trees_an_iteration(standin):
    rc, line = run_main(standin_args("standin-k3.train", trace=1))
    assert rc == 0 and line["correct"] is True
    assert line["attempted"] == 4 and line["failed"] == 0     # 2 + 2 iterations
    (_, (x, y, fields, params, trees, score)), = standin["compare"]
    assert params["num_class"] == 3 and set(np.unique(y)) == {0.0, 1.0, 2.0}
    assert len(trees) == 2 * 3 and score.shape == (3, 3000)
    assert all(len(t["split_feature"]) > 0 for t in trees)
    # the three classes' scores differ, and each is its own trees' sum
    assert line["checks"]["score_max_gap"]["value"] <= 1e-5
    assert not np.allclose(score[0], score[1])
    ctx = standin["ctx"][0]
    assert ctx["trees_per_iteration"] == 3 and ctx["block_iterations"] == 2
    assert len(ctx["trees"]) == 2 * 3 and ctx["params"] == params
    # the traced block's trees, not the first block's
    assert not np.array_equal(ctx["trees"][0]["leaf_value"], trees[0]["leaf_value"])


def test_an_unknown_field_is_an_error(standin):
    data = {"kind": "grouped", "rows": 60, "features": 2, "classes": 2,
            "group_rows": 30}
    x, y, fields = datagen.make_data(data, 3)
    assert sorted(fields) == ["group", "weight"] and x.shape == (60, 2)
    with pytest.raises(ValueError, match="init_score"):
        datagen.make_data(dict(data, also=["init_score"]), 3)


def test_a_two_value_generator_still_gives_no_fields():
    x, y, fields = datagen.make_data(
        {"kind": "higgs_like", "rows": 50, "features": 4, "base_seed": 1}, 7)
    assert fields == {} and x.shape == (50, 4) and y.shape == (50,)


def test_without_a_reference_key_the_binary_reference_gets_todays_arguments(
        monkeypatch):
    seen = []
    real = reference.compare

    def recorded(*args):
        seen.append(args)
        return real(*args)
    monkeypatch.setattr(reference, "compare", recorded)
    rc, line = run_main(["--workload", CELLS[0], "--seed", "2147483712",
                         "--trace", "0"] + SMALL)
    assert rc == 0 and line["correct"] is True
    (x, y, cfg, trees, score), = seen
    cell = run.load_cell(CELLS[0])
    assert "reference" not in cell["config"]
    assert cfg == cell["config"]["params"] and "tree_learner" not in cfg
    assert x.shape == (20000, 28) and y.shape == (20000,)
    assert len(trees) == cell["traffic"]["block_iterations"]
    assert score.shape == (20000,) and score.dtype == np.float32


def test_the_binary_reference_refuses_what_it_cannot_judge():
    cfg = {"name": "c", "params": {}}
    with pytest.raises(ValueError, match="reference"):
        run.compare(cfg, {}, None, None, {"weight": [1.0]}, [], np.zeros((1, 2)))
    with pytest.raises(ValueError, match="reference"):
        run.compare(cfg, {}, None, None, {}, [], np.zeros((3, 2)))


@pytest.mark.parametrize("splits,attempted,k,failed", [
    ([1, 1, 1], 3, 1, 0), ([1, 0, 1], 3, 1, 1), ([1, 1], 3, 1, 1),
    ([1, 1, 1, 1, 1, 1], 2, 3, 0), ([1, 1, 0, 1, 1, 1], 2, 3, 1),
    ([1, 0, 0, 1, 1, 1], 2, 3, 1), ([1, 1, 1], 2, 3, 1), ([1, 1, 1, 1], 2, 3, 1)])
def test_failed_counts_iterations_of_k_trees(splits, attempted, k, failed):
    trees = [{"split_feature": np.zeros(s)} for s in splits]
    assert run.failed_iterations(trees, attempted, k) == failed
