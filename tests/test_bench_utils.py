"""bench.py's measurement-integrity helpers: the data is reproducible
from its seed, the platform rule refuses a backend that was not asked
for, and the reference-time anchors are used as measured.
"""

import importlib.util
import os
import sys

import numpy as np
import pytest

BENCH_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench.py")


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", BENCH_PATH)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench_under_test"] = mod
    spec.loader.exec_module(mod)
    return mod


def test_make_data_is_reproducible_from_its_seed(bench):
    """Two calls with one seed give bit-identical features AND labels
    (a result must be re-measurable on the same data); another seed
    gives other data."""
    x1, y1 = bench.make_data(10_000)
    x2, y2 = bench.make_data(10_000)
    np.testing.assert_array_equal(x1, x2)
    np.testing.assert_array_equal(y1, y2)
    x3, _ = bench.make_data(10_000, seed=7)
    assert not np.array_equal(x1, x3)


def test_default_platform_choice_refuses_cpu(bench):
    """The default run measures the chip: a CPU backend is refused by
    name, and BENCH_FORCE_CPU is the only way to a CPU run (which in
    turn refuses anything but a CPU)."""
    with pytest.raises(SystemExit) as exc:
        bench.check_platform("cpu", force_cpu=False)
    assert "'cpu'" in str(exc.value) and "BENCH_FORCE_CPU" in str(exc.value)
    bench.check_platform("tpu", force_cpu=False)
    bench.check_platform("cpu", force_cpu=True)
    with pytest.raises(SystemExit):
        bench.check_platform("tpu", force_cpu=True)
    # an error result carries no timing
    out = bench._format_result({"error": "tpu: rc=1: no TPU"}, "note")
    assert out["value"] is None and "vs_baseline" not in out


def test_ref_time_anchors(bench):
    """The measured per-row-count anchors must be used verbatim at
    their measured iteration counts and scale linearly in iterations."""
    t, measured = bench._ref_time(1_000_000, 100)
    assert measured and abs(t - bench.REF_TRAIN_SECONDS) < 1e-9
    t10, m10 = bench._ref_time(100_000, 10)
    assert m10 and abs(t10 - 0.29 * bench.REF_TRAIN_SECONDS / 22.2) < 1e-9
    t11, m11 = bench._ref_time(11_000_000, 100)
    assert m11 and abs(t11 - 411.2 * bench.REF_TRAIN_SECONDS / 22.2) < 1e-9
    # unmeasured shape: linear row/iter scaling of the canonical anchor
    t_other, m_other = bench._ref_time(500_000, 50)
    assert not m_other
    assert abs(t_other - bench.REF_TRAIN_SECONDS * 0.5 * 0.5) < 1e-9
