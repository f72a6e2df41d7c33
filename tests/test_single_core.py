"""Single-core runner regression (ISSUE 18 satellite, PR 14 wedge).

An XLA CPU client with ONE device on a ONE-core host deadlocks
pure_callback inside async-dispatched jit programs: the lone worker
thread executes the program while the callback's operand delivery
waits for that same thread. The compacted learner auto-enables its
frontier/compacted host callbacks at n > HIST_CHUNK, so CLI training
past ~4k rows wedged forever on 1-core runners.

Two-part fix, both pinned here:
- utils/hostenv.ensure_callback_worker_devices forces >= 2 virtual
  host devices at the CLI/bench entry points (before the client
  exists) when the host has one core and no explicit flag;
- ops/histogram.host_callbacks_hazardous makes the serial learner and
  the fused block trace under callbacks_disabled (segment kernel —
  bit-identical, pinned by the segment==bincount parity suite) when
  the hazard configuration is live anyway (explicit 1-device flag).

The subprocess rung reproduces the EXACT wedge configuration — child
pinned to one CPU, one forced host device, n > HIST_CHUNK — and must
finish, timeout-bounded, instead of hanging.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from lightgbm_tpu.utils.hostenv import ensure_callback_worker_devices

REPO = os.path.dirname(os.path.dirname(__file__))


# ------------------------------------------------------- the env shim

def test_shim_respects_explicit_flag(monkeypatch):
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    assert ensure_callback_worker_devices() is False
    assert os.environ["XLA_FLAGS"] == \
        "--xla_force_host_platform_device_count=8"


def test_shim_noop_on_multicore(monkeypatch):
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: {0, 1},
                        raising=False)
    assert ensure_callback_worker_devices() is False
    assert "XLA_FLAGS" not in os.environ


def test_shim_adds_devices_on_single_core(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--some_other_flag=1")
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: {0},
                        raising=False)
    assert ensure_callback_worker_devices() is True
    assert "--some_other_flag=1" in os.environ["XLA_FLAGS"]
    assert "--xla_force_host_platform_device_count=2" \
        in os.environ["XLA_FLAGS"]
    # idempotent: the flag it just added counts as explicit
    assert ensure_callback_worker_devices() is False


# ------------------------------------------- the end-to-end regression

@pytest.mark.skipif(not hasattr(os, "sched_setaffinity"),
                    reason="needs Linux CPU affinity control")
def test_single_core_single_device_cli_does_not_wedge(tmp_path):
    """The PR 14 cliff, reproduced exactly: 1 CPU x 1 device x
    n > HIST_CHUNK through the CLI. Before the fix this hung forever in
    the first tree's bincount callback; with the hazard guard it must
    train to completion well inside the timeout."""
    rng = np.random.RandomState(5)
    n = 6000  # > HIST_CHUNK=4096: the compacted path auto-enables
    x = rng.rand(n, 6)
    y = ((x[:, 0] + x[:, 1] * x[:, 2]) > 0.9).astype(int)
    data = str(tmp_path / "tr.csv")
    np.savetxt(data, np.column_stack([y, x]), delimiter=",", fmt="%.6f")
    model = str(tmp_path / "model.txt")
    # the child pins ITSELF to one core before jax exists, and the
    # explicit 1-device flag defeats the entry-point shim — leaving
    # host_callbacks_hazardous as the only thing between us and a hang
    child = ("import os\n"
             "os.sched_setaffinity(0, {0})\n"
             "import runpy, sys\n"
             "sys.argv = ['lightgbm_tpu'] + sys.argv[1:]\n"
             "runpy.run_module('lightgbm_tpu', run_name='__main__')\n")
    args = [f"data={data}", "task=train", "objective=binary",
            "num_leaves=7", "num_iterations=2", "min_data_in_leaf=10",
            "metric_freq=0", "enable_load_from_binary_file=false",
            f"output_model={model}"]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               PYTHONPATH=REPO)
    env.pop("LIGHTGBM_TPU_FAULTS", None)
    r = subprocess.run([sys.executable, "-c", child] + args, cwd=REPO,
                       env=env, capture_output=True, text=True,
                       timeout=420)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    text = open(model).read()
    assert text.count("Tree=") == 2
