"""No host callback in any program (ISSUE 31; the PR 14 wedge).

An XLA CPU client with ONE device on a ONE-core host deadlocks a
host callback inside an async-dispatched jit program (the lone worker
thread executes the program while the callback's operand delivery
waits for that same thread), and so does a callback inside a
multi-device shard_map program. The package once had one family of
callbacks, the bincount histogram formulation, and a tail of guards
that traced around it. Both are gone; what stays pinned here:

- the package's source names no host-callback primitive, and the
  selection state the guards hung on is not there to set;
- the serial learner's CPU program past HIST_CHUNK rows, where the
  callbacks used to switch on, lowers to pure XLA;
- the EXACT wedge configuration — child pinned to one CPU, one forced
  host device, n > HIST_CHUNK, through the CLI — finishes,
  timeout-bounded.

Whoever brings a host callback back brings the guards back with it.
"""

import os
import re
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(__file__))


def test_package_has_no_host_callback():
    from lightgbm_tpu.ops import histogram as H
    for name in ("HIST_MODE", "set_hist_mode", "callbacks_disabled"):
        assert not hasattr(H, name), name
    pattern = re.compile(r"pure_callback|io_callback")
    hits = []
    for dirpath, _, files in os.walk(os.path.join(REPO, "lightgbm_tpu")):
        for fn in files:
            if fn.endswith(".py"):
                path = os.path.join(dirpath, fn)
                with open(path, encoding="utf-8") as f:
                    for i, line in enumerate(f, 1):
                        if pattern.search(line):
                            hits.append(f"{os.path.relpath(path, REPO)}:{i}")
    assert hits == []


def test_serial_cpu_program_is_pure_xla():
    """The reason the persistent compile cache serves the CPU program
    across processes: no custom call to a process-local callback."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import DatasetLoader
    from lightgbm_tpu.models.tree_learner import SerialTreeLearner

    rng = np.random.RandomState(9)
    n = 6000  # > HIST_CHUNK=4096: the compacted builder switches on
    x = rng.rand(n, 5).astype(np.float32)
    y = (x[:, 0] > 0.5).astype(np.float32)
    cfg = Config(objective="binary", num_leaves=7, min_data_in_leaf=10,
                 verbose=-1)
    ds = DatasetLoader(cfg).construct_from_matrix(x, label=y)
    learner = SerialTreeLearner(cfg)
    learner.init(ds)
    assert learner._use_compact
    rows = jnp.zeros(learner.n_pad, jnp.float32)
    text = jax.jit(learner._build_core).lower(
        learner._bins, rows, rows, rows,
        jnp.ones(learner.f_pad, bool), learner._num_bin_pf,
        learner._is_cat).as_text()
    assert "scatter" in text  # the segment formulation is in there
    assert "callback" not in text


@pytest.mark.skipif(not hasattr(os, "sched_setaffinity"),
                    reason="needs Linux CPU affinity control")
def test_single_core_single_device_cli_does_not_wedge(tmp_path):
    """The PR 14 cliff, reproduced exactly: 1 CPU x 1 device x
    n > HIST_CHUNK through the CLI. With a host callback in the first
    tree's histogram this hung forever; it must train to completion
    well inside the timeout."""
    rng = np.random.RandomState(5)
    n = 6000  # > HIST_CHUNK=4096: the compacted path auto-enables
    x = rng.rand(n, 6)
    y = ((x[:, 0] + x[:, 1] * x[:, 2]) > 0.9).astype(int)
    data = str(tmp_path / "tr.csv")
    np.savetxt(data, np.column_stack([y, x]), delimiter=",", fmt="%.6f")
    model = str(tmp_path / "model.txt")
    # the child pins ITSELF to one core before jax exists and forces
    # one host device
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
