"""What the chip bring-up rests on and a CPU can check: chip_smoke.py
refuses to run without a TPU, importing the entry points initialises no
JAX backend (a parent that touched JAX would hold the chip its child
needs), and the compile cache lands where the one rule says
(config.setup_compilation_cache; the env-set half of that rule is
checked in test_compact_hist.py on the run that already spawns a
child).

Every test here starts an interpreter, so the file is named to sort
last: a time-boxed run spends its budget on the in-process suite first.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, **env_overrides):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_overrides)
    return subprocess.run([sys.executable] + args, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_without_a_tpu_exits_nonzero_naming_the_platform():
    r = _run(["chip_smoke.py"])
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout and "stage" not in r.stdout


_IMPORT_CHILD = r"""
import importlib.util, json, os, sys
import lightgbm_tpu
import lightgbm_tpu.serving.server
import lightgbm_tpu.supervisor
spec = importlib.util.spec_from_file_location("bench", "bench.py")
bench = importlib.util.module_from_spec(spec)
spec.loader.exec_module(bench)
import jax
from jax._src import xla_bridge
backends_after_import = sorted(xla_bridge._backends)
from lightgbm_tpu.config import checkout_cache_dir, setup_compilation_cache
active = setup_compilation_cache()
print(json.dumps({
    "backends_after_import": backends_after_import,
    "backends_after_cache_setup": sorted(xla_bridge._backends),
    "active": active, "checkout": checkout_cache_dir(),
    "jax_dir": jax.config.jax_compilation_cache_dir}))
"""


def test_imports_touch_no_backend_and_cache_defaults_to_the_checkout():
    r = _run(["-c", _IMPORT_CHILD])
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["backends_after_import"] == []
    assert out["backends_after_cache_setup"] == []
    assert out["checkout"] == os.path.join(REPO, ".jax_cache")
    assert out["active"] == out["jax_dir"] == out["checkout"]
