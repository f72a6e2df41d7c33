"""The three distributed tree learners on a multi-device mesh.

Run with a virtual CPU mesh (from the repo root):
  PYTHONPATH=. JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/parallel_mesh.py

On TPU hardware the same code spans the real chips; multi-host setups
add machine_list_file/num_machines (docs/Parallel-Learning.md).
"""

import jax
import numpy as np

import lightgbm_tpu as lgb


def main():
    print(f"devices: {len(jax.devices())} x {jax.devices()[0].platform}")
    rng = np.random.RandomState(1)
    n = 20_000
    x = rng.randn(n, 15)
    y = ((x[:, 0] - x[:, 3]) * x[:, 7] + 0.4 * rng.randn(n) > 0).astype(float)

    for learner in ("data", "feature", "voting"):
        booster = lgb.train(
            {"objective": "binary", "num_leaves": 31, "verbose": -1,
             "tree_learner": learner},
            lgb.Dataset(x, y), num_boost_round=20)
        acc = float(((booster.predict(x) > 0.5) == (y > 0.5)).mean())
        print(f"tree_learner={learner:8s} train accuracy {acc:.3f}")


if __name__ == "__main__":
    main()
