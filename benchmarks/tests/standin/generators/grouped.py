"""Stand-in recipe for the harness's tests: a few thousand rows in query
groups with a weight a row, `classes` label values. `also` names a field
`lgb.Dataset` does not take, for the test that it is refused."""

import numpy as np


def make(data, seed):
    n, f, k = int(data["rows"]), int(data["features"]), int(data["classes"])
    rng = np.random.default_rng(int(seed))
    x = rng.standard_normal((n, f), dtype=np.float32)
    w = rng.standard_normal((f, k), dtype=np.float32)
    logit = x @ w + rng.standard_normal((n, k), dtype=np.float32)
    y = (logit[:, 0] > 0) if k == 2 else np.argmax(logit, axis=1)
    per = int(data["group_rows"])
    fields = {"group": np.full(n // per, per, np.int32),
              "weight": rng.uniform(0.5, 2.0, n).astype(np.float32)}
    fields.update({name: np.zeros(n, np.float32) for name in data.get("also", [])})
    return x, y.astype(np.float32), fields
